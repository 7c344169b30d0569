"""The traffic generator: the same seed gives the same requests, every
seed the same schedule (the mix's) with prompts of its own, and the
mix's rate, lengths and burstiness come out as its file states."""
import json

import numpy as np
import pytest

from chipbench import loadgen
from conftest import BENCH


def mix(name):
    """A traffic file; ``bursty`` is the chat mix with Gamma(0.25) gaps."""
    if name == "bursty":
        m = mix("serve.chat")
        m["arrivals"]["gamma_shape"] = 0.25
        return m
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def draw(m, seed, seconds=40.0, n=None):
    tr = loadgen.Traffic(m, seed, seconds, vocab=50280)
    items = [tr.next() for _ in range(n or tr.n_due)]
    return tr, items


@pytest.mark.parametrize("name", ["serve.chat", "bursty",
                                  "serve.offline"])
def test_same_seed_same_requests(name):
    m = mix(name)
    _, a = draw(m, 2**33 + 7, n=None if name != "serve.offline" else 70)
    _, b = draw(m, 2**33 + 7, n=None if name != "serve.offline" else 70)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["serve.chat", "bursty"])
def test_every_seed_same_schedule_own_prompts(name):
    m = mix(name)
    tr1, a = draw(m, 1)
    tr2, b = draw(m, 2)
    np.testing.assert_array_equal(tr1.due, tr2.due)
    assert [(len(x.prompt), x.max_new) for x in a] == \
        [(len(x.prompt), x.max_new) for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_schedule_seed_draws_the_schedule():
    m = mix("serve.chat")
    other = dict(m, schedule_seed=m["schedule_seed"] + 1)
    tr1, a = draw(m, 1)
    tr2, b = draw(other, 1)
    assert not np.array_equal(tr1.due, tr2.due)
    for size in (lambda x: len(x.prompt), lambda x: x.max_new):
        assert sorted(map(size, a)) == sorted(map(size, b))
        assert list(map(size, a)) != list(map(size, b))


@pytest.mark.parametrize("name", ["serve.chat", "bursty"])
def test_open_loop_rate(name):
    m = mix(name)
    tr, items = draw(m, 3, seconds=40.0)
    assert len(items) == round(m["arrivals"]["rate_per_s"] * 40.0)
    assert np.all(np.diff(tr.due) >= 0)
    assert 0.0 <= tr.due[0] and tr.due[-1] < 40.0


@pytest.mark.parametrize("shape,cv", [(1.0, 1.0), (0.25, 2.0)])
def test_gap_cv(shape, cv):
    t = loadgen.arrival_times({"rate_per_s": 100.0, "gamma_shape": shape},
                              200.0, 11)
    gaps = np.diff(t)
    assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.08)


def test_chat_lengths():
    m = mix("serve.chat")
    _, items = draw(m, 4)
    plens = np.array([len(x.prompt) for x in items])
    olens = np.array([x.max_new for x in items])
    assert set(plens) <= set(m["prompt_len"]["snap"])
    assert np.median(plens) == 256
    assert olens.min() >= 16 and olens.max() <= 512
    assert np.median(olens) == pytest.approx(128, rel=0.05)
    toks = np.concatenate([x.prompt for x in items])
    assert toks.min() >= 2 and toks.max() < 50280


def test_offline_rounds():
    m = mix("serve.offline")
    _, items = draw(m, 5, n=2 * m["round"])
    plens = [len(x.prompt) for x in items]
    assert set(plens) == {1024, 1536}
    first, second = items[:m["round"]], items[m["round"]:]
    for size in (lambda x: len(x.prompt), lambda x: x.max_new):
        # each round: every size once
        assert sorted(map(size, first)) == sorted(map(size, second))
    assert all(x.due is None for x in items)
    assert min(x.max_new for x in items) >= 16
    assert max(x.max_new for x in items) <= 256


def test_length_set_needs_finite_lengths():
    with pytest.raises(ValueError):
        loadgen.length_set({"dist": "lognormal", "median": 10, "sigma": 1})
