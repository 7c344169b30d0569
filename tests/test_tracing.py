"""The program's own tracing: ``repro.obs`` spans, counters and compile
events; ``SlotServer``'s spans, stamps and counters at a tiny size; the
spans on a profiler trace; and the named scopes, which change the compiled
decode step's metadata and nothing else."""
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.registry import get_config
from repro.launch.serve import latency_stats, serve
from repro.serve.engine import Request, ServeConfig, SlotServer


@pytest.fixture
def tracer():
    tr = obs.Tracer()
    yield tr
    tr.close()


def test_spans_nest_with_parents_and_rids(tracer):
    with tracer.span("a") as a:
        with tracer.span("b", rid=7) as b:
            with tracer.span("c", rid=7):
                pass
        a.count(x=2)
        a.count(x=1, y=4)
    with tracer.span("d"):
        pass
    s = {sp.name: sp for sp in tracer.spans}
    assert [sp.name for sp in tracer.spans] == ["c", "b", "a", "d"]
    assert s["a"].parent is None and s["d"].parent is None
    assert s["b"].parent == s["a"].id == a.id and s["c"].parent == b.id
    assert (s["b"].rid, s["c"].rid, s["a"].rid) == (7, 7, None)
    assert s["a"].start <= s["b"].start <= s["c"].start
    assert s["c"].end <= s["b"].end <= s["a"].end <= s["d"].start
    assert s["a"].counts == {"x": 3, "y": 4} and s["b"].counts is None
    assert tracer.totals == {"x": 3, "y": 4}


def test_span_closes_on_error(tracer):
    with pytest.raises(ValueError):
        with tracer.span("outer"):
            with tracer.span("inner"):
                raise ValueError
    with tracer.span("after"):
        pass
    names = {sp.name: sp for sp in tracer.spans}
    assert names["inner"].parent == names["outer"].id
    assert names["after"].parent is None


def test_ring_keeps_the_newest():
    small = obs.Tracer(capacity=4)
    small.close()
    for i in range(10):
        with small.span(f"s{i}") as sp:
            sp.count(n=1)
    assert [sp.name for sp in small.spans] == ["s6", "s7", "s8", "s9"]
    assert small.totals["n"] == 10          # totals outlive the ring


def test_compile_counts(tracer):
    t0 = tracer.compile_seconds

    def fresh_program(x):
        return jnp.sin(x) * 3.0 + 1.0
    x = np.arange(5.0, dtype=np.float32)
    jax.jit(fresh_program)(x).block_until_ready()
    jax.jit(fresh_program)(x).block_until_ready()      # cached: no compile
    new = [c for c in tracer.compiles if "fresh_program" in c.name]
    assert len(new) == 1 and new[0].seconds > 0
    assert tracer.compile_seconds > t0
    assert tracer.compiles_between(new[0].end, new[0].end) == 1
    tracer.close()
    n = len(tracer.compiles)
    jax.jit(lambda x: x * 5.0 - 2.0)(x).block_until_ready()
    assert len(tracer.compiles) == n


def test_default_tracer_is_one():
    assert obs.tracer() is obs.tracer()


# -- SlotServer ----------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cfg():
    return get_config("olmo-1b").reduced()


@pytest.fixture(scope="module")
def served(tiny_cfg):
    """A tiny server with its own tracer, after serving 5 requests of
    lengths that need more steps than it has slots."""
    tr = obs.Tracer()
    tr.close()
    srv = SlotServer(tiny_cfg, serve_cfg=ServeConfig(
        max_slots=2, max_len=32, max_new_tokens=4, eos_id=-1), tracer=tr)
    rng = np.random.default_rng(0)
    reqs = [srv.submit(rng.integers(2, 100, n).astype(np.int32),
                       max_new_tokens=m)
            for n, m in ((5, 3), (7, 4), (5, 1), (9, 2), (5, 4))]
    steps = 0
    while srv.queue or srv.active.any():
        srv.step()
        steps += 1
    return srv, tr, reqs, steps


def _by_name(tr, name):
    return [s for s in tr.spans if s.name == name]


def test_one_step_span_per_step(served):
    srv, tr, _, steps = served
    step_spans = _by_name(tr, "engine.step")
    assert len(step_spans) == steps
    assert all(s.parent is None for s in step_spans)
    assert len(_by_name(tr, "engine.admit")) == steps


def test_one_prefill_per_admission_with_its_rid(served):
    srv, tr, reqs, _ = served
    pre = _by_name(tr, "engine.prefill")
    sync = _by_name(tr, "engine.prefill.sync")
    assert sorted(s.rid for s in pre) == [r.rid for r in reqs]
    assert sorted(s.rid for s in sync) == [r.rid for r in reqs]
    admits = {s.id for s in _by_name(tr, "engine.admit")}
    assert all(s.parent in admits for s in pre + sync)


def test_stamps_one_per_token(served):
    srv, _, reqs, _ = served
    assert [len(r.output) for r in reqs] == [3, 4, 1, 2, 4]
    for r in reqs:
        assert len(r.token_times) == len(r.output)
        assert r.t_submit <= r.t_admit <= r.t_first_token == r.token_times[0]
        assert r.token_times == sorted(r.token_times)
        assert r.token_times[-1] <= r.t_finish
    # two slots: the third request waits for one
    assert reqs[2].t_admit > reqs[0].t_first_token


def test_syncs_inside_their_step(served):
    _, tr, _, _ = served
    by_id = {s.id: s for s in tr.spans}

    def root(s):
        while s.parent is not None:
            s = by_id[s.parent]
        return s
    for name in ("engine.decode", "engine.decode.sync", "engine.emit",
                 "engine.prefill.sync"):
        spans = _by_name(tr, name)
        assert spans
        for s in spans:
            step = root(s)
            assert step.name == "engine.step"
            assert step.start <= s.start <= s.end <= step.end


def test_counters_match_requests_served(served):
    srv, tr, reqs, _ = served
    steps = _by_name(tr, "engine.step")
    assert tr.totals["prefills"] == len(reqs) == len(srv.done)
    # every token after the first comes from a decode step
    assert tr.totals["slots"] == sum(len(r.output) - 1 for r in reqs)
    assert steps[0].counts["queued"] == len(reqs)
    assert sum(s.counts["prefills"] for s in steps) == len(reqs)
    decodes = len(_by_name(tr, "engine.decode"))
    assert decodes == sum(1 for s in steps if s.counts["slots"])


def test_injected_clock(tiny_cfg, tracer):
    ticks = iter(range(1000))
    srv = SlotServer(tiny_cfg, serve_cfg=ServeConfig(
        max_slots=2, max_len=16, max_new_tokens=2, eos_id=-1),
        clock=lambda: float(next(ticks)), tracer=tracer)
    req = srv.submit(np.arange(2, 6, dtype=np.int32))
    srv.run_until_drained()
    assert req.t_submit == 0.0 and req.t_admit == 1.0
    assert req.token_times == [2.0, 3.0] and req.t_finish == 4.0


def test_latency_stats_from_stamps():
    r = Request(0, np.zeros(3, np.int32), t_submit=1.0)
    r.t_admit, r.t_first_token = 1.5, 2.0
    r.token_times = [2.0, 2.1, 2.3]
    s = latency_stats([r])
    assert s["queue"] == (0.5, 0.5) and s["ttft"] == (1.0, 1.0)
    assert s["tpot"][0] == pytest.approx(0.15)
    assert s["tpot"][1] == pytest.approx(0.199)
    assert "tpot" not in latency_stats([Request(1, r.tokens, t_submit=0.0,
                                                t_admit=0.0,
                                                t_first_token=0.0)])


def test_serve_reports_after_warm_up(tiny_cfg, capsys):
    tr = obs.tracer()
    done, stats = serve(tiny_cfg, n_requests=3, max_slots=2, max_len=24,
                        max_new=3, seed=1)
    assert len(done) == 3 and set(stats) == {"queue", "ttft", "tpot"}
    assert "p50/p99 ms: queue" in capsys.readouterr().out
    # the warm-up compiled every prompt length: nothing in the timed part
    t_first = min(r.t_submit for r in done)
    assert tr.compiles_between(t_first, max(r.t_finish for r in done)) == 0


# -- on a profiler trace -----------------------------------------------------------

def test_spans_on_a_profiler_trace(tmp_path, tracer):
    from jax.profiler import ProfileData
    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with tracer.span("engine.step"):
                with tracer.span("engine.decode.sync", rid=i):
                    jax.block_until_ready(x @ x)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name in ("engine.step", "engine.decode.sync")]
    mine = list(tracer.spans)
    assert sorted(e.name for e in events) == sorted(s.name for s in mine)
    for name in ("engine.step", "engine.decode.sync"):
        got = sorted((e.end_ns - e.start_ns) * 1e-9 for e in events
                     if e.name == name)
        want = sorted(s.seconds for s in mine if s.name == name)
        assert got == pytest.approx(want, abs=50e-6)


# -- named scopes --------------------------------------------------------------------

def _canonical_hlo(text: str) -> str:
    """A compiled module's text without metadata and source tables, with
    instruction names numbered in order of first use."""
    text = text[text.index("\n%"):]
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    ids: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: ids.setdefault(m.group(0), f"%{len(ids)}"), text)


def test_named_scopes_change_metadata_only(tiny_cfg, monkeypatch, tracer):
    srv = SlotServer(tiny_cfg, serve_cfg=ServeConfig(max_slots=4, max_len=64),
                     tracer=tracer)
    args = (srv.params, srv._last, jnp.asarray(srv.pos), srv.caches,
            jnp.asarray(srv.active))

    def compiled():
        return jax.jit(srv._decode_impl).lower(*args).compile().as_text()
    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert scoped != plain
    assert _canonical_hlo(scoped) == _canonical_hlo(plain)
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    for path in ("decode/embed/", "decode/layers/while/body/squeeze",
                 "block/attention/", "block/kv_cache/", "block/mlp/",
                 "block/norm/", "decode/head/"):
        assert any(path in n for n in names), path
    assert not any("/decode/" in n for n in
                   re.findall(r'op_name="([^"]*)"', plain))
