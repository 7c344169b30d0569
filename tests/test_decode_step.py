"""The serving decode step against teacher-forced ``forward``, and the
serving engine's in-place cache update.

``decode_step`` carries the stacked K/V caches of the scanned attention
layers through its layer scan and writes only each step's new rows; the
recurrent states (``rec``, ``mlstm``, ``slstm``) are scanned per layer.
Each slot is prefilled at its own prompt length, so the slots decode at
different positions, as they do under continuous batching.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.models import transformer
from repro.serve.engine import ServeConfig, SlotServer

MAX_LEN = 32
PROMPTS = (5, 9, 13)        # one prompt length per slot
STEPS = 12


def _config(arch):
    cfg = get_config(arch).reduced()
    kw = {"dtype": "float32"}
    if arch == "olmo-1b":            # dense: three scanned attention layers
        kw["n_layers"] = 3
    elif arch == "recurrentgemma-9b":
        # (rec, rec, attn) twice and a remainder rec layer; a window of 8
        # so that the ring buffer wraps within the decode
        kw["n_layers"] = 7
        kw["hybrid"] = dataclasses.replace(cfg.hybrid, window=8)
    elif arch == "xlstm-1.3b":       # 7 mLSTM + 1 sLSTM, one remainder mLSTM
        kw["n_layers"] = 9
    return dataclasses.replace(cfg, **kw)


@pytest.mark.parametrize("arch", ["olmo-1b", "recurrentgemma-9b",
                                  "xlstm-1.3b"])
def test_decode_step_matches_forward(arch):
    cfg = _config(arch)
    B = len(PROMPTS)
    srv = SlotServer(cfg, serve_cfg=ServeConfig(max_slots=B, max_len=MAX_LEN))
    params = srv.params
    rng = np.random.default_rng(0)
    tokens = rng.integers(2, cfg.vocab_size, (B, max(PROMPTS) + STEPS)
                          ).astype(np.int32)
    h, _ = transformer.forward(params, cfg, jnp.asarray(tokens))
    want = np.asarray(transformer.lm_logits(params, cfg, h), np.float32)
    scale = np.abs(want).max()

    caches = srv.caches
    for b, n in enumerate(PROMPTS):
        logits, caches = srv._prefill(params, jnp.asarray(tokens[b:b + 1, :n]),
                                      caches, b)
        np.testing.assert_allclose(np.asarray(logits), want[b, n - 1],
                                   atol=2e-4 * scale)

    step = jax.jit(lambda p, t, pos, c: transformer.decode_step(p, cfg, t, pos, c),
                   donate_argnums=3)
    pos = np.array(PROMPTS)
    for _ in range(STEPS):
        tok = tokens[np.arange(B), pos]
        logits, caches = step(params, jnp.asarray(tok), jnp.asarray(pos), caches)
        np.testing.assert_allclose(np.asarray(logits), want[np.arange(B), pos],
                                   atol=2e-4 * scale)
        pos += 1
    if "attn" in transformer.layer_pattern(cfg) and cfg.hybrid is not None:
        assert pos.max() > 2 * cfg.hybrid.window      # the ring wrapped


def test_slotserver_donates_its_caches():
    """Each step hands its caches to the prefill and decode programs, which
    update them in place: the caches passed in are deleted, and the engine
    keeps the ones returned."""
    cfg = get_config("olmo-1b").reduced()
    srv = SlotServer(cfg, serve_cfg=ServeConfig(max_slots=2, max_len=32,
                                                max_new_tokens=4))
    srv.submit(np.arange(2, 8, dtype=np.int32))
    for _ in range(2):              # an admission and a decode, then a decode
        before = jax.tree.leaves(srv.caches)
        srv.step()
        assert all(x.is_deleted() for x in before)
        assert not any(x.is_deleted() for x in jax.tree.leaves(srv.caches))
