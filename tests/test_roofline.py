"""Roofline pipeline tests: trip-count-aware HLO analyzer vs closed-form
programs; collective parser; workload trace sanity (6ND)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_config
from repro.core.costmodel import CostModel
from repro.core.types import DeviceSpec
from repro.core.workloads import (decode_step_trace, prefill_trace,
                                  train_step_trace)
from repro.roofline.hlo import collective_bytes
from repro.roofline.hlo_cost import analyze


def test_analyzer_counts_scan_trips():
    def f(x, w):
        def body(c, _):
            return jnp.dot(c, w,
                           preferred_element_type=jnp.float32).astype(
                c.dtype), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    cost = analyze(comp.as_text())
    expected = 7 * 2 * 64 ** 3
    assert expected <= cost.flops <= 1.05 * expected
    # XLA's own analysis counts the body once — the bug we correct
    xla = float(comp.cost_analysis().get("flops", 0.0))
    assert xla < 0.5 * expected


def test_analyzer_nested_scans():
    def g(x, w):
        def outer(c, _):
            def inner(c2, _):
                return jnp.dot(c2, w, preferred_element_type=jnp.float32
                               ).astype(c2.dtype), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    comp = jax.jit(g).lower(x, w).compile()
    cost = analyze(comp.as_text())
    expected = 15 * 2 * 32 ** 3
    assert expected <= cost.flops <= 1.1 * expected


def test_collective_parser_on_synthetic_hlo():
    hlo = """
ENTRY %main (p: f32[16,1024]) -> f32[16,1024] {
  %p = f32[16,1024]{1,0} parameter(0)
  %ar = f32[16,1024]{1,0} all-reduce(%p), replica_groups=[4,8]<=[32], to_apply=%add
  %ag = f32[64,1024]{1,0} all-gather(%p), replica_groups=[8,4]<=[32], dimensions={0}
  ROOT %out = f32[16,1024]{1,0} add(%ar, %p)
}
"""
    by = collective_bytes(hlo)
    n = 16 * 1024 * 4
    assert by["all-reduce"] == pytest.approx(2 * n * 7 / 8)
    assert by["all-gather"] == pytest.approx(4 * n * 3 / 4)


def test_trace_flops_match_6nd():
    """Workload-compiler train traces land within 2x of 6·N·D."""
    for arch in ("llama3-8b", "olmo-1b"):
        cfg = get_config(arch)
        B, S = 4, 2048
        ops = train_step_trace(cfg, B, S)
        total = sum(op.flops for op in ops)
        model = 6.0 * cfg.param_count() * B * S
        assert 0.6 * model < total < 2.0 * model, (arch, total / model)


def test_decode_trace_memory_bound():
    cfg = get_config("llama3-8b")
    dev = DeviceSpec.a100_like()
    cm = CostModel(dev)
    ops = decode_step_trace(cfg, 1, 8192)
    big = max(ops, key=lambda o: o.bytes)
    assert not cm.is_compute_bound(big.work())


def test_prefill_trace_compute_heavier_than_decode():
    cfg = get_config("llama3-8b")
    pre = sum(op.flops for op in prefill_trace(cfg, 1, 8192))
    dec = sum(op.flops for op in decode_step_trace(cfg, 1, 8192))
    assert pre > 100 * dec
