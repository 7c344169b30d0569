"""Compile the main path for one chip of a described TPU v5e topology.

Nothing runs: the TPU compiler, installed with jax, refuses what the chip
would refuse (block shapes off the (8, 128) tiling, scoped-memory overruns,
programs too large for HBM), which interpret mode never sees.  Shapes are
olmo-1b's widths (d_model 2048, d_ff 8192, 16 heads of 128).

The topology is described only inside the module fixture: one process at a
time may load the TPU library, and every test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.atom_matmul.ops import atom_matmul
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import transformer
from repro.models.registry import init_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compile is written to the cache but cannot be read
    # back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_atom_matmul_compiles(one_chip):
    a, b = _spec(one_chip, (2048, 2048)), _spec(one_chip, (2048, 8192))
    _assert_kernel(jax.jit(functools.partial(atom_matmul, n_atoms=4))
                   .lower(a, b).compile())


def test_flash_attention_compiles(one_chip):
    qkv = [_spec(one_chip, (1, 2048, 16, 128))] * 3
    f = functools.partial(flash_attention, causal=True, n_atoms=2)
    _assert_kernel(jax.jit(f).lower(*qkv).compile())


@pytest.mark.parametrize("batch", [4, 8])
def test_decode_attention_compiles(one_chip, batch):
    """batch 4 is the shape whose per-row (1, 1) SMEM length block the chip's
    compiler refused; the lengths are now a scalar-prefetch operand."""
    q = _spec(one_chip, (batch, 16, 128))
    kv = _spec(one_chip, (batch, 2048, 16, 128))
    lens = _spec(one_chip, (batch,), jnp.int32)
    f = functools.partial(decode_attention, n_atoms=2)
    _assert_kernel(jax.jit(f).lower(q, kv, kv, lens).compile())


def test_olmo_1b_decode_step_compiles(one_chip, monkeypatch):
    # the process's backend is the CPU, where models.common upcasts bf16
    # dots; compile the bf16 dots the chip runs
    monkeypatch.setenv("REPRO_SAFE_DOT", "0")
    cfg = get_config("olmo-1b")
    on_chip = functools.partial(jax.tree.map,
                                lambda x: _spec(one_chip, x.shape, x.dtype))
    params = on_chip(jax.eval_shape(
        functools.partial(init_model, cfg), jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(
        lambda: transformer.init_caches(cfg, 8, 512)))
    tok = _spec(one_chip, (8,), jnp.int32)

    def step(params, tok, pos, caches):
        return transformer.decode_step(params, cfg, tok, pos, caches)

    compiled = jax.jit(step).lower(params, tok, tok, caches).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16 * 2 ** 30


def test_decode_scopes_name_the_recorded_chat_trace(one_chip, monkeypatch,
                                                    tmp_path):
    """The named scopes of ``SlotServer._decode_impl`` compiled at the chat
    cell's shapes name every operation of the decode step traced on a v5e
    chip (``chipbench/tests/data``, recorded before the scopes existed: the
    scopes change metadata only, so the operations and their names are the
    same), and put the step's time into its layers."""
    import gzip
    import types
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(root))
    monkeypatch.setenv("REPRO_SAFE_DOT", "0")
    from chipbench import scopes, trace, weights
    from repro.serve.engine import SlotServer

    arch = weights.arch_config(weights.load_config("olmo-1b"))
    on_chip = functools.partial(jax.tree.map,
                                lambda x: _spec(one_chip, x.shape, x.dtype))
    params = on_chip(weights._unflatten({
        p: {} if s is None else s for p, s in weights.layout(arch).items()}))
    caches = on_chip(jax.eval_shape(
        lambda: transformer.init_caches(arch, 16, 2048)))
    srv = SlotServer.__new__(SlotServer)
    srv.cfg = arch
    vec = _spec(one_chip, (16,), jnp.int32)
    hlo = jax.jit(srv._decode_impl).lower(
        params, vec, vec, caches, _spec(one_chip, (16,), jnp.bool_)
    ).compile().as_text()
    sc = scopes.hlo_scopes(hlo)
    path = tmp_path / "chat.xplane.pb"
    path.write_bytes(gzip.decompress(
        (root / "chipbench/tests/data/chat.xplane.pb.gz").read_bytes()))
    run = types.SimpleNamespace(reduced=trace.read(str(path), set()))
    whole = scopes.decode_ms(run, lambda p: True, sc)
    cache_io = scopes.decode_ms(
        run, lambda p: "layers" in p and "block" not in p, sc)
    attention = scopes.decode_ms(run, lambda p: "attention" in p, sc)
    assert whole == pytest.approx(35.98, abs=0.01)      # every op named
    assert cache_io == pytest.approx(26.68, abs=0.01)
    assert attention == pytest.approx(6.72, abs=0.01)
    assert "/block/attention/" in sc["%fusion.147 = bf16[16,16,128]"]
    # a trace of the program with its scopes: the compiled step names each
    # traced operation as the trace itself does
    path = tmp_path / "chat_scoped.xplane.pb"
    path.write_bytes(gzip.decompress(
        (root / "tests/data/chat_scoped.xplane.pb.gz").read_bytes()))
    traced = scopes.xplane_scopes(str(path))
    assert traced and {k: sc.get(k) for k in traced} == traced
