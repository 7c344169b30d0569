"""Compile the main path for one chip of a described TPU v5e topology.

Nothing runs: the TPU compiler, installed with jax, refuses what the chip
would refuse (block shapes off the (8, 128) tiling, scoped-memory overruns,
programs too large for HBM), which interpret mode never sees.  Shapes are
olmo-1b's widths (d_model 2048, d_ff 8192, 16 heads of 128).

The topology is described only inside the module fixture: one process at a
time may load the TPU library, and every test worker imports this file.
"""
import functools
import re
from pathlib import Path

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


def _chat_step(one_chip, monkeypatch):
    """olmo-1b at the chat cell's shapes (16 slots of 2048) on the described
    chip: a ``SlotServer`` that holds only its configuration, and the
    shapes of its parameters and caches."""
    root = Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(root))
    monkeypatch.setenv("REPRO_SAFE_DOT", "0")
    from chipbench import weights
    from repro.serve.engine import ServeConfig, SlotServer

    arch = weights.arch_config(weights.load_config("olmo-1b"))
    on_chip = functools.partial(jax.tree.map,
                                lambda x: _spec(one_chip, x.shape, x.dtype))
    params = on_chip(weights._unflatten({
        p: {} if s is None else s for p, s in weights.layout(arch).items()}))
    caches = on_chip(jax.eval_shape(
        lambda: transformer.init_caches(arch, 16, 2048)))
    srv = SlotServer.__new__(SlotServer)
    srv.cfg, srv.sc = arch, ServeConfig(max_slots=16, max_len=2048)
    return srv, params, caches


def _decode_args(one_chip, params, caches):
    vec = _spec(one_chip, (16,), jnp.int32)
    return params, vec, vec, caches, _spec(one_chip, (16,), jnp.bool_)


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w-]+)\(")


def _scheduled_ops(hlo: str) -> list:
    """(shape, opcode) of each instruction outside fused computations: the
    operations the chip runs and the buffers they write."""
    fused = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", hlo))
    comp, ops = None, []
    for line in hlo.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m and comp not in fused:
            ops.append(m.groups())
    return ops


def _hlo_shape(x) -> str:
    return f"bf16[{','.join(map(str, x))}]"


def test_chat_decode_step_updates_the_cache_in_place(one_chip, monkeypatch):
    """The decode step with its caches donated, as ``SlotServer`` runs it,
    writes each step's rows into the stacked caches in place: it aliases
    them whole, needs almost no scratch memory, and neither slices a
    layer's cache out (``bf16[16,2048,16,128]``) nor copies the stack."""
    srv, params, caches = _chat_step(one_chip, monkeypatch)
    compiled = jax.jit(srv._decode_impl, donate_argnames="caches").lower(
        *_decode_args(one_chip, params, caches)).compile()
    mem = compiled.memory_analysis()
    stacked = caches["groups"]["0"]["k"]
    assert mem.alias_size_in_bytes == 2 * stacked.size * stacked.dtype.itemsize
    assert mem.temp_size_in_bytes < 64 * 2 ** 20
    ops = _scheduled_ops(compiled.as_text())
    assert ops
    layer = _hlo_shape(stacked.shape[1:])
    assert not [op for shape, op in ops if shape == layer
                and op not in ("parameter", "get-tuple-element")]
    assert (_hlo_shape(stacked.shape), "copy") not in ops


@pytest.mark.parametrize("prompt", [64, 1024])
def test_chat_prefill_writes_the_slot_in_place(one_chip, monkeypatch, prompt):
    """The batch-1 prefill with its caches donated writes the new slot into
    the stacked caches without copying them."""
    srv, params, caches = _chat_step(one_chip, monkeypatch)
    compiled = jax.jit(srv._prefill_impl, donate_argnames="caches").lower(
        params, _spec(one_chip, (1, prompt), jnp.int32), caches, 3).compile()
    stacked = caches["groups"]["0"]["k"]
    assert compiled.memory_analysis().alias_size_in_bytes == \
        2 * stacked.size * stacked.dtype.itemsize
    ops = _scheduled_ops(compiled.as_text())
    assert ops and (_hlo_shape(stacked.shape), "copy") not in ops


def test_decode_scopes_name_the_recorded_chat_trace(one_chip, monkeypatch,
                                                    tmp_path):
    """The named scopes of ``SlotServer._decode_impl`` compiled at the chat
    cell's shapes, without donation as ``chipbench/scopes.py`` compiles
    it, name every operation of the decode step traced on a v5e chip with
    the cache updated in place (``tests/data/chat_inplace.xplane.pb.gz``,
    half a second of the chat cell), each as the trace itself does, and put
    the step's time into its layers."""
    import gzip
    import types

    srv, params, caches = _chat_step(one_chip, monkeypatch)
    from chipbench import scopes, trace

    hlo = jax.jit(srv._decode_impl).lower(
        *_decode_args(one_chip, params, caches)).compile().as_text()
    sc = scopes.hlo_scopes(hlo)
    root = Path(__file__).resolve().parents[1]
    path = tmp_path / "chat_inplace.xplane.pb"
    path.write_bytes(gzip.decompress(
        (root / "tests/data/chat_inplace.xplane.pb.gz").read_bytes()))
    traced = scopes.xplane_scopes(str(path))
    assert traced and {k: sc.get(k) for k in traced} == traced
    run = types.SimpleNamespace(reduced=trace.read(str(path), set()))

    def ms(keep):
        return scopes.decode_ms(run, keep, sc)
    assert ms(lambda p: True) == pytest.approx(9.88, abs=0.01)  # all named
    assert ms(lambda p: "attention" in p) == pytest.approx(6.70, abs=0.01)
    assert ms(lambda p: "kv_cache" in p) == pytest.approx(0.067, abs=0.001)
    # no operation slices a layer's cache out any more, and the only ones
    # that write the stacked caches are the row inserts under kv_cache
    assert not [k for k in traced if k.endswith("= bf16[16,2048,16,128]")]
    whole = [v for k, v in traced.items()
             if k.endswith("= bf16[16,16,2048,16,128]")]
    assert whole and all("/kv_cache/" in v for v in whole)
    # what the layer scan does outside any block is now only the slicing of
    # each layer's q, k and v weights (0.60 ms in the parent's trace, beside
    # 26.08 ms of slicing and writing back the caches)
    assert ms(lambda p: "layers" in p and "block" not in p) == \
        pytest.approx(0.59, abs=0.01)
