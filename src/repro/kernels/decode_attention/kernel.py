"""Atomizable GQA decode attention — one new token against a KV cache.

Layout (kernel-internal): rows = B*Hkv "request-head" units.

    q   [R, G, D]       (G = q heads per kv head)
    k,v [R, S, D]
    len [R] int32       valid cache length per row (continuous batching)
    out [R, G, D]

Grid = (num_rows, nK): row-major over schedulable rows, sequential online-
softmax accumulation over KV blocks of ``block_k``.  An *atom* executes rows
``[start, start+num_rows)`` — the TPU-native form of LithOS §4.4 atomization
for the decode hot loop (each row is one "thread block": it touches its own
KV stripe only, so disjoint row ranges compose exactly).

The running output is passed in and aliased (``input_output_aliases``) so
rows outside the atom pass through untouched.  The whole ``len`` vector is a
scalar-prefetch operand in SMEM: a per-row ``(1, 1)`` block over ``[R, 1]``
breaks the TPU's (8, 128) block-tiling rule and is refused by its compiler.

Memory behaviour: decode attention is HBM-bound (reads S*D keys+values per
row for O(S*D) flops); the kernel streams KV through VMEM in (block_k, D)
tiles with f32 online-softmax state in scratch — the TPU analogue of the
paper's "memory-bound kernels are frequency-insensitive" class (§4.6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_in_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, start: int, nk: int,
                        block_k: int, sm_scale: float):
    row, k_idx = pl.program_id(0), pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # [G, D]
    kb = k_ref[0].astype(jnp.float32)                 # [block_k, D]
    vb = v_ref[0].astype(jnp.float32)                 # [block_k, D]
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                   # [G, block_k]
    kpos = k_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    valid = kpos < len_ref[start + row]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                             # [G, block_k]
    corr = jnp.exp(m_prev - m_new)                     # [G, 1]
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(k_idx == nk - 1)
    def _flush():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_atom(q, k, v, lens, o, *, start: int, num_rows: int,
                          block_k: int = 512, interpret: bool = False):
    """Execute one atom: rows [start, start+num_rows) of decode attention.

    q: [R,G,D]; k/v: [R,S,D]; lens: [R] int32; o: running output [R,G,D]
    (aliased).  S must divide by block_k (ops pads)."""
    R, G, D = q.shape
    S = k.shape[1]
    assert k.shape == (R, S, D) and v.shape == (R, S, D)
    assert lens.shape == (R,) and o.shape == (R, G, D)
    assert S % block_k == 0, (S, block_k)
    assert 0 <= start and start + num_rows <= R, (start, num_rows, R)
    nk = S // block_k
    sm_scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(_decode_attn_kernel, start=start, nk=nk,
                               block_k=block_k, sm_scale=sm_scale)
    # index maps take the prefetched lens ref as a trailing argument
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_rows, nk),
            in_specs=[
                pl.BlockSpec((1, G, D), lambda r, k, _: (start + r, 0, 0)),
                pl.BlockSpec((1, block_k, D),
                             lambda r, k, _: (start + r, k, 0)),
                pl.BlockSpec((1, block_k, D),
                             lambda r, k, _: (start + r, k, 0)),
                pl.BlockSpec((1, G, D), lambda r, k, _: (start + r, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, G, D),
                                   lambda r, k, _: (start + r, 0, 0)),
            scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                            pltpu.VMEM((G, 1), jnp.float32),
                            pltpu.VMEM((G, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, G, D), o.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lens, q, k, v, o)
