"""Atomizable GQA flash-attention Pallas kernel (TPU target).

Flash attention with online softmax; the schedulable tile space is the
flattened (batch x q_head x q_block) dimension, so — like ``atom_matmul`` —
a LithOS atom is a contiguous range ``[start, start+num_tiles)`` of that
space, expressed with offset BlockSpec index maps (no early-exit waste).

Layouts (kernel-internal):
    q  [B*Hq, Sq, D]        k/v  [B*Hk, Sk, D]        o  [B*Hq, Sq, D]

GQA is resolved in the index maps: tile t serves flat q-row ``bh``, which
reads kv-row ``(bh // Hq) * Hk + (bh % Hq) // (Hq // Hk)``.

Causal masking aligns the query block to the *end* of the key range
(``qpos = Sk - Sq + global_q_index``), covering both self-attention
(Sq == Sk) and chunked prefill (Sq < Sk).  Fully-masked KV blocks are
skipped with ``pl.when`` — on TPU the grid is sequential, so a skipped step
costs one loop iteration, not a dead thread-block launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_in_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, sm_scale: float, causal: bool, nk: int, block_q: int,
                  block_k: int, q_pos_offset: int, start: int, n_qblocks: int):
    t, ki = pl.program_id(0), pl.program_id(1)
    qi = (start + t) % n_qblocks

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = q_pos_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)

    # visit only KV blocks with at least one unmasked element
    if causal:
        block_needed = ki * block_k <= (q_pos_offset + qi * block_q
                                        + block_q - 1)
    else:
        block_needed = ki >= 0                        # traced "always true"

    @pl.when(block_needed)
    def _step():
        q = q_ref[0].astype(jnp.float32)             # [bq, D]
        k = k_ref[0].astype(jnp.float32)             # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]      # [bq,1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_atom(q, k, v, o, *, start: int, num_tiles: int,
                         sm_scale: float, causal: bool = True,
                         block_q: int = 512, block_k: int = 512,
                         q_pos_offset: int = 0,
                         interpret: bool = False) -> jax.Array:
    """One atom of flash attention over flat tiles [start, start+num_tiles).

    q: [BHq, Sq, D]; k/v: [BHk, Sk, D]; o: running output [BHq, Sq, D]
    (aliased — tiles outside the atom pass through).
    """
    BHq, Sq, D = q.shape
    BHk, Sk, _ = k.shape
    assert BHq % BHk == 0
    G = BHq // BHk                         # q rows per kv row (within a batch)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_qblocks = Sq // block_q
    nk = Sk // block_k
    total = BHq * n_qblocks
    assert 0 <= start and start + num_tiles <= total

    def bh(t):
        return (start + t) // n_qblocks

    def qi(t):
        return (start + t) % n_qblocks

    def kvh(t):
        return bh(t) // G

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, nk=nk,
        block_q=block_q, block_k=block_k, q_pos_offset=q_pos_offset + Sk - Sq,
        start=start, n_qblocks=n_qblocks)
    return pl.pallas_call(
        kernel,
        grid=(num_tiles, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda t, ki: (bh(t), qi(t), 0)),
            pl.BlockSpec((1, block_k, D), lambda t, ki: (kvh(t), ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda t, ki: (kvh(t), ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda t, ki: (bh(t), qi(t), 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda t, ki: (bh(t), qi(t), 0)),
        out_shape=jax.ShapeDtypeStruct((BHq, Sq, D), o.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v, o)
