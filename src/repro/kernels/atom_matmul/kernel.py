"""Atomizable tiled matmul — the TPU-native form of LithOS kernel atomization.

The paper's Kernel Atomizer (§4.4) splits a CUDA kernel's grid of thread
blocks into contiguous block-index ranges ("atoms") via a Prelude kernel that
early-exits blocks outside ``[start, start+len)``.  On TPU the grid is
software-controlled, so an atom is expressed *exactly* — an offset BlockSpec
index map over a sub-grid — with zero early-exit waste (beyond-paper win, see
DESIGN.md §2).

    C[M,N] = A[M,K] @ B[K,N]

is tiled (bm, bn, bk); the 2-D output tile space (nm x nn) is flattened
row-major into ``T = nm*nn`` schedulable tiles.  One atom executes tiles
``[start, start+num_tiles)`` over the full K reduction:

    grid = (num_tiles, nk)       # ("arbitrary", "arbitrary") semantics
    A tile  (t, k) -> (m(start+t), k)
    B tile  (t, k) -> (k, n(start+t))
    C tile  (t, k) -> (m(start+t), n(start+t))

The running output C is passed in and aliased to the output buffer
(``input_output_aliases``), so tiles outside the atom pass through untouched
and atoms compose: running every atom once, in any order, over disjoint
ranges covering [0, T) yields exactly ``A @ B`` (property-tested).

f32 accumulation lives in a VMEM scratch tile; the cast to the output dtype
happens once per tile at the last K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_atom_kernel(a_ref, b_ref, c_in_ref, c_ref, acc_ref, *, nk: int):
    """One (tile, k) grid step: accumulate a_tile @ b_tile into acc scratch."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def matmul_atom(a: jax.Array, b: jax.Array, c: jax.Array, *, start: int,
                num_tiles: int, block_m: int = 256, block_n: int = 256,
                block_k: int = 256, interpret: bool = False) -> jax.Array:
    """Execute one atom: output tiles [start, start+num_tiles) of ``a @ b``.

    ``c`` is the running output (aliased to the result); tiles outside the
    atom are preserved.  All of M, N, K must divide by the block sizes
    (``ops.atom_matmul`` pads).
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and c.shape == (M, N)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        (M, N, K), (block_m, block_n, block_k))
    nm, nn, nk = M // block_m, N // block_n, K // block_k
    total = nm * nn
    assert 0 <= start and start + num_tiles <= total, (start, num_tiles, total)

    def mi(t):
        return (start + t) // nn

    def ni(t):
        return (start + t) % nn

    kernel = functools.partial(_matmul_atom_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(num_tiles, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda t, k: (mi(t), k)),
            pl.BlockSpec((block_k, block_n), lambda t, k: (k, ni(t))),
            pl.BlockSpec((block_m, block_n), lambda t, k: (mi(t), ni(t))),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda t, k: (mi(t), ni(t))),
        out_shape=jax.ShapeDtypeStruct((M, N), c.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(a, b, c)


def tile_count(M: int, N: int, block_m: int = 256, block_n: int = 256) -> int:
    """Schedulable tiles for an (M, N) output — the atomizer's grid size."""
    return -(-M // block_m) * -(-N // block_n)
