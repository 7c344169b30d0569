"""Attention: GQA with RoPE; memory-efficient blocked implementations.

Three execution paths:

* ``naive_attention``      — O(S^2) reference; oracle for tests, decode path.
* ``blocked_attention``    — pure-jnp online-softmax flash (lax.scan over KV
                             blocks).  Causal uses a *triangular* iteration
                             space (no masked-out block is ever computed) when
                             ``block_skip=True``; sliding-window iterates only
                             blocks inside the window.  This is the dry-run /
                             TPU-lowering path.
* Pallas flash kernel      — ``repro.kernels.flash_attention`` (TPU target,
                             validated in interpret mode); selected by the
                             runtime when ``use_pallas=True``.

All math accumulates in f32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import (KeyGen, einsum, fan_in_init, normal_init, scoped,
                                 zeros_init)
from repro.models.layers import apply_rope


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(keys: KeyGen, d: int, n_heads: int, n_kv: int, head_dim: int,
                   dtype, qkv_bias: bool = False):
    p = {
        "wq": normal_init(keys(), (d, n_heads, head_dim), dtype),
        "wk": normal_init(keys(), (d, n_kv, head_dim), dtype),
        "wv": normal_init(keys(), (d, n_kv, head_dim), dtype),
        "wo": fan_in_init(keys(), (n_heads, head_dim, d), dtype),
    }
    if qkv_bias:
        p["bq"] = zeros_init(keys(), (n_heads, head_dim), dtype)
        p["bk"] = zeros_init(keys(), (n_kv, head_dim), dtype)
        p["bv"] = zeros_init(keys(), (n_kv, head_dim), dtype)
    return p


@scoped("attention")
def qkv_project(params, x, positions, rope_theta: float, use_rope: bool = True):
    """x: [B,S,D] -> q [B,S,Hq,Dh], k,v [B,S,Hkv,Dh] (RoPE applied)."""
    q = einsum("btd,dhk->bthk", x, params["wq"])
    k = einsum("btd,dhk->bthk", x, params["wk"])
    v = einsum("btd,dhk->bthk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


@scoped("attention")
def out_project(params, attn_out):
    """attn_out: [B,S,Hq,Dh] -> [B,S,D]."""
    return einsum("bthk,hkd->btd", attn_out, params["wo"])


# ---------------------------------------------------------------------------
# Reference (oracle) attention
# ---------------------------------------------------------------------------

def naive_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0):
    """q: [B,Sq,Hq,Dh], k/v: [B,Sk,Hkv,Dh].  GQA via head grouping."""
    B, Sq, Hq, Dh = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    G = Hq // Hk
    qg = q.reshape(B, Sq, Hk, G, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(Dh).astype(jnp.float32)
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Blocked online-softmax attention (pure jnp; the lowering path)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _block_scores(qg, kb, qpos, kpos, causal, window):
    """qg: [B,bq,Hk,G,D], kb: [B,bk,Hk,D] -> masked f32 scores [B,Hk,G,bq,bk]."""
    Dh = qg.shape[-1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), kb.astype(jnp.float32))
    s = s / jnp.sqrt(Dh).astype(jnp.float32)
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    mask &= kpos[None, :] >= 0
    return jnp.where(mask[None, None, None], s, NEG_INF)


def _online_update(carry, s, vb):
    """One online-softmax accumulation step.

    carry: (m [B,H,G,bq], l [B,H,G,bq], acc [B,H,G,bq,D]); s: [B,H,G,bq,bk].
    """
    m, l, acc = carry
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def _pad_to(x, axis, mult):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@scoped("attention")
def blocked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      block_q: int = 512, block_kv: int = 512,
                      block_skip: bool = True, q_offset: int = 0):
    """Memory-efficient attention; never materializes [Sq,Sk].

    causal + block_skip: triangular iteration space — exactly the lower-
    triangular blocks are computed (FLOP-exact, no masked-block waste).
    window: only blocks intersecting the window are visited.
    """
    B, Sq, Hq, Dh = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    G = Hq // Hk
    in_dtype = q.dtype

    block_q = min(block_q, max(16, Sq))
    block_kv = min(block_kv, max(16, Sk))
    q, _padq = _pad_to(q, 1, block_q)
    k, _padk = _pad_to(k, 1, block_kv)
    v, _ = _pad_to(v, 1, block_kv)
    Sqp, Skp = q.shape[1], k.shape[1]
    nQ, nK = Sqp // block_q, Skp // block_kv

    qg = q.reshape(B, nQ, block_q, Hk, G, Dh)
    kb = k.reshape(B, nK, block_kv, Hk, Dh)
    vb = v.reshape(B, nK, block_kv, Hk, Dh)

    def init_carry():
        m = jnp.full((B, Hk, G, block_q), NEG_INF, jnp.float32)
        l = jnp.zeros((B, Hk, G, block_q), jnp.float32)
        acc = jnp.zeros((B, Hk, G, block_q, Dh), jnp.float32)
        return m, l, acc

    def finalize(m, l, acc):
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc / l[..., None]                      # [B,H,G,bq,D]
        return out.transpose(0, 3, 1, 2, 4)           # [B,bq,Hk,G,D]

    if causal and block_skip and window == 0 and q_offset == 0 and nQ == nK:
        # Triangular iteration: flat scan over (i,j) with j<=i.
        pairs = [(i, j) for i in range(nQ) for j in range(i + 1)]
        ij = jnp.array(pairs, jnp.int32)              # [T,2]
        is_row_start = jnp.array([j == 0 for _, j in pairs], bool)
        is_row_end = jnp.array([j == i for i, j in pairs], bool)

        out_buf = jnp.zeros((nQ, B, block_q, Hk, G, Dh), jnp.float32)

        def body(carry, inp):
            m, l, acc, out = carry
            (i, j), row_start, row_end = inp
            m = jnp.where(row_start, NEG_INF, m)
            l = jnp.where(row_start, 0.0, l)
            acc = jnp.where(row_start, 0.0, acc)
            qi = jax.lax.dynamic_index_in_dim(qg, i, 1, keepdims=False)
            kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
            qpos = i * block_q + jnp.arange(block_q)
            kpos = j * block_kv + jnp.arange(block_kv)
            s = _block_scores(qi, kj, qpos, kpos, True, 0)
            m, l, acc = _online_update((m, l, acc), s, vj)
            fin = finalize(m, l, acc)
            out = jax.lax.cond(
                row_end,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, fin, i, 0),
                lambda o: o, out)
            return (m, l, acc, out), None

        carry0 = (*init_carry(), out_buf)
        (m, l, acc, out_buf), _ = jax.lax.scan(
            body, carry0, (ij, is_row_start, is_row_end))
        out = out_buf.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sqp, Hq, Dh)
        return out[:, :Sq].astype(in_dtype)

    # Generic path: scan over q blocks; inner scan over a kv-block range.
    w_blocks = (window + block_kv - 1) // block_kv + 1 if window else 0

    def q_block_body(_, i):
        qi = jax.lax.dynamic_index_in_dim(qg, i, 1, keepdims=False)
        qpos = q_offset + i * block_q + jnp.arange(block_q)

        if window:
            # visit blocks j in [jc - w_blocks + ... , jc]; jc = block of q end
            jc = (q_offset + (i + 1) * block_q - 1) // block_kv
            deltas = jnp.arange(w_blocks + 1)
            js = jnp.clip(jc - w_blocks + deltas, -1, nK - 1)
        else:
            js = jnp.arange(nK)

        def kv_body(carry, j):
            kj = jax.lax.dynamic_index_in_dim(kb, jnp.maximum(j, 0), 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vb, jnp.maximum(j, 0), 1, keepdims=False)
            kpos = jnp.where(j < 0, -1, j * block_kv + jnp.arange(block_kv))
            s = _block_scores(qi, kj, qpos, kpos, causal, window)
            return _online_update(carry, s, vj), None

        (m, l, acc), _ = jax.lax.scan(kv_body, init_carry(), js)
        return None, finalize(m, l, acc)

    _, outs = jax.lax.scan(q_block_body, None, jnp.arange(nQ))   # [nQ,B,bq,H,G,D]
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sqp, Hq, Dh)
    return out[:, :Sq].astype(in_dtype)


# ---------------------------------------------------------------------------
# Decode attention (single new token against a KV cache)
# ---------------------------------------------------------------------------

@scoped("attention")
def decode_attention(q, k_cache, v_cache, cur_len, *, window: int = 0,
                     layer=None):
    """q: [B,Hq,Dh]; caches: [B,Smax,Hkv,Dh], or the stacked [G,B,Smax,Hkv,Dh]
    of a layer scan with ``layer`` selecting one; cur_len: int [] or
    per-slot [B] (tokens valid per batch row — continuous batching).

    For sliding-window layers the cache is a ring buffer of size ``window``
    and every slot < min(cur_len, window) is valid.
    """
    if layer is not None:
        k_cache = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
        v_cache = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
    B, Hq, Dh = q.shape
    Smax, Hk = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hk
    qg = q.reshape(B, Hk, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    s = s / jnp.sqrt(Dh).astype(jnp.float32)
    kpos = jnp.arange(Smax)
    cur = jnp.broadcast_to(jnp.asarray(cur_len), (B,))
    limit = jnp.minimum(cur, window) if window else cur
    valid = kpos[None, :] < limit[:, None]                 # [B,Smax]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, Hq, Dh).astype(q.dtype)


@scoped("kv_cache")
def update_kv_cache(k_cache, v_cache, k_new, v_new, pos, *, window: int = 0,
                    layer=None):
    """Insert k/v at ``pos`` ([B,1,Hkv,Dh] or [B,S,Hkv,Dh] prefill).

    ``pos`` may be a scalar (shared position) or [B] (per-slot positions —
    continuous batching; requires S == 1).  With ``layer`` the caches are
    the stacked [G,B,Smax,Hkv,Dh] of a layer scan, carried through it: only
    the B new rows are written, at ``[layer, b, pos[b]]``, so a donated
    cache is updated in place and never copied (decode only).
    """
    # never let the insert promote the cache (a f32 update would carry the
    # WHOLE cache in f32 through the layer scan — 2x HBM + convert traffic)
    k_new = k_new.astype(k_cache.dtype)
    v_new = v_new.astype(v_cache.dtype)
    pos = jnp.asarray(pos)
    if pos.ndim == 1 or layer is not None:
        assert k_new.shape[1] == 1, "per-slot insert is decode-only"
        B = k_new.shape[0]
        idx = jnp.broadcast_to((pos % window) if window else pos, (B,))
        at = (jnp.arange(B), idx) if layer is None else (layer, jnp.arange(B), idx)
        k_cache = k_cache.at[at].set(k_new[:, 0], unique_indices=True)
        v_cache = v_cache.at[at].set(v_new[:, 0], unique_indices=True)
        return k_cache, v_cache
    if window:
        S = k_new.shape[1]
        idx = (pos + jnp.arange(S)) % window
        k_cache = k_cache.at[:, idx].set(k_new)
        v_cache = v_cache.at[:, idx].set(v_new)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, pos, axis=1)
    return k_cache, v_cache
