"""Unified decoder-only LM covering dense / MoE / hybrid / SSM / VLM configs.

Layer stacks are *pattern-grouped scans*: the per-arch layer pattern (e.g.
``("rec","rec","attn")`` for RecurrentGemma, ``("mlstm",)*7+("slstm",)`` for
xLSTM, ``("attn",)`` for dense) is the scan body; params are stacked over
``n_groups = n_layers // len(pattern)`` so HLO size is O(1) in depth.  The
remainder ``n_layers % len(pattern)`` layers are applied unrolled.

Entry points:
    init_lm(cfg, key)                  -> params
    forward(params, cfg, tokens, ...)  -> final hidden states [B,S,D]
    lm_logits / lm_loss                -> chunked vocab projection (never
                                          materializes [B,S,V])
    prefill(...) / decode_step(...)    -> serving paths with caches/states
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.common import KeyGen, dtype_of, scoped
from repro.models.layers import (apply_head, apply_mlp, apply_norm, embed_tokens,
                                 init_embed, init_head, init_mlp, init_norm)
from repro.models.moe import apply_moe, init_moe
from repro.models.sharding import shard_act

PyTree = Any


def layer_pattern(cfg: ArchConfig) -> tuple[str, ...]:
    if cfg.hybrid is not None:
        return cfg.hybrid.pattern
    return ("attn",)


def _window_for(cfg: ArchConfig, kind: str) -> int:
    if kind == "attn" and cfg.hybrid is not None:
        return cfg.hybrid.window
    return 0


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(keys: KeyGen, cfg: ArchConfig, kind: str) -> PyTree:
    dt = dtype_of(cfg.dtype)
    d = cfg.d_model
    p: dict = {"ln1": init_norm(keys, d, cfg.norm, dt)}
    if kind == "attn":
        p["attn"] = attn_lib.init_attention(
            keys, d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dt, cfg.qkv_bias)
        p["ln2"] = init_norm(keys, d, cfg.norm, dt)
        if cfg.moe is not None:
            p["moe"] = init_moe(keys, d, cfg.moe, dt)
        else:
            p["mlp"] = init_mlp(keys, d, cfg.d_ff, cfg.activation, dt)
    elif kind == "rec":
        h = cfg.hybrid
        p["rec"] = rglru_lib.init_rglru_block(keys, d, h.lru_width or d, h.conv_width, dt)
        p["ln2"] = init_norm(keys, d, cfg.norm, dt)
        if cfg.moe is not None:
            p["moe"] = init_moe(keys, d, cfg.moe, dt)
        else:
            p["mlp"] = init_mlp(keys, d, cfg.d_ff, cfg.activation, dt)
    elif kind == "mlstm":
        p["mlstm"] = ssm_lib.init_mlstm_block(keys, d, cfg.n_heads, cfg.hybrid.conv_width, dt)
    elif kind == "slstm":
        p["slstm"] = ssm_lib.init_slstm_block(keys, d, cfg.n_heads, dt)
    else:
        raise ValueError(kind)
    return p


def init_lm(cfg: ArchConfig, key) -> PyTree:
    keys = KeyGen(key)
    dt = dtype_of(cfg.dtype)
    pat = layer_pattern(cfg)
    p_len = len(pat)
    n_groups, n_rem = cfg.n_layers // p_len, cfg.n_layers % p_len

    params: dict = {"embed": init_embed(keys, cfg.vocab_size, cfg.d_model, dt)}
    if not cfg.tie_embeddings:
        params["head"] = init_head(keys, cfg.d_model, cfg.vocab_size, dt)
    params["final_norm"] = init_norm(keys, cfg.d_model, cfg.norm, dt)
    if cfg.frontend == "patch_stub":
        from repro.models.common import normal_init
        params["vlm_proj"] = {"w": normal_init(keys(), (cfg.d_model, cfg.d_model), dt)}

    blocks = {}
    if n_groups:
        for pos, kind in enumerate(pat):
            stacked = [_init_block(keys, cfg, kind) for _ in range(n_groups)]
            blocks[str(pos)] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
    params["blocks"] = blocks
    if n_rem:
        params["rem"] = {str(i): _init_block(keys, cfg, pat[i]) for i in range(n_rem)}
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill hidden states)
# ---------------------------------------------------------------------------

@scoped("block")
def _apply_block(bp, x, cfg: ArchConfig, kind: str, positions, *,
                 block_skip: bool = True, attn_block: int = 512,
                 mlstm_chunk: int = 256):
    """Residual block application on [B,S,D] activations."""
    window = _window_for(cfg, kind)
    aux = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    if kind == "attn":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        q, k, v = attn_lib.qkv_project(bp["attn"], h, positions, cfg.rope_theta)
        q = shard_act(q, "act_bthd")
        o = attn_lib.blocked_attention(
            q, k, v, causal=True, window=window,
            block_q=attn_block, block_kv=attn_block, block_skip=block_skip)
        x = x + attn_lib.out_project(bp["attn"], o)
        h = apply_norm(bp["ln2"], x, cfg.norm)
        if cfg.moe is not None:
            mo, aux = apply_moe(bp["moe"], h, cfg.moe)
            x = x + mo
        else:
            x = x + apply_mlp(bp["mlp"], h, cfg.activation)
    elif kind == "rec":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        x = x + rglru_lib.apply_rglru_block(bp["rec"], h)
        h = apply_norm(bp["ln2"], x, cfg.norm)
        x = x + apply_mlp(bp["mlp"], h, cfg.activation)
    elif kind == "mlstm":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        x = x + ssm_lib.apply_mlstm_block(bp["mlstm"], h, chunk=mlstm_chunk)
    elif kind == "slstm":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        x = x + ssm_lib.apply_slstm_block(bp["slstm"], h)
    return x, aux


def embed_inputs(params, cfg: ArchConfig, tokens=None, input_embeds=None):
    if input_embeds is not None:
        x = input_embeds
        if "vlm_proj" in params:
            from repro.models.common import dot
            x = dot(x, params["vlm_proj"]["w"])
    else:
        x = embed_tokens(params["embed"], tokens)
    return x


def forward(params, cfg: ArchConfig, tokens=None, *, input_embeds=None,
            remat: str = "none", block_skip: bool = True,
            attn_block: int = 512) -> tuple[jax.Array, tuple]:
    """Token/embedding inputs -> final-norm hidden states [B,S,D] + aux losses."""
    x = embed_inputs(params, cfg, tokens, input_embeds)
    x = shard_act(x, "act_btd")
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    pat = layer_pattern(cfg)

    def group_body(carry, gp):
        x, lb, zl = carry
        for pos, kind in enumerate(pat):
            x, (a_lb, a_zl) = _apply_block(
                gp[str(pos)], x, cfg, kind, positions,
                block_skip=block_skip, attn_block=attn_block)
            lb, zl = lb + a_lb, zl + a_zl
        x = shard_act(x, "act_btd")
        return (x, lb, zl), None

    body = group_body
    if remat == "full":
        body = jax.checkpoint(group_body)
    elif remat == "dots":
        body = jax.checkpoint(
            group_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    zero = jnp.zeros((), jnp.float32)
    if params.get("blocks"):
        with jax.named_scope("layers"):
            (x, lb, zl), _ = jax.lax.scan(body, (x, zero, zero),
                                          params["blocks"])
    else:
        lb = zl = zero
    for i in sorted(params.get("rem", {})):
        x, (a_lb, a_zl) = _apply_block(
            params["rem"][i], x, cfg, pat[int(i)], positions,
            block_skip=block_skip, attn_block=attn_block)
        lb, zl = lb + a_lb, zl + a_zl
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, (lb, zl)


# ---------------------------------------------------------------------------
# Vocab projection: chunked (never materializes [B,S,V])
# ---------------------------------------------------------------------------

def lm_logits(params, cfg: ArchConfig, h):
    head = params.get("head")
    emb = params["embed"] if head is None else None
    return apply_head(head, h, emb, cfg.logit_softcap)


def lm_loss(params, cfg: ArchConfig, h, labels, *, chunk: int = 512,
            mask=None) -> jax.Array:
    """Mean next-token cross-entropy with seq-chunked vocab projection."""
    B, S, D = h.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        if mask is not None:
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
    Sp = h.shape[1]
    nC = Sp // chunk
    hc = h.reshape(B, nC, chunk, D).swapaxes(0, 1)
    lc = labels.reshape(B, nC, chunk).swapaxes(0, 1)
    mc = (mask.reshape(B, nC, chunk).swapaxes(0, 1) if mask is not None
          else (lc >= 0))

    @jax.checkpoint
    def chunk_loss(hx, lx, mx):
        logits = lm_logits(params, cfg, hx)          # [B,chunk,V] f32
        logits = shard_act(logits, "act_btv")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(lx, 0)[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mx
        return nll.sum(), mx.sum()

    def body(carry, xs):
        tot, cnt = carry
        s, c = chunk_loss(*xs)
        return (tot + s, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, lc, mc.astype(jnp.float32)))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Serving: caches and states
# ---------------------------------------------------------------------------
# Cache structure (plain dict, scan-compatible):
#   {"groups": {pos: stacked-cache [G,...]}, "rem": {i: cache}}
# where pos indexes the layer pattern and rem the remainder layers.

def _init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int):
    dt = dtype_of(cfg.dtype)
    if kind == "attn":
        w = _window_for(cfg, kind)
        S = min(max_len, w) if w else max_len
        shape = (batch, S, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if kind == "rec":
        width = cfg.hybrid.lru_width or cfg.d_model
        return (jnp.zeros((batch, width), dt),
                jnp.zeros((batch, cfg.hybrid.conv_width - 1, width), dt))
    if kind == "mlstm":
        di = int(ssm_lib.MLSTM_EXPANSION * cfg.d_model)
        hd = di // cfg.n_heads
        st = ssm_lib.init_mlstm_state(batch, cfg.n_heads, hd)
        return (st, jnp.zeros((batch, cfg.hybrid.conv_width - 1, di), dt))
    if kind == "slstm":
        hd = cfg.d_model // cfg.n_heads
        return ssm_lib.init_slstm_state(batch, cfg.n_heads, hd)
    raise ValueError(kind)


def init_caches(cfg: ArchConfig, batch: int, max_len: int):
    pat = layer_pattern(cfg)
    G = cfg.n_layers // len(pat)
    n_rem = cfg.n_layers % len(pat)
    groups = {}
    if G:
        for pos, kind in enumerate(pat):
            c = _init_block_cache(cfg, kind, batch, max_len)
            groups[str(pos)] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), c)
    rem = {str(i): _init_block_cache(cfg, pat[i], batch, max_len)
           for i in range(n_rem)}
    return {"groups": groups, "rem": rem}


@scoped("block")
def _decode_block(bp, x, cfg, kind, pos_scalar, cache, layer=None):
    """x: [B,1,D]; cache: this block's state slice or, for an attention
    block given its ``layer``, the stacked K/V caches of its position in
    the pattern.  Returns (x, new_cache)."""
    window = _window_for(cfg, kind)
    if kind == "attn":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        B = x.shape[0]
        pos_arr = jnp.asarray(pos_scalar)
        positions = (pos_arr[:, None] if pos_arr.ndim == 1
                     else jnp.broadcast_to(pos_arr, (B, 1)))
        q, k, v = attn_lib.qkv_project(bp["attn"], h, positions, cfg.rope_theta)
        kc, vc = attn_lib.update_kv_cache(
            cache["k"], cache["v"], k, v, pos_scalar, window=window, layer=layer)
        o = attn_lib.decode_attention(q[:, 0], kc, vc, pos_scalar + 1,
                                      window=window, layer=layer)
        x = x + attn_lib.out_project(bp["attn"], o[:, None])
        h = apply_norm(bp["ln2"], x, cfg.norm)
        if cfg.moe is not None:
            mo, _ = apply_moe(bp["moe"], h, cfg.moe)
            x = x + mo
        else:
            x = x + apply_mlp(bp["mlp"], h, cfg.activation)
        return x, {"k": kc, "v": vc}
    if kind == "rec":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        o, st = rglru_lib.decode_rglru_block(bp["rec"], h, cache)
        x = x + o
        h = apply_norm(bp["ln2"], x, cfg.norm)
        x = x + apply_mlp(bp["mlp"], h, cfg.activation)
        return x, st
    if kind == "mlstm":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        st, conv = cache
        o, st_new, conv_new = ssm_lib.decode_mlstm_block(bp["mlstm"], h, st, conv)
        return x + o, (st_new, conv_new)
    if kind == "slstm":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        o, st = ssm_lib.decode_slstm_block(bp["slstm"], h, cache)
        return x + o, st
    raise ValueError(kind)


@scoped("block")
def _prefill_block(bp, x, cfg, kind, positions, cache, *, block_skip, attn_block):
    """Prompt-length block application that also fills this block's cache."""
    window = _window_for(cfg, kind)
    S = x.shape[1]
    if kind == "attn":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        q, k, v = attn_lib.qkv_project(bp["attn"], h, positions, cfg.rope_theta)
        o = attn_lib.blocked_attention(
            q, k, v, causal=True, window=window,
            block_q=attn_block, block_kv=attn_block, block_skip=block_skip)
        x = x + attn_lib.out_project(bp["attn"], o)
        h = apply_norm(bp["ln2"], x, cfg.norm)
        if cfg.moe is not None:
            mo, _ = apply_moe(bp["moe"], h, cfg.moe)
            x = x + mo
        else:
            x = x + apply_mlp(bp["mlp"], h, cfg.activation)
        if window:
            keep = min(window, S)
            kc, vc = attn_lib.update_kv_cache(
                cache["k"], cache["v"], k[:, -keep:], v[:, -keep:],
                jnp.int32(max(0, S - keep)), window=window)
        else:
            kc, vc = attn_lib.update_kv_cache(cache["k"], cache["v"], k, v, jnp.int32(0))
        return x, {"k": kc, "v": vc}
    if kind == "rec":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        o, st = rglru_lib.apply_rglru_block(
            bp["rec"], h, conv_state=cache[1], return_state=True)
        x = x + o
        h = apply_norm(bp["ln2"], x, cfg.norm)
        x = x + apply_mlp(bp["mlp"], h, cfg.activation)
        return x, st
    if kind == "mlstm":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        st_in, _conv = cache
        o, st = ssm_lib.apply_mlstm_block(bp["mlstm"], h, state=st_in, return_state=True)
        return x + o, st
    if kind == "slstm":
        h = apply_norm(bp["ln1"], x, cfg.norm)
        o, st = ssm_lib.apply_slstm_block(bp["slstm"], h, state=cache, return_state=True)
        return x + o, st
    raise ValueError(kind)


def decode_step(params, cfg: ArchConfig, token, pos_scalar, caches, *,
                input_embeds=None):
    """One-token decode.  token: [B] int32 (or input_embeds [B,1,D]).
    ``pos_scalar`` may be a scalar (shared) or [B] per-slot positions
    (continuous batching).

    The stacked K/V caches of the scanned attention layers ride in the
    scan's carry, each layer writing only its new rows into them, so that
    a donated cache is updated in place; the recurrent states, a few MB a
    layer, are sliced out per layer and stacked again.

    Returns (logits [B,V] f32, new caches).
    """
    x = embed_inputs(params, cfg, token[:, None] if token is not None else None,
                     input_embeds)
    pat = layer_pattern(cfg)
    groups = caches["groups"]
    kv = {p: c for p, c in groups.items() if pat[int(p)] == "attn"}
    states = {p: c for p, c in groups.items() if p not in kv}

    def group_body(carry, xs):
        x, kv = carry
        gp, state_slices, layer = xs
        new_states = {}
        for pos, kind in enumerate(pat):
            p = str(pos)
            if p in kv:
                x, kv[p] = _decode_block(gp[p], x, cfg, kind, pos_scalar,
                                         kv[p], layer)
            else:
                x, new_states[p] = _decode_block(gp[p], x, cfg, kind, pos_scalar,
                                                 state_slices[p])
        return (x, kv), new_states

    new_groups = groups
    if params.get("blocks"):
        n_groups = cfg.n_layers // len(pat)
        with jax.named_scope("layers"):
            (x, kv), states = jax.lax.scan(
                group_body, (x, kv),
                (params["blocks"], states, jnp.arange(n_groups)))
        new_groups = {**kv, **states}
    new_rem = {}
    for i in sorted(params.get("rem", {})):
        x, new_rem[i] = _decode_block(
            params["rem"][i], x, cfg, pat[int(i)], pos_scalar, caches["rem"][i])
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x)[:, 0]
    return logits, {"groups": new_groups, "rem": new_rem}


def prefill(params, cfg: ArchConfig, tokens, *, input_embeds=None,
            max_len: Optional[int] = None, block_skip: bool = True,
            attn_block: int = 512):
    """Process a prompt, filling caches.  Returns (last-position logits, caches)."""
    x = embed_inputs(params, cfg, tokens, input_embeds)
    B, S, _ = x.shape
    max_len = max_len or S
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    pat = layer_pattern(cfg)
    caches = init_caches(cfg, B, max_len)

    def group_body(x, xs):
        gp, cache_slices = xs
        new_slices = {}
        for pos, kind in enumerate(pat):
            x, new_slices[str(pos)] = _prefill_block(
                gp[str(pos)], x, cfg, kind, positions, cache_slices[str(pos)],
                block_skip=block_skip, attn_block=attn_block)
        return x, new_slices

    new_groups = caches["groups"]
    if params.get("blocks"):
        with jax.named_scope("layers"):
            x, new_groups = jax.lax.scan(
                group_body, x, (params["blocks"], caches["groups"]))
    new_rem = {}
    for i in sorted(params.get("rem", {})):
        x, new_rem[i] = _prefill_block(
            params["rem"][i], x, cfg, pat[int(i)], positions, caches["rem"][i],
            block_skip=block_skip, attn_block=attn_block)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = lm_logits(params, cfg, x[:, -1:])[:, 0]
    return logits, {"groups": new_groups, "rem": new_rem}
