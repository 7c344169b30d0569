"""Core layers: norms, MLPs, rotary embeddings, token embedding / LM head."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (KeyGen, dot, fan_in_init, normal_init, ones_init,
                                 scoped, zeros_init)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(keys: KeyGen, d: int, kind: str, dtype):
    if kind == "rmsnorm":
        return {"scale": ones_init(keys(), (d,), dtype)}
    if kind == "layernorm":
        return {"scale": ones_init(keys(), (d,), dtype), "bias": zeros_init(keys(), (d,), dtype)}
    if kind == "nonparam_ln":      # OLMo: LN without learnable params
        return {}
    raise ValueError(kind)


@scoped("norm")
def apply_norm(params, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP  (swiglu / sq_relu / gelu)
# ---------------------------------------------------------------------------

def init_mlp(keys: KeyGen, d: int, f: int, activation: str, dtype):
    p = {"wi": normal_init(keys(), (d, f), dtype), "wo": fan_in_init(keys(), (f, d), dtype)}
    if activation in ("swiglu", "geglu"):
        p["wg"] = normal_init(keys(), (d, f), dtype)
    return p


@scoped("mlp")
def apply_mlp(params, x, activation: str):
    h = dot(x, params["wi"])
    if activation == "swiglu":
        g = dot(x, params["wg"])
        h = jax.nn.silu(g) * h
    elif activation == "geglu":             # Gemma family: gated GELU
        g = dot(x, params["wg"])
        h = jax.nn.gelu(g) * h
    elif activation == "sq_relu":           # Nemotron-4: squared ReLU
        h = jnp.square(jax.nn.relu(h))
    elif activation == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(activation)
    return dot(h, params["wo"])


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)              # [head_dim/2]


def apply_rope(x, positions, theta: float):
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    freqs = rope_frequencies(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs     # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embed(keys: KeyGen, vocab: int, d: int, dtype, with_pos: int = 0):
    p = {"tok": normal_init(keys(), (vocab, d), dtype)}
    if with_pos:
        p["pos"] = normal_init(keys(), (with_pos, d), dtype)
    return p


@scoped("embed")
def embed_tokens(params, tokens):
    return jnp.take(params["tok"], tokens, axis=0)


def init_head(keys: KeyGen, d: int, vocab: int, dtype):
    return {"w": normal_init(keys(), (d, vocab), dtype)}


@scoped("head")
def apply_head(params, x, embed_params=None, softcap: float = 0.0):
    """LM head; uses tied embedding transpose when ``params`` is None."""
    from repro.models.common import _safe_dot
    w = embed_params["tok"].T if params is None else params["w"]
    if _safe_dot() and x.dtype == jnp.bfloat16:
        logits = jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))
    else:
        logits = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits
