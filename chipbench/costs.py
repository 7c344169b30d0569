"""Operations and bytes that a step of a dense decoder needs, from its
shapes alone, whatever implements it.

Counts are of the work the algorithm requires: causal attention counts the
(query, key) pairs at or before each query, decode reads the live part of
each slot's cache, and recomputation (remat) is not counted.  A multiply
and an add are two operations.  ``arch`` is the ``arch`` block of a
configuration file (a dict).
"""
from __future__ import annotations


def _dims(arch: dict):
    d, h = arch["d_model"], arch["n_heads"]
    hk = arch.get("n_kv_heads") or h
    dh = arch.get("d_head") or d // h
    return d, h, hk, dh


def layer_matmul_params(arch: dict) -> int:
    """Weights one layer multiplies by: q, k, v, o and the MLP."""
    d, h, hk, dh = _dims(arch)
    attn = d * h * dh * 2 + d * hk * dh * 2
    gated = arch.get("activation", "swiglu") in ("swiglu", "geglu")
    mlp = d * arch["d_ff"] * (3 if gated else 2)
    return attn + mlp


def head_params(arch: dict) -> int:
    return arch["d_model"] * arch["vocab_size"]


def matmul_params(arch: dict) -> int:
    """Every weight a token is multiplied by, the LM head included."""
    return arch["n_layers"] * layer_matmul_params(arch) + head_params(arch)


def param_count(arch: dict) -> int:
    """All parameters (the untied embedding table too); the norms have
    none."""
    tied = arch.get("tie_embeddings", False)
    return matmul_params(arch) + (0 if tied else head_params(arch))


def kv_bytes_per_token(arch: dict, itemsize: int = 2) -> int:
    """Keys and values one position keeps, over all layers."""
    _, _, hk, dh = _dims(arch)
    return 2 * arch["n_layers"] * hk * dh * itemsize


def attn_pair_flops(arch: dict) -> int:
    """Operations of one (query, key) pair over all layers: q.k and p.v."""
    _, h, _, dh = _dims(arch)
    return 4 * arch["n_layers"] * h * dh


def prefill_flops(arch: dict, s: int) -> int:
    """One prompt of ``s`` tokens: every layer at every position, causal
    attention, and the head at the last position only (the one whose
    logits are served)."""
    layers = 2 * arch["n_layers"] * layer_matmul_params(arch) * s
    return layers + attn_pair_flops(arch) * s * (s + 1) // 2 \
        + 2 * head_params(arch)


def decode_flops(arch: dict, ctx: int) -> int:
    """One token that attends to ``ctx`` positions (itself included)."""
    return 2 * matmul_params(arch) + attn_pair_flops(arch) * ctx


def decode_step_cost(arch: dict, ctxs, itemsize: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one decode step over the active slots, each
    attending to its ``ctx`` positions.  Bytes: every weight read once
    (of an untied embedding only the rows looked up), each slot's live
    keys and values read, and the new ones written."""
    ctxs = list(ctxs)
    n = len(ctxs)
    d = arch["d_model"]
    flops = sum(decode_flops(arch, c) for c in ctxs)
    weights = matmul_params(arch) * itemsize
    if not arch.get("tie_embeddings", False):
        weights += n * d * itemsize
    kvb = kv_bytes_per_token(arch, itemsize)
    kv = sum(c - 1 for c in ctxs) * kvb + n * kvb
    return flops, weights + kv


def train_flops_per_token(arch: dict, seq: int) -> int:
    """Forward and backward (3x forward) of one token of a ``seq``-long
    row, causal attention, no recomputation: 6N plus attention."""
    return 6 * matmul_params(arch) + 3 * attn_pair_flops(arch) * (seq + 1) // 2


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
