"""Shared model infrastructure: param trees, initializers, logical axes, dtype helpers.

All models are pure-functional JAX: ``init_*`` builds a nested-dict param tree;
apply functions take ``(params, inputs)``.  Sharding is expressed through
*logical axes*: every param leaf has a name-path, and ``logical_axes()`` maps
paths to logical dimension names which ``sharding.py`` resolves to mesh axes.
"""
from __future__ import annotations

import functools
import math
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def scoped(name: str):
    """Decorator: the function's operations under ``jax.named_scope(name)``,
    so that compiled ops and profiler traces name the layer they belong
    to.  Names change the HLO's metadata only."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16, "int8": jnp.int8}[name]


# ---------------------------------------------------------------------------
# Initializers (seeded, shape-aware)
# ---------------------------------------------------------------------------

def normal_init(key, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def fan_in_init(key, shape, dtype, fan_axis: int = 0):
    fan_in = shape[fan_axis] if shape else 1
    scale = 1.0 / math.sqrt(max(1, fan_in))
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def zeros_init(_key, shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(_key, shape, dtype):
    return jnp.ones(shape, dtype)


class KeyGen:
    """Deterministic key splitter so init order changes don't reshuffle seeds."""

    def __init__(self, key):
        self._key = key
        self._n = 0

    def __call__(self):
        self._n += 1
        return jax.random.fold_in(self._key, self._n)


# ---------------------------------------------------------------------------
# High-precision contraction helpers
# ---------------------------------------------------------------------------
# XLA:CPU's DotThunk cannot execute some fused BF16xBF16=F32 dots (it surfaces
# inside lax.scan bodies).  ``REPRO_SAFE_DOT`` controls an upcast-to-f32
# workaround: "auto" (default) enables it only on the CPU backend; the dry-run
# sets it to "0" so lowered TPU programs keep pure-bf16 dots (dry-runs never
# execute, so the thunk limitation is irrelevant there).

import os as _os


def _safe_dot() -> bool:
    mode = _os.environ.get("REPRO_SAFE_DOT", "auto")
    if mode == "auto":
        return jax.default_backend() == "cpu"
    return mode == "1"


def dot(x, w):
    """Matmul with f32 accumulation, output in x.dtype."""
    if _safe_dot() and x.dtype == jnp.bfloat16:
        return jnp.matmul(x.astype(jnp.float32),
                          w.astype(jnp.float32)).astype(x.dtype)
    return jnp.matmul(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def einsum(spec, *args, out_dtype=None):
    dt = out_dtype if out_dtype is not None else args[0].dtype
    if _safe_dot() and any(a.dtype == jnp.bfloat16 for a in args):
        out = jnp.einsum(spec, *(a.astype(jnp.float32) for a in args))
        return out.astype(dt)
    out = jnp.einsum(spec, *args, preferred_element_type=jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Logical axes by param path
# ---------------------------------------------------------------------------
# Rules are (regex-on-path, axes-tuple).  Paths look like
# "layers/attn/wq", "embed/tok", "layers/moe/wi", ...  A leading "L" axis is
# automatically added for stacked (scanned) layer params.

AXIS_RULES: list[tuple[str, tuple[str, ...]]] = [
    (r".*embed/tok$", ("vocab", "embed")),
    (r".*embed/pos$", (None, "embed")),
    (r".*head/w$", ("embed", "vocab")),
    (r".*(attn|xattn)/wq$", ("embed", "q_heads", "head")),
    (r".*(attn|xattn)/wk$", ("embed", "kv_heads", "head")),
    (r".*(attn|xattn)/wv$", ("embed", "kv_heads", "head")),
    (r".*(attn|xattn)/wo$", ("q_heads", "head", "embed")),
    (r".*(attn|xattn)/bq$", ("q_heads", "head")),
    (r".*(attn|xattn)/bk$", ("kv_heads", "head")),
    (r".*(attn|xattn)/bv$", ("kv_heads", "head")),
    (r".*mlp/wi$", ("embed", "ff")),
    (r".*mlp/wg$", ("embed", "ff")),
    (r".*mlp/wo$", ("ff", "embed")),
    (r".*moe/router$", ("embed", "experts")),
    (r".*moe/wi$", ("experts", "embed", "expert_ff")),
    (r".*moe/wg$", ("experts", "embed", "expert_ff")),
    (r".*moe/wo$", ("experts", "expert_ff", "embed")),
    (r".*moe/shared_wi$", ("embed", "ff")),
    (r".*moe/shared_wg$", ("embed", "ff")),
    (r".*moe/shared_wo$", ("ff", "embed")),
    # RG-LRU recurrent block
    (r".*rec/w_in$", ("embed", "rnn")),
    (r".*rec/w_gate_in$", ("embed", "rnn")),
    (r".*rec/conv_w$", (None, "rnn")),
    (r".*rec/conv_b$", ("rnn",)),
    (r".*rec/w_a$", ("rnn", "rnn_heads")),
    (r".*rec/w_i$", ("rnn", "rnn_heads")),
    (r".*rec/lam$", ("rnn",)),
    (r".*rec/w_out$", ("rnn", "embed")),
    # mLSTM / sLSTM
    (r".*mlstm/w_up$", ("embed", "ff")),
    (r".*mlstm/w_(q|k|v)$", ("ff", "q_heads", "head")),
    (r".*mlstm/w_(ig|fg)$", ("ff", "q_heads")),
    (r".*mlstm/b_(ig|fg)$", ("q_heads",)),
    (r".*mlstm/conv_w$", (None, "ff")),
    (r".*mlstm/w_down$", ("ff", "embed")),
    (r".*slstm/w_(i|f|z|o)$", ("embed", "q_heads", "head")),
    (r".*slstm/r_(i|f|z|o)$", ("q_heads", "head", "head")),
    (r".*slstm/b_(i|f|z|o)$", ("q_heads", "head")),
    (r".*slstm/ffn_wi$", ("embed", "ff")),
    (r".*slstm/ffn_wg$", ("embed", "ff")),
    (r".*slstm/ffn_wo$", ("ff", "embed")),
    # norms / misc
    (r".*(norm|ln)[^/]*/scale$", ("embed",)),
    (r".*(norm|ln)[^/]*/bias$", ("embed",)),
    (r".*vlm_proj/w$", ("embed", "embed2")),
]


def logical_axes_for_path(path: str, ndim: int) -> tuple:
    for pat, axes in AXIS_RULES:
        if re.match(pat, path):
            if len(axes) == ndim:
                return axes
            if len(axes) == ndim - 1:
                # stacked (scanned) layer param: leading layer axis
                return ("layers",) + axes
    return (None,) * ndim


def tree_paths(tree: PyTree, prefix: str = "") -> list[tuple[str, Any]]:
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(tree_paths(tree[k], f"{prefix}/{k}" if prefix else k))
    else:
        out.append((prefix, tree))
    return out


def logical_axes(params: PyTree) -> PyTree:
    """Mirror tree of logical-axis tuples for a param tree."""

    def walk(tree, prefix):
        if isinstance(tree, dict):
            return {k: walk(v, f"{prefix}/{k}" if prefix else k) for k, v in tree.items()}
        return logical_axes_for_path(prefix, np.ndim(tree) if not hasattr(tree, "ndim") else tree.ndim)

    return walk(params, "")


def count_params(params: PyTree) -> int:
    return sum(int(np.prod(x.shape)) for _, x in tree_paths(params) if hasattr(x, "shape"))


def cast_tree(params: PyTree, dtype) -> PyTree:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
