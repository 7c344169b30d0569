"""Optimizers: AdamW with configurable moment dtype (fp32 / bf16 / int8).

The int8 mode stores both Adam moments block-quantized (per-256-block absmax
scales kept in fp32), cutting optimizer HBM from 8 to ~2 bytes/param — the
difference that lets nemotron-4-340b train on a 256-chip v5e pod
(DESIGN.md §6).  Moment trees inherit the parameter sharding, so quantized
blocks never cross shard boundaries in practice (block size 256 divides all
sharded dim products in the assigned configs).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

PyTree = Any
QBLOCK = 128     # one v5e lane; every sharded last-dim shard divides it


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Block-quantized int8 tensor + per-block fp32 scales.

    Layout is **sharding-preserving**: quantization blocks run along the
    last dimension only, so ``q`` has exactly the parameter's shape (last
    dim padded to a QBLOCK multiple) and inherits the parameter's
    PartitionSpec unchanged; ``scale`` drops the last dim to n_blocks.
    A global flatten (the naive layout) destroys GSPMD sharding
    propagation and costs a full parameter gather per optimizer step —
    the dominant collective in the 340B-config dry-runs before this fix
    (EXPERIMENTS.md §Perf).  ``shape`` is static pytree aux data."""

    def __init__(self, q: jax.Array, scale: jax.Array, shape: tuple):
        self.q = q            # int8 [..., last_padded]
        self.scale = scale    # f32  [..., n_blocks]
        self.shape = tuple(shape)

    def tree_flatten(self):
        return (self.q, self.scale), self.shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)


def quantize(x: jax.Array) -> QTensor:
    shape = tuple(x.shape) if x.ndim else (1,)
    x2 = x.reshape(shape).astype(jnp.float32)
    last = shape[-1]
    pad = (-last) % QBLOCK
    if pad:
        widths = [(0, 0)] * (len(shape) - 1) + [(0, pad)]
        x2 = jnp.pad(x2, widths)
    blocks = x2.reshape(shape[:-1] + ((last + pad) // QBLOCK, QBLOCK))
    scale = jnp.max(jnp.abs(blocks), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale[..., None]), -127, 127)
    q = q.reshape(shape[:-1] + (last + pad,)).astype(jnp.int8)
    return QTensor(q, scale, tuple(x.shape))


def dequantize(t: QTensor) -> jax.Array:
    shape = t.shape if t.shape else (1,)
    last_p = t.q.shape[-1]
    blocks = t.q.reshape(t.q.shape[:-1] + (last_p // QBLOCK, QBLOCK))
    out = blocks.astype(jnp.float32) * t.scale[..., None]
    out = out.reshape(t.q.shape[:-1] + (last_p,))[..., :shape[-1]]
    return out.reshape(t.shape)


class OptState(NamedTuple):
    step: jax.Array
    mu: PyTree
    nu: PyTree


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    moment_dtype: str = "float32"     # float32 | bfloat16 | int8


def _encode_moment(x, dtype: str, positive: bool = False):
    if dtype == "int8":
        # second moment (positive, huge dynamic range): quantize in sqrt
        # domain so relative error stays bounded and small values survive
        return quantize(jnp.sqrt(x) if positive else x)
    if dtype == "bfloat16":
        return x.astype(jnp.bfloat16)
    return x.astype(jnp.float32)


def _decode_moment(x, dtype: str, positive: bool = False):
    if dtype == "int8":
        d = dequantize(x)
        return jnp.square(d) if positive else d
    return x.astype(jnp.float32)


def adamw_init(params: PyTree, cfg: AdamWConfig) -> OptState:
    # mu and nu get buffers of their own: a train step that donates the
    # state may not be handed one buffer twice
    def zeros():
        return jax.tree.map(
            lambda p: _encode_moment(jnp.zeros(p.shape, jnp.float32),
                                     cfg.moment_dtype), params)
    return OptState(step=jnp.zeros((), jnp.int32), mu=zeros(), nu=zeros())


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def adamw_update(params: PyTree, grads: PyTree, state: OptState,
                 cfg: AdamWConfig, lr: Optional[jax.Array] = None
                 ) -> tuple[PyTree, OptState, dict]:
    """One AdamW step.  Works leaf-wise; moments round-trip through the
    configured encoding."""
    lr = cfg.lr if lr is None else lr
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12)) \
        if cfg.grad_clip > 0 else 1.0
    step = state.step + 1
    c1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    c2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def leaf(p, g, mu, nu):
        g = g.astype(jnp.float32) * clip
        mu = _decode_moment(mu, cfg.moment_dtype)
        nu = _decode_moment(nu, cfg.moment_dtype, positive=True)
        mu = cfg.b1 * mu + (1 - cfg.b1) * g
        nu = cfg.b2 * nu + (1 - cfg.b2) * g * g
        upd = (mu / c1) / (jnp.sqrt(nu / c2) + cfg.eps)
        if p.ndim >= 2:                       # decay matrices only
            upd = upd + cfg.weight_decay * p.astype(jnp.float32)
        new_p = (p.astype(jnp.float32) - lr * upd).astype(p.dtype)
        return (new_p, _encode_moment(mu, cfg.moment_dtype),
                _encode_moment(nu, cfg.moment_dtype, positive=True))

    is_q = lambda x: isinstance(x, QTensor)
    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    mu_leaves = jax.tree.flatten(state.mu, is_leaf=is_q)[0]
    nu_leaves = jax.tree.flatten(state.nu, is_leaf=is_q)[0]
    trip = [leaf(p, g, m, n) for p, g, m, n
            in zip(p_leaves, g_leaves, mu_leaves, nu_leaves)]
    new_p = treedef.unflatten([t[0] for t in trip])
    new_mu = treedef.unflatten([t[1] for t in trip])
    new_nu = treedef.unflatten([t[2] for t in trip])
    return new_p, OptState(step, new_mu, new_nu), {"grad_norm": gnorm}


def make_optimizer(moment_dtype: str = "float32", **kw):
    cfg = AdamWConfig(moment_dtype=moment_dtype, **kw)

    def init(params):
        return adamw_init(params, cfg)

    def update(params, grads, state, lr=None):
        return adamw_update(params, grads, state, cfg, lr)

    return cfg, init, update
