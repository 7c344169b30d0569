"""Plain float32 reference of OLMo (arXiv:2402.00838), with no import of the
program: embedding, then per layer a parameter-free LayerNorm (epsilon
1e-5, OLMo's, see ``LN_EPS``), multi-head
attention with rotary positions (rotate-half, theta from the
configuration), a parameter-free LayerNorm and a SwiGLU MLP, each added to
the residual; a final LayerNorm and the head (the embedding's transpose
where tied).  Every matrix product is float32 at HIGHEST precision.

It reads the weights by their names in the tree the benchmark made:
``embed/tok``, ``head/w`` (untied), ``blocks/0/attn/{wq,wk,wv,wo}`` and
``blocks/0/mlp/{wi,wg,wo}``, stacked over layers.  Layers run one at a
time in a scan, cast to float32 as each is reached, so the whole model is
never held in float32.

``lowp=True`` is the control: every linear layer (projections, MLP, head)
multiplies float8 operands, each tensor scaled to its format's range, as
a lower-precision serving or training path would: e4m3 for weights and
activations, e5m2 for the gradients flowing back (the usual hybrid float8
training recipe).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
# OLMo's LayerNorm epsilon, as the configuration files state it.
LN_EPS = 1e-5
E4M3, E5M2 = (jnp.float8_e4m3fn, 448.0), (jnp.float8_e5m2, 57344.0)


def _q8(x, fmt=E4M3):
    """x rounded through a float8 format with a per-tensor scale."""
    dtype, top = fmt
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _mm8(x, w):
    return jnp.matmul(_q8(x), _q8(w), precision=HI)


def _mm8_fwd(x, w):
    return _mm8(x, w), (x, w)


def _mm8_bwd(res, g):
    x, w = res
    qx, qw, qg = _q8(x), _q8(w), _q8(g, E5M2)
    dx = jnp.matmul(qg, qw.T, precision=HI)
    dw = jnp.matmul(qx.reshape(-1, qx.shape[-1]).T,
                    qg.reshape(-1, qg.shape[-1]), precision=HI)
    return dx, dw


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _linear(x, w, lowp):
    """x [..., K] times w [K, ...] -> [..., ...], in float32."""
    k = w.shape[0]
    w2 = w.astype(jnp.float32).reshape(k, -1)
    x2 = x.reshape(-1, k)
    y = _mm8(x2, w2) if lowp else jnp.matmul(x2, w2, precision=HI)
    return y.reshape(x.shape[:-1] + w.shape[1:])


def _ln(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _rope(x, theta):
    """x [B, S, H, Dh]; rotate-half rotary embedding at positions 0..S-1."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, theta, lowp):
    """One block on x [B, S, D] float32."""
    a, m = lp["attn"], lp["mlp"]
    h = _ln(x)
    q = _rope(_linear(h, a["wq"], lowp), theta)
    k = _rope(_linear(h, a["wk"], lowp), theta)
    v = _linear(h, a["wv"], lowp)
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(dh)
    n = x.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
    x = x + _linear(o.reshape(o.shape[:2] + (-1,)),
                    a["wo"].reshape(-1, a["wo"].shape[-1]), lowp)
    h = _ln(x)
    g = jax.nn.silu(_linear(h, m["wg"], lowp)) * _linear(h, m["wi"], lowp)
    return x + _linear(g, m["wo"], lowp)


def _head_w(params):
    if "head" in params:
        return params["head"]["w"].astype(jnp.float32)
    return params["embed"]["tok"].astype(jnp.float32).T


def hidden(params, tokens, theta, lowp=False):
    """Final-norm hidden states [B, S, D] float32 for tokens [B, S]."""
    x = params["embed"]["tok"][tokens].astype(jnp.float32)

    @jax.checkpoint
    def body(x, lp):
        lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
        return _layer(x, lp, theta, lowp), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["0"])
    return _ln(x)


@functools.partial(jax.jit, static_argnames=("theta",))
def serve_gaps(params, tokens, served, theta):
    """For one padded sequence tokens [S] and the token served after each
    position (served [S]): how far the served token's logit lies below
    the reference's best there.  Returns [S]."""
    ref = jnp.matmul(hidden(params, tokens[None], theta)[0],
                     _head_w(params), precision=HI)             # [S, V]
    return ref.max(-1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("theta",))
def control_gaps(params, tokens, theta):
    """The gap of the float8 control's greedy token under the float32
    reference, at every position of tokens [S]."""
    w = _head_w(params)
    ref = jnp.matmul(hidden(params, tokens[None], theta)[0], w, precision=HI)
    low = _mm8(hidden(params, tokens[None], theta, lowp=True)[0], w)
    pick = low.argmax(-1)
    return ref.max(-1) - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


# -- training ----------------------------------------------------------------

def _loss(params, tokens, labels, theta, lowp, chunk=512):
    """Mean next-token cross-entropy; the head is applied in row chunks."""
    h = hidden(params, tokens, theta, lowp)
    w = _head_w(params)
    b, s, d = h.shape
    chunk = min(chunk, s)
    hc = h.reshape(b, s // chunk, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, s // chunk, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def part(hx, lx):
        lg = _mm8(hx, w) if lowp else jnp.matmul(hx, w, precision=HI)
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, lx[..., None], -1)[..., 0]
        return (lse - gold).sum()

    tot, _ = jax.lax.scan(lambda c, xs: (c + part(*xs), None),
                          jnp.zeros((), jnp.float32), (hc, lc))
    return tot / (b * s)


@functools.partial(jax.jit, static_argnames=("theta", "lowp"))
def loss_and_grads(params, tokens, labels, theta, lowp=False):
    return jax.value_and_grad(_loss)(params, tokens, labels, theta, lowp)


def lr_at(step: int, opt: dict) -> float:
    """The configured schedule: linear warm-up, then cosine to
    ``min_lr_ratio`` of the peak over ``total_steps``."""
    base, warm = opt["lr"], opt["warmup_steps"]
    if step < warm:
        return base * min(1.0, (step + 1) / max(1, warm))
    frac = min(max((step - warm) / max(1, opt["total_steps"] - warm), 0.0),
               1.0)
    r = opt.get("min_lr_ratio", 0.1)
    return base * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * frac)))


@jax.jit
def _adamw_leaf(p, g, m, v, scale, lr, c1, c2, b1, b2, eps, wd):
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
    if p.ndim >= 2:                                  # decay matrices only
        upd = upd + wd * p
    return p - lr * upd, m, v


def _flat(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_flat(tree[k], f"{prefix}/{k}" if prefix else k))
        return out
    return {prefix: tree}


def _set(tree, path, value):
    *parents, leaf = path.split("/")
    for p in parents:
        tree = tree[p]
    tree[leaf] = value


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def train(make_params, batches, theta, opt: dict, lowp=False,
          sample_at=None):
    """AdamW from the weights ``make_params()`` returns (served dtype),
    over ``batches`` (list of (tokens, labels)), in float32.  The moments live on the host, one
    leaf at a time on the device, so that parameters, gradients and both
    moments never share the chip.  Returns the loss of each step, each
    leaf's clipped gradient norm at step 1, each leaf's change
    ``|p_n - p_0|`` after the last step, and the clipped step-1 gradient
    at the flat indices ``sample_at[leaf]``."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    params = jax.tree.map(lambda x: x.astype(jnp.float32), make_params())
    moments: dict = {}
    losses, grad_norms, samples = [], {}, {}
    for t, (tokens, labels) in enumerate(batches, start=1):
        loss, grads = loss_and_grads(params, tokens, labels, theta, lowp)
        losses.append(float(loss))
        gflat = _flat(grads)
        del grads
        gnorm = float(np.sqrt(sum(float(_norm(g)) ** 2
                                  for g in gflat.values())))
        scale = min(1.0, opt["grad_clip"] / max(gnorm, 1e-12))
        if t == 1:
            grad_norms = {k: float(_norm(g)) * scale
                          for k, g in gflat.items()}
            samples = {k: np.asarray(g.reshape(-1)[sample_at[k]]) * scale
                       for k, g in gflat.items() if sample_at}
        lr = lr_at(t - 1, opt)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        pflat = _flat(params)
        for k in sorted(gflat):
            m, v = moments.get(k, (0.0, 0.0))
            m = jnp.zeros_like(gflat[k]) if t == 1 else jnp.asarray(m)
            v = jnp.zeros_like(gflat[k]) if t == 1 else jnp.asarray(v)
            p, m, v = _adamw_leaf(pflat[k], gflat[k], m, v, scale, lr,
                                  c1, c2, b1, b2, eps, wd)
            gflat[k] = None
            _set(params, k, p)
            if t < len(batches):
                moments[k] = (np.asarray(m), np.asarray(v))
            del m, v
        del gflat, pflat
    p0 = _flat(make_params())
    change = {k: float(_diff_norm(p, p0[k]))
              for k, p in _flat(params).items()}
    return losses, grad_norms, change, samples


@functools.partial(jax.jit, static_argnames=("theta",))
def loss(params, tokens, labels, theta):
    return _loss(params, tokens, labels, theta, False)
