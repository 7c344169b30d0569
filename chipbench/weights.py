"""Configurations and their seeded weights.

A configuration file (``configs/<name>.json``) holds the source's own keys
as published, with ``reduced`` naming those changed, and under ``arch``
the program's ``ArchConfig`` fields as the benchmark runs them.

Weights are made here, not by the program: one jitted call from the seed,
on the device, in the dtype they are served in.  The tree's layout is the
program's (from ``jax.eval_shape`` of its initialiser, which allocates
nothing); every leaf is drawn by a rule on its path, so the reference
regenerates the same values from the same seed.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
INIT_STD = 0.02          # OLMo's init_std; output projections use 1/sqrt(fan_in)


def load_config(name: str, root: Path = HERE) -> dict:
    return json.loads((root / "configs" / f"{name}.json").read_text())


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    return ArchConfig(**cfg["arch"])


def jax_key(seed: int, stream: str):
    """A JAX key for one named use of a seed of any size."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1),
                                 *[ord(c) for c in stream]])
    return jax.random.PRNGKey(int(ss.generate_state(1, np.uint32)[0] >> 1))


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        if not tree:             # a parameter-free norm keeps its place
            yield prefix, None
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def flat(tree) -> dict:
    """{path: leaf} of a nested dict, parameter-free norms left out."""
    return {p: x for p, x in _paths(tree) if x is not None}


def _unflatten(items: dict):
    out: dict = {}
    for path, v in items.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def layout(arch) -> dict:
    """The program's parameter tree as shapes: {path: ShapeDtypeStruct}."""
    from repro.models.registry import init_model
    shapes = jax.eval_shape(lambda: init_model(arch, jax.random.PRNGKey(0)))
    return dict(_paths(shapes))


def _std(path: str, shape) -> float:
    if path.endswith("/wo"):     # attn [L,H,Dh,D] and mlp [L,F,D]: fan-in
        lead = 1 if path.startswith("blocks/") else 0
        return 1.0 / math.sqrt(int(np.prod(shape[lead:-1])))
    return INIT_STD


def make_weights(shapes: dict, seed: int):
    """Weights for ``shapes`` (from ``layout``), in their served dtype, in
    one jitted call."""
    # the key is an argument, not a constant of the program, so that every
    # seed runs the one compiled program
    def build(key):
        out = {}
        for i, (path, s) in enumerate(sorted(shapes.items())):
            if s is None:
                continue
            k = jax.random.fold_in(key, i)
            x = _std(path, s.shape) * jax.random.normal(k, s.shape,
                                                        jnp.float32)
            out[path] = x.astype(s.dtype)
        return out

    made = jax.jit(build)(jax_key(seed, "weights"))
    # a parameter-free norm keeps its place as an empty dict
    return _unflatten({p: made.get(p, {}) for p in shapes})
