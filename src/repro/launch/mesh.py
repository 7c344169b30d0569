"""Production mesh factory.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any JAX
initialization, and smoke tests must keep seeing 1 device.

Meshes:
    single-pod : (16, 16)    = ("data", "model")            256 chips
    multi-pod  : (2, 16, 16) = ("pod", "data", "model")     512 chips

The ``pod`` axis composes with ``data`` for gradient reduction
(hierarchical: reduce-scatter intra-pod over ICI, all-reduce across pods
over DCN); the ``model`` axis stays inside one pod's ICI domain.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto(axes) -> tuple:
    """Auto axis types: the sharding rules here are GSPMD constraints, and
    ``jax.make_mesh`` otherwise makes every axis Explicit."""
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) == n:
        return jax.make_mesh(shape, axes, _auto(axes))
    assert len(devs) >= n, (f"need {n} devices for the production mesh; "
                            f"have {len(devs)} — is XLA_FLAGS set?")
    # dry-run process exposes 512 placeholder devices; the single-pod mesh
    # takes the first 256
    return Mesh(np.asarray(devs[:n]).reshape(shape), axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Elastic variant: any (pods, data, model) factorization of the
    available device count (used by the elastic-scaling tests)."""
    return jax.make_mesh(shape, axes, _auto(axes))


def single_device_mesh() -> Mesh:
    return make_mesh((1, 1), ("data", "model"))
