"""Production mesh construction.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips.  The pod axis defaults
to data-parallel replication (gradients cross the inter-pod links once per
step); ``launch/train.py --pipeline`` repurposes it as pipeline stages.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the sharding
    rules here (distributed/sharding.py, the serve engine's data axis)
    place arrays with ``NamedSharding`` + ``jit`` and let the compiler
    propagate.  jax's own default is explicit axes, which demand an
    ``out_sharding`` on every ambiguous gather."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_abstract_mesh(shape, axes):
    """Device-free mesh for static sharding-rule checks (axis sizes only)."""
    return AbstractMesh(tuple(shape), tuple(axes))


def make_host_mesh(model_parallel: int = 1):
    """Tiny mesh over the real local devices (CPU tests, laptop runs)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return make_mesh((n // mp, mp), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Axes carrying pure data parallelism (pod axis included if present)."""
    return ("pod", "data") if "pod" in mesh.shape else ("data",)
