"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS first)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods = 512
    chips as (pod=2, data=16, model=16); the pod axis composes with data for
    batch/FSDP sharding and carries the cross-pod (DCN-class) collectives."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=types)


def make_host_mesh():
    """Whatever this host actually has (tests / examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
