"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 (data, model).  Multi-pod: 2x16x16 (pod, data,
    model) — the 'pod' axis is DP by default and the pipeline axis when
    ``ParallelConfig.pipeline_stages > 1``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small-scale runs)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist right now, as a 1D 'data' mesh (trainer)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
