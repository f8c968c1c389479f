"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE first jax
init; everything else sees the real single CPU device).

Mesh axes:
  single-pod  (16, 16)      ('data', 'model')   — 256 chips (one v5e pod)
  multi-pod   (2, 16, 16)   ('pod', 'data', 'model') — 512 chips
Growing the 'pod' axis scales to 1000+ nodes: cross-pod traffic is the
DP gradient all-reduce (optionally int8-compressed,
repro.optim.compression) — matched to the DCN-vs-ICI bandwidth split.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes are all Auto: the models place activations with
    sharding constraints that GSPMD may propagate, which Explicit axes
    (jax.make_mesh's default) would turn into asserts."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh(*, multi_pod: bool = False):
    """Tiny meshes for plumbing tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)
