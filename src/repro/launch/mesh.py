"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax init; tests see 1 device).

  single pod : (16, 16)        axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16)     axes ("pod", "data", "model") = 512 chips

`pod` is the slowest axis (data-center interconnect): only DP gradient
reduction and optional FSDP parameter sharding cross it (DESIGN.md §5).
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """jax.make_mesh with every axis of type Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh():
    """Trivial mesh over however many devices exist (tests / examples)."""
    n = len(jax.devices())
    if n >= 4:
        return make_auto_mesh((n // 2, 2), ("data", "model"))
    return make_auto_mesh((n, 1), ("data", "model"))
