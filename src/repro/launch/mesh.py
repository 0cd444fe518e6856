"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

A function, not a module-level constant: importing this module never
touches jax device state, so smoke tests keep seeing 1 device while the
dry-run sees the 512 placeholder devices it forces via XLA_FLAGS.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Every axis `Auto`: GSPMD propagates layouts from the few explicit
    placements and `with_sharding_constraint`s (DESIGN.md §11); jax's
    default `Explicit` axes would demand a sharding on every op."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis is
    the unit of elastic scaling and joins `data` for batch/FSDP sharding."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh over however many (host-platform) devices exist — used by
    the sharded integration tests."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
