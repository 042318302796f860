"""Production mesh definitions (TPU v5e).

single-pod: (data=16, model=16) = 256 chips.
multi-pod:  (pod=2, data=16, model=16) = 512 chips; the leading "pod"
axis carries only data parallelism (cross-pod DCI is the slow hop; see
optim/compression.py for the pod-axis gradient compressor).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over locally available devices (tests / examples)."""
    return _make_mesh((data, model), ("data", "model"))
