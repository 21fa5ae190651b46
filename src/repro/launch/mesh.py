"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first backend init — dryrun.py must set
XLA_FLAGS before anything imports jax).

Physical model: TPU v5e pods of 256 chips. Single-pod = (16, 16) over
("data", "model"); multi-pod adds a leading "pod" axis (2 × 256 = 512 chips)
— the "pod" axis carries only data parallelism (+ checkpoint-interval
gradient all-reduces), which is what survives the slower inter-pod (DCN)
links at 1000+ node scale.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """Auto axes: the sharding rules place arrays with
    `with_sharding_constraint`, which Explicit axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over however many (real or forced) devices exist — for
    tests and examples."""
    n = len(jax.devices())
    model = min(model, n)
    return _mesh((n // model, model), ("data", "model"))
