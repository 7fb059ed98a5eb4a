"""Production mesh definitions.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the pod axis
is pure data parallelism whose gradient all-reduce crosses the DCN.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices=None):
    # Auto axes: placement follows the argument shardings and the in-model
    # shard() constraints.  jax.make_mesh's default Explicit axes reject
    # ops whose output sharding is ambiguous, e.g. the embedding gather on
    # the vocab-sharded table.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 1):
    """Small (data, model) mesh over the first ``n_devices`` devices
    (capped at the number that exist); "model" is 2 wide when n is even."""
    devices = jax.devices()[:n_devices]
    n = len(devices)
    model = 2 if n % 2 == 0 else 1
    return _make_mesh((n // model, model), ("data", "model"), devices)
