"""Production mesh construction (TPU v5e pods).

Single-pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the "pod" axis carries
pure data parallelism (its collectives cross the inter-pod DCI links).

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS *before* the first jax
device query).

Every mesh is built with ``Auto`` axis types: the step builders place
arrays with explicit ``NamedSharding`` objects and let XLA propagate the rest,
which is the ``Auto`` contract. ``jax.make_mesh`` defaults to ``Explicit``
axes, under which ``jax.set_mesh`` turns on sharding-in-types and ops such
as gathers demand an ``out_sharding``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 2):
    """Small mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    model = min(model, n)
    data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple[str, ...]:
    """The mesh axes that carry (FL-device ×) batch parallelism."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_ways(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
