"""Production mesh builders (assignment: 16×16 single-pod, 2×16×16 multi-pod).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state; the dry-run sets XLA_FLAGS *before* any jax import.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple:
    """All non-'model' axes act as data parallelism."""
    return tuple(a for a in mesh.axis_names if a != "model")


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(1, min(data, n // model))
    return _make_mesh((data, model), ("data", "model"))


def make_data_mesh():
    """Pure data-parallel mesh over every visible device — what the
    summarization engine's mesh-dispatched shingle/Jaccard path shards over
    (`core/engine.SummarizerEngine`, DESIGN.md §8)."""
    return _make_mesh((len(jax.devices()),), ("data",))
