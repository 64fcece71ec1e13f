"""Device mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before any jax init).

Production topology (TPU v5e): one pod = a 16x16 slice = 256 chips, meshed
as (data=16, model=16). Multi-pod adds a leading "pod" axis over DCN:
(pod=2, data=16, model=16) = 512 chips. COBS shards documents over
("pod", "data") and Bloom rows over "model"; the LM substrate shards batch
over ("pod", "data") (FSDP on "data") and tensor/expert dims over "model".
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """jax.make_mesh with explicit Auto axis types (the shard_map/pjit
    behaviour the engines were written against)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The dry-run target: 16x16 single pod, or 2x16x16 across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes carrying the batch/document dimension on this mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: Mesh) -> str | None:
    return "model" if "model" in mesh.axis_names else None
