"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """Mesh with every axis Auto (GSPMD decides the layouts)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips.

    Axes: batch shards over ("pod", "data"); tensor/expert/sequence
    parallelism over "model".  At larger scale the pod axis generalizes to
    (n_pods, 16, 16) — collectives stay hierarchical (see README §Scale).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small host-device meshes)."""
    return _mesh(tuple(shape), tuple(axes))


def data_axes(mesh) -> tuple:
    """Axes over which the batch dimension shards."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    assert "model" in mesh.axis_names
    return "model"
