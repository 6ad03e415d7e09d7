"""Production meshes (spec: 16x16 single pod, 2x16x16 multi-pod).

`make_production_mesh` is a FUNCTION so importing this module never touches
jax device state; the dry-run sets XLA_FLAGS before calling it. The LM
stack is partitioned by GSPMD (sharding annotations, not explicit-sharding
types), so its meshes have Auto axes — ``jax.make_mesh`` defaults to
Explicit ones.
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


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / CPU benchmarks)."""
    n = jax.device_count()
    if shape is None:
        shape = (1, n)
    return _auto_mesh(shape, axes)


def data_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")
