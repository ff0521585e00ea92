"""Production meshes.  Functions, not module constants, so importing this
module never touches jax device state (device count is locked on first use).

Single pod: (16, 16) = 256 chips over ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips over ("pod", "data", "model") — the
"pod" axis is pure data parallelism across ICI-connected pods (DCN in a
real deployment; the dry-run proves the sharding is coherent either way).

Every mesh here has Auto axes: ``jax.make_mesh`` defaults to Explicit
axes, under which the partitioner refuses the ``with_sharding_constraint``
hints that ``repro.parallel.actctx`` places in the model.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh", "dp_axes",
           "TP_AXIS"]

TP_AXIS = "model"


def make_mesh(shape, axes, devices=None):
    """An Auto-axis mesh of ``shape`` over ``devices`` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first data*model local devices.  Raises
    when the host has fewer: a mesh quietly shrunk to what exists would
    run, and be measured as, a different deployment."""
    devices = jax.devices()
    need = data * model
    if need > len(devices):
        raise ValueError(f"mesh data={data} x model={model} needs {need} "
                         f"devices; this host has {len(devices)}")
    return make_mesh((data, model), ("data", "model"), devices[:need])


def dp_axes(mesh) -> tuple:
    """The data-parallel mesh axes (everything except the TP axis)."""
    return tuple(a for a in mesh.axis_names if a != TP_AXIS)
