"""Production meshes (per brief §MULTI-POD DRY-RUN).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  The 512-device host-platform override belongs
to ``dryrun.py`` ONLY (its first two lines) — tests and benches see the
single real CPU device.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)))


def _auto(n: int) -> tuple:
    """Auto axis types: ``with_sharding_constraint`` refuses Explicit axes,
    which ``jax.make_mesh`` now defaults to."""
    return (jax.sharding.AxisType.Auto,) * n


def data_axes(mesh) -> tuple:
    """The data-parallel axes of a production mesh ('pod'+'data')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def data_axis_devices(mesh) -> tuple:
    """One device per data-parallel slot of the mesh, in axis order.

    The model axes are collapsed to their first column: a row-sharded
    SpGEMM operand (shard s of A) lands on the s-th data slot, while B is
    replicated.  This is the placement surface the partition-aware engine
    uses (``repro.engine.partition``).
    """
    devs = np.asarray(mesh.devices)
    axes = data_axes(mesh)
    for i, name in enumerate(mesh.axis_names):
        if name not in axes:
            devs = np.take(devs, [0], axis=i)
    return tuple(devs.flatten())


def dp_size(mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out


def make_host_mesh(model_axis: int = 1):
    """A tiny mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model_axis, model_axis), ("data", "model"),
                         axis_types=_auto(2))
