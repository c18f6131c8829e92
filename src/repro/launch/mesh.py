"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
while tests and benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: the sharding annotations
    (``sharding/annotate``) and the shard_map shims are written for
    compiler-propagated shardings, not the Explicit axes ``make_mesh``
    defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one 256-chip v5e pod) or 2x16x16 (two pods).

    Axes: ``data`` carries batch/FSDP, ``model`` carries TP/EP/SP, ``pod``
    is an outer pure-DP axis (cross-pod traffic = gradient all-reduce only).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2, *,
                   multi_pod: bool = False):
    """Small mesh over host devices for distributed unit tests."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
