"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

Every mesh here uses ``Auto`` axes: the sharding engine
(:mod:`repro.parallel.sharding`) places arrays with
``with_sharding_constraint`` and lets the compiler propagate the rest.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e per-chip peaks (Google Cloud "TPU v5e" documentation), used only
# for the dry-run's modelled roofline terms; not keyed by device_kind
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link per direction
