"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls :func:`make_production_mesh`.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    return _make_mesh((data, model), ("data", "model"))


# TPU v5e hardware model for the roofline terms (assignment constants)
HW = dict(
    peak_flops_bf16=197e12,   # per chip
    hbm_bw=819e9,             # bytes/s per chip
    ici_bw=50e9,              # bytes/s per link
)
