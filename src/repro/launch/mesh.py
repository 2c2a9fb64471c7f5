"""Mesh construction — the one place the code base calls ``jax.make_mesh``.

Functions (not module-level constants) so importing this module never
touches jax device state — required because the dry-run must set
``--xla_force_host_platform_device_count=512`` before first jax init.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "HW"]


def make_mesh(shape, axes, *, devices=None):
    """A mesh whose axes are all ``Auto``.

    ``jax.make_mesh`` makes Explicit axes by default; the sharded code here
    is written for Auto axes (``with_sharding_constraint`` over mesh axes,
    ``.at[...].set`` on sharded arrays), which Explicit axes reject.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi-pod adds a leading pod axis (2×)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


class HW:
    """TPU v5e hardware constants for the roofline (per chip)."""
    PEAK_BF16_FLOPS = 197e12      # FLOP/s
    HBM_BW = 819e9                # B/s
    ICI_BW = 50e9                 # B/s per link (~3 links usable per axis)
    HBM_BYTES = 16 * 2 ** 30
    VMEM_BYTES = 128 * 2 ** 20
