"""Production mesh definitions for the TPU v5e target.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2 pods x 256 = 512 chips, axes (pod, data, model).

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12     # FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_debug_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """A small mesh over whatever devices exist (tests / CPU smoke)."""
    n = n_devices or len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
