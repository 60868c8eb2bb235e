"""Production mesh construction.

Target: TPU v5e pods — 16x16 = 256 chips per pod ('data', 'model'), and
2 pods = 512 chips ('pod', 'data', 'model').  Defined as functions so
importing this module never touches jax device state (the dry-run sets
--xla_force_host_platform_device_count=512 before any jax import)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh():
    """Single-process debug mesh (1 device)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# Hardware constants for the roofline analysis (TPU v5e)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
HBM_PER_CHIP = 16e9           # bytes
