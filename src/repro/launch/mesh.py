"""Production mesh definitions.

Target: TPU v5e-class pods — 16x16 = 256 chips per pod, 2 pods = 512 chips.
``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
Both meshes use auto axis types: sharding is propagated by the compiler,
as the models' ``with_sharding_constraint`` annotations
(``models/shardutil.py``) expect.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# Hardware constants used by the roofline model (assignment-specified).
PEAK_FLOPS_BF16 = 197e12      # per chip, FLOP/s
HBM_BW = 819e9                # per chip, B/s
ICI_BW = 50e9                 # per link, B/s
CHIPS_PER_POD = 256


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh for CPU smoke runs through the same code path."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
