"""Production mesh construction (single-pod 16x16 and 2-pod 2x16x16).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh helper (elastic re-shard paths, tests).  Every axis
    is Auto: the partitioner places what the shardings leave open."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e-class constants for the dry-run's compile-only roofline estimate
# (launch/dryrun.py) only; nothing that runs on a chip reads them
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
