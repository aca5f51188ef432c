"""Production meshes (TPU v5e numbers: 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI).

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state — the dry-run must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax

# Hardware constants used by the roofline analysis (launch/roofline.py).
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link


def _auto_mesh(shape, axes):
    # Auto axes: sharding is placed by with_sharding_constraint and the
    # partitioner, which JAX's default Explicit axes refuse.
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke runs (axes kept for spec reuse)."""
    return _auto_mesh((1, 1), ("data", "model"))
