"""Production meshes (TPU v5e target).

Kept as FUNCTIONS so importing this module never touches jax device
state; only ``launch/dryrun.py`` (which forces 512 host devices in its
first two lines) should build the production meshes.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods × 256 chips as (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples): (data=N, model=1)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


# TPU v5e hardware constants (per chip) for the roofline terms.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link (~)
HBM_BYTES = 16 * 1024**3        # 16 GiB
