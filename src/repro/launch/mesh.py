"""Production mesh builders.

Single pod = 16x16 (256 chips, v5e-256 topology); multi-pod = 2 pods x 256.
A function (not a module-level constant) so importing never touches jax
device state — the dry-run must set XLA_FLAGS before first jax init.

The ``make_mesh`` helper lives in ``core/sweep_core.py``
(the sharded sweep engine needs it too); this module re-exports it so
launch-side callers keep a single import point.
"""
from __future__ import annotations

from repro.core.sweep_core import make_mesh, resolve_devices  # noqa: F401


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e hardware constants (roofline targets; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW_PER_LINK = 50e9          # bytes/s per link
ICI_LINKS = 4                   # 2D torus on v5e: 4 links/chip
HBM_BYTES = 16 * 2 ** 30        # 16 GiB per chip
