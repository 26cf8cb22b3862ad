"""Production meshes (deliverable e) and per-chip peaks.

Meshes are built by FUNCTIONS (not module-level constants) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else must keep seeing the single real CPU device.

Every mesh has ``Auto`` axes: the auto-sharder
(:mod:`repro.sharding.auto`, :mod:`repro.sharding.context`) places
parameters and pins activations with sharding constraints and leaves
the rest to the partitioner, which is what Auto mode means.

Target hardware: TPU v5e — one pod = 16×16 = 256 chips
(``data`` × ``model``); two pods = 512 chips with a leading ``pod`` axis
(DCN between pods, ICI within).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over the real devices for CPU-scale examples/tests."""
    return make_mesh((n_data, n_model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float   # per chip, FLOP/s
    hbm_bw: float       # per chip, bytes/s
    ici_bw: float       # per link, bytes/s


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip over 4 ICI links.
V5E = "TPU v5 lite"
CHIP_PEAKS = {
    V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip; a kind missing from the table is an error."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None
