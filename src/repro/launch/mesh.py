"""Production mesh builders.

Single pod:  (data=16, model=16)           = 256 chips  (TPU v5e pod slice)
Multi-pod:   (pod=2, data=16, model=16)    = 512 chips; "pod" is a pure
data-parallel axis whose gradient all-reduce crosses DCN — the paper's
scale-out pattern (192 instances x 8 GPUs ~ outer DP axis over EFA).

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and only
dryrun.py forces 512 host devices).
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh


def _mk(shape, axes, devices=None):
    # make_mesh orders the devices for the physical topology; re-wrapping
    # them in Mesh keeps every axis Auto (make_mesh defaults to Explicit),
    # so shardings propagate through GSPMD as the step builders expect.
    return Mesh(jax.make_mesh(shape, axes, devices=devices).devices, axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(*, data: int = 1, model: int = 1, devices=None):
    """Mesh over the first data*model local devices (or over `devices`)."""
    return _mk((data, model), ("data", "model"), devices)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks for the roofline model."""

    flops_bf16: float          # FLOP/s
    hbm_bw: float              # bytes/s
    ici_bw: float              # bytes/s per link
    hbm_bytes: int


# Keyed by jax's `device_kind`. Source: Google Cloud documentation, "TPU
# v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 links (50 GB/s each).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9,
                   hbm_bytes=16 * 1024**3),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of `device_kind`; a kind with no published entry
    raises rather than borrowing another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
