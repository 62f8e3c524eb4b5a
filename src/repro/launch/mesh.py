"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  Single-pod:
(data=16, model=16) = 256 chips (one v5e pod); multi-pod adds a leading
pod axis: (pod=2, data=16, model=16) = 512 chips across the DCI.
"""
from __future__ import annotations

import dataclasses

import jax


def compat_make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto-sharded: the one place a
    mesh is built (repro-lint RL102/RL103 keep it so)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = None) -> jax.sharding.Mesh:
    """Elastic mesh for whatever device count is actually available."""
    model = model_parallel or min(devices, 16)
    while devices % model:
        model -= 1
    data = devices // model
    return compat_make_mesh((data, model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks, the roofline's denominators."""

    flops_bf16: float               # FLOP/s
    hbm_bytes_per_s: float          # B/s
    ici_bytes_per_s_per_link: float  # B/s per link direction
    hbm_bytes: int                  # HBM capacity


#: Peaks keyed by ``jax.Device.device_kind``.  TPU v5e ("TPU v5 lite"):
#: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GiB of
#: HBM at 819 GB/s, 1,600 Gbit/s of ICI over 4 links.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                             ici_bytes_per_s_per_link=50e9,
                             hbm_bytes=16 * 2**30),
}

#: the chip the production meshes above are made of (v5e pods): the
#: dry-run's roofline target
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a kind with no published entry is
    an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known "
            f"kinds: {sorted(CHIP_PEAKS)}") from None
