"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state.  The single-pod production mesh is
16×16 = 256 chips ("data", "model"); the multi-pod mesh adds a leading
"pod" axis: 2×16×16 = 512 chips.  The dry-run uses
``--xla_force_host_platform_device_count=512`` placeholder devices; real
deployments get the same shapes from the TPU runtime.
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..parallel.compat import make_mesh

__all__ = ["make_production_mesh", "make_test_mesh", "HardwareSpec", "TPU_V5E"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape: Tuple[int, ...] = (2, 2), axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for CPU tests (requires host-device-count override)."""
    return make_mesh(shape, axes)


class HardwareSpec:
    """Roofline constants for the target part."""

    def __init__(self, name: str, peak_flops: float, hbm_bw: float, ici_bw: float,
                 hbm_bytes: float, vmem_bytes: float) -> None:
        self.name = name
        self.peak_flops = peak_flops      # FLOP/s bf16 per chip
        self.hbm_bw = hbm_bw              # bytes/s per chip
        self.ici_bw = ici_bw              # bytes/s per link
        self.hbm_bytes = hbm_bytes        # capacity per chip
        self.vmem_bytes = vmem_bytes


# Assignment-mandated constants: 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link.
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
)
