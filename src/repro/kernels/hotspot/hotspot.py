"""HOTSPOT stencil Pallas TPU kernels — the paper's regular benchmark.

Two variants map the paper's AXI-port study onto the TPU memory hierarchy:

* :func:`hotspot_hpc_pallas` — the **HPC (cache-coherent) analogue**: the
  whole temperature grid is VMEM-resident; all ``steps`` time iterations
  run inside ONE ``pallas_call`` with a double buffer, so HBM is touched
  exactly twice (initial load, final store).  The kernel holds the input,
  power, output and scratch grids plus the stencil's temporaries: the
  TPU compiler asks 80.1 MiB of scoped VMEM for a 2048² f32 grid (five
  16 MiB grids), against a 16 MiB scoped default.  The limit is raised
  per call to six grids (:func:`~repro.kernels.platform.vmem_limit`); a
  row band of a few hundred rows fits the default.
* :func:`hotspot_hp_step_pallas` — the **HP (non-cacheable) analogue**:
  one ``pallas_call`` per time step, row-block tiled; the grid
  round-trips through HBM every step, and the halo rows are delivered as
  separately materialized shifted copies (mirroring the paper's
  intermediate software buffers on the HP port path).

Both compute the identical update as :mod:`.ref`.  The coefficients come
from the FULL grid's shape (``grid=``), so a row band handed to either
kernel is stepped with the same physics as the whole grid.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...configs.paper_eneac import HotspotConfig
from ..platform import resolve_interpret, vmem_limit
from .ref import hotspot_coefficients

__all__ = ["hotspot_hpc_pallas", "hotspot_hp_step_pallas"]


# grid-sized VMEM buffers the fused kernel may keep live (five measured,
# see module doc, plus one of headroom)
_HPC_RESIDENT_GRIDS = 6


def _coefficients(cfg: HotspotConfig, grid: Tuple[int, int]):
    cap, rx, ry, rz, dt = hotspot_coefficients(cfg, grid[0], grid[1])
    return (dt / cap, 1.0 / rx, 1.0 / ry, 1.0 / rz)


def _step_math(t, up, down, left, right, power, coeff, amb):
    cap_inv_dt, rx_inv, ry_inv, rz_inv = coeff
    return t + cap_inv_dt * (
        power
        + (left + right - 2.0 * t) * rx_inv
        + (up + down - 2.0 * t) * ry_inv
        + (amb - t) * rz_inv
    )


def _shift_rows(t, direction):
    if direction == "up":  # neighbour above: row r-1 (clamped)
        return jnp.concatenate([t[:1], t[:-1]], axis=0)
    return jnp.concatenate([t[1:], t[-1:]], axis=0)


def _shift_cols(t, direction):
    if direction == "left":
        return jnp.concatenate([t[:, :1], t[:, :-1]], axis=1)
    return jnp.concatenate([t[:, 1:], t[:, -1:]], axis=1)


# ---------------------------------------------------------------------------
# HPC variant: VMEM-resident, all time steps fused in-kernel
# ---------------------------------------------------------------------------
def _hpc_kernel(temp_ref, power_ref, out_ref, scratch_ref, *, steps, coeff, amb):
    scratch_ref[...] = temp_ref[...]

    def body(i, _):
        t = scratch_ref[...]
        up = _shift_rows(t, "up")
        down = _shift_rows(t, "down")
        left = _shift_cols(t, "left")
        right = _shift_cols(t, "right")
        scratch_ref[...] = _step_math(t, up, down, left, right, power_ref[...],
                                      coeff, amb)
        return 0

    jax.lax.fori_loop(0, steps, body, 0)
    out_ref[...] = scratch_ref[...]


def hotspot_hpc_pallas(
    temp: jax.Array, power: jax.Array, cfg: HotspotConfig, steps: int,
    *, grid: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``steps`` fused time steps; ``grid`` is the full grid's (R, C) when
    ``temp`` is a row band of it (default: ``temp`` is the whole grid)."""
    rows, cols = temp.shape
    coeff = _coefficients(cfg, grid or (rows, cols))
    nbytes = _HPC_RESIDENT_GRIDS * rows * cols * temp.dtype.itemsize
    kernel = functools.partial(_hpc_kernel, steps=steps, coeff=coeff, amb=cfg.amb_temp)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((rows, cols), lambda i: (0, 0)),
            pl.BlockSpec((rows, cols), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, cols), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), temp.dtype),
        scratch_shapes=[pltpu.VMEM((rows, cols), temp.dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(nbytes, what=f"a {rows}x{cols} "
                                        "VMEM-resident hotspot grid")),
        interpret=resolve_interpret(interpret),
    )(temp, power)


# ---------------------------------------------------------------------------
# HP variant: one step per call, row-block tiled, HBM round-trip per step
# ---------------------------------------------------------------------------
def _hp_kernel(t_ref, up_ref, down_ref, power_ref, out_ref, *, coeff, amb):
    t = t_ref[...]
    left = _shift_cols(t, "left")
    right = _shift_cols(t, "right")
    out_ref[...] = _step_math(t, up_ref[...], down_ref[...], left, right,
                              power_ref[...], coeff, amb)


def hotspot_hp_step_pallas(
    temp: jax.Array, power: jax.Array, cfg: HotspotConfig,
    *, block_rows: int = 256, grid: Optional[Tuple[int, int]] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One time step; halos come in as shifted copies (HP-port buffers)."""
    rows, cols = temp.shape
    br = min(block_rows, rows)
    while rows % br:
        br //= 2
    coeff = _coefficients(cfg, grid or (rows, cols))
    up = _shift_rows(temp, "up")      # materialized in HBM: the HP-port
    down = _shift_rows(temp, "down")  # intermediate-buffer penalty
    kernel = functools.partial(_hp_kernel, coeff=coeff, amb=cfg.amb_temp)
    spec = pl.BlockSpec((br, cols), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols), temp.dtype),
        interpret=resolve_interpret(interpret),
    )(temp, up, down, power)
