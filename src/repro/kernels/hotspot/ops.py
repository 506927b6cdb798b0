"""Jit'd wrappers for the HOTSPOT kernels + the CC (host) path.

``hotspot(mode=...)`` selects the Table-1 execution path:

* ``"cc"``  — jnp/XLA path (the paper's CPU-core path; XLA:CPU compiles it
  to vectorized loops, XLA:TPU to VPU code).
* ``"hp"``  — Pallas row-tiled kernel, HBM round-trip per time step.
* ``"hpc"`` — Pallas VMEM-resident kernel, all steps fused.

Row bands are the unit of work the MultiDynamic scheduler hands out (a
chunk of the 2048-row iteration space).  A band evolved for ``steps``
steps is exact when it carries ``steps`` halo rows on each interior side
and uses the full grid's coefficients:

* :func:`hotspot_hpc_window` — the ACC path: the fused Pallas kernel on a
  window of FIXED height ``chunk + 2·steps`` rows (:func:`band_window`
  places it), so every ACC chunk, the last partial one included, runs the
  one compiled program.
* :func:`hotspot_rows_host` — the CC path: the same update in numpy on
  the host CPU, for any band size.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from ...configs.paper_eneac import HotspotConfig
from .hotspot import hotspot_hp_step_pallas, hotspot_hpc_pallas
from .ref import hotspot_coefficients, hotspot_ref, hotspot_step_coeffs

__all__ = ["hotspot", "hotspot_step_banded", "band_window",
           "hotspot_hpc_window", "hotspot_rows_host"]


def hotspot(
    temp: jax.Array,
    power: jax.Array,
    cfg: HotspotConfig,
    steps: int,
    *,
    mode: str = "hpc",
    interpret: bool | None = None,
) -> jax.Array:
    if mode == "cc":
        return hotspot_ref(temp, power, cfg, steps)
    if mode == "hpc":
        return hotspot_hpc_pallas(temp, power, cfg, steps, interpret=interpret)
    if mode == "hp":
        t = temp
        for _ in range(steps):
            t = hotspot_hp_step_pallas(t, power, cfg, interpret=interpret)
        return t
    raise ValueError(f"mode must be cc|hp|hpc, got {mode!r}")


@functools.partial(jax.jit, static_argnames=("cfg", "grid"))
def hotspot_step_banded(
    temp_band: jax.Array,   # band rows plus any halo rows already included
    power_band: jax.Array,  # same shape as temp_band
    cfg: HotspotConfig,
    grid: tuple,            # (R, C) of the FULL grid
) -> jax.Array:
    """One step on a row band, bitwise equal to the whole-grid step.

    The caller slices ``temp``/``power`` to the band *plus one halo row on
    each interior side* and keeps only the band rows of the result.  Using
    the full grid's coefficients (not the band's) is what makes this
    exactly the rows the whole-grid :func:`~repro.kernels.hotspot.ref.
    hotspot_step_ref` would produce — the invariant the runtime-parity
    test pins under real-thread dispatch.
    """
    cap, rx, ry, rz, dt = hotspot_coefficients(cfg, grid[0], grid[1])
    return hotspot_step_coeffs(temp_band, power_band, cfg.amb_temp,
                               cap, rx, ry, rz, dt)


def band_window(start: int, stop: int, rows: int, window: int, halo: int) -> int:
    """First row of the ``window``-row slab that evolves rows [start, stop).

    The slab reaches ``halo`` rows past the band on each side, or ends at
    the grid's own edge, where the stencil's clamping is the real
    boundary.  Near the bottom edge the slab slides up instead of
    shrinking, which is what keeps its height fixed.
    """
    if stop - start > window - 2 * halo or window > rows:
        raise ValueError(
            f"band [{start}, {stop}) with {halo} halo rows does not fit a "
            f"{window}-row window of a {rows}-row grid"
        )
    return min(max(start - halo, 0), rows - window)


@functools.partial(jax.jit, static_argnames=("cfg", "window", "steps", "interpret"))
def hotspot_hpc_window(
    temp: jax.Array,    # (R, C) the full grid, resident on the unit's device
    power: jax.Array,   # (R, C)
    lo: jax.Array,      # int32 scalar: first row of the window
    *,
    cfg: HotspotConfig,
    window: int,
    steps: int,
    interpret: bool | None = None,
) -> jax.Array:
    """``steps`` fused steps of the Pallas HPC kernel on rows [lo, lo+window)."""
    rows, cols = temp.shape
    t = jax.lax.dynamic_slice(temp, (lo, 0), (window, cols))
    p = jax.lax.dynamic_slice(power, (lo, 0), (window, cols))
    return hotspot_hpc_pallas(t, p, cfg, steps, grid=(rows, cols),
                              interpret=interpret)


def hotspot_rows_host(
    temp: np.ndarray, power: np.ndarray, start: int, stop: int,
    cfg: HotspotConfig, steps: int,
) -> np.ndarray:
    """Rows [start, stop) after ``steps`` steps, computed in numpy (f32)."""
    rows, cols = temp.shape
    cap, rx, ry, rz, dt = hotspot_coefficients(cfg, rows, cols)
    f32 = np.float32
    k, rx, ry, rz, amb = f32(dt / cap), f32(rx), f32(ry), f32(rz), f32(cfg.amb_temp)
    lo, hi = max(start - steps, 0), min(stop + steps, rows)
    t = np.asarray(temp[lo:hi], f32)
    p = np.asarray(power[lo:hi], f32)
    for _ in range(steps):
        up = np.concatenate([t[:1], t[:-1]], axis=0)
        down = np.concatenate([t[1:], t[-1:]], axis=0)
        left = np.concatenate([t[:, :1], t[:, :-1]], axis=1)
        right = np.concatenate([t[:, 1:], t[:, -1:]], axis=1)
        t = t + k * (p + (left + right - 2 * t) / rx
                     + (up + down - 2 * t) / ry + (amb - t) / rz)
    return t[start - lo:stop - lo]
