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
  the host CPU, for any band size.  Its step is the update folded once
  per call into ``t' = c0·t + cx·(left + right) + cy·(up + down) + q``,
  with ``cx = k/Rx``, ``cy = k/Ry``, ``c0 = 1 − 2k/Rx − 2k/Ry − k/Rz`` and
  ``q = k·P + k·T_amb/Rz`` (``k = dt/Cap``; coefficients in float64, cast
  to float32 once): the same sum in another order.  Its halo shrinks by
  one row a step: the last step computes only the band, each earlier one
  the rows the next reads, so the band's rows stay exact.  It allocates
  its scratch per call and only reads ``temp`` and ``power``, since four
  CC threads call it at once beside the dispatcher, on one shared grid.
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
    """Rows [start, stop) after ``steps`` steps, computed in numpy (f32).

    Each step is the folded update ``t' = c0·t + cx·(left + right) +
    cy·(up + down) + q`` (module docstring), eight ufunc calls that each
    write into a buffer allocated once per call.  The state lives in two
    buffers of the band and its halo rows, padded by one row and column on
    each side and swapped every step; the pads hold the clamped
    neighbours, so no step concatenates or allocates.  Step ``s`` (from
    0) computes only the rows within ``steps - 1 - s`` of the band: each
    of them reads rows that the step before computed, so the band's own
    rows are exact.  The range stops at the grid's own edge, where the
    outer row repeats the edge row before each step, which is the
    stencil's clamp.

    A pure function of its arguments: ``temp`` and ``power`` are only
    read, and every buffer is the call's own (the result is a view of
    one), so the CC units call it from four threads at once, on one
    shared grid.
    """
    rows, cols = temp.shape
    cap, rx, ry, rz, dt = hotspot_coefficients(cfg, rows, cols)
    k = dt / cap
    f32 = np.float32
    c0 = f32(1.0 - 2.0 * k / rx - 2.0 * k / ry - k / rz)
    cx, cy = f32(k / rx), f32(k / ry)
    lo, hi = max(start - steps, 0), min(stop + steps, rows)
    n = hi - lo
    # Buffer row i holds grid row lo + i - 1; column j holds column j - 1.
    src, dst = np.empty((2, n + 2, cols + 2), f32)
    part = np.empty((n, cols), f32)
    q = np.multiply(power[lo:hi], f32(k), dtype=f32)
    np.add(q, f32(k * cfg.amb_temp / rz), out=q)
    src[1:n + 1, 1:cols + 1] = temp[lo:hi]
    for s in range(steps):
        reach = steps - 1 - s
        r0, r1 = max(start - reach, 0), min(stop + reach, rows)
        i0, i1 = r0 - lo + 1, r1 - lo + 1
        if r0 == 0:
            src[0, 1:-1] = src[1, 1:-1]
        if r1 == rows:
            src[i1, 1:-1] = src[i1 - 1, 1:-1]
        src[i0:i1, 0] = src[i0:i1, 1]
        src[i0:i1, -1] = src[i0:i1, -2]
        out, tmp = dst[i0:i1, 1:-1], part[:i1 - i0]
        np.add(src[i0:i1, :-2], src[i0:i1, 2:], out=out)
        np.multiply(out, cx, out=out)
        np.add(src[i0 - 1:i1 - 1, 1:-1], src[i0 + 1:i1 + 1, 1:-1], out=tmp)
        np.multiply(tmp, cy, out=tmp)
        np.add(out, tmp, out=out)
        np.multiply(src[i0:i1, 1:-1], c0, out=tmp)
        np.add(out, tmp, out=out)
        np.add(out, q[i0 - 1:i1 - 1], out=out)
        src, dst = dst, src
    return src[start - lo + 1:stop - lo + 1, 1:-1]
