"""HotSpot (Rodinia thermal stencil): the yardstick half of a configuration.

Everything here is the benchmark's own and imports nothing of the program:
the seeded problem generator, the plain float64 reference, the control (the
same reference in bfloat16, the precision a later change might be tempted
to drop to), and the algorithmic operations and bytes of a band of rows.

One explicit time step on an (R, C) grid, Rodinia's update:

    T' = T + dt/Cap * ((T[r, c-1] + T[r, c+1] - 2T) / Rx
                       + (T[r-1, c] + T[r+1, c] - 2T) / Ry
                       + (T_amb - T) / Rz + P)

Missing neighbours at the grid's edge are the cell itself.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Operations per cell and step of the update above, as written: the two
# neighbour sums (2), 2T twice (2), the two differences (2), the three
# divisions (3), T_amb - T (1), the three-term sum plus P (3), the scale by
# dt/Cap (1) and the add to T (1).
OPS_PER_CELL_STEP = 15
# Bytes per cell of one loop that the algorithm cannot avoid: T and P read
# once, T' written once, in float32.
BYTES_PER_CELL = 12


@dataclasses.dataclass(frozen=True)
class Problem:
    cfg: dict
    temp: np.ndarray   # (R, C) float32 initial temperatures
    power: np.ndarray  # (R, C) float32 power density

    @property
    def rows(self) -> int:
        return self.temp.shape[0]


def generate(cfg: dict, seed: int) -> Problem:
    """Temperatures uniform in [T_amb, T_amb + 10), power uniform in [0, 1)."""
    rng = np.random.default_rng(seed)
    shape = (cfg["grid"], cfg["grid"])
    temp = (cfg["amb_temp"] + 10.0 * rng.random(shape, dtype=np.float32))
    power = rng.random(shape, dtype=np.float32)
    return Problem(cfg=cfg, temp=temp.astype(np.float32), power=power)


def coefficients(cfg: dict) -> tuple:
    """(Cap, Rx, Ry, Rz, dt) of Rodinia's hotspot for the configured grid."""
    rows = cols = cfg["grid"]
    grid_h = cfg["chip_height"] / rows
    grid_w = cfg["chip_width"] / cols
    cap = cfg["spec_heat_si"] * cfg["t_chip"] * grid_w * grid_h
    rx = grid_w / (2.0 * cfg["k_si"] * cfg["t_chip"] * grid_h)
    ry = grid_h / (2.0 * cfg["k_si"] * cfg["t_chip"] * grid_w)
    rz = cfg["t_chip"] / (cfg["k_si"] * grid_h * grid_w)
    max_slope = cfg["max_pd"] / (cfg["spec_heat_si"] * cfg["t_chip"])
    dt = cfg["precision"] / max_slope
    return cap, rx, ry, rz, dt


def _steps(t, p, cfg: dict, xp, cast):
    """``cfg["sim_steps"]`` updates of ``t`` in the array module ``xp``;
    ``cast`` puts every constant in the working precision."""
    cap, rx, ry, rz, dt = coefficients(cfg)
    k, rx, ry, rz = cast(dt / cap), cast(rx), cast(ry), cast(rz)
    amb, two = cast(cfg["amb_temp"]), cast(2.0)
    for _ in range(cfg["sim_steps"]):
        up = xp.concatenate([t[:1], t[:-1]], axis=0)
        down = xp.concatenate([t[1:], t[-1:]], axis=0)
        left = xp.concatenate([t[:, :1], t[:, :-1]], axis=1)
        right = xp.concatenate([t[:, 1:], t[:, -1:]], axis=1)
        t = t + k * ((left + right - two * t) / rx + (up + down - two * t) / ry
                     + (amb - t) / rz + p)
    return t


def reference(prob: Problem, *, band: int = 128) -> np.ndarray:
    """The grid after ``sim_steps`` steps, in float64 on the host.

    Computed in bands of rows, each with ``sim_steps`` extra rows on every
    side that is not the grid's edge: a band's own rows are then exact,
    since a step moves information one row.
    """
    rows, steps = prob.rows, prob.cfg["sim_steps"]
    out = np.empty(prob.temp.shape, np.float64)
    for r0 in range(0, rows, band):
        r1 = min(r0 + band, rows)
        lo, hi = max(r0 - steps, 0), min(r1 + steps, rows)
        t = _steps(prob.temp[lo:hi].astype(np.float64),
                   prob.power[lo:hi].astype(np.float64), prob.cfg, np, np.float64)
        out[r0:r1] = t[r0 - lo:r1 - lo]
    return out


def control(prob: Problem, device) -> np.ndarray:
    """The reference computed in bfloat16 on ``device``."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    t = jax.device_put(prob.temp, device).astype(bf16)
    p = jax.device_put(prob.power, device).astype(bf16)
    out = jax.jit(lambda t, p: _steps(t, p, prob.cfg, jnp, bf16))(t, p)
    return np.asarray(out.astype(jnp.float32), np.float64)


def chunk_work(prob: Problem, start: int, stop: int) -> tuple:
    """(operations, bytes) the algorithm needs for rows [start, stop)."""
    cells = (stop - start) * prob.temp.shape[1]
    return (OPS_PER_CELL_STEP * prob.cfg["sim_steps"] * cells,
            BYTES_PER_CELL * cells)
