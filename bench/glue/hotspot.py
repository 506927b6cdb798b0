"""HotSpot per-chunk glue: a chunk of rows mapped onto the program's ops.

This is the user code of the paper's programmer, kept to plain closures
over the program's public API: ``band_window``, ``hotspot_hpc_window``
(ACC units, one compiled program per device on a fixed-height window) and
``hotspot_rows_host`` (CC units, numpy on the host).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import numpy as np

from repro.configs.paper_eneac import HotspotConfig
from repro.kernels.hotspot.ops import band_window, hotspot_hpc_window, hotspot_rows_host

# The jitted module an ACC chunk runs, as the profiler names it, and the
# host op a CC chunk runs: both are names of this module's imports.
KERNEL = "hotspot_hpc_window"
CC_OP = "hotspot_rows_host"


def program_config(cfg: dict) -> HotspotConfig:
    """The program's ``HotspotConfig`` holding the configuration's sizes."""
    return HotspotConfig(grid=cfg["grid"], iterations=cfg["grid"],
                         sim_steps=cfg["sim_steps"], t_chip=cfg["t_chip"],
                         chip_height=cfg["chip_height"], chip_width=cfg["chip_width"],
                         max_pd=cfg["max_pd"], precision=cfg["precision"],
                         spec_heat_si=cfg["spec_heat_si"], k_si=cfg["k_si"],
                         amb_temp=cfg["amb_temp"])


def make(prob, acc_chunk: int):
    """Closures for one problem: ``place(device)``, ``acc_work(device)``,
    ``cc_work``, ``begin_loop``, ``assemble`` and ``release``."""
    cfg = program_config(prob.cfg)
    temp, power = prob.temp, prob.power
    rows, steps = temp.shape[0], cfg.sim_steps
    window = min(acc_chunk + 2 * steps, rows)
    placed: Dict = {}
    loop: Dict = {}

    def place(device) -> None:
        args = jax.block_until_ready(
            (jax.device_put(temp, device), jax.device_put(power, device)))
        exe = hotspot_hpc_window.lower(*args, np.int32(0), cfg=cfg, window=window,
                                       steps=steps).compile()
        jax.block_until_ready(exe(*args, np.int32(0)))
        placed[device] = (exe, args)

    def acc_work(device):
        exe, args = placed[device]

        def work(chunk):
            lo = band_window(chunk.start, chunk.stop, rows, window, steps)
            out = exe(*args, np.int32(lo))
            loop["acc"][(chunk.start, chunk.stop)] = (lo, out)
            return out
        return work

    def cc_work(chunk) -> None:
        loop["result"][chunk.start:chunk.stop] = hotspot_rows_host(
            temp, power, chunk.start, chunk.stop, cfg, steps)

    def begin_loop(_loop: int) -> None:   # every loop has the same inputs
        loop["result"] = np.full(temp.shape, np.nan, np.float32)
        loop["acc"] = {}

    def assemble() -> Tuple[np.ndarray, List[Tuple[int, int]]]:
        result = loop["result"]
        for (s, e), (lo, out) in loop["acc"].items():
            result[s:e] = np.asarray(out)[s - lo:e - lo]
        return result, sorted(loop["acc"])

    def release() -> None:
        placed.clear()
        loop.clear()

    return dict(place=place, acc_work=acc_work, cc_work=cc_work,
                begin_loop=begin_loop, assemble=assemble, release=release)
