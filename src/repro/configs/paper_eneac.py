"""The paper's own benchmark configurations (ENEAC §4).

HOTSPOT: Rodinia thermal stencil, 2048×2048 chip grid, iteration space =
2048 rows.  SPMM: 29957×29957 sparse × 29957×100 dense, iteration space =
29957 rows.  Table-1 sweeps FPGA chunk sizes; the throughput cliff sits at
chunk > 1/4 of the space (512 rows HOTSPOT, 8192 rows SPMM).

The paper's matrix is not in this repository, and neither is a
description of its rows: SPMM's lognormal row lengths (mean 120, σ 1)
and uniform columns are assumed, not the paper's.  The benchmark's SpMM
configuration takes its pattern from a published generator instead (the
Graph 500 Kronecker graph, ``bench/configs/spmm-graph500-s20.json``).
"""

from dataclasses import dataclass

__all__ = ["HotspotConfig", "SpmmConfig"]


@dataclass(frozen=True)
class HotspotConfig:
    grid: int = 2048            # chip is grid × grid points
    iterations: int = 2048      # parallel rows
    sim_steps: int = 8          # time steps per run (paper loops the solver)
    # physical constants from the Rodinia kernel
    t_chip: float = 0.0005
    chip_height: float = 0.016
    chip_width: float = 0.016
    max_pd: float = 3.0e6
    precision: float = 0.001
    spec_heat_si: float = 1.75e6
    k_si: float = 100.0
    amb_temp: float = 80.0


@dataclass(frozen=True)
class SpmmConfig:
    rows: int = 29957
    cols: int = 29957
    dense_cols: int = 100
    nnz_per_row_mean: float = 120.0   # assumed: lognormal row lengths
    nnz_per_row_sigma: float = 1.0
    seed: int = 1234

