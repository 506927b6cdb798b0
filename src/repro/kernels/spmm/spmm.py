"""Block-ELL SPMM Pallas TPU kernel — the paper's irregular benchmark.

The accelerator (ACC/MXU) path: the dense RHS stays VMEM-resident (HPC
analogue) while row blocks of the sparse matrix stream through.  Each grid
step processes one (8, 128·K) row block: a ``fori_loop`` over its
occupied column blocks issues (8,128)·(128,N) MXU matmuls with dynamic
RHS slicing.  Irregularity (variable K per row block) bounds the loop —
the cost of a row block is its occupied-block count, the padding/imbalance
trade the MultiDynamic scheduler's chunk-size knob controls.

One call computes a WINDOW of ``n_row_blocks`` consecutive row blocks
starting at a traced ``row_block_start``:

* the window's column-block and count tables are sliced on the device
  and scalar-prefetched into SMEM, so SMEM holds one window's tables, not
  the whole matrix's (3745 row blocks × 235 column blocks of int32 at the
  paper's size is 3.5 MB against 1 MiB of SMEM);
* the value blocks are read straight from the full block-ELL array, with
  the window offset folded into the index map, so no copy is made;
* the window size is static and the offset is not, so every ACC chunk of
  a run reuses one compiled program.

VMEM: the RHS is double-buffered by the pipeline, so a 30080×128 f32 RHS
(15.4 MB) needs about 31 MiB, above the 16 MiB scoped default; the limit
is set from the operand sizes (:func:`~repro.kernels.platform.vmem_limit`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import resolve_interpret, vmem_limit
from .ref import BlockEll, COL_BLOCK, ROW_BLOCK

__all__ = ["spmm_block_ell_window", "spmm_block_ell_pallas", "BlockEllArrays"]

# Budget for one window's scalar-prefetched index tables (SMEM is 1 MiB).
SMEM_TABLE_BYTES = 512 * 1024


def _spmm_kernel(rb0_ref, count_ref, cols_ref, vals_ref, rhs_ref, out_ref, *,
                 k_max: int):
    """One row block: out (RB, N) = Σ_k vals[k] @ rhs[colblock_k]."""
    i = pl.program_id(0)

    def body(k, acc):
        start = pl.multiple_of(cols_ref[i * k_max + k] * COL_BLOCK, COL_BLOCK)
        b_blk = rhs_ref[pl.ds(start, COL_BLOCK), :]
        return acc + jnp.dot(vals_ref[0, k], b_blk,
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)

    out_ref[...] = jax.lax.fori_loop(
        0, count_ref[i], body, jnp.zeros(out_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_row_blocks", "interpret"))
def spmm_block_ell_window(
    vals: jax.Array,        # (n_rb, K, 8, 128) f32 block-ELL values
    colblocks: jax.Array,   # (n_rb, K) int32 occupied column blocks
    counts: jax.Array,      # (n_rb,) int32 occupied-block counts
    rhs: jax.Array,         # (C_pad, N) f32 — VMEM-resident
    rb0,                    # int32 scalar: first row block of the window
    *,
    n_row_blocks: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Rows [8·rb0, 8·(rb0 + n_row_blocks)) of the product, (8·n, N).

    The window must lie inside the matrix's row blocks; callers place it
    (see :func:`repro.kernels.spmm.ops.spmm_window_start`).
    """
    n_rb, k_max = colblocks.shape
    c_pad, n = rhs.shape
    if not 0 < n_row_blocks <= n_rb:
        raise ValueError(f"window of {n_row_blocks} row blocks, matrix has {n_rb}")
    if 4 * n_row_blocks * (k_max + 1) > SMEM_TABLE_BYTES:
        raise ValueError(
            f"index tables of {n_row_blocks} row blocks x {k_max} column "
            f"blocks exceed the {SMEM_TABLE_BYTES} B SMEM budget; dispatch "
            "fewer row blocks per call"
        )
    rb0 = jnp.asarray(rb0, jnp.int32).reshape(1)
    win_counts = jax.lax.dynamic_slice(counts, rb0, (n_row_blocks,))
    win_cols = jax.lax.dynamic_slice(
        colblocks, (rb0[0], 0), (n_row_blocks, k_max)).reshape(-1)
    blk_bytes = k_max * ROW_BLOCK * COL_BLOCK * 4
    resident = 2 * (c_pad * n * 4 + blk_bytes + ROW_BLOCK * n * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_row_blocks,),
        in_specs=[
            pl.BlockSpec((1, k_max, ROW_BLOCK, COL_BLOCK),
                         lambda i, rb0, cnt, cols: (rb0[0] + i, 0, 0, 0)),
            pl.BlockSpec((c_pad, n), lambda i, *_: (0, 0)),   # resident (HPC)
        ],
        out_specs=pl.BlockSpec((ROW_BLOCK, n), lambda i, *_: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_spmm_kernel, k_max=k_max),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * ROW_BLOCK, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            resident, what=f"a VMEM-resident {c_pad}x{n} f32 SpMM RHS")),
        interpret=resolve_interpret(interpret),
    )(rb0, win_counts, win_cols, vals, rhs)


def spmm_block_ell_pallas(
    ell: "BlockEllArrays",
    rhs: jax.Array,
    row_block_start=0,
    *,
    n_row_blocks: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """:func:`spmm_block_ell_window` over a :class:`BlockEllArrays`;
    ``n_row_blocks`` defaults to the whole matrix."""
    return spmm_block_ell_window(
        ell.vals, ell.colblocks, ell.counts, rhs, row_block_start,
        n_row_blocks=n_row_blocks or ell.colblocks.shape[0],
        interpret=interpret)


class BlockEllArrays:
    """Device-array view of a host BlockEll (on ``device``, if given)."""

    def __init__(self, be: BlockEll, device=None) -> None:
        put = functools.partial(jax.device_put, device=device)
        self.vals = put(be.vals)
        self.colblocks = put(be.colblocks)
        self.counts = put(be.counts)
        self.rows = be.rows
        self.n_cols = be.n_cols
