"""Pure-jnp oracles + host-side format builders for SPMM.

The paper's SPMM: sparse (29957×29957) × dense (29957×100), iteration
space = matrix rows, irregular nnz/row.  TPU-native layouts:

* **ELL** (row-major, for the CC/VPU gather path): per-row padded
  ``(R, maxnnz)`` value/col arrays.
* **Block-ELL** (for the ACC/MXU path): rows grouped in blocks of 8,
  columns in blocks of 128; per row-block the list of occupied column
  blocks, padded to the per-matrix max (irregularity shows up as padding —
  the exact trade the paper's ACC chunking makes).
* **CSR** (:class:`CsrProblem`, for skewed matrices whose longest row or
  dense operand makes both of the above too large: a power-law graph's
  hub rows, an operand larger than VMEM): ``indptr``, ``indices``,
  ``data``; its oracle is :func:`spmm_csr_ref`.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "SpmmProblem", "make_problem", "spmm_dense_ref", "spmm_ell_ref",
    "BlockEll", "to_block_ell", "CsrProblem", "make_csr_problem", "spmm_csr_ref",
]

ROW_BLOCK = 8
COL_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class SpmmProblem:
    """ELL-format sparse matrix + dense RHS."""

    vals: np.ndarray      # (R, maxnnz) f32, zero-padded
    cols: np.ndarray      # (R, maxnnz) int32, padded with 0 (vals 0 ⇒ no-op)
    nnz: np.ndarray       # (R,) int32
    n_cols: int
    rhs: np.ndarray       # (C, N) f32

    @property
    def rows(self) -> int:
        return self.vals.shape[0]


def make_problem(
    rows: int, cols: int, n_dense: int, *,
    nnz_mean: float = 8.0, nnz_sigma: float = 1.0, seed: int = 0,
) -> SpmmProblem:
    """Lognormal nnz/row — the irregular workload of the paper's §4."""
    rng = np.random.default_rng(seed)
    nnz = np.minimum(
        np.maximum(rng.lognormal(np.log(nnz_mean), nnz_sigma, rows).astype(np.int64), 1),
        cols,
    )
    maxnnz = int(nnz.max())
    vals = np.zeros((rows, maxnnz), np.float32)
    colix = np.zeros((rows, maxnnz), np.int32)
    for r in range(rows):
        k = int(nnz[r])
        colix[r, :k] = np.sort(rng.choice(cols, size=k, replace=False)).astype(np.int32)
        vals[r, :k] = rng.standard_normal(k).astype(np.float32)
    rhs = rng.standard_normal((cols, n_dense)).astype(np.float32)
    return SpmmProblem(vals=vals, cols=colix, nnz=nnz.astype(np.int32),
                       n_cols=cols, rhs=rhs)


def spmm_dense_ref(p: SpmmProblem) -> np.ndarray:
    """Densify + matmul — the ground-truth oracle (small problems only)."""
    dense = np.zeros((p.rows, p.n_cols), np.float32)
    for r in range(p.rows):
        k = int(p.nnz[r])
        np.add.at(dense[r], p.cols[r, :k], p.vals[r, :k])
    return dense @ p.rhs


def spmm_ell_ref(vals: jax.Array, cols: jax.Array, rhs: jax.Array) -> jax.Array:
    """Row-gather path (the CC/VPU analogue): y = Σ_j vals[:, j]·rhs[cols[:, j]]."""
    gathered = rhs[cols]                      # (R, maxnnz, N)
    return jnp.einsum("rk,rkn->rn", vals, gathered)


@dataclasses.dataclass(frozen=True)
class BlockEll:
    vals: np.ndarray    # (n_rb, K, ROW_BLOCK, COL_BLOCK) f32
    colblocks: np.ndarray  # (n_rb, K) int32 — column-block index
    counts: np.ndarray  # (n_rb,) int32 — occupied column blocks
    rows: int
    n_cols: int

    @property
    def n_row_blocks(self) -> int:
        return self.vals.shape[0]

    @property
    def k_max(self) -> int:
        return self.vals.shape[1]

    def padding_ratio(self) -> float:
        dense_elems = self.counts.sum() * ROW_BLOCK * COL_BLOCK
        nnz = np.count_nonzero(self.vals)
        return float(nnz) / max(dense_elems, 1)


def to_block_ell(p: SpmmProblem, *, k_cap: int = 0) -> BlockEll:
    """Host-side packing (part of the benchmark's data pipeline).

    ``k_cap`` bounds column blocks per row block (the ACC chunk-capacity
    knob); overflowing blocks are DROPPED here — the hybrid executor routes
    such rows to the gather path instead, ENEAC-style.  Occupied column
    blocks are kept in ascending order.  Vectorized over the nonzeros, so
    a 29957-row matrix with ~6M nonzeros packs in seconds.
    """
    R = p.rows
    n_rb = -(-R // ROW_BLOCK)
    n_cb = -(-p.n_cols // COL_BLOCK)
    live = np.arange(p.vals.shape[1])[None, :] < p.nnz[:, None]
    r = np.nonzero(live)[0]
    c = p.cols[live].astype(np.int64)
    v = p.vals[live]
    rb, ri = np.divmod(r, ROW_BLOCK)
    cb, ci = np.divmod(c, COL_BLOCK)
    blocks, inv = np.unique(rb * n_cb + cb, return_inverse=True)
    b_rb, b_cb = np.divmod(blocks, n_cb)
    occupied = np.bincount(b_rb, minlength=n_rb)
    # rank of each occupied block within its row block (blocks are sorted)
    rank = np.arange(len(blocks)) - np.searchsorted(b_rb, b_rb)
    K = max(int(occupied.max(initial=0)), 1)
    if k_cap:
        K = min(K, k_cap)
    vals = np.zeros((n_rb, K, ROW_BLOCK, COL_BLOCK), np.float32)
    colblocks = np.zeros((n_rb, K), np.int32)
    keep = rank < K
    colblocks[b_rb[keep], rank[keep]] = b_cb[keep]
    k_nz = rank[inv]
    sel = k_nz < K
    np.add.at(vals, (rb[sel], k_nz[sel], ri[sel], ci[sel]), v[sel])
    return BlockEll(vals=vals, colblocks=colblocks,
                    counts=np.minimum(occupied, K).astype(np.int32),
                    rows=R, n_cols=n_cb * COL_BLOCK)


@dataclasses.dataclass(frozen=True)
class CsrProblem:
    """CSR sparse matrix + dense RHS."""

    indptr: np.ndarray    # (R + 1,) int64 row pointers
    indices: np.ndarray   # (nnz,) int32 columns, row by row
    data: np.ndarray      # (nnz,) f32 values
    rhs: np.ndarray       # (C, N) f32

    @property
    def rows(self) -> int:
        return len(self.indptr) - 1

    @property
    def n_cols(self) -> int:
        return self.rhs.shape[0]

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def make_csr_problem(scale: int, n_dense: int, *, edgefactor: int = 16,
                     seed: int = 0) -> CsrProblem:
    """A power-law CSR matrix of 2**scale rows: the symmetrised adjacency
    of a Kronecker graph with the Graph 500 initiator (0.57, 0.19, 0.19,
    0.05), permuted labels, values uniform in [0, 1), duplicates summed and
    self-loops dropped; RHS standard normal."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    quad = rng.choice(4, size=(scale, m), p=[0.57, 0.19, 0.19, 0.05])
    weights = (1 << np.arange(scale))[:, None]
    perm = rng.permutation(n)
    src = perm[((quad >> 1) * weights).sum(0)]
    dst = perm[((quad & 1) * weights).sum(0)]
    keep = src != dst
    w = rng.random(m)[keep]
    src, dst = src[keep], dst[keep]
    dense = np.zeros((n, n))
    np.add.at(dense, (src, dst), w)
    dense += dense.T
    rows, cols = np.nonzero(dense)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    return CsrProblem(indptr=indptr, indices=cols.astype(np.int32),
                      data=dense[rows, cols].astype(np.float32),
                      rhs=rng.standard_normal((n, n_dense)).astype(np.float32))


def spmm_csr_ref(p: CsrProblem) -> jax.Array:
    """The product in float32 with plain ``jax.numpy``: densify, then one
    matmul at the highest precision (small problems only)."""
    rows = np.repeat(np.arange(p.rows), np.diff(p.indptr))
    dense = jnp.zeros((p.rows, p.n_cols), jnp.float32).at[rows, p.indices].add(p.data)
    with jax.default_matmul_precision("highest"):
        return dense @ jnp.asarray(p.rhs, jnp.float32)
