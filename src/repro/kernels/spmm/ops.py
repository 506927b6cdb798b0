"""SPMM execution paths + the ENEAC hybrid executor wiring.

Paths (Table-1 columns):
* ``cc``  — ELL gather path (jnp; VPU on TPU, vectorized loops on CPU), and
  :func:`spmm_rows_host`, the same gather over CSR arrays in numpy for
  host-core units.
* ``acc`` — block-ELL Pallas MXU kernel (RHS VMEM-resident), run on a
  fixed-size window of row blocks placed by :func:`spmm_window_start`;
  and, for matrices whose dense operand outgrows VMEM, the CSR window
  :func:`~repro.kernels.spmm.csr.spmm_csr_window` (operand left in HBM),
  placed and called through :class:`CsrWindowOp`.
* hybrid — MultiDynamic split: densest row-prefix on the ACC path, sparse
  tail on the CC path (rows pre-sorted by density; the split point is the
  scheduler's decision, see :class:`repro.core.parallel_for.HybridExecutor`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.parallel_for import HybridExecutor, SplitDecision
from ...core.trace import add_work, span
from .csr import csr_window_start, csr_window_tiles, spmm_csr_window
from .ref import COL_BLOCK, ROW_BLOCK, SpmmProblem, spmm_ell_ref, to_block_ell
from .spmm import BlockEllArrays, spmm_block_ell_pallas

__all__ = ["spmm_cc", "density_order", "sorted_by_density",
           "make_hybrid_executor", "spmm_window_start", "HostCsr",
           "spmm_rows_host", "CsrWindowOp"]


@jax.jit
def spmm_cc(vals: jax.Array, cols: jax.Array, rhs: jax.Array) -> jax.Array:
    return spmm_ell_ref(vals, cols, rhs)


def density_order(p: SpmmProblem) -> np.ndarray:
    """Row order, densest first — prefix split ⇒ ACC gets MXU-worthy rows."""
    return np.argsort(-p.nnz, kind="stable")


def sorted_by_density(p: SpmmProblem) -> Tuple[SpmmProblem, np.ndarray]:
    """(the problem with rows densest first, that row order)."""
    order = density_order(p)
    return SpmmProblem(vals=p.vals[order], cols=p.cols[order],
                       nnz=p.nnz[order], n_cols=p.n_cols, rhs=p.rhs), order


def spmm_window_start(start: int, stop: int, n_row_blocks: int, window: int) -> int:
    """First row block of the ``window``-block slab holding rows [start, stop).

    Near the end of the matrix the slab slides back instead of shrinking,
    so every ACC chunk runs on the same window shape.
    """
    rb0 = min(start // ROW_BLOCK, n_row_blocks - window)
    if rb0 < 0 or (stop - 1) // ROW_BLOCK >= rb0 + window:
        raise ValueError(
            f"rows [{start}, {stop}) do not fit a {window}-row-block window "
            f"of a {n_row_blocks}-row-block matrix"
        )
    return rb0


class HostCsr:
    """CSR arrays on the host, for the numpy gather path: ``indptr``
    (R + 1,) int64, ``indices`` (nnz,) int32, ``data`` (nnz,) f32."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray, data: np.ndarray) -> None:
        self.indptr = np.asarray(indptr, np.int64)
        self.indices = np.asarray(indices, np.int32)
        self.data = np.asarray(data, np.float32)

    @classmethod
    def from_ell(cls, p: SpmmProblem) -> "HostCsr":
        live = np.arange(p.vals.shape[1])[None, :] < p.nnz[:, None]
        return cls(np.concatenate([[0], np.cumsum(p.nnz, dtype=np.int64)]),
                   p.cols[live], p.vals[live])

    @property
    def rows(self) -> int:
        return len(self.indptr) - 1


def spmm_rows_host(csr: HostCsr, rhs: np.ndarray, start: int, stop: int) -> np.ndarray:
    """Rows [start, stop) of the product: Σ_j vals[r, j]·rhs[cols[r, j]].

    Counts the rows' stored entries as the chunk's work."""
    a, b = int(csr.indptr[start]), int(csr.indptr[stop])
    with span("spmm.cc_rows", nnz=b - a):
        prod = csr.data[a:b, None] * rhs[csr.indices[a:b]]
        out = np.zeros((stop - start, rhs.shape[1]), np.float32)
        nz = csr.indptr[start + 1:stop + 1] > csr.indptr[start:stop]
        if a < b:
            out[nz] = np.add.reduceat(prod, csr.indptr[start:stop][nz] - a, axis=0)
    add_work(b - a)
    return out


class CsrWindowOp:
    """A CSR matrix and its dense operand placed on one device, with
    ``kernel`` (:func:`~repro.kernels.spmm.csr.spmm_csr_window`) compiled
    for windows of ``window`` rows and warmed up.

    X is placed with its columns padded with zeros to a multiple of 128,
    the lane width: a (C, 100) array would be laid out column-major on a
    TPU and copied to row-major on every call.  ``op(start, stop)``
    enqueues the window holding rows [start, stop) and returns (its first
    row, its (window, N_pad) device rows, of which the first N are the
    product), without waiting; it counts the rows' stored entries as the
    chunk's work and opens the span ``spmm.acc_window`` (``nnz``,
    ``tiles``) over the enqueue.
    """

    def __init__(self, csr: HostCsr, rhs: np.ndarray, device, *, window: int,
                 kernel=spmm_csr_window) -> None:
        self.csr = csr
        self.window = min(window, csr.rows)
        n = rhs.shape[1]
        x = np.zeros((rhs.shape[0], -(-n // 128) * 128), np.float32)
        x[:, :n] = rhs
        self.args = jax.block_until_ready(tuple(
            jax.device_put(a, device) for a in
            (csr.indptr.astype(np.int32), csr.indices, csr.data, x)))
        self._exe = kernel.lower(*self.args, np.int32(0), window=self.window).compile()
        jax.block_until_ready(self._exe(*self.args, np.int32(0)))

    def __call__(self, start: int, stop: int):
        ip = self.csr.indptr
        lo = csr_window_start(start, stop, self.csr.rows, self.window)
        nnz = int(ip[stop] - ip[start])
        tiles = csr_window_tiles(ip, lo, self.window)
        with span("spmm.acc_window", nnz=nnz, tiles=tiles):
            out = self._exe(*self.args, np.int32(lo))
        add_work(nnz)
        return lo, out


def pad_rhs(p: SpmmProblem) -> np.ndarray:
    c_pad = ((p.n_cols + COL_BLOCK - 1) // COL_BLOCK) * COL_BLOCK
    n = p.rhs.shape[1]
    n_pad = ((n + 127) // 128) * 128
    out = np.zeros((c_pad, n_pad), np.float32)
    out[: p.n_cols, :n] = p.rhs
    return out


def make_hybrid_executor(
    p: SpmmProblem,
    *,
    mode: str = "parallel",
    interpret: Optional[bool] = None,
    dense_quantum: int = ROW_BLOCK,
) -> Tuple[HybridExecutor, np.ndarray]:
    """Build the two path callables over the density-sorted row space.

    Returns (executor, row_order).  ``executor.run()`` computes the full
    product; results come back in sorted-row order (invert with row_order).
    """
    sorted_problem, order = sorted_by_density(p)
    vals_s = jnp.asarray(sorted_problem.vals)
    cols_s = jnp.asarray(sorted_problem.cols)
    rhs = jnp.asarray(p.rhs)
    rhs_pad = jnp.asarray(pad_rhs(p))
    n = p.rhs.shape[1]
    R = p.rows

    # pack once at full size; a prefix split is a window from row block 0
    ell = BlockEllArrays(to_block_ell(sorted_problem))

    def dense_fn(n_rows: int):
        if n_rows <= 0:
            return None
        nrb = (n_rows + ROW_BLOCK - 1) // ROW_BLOCK
        out = spmm_block_ell_pallas(ell, rhs_pad, n_row_blocks=nrb,
                                    interpret=interpret)
        return jax.block_until_ready(out[:n_rows, :n])

    def sparse_fn(n_rows: int):
        if n_rows <= 0:
            return None
        out = spmm_cc(vals_s[R - n_rows:], cols_s[R - n_rows:], rhs)
        return jax.block_until_ready(out)

    def merge_fn(dense_res, sparse_res):
        parts = [r for r in (dense_res, sparse_res) if r is not None]
        return jnp.concatenate(parts, axis=0)

    execr = HybridExecutor(
        dense_fn, sparse_fn, merge_fn, num_items=R, mode=mode,
        dense_quantum=dense_quantum,
    )
    return execr, order
