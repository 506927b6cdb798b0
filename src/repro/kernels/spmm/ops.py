"""SPMM execution paths + the ENEAC hybrid executor wiring.

Paths (Table-1 columns):
* ``cc``  — ELL gather path (jnp; VPU on TPU, vectorized loops on CPU), and
  :func:`spmm_rows_host`, the same gather over CSR arrays in numpy for
  host-core units (:func:`appnp_rows_host`: an APPNP step's rows).
* ``acc`` — block-ELL Pallas MXU kernel (RHS VMEM-resident), run on a
  fixed-size window of row blocks placed by :func:`spmm_window_start`;
  and, for matrices whose dense operand outgrows VMEM, the CSR window
  :func:`~repro.kernels.spmm.csr.spmm_csr_window` (operand left in HBM),
  placed and called through :class:`CsrWindowOp`; and one APPNP
  propagation step on such a window, :class:`AppnpWindowOp`, which keeps
  the operand that changes from step to step on the device.
* hybrid — MultiDynamic split: densest row-prefix on the ACC path, sparse
  tail on the CC path (rows pre-sorted by density; the split point is the
  scheduler's decision, see :class:`repro.core.parallel_for.HybridExecutor`).
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core.parallel_for import HybridExecutor, SplitDecision
from ...core.trace import add_work, span
from .csr import (appnp_csr_window, csr_window_start, csr_window_tiles,
                  put_operand_rows, spmm_csr_window, write_window_rows)
from .ref import COL_BLOCK, ROW_BLOCK, SpmmProblem, spmm_ell_ref, to_block_ell
from .spmm import BlockEllArrays, spmm_block_ell_pallas

__all__ = ["spmm_cc", "density_order", "sorted_by_density",
           "make_hybrid_executor", "spmm_window_start", "HostCsr",
           "spmm_rows_host", "CsrWindowOp", "appnp_rows_host", "AppnpWindowOp",
           "PUT_BLOCK"]

# Rows in a block of host rows put into an APPNP operand: one compiled
# shape for every put.
PUT_BLOCK = 512


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
        out = _product_rows(csr, rhs, start, stop)
    add_work(b - a)
    return out


def appnp_rows_host(csr: HostCsr, z: np.ndarray, h: np.ndarray, start: int, stop: int,
                    alpha: float) -> np.ndarray:
    """Rows [start, stop) of one APPNP step, (1 − α)·Â·Z + α·H, with
    ``csr`` holding Â: :func:`spmm_rows_host`'s gather, then the teleport
    term.  Counts the rows' stored entries as the chunk's work."""
    a, b = int(csr.indptr[start]), int(csr.indptr[stop])
    with span("appnp.cc_rows", nnz=b - a):
        out = _product_rows(csr, z, start, stop)
        out *= np.float32(1.0 - alpha)
        out += np.float32(alpha) * h[start:stop]
    add_work(b - a)
    return out


def _product_rows(csr: HostCsr, rhs: np.ndarray, start: int, stop: int) -> np.ndarray:
    """Rows [start, stop) of A·rhs in float32: the entries' scaled rows of
    ``rhs``, summed per row with ``reduceat``."""
    a, b = int(csr.indptr[start]), int(csr.indptr[stop])
    prod = csr.data[a:b, None] * rhs[csr.indices[a:b]]
    out = np.zeros((stop - start, rhs.shape[1]), np.float32)
    nz = csr.indptr[start + 1:stop + 1] > csr.indptr[start:stop]
    if a < b:
        out[nz] = np.add.reduceat(prod, csr.indptr[start:stop][nz] - a, axis=0)
    return out


def _lane_padded(a: np.ndarray) -> np.ndarray:
    """``a`` with its columns padded with zeros to a multiple of 128, the
    lane width: a (C, 100) array would be laid out column-major on a TPU
    and copied to row-major on every call."""
    out = np.zeros((a.shape[0], -(-a.shape[1] // 128) * 128), np.float32)
    out[:, :a.shape[1]] = a
    return out


def _placed_csr(csr: HostCsr, device) -> tuple:
    """``csr``'s arrays on ``device``, row pointers as int32."""
    return tuple(jax.device_put(a, device)
                 for a in (csr.indptr.astype(np.int32), csr.indices, csr.data))


def _window(csr: HostCsr, start: int, stop: int, window: int) -> Tuple[int, int, int]:
    """(first row of the window holding rows [start, stop), the rows'
    stored entries, the window's tiles)."""
    ip = csr.indptr
    lo = csr_window_start(start, stop, csr.rows, window)
    return lo, int(ip[stop] - ip[start]), csr_window_tiles(ip, lo, window)


class CsrWindowOp:
    """A CSR matrix and its dense operand placed on one device, with
    ``kernel`` (:func:`~repro.kernels.spmm.csr.spmm_csr_window`) compiled
    for windows of ``window`` rows and warmed up.

    X is placed with its columns padded with zeros to a multiple of 128,
    the lane width.  ``op(start, stop)`` enqueues the window holding rows
    [start, stop) and returns (its first row, its (window, N_pad) device
    rows, of which the first N are the product), without waiting; it
    counts the rows' stored entries as the chunk's work and opens the span
    ``spmm.acc_window`` (``nnz``, ``tiles``) over the enqueue.
    """

    def __init__(self, csr: HostCsr, rhs: np.ndarray, device, *, window: int,
                 kernel=spmm_csr_window) -> None:
        self.csr = csr
        self.window = min(window, csr.rows)
        self.args = jax.block_until_ready(
            _placed_csr(csr, device) + (jax.device_put(_lane_padded(rhs), device),))
        self._exe = kernel.lower(*self.args, np.int32(0), window=self.window).compile()
        jax.block_until_ready(self._exe(*self.args, np.int32(0)))

    def __call__(self, start: int, stop: int):
        lo, nnz, tiles = _window(self.csr, start, stop, self.window)
        with span("spmm.acc_window", nnz=nnz, tiles=tiles):
            out = self._exe(*self.args, np.int32(lo))
        add_work(nnz)
        return lo, out


class AppnpWindowOp:
    """APPNP propagation, Z ← (1 − α)·Â·Z + α·H, on one device: Â (in
    ``csr``) and H placed once, and two operand buffers, each the size of
    H, that the steps take in turn.  ``kernel``
    (:func:`~repro.kernels.spmm.csr.appnp_csr_window`) is compiled for
    windows of ``window`` rows, and the writes into the next operand
    (:func:`~repro.kernels.spmm.csr.write_window_rows`,
    :func:`~repro.kernels.spmm.csr.put_operand_rows`) for a window's rows
    and for blocks of ``block`` host rows; all are warmed up.  H and the
    buffers have their columns padded with zeros to a multiple of 128.

    ``begin_step(k)`` starts step ``k`` (span ``appnp.swap``, ``step``):
    its operand is H where ``k`` is a multiple of ``steps`` (a restart),
    else the buffer the step before wrote; the other buffer becomes the
    next operand, which the step writes.  ``op(start, stop)`` enqueues the
    window holding rows [start, stop) and the write of those rows into the
    next operand, and returns (the window's first row, its (window, N_pad)
    device rows), without waiting; it counts the rows' stored entries as
    the chunk's work (span ``appnp.acc_window``, ``nnz``, ``tiles``).
    ``put_rows(start, rows)`` writes rows computed on the host into the
    next operand: blocks of ``block`` rows, the last one masked, each one
    host→device put (span ``appnp.put_rows``, ``rows``, ``bytes``) and one
    in-place update.  ``put_bytes`` counts the bytes put since the step
    began.

    Every write donates the next operand's buffer and takes the one it
    returns, under one lock, so the ACC units and host threads of a device
    may call at once and the writes run on the device in the order they
    were enqueued.  A step must write every row, by windows or puts,
    before the next begins.
    """

    def __init__(self, csr: HostCsr, h: np.ndarray, device, *, window: int, alpha: float,
                 steps: int, block: int = PUT_BLOCK, kernel=appnp_csr_window) -> None:
        self.csr = csr
        self.device = device
        self.window = min(window, csr.rows)
        self.block = min(block, csr.rows)
        self.steps = steps
        self.put_bytes = 0
        h_pad = _lane_padded(h)
        self.args = jax.block_until_ready(_placed_csr(csr, device))
        self.h = jax.block_until_ready(jax.device_put(h_pad, device))
        self._next, self._spare = (jax.device_put(np.zeros_like(h_pad), device) for _ in range(2))
        self._read = self.h
        self._lock = threading.Lock()
        i32 = np.int32(0)
        self._exe = kernel.lower(*self.args, self.h, self.h, i32, window=self.window,
                                 alpha=alpha).compile()
        rows = self._exe(*self.args, self.h, self.h, i32)
        block_rows = jax.device_put(np.zeros((self.block, h_pad.shape[1]), np.float32), device)
        self._write = write_window_rows.lower(self._next, rows, i32, i32, i32).compile()
        self._put = put_operand_rows.lower(self._next, block_rows, i32, i32, i32).compile()
        self._next = self._write(self._next, rows, i32, i32, i32)
        self._next = self._put(self._next, block_rows, i32, i32, i32)
        jax.block_until_ready(self._next)

    def begin_step(self, k: int) -> None:
        with self._lock, span("appnp.swap", step=k):
            written = self._next
            self._next, self._spare = self._spare, written
            self._read = self.h if k % self.steps == 0 else written
            self.put_bytes = 0

    def __call__(self, start: int, stop: int):
        lo, nnz, tiles = _window(self.csr, start, stop, self.window)
        with span("appnp.acc_window", nnz=nnz, tiles=tiles), self._lock:
            out = self._exe(*self.args, self._read, self.h, np.int32(lo))
            self._next = self._write(self._next, out, np.int32(lo), np.int32(start),
                                     np.int32(stop))
        add_work(nnz)
        return lo, out

    def put_rows(self, start: int, rows: np.ndarray) -> None:
        stop, n = start + len(rows), rows.shape[1]
        for a in range(start, stop, self.block):
            b = min(a + self.block, stop)
            lo = min(a, self.csr.rows - self.block)
            padded = np.zeros((self.block, self.h.shape[1]), np.float32)
            padded[a - lo:b - lo, :n] = rows[a - start:b - start]
            with span("appnp.put_rows", rows=b - a, bytes=padded.nbytes):
                put = jax.device_put(padded, self.device)
                with self._lock:
                    self._next = self._put(self._next, put, np.int32(lo), np.int32(a),
                                           np.int32(b))
                    self.put_bytes += padded.nbytes


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
