"""CSR SpMM on one device, with the dense operand left in HBM.

The ACC path for matrices whose rows are skewed and whose dense operand
is larger than VMEM (a power-law graph times a feature matrix): the
block-ELL kernel keeps the whole operand resident and pads every row
block to the most occupied column blocks, neither of which holds there.

One call computes rows [start, start + window) of A·X in one device loop
over the window's stored entries, which are contiguous in CSR.  A trip
takes the next ``tile`` entries, or fewer where they would reach past
``block`` rows from the row of the first: one XLA gather of the X rows
their column indices name, scaled by their values in float32 on the VPU,
then summed into those rows with a 0/1 matrix that maps each entry to its
row, an MXU ``dot`` at ``HIGHEST`` (float32: the 0/1 side is exact in
every pass).  The trip count follows the window's entries: empty rows
take no trip, and a row longer than a tile takes several.

The window and the loop sizes are static and ``start`` is not, so every
call on a device reuses one compiled program.

APPNP propagation (Klicpera et al., ICLR 2019) repeats the product with
an operand that each step computes: Z ← (1 − α)·Â·Z + α·H.
:func:`appnp_csr_window` is one step on a window, through the same trip
loop.  The next operand is a donated buffer written in place, a masked
fixed-shape slab at a time: :func:`write_window_rows` takes a window's
rows, :func:`put_operand_rows` a block of rows put up from the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["spmm_csr_window", "appnp_csr_window", "put_operand_rows",
           "write_window_rows", "csr_window_start", "csr_window_tiles", "CSR_TILE", "CSR_BLOCK"]

# Entries per trip and rows a trip may reach of the chip path (measured
# on a TPU v5e: PERF.md, section 6).
CSR_TILE = 8192
CSR_BLOCK = 256


@functools.partial(jax.jit, static_argnames=("window", "tile", "block"))
def spmm_csr_window(
    indptr: jax.Array,    # (R + 1,) int32 row pointers
    indices: jax.Array,   # (nnz,) int32 column of each stored entry
    data: jax.Array,      # (nnz,) f32 value of each stored entry
    x: jax.Array,         # (C, N) f32 dense operand, left in HBM
    start,                # int32 scalar: first row of the window
    *,
    window: int,
    tile: int = CSR_TILE,
    block: int = CSR_BLOCK,
) -> jax.Array:
    """Rows [start, start + window) of A·X, as a (window, N) f32 array.

    The window must lie inside the matrix's rows (callers place it with
    :func:`csr_window_start`).
    """
    return _window_rows(indptr, indices, data, x, start, window, tile, block)


@functools.partial(jax.jit, static_argnames=("window", "alpha", "tile", "block"))
def appnp_csr_window(
    indptr: jax.Array,    # (R + 1,) int32 row pointers of Â
    indices: jax.Array,   # (nnz,) int32 column of each stored entry
    data: jax.Array,      # (nnz,) f32 value of each stored entry
    z: jax.Array,         # (R, N) f32 operand of the step, left in HBM
    h: jax.Array,         # (R, N) f32 teleport rows H, left in HBM
    start,                # int32 scalar: first row of the window
    *,
    window: int,
    alpha: float,
    tile: int = CSR_TILE,
    block: int = CSR_BLOCK,
) -> jax.Array:
    """One APPNP propagation step on a window: rows [start, start + window)
    of (1 − α)·Â·Z + α·H, as a (window, N) f32 array.

    The product takes :func:`spmm_csr_window`'s trip loop; the teleport
    term reads the window's rows of H.
    """
    s = jnp.asarray(start, jnp.int32)
    az = _window_rows(indptr, indices, data, z, s, window, tile, block)
    return (1.0 - alpha) * az + alpha * jax.lax.dynamic_slice(h, (s, 0), (window, h.shape[1]))


@functools.partial(jax.jit, donate_argnames=("nxt",))
def put_operand_rows(nxt: jax.Array, rows: jax.Array, start, first, stop) -> jax.Array:
    """``nxt`` with its rows [first, stop) replaced by those of ``rows``,
    a block of rows put up from the host whose first row is row ``start``
    of ``nxt``.  ``nxt`` is donated: written in place."""
    return _write_rows(nxt, rows, start, first, stop)


@functools.partial(jax.jit, donate_argnames=("nxt",))
def write_window_rows(nxt: jax.Array, rows: jax.Array, start, first, stop) -> jax.Array:
    """As :func:`put_operand_rows`, for a window's rows that the device
    computed: a module of its own name, so that the profiler tells the two
    apart."""
    return _write_rows(nxt, rows, start, first, stop)


def _write_rows(nxt, rows, start, first, stop):
    """``nxt`` with rows [first, stop) of the slab at row ``start`` taken
    from ``rows``: a masked update of that slab alone."""
    s = jnp.asarray(start, jnp.int32)
    r = s + jnp.arange(rows.shape[0], dtype=jnp.int32)
    keep = (first <= r) & (r < stop)
    cur = jax.lax.dynamic_slice(nxt, (s, 0), rows.shape)
    return jax.lax.dynamic_update_slice(nxt, jnp.where(keep[:, None], rows, cur), (s, 0))


def _window_rows(indptr, indices, data, x, start, window, tile, block):
    """The trip loop of :func:`spmm_csr_window`: rows [start, start +
    window) of A·X."""
    rows = indptr.shape[0] - 1
    nnz, n = indices.shape[0], x.shape[1]
    if not 0 < window <= rows:
        raise ValueError(f"window of {window} rows, matrix has {rows}")
    if nnz == 0:
        raise ValueError("the matrix has no stored entries")
    tile = min(tile, nnz)
    s = jnp.asarray(start, jnp.int32)
    # row pointers of the window's rows and of ``block`` rows past it,
    # which hold none of its entries
    ptr = indptr[jnp.minimum(s + jnp.arange(window + block + 1, dtype=jnp.int32), rows)]
    ptr = jnp.minimum(ptr, ptr[window])
    lane = jnp.arange(tile, dtype=jnp.int32)

    def trip(carry):
        e, out = carry
        r = jnp.sum(ptr[1:window + 1] <= e).astype(jnp.int32)   # the row of entry e
        rp = jax.lax.dynamic_slice(ptr, (r,), (block + 1,))
        end = jnp.minimum(e + tile, rp[block])
        # a trip that would run off the arrays reads from earlier; the
        # entries before e are masked out below
        t0 = jnp.minimum(e, nnz - tile)
        cols = jax.lax.dynamic_slice(indices, (t0,), (tile,))
        vals = jax.lax.dynamic_slice(data, (t0,), (tile,))
        scaled = vals[:, None] * x.at[cols].get(mode="promise_in_bounds")
        pos = t0 + lane
        mine = ((jnp.maximum(rp[:-1], e)[:, None] <= pos[None, :])
                & (pos[None, :] < jnp.minimum(rp[1:], end)[:, None]))
        part = jnp.dot(mine.astype(jnp.float32), scaled,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        cur = jax.lax.dynamic_slice(out, (r, 0), (block, n))
        return end, jax.lax.dynamic_update_slice(out, cur + part, (r, 0))

    _, out = jax.lax.while_loop(lambda c: c[0] < ptr[window], trip,
                                (ptr[0], jnp.zeros((window + block, n), jnp.float32)))
    return out[:window]


def csr_window_start(start: int, stop: int, rows: int, window: int) -> int:
    """First row of the ``window``-row slab holding rows [start, stop).

    Near the end of the matrix the slab slides back instead of shrinking,
    so every ACC chunk runs on the same window shape.
    """
    lo = min(start, rows - window)
    if lo < 0 or stop > lo + window:
        raise ValueError(f"rows [{start}, {stop}) do not fit a {window}-row "
                         f"window of a {rows}-row matrix")
    return lo


def csr_window_tiles(indptr: np.ndarray, start: int, window: int, *,
                     tile: int = CSR_TILE) -> int:
    """Tiles of ``tile`` entries that the window at ``start`` fills: the
    fewest trips :func:`spmm_csr_window` takes there (a trip that reaches
    ``block`` rows ends early)."""
    return -(-int(indptr[start + window] - indptr[start]) // tile)
