"""SpMM on a Graph 500 Kronecker graph: the yardstick half of a configuration.

Everything here is the benchmark's own and imports nothing of the program:
the seeded problem generator, the plain float64 reference, the control (the
same product in bfloat16) and the algorithmic operations and bytes of a
band of rows.

The matrix is the adjacency of the Graph 500 benchmark specification's
Kronecker graph (graph500.org): 2**scale vertices, edgefactor·2**scale
edges, each edge's endpoints drawn bit by bit from the initiator
[[A, B], [C, D]], vertex labels randomly permuted, edge values uniform in
[0, 1) (the spec's SSSP weights).  Stored symmetrised, with self-loops
dropped and duplicate entries merged into one (values summed), rows in the
permuted label order.  The dense operand X has ``dense_cols`` standard
normal float32 columns.  The product is A·X.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

# Bytes per stored entry (int32 column, float32 value), per row pointer
# (int32) and per float32 element of a dense row.
BYTES_PER_ENTRY = 8
BYTES_PER_POINTER = 4
BYTES_PER_ELEMENT = 4
# Entries per block of the reference and of the control; slices of the
# edge list drawn apart, and the host threads that draw them and run the
# reference.
REF_BLOCK_ENTRIES = 1 << 16
CONTROL_BLOCK_ENTRIES = 1 << 21
EDGE_SLICES = 16
THREADS = 8


@dataclasses.dataclass(frozen=True)
class Problem:
    cfg: dict
    indptr: np.ndarray    # (R + 1,) int64
    indices: np.ndarray   # (nnz,) int32
    data: np.ndarray      # (nnz,) float32
    x: np.ndarray         # (R, N) float32
    # chunk_work's column mask (reused) and its answers by (start, stop)
    _mask: np.ndarray = dataclasses.field(repr=False)
    _work: Dict[Tuple[int, int], Tuple[int, int]] = dataclasses.field(
        default_factory=dict, repr=False)

    @property
    def rows(self) -> int:
        return len(self.indptr) - 1


def kronecker_edges(cfg: dict, seq: np.random.SeedSequence) -> Tuple[np.ndarray, np.ndarray]:
    """(start, end) vertex labels of the spec's edge list, labels permuted.

    Each edge's endpoints are drawn bit by bit: the start's bit is 1 with
    probability C + D, the end's with probability B / (A + B) after a 0
    and D / (C + D) after a 1.  The edges are drawn in ``EDGE_SLICES``
    slices, each from its own stream spawned from ``seq``, over a thread
    pool."""
    scale = cfg["scale"]
    n, m = 1 << scale, cfg["edgefactor"] << scale
    a, b, c = cfg["A"], cfg["B"], cfg["C"]
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    parts = EDGE_SLICES
    streams = seq.spawn(parts + 1)
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)

    def draw(k: int) -> None:
        rng = np.random.default_rng(streams[k])
        lo, hi = k * m // parts, (k + 1) * m // parts
        i, j = ii[lo:hi], jj[lo:hi]
        for bit in range(scale):
            i_bit = rng.random(hi - lo) > ab
            j_bit = rng.random(hi - lo) > np.where(i_bit, c_norm, a_norm)
            i |= i_bit.astype(np.int64) << bit
            j |= j_bit.astype(np.int64) << bit

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(draw, range(parts)))
    perm = np.random.default_rng(streams[parts]).permutation(n)
    return perm[ii], perm[jj]


def generate(cfg: dict, seed: int) -> Problem:
    """The symmetrised Kronecker graph and X, both from ``seed``."""
    n = 1 << cfg["scale"]
    edges, weights, operand = np.random.SeedSequence(seed).spawn(3)
    src, dst = kronecker_edges(cfg, edges)
    w = np.random.default_rng(weights).random(len(src))
    keep = src != dst
    src, dst, w = src[keep], dst[keep], w[keep]
    key = np.concatenate([src * n + dst, dst * n + src])
    val = np.concatenate([w, w])
    order = np.argsort(key)
    key, val = key[order], val[order]
    first = np.flatnonzero(np.concatenate([[True], key[1:] != key[:-1]]))
    key, val = key[first], np.add.reduceat(val, first)
    row, col = np.divmod(key, n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(row, minlength=n), out=indptr[1:])
    x = np.random.default_rng(operand).standard_normal((n, cfg["dense_cols"]), dtype=np.float32)
    return Problem(cfg=cfg, indptr=indptr, indices=col.astype(np.int32),
                   data=val.astype(np.float32), x=x, _mask=np.zeros(n, bool))


def _blocks(indptr: np.ndarray, entries: int):
    """Row ranges of about ``entries`` stored entries each, covering every
    row from 0, the empty rows before the first entry too (a longer row is
    a block of its own)."""
    cuts = np.searchsorted(indptr, np.arange(0, indptr[-1], entries), side="right") - 1
    cuts = np.unique(np.concatenate([[0], cuts, [len(indptr) - 1]]))
    return list(zip(cuts[:-1].tolist(), cuts[1:].tolist()))


def reference(prob: Problem, *, block_entries: int = REF_BLOCK_ENTRIES,
              workers: int = THREADS) -> np.ndarray:
    """A·X in float64 on the host: scipy's CSR product (for each row, a
    sum over its entries in order), in row blocks over a thread pool."""
    import scipy.sparse

    ip = prob.indptr
    x64 = prob.x.astype(np.float64)
    data64 = prob.data.astype(np.float64)
    out = np.empty((prob.rows, prob.x.shape[1]), np.float64)

    def rows_f64(rs) -> None:
        r0, r1 = rs
        a, b = ip[r0], ip[r1]
        block = scipy.sparse.csr_matrix(
            (data64[a:b], prob.indices[a:b], ip[r0:r1 + 1] - a),
            shape=(r1 - r0, x64.shape[0]))
        out[r0:r1] = block @ x64

    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(rows_f64, _blocks(ip, block_entries)))
    return out


def control(prob: Problem, device) -> np.ndarray:
    """The product computed in bfloat16 on ``device``: values, X, the
    products and their sums (a segment sum per block of entries, the
    blocks' partial results added in bfloat16)."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    rows = prob.rows
    e = CONTROL_BLOCK_ENTRIES
    nnz = int(prob.indptr[-1])
    row_of = np.repeat(np.arange(rows, dtype=np.int32), np.diff(prob.indptr))
    pad = -nnz % e
    # padding entries: value 0 on the last row, column 0
    cols = np.concatenate([prob.indices, np.zeros(pad, np.int32)])
    vals = np.concatenate([prob.data, np.zeros(pad, np.float32)])
    row_of = np.concatenate([row_of, np.full(pad, rows - 1, np.int32)])
    x = jax.device_put(prob.x, device).astype(bf16)

    @jax.jit
    def part(acc, c, v, r):
        prod = v.astype(bf16)[:, None] * x[c]
        return acc + jax.ops.segment_sum(prod, r, num_segments=rows, indices_are_sorted=True)

    acc = jax.device_put(jnp.zeros((rows, prob.x.shape[1]), bf16), device)
    for s in range(0, nnz + pad, e):
        acc = part(acc, *(jax.device_put(a[s:s + e], device) for a in (cols, vals, row_of)))
    return np.asarray(acc.astype(jnp.float32), np.float64)


def chunk_work(prob: Problem, start: int, stop: int) -> tuple:
    """(operations, bytes) the algorithm needs for rows [start, stop): a
    multiply and an add per stored entry and dense column; each entry, row
    pointer and written row once, and each distinct column's dense row
    once."""
    key = (start, stop)
    if key not in prob._work:
        ip = prob.indptr
        a, b = int(ip[start]), int(ip[stop])
        cols = prob.indices[a:b]
        prob._mask[cols] = True
        distinct = int(np.count_nonzero(prob._mask))
        prob._mask[cols] = False
        row_bytes = BYTES_PER_ELEMENT * prob.x.shape[1]
        prob._work[key] = (
            2 * (b - a) * prob.x.shape[1],
            BYTES_PER_ENTRY * (b - a) + BYTES_PER_POINTER * (stop - start + 1)
            + row_bytes * (distinct + stop - start))
    return prob._work[key]
