"""APPNP propagation on a Graph 500 Kronecker graph: the yardstick half of a
configuration.

Everything here is the benchmark's own and imports nothing of the program:
the seeded problem generator, the plain float64 reference, the control (the
same chain in bfloat16) and the algorithmic operations and bytes of a band
of rows.  The Kronecker edge list is ``spmm.py``'s, loaded from that file.

APPNP (Klicpera, Bojchevski and Günnemann, "Predict then Propagate", ICLR
2019, arXiv:1810.05997) propagates node features H over a graph:
Z⁽⁰⁾ = H, Z⁽ᵏ⁺¹⁾ = (1 − α)·Â·Z⁽ᵏ⁾ + α·H for ``steps`` steps, with
Â = D̃^-1/2 (A + I) D̃^-1/2 and D̃ the degrees of A + I.  A is the binary,
symmetric adjacency of the Graph 500 specification's Kronecker graph
(graph500.org): 2**scale vertices, edgefactor·2**scale edges, labels
permuted, self-loops and repeated edges dropped.  Â is stored in CSR in
float32, rows in the permuted label order.  H has ``dense_cols`` standard
normal float32 columns.

One loop is one propagation step.  Loop ``i`` computes step
k = i mod ``steps``: its answer is Z⁽ᵏ⁺¹⁾, and its operand is H where k is
0 (a propagation restarts from H every ``steps`` loops), else the loop
before's answer.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

from harness.registry import load_file

_spmm = load_file(Path(__file__).resolve().parent / "spmm.py")

PER_LOOP = True
BYTES_PER_ENTRY = _spmm.BYTES_PER_ENTRY
BYTES_PER_POINTER = _spmm.BYTES_PER_POINTER
BYTES_PER_ELEMENT = _spmm.BYTES_PER_ELEMENT
# Operations per element of a written row for the teleport term: two
# scalings and an add.
TELEPORT_OPS = 3


@dataclasses.dataclass(frozen=True)
class Problem:
    cfg: dict
    indptr: np.ndarray    # (R + 1,) int64 row pointers of Â
    indices: np.ndarray   # (nnz,) int32
    data: np.ndarray      # (nnz,) float32 values of Â
    h: np.ndarray         # (R, N) float32
    # chunk_work's column mask (reused) and its answers by (start, stop)
    _mask: np.ndarray = dataclasses.field(repr=False)
    _work: Dict[Tuple[int, int], Tuple[int, int]] = dataclasses.field(
        default_factory=dict, repr=False)
    # the float64 iterates the reference has computed: Z⁽⁰⁾, Z⁽¹⁾, ...
    _iterates: List[np.ndarray] = dataclasses.field(default_factory=list, repr=False)

    @property
    def rows(self) -> int:
        return len(self.indptr) - 1


def generate(cfg: dict, seed: int) -> Problem:
    """Â of the Kronecker graph and H, both from ``seed``."""
    n = 1 << cfg["scale"]
    edges, features = np.random.SeedSequence(seed).spawn(2)
    src, dst = _spmm.kronecker_edges(cfg, edges)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    loops = np.arange(n, dtype=np.int64) * (n + 1)
    key = np.unique(np.concatenate([src * n + dst, dst * n + src, loops]))
    row, col = np.divmod(key, n)
    degree = np.bincount(row, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(degree, out=indptr[1:])
    d = 1.0 / np.sqrt(degree.astype(np.float64))
    h = np.random.default_rng(features).standard_normal((n, cfg["dense_cols"]),
                                                       dtype=np.float32)
    return Problem(cfg=cfg, indptr=indptr, indices=col.astype(np.int32),
                   data=(d[row] * d[col]).astype(np.float32), h=h,
                   _mask=np.zeros(n, bool))


def step(loop: int, cfg: dict) -> int:
    """The propagation step k that loop ``loop`` computes."""
    return loop % cfg["steps"]


def reference(prob: Problem, *, loop: int) -> np.ndarray:
    """Z⁽ᵏ⁺¹⁾ for loop ``loop`` (k = loop mod steps) in float64 on the
    host: each step's product by scipy's CSR product in row blocks over a
    thread pool, as ``spmm.py``'s reference.  The iterates are kept on the
    problem, so the chain is computed once, however the loops are asked
    for."""
    target = step(loop, prob.cfg) + 1
    alpha = prob.cfg["alpha"]
    its = prob._iterates
    if not its:
        its.append(prob.h.astype(np.float64))
    while len(its) <= target:
        az = _spmm.reference(_Operand(prob.indptr, prob.indices, prob.data, its[-1]))
        az *= 1.0 - alpha
        az += alpha * its[0]
        its.append(az)
    return its[target]


@dataclasses.dataclass(frozen=True)
class _Operand:
    """Â and a float64 operand, as ``spmm.py``'s reference reads a problem."""

    indptr: np.ndarray
    indices: np.ndarray
    data: np.ndarray
    x: np.ndarray

    @property
    def rows(self) -> int:
        return len(self.indptr) - 1


def control(prob: Problem, device, *, loop: int) -> np.ndarray:
    """Z⁽ᵏ⁺¹⁾ computed in bfloat16 on ``device``: Â's values, H, every
    iterate, the products, their sums (a segment sum per block of
    entries, the blocks' partial results added in bfloat16) and the
    teleport term."""
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16
    rows, n = prob.rows, prob.h.shape[1]
    e = _spmm.CONTROL_BLOCK_ENTRIES
    nnz = int(prob.indptr[-1])
    pad = -nnz % e
    row_of = np.repeat(np.arange(rows, dtype=np.int32), np.diff(prob.indptr))
    # padding entries: value 0 on the last row, column 0
    cols = jax.device_put(np.concatenate([prob.indices, np.zeros(pad, np.int32)]), device)
    vals = jax.device_put(np.concatenate([prob.data, np.zeros(pad, np.float32)]),
                          device).astype(bf16)
    row_of = jax.device_put(np.concatenate([row_of, np.full(pad, rows - 1, np.int32)]), device)
    h = jax.device_put(prob.h, device).astype(bf16)
    a = jnp.asarray(prob.cfg["alpha"], bf16)

    @jax.jit
    def part(acc, z, entries, s):
        c, v, r = (jax.lax.dynamic_slice(x, (s,), (e,)) for x in entries)
        prod = v[:, None] * z[c]
        return acc + jax.ops.segment_sum(prod, r, num_segments=rows, indices_are_sorted=True)

    z = h
    for _ in range(step(loop, prob.cfg) + 1):
        acc = jax.device_put(jnp.zeros((rows, n), bf16), device)
        for s in range(0, nnz + pad, e):
            acc = part(acc, z, (cols, vals, row_of), np.int32(s))
        z = (1 - a) * acc + a * h
    return np.asarray(z.astype(jnp.float32), np.float64)


def chunk_work(prob: Problem, start: int, stop: int) -> tuple:
    """(operations, bytes) the algorithm needs for rows [start, stop) of a
    step: ``spmm.py``'s product (a multiply and an add per stored entry
    and dense column; each entry, row pointer and written row once, each
    distinct column's dense row once), plus the teleport term (three
    operations per element of a written row, and each written row's row
    of H read once)."""
    key = (start, stop)
    if key not in prob._work:
        ip = prob.indptr
        a, b = int(ip[start]), int(ip[stop])
        cols = prob.indices[a:b]
        prob._mask[cols] = True
        distinct = int(np.count_nonzero(prob._mask))
        prob._mask[cols] = False
        n, rows = prob.h.shape[1], stop - start
        row_bytes = BYTES_PER_ELEMENT * n
        prob._work[key] = (
            2 * (b - a) * n + TELEPORT_OPS * rows * n,
            BYTES_PER_ENTRY * (b - a) + BYTES_PER_POINTER * (rows + 1)
            + row_bytes * (distinct + rows) + row_bytes * rows)
    return prob._work[key]
