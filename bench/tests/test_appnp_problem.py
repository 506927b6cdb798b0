"""The APPNP yardstick (``bench/problems/appnp.py``): the normalised
Graph 500 adjacency, the float64 reference of each step against a dense
NumPy chain, the work counts, and three faults of the chain that the
check must read as not correct."""

import json

import numpy as np
import pytest

from conftest import ROOT
from harness import cell
from harness.registry import load_file
from repro.kernels.spmm.ops import AppnpWindowOp

appnp = load_file(ROOT / "bench" / "problems" / "appnp.py")
CFG = json.loads((ROOT / "bench/configs/appnp-graph500-s20.json").read_text())
SMALL = dict(CFG, **CFG["rehearsal"])
CELL = "appnp-graph500-s20.hybrid"


def _dense(p) -> np.ndarray:
    a = np.zeros((p.rows, p.rows))
    a[np.repeat(np.arange(p.rows), np.diff(p.indptr)), p.indices] = p.data
    return a


def test_seed_fixes_the_graph_and_the_features():
    a, b = appnp.generate(SMALL, 2**31 + 5), appnp.generate(SMALL, 2**31 + 5)
    c = appnp.generate(SMALL, 2**31 + 6)
    for name in ("indptr", "indices", "data", "h"):
        assert np.array_equal(getattr(a, name), getattr(b, name)), name
    assert a.rows == c.rows == 1 << SMALL["scale"]
    assert not np.array_equal(a.indices[:100], c.indices[:100])
    assert not np.array_equal(a.h, c.h)


def test_matrix_is_the_normalised_binary_graph_with_self_loops():
    p = appnp.generate(SMALL, 3)
    rows = np.repeat(np.arange(p.rows), np.diff(p.indptr))
    key = rows * p.rows + p.indices
    assert np.all(np.diff(key) > 0)                          # sorted, no duplicates
    assert np.count_nonzero(rows == p.indices) == p.rows     # one self-loop a vertex
    flipped = np.sort(p.indices.astype(np.int64) * p.rows + rows)
    assert np.array_equal(flipped, key)                      # symmetric pattern
    deg = np.diff(p.indptr).astype(np.float64)               # degrees of A + I
    np.testing.assert_allclose(p.data, 1.0 / np.sqrt(deg[rows] * deg[p.indices]), rtol=1e-6)
    assert p.data.dtype == np.float32
    assert p.h.shape == (p.rows, CFG["dense_cols"]) and p.h.dtype == np.float32
    assert deg.max() > 10 * np.median(deg)                   # skewed rows


def test_reference_matches_a_dense_chain():
    """Z⁽ᵏ⁺¹⁾ for k = 0, 1 and 9, asked for out of order and from a later
    cycle, equal a dense float64 chain from H."""
    p = appnp.generate(dict(CFG, scale=8), 4)
    a, h, alpha = _dense(p), p.h.astype(np.float64), CFG["alpha"]
    chain, z = [], h
    for _ in range(CFG["steps"]):
        z = (1.0 - alpha) * (a @ z) + alpha * h
        chain.append(z)
    for loop in (9, 0, 21, 11, 19):
        k = loop % CFG["steps"]
        np.testing.assert_allclose(appnp.reference(p, loop=loop), chain[k],
                                   rtol=1e-12, atol=1e-12)


def test_chunk_work_adds_the_teleport_term():
    p = appnp.generate(SMALL, 5)
    n = CFG["dense_cols"]
    for start, stop in [(0, 64), (100, 101), (0, p.rows)]:
        a, b = p.indptr[start], p.indptr[stop]
        distinct = len(np.unique(p.indices[a:b]))
        rows = stop - start
        ops, nbytes = appnp.chunk_work(p, start, stop)
        assert ops == 2 * (b - a) * n + 3 * rows * n
        assert nbytes == 8 * (b - a) + 4 * (rows + 1) + 400 * (distinct + rows) + 400 * rows
        assert appnp.chunk_work(p, start, stop) == (ops, nbytes)
    assert not p._mask.any()


def _stale_operand(monkeypatch, glue):
    """The operand buffers never swap after a restart: each loop reads
    the operand the loop before read, the result of two loops ago."""
    real = AppnpWindowOp.begin_step
    monkeypatch.setattr(AppnpWindowOp, "begin_step",
                        lambda self, k: real(self, k) if k % self.steps == 0 else None)


def _h_every_loop(monkeypatch, glue):
    """Every loop is handed H, as at a restart."""
    real_make = glue.make

    def make(prob, acc_chunk):
        g = real_make(prob, acc_chunk)
        begin, steps = g["begin_loop"], prob.cfg["steps"]
        g["begin_loop"] = lambda i: begin(i - i % steps)
        return g
    monkeypatch.setattr(glue, "make", make)


def _cc_rows_not_put(monkeypatch, glue):
    """The CC rows never reach the device's next operand."""
    monkeypatch.setattr(AppnpWindowOp, "put_rows", lambda self, start, rows: None)


@pytest.mark.parametrize("fault", [_stale_operand, _h_every_loop, _cc_rows_not_put],
                         ids=["two_loops_ago", "h_every_loop", "cc_rows_not_put"])
def test_chain_fault_is_not_correct(tiny_root, monkeypatch, fault):
    glue = load_file(tiny_root / "bench" / "glue" / "appnp.py")
    fault(monkeypatch, glue)
    res, log = cell.run_cell(tiny_root, CELL, 17, 0.2, False, need_chip=False)
    assert res["correct"] is False, (res["checks"], log)
    assert res["checks"]["unwritten_rows"]["value"] == 0
    assert res["checks"]["max_row_rel_err"]["value"] > res["checks"]["max_row_rel_err"]["limit"]
