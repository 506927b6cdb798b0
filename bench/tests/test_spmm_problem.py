"""The SpMM yardstick (``bench/problems/spmm.py``): the Graph 500 generator,
the blocked float64 reference and the work counts."""

import dataclasses
import json

import numpy as np
import pytest

from conftest import ROOT
from harness.cell import row_errors
from harness.registry import load_file

spmm = load_file(ROOT / "bench" / "problems" / "spmm.py")
CFG = json.loads((ROOT / "bench/configs/spmm-graph500-s20.json").read_text())
SMALL = dict(CFG, **CFG["rehearsal"])


def test_seed_fixes_the_matrix_and_the_operand():
    a, b = spmm.generate(SMALL, 2**31 + 5), spmm.generate(SMALL, 2**31 + 5)
    c = spmm.generate(SMALL, 2**31 + 6)
    for name in ("indptr", "indices", "data", "x"):
        assert np.array_equal(getattr(a, name), getattr(b, name)), name
    assert a.rows == c.rows == 1 << SMALL["scale"]
    assert not np.array_equal(a.indices[:100], c.indices[:100])
    assert not np.array_equal(a.x, c.x)


def test_matrix_is_the_symmetrised_graph():
    p = spmm.generate(SMALL, 3)
    rows = np.repeat(np.arange(p.rows), np.diff(p.indptr))
    assert not np.any(rows == p.indices)                    # no self-loops
    key = rows * p.rows + p.indices
    assert np.all(np.diff(key) > 0)                         # sorted, no duplicates
    flipped = np.sort(p.indices.astype(np.int64) * p.rows + rows)
    assert np.array_equal(flipped, key)                     # symmetric pattern
    assert p.x.shape == (p.rows, CFG["dense_cols"]) and p.x.dtype == np.float32
    deg = np.diff(p.indptr)
    assert deg.max() > 10 * np.median(deg[deg > 0])          # skewed rows
    assert (deg == 0).any()


def test_blocked_reference_equals_the_whole():
    p = spmm.generate(dict(SMALL, scale=12), 4)
    whole = spmm.reference(p, block_entries=1 << 40, workers=1)
    assert np.array_equal(spmm.reference(p, block_entries=500, workers=8), whole)
    dense = np.zeros((p.rows, p.rows))
    np.add.at(dense, (np.repeat(np.arange(p.rows), np.diff(p.indptr)), p.indices), p.data)
    np.testing.assert_allclose(whole, dense @ p.x.astype(np.float64), rtol=1e-12, atol=1e-12)


def test_reference_writes_the_empty_rows_before_the_first_entry():
    """Rows before the first stored entry are zero in the reference, not
    left as allocated, however the rows are cut into blocks."""
    p = spmm.generate(SMALL, 4)
    a = p.indptr[40]
    indptr = np.concatenate([np.zeros(40, np.int64), p.indptr[40:] - a])
    p = dataclasses.replace(p, indptr=indptr, indices=p.indices[a:], data=p.data[a:])
    dense = np.zeros((p.rows, p.rows))
    np.add.at(dense, (np.repeat(np.arange(p.rows), np.diff(p.indptr)), p.indices), p.data)
    want = dense @ p.x.astype(np.float64)
    for block_entries, workers in [(1 << 40, 1), (500, 8), (1, 2)]:
        blocks = spmm._blocks(p.indptr, block_entries)
        assert blocks[0][0] == 0 and blocks[-1][1] == p.rows
        assert all(b == c for (_, b), (c, _) in zip(blocks, blocks[1:]))
        np.testing.assert_allclose(spmm.reference(p, block_entries=block_entries,
                                                  workers=workers),
                                   want, rtol=1e-12, atol=1e-12)


def test_chunk_work_counts_distinct_columns():
    p = spmm.generate(SMALL, 5)
    for start, stop in [(0, 64), (100, 101), (0, p.rows)]:
        a, b = p.indptr[start], p.indptr[stop]
        distinct = len(np.unique(p.indices[a:b]))
        ops, nbytes = spmm.chunk_work(p, start, stop)
        assert ops == 2 * (b - a) * CFG["dense_cols"]
        assert nbytes == 8 * (b - a) + 4 * (stop - start + 1) + 400 * (distinct + stop - start)
        assert spmm.chunk_work(p, start, stop) == (ops, nbytes)
    assert not p._mask.any()


@pytest.mark.parametrize("scale", [SMALL["scale"], 14])
def test_output_as_allocated_fails_the_limit(scale):
    """A kernel that returns its output as allocated reads above the limit
    on every row that has an entry."""
    p = spmm.generate(dict(CFG, scale=scale), 7)
    errs = row_errors(np.zeros((p.rows, CFG["dense_cols"]), np.float32), spmm.reference(p))
    live = np.diff(p.indptr) > 0
    assert errs[live].min() > CFG["limits"]["max_row_rel_err"]


@pytest.mark.parametrize("unit", ["acc", "cc"])
def test_rows_no_chunk_wrote_read_as_unwritten(tiny_root, monkeypatch, unit):
    """The glue reuses its result buffers: rows of a chunk whose result
    never reaches this loop's buffer must still come out NaN, as
    unwritten, whatever an earlier loop left there."""
    from harness import cell

    glue = load_file(tiny_root / "bench" / "glue" / "spmm.py")
    real_make = glue.make

    def make(prob, acc_chunk):
        g = real_make(prob, acc_chunk)
        if unit == "cc":
            g["cc_work"] = lambda chunk: None
        else:
            g["acc_work"] = lambda device: (lambda chunk: None)
        return g
    monkeypatch.setattr(glue, "make", make)
    res, log = cell.run_cell(tiny_root, "spmm-graph500-s20.hybrid", 13, 0.2, False,
                             need_chip=False)
    assert res["checks"]["unwritten_rows"]["value"] > 0, (res["checks"], log)
    assert res["correct"] is False


def test_a_result_buffer_is_reused_only_once_no_view_is_held(tiny_root):
    glue = load_file(tiny_root / "bench" / "glue" / "spmm.py")
    g = glue.make(spmm.generate(SMALL, 3), 64)

    def next_result(loop):
        g["begin_loop"](loop)
        return g["assemble"]()[0]

    held = [next_result(loop) for loop in range(glue.BUFFERS + 2)]
    for i, a in enumerate(held):
        assert not any(np.shares_memory(a, b) for b in held[i + 1:])
    dropped = held.pop(3)
    base = dropped.base
    del dropped
    again = next_result(len(held) + 1)
    assert again.base is base
    assert not any(np.shares_memory(again, b) for b in held)
