"""APPNP propagation: the step kernel, the host op, the operand that the
device op rebuilds each step, and the runtime path over chained loops,
each against the plain float32 reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HeteroRuntime, WorkerKind
from repro.kernels.spmm.csr import (appnp_csr_window, csr_window_start, put_operand_rows,
                                    write_window_rows)
from repro.kernels.spmm.ops import AppnpWindowOp, HostCsr, appnp_rows_host
from repro.kernels.spmm.ref import (appnp_propagate_ref, make_csr_problem,
                                    normalized_adjacency)
from test_kernels import CSR, CSR_WINDOW_CASES

ALPHA = 0.1
STEPS = 10


def _ref(indptr, indices, data, h, steps):
    return np.asarray(appnp_propagate_ref(indptr, indices, data, h, ALPHA, steps))


# one step of CSR's matrix from Z1 (the step after H): Z2's rows depend on
# both operands
H = CSR.rhs
Z1 = _ref(CSR.indptr, CSR.indices, CSR.data, H, 1)
Z2 = _ref(CSR.indptr, CSR.indices, CSR.data, H, 2)


@pytest.mark.parametrize("start,stop,window,tile,block", list(CSR_WINDOW_CASES.values()),
                         ids=list(CSR_WINDOW_CASES))
def test_step_window_matches_reference(start, stop, window, tile, block):
    lo = csr_window_start(start, stop, CSR.rows, window)
    out = appnp_csr_window(jnp.asarray(CSR.indptr, jnp.int32), jnp.asarray(CSR.indices),
                           jnp.asarray(CSR.data), jnp.asarray(Z1), jnp.asarray(H),
                           np.int32(lo), window=window, alpha=ALPHA, tile=tile, block=block)
    assert out.shape == (window, H.shape[1])
    np.testing.assert_allclose(np.asarray(out), Z2[lo:lo + window], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("write", [write_window_rows, put_operand_rows])
def test_write_replaces_only_the_chunks_rows(write):
    nxt = np.arange(40 * 4, dtype=np.float32).reshape(40, 4)
    rows = -np.ones((8, 4), np.float32)
    out = np.asarray(write(jnp.asarray(nxt), jnp.asarray(rows), np.int32(30),
                           np.int32(33), np.int32(36)))
    want = nxt.copy()
    want[33:36] = -1.0
    np.testing.assert_array_equal(out, want)


def test_normalized_adjacency_matches_dense():
    # row 0 has a repeated entry and its own diagonal, row 2 none, row 3 is empty
    indptr = np.array([0, 3, 4, 5, 5])
    indices = np.array([0, 1, 1, 0, 1], np.int32)
    ip, ix, d = normalized_adjacency(indptr, indices)
    dense = np.zeros((4, 4))
    dense[np.repeat(np.arange(4), np.diff(indptr)), indices] = 1.0
    np.fill_diagonal(dense, 1.0)
    s = 1.0 / np.sqrt(dense.sum(1))
    want = s[:, None] * dense * s[None, :]
    got = np.zeros((4, 4))
    got[np.repeat(np.arange(4), np.diff(ip)), ix] = d
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert d.dtype == np.float32 and np.all(np.diff(ip) >= 1)


def test_normalized_adjacency_of_a_kronecker_graph_is_symmetric():
    p = make_csr_problem(8, 4, seed=3)
    ip, ix, d = normalized_adjacency(p.indptr, p.indices)
    dense = np.zeros((p.rows, p.rows))
    dense[np.repeat(np.arange(p.rows), np.diff(ip)), ix] = d
    np.testing.assert_allclose(dense, dense.T, rtol=1e-6)
    assert np.all(np.diag(dense) > 0)
    assert len(ix) == p.nnz + p.rows           # the pattern has no diagonal


def test_host_rows_match_reference():
    csr = HostCsr(CSR.indptr, CSR.indices, CSR.data)
    for start, stop in [(0, 256), (100, 140), (128, 192), (255, 256)]:
        np.testing.assert_allclose(appnp_rows_host(csr, Z1, H, start, stop, ALPHA),
                                   Z2[start:stop], rtol=1e-5, atol=1e-5)


def _graph(scale=10, cols=8, seed=2):
    p = make_csr_problem(scale, cols, seed=seed)
    ip, ix, d = normalized_adjacency(p.indptr, p.indices)
    return HostCsr(ip, ix, d), p.rhs


def _propagate(csr, h, loops, *, window=64, block=16):
    """``loops`` steps through ``HeteroRuntime.parallel_for`` (two ACC
    units on ``jax:0``, two CC thread units), restarting from H every
    STEPS loops, as a caller of a propagation hands the operand over.
    Returns, per loop, (the result, the CC chunks, the op's bytes put)."""
    op = AppnpWindowOp(csr, h, jax.devices()[0], window=window, alpha=ALPHA, steps=STEPS,
                       block=block)
    state = {}

    def acc(chunk):
        state["acc"].append((chunk.start, chunk.stop) + op(chunk.start, chunk.stop))
        return state["acc"][-1][3]

    def cc(chunk):
        rows = appnp_rows_host(csr, state["operand"], h, chunk.start, chunk.stop, ALPHA)
        state["result"][chunk.start:chunk.stop] = rows
        op.put_rows(chunk.start, rows)
        state["cc"].append((chunk.start, chunk.stop))

    rt = HeteroRuntime()
    for i in range(2):
        rt.register_unit(f"acc{i}", WorkerKind.ACC, backend="jax:0", work_fn=acc)
        rt.register_unit(f"cc{i}", WorkerKind.CC, work_fn=cc)
    out = []
    result = None
    with rt:
        for i in range(loops):
            k = i % STEPS
            state["operand"] = h if k == 0 else result
            op.begin_step(k)
            result = np.full(h.shape, np.nan, np.float32)
            state.update(result=result, acc=[], cc=[])
            rep = rt.parallel_for(num_items=csr.rows, acc_chunk=window)
            assert rep.items == csr.rows
            for s, e, lo, rows in state["acc"]:
                result[s:e] = np.asarray(rows)[s - lo:e - lo, :h.shape[1]]
            out.append((result, list(state["cc"]), op.put_bytes))
    return out


def test_runtime_chain_matches_reference_across_a_restart():
    csr, h = _graph()
    runs = _propagate(csr, h, 12)
    refs = [_ref(csr.indptr, csr.indices, csr.data, h, k + 1) for k in range(STEPS)]
    for i, (result, cc, _) in enumerate(runs):
        np.testing.assert_allclose(result, refs[i % STEPS], rtol=1e-5, atol=1e-5,
                                   err_msg=f"loop {i}")
    assert any(cc for _, cc, _ in runs)       # both kinds of unit took rows


def test_host_to_device_bytes_are_the_cc_rows(monkeypatch):
    """Per loop the host puts only the CC units' rows up, in blocks of
    ``block`` rows and 128 lanes, never the operand."""
    csr, h = _graph(seed=4)
    block, lane_bytes = 16, 128 * 4
    put = []
    real_put = jax.device_put

    def device_put(x, *a, **kw):
        if isinstance(x, np.ndarray) and started:
            put.append(x.shape)
        return real_put(x, *a, **kw)

    started = False
    monkeypatch.setattr(jax, "device_put", device_put)
    real_begin = AppnpWindowOp.begin_step
    per_loop = []

    def begin_step(self, k):
        nonlocal started
        started = True
        per_loop.append(put[:])
        put.clear()
        real_begin(self, k)
    monkeypatch.setattr(AppnpWindowOp, "begin_step", begin_step)
    runs = _propagate(csr, h, 4, block=block)
    per_loop = per_loop[1:] + [put]
    for (_, cc, put_bytes), shapes in zip(runs, per_loop):
        want = sum(-(-(e - s) // block) for s, e in cc) * block * lane_bytes
        assert put_bytes == want == len(shapes) * block * lane_bytes
        assert all(shape == (block, 128) for shape in shapes)   # never (rows, 128)
    assert any(cc for _, cc, _ in runs)
    assert block < csr.rows
