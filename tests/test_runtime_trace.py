"""Spans and the per-chunk timeline of a wall-clock ``parallel_for``.

``RunReport.timeline`` (``repro.core.trace.Timeline``) is filled by the
``"interrupt"`` engine from the same call sites that open the ``eneac.*``
profiler spans: the dispatcher's seconds per phase, one ``ChunkTimes`` per
completed chunk, and the completion bus's wake-ups.  Without a profiler
running the instrumentation changes no result and no scheduling decision.
"""

import glob
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

import repro.core.trace as trace_mod
from repro.core import HeteroRuntime, SimulatedClock, WallClock, WorkerKind
from repro.core.space import ShardedSpace

ROOT = Path(__file__).resolve().parents[1]
PHASES = {"units_start", "submit", "bus_wait", "complete", "units_close", "report"}
_X = jnp.ones((32, 32), jnp.float32)


# Chunks take milliseconds, as on a chip, so the dispatcher mostly waits.
def _acc_work(chunk):
    time.sleep(2.5e-3 * chunk.size)   # the host's share of an enqueue
    return _X * chunk.start           # enqueued on the device, awaited by the waiter


def _cc_work(chunk):
    time.sleep(2.5e-3 * chunk.size)


def _wall_runtime(acc_backend: str) -> HeteroRuntime:
    rt = HeteroRuntime(clock=WallClock())
    for i in range(2):
        rt.register_unit(f"acc{i}", WorkerKind.ACC, backend=acc_backend,
                         work_fn=_acc_work if acc_backend == "jax" else _cc_work)
        rt.register_unit(f"cc{i}", WorkerKind.CC, backend="thread", work_fn=_cc_work)
    return rt


@pytest.fixture(scope="module", params=["jax", "thread", "inline"])
def wall_report(request):
    """An interrupt run over 2 ACC units on the given backend + 2 CC threads."""
    rt = _wall_runtime(request.param)
    rt.parallel_for(num_items=96, acc_chunk=8)     # warm the jitted work up
    return rt.parallel_for(num_items=96, acc_chunk=8)


def test_one_record_per_completed_chunk(wall_report):
    tl = wall_report.timeline
    assert len(tl.chunks) == wall_report.chunks
    assert sorted((c.start, c.stop) for c in tl.chunks) == wall_report.coverage
    per_unit = {}
    for c in tl.chunks:
        per_unit[c.unit] = per_unit.get(c.unit, 0) + 1
    assert per_unit == {u: n for u, n in wall_report.per_worker_chunks.items() if n}


def test_chunk_times_are_ordered(wall_report):
    for c in wall_report.timeline.chunks:
        assert c.submitted <= c.enqueued <= c.ready <= c.posted <= c.drained, c


def test_phases_tile_the_dispatcher_wall(wall_report):
    tl = wall_report.timeline
    assert set(tl.phase_s) == PHASES
    assert all(v >= 0 for v in tl.phase_s.values())
    assert sum(tl.phase_s.values()) == pytest.approx(wall_report.wall_time, rel=0.02)


def test_bus_counts(wall_report):
    tl = wall_report.timeline
    assert tl.drained == wall_report.chunks
    assert 1 <= tl.wakeups <= tl.drained


def _events(log_dir):
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(path)
    return [(e.name, dict(e.stats)) for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name.startswith("eneac.")]


def test_profiler_records_the_spans(tmp_path):
    rt = _wall_runtime("jax")
    rt.parallel_for(num_items=64, acc_chunk=8)
    with jax.profiler.trace(str(tmp_path)):
        rep = rt.parallel_for(num_items=64, acc_chunk=8)
    events = _events(tmp_path)
    names = {n for n, _ in events}
    assert names == {"eneac." + p for p in PHASES} | {
        "eneac.parallel_for", "eneac.acc_wait", "eneac.unit_exec"}
    issued = sorted((a["unit"], a["start"]) for n, a in events
                    if n == "eneac.submit" and "start" in a)
    assert issued == sorted((c.unit, c.start) for c in rep.timeline.chunks)
    waited = sorted((a["unit"], a["start"]) for n, a in events if n == "eneac.acc_wait")
    assert waited == sorted((c.unit, c.start) for c in rep.timeline.chunks
                            if c.unit.startswith("acc"))
    ran = sorted((a["unit"], a["start"]) for n, a in events if n == "eneac.unit_exec")
    assert ran == sorted((c.unit, c.start) for c in rep.timeline.chunks
                         if c.unit.startswith("cc"))
    assert sum(n == "eneac.parallel_for" for n, _ in events) == 1


def _fixed_runs():
    def sim(engine):
        rt = HeteroRuntime(clock=SimulatedClock())
        rt.register_unit("acc0", WorkerKind.ACC, speed=8.0)
        rt.register_unit("cc0", WorkerKind.CC, speed=1.0)
        rt.register_unit("cc1", WorkerKind.CC, speed=1.5)
        return rt.parallel_for(num_items=500, engine=engine, acc_chunk=16)

    def wall(engine, backend=None):
        rt = HeteroRuntime(clock=WallClock())
        for i in range(3):
            rt.register_unit(f"acc{i}", WorkerKind.ACC, work_fn=lambda c: None)
        return rt.parallel_for(num_items=500, engine=engine, acc_chunk=16,
                               backend=backend)

    return {
        "simulated-interrupt": lambda: sim("interrupt"),
        "simulated-polling": lambda: sim("polling"),
        "wall-inline-engine": lambda: wall("inline"),
        "wall-interrupt-inline-backend": lambda: wall("interrupt", "inline"),
    }


@pytest.mark.parametrize("case", sorted(_fixed_runs()))
def test_spans_change_no_decision(case, monkeypatch):
    run = _fixed_runs()[case]
    traced = run()
    monkeypatch.setattr(trace_mod, "_annotation", lambda name, **ids: trace_mod._NO_SPAN)
    plain = run()
    assert traced.coverage == plain.coverage
    assert traced.per_worker_chunks == plain.per_worker_chunks
    assert traced.per_worker_items == plain.per_worker_items
    if case.startswith("simulated"):
        assert traced.wall_time == plain.wall_time
        assert traced.per_worker_busy == plain.per_worker_busy


class _SlowSpan:
    """A span whose unit-thread kinds stall on exit (and on enter where
    ``enter`` is set), to show the stall stays out of ``elapsed``."""

    STALL = 0.02

    def __init__(self, name, enter, **ids):
        self.stall = name in ("eneac.unit_exec", "eneac.acc_wait")
        self.enter = enter

    def __enter__(self):
        if self.stall and self.enter:
            time.sleep(self.STALL)
        return self

    def __exit__(self, *exc):
        if self.stall:
            time.sleep(self.STALL)

    def set_metadata(self, **ids):
        pass


# A JaxDeviceUnit's waiter opens its span while the device may still run,
# so only the span's exit is sure to fall outside the timed stretch there.
@pytest.mark.parametrize("backend,enter", [("thread", True), ("jax", False)])
def test_span_cost_stays_out_of_elapsed(backend, enter, monkeypatch):
    rt = _wall_runtime(backend)
    rt.parallel_for(num_items=32, acc_chunk=8)
    monkeypatch.setattr(trace_mod, "_annotation",
                        lambda name, **ids: _SlowSpan(name, enter, **ids))
    rep = rt.parallel_for(num_items=32, acc_chunk=8)
    for unit, n in rep.per_worker_chunks.items():
        # host units sleep 2.5 ms an item; ACC chunks' busy is the wait alone
        work = 2.5e-3 * rep.per_worker_items[unit] if backend == "thread" else 0.0
        if n and (backend == "thread" or unit.startswith("acc")):
            assert rep.per_worker_busy[unit] - work < n * _SlowSpan.STALL / 2, unit


def _sharded_wall():
    rt = _wall_runtime("thread")
    return rt.parallel_for(space=ShardedSpace(96, 2), acc_chunk=8)


@pytest.mark.parametrize("run", [
    lambda: _fixed_runs()["simulated-interrupt"](),
    lambda: _fixed_runs()["simulated-polling"](),
    lambda: _fixed_runs()["wall-inline-engine"](),
    lambda: _wall_runtime("thread").parallel_for(num_items=64, engine="polling", acc_chunk=8),
    _sharded_wall,
], ids=["simulated-interrupt", "simulated-polling", "wall-inline-engine",
        "wall-polling", "wall-sharded"])
def test_timeline_only_on_the_interrupt_engine(run):
    assert run().timeline is None


def test_sharded_run_keeps_each_shards_timeline():
    rep = _sharded_wall()
    for shard in rep.shard_reports:
        assert len(shard.timeline.chunks) == shard.chunks


def test_core_does_not_import_jax():
    code = (
        "import sys, time\n"
        "import repro.core.trace, repro.core.runtime, repro.core.backends, "
        "repro.core.scheduler\n"
        "from repro.core import HeteroRuntime, WorkerKind\n"
        "rt = HeteroRuntime()\n"
        "rt.register_unit('cc0', WorkerKind.CC, work_fn=lambda c: time.sleep(1e-4))\n"
        "rep = rt.parallel_for(num_items=32, acc_chunk=4)\n"
        "assert len(rep.timeline.chunks) == rep.chunks\n"
        "assert repro.core.trace.span('eneac.x') is repro.core.trace._NO_SPAN\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "print('NOJAX_OK')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                          text=True, timeout=120,
                          env=dict(os.environ, PYTHONPATH=str(ROOT / "src")))
    assert "NOJAX_OK" in proc.stdout, proc.stderr


# --- the per-chunk work counter (trace.add_work, RunReport.per_worker_work) --

def _spmm_runtime(acc_backend: str):
    """2 ACC units running the CSR window op (on the device, or on a host
    backend) and 2 CC threads running the numpy op, over a SCALE 9 graph."""
    import numpy as np

    from repro.kernels.spmm.ops import CsrWindowOp, HostCsr, spmm_rows_host
    from repro.kernels.spmm.ref import make_csr_problem

    p = make_csr_problem(9, 8, seed=1)
    csr = HostCsr(p.indptr, p.indices, p.data)
    op = CsrWindowOp(csr, p.rhs, jax.devices()[0], window=32)
    rt = HeteroRuntime(clock=WallClock())
    for i in range(2):
        rt.register_unit(f"acc{i}", WorkerKind.ACC, backend=acc_backend,
                         work_fn=lambda c: op(c.start, c.stop)[1])
        rt.register_unit(f"cc{i}", WorkerKind.CC, backend="thread",
                         work_fn=lambda c: spmm_rows_host(csr, p.rhs, c.start, c.stop))
    assert p.nnz == int(np.diff(p.indptr).sum()) > 0
    return rt, p


@pytest.mark.parametrize("acc_backend", ["jax", "thread", "inline"])
def test_work_totals_are_the_matrix_entries(acc_backend):
    rt, p = _spmm_runtime(acc_backend)
    trace_mod.add_work(1000)           # outside a chunk: counts nowhere
    rep = rt.parallel_for(num_items=p.rows, acc_chunk=32)
    work = rep.per_worker_work
    assert set(work) == set(rep.per_worker_items)
    assert sum(work.values()) == p.nnz
    entries = dict.fromkeys(work, 0)
    for c in rep.timeline.chunks:
        entries[c.unit] += int(p.indptr[c.stop] - p.indptr[c.start])
    assert work == entries
    assert work["acc0"] + work["acc1"] > 0


def test_work_is_none_where_no_op_counts(wall_report):
    assert wall_report.per_worker_work is None


def test_add_work_outside_a_chunk_is_a_no_op():
    trace_mod.add_work(5)
    assert trace_mod.close_work() is None
    trace_mod.open_work()
    trace_mod.add_work(2)
    trace_mod.add_work(3)
    assert trace_mod.close_work() == 5
    trace_mod.add_work(7)
    assert trace_mod.close_work() is None


def test_sharded_run_namespaces_the_work():
    rt, p = _spmm_runtime("thread")
    rep = rt.parallel_for(space=ShardedSpace(p.rows, 2), acc_chunk=32)
    assert sum(rep.per_worker_work.values()) == p.nnz
    assert all(name.startswith(("s0/", "s1/")) for name in rep.per_worker_work)


def test_profiler_records_the_spmm_spans(tmp_path):
    rt, p = _spmm_runtime("jax")
    rt.parallel_for(num_items=p.rows, acc_chunk=32)
    with jax.profiler.trace(str(tmp_path)):
        rep = rt.parallel_for(num_items=p.rows, acc_chunk=32)
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    events = [(e.name, dict(e.stats)) for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:") for line in plane.lines
              for e in line.events if e.name.startswith("spmm.")]
    acc = [a for n, a in events if n == "spmm.acc_window"]
    cc = [a for n, a in events if n == "spmm.cc_rows"]
    assert len(acc) + len(cc) == rep.chunks and acc
    assert all(a["tiles"] >= 0 for a in acc)
    assert sum(a["nnz"] for a in acc + cc) == p.nnz



# --- the host copy an ACC unit starts (RunReport.per_worker_host_copy_bytes) --

def test_host_copy_bytes_follow_the_acc_results(wall_report, request):
    copied = wall_report.per_worker_host_copy_bytes
    if request.node.callspec.params["wall_report"] != "jax":
        assert copied is None          # host units return host values
        return
    assert set(copied) == set(wall_report.per_worker_items)
    for unit, n in wall_report.per_worker_chunks.items():
        assert copied[unit] == (n * _X.nbytes if unit.startswith("acc") else 0), unit


def test_sharded_run_sums_the_host_copy_bytes():
    rep = _wall_runtime("jax").parallel_for(space=ShardedSpace(96, 2), acc_chunk=8)
    merged = rep.per_worker_host_copy_bytes
    assert all(name.startswith(("s0/", "s1/")) for name in merged)
    for k, shard in enumerate(rep.shard_reports):
        for unit, v in shard.per_worker_host_copy_bytes.items():
            assert merged[f"s{k}/{unit}"] == v
    acc_chunks = sum(n for u, n in rep.per_worker_chunks.items() if "/acc" in u)
    assert sum(merged.values()) == acc_chunks * _X.nbytes > 0


def test_acc_wait_span_carries_the_host_copy_bytes(tmp_path):
    rt = _wall_runtime("jax")
    rt.parallel_for(num_items=64, acc_chunk=8)
    with jax.profiler.trace(str(tmp_path)):
        rep = rt.parallel_for(num_items=64, acc_chunk=8)
    waits = [a for n, a in _events(tmp_path) if n == "eneac.acc_wait"]
    assert waits and all(a["host_copy_bytes"] == _X.nbytes for a in waits)
    assert sum(rep.per_worker_host_copy_bytes.values()) == len(waits) * _X.nbytes
