"""Backend units + the event-driven wall-clock engine (ISSUE 4).

The contract under test: real backend units (dedicated threads, process
pools, jax device streams) give *genuine* asynchronous dispatch — work
overlaps on real threads — while the scheduler invariants survive real
concurrency:

* completed chunks tile the space exactly (no index lost or duplicated),
* work-function side effects fire exactly once per index, even across
  randomized WallClock elastic join/leave schedules (a leave retires the
  unit: its in-flight chunk completes and counts; pre-split leftovers
  are requeued to survivors under the tracked scheduler's lock),
* ``RunReport.events`` is monotone in time and ``dispatch_latency`` is
  populated by the backend layer,
* kernels driven through ``parallel_for(space=TiledSpace,
  backend="threads")`` produce bit-exact results — thread dispatch can
  never silently reorder or corrupt tile writes,
* ``"jax:<i>"`` binds ``JaxDeviceUnit`` to ``jax.devices()[i]`` and never
  wraps an index that does not exist.

Everything here runs on a real WallClock with microsecond-scale sleeps,
so the whole module stays fast; the heavy randomized sweeps are marked
``slow`` per ``pytest.ini``.
"""

import threading
import time
from collections import Counter

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI container has no hypothesis; use the vendored shim
    from _propcheck import given, settings, strategies as st

import repro.core.backends as backends_mod
from repro.core import (
    CompletionBus,
    CompletionRecord,
    ElasticEvent,
    ElasticSchedule,
    HeteroRuntime,
    InlineUnit,
    JaxDeviceUnit,
    ProcessPoolUnit,
    ShardedSpace,
    ThreadUnit,
    TiledSpace,
    WorkerKind,
)
from repro.core.backends import make_backend
from repro.core.runtime import POLICIES
from repro.core.scheduler import Chunk


def assert_exact_tiling(spans, n_items):
    assert spans, "no chunks completed"
    assert spans[0][0] == 0
    assert spans[-1][1] == n_items
    for (a, b), (c, d) in zip(spans, spans[1:]):
        assert b == c, f"gap or overlap at {b}:{c}"


class Recorder:
    """Thread-safe exact-once ledger the work functions write into."""

    def __init__(self, per_item_sleep=0.0):
        self.lock = threading.Lock()
        self.counts = Counter()
        self.per_item_sleep = per_item_sleep

    def __call__(self, chunk):
        if self.per_item_sleep:
            time.sleep(chunk.size * self.per_item_sleep)
        with self.lock:
            self.counts.update(chunk.indices())

    def assert_exactly_once(self, n_items):
        assert set(self.counts) == set(range(n_items)), (
            f"missing {sorted(set(range(n_items)) - set(self.counts))[:5]}..."
        )
        dupes = {i: c for i, c in self.counts.items() if c != 1}
        assert not dupes, f"indices executed more than once: {dupes}"


# ---------------------------------------------------------------------------
# individual backend units
# ---------------------------------------------------------------------------
class TestUnits:
    def _drive(self, unit, chunks, work_fn):
        bus = CompletionBus()
        unit.start(bus)
        try:
            recs = []
            for c in chunks:
                unit.submit(c, work_fn)
                assert bus.wait(timeout=10.0)
                recs.extend(bus.drain())
            return recs
        finally:
            unit.close()

    @pytest.mark.parametrize("cls", [InlineUnit, ThreadUnit])
    def test_submit_completes_with_result_and_latency(self, cls):
        unit = cls("u0")
        recs = self._drive(
            unit, [Chunk(0, 4, "u0"), Chunk(4, 9, "u0")],
            lambda c: c.size * 10,
        )
        assert [r.result for r in recs] == [40, 50]
        assert all(r.error is None for r in recs)
        assert all(r.dispatch_latency >= 0 for r in recs)
        assert len(unit.dispatch_latencies) == 2

    def test_thread_unit_runs_off_the_caller_thread(self):
        unit = ThreadUnit("u0")
        caller = threading.get_ident()
        recs = self._drive(
            unit, [Chunk(0, 1, "u0")], lambda c: threading.get_ident()
        )
        assert recs[0].result != caller

    def test_inline_unit_runs_on_the_caller_thread(self):
        unit = InlineUnit("u0")
        recs = self._drive(
            unit, [Chunk(0, 1, "u0")], lambda c: threading.get_ident()
        )
        assert recs[0].result == threading.get_ident()

    def test_error_is_captured_not_raised(self):
        def boom(c):
            raise RuntimeError("kaput")

        recs = self._drive(ThreadUnit("u0"), [Chunk(0, 1, "u0")], boom)
        assert isinstance(recs[0].error, RuntimeError)

    def test_thread_unit_restartable_across_runs(self):
        unit = ThreadUnit("u0")
        r1 = self._drive(unit, [Chunk(0, 2, "u0")], lambda c: c.size)
        r2 = self._drive(unit, [Chunk(2, 5, "u0")], lambda c: c.size)
        assert (r1[0].result, r2[0].result) == (2, 3)

    def test_process_unit_executes_in_worker(self):
        unit = ProcessPoolUnit("p0")
        recs = self._drive(
            unit, [Chunk(0, 10, "p0")], _sum_indices
        )
        if unit.degraded:  # sandbox without process support: thread fallback
            pytest.skip("process pool unavailable; degraded to thread")
        assert recs[0].result == sum(range(10))
        assert recs[0].error is None

    def test_process_unit_counts_work_in_worker(self):
        unit = ProcessPoolUnit("p0")
        recs = self._drive(unit, [Chunk(3, 10, "p0")], _count_indices)
        assert recs[0].error is None and recs[0].work == 7

    def test_jax_unit_dispatches_jitted_work(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x * 2.0).sum())
        unit = JaxDeviceUnit("d0")
        recs = self._drive(
            unit, [Chunk(0, 8, "d0")],
            lambda c: f(jnp.arange(c.size, dtype=jnp.float32)),
        )
        assert float(recs[0].result) == float(sum(2.0 * i for i in range(8)))

    def test_jax_index_binds_that_device_and_posts_results_there(self):
        import jax
        import jax.numpy as jnp

        unit = make_backend("jax:0", "d0")
        assert isinstance(unit, JaxDeviceUnit)
        assert unit.device == jax.devices()[0]
        assert unit.platform == jax.devices()[0].platform
        recs = self._drive(unit, [Chunk(0, 6, "d0")],
                           lambda c: jnp.arange(c.size) * 2)
        assert recs[0].error is None
        assert recs[0].result.devices() == {jax.devices()[0]}
        assert list(map(int, recs[0].result)) == [0, 2, 4, 6, 8, 10]

    def test_unknown_backend_spec_rejected(self):
        rt = HeteroRuntime()
        with pytest.raises(ValueError, match="unknown backend"):
            rt.register_unit("a", WorkerKind.CC, backend="gpu-go-brrr")

    def test_instance_name_must_match_unit_name(self):
        # completions are routed by unit name: a mismatched (or shared)
        # instance would post completions the scheduler cannot attribute
        rt = HeteroRuntime()
        with pytest.raises(ValueError, match="names must match"):
            rt.register_unit("cc0", WorkerKind.CC, work_fn=lambda c: None,
                             backend=ThreadUnit("mine"))
        rt2 = HeteroRuntime()
        rt2.register_unit("cc0", WorkerKind.CC, work_fn=lambda c: None)
        with pytest.raises(ValueError, match="names must match"):
            rt2.parallel_for(num_items=10, engine="interrupt",
                             backend=ThreadUnit("other"))
        # a shared instance cannot back two units: the second unit's name
        # can never match too
        shared = ThreadUnit("u0")
        rt3 = HeteroRuntime()
        rt3.register_unit("u0", WorkerKind.CC, work_fn=lambda c: None,
                          backend=shared)
        with pytest.raises(ValueError, match="names must match"):
            rt3.register_unit("u1", WorkerKind.CC, work_fn=lambda c: None,
                              backend=shared)

    def test_matching_instance_backend_works(self):
        rec = Recorder()
        rt = HeteroRuntime()
        rt.register_unit("cc0", WorkerKind.CC, work_fn=rec,
                         backend=ThreadUnit("cc0"))
        rep = rt.parallel_for(num_items=50, engine="interrupt", acc_chunk=8)
        assert rep.items == 50
        rec.assert_exactly_once(50)


def _count_indices(chunk):
    """Picklable work that counts its chunk's indices (``trace.add_work``)."""
    from repro.core.trace import add_work

    add_work(chunk.size)


def _sum_indices(chunk):
    """Module-level so ProcessPoolUnit can pickle it."""
    return sum(range(chunk.start, chunk.stop))


def _raise_in_pool(chunk):
    """Module-level so ProcessPoolUnit can pickle it; always fails."""
    raise ValueError(f"pool boom at {chunk.start}")


# ---------------------------------------------------------------------------
# ProcessPoolUnit error paths (ISSUE 5 satellite): a raising work_fn must
# surface through the CompletionBus and fail parallel_for cleanly — never
# hang the dispatcher waiting on a completion that was swallowed
# ---------------------------------------------------------------------------
class TestProcessPoolErrors:
    def test_pool_exception_surfaces_on_the_bus(self):
        unit = ProcessPoolUnit("p0")
        bus = CompletionBus()
        unit.start(bus)
        try:
            unit.submit(Chunk(3, 7, "p0"), _raise_in_pool)
            assert bus.wait(timeout=60.0), "no completion posted for the error"
            recs = bus.drain()
            assert len(recs) == 1
            assert isinstance(recs[0].error, ValueError)
            assert "pool boom at 3" in str(recs[0].error)
            assert recs[0].result is None
        finally:
            unit.close()

    def test_pool_exception_fails_parallel_for_cleanly(self):
        rt = HeteroRuntime()
        rt.register_unit("p0", WorkerKind.CC, work_fn=_raise_in_pool,
                         backend="process")
        rt.register_unit("p1", WorkerKind.CC, work_fn=_raise_in_pool,
                         backend="process")
        with pytest.raises(ValueError, match="pool boom"):
            rt.parallel_for(num_items=64, engine="interrupt", acc_chunk=8)

    def test_pool_error_then_unit_still_usable(self):
        # an error completion must not wedge the pool: the same unit keeps
        # serving submissions afterwards
        unit = ProcessPoolUnit("p0")
        bus = CompletionBus()
        unit.start(bus)
        try:
            unit.submit(Chunk(0, 2, "p0"), _raise_in_pool)
            assert bus.wait(timeout=60.0)
            assert isinstance(bus.drain()[0].error, ValueError)
            unit.submit(Chunk(0, 4, "p0"), _sum_indices)
            assert bus.wait(timeout=60.0)
            rec = bus.drain()[0]
            assert rec.error is None and rec.result == sum(range(4))
        finally:
            unit.close()


# ---------------------------------------------------------------------------
# JaxDeviceUnit device binding: an index that does not exist is an error,
# never a silent wrap to device 0
# ---------------------------------------------------------------------------
class TestJaxDeviceIndex:
    def test_out_of_range_jax_index_raises(self):
        import jax

        n = len(jax.devices())
        with pytest.raises(ValueError, match=f"device index {n} does not exist"):
            make_backend(f"jax:{n}", "d0")
        rt = HeteroRuntime()
        with pytest.raises(ValueError, match="does not exist"):
            rt.register_unit("d0", WorkerKind.ACC, backend=f"jax:{n + 3}")
        with pytest.raises(ValueError, match="non-negative integer"):
            make_backend("jax:-1", "d0")


# ---------------------------------------------------------------------------
# JaxDeviceUnit starts the host copy of a chunk's jax.Array results when its
# waiter takes the chunk; the record counts the bytes it started
# ---------------------------------------------------------------------------
def _jax_results():
    """Work functions by result kind, and the bytes whose copy they start."""
    import jax.numpy as jnp
    import numpy as np

    a = jnp.arange(6, dtype=jnp.float32)
    return {
        "none": (lambda c: None, None),
        "numpy": (lambda c: np.ones(5, np.float32), None),
        "int-and-array": (lambda c: (c.start, a * 2), a.nbytes),
        "array": (lambda c: a + c.start, a.nbytes),
        "nested": (lambda c: {"x": a, "y": [a[:2], 3.0]}, a.nbytes + 8),
    }


class TestJaxHostCopy:
    def _record(self, work_fn):
        bus = CompletionBus()
        unit = JaxDeviceUnit("d0")
        unit.start(bus)
        try:
            unit.submit(Chunk(0, 4, "d0"), work_fn)
            assert bus.wait(timeout=10.0)
            (rec,) = bus.drain()
            return rec
        finally:
            unit.close()

    @pytest.mark.parametrize("kind", sorted(_jax_results()))
    def test_counts_only_the_array_leaves(self, kind):
        work_fn, nbytes = _jax_results()[kind]
        rec = self._record(work_fn)
        assert rec.error is None
        assert rec.host_copy_bytes == nbytes

    def test_a_raising_work_function_surfaces_and_counts_nothing(self):
        def boom(c):
            raise RuntimeError("kaput")

        rec = self._record(boom)
        assert isinstance(rec.error, RuntimeError)
        assert rec.host_copy_bytes is None and rec.result is None
        rt = HeteroRuntime()
        rt.register_unit("d0", WorkerKind.ACC, backend="jax", work_fn=boom)
        with pytest.raises(RuntimeError, match="kaput"):
            rt.parallel_for(num_items=16, engine="interrupt", acc_chunk=4)

    def test_results_are_bitwise_those_of_a_waiter_that_copies_nothing(
            self, monkeypatch):
        import jax
        import jax.numpy as jnp
        import numpy as np

        f = jax.jit(lambda x, s: jnp.sin(x * s) @ x.T)
        x = jax.random.normal(jax.random.key(7), (64, 64), jnp.float32)

        def run():
            rt = HeteroRuntime()
            out = {}

            def work(c):
                out[c.start] = f(x, jnp.float32(c.start + 1))
                return out[c.start]
            for i in range(2):
                rt.register_unit(f"d{i}", WorkerKind.ACC, backend="jax",
                                 work_fn=work)
            rep = rt.parallel_for(num_items=64, engine="interrupt", acc_chunk=8)
            return rep, {s: np.asarray(v) for s, v in out.items()}

        rep, copied = run()
        assert sum(rep.per_worker_host_copy_bytes.values()) == 8 * 64 * 64 * 4
        monkeypatch.setattr(JaxDeviceUnit, "_start_host_copy",
                            lambda self, out: None)
        rep_plain, plain = run()
        assert rep_plain.per_worker_host_copy_bytes is None
        assert sorted(copied) == sorted(plain) == list(range(0, 64, 8))
        for s in plain:
            assert copied[s].tobytes() == plain[s].tobytes(), s


# ---------------------------------------------------------------------------
# make_backend negatives (ISSUE 5 satellite): an unknown spec must teach
# the caller every valid spec, including the remote: form
# ---------------------------------------------------------------------------
class TestBackendSpecErrors:
    @pytest.mark.parametrize("bad", ["gpu-go-brrr", "remote", "threadz", ""])
    def test_unknown_spec_lists_all_valid_specs(self, bad):
        with pytest.raises(ValueError, match="unknown backend") as ei:
            make_backend(bad, "u0")
        message = str(ei.value)
        for expected in ("'inline'", "'thread'/'threads'",
                         "'process'/'processes'", "'jax'",
                         "'remote:<host:port>'", "BackendUnit instance"):
            assert expected in message, f"error does not teach {expected}"

    def test_register_unit_propagates_the_listing(self):
        rt = HeteroRuntime()
        with pytest.raises(ValueError, match="remote:<host:port>"):
            rt.register_unit("a", WorkerKind.CC, backend="gpu-go-brrr")


# ---------------------------------------------------------------------------
# CompletionBus under concurrent posters (ISSUE 5 satellite): N producer
# threads x M records each — no record lost, none duplicated, regardless
# of how posts interleave with waits/drains
# ---------------------------------------------------------------------------
class TestCompletionBusProperty:
    @given(n_threads=st.integers(2, 6), per_thread=st.integers(5, 40),
           seed=st.integers(0, 10**6))
    @settings(max_examples=25, deadline=None)
    def test_no_lost_or_duplicated_records(self, n_threads, per_thread, seed):
        import random

        bus = CompletionBus()
        barrier = threading.Barrier(n_threads)

        def producer(t):
            rng = random.Random(seed * 1009 + t)
            barrier.wait()
            for k in range(per_thread):
                if rng.random() < 0.25:
                    time.sleep(rng.uniform(0.0, 1e-4))
                bus.post(CompletionRecord(
                    unit=f"u{t}", chunk=Chunk(k, k + 1, f"u{t}"),
                    elapsed=0.0, dispatch_latency=0.0,
                ))

        threads = [threading.Thread(target=producer, args=(t,), daemon=True)
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        total = n_threads * per_thread
        collected = []
        deadline = time.perf_counter() + 30.0
        while len(collected) < total and time.perf_counter() < deadline:
            bus.wait(timeout=1.0)
            collected.extend(bus.drain())
        for t in threads:
            t.join(timeout=10.0)
        collected.extend(bus.drain())
        assert len(collected) == total
        tally = Counter((r.unit, r.chunk.start) for r in collected)
        assert all(c == 1 for c in tally.values()), (
            f"duplicated records: {[k for k, c in tally.items() if c != 1]}"
        )
        assert set(tally) == {(f"u{t}", k)
                              for t in range(n_threads)
                              for k in range(per_thread)}


# ---------------------------------------------------------------------------
# sharded CompletionBus (ISSUE 8): per-unit slots + a single notify event
# replace the global-lock scan; same API, so the contracts get harder —
# N producers x M registered slots, and wait() may never miss a notify
# ---------------------------------------------------------------------------
class TestCompletionBusSharded:
    @given(n_threads=st.integers(2, 8), n_units=st.integers(1, 5),
           per_thread=st.integers(10, 60), seed=st.integers(0, 10**6))
    @settings(max_examples=15, deadline=None)
    def test_n_producers_m_unit_slots_no_loss(self, n_threads, n_units,
                                              per_thread, seed):
        import random

        bus = CompletionBus()
        for m in range(n_units):
            bus.register(f"u{m}")  # dedicated slots (the fast path)
        # one unregistered unit exercises the default slot alongside them
        names = [f"u{m}" for m in range(n_units)] + ["ghost"]
        barrier = threading.Barrier(n_threads)

        def producer(t):
            rng = random.Random(seed * 7919 + t)
            barrier.wait()
            for k in range(per_thread):
                if rng.random() < 0.2:
                    time.sleep(rng.uniform(0.0, 1e-4))
                unit = names[rng.randrange(len(names))]
                bus.post(CompletionRecord(
                    unit=unit, chunk=Chunk(t * per_thread + k,
                                           t * per_thread + k + 1, unit),
                    elapsed=0.0, dispatch_latency=0.0,
                ))

        producers = [threading.Thread(target=producer, args=(t,), daemon=True)
                     for t in range(n_threads)]
        collected, clock = [], threading.Lock()
        stop = threading.Event()

        def consumer():
            while not stop.is_set():
                bus.wait(timeout=0.2)
                got = bus.drain()
                if got:
                    with clock:
                        collected.extend(got)

        consumers = [threading.Thread(target=consumer, daemon=True)
                     for _ in range(2)]
        for t in producers + consumers:
            t.start()
        for t in producers:
            t.join(timeout=30.0)
        total = n_threads * per_thread
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline:
            with clock:
                if len(collected) >= total:
                    break
            time.sleep(1e-3)
        stop.set()
        for t in consumers:
            t.join(timeout=10.0)
        collected.extend(bus.drain())
        assert len(collected) == total
        tally = Counter(r.chunk.start for r in collected)
        dupes = {k for k, c in tally.items() if c != 1}
        assert not dupes, f"lost or duplicated completions: {sorted(dupes)}"
        assert set(tally) == set(range(total))

    def test_wait_never_misses_a_notify_ping_pong(self):
        # strict alternation: every post must wake exactly one wait();
        # a lost wakeup shows up as a timed-out round
        bus = CompletionBus()
        bus.register("u0")
        ack = threading.Event()
        rounds = 400

        def producer():
            for k in range(rounds):
                bus.post(CompletionRecord(
                    unit="u0", chunk=Chunk(k, k + 1, "u0"),
                    elapsed=0.0, dispatch_latency=0.0,
                ))
                assert ack.wait(timeout=10.0)
                ack.clear()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        seen = 0
        for _ in range(rounds):
            assert bus.wait(timeout=10.0), (
                f"wait() missed the notify after {seen} records")
            got = bus.drain()
            assert len(got) == 1
            assert got[0].chunk.start == seen
            seen += 1
            ack.set()
        t.join(timeout=10.0)
        assert seen == rounds

    def test_register_is_idempotent_and_preserves_queued_records(self):
        bus = CompletionBus()
        bus.post(CompletionRecord(unit="u0", chunk=Chunk(0, 1, "u0"),
                                  elapsed=0.0, dispatch_latency=0.0))
        bus.register("u0")
        bus.register("u0")
        bus.post(CompletionRecord(unit="u0", chunk=Chunk(1, 2, "u0"),
                                  elapsed=0.0, dispatch_latency=0.0))
        got = bus.drain()
        assert sorted(r.chunk.start for r in got) == [0, 1]


# ---------------------------------------------------------------------------
# engine pipelining (ISSUE 8): a unit advertising capacity > 1 gets that
# many chunks in flight before the per-dispatch flush() fires
# ---------------------------------------------------------------------------
class BatchingProbeUnit(backends_mod.BackendUnit):
    """Pipelined fake: buffers submits, executes on flush, records depths."""

    def __init__(self, name, capacity):
        super().__init__(name)
        self.capacity = capacity
        self._buf = []
        self.flush_batches = []

    def submit(self, chunk, work_fn):
        self._buf.append((chunk, work_fn, time.perf_counter()))

    def flush(self):
        batch, self._buf = self._buf, []
        if not batch:
            return
        self.flush_batches.append(len(batch))
        for chunk, fn, t0 in batch:
            self._execute(chunk, fn, t0)


class TestEnginePipelining:
    def _run(self, capacity, n_items=64, acc_chunk=4):
        rec = Recorder()
        rt = HeteroRuntime()
        probe = BatchingProbeUnit("b0", capacity=capacity)
        rt.register_unit("b0", WorkerKind.CC, work_fn=rec, backend=probe)
        rep = rt.parallel_for(num_items=n_items, policy="multidynamic",
                              engine="interrupt", acc_chunk=acc_chunk)
        return rep, rec, probe

    def test_capacity_fills_before_flush(self):
        rep, rec, probe = self._run(capacity=4)
        assert rep.items == 64
        assert_exact_tiling(rep.coverage, 64)
        rec.assert_exactly_once(64)
        assert sum(probe.flush_batches) == rep.chunks  # all went via flush
        assert max(probe.flush_batches) >= 2, (
            "engine never pipelined past one in-flight chunk "
            f"(flush depths: {probe.flush_batches})")
        assert probe.flush_batches[0] == 4, (
            "first dispatch must fill the advertised capacity")

    def test_capacity_one_keeps_strict_alternation(self):
        rep, rec, probe = self._run(capacity=1)
        assert rep.items == 64
        rec.assert_exactly_once(64)
        assert probe.flush_batches == [1] * rep.chunks


# ---------------------------------------------------------------------------
# the event-driven engine through parallel_for
# ---------------------------------------------------------------------------
def make_wall_runtime(work_fn, n_units=3, backend=None):
    rt = HeteroRuntime()
    for i in range(n_units):
        rt.register_unit(f"cc{i}", WorkerKind.CC, work_fn=work_fn,
                         backend=backend)
    return rt


class TestWallEngine:
    def test_three_thread_units_cover_exactly_once(self):
        rec = Recorder(per_item_sleep=2e-5)
        rep = make_wall_runtime(rec).parallel_for(
            num_items=400, policy="multidynamic", engine="interrupt",
            acc_chunk=16,
        )
        assert rep.items == 400
        assert_exact_tiling(rep.coverage, 400)
        rec.assert_exactly_once(400)
        # every unit got work and the backend layer measured dispatch
        assert all(v > 0 for v in rep.per_worker_items.values())
        assert set(rep.dispatch_latency) == set(rep.per_worker_items)
        assert all(v >= 0 for v in rep.dispatch_latency.values())

    def test_work_overlaps_on_real_threads(self):
        # "threads": the four units' chunks (static policy: one each) meet
        # at a barrier, which only passes if all four run at once, and the
        # timeline's stamps share an instant.  "inline" (same engine, work
        # on the dispatcher thread) is the control: no two chunks overlap.
        barrier = threading.Barrier(4, timeout=30)
        rec = Recorder()

        def meet(chunk):
            barrier.wait()
            rec(chunk)

        rep = make_wall_runtime(meet, n_units=4, backend="threads").parallel_for(
            num_items=600, policy="static", engine="interrupt",
        )
        rec.assert_exactly_once(600)
        spans = rep.timeline.chunks
        assert len(spans) == 4
        assert max(c.enqueued for c in spans) < min(c.ready for c in spans)

        rec = Recorder(per_item_sleep=1e-5)
        rep = make_wall_runtime(rec, n_units=4, backend="inline").parallel_for(
            num_items=600, policy="static", engine="interrupt",
        )
        rec.assert_exactly_once(600)
        spans = sorted(rep.timeline.chunks, key=lambda c: c.enqueued)
        assert len(spans) == 4
        for a, b in zip(spans, spans[1:]):
            assert a.ready <= b.enqueued, (a, b)

    def test_error_in_work_fn_propagates(self):
        def boom(c):
            raise ValueError("chunk exploded")

        with pytest.raises(ValueError, match="chunk exploded"):
            make_wall_runtime(boom).parallel_for(
                num_items=100, engine="interrupt", acc_chunk=8
            )

    @pytest.mark.parametrize("policy", POLICIES)
    def test_every_policy_exact_once_on_threads(self, policy):
        rec = Recorder(per_item_sleep=1e-5)
        rep = make_wall_runtime(rec).parallel_for(
            num_items=331, policy=policy, engine="interrupt", acc_chunk=16,
        )
        assert rep.items == 331
        assert_exact_tiling(rep.coverage, 331)
        rec.assert_exactly_once(331)

    def test_process_backend_through_parallel_for(self):
        rt = HeteroRuntime()
        rt.register_unit("p0", WorkerKind.CC, work_fn=_sum_indices,
                         backend="process")
        rt.register_unit("p1", WorkerKind.CC, work_fn=_sum_indices,
                         backend="process")
        rep = rt.parallel_for(num_items=64, engine="interrupt", acc_chunk=8)
        assert rep.items == 64
        assert_exact_tiling(rep.coverage, 64)

    def test_sharded_wall_run_with_placement(self):
        rec = Recorder(per_item_sleep=1e-5)
        rt = HeteroRuntime()
        for i in range(2):
            rt.register_unit(f"acc{i}", WorkerKind.ACC, work_fn=rec)
            rt.register_unit(f"cc{i}", WorkerKind.CC, work_fn=rec)
        sp = ShardedSpace(300, 2, placement={"acc0": 0, "acc1": 1})
        rep = rt.parallel_for(space=sp, policy="multidynamic",
                              engine="interrupt", acc_chunk=16)
        assert rep.items == 300
        assert_exact_tiling(rep.coverage, 300)
        rec.assert_exactly_once(300)
        # pinned units appear only on their shard; cc units replicate
        keys = set(rep.per_worker_items)
        assert "s0/acc0" in keys and "s1/acc1" in keys
        assert "s1/acc0" not in keys and "s0/acc1" not in keys
        assert {"s0/cc0", "s0/cc1", "s1/cc0", "s1/cc1"} <= keys

    def test_placement_validation(self):
        with pytest.raises(ValueError, match="nonexistent"):
            ShardedSpace(100, 2, placement={"acc0": 5})
        rt = HeteroRuntime()
        rt.register_unit("a", WorkerKind.ACC, work_fn=lambda c: None)
        with pytest.raises(ValueError, match="unknown units"):
            rt.parallel_for(space=ShardedSpace(100, 2,
                                               placement={"ghost": 0}),
                            engine="inline")
        # a placement that strands a shard with no units is rejected
        with pytest.raises(ValueError, match="without any units"):
            rt.parallel_for(space=ShardedSpace(100, 2, placement={"a": 0}),
                            engine="inline")


# ---------------------------------------------------------------------------
# WallClock elasticity: thread-safe membership in the event engine
# ---------------------------------------------------------------------------
class TestWallElastic:
    def test_leave_and_join_exact_once(self):
        rec = Recorder(per_item_sleep=5e-5)
        rep = make_wall_runtime(rec).parallel_for(
            rec, num_items=400, policy="multidynamic", engine="interrupt",
            acc_chunk=8,
            elastic=(ElasticSchedule()
                     .leave(0.002, "cc0")
                     .join(0.004, "cc_new", kind="cc")),
        )
        assert rep.items == 400
        assert_exact_tiling(rep.coverage, 400)
        rec.assert_exactly_once(400)
        assert [e["action"] for e in rep.events] == ["leave", "join"]
        assert rep.per_worker_items["cc_new"] > 0
        # retired unit stopped early: it did less than the survivors
        assert (rep.per_worker_items["cc0"]
                < max(rep.per_worker_items.values()))

    def test_leave_retires_but_inflight_chunk_counts(self):
        # wall-clock semantics: real work cannot be recalled — the leave
        # event is recorded with requeued=None and coverage stays exact
        rec = Recorder(per_item_sleep=2e-4)
        rep = make_wall_runtime(rec).parallel_for(
            num_items=120, policy="multidynamic", engine="interrupt",
            acc_chunk=4, elastic=ElasticSchedule().leave(0.003, "cc1"),
        )
        assert rep.items == 120
        rec.assert_exactly_once(120)
        assert rep.events[0]["requeued"] is None

    def test_presplit_leftovers_requeued_to_survivors(self):
        # a leave due at t=0 lands before the unit's first dispatch, so its
        # entire never-issued static assignment must travel through the
        # requeue buffer to the survivors — the exact-once requeue path
        # under real concurrency
        rec = Recorder(per_item_sleep=2e-4)
        rep = make_wall_runtime(rec).parallel_for(
            num_items=300, policy="static", engine="interrupt",
            elastic=ElasticSchedule().leave(0.0, "cc2"),
        )
        assert rep.items == 300
        assert_exact_tiling(rep.coverage, 300)
        rec.assert_exactly_once(300)
        assert rep.per_worker_items["cc2"] == 0  # never dispatched
        survivors = {"cc0", "cc1"}
        assert sum(rep.per_worker_items[u] for u in survivors) == 300

    def test_all_units_leave_raises_stall(self):
        rec = Recorder(per_item_sleep=1e-3)
        with pytest.raises(RuntimeError, match="stalled"):
            make_wall_runtime(rec, n_units=2).parallel_for(
                num_items=500, policy="multidynamic", engine="interrupt",
                acc_chunk=4,
                elastic=ElasticSchedule().leave(0.004, "cc0").leave(0.004, "cc1"),
            )

    def test_rescue_join_after_total_departure(self):
        rec = Recorder(per_item_sleep=1e-4)
        rep = make_wall_runtime(rec, n_units=2).parallel_for(
            rec, num_items=100, policy="multidynamic", engine="interrupt",
            acc_chunk=4,
            elastic=(ElasticSchedule()
                     .leave(0.002, "cc0").leave(0.002, "cc1")
                     .join(0.01, "fresh", kind="cc")),
        )
        assert rep.items == 100
        rec.assert_exactly_once(100)
        assert rep.per_worker_items["fresh"] > 0

    def test_late_events_are_dropped(self):
        rec = Recorder()
        rep = make_wall_runtime(rec).parallel_for(
            num_items=60, policy="multidynamic", engine="interrupt",
            acc_chunk=8, elastic=ElasticSchedule().leave(30.0, "cc0"),
        )
        assert rep.items == 60
        assert not rep.events
        # and, critically, the run did not wait 30 seconds for the event
        # (parallel_for returned — reaching this line is the assertion)

    def test_events_are_monotone_and_run_relative(self):
        rec = Recorder(per_item_sleep=1e-4)
        sched = (ElasticSchedule()
                 .leave(0.002, "cc0")
                 .join(0.004, "j0", kind="cc")
                 .leave(0.006, "cc1")
                 .join(0.008, "j1", kind="cc"))
        rep = make_wall_runtime(rec, n_units=4).parallel_for(
            rec, num_items=600, policy="multidynamic", engine="interrupt",
            acc_chunk=8, elastic=sched,
        )
        times = [e["t"] for e in rep.events]
        assert times == sorted(times), "events not monotone in time"
        assert all(0.0 <= t <= rep.makespan + 0.5 for t in times)
        assert [e["unit"] for e in rep.events] == ["cc0", "j0", "cc1", "j1"]


# ---------------------------------------------------------------------------
# the randomized concurrency battery (the ISSUE's headline)
# ---------------------------------------------------------------------------
def random_elastic_battery(seed, n_items_max, sleep_scale):
    """One randomized WallClock elastic run; returns (report, recorder, n)."""
    import random

    rng = random.Random(seed)
    n_units = rng.randint(3, 5)
    n_items = rng.randint(60, n_items_max)
    acc_chunk = rng.choice([2, 4, 8, 16, 32])
    policy = POLICIES[rng.randrange(3)]
    rec = Recorder(per_item_sleep=rng.uniform(0.5, 2.0) * sleep_scale)
    rt = make_wall_runtime(rec, n_units=n_units)

    sched = ElasticSchedule()
    # leave at most n_units - 1 so the run can always finish (joins may
    # rescue, but must not be required to)
    for i, unit in enumerate(rng.sample(range(n_units), rng.randint(0, n_units - 1))):
        sched.leave(rng.uniform(0.0, 0.02), f"cc{unit}")
    for j in range(rng.randint(0, 2)):
        sched.join(rng.uniform(0.0, 0.03), f"joiner{j}", kind="cc")

    rep = rt.parallel_for(
        rec, num_items=n_items, policy=policy, engine="interrupt",
        acc_chunk=acc_chunk, elastic=sched,
    )
    return rep, rec, n_items


class TestConcurrencyBattery:
    """≥20 random WallClock elastic schedules: zero lost/duplicated items."""

    @given(seed=st.integers(0, 10**6))
    @settings(max_examples=20, deadline=None)
    def test_exact_once_under_random_churn(self, seed):
        rep, rec, n_items = random_elastic_battery(
            seed, n_items_max=200, sleep_scale=2e-5
        )
        assert rep.items == n_items
        assert rep.chunks == len(rep.coverage)
        assert_exact_tiling(rep.coverage, n_items)
        rec.assert_exactly_once(n_items)
        times = [e["t"] for e in (rep.events or [])]
        assert times == sorted(times), "events not monotone"

    @pytest.mark.slow
    @given(seed=st.integers(0, 10**6))
    @settings(max_examples=30, deadline=None)
    def test_exact_once_under_random_churn_heavy(self, seed):
        rep, rec, n_items = random_elastic_battery(
            seed + 7_777_777, n_items_max=1200, sleep_scale=5e-5
        )
        assert rep.items == n_items
        assert_exact_tiling(rep.coverage, n_items)
        rec.assert_exactly_once(n_items)
        times = [e["t"] for e in (rep.events or [])]
        assert times == sorted(times)


# ---------------------------------------------------------------------------
# kernels through the runtime: bit-exact under real-thread dispatch
# ---------------------------------------------------------------------------
class TestKernelRuntimeParity:
    def test_spmm_tiles_bit_exact_through_threads(self):
        np = pytest.importorskip("numpy")
        jnp = pytest.importorskip("jax.numpy")
        from repro.kernels.spmm.ref import make_problem, spmm_ell_ref

        R, C, N = 64, 96, 16
        p = make_problem(R, C, N, nnz_mean=6.0, seed=3)
        vals, cols, rhs = (jnp.asarray(p.vals), jnp.asarray(p.cols),
                           jnp.asarray(p.rhs))
        expect = np.asarray(spmm_ell_ref(vals, cols, rhs))

        space = TiledSpace(grid=(R, N), tile=(8, N))  # one tile = 8 rows
        out = np.zeros((R, N), np.float32)

        def work(chunk):
            for rs, _cs in space.chunk_slices(chunk):
                out[rs] = np.asarray(
                    spmm_ell_ref(vals[rs], cols[rs], rhs)
                )  # disjoint row bands: thread writes cannot collide

        rt = HeteroRuntime()
        for i in range(3):
            rt.register_unit(f"cc{i}", WorkerKind.CC, work_fn=work)
        rep = rt.parallel_for(space=space, policy="multidynamic",
                              engine="interrupt", acc_chunk=2,
                              backend="threads")
        assert rep.items == space.num_items
        assert_exact_tiling(rep.coverage, space.num_items)
        assert np.array_equal(out, expect), "thread dispatch corrupted tiles"

    def test_hotspot_tiles_bit_exact_through_threads(self):
        np = pytest.importorskip("numpy")
        jnp = pytest.importorskip("jax.numpy")
        from repro.configs.paper_eneac import HotspotConfig
        from repro.kernels.hotspot.ops import hotspot_step_banded
        from repro.kernels.hotspot.ref import hotspot_step_ref

        R = C = 64
        band = 8
        cfg = HotspotConfig(grid=R, iterations=1)
        rng = np.random.default_rng(0)
        t = jnp.asarray(80.0 + 10 * rng.random((R, C), np.float32))
        pw = jnp.asarray(rng.random((R, C), np.float32))
        expect = np.asarray(hotspot_step_ref(t, pw, cfg))

        space = TiledSpace(grid=(R, C), tile=(band, C))
        out = np.zeros((R, C), np.float32)

        def work(chunk):
            for rs, _cs in space.chunk_slices(chunk):
                lo = max(rs.start - 1, 0)     # one halo row each side
                hi = min(rs.stop + 1, R)
                res = np.asarray(
                    hotspot_step_banded(t[lo:hi], pw[lo:hi], cfg, (R, C))
                )
                out[rs] = res[rs.start - lo: rs.start - lo + (rs.stop - rs.start)]

        rt = HeteroRuntime()
        for i in range(3):
            rt.register_unit(f"cc{i}", WorkerKind.CC, work_fn=work)
        rep = rt.parallel_for(space=space, policy="multidynamic",
                              engine="interrupt", acc_chunk=2,
                              backend="threads")
        assert rep.items == space.num_items
        assert np.array_equal(out, expect), "banded stencil diverged from ref"


# ---------------------------------------------------------------------------
# kept units: a runtime keeps its in-process units started between runs
# ---------------------------------------------------------------------------
def _unit_threads(prefix):
    """Live ``eneac-unit-*`` / ``eneac-jaxwait-*`` threads of units whose
    names start with ``prefix``, by thread name."""
    return {t.name: t for t in threading.enumerate()
            if t.name.startswith((f"eneac-unit-{prefix}",
                                  f"eneac-jaxwait-{prefix}"))}


def _wait_for_no_threads(prefix, timeout=10.0):
    deadline = time.perf_counter() + timeout
    while _unit_threads(prefix) and time.perf_counter() < deadline:
        time.sleep(0.01)
    return _unit_threads(prefix)


def _prefix():
    import uuid

    return f"k{uuid.uuid4().hex[:8]}-"


class TestKeptUnits:
    N = 96

    def _mixed_runtime(self, prefix, cc_work=lambda chunk: None):
        """4 ``jax:0`` ACC units and 4 ``"thread"`` CC units."""
        import jax.numpy as jnp

        x = jnp.ones((8, 8), jnp.float32)
        rt = HeteroRuntime()
        for i in range(4):
            rt.register_unit(f"{prefix}acc{i}", WorkerKind.ACC, backend="jax:0",
                             work_fn=lambda c: x * c.start)
            rt.register_unit(f"{prefix}cc{i}", WorkerKind.CC, backend="thread",
                             work_fn=cc_work)
        return rt

    def test_consecutive_runs_reuse_each_units_thread(self):
        p = _prefix()
        run = [0]
        idents = {}   # (run, unit) -> threads that ran the unit's chunks

        def cc_work(chunk):
            idents.setdefault((run[0], chunk.worker), set()).add(threading.get_ident())

        rt = self._mixed_runtime(p, cc_work)
        counts, threads = [], []
        for run[0] in range(2):
            rep = rt.parallel_for(num_items=self.N, acc_chunk=8)
            assert_exact_tiling(rep.coverage, self.N)
            counts.append((rep.timeline.units_started, rep.timeline.units_kept))
            threads.append(_unit_threads(p))
        assert counts == [(8, 0), (0, 8)]
        # every unit keeps its one thread: the same Thread objects, alive
        assert len(threads[0]) == 8 and threads[0] == threads[1]
        assert all(t.is_alive() for t in threads[1].values())
        assert {u for _, u in idents} == {f"{p}cc{i}" for i in range(4)}
        for (_, unit), ids in idents.items():
            assert ids == {threads[1][f"eneac-unit-{unit}"].ident}, unit
        rt.close()

    @pytest.mark.parametrize("how", ["close", "with", "gc"])
    def test_threads_end_when_the_runtime_ends(self, how):
        import gc

        p = _prefix()
        if how == "with":
            with self._mixed_runtime(p) as rt:
                rt.parallel_for(num_items=self.N, acc_chunk=8)
                assert len(_unit_threads(p)) == 8
        else:
            rt = self._mixed_runtime(p)
            rt.parallel_for(num_items=self.N, acc_chunk=8)
            assert len(_unit_threads(p)) == 8
            if how == "close":
                rt.close()
            else:
                del rt
                gc.collect()
        assert not _wait_for_no_threads(p)

    def test_runtime_closes_and_restarts(self):
        p = _prefix()
        rt = self._mixed_runtime(p)
        rt.parallel_for(num_items=self.N, acc_chunk=8)
        rt.close()
        assert not _unit_threads(p)
        rep = rt.parallel_for(num_items=self.N, acc_chunk=8)
        assert_exact_tiling(rep.coverage, self.N)
        assert (rep.timeline.units_started, rep.timeline.units_kept) == (8, 0)
        rt.close()

    def test_dropped_runtimes_leave_no_threads(self):
        p = _prefix()
        for _ in range(50):
            rt = HeteroRuntime()
            for i in range(2):
                rt.register_unit(f"{p}cc{i}", WorkerKind.CC, backend="thread",
                                 work_fn=lambda c: None)
            rt.parallel_for(num_items=16, acc_chunk=4)
            del rt
        deadline = time.perf_counter() + 10.0
        while len(_unit_threads(p)) > 2 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert len(_unit_threads(p)) <= 2

    @pytest.mark.parametrize("how", ["leave", "straggler", "deregister",
                                     "deregister_mid_run"])
    def test_a_departed_unit_is_closed_and_rebuilt(self, how):
        from repro.core.straggler import StragglerDetector

        p = _prefix()
        names = [f"{p}u{i}" for i in range(3)]
        gone = names[2]
        slow = {"on": how == "straggler", "n": 0}
        lock = threading.Lock()
        mid_run = {"armed": how == "deregister_mid_run", "spec": None}

        def make_work(rec):
            def work(chunk):
                if mid_run["armed"] and chunk.start == 0:
                    # a registry change during a run only affects later runs
                    mid_run["armed"] = False
                    mid_run["spec"] = rt.deregister_unit(gone)
                per_item = 3e-4
                if slow["on"] and chunk.worker == gone:
                    with lock:
                        slow["n"] += 1
                        if slow["n"] > 2:
                            per_item = 3e-3
                time.sleep(per_item * chunk.size)
                rec(chunk)
            return work

        rt = HeteroRuntime()
        for n in names:
            rt.register_unit(n, WorkerKind.CC, backend="thread")
        n_items = 960
        kw = dict(num_items=n_items, policy="multidynamic", acc_chunk=8,
                  scheduler_kwargs=dict(min_cc_chunk=8, max_cc_chunk=8))
        recs, reps = [], []
        for k in range(3):
            rec = Recorder()
            extra = {}
            if k == 0 and how == "leave":
                extra["elastic"] = ElasticSchedule().leave(0.005, gone)
            if k == 0 and how == "straggler":
                extra["straggler"] = StragglerDetector(alpha=0.6, threshold=4.0,
                                                       patience=3)
            reps.append(rt.parallel_for(make_work(rec), **kw, **extra))
            recs.append(rec)
            if k == 0:
                slow["on"] = False
                spec = rt.deregister_unit(gone) if how == "deregister" else mid_run["spec"]
                if spec is not None:
                    rt.register_unit(gone, spec.kind, backend=spec.backend)
                # the departed unit's thread was joined; the others run on
                assert set(_unit_threads(p)) == {f"eneac-unit-{n}" for n in names[:2]}
        if how in ("leave", "straggler"):
            assert [e["action"] for e in reps[0].events] == [how]
            assert reps[0].events[0]["unit"] == gone
        for rep, rec in zip(reps, recs):
            assert_exact_tiling(rep.coverage, n_items)
            rec.assert_exactly_once(n_items)
        counts = [(r.timeline.units_started, r.timeline.units_kept) for r in reps]
        assert counts == [(3, 0), (1, 2), (0, 3)]
        assert reps[1].per_worker_items[gone] > 0
        rt.close()

    def test_a_work_fn_error_leaves_the_next_run_whole(self):
        p = _prefix()
        rt = self._mixed_runtime(p)
        fail = {"on": True}

        def work(chunk):
            if fail["on"] and chunk.start <= 40 < chunk.stop:
                raise ValueError("chunk at 40")

        with pytest.raises(ValueError, match="chunk at 40"):
            rt.parallel_for(work, num_items=self.N, acc_chunk=8)
        fail["on"] = False
        rec = Recorder()
        rep = rt.parallel_for(rec, num_items=self.N, acc_chunk=8)
        assert_exact_tiling(rep.coverage, self.N)
        rec.assert_exactly_once(self.N)
        assert (rep.timeline.units_started, rep.timeline.units_kept) == (0, 8)
        rt.close()

    def test_a_nested_run_builds_its_own_units(self):
        p = _prefix()
        rt = HeteroRuntime()
        for i in range(2):
            rt.register_unit(f"{p}cc{i}", WorkerKind.CC, backend="thread")
        inner = []

        def outer_work(chunk):
            if chunk.start == 0:   # the pool is held by the outer run
                rec = Recorder()
                inner.append((rt.parallel_for(rec, num_items=16, acc_chunk=4), rec))

        rt.parallel_for(outer_work, num_items=32, acc_chunk=4)
        (rep, rec), = inner
        rec.assert_exactly_once(16)
        assert (rep.timeline.units_started, rep.timeline.units_kept) == (2, 0)
        rep2 = rt.parallel_for(lambda c: None, num_items=32, acc_chunk=4)
        assert (rep2.timeline.units_started, rep2.timeline.units_kept) == (0, 2)
        assert len(_unit_threads(p)) == 2
        rt.close()

    @pytest.mark.parametrize("case", ["call_backend", "process"])
    def test_per_run_units_start_and_close_every_run(self, case):
        p = _prefix()
        rt = HeteroRuntime()
        if case == "process":
            unit = ProcessPoolUnit(f"{p}p0")
            rt.register_unit(unit.name, WorkerKind.CC, work_fn=_sum_indices,
                             backend=unit)
            n_units, kw = 1, {}
        else:
            for i in range(3):
                rt.register_unit(f"{p}cc{i}", WorkerKind.CC, work_fn=_sum_indices)
            n_units, kw = 3, {"backend": "thread"}
        for _ in range(2):
            rep = rt.parallel_for(num_items=32, acc_chunk=8, **kw)
            assert_exact_tiling(rep.coverage, 32)
            assert (rep.timeline.units_started, rep.timeline.units_kept) == (n_units, 0)
            assert not _unit_threads(p)
            if case == "process":
                assert not unit.running and unit._pool is None

    @pytest.mark.parametrize("backend", ["jax:0", "thread"])
    def test_a_kept_unit_holds_no_reference_to_its_last_result(self, backend):
        import gc
        import weakref

        import jax.numpy as jnp

        x = jnp.ones((16, 16), jnp.float32)
        refs = []

        def work(chunk):
            out = x * chunk.start if backend == "jax:0" else Chunk(0, 1, "r")
            refs.append(weakref.ref(out))
            return out

        p = _prefix()
        rt = HeteroRuntime()
        rt.register_unit(f"{p}u0", WorkerKind.ACC, backend=backend, work_fn=work)
        rep = rt.parallel_for(num_items=32, acc_chunk=8)
        assert rep.items == 32 and len(refs) == 4
        assert len(_unit_threads(p)) == 1   # kept, idle
        deadline = time.perf_counter() + 10.0
        while any(r() is not None for r in refs) and time.perf_counter() < deadline:
            gc.collect()
            time.sleep(0.01)
        assert all(r() is None for r in refs)
        rt.close()
