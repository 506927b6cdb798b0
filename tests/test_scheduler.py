"""MultiDynamic scheduler: unit + property tests (paper §3.3 semantics)."""

import random
import threading
import time

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI container has no hypothesis; use the vendored shim
    from _propcheck import given, settings, strategies as st

from repro.core import (
    HeteroRuntime,
    MultiDynamicScheduler,
    OracleStaticScheduler,
    StaticScheduler,
    WallClock,
    WorkerKind,
)
from repro.core.scheduler import (
    THROUGHPUT_FLOOR,
    latency_aware_split,
    proportional_split,
)


def make_sched(n_items=500, acc_chunk=64, n_acc=2, n_cc=2, **kw):
    s = MultiDynamicScheduler(n_items, acc_chunk, **kw)
    for i in range(n_acc):
        s.add_worker(f"acc{i}", WorkerKind.ACC)
    for i in range(n_cc):
        s.add_worker(f"cc{i}", WorkerKind.CC)
    return s


class TestChunkIssue:
    def test_acc_chunk_is_user_size(self):
        s = make_sched(n_items=1000, acc_chunk=128)
        c = s.next_chunk("acc0")
        assert c.size == 128

    def test_cc_chunk_adapts_to_throughput_ratio(self):
        s = make_sched(n_items=100_000, acc_chunk=100)
        s.next_chunk("acc0")
        s.complete("acc0", 0.001)       # 100k items/s
        s.next_chunk("cc0")
        s.complete("cc0", 0.1)          # ~adaptive seed chunk
        # now cc throughput known; next cc chunk ≈ acc_chunk * t_cc/t_acc
        c = s.next_chunk("cc0")
        t_cc = s.workers["cc0"].throughput
        t_acc = s.workers["acc0"].throughput
        expected = 100 * t_cc / t_acc
        assert c.size <= max(2 * expected, s.min_cc_chunk * 2)

    def test_busy_worker_cannot_double_issue(self):
        s = make_sched()
        s.next_chunk("acc0")
        with pytest.raises(RuntimeError):
            s.next_chunk("acc0")

    def test_exhaustion_returns_none(self):
        s = make_sched(n_items=64, acc_chunk=64)
        assert s.next_chunk("acc0") is not None
        assert s.next_chunk("acc1") is None


class TestCoverage:
    @given(
        n_items=st.integers(1, 2000),
        acc_chunk=st.integers(1, 300),
        n_acc=st.integers(1, 4),
        n_cc=st.integers(0, 4),
        seed=st.integers(0, 10_000),
    )
    @settings(max_examples=60, deadline=None)
    def test_exact_disjoint_coverage(self, n_items, acc_chunk, n_acc, n_cc, seed):
        """Property: every index processed exactly once, none skipped —
        regardless of worker mix, chunk size, and completion order."""
        rng = random.Random(seed)
        s = MultiDynamicScheduler(n_items, acc_chunk)
        names = [f"acc{i}" for i in range(n_acc)] + [f"cc{i}" for i in range(n_cc)]
        for n in names:
            s.add_worker(n, WorkerKind.ACC if n.startswith("acc") else WorkerKind.CC)
        outstanding = {}
        while True:
            idle = [n for n in names if n not in outstanding]
            progressed = False
            for n in idle:
                c = s.next_chunk(n)
                if c is not None:
                    outstanding[n] = c
                    progressed = True
            if not outstanding:
                break
            done = rng.choice(list(outstanding))
            outstanding.pop(done)
            s.complete(done, rng.uniform(1e-4, 1e-2))
            if not progressed and not outstanding and s.issued >= n_items:
                break
        spans = s.coverage()
        assert spans[0][0] == 0
        assert spans[-1][1] == n_items
        for (a, b), (c, d) in zip(spans, spans[1:]):
            assert b == c, f"gap or overlap at {b}:{c}"

    def test_throughput_ewma_positive(self):
        s = make_sched()
        c = s.next_chunk("acc0")
        s.complete("acc0", 0.01)
        assert s.workers["acc0"].throughput == pytest.approx(c.size / 0.01)


class TestEngines:
    """The runtime's wall-clock engines over thread units with sleep work."""

    def _run(self, rates, engine, n_items=400, meet=None):
        # every unit sleeps chunk.size / rate per chunk and records the
        # indices it ran and the [start, end) of its work; a unit named in
        # ``meet`` first waits at its barrier, on its first chunk only
        lock = threading.Lock()
        ran, spans = [], []
        meet = dict(meet or {})
        rt = HeteroRuntime(clock=WallClock())

        def work(name, rate):
            def fn(chunk):
                barrier = meet.pop(name, None)
                if barrier is not None:
                    barrier.wait()
                t0 = time.perf_counter()
                time.sleep(chunk.size / rate)
                t1 = time.perf_counter()
                with lock:
                    ran.extend(chunk.indices())
                    spans.append((t0, t1))
            return fn

        for name, rate in rates.items():
            kind = WorkerKind.ACC if "acc" in name else WorkerKind.CC
            rt.register_unit(name, kind, work_fn=work(name, rate))
        rep = rt.parallel_for(num_items=n_items, policy="multidynamic",
                              engine=engine, acc_chunk=64)
        return rep, sorted(ran), sorted(spans)

    def test_async_engine_completes_all(self):
        rep, ran, _ = self._run({"acc0": 8e4, "acc1": 8e4, "cc0": 1e4},
                                "interrupt")
        assert rep.items == 400
        assert ran == list(range(400))
        assert rep.coverage[0][0] == 0 and rep.coverage[-1][1] == 400
        for (a, b), (c, d) in zip(rep.coverage, rep.coverage[1:]):
            assert b == c, f"gap or overlap at {b}:{c}"

    def test_interrupt_overlaps_units_polling_serialises(self):
        rates = {"acc0": 8e4, "acc1": 8e4, "cc0": 2e4, "cc1": 2e4}
        # interrupt: an ACC and a CC unit's first chunks meet at a barrier,
        # which can only pass if the two run at once
        barrier = threading.Barrier(2, timeout=30)
        rep, ran, _ = self._run(rates, "interrupt",
                                meet={"acc0": barrier, "cc0": barrier})
        assert rep.items == 400 and ran == list(range(400))
        # polling: one driver thread runs every chunk, so no two overlap
        rep, ran, spans = self._run(rates, "polling")
        assert rep.items == 400 and ran == list(range(400))
        assert len(spans) == rep.chunks
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert end <= start, (end, start)

    def test_work_distribution_favours_fast_units(self):
        rep, _, _ = self._run({"acc0": 1e5, "cc0": 1e4}, "interrupt")
        assert rep.per_worker_items["acc0"] > rep.per_worker_items["cc0"]


class TestBaselines:
    def test_static_even_split(self):
        s = StaticScheduler(100, ["a", "b", "c"])
        sizes = [s.next_chunk(w).size for w in ("a", "b", "c")]
        assert sum(sizes) == 100
        assert max(sizes) - min(sizes) <= 1

    def test_oracle_proportional(self):
        s = OracleStaticScheduler(100, {"fast": 9.0, "slow": 1.0})
        assert s.next_chunk("fast").size == 90
        assert s.next_chunk("slow").size == 10

    def test_oracle_accepts_overheads(self):
        # equal speeds, one unit pays per-chunk dispatch: the oracle's
        # pre-split shifts that unit's share of the line to the free ones
        s = OracleStaticScheduler(300, {"loc": 1000.0, "rem": 1000.0},
                                  overheads={"rem": 0.1})
        assert s.next_chunk("loc").size > s.next_chunk("rem").size


# ---------------------------------------------------------------------------
# latency-aware water-filling split (ISSUE 9 tentpole)
# ---------------------------------------------------------------------------
class TestLatencyAwareSplit:
    def test_zero_overhead_matches_proportional(self):
        tp = {"a": 3.0, "b": 1.0, "c": 2.0}
        assert latency_aware_split(600, tp) == proportional_split(600, tp)
        assert latency_aware_split(
            600, tp, {"a": 0.0, "b": 0.0, "c": 0.0}
        ) == proportional_split(600, tp)

    def test_high_overhead_unit_gets_smaller_share(self):
        # throughput-only would hand 100 items each; the remote unit pays
        # 0.04 s of dispatch = 40 items' worth at 1000 items/s, and the
        # water-fill splits that burden across the free units:
        # level = (300 + 1000*0.04) / 3000, shares {113.3, 113.3, 73.3}
        sizes = latency_aware_split(
            300, {"a": 1000.0, "b": 1000.0, "r": 1000.0}, {"r": 0.04})
        assert sizes == {"a": 113, "b": 113, "r": 74}

    def test_equalizes_predicted_completion(self):
        tp = {"a": 200.0, "b": 50.0}
        ov = {"a": 0.0, "b": 0.1}
        sizes = latency_aware_split(1000, tp, ov)
        assert sizes == {"a": 804, "b": 196}
        finish = {w: sizes[w] / tp[w] + ov[w] for w in tp}
        # predicted completion times agree to within one slow-unit item
        assert abs(finish["a"] - finish["b"]) <= 1.5 / min(tp.values())

    def test_dominated_unit_floors_at_one_item(self):
        # overhead past the water level excludes the unit from the fill;
        # the starvation floor still keeps it live with one item
        assert latency_aware_split(
            300, {"a": 10.0, "r": 10.0}, {"r": 1e6}) == {"a": 299, "r": 1}

    def test_fewer_items_than_units_starves_worst_unit(self):
        # no floor when the space cannot feed everyone: the highest-
        # overhead unit is the one that goes hungry
        sizes = latency_aware_split(
            2, {"a": 1.0, "b": 1.0, "c": 1.0}, {"c": 99.0})
        assert sizes == {"a": 1, "b": 1, "c": 0}

    def test_zero_items_and_negative(self):
        assert latency_aware_split(0, {"a": 1.0, "b": 2.0}) == {"a": 0, "b": 0}
        with pytest.raises(ValueError):
            latency_aware_split(-1, {"a": 1.0})
        with pytest.raises(ValueError):
            latency_aware_split(10, {})
        with pytest.raises(ValueError):
            latency_aware_split(10, {"a": 0.0})

    def test_proportional_starvation_floor(self):
        # regression: round(10 * 0.001/100.001) == 0 used to starve "b"
        # even though it has positive throughput and the space has room
        assert proportional_split(10, {"a": 100.0, "b": 0.001}) == \
            {"a": 9, "b": 1}

    def test_bankers_rounding_pinned(self):
        # insertion order, round-half-even on the interior units, last
        # unit absorbs the remainder — the exact contract downstream
        # pre-split consumers (and the stores that compare plans) rely on
        assert proportional_split(10, {"a": 1.0, "b": 1.0, "c": 1.0,
                                       "d": 1.0}) == \
            {"a": 2, "b": 2, "c": 2, "d": 4}

    @given(
        n_items=st.integers(0, 5000),
        n_units=st.integers(1, 6),
        seed=st.integers(0, 10_000),
    )
    @settings(max_examples=80, deadline=None)
    def test_property_exact_tiling_and_floor(self, n_items, n_units, seed):
        """Property: sizes tile the space exactly and every positive-
        throughput unit gets >= 1 item whenever the space has room —
        for any throughput/overhead mix (including zero-throughput and
        huge-overhead units)."""
        rng = random.Random(seed)
        tp = {f"u{i}": (0.0 if rng.random() < 0.2
                        else rng.uniform(1e-3, 1000.0))
              for i in range(n_units)}
        tp["u0"] = max(tp["u0"], 1.0)  # keep the total positive
        ov = {f"u{i}": (0.0 if rng.random() < 0.5
                        else rng.uniform(0.0, 5.0))
              for i in range(n_units)}
        sizes = latency_aware_split(n_items, tp, ov)
        assert set(sizes) == set(tp)
        assert sum(sizes.values()) == n_items
        assert all(v >= 0 for v in sizes.values())
        assert all(sizes[w] == 0 for w in tp if tp[w] <= 0.0)
        if n_items >= n_units:
            assert all(sizes[w] >= 1 for w in tp if tp[w] > 0.0), (
                f"starved a live unit: {sizes} tp={tp} ov={ov}")


# ---------------------------------------------------------------------------
# elastic leave: abort/remove_worker must surrender *all* in-flight chunks
# ---------------------------------------------------------------------------
class TestElasticReturns:
    def test_abort_returns_all_outstanding_capacity_3(self):
        s = make_sched(n_items=1000, acc_chunk=64)
        s.set_capacity("acc0", 3)
        issued = [s.next_chunk("acc0") for _ in range(3)]
        with pytest.raises(RuntimeError):
            s.next_chunk("acc0")  # capacity still enforced at 3
        returned = s.abort("acc0")
        # regression: a pipelined worker held 3 chunks but abort used to
        # surrender only the oldest, silently losing the other spans
        assert returned == issued
        assert not s.workers["acc0"].busy

    def test_remove_worker_returns_all_and_unregisters(self):
        s = make_sched(n_items=1000, acc_chunk=64)
        s.set_capacity("acc0", 3)
        issued = [s.next_chunk("acc0") for _ in range(3)]
        returned = s.remove_worker("acc0")
        assert returned == issued
        assert "acc0" not in s.workers
        # the surrendered spans are disjoint and oldest-first: exactly
        # what the caller must requeue for coverage to stay exact-once
        spans = [(c.start, c.stop) for c in returned]
        assert spans == sorted(spans)
        for (a, b), (c, d) in zip(spans, spans[1:]):
            assert b <= c

    def test_abort_idle_worker_returns_empty_list(self):
        s = make_sched()
        assert s.abort("acc0") == []


# ---------------------------------------------------------------------------
# throughput estimation: a measured 0.0 is an observation, not "no data"
# ---------------------------------------------------------------------------
class TestThroughputFloor:
    def test_measured_zero_is_floored_not_bootstrapped(self):
        s = MultiDynamicScheduler(100, 10)
        s.add_worker("cc0", WorkerKind.CC, throughput=0.0)
        # regression: truthiness treated a stalled unit's 0.0 as
        # unobserved and handed it the optimistic bootstrap prior
        est = s._estimated_throughput(s.workers["cc0"])
        assert est == THROUGHPUT_FLOOR

    def test_bootstrap_prior_sees_zero_observation(self):
        s = MultiDynamicScheduler(100, 10)
        s.add_worker("cc0", WorkerKind.CC, throughput=0.0)
        s.add_worker("acc0", WorkerKind.ACC)
        # the unobserved ACC bootstraps relative to the *slowest observed*
        # unit — which is the stalled one, floored, not skipped
        est = s._estimated_throughput(s.workers["acc0"])
        assert est == pytest.approx(THROUGHPUT_FLOOR * s.initial_acc_speedup)

    def test_zero_throughput_worker_still_issues_chunks(self):
        s = MultiDynamicScheduler(100, 10)
        s.add_worker("cc0", WorkerKind.CC, throughput=0.0)
        c = s.next_chunk("cc0")
        assert c is not None and c.size >= 1
