"""Serving tier: admission policies, backpressure, loadgen, and the
ServingEngine prefill/sampling bug batch (errored/timeout prefills,
fixed-seed determinism across batch compositions, stable report schema)."""

import threading
import time

import numpy as np
import pytest

from repro.serving import Request
from repro.serving.admission import (
    AdmissionVerdict,
    CostAwarePolicy,
    DeadlinePolicy,
    FIFOPolicy,
    PriorityPolicy,
    make_policy,
)
from repro.serving.loadgen import (
    METRIC_KEYS,
    LoadgenScenario,
    make_trace,
    run_trace,
    summarize,
)


def _req(rid, plen=4, mx=4, **kw):
    return Request(rid=rid, prompt=np.zeros(plen, np.int32),
                   max_new_tokens=mx, **kw)


# ---------------------------------------------------------------------------
# policies: pure-python, no model
# ---------------------------------------------------------------------------
class TestAdmissionPolicies:
    def test_make_policy_names_and_errors(self):
        assert isinstance(make_policy("fifo"), FIFOPolicy)
        assert isinstance(make_policy(None), FIFOPolicy)
        assert isinstance(make_policy("priority"), PriorityPolicy)
        assert isinstance(make_policy("deadline"), DeadlinePolicy)
        assert isinstance(make_policy("cost"), CostAwarePolicy)
        with pytest.raises(ValueError, match="unknown admission policy"):
            make_policy("lifo")
        # instance passthrough + bound installation
        p = FIFOPolicy()
        assert make_policy(p, max_queue=3) is p and p.max_queue == 3
        with pytest.raises(ValueError, match="conflicting"):
            make_policy(FIFOPolicy(max_queue=2), max_queue=3)

    def test_bounded_queue_sheds(self):
        p = make_policy("fifo", max_queue=2)
        assert p.admit(_req(0), queue_depth=1, now=0.0)
        verdict = p.admit(_req(1), queue_depth=2, now=0.0)
        assert not verdict and verdict.reason == "queue_full"
        assert isinstance(verdict, AdmissionVerdict)

    def test_fifo_order_is_identity(self):
        reqs = [_req(i) for i in (3, 1, 2)]
        assert [r.rid for r in FIFOPolicy().order(reqs)] == [3, 1, 2]

    def test_priority_order_stable_within_class(self):
        reqs = [_req(0, priority=0), _req(1, priority=2),
                _req(2, priority=0), _req(3, priority=2)]
        assert [r.rid for r in PriorityPolicy().order(reqs)] == [1, 3, 0, 2]

    def test_deadline_edf_and_expired_shed(self):
        reqs = [
            _req(0),                                        # no SLO: last
            _req(1, deadline=0.5, submitted_at=10.0),       # abs 10.5
            _req(2, deadline=5.0, submitted_at=4.0),        # abs 9.0
        ]
        p = DeadlinePolicy()
        assert [r.rid for r in p.order(reqs, now=0.0)] == [2, 1, 0]
        verdict = p.admit(_req(9, deadline=0.0), queue_depth=0, now=0.0)
        assert not verdict and verdict.reason == "expired"
        assert p.admit(_req(9, deadline=1.0), queue_depth=0, now=0.0)

    def test_cost_aware_learns_from_observations(self):
        p = CostAwarePolicy()
        reqs = [_req(0, plen=32), _req(1, plen=2), _req(2, plen=8)]
        # default prediction = prompt_len: shortest-prompt-first
        assert [r.rid for r in p.order(reqs)] == [1, 2, 0]
        p.observe_prefill("slot0", tokens=100, elapsed=1.0)
        assert p.predicted_cost(_req(9, plen=50)) == pytest.approx(0.5, rel=0.2)
        assert [r.rid for r in p.order(reqs)] == [1, 2, 0]

    def test_cost_aware_straggler_report(self):
        p = CostAwarePolicy()
        for _ in range(5):
            p.observe_prefill("slot0", tokens=10, elapsed=0.01)
            p.observe_prefill("slot1", tokens=10, elapsed=1.0)
        rep = p.straggler_report
        assert rep is not None and "slot1" in rep.stragglers


# ---------------------------------------------------------------------------
# loadgen traces: pure numpy, no model
# ---------------------------------------------------------------------------
class TestLoadgenTraces:
    def test_seeded_trace_is_deterministic(self):
        a = make_trace(seed=3, n=16, arrival="bursty")
        b = make_trace(seed=3, n=16, arrival="bursty")
        assert [t.at for t in a] == [t.at for t in b]
        assert [t.request.max_new_tokens for t in a] == \
               [t.request.max_new_tokens for t in b]
        assert all(np.array_equal(x.request.prompt, y.request.prompt)
                   for x, y in zip(a, b))
        c = make_trace(seed=4, n=16, arrival="bursty")
        assert [t.at for t in a] != [t.at for t in c]

    @pytest.mark.parametrize("arrival", ["poisson", "bursty", "uniform"])
    def test_arrivals_monotone_and_lengths_bounded(self, arrival):
        sc = LoadgenScenario(seed=1, n=64, rate=100.0, arrival=arrival,
                             prompt_lens=(2, 9), gen_lens=(3, 7))
        trace = make_trace(sc)
        ats = [t.at for t in trace]
        assert ats == sorted(ats) and ats[0] > 0
        assert all(2 <= len(t.request.prompt) <= 9 for t in trace)
        assert all(3 <= t.request.max_new_tokens <= 7 for t in trace)

    def test_unknown_arrival_rejected(self):
        with pytest.raises(ValueError, match="unknown arrival"):
            make_trace(seed=0, n=4, arrival="lunar")

    def test_deadlines_and_priorities_assigned(self):
        trace = make_trace(seed=0, n=8, deadline_base=1.0,
                           deadline_per_token=0.5, priorities=(0, 7))
        for i, t in enumerate(trace):
            assert t.request.deadline == pytest.approx(
                1.0 + 0.5 * t.request.max_new_tokens)
            assert t.request.priority == (0, 7)[i % 2]


# ---------------------------------------------------------------------------
# engine-level behaviour (needs a real smoke model)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served(request):
    import jax
    from repro.configs import get_config
    from repro.models import make_model

    cfg = get_config("tinyllama-1.1b").smoke()
    m = make_model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _requests(cfg, n=6, seed=0, mx=(2, 10), **kw):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(2, 8))).astype(np.int32),
                max_new_tokens=int(rng.integers(*mx)), **kw)
        for i in range(n)
    ]


@pytest.mark.slow
class TestEngineAdmission:
    def test_submit_returns_verdict_and_sheds(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=2, max_len=48, max_queue=2)
        reqs = _requests(cfg, n=4)
        verdicts = [eng.submit(r) for r in reqs]
        assert [bool(v) for v in verdicts] == [True, True, False, False]
        assert verdicts[2].reason == "queue_full"
        assert set(eng.shed) == {2, 3}
        res = eng.run()
        assert set(res) == {0, 1}          # shed requests never ran
        rep = eng.throughput_report()
        assert rep["shed"] == 2 and rep["completed"] == 2

    def test_priority_policy_orders_completions(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=1, max_len=48, policy="priority")
        reqs = _requests(cfg, n=4, mx=(3, 4))
        for pr, r in zip((0, 5, 0, 9), reqs):
            r.priority = pr
            eng.submit(r)
        res = eng.run()
        finished = sorted(res.values(), key=lambda r: r.finish_time)
        assert [r.rid for r in finished] == [3, 1, 0, 2]

    def test_deadline_policy_edf_through_engine(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=1, max_len=48, policy="deadline")
        reqs = _requests(cfg, n=3, mx=(3, 4))
        for dl, r in zip((9.0, 100.0, 1.0), reqs):
            r.deadline = dl
            eng.submit(r)
        res = eng.run()
        finished = sorted(res.values(), key=lambda r: r.finish_time)
        assert [r.rid for r in finished] == [2, 0, 1]
        assert all(r.deadline is not None for r in res.values())

    def test_throughput_report_schema_stable(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=2, max_len=48)
        empty = eng.throughput_report()
        for r in _requests(cfg, n=3):
            eng.submit(r)
        eng.run()
        full = eng.throughput_report()
        assert set(empty) == set(full)      # same keys before/after
        for key in ("mean_latency", "p50_latency", "p95_latency",
                    "p99_latency", "mean_ttft", "goodput_tokens"):
            assert key in empty
        assert empty["mean_latency"] == 0.0
        assert full["completed"] == 3 and full["mean_latency"] > 0
        assert full["p99_latency"] >= full["p50_latency"] > 0
        assert all(r.ttft is not None and r.ttft <= r.latency
                   for r in eng.results.values())


@pytest.mark.slow
class TestEngineFailures:
    def test_errored_async_prefill_fails_request_not_batch(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=2, max_len=48, backend="threads")
        real = eng._prefill

        def flaky(req):
            if req.rid == 1:
                raise RuntimeError("injected prefill failure")
            return real(req)

        eng._prefill = flaky
        reqs = _requests(cfg, n=5)
        for r in reqs:
            eng.submit(r)
        res = eng.run()                      # must not raise or hang
        assert set(res) == {0, 1, 2, 3, 4}
        assert res[1].error is not None and "injected" in res[1].error
        assert res[1].tokens == [] and not res[1].ok
        for rid in (0, 2, 3, 4):
            assert res[rid].ok
            assert len(res[rid].tokens) == reqs[rid].max_new_tokens
        # batch accounting closed every chunk despite the failure
        assert eng.last_run_report is not None
        assert eng.last_run_report.items == 5
        rep = eng.throughput_report()
        assert rep["failed"] == 1 and rep["completed"] == 4

    def test_errored_inline_prefill_fails_request_not_batch(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=2, max_len=48)
        real = eng._prefill
        eng._prefill = lambda req: (_ for _ in ()).throw(
            ValueError("poisoned")) if req.rid == 0 else real(req)
        for r in _requests(cfg, n=3):
            eng.submit(r)
        res = eng.run()
        assert res[0].error is not None and res[1].ok and res[2].ok
        assert eng.last_run_report.items == 3

    def test_dead_prefill_unit_raises_instead_of_spinning(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=2, max_len=48,
                            backend="threads", prefill_timeout=0.2)
        # unit 0's submits vanish: nothing ever posts to the bus for it
        eng._prefill_units[0].submit = lambda chunk, work: None
        for r in _requests(cfg, n=2):
            eng.submit(r)
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError, match="slot0"):
            eng.run()
        assert time.perf_counter() - t0 < 30.0   # not a 60s-per-iter spin


@pytest.mark.slow
class TestSamplingDeterminism:
    TEMP = 0.8

    def _run(self, served, reqs, *, slots, seed=7):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=slots, max_len=48,
                            temperature=self.TEMP, seed=seed)
        for r in reqs:
            eng.submit(r)
        return {rid: tuple(res.tokens) for rid, res in eng.run().items()}

    def test_streams_identical_regardless_of_batch_composition(self, served):
        cfg, _, _ = served
        reqs = _requests(cfg, n=4, seed=5, mx=(4, 9))
        together = self._run(served, reqs, slots=4)
        alone = self._run(served, [reqs[0]], slots=4)
        assert alone[0] == together[0]
        # different co-runners, same slot count: r0's stream is unchanged
        partial = self._run(served, [reqs[0], reqs[3]], slots=4)
        assert partial[0] == together[0] and partial[3] == together[3]

    def test_streams_identical_regardless_of_submit_order(self, served):
        cfg, _, _ = served
        reqs = _requests(cfg, n=4, seed=6, mx=(4, 9))
        fwd = self._run(served, reqs, slots=2)
        rev = self._run(served, list(reversed(reqs)), slots=2)
        assert fwd == rev

    def test_temperature_zero_still_greedy_deterministic(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        outs = []
        for _ in range(2):
            eng = ServingEngine(m, params, slots=2, max_len=48)
            for r in _requests(cfg, n=3, seed=2):
                eng.submit(r)
            outs.append({k: tuple(v.tokens) for k, v in eng.run().items()})
        assert outs[0] == outs[1]

    def test_first_token_honours_temperature(self, served):
        """With a temperature set, the first sampled token is from the
        tempered distribution, not hard-coded greedy: across seeds the
        first token varies, while greedy engines always agree."""
        cfg, _, _ = served
        req = _requests(cfg, n=1, seed=9, mx=(2, 3))[0]
        firsts = {
            self._run(served, [Request(rid=0, prompt=req.prompt,
                                       max_new_tokens=2)],
                      slots=2, seed=s)[0][0]
            for s in range(8)
        }
        assert len(firsts) > 1


@pytest.mark.slow
class TestLoadgenSmoke:
    def test_open_loop_run_reports_stable_schema(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        trace = make_trace(seed=0, n=6, rate=200.0, arrival="poisson",
                           vocab_size=cfg.vocab_size, prompt_lens=(2, 8),
                           gen_lens=(2, 8), deadline_base=60.0)
        eng = ServingEngine(m, params, slots=2, max_len=48)
        metrics = run_trace(eng, trace)
        assert set(metrics) == set(METRIC_KEYS)
        assert metrics["completed"] == 6 and metrics["failed"] == 0
        assert metrics["goodput_tokens"] == metrics["tokens"] > 0
        assert metrics["p99_latency_s"] >= metrics["p50_latency_s"] > 0
        assert metrics["deadline_hit_rate"] == 1.0

    @pytest.mark.parametrize("mode", ["static", "continuous"])
    @pytest.mark.parametrize("policy", ["fifo", "cost"])
    def test_every_policy_and_mode_completes_every_request(
            self, served, policy, mode):
        from repro.serving import ServingEngine

        cfg, m, params = served
        trace = make_trace(seed=0, n=6, rate=200.0, arrival="poisson",
                           vocab_size=cfg.vocab_size, prompt_lens=(2, 8),
                           gen_lens=(2, 8))
        eng = ServingEngine(m, params, slots=2, max_len=48, mode=mode,
                            policy=policy)
        metrics = run_trace(eng, trace, time_scale=0.0)
        assert set(metrics) == set(METRIC_KEYS)
        assert metrics["completed"] == 6
        assert metrics["failed"] == 0 and metrics["shed"] == 0
        assert set(eng.results) == {t.request.rid for t in trace}
        for t in trace:
            got = eng.results[t.request.rid]
            assert len(got.tokens) == t.request.max_new_tokens
        assert metrics["p99_latency_s"] >= metrics["p50_latency_s"] > 0

    def test_mid_run_submissions_are_served(self, served):
        """submit() racing run(): every admitted request completes
        exactly once (the queue-snapshot lock)."""
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=2, max_len=48)
        reqs = _requests(cfg, n=8, mx=(2, 4))
        for r in reqs[:2]:
            eng.submit(r)

        def late():
            for r in reqs[2:]:
                time.sleep(0.02)
                eng.submit(r)

        th = threading.Thread(target=late)
        th.start()
        while th.is_alive() or eng.has_work:
            if eng.has_work:
                eng.run()
            else:
                time.sleep(0.005)
        th.join()
        assert set(eng.results) == {r.rid for r in reqs}

    def test_summarize_counts_shed_and_failed(self, served):
        from repro.serving import ServingEngine

        cfg, m, params = served
        eng = ServingEngine(m, params, slots=1, max_len=48, max_queue=2)
        for r in _requests(cfg, n=4, mx=(2, 3)):
            eng.submit(r)
        eng.run()
        metrics = summarize(eng, wall=1.0, offered=4)
        assert metrics["shed"] == 2 and metrics["completed"] == 2
