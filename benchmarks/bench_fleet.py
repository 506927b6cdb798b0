"""Fleet membership benchmark (ISSUE 10): what liveness + checkpoints buy.

Two SimulatedClock studies, both deterministic (seeded, virtual time),
so the committed artifact is reproducible and CI can gate orderings:

* **recovery** — a run dies mid-flight (``done_frac`` of the space
  covered, the coverage bitmap on disk via
  :func:`repro.checkpoint.coverage.save_coverage`).  Restarting with
  :func:`~repro.checkpoint.coverage.checkpointed_parallel_for` restores
  the bitmap through the verifying path and recomputes only the
  remainder; the baseline recomputes the whole pre-split from zero.
  ``recovery_ratio = full_recompute_s / resume_s`` must be > 1.0
  (strictly faster) and CI pins a margin via
  ``check_bench.py --min-recovery-ratio``.

* **churn** — one worker of the fleet is dead from the start (crashed,
  silent, chunk in flight).  With heartbeat liveness the unit is
  convicted after ``patience x heartbeat`` seconds and its hostage
  chunk requeues to the survivors; with static membership the engine
  only learns at retransmit exhaustion (``max_retries x
  retry_interval``).  Both timelines run through the real engine as
  elastic leaves at the respective *detection* times; goodput is
  ``items / makespan``.  ``detect_ratio`` and ``goodput_ratio``
  (heartbeat over static) must be >= 1.0.

    PYTHONPATH=src python benchmarks/bench_fleet.py --json BENCH_fleet.json
    PYTHONPATH=src python benchmarks/bench_fleet.py --quick --json /tmp/smoke.json

``tools/check_bench.py --schema bench_fleet/v1`` validates structure
and orderings; the CI ``fleet`` job gates the committed artifact.
"""

from __future__ import annotations

import json
import tempfile
from typing import Dict

from repro.checkpoint import (
    Checkpointer,
    CoverageMap,
    checkpointed_parallel_for,
    save_coverage,
)
from repro.core import ElasticSchedule, HeteroRuntime, SimulatedClock
from repro.core.scheduler import WorkerKind
from repro.launch.compile_cache import configure_compile_cache

BENCH_SCHEMA = "bench_fleet/v1"

# liveness/transport timing constants the studies derive detection from
HEARTBEAT_S = 0.05
PATIENCE = 3
RETRY_INTERVAL_S = 0.05
MAX_RETRIES = 600


def _noop(chunk) -> None:
    """The items are pure virtual time here; coverage is what we measure."""


def _sim_runtime(num_units: int, *, dead: int = 0) -> HeteroRuntime:
    """A fresh simulated fleet; the first ``dead`` units are crashed
    (near-zero speed: they accept a chunk and never finish it)."""
    rt = HeteroRuntime(clock=SimulatedClock())
    for i in range(num_units):
        speed = 1e-9 if i < dead else 1.0
        rt.register_unit(f"u{i}", WorkerKind.CC, speed=speed)
    return rt


def recovery_study(*, items: int, num_units: int, done_frac: float,
                   round_items: int) -> Dict[str, float]:
    """Checkpoint-backed resume vs full recompute after mid-run death."""
    # the death scene: a real bitmap covering done_frac of the space,
    # written through the standard checkpointer (what a dying run left)
    done_items = int(items * done_frac)
    with tempfile.TemporaryDirectory() as death_dir:
        ckpt = Checkpointer(death_dir)
        cov = CoverageMap(items)
        cov.mark(0, done_items)
        save_coverage(ckpt, done_items, cov, blocking=True)
        ckpt.wait_all()
        resume = checkpointed_parallel_for(
            _sim_runtime(num_units), _noop, items, checkpointer=ckpt,
            round_items=round_items, policy="multidynamic", acc_chunk=16)
    with tempfile.TemporaryDirectory() as fresh_dir:
        full = checkpointed_parallel_for(
            _sim_runtime(num_units), _noop, items,
            checkpointer=Checkpointer(fresh_dir), resume=False,
            round_items=round_items, policy="multidynamic", acc_chunk=16)
    resume_s = sum(r.wall_time for r in resume.reports)
    full_s = sum(r.wall_time for r in full.reports)
    assert resume.items_run == items - done_items
    return {
        "full_recompute_items": full.items_run,
        "resume_items": resume.items_run,
        "full_recompute_s": full_s,
        "resume_s": resume_s,
        "recovery_ratio": full_s / resume_s,
    }


def churn_study(*, items: int, num_units: int) -> Dict[str, float]:
    """Goodput with heartbeat-convicted vs static membership, one dead
    worker holding a chunk hostage until detection."""
    hb_detect = PATIENCE * HEARTBEAT_S
    static_detect = MAX_RETRIES * RETRY_INTERVAL_S

    def run(detect_s: float) -> float:
        rt = _sim_runtime(num_units, dead=1)
        sched = ElasticSchedule().leave(detect_s, "u0")
        rep = rt.parallel_for(num_items=items, policy="multidynamic",
                              acc_chunk=8, elastic=sched)
        assert rep.items == items
        return rep.wall_time

    hb_makespan = run(hb_detect)
    static_makespan = run(static_detect)
    return {
        "heartbeat_detect_s": hb_detect,
        "static_detect_s": static_detect,
        "detect_ratio": static_detect / hb_detect,
        "heartbeat_makespan_s": hb_makespan,
        "static_makespan_s": static_makespan,
        "heartbeat_goodput": items / hb_makespan,
        "static_goodput": items / static_makespan,
        "goodput_ratio": static_makespan / hb_makespan,
    }


def run_bench(*, quick: bool = False) -> dict:
    items = 800 if quick else 4000
    num_units = 4 if quick else 8
    done_frac = 0.75
    round_items = items // 8
    # small enough that the survivors drain well before static detection
    # fires — the regime where the hostage chunk dominates the makespan
    churn_items = 60 if quick else 120
    doc = {
        "schema": BENCH_SCHEMA,
        "params": {
            "seed": 0,
            "num_units": num_units,
            "items": items,
            "heartbeat": HEARTBEAT_S,
            "patience": PATIENCE,
            "retry_interval": RETRY_INTERVAL_S,
            "max_retries": MAX_RETRIES,
            "done_frac": done_frac,
            "round_items": round_items,
            "churn_items": churn_items,
            "quick": quick,
        },
        "recovery": recovery_study(items=items, num_units=num_units,
                                   done_frac=done_frac,
                                   round_items=round_items),
        "churn": churn_study(items=churn_items, num_units=num_units),
    }
    return doc


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller spaces for a CI smoke run")
    ap.add_argument("--json", metavar="PATH",
                    help="write the artifact to PATH")
    args = ap.parse_args()
    configure_compile_cache()
    doc = run_bench(quick=args.quick)
    rec, ch = doc["recovery"], doc["churn"]
    print(f"recovery: full {rec['full_recompute_s']:.1f}s vs resume "
          f"{rec['resume_s']:.1f}s -> {rec['recovery_ratio']:.2f}x "
          f"({rec['resume_items']}/{rec['full_recompute_items']} items re-run)")
    print(f"churn: detect {ch['heartbeat_detect_s']:.2f}s vs "
          f"{ch['static_detect_s']:.2f}s (ratio {ch['detect_ratio']:.1f}x), "
          f"goodput {ch['heartbeat_goodput']:.1f} vs "
          f"{ch['static_goodput']:.1f} items/s "
          f"(ratio {ch['goodput_ratio']:.2f}x)")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
