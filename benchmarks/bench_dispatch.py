"""Dispatch fast-path microbenchmark (ISSUE 8): per-chunk dispatch cost
through a :class:`~repro.core.transport.RemoteUnit` with the fast-path
knobs toggled — session-cached work descriptors (``fn_cache``) and
chunk-batched frames (``batch_frames``) — against the PR-7 baseline
(inline fn pickling, one frame per chunk).

Three transports x three modes:

* transports: ``loopback`` (in-process queue pair), ``socket`` (real TCP
  through an in-process :class:`WorkerServer`), ``flaky`` (seeded
  drop/dup/reorder injection over loopback — the fast path must stay
  fast *and* correct when frames need retransmits);
* modes: ``baseline`` (fn_cache off, batch_frames=1 — the pre-fast-path
  wire protocol), ``cached`` (descriptor cache on, unbatched),
  ``batched`` (cache on, ``batch_frames`` chunks per frame).

The work function carries a ~4 KiB payload attribute so the baseline
pays the real per-frame descriptor pickling cost the cache elides.  Per
config we report median-of-repeats ``chunks_per_sec``, the amortized
``dispatch_us`` (wall clock per chunk — the number batching must lower),
the raw per-chunk ``submit_latency_us`` (which legitimately *rises*
under batching as chunks pipeline behind their batch siblings) and the
per-chunk ``wire_us`` attribution from the unit's latency ledgers, plus
a ``speedups`` block (batched-vs-baseline chunks_per_sec per transport).

On top of the 3x3 grid, the artifact carries a ``latency_aware`` block
(ISSUE 9) with two studies on a *flaky-delay* transport (seeded
per-frame delivery delay — a high-latency link, not just a lossy one):

* adaptive frame batching: ``batch_frames="auto"`` (width learned from
  frame transit vs. per-chunk service time) against the fixed
  ``batch_frames`` row on the same link; ``auto_ratio`` is the
  chunks/s ratio and must stay >= 1.0 (auto must find at least the
  hand-tuned width);
* latency-aware learned splits: a mixed local+high-latency-remote unit
  set driven through ``HeteroRuntime`` with a shared ``CostModel`` —
  after a learned warmup, the makespan of a *throughput-only*
  proportional pre-split vs. ``policy="learned"``'s latency-aware
  split (``makespan_ratio`` > 1.0 means the latency terms paid off).

    PYTHONPATH=src python benchmarks/bench_dispatch.py --json BENCH_dispatch.json
    PYTHONPATH=src python benchmarks/bench_dispatch.py --quick --json /tmp/smoke.json

``tools/check_bench.py --schema bench_dispatch/v2`` validates the
artifact; CI additionally gates the committed one on a >=2x socket
speedup plus the two latency-aware ratios (``--min-auto-ratio`` /
``--min-split-ratio``).
"""

from __future__ import annotations

import json
import statistics
import threading
import time
from typing import Dict, List, Optional

from repro.core.backends import CompletionBus
from repro.core.costmodel import CostModel
from repro.core.runtime import HeteroRuntime
from repro.core.scheduler import (
    Chunk,
    WorkerKind,
    latency_aware_split,
    proportional_split,
)
from repro.core.transport import (
    FlakyTransport,
    LoopbackTransport,
    RemoteUnit,
    RemoteWorker,
    SleepWork,
    WorkerServer,
)
from repro.launch.compile_cache import configure_compile_cache

BENCH_SCHEMA = "bench_dispatch/v2"

MODES = (
    # (mode, fn_cache, batched) — batch_frames filled in from params
    ("baseline", False, False),
    ("cached", True, False),
    ("batched", True, True),
)
TRANSPORTS = ("loopback", "socket", "flaky")


class DispatchWork:
    """Trivial per-chunk work with a deliberately chunky pickle.

    The payload models a real work descriptor (closure constants, kernel
    params): ~4 KiB that the baseline protocol re-pickles onto every
    frame and the descriptor cache ships exactly once per session.
    """

    def __init__(self, payload_bytes: int) -> None:
        self.payload = b"\x5a" * payload_bytes

    def __call__(self, chunk) -> int:
        return chunk.stop - chunk.start


def _make_unit(transport: str, name: str, *, mode_batch: int,
               fn_cache: bool, seed: int,
               server: Optional[WorkerServer]) -> RemoteUnit:
    if transport == "socket":
        assert server is not None
        return RemoteUnit(name, address=server.address,
                          batch_frames=mode_batch, fn_cache=fn_cache)
    client_end, worker_end = LoopbackTransport.pair()
    client_side, worker_side = client_end, worker_end
    if transport == "flaky":
        faults = dict(drop=0.05, duplicate=0.05, reorder=0.10)
        client_side = FlakyTransport(client_end, seed=seed, **faults)
        worker_side = FlakyTransport(worker_end, seed=seed + 1, **faults)
    worker = RemoteWorker(worker_side, poll_interval=0.05)
    import threading

    threading.Thread(target=worker.serve, daemon=True).start()
    return RemoteUnit(name, transport=client_side, retry_interval=0.02,
                      max_retries=600, batch_frames=mode_batch,
                      fn_cache=fn_cache)


def _drive(unit: RemoteUnit, n_chunks: int, work_fn) -> Dict[str, float]:
    """Pump ``n_chunks`` through the unit, windowed at its capacity."""
    bus = CompletionBus()
    unit.start(bus)
    try:
        issued = done = 0
        t0 = time.perf_counter()
        while done < n_chunks:
            while issued < n_chunks and issued - done < unit.capacity:
                unit.submit(Chunk(issued, issued + 1, unit.name), work_fn)
                issued += 1
            unit.flush()
            if not bus.wait(timeout=60.0):
                raise RuntimeError(f"unit {unit.name}: completions stalled "
                                   f"at {done}/{n_chunks}")
            for rec in bus.drain():
                if rec.error is not None:
                    raise rec.error
                done += 1
        wall = time.perf_counter() - t0
        final_width = unit.batch_frames
    finally:
        unit.close()
    return {
        "wall_s": wall,
        "chunks_per_sec": n_chunks / max(wall, 1e-12),
        # amortized cost of dispatching one chunk end-to-end — the number
        # batching must lower (per-chunk *latency* legitimately rises as
        # chunks pipeline behind batch siblings; that is submit_latency_us)
        "dispatch_us": 1e6 * wall / n_chunks,
        "submit_latency_us": 1e6 * statistics.fmean(unit.dispatch_latencies),
        "wire_us": 1e6 * statistics.fmean(unit.wire_latencies),
        "final_batch_frames": final_width,
    }


# ---------------------------------------------------------------------------
# latency-aware studies (flaky-delay transport)
# ---------------------------------------------------------------------------
def _delayed_loopback_unit(name: str, *, seed: int, max_delay: float,
                           batch_frames, retry_interval: float = 0.5,
                           fn_cache: bool = True) -> RemoteUnit:
    """Loopback unit behind a seeded high-latency link: every frame in
    both directions is delayed uniform(0, max_delay) seconds."""
    client_end, worker_end = LoopbackTransport.pair()
    client_side = FlakyTransport(client_end, seed=seed,
                                 delay=1.0, max_delay=max_delay)
    worker_side = FlakyTransport(worker_end, seed=seed + 1,
                                 delay=1.0, max_delay=max_delay)
    worker = RemoteWorker(worker_side, poll_interval=0.02)
    threading.Thread(target=worker.serve, daemon=True).start()
    return RemoteUnit(name, transport=client_side,
                      retry_interval=retry_interval, max_retries=200,
                      batch_frames=batch_frames, fn_cache=fn_cache)


def _auto_batch_study(*, n_chunks: int, repeats: int, batch_frames: int,
                      payload_bytes: int, max_delay: float, seed: int) -> dict:
    """Fixed ``batch_frames`` vs ``"auto"`` on the flaky-delay link."""
    entries = {}
    for mode, bf in (("batched", batch_frames), ("auto", "auto")):
        runs = []
        for r in range(repeats):
            unit = _delayed_loopback_unit(
                f"d{r}", seed=seed * 313 + r * 17 + 1, max_delay=max_delay,
                batch_frames=bf, retry_interval=0.5)
            runs.append(_drive(unit, n_chunks, DispatchWork(payload_bytes)))
        entry = {
            "transport": "flaky-delay", "mode": mode, "fn_cache": True,
            "batch_frames": bf, "n_chunks": n_chunks,
        }
        for key in ("wall_s", "chunks_per_sec", "dispatch_us",
                    "submit_latency_us", "wire_us"):
            entry[key] = statistics.median(r[key] for r in runs)
        entry["final_batch_frames"] = int(statistics.median(
            r["final_batch_frames"] for r in runs))
        entries[mode] = entry
        print(f"  {'fl-delay':8s} {mode:8s}  "
              f"{entry['chunks_per_sec']:10.0f} chunks/s  "
              f"dispatch {entry['dispatch_us']:8.1f}us  "
              f"width -> {entry['final_batch_frames']}")
    ratio = (entries["auto"]["chunks_per_sec"]
             / max(entries["batched"]["chunks_per_sec"], 1e-12))
    print(f"  flaky-delay auto/fixed chunks_per_sec ratio: {ratio:.2f}x")
    return {"fixed": entries["batched"], "auto": entries["auto"],
            "auto_ratio": ratio}


def _split_run(model: CostModel, *, policy, n_items: int, acc_chunk: int,
               per_item_s: float, max_delay: float, seed: int):
    """One wall-clock run over 2 local + 1 high-latency-remote unit.

    Transports are single-session, so every run builds a fresh runtime
    and remote unit; the shared ``model`` is the state that carries the
    learned speeds and latencies across runs (the runtime folds every
    finished report back in).
    """
    rt = HeteroRuntime(cost_model=model)
    work = SleepWork(per_item_s)
    rt.register_unit("loc0", WorkerKind.CC, work_fn=work)
    rt.register_unit("loc1", WorkerKind.CC, work_fn=work)
    rt.register_unit("rem0", WorkerKind.ACC, work_fn=work,
                     backend=_delayed_loopback_unit(
                         "rem0", seed=seed, max_delay=max_delay,
                         batch_frames=1))
    return rt.parallel_for(num_items=n_items, policy=policy,
                           acc_chunk=acc_chunk, kernel="latsplit")


def _split_study(*, n_items: int, repeats: int, warmups: int,
                 per_item_s: float, max_delay: float, seed: int) -> dict:
    """Throughput-only vs latency-aware learned splits, measured.

    The remote unit computes as fast as the locals but pays a learned
    ~``max_delay/2`` wire overhead per dispatch; equalizing predicted
    *completion* time hands it fewer items, so the latency-aware run's
    makespan must come in under the throughput-only pre-split's.
    """
    model = CostModel()
    names = ["loc0", "loc1", "rem0"]
    acc_chunk = max(16, n_items // 5)
    for w in range(warmups):
        _split_run(model, policy="learned", n_items=n_items,
                   acc_chunk=acc_chunk, per_item_s=per_item_s,
                   max_delay=max_delay, seed=seed * 977 + w * 29 + 3)
    speeds = model.speeds(names, "latsplit")
    overheads = model.overheads(names, "latsplit")
    t_only_sizes = proportional_split(n_items, {n: speeds[n] for n in names})
    lat_sizes = latency_aware_split(n_items, {n: speeds[n] for n in names},
                                    overheads)
    mapping, start = {}, 0
    for n in names:
        mapping[n] = (start, start + t_only_sizes[n])
        start += t_only_sizes[n]
    t_only_walls, lat_walls = [], []
    for r in range(repeats):
        rep_t = _split_run(model, policy=mapping, n_items=n_items,
                           acc_chunk=acc_chunk, per_item_s=per_item_s,
                           max_delay=max_delay, seed=seed * 601 + r * 41 + 7)
        rep_l = _split_run(model, policy="learned", n_items=n_items,
                           acc_chunk=acc_chunk, per_item_s=per_item_s,
                           max_delay=max_delay, seed=seed * 601 + r * 41 + 19)
        t_only_walls.append(rep_t.makespan)
        lat_walls.append(rep_l.makespan)
    t_only = statistics.median(t_only_walls)
    lat = statistics.median(lat_walls)
    ratio = t_only / max(lat, 1e-12)
    print(f"  split    t-only {1e3 * t_only:7.1f}ms  "
          f"latency-aware {1e3 * lat:7.1f}ms  ratio {ratio:.2f}x  "
          f"shares {t_only_sizes} -> {lat_sizes}")
    return {
        "n_items": n_items, "per_item_s": per_item_s,
        "speeds": speeds, "overheads": overheads,
        "throughput_only_split": t_only_sizes,
        "latency_aware_split": lat_sizes,
        "throughput_only_makespan_s": t_only,
        "latency_aware_makespan_s": lat,
        "makespan_ratio": ratio,
    }


def run(*, quick: bool = False, seed: int = 0,
        batch_frames: int = 8) -> dict:
    n_chunks = 96 if quick else 512
    repeats = 2 if quick else 5
    payload_bytes = 4096
    params = {
        "n_chunks": n_chunks, "repeats": repeats,
        "batch_frames": batch_frames, "payload_bytes": payload_bytes,
        "seed": seed, "quick": quick,
    }
    server = WorkerServer().start()
    configs: List[dict] = []
    try:
        for transport in TRANSPORTS:
            for mode, fn_cache, batched in MODES:
                bf = batch_frames if batched else 1
                runs = []
                for r in range(repeats):
                    work = DispatchWork(payload_bytes)
                    unit = _make_unit(
                        transport, f"{transport[0]}{r}", mode_batch=bf,
                        fn_cache=fn_cache,
                        seed=seed * 101 + r * 13 + 1, server=server)
                    runs.append(_drive(unit, n_chunks, work))
                entry = {
                    "transport": transport, "mode": mode,
                    "fn_cache": fn_cache, "batch_frames": bf,
                    "n_chunks": n_chunks,
                }
                for key in ("wall_s", "chunks_per_sec", "dispatch_us",
                            "submit_latency_us", "wire_us"):
                    entry[key] = statistics.median(r[key] for r in runs)
                configs.append(entry)
                print(f"  {transport:8s} {mode:8s}  "
                      f"{entry['chunks_per_sec']:10.0f} chunks/s  "
                      f"dispatch {entry['dispatch_us']:8.1f}us  "
                      f"wire {entry['wire_us']:8.1f}us")
    finally:
        server.stop()

    by_key = {(c["transport"], c["mode"]): c for c in configs}
    speedups = {
        t: (by_key[(t, "batched")]["chunks_per_sec"]
            / max(by_key[(t, "baseline")]["chunks_per_sec"], 1e-12))
        for t in TRANSPORTS
    }
    for t, s in speedups.items():
        print(f"  {t:8s} batched/baseline speedup: {s:.2f}x")

    # latency-aware studies: adaptive width and learned splits on a
    # high-latency (delayed, not just lossy) link
    delay_s = 0.004
    latency_aware = _auto_batch_study(
        n_chunks=n_chunks, repeats=repeats, batch_frames=batch_frames,
        payload_bytes=payload_bytes, max_delay=delay_s, seed=seed)
    latency_aware["transport"] = "flaky-delay"
    latency_aware["max_delay_s"] = delay_s
    latency_aware["split"] = _split_study(
        n_items=120 if quick else 240, repeats=2 if quick else 3,
        warmups=2, per_item_s=0.001, max_delay=0.08, seed=seed)

    return {"schema": BENCH_SCHEMA, "params": params, "configs": configs,
            "speedups": speedups, "latency_aware": latency_aware}


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small chunk count / fewer repeats (CI smoke)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch-frames", type=int, default=8,
                    help="frames coalesced per work_batch in batched mode")
    ap.add_argument("--json", metavar="PATH",
                    help="write the bench_dispatch/v2 artifact here")
    args = ap.parse_args()
    configure_compile_cache()
    result = run(quick=args.quick, seed=args.seed,
                 batch_frames=args.batch_frames)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(result, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
