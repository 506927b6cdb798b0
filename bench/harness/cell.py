"""One run of one cell: set-up, a measured window of loops, the check.

A *loop* is one ``HeteroRuntime.parallel_for`` over the whole iteration
space plus the assembly of the ACC units' rows into the host result.  The
window runs loops back to back (a closed loop: one caller doing repeated
solves) for ``seconds``, then closes at the end of the loop that crossed
the deadline.  Everything before the window is set-up: generating the
problem, placing operands on each ACC device, compiling and warming up.

After the window, with the device state freed, the assembled results of a
few loops drawn from the seed are compared with the configuration's plain
float64 reference.

Loops are numbered from 0, the first warm-up loop, on through the window,
and the glue's ``begin_loop(loop)`` is told each loop's number.  A problem
module whose answer changes from loop to loop declares ``PER_LOOP = True``:
its inputs for every loop are made in ``generate``, before the window, and
``begin_loop`` only hands that loop's inputs over, as a caller would.  The
check then compares each sampled loop with ``reference(prob, loop=i)`` of
its own number ``i``, one reference at a time.  Without the flag the
answer is the same in every loop, and one ``reference(prob)`` serves all.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import random
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import peaks as peaks_mod
from . import trace as trace_mod
from .registry import Benchmark


# Loops run before the window, to warm every program and path up, and loops
# of the window whose results the check compares.
WARMUP_LOOPS = 2
SAMPLED_LOOPS = 4


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise NoChip(f"JAX could not start: {exc}") from None
    if devices[0].platform == "cpu":
        raise NoChip("JAX finds only CPU devices; the benchmark measures an "
                     "accelerator and never falls back to the CPU")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devices)}")
    return devices


def use_compile_cache() -> str:
    """The program's compile cache, keeping every compile however short."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts JAX's backend compiles and persistent-cache misses."""

    def __init__(self) -> None:
        from jax import monitoring

        self.compiles = 0
        self.misses = 0
        self._mon = monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


def _annotated(name: str, fn):
    from jax.profiler import TraceAnnotation

    def work(chunk):
        with TraceAnnotation(name):
            return fn(chunk)
    return work


def _tiles(coverage, n: int) -> bool:
    return bool(coverage) and coverage[0][0] == 0 and coverage[-1][1] == n and all(
        b == c for (_, b), (c, _) in zip(coverage, coverage[1:]))


@dataclasses.dataclass
class Readings:
    """What the per-layer metric readers read."""

    reports: list                    # RunReport of every loop in the window
    acc_units: List[str]
    cc_units: List[str]
    kernel: str                      # the jitted module ACC chunks run
    acc_bound_s: float               # least chip time for the ACC rows' work
    trace: Optional[trace_mod.Reduction]

    def kernel_roofline_pct(self, kernel: str) -> Optional[float]:
        if self.trace is None or kernel != self.kernel or self.acc_bound_s <= 0:
            return None
        t = self.trace.kernel_seconds(kernel)
        return 100.0 * self.acc_bound_s / t if t > 0 else None


def row_errors(out: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: max |out - ref| over max |ref|; a row not written is inf."""
    d = np.abs(out.astype(np.float64) - ref)
    d[np.isnan(d)] = np.inf
    scale = np.maximum(np.max(np.abs(ref), axis=1), np.finfo(np.float64).tiny)
    return np.max(d, axis=1) / scale


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None,
             need_chip: bool = True) -> Tuple[Dict, List[str]]:
    """Run one cell once; returns (the result line, the stderr lines).

    ``need_chip=False`` skips the look for an accelerator and the
    compilation cache (the CPU tests drive the rest of a run that way).
    """
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import HeteroRuntime, WallClock, WorkerKind
    from repro.core.backends import make_backend

    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    bench = Benchmark(root)
    wl = bench.workload(workload)
    cfg = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    log: List[str] = [f"imports {time.perf_counter() - t_start:.3f} s"]
    if need_chip:
        t = time.perf_counter()
        devices = require_chips(wl["chips"])
        log.append(f"devices {time.perf_counter() - t:.3f} s")
        log.append(f"compile cache {use_compile_cache()}")
    else:
        devices = jax.devices()
    kind = devices[0].device_kind
    peaks = peaks_mod.peaks_for(kind) if need_chip else None
    problem = bench.problem(cfg["problem"])
    glue_mod = bench.glue(cfg["problem"])
    counter = _CompileCounter()
    try:
        t0 = time.perf_counter()
        prob = problem.generate(cfg, seed)
        t1 = time.perf_counter()
        glue = glue_mod.make(prob, mix["acc_chunk"])
        t2 = time.perf_counter()
        acc_units = [make_backend(f"jax:{d}", f"acc{i}")
                     for i, d in enumerate(mix["acc_devices"])]
        acc_names = [u.name for u in acc_units]
        used = sorted({u.device for u in acc_units}, key=lambda d: d.id)
        for dev in used:
            glue["place"](dev)
        t3 = time.perf_counter()
        log.append(f"set-up: generate {t1 - t0:.3f} s, program host ops "
                   f"{t2 - t1:.3f} s, place+compile+warm {t3 - t2:.3f} s "
                   f"on {len(used)} device(s)")
        rt = HeteroRuntime(clock=WallClock())
        for u in acc_units:
            rt.register_unit(u.name, WorkerKind.ACC, backend=u,
                             work_fn=_annotated("bench.acc_enqueue", glue["acc_work"](u.device)))
        cc_names = [f"cc{i}" for i in range(mix["cc_units"])]
        for name in cc_names:
            rt.register_unit(name, WorkerKind.CC, backend="thread",
                             work_fn=_annotated("bench.cc_chunk", glue["cc_work"]))

        def one_loop(loop: int):
            with TraceAnnotation("bench.loop"):
                glue["begin_loop"](loop)
                rep = rt.parallel_for(num_items=prob.rows, policy=mix["policy"],
                                      engine=mix["engine"], acc_chunk=mix["acc_chunk"])
                with TraceAnnotation("bench.assemble"):
                    result, acc_spans = glue["assemble"]()
            return rep, result, acc_spans

        for loop in range(WARMUP_LOOPS):
            one_loop(loop)
        compiles_setup, misses_setup = counter.compiles, counter.misses

        rng = random.Random(seed)
        k = SAMPLED_LOOPS
        sample: List[Tuple] = []
        times: List[float] = []
        reports: list = []
        acc_spans_all: List[Tuple[int, int]] = []
        failed = 0
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
            with trace_mod.capture(tmp) if trace else contextlib.nullcontext():
                w0 = time.perf_counter()
                setup_s = w0 - t_start
                deadline = w0 + seconds
                with TraceAnnotation(trace_mod.WINDOW_SPAN):
                    while True:
                        a = time.perf_counter()
                        rep, out, acc_spans = one_loop(WARMUP_LOOPS + len(times))
                        b = time.perf_counter()
                        times.append(b - a)
                        reports.append(rep)
                        acc_spans_all.extend(acc_spans)
                        if not _tiles(rep.coverage, prob.rows):
                            failed += 1
                        i = len(times) - 1
                        j = i if i < k else rng.randrange(i + 1)
                        if j < k:
                            entry = (WARMUP_LOOPS + i, out, acc_spans)
                            if j < len(sample):
                                sample[j] = entry
                            else:
                                sample.append(entry)
                        if b >= deadline:
                            break
                window_s = b - w0
                compiles_window = counter.compiles - compiles_setup
            if trace:
                red = trace_mod.read_window(tmp, [d.id for d in used])
    finally:
        counter.close()

    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    glue["release"]()
    del glue, rt, acc_units
    gc.collect()

    t_ref = time.perf_counter()
    per_loop = getattr(problem, "PER_LOOP", False)
    ref = None if per_loop else problem.reference(prob)
    errs, acc_err, cc_err, unwritten = [], 0.0, 0.0, 0
    for loop, out, spans in sample:
        if per_loop:
            ref = None   # free the last loop's reference before the next
            ref = problem.reference(prob, loop=loop)
        e = row_errors(out, ref)
        unwritten += int(np.isinf(e).sum())
        on_acc = np.zeros(e.shape[0], bool)
        for s, t in spans:
            on_acc[s:t] = True
        errs.append(float(e.max()))
        acc_err = max(acc_err, float(e[on_acc].max(initial=0.0)))
        cc_err = max(cc_err, float(e[~on_acc].max(initial=0.0)))
    err = max(errs) if errs else float("inf")
    limit = cfg["limits"]["max_row_rel_err"]
    checks = {
        "max_row_rel_err": {"value": err, "limit": limit},
        "unwritten_rows": {"value": unwritten, "limit": 0},
        "failed_loops": {"value": failed, "limit": 0},
    }
    correct = bool(times) and all(c["value"] <= c["limit"] for c in checks.values())
    q = np.percentile(times, [5, 25, 50, 75, 95]) * 1e3 if times else []
    log.append(f"loop ms p5/p25/p50/p75/p95: {' / '.join(f'{v:.3f}' for v in q)}")
    log.append(f"window: {len(times)} loops in {window_s:.3f} s; compiles in window "
               f"{compiles_window}; backend compiles in set-up {compiles_setup}, "
               f"cache misses {misses_setup}; reference {time.perf_counter() - t_ref:.3f} s")
    log.append(f"compared loops {[i for i, _, _ in sample]} "
               f"({'each with its own reference' if per_loop else 'with one reference'}): "
               f"max_row_rel_err on ACC "
               f"rows {acc_err:.6e}, on CC rows {cc_err:.6e}")

    n = len(times)
    metrics: Dict[str, Dict] = {}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result: Dict = {"correct": correct, "attempted": n, "failed": failed}
    if not trace:
        values = {"makespan_ms": 1e3 * window_s / n,
                  "makespan_p95_ms": 1e3 * float(np.percentile(times, 95)),
                  "setup_s": setup_s}
        for m in bench.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ops = nbytes = bound = 0.0
        for s, t in acc_spans_all:
            o, nb = problem.chunk_work(prob, s, t)
            ops, nbytes = ops + o, nbytes + nb
            if peaks is not None:
                bound += peaks_mod.bound_seconds(o, nb, peaks)[0]
        readings = Readings(reports=reports, acc_units=acc_names, cc_units=cc_names,
                            kernel=glue_mod.KERNEL, acc_bound_s=bound, trace=red)
        for m in bench.per_layer(workload):
            v = bench.metric_reader(m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.mean_busy_s()
        device["window_s"] = red.window_s
        result["breakdown"] = trace_mod.breakdown(red)
        term = peaks_mod.bound_seconds(ops, nbytes, peaks)[1] if peaks else "n/a"
        log.append(f"ACC rows in the traced window: {ops:.6e} ops, {nbytes:.6e} bytes, "
                   f"bounded by {term}; module device seconds {red.module_s}")
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result, log


def main(argv: Optional[Sequence[str]] = None, *, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    try:
        result, log = run_cell(root, args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=t_start)
    except NoChip as exc:
        print(f"bench: no accelerator for this cell: {exc}", file=sys.stderr)
        return 2
    for line in log:
        print(f"bench: {line}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"bench: check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
