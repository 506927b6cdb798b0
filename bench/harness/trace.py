"""The profiler trace of a run, reduced to what the per-layer metrics read.

Capture: :func:`capture` starts JAX's profiler with the Python tracer off,
and the benchmark writes its own host spans (``bench.*``) with
``jax.profiler.TraceAnnotation``.  Reduction, from the ``.xplane.pb``:

* device busy time: the union of the intervals of the ops on each device's
  ``XLA Ops`` line, inside the ``bench.window`` span; idle is the rest;
* device time per jitted module, from the ``XLA Modules`` line, by name;
* the device ops that took most time, and the device's idle time split by
  what the host was doing: each stretch of a gap is named by the
  benchmark's host spans open over it (under none of them it is
  ``runtime (unannotated)``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gzip
import re
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
# Host spans that name an idle gap, most specific first.
GAP_SPANS = ("bench.acc_enqueue", "bench.cc_chunk", "bench.assemble", "bench.loop")
UNANNOTATED = "runtime (unannotated)"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# "%name.3 = f32[8,128]{1,0} fusion(...), kind=kLoop" -> ("name", "fusion")
_HLO_OP = re.compile(r"^%?([^ ]+?)(?:\.\d+)? = .*? ([a-z][\w\-]*)\(")

Interval = Tuple[float, float, str]   # start_ns, end_ns, name


def op_label(name: str) -> str:
    """An HLO op event's instruction name without its number, and its
    opcode: the key device time is summed under."""
    m = _HLO_OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


@dataclasses.dataclass
class TraceData:
    """The parts of one trace the reduction reads."""

    ops: Dict[int, List[Interval]]        # device id -> op events
    modules: Dict[int, List[Interval]]    # device id -> jitted-module events
    spans: List[Interval]                 # host spans named bench.*


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the body into ``log_dir``; yields nothing."""
    import jax.profiler as prof

    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    prof.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        prof.stop_trace()


def find_xspace(log_dir: str) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path) -> TraceData:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(dev, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events if e.name.startswith("bench."))
    return TraceData(ops=ops, modules=modules, spans=spans)


def _union(intervals: Sequence[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Disjoint sorted intervals covering the given ones, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class _OpenSpans:
    """Which span names are open at a time, from the host spans."""

    def __init__(self, spans: Sequence[Interval]) -> None:
        self._by_name = {}
        for name in GAP_SPANS:
            iv = sorted((s, e) for s, e, n in spans if n == name)
            if iv:
                starts = np.array([s for s, _ in iv])
                reach = np.maximum.accumulate(np.array([e for _, e in iv]))
                self._by_name[name] = (starts, reach)

    def label(self, t: float) -> str:
        names = []
        for name in GAP_SPANS:
            if name not in self._by_name:
                continue
            starts, reach = self._by_name[name]
            i = int(np.searchsorted(starts, t, side="right")) - 1
            if i >= 0 and reach[i] > t:
                names.append(name[len("bench."):])
        return "+".join(names) if names else UNANNOTATED


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: Dict[int, float]            # per device
    module_s: Dict[str, float]          # module event name -> seconds, all devices
    op_s: Dict[str, float]              # op name -> seconds, all devices
    idle_s: Dict[str, float]            # gap label -> seconds, mean over devices

    def kernel_seconds(self, kernel: str) -> float:
        """Device seconds of the jitted modules whose name holds ``kernel``."""
        return sum(s for name, s in self.module_s.items() if kernel in name)

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)


def _labelled_stretches(spans: Sequence[Interval], lo: float,
                        hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut at every span edge, each stretch with its label."""
    open_spans = _OpenSpans(spans)
    edges = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e) if lo < x < hi})
    return [(a, b, open_spans.label(0.5 * (a + b))) for a, b in zip(edges, edges[1:])]


def reduce(td: TraceData, devices: Sequence[int]) -> Reduction:
    """Reduce the events inside the ``bench.window`` span on ``devices``."""
    windows = [(s, e) for s, e, n in td.spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0]
    missing = [d for d in devices if d not in td.ops]
    if missing:
        raise ValueError(f"no {OPS_LINE} events for devices {missing}")
    stretches = _labelled_stretches(td.spans, lo, hi)
    busy: Dict[int, float] = {}
    op_s: Dict[str, float] = collections.defaultdict(float)
    module_s: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for dev in devices:
        for s, e, name in td.ops[dev]:
            if lo <= s and e <= hi:
                op_s[op_label(name)] += (e - s) * 1e-9
        for s, e, name in td.modules.get(dev, ()):
            if lo <= s and e <= hi:
                module_s[re.sub(r"\(\d+\)$", "", name)] += (e - s) * 1e-9
        merged = _union([(s, e) for s, e, _ in td.ops[dev]], lo, hi)
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2]) if ge > gs]
        i = 0
        for gs, ge in gaps:   # both lists sorted and disjoint: one sweep
            while i < len(stretches) and stretches[i][1] <= gs:
                i += 1
            j = i
            while j < len(stretches) and stretches[j][0] < ge:
                a, b, name = stretches[j]
                idle[name] += (min(b, ge) - max(a, gs)) * 1e-9 / len(devices)
                j += 1
    return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy, module_s=dict(module_s),
                     op_s=dict(op_s), idle_s=dict(idle))


def breakdown(red: Reduction, top: int = 10) -> Dict[str, list]:
    """The ``breakdown`` of a traced run's result line."""
    def largest(d: Dict[str, float]) -> list:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": largest(red.op_s), "idle_gaps": largest(red.idle_s)}


def read_window(log_dir: str, devices: Sequence[int]) -> Reduction:
    return reduce(load(find_xspace(log_dir)), devices)
