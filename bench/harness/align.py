"""The program's ``eneac.*`` spans and the device trace, on one clock.

The TPU runtime records a device's ``XLA Modules`` events on a clock of its
own: on a v5e every module is recorded about a millisecond *before* the
host enqueued it.  Causality bounds the shift the device's times need.
Each module carries the ``run_id`` of the host's ``DoEnqueueProgram``
event that enqueued it, so

* ``lo`` = the largest (enqueue start - module start): no module starts
  before its enqueue;
* ``hi`` = the smallest (end of the wait - module end), with the wait's end
  the runtime's ``tpu::System::Execute=>Done`` event (the k-th on a core
  for its k-th module) or the chunk's ``eneac.acc_wait`` span (the k-th
  ACC chunk submitted to a device for its k-th module): no result is ready
  before its module ends.

The device's events are shifted by ``lo``, the least shift that makes
every chunk causal.  Then each idle stretch of the device inside
``bench.window`` is named by the most specific runtime phase open over it
(:data:`PHASE_LABELS`), and the window's modules are counted on shifted
times.  :func:`summary` gathers it all for ``bench/timeline.py``.

The harness's own reduction (``trace.py``) reads bench spans alone, on
the device's clock; this module reuses its parsing constants and interval
union, and reads the extra stats (``run_id``, ``core_id``) and the
``eneac.*`` spans that reduction drops.
"""

from __future__ import annotations

import collections
import dataclasses
import gzip
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import trace as trace_mod

ENQUEUE = "DoEnqueueProgram"
EXECUTE_DONE = "tpu::System::Execute=>Done"
PARALLEL_FOR = "eneac.parallel_for"
# The dispatcher's phases, which tile eneac.parallel_for.
DISPATCH_PHASES = ("eneac.units_start", "eneac.submit", "eneac.bus_wait",
                   "eneac.complete", "eneac.units_close", "eneac.report")
# Spans that name an idle stretch, most specific first, in the harness's
# order (trace.GAP_SPANS): the glue's enqueue inside a submit, a host
# unit's chunk (eneac.unit_exec, on its own thread), the dispatcher's
# phases, parallel_for outside every phase, then the glue around it.
PHASE_LABELS = (("bench.acc_enqueue", "eneac.unit_exec") + DISPATCH_PHASES
                + (PARALLEL_FOR, "bench.assemble", "bench.loop"))


@dataclasses.dataclass
class Module:
    start: float
    end: float
    name: str
    run_id: Optional[int]


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    args: Dict


@dataclasses.dataclass
class Events:
    """The parts of one trace the alignment reads; times in ns."""

    ops: Dict[int, List[trace_mod.Interval]]      # device -> XLA Ops events
    modules: Dict[int, List[Module]]              # device -> XLA Modules events
    spans: List[Span]                             # host spans named eneac.* and bench.*
    enqueues: List[Tuple[float, Optional[int], Optional[int]]]  # start, run_id, device
    dones: List[Tuple[float, Optional[int]]]      # start, core


def load(path) -> Events:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    ev = Events(ops={}, modules={}, spans=[], enqueues=[], dones=[])
    for plane in pd.planes:
        m = trace_mod._DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == trace_mod.OPS_LINE:
                    ev.ops.setdefault(dev, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events)
                elif line.name == trace_mod.MODULES_LINE:
                    ev.modules.setdefault(dev, []).extend(
                        Module(e.start_ns, e.start_ns + e.duration_ns, e.name,
                               dict(e.stats).get("run_id")) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(("eneac.", "bench.")):
                        ev.spans.append(Span(e.start_ns, e.start_ns + e.duration_ns, name,
                                             dict(e.stats)))
                    elif name == ENQUEUE:
                        st = dict(e.stats)
                        ev.enqueues.append((e.start_ns, st.get("run_id"),
                                            st.get("device_ordinal")))
                    elif name == EXECUTE_DONE:
                        ev.dones.append((e.start_ns, dict(e.stats).get("core_id")))
    return ev


@dataclasses.dataclass
class Chunk:
    """One ACC chunk, from its ``eneac.submit`` and ``eneac.acc_wait`` spans."""

    unit: str
    start: int
    submitted: float     # the submit span's start
    ready: float         # the acc_wait span's end


def acc_chunks(spans: Sequence[Span]) -> List[Chunk]:
    """ACC chunks in submit order; the k-th submit of a (unit, start) pairs
    with its k-th wait, since one loop runs after another."""
    subs, waits = collections.defaultdict(list), collections.defaultdict(list)
    for s in spans:
        if s.name == "eneac.acc_wait":
            waits[(s.args.get("unit"), s.args.get("start"))].append(s.end)
        elif s.name == "eneac.submit" and "start" in s.args:
            subs[(s.args.get("unit"), s.args["start"])].append(s.start)
    out = [Chunk(unit, start, t, w)
           for (unit, start), ws in waits.items()
           for t, w in zip(sorted(subs.get((unit, start), ())), sorted(ws))]
    return sorted(out, key=lambda c: c.submitted)


@dataclasses.dataclass
class Shift:
    device: int
    modules: int            # modules of the kernel in the trace
    matched: int            # of them matched to their host enqueue
    by: str                 # "run_id", "order" or "none"
    lo_ns: Optional[float]
    hi_ns: Optional[float]
    acausal: Optional[int]  # matched chunks whose module ends after their wait, shifted

    @property
    def shift_ns(self) -> float:
        return self.lo_ns if self.lo_ns is not None else 0.0


def clock_shift(ev: Events, device: int, kernel: str,
                chunks: Sequence[Chunk] = ()) -> Shift:
    """The shift window of ``device`` from its ``kernel`` modules; ``chunks``
    are the ACC chunks submitted to this device."""
    mods = [m for m in ev.modules.get(device, ()) if kernel in m.name]
    have_ids = bool(mods) and all(m.run_id is not None for m in mods)
    mods.sort(key=lambda m: m.run_id if have_ids else m.start)
    enq = [(t, r) for t, r, d in ev.enqueues if d in (device, None)]
    by, pairs = "none", []
    if have_ids:
        first = {}
        for t, r in sorted(enq):
            first.setdefault(r, t)
        pairs = [(m, first[m.run_id]) for m in mods if m.run_id in first]
        by = "run_id" if pairs else by
    if not pairs and mods and len(enq) == len(mods):
        pairs, by = list(zip(mods, sorted(t for t, _ in enq))), "order"
    lo = max((t - m.start for m, t in pairs), default=None)
    ends = []
    dones = sorted(t for t, core in ev.dones if core in (device, None))
    if mods and len(dones) == len(mods):
        ends += [d - m.end for m, d in zip(mods, dones)]
    acausal = None
    if mods and len(chunks) == len(mods):
        waits = [c.ready - m.end for m, c in zip(mods, chunks)]
        ends += waits
        if lo is not None:
            acausal = sum(w < lo for w in waits)
    hi = min(ends) if ends else None
    return Shift(device=device, modules=len(mods), matched=len(pairs), by=by,
                 lo_ns=lo, hi_ns=hi, acausal=acausal)


def window(ev: Events) -> Tuple[float, float]:
    found = [(s.start, s.end) for s in ev.spans if s.name == trace_mod.WINDOW_SPAN]
    if len(found) != 1:
        raise ValueError(f"expected one {trace_mod.WINDOW_SPAN} span, found {len(found)}")
    return found[0]


def window_modules(ev: Events, device: int, kernel: str, shift_ns: float,
                   lo: float, hi: float) -> Tuple[int, float]:
    """Count and device seconds of the ``kernel`` modules wholly inside
    [lo, hi] once shifted."""
    inside = [m for m in ev.modules.get(device, ()) if kernel in m.name
              and lo <= m.start + shift_ns and m.end + shift_ns <= hi]
    return len(inside), sum(m.end - m.start for m in inside) * 1e-9


def _label_stretches(spans: Sequence[Span], lo: float, hi: float):
    """[lo, hi] cut at every labelling span's edge: (edges, labels)."""
    mine = [s for s in spans if s.name in PHASE_LABELS]
    edges = np.unique(np.array([lo, hi] + [x for s in mine for x in (s.start, s.end)
                                           if lo < x < hi], dtype=float))
    mids = 0.5 * (edges[:-1] + edges[1:])
    labels = np.full(mids.shape, trace_mod.UNANNOTATED, dtype=object)
    free = np.ones(mids.shape, bool)
    for name in PHASE_LABELS:
        iv = sorted((s.start, s.end) for s in mine if s.name == name)
        if not iv:
            continue
        starts = np.array([a for a, _ in iv])
        reach = np.maximum.accumulate(np.array([b for _, b in iv]))
        i = np.searchsorted(starts, mids, side="right") - 1
        covered = (i >= 0) & (reach[np.maximum(i, 0)] > mids) & free
        labels[covered] = name.split(".", 1)[1]
        free &= ~covered
    return edges, labels


def idle_by_phase(ev: Events, shifts: Dict[int, float]) -> Dict[str, float]:
    """Device idle seconds inside ``bench.window`` on shifted times, named
    by the phase open over them; mean over the devices in ``shifts``."""
    lo, hi = window(ev)
    edges, labels = _label_stretches(ev.spans, lo, hi)
    idle: Dict[str, float] = collections.defaultdict(float)
    for dev, shift in shifts.items():
        busy = trace_mod._union([(s + shift, e + shift) for s, e, _ in ev.ops.get(dev, ())],
                                lo, hi)
        # busy time before t, a piecewise linear function of t
        knots = np.array([lo] + [x for iv in busy for x in iv] + [hi], dtype=float)
        lengths = np.diff(knots) * (np.arange(len(knots) - 1) % 2 == 1)
        cum = np.concatenate([[0.0], np.cumsum(lengths)])
        before = np.interp(edges, knots, cum)
        idle_ns = np.diff(edges) - np.diff(before)
        for name, v in zip(labels, idle_ns):
            idle[name] += v * 1e-9 / len(shifts)
    return dict(idle)


def parallel_for_self(ev: Events) -> Tuple[float, float]:
    """Seconds of ``eneac.parallel_for`` in all, and of it under no
    dispatcher phase."""
    pfs = sorted((s.start, s.end) for s in ev.spans if s.name == PARALLEL_FOR)
    phases = sorted((s.start, s.end) for s in ev.spans if s.name in DISPATCH_PHASES)
    starts = np.array([a for a, _ in phases])
    total = own = 0.0
    for a, b in pfs:
        i, j = np.searchsorted(starts, [a, b])
        covered = sum(e - s for s, e in trace_mod._union(phases[i:j], a, b))
        total += b - a
        own += b - a - covered
    return total * 1e-9, own * 1e-9


def acc_host_overhead_us(chunks: Sequence[Chunk], modules: int, module_s: float,
                         lo: float, hi: float) -> Optional[float]:
    """Host microseconds per ACC chunk outside its module's device time:
    (sum over the window's ACC chunks of ready - submitted, less the
    window's module seconds) over the chunks; None when the window's
    module count is not its ACC chunk count."""
    inside = [c for c in chunks if lo <= c.submitted and c.ready <= hi]
    if not inside or modules != len(inside):
        return None
    host_s = sum(c.ready - c.submitted for c in inside) * 1e-9
    return 1e6 * (host_s - module_s) / len(inside)


def summary(ev: Events, devices: Sequence[int], kernel: str,
            unit_device: Dict[str, int]) -> Dict:
    """Everything ``bench/timeline.py`` prints for one traced run."""
    lo, hi = window(ev)
    chunks = acc_chunks(ev.spans)
    out: Dict = {"devices": {}}
    n_mod, mod_s = 0, 0.0
    shifts: Dict[int, float] = {}
    for dev in devices:
        mine = [c for c in chunks if unit_device.get(c.unit, dev) == dev]
        sh = clock_shift(ev, dev, kernel, mine)
        shifts[dev] = sh.shift_ns
        n, s = window_modules(ev, dev, kernel, sh.shift_ns, lo, hi)
        n0, s0 = window_modules(ev, dev, kernel, 0.0, lo, hi)
        n_mod, mod_s = n_mod + n, mod_s + s
        out["devices"][dev] = {
            "modules": sh.modules, "matched": sh.matched, "by": sh.by,
            "shift_window_ms": [None if x is None else x * 1e-6 for x in (sh.lo_ns, sh.hi_ns)],
            "acausal_after_shift": sh.acausal,
            "window_modules": n, "window_module_s": s,
            "window_modules_unshifted": n0, "window_module_s_unshifted": s0,
        }
    pf_s, pf_self_s = parallel_for_self(ev)
    in_window = [c for c in chunks if lo <= c.submitted and c.ready <= hi]
    out.update(
        acc_chunks=len(in_window),
        acc_host_overhead_us=acc_host_overhead_us(chunks, n_mod, mod_s, lo, hi),
        parallel_for_s=pf_s, parallel_for_self_s=pf_self_s,
        parallel_for_self_pct=100.0 * pf_self_s / pf_s if pf_s else None,
        idle_by_phase_s=dict(sorted(idle_by_phase(ev, shifts).items(),
                                    key=lambda kv: -kv[1])),
        idle_by_phase_unshifted_s=dict(sorted(
            idle_by_phase(ev, {d: 0.0 for d in shifts}).items(), key=lambda kv: -kv[1])),
    )
    return out
