"""The program's spans against the device trace on one clock, and the
readers of the per-layer metrics that read ``RunReport.timeline``."""

import types

import pytest

from conftest import ROOT
from harness import align, cell
from harness.registry import Benchmark
from repro.core.trace import ChunkTimes, Timeline

import timeline as timeline_tool

KERNEL = "hotspot_hpc_window"


def test_recorded_chip_trace_clock_shift(recorded_trace):
    """Four HotSpot loops on one v5e: each module is recorded before its
    enqueue, by 1.211 ms at least and 1.446 ms at most."""
    ev = align.load(recorded_trace)
    sh = align.clock_shift(ev, 0, KERNEL)
    assert (sh.modules, sh.matched, sh.by) == (64, 64, "run_id")
    assert sh.lo_ns * 1e-6 == pytest.approx(1.211, abs=0.005)
    assert sh.hi_ns * 1e-6 == pytest.approx(1.446, abs=0.005)
    lo, hi = align.window(ev)
    assert align.window_modules(ev, 0, KERNEL, sh.shift_ns, lo, hi) == (
        64, pytest.approx(0.001208632, rel=1e-9))


def _span(a, b, name, **args):
    return align.Span(a, b, name, args)


def _synthetic(offset=100.0):
    """Two ACC chunks whose device events are recorded ``offset`` ns early.

    True device times: chunk A [150, 300], chunk B [600, 700]."""
    mods = {0: [align.Module(150 - offset, 300 - offset, "jit_k(7)", 1),
                align.Module(600 - offset, 700 - offset, "jit_k(7)", 2)]}
    ops = {0: [(m.start, m.end, "%k = f32[8]{0} custom-call()") for m in mods[0]]}
    spans = [
        _span(0, 1000, "bench.window"), _span(0, 1000, "bench.loop"),
        _span(50, 900, "eneac.parallel_for"),
        _span(50, 100, "eneac.units_start"),
        _span(100, 200, "eneac.submit", unit="acc0", start=0),
        _span(200, 320, "eneac.bus_wait"), _span(320, 340, "eneac.complete"),
        _span(400, 500, "eneac.submit", unit="acc1", start=8),
        _span(500, 720, "eneac.bus_wait"), _span(720, 740, "eneac.complete"),
        _span(740, 850, "eneac.units_close"), _span(850, 900, "eneac.report"),
        _span(200, 320, "eneac.acc_wait", unit="acc0", start=0),
        _span(500, 720, "eneac.acc_wait", unit="acc1", start=8),
        _span(520, 560, "eneac.unit_exec", unit="cc0", start=16),
    ]
    return align.Events(ops=ops, modules=mods, spans=spans,
                        enqueues=[(150.0, 1, 0), (480.0, 2, 0)], dones=[])


@pytest.mark.parametrize("ids", [True, False], ids=["run_id", "order"])
def test_synthetic_offset_is_recovered(ids):
    ev = _synthetic()
    if not ids:
        for m in ev.modules[0]:
            m.run_id = None
    sh = align.clock_shift(ev, 0, "jit_k", align.acc_chunks(ev.spans))
    assert sh.by == ("run_id" if ids else "order") and sh.matched == 2
    assert sh.lo_ns == pytest.approx(100.0)        # chunk A's enqueue starts its module
    assert sh.hi_ns == pytest.approx(120.0)        # both waits end 20 ns after their module
    assert sh.acausal == 0


def test_idle_named_by_the_phase_open_over_it():
    ev = _synthetic()
    idle = align.idle_by_phase(ev, {0: 100.0})
    ns = {k: pytest.approx(v * 1e-9) for k, v in {
        "loop": 150, "units_start": 50, "submit": 150, "bus_wait": 100, "unit_exec": 40,
        "complete": 40, "parallel_for": 60, "units_close": 110, "report": 50}.items()}
    assert idle == ns
    # on unshifted times the device looks busy during the first submit
    assert align.idle_by_phase(ev, {0: 0.0})["submit"] == pytest.approx(150e-9 - 50e-9)


def test_parallel_for_self_time():
    total, own = align.parallel_for_self(_synthetic())
    assert total == pytest.approx(850e-9) and own == pytest.approx(60e-9)


def test_acc_host_overhead():
    ev = _synthetic()
    chunks = align.acc_chunks(ev.spans)
    assert [(c.unit, c.start, c.submitted, c.ready) for c in chunks] == [
        ("acc0", 0, 100, 320), ("acc1", 8, 400, 720)]
    lo, hi = align.window(ev)
    n, s = align.window_modules(ev, 0, "jit_k", 100.0, lo, hi)
    assert (n, s) == (2, pytest.approx(250e-9))
    # (220 + 320 - 250) ns over 2 chunks
    assert align.acc_host_overhead_us(chunks, n, s, lo, hi) == pytest.approx(0.145)
    assert align.acc_host_overhead_us(chunks, 1, s, lo, hi) is None
    summary = align.summary(ev, [0], "jit_k", {"acc0": 0, "acc1": 0})
    assert summary["acc_host_overhead_us"] == pytest.approx(0.145)
    assert summary["devices"][0]["shift_window_ms"] == [pytest.approx(1e-4), pytest.approx(1.2e-4)]
    assert summary["parallel_for_self_pct"] == pytest.approx(100 * 60 / 850)


def _chunk(unit, start, submitted, ready, posted, drained):
    return ChunkTimes(unit, start, start + 8, submitted, submitted + 10, ready, posted, drained)


def _readings(timelines):
    reports = [types.SimpleNamespace(timeline=t) for t in timelines]
    return cell.Readings(reports=reports, acc_units=["acc0", "acc1"], cc_units=["cc0"],
                         kernel=KERNEL, acc_bound_s=0.0, trace=None)


def _timeline(chunks, wakeups=0, **phases):
    return Timeline(phase_s=phases, chunks=chunks, wakeups=wakeups or len(chunks),
                    drained=len(chunks))


LOOP_A = _timeline(
    [_chunk("acc0", 0, 0, 5_000, 6_000, 9_000),
     _chunk("cc0", 16, 1_000, 20_000, 21_000, 22_000),
     _chunk("acc0", 8, 11_000, 15_000, 15_500, 17_000),      # 6 us after acc0's first
     _chunk("acc1", 24, 2_000, 30_000, 30_100, 30_200)],
    wakeups=3, units_start=1e-3, submit=2e-3, units_close=2e-4, report=1e-4)
LOOP_B = _timeline(
    [_chunk("acc1", 0, 0, 1_000, 2_000, 3_000),
     _chunk("acc1", 8, 5_000, 6_000, 7_000, 8_000)],           # 4 us after acc1's first
    units_start=3e-3, units_close=0.0, report=2e-4)


@pytest.mark.parametrize("name,value", [
    ("loop_fixed_us", (1e-3 + 2e-4 + 1e-4 + 3e-3 + 2e-4) * 1e6 / 2),
    ("bus_wake_us", (3 + 1 + 1.5 + 0.1 + 1 + 1) / 6),
    ("acc_refill_us", (6 + 4) / 2),
    ("completions_per_wake", (4 + 2) / (3 + 2)),
])
def test_timeline_metric_readers(name, value):
    read = Benchmark(ROOT).metric_reader(name)
    assert read(_readings([LOOP_A, LOOP_B])) == pytest.approx(value)
    # a runtime without RunReport.timeline (the parent of this reader): nothing
    assert read(_readings([None, None])) is None
    assert read(cell.Readings(reports=[types.SimpleNamespace()], acc_units=["acc0"],
                              cc_units=[], kernel=KERNEL, acc_bound_s=0.0,
                              trace=None)) is None


def test_refill_needs_a_second_chunk_on_a_unit():
    one_each = _timeline([_chunk("acc0", 0, 0, 1, 2, 3), _chunk("acc1", 8, 0, 1, 2, 3)])
    assert Benchmark(ROOT).metric_reader("acc_refill_us")(_readings([one_each])) is None


def test_timeline_tool_refuses_without_a_chip(capsys):
    assert timeline_tool.main(["--workload", "hotspot-paper.hybrid", "--seed", "1",
                               "--seconds", "1"]) == 2
    assert "no accelerator" in capsys.readouterr().err
