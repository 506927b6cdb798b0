"""The reduction from a profiler trace to device busy time, module time,
top ops and named idle gaps."""

import pytest

from harness import trace as T


def test_recorded_chip_trace(recorded_trace):
    """Four HotSpot loops traced on one TPU v5e (4 ACC units, 4 CC units)."""
    red = T.reduce(T.load(recorded_trace), [0])
    assert red.window_s == pytest.approx(0.219166811, rel=1e-9)
    assert red.busy_s[0] == pytest.approx(0.00118442, rel=1e-9)
    assert red.module_s == {"jit_hotspot_hpc_window": pytest.approx(0.001208632, rel=1e-9)}
    assert red.kernel_seconds("hotspot_hpc_window") == pytest.approx(0.001208632, rel=1e-9)
    assert red.kernel_seconds("no_such_kernel") == 0
    assert sum(red.idle_s.values()) + red.busy_s[0] == pytest.approx(red.window_s)
    assert max(red.idle_s, key=red.idle_s.get) == "cc_chunk+loop"
    assert set(red.idle_s) <= {"cc_chunk+loop", "acc_enqueue+cc_chunk+loop",
                               "acc_enqueue+loop", "loop", "assemble+loop", T.UNANNOTATED}
    assert red.idle_s["assemble+loop"] == pytest.approx(0.050490715, rel=1e-6)
    bd = T.breakdown(red)
    assert bd["device_ops"][0][0] == "hotspot_hpc_window custom-call"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduce_synthetic():
    ops = [(5.0, 20.0, "%a.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"),
           (15.0, 30.0, "%b = f32[8]{0} custom-call(f32[8]{0} %y)"),
           (50.0, 60.0, "%a.2 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"),
           (90.0, 130.0, "%late = f32[8]{0} copy(f32[8]{0} %z)")]
    spans = [(10.0, 110.0, "bench.window"), (10.0, 100.0, "bench.loop"),
             (32.0, 48.0, "bench.acc_enqueue"), (65.0, 85.0, "bench.cc_chunk"),
             (70.0, 80.0, "bench.cc_chunk")]
    td = T.TraceData(ops={0: ops}, modules={0: [(5.0, 30.0, "jit_k(123)"), (50.0, 60.0, "jit_k(123)")]},
                     spans=spans)
    red = T.reduce(td, [0])
    assert red.window_s == pytest.approx(100e-9)
    # [10,30] and [50,60] and [90,110] are busy inside the window
    assert red.busy_s[0] == pytest.approx(50e-9)
    assert red.module_s == {"jit_k": pytest.approx(10e-9)}   # only the one inside
    # gap [30, 50]: enqueue open over [32, 48]; gap [60, 90]: CC over [65, 85]
    assert red.idle_s == {"loop": pytest.approx(14e-9),
                          "acc_enqueue+loop": pytest.approx(16e-9),
                          "cc_chunk+loop": pytest.approx(20e-9)}
    # ops count where they lie wholly inside the window
    assert red.op_s == {"a fusion": pytest.approx(10e-9), "b custom-call": pytest.approx(15e-9)}


def test_gap_outside_every_span_is_unannotated():
    td = T.TraceData(ops={0: [(0.0, 10.0, "x")]}, modules={},
                     spans=[(0.0, 30.0, "bench.window")])
    assert T.reduce(td, [0]).idle_s == {T.UNANNOTATED: pytest.approx(20e-9)}


def test_reduce_refuses_a_trace_without_device_ops():
    td = T.TraceData(ops={}, modules={}, spans=[(0.0, 1.0, "bench.window")])
    with pytest.raises(ValueError, match="no XLA Ops events"):
        T.reduce(td, [0])


def test_op_label():
    assert T.op_label("%hotspot_hpc_window.1 = f32[144,2048]{1,0:T(8,128)} custom-call("
                      "f32[144,2048]{1,0} %x), custom_call_target=\"tpu_custom_call\"") \
        == "hotspot_hpc_window custom-call"
    assert T.op_label("%copy-start = (f32[2]{0:T(8)S(1)}, u32[]{:S(2)}) copy-start(f32[2] %t)") \
        == "copy-start copy-start"
    assert T.op_label("something else") == "something else"
