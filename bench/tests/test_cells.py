"""Every cell at a tiny size on the CPU, through the same glue, window and
check as on the chip; and the shape of the result line."""

import json

import pytest

from conftest import ROOT
from harness import cell
from harness import trace as T

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_correct_at_tiny_size(tiny_root, workload):
    res, log = cell.run_cell(tiny_root, workload, 2**31 + 7, 0.3, False, need_chip=False)
    assert list(res) == KEYS
    assert res["correct"], (res["checks"], log)
    if "hybrid" in workload:   # both kinds of unit took rows
        assert "on CC rows 0.000000e+00" not in "\n".join(log), log
    assert res["attempted"] >= 1 and res["failed"] == 0
    e2e = {m["name"]: m["unit"] for m in SPEC["end_to_end"]}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == e2e
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_same_seed_same_inputs(tiny_root):
    """The compared number depends only on the inputs, which the seed fixes."""
    a, _ = cell.run_cell(tiny_root, WORKLOADS[0], 99, 0.1, False, need_chip=False)
    b, _ = cell.run_cell(tiny_root, WORKLOADS[0], 99, 0.1, False, need_chip=False)
    assert a["checks"]["max_row_rel_err"] == b["checks"]["max_row_rel_err"]


@pytest.fixture
def chip_trace(monkeypatch, recorded_trace):
    """The CPU has no device plane: the recorded chip trace stands in."""
    def read_window(log_dir, devices):
        return T.reduce(T.load(recorded_trace), [0])
    monkeypatch.setattr(cell.trace_mod, "read_window", read_window)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traced_result_line(tiny_root, chip_trace, workload):
    """A traced run reports every per-layer metric that lists the cell."""
    res, _ = cell.run_cell(tiny_root, workload, 5, 0.3, True, need_chip=False)
    assert list(res) == ["correct", "attempted", "failed", "breakdown"] + KEYS[3:]
    assert res["correct"]
    layer = {m["name"] for m in SPEC["per_layer"] if workload in m["workloads"]}
    # no peaks for a CPU device, so no roofline share: the reader returns nothing
    assert set(res["metrics"]) == {n for n in layer if not n.endswith("_roofline")}
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    m = res["metrics"]
    if "acc_rows_pct" in layer:   # cells with CC units
        assert 0 < m["acc_rows_pct"]["value"] <= 100
    assert 0 <= m["device_idle_pct"]["value"] <= 100


def test_main_refuses_without_a_chip(capsys):
    assert cell.main(["--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1"]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no accelerator" in out.err

