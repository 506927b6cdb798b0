"""A configuration, a traffic mix and a per-layer metric added as new files
and entries are found by name, with no edit to a file already there."""

import hashlib
import json
from pathlib import Path

import pytest

from conftest import ROOT, copy_benchmark, make_tiny_checkout
from harness import cell
from harness import trace as T
from harness.registry import Benchmark


def _digests(root: Path):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and p.name != "BENCHMARK.json"}


def test_cell_mix_and_metric_added_by_name(tmp_path, monkeypatch, recorded_trace):
    src = copy_benchmark(ROOT, tmp_path / "src")
    before = _digests(src)
    bench = src / "bench"
    cfg = json.loads((bench / "configs" / "hotspot-paper.json").read_text())
    cfg.update(name="hotspot-small", grid=256, sim_steps=4, rehearsal={"grid": 32})
    (bench / "configs" / "hotspot-small.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "hybrid-c128.json").read_text())
    mix.update(name="acc-two-c64", acc_devices=[0, 1], cc_units=0, acc_chunk=64,
               rehearsal={"acc_chunk": 8})
    (bench / "traffic" / "acc-two-c64.json").write_text(json.dumps(mix))
    (bench / "metrics" / "loops_traced.py").write_text(
        "def read(r):\n    return float(len(r.reports))\n")
    spec = json.loads((src / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "hotspot-small", "source": "a test",
                            "file": "bench/configs/hotspot-small.json",
                            "reduced": ["grid"], "why": "a test"})
    spec["workloads"].append({"name": "hotspot-small.acc-two", "config": "hotspot-small",
                              "traffic": "acc-two-c64", "chips": 1, "why": "a test"})
    spec["per_layer"].append({"name": "loops_traced", "unit": "loops", "better": "higher",
                              "source": "program_counter", "layer": "runtime",
                              "moves": "makespan_ms", "workloads": ["hotspot-small.acc-two"]})
    (src / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digests(src)
    assert all(after[p] == d for p, d in before.items())

    root = make_tiny_checkout(tmp_path / "tiny", src)
    tiny = Benchmark(root)
    assert tiny.config("hotspot-small")["grid"] == 32
    assert tiny.traffic("acc-two-c64")["acc_chunk"] == 8
    res, _ = cell.run_cell(root, "hotspot-small.acc-two", 3, 0.2, False, need_chip=False)
    assert res["correct"] and set(res["metrics"]) == {"makespan_ms", "makespan_p95_ms", "setup_s"}

    monkeypatch.setattr(cell.trace_mod, "read_window",
                        lambda d, devs: T.reduce(T.load(recorded_trace), [0]))
    res, _ = cell.run_cell(root, "hotspot-small.acc-two", 3, 0.2, True, need_chip=False)
    assert res["correct"]
    assert res["metrics"]["loops_traced"]["value"] == res["attempted"]
    assert "acc_rows_pct" not in res["metrics"]   # the metric does not list this cell


def test_a_file_without_rehearsal_sizes_is_named(tmp_path):
    src = copy_benchmark(ROOT, tmp_path / "src")
    path = src / "bench" / "traffic" / "hybrid-c128.json"
    mix = json.loads(path.read_text())
    del mix["rehearsal"]
    path.write_text(json.dumps(mix))
    with pytest.raises(KeyError, match="hybrid-c128.json has no 'rehearsal'"):
        make_tiny_checkout(tmp_path / "tiny", src)


def test_a_per_layer_metric_must_list_its_cells(tmp_path):
    src = copy_benchmark(ROOT, tmp_path)
    spec = json.loads((src / "BENCHMARK.json").read_text())
    del spec["per_layer"][0]["workloads"]
    (src / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(KeyError, match="no 'workloads' list"):
        Benchmark(src).per_layer(spec["workloads"][0]["name"])
