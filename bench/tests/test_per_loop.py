"""A problem whose answer changes from loop to loop is checked loop by loop.

The glue's ``begin_loop`` is told each loop's number, and a problem module
that sets ``PER_LOOP`` has every compared loop checked against
``reference(prob, loop=i)`` of its own number.  A small such problem and
its glue are written into a tiny checkout here: loop ``i`` doubles the
``i mod operands``-th of a few operands that ``generate`` makes.  Its
answer is read correct, and comes out not correct when the glue hands
over the loop before's operand or the reference is taken a loop early.
The problems without the flag still compute one reference a run.
"""

import json

import pytest

from conftest import ROOT, copy_benchmark, make_tiny_checkout
from control import readings
from harness import cell
from harness.registry import load_file

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "doubled-small.acc"

PROBLEM = '''
import dataclasses

import numpy as np

PER_LOOP = True


@dataclasses.dataclass(frozen=True)
class Problem:
    cfg: dict
    x: np.ndarray   # (operands, rows, cols) float32: loop i takes x[i % operands]

    @property
    def rows(self):
        return self.x.shape[1]


def generate(cfg, seed):
    rng = np.random.default_rng(seed)
    shape = (cfg["operands"], cfg["rows"], cfg["cols"])
    return Problem(cfg=cfg, x=rng.standard_normal(shape, dtype=np.float32))


def reference(prob, *, loop):
    return 2.0 * prob.x[loop % len(prob.x)].astype(np.float64)


def control(prob, device, *, loop):
    import jax
    import jax.numpy as jnp

    x = jax.device_put(prob.x[loop % len(prob.x)], device).astype(jnp.bfloat16)
    return np.asarray((2 * x).astype(jnp.float32), np.float64)


def chunk_work(prob, start, stop):
    cells = (stop - start) * prob.x.shape[2]
    return cells, 8 * cells
'''

GLUE = '''
import jax
import numpy as np

KERNEL = "doubled"
CC_OP = "doubled_rows"


@jax.jit
def doubled(xs, i):
    return 2 * xs[i]


def doubled_rows(x, start, stop):
    return 2 * x[start:stop]


def make(prob, acc_chunk):
    placed, loop = {}, {}

    def place(device):
        placed[device] = jax.device_put(prob.x, device)
        jax.block_until_ready(doubled(placed[device], np.int32(0)))

    def acc_work(device):
        def work(chunk):
            out = doubled(placed[device], np.int32(loop["operand"]))
            loop["acc"][(chunk.start, chunk.stop)] = out
            return out
        return work

    def cc_work(chunk):
        loop["result"][chunk.start:chunk.stop] = doubled_rows(
            prob.x[loop["operand"]], chunk.start, chunk.stop)

    def begin_loop(i):
        loop["operand"] = i % len(prob.x)
        loop["result"] = np.full(prob.x.shape[1:], np.nan, np.float32)
        loop["acc"] = {}

    def assemble():
        result = loop["result"]
        for (s, e), out in loop["acc"].items():
            result[s:e] = np.asarray(out)[s:e]
        return result, sorted(loop["acc"])

    def release():
        placed.clear()
        loop.clear()

    return dict(place=place, acc_work=acc_work, cc_work=cc_work,
                begin_loop=begin_loop, assemble=assemble, release=release)
'''


@pytest.fixture(scope="module")
def per_loop_root(tmp_path_factory):
    """A tiny checkout with the cell ``doubled-small.acc`` added."""
    src = copy_benchmark(ROOT, tmp_path_factory.mktemp("src"))
    bench = src / "bench"
    (bench / "problems" / "doubled.py").write_text(PROBLEM)
    (bench / "glue" / "doubled.py").write_text(GLUE)
    (bench / "configs" / "doubled-small.json").write_text(json.dumps(
        {"name": "doubled-small", "problem": "doubled", "operands": 5, "rows": 96,
         "cols": 8, "limits": {"max_row_rel_err": 1e-3}, "rehearsal": {}}))
    mix = json.loads((bench / "traffic" / "hybrid-c128.json").read_text())
    mix.update(name="doubled-c16", acc_chunk=16, rehearsal={})
    (bench / "traffic" / "doubled-c16.json").write_text(json.dumps(mix))
    spec = json.loads((src / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "doubled-small", "source": "a test",
                            "file": "bench/configs/doubled-small.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": CELL, "config": "doubled-small",
                              "traffic": "doubled-c16", "chips": 1, "why": "a test"})
    (src / "BENCHMARK.json").write_text(json.dumps(spec))
    return make_tiny_checkout(tmp_path_factory.mktemp("tiny"), src)


def _modules(root):
    return (load_file(root / "bench" / "problems" / "doubled.py"),
            load_file(root / "bench" / "glue" / "doubled.py"))


def test_answer_that_changes_each_loop_reads_correct(per_loop_root):
    res, log = cell.run_cell(per_loop_root, CELL, 2**31 + 11, 0.2, False, need_chip=False)
    assert res["correct"], (res["checks"], log)
    assert res["attempted"] >= cell.SAMPLED_LOOPS
    assert "each with its own reference" in "\n".join(log)


def test_previous_loops_answer_is_not_correct(per_loop_root, monkeypatch):
    _, glue = _modules(per_loop_root)
    real_make = glue.make

    def make(prob, acc_chunk):
        g = real_make(prob, acc_chunk)
        begin = g["begin_loop"]
        g["begin_loop"] = lambda i: begin(i - 1)
        return g
    monkeypatch.setattr(glue, "make", make)
    res, log = cell.run_cell(per_loop_root, CELL, 21, 0.2, False, need_chip=False)
    assert res["correct"] is False, (res["checks"], log)


def test_reference_a_loop_early_is_not_correct(per_loop_root, monkeypatch):
    problem, _ = _modules(per_loop_root)
    real = problem.reference
    monkeypatch.setattr(problem, "reference", lambda prob, *, loop: real(prob, loop=loop - 1))
    res, log = cell.run_cell(per_loop_root, CELL, 22, 0.2, False, need_chip=False)
    assert res["correct"] is False, (res["checks"], log)


def test_each_loop_gets_its_own_reference(per_loop_root, monkeypatch):
    """One reference a compared loop, at the number its ``begin_loop`` got."""
    problem, glue = _modules(per_loop_root)
    begun, asked = [], []
    real_ref, real_make = problem.reference, glue.make

    def make(prob, acc_chunk):
        g = real_make(prob, acc_chunk)
        begin = g["begin_loop"]
        g["begin_loop"] = lambda i: (begun.append(i), begin(i))[1]
        return g

    def reference(prob, *, loop):
        asked.append(loop)
        return real_ref(prob, loop=loop)
    monkeypatch.setattr(glue, "make", make)
    monkeypatch.setattr(problem, "reference", reference)
    res, log = cell.run_cell(per_loop_root, CELL, 23, 0.2, False, need_chip=False)
    assert res["correct"], (res["checks"], log)
    assert begun == list(range(cell.WARMUP_LOOPS + res["attempted"]))
    assert len(asked) == len(set(asked)) == cell.SAMPLED_LOOPS
    assert all(cell.WARMUP_LOOPS <= i < len(begun) for i in asked)


def test_control_takes_the_loop(per_loop_root):
    r = readings(per_loop_root, CELL, [1, 2], [1, 2], 0.1, need_chip=False)
    assert r["lower"] < 1e-3 < r["upper"], r


# one cell of each configuration the benchmark runs
CELLS = sorted({w["config"]: w["name"] for w in reversed(SPEC["workloads"])}.values())


@pytest.mark.parametrize("workload", CELLS)
def test_one_reference_without_the_flag(tiny_root, monkeypatch, workload):
    cfg = next(c for c in SPEC["configs"]
               if c["name"] == next(w["config"] for w in SPEC["workloads"]
                                    if w["name"] == workload))
    kind = json.loads((tiny_root / cfg["file"]).read_text())["problem"]
    problem = load_file(tiny_root / "bench" / "problems" / f"{kind}.py")
    assert not getattr(problem, "PER_LOOP", False)
    calls = []
    real = problem.reference
    monkeypatch.setattr(problem, "reference",
                        lambda *a, **kw: (calls.append((a[1:], kw)), real(*a, **kw))[1])
    res, log = cell.run_cell(tiny_root, workload, 31, 0.2, False, need_chip=False)
    assert res["correct"], (res["checks"], log)
    assert res["attempted"] >= 2
    assert calls == [((), {})]
