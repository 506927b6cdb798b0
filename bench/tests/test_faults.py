"""A run with the timed path broken underneath comes out not correct.

Each cell is driven as on the chip (only the look for a chip is skipped),
with one fault planted in the program's ops that the glue calls.  The ops
are found by the names the glue module gives (``KERNEL``, ``CC_OP``):

* ``unwritten``: the ACC kernel returns its output as allocated;
* ``half_left_out``: the ACC kernel leaves the second half of its rows out;
* ``exchange_left_out``: rows computed on every chip but the first never
  reach the host result (cells whose ACC units span several chips);
* ``altered_acc`` / ``altered_cc``: one value of every ACC (or CC) chunk is
  altered where it is produced.

A kernel that returns its state unchanged is the yardstick's to catch:
``test_generators.py`` reads it against the limit.
"""

import inspect
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import ROOT
from harness import cell
from harness.registry import load_file

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def _kernel_fault(real, fault):
    """A jitted stand-in for the ACC kernel ``real`` with ``fault`` planted;
    its keyword-only arguments are static, as the program's are."""
    static = [p.name for p in inspect.signature(real).parameters.values()
              if p.kind is inspect.Parameter.KEYWORD_ONLY]

    def broken(*args, **kw):
        out = real(*args, **kw)
        half = out.shape[0] // 2
        if fault == "unwritten":
            return jnp.zeros_like(out)
        if fault == "half_left_out":
            return out.at[half:].set(0.0)
        assert fault == "altered_acc"
        return out.at[half, 0].add(1.0 + jnp.abs(out[half, 0]))
    return jax.jit(broken, static_argnames=static)


def _rehearsal(path):
    data = json.loads(path.read_text())
    return dict(data, **data["rehearsal"])


def _cases():
    """The faults each cell can have.  CC units take rows only where the
    ACC units, which are registered and served first, leave some after
    their first chunks."""
    for w in SPEC["workloads"]:
        mix = _rehearsal(ROOT / "bench/traffic" / f"{w['traffic']}.json")
        cfg = _rehearsal(ROOT / next(c["file"] for c in SPEC["configs"]
                                     if c["name"] == w["config"]))
        rows = load_file(ROOT / "bench/problems" / f"{cfg['problem']}.py").generate(cfg, 0).rows
        faults = ["unwritten", "half_left_out", "altered_acc"]
        if mix["cc_units"] and len(mix["acc_devices"]) * mix["acc_chunk"] < rows:
            faults.append("altered_cc")
        if len(set(mix["acc_devices"])) > 1:
            faults.append("exchange_left_out")
        for f in faults:
            yield pytest.param(w["name"], w["config"], f, id=f"{w['name']}-{f}")


@pytest.mark.parametrize("workload,config,fault", list(_cases()))
def test_fault_is_not_correct(tiny_root, monkeypatch, workload, config, fault):
    file = next(c["file"] for c in SPEC["configs"] if c["name"] == config)
    kind = json.loads((tiny_root / file).read_text())["problem"]
    glue = load_file(tiny_root / "bench" / "glue" / f"{kind}.py")
    if fault == "altered_cc":
        real_cc = getattr(glue, glue.CC_OP)

        def altered(*args, **kw):
            out = real_cc(*args, **kw)
            out[len(out) // 2, 0] += 1.0 + abs(out[len(out) // 2, 0])
            return out
        monkeypatch.setattr(glue, glue.CC_OP, altered)
    elif fault == "exchange_left_out":
        real_make = glue.make

        def make(prob, acc_chunk):
            g = real_make(prob, acc_chunk)
            acc_work = g["acc_work"]
            g["acc_work"] = lambda device: (acc_work(device) if device.id == 0
                                            else (lambda chunk: None))
            return g
        monkeypatch.setattr(glue, "make", make)
    else:
        monkeypatch.setattr(glue, glue.KERNEL, _kernel_fault(getattr(glue, glue.KERNEL), fault))
    res, log = cell.run_cell(tiny_root, workload, 11, 0.2, False, need_chip=False)
    assert res["correct"] is False, (res["checks"], log)
