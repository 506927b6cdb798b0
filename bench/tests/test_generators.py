"""The benchmark's own generators, references and work counts."""

import json

import numpy as np
import pytest

from conftest import ROOT
from harness.cell import row_errors
from harness.registry import load_file

hotspot = load_file(ROOT / "bench" / "problems" / "hotspot.py")
HOT_CFG = json.loads((ROOT / "bench/configs/hotspot-paper.json").read_text())


def test_seed_fixes_inputs_and_not_sizes():
    cfg = dict(HOT_CFG, grid=48)
    a, b = hotspot.generate(cfg, 2**31 + 5), hotspot.generate(cfg, 2**31 + 5)
    c = hotspot.generate(cfg, 2**31 + 6)
    assert np.array_equal(a.temp, b.temp) and np.array_equal(a.power, b.power)
    assert a.temp.shape == c.temp.shape and not np.array_equal(a.temp, c.temp)


def test_hotspot_reference_bands_and_work():
    cfg = dict(HOT_CFG, grid=96)
    p = hotspot.generate(cfg, 4)
    whole = hotspot._steps(p.temp.astype(np.float64), p.power.astype(np.float64),
                           cfg, np, np.float64)
    assert np.array_equal(hotspot.reference(p, band=20), whole)
    ops, nbytes = hotspot.chunk_work(p, 10, 30)
    assert (ops, nbytes) == (15 * 8 * 20 * 96, 12 * 20 * 96)


@pytest.mark.parametrize("grid", [HOT_CFG["rehearsal"]["grid"], HOT_CFG["grid"]])
def test_state_left_unchanged_fails_the_limit(grid):
    """A kernel that returns its state unchanged, at the rehearsal size and
    at the configuration's own, reads above the limit on every row."""
    cfg = dict(HOT_CFG, grid=grid)
    p = hotspot.generate(cfg, 7)
    errs = row_errors(p.temp, hotspot.reference(p))
    assert errs.min() > HOT_CFG["limits"]["max_row_rel_err"]
