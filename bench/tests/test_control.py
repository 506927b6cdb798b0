"""The control, the reference in the next lower precision put in the
program's place, fails the check that sound runs pass: at a size a test
run holds, on three seeds, for every configuration."""

import json

import pytest

from conftest import ROOT
from control import readings

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
# one cell of each configuration
CELLS = {w["config"]: w["name"] for w in reversed(SPEC["workloads"])}


@pytest.mark.parametrize("config", sorted(CELLS))
def test_control_fails_where_the_program_passes(tiny_root, config):
    limit = json.loads((tiny_root / f"bench/configs/{config}.json").read_text())[
        "limits"]["max_row_rel_err"]
    r = readings(tiny_root, CELLS[config], [1, 2, 3], [1, 2, 3], 0.1, need_chip=False)
    assert r["lower"] < limit < r["upper"], r
