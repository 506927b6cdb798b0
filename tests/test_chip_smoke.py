"""``chip_smoke.py`` at a tiny size on the CPU, Pallas kernels interpreted.

The script itself refuses to run without a TPU; these tests drive its
phase functions directly, so a wrong path, argument or check is found
here and not on the chip.  The four-device layout runs in a subprocess on
four virtual CPU devices.
"""

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.configs.paper_eneac import HotspotConfig, SpmmConfig

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

HOT = HotspotConfig(grid=128, iterations=128, sim_steps=3)
SPMM = SpmmConfig(rows=300, cols=700, dense_cols=10, nnz_per_row_mean=12.0, seed=3)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=120)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_hotspot_phase_matches_the_reference():
    prob = smoke.hotspot_problem(HOT, seed=5)
    out, stats = smoke.run_hotspot(prob, [0, 0], n_cc=2, acc_chunk=16,
                                   expect_platform="cpu")
    assert smoke._rel_err(out, smoke.hotspot_reference(prob)) <= smoke.HOTSPOT_TOL
    assert stats["wall_s"] > 0


def test_spmm_phase_matches_the_dense_reference():
    from repro.kernels.spmm.ref import spmm_dense_ref

    prob = smoke.spmm_problem(SPMM)
    ref = smoke.spmm_reference(prob, budget=2048)
    assert smoke._rel_err(ref, spmm_dense_ref(prob["sp"])) <= smoke.SPMM_TOL
    out, _ = smoke.run_spmm(prob, [0, 0], n_cc=2, acc_chunk=64,
                            expect_platform="cpu")
    assert smoke._rel_err(out, ref) <= smoke.SPMM_TOL


def test_acc_units_must_be_on_the_expected_platform():
    prob = smoke.hotspot_problem(HOT, seed=5)
    with pytest.raises(smoke.SmokeFailure, match="expected tpu"):
        smoke.run_hotspot(prob, [0], n_cc=1, acc_chunk=16)


def test_serving_phase_batched_equals_alone():
    out = smoke.run_serving(get_config("tinyllama-1.1b").smoke(), seed=1,
                            slots=2, max_len=64, requests=4, max_new=4,
                            prompt_lens=(4, 8))
    assert out["tokens"] == 16


FOUR_DEVICES = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import importlib.util
    spec = importlib.util.spec_from_file_location("chip_smoke", %r)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.configs.paper_eneac import HotspotConfig, SpmmConfig
    layouts = [("one device", [0, 0, 0, 0]), ("four devices", [0, 1, 2, 3])]
    smoke.runtime_phase(
        smoke.hotspot_problem(HotspotConfig(grid=256, iterations=256,
                                            sim_steps=2), 3),
        smoke.hotspot_reference, smoke.run_hotspot, layouts,
        smoke.HOTSPOT_TOL, n_cc=2, acc_chunk=16, expect_platform="cpu")
    smoke.runtime_phase(
        smoke.spmm_problem(SpmmConfig(rows=600, cols=700, dense_cols=10,
                                      nnz_per_row_mean=12.0, seed=3)),
        smoke.spmm_reference, smoke.run_spmm, layouts, smoke.SPMM_TOL,
        n_cc=2, acc_chunk=64, expect_platform="cpu")
    print("FOUR_DEVICES_OK")
""") % str(ROOT / "chip_smoke.py")


def test_four_device_layout_matches_one_device():
    res = subprocess.run([sys.executable, "-c", FOUR_DEVICES],
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "FOUR_DEVICES_OK" in res.stdout, res.stdout + res.stderr
    for dev in ("TFRT_CPU_1", "TFRT_CPU_2", "TFRT_CPU_3"):
        assert f"device {dev} platform cpu" in res.stdout
