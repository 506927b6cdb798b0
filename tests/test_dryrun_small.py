"""Small-mesh dry-run: the full lower+compile+analyze pipeline on CPU.

These tests exercise the same code path as the 512-device production
dry-run but on the single real device (mesh 1×1), so the pipeline itself
is covered by every CI run; the production meshes are certified by
``python -m repro.launch.dryrun --all --both-meshes``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.configs.base import InputShape
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.models import make_model
from repro.optim import AdamW
from repro.parallel.compat import make_mesh
from repro.parallel.mesh_rules import MeshRules


def _mesh():
    return make_mesh((1, 1), ("data", "model"))


class TestLowerCompile:
    @pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-130m",
                                      "qwen3-moe-30b-a3b"])
    def test_train_step_lowers_smoke(self, arch):
        cfg = get_config(arch).smoke()
        model = make_model(cfg)
        mesh = _mesh()
        rules = MeshRules(mesh, cfg.parallel)
        shape = InputShape("t", 32, 4, "train")
        opt = AdamW()
        bundle = make_train_step(model, opt, rules, shape, loss_chunk=0)
        with mesh:
            compiled = bundle.jit().lower(
                model.abstract_params(),
                opt.abstract_state(model.abstract_params()),
                model.input_specs(shape)["batch"],
            ).compile()
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes > 0
        rep = analyze_hlo(compiled.as_text())
        assert rep.dot_flops > 0

    def test_decode_step_lowers_smoke(self):
        cfg = get_config("recurrentgemma-9b").smoke()
        model = make_model(cfg)
        mesh = _mesh()
        rules = MeshRules(mesh, cfg.parallel)
        shape = InputShape("d", 64, 4, "decode")
        bundle = make_decode_step(model, rules, shape)
        spec = model.input_specs(shape)
        with mesh:
            compiled = bundle.jit().lower(
                model.abstract_params(), spec["tokens"], spec["positions"],
                spec["caches"],
            ).compile()
        assert compiled.memory_analysis().argument_size_in_bytes > 0


class TestHloAnalysis:
    def test_scan_trip_count_correction(self):
        """The analyzer multiplies loop bodies; cost_analysis does not."""
        L, d = 8, 64

        def f(params, x):
            def body(x, w):
                return jnp.tanh(x @ w), None
            y, _ = jax.lax.scan(body, x, params)
            return jnp.sum(y)

        params = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
        x = jax.ShapeDtypeStruct((4, d), jnp.float32)
        compiled = jax.jit(f).lower(params, x).compile()
        rep = analyze_hlo(compiled.as_text())
        analytic = L * 2 * 4 * d * d
        assert rep.dot_flops == pytest.approx(analytic, rel=0.01)
        assert L in rep.trip_counts.values()

    def test_collective_detection(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        from jax.sharding import NamedSharding, PartitionSpec as P

        def f(x):
            return jnp.sum(x)

        with mesh:
            compiled = jax.jit(
                f, in_shardings=NamedSharding(mesh, P(None))
            ).lower(jax.ShapeDtypeStruct((64,), jnp.float32)).compile()
        rep = analyze_hlo(compiled.as_text())
        assert rep.collective_bytes >= 0  # no collectives on 1 device

    def test_shape_bytes_parser(self):
        from repro.launch.hlo_analysis import _shape_bytes
        assert _shape_bytes("bf16[2,4]{1,0}") == 16
        assert _shape_bytes("f32[10]") == 40
        assert _shape_bytes("(f32[2], s32[2])") == 16
        assert _shape_bytes("pred[8]") == 8


class TestProductionArtifacts:
    """The committed dry-run artifacts (if present) are coherent."""

    def test_artifacts_cover_all_cells(self):
        import json
        from pathlib import Path
        d = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
        files = list(d.glob("*__pod16x16.json")) if d.exists() else []
        if len(files) < 40:
            pytest.skip("production dry-run artifacts not generated yet")
        ok = skip = 0
        for f in files:
            rec = json.loads(f.read_text())
            if rec["status"] == "ok":
                ok += 1
                assert rec["roofline"]["bound_s"] > 0
            else:
                skip += 1
                assert "sub-quadratic" in rec["reason"]
        assert ok + skip == 40
