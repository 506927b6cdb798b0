"""End-to-end system behaviour: training convergence, checkpoint-restart
equivalence, hetero microbatching integration, hybrid executor adaptation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core.parallel_for import HybridExecutor
from repro.launch.train import TrainLoopConfig, run_training
from repro.models import make_model
from repro.optim import AdamW
from repro.parallel.compat import make_mesh
from repro.parallel.mesh_rules import MeshRules
from repro.launch.steps import make_train_step


class TestTrainingLoop:
    def test_loss_decreases_on_learnable_data(self, tmp_path):
        out = run_training(TrainLoopConfig(
            arch="tinyllama-1.1b", steps=40, global_batch=8, seq_len=64,
            lr=3e-3, ckpt_dir=str(tmp_path), ckpt_every=20,
        ))
        assert out["steps"] == 40
        assert out["final_loss"] < out["first_loss"]

    def test_checkpoint_restart_resumes(self, tmp_path):
        run_training(TrainLoopConfig(
            arch="tinyllama-1.1b", steps=10, global_batch=4, seq_len=32,
            ckpt_dir=str(tmp_path), ckpt_every=10,
        ))
        out = run_training(TrainLoopConfig(
            arch="tinyllama-1.1b", steps=14, global_batch=4, seq_len=32,
            ckpt_dir=str(tmp_path), ckpt_every=100, resume=True,
        ))
        assert out["steps"] == 4  # resumed from step 10

    def test_microbatched_step_matches_monolithic(self):
        """Grad accumulation is numerically equivalent to one big batch."""
        cfg = get_config("tinyllama-1.1b").smoke()
        model = make_model(cfg)
        mesh = make_mesh((1, 1), ("data", "model"))
        rules = MeshRules(mesh, cfg.parallel)
        shape = InputShape("t", 32, 8, "train")
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW()
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                 "mask": jnp.ones((8, 32), jnp.float32)}
        outs = {}
        with mesh:
            for mb in (1, 4):
                bundle = make_train_step(model, opt, rules, shape,
                                         microbatches=mb, loss_chunk=0)
                p2, _, metrics = bundle.jit()(
                    jax.tree.map(jnp.copy, params), opt.init(params), batch)
                outs[mb] = (float(metrics["loss"]), p2)
        assert outs[1][0] == pytest.approx(outs[4][0], rel=1e-3)
        diffs = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                               - b.astype(jnp.float32)))),
            outs[1][1], outs[4][1])
        assert max(jax.tree.leaves(diffs)) < 5e-2


class TestHybridExecutor:
    def test_split_converges_to_balance(self):
        import time as _t

        def dense(n):  # 10x faster per item
            _t.sleep(n * 1e-5)
            return n

        def sparse(n):
            _t.sleep(n * 1e-4)
            return n

        ex = HybridExecutor(dense, sparse, lambda a, b: (a, b), num_items=1000,
                            mode="parallel", dense_quantum=1)
        dec = ex.converge(rounds=6)
        # balance point: n_d/t_d == n_s/t_s ⇒ dense fraction ≈ 10/11 ≈ 0.91
        assert 0.75 < dec.dense_fraction <= 1.0

    def test_serial_mode_picks_faster_path(self):
        ex = HybridExecutor(lambda n: n, lambda n: n, lambda a, b: (a, b),
                            num_items=100, mode="serial",
                            init_dense_throughput=10.0,
                            init_sparse_throughput=1.0, dense_quantum=1)
        dec = ex.decide()
        assert dec.n_dense == 100


class TestShardingRules:
    def test_grok_expert_premise(self):
        """8 experts % 16 ≠ 0 ⇒ the rules fall back to TP over expert ff."""
        cfg = get_config("grok-1-314b")
        assert cfg.num_experts % 16 != 0 and cfg.moe_d_ff % 16 == 0

    def test_qwen3_moe_ep_premise(self):
        cfg = get_config("qwen3-moe-30b-a3b")
        assert cfg.num_experts % 16 == 0  # true expert parallelism

    def test_fused_head_dims_divide_model_axis(self):
        """The fused-QKV layout divides 16 for EVERY assigned arch — the
        property that makes qwen3's 40 heads shardable."""
        from repro.configs import all_configs
        for cfg in all_configs().values():
            if cfg.num_heads:
                assert cfg.q_dim % 16 == 0, cfg.name
                assert cfg.kv_dim % 16 == 0, cfg.name
            assert cfg.padded_vocab % 16 == 0, cfg.name
