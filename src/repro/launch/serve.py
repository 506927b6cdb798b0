"""Serving driver: batched requests through the continuous-batching engine.

CLI:
  python -m repro.launch.serve --arch tinyllama-1.1b --requests 16 --slots 4
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import get_config
from ..models import make_model
from ..serving import Request, ServingEngine
from .compile_cache import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--mode", choices=("continuous", "static"), default="continuous")
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.smoke()
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    engine = ServingEngine(model, params, slots=args.slots, max_len=args.max_len,
                           mode=args.mode)
    for i in range(args.requests):
        plen = int(rng.integers(4, 16))
        engine.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(4, args.max_new)),
        ))
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0
    rep = engine.throughput_report()
    print(f"{len(results)} requests, {rep['tokens']} tokens, "
          f"{rep['steps']} decode steps, {rep['tokens_per_step']:.2f} tok/step, "
          f"{rep['tokens'] / wall:.1f} tok/s wall ({args.mode})")


if __name__ == "__main__":
    main()
