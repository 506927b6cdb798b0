"""Serving benchmark: continuous vs static batching (the serving face of
the paper's interrupt-vs-polling comparison) on identical request sets,
plus the open-loop loadgen sweep that commits ``BENCH_serving.json`` —
admission policies x refill modes on a seeded Zipf/Poisson trace with
p50/p95/p99 latency, TTFT, and goodput per configuration."""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.models import make_model
from repro.launch.compile_cache import configure_compile_cache
from repro.serving import LoadgenScenario, Request, ServingEngine
from repro.serving.loadgen import make_trace, run_trace

BENCH_SCHEMA = "bench_serving/v1"


def _build_model(config_name: str = "tinyllama-1.1b", seed: int = 0):
    cfg = get_config(config_name).smoke()
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    return cfg, model, params


# ---------------------------------------------------------------------------
# classic closed-batch comparison (CSV rows, kept from earlier PRs)
# ---------------------------------------------------------------------------
def serving_rows(
    *, quick: bool = False, backend: str = "inline", workers: int = 1
) -> List[Tuple[str, float, str]]:
    config_name, seed = "tinyllama-1.1b", 0
    cfg, model, params = _build_model(config_name, seed)
    n_req = 12 if quick else 24
    rng = np.random.default_rng(0)
    protos = [
        (rng.integers(0, cfg.vocab_size, int(rng.integers(2, 10))).astype(np.int32),
         int(rng.integers(2, 24)))
        for _ in range(n_req)
    ]
    # --backend remote: prefill admission runs in worker subprocesses —
    # they rebuild the model from (config, smoke, seed), so results are
    # identical; the transport cost shows up in prefill_disp_us.
    handles: List = []
    model_spec = None
    engine_backend = backend
    if backend == "remote":
        from repro.core.transport import spawn_worker

        handles = [spawn_worker() for _ in range(max(workers, 1))]
        engine_backend = "remote:" + ",".join(h.address for h in handles)
        model_spec = {"config": config_name, "smoke": True, "seed": seed}
    rows = []
    suffix = f"_{backend}" if backend != "inline" else ""
    try:
        for mode in ("static", "continuous"):
            rows.append(_run_mode(model, params, protos, mode, suffix,
                                  engine_backend, model_spec))
    finally:
        for h in handles:
            h.terminate()
    return rows


def _run_mode(model, params, protos, mode, suffix, engine_backend,
              model_spec) -> Tuple[str, float, str]:
    eng = ServingEngine(model, params, slots=4, max_len=96, mode=mode,
                        backend=engine_backend, model_spec=model_spec)
    for i, (prompt, mx) in enumerate(protos):
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=mx))
    t0 = time.perf_counter()
    eng.run()
    wall = time.perf_counter() - t0
    rep = eng.throughput_report()
    # per-slot coverage/utilization from the runtime's RunReport of the
    # final batch (the ROADMAP's last_run_report exposure)
    run_rep = eng.last_run_report
    slot_cols = ""
    if run_rep is not None:
        utils = run_rep.utilization.values()
        slot_cols = (
            f";load_balance={run_rep.load_balance:.3f}"
            f";slot_util_mean={sum(utils) / len(utils):.3f}"
            f";slot_items={'/'.join(str(v) for v in run_rep.per_worker_items.values())}"
        )
        if run_rep.dispatch_latency:
            disp = run_rep.dispatch_latency.values()
            slot_cols += (
                f";prefill_disp_us={sum(disp) / len(disp) * 1e6:.1f}"
            )
    return (
        f"serving_{mode}{suffix}",
        wall / max(rep["steps"], 1) * 1e6,
        f"us_per_step;tok_per_step={rep['tokens_per_step']:.3f};"
        f"steps={rep['steps']};tokens={rep['tokens']}" + slot_cols,
    )


# ---------------------------------------------------------------------------
# open-loop loadgen sweep -> BENCH_serving.json
# ---------------------------------------------------------------------------
def mixed_scenario(*, quick: bool = False, vocab_size: int,
                   seed: int = 0) -> LoadgenScenario:
    """The mixed-length Zipf/Poisson scenario the acceptance gate pins:
    short prompts with a wide Zipf generation-length spread (8-96
    tokens), Poisson arrivals fast enough to saturate the 4 decode
    slots, and per-request SLOs loose enough that misses measure
    scheduling (batch stragglers holding short requests hostage), not
    model compile noise.  At this operating point static batching
    strands capacity behind its longest in-flight request while
    continuous refill backfills freed slots — the paper's
    interrupt-beats-polling claim at the serving tier."""
    return LoadgenScenario(
        name="mixed-zipf-poisson",
        seed=seed,
        n=12 if quick else 32,
        rate=10.0,
        arrival="poisson",
        prompt_lens=(2, 12),
        gen_lens=(8, 48) if quick else (8, 96),
        zipf_a=1.4,
        vocab_size=vocab_size,
        deadline_base=1.5,
        deadline_per_token=0.08,
    )


def loadgen_sweep(
    *,
    quick: bool = False,
    policies: Tuple[str, ...] = ("fifo", "cost"),
    modes: Tuple[str, ...] = ("static", "continuous"),
    backends: Tuple[str, ...] = ("inline",),
    slots: int = 4,
    max_len: int = 128,
    seed: int = 0,
    repeats: int = 1,
) -> Dict:
    """Run the policy x mode x backend sweep on one seeded trace.

    Every configuration replays the *same* scenario (fresh Request
    objects per run — the engine stamps them).  A warmup pass first
    drives the whole trace through a throwaway engine so jit compilation
    of every prompt-length variant is paid before anything is timed.
    With ``repeats > 1`` each configuration runs that many times and the
    reported entry is the run with median goodput — wall-clock noise on
    a loaded host is the dominant error source, and a median run keeps
    the metrics internally consistent (unlike element-wise medians).
    """
    config_name = "tinyllama-1.1b"
    cfg, model, params = _build_model(config_name, seed)
    scenario = mixed_scenario(quick=quick, vocab_size=cfg.vocab_size,
                              seed=seed)

    warm = ServingEngine(model, params, slots=slots, max_len=max_len)
    run_trace(warm, make_trace(scenario), time_scale=0.0)

    entries = []
    for backend in backends:
        for policy in policies:
            for mode in modes:
                runs = []
                for _ in range(max(repeats, 1)):
                    eng = ServingEngine(
                        model, params, slots=slots, max_len=max_len,
                        mode=mode, policy=policy, backend=backend,
                        seed=seed,
                    )
                    runs.append(run_trace(eng, make_trace(scenario)))
                runs.sort(key=lambda m: m["goodput_tokens_per_s"])
                metrics = runs[len(runs) // 2]
                entries.append({
                    "policy": policy,
                    "mode": mode,
                    "backend": backend,
                    "repeats": len(runs),
                    "metrics": metrics,
                })
                print(f"  {policy}/{mode}/{backend}: "
                      f"p50={metrics['p50_latency_s']:.3f}s "
                      f"p99={metrics['p99_latency_s']:.3f}s "
                      f"ttft={metrics['mean_ttft_s']:.3f}s "
                      f"goodput={metrics['goodput_tokens_per_s']:.1f}tok/s "
                      f"hit={metrics['deadline_hit_rate']:.2f}")
    return {
        "schema": BENCH_SCHEMA,
        "scenario": scenario.describe(),
        "engine": {
            "model": config_name, "smoke": True, "slots": slots,
            "max_len": max_len, "temperature": 0.0, "seed": seed,
        },
        "configs": entries,
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-scale)")
    ap.add_argument("--backend", default="inline",
                    choices=["inline", "threads", "remote"],
                    help="prefill admission path: synchronous (inline), "
                         "per-slot ThreadUnits (async prefill overlapping "
                         "the decode loop), or per-slot RemoteUnits "
                         "prefilling in spawned worker subprocesses over "
                         "SocketTransport")
    ap.add_argument("--workers", type=int, default=1,
                    help="worker subprocesses for --backend remote")
    ap.add_argument("--loadgen", action="store_true",
                    help="run the open-loop admission-policy sweep "
                         "(policies x modes x backends on a seeded "
                         "Zipf/Poisson trace) instead of the closed-batch "
                         "CSV comparison")
    ap.add_argument("--policies", default="fifo,cost",
                    help="comma list for --loadgen (fifo,priority,"
                         "deadline,cost)")
    ap.add_argument("--backends", default="inline",
                    help="comma list for --loadgen (inline,threads)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the --loadgen result as JSON "
                         "(the BENCH_serving.json artifact)")
    ap.add_argument("--repeats", type=int, default=None,
                    help="runs per --loadgen config, median reported "
                         "(default: 1 with --quick, 3 otherwise)")
    args = ap.parse_args()
    configure_compile_cache()
    if args.loadgen:
        result = loadgen_sweep(
            quick=args.quick,
            policies=tuple(args.policies.split(",")),
            backends=tuple(args.backends.split(",")),
            repeats=args.repeats or (1 if args.quick else 3),
        )
        if args.json:
            with open(args.json, "w") as fh:
                json.dump(result, fh, indent=2, sort_keys=True)
                fh.write("\n")
            print(f"wrote {args.json}")
        return
    print("name,us_per_step,derived")
    for name, us, derived in serving_rows(quick=args.quick,
                                          backend=args.backend,
                                          workers=args.workers):
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
