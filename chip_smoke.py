"""Smoke run of the ENEAC runtime and the serving tier on a TPU.

Drives the main path once through its public entry points, at the
paper's problem sizes and at one model's published width:

1. **HotSpot** — Table 1 config 7 (4CC+4HPCACC+INT):
   ``HeteroRuntime.parallel_for(policy="multidynamic", engine="interrupt",
   acc_chunk=128)`` over the 2048 rows of a 2048² f32 grid, 8 time steps.
   Four ``JaxDeviceUnit`` ACC units run the compiled Pallas HPC kernel on
   fixed 144-row windows (the band plus 8 halo rows each side); four
   ``ThreadUnit`` CC units step their bands in numpy on the host.  The
   assembled grid is compared with ``hotspot_ref`` run on the host CPU.
2. **SpMM** — the same units over the 29957 rows (densest first) of the
   paper's lognormal sparse matrix times a 100-column dense matrix: ACC
   units run the compiled block-ELL kernel on fixed 129-row-block windows,
   CC units run the ELL gather over CSR arrays in numpy.  Compared with
   ``spmm_ell_ref`` run on the host CPU.
3. **Serving** — ``ServingEngine`` with tinyllama-1.1b at its published
   width (random bf16 weights from the seed), continuous batching, 4
   slots, ``max_len`` 512, 8 greedy requests of 16 new tokens.  Every
   request must return 16 tokens, and one request served alone must
   return the tokens it got in the full batch.

Usage::

    python chip_smoke.py                # one chip: the three phases
    python chip_smoke.py --four-chips   # runtime phases only: ACC units on
                                        # one chip, then on devices 0..3

Every line before the last is smoke output, not a measurement.  The last
line of standard output is one JSON object, ``{"ok": true, "device":
{"platform", "kind", "count"}}``.  Without a TPU the script exits non-zero
and prints no JSON; it never falls back to the CPU.  It runs in one
process and starts none.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.paper_eneac import HotspotConfig, SpmmConfig  # noqa: E402
from repro.core import HeteroRuntime, WallClock, WorkerKind  # noqa: E402
from repro.core.backends import make_backend  # noqa: E402
from repro.kernels.hotspot.ops import (  # noqa: E402
    band_window, hotspot_hpc_window, hotspot_rows_host)
from repro.kernels.hotspot.ref import hotspot_ref  # noqa: E402
from repro.kernels.spmm.ops import (  # noqa: E402
    HostCsr, pad_rhs, sorted_by_density, spmm_rows_host, spmm_window_start)
from repro.kernels.spmm.ref import (  # noqa: E402
    ROW_BLOCK, make_problem, spmm_ell_ref, to_block_ell)
from repro.kernels.spmm.spmm import BlockEllArrays, spmm_block_ell_window  # noqa: E402
from repro.models import make_model  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

# max |result - reference| / max |reference| allowed in f32.  At 2048² the
# explicit HotSpot update amplifies its checkerboard mode 3.36x per step,
# so errors are judged against the grid's largest value.
HOTSPOT_TOL = 1e-4
SPMM_TOL = 1e-4


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu() -> List:
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise SystemExit(f"chip_smoke: no TPU: JAX could not start ({exc})")
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: JAX finds only {devices[0].platform} "
            "devices; this smoke run needs a TPU and does not fall back to "
            "the CPU"
        )
    return devices


def assert_compiled_kernel(compiled, name: str) -> None:
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: no tpu_custom_call in the compiled program; the Pallas "
          "kernel did not lower for the TPU")


def _rel_err(out: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(out - ref)) / max(float(np.max(np.abs(ref))), 1e-30))


def _run_units(name: str, n_items: int, acc_fns: Sequence, acc_units: Sequence,
               cc_fn, n_cc: int, acc_chunk: int):
    """One multidynamic parallel_for over ACC device units + CC threads."""
    rt = HeteroRuntime(clock=WallClock())
    for unit, fn in zip(acc_units, acc_fns):
        rt.register_unit(unit.name, WorkerKind.ACC, work_fn=fn, backend=unit)
    for i in range(n_cc):
        rt.register_unit(f"cc{i}", WorkerKind.CC, work_fn=cc_fn, backend="thread")
    t0 = time.perf_counter()
    rep = rt.parallel_for(num_items=n_items, policy="multidynamic",
                          engine="interrupt", acc_chunk=acc_chunk)
    wall = time.perf_counter() - t0
    spans = rep.coverage
    check(spans[0][0] == 0 and spans[-1][1] == n_items
          and all(b == c for (_, b), (c, _) in zip(spans, spans[1:])),
          f"{name}: completed chunks do not tile [0, {n_items})")
    for u in acc_units:
        check(rep.per_worker_items.get(u.name, 0) > 0,
              f"{name}: ACC unit {u.name} completed no items")
    for i in range(n_cc):
        check(rep.per_worker_items.get(f"cc{i}", 0) > 0,
              f"{name}: CC unit cc{i} completed no items")
    return rep, wall


def _report_units(name: str, rep, acc_units, n_cc: int) -> None:
    for u in acc_units:
        say(f"{name}: unit {u.name} device {u.device} platform {u.platform}: "
            f"items {rep.per_worker_items[u.name]} chunks "
            f"{rep.per_worker_chunks[u.name]}")
    for i in range(n_cc):
        say(f"{name}: unit cc{i} host thread platform cpu: items "
            f"{rep.per_worker_items[f'cc{i}']} chunks "
            f"{rep.per_worker_chunks[f'cc{i}']}")


def _acc_units(device_indices: Sequence[int], expect_platform: str):
    units = [make_backend(f"jax:{d}", f"acc{i}")
             for i, d in enumerate(device_indices)]
    for u in units:
        check(u.platform == expect_platform,
              f"ACC unit {u.name} is on {u.platform}, expected {expect_platform}")
    return units


def _place_and_compile(units, place, lower, kernel: str, expect_platform: str):
    """Per device of ``units``: ``place(device)`` puts the operands there
    and ``lower(operands)`` lowers the kernel for them.  The transfer is
    waited for, so neither it nor the compile runs inside the
    ``parallel_for``.  Returns ({device: (executable, operands)}, a line
    of per-device seconds)."""
    compiled, times = {}, []
    for dev in sorted({u.device for u in units}, key=lambda d: d.id):
        t0 = time.perf_counter()
        args = jax.block_until_ready(place(dev))
        t1 = time.perf_counter()
        exe = lower(args).compile()
        t2 = time.perf_counter()
        if expect_platform == "tpu":
            assert_compiled_kernel(exe, kernel)
        compiled[dev] = (exe, args)
        times.append(f"{dev}: to_device_s {t1 - t0:.2f} compile_s {t2 - t1:.2f}")
    return compiled, "; ".join(times)


# ---------------------------------------------------------------------------
# HotSpot
# ---------------------------------------------------------------------------
def hotspot_problem(cfg: HotspotConfig, seed: int) -> Dict:
    rng = np.random.default_rng(seed)
    shape = (cfg.grid, cfg.grid)
    return {"cfg": cfg,
            "temp": (cfg.amb_temp + 10.0 * rng.random(shape)).astype(np.float32),
            "power": rng.random(shape).astype(np.float32)}


def hotspot_reference(prob: Dict) -> np.ndarray:
    cpu = jax.devices("cpu")[0]
    cfg = prob["cfg"]
    ref = jax.jit(hotspot_ref, static_argnums=(2, 3))(
        jax.device_put(prob["temp"], cpu), jax.device_put(prob["power"], cpu),
        cfg, cfg.sim_steps)
    return np.asarray(ref)


def run_hotspot(prob: Dict, device_indices: Sequence[int], *, n_cc: int = 4,
                acc_chunk: int = 128, expect_platform: str = "tpu",
                label: str = "hotspot") -> Tuple[np.ndarray, Dict]:
    cfg, temp, power = prob["cfg"], prob["temp"], prob["power"]
    rows, steps = cfg.grid, cfg.sim_steps
    window = acc_chunk + 2 * steps
    units = _acc_units(device_indices, expect_platform)
    result = np.empty_like(temp)
    acc_out: Dict[Tuple[int, int], Tuple[int, jax.Array]] = {}

    compiled, setup = _place_and_compile(
        units, lambda dev: (jax.device_put(temp, dev), jax.device_put(power, dev)),
        lambda args: hotspot_hpc_window.lower(*args, np.int32(0), cfg=cfg,
                                              window=window, steps=steps),
        "hotspot HPC window kernel", expect_platform)

    def acc_fn(device):
        exe, args = compiled[device]

        def work(chunk):
            lo = band_window(chunk.start, chunk.stop, rows, window, steps)
            out = exe(*args, np.int32(lo))
            acc_out[(chunk.start, chunk.stop)] = (lo, out)
            return out
        return work

    def cc_work(chunk):
        result[chunk.start:chunk.stop] = hotspot_rows_host(
            temp, power, chunk.start, chunk.stop, cfg, steps)

    rep, wall = _run_units(label, rows, [acc_fn(u.device) for u in units],
                           units, cc_work, n_cc, acc_chunk)
    for (s, e), (lo, out) in acc_out.items():
        result[s:e] = np.asarray(out)[s - lo:e - lo]
    say(f"{label}: grid {rows}x{rows} steps {steps} acc_chunk {acc_chunk} "
        f"window {window} rows; {setup}; parallel_for wall_s {wall:.3f}, "
        f"chunks {rep.chunks}")
    _report_units(label, rep, units, n_cc)
    return result, {"wall_s": wall}


# ---------------------------------------------------------------------------
# SpMM
# ---------------------------------------------------------------------------
def spmm_problem(cfg: SpmmConfig) -> Dict:
    t0 = time.perf_counter()
    p = make_problem(cfg.rows, cfg.cols, cfg.dense_cols,
                     nnz_mean=cfg.nnz_per_row_mean,
                     nnz_sigma=cfg.nnz_per_row_sigma, seed=cfg.seed)
    sp, _ = sorted_by_density(p)
    prob = {"cfg": cfg, "sp": sp, "be": to_block_ell(sp), "csr": HostCsr(sp),
            "rhs_pad": pad_rhs(sp)}
    say(f"spmm: {cfg.rows}x{cfg.cols} x {cfg.dense_cols} dense cols, "
        f"{int(sp.nnz.sum())} nonzeros (row nnz max {int(sp.nnz.max())}, "
        f"median {int(np.median(sp.nnz))}), block-ELL {prob['be'].n_row_blocks} "
        f"row blocks x K {prob['be'].k_max}: host set-up "
        f"{time.perf_counter() - t0:.1f}s")
    return prob


def spmm_reference(prob: Dict, *, budget: int = 1 << 20) -> np.ndarray:
    """``spmm_ell_ref`` on the host CPU, over row groups of bounded size.

    Rows are densest first, so each group's width is its first row's nnz;
    widths and heights are powers of two to bound the number of compiles.
    """
    sp = prob["sp"]
    cpu = jax.devices("cpu")[0]
    ref_fn = jax.jit(spmm_ell_ref)
    rhs = jax.device_put(sp.rhs, cpu)
    out = np.empty((sp.rows, sp.rhs.shape[1]), np.float32)
    s = 0
    while s < sp.rows:
        width = 1 << max(int(sp.nnz[s]) - 1, 0).bit_length()
        height = max(8, min(4096, budget // width))
        e = min(s + height, sp.rows)
        vals = np.zeros((height, width), np.float32)
        cols = np.zeros((height, width), np.int32)
        w = min(width, sp.vals.shape[1])
        vals[:e - s, :w] = sp.vals[s:e, :w]
        cols[:e - s, :w] = sp.cols[s:e, :w]
        y = ref_fn(jax.device_put(vals, cpu), jax.device_put(cols, cpu), rhs)
        out[s:e] = np.asarray(y)[:e - s]
        s = e
    return out


def run_spmm(prob: Dict, device_indices: Sequence[int], *, n_cc: int = 4,
             acc_chunk: int = 1024, expect_platform: str = "tpu",
             label: str = "spmm") -> Tuple[np.ndarray, Dict]:
    sp, be, csr = prob["sp"], prob["be"], prob["csr"]
    n = sp.rhs.shape[1]
    window = acc_chunk // ROW_BLOCK + 1
    units = _acc_units(device_indices, expect_platform)
    result = np.empty((sp.rows, n), np.float32)
    acc_out: Dict[Tuple[int, int], Tuple[int, jax.Array]] = {}

    def place(dev):
        ell = BlockEllArrays(be, device=dev)
        return (ell.vals, ell.colblocks, ell.counts,
                jax.device_put(prob["rhs_pad"], dev))

    compiled, setup = _place_and_compile(
        units, place,
        lambda args: spmm_block_ell_window.lower(*args, np.int32(0),
                                                 n_row_blocks=window),
        "spmm block-ELL window kernel", expect_platform)

    def acc_fn(device):
        exe, args = compiled[device]

        def work(chunk):
            rb0 = spmm_window_start(chunk.start, chunk.stop, be.n_row_blocks, window)
            out = exe(*args, np.int32(rb0))
            acc_out[(chunk.start, chunk.stop)] = (rb0, out)
            return out
        return work

    def cc_work(chunk):
        result[chunk.start:chunk.stop] = spmm_rows_host(
            csr, sp.rhs, chunk.start, chunk.stop)

    rep, wall = _run_units(label, sp.rows, [acc_fn(u.device) for u in units],
                           units, cc_work, n_cc, acc_chunk)
    for (s, e), (rb0, out) in acc_out.items():
        off = rb0 * ROW_BLOCK
        result[s:e] = np.asarray(out)[s - off:e - off, :n]
    say(f"{label}: rows {sp.rows} acc_chunk {acc_chunk} window {window} row "
        f"blocks; {setup}; parallel_for wall_s {wall:.3f}, chunks {rep.chunks}")
    _report_units(label, rep, units, n_cc)
    return result, {"wall_s": wall}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def run_serving(cfg, *, seed: int, slots: int = 4, max_len: int = 512,
                requests: int = 8, max_new: int = 16,
                prompt_lens: Sequence[int] = (16, 32, 64, 128)) -> Dict:
    """Prompt lengths come from a small set: each distinct one compiles a
    prefill program."""
    t0 = time.perf_counter()
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    engine = ServingEngine(model, params, slots=slots, max_len=max_len,
                           mode="continuous", temperature=0.0, seed=seed)
    say(f"serving: {cfg.name} {cfg.num_layers} layers d_model {cfg.d_model} "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab_size} {cfg.param_dtype}: {n_params} params on "
        f"{jax.tree.leaves(params)[0].devices()}, built in "
        f"{time.perf_counter() - t0:.1f}s")
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.choice(prompt_lens)),
                                               dtype=np.int32),
                    max_new_tokens=max_new) for i in range(requests)]
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0
    check(sorted(results) == list(range(requests)),
          f"serving: {len(results)} of {requests} requests returned")
    for r in reqs:
        res = results[r.rid]
        check(res.ok and len(res.tokens) == max_new,
              f"serving: request {r.rid} returned {len(res.tokens)} tokens "
              f"of {max_new} (error: {res.error})")
    tp = engine.throughput_report()
    say(f"serving: {requests} requests (prompt lengths "
        f"{[len(r.prompt) for r in reqs]}), {tp['tokens']} tokens in "
        f"{tp['steps']} decode steps, wall_s {wall:.2f} (compiles included)")
    probe = reqs[0]
    engine.submit(dataclasses.replace(probe, rid=requests, submitted_at=None))
    t0 = time.perf_counter()
    alone = engine.run()[requests]
    check(alone.tokens == results[probe.rid].tokens,
          f"serving: request {probe.rid} alone gave {alone.tokens}, in the "
          f"batch {results[probe.rid].tokens}")
    say(f"serving: request {probe.rid} served alone matches its batched "
        f"tokens ({max_new} tokens, wall_s {time.perf_counter() - t0:.2f})")
    return {"wall_s": wall, "tokens": tp["tokens"]}


# ---------------------------------------------------------------------------
def runtime_phase(problem, reference, run, layouts, tol, **run_kw) -> None:
    """Run ``problem`` under each (label, device indices) layout, check each
    against the reference, and the later layouts against the first."""
    ref = reference(problem)
    first = None
    for label, devices in layouts:
        out, _ = run(problem, devices, label=label, **run_kw)
        err = _rel_err(out, ref)
        say(f"{label}: max_rel_err vs host reference {err:.3e} (tol {tol:.0e})")
        check(np.all(np.isfinite(out)), f"{label}: non-finite values")
        check(err <= tol, f"{label}: error {err:.3e} above {tol:.0e}")
        if first is None:
            first = out
        else:
            diff = _rel_err(out, first)
            say(f"{label}: max_rel_diff vs {layouts[0][0]} {diff:.3e}")
            check(diff <= tol, f"{label}: differs from {layouts[0][0]} by {diff:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="runtime phases only: ACC units on one chip, then "
                         "on devices 0..3")
    ap.add_argument("--seed", type=int, default=1234,
                    help="seed of every generated input and of the weights")
    args = ap.parse_args(argv)

    devices = require_tpu()
    from repro.launch.compile_cache import configure_compile_cache

    say(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{configure_compile_cache()}")
    hot = hotspot_problem(HotspotConfig(), args.seed)
    spmm_cfg = dataclasses.replace(SpmmConfig(), seed=args.seed)
    one_chip = [("one chip", [0, 0, 0, 0])]
    if args.four_chips:
        if len(devices) < 4:
            raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, "
                             f"found {len(devices)}")
        layouts = one_chip + [("four chips", [0, 1, 2, 3])]
    else:
        layouts = one_chip
    runtime_phase(hot, hotspot_reference, run_hotspot,
                  [(f"hotspot {lbl}", d) for lbl, d in layouts], HOTSPOT_TOL)
    runtime_phase(spmm_problem(spmm_cfg), spmm_reference, run_spmm,
                  [(f"spmm {lbl}", d) for lbl, d in layouts], SPMM_TOL)
    if not args.four_chips:
        run_serving(get_config("tinyllama-1.1b"), seed=args.seed)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
