"""Readings that a correctness limit is set from, for one cell.

    python3 bench/control.py --workload hotspot-paper.hybrid --seeds 1-12 \
        --control-seeds 1-3 --seconds 3 [--out readings.json]

For each of ``--seeds`` it runs the cell as the benchmark does, with a short
window, and reads the number the check compares (the lower reading is the
largest of these).  For each of ``--control-seeds`` it puts the control, the
configuration's reference in the next lower precision, in the program's
place on the first ACC device and reads the same number (the upper reading
is the smallest of these); for a problem that sets ``PER_LOOP``, on the
first loop of the window, beside that loop's reference.  The benchmark's
own runs never run this.  It needs the chip, and runs everything in one
process.
"""

from __future__ import annotations

import json
import os
import sys
import time


def _seeds(text: str):
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def control_reading(bench, workload: str, seed: int, device) -> float:
    """The compared number with the control in the program's place."""
    from harness.cell import WARMUP_LOOPS, row_errors

    wl = bench.workload(workload)
    cfg = bench.config(wl["config"])
    problem = bench.problem(cfg["problem"])
    prob = problem.generate(cfg, seed)
    kw = {"loop": WARMUP_LOOPS} if getattr(problem, "PER_LOOP", False) else {}
    return float(row_errors(problem.control(prob, device, **kw),
                            problem.reference(prob, **kw)).max())


def readings(root, workload: str, seeds, control_seeds, seconds: float, *,
             need_chip: bool = True) -> dict:
    import jax
    from harness.cell import require_chips, run_cell
    from harness.registry import Benchmark

    bench = Benchmark(root)
    mix = bench.traffic(bench.workload(workload)["traffic"])
    if need_chip:
        require_chips(bench.workload(workload)["chips"])
    device = jax.devices()[mix["acc_devices"][0]]
    program, control = {}, {}
    for s in seeds:
        t = time.perf_counter()
        res, _ = run_cell(root, workload, s, seconds, False, need_chip=need_chip)
        program[s] = res["checks"]["max_row_rel_err"]["value"]
        print(f"control: {workload} seed {s} program {program[s]!r} correct "
              f"{res['correct']} ({time.perf_counter() - t:.1f} s)", file=sys.stderr, flush=True)
    for s in control_seeds:
        t = time.perf_counter()
        control[s] = control_reading(bench, workload, s, device)
        print(f"control: {workload} seed {s} control {control[s]!r} "
              f"({time.perf_counter() - t:.1f} s)", file=sys.stderr, flush=True)
    return {"workload": workload, "number": "max_row_rel_err",
            "program": program, "control": control,
            "lower": max(program.values()) if program else None,
            "upper": min(control.values()) if control else None}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = [readings(root, w, _seeds(args.seeds), _seeds(args.control_seeds), args.seconds)
           for w in args.workload]
    text = json.dumps(out, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, os.path.join(_root, "bench"))
    sys.exit(main())
