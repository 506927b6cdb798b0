"""Run one cell traced, as ``bench/run.py --trace 1`` does, and put the
program's ``eneac.*`` spans and the device trace on one clock.

    python3 bench/timeline.py --workload <name> --seed <n> --seconds <s> [--keep <dir>]

Prints the run's result line, then, as the last line of standard output,
a JSON summary (``harness.align.summary``): each device's clock-shift
window, its modules matched to their host enqueues, and the matched chunks
still acausal after the shift; the device's idle seconds named by the
runtime phase open over them, on shifted and on unshifted times; the
share of ``eneac.parallel_for`` under no dispatcher phase; and the ACC
chunks' host overhead.  The shift windows are also logged on standard
error.  ``--keep`` writes the gzipped trace into that directory.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, os.path.join(_ROOT, "bench"))

from harness import align, cell  # noqa: E402
from harness import trace as trace_mod  # noqa: E402
from harness.registry import Benchmark  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="directory for the gzipped trace")
    args = ap.parse_args(argv)
    bench = Benchmark(_ROOT)
    wl = bench.workload(args.workload)
    mix = bench.traffic(wl["traffic"])
    kernel = bench.glue(bench.config(wl["config"])["problem"]).KERNEL
    unit_device = {f"acc{i}": d for i, d in enumerate(mix["acc_devices"])}

    found = {}
    reduce_window = trace_mod.read_window

    def read_window(log_dir, devices):
        """The harness's reduction, and the alignment of the same trace."""
        path = trace_mod.find_xspace(log_dir)
        if args.keep:
            dest = Path(args.keep) / f"{args.workload}.{args.seed}.xplane.pb.gz"
            dest.parent.mkdir(parents=True, exist_ok=True)
            dest.write_bytes(gzip.compress(path.read_bytes()))
        found["summary"] = align.summary(align.load(path), devices, kernel, unit_device)
        return reduce_window(log_dir, devices)

    # cell.run_cell reduces its trace through trace_mod.read_window, then
    # deletes it: the one place the trace can be read as well
    assert cell.trace_mod is trace_mod, "cell.py no longer reads trace.read_window"
    trace_mod.read_window = read_window
    try:
        result, log = cell.run_cell(_ROOT, args.workload, args.seed, args.seconds, True,
                                    t_start=T_START)
    except cell.NoChip as exc:
        print(f"timeline: no accelerator for this cell: {exc}", file=sys.stderr)
        return 2
    finally:
        trace_mod.read_window = reduce_window
    if "summary" not in found:
        raise RuntimeError("the cell's trace was not reduced through trace.read_window")
    summary = found["summary"]
    for line in log:
        print(f"bench: {line}", file=sys.stderr)
    for dev, d in summary["devices"].items():
        lo, hi = d["shift_window_ms"]
        print(f"timeline: device {dev}: {d['matched']}/{d['modules']} {kernel} modules "
              f"matched by {d['by']}; shift window [{lo}, {hi}] ms, shifted by {lo} ms; "
              f"acausal after the shift: {d['acausal_after_shift']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
