"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  * table1_{hotspot,spmm}_*  — Table 1 reproduction (7 configs each)
  * chunksweep_*             — Fig. 4 chunk-size sweep (the >1/4 cliff)
  * serving_*                — continuous vs static batching (interrupt
                               analogue at the serving layer)
  * hotspot_/spmm_/flash_*   — kernel micro-benchmarks
  * roofline_*               — per-(arch × shape) three-term roofline from
                               the committed dry-run artifacts

``python -m benchmarks.run [--quick]``
"""

from __future__ import annotations

import argparse
import sys

from repro.launch.compile_cache import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-scale)")
    ap.add_argument("--skip-table1", action="store_true")
    args = ap.parse_args()
    configure_compile_cache()
    quick = args.quick

    rows = []

    from benchmarks.bench_kernels import kernel_rows
    rows += kernel_rows(quick=quick)

    from benchmarks.bench_serving import serving_rows
    rows += serving_rows(quick=quick)

    if not args.skip_table1:
        from benchmarks.table1_eneac import chunk_sweep, table1
        for bench in ("hotspot", "spmm"):
            t1 = table1(bench, quick=quick)
            rows += [(n, 1e3 / max(thr, 1e-9),
                      f"throughput={thr:.2f}items_per_ms;load_balance={lb:.2f}")
                     for n, thr, _, lb, *_rest in t1]
        rows += [(n, 1e3 / max(thr, 1e-9),
                  f"throughput={thr:.2f}items_per_ms;load_balance={lb:.2f}")
                 for n, thr, _, lb, *_rest in chunk_sweep(quick=quick)]

    from benchmarks.roofline import roofline_rows
    rows += roofline_rows()

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
