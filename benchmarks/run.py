# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark orchestrator.

  PYTHONPATH=src python -m benchmarks.run [--full]

One section per paper table/figure:
  tab3      -- Table 3 parameter derivations (exact reproduction)
  fig2      -- Figure 2 convex experiments: EF-BV vs EF21 bits-to-accuracy
  fig3      -- Figure/Appx C.3 nonconvex experiments
  n_scaling -- Table 1 row 5: rate improves with n (EF-BV), flat (EF21)
               (benchmarks/zoo_scaling.py; the zoo model-scale rows run in
               benchmarks/ci_bench.py)
  compressor-- compression micro-benchmarks incl. the Pallas kernel
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (slower); default is fast mode")
    ap.add_argument("--only", default="",
                    help="comma list of sections (tab3,fig2,fig3,n_scaling,"
                         "compressor)")
    args = ap.parse_args()
    fast = not args.full
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (compressor_bench, paper_fig2, paper_fig3,
                            paper_tab3, zoo_scaling)
    from benchmarks.common import emit

    sections = [
        ("tab3", lambda: paper_tab3.run(fast)),
        ("compressor", lambda: compressor_bench.run(fast)),
        ("fig2", lambda: paper_fig2.run(fast)[0]),
        ("fig3", lambda: paper_fig3.run_bench(fast)),
        ("n_scaling", lambda: zoo_scaling.run_bench(fast)),
    ]
    print("name,us_per_call,derived")
    for name, fn in sections:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # keep the harness running, report the section
            print(f"{name}/ERROR,,{type(e).__name__}:{e}", flush=True)
            continue
        emit(rows)
        print(f"{name}/_elapsed,{(time.time() - t0) * 1e6:.0f},s={time.time() - t0:.1f}",
              flush=True)


if __name__ == "__main__":
    main()
