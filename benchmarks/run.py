# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV for the micro-benches, then the paper-table reproductions and the
# roofline analysis derived from the dry-run artifacts.
#
#   PYTHONPATH=src python -m benchmarks.run [--full] [--skip-convergence]
#                                           [--diff [BASELINE_DIR]]
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale budgets (hours); default quick mode")
    ap.add_argument("--skip-convergence", action="store_true",
                    help="only micro-benches + complexity + roofline")
    ap.add_argument("--diff", nargs="?", const="benchmarks/results/smoke",
                    default=None, metavar="BASELINE_DIR",
                    help="after the sweeps, diff the fresh BENCH_*.json "
                         "artifacts against this baseline directory "
                         "(repro.obs.diff; exits nonzero on a >5%% "
                         "regression in any monitored modeled column)")
    args = ap.parse_args()
    quick = not args.full
    t0 = time.time()

    print("name,us_per_call,derived")
    from benchmarks import microbench

    microbench.run(quick=quick)

    from benchmarks import table3_complexity

    table3_complexity.run(quick=quick)

    from benchmarks import roofline

    roofline.run()

    if not args.skip_convergence:
        from benchmarks import table1_convex, table2_nonconvex, table4_comm_cost

        table1_convex.run(quick=quick)
        table2_nonconvex.run(quick=quick)
        table4_comm_cost.run(quick=quick)

    print(f"\n[benchmarks] done in {time.time() - t0:.0f}s")

    if args.diff:
        from tools.bench_diff import main as bench_diff_main

        rc = bench_diff_main([args.diff, "artifacts/bench"])
        if rc:
            raise SystemExit(rc)


if __name__ == "__main__":
    main()
