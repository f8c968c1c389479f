"""Benchmark orchestrator: one suite per paper table/figure + roofline.

  python -m benchmarks.run [--quick] [--only fig2,dual,...]

Prints ``name,us_per_call,derived`` CSV (one line per measurement) and
persists raw JSON under experiments/bench/.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps (CI-sized)")
    ap.add_argument("--only", default="all",
                    help="comma list: fig2,table23,dual,serve,kernels,roofline")
    args = ap.parse_args()
    which = set(args.only.split(",")) if args.only != "all" else {
        "fig2", "table23", "dual", "serve", "kernels", "roofline"}

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = []

    def section(name, fn):
        if name not in which:
            return
        try:
            for rec in fn():
                print(rec.csv(), flush=True)
        except Exception as e:
            failures.append((name, e))
            traceback.print_exc()

    if "fig2" in which or "table23" in which:
        try:
            from benchmarks import fig2_strategies
            kw = (dict(n_users=60, n_items=2000, scenarios=(50, 500),
                       dual_iters=200) if args.quick else {})
            rows = fig2_strategies.run(**kw)
            section("fig2", lambda: fig2_strategies.records(rows))

            from benchmarks import table23_regression
            tables = table23_regression.build_table(rows)
            section("table23", lambda: table23_regression.records(tables))
        except Exception as e:
            failures.append(("fig2", e))
            traceback.print_exc()

    if "dual" in which:
        from benchmarks import dual_scaling
        kw = (dict(batch=32, iters=200, sweeps=((100, 5), (1000, 5)))
              if args.quick else {})
        section("dual", lambda: dual_scaling.records(dual_scaling.run(**kw)))

    if "serve" in which:
        from benchmarks import latency_serve
        kw = (dict(sizes=((1000, 5, 50), (10000, 8, 50)), batches=(1, 64),
                   n_db=2000) if args.quick else {})
        section("serve", lambda: latency_serve.records(latency_serve.run(**kw)))
        ekw = dict(n_requests=320, trials=3) if args.quick else {}
        section("serve", lambda: latency_serve.records_engine(
            latency_serve.run_engine(**ekw)))

    if "kernels" in which:
        from benchmarks import kernel_bench
        section("kernels", lambda: kernel_bench.records(
            kernel_bench.run(quick=args.quick)))

    if "roofline" in which:
        from benchmarks import roofline_report
        recs = []
        for mesh in ("single", "multi"):
            rows = roofline_report.build_table(mesh)
            recs += roofline_report.records(rows, mesh)
        section("roofline", lambda: recs)

    if failures:
        print(f"# {len(failures)} benchmark sections failed", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
