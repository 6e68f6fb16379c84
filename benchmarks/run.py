"""Benchmark harness — one entry per paper table/figure, plus the
deep-trainer LAG benchmark and (when dry-run artifacts exist) the roofline
table.  Prints ``name,us_per_call,derived`` CSV to stdout and a claim
validation summary to stderr.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced iteration caps
"""
from __future__ import annotations

import argparse
import os
import sys

import jax
jax.config.update("jax_enable_x64", True)   # the convex repro needs 1e-8 gaps


def main(argv=None) -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--dryrun-dir", default="experiments/dryrun")
    args = p.parse_args(argv)

    from benchmarks import (lag_convex, lag_deep, fleet_scale, graph_sweep,
                            netsim_sweep, perf_comm)

    rows, claims = [], []
    suites = [
        ("fig3", lambda: lag_convex.fig3_linreg_increasing(
            K=1500 if args.quick else 4000)),
        ("fig4", lambda: lag_convex.fig4_logreg_uniform(
            K=2000 if args.quick else 6000)),
        ("fig5", lambda: lag_convex.fig5_linreg_real(
            K=2000 if args.quick else 6000)),
        ("fig6", lambda: lag_convex.fig6_logreg_real(
            K=2000 if args.quick else 6000)),
        ("fig7", lambda: lag_convex.fig7_gisette(
            K=1000 if args.quick else 3000)),
        ("table5", lambda: lag_convex.table5_worker_scaling(
            K=2000 if args.quick else 5000)),
        ("lag_deep", lambda: lag_deep.lag_trainer_bench(
            steps=20 if args.quick else 50)),
        ("prox_lasso", lambda: lag_convex.prox_lasso(
            K=1500 if args.quick else 5000)),
        ("xi_tradeoff", lambda: lag_convex.xi_tradeoff(
            K=1500 if args.quick else 3000)),
        ("policy_cmp", lambda: lag_convex.policy_comparison(
            K=1500 if args.quick else 3000)),
        ("engine", lambda: lag_convex.engine_scenarios(
            K=800 if args.quick else 1500)),
        ("netsim", lambda: netsim_sweep.netsim_suite(
            K=2000 if args.quick else 4000,
            steps=12 if args.quick else 50)),
        ("fleet", lambda: fleet_scale.fleet_suite(
            K=100 if args.quick else 300)),
        ("graph", lambda: graph_sweep.graph_suite(
            K=200 if args.quick else 400)),
        ("perf_comm", lambda: perf_comm.perf_comm_suite(quick=args.quick)),
    ]
    for name, fn in suites:
        try:
            r, c = fn()
            rows += r
            claims += c
        except Exception as e:  # noqa: BLE001
            claims.append((f"{name}: ran", False, f"{type(e).__name__}: {e}"))

    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")

    # roofline table from dry-run artifacts, if present
    if os.path.isdir(args.dryrun_dir) and os.listdir(args.dryrun_dir):
        try:
            from benchmarks import roofline
            tab = roofline.table(args.dryrun_dir)
            ok_rows = [t for t in tab if t.get("status") == "ok"]
            for t in ok_rows:
                print(f"roofline/{t['arch']}/{t['shape']},0,"
                      f"bottleneck={t['bottleneck']};"
                      f"compute_s={t['compute_s']:.5f};"
                      f"memory_s={t['memory_s']:.5f};"
                      f"collective_s={t['collective_s']:.5f}")
        except Exception as e:  # noqa: BLE001
            claims.append(("roofline: ran", False, str(e)))

    print("\n== claim validation ==", file=sys.stderr)
    n_fail = 0
    for name, ok, detail in claims:
        n_fail += (not ok)
        print(f"  [{'PASS' if ok else 'FAIL'}] {name} {detail}",
              file=sys.stderr)
    print(f"{len(claims) - n_fail}/{len(claims)} claims validated",
          file=sys.stderr)
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
