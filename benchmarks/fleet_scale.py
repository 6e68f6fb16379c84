"""The fleet suite: sampled-cohort federated LAG at population scale.

Demonstrates the ``repro.fleet`` acceptance claims on the convex
parameter-server repro — cohort-sized rounds over populations the dense
drivers cannot touch:

  scale_sweep       lag-wk at N ∈ {10³, 10⁴, 10⁵} clients at a fixed
                    ~6% participation ratio (k ≈ N/16): the loss gap
                    descends at every N, per-round uploads never exceed
                    k (lazy triggers keep them BELOW k), every run
                    priced per-client on a heavy-tailed ``fleet:N``
                    cluster — the O(K·k) cohort pricer.  The ratio is
                    held fixed because it is what bounds the staleness
                    of the server's aggregate: shrinking k/N at a fixed
                    stepsize α = 1/L eventually diverges (delayed-
                    gradient stability needs α·L·(N/k) ≲ O(1))
  cohort_sweep      convergence vs cohort size k at N = 10³ (bigger
                    cohorts buy more progress per round; the identity
                    cohort k = N degenerates to the sync sim, pinned by
                    tests/test_fleet.py)
  churn_selection   the churn dial × the selection rule at N = 10³:
                    Markov dropout (leave / re-join stale) stays finite,
                    and the lazy (innovation-ranked, LASG-style) rule is
                    reported next to uniform sampling
  pricing_scale     N = 10⁶ pricing-only row: price 200 sampled cohorts
                    on a million-client cluster — the pricer's cost is
                    the cohorts', never O(K·N)

Run as a script to write the artifact:

  PYTHONPATH=src python -m benchmarks.fleet_scale [--K N] [--out P]

writes ``BENCH_fleet.json`` so successive PRs can diff the trend;
``benchmarks/update_experiments.py`` splices it into EXPERIMENTS.md
between the FLEET_TABLE markers.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Tuple

import numpy as np

SCALE_NS = (1_000, 10_000, 100_000)
# ~6% participation at every N — fixed ratio, not fixed k (see docstring)
SCALE_KS = (64, 625, 6_250)
PRICING_K = 64
COHORTS = (8, 32, 128)
CHURNS = (0.0, 0.1, 0.3)
CLUSTER = "fleet:{N}@50ms/20Mbps"
PRICING_N = 1_000_000


def _run(prob, N, k, K, churn=0.0, selection="uniform", cluster=True):
    from repro.engine import Experiment
    from repro.fleet import FleetTopology
    topo = FleetTopology(population=N, cohort=k, churn=churn,
                         selection=selection)
    return Experiment(
        problem=prob, algo="lag-wk", steps=K, topology=topo,
        cluster=CLUSTER.format(N=N) if cluster else None).run()


def _gap(r):
    return (float(r.losses[0] - r.opt_loss),
            float(r.losses[-1] - r.opt_loss))


def scale_sweep(K: int = 300) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): lag-wk across population sizes at a
    fixed ~6% participation ratio (k ≈ N/16)."""
    from repro.fleet import fleet_problem

    rows, claims, recs = [], [], []
    for N, k in zip(SCALE_NS, SCALE_KS):
        prob = fleet_problem("linreg", num_clients=N, n_per=2, d=4, seed=0)
        t0 = time.time()
        r = _run(prob, N, k, K)
        us = (time.time() - t0) / K * 1e6
        gap0, gapK = _gap(r)
        rec = {
            "N": N, "k": k, "K": K,
            "gap0": gap0, "gapK": gapK,
            "uploads": r.total_comms,
            "upload_budget": K * k,                # all-cohort-upload GD
            "max_round_uploads": int(r.comms_per_iter.max()),
            "wall_seconds": r.wall_seconds,
            "us_per_round": round(us, 1),
        }
        recs.append(rec)
        rows.append({
            "name": f"fleet_scale/N={N},k={k}",
            "us_per_call": rec["us_per_round"],
            "derived": f"gap={gapK:.3g};uploads={rec['uploads']}"
                       f"/{rec['upload_budget']};"
                       f"wall_s={rec['wall_seconds']:.1f}",
        })
    claims.append(("fleet: loss gap shrinks >1000x at every N (incl. 1e5)",
                   all(r["gapK"] < 1e-3 * r["gap0"] for r in recs),
                   str([f"{r['gapK'] / r['gap0']:.3g}" for r in recs])))
    claims.append(("fleet: per-round uploads never exceed the cohort k",
                   all(r["max_round_uploads"] <= r["k"] for r in recs),
                   str([r["max_round_uploads"] for r in recs])))
    claims.append(("fleet: lazy triggers save uploads vs all-cohort GD",
                   all(r["uploads"] < r["upload_budget"] for r in recs),
                   str([r["uploads"] for r in recs])))
    claims.append(("fleet: every N priced per-client (cohort pricer)",
                   all(np.isfinite(r["wall_seconds"])
                       and r["wall_seconds"] > 0 for r in recs),
                   str([round(r["wall_seconds"], 1) for r in recs])))
    return rows, claims, recs


def cohort_sweep(K: int = 300) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): convergence vs cohort size at N = 10³."""
    from repro.fleet import fleet_problem

    N = SCALE_NS[0]
    prob = fleet_problem("linreg", num_clients=N, n_per=2, d=4, seed=0)
    rows, claims, recs = [], [], []
    for k in COHORTS:
        t0 = time.time()
        r = _run(prob, N, k, K)
        us = (time.time() - t0) / K * 1e6
        gap0, gapK = _gap(r)
        rec = {"N": N, "k": k, "K": K, "gap0": gap0, "gapK": gapK,
               "uploads": r.total_comms,
               "wall_seconds": r.wall_seconds}
        recs.append(rec)
        rows.append({
            "name": f"fleet_cohort/k={k}",
            "us_per_call": round(us, 1),
            "derived": f"gap={gapK:.3g};uploads={rec['uploads']};"
                       f"wall_s={rec['wall_seconds']:.1f}",
        })
    claims.append(("fleet: larger cohorts converge further per round",
                   all(a["gapK"] > b["gapK"]
                       for a, b in zip(recs, recs[1:])),
                   str([round(r["gapK"], 4) for r in recs])))
    return rows, claims, recs


def churn_selection(K: int = 300
                    ) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): churn dial × selection rule at N = 10³."""
    from repro.fleet import fleet_problem

    N, k = SCALE_NS[0], 32
    prob = fleet_problem("linreg", num_clients=N, n_per=2, d=4, seed=0)
    rows, claims, recs = [], [], []
    for sel in ("uniform", "innovation"):
        for churn in CHURNS:
            t0 = time.time()
            r = _run(prob, N, k, K, churn=churn, selection=sel,
                     cluster=False)
            us = (time.time() - t0) / K * 1e6
            _, gapK = _gap(r)
            rec = {"selection": sel, "churn": churn, "N": N, "k": k,
                   "gapK": gapK, "uploads": r.total_comms}
            recs.append(rec)
            rows.append({
                "name": f"fleet_dials/{sel}/churn={churn:g}",
                "us_per_call": round(us, 1),
                "derived": f"gap={gapK:.3g};uploads={rec['uploads']}",
            })
    claims.append(("fleet: every churn × selection cell runs finite",
                   all(np.isfinite(r["gapK"]) for r in recs),
                   str([round(r["gapK"], 3) for r in recs])))
    uni = {r["churn"]: r for r in recs if r["selection"] == "uniform"}
    lazy = {r["churn"]: r for r in recs if r["selection"] == "innovation"}
    claims.append(("fleet: lazy (innovation) selection converges at least "
                   "as far as uniform at churn 0 (LASG reading)",
                   lazy[0.0]["gapK"] <= uni[0.0]["gapK"],
                   f"{lazy[0.0]['gapK']:.4g} vs {uni[0.0]['gapK']:.4g}"))
    return rows, claims, recs


def pricing_scale(K: int = 200
                  ) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): the N = 10⁶ pricing-only row — price
    K sampled cohorts on a million-client cluster without ever building
    an O(K·N) mask."""
    from repro.netsim import make_cluster, price_cohort_mask

    N, k = PRICING_N, PRICING_K
    t0 = time.time()
    cl = make_cluster(CLUSTER.format(N=N))
    rng = np.random.default_rng(0)
    ids = np.sort(rng.integers(0, N, size=(K, k)), axis=1)
    mask = rng.random((K, k)) < 0.5
    secs = price_cohort_mask(ids, mask, 4 * 4.0, cl, dense_bytes=4 * 4.0)
    secs2 = price_cohort_mask(ids, mask, 4 * 4.0, cl, dense_bytes=4 * 4.0)
    us = (time.time() - t0) / K * 1e6
    rec = {"N": N, "k": k, "K": K,
           "wall_seconds": float(secs.sum()),
           "us_per_round": round(us, 1)}
    rows = [{
        "name": f"fleet_pricing/N={N}",
        "us_per_call": rec["us_per_round"],
        "derived": f"wall_s={rec['wall_seconds']:.1f}",
    }]
    claims = [("fleet: 1e6-client cohort pricing finite and deterministic "
               "per seed",
               bool(np.isfinite(secs).all() and (secs > 0).all()
                    and np.array_equal(secs, secs2)),
               f"wall_s={rec['wall_seconds']:.1f}")]
    return rows, claims, [rec]


def fleet_suite(K: int = 300):
    """benchmarks.run entry: all sub-suites' (rows, claims)."""
    r1, c1, _ = scale_sweep(K)
    r2, c2, _ = cohort_sweep(K)
    r3, c3, _ = churn_selection(K)
    r4, c4, _ = pricing_scale()
    return r1 + r2 + r3 + r4, c1 + c2 + c3 + c4


def main(argv=None) -> int:
    """Write BENCH_fleet.json: convergence + pricing vs population size,
    cohort size, churn and selection rule, diffable PR-to-PR."""
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--K", type=int, default=300)
    p.add_argument("--out", default="BENCH_fleet.json")
    args = p.parse_args(argv)

    _, claims_n, recs_n = scale_sweep(args.K)
    _, claims_k, recs_k = cohort_sweep(args.K)
    _, claims_d, recs_d = churn_selection(args.K)
    _, claims_p, recs_p = pricing_scale()
    rec = {
        "bench": "fleet",
        "problem": "fleet_problem('linreg', n_per=2, d=4) float32",
        "cluster": CLUSTER,
        "algo": "lag-wk",
        "K": args.K,
        "scale": recs_n,
        "cohort": recs_k,
        "dials": recs_d,
        "pricing": recs_p,
        "claims": [{"name": n, "ok": bool(ok), "detail": d}
                   for n, ok, d in claims_n + claims_k + claims_d
                   + claims_p],
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return 0 if all(c["ok"] for c in rec["claims"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
