"""The netsim suite: heterogeneity sweep + async staleness sensitivity.

Reproduces the paper's Sec.-3 trend — LAG's communication savings GROW
with data heterogeneity — on the axis the motivation actually lives on:
simulated wall-clock to target accuracy under an event-driven network
cost model (``repro.netsim``).  Two sub-suites:

  hetero_sweep            the heterogeneity dial h ∈ [0, 1]
                          (``repro.netsim.hetero_problem``: realized L_m
                          spread 1×→21×, largest L_m fixed) × {gd,
                          lag-wk}, every run priced on the same cluster;
                          claims pin the realized spread AND the
                          wall-clock advantage increasing monotonically
                          along the dial
  staleness_sensitivity   bounded-staleness async LAG
                          (``topology="async:W@τ"``) on the reduced deep
                          trainer: τ = 0 must match the sync trajectory
                          exactly (the tests/golden/ pinning, asserted
                          here on upload counts + final loss), larger τ
                          gives the reference numbers EXPERIMENTS.md
                          §Heterogeneity & wall-clock quotes

Run as a script to write the trajectory artifact:

  PYTHONPATH=src python -m benchmarks.netsim_sweep [--K N] [--steps N] [--out P]

writes ``BENCH_netsim.json`` so successive PRs can diff the trend;
``benchmarks/update_experiments.py`` splices it into EXPERIMENTS.md
between the NETSIM_TABLE markers.

The pricing cluster is bandwidth-bound on purpose (1 Mbps uplinks, 400-B
float64 payloads): on a fat 1 Gbps link a d = 50 convex upload moves in
3 µs and latency swamps the trend — LAG's wall-clock win needs uploads
to actually cost something, exactly the paper's WAN setting.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Tuple

import numpy as np

import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

EPS = 1e-8
DIAL = (0.0, 0.25, 0.5, 0.75, 1.0)
CLUSTER = "hetero:9@2ms/1Mbps"
STALENESS = (0, 1, 2, 4)


def hetero_sweep(K: int = 4000) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): gd vs lag-wk across the dial, priced."""
    from repro.engine import Experiment
    from repro.netsim import hetero_problem

    rows, claims, recs = [], [], []
    for h in DIAL:
        prob = hetero_problem("linreg", h=h, seed=0, dtype=jnp.float64)
        _, opt = prob.optimum()
        t0 = time.time()
        res = {algo: Experiment(problem=prob, algo=algo, steps=K,
                                opt_loss=opt, cluster=CLUSTER).run()
               for algo in ("gd", "lag-wk")}
        us = (time.time() - t0) / (2 * K) * 1e6
        gd, wk = res["gd"], res["lag-wk"]
        rec = {
            "h": h,
            "L_m_spread": wk.extras["L_m_spread"],
            "hetero_score": wk.extras["hetero_score"],
            "gd": {"iters": gd.iters_to(EPS), "comms": gd.comms_to(EPS),
                   "seconds": gd.seconds_to(EPS)},
            "lag_wk": {"iters": wk.iters_to(EPS), "comms": wk.comms_to(EPS),
                       "seconds": wk.seconds_to(EPS)},
        }
        ok = all(v is not None for v in
                 (rec["gd"]["seconds"], rec["lag_wk"]["seconds"]))
        rec["comm_advantage"] = (rec["gd"]["comms"] / rec["lag_wk"]["comms"]
                                 if ok else None)
        rec["wallclock_advantage"] = (
            rec["gd"]["seconds"] / rec["lag_wk"]["seconds"] if ok else None)
        recs.append(rec)
        rows.append({
            "name": f"netsim_hetero/h={h:g}",
            "us_per_call": round(us, 2),
            "derived": f"spread={rec['L_m_spread']:.2f};"
                       f"t_gd={rec['gd']['seconds']};"
                       f"t_wk={rec['lag_wk']['seconds']};"
                       f"adv={rec['wallclock_advantage']}",
        })

    ok_all = all(r["wallclock_advantage"] is not None for r in recs)
    claims.append(("netsim: gd AND lag-wk converge to 1e-8 at every h",
                   ok_all, ""))
    if ok_all:
        spreads = [r["L_m_spread"] for r in recs]
        claims.append(("netsim: realized L_m spread increases monotonically "
                       "along the dial",
                       all(a < b for a, b in zip(spreads, spreads[1:])),
                       str([round(s, 2) for s in spreads])))
        advs = [r["wallclock_advantage"] for r in recs]
        claims.append(("netsim: LAG-WK wall-clock advantage over GD "
                       "increases monotonically along the dial (Sec. 3)",
                       all(a < b for a, b in zip(advs, advs[1:])),
                       str([round(a, 2) for a in advs])))
        cadvs = [r["comm_advantage"] for r in recs]
        claims.append(("netsim: upload-count advantage increases "
                       "monotonically along the dial",
                       all(a < b for a, b in zip(cadvs, cadvs[1:])),
                       str([round(a, 2) for a in cadvs])))
    return rows, claims, recs


def staleness_sensitivity(steps: int = 50, workers: int = 4
                          ) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): async LAG-WK across staleness bounds."""
    from repro.engine import Experiment

    rows, claims, recs = [], [], []
    sync = Experiment(model="llama3.2-1b", algo="lag-wk", steps=steps,
                      workers=workers).run()
    for tau in STALENESS:
        t0 = time.time()
        r = Experiment(model="llama3.2-1b", algo="lag-wk",
                       topology=f"async:{workers}@{tau}", steps=steps).run()
        us = (time.time() - t0) / steps * 1e6
        rec = {"staleness": tau, "final_loss": float(r.losses[-1]),
               "uploads": r.total_comms,
               "uploads_per_worker": r.uploads_per_worker.tolist()}
        recs.append(rec)
        rows.append({
            "name": f"netsim_async/tau={tau}",
            "us_per_call": round(us, 2),
            "derived": f"final_loss={rec['final_loss']:.4f};"
                       f"uploads={rec['uploads']}",
        })
        if tau == 0:
            claims.append(("netsim: async@0 ≡ sync (uploads + final loss, "
                           "the golden pinning)",
                           rec["uploads"] == sync.total_comms
                           and rec["final_loss"] == float(sync.losses[-1]),
                           f"{rec['uploads']}/{rec['final_loss']:.4f} vs "
                           f"{sync.total_comms}/"
                           f"{float(sync.losses[-1]):.4f}"))
    claims.append(("netsim: async finite at every staleness bound",
                   all(np.isfinite(r["final_loss"]) for r in recs),
                   str([r["final_loss"] for r in recs])))
    return rows, claims, recs


def netsim_suite(K: int = 4000, steps: int = 50):
    """benchmarks.run entry: both sub-suites' (rows, claims)."""
    r1, c1, _ = hetero_sweep(K)
    r2, c2, _ = staleness_sensitivity(steps)
    return r1 + r2, c1 + c2


def main(argv=None) -> int:
    """Write BENCH_netsim.json: the rounds/wall-clock-vs-heterogeneity
    trend plus async staleness sensitivity, diffable PR-to-PR."""
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--K", type=int, default=4000)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--out", default="BENCH_netsim.json")
    args = p.parse_args(argv)

    _, claims_h, recs_h = hetero_sweep(args.K)
    _, claims_s, recs_s = staleness_sensitivity(args.steps)
    rec = {
        "bench": "netsim",
        "problem": "hetero_problem('linreg', h) M=9 float64, L_max fixed",
        "cluster": CLUSTER,
        "eps": EPS,
        "K": args.K,
        "dial": recs_h,
        "async_steps": args.steps,
        "staleness": recs_s,
        "claims": [{"name": n, "ok": bool(ok), "detail": d}
                   for n, ok, d in claims_h + claims_s],
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return 0 if all(c["ok"] for c in rec["claims"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
