"""The graph suite: lazy gossip vs always-on gossip across topologies.

Demonstrates the ``repro.graph`` acceptance claims on the convex repro —
the serverless plane keeps the paper's communication savings when the
star is replaced by a gossip graph and the lazy units become the E
DIRECTED EDGES:

  family_sweep      gd (always-on gossip) vs lag-wk (lazy edges) vs
                    laq@4 (lazy + 4-bit edge payloads) on ring,
                    torus:3x3 and expander:4 at W = 9 with the paper's
                    heterogeneous L_m.  Savings are compared at MATCHED
                    final loss: the target is the slowest-converging
                    algo's final gap per family, and each run is charged
                    the wire bytes it spent reaching that gap
                    (``RunReport.bytes_to``).  Claims: lazy gossip cuts
                    link bytes >= 2x vs always-on on ring AND expander,
                    and laq@4 compounds (fewer bytes than lag-wk
                    everywhere) — all at a consensus residual that
                    actually shrank
  pricing_row       the same ring masks priced per directed edge on a
                    heterogeneous cluster (``price_edge_mask``): lazy
                    wall-clock beats always-on wall-clock

Run as a script to write the artifact:

  PYTHONPATH=src python -m benchmarks.graph_sweep [--K N] [--out P]

writes ``BENCH_graph.json`` so successive PRs can diff the trend;
``benchmarks/update_experiments.py`` splices it into EXPERIMENTS.md
between the GRAPH_TABLE markers.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Tuple

import numpy as np

W = 9
FAMILIES = ("ring", "torus:3x3", "expander:4")
ALGOS = ("gd", "lag-wk", "laq@4")
CLUSTER = "hetero:{E}@10ms/1Gbps"


def _problem():
    from repro.core import convex
    # the paper's increasing-L_m heterogeneity (Fig. 3 regime), one shard
    # per node
    return convex.synthetic("linreg", num_workers=W, n_per=20, d=10, seed=0)


def _bytes_to(r, eps: float) -> float:
    """Wire bytes spent reaching gap <= eps (inf if never reached)."""
    b = r.bytes_to(eps)
    return float(b) if b is not None else float("inf")


def family_sweep(K: int = 400
                 ) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): algo x family grid at matched final loss."""
    from repro.engine import Experiment

    prob = _problem()
    rows, claims, recs = [], [], []
    by_family = {}
    for family in FAMILIES:
        runs = {}
        for algo in ALGOS:
            t0 = time.time()
            r = Experiment(problem=prob, algo=algo, steps=K,
                           topology=f"graph:{W}@{family}").run()
            us = (time.time() - t0) / K * 1e6
            runs[algo] = (r, us)
        # matched target: the slowest algo's final gap (every run reaches
        # its own final gap by construction, so every cell is charged the
        # bytes it spent getting THERE)
        eps = 1.001 * max(float(r.losses[-1] - r.opt_loss)
                          for r, _ in runs.values())
        fam_recs = {}
        for algo, (r, us) in runs.items():
            rec = {
                "family": family, "algo": algo, "K": K,
                "num_edges": int(r.extras["num_edges"]),
                "spectral_gap": float(r.extras["spectral_gap"]),
                "gapK": float(r.losses[-1] - r.opt_loss),
                "target_gap": eps,
                "uploads": r.total_comms,
                "upload_budget": K * int(r.extras["num_edges"]),
                "bytes_per_upload": float(r.bytes_per_upload),
                "bytes_to_target": _bytes_to(r, eps),
                "consensus_final": float(r.extras["consensus_final"]),
                "us_per_round": round(us, 1),
            }
            fam_recs[algo] = rec
            recs.append(rec)
            rows.append({
                "name": f"graph/{family}/{algo}",
                "us_per_call": rec["us_per_round"],
                "derived": f"gap={rec['gapK']:.3g};"
                           f"bytes_to_eps={rec['bytes_to_target']:.4g};"
                           f"uploads={rec['uploads']}"
                           f"/{rec['upload_budget']}",
            })
        by_family[family] = fam_recs

    for family in ("ring", "expander:4"):
        gd_b = by_family[family]["gd"]["bytes_to_target"]
        lw_b = by_family[family]["lag-wk"]["bytes_to_target"]
        claims.append((f"graph: lazy gossip cuts link bytes >= 2x vs "
                       f"always-on at matched loss on {family}",
                       np.isfinite(lw_b) and gd_b >= 2.0 * lw_b,
                       f"gd={gd_b:.4g} lag-wk={lw_b:.4g} "
                       f"({gd_b / max(lw_b, 1e-12):.1f}x)"))
    claims.append(("graph: laq@4 compounds (fewer bytes than lag-wk on "
                   "every family)",
                   all(by_family[f]["laq@4"]["bytes_to_target"]
                       < by_family[f]["lag-wk"]["bytes_to_target"]
                       for f in FAMILIES),
                   str([f"{f}:{by_family[f]['laq@4']['bytes_to_target']:.4g}"
                        for f in FAMILIES])))
    claims.append(("graph: every cell converged to the matched target "
                   "with shrinking consensus residual",
                   all(np.isfinite(r["bytes_to_target"])
                       and r["consensus_final"] < 1.0 for r in recs),
                   str([round(r["consensus_final"], 4) for r in recs])))
    return rows, claims, recs


def pricing_row(K: int = 400) -> Tuple[List[dict], List[tuple], List[dict]]:
    """(rows, claims, records): lazy vs always-on ring wall-clock under
    the per-edge pricer.  The recorded lag-wk masks are priced at a
    MODEL-scale payload (1M f32 params ≈ 4 MB per edge — a 40-byte d=10
    iterate is invisible next to 10 ms of link latency), so destination
    NIC serialization is what the numbers measure."""
    from repro.engine import Experiment
    from repro.netsim import make_cluster, price_edge_mask

    prob = _problem()
    r = Experiment(problem=prob, algo="lag-wk", steps=K,
                   topology=f"graph:{W}@ring").run()
    E = int(r.extras["num_edges"])
    cl = make_cluster(CLUSTER.format(E=E))
    payload = 4e6
    t0 = time.time()
    lazy_s = price_edge_mask(r.comm_mask, payload, cl,
                             r.extras["edge_dst"], dense_bytes=payload)
    us = (time.time() - t0) / K * 1e6
    busy_s = price_edge_mask(np.ones_like(r.comm_mask), payload, cl,
                             r.extras["edge_dst"], dense_bytes=payload)
    rec = {"family": "ring", "K": K, "num_edges": E,
           "payload_bytes": payload,
           "lazy_wall_s": float(lazy_s.sum()),
           "always_on_wall_s": float(busy_s.sum()),
           "us_per_round": round(us, 1)}
    rows = [{
        "name": "graph_pricing/ring",
        "us_per_call": rec["us_per_round"],
        "derived": f"lazy_s={rec['lazy_wall_s']:.2f};"
                   f"gd_s={rec['always_on_wall_s']:.2f}",
    }]
    claims = [("graph: lazy ring wall-clock beats always-on gossip",
               rec["lazy_wall_s"] < rec["always_on_wall_s"],
               f"{rec['lazy_wall_s']:.2f}s vs "
               f"{rec['always_on_wall_s']:.2f}s")]
    return rows, claims, [rec]


def graph_suite(K: int = 400):
    """benchmarks.run entry: all sub-suites' (rows, claims)."""
    r1, c1, _ = family_sweep(K)
    r2, c2, _ = pricing_row(K)
    return r1 + r2, c1 + c2


def main(argv=None) -> int:
    """Write BENCH_graph.json: lazy-vs-dense gossip bytes at matched loss
    across graph families, diffable PR-to-PR."""
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--K", type=int, default=400)
    p.add_argument("--out", default="BENCH_graph.json")
    args = p.parse_args(argv)

    _, claims_f, recs_f = family_sweep(args.K)
    _, claims_p, recs_p = pricing_row(args.K)
    rec = {
        "bench": "graph",
        "problem": "synthetic('linreg', num_workers=9, n_per=20, d=10) "
                   "float32 (paper increasing-L_m)",
        "cluster": CLUSTER,
        "W": W,
        "K": args.K,
        "families": recs_f,
        "pricing": recs_p,
        "claims": [{"name": n, "ok": bool(ok), "detail": d}
                   for n, ok, d in claims_f + claims_p],
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return 0 if all(c["ok"] for c in rec["claims"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
