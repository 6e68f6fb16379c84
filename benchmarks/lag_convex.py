"""Paper-experiment benchmarks (Sec. 4): one function per table/figure.

Each returns (rows, claims) where rows are CSV-able dicts and claims is a
list of (name, passed, detail) validating the paper's qualitative results:

  Fig. 3  linear regression, increasing L_m = (1.3^{m-1}+1)²
  Fig. 4  logistic regression, uniform L_m = 4
  Fig. 5  linear regression, real-dataset stand-ins (Housing/Bodyfat/Abalone)
  Fig. 6  logistic regression, stand-ins (Ionosphere/Adult/Derm)
  Fig. 7  Gisette-shaped logistic regression
  Tab. 5  communication complexity at M = 9, 18, 27

plus the ``repro.comm`` policy comparison (rounds AND wire bytes to target
accuracy per policy — LAQ's b-bit uploads only show up in bytes).  Run as a
script to write the trajectory artifact:

  PYTHONPATH=src python -m benchmarks.lag_convex [--K N] [--bits B] [--out PATH]

writes ``BENCH_lag_convex.json`` so successive PRs can diff communication
rounds and wire bytes per policy.

The container has no UCI access: stand-ins are shape/conditioning matched
(DESIGN.md §7), so we validate orderings and reduction ratios, not the
paper's exact table values.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Tuple

import numpy as np

import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp

from repro.core import convex, simulate

EPS = 1e-8
ALGOS = ["gd", "lag-wk", "lag-ps", "cyc-iag", "num-iag"]
POLICY_ALGOS = ["gd", "lag-wk", "lag-ps", "laq", "lasg-wk"]


def _run_suite(problem, K: int, name: str) -> Tuple[List[dict], Dict[str, simulate.RunResult]]:
    theta_opt, opt_loss = problem.optimum()
    rows, results = [], {}
    for algo in ALGOS:
        t0 = time.time()
        r = simulate.run(problem, algo, K=K, opt_loss=opt_loss)
        dt_us = (time.time() - t0) / K * 1e6
        results[algo] = r
        rows.append({
            "name": f"{name}/{algo}",
            "us_per_call": round(dt_us, 2),
            "derived": f"iters={r.iters_to(EPS)};comms={r.comms_to(EPS)}",
        })
    return rows, results


def _standard_claims(name: str, res: Dict[str, simulate.RunResult],
                     iter_slack: float = 2.0) -> List[tuple]:
    claims = []
    gd, wk = res["gd"], res["lag-wk"]
    c_gd, c_wk, c_ps = gd.comms_to(EPS), wk.comms_to(EPS), res["lag-ps"].comms_to(EPS)
    i_gd, i_wk = gd.iters_to(EPS), wk.iters_to(EPS)
    ok_all = all(v is not None for v in (c_gd, c_wk, c_ps, i_gd, i_wk))
    claims.append((f"{name}: all converge to 1e-8", ok_all, ""))
    if ok_all:
        claims.append((f"{name}: LAG-WK comms < GD comms",
                       c_wk < c_gd, f"{c_wk} vs {c_gd}"))
        claims.append((f"{name}: LAG-WK iters ≈ GD iters (≤{iter_slack}×)",
                       i_wk <= iter_slack * i_gd, f"{i_wk} vs {i_gd}"))
        claims.append((f"{name}: LAG-PS comms < GD comms",
                       c_ps < c_gd, f"{c_ps} vs {c_gd}"))
    return claims


def fig3_linreg_increasing(K: int = 4000):
    prob = convex.synthetic("linreg", num_workers=9, seed=0,
                            dtype=jnp.float64)
    rows, res = _run_suite(prob, K, "fig3_linreg_incLm")
    claims = _standard_claims("fig3", res)
    # Lemma 4: small-L_m workers upload less often under LAG-WK
    per_worker = res["lag-wk"].comm_mask.sum(0)
    claims.append(("fig3: Lemma-4 skip pattern (corr(L_m, uploads) > 0.5)",
                   float(np.corrcoef(np.asarray(prob.L_m), per_worker)[0, 1]) > 0.5,
                   f"uploads per worker {per_worker.tolist()}"))
    # order-of-magnitude reduction in heterogeneous setting
    c_gd, c_wk = res["gd"].comms_to(EPS), res["lag-wk"].comms_to(EPS)
    if c_gd and c_wk:
        claims.append(("fig3: LAG-WK ≥ 3× fewer comms than GD",
                       c_wk * 3 <= c_gd, f"{c_wk} vs {c_gd}"))
    return rows, claims


def fig4_logreg_uniform(K: int = 6000):
    prob = convex.synthetic("logreg", num_workers=9, seed=1,
                            L_targets=[4.0] * 9, lam=1e-3, dtype=jnp.float64)
    rows, res = _run_suite(prob, K, "fig4_logreg_uniLm")
    claims = _standard_claims("fig4", res)
    return rows, claims


def fig5_linreg_real(K: int = 6000):
    # scale_spread 6 ≈ the conditioning spread of the paper's three UCI
    # linreg sets; the absolute iteration counts are tiny (GD ≈ 20), so the
    # iteration-parity slack is 4× ("same order", constant factors dominate)
    prob = convex.real_standin("linreg", seed=2, dtype=jnp.float64,
                               scale_spread=6.0)
    rows, res = _run_suite(prob, K, "fig5_linreg_real")
    return rows, _standard_claims("fig5", res, iter_slack=4.0)


def fig6_logreg_real(K: int = 6000):
    prob = convex.real_standin("logreg", lam=1e-3, seed=3, dtype=jnp.float64)
    rows, res = _run_suite(prob, K, "fig6_logreg_real")
    return rows, _standard_claims("fig6", res)


def fig7_gisette(K: int = 3000):
    prob = convex.gisette_standin(d=512, lam=1e-3, dtype=jnp.float64)
    rows, res = _run_suite(prob, K, "fig7_gisette")
    return rows, _standard_claims("fig7", res)


def table5_worker_scaling(K: int = 5000):
    rows, claims = [], []
    for M in (9, 18, 27):
        L_targets = [(1.3 ** (m % 9) + 1.0) ** 2 for m in range(M)]
        prob = convex.synthetic("linreg", num_workers=M, seed=4,
                                L_targets=L_targets, dtype=jnp.float64)
        r, res = _run_suite(prob, K, f"table5_M{M}")
        rows += r
        c_gd, c_wk = res["gd"].comms_to(EPS), res["lag-wk"].comms_to(EPS)
        ok = c_gd is not None and c_wk is not None and c_wk < c_gd
        claims.append((f"table5 M={M}: LAG-WK < GD comms", ok,
                       f"{c_wk} vs {c_gd}"))
    return rows, claims


def policy_comparison(K: int = 3000, bits: int = 4):
    """Every ``repro.comm`` policy on the fig-3 problem: iterations,
    communication ROUNDS and wire BYTES to the 1e-8 optimality gap.

    The point LAQ makes (Sun et al. 2019): savings must be measured in
    bytes — LAQ uploads about as often as LAG-WK but each upload is a b-bit
    quantized innovation, ~32/b× smaller than a dense float upload.
    """
    _, res = _policy_comparison_results(K=K, bits=bits)
    return _policy_rows_claims(res, bits)


def _policy_rows_claims(res, bits: int):
    rows, claims = [], []
    for algo, r in res.items():
        rows.append({
            "name": f"policy_cmp/{algo}",
            "us_per_call": 0.0,
            "derived": f"iters={r.iters_to(EPS)};comms={r.comms_to(EPS)};"
                       f"bytes={r.bytes_to(EPS)}",
        })
    ok_all = all(r.iters_to(EPS) is not None for r in res.values())
    claims.append(("policy_cmp: all policies converge to 1e-8", ok_all, ""))
    if ok_all:
        b_wk, b_laq = res["lag-wk"].bytes_to(EPS), res["laq"].bytes_to(EPS)
        claims.append((f"policy_cmp: LAQ@{bits}b wire bytes < ½ LAG-WK's",
                       b_laq < 0.5 * b_wk, f"{b_laq:.0f} vs {b_wk:.0f}"))
        c_gd, c_wk = res["gd"].comms_to(EPS), res["lag-wk"].comms_to(EPS)
        claims.append(("policy_cmp: LAG-WK comms < GD comms",
                       c_wk < c_gd, f"{c_wk} vs {c_gd}"))
        claims.append(("policy_cmp: LASG-WK ≡ LAG-WK on full batch",
                       res["lasg-wk"].comms_to(EPS) == c_wk,
                       f"{res['lasg-wk'].comms_to(EPS)} vs {c_wk}"))
    return rows, claims


def _policy_comparison_results(K: int, bits: int):
    prob = convex.synthetic("linreg", num_workers=9, seed=0,
                            dtype=jnp.float64)
    _, opt = prob.optimum()
    res = {}
    for algo in POLICY_ALGOS:
        t0 = time.time()
        r = simulate.run(prob, algo, K=K, opt_loss=opt, bits=bits)
        res[algo] = (r, time.time() - t0)
    return prob, {a: r for a, (r, _) in res.items()}


ALL_BENCHES = [fig3_linreg_increasing, fig4_logreg_uniform, fig5_linreg_real,
               fig6_logreg_real, fig7_gisette, table5_worker_scaling,
               policy_comparison]


def engine_scenarios(K: int = 1500):
    """Beyond-paper combinations the ``repro.engine`` redesign makes
    one-config (EXPERIMENTS.md §Engine scenarios): LAG-Adam in the convex
    sim, scheduled LAQ, and prox-LAG — all through the ``Experiment``
    front door."""
    from repro.engine import Experiment
    prob = convex.synthetic("linreg", num_workers=9, seed=0,
                            dtype=jnp.float64)
    _, opt = prob.optimum()
    rows, claims = [], []
    runs = {
        "lag-wk": Experiment(problem=prob, algo="lag-wk", steps=K,
                             opt_loss=opt),
        "lag-adam": Experiment(problem=prob, algo="lag-wk", server="adam",
                               steps=K, opt_loss=opt),
        "cyc-laq@4": Experiment(problem=prob, algo="cyc-laq@4", steps=K,
                                opt_loss=opt),
        "prox-lag": Experiment(problem=prob, algo="lag-wk", l1=5.0,
                               steps=K),
    }
    res = {}
    for name, exp in runs.items():
        t0 = time.time()
        r = exp.run()
        res[name] = r
        row = r.summary(eps=EPS)
        rows.append({
            "name": f"engine/{name}",
            "us_per_call": round((time.time() - t0) / K * 1e6, 2),
            "derived": f"iters={row['iters_to_eps']};"
                       f"comms={row['comms_to_eps']};"
                       f"bytes={row['bytes_to_eps']};server={r.server}",
        })
    claims.append(("engine: lag-adam (convex) converges to 1e-4",
                   res["lag-adam"].iters_to(1e-4) is not None,
                   f"iters={res['lag-adam'].iters_to(1e-4)}"))
    claims.append(("engine: lag-adam uploads < adam-equivalent GD uploads",
                   res["lag-adam"].total_comms < K * prob.num_workers,
                   f"{res['lag-adam'].total_comms} vs {K * prob.num_workers}"))
    claims.append(("engine: cyc-laq is one b-bit upload per round",
                   (res["cyc-laq@4"].comms_per_iter <= 1).all()
                   and res["cyc-laq@4"].bytes_per_upload
                   < 0.25 * res["lag-wk"].bytes_per_upload,
                   f"bpu={res['cyc-laq@4'].bytes_per_upload}"))
    claims.append(("engine: prox-LAG composite objective decreases",
                   res["prox-lag"].losses[-1] < res["prox-lag"].losses[0],
                   f"{res['prox-lag'].losses[0]:.3f} → "
                   f"{res['prox-lag'].losses[-1]:.3f}"))
    return rows, claims


ALL_BENCHES.append(engine_scenarios)



def prox_lasso(K: int = 5000):
    """Beyond-paper: PROXIMAL LAG (the extension flagged in the paper's
    R2/Conclusions) on l1-regularized linear regression."""
    prob = convex.synthetic("linreg", num_workers=9, seed=0,
                            dtype=jnp.float64)
    l1 = 5.0
    gd = simulate.run(prob, "gd", K=K, l1=l1)
    opt = float(gd.losses.min())
    rows, claims = [], []
    res = {}
    for algo in ("gd", "lag-wk", "lag-ps"):
        t0 = time.time()
        r = simulate.run(prob, algo, K=K, l1=l1, opt_loss=opt)
        res[algo] = r
        eps = max(1e-8, 1e-9 * opt)
        rows.append({"name": f"prox_lasso/{algo}",
                     "us_per_call": round((time.time() - t0) / K * 1e6, 2),
                     "derived": f"iters={r.iters_to(eps)};comms={r.comms_to(eps)}"})
    eps = max(1e-8, 1e-9 * opt)
    c_gd, c_wk = res["gd"].comms_to(eps), res["lag-wk"].comms_to(eps)
    claims.append(("prox_lasso: prox-LAG-WK < prox-GD comms",
                   c_gd is not None and c_wk is not None and c_wk < c_gd,
                   f"{c_wk} vs {c_gd}"))
    return rows, claims


def xi_tradeoff(K: int = 3000):
    """Ablation of the paper's ξ knob (eq. 24 trade-off): larger ξ skips
    more aggressively — fewer uploads per iteration, more iterations."""
    prob = convex.synthetic("linreg", num_workers=9, seed=0,
                            dtype=jnp.float64)
    _, opt = prob.optimum()
    rows, claims = [], []
    iters_list, comms_list = [], []
    for xi in (0.02, 0.1, 0.5, 0.9):
        t0 = time.time()
        r = simulate.run(prob, "lag-wk", K=K, xi=xi, opt_loss=opt)
        it, cm = r.iters_to(EPS), r.comms_to(EPS)
        iters_list.append(it)
        comms_list.append(cm)
        rows.append({"name": f"xi_tradeoff/xi={xi}",
                     "us_per_call": round((time.time() - t0) / K * 1e6, 2),
                     "derived": f"iters={it};comms={cm}"})
    ok = all(v is not None for v in iters_list + comms_list)
    claims.append(("xi_tradeoff: all ξ converge", ok, ""))
    if ok:
        claims.append(("xi_tradeoff: iterations nondecreasing in ξ",
                       iters_list == sorted(iters_list), str(iters_list)))
        # eq. (24)'s trade-off: per-ROUND upload fraction falls with ξ
        # (total-to-ε can still favour small ξ — iteration growth wins here)
        per_round = [c / i for c, i in zip(comms_list, iters_list)]
        claims.append(("xi_tradeoff: uploads-per-round decreasing in ξ",
                       all(a > b for a, b in zip(per_round, per_round[1:])),
                       str([round(p, 2) for p in per_round])))
    return rows, claims


def main(argv=None) -> int:
    """Write BENCH_lag_convex.json: per-policy rounds AND wire bytes to the
    target accuracy, so the convex-bench trajectory can be diffed PR-to-PR."""
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--K", type=int, default=3000)
    p.add_argument("--bits", type=int, default=4)
    p.add_argument("--out", default="BENCH_lag_convex.json")
    args = p.parse_args(argv)

    _, res = _policy_comparison_results(K=args.K, bits=args.bits)
    _, claims = _policy_rows_claims(res, args.bits)
    policies = {}
    for algo, r in res.items():
        policies[algo] = {
            "iters_to_eps": r.iters_to(EPS),
            "comm_rounds_to_eps": r.comms_to(EPS),
            "wire_bytes_to_eps": r.bytes_to(EPS),
            "bytes_per_upload": r.bytes_per_upload,
        }
    rec = {
        "bench": "lag_convex",
        "problem": "fig3 linreg M=9 increasing L_m, float64",
        "eps": EPS,
        "K": args.K,
        "laq_bits": args.bits,
        "policies": policies,
        "claims": [{"name": n, "ok": bool(ok), "detail": d}
                   for n, ok, d in claims],
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return 0 if all(c["ok"] for c in rec["claims"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
