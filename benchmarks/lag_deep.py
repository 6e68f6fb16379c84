"""LAG inside the deep-learning trainer (beyond the paper's convex tests):
reduced llama3.2-1b, heterogeneous worker shards, full-batch regime.
Validates that the distributed LAG trainer reduces uploads while matching
GD's loss trajectory.

Run as a script to start the perf trajectory:

  PYTHONPATH=src python -m benchmarks.lag_deep [--steps N] [--out PATH]

writes ``BENCH_lag_deep.json`` (steps/sec per algorithm + uploads saved vs
GD) so successive PRs can diff throughput and communication.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs
from repro.dist import TrainerConfig, init_state, make_train_step


def lag_trainer_bench(steps: int = 50, workers: int = 8):
    cfg = get_config("llama3.2-1b").reduced()
    stream = TokenStream(vocab=cfg.vocab_size, seed=0)
    batch = make_heterogeneous_inputs(cfg, stream, 0, workers, 16, 128)
    rows, claims = [], []
    losses = {}
    comms = {}
    for algo in ("gd", "lag-wk", "lag-adam"):
        tcfg = TrainerConfig(algo=algo, num_workers=workers,
                             lr=0.05 if algo != "lag-adam" else 3e-3)
        state = init_state(jax.random.PRNGKey(0), cfg, tcfg)
        step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
        state, m = step_fn(state, batch)   # compile + step 0
        t0 = time.time()
        for _ in range(steps - 1):
            state, m = step_fn(state, batch)
        dt_us = (time.time() - t0) / max(steps - 1, 1) * 1e6
        loss = float(m["loss"])
        total = int(jax.device_get(state["lag"]["comm_total"]))
        losses[algo], comms[algo] = loss, total
        rows.append({"name": f"lag_deep/{algo}",
                     "us_per_call": round(dt_us, 1),
                     "derived": f"loss={loss:.4f};uploads={total}"})
    gd_total = steps * workers
    claims.append(("lag_deep: LAG-WK saves uploads vs GD",
                   comms["lag-wk"] < comms["gd"],
                   f"{comms['lag-wk']} vs {comms['gd']}"))
    claims.append(("lag_deep: LAG-WK loss within 10% of GD",
                   losses["lag-wk"] <= 1.10 * losses["gd"],
                   f"{losses['lag-wk']:.4f} vs {losses['gd']:.4f}"))
    return rows, claims


ALL_BENCHES = [lag_trainer_bench]


def main(argv=None) -> int:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("--out", default="BENCH_lag_deep.json")
    args = p.parse_args(argv)

    rows, claims = lag_trainer_bench(steps=args.steps, workers=args.workers)
    algos = {}
    for r in rows:
        algo = r["name"].split("/", 1)[1]
        derived = dict(kv.split("=") for kv in r["derived"].split(";"))
        algos[algo] = {
            "us_per_call": r["us_per_call"],
            "steps_per_sec": round(1e6 / r["us_per_call"], 3),
            "loss": float(derived["loss"]),
            "uploads": int(derived["uploads"]),
        }
    gd_uploads = algos["gd"]["uploads"]
    rec = {
        "bench": "lag_deep",
        "steps": args.steps,
        "workers": args.workers,
        "algos": algos,
        "uploads_saved_vs_gd": {
            a: gd_uploads - algos[a]["uploads"] for a in algos if a != "gd"},
        "claims": [{"name": n, "ok": bool(ok), "detail": d}
                   for n, ok, d in claims],
    }
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    return 0 if all(c["ok"] for c in rec["claims"]) else 1


if __name__ == "__main__":
    raise SystemExit(main())
