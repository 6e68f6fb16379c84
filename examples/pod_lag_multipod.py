"""Pod-level LAG demo: 2 simulated pods, cross-pod all-reduce actually
SKIPPED (lax.cond) on rounds where no pod's gradient changed enough.

  PYTHONPATH=src python examples/pod_lag_multipod.py --steps 60

This is the beyond-paper deployment of LAG on the TPU cost model (DCI
between pods = the paper's expensive WAN link); see DESIGN.md §3.
Run standalone — it forces 8 host devices before importing jax.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs
from repro.dist import pod_lag
from repro.dist.lag_trainer import TrainerConfig
from repro.launch.mesh import make_mesh


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args()

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("llama3.2-1b").reduced()
    tcfg = TrainerConfig(algo="lag-wk", num_workers=2, lr=args.lr)
    state = pod_lag.init_state(jax.random.PRNGKey(0), cfg, tcfg, n_pods=2)
    step_fn = jax.jit(pod_lag.make_pod_lag_step(cfg, tcfg, mesh),
                      donate_argnums=(0,))
    stream = TokenStream(vocab=cfg.vocab_size, seed=0)
    batch = make_heterogeneous_inputs(cfg, stream, 0, 2, 16, 128)

    grad_bytes = sum(x.size * 4 for x in jax.tree_util.tree_leaves(
        state["params"]))
    with jax.set_mesh(mesh):
        for step in range(args.steps):
            state, m = step_fn(state, batch)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:3d} loss {float(m['loss']):.4f} "
                      f"pod-uploads {int(m['comm_this_round'])}/2 "
                      f"round skipped: {bool(m['skipped_round'])}")
    skipped = int(jax.device_get(state["lag"]["rounds_skipped"]))
    saved = skipped * 2 * grad_bytes * 0.5   # ring all-reduce ≈ 2·(n-1)/n·B
    print(f"\nrounds with ZERO cross-pod traffic: {skipped}/{args.steps} "
          f"(≈{saved/2**20:.0f} MiB DCI saved for this toy model)")


if __name__ == "__main__":
    main()
