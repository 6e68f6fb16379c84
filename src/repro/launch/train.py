"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \\
      --algo lag-wk --steps 200 --batch 32 --seq 256 --workers 8 \\
      --hetero 0.8 --cluster hetero:8@10ms/1Gbps

Runs on whatever devices the process sees (the TPU mesh via --mesh prod).
Logs loss + LAG communication counters; checkpoints include LAG state.
``--hetero`` dials the worker shards' data heterogeneity
(``repro.netsim.hetero``), ``--cluster`` prices the run's upload mask
through the event-driven network cost model (``repro.netsim.cluster``)
and prints simulated wall-clock vs the GD baseline at exit.

``--topology`` selects the placement backend (``repro.engine.topology``
specs): ``shards`` (the default flat vmap), ``pods:2``, ``async:4@2``,
``devices:8`` (one worker per real device, ``repro.devrun``), the
sampled-cohort federated fleet ``fleet:100000@64`` (``repro.fleet`` —
per-round k-client cohorts from an N-client population; ``--fleet-churn``
/ ``--fleet-selection`` dial dropout and lazy server-side client
selection, and ``--cluster`` prices the cohort uploads per-client via
``price_cohort_mask``), or the serverless gossip graph ``graph:9@ring``
(``repro.graph`` — per-edge lazy triggers + Metropolis mixing;
``--cluster`` is sized to the E directed edges and priced via
``price_edge_mask``).

``--profile DIR`` writes a JAX profiler trace of the steps after the
first (which compiles): each step is a ``train`` step annotation, the
host spans are ``repro.input`` (the batch and its ``device_put``),
``repro.log`` (the logger's sync on the loss) and ``repro.ckpt``
(checkpoint saves), and the program's device ops carry the round's
``lag.*`` stage scopes (``repro.engine.rounds.STAGES``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import metrics as metrics_lib
from repro.checkpoint import save, restore, latest_step
from repro.configs import get_config
from repro.data import TokenStream, make_heterogeneous_inputs, make_inputs
from repro.dist import (TrainerConfig, lag_trainer, make_train_step,
                        tree_shardings, batch_shardings)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh


@dataclasses.dataclass
class TrainRun:
    """What one launcher run did: its final state, one loss per step, the
    upload count against GD's, and host-clock step times that end in
    ``block_until_ready``."""
    state: Dict
    losses: np.ndarray
    comm_masks: np.ndarray       # (steps, units) bool upload decisions
    uploads: int
    gd_uploads: int
    first_step_seconds: float    # compilation + the first step
    step_seconds: float          # mean of the later steps (nan if none)
    step_fn: Any                 # the jitted step
    arg_specs: Tuple             # shapes and shardings of its first call
    mesh: Any                    # the context mesh it was traced under

    def compiled(self):
        """The step as compiled for the run (a hit in jit's own cache)."""
        with jax.set_mesh(self.mesh):
            return self.step_fn.lower(*self.arg_specs).compile()


def build_argparser():
    p = argparse.ArgumentParser(description="LAG distributed trainer")
    p.add_argument("--arch", default="llama3.2-1b")
    p.add_argument("--algo", default="lag-wk",
                   help="trainer algo or any repro.comm policy spec "
                        f"({', '.join(lag_trainer.ALGOS)}, 'laq@8', "
                        "'cyc-iag', ...)")
    p.add_argument("--server", default=None,
                   help="repro.engine server-optimizer spec overriding the "
                        "algo default (e.g. 'prox-l1@1e-4', 'momentum@0.9')")
    p.add_argument("--topology", default=None,
                   help="repro.engine topology spec (e.g. 'shards', "
                        "'pods:2', 'async:4@2', 'devices:8', "
                        "'fleet:100000@64', 'graph:9@ring'); default: flat "
                        "batch shards.  devices:D pins one worker per real "
                        "device (repro.devrun); fleet:N@k samples a "
                        "k-client cohort per round from N virtual clients; "
                        "graph:W@<family> is the serverless gossip plane "
                        "(repro.graph — families ring, torus:RxC, "
                        "complete, expander:d, smallworld:k@p; lazy "
                        "triggers per directed edge)")
    p.add_argument("--fleet-churn", type=float, default=0.0,
                   help="fleet only: per-round client leave probability "
                        "(clients re-join with stale state)")
    p.add_argument("--fleet-selection", default="uniform",
                   choices=["uniform", "innovation"],
                   help="fleet only: cohort selection rule — 'innovation' "
                        "is the lazy (LAG-trigger-ranked) server-side "
                        "client selection")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--workers", type=int, default=8)
    p.add_argument("--lr", type=float, default=0.3)
    p.add_argument("--xi", type=float, default=0.1)
    p.add_argument("--D", type=int, default=10)
    p.add_argument("--hetero", type=float, default=None,
                   help="worker-shard heterogeneity dial h in [0,1] "
                        "(repro.netsim noise ramp; LM archs only); "
                        "default: homogeneous single-stream batches")
    p.add_argument("--cluster", default=None,
                   help="price the run on a simulated network, e.g. "
                        "'hetero:8@10ms/1Gbps' (repro.netsim.make_cluster "
                        "spec; worker count must match --workers)")
    p.add_argument("--fastpath", default="auto",
                   choices=["auto", "on", "off"],
                   help="batched flat-buffer comm plane (repro.fastpath): "
                        "auto = ON on TPU / jnp oracle on CPU, on = force "
                        "(interpret-mode Pallas off-TPU)")
    p.add_argument("--reduced", action="store_true",
                   help="CPU-sized variant of the arch")
    p.add_argument("--mesh", default="host", choices=["host", "prod", "prod2"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log", default=None)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a JAX profiler trace of the steps after the "
                        "first (which compiles) to DIR: each step is a "
                        "'train' step annotation, the host spans "
                        "repro.input, repro.log and repro.ckpt, and the "
                        "program's device ops carry the round's lag.* "
                        "stage scopes")
    return p


def _span(on: bool, name: str, step: int = None):
    """A profiler annotation while profiling, else nothing: the step
    (``step`` given) or the host span ``name``."""
    if not on:
        return contextlib.nullcontext()
    if step is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=step)
    return jax.profiler.TraceAnnotation(name)


def _spec(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


def main(argv=None) -> TrainRun:
    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainerConfig(algo=args.algo, num_workers=args.workers,
                         lr=args.lr, D=args.D, xi=args.xi,
                         server=args.server, fastpath=args.fastpath)
    mesh = {"host": make_host_mesh,
            "prod": lambda: make_production_mesh(multi_pod=False),
            "prod2": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    if args.hetero is not None and cfg.family in ("audio", "vlm"):
        raise SystemExit(f"--hetero shards are LM-only (token-noise ramp); "
                         f"--arch {args.arch} is family {cfg.family!r}")

    topo = None
    if args.topology is not None:
        from repro.engine import make_topology
        topo = make_topology(args.topology, mesh=mesh)
    fleet = getattr(topo, "name", None) == "fleet"
    if fleet and (args.fleet_churn or args.fleet_selection != "uniform"):
        from repro.fleet import FleetTopology
        topo = FleetTopology(population=topo.population, cohort=topo.cohort,
                             mesh=mesh, churn=args.fleet_churn,
                             selection=args.fleet_selection)
    graph = getattr(topo, "name", None) == "graph"
    devices = getattr(topo, "name", None) == "devices"
    if devices:
        # the workers' own 1-D mesh is the context shard_map runs under,
        # and batches shard along it
        mesh = topo.device_mesh(args.workers)
    # W = batch-shard count: the cohort size for fleet, the node count
    # for graph, the topology's unit count otherwise (--workers default).
    W = topo.units(args.workers) if topo is not None else args.workers
    # uploads = lazy-unit count per round: the E directed EDGES on a
    # graph (per-edge triggers), W everywhere else
    units = topo.num_edges if graph else W
    if args.cluster is not None:
        from repro.netsim import make_cluster
        # fleet runs price per-CLIENT links (population-sized cluster),
        # graph runs per directed EDGE; everything else per-worker
        make_cluster(args.cluster,
                     num_workers=topo.population if fleet else units)

    if fleet:
        from repro import fleet as fleet_lib
        state = fleet_lib.init_fleet_state(
            jax.random.PRNGKey(args.seed), cfg, tcfg, topo)
        train_step = fleet_lib.make_fleet_step(cfg, tcfg, topo)
    elif devices:
        # one worker per real device (repro.devrun): shard_map round,
        # packed wire collectives, per-worker state pinned at init —
        # the devrun builders own placement, so the generic host-mesh
        # sharding pass below is skipped
        from repro import devrun
        state = devrun.init_device_state(jax.random.PRNGKey(args.seed),
                                         cfg, tcfg, topology=topo)
        train_step = devrun.make_device_step(cfg, tcfg, topology=topo)
    elif graph:
        # serverless gossip: stacked per-node params + packed per-edge
        # mirrors own their layout, so the generic host-mesh sharding
        # pass below is skipped (like devices)
        from repro import graph as graph_lib
        state = graph_lib.init_graph_state(jax.random.PRNGKey(args.seed),
                                           cfg, tcfg, topo)
        train_step = graph_lib.make_graph_step(cfg, tcfg, topo,
                                               schedule_seed=args.seed)
    else:
        state = lag_trainer.init_state(
            jax.random.PRNGKey(args.seed), cfg, tcfg, topology=topo,
            shardings=lambda st: tree_shardings(st, mesh))
        train_step = make_train_step(cfg, tcfg, topology=topo)
    start = 0
    resumed = bool(args.resume and args.ckpt_dir
                   and latest_step(args.ckpt_dir) is not None)
    if resumed:
        state, start = restore(args.ckpt_dir, state)
        print(f"resumed from step {start}")
    with jax.set_mesh(mesh):
        # the shards state is built on the mesh; fleet state and a
        # restored checkpoint arrive unplaced
        if fleet or (resumed and not (devices or graph)):
            state = jax.device_put(state, tree_shardings(state, mesh))
        step_fn = jax.jit(train_step, donate_argnums=(0,))

        stream = TokenStream(vocab=cfg.vocab_size, seed=args.seed)
        log = metrics_lib.Logger(args.log)
        t0 = time.time()
        t_first, t_steady, arg_specs = float("nan"), None, None
        losses, masks, cohorts, cohort_comm = [], [], [], []
        profiling = False
        for step in range(start, args.steps):
            if args.profile and step == start + 1:
                jax.profiler.start_trace(args.profile)
                profiling = True
            with _span(profiling, "train", step=step):
                with _span(profiling, "repro.input"):
                    if args.hetero is not None:
                        batch = make_heterogeneous_inputs(
                            cfg, stream, step, W, args.batch, args.seq,
                            fixed=False, h=args.hetero)
                    else:
                        batch = make_inputs(cfg, stream, step, args.batch,
                                            args.seq)
                    batch = jax.device_put(batch,
                                           batch_shardings(batch, mesh))
                if arg_specs is None:
                    arg_specs = jax.tree_util.tree_map(_spec, (state, batch))
                state, m = step_fn(state, batch)
                losses.append(m["loss"])
                masks.append(m["comm_mask"])
                if t_steady is None:
                    jax.block_until_ready(state)
                    t_first = time.time() - t0
                    t_steady = time.time()
                if args.cluster is not None and fleet:
                    cohorts.append(
                        np.asarray(jax.device_get(m["cohort_ids"])))
                    cohort_comm.append(
                        np.asarray(jax.device_get(m["cohort_comm"])))
                if step % 10 == 0 or step == args.steps - 1:
                    with _span(profiling, "repro.log"):
                        log.log(step, loss=m["loss"],
                                comm_round=m["comm_this_round"],
                                comm_total=m["comm_total"])
                if args.ckpt_every and args.ckpt_dir \
                        and (step + 1) % args.ckpt_every == 0:
                    with _span(profiling, "repro.ckpt"):
                        save(args.ckpt_dir, step + 1, state)
        jax.block_until_ready(state)
        if profiling:
            jax.profiler.stop_trace()
        dt = time.time() - t0
        rounds = args.steps - start
        step_s = ((time.time() - t_steady) / (rounds - 1) if rounds > 1
                  else float("nan"))
        total = int(jax.device_get(state["lag"]["comm_total"]))
        masks = np.asarray(jax.device_get(masks), bool)
        # GD baseline: every lazy unit uploads every round — the whole
        # COHORT for fleet (the round only polls k of N clients), every
        # directed EDGE for graph, every worker otherwise
        print(f"done: {rounds} rounds in {dt:.1f}s (first step incl. "
              f"compile {t_first:.1f}s, then {step_s:.3f}s/step) | uploads "
              f"{total} vs GD {rounds * units} "
              f"({100.0 * total / max(rounds * units, 1):.1f}% of GD)")
        if args.cluster is not None and rounds:
            from repro.netsim import (make_cluster, price_cohort_mask,
                                      price_edge_mask, price_mask)
            byte_tmpl = state["params"]
            if graph:
                # stacked (W, ...) per-node replicas: one node's iterate
                # moves per edge, so size bytes from a single slice
                byte_tmpl = jax.tree_util.tree_map(lambda l: l[0],
                                                   state["params"])
            bpu = tcfg.comm_policy().wire_bytes(byte_tmpl)
            dense = float(sum(
                l.size * jnp.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(byte_tmpl)))
            if fleet:
                cl = make_cluster(args.cluster, num_workers=topo.population)
                ids = np.stack(cohorts)
                cm = np.stack(cohort_comm).astype(bool)
                t_run = price_cohort_mask(ids, cm, bpu, cl,
                                          dense_bytes=dense).sum()
                t_gd = price_cohort_mask(ids, np.ones_like(cm), dense, cl,
                                         dense_bytes=dense).sum()
            elif graph:
                cl = make_cluster(args.cluster, num_workers=units)
                dst = np.asarray(topo.spec.edge_dst)
                t_run = price_edge_mask(masks, bpu, cl, dst,
                                        dense_bytes=dense).sum()
                t_gd = price_edge_mask(np.ones((rounds, units), bool),
                                       dense, cl, dst,
                                       dense_bytes=dense).sum()
            else:
                cl = make_cluster(args.cluster, num_workers=W)
                t_run = price_mask(masks, bpu, cl,
                                   dense_bytes=dense).sum()
                t_gd = price_mask(np.ones((rounds, W), bool), dense, cl,
                                  dense_bytes=dense).sum()
            print(f"simulated wall-clock on '{args.cluster}': "
                  f"{t_run:.2f}s vs GD {t_gd:.2f}s "
                  f"({t_gd / max(t_run, 1e-12):.2f}x advantage)")
    return TrainRun(state=state,
                    losses=np.asarray(jax.device_get(losses), np.float32),
                    comm_masks=masks,
                    uploads=total, gd_uploads=rounds * units,
                    first_step_seconds=t_first, step_seconds=step_s,
                    step_fn=step_fn, arg_specs=arg_specs, mesh=mesh)


if __name__ == "__main__":
    main()
