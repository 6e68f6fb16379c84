"""``Experiment`` — the one front door: any policy × any server × any
topology as a config, not a new driver.

    from repro.engine import Experiment

    # the paper's Fig.-3 run
    Experiment(problem=synthetic("linreg"), algo="lag-wk", steps=3000).run()

    # proximal LAG on the deep trainer (new scenario: the paper's
    # Conclusions extension, previously convex-only)
    Experiment(model="llama3.2-1b", algo="lag-wk", server="prox-l1@1e-4",
               steps=20, workers=4).run()

    # LAG-Adam in the convex sim (new scenario: previously trainer-only)
    Experiment(problem=prob, algo="lag-wk", server="adam", steps=200).run()

    # cyclic LAQ across two lazy pods
    Experiment(model=cfg, algo="cyc-laq@8", topology="pods:2", steps=10).run()

    # netsim: a dialed-heterogeneity problem priced on a simulated
    # network — the report gains seconds_to(eps)/wall_seconds
    Experiment(problem=hetero_problem("linreg", h=0.8), algo="lag-wk",
               steps=1000, cluster="hetero:9@10ms/1Gbps").run()

    # bounded-staleness async LAG (slowest worker 2 rounds behind)
    Experiment(model=cfg, algo="lag-wk", topology="async:4@2", steps=20).run()

Every run returns a :class:`repro.engine.report.RunReport` with the same
trajectory fields (losses / comm_mask / wire bytes / -to-ε accessors)
whether the units are convex workers, vmapped batch shards, or pods.
Convex defaults follow the paper (α = 1/L, or 1/(M·L) for the IAG
schedules; ξ = 1/D, 10/D for LAG-PS); deep defaults follow
``repro.dist.TrainerConfig``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import comm as comm_lib
from repro.core import lag
from repro.engine.report import RunReport
from repro.engine.server import ProxL1Server, make_server
from repro.engine.topology import SimWorkers, make_topology


@dataclasses.dataclass
class Experiment:
    """A declarative experiment spec.  Exactly one of ``problem`` (a
    ``repro.core.convex.Problem``) or ``model`` (a ``ModelConfig`` or an
    arch name for ``repro.configs.get_config``) selects the workload;
    ``algo``/``server``/``topology`` are spec strings (or objects) for
    the three composable axes.
    """
    # workload (exactly one)
    problem: Optional[Any] = None
    model: Optional[Any] = None          # ModelConfig | arch-name str

    # the three axes
    algo: str = "lag-wk"                 # policy spec → repro.comm.make_policy
    server: Optional[Any] = None         # spec/object; None → paper default
    topology: Optional[Any] = None       # spec/object; None → sim | shards

    # shared knobs
    steps: int = 500                     # rounds [K]
    D: int = 10                          # iterate-lag window [D]
    xi: Optional[float] = None           # trigger weight [ξ]; None → default
    seed: int = 0
    bits: int = 4                        # LAQ width (spec '@b' overrides)
    l1: float = 0.0                      # sugar for server="prox-l1@<l1>"
    rhs_floor: float = 0.0               # trigger-RHS floor (f32 quirk knob)
    fastpath: Optional[str] = None       # batched comm plane (repro.fastpath):
    #   None → "auto" (ON on TPU, jnp oracle on CPU), "on" forces the
    #   flat-buffer Pallas plane (interpret mode off-TPU — the parity
    #   tier / perf bench), "off" disables it.  Ignored when policy= is
    #   an object override (the object's own resolved plan wins).
    policy: Optional[Any] = None         # CommPolicy object override
    cluster: Optional[Any] = None        # repro.netsim cluster spec/object;
    #   when set, the run is priced through the event-driven cost model and
    #   the report gains round_seconds / wall_seconds / seconds_to(eps)

    # convex knobs
    alpha: Optional[float] = None        # stepsize; None → 1/L (paper)
    theta0: Optional[Any] = None
    opt_loss: Optional[float] = None

    # deep knobs
    workers: int = 4
    lr: float = 0.05
    batch: int = 8
    seq: int = 64
    hetero: Optional[float] = None       # deep heterogeneity dial h ∈ [0, 1]
    #   for the worker shards (repro.netsim.hetero); None → the historical
    #   full ramp (h = 1).  Convex heterogeneity is a property of the
    #   Problem — build one with repro.netsim.hetero_problem(h=...)
    fixed_batch: bool = True             # True: one batch every round (the
    #   paper's full-batch regime, matching the golden harness and the
    #   convex sim); False: a fresh heterogeneous batch per step — what
    #   the stochastic policies (lasg-wk, whose trigger differences two
    #   gradients on the CURRENT minibatch) are actually built for
    reduced: bool = True                 # CPU-sized arch when model is a str
    mesh: Optional[Any] = None           # pod placement (PodMesh)

    def run(self) -> RunReport:
        if (self.problem is None) == (self.model is None):
            raise ValueError("Experiment needs exactly one of problem= "
                             "(convex) or model= (deep)")
        if self.problem is not None:
            if self.hetero is not None:
                raise ValueError(
                    "hetero= is the DEEP shard dial; convex heterogeneity "
                    "is a property of the Problem — build one with "
                    "repro.netsim.hetero_problem(h=...)")
            report, dense = self._run_convex(), \
                float(self.problem.dim
                      * jnp.dtype(self.problem.X.dtype).itemsize)
        else:
            report, dense = self._run_deep()
        if self.cluster is not None:
            # price the upload mask through the event-driven cost model;
            # the broadcast moves DENSE params even when uploads are
            # quantized, so it is sized separately from bytes_per_upload
            from repro.netsim import cluster as netsim_cluster
            if "cohort_ids" in report.extras:
                # fleet runs: price only the k sampled uplinks per round
                # (O(K·k), never O(K·N)) via the cohort-aware pricer
                netsim_cluster.price_fleet_report(report, self.cluster,
                                                  dense_bytes=dense)
            elif "edge_dst" in report.extras:
                # graph runs: the (K, E) mask is per DIRECTED EDGE — one
                # link draw per edge, in-edges drain per destination node
                netsim_cluster.price_edge_report(report, self.cluster,
                                                 dense_bytes=dense)
            else:
                netsim_cluster.price_report(report, self.cluster,
                                            dense_bytes=dense)
        return report

    # -- shared resolution --------------------------------------------------

    def _resolve_server(self, default: str = "sgd"):
        if self.l1 > 0.0:
            # l1 is sugar for the prox-l1 server — refuse to silently
            # drop it when another server source also claims the slot
            if self.server is not None:
                raise ValueError(
                    f"conflicting server specs: l1={self.l1} selects "
                    f"'prox-l1' but server={self.server!r} was also given "
                    f"— pass one of them (e.g. server='prox-l1@{self.l1}')")
            if self.algo in ("adam", "lag-adam"):
                raise ValueError(
                    f"conflicting server specs: algo={self.algo!r} selects "
                    f"the 'adam' server but l1={self.l1} selects 'prox-l1' "
                    f"— spell the trigger explicitly (algo='lag-wk' or "
                    f"'gd') plus the server you want")
            return ProxL1Server(self.l1)
        if self.server is not None:
            return make_server(self.server)
        if self.algo in ("adam", "lag-adam"):
            return make_server("adam")
        return make_server(default)

    def _resolve_policy(self, probs=None, sqnorm_fn=None):
        if self.policy is not None:
            policy = self.policy
            # pre-engine semantics: the schedule came from the ALGO, the
            # policy= override only swapped the payload — so a scheduled
            # algo wraps a custom payload policy in its schedule
            prefix = self.algo.split("-", 1)[0]
            if prefix in comm_lib.SCHEDULES and not isinstance(
                    policy, comm_lib.ScheduledPolicy):
                policy = comm_lib.ScheduledPolicy(
                    policy, comm_lib.SCHEDULES[prefix](probs))
            return policy
        return comm_lib.make_policy(self.algo, bits=self.bits, probs=probs,
                                    sqnorm_fn=sqnorm_fn,
                                    fastpath=self.fastpath or "auto")

    # -- convex -------------------------------------------------------------

    def _run_convex(self) -> RunReport:
        prob = self.problem
        M = prob.num_workers
        topo = make_topology(self.topology or "sim", mesh=self.mesh)
        is_graph = getattr(topo, "name", None) == "graph"
        alpha = self.alpha
        if alpha is None:
            # paper defaults: α = 1/L, except 1/(M·L) for the one-upload-
            # per-round IAG schedules.  Decentralized runs take the
            # diffusion-stable default instead: the adapt step applies
            # α·W·∇L_i(θ_i) LOCALLY (so uniform mixing reproduces the
            # centralized recursion), which is only stable when the local
            # step α·W stays under 2/max(L_m) — 1/L diverges on sparse
            # graphs the moment L_m is heterogeneous.
            if is_graph:
                alpha = 1.0 / (M * float(jnp.max(prob.L_m)))
            elif "iag" in self.algo:
                alpha = 1.0 / (M * prob.L)
            else:
                alpha = 1.0 / prob.L
        xi = self.xi
        if xi is None:
            xi = (10.0 / self.D) if self.algo == "lag-ps" else (1.0 / self.D)
        cfg = lag.LAGConfig(
            num_workers=M, alpha=float(alpha), D=self.D, xi=float(xi),
            rule="ps" if "lag-ps" in self.algo else "wk",
            rhs_floor=self.rhs_floor)
        # num-IAG samples lazy units ∝ L_m (paper Sec. 4); on a graph the
        # lazy units are the E directed EDGES, so each edge inherits its
        # SOURCE node's smoothness weight
        if self.algo.startswith("num-"):
            L_u = prob.L_m[topo.spec.edge_src] if is_graph else prob.L_m
            probs = L_u / jnp.sum(L_u)
        else:
            probs = None
        policy = self._resolve_policy(probs=probs)
        server = self._resolve_server()
        if is_graph:
            # serverless gossip rounds: per-edge triggers, Metropolis
            # mixing (function-level import: repro.graph consumes the
            # engine, like repro.fleet)
            from repro import graph as graph_lib
            report = graph_lib.run_convex(prob, policy, server, cfg, topo,
                                          K=self.steps, seed=self.seed,
                                          theta0=self.theta0,
                                          opt_loss=self.opt_loss)
            report.algo = self.algo
            return report
        if getattr(topo, "name", None) == "fleet":
            # cohort-sampled convex rounds over an N-client population
            # (function-level import: repro.fleet consumes the engine)
            from repro import fleet as fleet_lib
            report = fleet_lib.run_convex(prob, policy, server, cfg, topo,
                                          K=self.steps, seed=self.seed,
                                          theta0=self.theta0,
                                          opt_loss=self.opt_loss)
            report.algo = self.algo
            return report
        if not isinstance(topo, SimWorkers):
            raise ValueError(
                f"convex problems run on the 'sim' topology, got "
                f"{topo.name!r} (deep topologies need model=)")
        report = topo.run(prob, policy, server, cfg, K=self.steps,
                          seed=self.seed, theta0=self.theta0,
                          opt_loss=self.opt_loss)
        report.algo = self.algo
        return report

    # -- deep ---------------------------------------------------------------

    def _run_deep(self) -> RunReport:
        # function-level: repro.dist consumes repro.engine (rounds/server/
        # topology); importing it at module scope would close the cycle
        from repro.configs import get_config
        from repro.data import TokenStream, make_heterogeneous_inputs
        from repro.dist import lag_trainer
        from repro.models.common import ModelConfig

        cfg = self.model
        if isinstance(cfg, str):
            cfg = get_config(cfg)
            if self.reduced:
                cfg = cfg.reduced()
        if not isinstance(cfg, ModelConfig):
            raise ValueError(f"model= must be a ModelConfig or an arch "
                             f"name, got {type(self.model).__name__}")

        topo = make_topology(self.topology or "shards", mesh=self.mesh)
        if isinstance(topo, SimWorkers):
            raise ValueError("deep models run on 'shards' or 'pods:N' "
                             "topologies, not 'sim' (sim needs problem=)")
        W = topo.units(self.workers)
        tcfg = lag_trainer.TrainerConfig(
            algo=self.algo, num_workers=W, lr=self.lr, D=self.D,
            xi=self.xi if self.xi is not None else 0.1,
            laq_bits=self.bits, rhs_floor=self.rhs_floor)
        policy = self._resolve_policy()
        server = self._resolve_server()

        if getattr(topo, "name", None) == "fleet":
            # fleet state/step: flat population arrays, cohort-sized
            # rounds (function-level import — repro.fleet consumes the
            # engine, like repro.dist)
            from repro import fleet as fleet_lib
            state = fleet_lib.init_fleet_state(
                jax.random.PRNGKey(self.seed), cfg, tcfg, topo,
                policy=policy, server=server)
            step_fn = jax.jit(fleet_lib.make_fleet_step(
                cfg, tcfg, topo, policy=policy, server=server,
                schedule_seed=self.seed))
        elif getattr(topo, "name", None) == "graph":
            # serverless gossip plane: stacked per-node params, per-edge
            # lazy mirrors (function-level import — repro.graph consumes
            # the engine, like repro.fleet)
            from repro import graph as graph_lib
            state = graph_lib.init_graph_state(
                jax.random.PRNGKey(self.seed), cfg, tcfg, topo,
                policy=policy, server=server)
            step_fn = jax.jit(graph_lib.make_graph_step(
                cfg, tcfg, topo, policy=policy, server=server,
                schedule_seed=self.seed))
        elif getattr(topo, "name", None) == "devices":
            # real multi-device plane: shard_map workers + packed wire
            # collectives; raises on a process without the devices
            # (function-level import — repro.devrun consumes the engine,
            # like repro.dist)
            from repro import devrun
            state = devrun.init_device_state(
                jax.random.PRNGKey(self.seed), cfg, tcfg, policy=policy,
                server=server, topology=topo)
            step_fn = devrun.jit_device_step(
                cfg, tcfg, policy=policy, server=server, topology=topo,
                schedule_seed=self.seed)
        else:
            state = lag_trainer.init_state(jax.random.PRNGKey(self.seed),
                                           cfg, tcfg, policy=policy,
                                           server=server, topology=topo)
            step_fn = jax.jit(lag_trainer.make_train_step(
                cfg, tcfg, policy=policy, server=server, topology=topo,
                schedule_seed=self.seed))
        stream = TokenStream(vocab=cfg.vocab_size, seed=self.seed)

        losses, masks, underflow = [], [], 0
        cohorts, cohort_comm = [], []
        batch = None
        h = 1.0 if self.hetero is None else self.hetero
        for k in range(self.steps):
            if batch is None or not self.fixed_batch:
                batch = make_heterogeneous_inputs(
                    cfg, stream, k, W, self.batch, self.seq,
                    fixed=self.fixed_batch, h=h)
            state, m = step_fn(state, batch)
            losses.append(float(m["loss"]))
            masks.append(np.asarray(jax.device_get(m["comm_mask"])))
            underflow += int(m["trigger_rhs_underflow"])
            if "cohort_ids" in m:
                cohorts.append(np.asarray(jax.device_get(m["cohort_ids"])))
                cohort_comm.append(
                    np.asarray(jax.device_get(m["cohort_comm"])))
        extras = {"trigger_rhs_underflow_rounds": underflow}
        if cohorts:
            extras["cohort_ids"] = np.stack(cohorts)
            extras["cohort_comm"] = np.stack(cohort_comm)
            extras["population"] = topo.population
            extras["cohort"] = topo.cohort
        if self.hetero is not None:
            extras["hetero_dial"] = float(self.hetero)
        if "rounds_skipped" in state["lag"]:
            extras["rounds_skipped"] = int(
                jax.device_get(state["lag"]["rounds_skipped"]))
        byte_tmpl = state["params"]
        if getattr(topo, "name", None) == "graph":
            # graph params are stacked (W, ...) per-node replicas — the
            # wire moves ONE node's iterate per edge, so size bytes from
            # a single slice, and expose the edge map for the pricer
            byte_tmpl = jax.tree_util.tree_map(lambda l: l[0],
                                               state["params"])
            extras["edge_src"] = np.asarray(topo.spec.edge_src)
            extras["edge_dst"] = np.asarray(topo.spec.edge_dst)
            extras["graph_family"] = topo.family
            extras["num_nodes"] = topo.num_nodes
        dense_bytes = float(sum(
            l.size * jnp.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(byte_tmpl)))
        return RunReport(
            algo=self.algo, losses=np.asarray(losses),
            comm_mask=np.stack(masks), opt_loss=0.0,
            bytes_per_upload=policy.wire_bytes(byte_tmpl),
            server=server.name, topology=topo.name,
            extras=extras), dense_bytes
