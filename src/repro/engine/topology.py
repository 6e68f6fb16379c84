"""Topologies: WHERE the lazy-aggregation units live and HOW their masked
deltas cross the expensive link.

A topology owns ONLY batching and placement — the round itself
(encode → trigger → decode → server-update → metrics) is
``repro.engine.rounds.lag_round`` for every backend:

  SimWorkers   the paper's parameter-server simulation: units are the M
               convex workers, the whole K-round run is one ``lax.scan``
  BatchShards  deep trainer: units are vmapped slices of the global
               batch (rows m·B/W:(m+1)·B/W), deltas reduced by plain sum
  PodMesh      pod-level deployment: units are whole pods, the cross-pod
               reduction sits inside ``lax.cond`` so all-quiet rounds
               move ZERO bytes across the DCI link (the
               ``repro.dist.pod_lag`` move), batch shards pinned to the
               mesh's pod axis
  AsyncShards  bounded-staleness batch shards (async LAG): worker m
               computes its gradient — and evaluates its trigger —
               against θ^{k−s_m}, the parameters it last saw, via a
               (τ+1)-deep parameter ring in the lag state; staleness 0
               is bit-exact with ``BatchShards`` (pinned by
               tests/test_netsim.py against tests/golden/)

``make_topology("pods:2")`` / ``make_topology("async:4@2")`` parse spec
strings; the deep drivers in ``repro.dist`` consume ``place_batch`` /
``reduce_fn`` / ``extra_state`` / ``worker_views`` / ``advance_views``,
the convex driver consumes ``SimWorkers.run``.  Simulated wall-clock for
any topology's upload mask comes from ``repro.netsim.cluster`` (see
docs/ARCHITECTURE.md §netsim).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lag
from repro.engine import rounds
from repro.engine.report import RunReport
from repro.engine.server import ServerOptimizer

Pytree = Dict


# ---------------------------------------------------------------------------
# Batch splitting (shared by every deep backend; re-exported by
# repro.dist.lag_trainer for backwards compatibility)
# ---------------------------------------------------------------------------

def split_batch(batch: Dict[str, jnp.ndarray], num_workers: int) -> Dict:
    """Reshape every leaf's batch dim into a leading worker dim.

    ``(B, …) → (W, B/W, …)``; mRoPE ``positions3`` leaves carry a leading
    3-axis, so their batch dim is axis 1 and the worker dim still lands in
    front: ``(3, B, S) → (W, 3, B/W, S)``.  Scalars are broadcast to (W,).
    """
    W = num_workers

    def one(path, x):
        key = jax.tree_util.keystr(path)
        if x.ndim == 0:
            return jnp.broadcast_to(x, (W,))
        b_ax = 1 if "positions3" in key else 0
        B = x.shape[b_ax]
        if B % W:
            raise ValueError(f"batch dim {B} not divisible by {W} workers"
                             f" at {key}")
        shp = x.shape[:b_ax] + (W, B // W) + x.shape[b_ax + 1:]
        return jnp.moveaxis(x.reshape(shp), b_ax, 0)

    return jax.tree_util.tree_map_with_path(one, batch)


# ---------------------------------------------------------------------------
# Deep backends
# ---------------------------------------------------------------------------

class Topology:
    """Placement contract the deep step builder consumes."""
    name: str = "topology"
    kind: str = "deep"                   # "deep" | "convex"

    def __init__(self, num_units: Optional[int] = None, mesh=None):
        self.num_units = num_units
        self.mesh = mesh

    def units(self, default: int) -> int:
        """Lazy-aggregation unit count (``num_units`` wins over the
        trainer config's worker count)."""
        return self.num_units or default

    def place_batch(self, batch: Dict, num_units: int) -> Dict:
        """Split the global batch into per-unit shards and pin them."""
        return split_batch(batch, num_units)

    def reduce_fn(self):
        """``(comm, delta) → sum_delta`` or None for the default sum."""
        return None

    def extra_state(self, params=None) -> Dict:
        """Extra ``lag``-group state this topology maintains (counters,
        the async parameter ring — sized from ``params``)."""
        return {}

    def worker_views(self, params, lag_state: Dict, num_units: int):
        """Stacked (W, …) per-worker parameter views, or None when every
        worker sees the server's current θ^k (the sync topologies).
        Async backends return each worker's stale view θ^{k−s_m}; the
        step builder computes gradients — and the engine evaluates
        triggers — against it."""
        return None

    def advance_views(self, lag_state: Dict, new_params) -> Dict:
        """Post-round ``lag``-state updates for the view machinery (the
        async ring push).  Returns a dict merged into the new lag state."""
        return {}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}(num_units={self.num_units})"


class BatchShards(Topology):
    """Vmapped batch-shard workers reduced by plain sum — the flat
    distributed trainer (``repro.dist.lag_trainer``)."""
    name = "shards"


class PodMesh(Topology):
    """Whole pods as lazy units; the cross-pod reduction only exists on
    the ``lax.cond`` true branch, so all-quiet rounds move zero bytes
    across the pod boundary (verified structurally by tests/test_dist.py
    and quantitatively by ``repro.dist.hlo_analysis``)."""
    name = "pods"

    def place_batch(self, batch: Dict, num_units: int) -> Dict:
        shards = split_batch(batch, num_units)
        mesh = self.mesh
        if mesh is None or "pod" not in getattr(mesh, "axis_names", ()):
            return shards
        from jax.sharding import NamedSharding, PartitionSpec as P

        def pin(x):
            spec = P(*(("pod",) + (None,) * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))

        return jax.tree_util.tree_map(pin, shards)

    def reduce_fn(self):
        def cond_sum(comm, delta):
            # THE pod-LAG move: when no pod triggered every delta is
            # exactly zero, so the false branch returns zeros and the DCI
            # link carries nothing.  The zeros mirror the summed DELTA's
            # shape/dtype (LAQ payloads are float32 regardless of param
            # dtype, and cond branches must agree).
            return jax.lax.cond(
                jnp.any(comm),
                lambda d: jax.tree_util.tree_map(
                    lambda x: jnp.sum(x, axis=0), d),
                lambda d: jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape[1:], x.dtype), d),
                delta)

        return cond_sum

    def extra_state(self, params=None) -> Dict:
        return {"rounds_skipped": jnp.zeros((), jnp.int32)}


class AsyncShards(Topology):
    """Bounded-staleness async LAG: slow workers trigger on the
    parameters they LAST SAW.

    Worker m's gradient and trigger are evaluated at θ^{k−s_m}, where the
    per-worker staleness ramp ``s_m = ⌊m·τ/(W−1)⌋`` runs from 0 (fastest
    worker, fully synchronous) to the bound τ (= ``staleness``, the
    slowest worker) — the bulk-synchronous-with-stale-reads model of the
    LASG line (Chen et al., 2020).  Implementation: the lag state carries
    a (τ+1)-deep ring of past parameters (``theta_ring``, pushed by
    :meth:`advance_views` after every server step); :meth:`worker_views`
    gathers each worker's view, the step builder computes gradients at it
    and ``engine.rounds.lag_round`` routes it into the per-worker
    ``CommRound.theta`` so the PS-rule compare and the θ̂ mirror refresh
    see the worker's own stale iterate.

    The server side is untouched — aggregate ∇^k recursion, server step
    and the iterate-lag history all measure the shared θ — so at
    ``staleness=0`` the ring holds exactly θ^k and the trajectory is
    BIT-exact with ``BatchShards`` (pinned against the sync golden by
    tests/test_netsim.py).  Memory cost: (τ+1) parameter copies.
    """
    name = "async"

    def __init__(self, num_units: Optional[int] = None, mesh=None,
                 staleness: int = 1):
        super().__init__(num_units, mesh)
        if staleness < 0:
            raise ValueError(f"staleness bound must be >= 0, got "
                             f"{staleness}")
        self.staleness = int(staleness)

    def stale_steps(self, num_units: int) -> np.ndarray:
        """(W,) per-worker staleness: a 0→τ ramp over the worker index."""
        W, tau = num_units, self.staleness
        if W <= 1:
            return np.full((W,), tau, np.int32)
        return ((np.arange(W) * tau) // (W - 1)).astype(np.int32)

    def extra_state(self, params=None) -> Dict:
        if params is None:
            raise ValueError("AsyncShards.extra_state needs params to size "
                             "the staleness ring")
        depth = self.staleness + 1
        ring = jax.tree_util.tree_map(
            lambda p: jnp.stack([p] * depth), params)
        return {"theta_ring": ring}

    def worker_views(self, params, lag_state: Dict, num_units: int):
        idx = jnp.asarray(self.stale_steps(num_units))
        return jax.tree_util.tree_map(lambda r: r[idx],
                                      lag_state["theta_ring"])

    def advance_views(self, lag_state: Dict, new_params) -> Dict:
        ring = jax.tree_util.tree_map(
            lambda r, p: jnp.concatenate([p[None].astype(r.dtype), r[:-1]]),
            lag_state["theta_ring"], new_params)
        return {"theta_ring": ring}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"AsyncShards(num_units={self.num_units}, "
                f"staleness={self.staleness})")


class DeviceWorkers(Topology):
    """One lazy worker pinned per REAL device — the ``repro.devrun``
    execution plane.

    Same round math as ``BatchShards`` (the 50-step lag-wk golden's
    upload decisions are reproduced exactly, losses to float tolerance —
    pinned by tests/test_devrun.py), but the
    units live on separate ``jax.devices()`` under ``shard_map``: each
    device runs ``engine.rounds.policy_rounds`` on its own shard at
    local W = 1, and the masked deltas cross the interconnect as the
    policy's PACKED wire arrays (``CommPolicy.wire_pack`` — LAQ moves
    b-bit integer codes + per-leaf quantizer steps, not dense f32),
    gathered and summed in worker order so the reduction is bit-exact
    with the in-process ``sum_reduce``.  The step builder lives in
    ``repro.devrun.runner``; on a process with fewer devices than
    workers, building the mesh raises (``require``) rather than running
    a different topology.  CI exercises the multi-device path via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` subprocess
    tests.
    """
    name = "devices"

    def num_devices(self, default: int = None) -> int:
        """The worker/device count: ``devices:D`` pins D, bare
        ``devices`` takes every visible device (or the trainer default
        when given)."""
        if self.num_units:
            return self.num_units
        return default or len(jax.devices())

    def available(self, default: int = None) -> bool:
        """True when this process actually has enough devices."""
        return len(jax.devices()) >= self.num_devices(default)

    def require(self, default: int = None) -> None:
        """Raise unless this process has the D devices the spec names."""
        if not self.available(default):
            found = jax.devices()
            raise RuntimeError(
                f"devices:{self.num_devices(default)} needs "
                f"{self.num_devices(default)} devices; this process sees "
                f"{len(found)}: {found}")

    def device_mesh(self, default: int = None):
        """1-D ``("workers",)`` mesh over the first D devices."""
        from repro.launch.mesh import make_mesh
        self.require(default)
        D = self.num_devices(default)
        return make_mesh((D,), ("workers",), devices=jax.devices()[:D])


# ---------------------------------------------------------------------------
# Convex backend
# ---------------------------------------------------------------------------

class SimWorkers(Topology):
    """The paper's Sec.-4 parameter-server simulation: full-batch
    gradients per convex worker, the whole K-iteration run in one
    ``lax.scan`` over :func:`repro.engine.rounds.lag_round`."""
    name = "sim"
    kind = "convex"

    def run(self, problem, policy, server: ServerOptimizer,
            lagcfg: lag.LAGConfig, *, K: int, seed: int = 0,
            theta0: Optional[jnp.ndarray] = None,
            opt_loss: Optional[float] = None) -> RunReport:
        M, d = problem.num_workers, problem.dim
        theta0 = jnp.zeros((d,), problem.X.dtype) if theta0 is None \
            else theta0
        # Initialization (paper Alg. 1/2 line 2): all workers upload at
        # k=0 — the policy mirrors start at the exact ∇L_m(θ⁰).
        g0 = problem.worker_grads(theta0)                  # (M, d)
        lag_state = dict(policy.init_state(
            g0, jnp.broadcast_to(theta0, (M, d)) if policy.needs_theta_hat
            else None))
        lag_state.update(
            nabla=jnp.sum(g0, axis=0),
            hist=lag.hist_init(lagcfg.D),
            comm_total=jnp.zeros((), jnp.int32),
            comm_per_worker=jnp.zeros((M,), jnp.int32),
            L_m=problem.L_m,
        )
        carry0 = dict(
            theta=theta0,
            opt=server.init(theta0),
            lag=lag_state,
            key=jax.random.PRNGKey(seed),
            k=jnp.zeros((), jnp.int32),
        )

        def step(carry, _):
            theta = carry["theta"]
            loss = server.composite_loss(problem.loss(theta), theta)
            grads = problem.worker_grads(theta)            # (M, d)
            if policy.needs_grad_at_hat:
                gah = problem.worker_grads_at(carry["lag"]["theta_hat"])
            else:
                gah = None
            if policy.needs_rng:
                key, sub = jax.random.split(carry["key"])
            else:
                key, sub = carry["key"], None
            new_theta, new_opt, new_lag, metrics = rounds.lag_round(
                policy, server, lagcfg, params=theta, opt_state=carry["opt"],
                lag_state=carry["lag"], grads=grads, step=carry["k"],
                grad_at_hat=gah, key=sub)
            new_carry = dict(theta=new_theta, opt=new_opt, lag=new_lag,
                             key=key, k=carry["k"] + 1)
            out = (loss, metrics["comm_mask"],
                   metrics["trigger_rhs_underflow"])
            return new_carry, out

        _, (losses, comm_mask, underflow) = jax.jit(
            lambda c: jax.lax.scan(step, c, None, length=K))(carry0)
        if opt_loss is None:
            _, opt_loss = problem.optimum()
        # the netsim measurables (paper Sec. 3): realized smoothness
        # spread + the trigger-derived heterogeneity score, so every
        # convex report carries the dial position it actually ran at
        from repro.netsim import hetero as netsim_hetero
        extras = {
            "trigger_rhs_underflow_rounds": int(np.asarray(underflow).sum()),
            "L_m_spread": netsim_hetero.realized_spread(problem.L_m),
            "hetero_score": netsim_hetero.hetero_score(
                problem.L_m, alpha=lagcfg.alpha, xi=lagcfg.xi, D=lagcfg.D,
                num_workers=M),
        }
        return RunReport(
            algo=policy.name, losses=np.asarray(losses),
            comm_mask=np.asarray(comm_mask), opt_loss=float(opt_loss),
            bytes_per_upload=policy.wire_bytes(g0[0]),
            server=server.name, topology=self.name, extras=extras)


# ---------------------------------------------------------------------------
# Registry + spec parsing
# ---------------------------------------------------------------------------

def _make_fleet(population=None, cohort=None, mesh=None, **kw):
    """Lazy ``repro.fleet`` factory: the fleet topology imports the engine
    round seam, so importing it here at module scope would close a cycle."""
    from repro.fleet.topology import FleetTopology
    return FleetTopology(population=population, cohort=cohort, mesh=mesh,
                         **kw)


def _make_graph(num_nodes=None, family=None, mesh=None, **kw):
    """Lazy ``repro.graph`` factory (same cycle-avoidance as the fleet's):
    the decentralized gossip plane consumes the engine round seam."""
    from repro.graph.topology import GraphTopology
    return GraphTopology(num_nodes=num_nodes, family=family, mesh=mesh,
                         **kw)


TOPOLOGIES = {
    "sim": SimWorkers,
    "shards": BatchShards,
    "pods": PodMesh,
    "async": AsyncShards,
    "devices": DeviceWorkers,
    "fleet": _make_fleet,
    "graph": _make_graph,
}

_FLEET_GRAMMAR = ("fleet needs BOTH a population and a cohort size — "
                  "'fleet:<population>@<cohort>', e.g. 'fleet:100000@64' "
                  "(sample 64 of 100000 clients per round)")


def make_topology(spec, mesh=None) -> Topology:
    """Build a ``Topology`` from a spec string (or pass one through).

    Grammar: ``<name>[:<units>][@<staleness>]`` — ``"sim"``,
    ``"shards"``, ``"pods:2"`` (two lazy pods), ``"async:4@2"`` (four
    bounded-staleness workers, slowest 2 rounds behind; ``"async"``
    alone defaults to staleness 1), ``"devices:8"`` (one worker per
    real device via ``repro.devrun``).  The fleet topology requires both
    parts: ``"fleet:<population>@<cohort>"`` — ``"fleet:100000@64"``
    samples a 64-client cohort per round from 10⁵ clients.  So does the
    decentralized gossip plane: ``"graph:<nodes>@<family>"`` —
    ``"graph:9@ring"``, ``"graph:12@torus:3x4"``, ``"graph:9@complete"``,
    ``"graph:16@expander:4"``, ``"graph:16@smallworld:4@0.2"``
    (``repro.graph``; the family may itself carry ``:``/``@`` arguments).
    ``mesh`` reaches placement-aware backends (the pod axis pin).
    """
    if isinstance(spec, Topology):
        return spec
    if not isinstance(spec, str) or not spec:
        raise ValueError(f"topology spec must be a non-empty string or a "
                         f"Topology, got {spec!r}")
    head, sep_at, stale_s = spec.partition("@")
    name, sep, units = head.partition(":")
    name = name.strip()
    if name not in TOPOLOGIES:
        raise ValueError(f"unknown topology {spec!r}; known: "
                         f"{tuple(TOPOLOGIES)} (optionally ':<units>', "
                         f"e.g. 'pods:2'; async also takes '@<staleness>'; "
                         f"fleet needs 'fleet:<population>@<cohort>'; "
                         f"graph needs 'graph:<nodes>@<family>')")
    if name == "graph":
        # function-level import: repro.graph.spec is numpy-only, but the
        # package __init__ pulls in the round seam — same laziness as
        # _make_graph.  partition("@") split at the FIRST @, so the
        # family half may itself contain '@' ('smallworld:4@0.2').
        from repro.graph.spec import GRAPH_GRAMMAR
        if not sep or not sep_at:
            raise ValueError(f"bad topology spec {spec!r}: graph needs "
                             f"BOTH a node count and a family — "
                             f"{GRAPH_GRAMMAR}")
        try:
            n = int(units)
        except ValueError:
            raise ValueError(
                f"bad topology spec {spec!r}: ':{units}' is not an integer "
                f"node count — {GRAPH_GRAMMAR}") from None
        return TOPOLOGIES["graph"](num_nodes=n, family=stale_s, mesh=mesh)
    if name == "fleet":
        if not sep or not sep_at:
            raise ValueError(f"bad topology spec {spec!r}: "
                             f"{_FLEET_GRAMMAR}")
        try:
            population = int(units)
        except ValueError:
            raise ValueError(
                f"bad topology spec {spec!r}: ':{units}' is not an integer "
                f"population — {_FLEET_GRAMMAR}") from None
        try:
            cohort = int(stale_s)
        except ValueError:
            raise ValueError(
                f"bad topology spec {spec!r}: '@{stale_s}' is not an "
                f"integer cohort size — {_FLEET_GRAMMAR}") from None
        if population < 1:
            raise ValueError(f"bad topology spec {spec!r}: population must "
                             f"be >= 1 — {_FLEET_GRAMMAR}")
        if not 1 <= cohort <= population:
            raise ValueError(f"bad topology spec {spec!r}: cohort must be "
                             f"in [1, population={population}] — "
                             f"{_FLEET_GRAMMAR}")
        return TOPOLOGIES["fleet"](population=population, cohort=cohort,
                                   mesh=mesh)
    kwargs = {}
    if sep_at:
        if name != "async":
            raise ValueError(
                f"bad topology spec {spec!r}: only 'async', 'fleet' and "
                f"'graph' take an '@' suffix (e.g. 'async:4@2', "
                f"'fleet:100000@64', 'graph:9@ring')")
        try:
            kwargs["staleness"] = int(stale_s)
        except ValueError:
            raise ValueError(
                f"bad topology spec {spec!r}: '@{stale_s}' is not an "
                f"integer staleness bound (want e.g. 'async:4@2')") from None
        if kwargs["staleness"] < 0:
            raise ValueError(f"bad topology spec {spec!r}: staleness must "
                             f"be >= 0")
    n = None
    if sep:
        try:
            n = int(units)
        except ValueError:
            raise ValueError(
                f"bad topology spec {spec!r}: ':{units}' is not an integer "
                f"unit count (want e.g. 'pods:2')") from None
        if n < 1:
            raise ValueError(f"bad topology spec {spec!r}: unit count must "
                             f"be >= 1")
    return TOPOLOGIES[name](num_units=n, mesh=mesh, **kwargs)
