"""Distributed LAG trainer — a THIN SHIM over the shared engine round.

This module owns NO algorithm logic: every step hands the whole round —
encode → trigger → decode → reduce → server-update → metrics — to
:func:`repro.engine.rounds.lag_round`, exactly like the convex driver
(``repro.core.simulate`` via ``repro.engine.topology.SimWorkers.run``)
and the pod driver (``repro.dist.pod_lag``).  What lives HERE is only
the deep-specific glue the engine delegates back out:

  * batch splitting/placement and delta reduction, via a
    ``repro.engine.topology`` backend (``BatchShards`` flat vmap,
    ``PodMesh`` lax.cond skip, ``AsyncShards`` bounded-staleness views);
  * the vmapped backward pass(es) — at the shared θ^k, at each worker's
    stale view θ^{k−s_m} (async), and at θ̂_m for LASG-WK's trigger;
  * the loss metric and the ``TrainerConfig`` → (policy, server, LAGConfig)
    spec resolution.

A "worker" is a slice of the global batch (rows ``m·B/W:(m+1)·B/W``, the
layout ``repro.data.make_heterogeneous_inputs`` produces — heterogeneity
dialable via ``repro.netsim.hetero``).  New code should prefer the
``repro.engine.Experiment`` front door (docs/ARCHITECTURE.md has the
layer map and a walkthrough of one round); this module keeps the
pre-engine ``init_state``/``make_train_step`` signatures alive,
golden-pinned by tests/golden/lag_wk_50step.json.  Algorithm choice is
one config switch:

  gd        every worker uploads every round (synchronous baseline)
  lag-wk    LAG with the worker-side trigger (15a)
  lag-ps    LAG with the server-side trigger (15b)
  laq       LAG trigger on the b-bit quantized innovation with error
            feedback (LAQ, Sun et al. 2019)
  lasg-wk   stochastic worker trigger (LASG-WK, Chen et al. 2020): one
            extra vmapped backward pass at the stale iterate θ̂_m
  adam      every-round uploads, Adam server step (beyond-paper baseline)
  lag-adam  LAG-WK trigger + Adam server step (beyond-paper; known trigger
            pathology under preconditioning — see EXPERIMENTS.md)

plus any ``repro.comm.make_policy`` spec (``"laq@8"``, ``"cyc-iag"``,
``"num-lag-wk"``, …).  The server step is its own axis now
(``TrainerConfig.server`` / ``repro.engine.server``), so e.g. proximal
LAG runs on the deep trainer: ``TrainerConfig(algo="lag-wk",
server="prox-l1@1e-4")``.

State is a flat dict pytree (checkpoint- and donation-friendly) with the
LAG group under ``state["lag"]`` — the layout documented in
``repro.engine.rounds`` and unchanged from the pre-engine trainer, so old
checkpoints restore.  Wire traffic is policy-declared: metrics report
``wire_bytes_total`` = uploads × ``policy.wire_bytes(params)``.

Sharding is applied OUTSIDE via ``repro.dist.sharding.tree_shardings`` —
the step function itself is placement-free and jit/donate-friendly (pod
placement comes from the ``PodMesh`` topology's sharding constraints).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lag
from repro.engine import rounds as engine_rounds
from repro.engine import server as server_lib
from repro.engine import topology as topo_lib
# re-exported names (pre-engine home of these helpers)
from repro.engine.rounds import comm_counter_updates, policy_rounds  # noqa: F401
from repro.engine.topology import split_batch  # noqa: F401
from repro.models import model
from repro.models.common import ModelConfig

Pytree = Any

ALGOS = ("gd", "lag-wk", "lag-ps", "laq", "lasg-wk", "adam", "lag-adam")


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Distributed-trainer hyper-parameters (paper notation in brackets).

    ``lr`` is the stepsize on the MEAN aggregated gradient: the server
    update is θ^{k+1} = θ^k − (lr/M)·∇^k with ∇^k = Σ_m ∇L_m, i.e. the
    paper's eq. (4) with α = lr/M, so tuning lr is worker-count-independent
    (the data-parallel convention).  The triggers are exactly (15a)/(15b)
    with that same α, which makes the skip condition ≈ L_m ≤ √(ξD)/lr —
    smooth (low-noise) workers skip, rough ones upload (paper Lemma 4).

    ``algo`` accepts the trainer names above or any ``repro.comm``
    policy spec; ``server`` overrides the algo-derived server optimizer
    with any ``repro.engine.server`` spec (``"prox-l1@1e-4"``,
    ``"momentum@0.9"``, …).  ``rhs_floor`` floors the trigger RHS against
    the f32 exact-convergence underflow quirk; ``laq_bits`` sets LAQ's
    quantization width.  ``fastpath`` resolves the batched flat-buffer
    comm plane (``repro.fastpath``) — the DEFAULT hot path on TPU
    (``"auto"``): one Pallas launch per round for all workers' trigger
    sqnorms / LAQ encode / masked updates instead of per-leaf per-worker
    loops; ``"on"`` forces it (interpret mode off-TPU, parity only).
    ``use_pallas_comm`` keeps the legacy per-leaf route (the fused
    per-leaf kernels in ``repro.kernels.lag_trigger``) reachable for
    comparison — selecting it disables an ``"auto"`` plane on every
    backend (the plane would silently shadow it on TPU otherwise), and
    combining it with ``fastpath="on"`` raises.
    ``benchmarks/perf_comm.py`` measures all three routes.
    """
    algo: str = "lag-wk"
    num_workers: int = 4
    lr: float = 0.05
    D: int = 10                     # iterate-lag window [D]
    xi: float = 0.1                 # trigger weight [ξ]; paper 1/D
    grad_hat_dtype: Optional[str] = None   # e.g. "bfloat16" to halve HBM
    momentum: float = 0.0           # SGD momentum for gd/lag-wk/lag-ps
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    laq_bits: int = 4               # LAQ quantization width [b]
    use_pallas_comm: bool = False   # legacy per-leaf Pallas sqnorm/encode
    fastpath: str = "auto"          # batched flat-buffer comm plane
    #   (repro.fastpath): "auto" = ON on TPU / jnp oracle on CPU, "on"
    #   forces it (interpret-mode parity off-TPU), "off" disables
    server: Optional[str] = None    # repro.engine.server spec override
    rhs_floor: float = 0.0          # trigger-RHS floor (f32 quirk knob)

    def __post_init__(self):
        if self.algo not in ALGOS:
            # any spec the policy registry parses is a valid algo; this
            # raises the registry's actionable message otherwise
            from repro import comm
            comm.make_policy(self.algo, bits=self.laq_bits)
        if self.server is not None:
            server_lib.make_server(self.server)   # validate spec early
        from repro import fastpath as fastpath_lib
        fastpath_lib.make_plan(self.fastpath)     # validate mode early
        if self.use_pallas_comm and self.fastpath == "on":
            raise ValueError(
                "conflicting comm-plane configs: use_pallas_comm=True "
                "selects the legacy per-leaf Pallas route but "
                "fastpath='on' forces the batched plane — pass one of "
                "them (use_pallas_comm alone implies fastpath='off')")

    @property
    def uses_adam(self) -> bool:
        return self.algo in ("adam", "lag-adam")

    @property
    def lag_rule(self) -> str:
        return "ps" if self.algo == "lag-ps" else "wk"

    def lag_config(self, num_units: Optional[int] = None) -> lag.LAGConfig:
        # α = lr/M: eq. (4) with the aggregate normalized by worker count —
        # the server step and trigger_rhs both read this α, so the update
        # and the trigger stay mutually consistent (see class docstring)
        m = num_units or self.num_workers
        return lag.LAGConfig(num_workers=m, alpha=self.lr / m, D=self.D,
                             xi=self.xi, rule=self.lag_rule,
                             rhs_floor=self.rhs_floor)

    def comm_policy(self):
        """The ``repro.comm`` policy this config selects (adam aliases map
        to their trigger: adam → gd uploads, lag-adam → the 15a trigger)."""
        from repro import comm
        sqnorm_fn = None
        if self.use_pallas_comm:
            from repro.kernels.lag_trigger import ops as lag_ops
            sqnorm_fn = lag_ops.fused_tree_sqnorm
        return comm.make_policy(self.algo, bits=self.laq_bits,
                                use_pallas=self.use_pallas_comm,
                                sqnorm_fn=sqnorm_fn,
                                fastpath=self.fastpath)

    def server_optimizer(self) -> server_lib.ServerOptimizer:
        """The ``repro.engine.server`` optimizer this config selects:
        ``server`` spec if set, else adam for the adam algos, heavy-ball
        when ``momentum > 0``, else the paper's SGD (eq. 4)."""
        if self.server is not None:
            return server_lib.make_server(self.server)
        if self.uses_adam:
            return server_lib.AdamServer(b1=self.adam_b1, b2=self.adam_b2)
        if self.momentum:
            return server_lib.MomentumServer(self.momentum)
        return server_lib.SGDServer()

    def replace(self, **kw) -> "TrainerConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

def init_state(key, cfg: ModelConfig, tcfg: TrainerConfig,
               policy=None, server=None, topology=None,
               shardings=None) -> Dict:
    """Fresh trainer state.  ``grad_hat`` starts at zero with an empty
    history, so round 0 triggers every worker (lhs ‖∇L_m‖² > rhs 0) and
    delivers the exact first GD step — the paper's all-upload init.

    ``shardings`` maps the state's abstract tree to its shardings; the
    state is then built in place, shard by shard, around parameters from
    the same eager ``model.init``: a full-width model's W-stacked mirrors
    do not fit on one device."""
    params = model.init(key, cfg)
    build = functools.partial(state_for_params, tcfg=tcfg, policy=policy,
                              server=server, topology=topology)
    if shardings is None:
        return build(params)
    return jax.jit(build, out_shardings=shardings(
        jax.eval_shape(build, params)))(params)


def state_for_params(params, tcfg: TrainerConfig, policy=None, server=None,
                     topology=None) -> Dict:
    """:func:`init_state` around given parameters (jit-traceable)."""
    W = tcfg.num_workers
    policy = policy if policy is not None else tcfg.comm_policy()
    server = server if server is not None else tcfg.server_optimizer()
    gh_dtype = jnp.dtype(tcfg.grad_hat_dtype) if tcfg.grad_hat_dtype \
        else None

    def stacked_zeros(p):
        return jnp.zeros((W,) + p.shape, gh_dtype or p.dtype)

    grad0 = jax.tree_util.tree_map(stacked_zeros, params)
    theta0 = None
    if policy.needs_theta_hat:
        # per-worker last-upload iterate copies θ̂_m, zero-initialized like
        # grad_hat (round 0 fires for every worker either way)
        theta0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros((W,) + p.shape, p.dtype), params)
    lag_state = dict(policy.init_state(grad0, theta0))
    lag_state.update({
        "nabla": jax.tree_util.tree_map(jnp.zeros_like, params),
        "hist": lag.hist_init(tcfg.D),
        "comm_total": jnp.zeros((), jnp.int32),
        "comm_per_worker": jnp.zeros((W,), jnp.int32),
    })
    if policy.needs_L_m:
        # with no oracle L_m for a deep net we use the 1/α heuristic
        # (paper: α = 1/L)
        lag_state["L_m"] = jnp.full((W,), 1.0 / tcfg.lr, jnp.float32)
    if topology is not None:
        lag_state.update(topology.extra_state(params))

    state = {"params": params, "lag": lag_state,
             "step": jnp.zeros((), jnp.int32)}
    opt0 = server.init(params)
    if opt0 is not None:
        state["opt"] = opt0
    return state


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, tcfg: TrainerConfig,
                    policy=None, server=None, topology=None,
                    schedule_seed: int = 0):
    """Build the jit/donate-friendly ``(state, batch) → (state, metrics)``.

    ``policy``/``server``/``topology`` default to what ``tcfg`` selects /
    the flat ``BatchShards`` backend; ``repro.dist.pod_lag`` passes the
    ``PodMesh`` topology instead, and ``AsyncShards`` (spec
    ``"async:4@2"``) swaps in bounded-staleness per-worker parameter
    views — the round itself is ``repro.engine.rounds.lag_round`` every
    time.  ``schedule_seed``
    seeds the per-round keys of stochastic schedule policies (num-IAG);
    it is deterministic in the step counter, so no RNG state needs
    checkpointing.  Each part of the step runs under one of the round's
    stages (``repro.engine.rounds.STAGES``).
    """
    policy = policy if policy is not None else tcfg.comm_policy()
    server = server if server is not None else tcfg.server_optimizer()
    topology = topology if topology is not None else topo_lib.BatchShards()
    reduce_fn = topology.reduce_fn()

    def train_step(state: Dict, batch: Dict) -> Tuple[Dict, Dict]:
        params, lag_state = state["params"], state["lag"]
        # unit count from the state's worker dim (pod_lag inits it with
        # n_pods); for the flat trainer it equals tcfg.num_workers
        W = lag_state["comm_per_worker"].shape[0]
        lagcfg = tcfg.lag_config(num_units=W)

        # async topologies hand each worker the params it LAST SAW
        # (θ^{k−s_m}); sync topologies return None and every worker's
        # backward pass runs at the shared θ^k — a trace-time branch
        with engine_rounds.stage("grad"):
            shards = topology.place_batch(batch, W)
            views = topology.worker_views(params, lag_state, W)
            if views is None:
                losses, grads = jax.vmap(
                    lambda b: jax.value_and_grad(
                        lambda p: model.loss_fn(p, cfg, b))(params))(shards)
            else:
                losses, grads = jax.vmap(
                    lambda th, b: jax.value_and_grad(
                        lambda p: model.loss_fn(p, cfg, b))(th))(
                    views, shards)
        with engine_rounds.stage("server"):
            loss = server.composite_loss(jnp.mean(losses), params)

        grad_at_hat = None
        if policy.needs_grad_at_hat:
            # LASG-WK: ∇ℓ_m(θ̂_m) on the CURRENT shard — a second vmapped
            # backward pass, each worker at its own stale iterate
            with engine_rounds.stage("grad"):
                grad_at_hat = jax.vmap(
                    lambda th, b: jax.grad(
                        lambda p: model.loss_fn(p, cfg, b))(th),
                    in_axes=(0, 0))(lag_state["theta_hat"], shards)

        key = None
        if policy.needs_rng:
            # stochastic schedules: a per-round key derived from the step
            # counter (deterministic, checkpoint-free)
            key = jax.random.fold_in(jax.random.PRNGKey(schedule_seed),
                                     state["step"])

        new_params, new_opt, new_lag, metrics = engine_rounds.lag_round(
            policy, server, lagcfg, params=params,
            opt_state=state.get("opt"), lag_state=lag_state, grads=grads,
            step=state["step"], grad_at_hat=grad_at_hat, key=key,
            reduce_fn=reduce_fn, theta_view=views)
        with engine_rounds.stage("server"):
            adv = topology.advance_views(new_lag, new_params)
            if adv:
                new_lag = dict(new_lag, **adv)
            new_state = dict(state, params=new_params, lag=new_lag,
                             step=state["step"] + 1)
        if new_opt is not None:
            new_state["opt"] = new_opt
        metrics["loss"] = loss
        return new_state, metrics

    return train_step
