"""Device execution plane: one lazy worker PER REAL DEVICE.

Every in-process topology (``repro.engine.topology``) batches its units
inside one device — workers are a vmapped leading dim, and the
"collective" is a ``jnp.sum`` XLA never has to move anywhere.  This
module is where the units become real: a 1-D ``("workers",)`` device
mesh (``repro.launch.mesh.make_mesh``), ``shard_map`` pinning worker m's
batch shard and mirror state to device m, and the masked deltas crossing
the interconnect as each policy's PACKED wire arrays
(``repro.comm.CommPolicy.wire_pack`` — LAQ moves b-bit integer codes
plus per-leaf quantizer steps, ~8× fewer bytes than the dense f32
payload at b = 4).

Design constraints, in order:

  1. **Decision-exactness with the sync path.**  The per-shard round is
     the UNCHANGED ``engine.rounds.policy_rounds`` at local W = 1 (with
     ``worker_offset = lax.axis_index`` so worker ids match the vmapped
     run); the reduction is all-gather + ``jnp.sum(axis=0)`` in worker
     order — NOT ``psum``, whose accumulation order is
     implementation-defined — over wire buffers whose pack/unpack
     round-trip is bitwise (the ``wire_pack`` contract).  The server
     half rejoins the shared round at ``engine.rounds.finish_round``.
     The ONLY divergence from the vmapped run is the backward pass
     itself: XLA reassociates matmul reductions differently at local
     batch shape, a ≤ 1-ulp gradient wiggle that leaves every trigger
     decision intact — tests/test_devrun.py pins ``devices:8`` against
     the 50-step lag-wk golden's exact upload decisions (losses to
     float tolerance).
  2. **Lazy skips cost nothing.**  A quiet worker's wire slot is
     all-zero (absorbing under the sum), and the payload gather itself
     sits inside ``lax.cond`` on the gathered trigger mask — an
     all-quiet round moves only the (D,)-bool mask, the same move
     ``PodMesh.reduce_fn`` makes in-process.
  3. **Overlap + donation.**  ``jit_device_step`` donates the round
     state (``donate_argnums=(0,)``) so parameters, mirrors and
     counters update in place — no doubled live memory; and
     :func:`run_rounds` never syncs the host inside the loop, so round
     k+1's dispatch (its backward + fastpath encode) overlaps round k's
     execution — the double-buffered schedule, with at most two round
     states live at once (the in-flight donated one and the result).

On a process with fewer devices than workers the builders raise
(``DeviceWorkers.require``): a run that asked for D devices never
quietly becomes a one-device run.  ``devices:1`` runs the real plane on
any single device; CI exercises D > 1 with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` subprocess tests.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import lag_trainer
from repro.engine import rounds as engine_rounds
from repro.engine import topology as topo_lib
from repro.fastpath.layout import LANES, FlatLayout
from repro.models import model
from repro.models.common import ModelConfig

Pytree = Any


def _resolve(cfg, tcfg, policy, server, topology):
    policy = policy if policy is not None else tcfg.comm_policy()
    server = server if server is not None else tcfg.server_optimizer()
    topology = topology if topology is not None \
        else topo_lib.DeviceWorkers(num_units=tcfg.num_workers)
    if not isinstance(topology, topo_lib.DeviceWorkers):
        raise ValueError(f"devrun builders need a DeviceWorkers topology "
                         f"('devices:D'), got {topology!r}")
    return policy, server, topology


def _payload_layout(params: Pytree) -> FlatLayout:
    """The wire layout: one flat-buffer table for the param-shaped f32
    candidate payload every policy's ``wire_pack`` consumes."""
    return FlatLayout.for_tree(jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params))


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

def init_device_state(key, cfg: ModelConfig, tcfg, policy=None, server=None,
                      topology=None) -> Dict:
    """``lag_trainer.init_state`` + explicit device placement.

    Per-worker leaves (the policy mirror state, per-worker counters,
    L_m) are sharded along the ``("workers",)`` mesh axis — worker m's
    mirror lives on device m, where its triggers read it — and the
    shared state (params, aggregate ∇, history, opt state) is
    replicated.  Placement at init (rather than reshard-on-entry every
    step) is what lets ``donate_argnums`` actually reuse the buffers:
    donated input and output shardings match from round 0.  The state is
    built in place (``lag_trainer.init_state``'s ``shardings``).  Raises
    when the process lacks the devices.
    """
    policy, server, topology = _resolve(cfg, tcfg, policy, server, topology)
    mesh = topology.device_mesh(tcfg.num_workers)
    per_worker = set(policy.state_keys) | {"comm_per_worker", "L_m"}

    def placement(path, _):
        keys = [getattr(k, "key", None) for k in path[:2]]
        worker_dim = keys[0] == "lag" and keys[1] in per_worker
        return NamedSharding(mesh, P("workers") if worker_dim else P())

    return lag_trainer.init_state(
        key, cfg, tcfg, policy=policy, server=server, topology=topology,
        shardings=lambda st: jax.tree_util.tree_map_with_path(placement, st))


# ---------------------------------------------------------------------------
# Step
# ---------------------------------------------------------------------------

def make_device_step(cfg: ModelConfig, tcfg, policy=None, server=None,
                     topology=None, schedule_seed: int = 0):
    """Build ``(state, batch) → (state, metrics)`` over real devices.

    The shard_map body runs the shared per-worker round at local W = 1;
    what crosses devices is (a) the (D,)-bool trigger mask and (b) —
    only on rounds where ANY worker triggered — the policy's packed wire
    arrays, gathered and decoded into worker-order f32 summands.  The
    server half (``engine.rounds.finish_round``) runs replicated outside
    the shard_map, so metrics/counters/history match the in-process
    topologies exactly.  Each part of the step runs under one of the
    round's stages (``repro.engine.rounds.STAGES``): the gathers, their
    ``lax.cond`` and the sum are ``lag.gather``, the unpack inside it
    ``lag.wire``.
    """
    policy, server, topology = _resolve(cfg, tcfg, policy, server, topology)
    mesh = topology.device_mesh(tcfg.num_workers)

    def train_step(state: Dict, batch: Dict) -> Tuple[Dict, Dict]:
        params, lag_state = state["params"], state["lag"]
        W = lag_state["comm_per_worker"].shape[0]
        lagcfg = tcfg.lag_config(num_units=W)
        shards = topology.place_batch(batch, W)
        layout = _payload_layout(params)

        pst = {k: lag_state[k] for k in policy.state_keys}
        L_arr = lag_state["L_m"] if policy.needs_L_m \
            else jnp.zeros((W,), jnp.float32)
        key = None
        if policy.needs_rng:
            key = jax.random.fold_in(jax.random.PRNGKey(schedule_seed),
                                     state["step"])

        def shard_body(pst_m, L_m, shards_m, params, hist, k_idx, key):
            # this device's worker: every leading per-worker dim is 1
            with engine_rounds.stage("grad"):
                losses, grads = jax.vmap(
                    lambda b: jax.value_and_grad(
                        lambda p: model.loss_fn(p, cfg, b))(params))(shards_m)
                gah = None
                if policy.needs_grad_at_hat:
                    gah = jax.vmap(
                        lambda th, b: jax.grad(
                            lambda p: model.loss_fn(p, cfg, b))(th),
                        in_axes=(0, 0))(pst_m["theta_hat"], shards_m)
            local = dict(pst_m, hist=hist, L_m=L_m)
            comm, _delta, new_pst, wire = engine_rounds.policy_rounds(
                policy, lagcfg, params, grads, local, grad_at_hat=gah,
                step=k_idx, key=key,
                worker_offset=jax.lax.axis_index("workers"),
                wire_layout=layout)

            def gather_sum(w):
                gw = {k: jax.lax.all_gather(v, "workers", tiled=True)
                      for k, v in w.items()}
                with engine_rounds.stage("wire"):
                    buf = policy.wire_unpack(layout, gw)  # (W, rows, LANES)
                return jnp.sum(buf, axis=0)             # worker order

            with engine_rounds.stage("gather"):
                gmask = jax.lax.all_gather(comm, "workers", tiled=True)
                # the pod-LAG move at device scale: the payload gather
                # only exists on the any-triggered branch — an all-quiet
                # round moves nothing but the (W,) mask
                sum_flat = jax.lax.cond(
                    jnp.any(gmask), gather_sum,
                    lambda w: jnp.zeros((layout.rows, LANES), jnp.float32),
                    wire)
            sum_delta = layout.unflatten(sum_flat, like=jnp.float32)
            return gmask, losses, new_pst, sum_delta

        gmask, losses, new_pst, sum_delta = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P("workers"), P("workers"), P("workers"),
                      P(), P(), P(), P()),
            out_specs=(P(), P("workers"), P("workers"), P()),
            check_vma=False,
        )(pst, L_arr, shards, params, lag_state["hist"], state["step"], key)

        with engine_rounds.stage("server"):
            loss = server.composite_loss(jnp.mean(losses), params)
        new_params, new_opt, new_lag, metrics = engine_rounds.finish_round(
            policy, server, lagcfg, params=params,
            opt_state=state.get("opt"), lag_state=lag_state, comm=gmask,
            sum_delta=sum_delta, new_pst=new_pst, step=state["step"])
        with engine_rounds.stage("server"):
            new_state = dict(state, params=new_params, lag=new_lag,
                             step=state["step"] + 1)
        if new_opt is not None:
            new_state["opt"] = new_opt
        metrics["loss"] = loss
        return new_state, metrics

    return train_step


def jit_device_step(cfg: ModelConfig, tcfg, policy=None, server=None,
                    topology=None, schedule_seed: int = 0):
    """The compiled round with END-TO-END state donation: the previous
    round's parameters, mirrors, counters and opt state are consumed in
    place (``donate_argnums=(0,)``), so steady-state live memory is one
    round state plus the in-flight result — not two generations."""
    return jax.jit(
        make_device_step(cfg, tcfg, policy=policy, server=server,
                         topology=topology, schedule_seed=schedule_seed),
        donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Round loop
# ---------------------------------------------------------------------------

def run_rounds(step_fn, state: Dict, batches) -> Tuple[Dict, list]:
    """Double-buffered driver: dispatch every round WITHOUT host sync.

    Because nothing inside the loop blocks (no ``float()``/``device_get``
    on a metric), jax's async dispatch enqueues round k+1 — its backward
    pass and fastpath encode launches — while round k's collectives are
    still executing, overlapping encode with the previous round's wire
    phase; donation (``jit_device_step``) bounds the overlap at two live
    round states.  Metrics are fetched ONCE at the end.
    """
    metrics = []
    for batch in batches:
        state, m = step_fn(state, batch)
        metrics.append(m)
    return state, jax.device_get(metrics)
