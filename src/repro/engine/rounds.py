"""THE round: encode → trigger → decode → reduce → server-update → metrics.

Pre-engine, this sequence was owned three separate times — by
``repro.core.simulate.run`` (convex scan), ``repro.dist.lag_trainer.
make_train_step`` (vmapped deep workers) and ``repro.dist.pod_lag``
(lax.cond pod skip) — so capabilities didn't compose across drivers.
``lag_round`` owns it once; topologies (``repro.engine.topology``) own
only batching/placement: they produce the stacked per-unit gradients,
choose how the masked deltas are reduced (plain sum, or the pod
``lax.cond`` that actually skips the collective), and hand everything
here.  Any ``repro.comm.CommPolicy`` × any ``repro.engine.server.
ServerOptimizer`` plugs in.

State contract (the drivers' ``lag`` group, layout unchanged from the
pre-engine trainer so checkpoints restore across the refactor):

  <policy.state_keys>   per-unit mirror state, leading worker/pod dim
  nabla                 aggregate ∇^k = Σ_m ĝ_m
  hist                  (D,) iterate-lag ring buffer
  comm_total            scalar upload counter
  comm_per_worker       (W,) per-unit upload counts
  L_m                   (W,) per-unit smoothness (PS-rule policies)
  rounds_skipped        optional scalar — advanced when no unit uploads
                        (the pod driver's all-quiet counter)

Stages: each part of the round runs under one ``jax.named_scope`` of
:data:`STAGES`.  A scope is HLO metadata (``op_name``) only, so the
compiled step is the same with or without it; a profile reads device
time per stage from it (``bench/stages.py``).  Where scopes nest, the
innermost names the op.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import fastpath as fastpath_lib
from repro.comm import CommPolicy, CommRound
from repro.core import lag
from repro.engine.server import ServerOptimizer

Pytree = Any

#: The round's stages, as ``jax.named_scope`` names:
#:   lag.grad    the workers' ``value_and_grad`` (LASG's second backward
#:               too); forward ops read ``jvp(``, backward ops
#:               ``transpose(jvp(``
#:   lag.encode  innovation, trigger norms, LAQ absmax/quantize and
#:               ``should_upload`` (``fast_precompute`` + the vmapped
#:               trigger, or ``encode`` + ``should_upload``)
#:   lag.layout  ``FlatLayout``'s flattens and scatters (named in
#:               ``repro.fastpath.layout``, below the engine)
#:   lag.fold    ``fast_decode`` / ``decode``: the masked mirror folds
#:   lag.wire    ``wire_pack`` / ``wire_unpack``
#:   lag.gather  ``repro.devrun``'s mask and payload all-gathers, their
#:               ``lax.cond`` and the worker-order sum
#:   lag.server  the reduction of the masked deltas in process,
#:               :func:`finish_round` and the step's loss and counter
STAGES = ("lag.grad", "lag.encode", "lag.layout", "lag.fold", "lag.wire",
          "lag.gather", "lag.server")


def stage(name: str):
    """The ``jax.named_scope`` of stage ``lag.<name>`` of :data:`STAGES`."""
    scope = f"lag.{name}"
    if scope not in STAGES:
        raise ValueError(f"{scope!r} is not one of the round's stages "
                         f"{STAGES}")
    return jax.named_scope(scope)


def comm_counter_updates(lag_state: Dict, comm: jnp.ndarray,
                         index: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, Dict]:
    """(int mask, {comm_total, comm_per_worker} updates) for this round.

    ``index`` maps each mask slot to its row in ``comm_per_worker`` when
    the two differ — the fleet topology's cohort: ``comm`` is (k,) over
    the sampled clients while the counter is per-client (N,), so the
    update is a scatter-add at the cohort ids instead of a dense add.
    """
    comm_i = comm.astype(jnp.int32)
    if index is None:
        per_worker = lag_state["comm_per_worker"] + comm_i
    else:
        per_worker = lag_state["comm_per_worker"].at[index].add(comm_i)
    # sum with an explicit dtype: under jax_enable_x64 a bare int32 sum
    # promotes to int64 and breaks the scan-carry contract
    return comm_i, {
        "comm_total": lag_state["comm_total"]
        + jnp.sum(comm_i, dtype=jnp.int32),
        "comm_per_worker": per_worker,
    }


def policy_rounds(policy: CommPolicy, lagcfg: lag.LAGConfig, params: Pytree,
                  grads: Pytree, lag_state: Dict,
                  grad_at_hat: Optional[Pytree] = None,
                  step: Optional[jnp.ndarray] = None,
                  key: Optional[jnp.ndarray] = None,
                  theta_view: Optional[Pytree] = None,
                  worker_offset=0,
                  wire_layout=None):
    """Vmap a ``CommPolicy`` over the leading worker/pod dim.

    Returns (comm (W,) bool, delta stacked pytree, new policy-state dict).
    ``step`` and ``key`` are broadcast into the per-worker ``CommRound``
    (round index + shared per-round PRNG key) so schedule policies can
    compute their mask; each worker additionally sees its own
    ``worker_id`` slot.

    ``worker_offset`` shifts the ``worker_id`` range — the device plane
    (``repro.devrun``) runs this function per shard at local W = 1 and
    passes ``lax.axis_index`` so worker m on device m sees the SAME id it
    would in the vmapped sync run (schedule policies' round-robin masks
    depend on it).

    ``wire_layout`` (a ``repro.fastpath.FlatLayout``) switches the return
    to a 4-tuple ``(comm, delta, new_pst, wire)`` where ``wire`` is the
    policy's collective wire dict (``policy.wire_pack``) for this shard's
    candidate payload — the concrete arrays the device plane moves
    through the cross-device gather instead of the dense delta tree.

    ``theta_view`` (stacked (W, …), optional) is the bounded-staleness
    hook: when an async topology hands each worker the parameters it
    LAST SAW (θ^{k−s_m}), the per-worker ``CommRound.theta`` becomes that
    view, so triggers and mirror-state updates (the PS rule's θ̂ compare,
    ``decode``'s θ̂ refresh) are evaluated against the worker's own stale
    iterate — not the server's current one.  None (default, every sync
    topology) broadcasts the shared ``params``.

    Fast path: when the policy carries an ACTIVE ``repro.fastpath`` plan,
    the kernel-served per-round quantities (the LAQ encode) are computed
    ONCE for all workers — batched flat-buffer Pallas launches — via
    ``policy.fast_precompute`` before the vmap;
    each worker's slice arrives through ``ctx.fast``, and the state fold
    (masked lazy updates) runs batched through ``policy.fast_decode``
    after the vmapped trigger.  Policies with nothing kernel-served
    (``fast_precompute`` → None: the dense family) take the plain vmapped
    round, per-leaf XLA fusions with no flatten; float64
    trees fall back in ``auto`` mode and raise under a forced plan.
    """
    W = jax.tree_util.tree_leaves(grads)[0].shape[0]
    pst = {k: lag_state[k] for k in policy.state_keys}
    L_arr = lag_state["L_m"] if policy.needs_L_m \
        else jnp.zeros((W,), jnp.float32)
    gah = grad_at_hat if grad_at_hat is not None else grads  # DCE'd if unused
    hist = lag_state["hist"]
    k_idx = jnp.zeros((), jnp.int32) if step is None \
        else jnp.asarray(step, jnp.int32)
    worker_ids = worker_offset + jnp.arange(W, dtype=jnp.int32)
    theta_stacked = theta_view is not None
    theta_arg = theta_view if theta_stacked else params
    th_ax = 0 if theta_stacked else None

    plan = fastpath_lib.active_plan(policy)
    if plan is not None and not plan.supports(grads):
        if plan.forced:
            raise ValueError(
                f"fastpath='on' but the gradient tree has leaf dtypes the "
                f"float32 comm plane cannot serve (e.g. float64 under "
                f"jax_enable_x64): "
                f"{sorted({str(l.dtype) for l in jax.tree_util.tree_leaves(grads)})}"
                f" — use fastpath='auto'/'off' for x64 runs")
        plan = None
    if plan is not None and (plan.below_dispatch_floor(grads)
                             or plan.partitioned_by_context()):
        # auto mode only: tiny stacked trees (rows × workers below the
        # static floor) run the jnp oracle outright — the batched launch
        # cannot amortize its flatten/scatter overhead there (the
        # convex-d50 M=1 regression BENCH_perf_comm.json pinned) — and so
        # does a round a multi-device mesh partitions, which Mosaic
        # kernels cannot follow
        plan = None
    fast = None
    if plan is not None:
        with stage("encode"):
            fast = policy.fast_precompute(plan, grads, pst, theta=theta_arg,
                                          theta_stacked=theta_stacked,
                                          grad_at_hat=grad_at_hat)

    if fast is None:
        def one_worker(g, pst_m, gah_m, lm, wid, theta_m):
            # repro.comm.run_round, with its halves in their stages
            ctx = CommRound(theta=theta_m, grad_new=g, hist=hist, cfg=lagcfg,
                            L_m=lm, grad_at_hat=gah_m, k=k_idx,
                            worker_id=wid, key=key)
            with stage("encode"):
                payload, aux = policy.encode(ctx, pst_m)
                comm_m = policy.should_upload(ctx, pst_m, payload, aux)
            with stage("fold"):
                delta_m, new_st = policy.decode(ctx, pst_m, payload, aux,
                                                comm_m)
            if wire_layout is None:
                return comm_m, delta_m, new_st
            # wire route keeps payload + aux visible past the decode so
            # the stacked candidate can be packed for the collective
            return comm_m, delta_m, new_st, payload, aux

        out = jax.vmap(one_worker, in_axes=(0, 0, 0, 0, 0, th_ax))(
            grads, pst, gah, L_arr, worker_ids, theta_arg)
        if wire_layout is None:
            return out
        comm, delta, new_pst, payload, aux = out
        with stage("wire"):
            wire = policy.wire_pack(wire_layout, payload, aux, comm)
        return comm, delta, new_pst, wire

    # fast route: encode + trigger stay per-worker (cheap — the heavy
    # reductions arrive precomputed in fast_m), the state fold is batched
    def enc_and_trigger(g, pst_m, gah_m, lm, wid, theta_m, fast_m):
        ctx = CommRound(theta=theta_m, grad_new=g, hist=hist, cfg=lagcfg,
                        L_m=lm, grad_at_hat=gah_m, k=k_idx, worker_id=wid,
                        key=key, fast=fast_m)
        with stage("encode"):
            payload, aux = policy.encode(ctx, pst_m)
            return (policy.should_upload(ctx, pst_m, payload, aux), payload,
                    aux)

    comm, payload, aux = jax.vmap(
        enc_and_trigger, in_axes=(0, 0, 0, 0, 0, th_ax, 0))(
        grads, pst, gah, L_arr, worker_ids, theta_arg, fast)
    with stage("fold"):
        delta, new_pst = policy.fast_decode(plan, pst, payload, aux, comm,
                                            theta=theta_arg,
                                            theta_stacked=theta_stacked)
    if wire_layout is None:
        return comm, delta, new_pst
    with stage("wire"):
        wire = policy.wire_pack(wire_layout, payload, aux, comm)
    return comm, delta, new_pst, wire


def sum_reduce(comm: jnp.ndarray, delta: Pytree) -> Pytree:
    """Default delta reduction: plain sum over the worker dim."""
    return jax.tree_util.tree_map(lambda d: jnp.sum(d, axis=0), delta)


def lag_round(policy: CommPolicy, server: ServerOptimizer,
              lagcfg: lag.LAGConfig, *, params: Pytree,
              opt_state: Optional[Pytree], lag_state: Dict, grads: Pytree,
              step: jnp.ndarray, grad_at_hat: Optional[Pytree] = None,
              key: Optional[jnp.ndarray] = None,
              reduce_fn: Optional[Callable] = None,
              theta_view: Optional[Pytree] = None
              ) -> Tuple[Pytree, Optional[Pytree], Dict, Dict]:
    """One full lazy-aggregation round for every unit at once.

    Returns ``(new_params, new_opt_state, new_lag_state, metrics)``.
    ``reduce_fn(comm, delta) → sum_delta`` is the topology's hook for HOW
    the masked deltas cross the expensive link (the pod topology wraps
    the sum in ``lax.cond`` so quiet rounds move zero bytes); the policy
    invariant guarantees any reduction of the exact deltas yields the
    same trajectory.

    ``theta_view`` is the async topology's bounded-staleness hook (see
    :func:`policy_rounds`): per-worker stale iterates the triggers are
    evaluated against.  The server step, the aggregate ∇^k recursion and
    the iterate-lag history all stay SERVER-side (they measure what the
    server actually did to the shared θ), so staleness only enters
    through the workers' gradients/triggers — at staleness 0 the round
    is bit-exact with the sync path.
    """
    comm, delta, new_pst = policy_rounds(policy, lagcfg, params, grads,
                                         lag_state, grad_at_hat,
                                         step=step, key=key,
                                         theta_view=theta_view)
    with stage("server"):
        sum_delta = (reduce_fn or sum_reduce)(comm, delta)
    return finish_round(policy, server, lagcfg, params=params,
                        opt_state=opt_state, lag_state=lag_state, comm=comm,
                        sum_delta=sum_delta, new_pst=new_pst, step=step)


def finish_round(policy: CommPolicy, server: ServerOptimizer,
                 lagcfg: lag.LAGConfig, *, params: Pytree,
                 opt_state: Optional[Pytree], lag_state: Dict,
                 comm: jnp.ndarray, sum_delta: Pytree, new_pst: Dict,
                 step: jnp.ndarray
                 ) -> Tuple[Pytree, Optional[Pytree], Dict, Dict]:
    """The server half of :func:`lag_round`, from the reduced Σ δ∇ on:
    aggregate recursion, server step, history push, counters, metrics.

    Split out so drivers that own their OWN reduction — the device plane
    (``repro.devrun``) reduces packed wire payloads across real devices
    inside ``shard_map`` — can rejoin the shared round here and stay
    bit-identical with the in-process topologies from this point down.
    All of it is stage ``lag.server``.
    """
    with stage("server"):
        # server recursion (eq. 4 aggregate) + the pluggable server step
        nabla_new = lag.tree_add(lag_state["nabla"], sum_delta)
        new_params, new_opt = server.apply(params, opt_state, nabla_new,
                                           step, lagcfg)
        # iterate-lag entry from the ACTUAL movement (post-prox /
        # post-Adam), so the trigger RHS always measures what the server
        # really did
        hist_new = lag.hist_push(
            lag_state["hist"],
            lag.tree_sqnorm(lag.tree_sub(new_params, params)))

        comm_i, counters = comm_counter_updates(lag_state, comm)
        new_lag = dict(lag_state, nabla=nabla_new, hist=hist_new,
                       **new_pst, **counters)
        any_comm = jnp.any(comm)
        if "rounds_skipped" in lag_state:
            new_lag["rounds_skipped"] = lag_state["rounds_skipped"] \
                + (1 - any_comm.astype(jnp.int32))

        # policy-declared traffic: ONE upload of the param-shaped gradient
        # costs wire_bytes (a trace-time constant), so totals are exact
        # rescalings of the upload counters
        bytes_per_upload = policy.wire_bytes(params)
        metrics = {
            "comm_mask": comm,
            "comm_this_round": jnp.sum(comm_i),
            "comm_total": new_lag["comm_total"],
            "wire_bytes_this_round":
                jnp.sum(comm_i).astype(jnp.float32) * bytes_per_upload,
            "wire_bytes_total":
                new_lag["comm_total"].astype(jnp.float32) * bytes_per_upload,
            "trigger_rhs": lag.trigger_rhs(lag_state["hist"], lagcfg),
            "trigger_rhs_underflow":
                lag.rhs_underflow(lag_state["hist"], lagcfg, step),
            "skipped_round": (~any_comm).astype(jnp.int32),
        }
        return new_params, new_opt, new_lag, metrics
