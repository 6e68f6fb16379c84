"""The communication-policy protocol: WHO uploads WHAT, in HOW many bytes.

LAG (Chen et al., NIPS 2018) is one point in a family of lazy-communication
rules — LAQ adds quantized lazy uploads (Sun et al., 2019), LASG moves the
trigger to stochastic gradients (Chen et al., 2020).  All of them factor
into the same per-worker round:

  1. ``encode``         build the *candidate* upload from the fresh gradient
                        and the worker's mirror state (δ∇ for LAG, a b-bit
                        quantized innovation for LAQ, …)
  2. ``should_upload``  the trigger: is the candidate worth its wire bytes?
  3. ``decode``         apply the masked payload on the server's ledger and
                        advance the worker's mirror state
  4. ``wire_bytes``     what one triggered upload actually costs on the wire

``CommPolicy`` owns all four (plus ``init_state``).  The shared round —
vmapping over workers/pods, the pluggable server update, the iterate-lag
history, metrics — is ``repro.engine.rounds.lag_round``, which consumes
any policy through :func:`run_round`; batching/placement is the
``repro.engine.topology`` backends', and the old drivers
(``repro.core.simulate.run``, ``repro.dist.lag_trainer``,
``repro.dist.pod_lag``) are thin consumers.  Schedule-driven baselines
(cyc-IAG, num-IAG) are policies too: ``repro.comm.schedule``.

Everything is functional and shape-polymorphic: policy state is a flat dict
of pytrees (one leading worker dim added by the driver, stripped by vmap
before the policy sees it), every method is jit/vmap/scan safe, and the
server recursion's invariant ∇^k = Σ_m ĝ_m holds for every policy because
``decode`` returns exactly the delta it folded into ``grad_hat``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import lag

Pytree = Any
PolicyState = Dict[str, Pytree]


# ---------------------------------------------------------------------------
# Per-round context
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CommRound:
    """Everything ONE worker sees when deciding/encoding one round.

    Per-worker fields (``grad_new``, ``L_m``, ``grad_at_hat``) are the
    un-stacked slices — the driver vmaps over the worker dim and builds a
    ``CommRound`` inside the vmapped closure.  ``theta``, ``hist`` and
    ``cfg`` are broadcast.
    """
    theta: Pytree                        # current iterate θ^k
    grad_new: Pytree                     # fresh gradient ∇L_m(θ^k) (or ∇ℓ(θ^k;ξ^k))
    hist: jnp.ndarray                    # (D,) ‖θ^{k+1-d} − θ^{k-d}‖² ring buffer
    cfg: lag.LAGConfig                   # α, M, D, ξ — the trigger constants
    L_m: Optional[jnp.ndarray] = None    # per-worker smoothness (PS rule only)
    grad_at_hat: Optional[Pytree] = None  # ∇ℓ_m(θ̂_m; current sample) (LASG-WK)
    k: Optional[jnp.ndarray] = None      # () int round index (schedules)
    worker_id: Optional[jnp.ndarray] = None  # () int slot in the worker dim
    key: Optional[jnp.ndarray] = None    # per-round PRNG key, broadcast to
    #                                      every worker (stochastic schedules)
    fast: Optional[Dict[str, Any]] = None    # this worker's slice of the
    #   batched fast-path precompute (repro.fastpath): kernel-served trigger
    #   sqnorms / LAQ payloads the policy consumes instead of recomputing
    #   per leaf.  None on the oracle path.


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------

class CommPolicy:
    """Base class: the dense δ∇ = ∇L_m(θ^k) − ĝ_m upload family.

    Subclasses override the trigger (``should_upload``) and/or the payload
    (``encode``/``decode``/``wire_bytes``).  Class attributes tell drivers
    which optional inputs/state to provision:

      ``state_keys``         keys of the per-worker state dict this policy
                             reads and writes (subset of the driver's
                             ``state["lag"]`` group, checkpoint-compatible)
      ``needs_theta_hat``    driver stores the last-upload iterate θ̂_m
      ``needs_L_m``          driver supplies per-worker smoothness in ctx
      ``needs_grad_at_hat``  driver evaluates ∇ℓ_m(θ̂_m) on the CURRENT
                             sample (second vmapped backward pass)
      ``needs_rng``          driver splits a fresh per-round PRNG key into
                             ``ctx.key`` (stochastic schedules)

    The batched fast path (``repro.fastpath``) is resolved ONCE per policy
    into ``self.fastpath`` — a ``FastPathPlan`` or None.  When the plan is
    active, ``repro.engine.rounds.policy_rounds`` calls
    :meth:`fast_precompute` BEFORE vmapping (one flat-buffer Pallas launch
    for all workers), routes each worker's slice in via ``ctx.fast``, and
    folds state through :meth:`fast_decode` AFTER the vmapped trigger
    (batched masked lazy updates) — so the per-leaf per-worker kernel
    launches of ``repro.kernels.lag_trigger.ops`` never happen on the hot
    path.  Every shipped policy implements :meth:`fast_precompute`
    explicitly; the base method raises, which is the registry tripwire
    against new policies silently bypassing the plane
    (tests/test_engine.py runs the smoke matrix with the plan forced on).
    """
    name: str = "base"
    state_keys: Tuple[str, ...] = ("grad_hat",)
    needs_theta_hat: bool = False
    needs_L_m: bool = False
    needs_grad_at_hat: bool = False
    needs_rng: bool = False

    def __init__(self, sqnorm_fn: Callable[[Pytree], jnp.ndarray] = lag.tree_sqnorm,
                 fastpath="auto"):
        # injectable so drivers can supply a model-axis-psum'd or
        # Pallas-fused squared norm (repro.kernels.lag_trigger)
        self.sqnorm_fn = sqnorm_fn
        # the batched comm plane, resolved once per policy ("auto" → on
        # when on_tpu(); "on" forces interpret-mode parity off-TPU)
        from repro import fastpath as fastpath_lib
        self.fastpath = fastpath_lib.make_plan(fastpath)

    # -- state --------------------------------------------------------------
    def init_state(self, grad0: Pytree,
                   theta0: Optional[Pytree] = None) -> PolicyState:
        """Per-worker mirror state from a zeros-like gradient template.

        Zero ``grad_hat`` with an empty history reproduces the paper's
        all-upload initialization: round 0 triggers every worker.  The
        driver may pass stacked (W, …) templates — ``init_state`` is
        shape-polymorphic.
        """
        st: PolicyState = {"grad_hat": grad0}
        if self.needs_theta_hat:
            if theta0 is None:
                raise ValueError(f"{self.name} policy needs theta0")
            st["theta_hat"] = theta0
        return st

    # -- the four protocol methods ------------------------------------------
    def encode(self, ctx: CommRound, st: PolicyState
               ) -> Tuple[Pytree, Dict[str, Any]]:
        """Candidate upload (payload, aux).  Dense family: the gradient
        innovation δ∇ = ∇L_m(θ^k) − ĝ_m, bit-exactly the masked-delta math
        of the pre-policy drivers (stale ĝ cast to the fresh grad dtype)."""
        return self.innovation(ctx.grad_new, st["grad_hat"]), {}

    @staticmethod
    def innovation(grads: Pytree, grad_hat: Pytree) -> Pytree:
        """δ∇ = ∇L_m(θ^k) − ĝ_m (stale ĝ cast to the fresh grad dtype)."""
        return jax.tree_util.tree_map(lambda g, gh: g - gh.astype(g.dtype),
                                      grads, grad_hat)

    def should_upload(self, ctx: CommRound, st: PolicyState, payload: Pytree,
                      aux: Dict[str, Any]) -> jnp.ndarray:
        raise NotImplementedError

    def decode(self, ctx: CommRound, st: PolicyState, payload: Pytree,
               aux: Dict[str, Any], comm: jnp.ndarray
               ) -> Tuple[Pytree, PolicyState]:
        """(server-side δ∇ contribution, advanced worker state).

        The returned delta is all-zeros when ``comm`` is False, and
        ``grad_hat`` absorbs exactly that delta — the Σ_m ĝ_m = ∇^k
        invariant every driver relies on.
        """
        delta = jax.tree_util.tree_map(
            lambda p: comm.astype(p.dtype) * p, payload)
        new_st = dict(st)
        new_st["grad_hat"] = jax.tree_util.tree_map(
            lambda gh, d: gh + d.astype(gh.dtype), st["grad_hat"], delta)
        if "theta_hat" in st:
            new_st["theta_hat"] = lag.tree_select(comm, ctx.theta,
                                                  st["theta_hat"])
        return delta, new_st

    # -- the batched fast path ----------------------------------------------
    def fast_precompute(self, plan, grads: Pytree, st: PolicyState, *,
                        theta: Pytree, theta_stacked: bool,
                        grad_at_hat: Optional[Pytree] = None
                        ) -> Optional[Dict[str, Any]]:
        """Batched per-round precompute: a dict of stacked (W, …) arrays
        the driver vmaps into each worker's ``ctx.fast``, or None when
        this policy has nothing kernel-served (the driver then runs the
        plain vmapped round).

        This base method raising IS the fast-path tripwire: a new policy
        must either route its trigger/encode reductions through ``plan``
        or explicitly ``return None`` to declare the oracle path — it
        cannot silently inherit a bypass.
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not declare a fast-path route: "
            f"implement fast_precompute() to serve its trigger/encode "
            f"reductions from the batched plane (repro.fastpath), or "
            f"'return None' to explicitly opt out (see CommPolicy."
            f"fast_precompute)")

    def fast_decode(self, plan, st: PolicyState, payload: Pytree,
                    aux: Dict[str, Any], comm: jnp.ndarray, *,
                    theta: Pytree, theta_stacked: bool
                    ) -> Tuple[Pytree, PolicyState]:
        """Batched :meth:`decode` over stacked (W, …) trees — the masked
        lazy updates served by ONE plane launch instead of per-worker
        elementwise folds.  Same contract as ``decode``: the returned
        stacked delta is exactly what ``grad_hat`` absorbs.
        """
        W = comm.shape[0]
        delta = jax.tree_util.tree_map(
            lambda p: comm.reshape((W,) + (1,) * (p.ndim - 1)
                                   ).astype(p.dtype) * p, payload)
        new_st = dict(st)
        # ĝ ← ĝ + mask·payload: bitwise the per-worker decode for f32
        # state (same precomputed payload, same f32 ops); bf16 mirrors
        # round once from f32 instead of twice (≤1 ulp, see the parity
        # tier's documented tolerance)
        new_st["grad_hat"] = plan.masked_add(payload, st["grad_hat"], comm)
        if "theta_hat" in st:
            new_st["theta_hat"] = plan.masked_select(
                theta, st["theta_hat"], comm, a_stacked=theta_stacked)
        return delta, new_st

    def wire_bytes(self, grad_like: Pytree) -> float:
        """Bytes ONE triggered upload of ``grad_like`` puts on the wire.
        Dense family: the raw payload (size × itemsize per leaf)."""
        return float(sum(l.size * jnp.dtype(l.dtype).itemsize
                         for l in jax.tree_util.tree_leaves(grad_like)))

    # -- the collective wire format (repro.devrun) ---------------------------
    #
    # When workers are pinned to real devices the masked payloads cross
    # the interconnect as CONCRETE arrays, so each policy declares what
    # those arrays are: ``wire_pack`` turns a stacked candidate payload
    # (plus its encode ``aux`` and the upload mask) into a dict of
    # fixed-shape wire arrays — a quiet worker's slot is all-zero, an
    # absorbing element under the cross-device sum — ``wire_unpack``
    # turns the gathered arrays back into per-worker flat float32
    # summands, and ``wire_slot_bytes`` is the exact per-worker byte
    # account the measured-vs-predicted HLO assertion
    # (``repro.devrun.verify``) checks against.  The contract is
    # round-trip BIT-exactness: ``wire_unpack(wire_pack(payload))`` must
    # reproduce the masked payload's float32 flat buffer bitwise, so the
    # device plane's trajectory stays bit-identical to the vmapped sync
    # path.  The dense family moves the flat float32 buffer verbatim;
    # LAQ overrides with packed integer codes + per-leaf quantizer steps
    # (``repro.comm.laq``).

    def wire_pack(self, layout, payload_st: Pytree, aux: Dict[str, Any],
                  comm: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Stacked candidate payload → wire arrays, each with a leading
        worker dim.  ``layout`` is the tree's
        ``repro.fastpath.layout.FlatLayout``; dense payloads ship the
        masked ``(W, rows, LANES)`` float32 buffer."""
        buf = layout.flatten_stacked(payload_st)
        mask = comm.reshape((-1, 1, 1)).astype(buf.dtype)
        return {"payload": buf * mask}

    def wire_unpack(self, layout, wire: Dict[str, jnp.ndarray]
                    ) -> jnp.ndarray:
        """Gathered wire arrays (leading worker dim) → ``(W, rows, LANES)``
        float32 summands; summing axis 0 in worker order reproduces the
        engine's ``sum_reduce`` bit-exactly for float32 trees."""
        return wire["payload"]

    def wire_slot_bytes(self, layout) -> Dict[str, int]:
        """Exact bytes of ONE worker's wire arrays, keyed like
        :meth:`wire_pack`'s dict — what the all-gather actually moves per
        participant (framing included: sub-block padding, scales)."""
        from repro.fastpath.layout import LANES
        return {"payload": layout.rows * LANES * 4}

    def transfer_seconds(self, grad_like: Pytree, link) -> float:
        """Seconds ONE triggered upload spends alone on ``link`` — a
        convenience for costing a single upload in isolation.  ``link``
        is anything with ``transfer_seconds(nbytes)``
        (``repro.netsim.cluster.Link``).  The batch pricer
        (``repro.netsim.cluster.price_mask``) does NOT call this — it
        consumes the same policy-declared :meth:`wire_bytes` via
        ``RunReport.bytes_per_upload`` and additionally models ingress
        contention (transfers serialize at ``min(uplink, server NIC)``
        rate) — but both views share ``wire_bytes``, so quantized
        policies' byte savings carry into seconds either way."""
        return float(link.transfer_seconds(self.wire_bytes(grad_like)))

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}(name={self.name!r})"


# ---------------------------------------------------------------------------
# Driver entry point
# ---------------------------------------------------------------------------

def run_round(policy: CommPolicy, ctx: CommRound, st: PolicyState
              ) -> Tuple[jnp.ndarray, Pytree, PolicyState]:
    """One worker's full round: encode → trigger → decode.

    Returns (comm: () bool, delta: pytree, new_state).  Drivers vmap this
    over the worker/pod dim.  Schedule-driven baselines (cyc-IAG,
    num-IAG) are ordinary policies now — ``repro.comm.schedule.
    ScheduledPolicy`` owns the mask, so there is no override side door.
    """
    payload, aux = policy.encode(ctx, st)
    comm = policy.should_upload(ctx, st, payload, aux)
    delta, new_st = policy.decode(ctx, st, payload, aux, comm)
    return comm, delta, new_st
