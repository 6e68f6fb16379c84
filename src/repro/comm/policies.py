"""The dense-upload policies: GD, LAG-WK, LAG-PS, LASG-WK.

All four upload the raw gradient innovation δ∇_m = ∇L_m(θ^k) − ĝ_m (the
base-class payload); they differ only in the trigger:

  GDPolicy       always upload (synchronous baseline, eq. 2)
  LAGWKPolicy    worker-side trigger (15a): ‖δ∇_m‖² > RHS
  LAGPSPolicy    server-side trigger (15b): L_m²‖θ̂_m − θ^k‖² > RHS
  LASGWKPolicy   stochastic trigger (LASG-WK, Chen et al. 2020):
                 ‖∇ℓ_m(θ^k; ξ^k) − ∇ℓ_m(θ̂_m; ξ^k)‖² > RHS — both gradients
                 on the CURRENT sample, so the comparison is correlated and
                 the stale-gradient variance cancels.  With full-batch
                 gradients ∇ℓ_m(θ̂_m; ξ) ≡ ĝ_m and LASG-WK reduces exactly
                 to LAG-WK (tested).

RHS is the shared iterate-lag quantity (1/(α²M²)) Σ_d ξ_d ‖θ^{k+1-d} −
θ^{k-d}‖² of eq. (14), via ``repro.core.lag.trigger_rhs``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from repro.comm.base import CommPolicy, CommRound, PolicyState, Pytree
from repro.core import lag


class GDPolicy(CommPolicy):
    """Every worker uploads every round — the synchronous baseline."""
    name = "gd"

    def should_upload(self, ctx: CommRound, st: PolicyState, payload: Pytree,
                      aux: Dict[str, Any]) -> jnp.ndarray:
        return jnp.ones((), bool)

    def fast_precompute(self, plan, grads, st, *, theta, theta_stacked,
                        grad_at_hat=None):
        # explicit opt-out: GD has no trigger reduction or encode sweep to
        # serve from the plane — the round is pure elementwise math
        return None


class LAGWKPolicy(CommPolicy):
    """LAG with the worker-side trigger (15a).

    The LHS re-uses the encoded payload (δ∇ is exactly the quantity the
    trigger norms), so the gradient difference is materialized once.
    """
    name = "lag-wk"

    def should_upload(self, ctx: CommRound, st: PolicyState, payload: Pytree,
                      aux: Dict[str, Any]) -> jnp.ndarray:
        return self.sqnorm_fn(payload) > lag.trigger_rhs(ctx.hist, ctx.cfg)

    def fast_precompute(self, plan, grads, st, *, theta, theta_stacked,
                        grad_at_hat=None):
        # opt out: XLA fuses ‖δ∇‖² and ĝ + c·δ∇ per leaf, with no flatten
        return None


class LAGPSPolicy(CommPolicy):
    """LAG with the server-side trigger (15b): the server decides from the
    iterate drift ‖θ̂_m − θ^k‖² and a smoothness bound L_m — no fresh
    gradient needed on skipped rounds (the compute saving of PS)."""
    name = "lag-ps"
    state_keys = ("grad_hat", "theta_hat")
    needs_theta_hat = True
    needs_L_m = True

    def should_upload(self, ctx: CommRound, st: PolicyState, payload: Pytree,
                      aux: Dict[str, Any]) -> jnp.ndarray:
        if ctx.L_m is None:
            raise ValueError("LAG-PS requires per-worker smoothness L_m")
        return lag.ps_communicate(ctx.theta, st["theta_hat"], ctx.L_m,
                                  ctx.hist, ctx.cfg, sqnorm_fn=self.sqnorm_fn)

    def fast_precompute(self, plan, grads, st, *, theta, theta_stacked,
                        grad_at_hat=None):
        # opt out: XLA fuses ‖θ̂_m − θ‖² and the θ̂ select per leaf
        return None


class LASGWKPolicy(CommPolicy):
    """LASG-WK: the worker trigger evaluated on stochastic gradients.

    The naive LAG-WK LHS ‖∇ℓ(θ^k; ξ^k) − ĝ_m‖² never shrinks under
    minibatch noise (ĝ_m was computed on an OLD sample), so LAG-WK degrades
    to always-upload in the stochastic regime.  LASG-WK fixes this by
    differencing two gradients on the SAME fresh sample: the worker keeps
    its last-upload iterate θ̂_m, evaluates ∇ℓ_m(θ̂_m; ξ^k) alongside the
    fresh ∇ℓ_m(θ^k; ξ^k) (the driver's second vmapped backward pass,
    ``needs_grad_at_hat``), and uploads iff

        ‖∇ℓ_m(θ^k; ξ^k) − ∇ℓ_m(θ̂_m; ξ^k)‖² > RHS  (15a-style).

    The upload itself is still the dense innovation against ĝ_m, so the
    server recursion (eq. 4) is unchanged.
    """
    name = "lasg-wk"
    state_keys = ("grad_hat", "theta_hat")
    needs_theta_hat = True
    needs_grad_at_hat = True

    def should_upload(self, ctx: CommRound, st: PolicyState, payload: Pytree,
                      aux: Dict[str, Any]) -> jnp.ndarray:
        if ctx.grad_at_hat is None:
            raise ValueError("LASG-WK requires grad_at_hat (the driver must "
                             "evaluate ∇ℓ_m(θ̂_m) on the current sample)")
        lhs = self.sqnorm_fn(lag.tree_sub(ctx.grad_new, ctx.grad_at_hat))
        return lhs > lag.trigger_rhs(ctx.hist, ctx.cfg)

    def fast_precompute(self, plan, grads, st, *, theta, theta_stacked,
                        grad_at_hat=None):
        # opt out: XLA fuses ‖∇ℓ(θ^k;ξ) − ∇ℓ(θ̂;ξ)‖² and the folds per leaf
        return None
