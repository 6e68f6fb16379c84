"""``FastPathPlan`` — the batched comm plane, resolved once per policy.

A plan is what ``sqnorm_fn`` / ``use_pallas`` used to be: the policy's
route to accelerated trigger/encode math.  On TPU it serves LAQ, whose
encode needs the flat buffer; the dense policies (gd, lag-wk, lag-ps,
lasg-wk) return None from ``fast_precompute`` and run their reduce and
masked fold as XLA fusions on the parameter tree, where the plane's
flatten and scatter would cost more than its kernels save.
:meth:`FastPathPlan.sqnorm` and :meth:`FastPathPlan.delta_sqnorm` serve
no round; the parity tier and ``benchmarks/perf_comm.py`` call
them.  It owns

  * the activation decision (``mode="auto"`` → on when running on TPU,
    except below the dispatch floor and for a round a multi-device mesh
    partitions; ``"on"`` forces the plane — interpret-mode Pallas off
    TPU, what the parity tier and the CPU benchmarks run),
  * a cache of :class:`repro.fastpath.layout.FlatLayout` offset tables
    keyed by tree structure (resolved at first trace, static afterwards),
  * the pytree-level ops — each ONE batched Pallas launch over
    ``(workers, row-blocks)`` plus a deterministic fixed-order segment
    reduction from per-block partials to per-(worker, leaf) scalars.

Reduction-order contract: partials are reduced per (worker, leaf-offset)
in static block order, then across leaves in pytree order — the same
inputs produce bit-identical results on every call (pinned by
tests/test_fastpath.py's seed-repeat determinism tests), unlike a
reduction whose grouping depends on how XLA schedules a fused loop.

Float64 trees (the x64 convex benchmarks) are NOT served — the plane
computes in float32.  ``supports`` reports this; in ``auto`` mode
callers silently fall back to the jnp oracle, in forced mode they raise.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.fastpath import kernels
from repro.fastpath.layout import (SUPPORTED_DTYPES, FlatLayout,
                                   tree_signature)

Pytree = Any

MODES = ("auto", "on")

#: auto-mode dispatch floor, in flat-buffer rows × workers.  Below this
#: much work the batched launch cannot amortize its flatten/scatter
#: overhead and the jnp oracle wins outright — the regression
#: ``BENCH_perf_comm.json`` pinned at convex-d50 M=1 (256 rows × 1
#: worker: batched 0.88× the per-leaf route, 0.09× the oracle).  1024 =
#: four single-block workers or one worker of four grid blocks; at and
#: above it the batched plane's measured speedups hold.  Forced plans
#: (``mode="on"``) ignore the floor — they exist for kernel parity, not
#: speed.
SMALL_DISPATCH_ROWS = 1024


def on_tpu() -> bool:
    from repro.kernels import on_tpu as _on_tpu
    return _on_tpu()


class FastPathPlan:
    """Resolved batched-comm-plane configuration for one policy."""

    def __init__(self, mode: str = "auto"):
        if mode not in MODES:
            raise ValueError(f"fastpath mode must be one of {MODES} (or "
                             f"'off'/None for no plan), got {mode!r}")
        self.mode = mode
        self._layouts: Dict[Tuple, FlatLayout] = {}

    # -- activation ---------------------------------------------------------

    @property
    def enabled(self) -> bool:
        """The default-on flip: auto plans activate on TPU only."""
        return self.mode == "on" or on_tpu()

    @property
    def forced(self) -> bool:
        return self.mode == "on"

    @property
    def interpret(self) -> bool:
        return not on_tpu()

    def supports(self, tree: Pytree) -> bool:
        """True iff every leaf dtype is one the f32 plane can serve."""
        return all(any(jnp.dtype(l.dtype) == jnp.dtype(d)
                       for d in SUPPORTED_DTYPES)
                   for l in jax.tree_util.tree_leaves(tree))

    def below_dispatch_floor(self, tree_st: Pytree) -> bool:
        """True when a stacked tree is too small for the batched launch
        to pay for itself (rows × workers < ``SMALL_DISPATCH_ROWS``) —
        ``repro.engine.rounds.policy_rounds`` then takes the jnp oracle
        instead.  Static: decided from shapes at trace time.  Forced
        plans always return False (the parity tier runs the kernels on
        every shape by design)."""
        if self.forced:
            return False
        leaves = jax.tree_util.tree_leaves(tree_st)
        if not leaves:
            return True
        W = leaves[0].shape[0]
        return self.layout_for(tree_st).rows * W < SMALL_DISPATCH_ROWS

    def partitioned_by_context(self) -> bool:
        """True when the surrounding mesh (``jax.set_mesh``) spreads the
        plane's operands over several devices outside ``shard_map``.
        Mosaic kernels cannot be partitioned automatically — the compiler
        refuses them — so ``repro.engine.rounds.policy_rounds`` then
        takes the jnp oracle.  Inside ``repro.devrun``'s shard_map every
        axis is manual and the plane runs per device.  Forced plans
        return False (interpret mode partitions like any jnp code)."""
        if self.forced:
            return False
        mesh = jax.sharding.get_abstract_mesh()
        return any(size > 1 and kind != jax.sharding.AxisType.Manual
                   for size, kind in zip(mesh.axis_sizes, mesh.axis_types))

    # -- layout -------------------------------------------------------------

    def layout_for(self, tree: Pytree, stacked: bool = True) -> FlatLayout:
        """The (cached) offset table; ``stacked`` strips the leading
        worker dim from the signature so per-worker and template trees
        share one layout."""
        strip = 1 if stacked else 0
        # shape-only template (no tracer ops — the layout is static)
        template = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape[strip:], jnp.float32),
            tree)
        key = tree_signature(template)
        lo = self._layouts.get(key)
        if lo is None:
            lo = FlatLayout.for_tree(template)
            self._layouts[key] = lo
        return lo

    # -- reductions: per-block partials → per-leaf → scalar -----------------

    @staticmethod
    def _per_leaf(partials: jnp.ndarray, lo: FlatLayout, op: str):
        """(W, nsubs) partials → (W, num_leaves), fixed sub-block order.
        Tail sub-blocks carry zeros into leaf 0 — absorbing for both the
        sum and the |·|-max."""
        seg = jnp.asarray(lo.sub_leaf)
        if op == "sum":
            f = lambda p: jax.ops.segment_sum(p, seg, lo.num_leaves)
        else:
            f = lambda p: jax.ops.segment_max(p, seg, lo.num_leaves)
        return jax.vmap(f)(partials)

    def _total(self, partials: jnp.ndarray, lo: FlatLayout) -> jnp.ndarray:
        # per-(worker, leaf-offset) partial sums first, leaves last — the
        # deterministic ordering contract
        return jnp.sum(self._per_leaf(partials, lo, "sum"), axis=1)

    # -- pytree-level ops (one batched launch each) -------------------------

    def _flat2(self, lo: FlatLayout, a_st: Pytree, b: Pytree,
               b_stacked: bool):
        fa = lo.flatten_stacked(a_st)
        fb = lo.flatten_stacked(b) if b_stacked else lo.flatten(b)
        return fa, fb

    def delta_sqnorm(self, a_st: Pytree, b: Pytree,
                     *, b_stacked: bool = True) -> jnp.ndarray:
        """Per-worker ‖a − b‖² over stacked trees → (W,) float32.  ``b``
        may be the unstacked shared tree (broadcast in the kernel)."""
        lo = self.layout_for(a_st)
        W = jax.tree_util.tree_leaves(a_st)[0].shape[0]
        if lo.nblocks == 0:
            return jnp.zeros((W,), jnp.float32)
        fa, fb = self._flat2(lo, a_st, b, b_stacked)
        parts = kernels.delta_sqnorm_blocks(fa, fb, interpret=self.interpret)
        return self._total(parts, lo)

    def sqnorm(self, t_st: Pytree) -> jnp.ndarray:
        """Per-worker ‖t‖² over a stacked tree → (W,) float32."""
        lo = self.layout_for(t_st)
        W = jax.tree_util.tree_leaves(t_st)[0].shape[0]
        if lo.nblocks == 0:
            return jnp.zeros((W,), jnp.float32)
        parts = kernels.sqnorm_blocks(lo.flatten_stacked(t_st),
                                      interpret=self.interpret)
        return self._total(parts, lo)

    def laq_encode(self, g_st: Pytree, q_st: Pytree, e_st: Pytree,
                   *, bits: int, return_steps: bool = False):
        """Batched LAQ encode with per-(worker, leaf) quantizer scales.

        Returns (payload stacked f32 tree, residual stacked f32 tree,
        trigger LHS ‖payload‖² (W,)) — the semantics of
        ``repro.kernels.lag_trigger.ops.laq_encode`` for every worker in
        two launches (absmax sweep + fused encode sweep) instead of
        2·L·W.  ``return_steps`` appends the ``(W, num_leaves)`` float32
        quantizer steps scale/qmax — the grid the encode kernel divides
        by — which the collective wire format (``repro.comm.laq``)
        transmits so packed integer codes decode to the payload bitwise
        (payload coordinates are exactly code·step; see
        ``lag_trigger.ops.laq_encode`` for why the step, not the raw
        scale, is the safe thing to transmit).  The scale/qmax division
        happens exactly once, here — the encode kernel receives the
        already-divided steps as an operand — so the grid the payload
        multiply used and the grid on the wire are the same f32 value on
        every backend.
        """
        lo = self.layout_for(g_st)
        W = jax.tree_util.tree_leaves(g_st)[0].shape[0]
        if lo.nblocks == 0:
            zt = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape, jnp.float32), g_st)
            out = (zt, zt, jnp.zeros((W,), jnp.float32))
            return out + (jnp.zeros((W, lo.num_leaves), jnp.float32),) \
                if return_steps else out
        payload, resid, sq, steps = self.laq_encode_flat(
            lo, lo.flatten_stacked(g_st), lo.flatten_stacked(q_st),
            lo.flatten_stacked(e_st), bits=bits)
        out = (lo.unflatten_stacked(payload, like=jnp.float32),
               lo.unflatten_stacked(resid, like=jnp.float32),
               self._total(sq, lo))
        return out + (steps,) if return_steps else out

    def laq_encode_flat(self, lo: FlatLayout, fg: jnp.ndarray,
                        fq: jnp.ndarray, fe: jnp.ndarray, *, bits: int):
        """:meth:`laq_encode` on flat ``(W, rows, LANES)`` buffers of
        layout ``lo``: (payload, residual, ‖p‖² per-sub-block partials,
        ``(W, num_leaves)`` steps)."""
        parts = kernels.absmax_blocks(fg, fq, fe, interpret=self.interpret)
        scales = self._per_leaf(parts, lo, "max")          # (W, num_leaves)
        # divide ONCE: this per-leaf step array both feeds the kernel
        # (gathered per sub-block) and is what ``return_steps`` hands to
        # the collective wire format — one rounding, everywhere
        steps = scales / float(2 ** (bits - 1) - 1)
        steps_subs = steps[:, jnp.asarray(lo.sub_leaf)]
        payload, resid, sq = kernels.laq_encode_blocks(
            fg, fq, fe, steps_subs, bits, interpret=self.interpret)
        return payload, resid, sq, steps

    def _masked(self, a: Pytree, b_st: Pytree, mask: jnp.ndarray, mode: str,
                a_stacked: bool) -> Pytree:
        lo = self.layout_for(b_st)
        if lo.nblocks == 0:
            return b_st
        fa, fb = (lo.flatten_stacked(a) if a_stacked else lo.flatten(a),
                  lo.flatten_stacked(b_st))
        out = kernels.masked_combine(fa, fb, mask, mode,
                                     interpret=self.interpret)
        return lo.unflatten_stacked(out, like=b_st)

    def masked_add(self, a: Pytree, b_st: Pytree, mask: jnp.ndarray,
                   *, a_stacked: bool = True) -> Pytree:
        """b + mask·a per worker (fold a masked payload into a mirror)."""
        return self._masked(a, b_st, mask, "add", a_stacked)

    def masked_update(self, a: Pytree, b_st: Pytree, mask: jnp.ndarray,
                      *, a_stacked: bool = True) -> Pytree:
        """b + mask·(a − b) per worker — the classic lazy update."""
        return self._masked(a, b_st, mask, "update", a_stacked)

    def masked_select(self, a: Pytree, b_st: Pytree, mask: jnp.ndarray,
                      *, a_stacked: bool = True) -> Pytree:
        """where(mask, a, b) per worker — an EXACT copy on upload (θ̂ ← θ
        and the LAQ residual advance must not round through arithmetic)."""
        return self._masked(a, b_st, mask, "select", a_stacked)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"FastPathPlan(mode={self.mode!r}, enabled={self.enabled}, "
                f"interpret={self.interpret})")


def make_plan(spec) -> Optional[FastPathPlan]:
    """None/'off' → no plan; 'auto'/'on' → a plan; plans pass through."""
    if spec is None or spec == "off":
        return None
    if isinstance(spec, FastPathPlan):
        return spec
    return FastPathPlan(spec)


def active_plan(policy) -> Optional[FastPathPlan]:
    """The policy's plan iff it is resolved AND active on this backend."""
    plan = getattr(policy, "fastpath", None)
    if isinstance(plan, FastPathPlan) and plan.enabled:
        return plan
    return None
