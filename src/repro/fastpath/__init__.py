"""``repro.fastpath`` — the batched flat-buffer comm plane.

LAQ's per-round encode (absmax, then the fused quantize + residual +
trigger-sqnorm sweep, and the masked folds of q̂ and the residual) used to
launch one Pallas kernel per pytree leaf per worker.  It is the plane's
one hot path on TPU: the dense policies (gd, lag-wk, lag-ps, lasg-wk)
opt out, because their round is a per-leaf reduce and a masked fold that
XLA fuses on the parameter tree for less HBM traffic than a flatten,
kernel and scatter.  The sqnorm kernels stay for the parity tier and
``benchmarks/perf_comm.py``.  This package flattens the gradient pytree ONCE
into a single padded ``(rows, 128)`` buffer with a static leaf-offset
table (:mod:`repro.fastpath.layout`), then issues ONE batched launch per
round per quantity with grid (workers × row-blocks)
(:mod:`repro.fastpath.kernels`), with deterministic per-(worker,
leaf-offset) segment reductions (:mod:`repro.fastpath.plan`).

Entry point: :class:`FastPathPlan`, resolved once per
``repro.comm.CommPolicy`` (the ``fastpath=`` knob of
``repro.comm.make_policy`` / ``repro.dist.TrainerConfig`` /
``repro.engine.Experiment``).  Mode ``"auto"`` (the default everywhere)
activates the plane on TPU and falls back to the jnp oracle on CPU;
``"on"`` forces it (interpret-mode Pallas off-TPU — what the parity test
tier and ``benchmarks/perf_comm.py`` run); ``"off"``/None disables it.
See docs/ARCHITECTURE.md §fast path for the flatten → launch → scatter
walkthrough.
"""
from repro.fastpath.layout import (BLOCK, BLOCK_ROWS, LANES, SUB, SUB_ROWS,
                                   SUBS_PER_BLOCK, FlatLayout)
from repro.fastpath.plan import FastPathPlan, active_plan, make_plan

__all__ = ["FlatLayout", "FastPathPlan", "make_plan", "active_plan",
           "BLOCK", "BLOCK_ROWS", "LANES", "SUB", "SUB_ROWS",
           "SUBS_PER_BLOCK"]
