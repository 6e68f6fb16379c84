"""Batched Pallas kernels for the flat-buffer comm plane.

Every kernel runs ONE launch per round with grid (worker-chunks ×
row-blocks) over the :mod:`repro.fastpath.layout` flat buffer — replacing
the per-leaf, per-worker launches of ``repro.kernels.lag_trigger.ops``.
Workers are VECTORIZED inside each block: a grid step reads a
``(W_chunk, BLOCK_ROWS, LANES)`` slab, so the worker dim rides the VPU's
batch lanes instead of serializing the grid (what a vmapped per-leaf
launch gets for free, preserved here), with ``MAX_WORKER_BLOCK`` capping
the slab so VMEM stays bounded on real hardware (8 workers × 128 KiB =
1 MiB per f32 operand); larger fleets tile over worker-chunks.  The
worker dim is zero-padded up to the chunk multiple — zeros are absorbing
for every plane op and the wrappers slice the pad back off.

Reductions never accumulate across grid steps: each (chunk, block) cell
writes per-(worker, SUB-BLOCK) partials — the layout's leaf-padding
granularity, so partials never mix leaves — and the deterministic
fixed-order segment reduction down to per-(worker, leaf) scalars happens
in plain jnp in :mod:`repro.fastpath.plan`.  Per-sub-block quantizer
steps enter the LAQ kernel the same way, so batching preserves LAQ's
per-leaf grid.  Across the ``pallas_call`` boundary these scalars are
laid out ``(row_blocks, W, subs_per_step)`` with a ``(1, W_chunk,
subs_per_step)`` block: Mosaic needs a block's last two dims to be
(8, 128)-divisible or whole, and here they are the whole sub-block axis
of one step and either all workers or an 8-worker chunk.  The wrappers
convert to and from the ``(W, nsubs)`` view in jnp.  Second operands
may be UNSTACKED ``(rows, LANES)`` (e.g. the shared θ^k under a
per-worker θ̂_m sweep): their BlockSpec ignores the worker-chunk index,
so the broadcast costs no extra HBM.

All compute is float32 (the jnp oracle's convention); callers cast at
scatter time.  On CPU the kernels run in interpret mode — parity
validation, not speed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.fastpath.layout import BLOCK_ROWS, LANES, SUB_ROWS

#: cap on workers per block: 8 × (256, 128) f32 = 1 MiB VMEM per operand.
#: The LAQ encode streams five slab operands, double-buffered: at 16
#: workers that is 20 MiB, over v5e's 16 MiB scoped-VMEM limit (Mosaic
#: refuses it); at 8 it is 10 MiB.
MAX_WORKER_BLOCK = 8

# masked-combine modes: how (candidate a, state b, per-worker mask m) fold
MASK_MODES = ("add", "update", "select")


def _tiling(W: int, R: int, interpret: bool):
    """(worker_chunk, padded_W, rows_per_step) for one launch.

    Compiled (TPU): workers chunk at ``MAX_WORKER_BLOCK`` and rows step
    by ``BLOCK_ROWS`` so a slab stays VMEM-sized.  Interpret mode has no
    VMEM — and pays a full output-buffer copy per grid step — so the
    whole buffer is ONE grid step there (same arithmetic, same
    per-sub-block partials; only the schedule differs).
    """
    if interpret:
        return W, W, max(R, BLOCK_ROWS)
    wc = min(W, MAX_WORKER_BLOCK)
    return wc, -(-W // wc) * wc, BLOCK_ROWS


def _pad_w(x: jnp.ndarray, Wp: int) -> jnp.ndarray:
    if x.shape[0] == Wp:
        return x
    return jnp.pad(x, [(0, Wp - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


def _data_spec(ndim: int, wc: int, rows: int) -> pl.BlockSpec:
    """Spec for a flat operand: stacked (W, R, L) slab or broadcast (R, L)."""
    if ndim == 3:
        return pl.BlockSpec((wc, rows, LANES), lambda w, i: (w, i, 0))
    return pl.BlockSpec((rows, LANES), lambda w, i: (i, 0))


def _sub_spec(wc: int, rows: int) -> pl.BlockSpec:
    """(1, wc, subs-per-step) spec for per-(worker, sub-block) scalars
    stored ``(row_blocks, Wp, subs_per_step)``."""
    return pl.BlockSpec((1, wc, rows // SUB_ROWS), lambda w, i: (i, w, 0))


def _sub_shape(Wp: int, R: int, rows: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((R // rows, Wp, rows // SUB_ROWS),
                                jnp.float32)


def _to_blocked(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(Wp, nsubs) → (row_blocks, Wp, subs_per_step)."""
    Wp, nsubs = x.shape
    per = rows // SUB_ROWS
    return x.reshape(Wp, nsubs // per, per).transpose(1, 0, 2)


def _from_blocked(x: jnp.ndarray, W: int) -> jnp.ndarray:
    """(row_blocks, Wp, subs_per_step) → (W, nsubs)."""
    nb, Wp, per = x.shape
    return x.transpose(1, 0, 2).reshape(Wp, nb * per)[:W]


def _worker_spec(wc: int) -> pl.BlockSpec:
    """(wc, 1) spec for per-worker scalars (the upload mask)."""
    return pl.BlockSpec((wc, 1), lambda w, i: (w, 0))


def _slab(ref) -> jnp.ndarray:
    """Read a data ref as an (wc | 1, subs-per-step, SUB_ROWS, LANES)
    float32 slab — sub-block-major so reductions stay per sub-block."""
    x = ref[...].astype(jnp.float32)
    if x.ndim == 2:
        x = x[None]
    return x.reshape(x.shape[0], -1, SUB_ROWS, LANES)


def _per_sub(reduce, x: jnp.ndarray) -> jnp.ndarray:
    """(wc, subs, SUB_ROWS, LANES) → (1, wc, subs): sublanes first, then
    lanes — Mosaic reduces one trailing dim at a time."""
    return reduce(reduce(x, axis=2), axis=-1)[None]


def _sub_bcast(s: jnp.ndarray) -> jnp.ndarray:
    """(1, wc, subs) per-sub-block scalars → (wc, subs, 1, LANES), which
    broadcasts over a slab's sub-block rows."""
    s = s[0][:, :, None]
    return jnp.broadcast_to(s, s.shape[:2] + (LANES,))[:, :, None, :]


# ---------------------------------------------------------------------------
# Per-sub-block partial reductions (one write per grid cell)
# ---------------------------------------------------------------------------

def _delta_sq_kernel(a_ref, b_ref, out_ref):
    d = _slab(a_ref) - _slab(b_ref)
    out_ref[...] = _per_sub(jnp.sum, d * d)


def _sq_kernel(a_ref, out_ref):
    x = _slab(a_ref)
    out_ref[...] = _per_sub(jnp.sum, x * x)


def _absmax_kernel(g_ref, q_ref, e_ref, out_ref):
    v = _slab(g_ref) - _slab(q_ref) + _slab(e_ref)
    out_ref[...] = _per_sub(jnp.max, jnp.abs(v))


def _partials(kernel, ops, *, interpret: bool) -> jnp.ndarray:
    """Launch a partial-reduction kernel → (W, nsubs) float32."""
    W, R = ops[0].shape[0], ops[0].shape[1]
    wc, Wp, rows = _tiling(W, R, interpret)
    ops = [op if op.ndim == 2 else _pad_w(op, Wp) for op in ops]
    out = pl.pallas_call(
        kernel,
        grid=(Wp // wc, R // rows),
        in_specs=[_data_spec(op.ndim, wc, rows) for op in ops],
        out_specs=_sub_spec(wc, rows),
        out_shape=_sub_shape(Wp, R, rows),
        interpret=interpret,
    )(*ops)
    return _from_blocked(out, W)


def delta_sqnorm_blocks(a: jnp.ndarray, b: jnp.ndarray,
                        *, interpret: bool = True) -> jnp.ndarray:
    """Per-sub-block partials of ‖a − b‖²: (W, R, L) × (W|·, R, L) →
    (W, nsubs)."""
    return _partials(_delta_sq_kernel, [a, b], interpret=interpret)


def sqnorm_blocks(a: jnp.ndarray, *, interpret: bool = True) -> jnp.ndarray:
    """Per-sub-block partials of ‖a‖²: (W, R, L) → (W, nsubs)."""
    return _partials(_sq_kernel, [a], interpret=interpret)


def absmax_blocks(g: jnp.ndarray, q: jnp.ndarray, e: jnp.ndarray,
                  *, interpret: bool = True) -> jnp.ndarray:
    """Per-sub-block max|(g − q) + e| — the LAQ quantizer-scale sweep."""
    return _partials(_absmax_kernel, [g, q, e], interpret=interpret)


# ---------------------------------------------------------------------------
# Fused LAQ encode: quantize + residual + trigger-sqnorm partial, one sweep
# ---------------------------------------------------------------------------

def _laq_kernel(qmax, g_ref, q_ref, e_ref, s_ref, p_ref, eout_ref, sq_ref):
    v = _slab(g_ref) - _slab(q_ref) + _slab(e_ref)
    # per-(worker, sub-block) quantizer step — precomputed OUTSIDE the
    # kernel (scale/qmax divides once in plan.laq_encode) so the exact
    # f32 grid the payload multiply uses is also the value the
    # collective wire format transmits; a division in the kernel body
    # could round differently from one in the surrounding module
    step = _sub_bcast(s_ref[...].astype(jnp.float32))
    inv = jnp.where(step > 0.0, 1.0 / jnp.where(step > 0.0, step, 1.0), 0.0)
    codes = jnp.clip(jnp.round(v * inv), -qmax, qmax)
    p = codes * step
    p_ref[...] = p.reshape(p_ref.shape)
    eout_ref[...] = (v - p).reshape(eout_ref.shape)
    sq_ref[...] = _per_sub(jnp.sum, p * p)


def laq_encode_blocks(g: jnp.ndarray, q: jnp.ndarray, e: jnp.ndarray,
                      steps_subs: jnp.ndarray, bits: int,
                      *, interpret: bool = True):
    """Fused b-bit encode over the batched flat buffer.

    ``steps_subs`` is the (W, nsubs) per-sub-block quantizer STEP
    (absmax scale already divided by qmax) — the per-(worker, LEAF)
    value gathered through the layout's static ``sub_leaf`` table, so
    batching preserves LAQ's per-leaf grid.  Returns (payload (W, R, L)
    f32, residual (W, R, L) f32, ‖p‖² per-sub-block partials (W, nsubs)).
    """
    W, R = g.shape[0], g.shape[1]
    wc, Wp, rows = _tiling(W, R, interpret)
    qmax = float(2 ** (bits - 1) - 1)
    gp, qp, ep = (_pad_w(x, Wp) for x in (g, q, e))
    sp = _to_blocked(_pad_w(steps_subs, Wp), rows)
    p, eout, sq = pl.pallas_call(
        functools.partial(_laq_kernel, qmax),
        grid=(Wp // wc, R // rows),
        in_specs=[_data_spec(3, wc, rows)] * 3 + [_sub_spec(wc, rows)],
        out_specs=[_data_spec(3, wc, rows), _data_spec(3, wc, rows),
                   _sub_spec(wc, rows)],
        out_shape=[jax.ShapeDtypeStruct((Wp,) + g.shape[1:], jnp.float32),
                   jax.ShapeDtypeStruct((Wp,) + g.shape[1:], jnp.float32),
                   _sub_shape(Wp, R, rows)],
        interpret=interpret,
    )(gp, qp, ep, sp)
    return p[:W], eout[:W], _from_blocked(sq, W)


# ---------------------------------------------------------------------------
# Masked lazy updates (the state fold), batched over workers
# ---------------------------------------------------------------------------

def _masked_kernel(mode, a_ref, b_ref, m_ref, out_ref):
    a, b = _slab(a_ref), _slab(b_ref)
    m = m_ref[...].astype(jnp.float32)[:, :, None, None]
    if mode == "add":          # b + m·a          (fold a masked payload)
        out = b + m * a
    elif mode == "update":     # b + m·(a − b)    (the classic lazy update)
        out = b + m * (a - b)
    else:                      # select           (exact copy, no arithmetic)
        out = jnp.where(m != 0.0, a, b)
    out_ref[...] = out.reshape(out_ref.shape)


def masked_combine(a: jnp.ndarray, b: jnp.ndarray, mask: jnp.ndarray,
                   mode: str, *, interpret: bool = True) -> jnp.ndarray:
    """Per-worker masked fold of candidate ``a`` into state ``b``.

    ``mask`` is (W,) bool/float; ``mode`` ∈ ``MASK_MODES``.  ``select``
    copies bit-exactly (θ̂ ← θ must not round-trip through b + (a − b)).
    """
    if mode not in MASK_MODES:
        raise ValueError(f"mode must be one of {MASK_MODES}, got {mode!r}")
    W, R = b.shape[0], b.shape[1]
    wc, Wp, rows = _tiling(W, R, interpret)
    a = a if a.ndim == 2 else _pad_w(a, Wp)
    bp = _pad_w(b, Wp)
    m2d = _pad_w(mask.reshape(W, 1).astype(jnp.float32), Wp)
    out = pl.pallas_call(
        functools.partial(_masked_kernel, mode),
        grid=(Wp // wc, R // rows),
        in_specs=[_data_spec(a.ndim, wc, rows), _data_spec(3, wc, rows),
                  _worker_spec(wc)],
        out_specs=_data_spec(3, wc, rows),
        out_shape=jax.ShapeDtypeStruct(bp.shape, jnp.float32),
        # the fold is in place: each grid cell reads its block of b
        # before writing the same block, so b's flat copy is the output
        input_output_aliases={1: 0},
        interpret=interpret,
    )(a, bp, m2d)
    return out[:W]
