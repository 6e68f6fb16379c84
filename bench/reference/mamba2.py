"""Plain reference of the Mamba-2 language model (arXiv:2405.21060).

Straightforward ``jax.numpy``: no kernels, no chunking, no batching of
workers.  The SSD layer is the quadratic "attention" form of the paper's
state-space duality over the whole sequence,

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t,

with the segment sums taken term by term (the paper's ``segsum``), so no
difference of large cumulative sums enters.  In float32 every matmul runs
at ``Precision.HIGHEST``; in any other dtype every array, parameters
included, is held in that dtype (the control).

Departures from the published model, as the program runs it: RMS norms
use eps 1e-6 (published 1e-5).  The parameter tree is laid out as the
program's, layers stacked on a leading axis, so one set of weights made
from the seed serves both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _prec(dtype):
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


def _sizes(m: dict):
    di = m["ssm_expand"] * m["d_model"]
    return di, m["ssm_state"], di // m["ssm_headdim"], m["ssm_headdim"]


def init(key, m: dict) -> dict:
    """Weights from ``key``: matrices N(0, 0.02) (out_proj further over
    sqrt(layers)), A in [1, 16], dt in [1e-3, 0.1] through dt_bias (the
    published initialisation), conv uniform in +-1/sqrt(kernel)."""
    L, d, V, K = m["num_layers"], m["d_model"], m["vocab_size"], m["ssm_conv"]
    di, ds, h, _ = _sizes(m)
    conv = di + 2 * ds
    ks = jax.random.split(key, 8)
    bound = 1.0 / math.sqrt(K)
    dt = jnp.exp(jax.random.uniform(ks[5], (L, h), F32, math.log(1e-3),
                                    math.log(1e-1)))
    mixer = {
        "in_proj": 0.02 * jax.random.normal(ks[1], (L, d, 2 * di + 2 * ds + h)),
        "conv_w": jax.random.uniform(ks[2], (L, K, conv), F32, -bound, bound),
        "conv_b": jax.random.uniform(ks[3], (L, conv), F32, -bound, bound),
        "A_log": jnp.log(jax.random.uniform(ks[4], (L, h), F32, 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D": jnp.ones((L, h), F32),
        "norm_scale": jnp.ones((L, di), F32),
        "out_proj": (0.02 / math.sqrt(L))
        * jax.random.normal(ks[6], (L, di, d)),
    }
    return {"embed": 0.02 * jax.random.normal(ks[0], (V, d)),
            "blocks": {"0": {"norm1": {"scale": jnp.ones((L, d), F32)},
                             "mixer": mixer}},
            "tail": [],
            "final_norm": {"scale": jnp.ones((d,), F32)}}


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def segsum(a):
    """(..., T) -> (..., T, T): out[t, s] = sum_{r=s+1..t} a_r for t >= s,
    -inf above the diagonal."""
    T = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (T,))
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)),
                     jnp.cumsum(x, axis=-2), -jnp.inf)


def mixer(p, u, m: dict):
    di, ds, h, hd = _sizes(m)
    K, prec = m["ssm_conv"], _prec(u.dtype)
    b, S, _ = u.shape
    zxbcdt = jnp.einsum("bsd,de->bse", u, p["in_proj"], precision=prec)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * ds]
    dtr = zxbcdt[..., 2 * di + 2 * ds:]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + S] * p["conv_w"][i] for i in range(K))
                      + p["conv_b"])
    x = xbc[..., :di].reshape(b, S, h, hd)
    B, C = xbc[..., di:di + ds], xbc[..., di + ds:]
    dt = jax.nn.softplus(dtr + p["dt_bias"])                   # (b, S, h)
    decay = jnp.exp(segsum((dt * -jnp.exp(p["A_log"])).transpose(0, 2, 1)))
    G = jnp.einsum("btn,bsn->bts", C, B, precision=prec)
    y = jnp.einsum("bhts,bshp->bthp", G[:, None] * decay,
                   x * dt[..., None], precision=prec)
    y = (y + p["D"][:, None] * x).reshape(b, S, di)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_scale"], m["norm_eps"])
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"], precision=prec)


def loss(params, m: dict, tokens, targets, dtype=F32):
    """Mean next-token cross-entropy of ``tokens`` (rows, S)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = m["norm_eps"]

    def layer(x, lp):
        return x + mixer(lp["mixer"], rmsnorm(x, lp["norm1"]["scale"], eps),
                         m), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), p["embed"][tokens],
                        p["blocks"]["0"])
    x = rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = jnp.einsum("bsd,vd->bsv", x, p["embed"], precision=_prec(dtype))
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def matmul_params(m: dict) -> int:
    """in_proj and out_proj of every layer, and the tied head."""
    d = m["d_model"]
    di, ds, h, _ = _sizes(m)
    return (m["num_layers"] * (d * (2 * di + 2 * ds + h) + di * d)
            + m["vocab_size"] * d)


def attention_flops(m: dict, seq: int) -> float:
    return 0.0
