"""Plain reference of a pre-norm decoder with grouped-query attention,
rotary positions and a SwiGLU feed-forward (Llama / Granite code models).

Straightforward ``jax.numpy``: the whole (S x S) causal score matrix, no
kernels, no chunking.  In float32 every matmul runs at
``Precision.HIGHEST``; in any other dtype every array, parameters
included, is held in that dtype (the control).  The parameter tree is laid
out as the program's, layers stacked on a leading axis.

Departures from the published models, as the program runs them: RMS norms
use eps 1e-6 (Granite publishes 1e-5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _prec(dtype):
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


def init(key, m: dict) -> dict:
    """Weights from ``key``: every matrix N(0, 0.02), norm scales 1."""
    L, d, V = m["num_layers"], m["d_model"], m["vocab_size"]
    H, KV, hd, ff = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_ff"]
    ks = jax.random.split(key, 9)
    n = lambda k, shape: 0.02 * jax.random.normal(k, shape, F32)
    return {
        "embed": n(ks[0], (V, d)),
        "blocks": {"0": {
            "norm1": {"scale": jnp.ones((L, d), F32)},
            "attn": {"wq": n(ks[1], (L, d, H, hd)),
                     "wk": n(ks[2], (L, d, KV, hd)),
                     "wv": n(ks[3], (L, d, KV, hd)),
                     "wo": n(ks[4], (L, H, hd, d))},
            "norm2": {"scale": jnp.ones((L, d), F32)},
            "mlp": {"w_gate": n(ks[5], (L, d, ff)),
                    "w_up": n(ks[6], (L, d, ff)),
                    "w_down": n(ks[7], (L, ff, d))},
        }},
        "tail": [],
        "final_norm": {"scale": jnp.ones((d,), F32)},
        "head": n(ks[8], (d, V)),
    }


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rotate(x, theta):
    """Rotary embedding, half-split pairs, positions 0..S-1; x (b,S,h,k)."""
    S, half = x.shape[1], x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    c = jnp.cos(ang)[None, :, None].astype(x.dtype)
    s = jnp.sin(ang)[None, :, None].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(p, u, m: dict):
    prec = _prec(u.dtype)
    q = rotate(jnp.einsum("bsd,dhk->bshk", u, p["wq"], precision=prec),
               m["rope_theta"])
    k = rotate(jnp.einsum("bsd,dhk->bshk", u, p["wk"], precision=prec),
               m["rope_theta"])
    v = jnp.einsum("bsd,dhk->bshk", u, p["wv"], precision=prec)
    rep = m["num_heads"] // m["num_kv_heads"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    S = u.shape[1]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=prec) \
        * m["head_dim"] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v,
                   precision=prec)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"], precision=prec)


def mlp(p, u):
    prec = _prec(u.dtype)
    gate = jnp.einsum("bsd,df->bsf", u, p["w_gate"], precision=prec)
    up = jnp.einsum("bsd,df->bsf", u, p["w_up"], precision=prec)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"],
                      precision=prec)


def loss(params, m: dict, tokens, targets, dtype=F32):
    """Mean next-token cross-entropy of ``tokens`` (rows, S)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = m["norm_eps"]

    def layer(x, lp):
        x = x + attention(lp["attn"], rmsnorm(x, lp["norm1"]["scale"], eps), m)
        return x + mlp(lp["mlp"], rmsnorm(x, lp["norm2"]["scale"], eps)), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), p["embed"][tokens],
                        p["blocks"]["0"])
    x = rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = jnp.einsum("bsd,dv->bsv", x, p["head"], precision=_prec(dtype))
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def matmul_params(m: dict) -> int:
    """q, k, v, o and the three feed-forward matrices of every layer, and
    the head (the embedding lookup is not a matmul)."""
    d, H, KV, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    per_layer = d * hd * (2 * H + 2 * KV) + 3 * d * m["d_ff"]
    return m["num_layers"] * per_layer + d * m["vocab_size"]


def attention_flops(m: dict, seq: int) -> float:
    """Causal scores and values, forward and backward: per layer and token
    3 x 2 x 2 x (heads x head_dim) x the mean context (seq + 1) / 2."""
    return 6.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] * (seq + 1)
