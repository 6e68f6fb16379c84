"""Plain reference of LAG's first training steps (Chen et al., NIPS 2018).

W workers each take their shard of the global batch (rows m*B/W to
(m+1)*B/W).  In round k worker m forms its gradient g_m = grad L_m(theta^k)
and its innovation delta_m = g_m - ghat_m, and uploads iff

    lag-wk (15a):  ||delta_m||^2 > xi/(alpha^2 W^2) * sum_{d<=D} ||theta^{k+1-d} - theta^{k-d}||^2,

and then ghat_m <- ghat_m + delta_m.  The server keeps nabla = sum_m ghat_m
and steps theta <- theta - alpha * nabla with alpha = lr / W (eq. 4).  All
state starts at zero, so every worker uploads in round 0.

``bits`` > 0 makes it LAQ (Sun et al., NeurIPS 2019): the upload is the
b-bit quantization p_m = Q(v_m) of v_m = (g_m - ghat_m) + e_m, one grid per
leaf with step max|v_leaf| / (2^(b-1) - 1), the trigger norms p_m, and on
upload ghat_m <- ghat_m + p_m and the residual e_m <- v_m - p_m.

``fault`` plants one of the faults a benchmark cell can have in this
reference put in the program's place: ``half`` leaves out half of each
worker's rows and takes the mean over the rest (half of the workers'
rows where each has one);
``alone`` leaves out the exchange between chips, so the server steps with
worker 0's upload only, as chip 0 would.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("half", "alone")


def _sq(tree) -> jnp.ndarray:
    return sum(jnp.sum(jnp.square(l.astype(F32)))
               for l in jax.tree_util.tree_leaves(tree))


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    """{path: float32 norm} of every leaf."""
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
            for p, l in jax.tree_util.tree_leaves_with_path(tree)}


def quantize(v, bits: int):
    """Per-leaf symmetric uniform b-bit grid: (dequantized codes, v - them)."""
    qmax = float(2 ** (bits - 1) - 1)

    def one(x):
        step = jnp.max(jnp.abs(x)) / qmax
        safe = jnp.where(step > 0, step, 1)
        p = jnp.where(step > 0,
                      jnp.clip(jnp.round(x / safe), -qmax, qmax) * step, 0)
        return p.astype(x.dtype)
    p = jax.tree_util.tree_map(one, v)
    return p, jax.tree_util.tree_map(lambda a, b: a - b, v, p)


def worker_round(loss: Callable, dtype, bits: int):
    """Worker m's part of a round: ``(theta, ghat_m, resid_m, tokens,
    targets, rhs) -> (ghat_m, resid_m, upload or zeros, loss, up, lhs)``."""
    tree = jax.tree_util.tree_map

    def one(theta, ghat, resid, tokens, targets, rhs):
        l, g = jax.value_and_grad(loss)(theta, tokens, targets, dtype)
        delta, r = tree(lambda a, b: a - b, g, ghat), resid
        if bits:
            delta, r = quantize(tree(lambda a, b: a + b, delta, resid), bits)
        lhs = _sq(delta)
        up = lhs > rhs
        sent = tree(lambda d: jnp.where(up, d, jnp.zeros_like(d)), delta)
        return (tree(lambda h, d: h + d, ghat, sent),
                tree(lambda new, old: jnp.where(up, new, old), r, resid),
                sent, l.astype(F32), up, lhs)
    return one


def server_round(alpha: float):
    """``(theta, nabla, hist, sum of uploads) -> (theta, nabla, hist)``:
    nabla += the uploads, theta -= alpha * nabla, and the step's squared
    norm pushed onto the iterate-lag history."""
    tree = jax.tree_util.tree_map

    def one(theta, nabla, hist, sent):
        nabla = tree(lambda a, b: a + b, nabla, sent)
        new = tree(lambda t, g: (t - alpha * g).astype(t.dtype), theta, nabla)
        moved = _sq(tree(lambda a, b: a - b, new, theta))
        return new, nabla, jnp.concatenate([moved[None], hist[:-1]])
    return one


def run(init: Callable, key, loss: Callable, batches: List, *, workers: int,
        lr: float, xi: float, D: int, steps: int, dtype=F32,
        fault: Optional[str] = None, bits: int = 0,
        devices: Optional[List] = None) -> Dict:
    """The readings of ``steps`` rounds from the weights ``init(key)`` makes:
    each step's loss and upload mask, the trigger's lhs/rhs, the norm of
    every leaf of nabla after one step (the first gradient as the server
    gets it), and of every leaf's change after ``steps``.

    Worker m's state lives on ``devices[m % len(devices)]`` (default: the
    first device) and its part of a round runs there; the server's part
    runs on the first device."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    devices = devices or jax.devices()[:1]
    home = devices[0]
    at = lambda m: devices[m % len(devices)]
    alpha = lr / workers
    work = jax.jit(worker_round(loss, dtype, bits), donate_argnums=(1, 2))
    serve = jax.jit(server_round(alpha), donate_argnums=(0, 1, 2, 3))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    shapes = jax.eval_shape(init, key)

    def zeros(dev):
        return jax.jit(lambda: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, dtype), shapes), device=dev)()

    theta = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda a: a.astype(dtype), init(k)), device=home)(key)
    ghat = [zeros(at(m)) for m in range(workers)]
    resid = [zeros(at(m)) if bits else None for m in range(workers)]
    nabla, hist = zeros(home), jax.device_put(jnp.zeros((D,), F32), home)
    losses, masks, ratios, grad = [], [], [], None
    for k in range(steps):
        tokens, targets = batches[k]
        per = tokens.shape[0] // workers
        rows = [slice(m * per, (m + 1) * per) for m in range(workers)]
        if fault == "half":
            rows = ([slice(m * per, m * per + per // 2) for m in range(workers)]
                    if per > 1 else rows[:max(workers // 2, 1)])
        rhs = float(xi * np.sum(np.asarray(hist), dtype=np.float32)
                    / np.float32(alpha ** 2 * workers ** 2))
        sent_sum, step_loss, mk, lhs = zeros(home), [], [], []
        for m in range(workers):
            if m >= len(rows):              # a worker left out entirely
                mk.append(False)
                lhs.append(0.0)
                continue
            th = jax.device_put(theta, at(m))
            ghat[m], resid[m], sent, l, up, q = work(
                th, ghat[m], resid[m], tokens[rows[m]], targets[rows[m]],
                np.float32(rhs))
            del th
            if fault != "alone" or m == 0:
                sent_sum = add(sent_sum, jax.device_put(sent, home))
            del sent
            step_loss.append(float(l))
            mk.append(bool(up))
            lhs.append(float(q))
        theta, nabla, hist = serve(theta, nabla, hist, sent_sum)
        del sent_sum
        losses.append(float(np.mean(np.asarray(step_loss, np.float32))))
        masks.append(mk)
        ratios.append([x / rhs if rhs > 0 else float("inf") for x in lhs])
        if k == 0:
            grad = {p: float(v) for p, v in
                    jax.jit(leaf_norms)(nabla).items()}
    del ghat, resid, nabla
    moved = jax.jit(lambda t, k: leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(F32) - b.astype(dtype).astype(F32), t,
        init(k))))(theta, key)
    return {"loss": losses, "mask": masks, "lhs_over_rhs": ratios,
            "grad": grad, "dtheta": {p: float(v) for p, v in moved.items()}}
