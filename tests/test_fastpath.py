"""The kernel-parity tier for the batched flat-buffer comm plane
(``repro.fastpath``): the batched plane vs the jnp oracle across dtypes,
ragged/empty leaf sizes, worker counts and LAQ bit widths; layout
round-trips; seed-repeat reduction determinism; which policies an active
plan serves (LAQ only); the lag-wk 50-step golden with the plane forced
on; and the forced-mode error paths.

Mirrors tests/test_comm.py's twin structure: the hypothesis property
tests at the bottom deepen coverage where the optional dep is installed
(CI installs it), and every property has a non-hypothesis twin above so
the tier runs green without hypothesis.  Interpret-mode Pallas
throughout — parity, not speed (CPU CI's regime).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fastpath
from repro.core import lag
from repro.fastpath import FastPathPlan, FlatLayout
from repro.kernels.lag_trigger import ref

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "lag_wk_50step.json")

# ragged leaf-size vocabulary: sub-lane, LANES−1, LANES+1, one exact
# block, an empty leaf — everything the padding path must absorb
RAGGED_SIZES = (1, fastpath.LANES - 1, fastpath.LANES + 1,
                fastpath.BLOCK, 0)


def make_tree(sizes, W=None, dtype=jnp.float32, seed=0, scale=1.0):
    """Stacked (W, …) or unstacked tree with one leaf per size."""
    keys = jax.random.split(jax.random.PRNGKey(seed), max(len(sizes), 1))
    lead = () if W is None else (W,)
    return {f"leaf{i}": scale * jax.random.normal(
                keys[i], lead + (s,), dtype)
            for i, s in enumerate(sizes)}


def worker_slice(tree, m):
    return jax.tree_util.tree_map(lambda l: l[m], tree)


def oracle_sqnorm(tree):
    """The jnp oracle: Σ_leaf ‖leaf‖² in f32 (empty leaves contribute 0)."""
    return sum((float(ref.sqnorm(l)) for l in
                jax.tree_util.tree_leaves(tree) if l.size), 0.0)


def oracle_laq(tree_g, tree_q, tree_e, bits):
    """Per-leaf ref LAQ encode (skipping empty leaves, which the per-leaf
    ref cannot reduce): (payload, resid, lhs)."""
    ps, es, tot = {}, {}, 0.0
    for k in tree_g:
        g, q, e = tree_g[k], tree_q[k], tree_e[k]
        if g.size == 0:
            ps[k] = jnp.zeros(g.shape, jnp.float32)
            es[k] = jnp.zeros(g.shape, jnp.float32)
            continue
        scale = ref.innovation_absmax(g, q, e)
        p, en, sq = ref.laq_encode(g, q, e, scale, bits)
        ps[k], es[k] = p, en
        tot += float(sq)
    return ps, es, tot


@pytest.fixture(scope="module")
def plan():
    return FastPathPlan("on")


# ---------------------------------------------------------------------------
# Layout: the static offset table round-trips exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layout_roundtrip_ragged(dtype):
    tree = make_tree(RAGGED_SIZES, dtype=dtype)
    lo = FlatLayout.for_tree(tree)
    # leaves pad to whole SUB-blocks (so none straddle), the buffer tail
    # to a whole kernel grid block
    nsubs = sum(-(-s // fastpath.SUB) for s in RAGGED_SIZES)
    assert lo.nsubs == nsubs
    assert lo.nblocks == -(-nsubs // fastpath.SUBS_PER_BLOCK)
    back = lo.unflatten(lo.flatten(tree), like=tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_layout_roundtrip_stacked_and_empty_tree():
    tree = make_tree((5, 300), W=4)
    lo = FlatLayout.for_tree(worker_slice(tree, 0))
    back = lo.unflatten_stacked(lo.flatten_stacked(tree), like=tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a tree with no elements at all still flattens/scatters
    empty = {"e": jnp.zeros((3, 0))}
    lo = FlatLayout.for_tree(worker_slice(empty, 0))
    assert lo.nblocks == 0 and lo.rows == 0
    assert lo.flatten_stacked(empty).shape == (3, 0, fastpath.LANES)


@pytest.mark.parametrize("W", [0, 1, 5])
def test_layout_stacked_roundtrip_leading_dims(W):
    """flatten_stacked/unflatten_stacked round-trip any leading dim —
    including the ZERO-size one (an empty cohort) — with zero-size
    leaves mixed in.  Twin of the hypothesis property below."""
    sizes = (3, 0, fastpath.LANES + 1)
    tree = make_tree(sizes, W=W, dtype=jnp.bfloat16, seed=11)
    lo = FlatLayout.for_tree(worker_slice(tree, 0) if W else
                             make_tree(sizes))
    buf = lo.flatten_stacked(tree)
    assert buf.shape == (W, lo.rows, fastpath.LANES)
    back = lo.unflatten_stacked(buf, like=tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("W", [0, 1, 4])
def test_layout_packed_roundtrip_and_cols(W):
    """The compact per-client view (the fleet population substrate):
    pack_stacked/unpack_stacked round-trips exactly, its row is per-leaf
    LANES-padded only (strictly smaller than the grid-padded row for
    ragged trees), and zero-lane leaves scatter back as zeros."""
    sizes = (1, 0, fastpath.LANES - 1, 300)
    tree = make_tree(sizes, W=W, seed=12)
    lo = FlatLayout.for_tree(worker_slice(tree, 0) if W else
                             make_tree(sizes))
    assert lo.packed_cols == sum(-(-s // fastpath.LANES) * fastpath.LANES
                                 for s in sizes)
    assert lo.packed_cols < lo.rows * fastpath.LANES    # no grid tail
    packed = lo.pack_stacked(tree)
    assert packed.shape == (W, lo.packed_cols)
    back = lo.unpack_stacked(packed, like=tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # an all-empty template packs to (W, 0) and unpacks to zeros
    empty = {"e": jnp.zeros((W, 0))}
    le = FlatLayout.for_tree({"e": jnp.zeros((0,))})
    assert le.packed_cols == 0
    assert le.pack_stacked(empty).shape == (W, 0)
    assert le.unpack_stacked(le.pack_stacked(empty))["e"].shape == (W, 0)
    with pytest.raises(ValueError, match="leaves"):
        lo.pack_stacked({"only": jnp.zeros((W, 3))})


def test_layout_pad_region_is_zero():
    tree = {"x": jnp.ones((7,))}
    lo = FlatLayout.for_tree(tree)
    buf = np.asarray(lo.flatten(tree))
    assert buf.shape == (fastpath.BLOCK_ROWS, fastpath.LANES)
    assert buf.sum() == 7.0            # padding is absorbing


# ---------------------------------------------------------------------------
# Batched sqnorms vs the oracle (non-hypothesis twins)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [1, 3, 9])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_delta_sqnorm_matches_oracle(plan, W, dtype):
    a = make_tree(RAGGED_SIZES, W=W, dtype=dtype, seed=1)
    b = make_tree(RAGGED_SIZES, W=W, dtype=dtype, seed=2)
    got = np.asarray(plan.delta_sqnorm(a, b))
    want = [oracle_sqnorm(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
        worker_slice(a, m), worker_slice(b, m))) for m in range(W)]
    # f32 tolerance: the plane reduces per (worker, leaf-offset) block
    # partials in fixed order; the oracle reduces per leaf — same values,
    # different f32 summation grouping
    np.testing.assert_allclose(got, want, rtol=2e-5)


@pytest.mark.parametrize("W", [1, 4])
def test_batched_sqnorm_and_broadcast_operand(plan, W):
    a = make_tree((130, 31), W=W, seed=3)
    got = np.asarray(plan.sqnorm(a))
    want = [oracle_sqnorm(worker_slice(a, m)) for m in range(W)]
    np.testing.assert_allclose(got, want, rtol=2e-5)
    # unstacked second operand (the shared θ under a per-worker θ̂ sweep)
    theta = make_tree((130, 31), seed=4)
    got = np.asarray(plan.delta_sqnorm(a, theta, b_stacked=False))
    want = [oracle_sqnorm(jax.tree_util.tree_map(
        lambda x, y: x - y, worker_slice(a, m), theta)) for m in range(W)]
    np.testing.assert_allclose(got, want, rtol=2e-5)


# ---------------------------------------------------------------------------
# Batched LAQ encode vs the per-leaf oracle (non-hypothesis twins)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("W", [1, 3])
def test_batched_laq_encode_matches_per_leaf_oracle(plan, bits, W):
    g = make_tree(RAGGED_SIZES, W=W, seed=5)
    q = jax.tree_util.tree_map(lambda x: 0.25 * x, g)
    e = jax.tree_util.tree_map(
        lambda x: 0.01 * jnp.ones(x.shape, jnp.float32), g)
    p_st, r_st, lhs = plan.laq_encode(g, q, e, bits=bits)
    for m in range(W):
        p_w, r_w, tot = oracle_laq(worker_slice(g, m), worker_slice(q, m),
                                   worker_slice(e, m), bits)
        for k in g:
            np.testing.assert_allclose(np.asarray(p_st[k][m]),
                                       np.asarray(p_w[k]),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(r_st[k][m]),
                                       np.asarray(r_w[k]),
                                       rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(lhs[m]), tot, rtol=1e-4,
                                   atol=1e-6)


def test_batched_laq_scales_are_per_leaf(plan):
    """Batching must NOT widen the quantizer grid to the whole buffer:
    a small-magnitude leaf keeps its own fine grid next to a huge one."""
    g = {"big": 1000.0 * jnp.ones((2, 64)), "small": 0.001 * jnp.ones((2, 64))}
    z = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), g)
    p, _, _ = plan.laq_encode(g, z, z, bits=4)
    # with a shared scale the small leaf would quantize to 0; per-leaf
    # scales reproduce it exactly (it sits on its own grid's max point)
    np.testing.assert_allclose(np.asarray(p["small"]), 0.001, rtol=1e-5)


def test_batched_laq_zero_innovation(plan):
    z = {"a": jnp.zeros((2, 200))}
    p, r, lhs = plan.laq_encode(z, z, z, bits=4)
    assert float(jnp.max(jnp.abs(p["a"]))) == 0.0
    assert np.asarray(lhs).tolist() == [0.0, 0.0]


# ---------------------------------------------------------------------------
# Masked lazy updates (the batched state fold)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_combine_modes_match_oracle(plan, dtype):
    # candidate and state share a dtype, as in every real fold (θ/θ̂,
    # resid_new/resid, payload/mirror after the encode cast)
    W = 3
    a = make_tree((129, 5), W=W, dtype=dtype, seed=6)
    b = make_tree((129, 5), W=W, dtype=dtype, seed=7)
    mask = jnp.asarray([1.0, 0.0, 1.0])
    sel = plan.masked_select(a, b, mask)
    upd = plan.masked_update(a, b, mask)
    add = plan.masked_add(a, b, mask)
    for m, on in enumerate([True, False, True]):
        for k in a:
            am = np.asarray(a[k][m], np.float32)
            bm = np.asarray(b[k][m], np.float32)
            # select is an EXACT copy (θ̂ ← θ / residual advance)
            np.testing.assert_array_equal(
                np.asarray(sel[k][m], np.float32), am if on else bm)
            # f32 state: bitwise the per-worker fold; bf16 state rounds
            # once from f32 (≤1 ulp) — the documented plane tolerance
            tol = 0 if dtype == jnp.float32 else 1e-2
            np.testing.assert_allclose(
                np.asarray(upd[k][m], np.float32),
                (bm + (am - bm)).astype(np.float32) if on else bm, rtol=tol)
            np.testing.assert_allclose(
                np.asarray(add[k][m], np.float32),
                (bm + am) if on else bm, rtol=tol)
    for k in a:
        assert sel[k].dtype == b[k].dtype == upd[k].dtype == add[k].dtype


def test_masked_combine_bad_mode_raises():
    from repro.fastpath import kernels
    with pytest.raises(ValueError, match="mode must be one of"):
        kernels.masked_combine(jnp.zeros((1, 256, 128)),
                               jnp.zeros((1, 256, 128)),
                               jnp.ones((1,)), "xor")


# ---------------------------------------------------------------------------
# Determinism: the reduction order is a static function of the layout
# (the fused_tree_sqnorm loop-order quirk, fixed for the batched plane)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_seed_repeat_reduction_determinism(seed):
    """Same inputs ⇒ bit-identical per-worker reductions across fresh
    plans, fresh jits and repeated calls — per (worker, leaf-offset)
    partials in fixed block order, leaves in pytree order."""
    a = make_tree((300, 7, 129), W=4, seed=seed)
    b = make_tree((300, 7, 129), W=4, seed=seed + 100)

    def compute():
        plan = FastPathPlan("on")          # fresh layout cache each time
        f = jax.jit(lambda x, y: (plan.delta_sqnorm(x, y), plan.sqnorm(x)))
        d, s = f(a, b)
        return np.asarray(d), np.asarray(s)

    d1, s1 = compute()
    d2, s2 = compute()
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(s1, s2)
    d3 = np.asarray(FastPathPlan("on").delta_sqnorm(a, b))
    np.testing.assert_array_equal(d1, d3)


def test_laq_encode_determinism():
    g = make_tree((500, 33), W=3, seed=9)
    z = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), g)
    runs = [np.asarray(FastPathPlan("on").laq_encode(g, z, z, bits=4)[2])
            for _ in range(2)]
    np.testing.assert_array_equal(runs[0], runs[1])


# ---------------------------------------------------------------------------
# Plan resolution, activation and error paths
# ---------------------------------------------------------------------------

def test_make_plan_modes():
    assert fastpath.make_plan(None) is None
    assert fastpath.make_plan("off") is None
    p = fastpath.make_plan("on")
    assert p.enabled and p.forced and fastpath.make_plan(p) is p
    auto = fastpath.make_plan("auto")
    assert not auto.forced
    # on this CPU container: auto stays dormant, interpret mode is on
    from repro.kernels import on_tpu
    if not on_tpu():
        assert not auto.enabled and p.interpret
    with pytest.raises(ValueError, match="fastpath mode"):
        fastpath.make_plan("maybe")


def test_below_dispatch_floor_unit():
    """The auto-mode small-shape floor (SMALL_DISPATCH_ROWS): static,
    shape-only, and never applied to forced plans."""
    auto = FastPathPlan("auto")
    forced = FastPathPlan("on")
    tiny = make_tree((50,), W=1)        # 1 grid block = 256 rows × 1
    assert auto.below_dispatch_floor(tiny)
    assert not forced.below_dispatch_floor(tiny)      # parity tier exempt
    assert auto.below_dispatch_floor({})              # empty tree
    assert auto.below_dispatch_floor(make_tree((50,), W=3))     # 768
    assert not auto.below_dispatch_floor(make_tree((50,), W=4))  # 1024
    assert not auto.below_dispatch_floor(
        make_tree((4 * fastpath.BLOCK,), W=1))        # 1024 rows × 1


def test_small_shape_dispatch_choice(monkeypatch):
    """The convex-d50 M=1 regression fix: under an ACTIVE auto plan,
    ``policy_rounds`` must route sub-floor stacked trees straight to the
    jnp oracle — ``fast_precompute`` is never consulted — while at-floor
    trees ask the policy for its plane route."""
    from repro import comm
    from repro.engine import rounds
    from repro.fastpath import plan as plan_mod

    policy = comm.make_policy("lag-wk", fastpath="auto")
    monkeypatch.setattr(plan_mod, "on_tpu", lambda: True)   # activate auto
    assert fastpath.active_plan(policy) is not None
    calls = []

    def spy(self, plan, grads, st, **kw):
        calls.append(jax.tree_util.tree_leaves(grads)[0].shape[0])
        return None          # observe routing only; oracle math either way

    monkeypatch.setattr(type(policy), "fast_precompute", spy)
    params = {"w": jnp.zeros((50,))}

    def run(W):
        cfg = lag.LAGConfig(num_workers=W, alpha=0.1, D=2, xi=0.1)
        grads = {"w": jnp.ones((W, 50))}
        st = {"grad_hat": {"w": jnp.zeros((W, 50))},
              "hist": lag.hist_init(2)}
        rounds.policy_rounds(policy, cfg, params, grads, st)

    run(1)                   # 256 rows × 1 worker < 1024: oracle outright
    assert calls == []
    run(4)                   # 256 × 4 = 1024: the plane serves it
    assert calls == [4]


#: the plan's pytree-level ops a round can reach
PLANE_OPS = ("sqnorm", "delta_sqnorm", "masked_add", "masked_select",
             "laq_encode_flat")


@pytest.mark.parametrize("spec", ["lag-wk", "lag-ps", "lasg-wk", "laq@4"])
def test_active_auto_plan_serves_laq_only(monkeypatch, spec):
    """Under an ACTIVE auto plan at a size above the dispatch floor, the
    dense policies run their round as the jnp oracle (per-leaf XLA
    fusions: no plane op, so no flatten, launch or scatter) and give
    exactly what ``fastpath="off"`` gives; LAQ still takes the plane's
    encode and makes the same upload decisions."""
    from repro import comm
    from repro.engine import rounds
    from repro.fastpath import plan as plan_mod

    W = 4
    monkeypatch.setattr(plan_mod, "on_tpu", lambda: True)   # activate auto
    # ...while LAQ's kernels still run through the Pallas interpreter here
    monkeypatch.setattr(FastPathPlan, "interpret",
                        property(lambda self: True))
    calls = []
    for op in PLANE_OPS:
        def spy(self, *a, _op=op, _orig=getattr(FastPathPlan, op), **kw):
            calls.append(_op)
            return _orig(self, *a, **kw)
        monkeypatch.setattr(FastPathPlan, op, spy)

    sizes = (50, 7)
    params = make_tree(sizes, seed=1)
    grads = make_tree(sizes, W=W, seed=2)
    # per-worker innovations from none to large: against RHS = 1 the
    # first two workers skip and the last two upload
    scale = jnp.asarray([0.0, 0.03, 0.3, 3.0])
    step = jax.tree_util.tree_map(lambda n: scale[:, None] * n,
                                  make_tree(sizes, W=W, seed=3))
    theta_hat = jax.tree_util.tree_map(lambda p, s: p + s, params, step)
    ghat = lag.tree_sub(grads, step)
    cfg = lag.LAGConfig(num_workers=W, alpha=0.1, D=2, xi=0.1)

    def run(fp):
        policy = comm.make_policy(spec, fastpath=fp)
        st = policy.init_state(ghat, theta0=theta_hat)
        st.update(hist=jnp.full((2,), 0.8, jnp.float32),
                  L_m=jnp.ones((W,), jnp.float32))
        gah = ghat if policy.needs_grad_at_hat else None
        return policy, rounds.policy_rounds(policy, cfg, params, grads, st,
                                            gah)

    _, (comm_off, delta_off, st_off) = run("off")
    assert calls == []
    policy, (comm_on, delta_on, st_on) = run("auto")
    assert fastpath.active_plan(policy) is not None
    assert not policy.fastpath.below_dispatch_floor(grads)
    np.testing.assert_array_equal(np.asarray(comm_on), [0, 0, 1, 1])
    np.testing.assert_array_equal(np.asarray(comm_on), np.asarray(comm_off))
    if spec.startswith("laq"):
        assert "laq_encode_flat" in calls
        return
    assert calls == []
    for a, b in zip(jax.tree_util.tree_leaves((delta_on, st_on)),
                    jax.tree_util.tree_leaves((delta_off, st_off))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_small_shape_parity_convex_d50():
    """The regression shape itself (d = 50, M = 1): floor-dispatched
    oracle vs the forced plane — identical upload decisions, close
    losses, so the dispatch switch is invisible to trajectories."""
    from repro.core import convex, simulate
    prob = convex.synthetic("linreg", num_workers=1, n_per=12, d=50, seed=3)
    for algo in ("lag-wk", "laq@4"):
        r0 = simulate.run(prob, algo, K=20)
        r1 = simulate.run(prob, algo, K=20, fastpath="on")
        np.testing.assert_array_equal(np.asarray(r0.comm_mask),
                                      np.asarray(r1.comm_mask))
        np.testing.assert_allclose(r0.losses, r1.losses, rtol=1e-5)


def test_policy_resolves_plan_once():
    from repro import comm
    pol = comm.make_policy("lag-wk", fastpath="on")
    assert isinstance(pol.fastpath, FastPathPlan) and pol.fastpath.forced
    assert comm.make_policy("lag-wk", fastpath="off").fastpath is None
    # scheduled wrappers mirror the inner policy's resolved plan
    sched = comm.make_policy("cyc-laq@3", fastpath="on")
    assert sched.fastpath is sched.inner.fastpath


def test_use_pallas_selects_legacy_route_over_auto_plane():
    """use_pallas=True SELECTS the per-leaf route: an 'auto' plane is
    disabled on every backend (it would shadow the selection on TPU
    only), and forcing both raises."""
    from repro import comm
    from repro.dist import TrainerConfig
    assert comm.make_policy("laq", use_pallas=True).fastpath is None
    assert comm.make_policy("laq", use_pallas=True,
                            fastpath="auto").fastpath is None
    with pytest.raises(ValueError, match="conflicting comm-plane"):
        comm.make_policy("laq", use_pallas=True, fastpath="on")
    with pytest.raises(ValueError, match="conflicting comm-plane"):
        TrainerConfig(algo="lag-wk", use_pallas_comm=True, fastpath="on")


def test_forced_plan_rejects_unsupported_dtypes():
    """The f32 plane refuses int/f64 trees under fastpath='on' with an
    actionable message (auto mode falls back silently)."""
    from repro import comm
    from repro.engine import rounds
    policy = comm.make_policy("lag-wk", fastpath="on")
    cfg = lag.LAGConfig(num_workers=2, alpha=0.1, D=2, xi=0.1)
    grads = {"w": jnp.zeros((2, 8), jnp.int32)}
    lag_state = {"grad_hat": {"w": jnp.zeros((2, 8), jnp.int32)},
                 "hist": lag.hist_init(2)}
    with pytest.raises(ValueError, match="float32 comm plane"):
        rounds.policy_rounds(policy, cfg, {"w": jnp.zeros((8,))}, grads,
                             lag_state)


def test_new_policy_without_fast_route_trips(plan):
    """The tripwire: a policy that neither serves its reductions from the
    plane nor explicitly opts out fails LOUDLY when the plane is forced."""
    from repro import comm
    from repro.engine import rounds

    class SneakyPolicy(comm.CommPolicy):
        name = "sneaky"

        def should_upload(self, ctx, st, payload, aux):
            return jnp.ones((), bool)

    policy = SneakyPolicy(fastpath="on")
    cfg = lag.LAGConfig(num_workers=2, alpha=0.1, D=2, xi=0.1)
    grads = {"w": jnp.ones((2, 8))}
    lag_state = {"grad_hat": {"w": jnp.zeros((2, 8))},
                 "hist": lag.hist_init(2)}
    with pytest.raises(NotImplementedError, match="fast-path route"):
        rounds.policy_rounds(policy, cfg, {"w": jnp.zeros((8,))}, grads,
                             lag_state)


# ---------------------------------------------------------------------------
# End-to-end: the golden trajectory with the plane forced on
# ---------------------------------------------------------------------------

def test_lag_wk_golden_upload_decisions_with_fastpath_on():
    """tests/golden/lag_wk_50step.json with the plane forced on:
    per-round and per-worker upload decisions BIT-identical to the
    recorded oracle trajectory (acceptance criterion).  lag-wk opts out
    of the plane (its trigger and ĝ fold are per-leaf XLA fusions), so a
    forced plan must leave its trajectory the oracle's.  Losses are
    allclose at rtol=1e-4."""
    from repro.engine import Experiment
    gold = json.load(open(GOLDEN))
    r = Experiment(model="llama3.2-1b", algo="lag-wk", steps=50,
                   workers=4, lr=0.05, batch=8, seq=64,
                   fastpath="on").run()
    assert r.comms_per_iter.tolist() == gold["comm_this_round"]
    assert r.uploads_per_worker.tolist() == gold["comm_per_worker"]
    assert r.total_comms == gold["comm_total"]
    np.testing.assert_allclose(r.losses, gold["losses"], rtol=1e-4)


def test_convex_fastpath_decision_parity():
    """One convex sweep, forced plan vs oracle: identical upload masks for
    a trigger policy (which opts out of the plane) AND a quantized one
    (which the plane serves)."""
    from repro.core import convex, simulate
    prob = convex.synthetic("linreg", num_workers=5, n_per=12, d=9, seed=2)
    for algo in ("lag-wk", "laq@3"):
        r0 = simulate.run(prob, algo, K=30)
        r1 = simulate.run(prob, algo, K=30, fastpath="on")
        np.testing.assert_array_equal(np.asarray(r0.comm_mask),
                                      np.asarray(r1.comm_mask))
        np.testing.assert_allclose(r0.losses, r1.losses, rtol=1e-5)


# ---------------------------------------------------------------------------
# Hypothesis deepening (optional dep; every property has a twin above)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
    settings.register_profile("fastpath", max_examples=15, deadline=None)
    settings.load_profile("fastpath")
except ImportError:       # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    leaf_sizes = st.lists(
        st.sampled_from([0, 1, 2, fastpath.LANES - 1, fastpath.LANES,
                         fastpath.LANES + 1, 1000]),
        min_size=1, max_size=5)
    dtypes = st.sampled_from([jnp.float32, jnp.bfloat16])
    workers = st.integers(1, 6)

    @given(leaf_sizes, dtypes, workers, st.integers(0, 1000))
    def test_property_delta_sqnorm_parity(sizes, dtype, W, seed):
        plan = FastPathPlan("on")
        a = make_tree(tuple(sizes), W=W, dtype=dtype, seed=seed)
        b = make_tree(tuple(sizes), W=W, dtype=dtype, seed=seed + 1)
        got = np.asarray(plan.delta_sqnorm(a, b))
        want = [oracle_sqnorm(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            worker_slice(a, m), worker_slice(b, m))) for m in range(W)]
        np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-6)

    @given(leaf_sizes, workers, st.sampled_from([2, 4, 8]),
           st.integers(0, 1000))
    def test_property_laq_encode_parity(sizes, W, bits, seed):
        plan = FastPathPlan("on")
        g = make_tree(tuple(sizes), W=W, seed=seed)
        q = jax.tree_util.tree_map(lambda x: 0.5 * x, g)
        e = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), g)
        p_st, r_st, lhs = plan.laq_encode(g, q, e, bits=bits)
        for m in range(W):
            p_w, r_w, tot = oracle_laq(
                worker_slice(g, m), worker_slice(q, m),
                worker_slice(e, m), bits)
            for k in g:
                np.testing.assert_allclose(np.asarray(p_st[k][m]),
                                           np.asarray(p_w[k]),
                                           rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(lhs[m]), tot,
                                       rtol=1e-4, atol=1e-6)

    lead_dims = st.integers(0, 6)          # leading dims INCLUDING zero

    @given(leaf_sizes, dtypes, lead_dims, st.integers(0, 1000))
    def test_property_stacked_roundtrip(sizes, dtype, W, seed):
        tree = make_tree(tuple(sizes), W=W, dtype=dtype, seed=seed)
        lo = FlatLayout.for_tree(make_tree(tuple(sizes), dtype=dtype))
        back = lo.unflatten_stacked(lo.flatten_stacked(tree), like=tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    @given(leaf_sizes, lead_dims, st.integers(0, 1000))
    def test_property_packed_roundtrip(sizes, W, seed):
        tree = make_tree(tuple(sizes), W=W, seed=seed)
        lo = FlatLayout.for_tree(make_tree(tuple(sizes)))
        packed = lo.pack_stacked(tree)
        assert packed.shape == (W, lo.packed_cols)
        back = lo.unpack_stacked(packed, like=tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @given(leaf_sizes, dtypes, workers, st.integers(0, 1000))
    def test_property_masked_select_exact(sizes, dtype, W, seed):
        plan = FastPathPlan("on")
        a = make_tree(tuple(sizes), W=W, dtype=dtype, seed=seed)
        b = make_tree(tuple(sizes), W=W, dtype=dtype, seed=seed + 1)
        mask = jnp.asarray(np.arange(W) % 2, jnp.float32)
        out = plan.masked_select(a, b, mask)
        for m in range(W):
            src = b if m % 2 == 0 else a
            for k in a:
                np.testing.assert_array_equal(
                    np.asarray(out[k][m], np.float32),
                    np.asarray(src[k][m], np.float32))
