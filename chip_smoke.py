#!/usr/bin/env python3
"""Chip smoke: the LAG trainer's main path on a TPU at published widths.

    python3 chip_smoke.py              # one chip (the default)
    python3 chip_smoke.py --chips 4    # the devices:4 phase on a 4-chip host

One chip: ``repro.launch.train.main`` trains mamba2-370m (48 layers, d_model
1024, vocab 50280; random weights from ``--seed``) for a few lag-wk steps with
W = 2 lazy workers under ``--fastpath auto``, in this process.  It checks that
the auto route takes the Pallas comm plane (``tpu_custom_call`` in the compiled
step), that every loss is finite, and then runs the plane's kernels on the
run's own real-size flat buffers against the jnp oracle
(``repro.kernels.lag_trigger.ref``).

Four chips: the same model under ``--topology devices:4`` (one lazy worker per
chip, the Pallas plane on each, packed wire arrays in a ``lax.cond``-gated
all-gather) for lag-wk and laq@4, each against ``shards`` with ``--workers 4``
on the same host mesh; laq@4 also against ``devices:4`` with the jnp oracle
(``--fastpath off``), which isolates the plane from the topology.  Each pair
makes equal upload decisions, and its losses agree within rtol 1e-4.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU,
or when any check fails, the script exits non-zero and does not print it.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402

ARCH = "mamba2-370m"
STEPS = 5
BATCH, SEQ = 4, 1024
LAQ_BITS = 4
#: per-sub-block partials sum 1024 squares: two f32 summation orders
PARTIAL_RTOL = 1e-5
#: whole-buffer totals sum 3.7e8 squares in different orders
TOTAL_RTOL = 1e-4
#: devices:4 against shards, and the plane against the oracle
#: (tests/test_devrun.py's documented tolerance), with f32 matmuls at full
#: precision: at TPU's default precision an f32 matmul is one bf16 pass,
#: and the two partitionings of the same backward then drift apart beyond
#: it (1.09e-4 in 5 lag-wk steps on a v5e 2x2, upload decisions still
#: equal)
DEVICES_LOSS_RTOL = 1e-4


def check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4])
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}: {devs})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    warm = os.path.isdir(cache_dir) and len(os.listdir(cache_dir))
    print(f"device: {devs[0].device_kind} x{len(devs)} | compile cache "
          f"{cache_dir} ({warm or 0} entries at start)")
    if args.chips == 4:
        four_chips(devs, args.seed)
    else:
        one_chip(devs, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def one_chip(devs, seed: int) -> None:
    from repro.configs import get_config
    from repro.fastpath import FastPathPlan
    from repro.launch import train
    from repro.models import model

    W = 2
    cfg = get_config(ARCH)
    shapes = jax.eval_shape(lambda k: model.init(k, cfg),
                            jax.random.PRNGKey(seed))
    stacked = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((W,) + l.shape, l.dtype), shapes)
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(shapes))
    plan = FastPathPlan("auto")
    lo = plan.layout_for(stacked)
    print(f"model: {ARCH} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} params={n_params} | plane buffer "
          f"{lo.rows} rows x 128 per worker, W={W}")
    # the auto route's two oracle cases must not apply at this size
    check(plan.enabled and not plan.interpret,
          "fastpath auto is not compiled-Pallas on this TPU")
    check(plan.supports(stacked), "param dtypes the f32 plane cannot serve")
    check(not plan.below_dispatch_floor(stacked),
          "the tree is below the plane's dispatch floor")

    counter_probe(devs[0])
    run = train.main(["--arch", ARCH, "--algo", "lag-wk",
                      "--workers", str(W), "--batch", str(BATCH),
                      "--seq", str(SEQ), "--steps", str(STEPS),
                      "--seed", str(seed)])
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    compiled = run.compiled()
    n_custom = compiled.as_text().count("tpu_custom_call")
    print(f"compile+first step s: {run.first_step_seconds!r}")
    print(f"steady step s: {run.step_seconds!r}")
    print(f"losses: {run.losses.tolist()}")
    print(f"uploads: {run.uploads} vs GD {run.gd_uploads}")
    print(f"tpu_custom_call in compiled step: {n_custom}")
    print(f"peak_bytes_in_use: {peak} | compiled step (compiler's "
          f"estimate): {_memory(compiled)}")
    del compiled
    check(len(run.losses) == STEPS and np.isfinite(run.losses).all(),
          f"non-finite loss: {run.losses}")
    check(n_custom > 0, "the compiled step holds no Pallas kernel")

    # the run's own real-size buffers: both workers' ĝ mirrors and θ
    gh, params = run.state["lag"]["grad_hat"], run.state["params"]
    del run
    A = jax.jit(lo.flatten_stacked)(gh)                     # (W, R, 128)
    b = jax.jit(lo.flatten)(params)                         # (R, 128)
    del gh, params
    kernel_parity(plan, lo, A, b)
    print(f"peak_bytes_in_use after parity: "
          f"{devs[0].memory_stats()['peak_bytes_in_use']}")


def _memory(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"arguments {m.argument_size_in_bytes}, outputs "
            f"{m.output_size_in_bytes}, aliased {m.alias_size_in_bytes}, "
            f"temporaries {m.temp_size_in_bytes}, code "
            f"{m.generated_code_size_in_bytes}")


def counter_probe(dev, n: int = 1 << 26) -> None:
    """What ``peak_bytes_in_use`` covers: a program whose only large
    temporary is a sort's n-float output, run while little else is live.
    If the counter counts program temporaries, the peak rises by about
    that temporary beyond the input's own bytes."""
    x = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
    jax.block_until_ready(x)
    before = dev.memory_stats()
    prog = jax.jit(lambda v: jnp.sort(v)[::4096]).lower(x).compile()
    jax.block_until_ready(prog(x))
    after = dev.memory_stats()["peak_bytes_in_use"]
    del x
    print(f"counter probe: {n * 4} input bytes live "
          f"(bytes_in_use {before['bytes_in_use']}, peak "
          f"{before['peak_bytes_in_use']}); sort program {_memory(prog)}; "
          f"peak_bytes_in_use after it {after}")


# The parity programs: the plane's kernels compiled (not interpreted) and
# the jnp oracle, on real-size flat buffers ``a`` (W, R, 128), ``b`` (R, 128)

def _kernels():
    from repro.fastpath import kernels
    return kernels


def _ref():
    from repro.kernels.lag_trigger import ref
    return ref


def _subs(x):
    from repro.fastpath.layout import LANES, SUB_ROWS
    return x.reshape(x.shape[:-2] + (-1, SUB_ROWS, LANES))


@jax.jit
def delta_kernel(a, b):
    return _kernels().delta_sqnorm_blocks(a, b, interpret=False)


@jax.jit
def delta_oracle(a, b):
    d = _ref().delta_sqnorm
    return (jax.vmap(jax.vmap(d), in_axes=(0, None))(_subs(a), _subs(b)),
            jax.vmap(d, in_axes=(0, None))(a, b))


def _gqe(a, b):
    """LAQ operands, one worker: v = (ĝ_0 − ĝ_1) + 1e-3·θ."""
    return a[0:1], a[1:2], 1e-3 * b[None]


def laq_programs(plan, lo):
    """(plane, oracle) LAQ encode programs for layout ``lo``."""
    ref = _ref()
    seg = jnp.asarray(lo.sub_leaf)
    qmax = float(2 ** (LAQ_BITS - 1) - 1)

    @jax.jit
    def plane(a, b):
        """plan.laq_encode's flat half: per-leaf absmax scales, steps,
        then the fused encode kernel."""
        p, r, sq, steps = plan.laq_encode_flat(lo, *_gqe(a, b),
                                               bits=LAQ_BITS)
        return p[0], r[0], jnp.sum(sq), steps[0]

    @jax.jit
    def oracle(a, b, p, r):
        """The jnp oracle leaf by leaf: a leaf's scale is its own max|v|
        (``ref.innovation_absmax``) and ``ref.laq_encode`` quantizes its
        sub-blocks with it.  Returns the per-leaf steps and, per leaf, how
        many payload codes and residual values differ from the plane's."""
        g, q, e = (_subs(x[0]) for x in _gqe(a, b))      # (nsubs, 8, 128)
        scale = jax.ops.segment_max(
            jax.vmap(ref.innovation_absmax)(g, q, e), seg, lo.num_leaves)
        p_ref, r_ref, sq_ref = ref.laq_encode(
            g, q, e, scale[seg][:, None, None], LAQ_BITS)
        per_leaf = lambda d: jax.ops.segment_sum(
            jnp.sum(d, axis=(1, 2), dtype=jnp.int32), seg, lo.num_leaves)
        return (scale / qmax, per_leaf(_subs(p) != p_ref),
                per_leaf(_subs(r) != r_ref), sq_ref)

    return plane, oracle


def _first_only(W):
    return jnp.arange(W) == 0


@jax.jit
def masked_kernel(a, b):
    """Worker 0 takes ``b``; the others keep their mirror."""
    return _kernels().masked_combine(b, a, _first_only(a.shape[0]), "update",
                                     interpret=False)


@jax.jit
def masked_err(out, a, b):
    m = _first_only(a.shape[0])
    want = jax.vmap(_ref().masked_lazy_update, in_axes=(None, 0, 0))(b, a, m)
    return (jnp.max(jnp.abs(out - want)),
            jnp.maximum(jnp.max(jnp.abs(a)), jnp.max(jnp.abs(b))))


def kernel_parity(plan, lo, A, b) -> None:
    n = A.shape[1] * A.shape[2]
    parts = np.asarray(delta_kernel(A, b))
    ref_parts, ref_tot = (np.asarray(x) for x in delta_oracle(A, b))
    part_err = float(np.max(np.abs(parts - ref_parts)
                            / np.maximum(np.abs(ref_parts), 1e-30)))
    tot = parts.astype(np.float64).sum(axis=1)
    tot_err = float(np.max(np.abs(tot - ref_tot) / np.abs(ref_tot)))
    print(f"parity delta_sqnorm: {parts.shape} partials max rel err "
          f"{part_err!r} (<= {PARTIAL_RTOL}), totals {tot.tolist()} vs "
          f"{ref_tot.tolist()} rel err {tot_err!r} (<= {TOTAL_RTOL})")
    check(part_err <= PARTIAL_RTOL and tot_err <= TOTAL_RTOL,
          "delta_sqnorm kernel disagrees with the oracle")

    plane, oracle = laq_programs(plan, lo)
    P, Rz, sq, steps = plane(A, b)
    steps_ref, flips, rdiff, sq_ref = (np.asarray(x)
                                       for x in oracle(A, b, P, Rz))
    del P, Rz
    steps = np.asarray(steps)
    sq_err = abs(float(sq) - float(sq_ref)) / float(sq_ref)
    off_steps = int(np.sum(steps != steps_ref))
    print(f"parity laq_encode@{LAQ_BITS}, per-leaf steps: {lo.num_leaves} "
          f"leaves, steps {float(steps.min())!r}..{float(steps.max())!r} "
          f"({int(np.sum(steps == 0))} zero), {off_steps} leaves whose step "
          f"differs; {int(flips.sum())} of "
          f"{n} codes and {int(rdiff.sum())} residuals differ; ||p||^2 "
          f"rel err {sq_err!r} (<= {TOTAL_RTOL})")
    for i in np.flatnonzero(flips + rdiff):
        print(f"  leaf {i} {lo.shapes[i]}: step {float(steps[i])!r} vs "
              f"{float(steps_ref[i])!r}, {int(flips[i])} codes and "
              f"{int(rdiff[i])} residuals differ")
    check(off_steps == 0,
          "the plane's per-leaf LAQ steps disagree with the oracle's")
    check(flips.sum() == 0 and rdiff.sum() == 0 and sq_err <= TOTAL_RTOL,
          "laq_encode kernel disagrees with the oracle")

    out = masked_kernel(A, b)
    err, mag = (float(x) for x in masked_err(out, A, b))
    del out
    print(f"parity masked_combine(update): max |out - oracle| {err!r} "
          f"(<= 1e-6 x {mag!r})")
    check(err <= 1e-6 * mag, "masked_combine kernel disagrees with the oracle")


# ---------------------------------------------------------------------------
# Four chips: devices:4 against shards W=4 on the same host mesh
# ---------------------------------------------------------------------------

_CALLEE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations|"
                     r"true_computation|false_computation)=\{?([^}\s,]+(?:,"
                     r"\s*%[^}\s,]+)*)\}?")


def gather_is_gated(hlo: str) -> bool:
    """True iff an all-gather is reachable only from a conditional's
    branches in the compiled module text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)

    def callees(lines):
        out = []
        for line in lines:
            for grp in _CALLEE.findall(line):
                out += [c.strip().lstrip("%") for c in grp.split(",")]
        return out

    def reaches_gather(root, seen):
        if root in seen or root not in comps:
            return False
        seen.add(root)
        return (any("all-gather" in l for l in comps[root])
                or any(reaches_gather(c, seen) for c in callees(comps[root])))

    branches = []
    for lines in comps.values():
        for line in lines:
            if " conditional(" in line:
                m = re.search(r"branch_computations=\{([^}]*)\}", line)
                names = m.group(1).split(",") if m else re.findall(
                    r"(?:true|false)_computation=%?([\w.\-]+)", line)
                branches += [c.strip().lstrip("%") for c in names]
    return any(reaches_gather(b, set()) for b in branches)


def four_chips(devs, seed: int) -> None:
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    common = ["--arch", ARCH, "--workers", "4", "--batch", str(BATCH),
              "--seq", str(SEQ), "--steps", str(STEPS), "--seed", str(seed)]
    with jax.default_matmul_precision("highest"):
        for algo in ("lag-wk", f"laq@{LAQ_BITS}"):
            argv = common + ["--algo", algo]
            dev = launch(devs, argv + ["--topology", "devices:4"],
                         f"devices:4 {algo} (plane)")
            if algo != "lag-wk":
                # the plane against the oracle on the same topology
                compare(dev, launch(devs, argv + ["--topology", "devices:4",
                                                  "--fastpath", "off"],
                                    f"devices:4 {algo} (oracle)"))
            # devices:4 against shards (there the oracle under GSPMD:
            # Mosaic kernels cannot be partitioned automatically)
            compare(dev, launch(devs, argv, f"shards W=4 {algo} (oracle)"))


def launch(devs, argv, what: str):
    """One ``launch.train`` run → (what, losses, upload masks)."""
    from repro.launch import train

    run = train.main(argv)
    hlo = run.compiled().as_text()
    gated = gather_is_gated(hlo)
    print(f"{what}: compile+first step s {run.first_step_seconds!r}, "
          f"steady step s {run.step_seconds!r}, tpu_custom_call "
          f"{hlo.count('tpu_custom_call')}, all-gather gated by a "
          f"conditional {gated}, peak_bytes_in_use "
          f"{[d.memory_stats()['peak_bytes_in_use'] for d in devs]}")
    print(f"  losses {run.losses.tolist()}")
    check(np.isfinite(run.losses).all(), f"{what}: non-finite loss")
    if "devices:4" in argv:
        check(gated, f"{what}: no all-gather inside a conditional")
    return what, run.losses, run.comm_masks


def compare(a, b) -> None:
    (wa, la, ma), (wb, lb, mb) = a, b
    rel = float(np.max(np.abs(la - lb) / np.abs(lb)))
    print(f"{wa} vs {wb}: upload masks equal {np.array_equal(ma, mb)} "
          f"({int(ma.sum())} vs {int(mb.sum())} uploads), loss max rel "
          f"diff {rel!r} (<= {DEVICES_LOSS_RTOL})")
    check(np.array_equal(ma, mb), f"{wa} and {wb} made different uploads")
    check(rel <= DEVICES_LOSS_RTOL, f"{wa} and {wb}: losses differ by {rel}")


if __name__ == "__main__":
    sys.exit(main())
