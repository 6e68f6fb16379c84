import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape) combination this lowers + compiles
the appropriate step on the production mesh(es) with ShapeDtypeStruct
stand-ins (no allocation), prints ``memory_analysis`` / ``cost_analysis``,
parses collective traffic from the optimized HLO, and writes one JSON per
combination under --out.

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \\
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \\
      --mesh both --out experiments/dryrun

NOTE: the XLA_FLAGS line above MUST run before any jax import — jax locks
the device count on first init.  Do not import this module from processes
that need the real device topology.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, ALL_ARCHS, get_config
from repro.configs.shapes import SHAPES, applicable, input_specs
from repro.dist import (TrainerConfig, batch_shardings, init_state,
                        make_train_step, tree_shardings)
from repro.dist.hlo_analysis import collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import model

POD_SIZE = 256          # devices per pod in the production meshes


def arch_worker_count(n_params: int) -> int:
    """LAG worker count that keeps grad_hat memory sane (DESIGN.md §6):
    per-device extra = W·|θ|·bytes/N_devices."""
    if n_params > 6e10:
        return 2
    if n_params > 5e9:
        return 4
    return 16


def count_params(cfg) -> int:
    import math
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
    # NB: python ints — jnp.prod would overflow int32 on 2e11-element leaves
    return sum(math.prod(l.shape)
               for l in jax.tree_util.tree_leaves(shapes))


def dryrun_config(arch: str):
    """bf16 params+compute for the production memory budget; MoE groups
    aligned with the 16-way model axis."""
    cfg = get_config(arch, dtype="bfloat16", param_dtype="bfloat16")
    if cfg.num_experts:
        cfg = cfg.replace(moe_seq_shards=16)
    return cfg


def build_lowerable(cfg, shape_name: str, mesh, workers: int,
                    seq_shard: bool = True, mode: str = "tp"):
    """Returns (fn, arg_shapes, in_shardings, out_shardings)."""
    shp = SHAPES[shape_name]
    specs = input_specs(cfg, shape_name)

    if shp.kind == "train":
        tcfg = TrainerConfig(algo="lag-wk", num_workers=workers, lr=1e-3,
                             grad_hat_dtype="bfloat16")
        state_shapes = jax.eval_shape(
            lambda: init_state(jax.random.PRNGKey(0), cfg, tcfg))
        step = make_train_step(cfg, tcfg)
        state_sh = tree_shardings(state_shapes, mesh, mode)
        batch_sh = batch_shardings(specs, mesh, seq_shard=seq_shard, mode=mode)
        metrics_sh = jax.tree_util.tree_map(
            lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            jax.eval_shape(step, state_shapes, specs)[1])
        return (step, (state_shapes, specs), (state_sh, batch_sh),
                (state_sh, metrics_sh))

    params_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg))
    params_sh = tree_shardings(params_shapes, mesh)

    if shp.kind == "prefill":
        def prefill_fn(params, inputs):
            return model.prefill(params, cfg, inputs, max_len=shp.seq_len)
        out_shapes = jax.eval_shape(prefill_fn, params_shapes, specs)
        out_sh = tree_shardings(out_shapes, mesh)
        return (prefill_fn, (params_shapes, specs),
                (params_sh, batch_shardings(specs, mesh, seq_shard=seq_shard)), out_sh)

    # decode
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(cfg, shp.global_batch, shp.seq_len))
    cache_sh = tree_shardings(cache_shapes, mesh)

    def decode_fn(params, cache, tokens, pos):
        return model.decode_step(params, cfg, cache, tokens, pos)

    tok, pos = specs["tokens"], specs["pos"]
    tok_sh = batch_shardings({"tokens": tok}, mesh)["tokens"]
    rep = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
    logits_shapes = jax.eval_shape(decode_fn, params_shapes, cache_shapes,
                                   tok, pos)
    logits_sh = tree_shardings(logits_shapes[0], mesh)
    return (decode_fn, (params_shapes, cache_shapes, tok, pos),
            (params_sh, cache_sh, tok_sh, rep), (logits_sh, cache_sh))


def _compile_and_measure(cfg, shape_name: str, mesh, workers: int) -> dict:
    t0 = time.time()
    with jax.set_mesh(mesh):   # tracing may emit sharding constraints
        fn, arg_shapes, in_sh, out_sh = build_lowerable(
            cfg, shape_name, mesh, workers)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*arg_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, pod_size=POD_SIZE,
                            n_devices=int(mesh.devices.size))

    mem_rec = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem_rec[k] = int(getattr(mem, k, 0) or 0)
    cost_rec = {}
    if cost:
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in cost:
                cost_rec[k.replace(" ", "_")] = float(cost[k])
    return {"lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
            "memory": mem_rec, "cost": cost_rec, "collectives": coll.as_dict()}


def _extrapolate(v1: float, v2: float, nsb: int, tail_ratio: float) -> float:
    """XLA's cost model counts while-loop bodies ONCE, so the layer scan is
    undercounted.  Compile the same program at 1 and 2 superblocks; the
    difference is one loop body; extrapolate linearly to the full depth
    (+ the unscanned tail, which scales like tail_ratio bodies)."""
    body = max(v2 - v1, 0.0)
    base = max(v1 - body, 0.0)
    return base + (nsb + tail_ratio) * body


def run_one(arch: str, shape_name: str, mesh, mesh_name: str,
            workers: int, *, extrapolate: bool = True) -> dict:
    cfg = dryrun_config(arch)
    ok, reason = applicable(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_devices": int(mesh.devices.size)}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    try:
        full = _compile_and_measure(cfg, shape_name, mesh, workers)
        rec.update(status="ok", workers=workers, **full)

        if extrapolate and cfg.num_superblocks > 2:
            # the calibration compiles UNROLL every sequence/layer loop so
            # the HLO has no while ops (XLA counts while bodies once)
            pat = len(cfg.block_pattern)
            tail_ratio = cfg.tail_layers / pat
            m1 = _compile_and_measure(
                cfg.replace(num_layers=pat, scan_unroll=True), shape_name,
                mesh, workers)
            m2 = _compile_and_measure(
                cfg.replace(num_layers=2 * pat, scan_unroll=True), shape_name,
                mesh, workers)
            nsb = cfg.num_superblocks
            corr = {}
            for key in ("flops", "bytes_accessed"):
                v1 = m1["cost"].get(key)
                v2 = m2["cost"].get(key)
                if v1 is not None and v2 is not None:
                    corr[key] = _extrapolate(v1, v2, nsb, tail_ratio)
            c1, c2 = m1["collectives"], m2["collectives"]
            corr["collective_total_bytes"] = _extrapolate(
                c1["total_bytes"], c2["total_bytes"], nsb, tail_ratio)
            corr["collective_cross_pod_bytes"] = _extrapolate(
                c1["cross_pod_bytes"], c2["cross_pod_bytes"], nsb, tail_ratio)
            corr["by_kind_bytes"] = {
                k: _extrapolate(c1["by_kind_bytes"].get(k, 0.0),
                                c2["by_kind_bytes"].get(k, 0.0),
                                nsb, tail_ratio)
                for k in set(c1["by_kind_bytes"]) | set(c2["by_kind_bytes"])}
            rec["corrected"] = corr
    except Exception as e:  # noqa: BLE001 — a failure here is a finding
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="all")
    p.add_argument("--shape", default="all")
    p.add_argument("--mesh", default="both",
                   choices=["single", "multi", "both"])
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--include-sw", action="store_true",
                   help="also run the llama3.2-1b-sw beyond-paper variant")
    args = p.parse_args(argv)

    archs = ([args.arch] if args.arch != "all"
             else (ALL_ARCHS if args.include_sw else ASSIGNED))
    shapes = [args.shape] if args.shape != "all" else list(SHAPES)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for arch in archs:
        workers = arch_worker_count(count_params(dryrun_config(arch)))
        for shape_name in shapes:
            for mesh_name, mesh in meshes:
                # extrapolation compiles only needed for the (single-pod)
                # roofline; multi-pod pass just proves lowering
                rec = run_one(arch, shape_name, mesh, mesh_name, workers,
                              extrapolate=(mesh_name.startswith("single")))
                fname = f"{arch}_{shape_name}_{mesh_name}.json".replace("/", "_")
                with open(os.path.join(args.out, fname), "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    mem_gib = rec["memory"].get("argument_size_in_bytes", 0) / 2**30
                    extra = (f" compile={rec['compile_s']}s "
                             f"args/dev={mem_gib:.2f}GiB "
                             f"flops={rec['cost'].get('flops', 0):.3g} "
                             f"coll={rec['collectives']['total_bytes']/2**30:.3f}GiB")
                elif status == "error":
                    n_fail += 1
                    extra = " " + rec["error"][:160]
                elif status == "skipped":
                    extra = " " + rec["reason"]
                print(f"[{status:7s}] {arch} × {shape_name} × {mesh_name}{extra}",
                      flush=True)
    print(f"done ({n_fail} failures)")
    return n_fail


if __name__ == "__main__":
    raise SystemExit(main())
