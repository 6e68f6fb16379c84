"""§Perf single-iteration harness: lower ONE (arch × shape) variant on the
single-pod production mesh and print its roofline terms + collective
breakdown as JSON.  Each invocation is a fresh process (512 host devices).

  PYTHONPATH=src python -m benchmarks.perf_iter --arch llama3.2-1b \\
      --shape train_4k --act-shard batch --no-input-seq-shard

Knobs (the §Perf candidate changes):
  --act-shard {none,batch,batch_seq}   activation sharding constraints
  --no-input-seq-shard                 don't shard the token seq dim
  --workers N                          LAG worker count
  --grad-hat-dtype {bfloat16,float32}
  --moe-seq-shards N                   MoE group alignment
  --no-remat                           disable activation checkpointing
  --capacity-factor F
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json

import jax

from repro.launch import dryrun as dr
from repro.launch.mesh import make_production_mesh


def main():
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", required=True)
    p.add_argument("--act-shard", default="none",
                   choices=["none", "batch", "batch_seq"])
    p.add_argument("--no-input-seq-shard", action="store_true")
    p.add_argument("--workers", type=int, default=0)
    p.add_argument("--grad-hat-dtype", default="bfloat16")
    p.add_argument("--moe-seq-shards", type=int, default=0)
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--capacity-factor", type=float, default=0.0)
    p.add_argument("--mode", default="tp", choices=["tp", "dp"])
    p.add_argument("--embed-onehot", action="store_true")
    p.add_argument("--depth", type=int, default=0,
                   help="override num_layers (0 = full)")
    args = p.parse_args()

    cfg = dr.dryrun_config(args.arch)
    if args.act_shard != "none":
        cfg = cfg.replace(act_shard_axes=("data",),
                          act_shard_seq=(args.act_shard == "batch_seq"))
    if args.moe_seq_shards:
        cfg = cfg.replace(moe_seq_shards=args.moe_seq_shards)
    if args.no_remat:
        cfg = cfg.replace(remat=False)
    if args.capacity_factor:
        cfg = cfg.replace(capacity_factor=args.capacity_factor)
    if args.embed_onehot:
        cfg = cfg.replace(embed_onehot=True)
    if args.depth:
        cfg = cfg.replace(num_layers=args.depth)

    workers = args.workers or dr.arch_worker_count(dr.count_params(cfg))
    mesh = make_production_mesh(multi_pod=False)

    import time
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, arg_shapes, in_sh, out_sh = dr.build_lowerable(
            cfg, args.shape, mesh, workers,
            seq_shard=not args.no_input_seq_shard, mode=args.mode)
        compiled = jax.jit(fn, in_shardings=in_sh,
                           out_shardings=out_sh).lower(*arg_shapes).compile()
    from repro.dist.hlo_analysis import collective_bytes
    coll = collective_bytes(compiled.as_text(), pod_size=dr.POD_SIZE,
                            n_devices=int(mesh.devices.size))
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    out = {
        "arch": args.arch, "shape": args.shape,
        "variant": {"act_shard": args.act_shard, "mode": args.mode,
                    "input_seq_shard": not args.no_input_seq_shard,
                    "workers": workers,
                    "moe_seq_shards": cfg.moe_seq_shards,
                    "remat": cfg.remat,
                    "depth": cfg.num_layers},
        "compile_s": round(time.time() - t0, 1),
        "flops_per_dev": float(cost.get("flops", 0.0)),
        "bytes_per_dev": float(cost.get("bytes accessed", 0.0)),
        "collectives": coll.as_dict(),
        "temp_gib_per_dev": (mem.temp_size_in_bytes / 2**30) if mem else None,
        "args_gib_per_dev": (mem.argument_size_in_bytes / 2**30) if mem else None,
        "top_ops": sorted(coll.ops, key=lambda o: -o["wire_bytes"])[:12],
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
