"""Serving launcher: continuous batched greedy decoding with prefill.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \\
      --batch 4 --prompt-len 64 --gen 64

Uses the same model/prefill/decode path the dry-run lowers at production
scale; on this host it runs the reduced configs.  Reports prefill latency
and per-token decode latency.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.shapes import applicable
from repro.launch.cache import enable_compile_cache
from repro.models import model


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3.2-1b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=64)
    p.add_argument("--rounds", type=int, default=3,
                   help="request batches to serve")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ok, reason = applicable(cfg, "decode_32k")
    if not ok:
        raise SystemExit(f"{args.arch}: {reason}")

    params = model.init(jax.random.PRNGKey(args.seed), cfg)
    max_len = args.prompt_len + args.gen

    prefill = jax.jit(lambda prm, toks: model.prefill(
        prm, cfg, {"tokens": toks}, max_len=max_len))
    decode = jax.jit(lambda prm, c, t, pos: model.decode_step(
        prm, cfg, c, t, pos))

    for rnd in range(args.rounds):
        key = jax.random.PRNGKey(args.seed + rnd + 1)
        prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size, jnp.int32)
        t0 = time.time()
        last, cache = prefill(params, prompts)
        jax.block_until_ready(last)
        t_pre = time.time() - t0

        tok = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
        out = [tok]
        t0 = time.time()
        for t in range(args.prompt_len, max_len - 1):
            logits, cache = decode(params, cache, out[-1],
                                   jnp.asarray(t, jnp.int32))
            out.append(jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32))
        gen = jnp.concatenate(out, 1)
        jax.block_until_ready(gen)
        t_dec = time.time() - t0
        n_tok = gen.shape[1] - 1
        print(f"round {rnd}: prefill {args.prompt_len}tok "
              f"{t_pre * 1e3:8.1f}ms | decode {n_tok}tok "
              f"{t_dec * 1e3:8.1f}ms ({t_dec / max(n_tok, 1) * 1e3:.2f} ms/tok)"
              f" | batch {args.batch}")


if __name__ == "__main__":
    main()
