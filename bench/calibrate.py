#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        --control-seeds 1 2 3 [--out <file.json>]

For each seed of ``--seeds``: the program's first three steps (as a
benchmark run takes them) against the float32 reference, the lower
readings.  For each of ``--control-seeds``, against the same reference:
the control (the reference computed in bfloat16, put in the program's
place) and the planted faults ``half`` (half of the rows left out) and,
on several chips, ``alone`` (the exchange between them left out), which
give the upper readings.  ``frozen`` (state returned
unchanged) reads 1 on ``dtheta_gap`` by construction and needs no run.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

#: faults planted in the reference put in the program's place; ``alone``
#: (the exchange between chips left out) only where a cell has several
FAULTS = ("half", "alone")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.use_checkout_cache()

    import jax
    import jax.numpy as jnp
    import check
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        st = run.start(cell, seed)
        prog = st.readings
        del st
        run.free()
        ref = run.reference_readings(cell, seed)
        row = {"seed": seed, "program": check.gaps(prog, ref),
               "lhs_over_rhs": ref["lhs_over_rhs"], "loss": ref["loss"]}
        if seed in args.control_seeds:
            row["control"] = check.gaps(
                run.reference_readings(cell, seed, dtype=jnp.bfloat16), ref)
            for fault in FAULTS[:2 if cell.chips > 1 else 1]:
                row[fault] = check.gaps(
                    run.reference_readings(cell, seed, fault=fault), ref)
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for kind in ("program", "control") + FAULTS:
        got = [r[kind] for r in rows if kind in r]
        if got:
            summary[kind] = {n: {"min": min(g[n] for g in got),
                                 "max": max(g[n] for g in got)}
                             for n in check.NUMBERS}
    print(json.dumps({"workload": cell.name, "summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "rows": rows,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
