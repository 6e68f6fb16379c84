#!/usr/bin/env python3
"""Chip benchmark of LAG training steps.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process.  It reads ``BENCHMARK.json`` and the cell's files
(``bench/workloads/<cell>.json``, ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``), fails unless JAX sees the cell's TPU
chips, builds the weights and the LAG state on the device from the seed,
runs the first three steps through the window's own feed and call (they
compile the step, and give the program's readings for the check), then
measures for ``--seconds``: steps are dispatched with no host sync, at
most sixteen in flight, and a waiter thread stamps each step's completion.
Afterwards the plain reference repeats the first three steps from the same
seed, and ``bench/check.py`` decides ``correct``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checked``: each number compared beside its limit, which also
close standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

CHECK_STEPS = 3          # steps the reference follows
# steps dispatched ahead of the last completion: enough queued device
# work to ride out a host stall of a second or two (one-chip machines
# share their host's cores); an in-flight step holds only its batch
IN_FLIGHT = 16


# ---------------------------------------------------------------------------
# Loading the cell
# ---------------------------------------------------------------------------

def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    spec: Dict            # the BENCHMARK.json entry
    work: Dict            # bench/workloads/<name>.json
    config: Dict          # bench/configs/<config>.json
    traffic: Dict         # bench/traffic/<traffic>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def chips(self) -> int:
        return int(self.spec["chips"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    specs = {w["name"]: w for w in bench["workloads"]}
    if name not in specs:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(specs)}")
    spec = specs[name]
    conf = {c["name"]: c for c in bench["configs"]}[spec["config"]]
    work = _json(root / "bench" / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if work[key] != spec[key]:
            raise SystemExit(f"{name}: {key} is {spec[key]!r} in "
                             f"BENCHMARK.json but {work[key]!r} in its file")

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(name=name, spec=spec, work=work,
                config=_json(root / conf["file"]),
                traffic=_json(root / "bench" / "traffic"
                              / f"{spec['traffic']}.json"),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.stem.replace('.', '_').replace('-', '_')}", path)
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference_module(cell: Cell):
    return _module(BENCH / "reference" / f"{cell.config['reference']}.py")


def reference_model(cell: Cell) -> Dict:
    """The sizes the reference reads: the file's, and its norm epsilon."""
    m = cell.config["model"]
    return dict(m["sizes"], norm_eps=m["rms_norm_eps"])


def use_checkout_cache() -> None:
    """Keep JAX's compile cache in the checkout, at a fixed path, for the
    program too (``repro.launch.cache`` defers to this variable), small
    programs included.  Called before anything touches JAX."""
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    import program
    program.enable_compile_cache()


def seed_key(seed: int):
    """A PRNG key from a seed of any size (PRNGKey keeps 32 bits)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

class Spans:
    """Host spans of the window: summed seconds per name, and in a traced
    run a ``bench.<name>`` annotation in the profiler's trace."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.seconds: Dict[str, float] = defaultdict(float)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation(f"bench.{name}") if self.traced
               else contextlib.nullcontext())
        t = time.perf_counter()
        with ann:
            yield
        self.seconds[name] += time.perf_counter() - t


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    steps: int
    seconds: float                 # window start to the last completion
    intervals: List[float]         # completion to completion, s
    losses: List[float]
    masks: List[List[bool]]
    spans: Dict[str, float]
    compiles: int


def drive(step: Callable, state, feed: Callable, first: int, seconds: float,
          spans: Spans):
    """The window: dispatch steps from ``first`` until ``seconds`` have
    passed, never more than ``IN_FLIGHT`` ahead of the last completion, and
    let a waiter thread stamp each completion.  Returns (state, Window)."""
    import jax
    done: List[float] = []
    pending: "queue.Queue" = queue.Queue()
    slots = threading.Semaphore(IN_FLIGHT)

    def waiter():
        while True:
            out = pending.get()
            if out is None:
                return
            with spans("wait"):
                jax.block_until_ready(out)
            done.append(time.perf_counter())
            slots.release()

    compiles = [0]

    def count(event, *_, **__):
        if "backend_compile" in event:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    thread = threading.Thread(target=waiter, daemon=True)
    thread.start()
    outs = []
    k = first
    try:
        with spans("window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                with spans("input"):
                    batch = feed(k)
                with spans("throttle"):
                    slots.acquire()
                with spans("dispatch"):
                    state, m = step(state, batch)
                outs.append(m)
                pending.put(m)
                k += 1
            pending.put(None)
            thread.join()
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    stamps = [t0] + done
    losses, masks = zip(*jax.device_get(outs))
    return state, Window(
        steps=len(done), seconds=done[-1] - t0,
        intervals=[b - a for a, b in zip(stamps, stamps[1:])],
        losses=[float(x) for x in losses],
        masks=[list(map(bool, m)) for m in masks],
        spans=dict(spans.seconds), compiles=compiles[0])


def step_p90_ms(intervals: List[float]) -> float:
    """90th percentile, over all steps of the window, of the interval
    between one step's completion and the next, in ms."""
    import numpy as np
    return float(np.percentile(intervals, 90)) * 1e3


@dataclasses.dataclass
class Started:
    """A cell's program after its first steps: the state the window takes
    over, the window's feed and call, and the program's readings."""
    prog: object
    state: object
    feed: Callable
    call: Callable
    readings: Dict
    leaf_sizes: List[int]


def start(cell: Cell, seed: int, step_hook: Optional[Callable] = None
          ) -> Started:
    """Build the weights and the state on the device from ``seed`` and run
    the first ``CHECK_STEPS`` steps through the window's own feed and
    call.  ``step_hook`` wraps the program's unjitted step (the fault
    tests plant faults with it)."""
    import jax
    import traffic as traffic_lib
    from reference import lag as ref_lag
    import program as program_lib

    ref, rmodel = reference_module(cell), reference_model(cell)
    W = cell.work["workers"]
    tr = traffic_lib.Traffic.from_dict(cell.traffic)
    key = seed_key(seed)
    init = lambda k: ref.init(k, rmodel)

    make = program_lib.make_step
    if step_hook is not None:
        program_lib.make_step = lambda *a: step_hook(make(*a))
    try:
        prog = program_lib.Program(cell.config["model"], cell.work, init, key)
    finally:
        program_lib.make_step = make

    def feed(k):
        return prog.put(traffic_lib.make_batch(tr, rmodel["vocab_size"],
                                               seed, k, W))

    def call(state, batch):
        state, m = prog.step(state, batch)
        return state, prog.outputs(m)

    norms = jax.jit(ref_lag.leaf_norms)
    moved = jax.jit(lambda p, k: ref_lag.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, init(k))))
    with prog.context():
        state, prog.state = prog.state, None
        first = []
        for k in range(CHECK_STEPS):
            state, out = call(state, feed(k))
            first.append(out)
            if k == 0:
                grad = norms(prog.nabla(state))
        dtheta = moved(prog.params(state), key)
        jax.block_until_ready((state, grad, dtheta))
    first = jax.device_get(first)
    readings = {"loss": [float(l) for l, _ in first],
                "mask": [list(map(bool, m)) for _, m in first],
                "grad": {p: float(v) for p, v in jax.device_get(grad).items()},
                "dtheta": {p: float(v)
                           for p, v in jax.device_get(dtheta).items()}}
    leaf_sizes = [l.size for l in jax.tree_util.tree_leaves(
        jax.eval_shape(init, key))]
    return Started(prog, state, feed, call, readings, leaf_sizes)


def reference_readings(cell: Cell, seed: int, dtype=None,
                       fault: Optional[str] = None) -> Dict:
    """The plain reference's readings of the first ``CHECK_STEPS`` steps
    from the same seed: float32 at the highest matmul precision, or the
    control in a lower ``dtype``, or with a planted ``fault``.  Worker m
    runs on the cell's chip m mod chips."""
    import jax
    import jax.numpy as jnp
    import traffic as traffic_lib
    from reference import lag as ref_lag

    ref, rmodel = reference_module(cell), reference_model(cell)
    W, work = cell.work["workers"], cell.work
    tr = traffic_lib.Traffic.from_dict(cell.traffic)
    key = seed_key(seed)
    batches = [traffic_lib.make_batch(tr, rmodel["vocab_size"], seed, k, W)
               for k in range(CHECK_STEPS)]
    return ref_lag.run(
        lambda k: ref.init(k, rmodel), key,
        lambda p, t, y, dt: ref.loss(p, rmodel, t, y, dt),
        [(b["tokens"], b["targets"]) for b in batches], workers=W,
        lr=work["lr"], xi=work["xi"], D=work["D"], steps=CHECK_STEPS,
        dtype=dtype or jnp.float32, fault=fault, bits=laq_bits(work["algo"]),
        devices=jax.devices()[:cell.chips])


def laq_bits(algo: str) -> int:
    """The quantization width of an ``laq@b`` cell, 0 for the dense
    policies."""
    if algo.startswith("laq"):
        return int(algo.partition("@")[2] or 4)
    if algo != "lag-wk":
        raise ValueError(f"the reference follows lag-wk and laq@b, not "
                         f"{algo!r}")
    return 0


def free() -> None:
    """Let go of the program's device state before the reference runs."""
    import jax
    gc.collect()
    jax.clear_caches()


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             step_hook: Optional[Callable] = None) -> Dict:
    """Everything but the look for a chip."""
    import jax
    import check
    import counts
    import traffic as traffic_lib
    import program as program_lib

    st = start(cell, seed, step_hook)
    setup_s = time.perf_counter() - T_START
    trace_dir = tempfile.TemporaryDirectory() if traced else None
    with st.prog.context():
        if traced:
            jax.profiler.start_trace(trace_dir.name)
        try:
            state, win = drive(st.call, st.state, st.feed, CHECK_STEPS,
                               seconds, Spans(traced))
        finally:
            if traced:
                jax.profiler.stop_trace()
    devices = jax.devices()[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    readings, leaf_sizes = st.readings, st.leaf_sizes
    del state, st
    free()

    t_ref = time.perf_counter()
    ref_readings = reference_readings(cell, seed)
    ref_s = time.perf_counter() - t_ref
    correct, table = check.judge(check.gaps(readings, ref_readings),
                                 cell.work["limits"])

    tr = traffic_lib.Traffic.from_dict(cell.traffic)
    tokens_per_s = win.steps * tr.tokens_per_step / win.seconds
    p90_ms = step_p90_ms(win.intervals)
    log = sys.stderr
    print(f"{cell.name} seed {seed}: setup {setup_s:.3f} s; window "
          f"{win.steps} steps in {win.seconds:.3f} s, {win.compiles} "
          f"compiles inside it; step_ms_p90 over {len(win.intervals)} "
          f"intervals; reference {ref_s:.3f} s",
          file=log)
    print(f"first losses program {readings['loss']} reference "
          f"{ref_readings['loss']}; trigger lhs/rhs "
          f"{ref_readings['lhs_over_rhs']}", file=log)
    longest = sorted(range(len(win.intervals)), key=win.intervals.__getitem__)
    print(f"longest step intervals (step, ms): "
          f"{[(i, round(win.intervals[i] * 1e3, 2)) for i in longest[-5:]]}; "
          f"host spans (s): "
          f"{ {k: round(v, 3) for k, v in win.spans.items()} }", file=log)
    print(f"window losses: {[round(x, 5) for x in win.losses]}", file=log)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": 1,
              "failed": 0 if correct else 1}
    if traced:
        import peaks as peaks_lib
        trace_lib = _module(BENCH / "trace.py")
        tr_ = trace_lib.load(trace_lib.find_xplane(trace_dir.name))
        trace_dir.cleanup()
        device["busy_s"] = tr_.busy_s()
        device["window_s"] = tr_.window_s()
        ref = reference_module(cell)
        ctx = Context(
            trace=tr_, window=win, tokens_per_s=tokens_per_s,
            flops_per_token=counts.flops_per_token(
                ref, reference_model(cell), tr.seq),
            chips=len(devices), peaks=peaks_lib.peaks(dev.device_kind),
            plane_workers=program_lib.Program.plane_workers(cell.work),
            plane_rows=counts.plane_rows(leaf_sizes))
        metrics = {}
        for m in cell.per_layer:
            value = _module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr_.top_ops(),
                               "idle_gaps": tr_.idle_gaps()}
    else:
        values = {"tokens_per_s": tokens_per_s, "step_ms_p90": p90_ms,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result.update(metrics=metrics, device=device, checked=table)
    for name, row in table.items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=log)
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader reads."""
    trace: object
    window: Window
    tokens_per_s: float
    flops_per_token: float
    chips: int
    peaks: object
    plane_workers: int
    plane_rows: int


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    use_checkout_cache()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
