"""Shared helpers of the benchmark's CPU tests: the bench directory on the
path, and a cell cut to a size a test run holds."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import run  # noqa: E402

#: the cells' model families at a size the CPU runs in seconds
TINY = {
    "mamba2-370m": {
        "arch": "mamba2-370m",
        "overrides": {"num_layers": 2, "d_model": 64, "vocab_size": 256,
                      "ssm_state": 16, "ssm_headdim": 16, "ssm_chunk": 32},
        "sizes": {"num_layers": 2, "d_model": 64, "vocab_size": 256,
                  "ssm_state": 16, "ssm_headdim": 16, "ssm_chunk": 32,
                  "ssm_expand": 2, "ssm_conv": 4},
        "rms_norm_eps": 1e-6},
    "granite-8b-l1v8": {
        "arch": "granite-8b",
        "overrides": {"num_layers": 1, "d_model": 64, "vocab_size": 256,
                      "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                      "d_ff": 128},
        "sizes": {"num_layers": 1, "d_model": 64, "vocab_size": 256,
                  "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                  "d_ff": 128, "rope_theta": 1e7},
        "rms_norm_eps": 1e-6},
}


def tiny_cell(name: str):
    """Cell ``name`` as BENCHMARK.json has it (workers, algo, limits), with
    its model and batch cut to ``TINY`` size."""
    cell = run.load_cell(name)
    cell.config = dict(cell.config, model=TINY[cell.spec["config"]])
    cell.traffic = dict(cell.traffic, batch=4, seq=64)
    return cell


def cells():
    import json
    with open(ROOT / "BENCHMARK.json") as f:
        return [w["name"] for w in json.load(f)["workloads"]]


# -- faults planted under the timed path ---------------------------------

def frozen(step):
    """A step that returns its state unchanged."""
    return lambda state, batch: (state, step(state, batch)[1])


def half(workers):
    """A step that leaves out half of each worker's rows and takes the mean
    over the rest; with one row per worker, half of the workers see the
    rows of the others."""
    import jax

    def cut(x):
        per = x.shape[0] // workers
        if per > 1:
            return x.reshape((workers, per) + x.shape[1:])[
                :, :per // 2].reshape((-1,) + x.shape[1:])
        kept = x[:max(workers // 2, 1)]
        return jax.numpy.concatenate([kept] * (x.shape[0] // kept.shape[0]))

    def hook(step):
        return lambda state, batch: step(state,
                                         jax.tree_util.tree_map(cut, batch))
    return hook


def alone():
    """Leave out the exchange between chips: every all-gather of the step
    returns this chip's own value in each slot."""
    import jax
    import jax.numpy as jnp

    def local(x, axis_name, *, axis=0, tiled=False, **kw):
        n = jax.lax.axis_size(axis_name)
        if tiled:
            return jnp.concatenate([x] * n, axis=axis)
        return jnp.stack([x] * n, axis=axis)
    jax.lax.all_gather = local
    return None


def drive(name: str, fault: str = None, seed: int = 2 ** 33 + 41) -> dict:
    """A tiny run of cell ``name`` with ``fault`` planted; its result."""
    cell = tiny_cell(name)
    hook = None
    if fault == "frozen":
        hook = frozen
    elif fault == "half":
        hook = half(cell.work["workers"])
    elif fault == "alone":
        hook = alone()
    elif fault is not None:
        raise ValueError(fault)
    return run.run_cell(cell, seed, 0.3, traced=False, step_hook=hook)


def drive_in_subprocess(name: str, fault: str = None, devices: int = 4,
                        timeout: float = 600) -> dict:
    """:func:`drive` in a child process that sees ``devices`` CPU devices."""
    import json
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, __file__, name, fault or "none"], env=env,
        capture_output=True, text=True, timeout=timeout)
    if out.returncode:
        raise RuntimeError(f"child exited {out.returncode}: "
                           f"{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    import json
    res = drive(sys.argv[1], None if sys.argv[2] == "none" else sys.argv[2])
    print(json.dumps(res))
