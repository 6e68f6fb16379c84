"""plane_roofline: the HBM bytes the comm plane's kernels must move
(bench/counts.py, from the flat buffer's shapes) over the chip's HBM
bandwidth, divided by the kernels' device time, in %.  The kernels are
bound by bytes, not FLOPs.  Moves tokens_per_s; layer: comm plane."""

import importlib.util
from pathlib import Path


def _sibling(name):
    path = Path(__file__).resolve().parent / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    import counts
    kernel_of = _sibling("plane_kernel_ms").kernel_of
    rows = ctx.plane_rows
    found = ctx.trace.matching(lambda e: kernel_of(e, rows) is not None)
    if not any(found.values()):
        return None
    need = took = 0.0
    for evs in found.values():
        for e in evs:
            need += counts.kernel_bytes(kernel_of(e, rows), ctx.plane_workers,
                                        rows)
            took += e.dur * 1e-9
    return 100.0 * (need / ctx.peaks.hbm_bytes_per_s) / took
