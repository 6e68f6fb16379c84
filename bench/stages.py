"""Device time per stage of the LAG round, from the program's own scopes.

The program runs each stage of its round under a ``jax.named_scope``
(``repro.engine.rounds.STAGES``: ``lag.grad``, ``lag.encode``,
``lag.layout``, ``lag.fold``, ``lag.wire``, ``lag.gather``,
``lag.server``).  A scope is HLO metadata: every instruction of the
compiled step, fusions included, carries it in its ``op_name``.  A trace
event names its instruction, but not its ``op_name``, so a run maps
each event through the compiled step's text to its stage:

  * :func:`op_names` maps each instruction of every computation (a
    ``lax.cond`` branch's ops run as events of their own) to its
    ``op_name``;
  * :func:`step_op_names` builds the run's cell again from its seed and
    compiles its step as the window ran it (a load from the checkout's
    compile cache), for those names;
  * :func:`stage` reads the stage from an ``op_name``: the last
    ``lag.<name>`` in the path; under ``lag.grad`` an op is ``backward``
    when ``transpose(`` follows the scope (JAX names the VJP's ops so),
    else ``forward``; anything else is ``unscoped``;
  * :func:`stage_ms` sums the self time of the window's events of one
    stage per chip, over the window's steps, averaged over the chips.

A program without the scopes has no stage: every reader returns None.
"""
from __future__ import annotations

import argparse
import functools
import importlib.util
import re
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, Optional, Tuple

#: what :func:`stage` returns; ``grad`` splits into forward and backward
STAGES = ("forward", "backward", "encode", "layout", "fold", "wire",
          "gather", "server", "unscoped")
SCOPES = ("grad", "encode", "layout", "fold", "wire", "gather", "server")

_SCOPE = re.compile(r"(?<![\w.])lag\.(%s)\b" % "|".join(SCOPES))
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def instructions(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name (without ``%``) -> (opcode, ``op_name``, "" when
    it has none), over every computation of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _LINE.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = (m.group(2), n.group(1) if n else "")
    return out


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name (without ``%``) -> its ``op_name`` ("" when it
    has none), over every computation of a compiled module's text."""
    return {k: v for k, (_, v) in instructions(hlo_text).items()}


def stage(op_name: Optional[str]) -> str:
    """The stage of an op with ``op_name`` (one of :data:`STAGES`)."""
    found = list(_SCOPE.finditer(op_name or ""))
    if not found:
        return "unscoped"
    last = found[-1]
    if last.group(1) != "grad":
        return last.group(1)
    return "backward" if "transpose(" in op_name[last.end():] else "forward"


def _trace():
    """``bench/trace.py``, loaded by path as ``bench/run.py`` loads it
    (a plain import would find the standard library's ``trace``)."""
    name = "bench_trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, Path(__file__).resolve().parent / "trace.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def instruction(event) -> str:
    """The HLO instruction an event ran: on a chip the event's name is
    the whole instruction; on the CPU its ``hlo_op`` stat names it."""
    m = _trace().INSTRUCTION.match(event.name)
    if m:
        return m.group(1).lstrip("%")
    return str(event.stats.get("hlo_op", event.name))


def scoped(names: Iterable[str]) -> bool:
    """True when any of the op ``names`` carries one of the round's
    stages: the program that ran has the scopes."""
    return any(_SCOPE.search(v) for v in names)


def compiled_step(cell, seed: int) -> str:
    """The text of ``cell``'s step compiled as ``bench/run.py``'s window
    runs it: the program built from ``seed`` again (once what the run
    held is let go), after the first steps.  With the window's compile
    cache this is a load."""
    import run
    run.free()
    st = run.start(cell, seed)
    with st.prog.context():
        text = st.prog.step.lower(st.state, st.feed(run.CHECK_STEPS)
                                  ).compile().as_text()
    del st
    run.free()
    return text


@functools.lru_cache(maxsize=None)
def step_instructions(workload: str, seed: int
                      ) -> Dict[str, Tuple[str, str]]:
    """:func:`instructions` of the step of the cell ``workload``, run
    from ``seed``; once per process, for all the stage readers."""
    import run
    t = time.perf_counter()
    found = instructions(compiled_step(run.load_cell(workload), seed))
    print(f"stages: op names of {len(found)} instructions of the step in "
          f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
    return found


def run_args() -> Tuple[str, int]:
    """The ``--workload`` and ``--seed`` of this ``bench/run.py`` run."""
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    args, _ = p.parse_known_args()
    return args.workload, args.seed


def stage_ms(ctx, name: str,
             found: Optional[Dict[str, Tuple[str, str]]] = None
             ) -> Optional[float]:
    """Device self time per step of stage ``name`` in the window of
    ``ctx`` (``bench/run.py``'s ``Context``), averaged over the chips, in
    ms; None when the program has no stages or the window no steps.
    ``found`` is the step's :func:`instructions`, by default those of
    this run's cell (:func:`step_instructions`).  Should an op of the
    window have another opcode than the instruction of its name, the
    names are another program's: None, and a line on stderr."""
    if name not in STAGES:
        raise ValueError(f"{name!r} is not one of {STAGES}")
    if not ctx.window.steps:
        return None
    if found is None:
        found = step_instructions(*run_args())
    if not scoped(n for _, n in found.values()):
        return None
    of: Dict[str, str] = {}       # event name -> stage, looked up once
    ns = 0.0
    for c in ctx.trace.ops:
        for e in ctx.trace.in_window(c):
            if e.name not in of:
                op = _trace().INSTRUCTION.match(e.name)
                opcode, op_name = found.get(instruction(e), ("", ""))
                if op and opcode and op.group(3) != opcode:
                    print(f"stages: {e.name[:80]} is not the step's "
                          f"{opcode}: no stage is read", file=sys.stderr)
                    return None
                of[e.name] = stage(op_name)
            if of[e.name] == name:
                ns += e.self_time
    return 1e-6 * ns / len(ctx.trace.ops) / ctx.window.steps
