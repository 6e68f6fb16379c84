"""Reduction of a profiler trace to what the per-layer metrics read.

A run with ``--trace 1`` records the measured window with the JAX profiler
and annotates its own host spans (``bench.window``, ``bench.input``,
``bench.dispatch``, ``bench.throttle``, ``bench.wait``).  This module reads
the ``.xplane.pb`` with ``jax.profiler.ProfileData`` alone and gives:

  * the device operations of each chip: the ``XLA Ops`` line of each
    ``/device:*`` plane; a chip's plane (``/device:TPU:<n>``) without
    that line, or a trace without device operations, is an error.
    Only with ``cpu=True`` (a CPU run, in the tests) are they the host
    events that carry an ``hlo_op`` stat, grouped by their
    ``device_ordinal``;
  * each operation's self time (its duration less that of the operations
    nested in it on the same line);
  * the busy time: the union of the operation intervals inside the window;
  * the idle gaps inside the window, each named by the host span of the
    main thread that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
MAIN_SPANS = ("input", "throttle", "dispatch")
CORE = re.compile(r"/device:TPU:\d+")     # a chip's own plane


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns
    stats: Dict
    self_time: float = 0.0

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def opcode(self) -> str:
        return opcode(self.name)


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]          # chip -> device operations
    spans: List[Event]                   # the benchmark's host spans

    # -- the window --------------------------------------------------------
    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s.name == SPAN_PREFIX + "window"]
        if not w:
            raise ValueError("the trace holds no bench.window span")
        return w[0].start, w[0].end

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) * 1e-9

    # -- busy and idle -----------------------------------------------------
    def busy_intervals(self, chip: int) -> List[Tuple[float, float]]:
        """Merged operation intervals of ``chip``, clipped to the window."""
        a, b = self.window()
        iv = sorted((max(e.start, a), min(e.end, b)) for e in self.ops[chip]
                    if e.end > a and e.start < b)
        merged: List[List[float]] = []
        for s, t in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t)
            else:
                merged.append([s, t])
        return [(s, t) for s, t in merged]

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the chips."""
        if not self.ops:
            return 0.0
        per = [sum(t - s for s, t in self.busy_intervals(c))
               for c in self.ops]
        return 1e-9 * sum(per) / len(per)

    def idle_gaps(self, chip: int = None, top: int = 10
                  ) -> List[Tuple[str, float]]:
        """The longest idle gaps of ``chip`` (default: the first) in the
        window, longest first, each as (host span open during it, s)."""
        chip = min(self.ops) if chip is None else chip
        a, b = self.window()
        busy = self.busy_intervals(chip)
        edges = [a] + [x for iv in busy for x in iv] + [b]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        main = [s for s in self.spans
                if s.name[len(SPAN_PREFIX):] in MAIN_SPANS]
        out = []
        for s, t in gaps[:top]:
            best, name = 0.0, "none"
            for sp in main:
                ov = min(t, sp.end) - max(s, sp.start)
                if ov > best:
                    best, name = ov, sp.name[len(SPAN_PREFIX):]
            out.append((name, (t - s) * 1e-9))
        return out

    # -- operations --------------------------------------------------------
    def in_window(self, chip: int) -> List[Event]:
        a, b = self.window()
        return [e for e in self.ops[chip] if e.start >= a and e.end <= b]

    def matching(self, pred: Callable[[Event], bool]) -> Dict[int, List[Event]]:
        """Per chip, the window's operations for which ``pred`` holds."""
        return {c: [e for e in self.in_window(c) if pred(e)]
                for c in self.ops}

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Operations by self time in the window, averaged over the chips,
        most first, each named by :func:`label`."""
        tot: Dict[str, float] = defaultdict(float)
        for c in self.ops:
            for e in self.in_window(c):
                tot[label(e.name)] += e.self_time
        n = max(len(self.ops), 1)
        ranked = sorted(tot.items(), key=lambda kv: kv[1], reverse=True)
        return [(k, v * 1e-9 / n) for k, v in ranked[:top]]


INSTRUCTION = re.compile(r"(%\S+) = (.*?)\s([a-z][\w\-]*)\(")


def opcode(name: str) -> str:
    """The opcode of a TPU op, whose trace name is its whole HLO
    instruction (``%fusion.1 = u8[..] fusion(u8[..] %all-gather.6), ...``
    is a ``fusion``); a name that is no instruction is its own opcode."""
    m = INSTRUCTION.match(name)
    return m.group(3) if m else name


def label(name: str) -> str:
    """A TPU op's trace name is its whole HLO instruction; keep the
    instruction's name, opcode and output shape, without layouts:
    ``%fusion.81 fusion f32[2,2,1024,4096]``."""
    m = INSTRUCTION.match(name)
    if not m:
        return name
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    target = re.search(r'custom_call_target="([^"]+)"', name)
    extra = f" {target.group(1)}" if target else ""
    return f"{m.group(1)} {m.group(3)}{extra} {shape}"


def _self_times(events: List[Event]) -> None:
    """Fill ``self_time``: duration less that of directly nested events."""
    events.sort(key=lambda e: (e.start, -e.dur))
    stack: List[Event] = []
    for e in events:
        e.self_time = e.dur
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            stack[-1].self_time -= e.dur
        stack.append(e)


def _event(ev) -> Event:
    return Event(ev.name, float(ev.start_ns), float(ev.duration_ns),
                 dict(ev.stats))


def load(path: str, cpu: bool = False) -> Trace:
    """Read one ``.xplane.pb``.  The device operations come from the
    device planes' ``XLA Ops`` lines, or with ``cpu`` from the host's."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    host_ops: Dict[int, List[Event]] = defaultdict(list)
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = [l for l in plane.lines if l.name == "XLA Ops"]
            if lines:
                ops[len(ops)] = [_event(e) for e in lines[0].events]
            elif CORE.fullmatch(plane.name):
                raise ValueError(f"{plane.name} has no XLA Ops line")
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(_event(e))
                    elif cpu:
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            host_ops[int(st.get("device_ordinal", 0))].append(
                                Event(e.name, float(e.start_ns),
                                      float(e.duration_ns), st))
    if cpu:
        ops = dict(host_ops)
    elif not ops:
        raise ValueError(f"{path} holds no device operations")
    for evs in ops.values():
        _self_times(evs)
    return Trace(ops=ops, spans=spans)


def find_xplane(directory: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None
