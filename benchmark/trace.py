"""Reduction of one traced window to the numbers the per-layer readers
take: host spans, device busy time, device time per program and per
layer, and the idle gaps labelled with what the host was doing.

A trace is plain data here (`Trace`), so a test can build one by hand;
`load_xplane` fills it from the profiler's `.xplane.pb`.
"""
from __future__ import annotations

import fnmatch
import glob
import os
from dataclasses import dataclass, field

SPAN_PREFIX = "bench:"
WINDOW = "window"


@dataclass
class Trace:
    # (span name, host thread, start ns, end ns) of the harness's spans
    spans: list = field(default_factory=list)
    # chip -> [(program name, start ns, end ns)]: one event per program
    # run; the device is busy while a program runs
    programs: dict = field(default_factory=dict)


def load_xplane(directory: str) -> Trace:
    """The newest `.xplane.pb` under `directory`, as a `Trace`."""
    import jax

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name.rsplit(":", 1)[-1]
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out.programs[chip] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.spans.append((e.name[len(SPAN_PREFIX):],
                                          line.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return out


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


@dataclass
class Reduced:
    window: tuple                 # (start, end) ns of the traced window
    thread: str                   # the host thread that drove it
    spans: list                   # spans inside the window
    busy_ns: float                # mean over chips of the busy union
    program_ns: dict              # program -> device ns (mean over chips)
    layer_ns: dict                # layer -> device ns (mean over chips)
    unassigned: dict              # program -> ns, matched by no layer
    gaps: list                    # [(label, ns)] longest idle gaps first

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def layer_of(program: str, layers: dict) -> str | None:
    for layer, patterns in layers.items():
        if any(fnmatch.fnmatchcase(program, p) for p in patterns):
            return layer
    return None


def reduce(trace: Trace, layers: dict) -> Reduced:
    """Reduce `trace` over its window span; `layers` maps a layer name
    to program-name patterns (fnmatch)."""
    windows = [s for s in trace.spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} window spans in the trace")
    _name, thread, lo, hi = windows[0]
    spans = [s for s in trace.spans if s[0] != WINDOW and s[3] > lo
             and s[2] < hi]
    chips = sorted(trace.programs)
    busy, prog_ns, layer_ns, unassigned = 0, {}, {}, {}
    busy_union = []
    for chip in chips:
        progs = [(n, max(s, lo), min(e, hi))
                 for n, s, e in trace.programs[chip] if e > lo and s < hi]
        intervals = union([(s, e) for _n, s, e in progs])
        busy += covered(intervals)
        busy_union += intervals
        for name, s, e in progs:
            prog_ns[name] = prog_ns.get(name, 0) + e - s
    n = max(len(chips), 1)
    prog_ns = {k: v / n for k, v in prog_ns.items()}
    for name, ns in prog_ns.items():
        layer = layer_of(name, layers)
        if layer is None:
            unassigned[name] = ns
        else:
            layer_ns[layer] = layer_ns.get(layer, 0) + ns
    idle, at = [], lo
    for s, e in union(busy_union):
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if at < hi:
        idle.append((at, hi))
    gaps = sorted(((host_label(spans, thread, (s + e) // 2), e - s)
                   for s, e in idle), key=lambda g: -g[1])
    return Reduced(window=(lo, hi), thread=thread, spans=spans,
                   busy_ns=busy / n, program_ns=prog_ns, layer_ns=layer_ns,
                   unassigned=unassigned, gaps=gaps)


def host_label(spans, thread: str, t: int) -> str:
    """The innermost harness span of `thread` open at time `t`."""
    open_ = [s for s in spans if s[1] == thread and s[2] <= t < s[3]]
    if not open_:
        return "harness"
    return max(open_, key=lambda s: (s[2], -s[3]))[0]


def self_ns(spans, name: str, below) -> int:
    """Time in spans `name` minus the part of it that the spans named in
    `below` (the layers it calls, on any thread) cover."""
    total = 0
    for sname, _thread, s, e in spans:
        if sname == name:
            total += (e - s) - covered(clip(
                [(cs, ce) for cn, _ct, cs, ce in spans if cn in below], s, e))
    return total


def span_ns(spans, names) -> int:
    """Wall time covered by any span in `names`, on any thread."""
    return covered([(s, e) for n, _t, s, e in spans if n in names])
