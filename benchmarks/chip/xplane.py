"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an XSpace (`*.xplane.pb`).  Its device planes
(`/device:TPU:<i>`) carry one line of XLA programs ("XLA Modules", one
event per execution, named after the jitted function) and one of the
operations inside them ("XLA Ops"); the host plane carries the
benchmark's own annotations (`bench:*`).  All share one clock, in ns.

`Trace` keeps those three kinds of event as plain (name, start, end)
tuples, so the arithmetic below runs the same on a recorded trace and on
one synthesised in a test.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench:"


@dataclasses.dataclass
class Trace:
    ops: dict            # device plane -> [(name, start, end)]
    programs: dict       # device plane -> [(name, start, end)]
    annotations: list    # [(name, start, end)] on the host

    def window(self, name: str = "bench:window") -> tuple:
        spans = [(s, e) for n, s, e in self.annotations if n == name]
        if not spans:
            raise ValueError(f"no {name!r} annotation in the trace")
        return min(s for s, _ in spans), max(e for _, e in spans)

    def spans(self, name: str) -> list:
        return sorted((s, e) for n, s, e in self.annotations if n == name)


def from_planes(planes) -> Trace:
    """Build a Trace from jax.profiler.ProfileData planes."""
    ops, programs, notes = defaultdict(list), defaultdict(list), []
    for plane in planes:
        device = plane.name.startswith("/device:") and \
            not plane.name.startswith("/device:CUSTOM")
        for line in plane.lines:
            for ev in line.events:
                item = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if device and line.name == OP_LINE:
                    ops[plane.name].append(item)
                elif device and line.name == PROGRAM_LINE:
                    programs[plane.name].append(item)
                elif plane.name.startswith("/host:") and \
                        ev.name.startswith(ANNOTATION_PREFIX):
                    notes.append(item)
    return Trace(dict(ops), dict(programs), sorted(notes, key=lambda t: t[1]))


def load(log_dir: str) -> Trace:
    """The Trace of the one xplane file the profiler wrote under log_dir."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one xplane file under {log_dir}, "
                         f"found {len(paths)}")
    return from_planes(ProfileData.from_file(paths[0]).planes)


# ----------------------------------------------------------- interval math

def merged(intervals, lo: float, hi: float) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    gaps, cursor = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


# ------------------------------------------------------- what metrics read

def device_busy_s(trace: Trace) -> float:
    """Seconds some operation ran, averaged over the devices used."""
    lo, hi = trace.window()
    per = [busy_ns([(s, e) for _, s, e in evs], lo, hi)
           for evs in trace.ops.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def idle_share(trace: Trace) -> float | None:
    """1 - busy / window, over the traced window, averaged over devices."""
    if not trace.ops:
        return None
    return 1.0 - device_busy_s(trace) / window_s(trace)


def program_events(trace: Trace, name: str) -> list:
    """(start, end) of every execution of programs whose name holds
    `name`, inside the window, on every device."""
    lo, hi = trace.window()
    return sorted((s, e) for evs in trace.programs.values()
                  for n, s, e in evs if name in n and lo <= s < hi)


def program_time_s(trace: Trace, name: str) -> float:
    """Device seconds of `name`'s executions, averaged over devices."""
    n_dev = max(1, len(trace.programs))
    return sum(e - s for s, e in program_events(trace, name)) / n_dev / 1e9


def lead_in(trace: Trace, span: str, program: str) -> list:
    """For each `span` annotation: (ns from its start to the first
    `program` execution in it, programs that started before that one on
    the first device).  Spans with no such execution are left out."""
    firsts = program_events(trace, program)
    device = sorted(trace.programs)[0] if trace.programs else None
    starts = sorted(s for _, s, _ in trace.programs.get(device, ()))
    out = []
    for lo, hi in trace.spans(span):
        first = next((s for s, _ in firsts if lo <= s < hi), None)
        if first is not None:
            out.append((first - lo, sum(1 for s in starts if lo <= s < first)))
    return out


def self_times(events) -> list:
    """[(name, self ns)]: each event's duration less the parts of it that
    events nested inside it cover (a loop's body runs inside its while).
    Events that only overlap count as siblings."""
    out, stack = [], []            # stack: [name, start, end, child ns]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and (s >= stack[-1][2] or e > stack[-1][2]):
            done = stack.pop()
            out.append((done[0], done[2] - done[1] - done[3]))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    out += [(n, e - s - c) for n, s, e, c in stack]
    return out


def short_name(hlo: str) -> str:
    """`%fusion.610 = s32[50,153650]{...} fusion(...)` -> `%fusion.610
    fusion s32[50,153650]`: the instruction, its opcode and its shape."""
    if " = " not in hlo:
        return hlo
    lhs, rhs = hlo.split(" = ", 1)
    op = re.search(r"\s([\w-]+)\(", rhs)
    shape = rhs.split("{", 1)[0] if not rhs.startswith("(") else "tuple"
    return f"{lhs} {op.group(1) if op else '?'} {shape}"


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[op, device seconds]]: the n operations with the most self time in
    the window, averaged over devices."""
    lo, hi = trace.window()
    tot = defaultdict(float)
    n_dev = max(1, len(trace.ops))
    for evs in trace.ops.values():
        inside = [(nm, s, min(e, hi)) for nm, s, e in evs if lo <= s < hi]
        for name, ns in self_times(inside):
            tot[short_name(name)] += ns / 1e9 / n_dev
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def attributed_gaps(trace: Trace, phases, cuts=(), n: int = 10) -> list:
    """[[host activity, idle seconds]]: the device's idle time in the
    window, split at the times in `cuts` and summed by what the host was
    doing in each piece; `phases(trace, t)` names the activity at t."""
    lo, hi = trace.window()
    cuts = sorted(cuts)
    tot = defaultdict(float)
    for evs in trace.ops.values():
        for s, e in idle_gaps([(s, e) for _, s, e in evs], lo, hi):
            edges = [s] + [c for c in cuts if s < c < e] + [e]
            for a, b in zip(edges, edges[1:]):
                tot[phases(trace, (a + b) / 2)] += \
                    (b - a) / 1e9 / len(trace.ops)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
