"""Reduce a JAX profiler trace to the intervals the metrics read.

A trace (``.xplane.pb``) holds device planes (``/device:TPU:<n>``), whose
``XLA Ops`` line has one event per device operation, and host planes,
whose threads carry the ``jax.profiler.TraceAnnotation`` spans the
benchmark puts around each job, ingest, flush and result transfer.  Both
are on one clock, in nanoseconds.  Everything here is plain arithmetic on
``(start, end)`` pairs, so a recorded trace checks it on the CPU.
"""

from __future__ import annotations

import bisect
import dataclasses
import itertools
import pathlib
import re

#: prefix of every host span the benchmark itself records
SPAN_PREFIX = "chipbench."
#: the line of a device plane that holds one event per device operation
OPS_LINE = "XLA Ops"
#: the line that holds one event per run of a compiled program
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    """One device operation: a short name (``%fusion.12 fusion s32[4096]``),
    the HLO text it came from, its interval in nanoseconds, and its self
    time (what the operations nested inside it, such as a while loop's
    body, do not cover)."""

    name: str
    long_name: str
    start: int
    end: int
    self_ns: int = 0


@dataclasses.dataclass(frozen=True)
class Span:
    """One benchmark host span (name without ``SPAN_PREFIX``)."""

    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    """Device operations per device plane, and the benchmark's host spans."""

    ops: dict[str, list[Op]]
    spans: list[Span]

    def window(self) -> tuple[int, int] | None:
        """From the first traced job's start to the last one's end."""
        jobs = [s for s in self.spans if s.name == "job"]
        if not jobs:
            return None
        return min(s.start for s in jobs), max(s.end for s in jobs)


def find_xplane(trace_dir: str | pathlib.Path) -> pathlib.Path:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(found)}")
    return found[0]


def short_name(text: str) -> str:
    """``%name opcode first-shape`` of an HLO instruction's text."""
    head, _, rest = text.partition(" = ")
    opcode = re.search(r"[})\]] ([a-z][a-z0-9-]*)\(", rest)
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    return " ".join([head] + [m.group(1) if m is opcode else m.group(0)
                              for m in (opcode, shape) if m])


def _in_modules(ops: list[Op], modules) -> list[Op]:
    """Prefix each op's name with the program (``jit_...``) it ran in."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.end <= modules[i][1]:
            o.name = f"{modules[i][2]}/{o.name}"
    return ops


def _self_times(ops: list[Op]) -> None:
    """Each op's duration less that of the ops nested directly inside it."""
    stack: list[Op] = []
    for o in ops:
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)


def load(path: str | pathlib.Path) -> Trace:
    """Read a profiler trace file into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[str, list[Op]] = {}
    spans: list[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = [(int(e.start_ns), int(e.start_ns + e.duration_ns),
                        e.name.split("(")[0])
                       for e in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else ())]
            ops[plane.name] = _in_modules(
                [Op(short_name(e.name), e.name, int(e.start_ns),
                    int(e.start_ns + e.duration_ns))
                 for e in lines[OPS_LINE].events], modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name[len(SPAN_PREFIX):],
                                          int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    for v in ops.values():
        v.sort(key=lambda o: (o.start, -o.end))
        _self_times(v)
    spans.sort(key=lambda s: s.start)
    return Trace(ops, spans)


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals, as sorted disjoint intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi]`` that the merged intervals cover."""
    i = max(0, bisect.bisect_right(merged, (lo, lo)) - 1)
    total = 0
    for s, e in itertools.islice(merged, i, None):
        if s >= hi:
            break
        total += max(0, min(e, hi) - max(s, lo))
    return total


def gaps(merged: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """The parts of ``[lo, hi]`` that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Labeller:
    """What the host was doing at an instant: the innermost benchmark span
    that holds it.  The spans are cut once into elementary segments, so a
    lookup is one binary search."""

    OUTSIDE = "outside the benchmark's spans"

    def __init__(self, spans: list[Span]):
        opening: dict[int, list[Span]] = {}
        for s in spans:
            opening.setdefault(s.start, []).append(s)
        points = sorted({t for s in spans for t in (s.start, s.end)})
        self.starts, self.names, live = [], [], []
        for a, b in zip(points, points[1:]):
            live = [s for s in live if s.end > a] + opening.get(a, [])
            self.starts.append(a)
            self.names.append(min(live, key=lambda s: s.end - s.start).name
                              if live else self.OUTSIDE)
        self.last = points[-1] if points else 0

    def __call__(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0 or t >= self.last:
            return self.OUTSIDE
        return self.names[i]


@dataclasses.dataclass
class Reduced:
    """What the metrics read from one traced window."""

    lo: int
    hi: int
    busy: list[list[tuple[int, int]]]  # merged op intervals per device
    ops: list[Op]  # every op of every device, inside the window
    spans: list[Span]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(covered(m, self.lo, self.hi)
                   for m in self.busy) / len(self.busy) / 1e9

    def busy_in(self, lo: int, hi: int) -> float:
        """Device-busy seconds inside ``[lo, hi]``, averaged over devices."""
        if not self.busy:
            return 0.0
        return sum(covered(m, lo, hi) for m in self.busy) / len(self.busy) / 1e9

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Every idle gap of the first device, labelled by the benchmark
        span that held its midpoint, longest first."""
        if not self.busy:
            return []
        at = Labeller(self.spans)
        return sorted(((at((s + e) // 2), (e - s) / 1e9)
                       for s, e in gaps(self.busy[0], self.lo, self.hi)),
                      key=lambda g: -g[1])

    def idle_by_span(self) -> list[tuple[str, float]]:
        """Idle seconds of the first device summed by the span that held
        each gap, largest first."""
        tot: dict[str, float] = {}
        for name, sec in self.idle_gaps():
            tot[name] = tot.get(name, 0.0) + sec
        return sorted(tot.items(), key=lambda kv: -kv[1])

    def top_ops(self, n: int = 10) -> list[tuple[str, float]]:
        """Device operations by self seconds in the window, summed by name
        and over devices."""
        tot: dict[str, float] = {}
        for o in self.ops:
            tot[o.name] = tot.get(o.name, 0.0) + o.self_ns / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def reduce(trace: Trace) -> Reduced | None:
    """Clip the trace to its job window; None where it has no job span."""
    w = trace.window()
    if w is None:
        return None
    lo, hi = w
    busy, ops = [], []
    for plane in sorted(trace.ops):
        inside = [o for o in trace.ops[plane] if o.end > lo and o.start < hi]
        ops.extend(inside)
        busy.append(merge((o.start, o.end) for o in inside))
    return Reduced(lo, hi, busy, ops,
                   [s for s in trace.spans if s.end > lo and s.start < hi])
