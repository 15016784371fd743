"""Hierarchical span tracing with Chrome trace-event export (DESIGN.md §11).

A process-wide :class:`Tracer` records *spans* — named intervals with a
category and optional args — and exports them as Chrome trace-event JSON
(the ``{"traceEvents": [...]}`` dict format) that loads directly in
Perfetto / ``chrome://tracing``.

Two kinds of time coexist in one trace:

* **wall clock** (pid 0): ``tracer.span(...)`` context-managers measure
  host time via ``perf_counter`` — planner searches, jit lowering,
  bench cells.
* **simulated time** (one pid per sim run, allocated with
  :meth:`Tracer.new_track`): the network simulator replays its virtual
  clock as explicit ``add_span(name, t0_s, t1_s)`` calls, so a
  simulated job renders as a timeline of per-level ingest /
  transport-drain lanes even though the whole thing executed in
  milliseconds of host time.

Timestamps are exported in microseconds (the trace-event unit);
fractional values are allowed and preserved.

A wall span has a second sink: while a JAX profiler capture is running
(``jax.profiler.start_trace`` / ``trace``), ``span()`` also opens a
``jax.profiler.TraceAnnotation`` of the same name, whatever the tracer's
own state, with the span's args as the event's stats.  The span then
lands on the host plane of the captured ``.xplane.pb``, on the same
clock as the device's operations.  Capturing a profile is the only
switch; jax is looked up only once something else has imported it.
Each collection of Python's cyclic garbage collector is a ``python.gc``
span (arg ``generation``) in both sinks while either is on.

Zero overhead when disabled: with the tracer off and no capture running,
``span()`` returns a module-level no-op singleton without allocating, and
``add_span`` returns before touching any state.  ``tests/test_obs.py``
pins both the zero-entry and the zero-allocation behaviour;
``bench_sim.py``'s ``obs_overhead`` cell floor-gates the throughput of
the disabled path in CI.

Stdlib-only on purpose: every layer (core, net, train, tools) may import
this module without creating cycles or dragging in jax.
"""

from __future__ import annotations

import contextlib
import gc
import json
import sys
import time
from typing import Any, Iterator, Optional

__all__ = [
    "Tracer",
    "get_tracer",
    "set_tracer",
    "scoped_tracer",
    "enable",
    "disable",
    "capturing",
]

#: wall-clock track: every ``span()`` context-manager lands here.
WALL_PID = 0
#: first pid handed out by :meth:`Tracer.new_track` for virtual-time tracks.
_FIRST_TRACK_PID = 1


class _NullSpan:
    """No-op context manager returned by a disabled tracer (singleton)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

#: ``jax.profiler.TraceAnnotation``, once jax has been imported
_annotation = None


def capturing() -> bool:
    """Whether a JAX profiler capture is running.  Never imports jax: a
    process that has not imported it cannot be capturing."""
    global _annotation
    if _annotation is None:
        profiler = sys.modules.get("jax.profiler")
        _annotation = getattr(profiler, "TraceAnnotation", None)
        if _annotation is None:
            return False
    return _annotation.is_enabled()


def _annotate(name: str, args: Optional[dict]):
    """A TraceAnnotation for the running capture; args become its stats."""
    return _annotation(name, **args) if args else _annotation(name)


class _Span:
    """Live wall-clock span; appends one complete ("X") event on exit, and
    annotates the running JAX profiler capture, if any."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_tid", "_t0", "_ann")

    def __init__(self, tracer, name, cat, args, tid):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._tid = tid
        self._ann = _annotate(name, args) if capturing() else None
        self._t0 = time.perf_counter()

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        t0 = (self._t0 - tr._epoch) * 1e6
        dur = (time.perf_counter() - self._t0) * 1e6
        if self._ann is not None:
            self._ann.__exit__(*exc)
        ev = {"name": self._name, "cat": self._cat, "ph": "X",
              "ts": t0, "dur": dur, "pid": WALL_PID, "tid": self._tid}
        if self._args:
            ev["args"] = self._args
        tr.events.append(ev)
        return False


class Tracer:
    """Collects trace events; exports Chrome trace-event JSON.

    Disabled by default.  All record methods are no-ops while disabled;
    ``enable()``/``disable()`` flip recording without losing prior events.
    """

    def __init__(self, enabled: bool = False):
        self.enabled = bool(enabled)
        self.events: list[dict] = []
        self._epoch = time.perf_counter()
        self._next_pid = _FIRST_TRACK_PID
        self._meta: list[dict] = []

    # -- lifecycle ---------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        self.events.clear()
        self._meta.clear()
        self._next_pid = _FIRST_TRACK_PID
        self._epoch = time.perf_counter()

    # -- recording ---------------------------------------------------------
    def span(self, name: str, cat: str = "wall", args: Optional[dict] = None,
             tid: int = 0):
        """Wall-clock span context manager.  While a JAX profiler capture
        runs it also annotates the capture, enabled or not; with neither,
        the no-op singleton."""
        if self.enabled:
            return _Span(self, name, cat, args, tid)
        if capturing():
            return _annotate(name, args)
        return _NULL_SPAN

    def add_span(self, name: str, t0_s: float, t1_s: float, *,
                 cat: str = "sim", pid: int = WALL_PID, tid: int = 0,
                 args: Optional[dict] = None) -> None:
        """Record a span with explicit start/end times in *seconds*.

        Used by the simulator to replay virtual time: ``t0_s``/``t1_s``
        are simulated seconds, exported as microseconds on track ``pid``.
        """
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "X", "ts": t0_s * 1e6,
              "dur": max(t1_s - t0_s, 0.0) * 1e6, "pid": pid, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def add_wall_span(self, name: str, t0_perf: float, t1_perf: float, *,
                      cat: str = "wall", tid: int = 0,
                      args: Optional[dict] = None) -> None:
        """Record a wall-clock span from explicit ``perf_counter`` stamps
        (for callers that measured before deciding to record)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": (t0_perf - self._epoch) * 1e6,
              "dur": max(t1_perf - t0_perf, 0.0) * 1e6,
              "pid": WALL_PID, "tid": tid}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def new_track(self, name: str) -> int:
        """Allocate a fresh pid for a virtual-time track (e.g. one sim job).

        Each simulated job gets its own track so repeated runs never
        interleave partially-overlapping spans on one lane — nesting per
        (pid, tid) stays well-formed by construction.
        """
        pid = self._next_pid
        self._next_pid += 1
        self._meta.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "args": {"name": name}})
        return pid

    def name_thread(self, pid: int, tid: int, name: str) -> None:
        """Attach a human-readable lane name to (pid, tid)."""
        if not self.enabled:
            return
        self._meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": name}})

    # -- export ------------------------------------------------------------
    def to_chrome(self) -> dict:
        """Chrome trace-event JSON dict (loads in Perfetto)."""
        meta = [{"name": "process_name", "ph": "M", "pid": WALL_PID,
                 "tid": 0, "args": {"name": "wall-clock"}}]
        return {"traceEvents": meta + self._meta + self.events,
                "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


# -- process-wide default --------------------------------------------------

_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer (disabled until someone calls enable())."""
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Replace the process-wide tracer; returns the previous one."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev


@contextlib.contextmanager
def scoped_tracer(tracer: Optional[Tracer] = None) -> Iterator[Tracer]:
    """Temporarily install ``tracer`` (a fresh enabled one by default)."""
    t = Tracer(enabled=True) if tracer is None else tracer
    prev = set_tracer(t)
    try:
        yield t
    finally:
        set_tracer(prev)


def enable() -> Tracer:
    _TRACER.enable()
    return _TRACER


def disable() -> Tracer:
    _TRACER.disable()
    return _TRACER


# -- Python's cyclic garbage collector ---------------------------------------

_gc_span = None  # the open ``python.gc`` span of the collection under way


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``python.gc`` span per collection, in
    whichever sinks are on (none: nothing is allocated)."""
    global _gc_span
    if phase == "start":
        if _TRACER.enabled or capturing():
            _gc_span = _TRACER.span("python.gc", cat="gc",
                                    args={"generation": info["generation"]})
            _gc_span.__enter__()
    elif _gc_span is not None:
        span, _gc_span = _gc_span, None
        span.__exit__(None, None, None)


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)
