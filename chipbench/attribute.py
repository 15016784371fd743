#!/usr/bin/env python3
"""Name the layer behind each idle gap and padded slot of a traced run.

    python3 chipbench/attribute.py --workload <cell> --seed <n> --seconds <s> [--keep <file.gz>]

Runs the cell as ``run.py --trace 1`` does, keeps the run's profiler trace
and reads from it, beside what ``tracereduce`` reads, the host events the
program records while a capture runs (DESIGN.md §11): its ``dataplane.*``
spans with their args, ``python.gc``, and JAX's own
``backend_compile_and_load``.  Prints the device's idle seconds by the
innermost span, the benchmark's or the program's, that held each gap; and
last one JSON object: the run's result line under ``result``, then the
readings below over the traced window.

* ``ingest_fetch_idle_ms`` / ``ingest_loop_idle_ms``: medians over the
  program's ``dataplane.ingest`` spans of the device-idle milliseconds
  inside their ``dataplane.fetch`` spans, and inside the ingest but
  outside them (padding, masking, dispatch);
* ``empty_slot_pct``: 100 x (1 - sum of ``real`` / sum of ``slots``) over
  the ``dataplane.fpe`` and ``dataplane.bpe`` spans;
* ``ingest_idle_in_program_pct``: the share of the device-idle time
  inside the benchmark's ``ingest`` spans that a program span inside
  them covers (the job's ``dataplane.run_cascade_stream`` holds them).

The benchmark's own runs never run this: ``tracereduce`` keeps the
benchmark's spans alone, so none of these is a per-layer metric of the
result line.  ``--keep`` writes the trace, gzipped, to a file.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import gzip
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import tracereduce  # noqa: E402

#: host events of the program's own, kept with their names whole
PROGRAM_PREFIX = "dataplane."
PROGRAM_NAMES = ("python.gc", "backend_compile_and_load")


@dataclasses.dataclass(frozen=True)
class ProgramSpan(tracereduce.Span):
    """One host event the program recorded, with its stats as ``args``."""

    args: dict = dataclasses.field(default_factory=dict)


def load_program_spans(path) -> list[ProgramSpan]:
    """The program's host events of a profiler trace file, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if (e.name.startswith(PROGRAM_PREFIX)
                        or e.name in PROGRAM_NAMES):
                    out.append(ProgramSpan(
                        e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns), dict(e.stats)))
    out.sort(key=lambda s: s.start)
    return out


def in_window(spans, r: tracereduce.Reduced) -> list:
    return [s for s in spans if s.end > r.lo and s.start < r.hi]


def idle_ns(r: tracereduce.Reduced, lo: int, hi: int) -> float:
    """Device-idle nanoseconds of ``[lo, hi]``, averaged over devices."""
    return (hi - lo) - r.busy_in(lo, hi) * 1e9


def ingest_idle(r: tracereduce.Reduced, spans) -> list[tuple[float, float]]:
    """(fetch, loop) device-idle milliseconds of each ``dataplane.ingest``
    span: inside its ``dataplane.fetch`` spans, and the rest."""
    fetches = [s for s in spans if s.name == "dataplane.fetch"]
    starts = [s.start for s in fetches]
    out = []
    for ing in (s for s in spans if s.name == "dataplane.ingest"):
        i = bisect.bisect_left(starts, ing.start)
        fetch = 0.0
        while i < len(fetches) and fetches[i].start < ing.end:
            f = fetches[i]
            fetch += idle_ns(r, f.start, min(f.end, ing.end))
            i += 1
        out.append((fetch / 1e6, (idle_ns(r, ing.start, ing.end) - fetch)
                    / 1e6))
    return out


def empty_slot_pct(spans) -> float | None:
    slots = real = 0
    for s in spans:
        if s.name in ("dataplane.fpe", "dataplane.bpe"):
            slots += int(s.args["slots"])
            real += int(s.args["real"])
    return 100.0 * (1.0 - real / slots) if slots else None


def idle_in(r: tracereduce.Reduced, outer, inner) -> tuple[float, float]:
    """Idle seconds of the first device inside the ``outer`` intervals, and
    how many of them the ``inner`` intervals cover too."""
    if not r.busy:
        return 0.0, 0.0
    inner = tracereduce.merge(inner)
    total = covered = 0
    for lo, hi in tracereduce.merge(outer):
        for s, e in tracereduce.gaps(r.busy[0], lo, hi):
            total += e - s
            covered += tracereduce.covered(inner, s, e)
    return total / 1e9, covered / 1e9


def readings(r: tracereduce.Reduced, spans) -> dict:
    """Every reading of the module's docstring, over the traced window."""
    spans = in_window(spans, r)
    per_ingest = ingest_idle(r, spans)
    out = {"program_spans": len(spans)}
    if per_ingest:
        out["ingests"] = len(per_ingest)
        out["ingest_fetch_idle_ms"] = statistics.median(
            f for f, _ in per_ingest)
        out["ingest_loop_idle_ms"] = statistics.median(
            lp for _, lp in per_ingest)
    pct = empty_slot_pct(spans)
    if pct is not None:
        out["empty_slot_pct"] = pct
    total, inside = idle_in(
        r, [(s.start, s.end) for s in r.spans if s.name == "ingest"],
        [(s.start, s.end) for s in spans
         if s.name != "dataplane.run_cascade_stream"])  # holds the ingests
    if total:
        out["ingest_idle_s"] = total
        out["ingest_idle_in_program_pct"] = 100.0 * inside / total
    gcs = [s for s in spans if s.name == "python.gc"]
    out["gc_collections"] = len(gcs)
    out["gc_s"] = sum((s.end - s.start) / 1e9 for s in gcs)
    out["compile_in_window_s"] = sum(
        (s.end - s.start) / 1e9 for s in spans
        if s.name == "backend_compile_and_load")
    return out


def labelled(r: tracereduce.Reduced, spans) -> tracereduce.Labeller:
    """A labeller over the benchmark's spans and the program's, these
    named with their ``what`` and ``level`` args where they have them:
    ``dataplane.fetch(evictions, level 1)``."""
    named = []
    for s in in_window(spans, r):
        tags = [str(s.args["what"])] if "what" in s.args else []
        if "level" in s.args:
            tags.append(f"level {s.args['level']}")
        name = f"{s.name}({', '.join(tags)})" if tags else s.name
        named.append(tracereduce.Span(name, s.start, s.end))
    return tracereduce.Labeller(list(r.spans) + named)


def idle_gaps(r: tracereduce.Reduced, spans) -> list[tuple[str, float]]:
    """Every idle gap of the first device, longest first, labelled by the
    innermost span, the benchmark's or the program's, at its midpoint."""
    if not r.busy:
        return []
    at = labelled(r, spans)
    return sorted(((at((s + e) // 2), (e - s) / 1e9)
                   for s, e in tracereduce.gaps(r.busy[0], r.lo, r.hi)),
                  key=lambda g: -g[1])


def idle_by_span(r: tracereduce.Reduced, spans) -> list[tuple[str, float]]:
    """Idle seconds of the first device by the innermost span that held
    each part of each gap (a gap across several spans is split), largest
    first."""
    if not r.busy:
        return []
    at = labelled(r, spans)
    edges = at.starts + [at.last]
    tot: dict[str, float] = {}

    def add(name, ns):
        tot[name] = tot.get(name, 0.0) + ns / 1e9

    for s, e in tracereduce.gaps(r.busy[0], r.lo, r.hi):
        i = bisect.bisect_right(edges, s) - 1
        t = s
        while t < e:
            if i < 0 or i >= len(at.starts):  # before or after every span
                nxt = edges[0] if i < 0 else e
                add(at.OUTSIDE, min(nxt, e) - t)
            else:
                nxt = edges[i + 1]
                add(at.names[i], min(nxt, e) - t)
            t, i = min(nxt, e), i + 1
    return sorted(tot.items(), key=lambda kv: -kv[1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=pathlib.Path)
    args = ap.parse_args()

    import jax

    from chipbench import harness, run

    cell = harness.load_cell(ROOT, args.workload)
    try:
        harness.check_devices(cell.chips)
    except harness.NoChip as e:
        print(f"attribute: {e}", file=sys.stderr)
        return 3
    run.CACHE.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    with tempfile.TemporaryDirectory() as tmp:
        path = pathlib.Path(tmp) / "trace.xplane.pb"
        result = harness.run_cell(cell, args.seed, args.seconds, True,
                                  t_start=T_START, keep_trace=path)
        if args.keep:
            with open(path, "rb") as src, gzip.open(args.keep, "wb") as dst:
                shutil.copyfileobj(src, dst)
        r = tracereduce.reduce(tracereduce.load(path))
        spans = load_program_spans(path)
    by_span = idle_by_span(r, spans)
    for name, sec in by_span[:12]:
        print(f"[attribute] idle {sec:.6f} s while {name}", file=sys.stderr)
    out = {"result": result, "readings": readings(r, spans),
           "idle_by_span": by_span,
           "longest_idle_gaps": idle_gaps(r, spans)[:20]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
