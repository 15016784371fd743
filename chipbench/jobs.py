"""What a path driver hands back for one job, and the spans it records."""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.tracereduce import SPAN_PREFIX


@dataclasses.dataclass
class JobResult:
    """One job's grouped result on the host, and the cascade's counters.

    ``keys``/``values`` are the root output as the program returned it
    (``EMPTY_KEY`` = -1 where a slot is unused); ``n_in``/``n_out`` are
    the records entering the leaf level and leaving the last placed
    level; ``level_in``/``level_out`` the same per level, leaf to root;
    ``records_sent`` the real records the harness handed the program;
    ``ingest_s`` the seconds of each ingest (stream paths only).
    """

    keys: np.ndarray
    values: np.ndarray
    n_in: int
    n_out: int
    level_in: tuple[int, ...]
    level_out: tuple[int, ...]
    records_sent: int
    ingest_s: tuple[float, ...] = ()


def span(name: str):
    """A host span of the benchmark's own, on the profiler's clock."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
