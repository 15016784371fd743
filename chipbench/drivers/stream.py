"""Stream path: each job's rows are host-side batches of ``batch_records``
records, ingested through ``dataplane.run_cascade_stream`` on the
sort-based fast path, with the level tables persisting across ingests and
flushed at the end of the job.

A batch is a host batching choice, not one packet on the wire: at the
system's own MTU framing (``repro.net.wire.RECORDS_PER_PACKET``, 59
records) a 32,767-record batch is about 555 packets.  The last batch is
filled up with ``EMPTY_KEY`` (-1) records, so every ingest has one shape.
One ingest is timed from the moment the batch iterator hands it to the
cascade to the moment the cascade asks for the next; the end-of-job flush
is not an ingest.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from chipbench.jobs import JobResult, span
from repro.core import dataplane

#: the ``dataplane`` entry point this path drives
ENTRY = "run_cascade_stream"


def batches(keys: np.ndarray, values: np.ndarray, size: int):
    """The stream cut into ``size``-record batches, the last one filled."""
    fill = (-keys.shape[0]) % size
    keys = np.concatenate([keys, np.full((fill,), -1, np.int32)])
    values = np.concatenate([values, np.zeros((fill,), values.dtype)])
    return [(keys[i:i + size], values[i:i + size])
            for i in range(0, keys.shape[0], size)]


def prepare(cell, keys, values, *, interpret: bool = False):
    """The job callable: one pass of the batches through the cascade."""
    size = cell.traffic["batch_records"]
    keys = np.asarray(keys, np.int32)
    values = np.asarray(values, np.float32)
    sent = int(np.sum(keys != -1))
    parts = batches(keys, values, size)

    def job() -> JobResult:
        ingest_s: list[float] = []
        flush = contextlib.ExitStack()

        def feed():
            for k, v in parts:
                with span("ingest"):
                    t0 = time.perf_counter()
                    yield k, v
                    ingest_s.append(time.perf_counter() - t0)
            flush.enter_context(span("flush"))

        with flush:
            res = dataplane.run_cascade_stream(
                feed(), cell.plan, batch_pad=size, exact_stream=False)
        with span("result"):
            h = jax.device_get(res)
        return JobResult(
            keys=h.keys, values=h.values, n_in=int(h.n_in),
            n_out=int(h.n_out),
            level_in=tuple(int(x) for x in h.level_in),
            level_out=tuple(int(x) for x in h.level_out),
            records_sent=sent, ingest_s=tuple(ingest_s))

    return job
