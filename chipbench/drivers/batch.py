"""Batch path: each job's whole stream sits on the device and runs through
``dataplane.run_cascade`` on the Pallas FPE kernel as one device program,
one job in flight.  The traffic mix gives no parameters to this path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.jobs import JobResult, span
from repro.core import dataplane

#: the ``dataplane`` entry point this path drives
ENTRY = "run_cascade"


def prepare(cell, keys, values, *, interpret: bool = False):
    """The job callable: one cascade over the device-resident stream."""
    keys, values = jnp.asarray(keys), jnp.asarray(values)
    sent = int(jnp.sum(keys != -1))

    def job() -> JobResult:
        with span("cascade"):
            res = jax.block_until_ready(dataplane.run_cascade(
                keys, values, cell.plan, backend="pallas",
                interpret=interpret))
        with span("result"):
            h = jax.device_get(res)
        return JobResult(
            keys=h.keys, values=h.values, n_in=int(h.n_in),
            n_out=int(h.n_out),
            level_in=tuple(int(x) for x in h.level_in),
            level_out=tuple(int(x) for x in h.level_out),
            records_sent=sent)

    return job
