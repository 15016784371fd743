"""Faults planted under the timed path, each of which the comparison that
decides ``correct`` has to catch.

``plant(fault, entry, setattr)`` breaks the program in place: ``entry`` is
the ``dataplane`` function the cell's driver calls (its ``ENTRY``), and
``setattr`` is the builtin or a test's ``monkeypatch.setattr``.  The CPU
tests plant each fault in a tiny cell; ``readings.py --fault`` plants one
at a cell's own size on the chip.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import dataplane

FAULTS = ("state_unchanged", "half_batch", "answer_altered",
          "counter_altered")


def _wrap_result(change):
    def wrap(orig):
        def wrapped(*a, **kw):
            return change(orig(*a, **kw))
        return wrapped
    return wrap


def _half_batch(entry):
    """Half of each job's records left out: the first half of a device
    stream, every other packet of a packet stream."""
    def wrap(orig):
        def wrapped(stream, *a, **kw):
            if entry == "run_cascade":
                n = stream.shape[0] // 2
                return orig(stream[:n], a[0][:n], *a[1:], **kw)
            return orig((b for i, b in enumerate(stream) if i % 2), *a, **kw)
        return wrapped
    return wrap


def _stale_stream_state(setattr):
    """Every ingest starts from the table the level had before it."""
    orig = dataplane.LevelState._ingest_chunk

    def stale(self, *a):
        tk, tv = self._tk, self._tv
        out = orig(self, *a)
        self._tk, self._tv = tk, tv
        return out

    setattr(dataplane.LevelState, "_ingest_chunk", stale)


def _stale_kernel_state(setattr):
    """The kernel hands back the table it started from (all empty)."""
    from repro.kernels import kv_aggregate

    orig = kv_aggregate.fpe_aggregate_pallas

    def stale(*a, **kw):
        tk, tv, ek, ev = orig(*a, **kw)
        return jnp.full_like(tk, -1), jnp.zeros_like(tv), ek, ev

    setattr(kv_aggregate, "fpe_aggregate_pallas", stale)


def plant(fault: str, entry: str, setattr=setattr) -> None:
    """Break the program under ``entry`` with ``fault`` (one of
    :data:`FAULTS`).  Clear JAX's caches afterwards, since jitted callers
    may have traced the function replaced."""
    if fault == "state_unchanged":
        (_stale_kernel_state if entry == "run_cascade"
         else _stale_stream_state)(setattr)
        return
    if fault == "half_batch":
        wrap = _half_batch(entry)
    elif fault == "answer_altered":
        wrap = _wrap_result(lambda r: r._replace(
            values=jnp.asarray(r.values).at[0].add(1.0)))
    elif fault == "counter_altered":
        # the leaf level counts one slot of padding as a record
        wrap = _wrap_result(lambda r: r._replace(n_in=r.n_in + 1))
    else:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    setattr(dataplane, entry, wrap(getattr(dataplane, entry)))
