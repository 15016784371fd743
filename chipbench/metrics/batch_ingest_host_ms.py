"""Median, over the traced ingests, of an ingest's wall time that no device
operation covers: the host's round trips and numpy work per host batch."""

import numpy as np


def read(w):
    t = w.trace
    if t is None or not t.busy:
        return None
    host = [(s.end - s.start) / 1e9 - t.busy_in(s.start, s.end)
            for s in t.spans if s.name == "ingest"]
    if not host:
        return None
    return float(np.median(host)) * 1e3
