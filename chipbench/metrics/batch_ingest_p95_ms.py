"""95th percentile, over every ingest of the window, of the milliseconds
one host batch (the traffic's ``batch_records``, not one wire packet)
spends in the cascade (stream paths only)."""

import numpy as np


def read(w):
    xs = [t for j in w.jobs for t in j.ingest_s]
    if not xs:
        return None
    return float(np.percentile(xs, 95)) * 1e3
