"""Plain reference of ``select key, sum(value) ... group by key``.

``totals`` is the reference: a float64 ``bincount`` on the host, which
imports nothing of the program.  ``control`` is the same group-by computed
in bfloat16 on the device (the nearest precision below the configuration's
float32), which the comparison has to refuse.
"""

from __future__ import annotations

import numpy as np

#: carried value lanes of the op (one float32 per pair)
LANES = 1


def totals(keys: np.ndarray, values: np.ndarray):
    """(sorted distinct keys, float64 total of each)."""
    keys = np.asarray(keys)
    counts = np.bincount(keys)
    sums = np.bincount(keys, weights=np.asarray(values, np.float64))
    ids = np.flatnonzero(counts)
    return ids, sums[ids]


def control(keys: np.ndarray, values: np.ndarray):
    """The same group-by with every total accumulated in bfloat16."""
    import jax
    import jax.numpy as jnp

    keys = np.asarray(keys)
    n = int(keys.max()) + 1
    k = jnp.asarray(keys)
    s = jax.ops.segment_sum(jnp.asarray(values).astype(jnp.bfloat16), k,
                            num_segments=n)
    c = jax.ops.segment_sum(jnp.ones(k.shape, jnp.int32), k, num_segments=n)
    ids = np.flatnonzero(np.asarray(c))
    return ids, np.asarray(s.astype(jnp.float32), np.float64)[ids]
