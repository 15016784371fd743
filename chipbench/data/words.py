"""Word-count stream: word ids over a finite vocabulary, each with the
value 1, drawn on the device by inverse CDF from the seed.

The word of rank ``r`` (0-based) has probability ``(r + 1) ** -skew``
normalised over the vocabulary: Zipf(skew), as in the paper's Zipf
analysis and ``repro.core.reduction_model.zipf_keys``; ``skew`` 0 draws
every word alike, as Hadoop's RandomTextWriter does.  The CDF is cut into 2**32
steps on the host in float64 (it does not depend on the seed), and one
jitted call turns 32-bit uniform draws into word ids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def cdf_steps(vocabulary: int, skew: float) -> np.ndarray:
    """uint32 upper edge of each word's share of [0, 2**32)."""
    p = np.arange(1, vocabulary + 1, dtype=np.float64) ** -skew
    c = np.cumsum(p)
    c /= c[-1]
    return np.minimum(np.floor(c * 2.0**32), 2.0**32 - 1).astype(np.uint32)


@functools.partial(jax.jit, static_argnames=("records",))
def _draw(key, steps, records: int):
    u = jax.random.bits(key, (records,), jnp.uint32)
    ids = jnp.searchsorted(steps, u, side="right")
    return (jnp.minimum(ids, steps.shape[0] - 1).astype(jnp.int32),
            jnp.ones((records,), jnp.float32))


def generate(config: dict, seed: int, prng_key):
    """(keys, values) on the device: ``config["records"]`` draws."""
    steps = jnp.asarray(cdf_steps(config["vocabulary"], config["skew"]))
    return _draw(prng_key, steps, config["records"])
