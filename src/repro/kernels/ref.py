"""Pure-jnp oracles for the Pallas kernels.

Each kernel in this package has an exact reference here; kernel tests sweep
shapes/dtypes and assert_allclose kernel-vs-ref (interpret=True on CPU).

Op semantics are NOT defined here: the ``repro.core.aggops`` registry
(DESIGN.md §6) is the one source of truth for combine/identity/segment
reductions, re-exported below so kernel callers and tests resolve ops
through the same table the kernels compile against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import aggops
from repro.core import kvagg as _kvagg
from repro.core.aggops import AggOp, get as get_aggop, names as aggop_names
from repro.core.aggops import hash_key  # THE shared bucket hash (noqa: F401)

EMPTY_KEY = _kvagg.EMPTY_KEY


def fpe_aggregate_ref(keys, values, *, capacity: int, ways: int = 4,
                      op: str = "sum", exact_stream: bool = True):
    """Oracle for the FPE hash-combine kernel: the core.kvagg scan impl.

    The Pallas kernel processes the stream block-by-block with a persistent
    VMEM table — semantically identical to this element-sequential scan.
    ``exact_stream=False`` is the batched-block fast path oracle
    (DESIGN.md §8) matching the kernel wrapper's pre-combined mode: the
    resident tables are bit-identical, but the eviction STREAM SHAPES
    differ ([n + capacity] here vs the kernel's [n]) — compare fast modes
    by table and grouped totals, not elementwise eviction slots.
    """
    return _kvagg.fpe_aggregate(keys, values, capacity=capacity, ways=ways,
                                op=op, exact_stream=exact_stream)


def sorted_combine_ref(keys, values, *, op: str = "sum"):
    return _kvagg.sorted_combine(keys, values, op=op)


def segment_sum_ref(values: jnp.ndarray, segment_ids: jnp.ndarray, num_segments: int):
    return jax.ops.segment_sum(values, segment_ids, num_segments=num_segments)
