"""jit'd public wrappers for the Pallas kernels.

``two_level_aggregate`` is the full SwitchAgg node: the Pallas FPE kernel
(VMEM hash table, evict-on-collision) feeding a BPE bulk combine
(sort + segment reduce over the eviction stream — the large/slow memory
level, overlapped with the next FPE block on real hardware).

Op semantics resolve through the ``core.aggops`` registry (DESIGN.md §6);
any registered op — including multi-lane carried ops like ``mean`` — works
here, and ``n_out`` follows the forwarded-pairs traffic invariant
documented on ``core.kvagg.TwoLevelResult``.  Multi-level plans run via
``core.dataplane.run_cascade(backend="pallas")``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kvagg as _kvagg
from .kv_aggregate import fpe_aggregate_pallas

EMPTY_KEY = _kvagg.EMPTY_KEY


class TwoLevelOut(NamedTuple):
    out_keys: jnp.ndarray
    out_values: jnp.ndarray
    n_out: jnp.ndarray
    n_in: jnp.ndarray
    n_evict: jnp.ndarray


@functools.partial(
    jax.jit,
    static_argnames=("capacity", "ways", "op", "block_n", "bpe",
                     "exact_stream", "interpret"),
)
def two_level_aggregate(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    capacity: int,
    ways: int = 4,
    op: str = "sum",
    block_n: int = 512,
    bpe: bool = True,
    exact_stream: bool = True,
    interpret: bool = False,
) -> TwoLevelOut:
    """SwitchAgg node with the Pallas FPE (kernel) + BPE (bulk combine).

    Node assembly/accounting delegates to ``kvagg.assemble_node`` — the one
    copy of the policy shared with the jnp node and the cascade executor.
    ``exact_stream=False`` pre-combines each block before the kernel
    (DESIGN.md §8 fast path): identical grouped output, different
    eviction pattern.
    """
    tk, tv, ek, ev = fpe_aggregate_pallas(
        keys, values, capacity=capacity, ways=ways, op=op, block_n=block_n,
        exact_stream=exact_stream, interpret=interpret,
    )
    return TwoLevelOut(*_kvagg.assemble_node(keys, tk, tv, ek, ev,
                                             op=op, bpe=bpe))

