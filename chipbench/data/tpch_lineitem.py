"""TPC-H lineitem rows as (l_orderkey, l_quantity), in dbgen's order.

Follows dbgen's rules for one load at scale factor ``scale_factor``:
1,500,000 orders per unit of scale; order ``i`` (1-based) gets the sparse
key of ``mk_sparse`` (the low 3 bits kept, 2 zero bits inserted above
them: 8 keys used in every 32); each order has 1 to 7 lineitems, uniform;
``l_quantity`` is uniform in 1..50.  Each order's lineitems are adjacent,
as dbgen writes them.  Rows are made in bulk on the host, where the
packet path keeps them.
"""

from __future__ import annotations

import numpy as np

SPARSE_KEEP = 3
SPARSE_BITS = 2


def order_keys(n_orders: int) -> np.ndarray:
    """dbgen's sparse order keys of orders 1..n_orders."""
    i = np.arange(1, n_orders + 1, dtype=np.int64)
    low = i & ((1 << SPARSE_KEEP) - 1)
    return (((i >> SPARSE_KEEP) << (SPARSE_BITS + SPARSE_KEEP)) | low)


def generate(config: dict, seed: int, prng_key):
    """(keys int32, values float32) numpy arrays of one pass over lineitem."""
    rng = np.random.Generator(np.random.PCG64(np.random.SeedSequence(seed)))
    n_orders = int(config["orders_per_scale"] * config["scale_factor"])
    lo, hi = config["lines_per_order"]
    lines = rng.integers(lo, hi + 1, size=n_orders)
    keys = np.repeat(order_keys(n_orders), lines)
    if keys.max() >= 2**31 - 1:
        raise ValueError("order keys exceed int32")
    qlo, qhi = config["quantity"]
    qty = rng.integers(qlo, qhi + 1, size=keys.shape[0])
    return keys.astype(np.int32), qty.astype(np.float32)
