"""AggOp registry — the single source of truth for aggregation-operator
semantics (DESIGN.md §6).

The paper's processing engine is parameterized by its reduction function
(§2: SUM/MAX/MIN word-count-style combines); Flare-style flexible reduction
support means the op set must be pluggable, not string-dispatched in every
execution layer.  Every aggregation path in this repo — the pure-jnp FPE
scan and BPE sorted combine (``core.kvagg``), the Pallas FPE kernel
(``kernels.kv_aggregate``), and the plan-driven cascade executor
(``core.dataplane``) — resolves its op HERE, statically at trace time, so
kernels stay specialized while op semantics live in exactly one place.

An :class:`AggOp` carries:

  * ``combine(a, b)``     — the elementwise merge applied when two values of
                            the same key meet (per carried lane).
  * ``identity(dtype)``   — the dtype-aware neutral element.  max/min use
                            ``jnp.finfo``/``jnp.iinfo`` bounds, NOT ±inf,
                            which does not exist for integer value dtypes.
  * ``lanes``             — carried value lanes.  ``mean`` carries paired
                            (sum, count) lanes: the paper's word-count
                            semantics generalized, combined lane-wise by a
                            plain add and divided only at ``finalize``.
  * ``prepare(values)``   — user values -> carried representation (e.g.
                            ``count`` maps every record to 1, ``mean``
                            stacks (value, 1) lanes).
  * ``finalize(values)``  — carried representation -> user-visible result
                            at the root of the cascade.
  * ``segment_reduce``    — the bulk (BPE / sorted-combine) form of
                            ``combine`` over sorted segments.

Associativity + commutativity of ``combine`` is the contract every
registered op must honor — it is what makes multi-level cascades exact
(Theorem 2.1) — and is what the property tests in
``tests/test_dataplane.py`` check for every registered op.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Shared key hash.
# ---------------------------------------------------------------------------

#: Knuth/Fibonacci multiplicative hash constant — THE one copy shared by the
#: jnp FPE (``core.kvagg``) and the Pallas kernel (``kernels.kv_aggregate``),
#: so the two bucket functions cannot drift apart.
HASH_MULT = 0x9E3779B1


def hash_key(key: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Multiplicative hash of int32 keys into [0, n_buckets).

    Pure jnp, traceable both in regular jax programs and inside Pallas
    kernel bodies (``n_buckets`` is a trace-time python int in both).
    """
    h = key.astype(jnp.uint32) * jnp.uint32(HASH_MULT)
    h = h ^ (h >> jnp.uint32(15))
    return (h % jnp.uint32(n_buckets)).astype(jnp.int32)


def _bound_identity(dtype, kind: str) -> jnp.ndarray:
    """Dtype-aware max/min identity: finfo/iinfo bounds, never ±inf.

    ``-inf`` cast to an integer dtype is undefined (and wrong even where it
    "works": it wraps to implementation-defined garbage), so int32 MAX
    aggregation with a ±inf identity silently corrupts padding slots.
    """
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        info = jnp.finfo(dtype)
    elif jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
    else:
        raise TypeError(f"unsupported value dtype for max/min: {dtype}")
    return jnp.array(info.min if kind == "max" else info.max, dtype)


def _as_float(values: jnp.ndarray) -> jnp.ndarray:
    """Carried dtype for ops whose algebra needs a field (mean, logsumexp)."""
    if jnp.issubdtype(values.dtype, jnp.floating):
        return values
    return values.astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class AggOp:
    """One registered aggregation operator; see the module docstring."""

    name: str
    combine: Callable  # (a, b) -> merged, elementwise per carried lane
    identity: Callable  # dtype -> scalar neutral element (carried dtype)
    # (values, segment_ids, num_segments, indices_are_sorted=False) -> [S,...]
    segment_reduce: Callable
    lanes: int = 1
    prepare: Callable | None = None  # user values -> carried values
    finalize: Callable | None = None  # carried values -> user values

    def prepare_values(self, values: jnp.ndarray) -> jnp.ndarray:
        """Map raw values [n] to the carried representation.

        lanes == 1 ops carry [n]; lanes > 1 ops carry [n, lanes] — the
        declared ``lanes`` is validated against what ``prepare`` produced,
        so a registration whose metadata and prepare disagree fails loudly.
        """
        out = values if self.prepare is None else self.prepare(values)
        want = values.shape[:1] + ((self.lanes,) if self.lanes > 1 else ())
        if out.shape != want:
            raise ValueError(
                f"op {self.name!r} declares lanes={self.lanes} but prepare "
                f"produced shape {out.shape} (expected {want})")
        return out

    def finalize_values(self, values: jnp.ndarray) -> jnp.ndarray:
        """Collapse the carried representation back to user values."""
        return values if self.finalize is None else self.finalize(values)


_REGISTRY: dict[str, AggOp] = {}


def register(op: AggOp) -> AggOp:
    """Add an op to the registry (last registration wins, enabling tests to
    shadow an op); returns it so definitions read as assignments."""
    _REGISTRY[op.name] = op
    return op


def get(name: str) -> AggOp:
    """Resolve an op by name; raises ValueError listing what IS registered."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unsupported aggregation op: {name!r} (registered: {names()})"
        ) from None


def names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Registered ops.
# ---------------------------------------------------------------------------


def _segment_logsumexp(values, segment_ids, num_segments,
                       indices_are_sorted=False):
    """Numerically stable segmented logsumexp (two-pass max-shift)."""
    m = jax.ops.segment_max(values, segment_ids, num_segments=num_segments,
                            indices_are_sorted=indices_are_sorted)
    m_safe = jnp.where(jnp.isfinite(m), m, jnp.zeros_like(m))
    s = jax.ops.segment_sum(
        jnp.exp(values - m_safe[segment_ids]), segment_ids,
        num_segments=num_segments, indices_are_sorted=indices_are_sorted)
    out = m_safe + jnp.log(s)
    neg_inf = jnp.array(-jnp.inf, values.dtype)
    return jnp.where(s > 0, out, neg_inf)


def _mean_prepare(values: jnp.ndarray) -> jnp.ndarray:
    v = _as_float(values)
    return jnp.stack([v, jnp.ones_like(v)], axis=-1)


def _mean_finalize(carried: jnp.ndarray) -> jnp.ndarray:
    total, count = carried[..., 0], carried[..., 1]
    safe = jnp.where(count != 0, count, jnp.ones_like(count))
    return jnp.where(count != 0, total / safe, jnp.zeros_like(total))


SUM = register(AggOp(
    name="sum",
    combine=lambda a, b: a + b,
    identity=lambda dtype: jnp.zeros((), dtype),
    segment_reduce=jax.ops.segment_sum,
))

MAX = register(AggOp(
    name="max",
    combine=jnp.maximum,
    identity=lambda dtype: _bound_identity(dtype, "max"),
    segment_reduce=jax.ops.segment_max,
))

MIN = register(AggOp(
    name="min",
    combine=jnp.minimum,
    identity=lambda dtype: _bound_identity(dtype, "min"),
    segment_reduce=jax.ops.segment_min,
))

COUNT = register(AggOp(
    name="count",
    combine=lambda a, b: a + b,
    identity=lambda dtype: jnp.zeros((), dtype),
    segment_reduce=jax.ops.segment_sum,
    # every record carries weight 1; the values' own payload is irrelevant
    prepare=lambda values: jnp.ones(values.shape[:1], jnp.int32),
))

MEAN = register(AggOp(
    name="mean",
    combine=lambda a, b: a + b,  # (sum, count) lanes both merge by add
    identity=lambda dtype: jnp.zeros((), dtype),
    segment_reduce=jax.ops.segment_sum,
    lanes=2,
    prepare=_mean_prepare,
    finalize=_mean_finalize,
))

LOGSUMEXP = register(AggOp(
    name="logsumexp",
    combine=jnp.logaddexp,
    # -inf IS the logaddexp identity and exists for every float dtype;
    # integer inputs are lifted to f32 by prepare, so no iinfo case arises
    identity=lambda dtype: jnp.array(-jnp.inf, jnp.dtype(dtype)),
    segment_reduce=_segment_logsumexp,
    prepare=_as_float,
))
