"""Two-level bounded-memory KV aggregation — the SwitchAgg FPE/BPE hierarchy.

Semantics (paper §4.2.4):

  * The **FPE** is a hash table of ``capacity`` slots held in fast memory
    (SRAM on the switch, VMEM in the Pallas kernel).  For each incoming
    (key, value) pair: hash the key, probe the bucket; on hit aggregate
    (SUM/MAX/MIN); on empty slot insert; on collision EVICT the resident
    pair downstream and insert the new pair.  The engine never stalls.
  * The **BPE** digests the eviction stream with a much larger (HBM/DRAM)
    table; we realize it as an exact sort-based combine, which on TPU is the
    natural "large slow memory" aggregation (sort + segment-sum is MXU/VPU
    friendly, and its latency is overlapped with the next FPE block exactly
    like the paper overlaps DRAM latency).

Invariant (checked by property tests): grouping the *outputs* (FPE flush +
BPE output) by key and combining gives exactly the input grouped-by-key
combine — aggregation never loses or double-counts data.

Op semantics (combine / identity / segment reduce) come from the
``core.aggops`` registry (DESIGN.md §6) — the one source of truth shared
with the Pallas kernels; this module never hardcodes an op.  Values may
carry trailing lane dimensions (e.g. ``mean``'s paired (sum, count) lanes):
eviction decisions are key-driven, so lanes ride along untouched.

This module is the pure-jnp implementation; ``repro.kernels.kv_aggregate``
is the Pallas/TPU version of the FPE loop with identical semantics.

Every FPE entry point takes ``exact_stream`` (DESIGN.md §8): True is the
paper-faithful sequential scan with a bit-reproducible eviction trace;
False is the batched-block fast path — within-block pre-combine plus a
closed-form vectorized bucket update — with identical grouped-combine
totals but a different eviction pattern, ~5-8x the scan's pairs/sec.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import aggops

EMPTY_KEY = jnp.int32(-1)

# THE key hash lives in core.aggops (one copy for the jnp engine and the
# Pallas kernel); re-exported here for existing callers.
hash_key = aggops.hash_key


class FPEResult(NamedTuple):
    table_keys: jnp.ndarray  # [capacity] int32, EMPTY_KEY where vacant
    table_values: jnp.ndarray  # [capacity, *lanes]
    evict_keys: jnp.ndarray  # [n] int32, EMPTY_KEY where no eviction
    evict_values: jnp.ndarray  # [n, *lanes]


def _fpe_geometry(capacity: int, ways: int) -> tuple[int, int, int]:
    """(ways, n_buckets, cap) — THE table-geometry clamp, shared by the
    scan path, the batched fast path, and the Pallas wrapper."""
    ways = max(1, min(ways, capacity))
    n_buckets = max(1, capacity // ways)
    return ways, n_buckets, n_buckets * ways


@functools.partial(
    jax.jit, static_argnames=("capacity", "ways", "op", "exact_stream"))
def fpe_aggregate(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    capacity: int,
    ways: int = 4,
    op: str = "sum",
    exact_stream: bool = True,
    table_keys: jnp.ndarray | None = None,
    table_values: jnp.ndarray | None = None,
) -> FPEResult:
    """The FPE hash engine: hash-probe-aggregate-or-evict (DESIGN.md §8).

    keys: [n] int32 (EMPTY_KEY entries are skipped — allows padded streams)
    values: [n] or [n, lanes] (carried lane dims, e.g. mean's (sum, count))
    Returns the resident table plus an eviction stream of n slots,
    EMPTY_KEY where nothing was evicted.

    ``exact_stream=True`` is the paper-faithful sequential scan: pairs are
    processed one at a time in stream order, so the eviction stream is
    bit-reproducible against the switch model (the Fig. 9 traffic curves).
    ``exact_stream=False`` is the batched-block fast path: duplicate keys
    in the block are pre-combined (sort + segment reduce), then the
    surviving distinct keys update the table via vectorized bucket rounds.
    The grouped-by-key combine of (flush + evictions) is IDENTICAL in both
    modes — only the eviction *order/pattern* (which pair left when) may
    differ; see DESIGN.md §8 for the contract.

    ``table_keys``/``table_values`` (the flat ``[capacity]`` layout a prior
    call returned) resume from an existing resident table — the streaming
    ingest used by ``core.dataplane.LevelState`` and the packet simulator
    (``net.sim``), where a switch's table persists across packets and is
    flushed only at end-of-task.
    """
    if exact_stream:
        return _fpe_scan(keys, values, capacity=capacity, ways=ways, op=op,
                         table_keys=table_keys, table_values=table_values)
    return _fpe_batched(keys, values, capacity=capacity, ways=ways, op=op,
                        table_keys=table_keys, table_values=table_values)


def _fpe_scan(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    capacity: int,
    ways: int,
    op: str,
    table_keys: jnp.ndarray | None,
    table_values: jnp.ndarray | None,
) -> FPEResult:
    """Paper-faithful FPE: sequential hash-probe-aggregate-or-evict.

    evict_keys[i] is the pair evicted while processing input i.
    """
    aggop = aggops.get(op)
    n = keys.shape[0]
    ways, n_buckets, cap = _fpe_geometry(capacity, ways)
    lane_shape = values.shape[1:]  # () for scalar values
    lane_nd = len(lane_shape)

    if table_keys is None:
        tk0 = jnp.full((n_buckets, ways), EMPTY_KEY, dtype=jnp.int32)
        tv0 = jnp.zeros((n_buckets, ways) + lane_shape, dtype=values.dtype)
    else:
        tk0 = table_keys.reshape(n_buckets, ways)
        tv0 = table_values.reshape((n_buckets, ways) + lane_shape)

    def step(carry, inp):
        tk, tv = carry
        k, v = inp
        b = hash_key(k, n_buckets)
        row_k = tk[b]  # [ways]
        row_v = tv[b]  # [ways, *lanes]
        is_pad = k == EMPTY_KEY

        hit = row_k == k  # [ways]
        any_hit = jnp.any(hit) & ~is_pad
        empty = row_k == EMPTY_KEY
        any_empty = jnp.any(empty) & ~is_pad
        # first empty way
        empty_idx = jnp.argmax(empty)
        hit_l = hit.reshape(hit.shape + (1,) * lane_nd)  # broadcast over lanes

        # --- hit: aggregate into the matching way
        agg_row_v = jnp.where(hit_l, aggop.combine(row_v, v), row_v)

        # --- miss+empty: insert at first empty way
        ins_row_k = row_k.at[empty_idx].set(k)
        ins_row_v = row_v.at[empty_idx].set(v)

        # --- miss+full: evict way 0, shift left, insert at last way (paper:
        # the previously stored key is evicted and forwarded to the BPE)
        ev_k, ev_v = row_k[0], row_v[0]
        sh_row_k = jnp.concatenate([row_k[1:], k[None]])
        sh_row_v = jnp.concatenate([row_v[1:], v[None]])

        new_row_k = jnp.where(any_hit, row_k, jnp.where(any_empty, ins_row_k, sh_row_k))
        new_row_v = jnp.where(
            any_hit, agg_row_v, jnp.where(any_empty, ins_row_v, sh_row_v)
        )
        evicted = (~any_hit) & (~any_empty) & (~is_pad)
        out_k = jnp.where(evicted, ev_k, EMPTY_KEY)
        out_v = jnp.where(evicted, ev_v, jnp.zeros_like(ev_v))

        new_row_k = jnp.where(is_pad, row_k, new_row_k)
        new_row_v = jnp.where(is_pad, row_v, new_row_v)
        tk = tk.at[b].set(new_row_k)
        tv = tv.at[b].set(new_row_v)
        return (tk, tv), (out_k, out_v)

    (tk, tv), (ek, ev) = jax.lax.scan(step, (tk0, tv0), (keys, values))
    return FPEResult(tk.reshape(cap), tv.reshape((cap,) + lane_shape), ek, ev)


def _group_reduce(keys, values, *, op):
    """THE bulk group-by-key reduction (DESIGN.md §8): a key sort, a map
    from every element to its group, one segment reduce.

    Returns (k_s, real_start, comb):
      k_s        [n] keys sorted ascending (EMPTY_KEY is just another value
                 in the sort; any key except EMPTY_KEY itself is legal),
      real_start [n] True at the first sorted occurrence of each real key,
      comb       [n, *lanes] combined value of each key's group, indexed by
                 the key's FIRST SORTED POSITION (entries that are not a
                 real first occurrence hold garbage — never read).

    The group map is chosen by the platform the program is lowered for
    (``lax.platform_dependent``, so a compile for a described TPU takes
    the TPU branch too); both branches return the same shapes, dtypes and
    first-position totals:

    * XLA:CPU (``_group_reduce_searched``): the single-operand int sort
      takes the fast radix path while a variadic sort that co-sorts values
      is several times slower, and scatters cost ~30x a gather.  So values
      never ride a sort: each ORIGINAL element finds its group with one
      ``searchsorted`` pass and the reduce runs over unsorted segment ids
      (3-4x faster than the other branch there; re-measure with
      ``benchmarks/bench_group_map.py``).
    * Every other backend (``_group_reduce_sorted``): that ``searchsorted``
      is a loop of log2(n) steps of one dynamic gather over the whole
      stream each, which took most of the TPU's busy time, while a sort
      that carries the values is cheap there.  So the values ride the key
      sort and each sorted element takes the first position of its run
      from a running max: no loop, and the reduce's ids arrive sorted.
    """
    return jax.lax.platform_dependent(
        keys, values,
        cpu=functools.partial(_group_reduce_searched, op=op),
        default=functools.partial(_group_reduce_sorted, op=op))


def _run_change(k_s):
    """[n] True where a sorted key differs from its predecessor."""
    return jnp.concatenate([jnp.ones((1,), bool), k_s[1:] != k_s[:-1]])


def _group_reduce_searched(keys, values, *, op):
    """``_group_reduce`` for XLA:CPU: radix sort + binary-search group map
    + unsorted segment reduce."""
    aggop = aggops.get(op)
    n = keys.shape[0]
    k_s = jnp.sort(keys)
    real_start = _run_change(k_s) & (k_s != EMPTY_KEY)
    seg = jnp.searchsorted(k_s, keys, method="scan")
    comb = aggop.segment_reduce(values, seg, num_segments=n)
    return k_s, real_start, comb


def _group_reduce_sorted(keys, values, *, op):
    """``_group_reduce`` off the CPU: one sort carrying every value lane as
    an operand, run starts by a running max, sorted segment reduce."""
    aggop = aggops.get(op)
    n = keys.shape[0]
    lane_shape = values.shape[1:]
    cols = values.reshape(n, math.prod(lane_shape))
    k_s, *v_cols = jax.lax.sort(
        (keys, *(cols[:, i] for i in range(cols.shape[1]))), num_keys=1)
    v_s = jnp.stack(v_cols, axis=1).reshape(values.shape)
    change = _run_change(k_s)
    real_start = change & (k_s != EMPTY_KEY)
    seg = jax.lax.cummax(
        jnp.where(change, jnp.arange(n, dtype=jnp.int32), 0), axis=0)
    comb = aggop.segment_reduce(v_s, seg, num_segments=n,
                                indices_are_sorted=True)
    return k_s, real_start, comb


def _run_starts(b_d, n_buckets):
    """[n_buckets + 1] int32: the first position of each bucket's run in
    the ascending bucket ids ``b_d`` (all in [0, n_buckets]); entry j is
    the number of ids below j.  A sorted count and an exclusive prefix
    sum: no binary-search loop on the TPU, and as fast as the search on
    XLA:CPU (DESIGN.md §8)."""
    counts = jax.ops.segment_sum(jnp.ones_like(b_d), b_d,
                                 num_segments=n_buckets + 1,
                                 indices_are_sorted=True)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts)[:n_buckets]]).astype(jnp.int32)


def _fpe_batched(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    capacity: int,
    ways: int,
    op: str,
    table_keys: jnp.ndarray | None,
    table_values: jnp.ndarray | None,
) -> FPEResult:
    """Batched-block FPE fast path (DESIGN.md §8): within-block pre-combine
    + one closed-form vectorized bucket update instead of one sequential
    step per pair.

    1. Duplicate keys in the block collapse to one carried value each
       (``_group_reduce``: sort + ``aggops.segment_reduce``).  Eviction
       decisions are key-driven, so combining same-key pairs *before*
       table insertion preserves the grouped-combine conservation
       invariant.
    2. One more radix sort orders the distinct keys bucket-major, and the
       whole block's table update collapses to closed form: each bucket
       row is a FIFO queue — [residents, new distinct keys] — of which the
       last ``ways`` survive and the prefix is evicted.  Hits combine into
       their resident way; every survivor's (slot, key) write rides one
       int32 composite sort (``slot * n + index``), so the scatter that
       applies the block touches at most ``capacity`` slots.  No
       per-element loop, no per-conflict rounds: intra-block bucket
       conflicts are resolved analytically by the queue arithmetic.

    The eviction stream is [n + capacity] (block evictions in distinct-key
    order, then residents displaced by the block) instead of the scan
    path's input-aligned [n]; slots hold EMPTY_KEY where nothing was
    evicted.  Callers treat both as masked streams, but the *pattern* is
    not the paper's per-arrival trace — use ``exact_stream=True`` for
    that.  Requires ``n * max(n_buckets, capacity) < 2**31 - 1`` (int32
    composites) and raises ``ValueError`` beyond it: callers chunk longer
    streams, as ``dataplane.LevelState`` does with ``batch_pad``.
    """
    aggop = aggops.get(op)
    combine = aggop.combine  # resolved once, outside all vector math
    n = keys.shape[0]
    ways, n_buckets, cap = _fpe_geometry(capacity, ways)
    imax = jnp.iinfo(jnp.int32).max
    if n * max(n_buckets, cap) >= imax:
        raise ValueError(
            f"FPE fast path: n * max(n_buckets, capacity) = "
            f"{n * max(n_buckets, cap)} is not below the int32 limit "
            f"2**31 - 1 (n={n}, capacity={cap}); chunk the stream into "
            f"calls of at most {(imax - 1) // max(n_buckets, cap)} records")
    if n == 0:
        res = _fpe_scan(keys, values, capacity=capacity, ways=ways, op=op,
                        table_keys=table_keys, table_values=table_values)
        pad_ev = jnp.full((cap,), EMPTY_KEY, jnp.int32)
        pad_vv = jnp.zeros((cap,) + values.shape[1:], values.dtype)
        return FPEResult(  # keep the fast path's [n + cap] stream shape
            res.table_keys, res.table_values,
            jnp.concatenate([res.evict_keys, pad_ev]),
            jnp.concatenate([res.evict_values, pad_vv]))
    lane_shape = values.shape[1:]
    lane_nd = len(lane_shape)

    def lanes(m):  # broadcast a mask over trailing lane dims
        return m.reshape(m.shape + (1,) * lane_nd)

    if table_keys is None:
        tk = jnp.full((n_buckets, ways), EMPTY_KEY, jnp.int32)
        tv = jnp.zeros((n_buckets, ways) + lane_shape, values.dtype)
    else:
        tk = table_keys.reshape(n_buckets, ways)
        tv = table_values.reshape((n_buckets, ways) + lane_shape)

    # --- stage 1: within-block pre-combine -------------------------------
    k_s, real_start, comb = _group_reduce(keys, values, op=op)
    pos = jnp.arange(n, dtype=jnp.int32)

    # --- stage 2: bucket-major distinct stream (one radix sort) ----------
    bucket_s = hash_key(k_s, n_buckets)
    c1 = jnp.sort(jnp.where(real_start, bucket_s * n + pos, imax))
    valid_d = c1 != imax
    fp = jnp.where(valid_d, c1 % n, 0)  # first sorted position of key d
    b_d = jnp.where(valid_d, c1 // n, n_buckets)  # ascending buckets
    uk = jnp.where(valid_d, k_s[fp], EMPTY_KEY)
    cv = jnp.where(lanes(valid_d), comb[fp],
                   jnp.zeros((), values.dtype))

    # --- stage 3: hit detection + FIFO queue arithmetic ------------------
    b_c = jnp.clip(b_d, 0, n_buckets - 1)
    rows_k = tk[b_c]  # [n, ways]
    rows_v = tv[b_c]  # [n, ways, *lanes]
    hit = (rows_k == uk[:, None]) & valid_d[:, None]
    is_hit = jnp.any(hit, axis=1)
    hit_way = jnp.argmax(hit, axis=1).astype(jnp.int32)
    # resident rows are front-contiguous (both engines insert at the first
    # empty way and shift full rows left), so the count locates the queue
    r_d = jnp.sum(rows_k != EMPTY_KEY, axis=1).astype(jnp.int32)
    nh = valid_d & ~is_hit  # distinct new keys joining the queue

    # per-bucket totals / per-key queue rank from prefix sums over the
    # bucket-major layout (b_d is sorted, so each bucket is one run)
    sx = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(nh.astype(jnp.int32))])
    rs = _run_starts(b_d, n_buckets)
    q_arr = sx[rs[1:]] - sx[rs[:-1]]  # [n_buckets] new keys per bucket
    j_d = sx[pos] - sx[rs[b_c]]  # rank of d among its bucket's new keys
    q_d = q_arr[b_c]
    # queue [r residents, q new keys]: evict the first E, keep the last W
    e_d = r_d + q_d - ways  # evictions this bucket must make
    er_d = jnp.clip(jnp.minimum(r_d, e_d), 0, ways)  # evicted residents

    hit_surv = is_hit & (hit_way >= er_d)
    hit_evic = is_hit & (hit_way < er_d)  # resident dies before the merge
    new_surv = nh & (j_d >= jnp.maximum(e_d - r_d, 0))
    # a key whose resident was shift-evicted re-enters the stream as its
    # own pair (the resident pair leaves separately): same grouped total
    self_evict = (nh & ~new_surv) | hit_evic

    way_tgt = jnp.where(
        hit_surv, hit_way - er_d,
        r_d + j_d - jnp.maximum(e_d, 0))  # post-shift way of each writer
    writer = hit_surv | new_surv
    rows_v_hit = jnp.take_along_axis(
        rows_v, lanes(hit_way[:, None]), axis=1)[:, 0]
    wval = jnp.where(lanes(is_hit), combine(rows_v_hit, cv), cv)

    # --- stage 4: apply — shift rows, then scatter the <= cap writers ----
    r_b = jnp.sum(tk != EMPTY_KEY, axis=1).astype(jnp.int32)
    e_b = jnp.clip(jnp.minimum(r_b, r_b + q_arr - ways), 0, ways)
    wi = jnp.arange(ways, dtype=jnp.int32)[None, :]
    src = jnp.clip(wi + e_b[:, None], 0, ways - 1)
    keep = (wi + e_b[:, None]) < ways
    sh_tk = jnp.where(keep, jnp.take_along_axis(tk, src, axis=1), EMPTY_KEY)
    sh_tv = jnp.where(lanes(keep),
                      jnp.take_along_axis(tv, lanes(src), axis=1),
                      jnp.zeros((), values.dtype))

    # every write target (bucket, way) is unique, so there are at most
    # cap writers: one composite sort packs them for a cap-sized scatter
    c2 = jnp.sort(jnp.where(writer, (b_d * ways + way_tgt) * n + pos,
                            imax))[:cap]
    w2 = c2 != imax
    slot2 = jnp.where(w2, c2 // n, cap)  # cap = out of bounds -> dropped
    d2 = jnp.where(w2, c2 % n, 0)
    flat_k = sh_tk.reshape(cap).at[slot2].set(uk[d2], mode="drop")
    flat_v = sh_tv.reshape((cap,) + lane_shape).at[slot2].set(
        wval[d2], mode="drop")

    # --- eviction stream: block self-evictions + displaced residents -----
    ev_k = jnp.where(self_evict, uk, EMPTY_KEY)
    ev_v = jnp.where(lanes(self_evict), cv, jnp.zeros((), values.dtype))
    res_ev = wi < e_b[:, None]  # [n_buckets, ways]
    rv_k = jnp.where(res_ev, tk, EMPTY_KEY).reshape(cap)
    rv_v = jnp.where(lanes(res_ev), tv,
                     jnp.zeros((), values.dtype)).reshape(
        (cap,) + lane_shape)
    return FPEResult(flat_k, flat_v,
                     jnp.concatenate([ev_k, rv_k]),
                     jnp.concatenate([ev_v, rv_v]))


class CombineResult(NamedTuple):
    unique_keys: jnp.ndarray  # [n] int32, EMPTY_KEY past n_unique
    combined_values: jnp.ndarray  # [n, *lanes]
    n_unique: jnp.ndarray  # [] int32


@functools.partial(jax.jit, static_argnames=("op",))
def sorted_combine(keys: jnp.ndarray, values: jnp.ndarray, *, op: str = "sum") -> CombineResult:
    """Exact combine-by-key via sort + segment reduction (the BPE / the
    beyond-paper vectorized aggregator).  EMPTY_KEY inputs are ignored.

    Output is fixed-shape [n]: unique keys packed to the front in ascending
    order, EMPTY_KEY padding after ``n_unique`` (padding value slots hold
    the op's dtype-aware identity).  Values may carry trailing lane dims.
    """
    aggop = aggops.get(op)
    n = keys.shape[0]
    lane_nd = values.ndim - 1
    if n == 0:
        return CombineResult(keys.astype(jnp.int32), values,
                             jnp.zeros((), jnp.int32))
    # One key sort + group map + segment reduce (_group_reduce, whose map
    # is split by platform), and no sentinel remap, so INT32_MAX stays a
    # legal, distinct key.
    k_s, real_start, comb = _group_reduce(keys, values, op=op)
    pos = jnp.arange(n, dtype=jnp.int32)
    # k_s is ascending, so first positions sort to ascending-key order
    fp = jnp.sort(jnp.where(real_start, pos, jnp.iinfo(jnp.int32).max))
    n_unique = jnp.sum(real_start).astype(jnp.int32)
    valid = pos < n_unique
    valid_l = valid.reshape(valid.shape + (1,) * lane_nd)
    fp_c = jnp.clip(fp, 0, n - 1)
    ident = aggop.identity(values.dtype)
    uk = jnp.where(valid, k_s[fp_c], EMPTY_KEY)
    cv = jnp.where(valid_l, comb[fp_c], ident)
    return CombineResult(uk.astype(jnp.int32), cv, n_unique)


class TwoLevelResult(NamedTuple):
    """Full SwitchAgg node output: FPE flush + BPE combine, plus traffic stats.

    INVARIANT (traffic semantics, paper Fig. 9): ``out_keys`` is a traffic
    stream, not a key set — the same key may appear more than once.  With
    ``bpe=False`` the raw eviction stream is forwarded unaggregated (the
    SRAM-only "S-*" switch), so every re-eviction of a key is a distinct
    forwarded pair; with ``bpe=True`` the evictions are combined but a key
    resident in the FPE table at flush may ALSO appear in the BPE output.
    ``n_out`` therefore counts forwarded pairs (the bytes a downstream link
    carries), NOT distinct keys — use :func:`n_distinct_keys` for the
    latter.  Grouping ``out`` by key always reproduces the exact input
    combine (the conservation property tests).
    """

    out_keys: jnp.ndarray  # [capacity + n]
    out_values: jnp.ndarray  # [capacity + n, *lanes]
    n_out: jnp.ndarray  # [] int32 — number of forwarded output pairs
    n_in: jnp.ndarray  # [] int32 — number of real input pairs
    n_evict: jnp.ndarray  # [] int32 — FPE evictions (pre-BPE traffic)


@functools.partial(
    jax.jit, static_argnames=("capacity", "ways", "op", "bpe", "exact_stream"))
def two_level_aggregate(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    capacity: int,
    ways: int = 4,
    op: str = "sum",
    bpe: bool = True,
    exact_stream: bool = True,
) -> TwoLevelResult:
    """One SwitchAgg aggregation node: FPE hash stage + optional BPE stage.

    With ``bpe=False`` this models the SRAM-only programmable switch
    (DAIET-like): evictions leave the node unaggregated — the paper's Fig. 9
    "S-*" curves.  With ``bpe=True`` evictions are combined in the back-end
    ("M-*" curves).  See :class:`TwoLevelResult` for the ``n_out``
    duplicate-key invariant.  Ops operate on *carried* values (see
    ``aggops.AggOp.prepare_values``); multi-lane ops pass [n, lanes] values.
    ``exact_stream=False`` runs the batched-block FPE fast path (DESIGN.md
    §8): same grouped-combine result, different eviction pattern — keep the
    default for paper-faithful Fig. 9 traffic curves.
    """
    fpe = fpe_aggregate(keys, values, capacity=capacity, ways=ways, op=op,
                        exact_stream=exact_stream)
    return assemble_node(keys, fpe.table_keys, fpe.table_values,
                         fpe.evict_keys, fpe.evict_values, op=op, bpe=bpe)


def assemble_node(keys, table_keys, table_values, evict_keys, evict_values,
                  *, op: str, bpe: bool) -> TwoLevelResult:
    """THE node-assembly policy (flush + eviction stream -> output stream),
    shared by the jnp node above, the Pallas node (``kernels.ops``), and the
    cascade executor (``core.dataplane.run_level``) — one copy of the
    n_out/n_in/n_evict accounting and the BPE-vs-raw forwarding choice."""
    n_evict = jnp.sum(evict_keys != EMPTY_KEY).astype(jnp.int32)
    if bpe:
        bpe_out = sorted_combine(evict_keys, evict_values, op=op)
        ok = jnp.concatenate([table_keys, bpe_out.unique_keys])
        ov = jnp.concatenate([table_values, bpe_out.combined_values])
    else:
        ok = jnp.concatenate([table_keys, evict_keys])
        ov = jnp.concatenate([table_values, evict_values])
    n_out = jnp.sum(ok != EMPTY_KEY).astype(jnp.int32)
    n_in = jnp.sum(keys != EMPTY_KEY).astype(jnp.int32)
    return TwoLevelResult(ok, ov, n_out, n_in, n_evict)


def n_distinct_keys(keys: jnp.ndarray) -> jnp.ndarray:
    """Number of distinct non-EMPTY keys in a stream (telemetry helper).

    Counts segment starts in sorted order — the set-size counterpart to the
    pair-count ``n_out`` (which may exceed it; see TwoLevelResult).  No
    sentinel remapping: every key value except EMPTY_KEY itself is legal,
    including INT32_MAX.
    """
    n = keys.shape[0]
    if n == 0:
        return jnp.zeros((), jnp.int32)
    sk = jnp.sort(keys)
    starts = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    return jnp.sum(starts & (sk != EMPTY_KEY)).astype(jnp.int32)


def reduction_ratio(res: TwoLevelResult) -> jnp.ndarray:
    """Traffic reduction achieved by the node (paper's R)."""
    return 1.0 - res.n_out / jnp.maximum(res.n_in, 1)


# ---------------------------------------------------------------------------
# Length-grouped dispatch — the payload analyzer (paper §4.2.3).
# ---------------------------------------------------------------------------


def length_group(key_lengths: jnp.ndarray, base: int = 8, n_groups: int = 8) -> jnp.ndarray:
    """Payload-analyzer binning: key length L -> group index.

    The paper divides key lengths [8B, 64B] into 8 groups of base B=8; each
    group is served by a dedicated FPE.  Returns clip(ceil(L/base)-1, 0, G-1).
    """
    g = (key_lengths + base - 1) // base - 1
    return jnp.clip(g, 0, n_groups - 1).astype(jnp.int32)
