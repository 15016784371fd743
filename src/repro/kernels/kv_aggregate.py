"""Pallas TPU kernel: the SwitchAgg FPE hash-combine engine.

TPU adaptation of the paper's front-end processing engine (§4.2.4):

  * The hash table lives in **VMEM** (the switch's SRAM analogue) as the
    kernel's resident output blocks, so it persists across grid steps while
    the input stream is tiled through block by block (the BlockSpec
    pipeline is the paper's line-rate packet flow).
  * The table is **lane-dense**: slot ``b * stride + w`` (bucket ``b``,
    way ``w``, ``stride`` = ``ways`` rounded up to a power of two) lives in
    a stack of ``(8, 128)`` tiles, one 32-bit vreg each.  A probe loads the
    one tile holding the bucket, compares its ways with masked VPU ops,
    and stores the tile back: no narrow minor dimension pads the table to
    128 lanes, so a 65,536-pair table takes 256 KiB of VMEM per lane.
  * Keys and values stream through **SMEM**: each record is a scalar read
    at a dynamic index, which a VMEM vector block cannot serve.  The
    eviction stream leaves through SMEM blocks the same way.
  * On collision the resident way-0 pair is **evicted to the output stream**
    (never a stall/retry — the paper's no-penalty miss), the row shifts
    left, and the new pair occupies the last way.  The eviction stream
    (the BPE feed) has one slot per input element, EMPTY_KEY where nothing
    was evicted; the BPE combine is a bulk sort+segment-sum on it
    (``kvagg.assemble_node``).

Semantics are bit-identical to ``repro.core.kvagg.fpe_aggregate`` (the
pure-jnp oracle re-exported via ``ref.py``).

Op semantics come from the ``core.aggops`` registry (DESIGN.md §6): the
``op`` string is resolved to its ``combine`` ONCE at trace time, before the
kernel body is built, so each compiled kernel stays specialized to one op.
Multi-lane carried ops (``mean``'s paired (sum, count) lanes) run in the
SAME single ``pallas_call``: every combine is elementwise per lane, so the
table keeps one tile stack per lane and each probe updates all of them.

``exact_stream=False`` runs the batched-block fast path (DESIGN.md §8):
the block is pre-combined to distinct keys by the jnp ``sorted_combine``
(vectorized VPU work) and only the surviving distinct keys stream through
the sequential VMEM engine — same grouped-combine result, shorter
effective stream, non-paper-faithful eviction pattern.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import aggops
from repro.core import kvagg as _kvagg

EMPTY_KEY = -1  # plain int so kernels inline it as a literal

# One table tile is one 32-bit vreg: (sublanes, lanes).
_SUB, _LANE = 8, 128
_TILE = _SUB * _LANE

# THE key hash (core.aggops.hash_key): one copy shared with the jnp engine
# so the kernel's bucket function can never drift from the oracle's.
_hash = aggops.hash_key


def _table_geometry(capacity: int, ways: int) -> tuple[int, int, int, int]:
    """(ways, n_buckets, stride, n_tiles) of the lane-dense VMEM table.

    A bucket occupies ``stride`` consecutive lanes of one tile row, so its
    ways never straddle a row and a one-lane roll shifts it left."""
    ways, n_buckets, _ = _kvagg._fpe_geometry(capacity, ways)
    stride = 1 << (ways - 1).bit_length()
    if stride > _LANE:
        raise ValueError(f"ways={ways} exceeds one {_LANE}-lane tile row")
    return ways, n_buckets, stride, -(-n_buckets * stride // _TILE)


def _fpe_kernel(
    keys_ref,  # [1, block_n] int32 (SMEM, streamed)
    vals_ref,  # [lanes, 1, block_n] (SMEM, streamed)
    evk_ref,  # [1, block_n] int32 out (SMEM) — eviction stream block
    evv_ref,  # [lanes, 1, block_n] out (SMEM)
    tk_ref,  # [n_tiles, 8, 128] int32 out (VMEM, resident) — table keys
    tv_ref,  # [lanes, n_tiles, 8, 128] out (VMEM, resident) — table values
    *,
    n_buckets: int,
    ways: int,
    stride: int,
    lanes: int,
    combine,  # aggops combine fn, resolved ONCE before the body is traced
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        tk_ref[...] = jnp.full(tk_ref.shape, EMPTY_KEY, jnp.int32)
        tv_ref[...] = jnp.zeros(tv_ref.shape, tv_ref.dtype)

    vdt = tv_ref.dtype
    # flat slot of every element of a tile, row-major over (8, 128)
    slot = (jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANE), 0) * _LANE
            + jax.lax.broadcasted_iota(jnp.int32, (_SUB, _LANE), 1))

    def shift_left(x):  # element j takes element j + 1 of its row
        return pltpu.roll(x, _LANE - 1, 1)

    def pick(mask, x):  # the one element of x under mask, as a scalar
        return jnp.sum(jnp.where(mask, x, jnp.zeros((), x.dtype)))

    def body(i, carry):
        k = keys_ref[0, i]
        is_pad = k == EMPTY_KEY
        base = _hash(k, n_buckets) * stride
        t = base // _TILE
        # a padding record addresses no slot, so every mask below is empty
        w = slot - jnp.where(is_pad, 2 * _TILE, base % _TILE)  # way index
        in_b = (w >= 0) & (w < ways)

        row_k = tk_ref[t]
        hit = in_b & (row_k == k)  # the bucket probe: one VPU compare
        any_hit = jnp.max(hit.astype(jnp.int32)) > 0
        empty_idx = jnp.min(jnp.where(in_b & (row_k == EMPTY_KEY), w, ways))
        any_empty = empty_idx < ways
        evicted = ~any_hit & ~any_empty & ~is_pad
        # miss+empty: insert at the first empty way; miss+full: evict way 0,
        # shift the bucket left and insert at the last way
        ins = in_b & (w == jnp.where(any_hit, -1,
                                     jnp.where(any_empty, empty_idx,
                                               ways - 1)))
        shifted = in_b & (w < jnp.where(evicted, ways - 1, 0))
        way0 = in_b & (w == 0)

        ev_k = pick(way0, row_k)
        tk_ref[t] = jnp.where(ins, k, jnp.where(shifted, shift_left(row_k),
                                                row_k))
        evk_ref[0, i] = jnp.where(evicted, ev_k, EMPTY_KEY)
        for l in range(lanes):
            v = vals_ref[l, 0, i]
            row_v = tv_ref[l, t]
            ev_v = pick(way0, row_v)
            tv_ref[l, t] = jnp.where(
                hit, combine(row_v, v),
                jnp.where(ins, v, jnp.where(shifted, shift_left(row_v),
                                            row_v)))
            evv_ref[l, 0, i] = jnp.where(evicted, ev_v, jnp.zeros((), vdt))
        return carry

    jax.lax.fori_loop(0, keys_ref.shape[1], body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("capacity", "ways", "op", "block_n", "exact_stream",
                     "interpret"),
)
def fpe_aggregate_pallas(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    *,
    capacity: int,
    ways: int = 4,
    op: str = "sum",
    block_n: int = 512,
    exact_stream: bool = True,
    interpret: bool = False,
):
    """Run the FPE kernel over a KV stream.

    Returns (table_keys [capacity], table_values [capacity, *lanes],
             evict_keys [n], evict_values [n, *lanes]) — same contract as
    ``core.kvagg.fpe_aggregate``.  Values with a trailing lane dim (multi-
    lane carried ops, e.g. ``mean``) run in the SAME kernel launch: the
    VMEM table keeps one tile stack per lane and each element's probe
    updates every lane at once.

    ``interpret=True`` runs the kernel in the Pallas interpreter (the CPU
    tests); the default compiles it for the TPU.

    ``exact_stream=False`` pre-combines the block to distinct keys
    (``kvagg.sorted_combine`` — vectorized) before streaming it through
    the kernel, so the sequential engine touches each distinct key once;
    the eviction *pattern* then differs from the paper-faithful trace
    (DESIGN.md §8) while the grouped-combine result is identical.  NOTE:
    in that mode the eviction stream stays [n] (slot d = the d-th sorted
    distinct key) whereas the jnp fast path emits [n + capacity]
    (displaced residents appended) — compare the two fast modes by
    resident table and grouped totals, not elementwise stream shape.
    """
    n = keys.shape[0]
    squeeze = values.ndim == 1
    if exact_stream is False:
        c = _kvagg.sorted_combine(keys, values, op=op)
        keys, values = c.unique_keys, c.combined_values
    # lane-major [lanes, n]: an [n, lanes] array with a narrow minor dim
    # pads to 128 lanes in HBM
    values = values[None] if squeeze else values.T
    lanes = values.shape[0]
    ways, n_buckets, stride, n_tiles = _table_geometry(capacity, ways)

    pad = (-n) % block_n
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), EMPTY_KEY, jnp.int32)])
        values = jnp.concatenate(
            [values, jnp.zeros((lanes, pad), values.dtype)], axis=1)
    total = keys.shape[0]

    kernel = functools.partial(
        _fpe_kernel, n_buckets=n_buckets, ways=ways, stride=stride,
        lanes=lanes, combine=aggops.get(op).combine)
    n_blocks = total // block_n

    def stream(lead):  # block i of a [*lead, n_blocks, 1, block_n] stream
        return pl.BlockSpec((*lead, None, 1, block_n),
                            lambda i: (*(0 for _ in lead), i, 0, 0),
                            memory_space=pltpu.SMEM)

    evk, evv, otk, otv = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[stream(()), stream((lanes,))],
        out_specs=[
            stream(()),
            stream((lanes,)),
            pl.BlockSpec((n_tiles, _SUB, _LANE), lambda i: (0, 0, 0)),
            pl.BlockSpec((lanes, n_tiles, _SUB, _LANE),
                         lambda i: (0, 0, 0, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((n_blocks, 1, block_n), jnp.int32),
            jax.ShapeDtypeStruct((lanes, n_blocks, 1, block_n),
                                 values.dtype),
            jax.ShapeDtypeStruct((n_tiles, _SUB, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((lanes, n_tiles, _SUB, _LANE),
                                 values.dtype),
        ),
        # the table carries from one block to the next: a sequential grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(keys.reshape(n_blocks, 1, block_n),
      values.reshape(lanes, n_blocks, 1, block_n))
    # lane-dense tiles -> the oracle's flat [n_buckets * ways] slot order
    tk = otk.reshape(-1)[:n_buckets * stride].reshape(n_buckets, stride)
    tv = otv.reshape(lanes, -1)[:, :n_buckets * stride].reshape(
        lanes, n_buckets, stride)
    tk = tk[:, :ways].reshape(-1)
    tv = tv[:, :, :ways].reshape(lanes, -1)
    ek, ev = evk.reshape(total)[:n], evv.reshape(lanes, total)[:, :n]
    if squeeze:
        return tk, tv[0], ek, ev[0]
    return tk, tv.T, ek, ev.T
