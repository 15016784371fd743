"""Plan-driven multi-level aggregation dataplane (DESIGN.md §6).

The paper's data reduction ratio is governed by how much aggregation state
each hop holds (Eq. 3's ``C``) and what reduction function it runs; the
FPE/BPE hierarchy exists to lift that bound at EVERY level of the tree.
This module is the execution layer that honors a controller plan end to
end: it takes the per-tree memory partition the planner emitted
(``ConfigureMsg`` / ``ExchangePlan``, DESIGN.md §3) and runs the full
multi-level cascade —

    level 0 FPE/BPE node  --evictions+flush-->  level 1 node  --> ... root

— each level a bounded-memory SwitchAgg node sized by its slice of the
plan's combiner budget, with per-level telemetry (records in/out,
evictions, reduction ratio: the paper's key metric, Fig. 2b/Fig. 9).

Two backends execute the same plan: ``jnp`` (the ``core.kvagg`` scan
oracle) and ``pallas`` (the VMEM FPE kernel, ``kernels.kv_aggregate``).
Op semantics come from the ``core.aggops`` registry; cascades carry the
op's *carried* representation between levels (e.g. ``mean``'s (sum, count)
lanes) and finalize only at the root, which is what makes multi-level
mean/logsumexp exact.

A ``LevelSpec`` with ``capacity == 0`` is the exact unbounded node (pure
sorted combine, no FPE) — the planner's ``fpe_capacity=0`` convention.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from . import aggops, kvagg
from . import reduction_model as rm

EMPTY_KEY = kvagg.EMPTY_KEY

# compiles and persistent-cache loads feed the registry (DESIGN.md §11)
jax.monitoring.register_event_duration_secs_listener(
    obs_metrics.record_compile)


@dataclasses.dataclass(frozen=True)
class LevelSpec:
    """One cascade hop: an FPE/BPE node's geometry.

    capacity == 0 means the exact unbounded combine (no FPE, no evictions).
    ``enabled == False`` is a forward-only hop (DESIGN.md §9): the level's
    switches have no aggregation capability (or the placement search left
    them out) and relay every record unaggregated — the per-level knob the
    fat-tree placement uses to express host-only / ToR-only / full-tree
    deployments inside one cascade.
    """

    capacity: int
    ways: int = 4
    bpe: bool = True
    enabled: bool = True


@dataclasses.dataclass(frozen=True)
class CascadePlan:
    """The dataplane's view of a controller plan: op + per-level nodes."""

    op: str
    levels: tuple[LevelSpec, ...]

    def __post_init__(self):
        if not self.levels:
            raise ValueError("a cascade needs at least one level")
        aggops.get(self.op)  # fail fast on unknown ops

    @property
    def capacities(self) -> tuple[int, ...]:
        return tuple(l.capacity for l in self.levels)

    def describe(self) -> str:
        caps = " -> ".join(str(c) for c in self.capacities)
        return f"{self.op} cascade [{caps}]"


def even_split_levels(budget: int, n_levels: int, *, ways: int = 4,
                      bpe: bool = True) -> tuple[LevelSpec, ...]:
    """THE per-level memory partition rule: a tree's combiner budget split
    evenly among its levels (each slice >= 1 pair); budget 0 means every
    level is the exact unbounded node.  Both plan builders below use this —
    change the partition policy here and nowhere else."""
    n_levels = max(1, n_levels)
    cap = max(1, budget // n_levels) if budget > 0 else 0
    return tuple(LevelSpec(capacity=cap, ways=ways, bpe=bpe)
                 for _ in range(n_levels))


def uniform_levels(capacity: int, n_levels: int, *, ways: int = 4,
                   bpe: bool = True) -> tuple[LevelSpec, ...]:
    """Per-NODE sizing: every level gets the full ``capacity`` (each switch
    owns its own memory — the paper's testbed view, and the legacy
    ``fpe_capacity=`` call convention)."""
    return tuple(LevelSpec(capacity=max(0, capacity), ways=ways, bpe=bpe)
                 for _ in range(max(1, n_levels)))


def placement_levels(capacities: Sequence[int], enabled: Sequence[bool],
                     *, ways: int = 4, bpe: bool = True
                     ) -> tuple[LevelSpec, ...]:
    """Per-level specs from a fat-tree placement (DESIGN.md §9): each level
    gets its own per-switch capacity, and unplaced levels are forward-only
    hops — the per-switch knob replacing the uniform-budget split."""
    capacities = tuple(int(c) for c in capacities)
    enabled = tuple(bool(e) for e in enabled)
    if len(capacities) != len(enabled):
        raise ValueError("level_capacities and level_enabled differ in length")
    if not capacities:
        raise ValueError("a placement needs at least one level")
    return tuple(LevelSpec(capacity=c, ways=ways, bpe=bpe, enabled=e)
                 for c, e in zip(capacities, enabled))


def plan_from_placement(placement, *, op: str = "sum", ways: int = 4,
                        bpe: bool = True) -> CascadePlan:
    """Cascade for a ``planner.TreePlacement`` (duck-typed on
    ``level_capacities``/``level_enabled``): one node per tree level, each
    sized by the placed switch's own table budget."""
    return CascadePlan(op=op, levels=placement_levels(
        placement.level_capacities, placement.level_enabled,
        ways=ways, bpe=bpe))


def plan_from_configure(cfg, *, ways: int = 4, bpe: bool = True) -> CascadePlan:
    """Per-level memory partition of a controller ``ConfigureMsg``.

    ``cfg.fpe_capacity`` is the whole tree's combiner budget (the §4.2.2
    per-job partition); each of the tree's levels gets an even slice — the
    per-LEVEL partition the cascade executes.  A fat-tree placement
    (DESIGN.md §9) overrides that: when ``cfg.level_capacities`` is
    non-empty, every level runs at its placed switch's own capacity and
    unplaced levels forward.  ``cfg`` is duck-typed (``level_axes``,
    ``fpe_capacity``, ``op``) to avoid importing planner.
    """
    cfg = getattr(cfg, "configure", cfg)  # accept a JobPlan directly
    caps = tuple(getattr(cfg, "level_capacities", ()) or ())
    if caps:
        enabled = tuple(getattr(cfg, "level_enabled", ()) or
                        (True,) * len(caps))
        return CascadePlan(op=cfg.op, levels=placement_levels(
            caps, enabled, ways=ways, bpe=bpe))
    return CascadePlan(
        op=cfg.op,
        levels=even_split_levels(cfg.fpe_capacity, len(cfg.level_axes),
                                 ways=ways, bpe=bpe),
    )


def cascade_from_exchange_plan(xplan, *, ways: int = 4,
                               bpe: bool = True, op: str | None = None
                               ) -> CascadePlan:
    """Cascade for a gradient ``ExchangePlan``: one node per upper (scarce)
    axis hop.  A placement-carrying plan (``level_capacities`` set,
    DESIGN.md §9) sizes each hop from its placed switch's table; otherwise
    the plan's combiner budget is split evenly among the hops."""
    op = op if op is not None else getattr(xplan, "op", "sum")
    n = max(1, len(xplan.upper_axes))
    caps = tuple(getattr(xplan, "level_capacities", ()) or ())
    if len(caps) >= n:  # trailing entries = the upper (scarce) hops
        enabled = tuple(getattr(xplan, "level_enabled", ()) or
                        (True,) * len(caps))
        return CascadePlan(op=op, levels=placement_levels(
            caps[-n:], enabled[-n:], ways=ways, bpe=bpe))
    return CascadePlan(
        op=op,
        levels=even_split_levels(xplan.fpe_capacity, len(xplan.upper_axes),
                                 ways=ways, bpe=bpe),
    )


class LevelStats(NamedTuple):
    n_in: jnp.ndarray  # [] int32 — real pairs entering the node
    n_out: jnp.ndarray  # [] int32 — forwarded pairs leaving the node
    n_evict: jnp.ndarray  # [] int32 — FPE evictions at the node


def run_level(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    spec: LevelSpec,
    op: str,
    *,
    backend: str = "jnp",
    block_n: int = 512,
    interpret: bool = False,
    exact_stream: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray, LevelStats]:
    """One cascade hop on carried values; traceable inside jit/shard_map.

    Returns (out_keys, out_values, stats).  With ``capacity > 0`` the
    output is [capacity + n(+capacity)] (table flush + eviction stream,
    BPE-combined when ``spec.bpe``); with ``capacity == 0`` it is the
    exact packed combine of shape [n].  A disabled spec (``enabled ==
    False``, DESIGN.md §9) forwards the stream untouched: out == in,
    no evictions — the placement search's "this tier has no aggregation
    capability" hop.  ``exact_stream=False`` runs the node's FPE on the
    batched-block fast path (DESIGN.md §8): identical grouped totals,
    non-paper-faithful eviction pattern.
    """
    if not spec.enabled:
        n_real = jnp.sum(keys != EMPTY_KEY).astype(jnp.int32)
        return keys, values, LevelStats(
            n_in=n_real, n_out=n_real, n_evict=jnp.zeros((), jnp.int32))
    if spec.capacity == 0:
        n_in = jnp.sum(keys != EMPTY_KEY).astype(jnp.int32)
        c = kvagg.sorted_combine(keys, values, op=op)
        return c.unique_keys, c.combined_values, LevelStats(
            n_in=n_in, n_out=c.n_unique, n_evict=jnp.zeros((), jnp.int32))
    if backend == "pallas":
        from repro.kernels.kv_aggregate import fpe_aggregate_pallas

        tk, tv, ek, ev = fpe_aggregate_pallas(
            keys, values, capacity=spec.capacity, ways=spec.ways, op=op,
            block_n=block_n, interpret=interpret, exact_stream=exact_stream)
    elif backend == "jnp":
        tk, tv, ek, ev = kvagg.fpe_aggregate(
            keys, values, capacity=spec.capacity, ways=spec.ways, op=op,
            exact_stream=exact_stream)
    else:
        raise ValueError(f"unknown dataplane backend: {backend!r}")
    # one node-assembly policy for all paths (kvagg.assemble_node)
    res = kvagg.assemble_node(keys, tk, tv, ek, ev, op=op, bpe=spec.bpe)
    return res.out_keys, res.out_values, LevelStats(
        n_in=res.n_in, n_out=res.n_out, n_evict=res.n_evict)


class CascadeResult(NamedTuple):
    """Root output + per-level telemetry of one cascade execution.

    ``keys``/``values`` are the root stream (packed unique + finalized when
    run with the defaults).  ``n_in``/``n_out`` are the cascade's traffic
    endpoints: pairs entering level 0 and pairs leaving the last level
    (BEFORE any final packing — the wire metric).  The ``level_*`` arrays
    are leaf->root telemetry.
    """

    keys: jnp.ndarray
    values: jnp.ndarray
    n_in: jnp.ndarray  # [] int32
    n_out: jnp.ndarray  # [] int32
    level_in: jnp.ndarray  # [n_levels] int32
    level_out: jnp.ndarray  # [n_levels] int32
    level_evict: jnp.ndarray  # [n_levels] int32


@functools.partial(
    jax.jit,
    static_argnames=("plan", "backend", "block_n", "interpret",
                     "final_combine", "prepare", "finalize", "exact_stream"),
)
def run_cascade(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    plan: CascadePlan,
    *,
    backend: str = "jnp",
    block_n: int = 512,
    interpret: bool = False,
    final_combine: bool = True,
    prepare: bool = True,
    finalize: bool = True,
    exact_stream: bool = True,
) -> CascadeResult:
    """Execute a full multi-level cascade plan over one KV stream.

    The eviction-plus-flush stream of level *i* feeds level *i+1* (the
    paper's multi-hop streamline, Fig. 2b / ``reduction_model.simulate_chain``).
    ``prepare``/``finalize`` apply the op's carried-representation
    conversions at the edges; ``final_combine`` packs the root stream into
    unique keys (exact grouped result) without affecting ``n_out``, which
    always measures the traffic leaving the last level.  ``exact_stream=
    False`` runs every FPE on the batched-block fast path (DESIGN.md §8):
    grouped totals are identical, per-level eviction *traffic* may differ
    from the paper-faithful scan — keep the default for Fig. 9 curves.
    """
    op = aggops.get(plan.op)
    k = keys
    v = op.prepare_values(values) if prepare else values
    li, lo, le = [], [], []
    for spec in plan.levels:
        k, v, stats = run_level(k, v, spec, plan.op, backend=backend,
                                block_n=block_n, interpret=interpret,
                                exact_stream=exact_stream)
        li.append(stats.n_in)
        lo.append(stats.n_out)
        le.append(stats.n_evict)
    n_out = lo[-1]
    if final_combine:
        c = kvagg.sorted_combine(k, v, op=plan.op)
        k, v = c.unique_keys, c.combined_values
    if finalize:
        v = op.finalize_values(v)
    return CascadeResult(
        keys=k, values=v, n_in=li[0], n_out=n_out,
        level_in=jnp.stack(li), level_out=jnp.stack(lo),
        level_evict=jnp.stack(le),
    )


# ---------------------------------------------------------------------------
# Streaming (packet-batched) ingest — DESIGN.md §7.
# ---------------------------------------------------------------------------

_EMPTY = int(EMPTY_KEY)


class LevelState:
    """One cascade node ingesting packet-sized batches (DESIGN.md §7).

    The stateful, eager counterpart of :func:`run_level`: the FPE table
    persists *across* ``ingest`` calls — exactly a switch whose resident
    pairs survive between packets and leave only as evictions or in the
    end-of-task ``flush``.  ``net.sim`` runs one ``LevelState`` per switch;
    :func:`run_cascade_stream` chains one per level.

    ``batch_pad`` pads every ingest to a fixed length (the packet record
    capacity) so the underlying jitted FPE compiles once; batches longer
    than ``batch_pad`` are chunked.  Without ``batch_pad``, ingests are
    padded to the next power of two (min ``MIN_PAD``) — the shape-stable
    size buckets that keep the trace count O(log max_batch) across
    arbitrary packet lengths instead of one retrace per distinct length
    (DESIGN.md §8).  A ``capacity == 0`` spec is the exact unbounded
    node: it absorbs every record (no evictions) and emits its whole
    table at ``flush`` — ingests just buffer rows, compacted to the
    unique-key combine by a bulk ``sorted_combine`` (pow2-padded so the
    jit compiles once per size bucket) whenever the buffer tops
    ``COMPACT_THRESHOLD`` and at flush.

    ``exact_stream=False`` runs each ingest's FPE on the batched-block
    fast path (DESIGN.md §8) — same grouped totals and resident table
    geometry, eviction pattern not paper-faithful.  A disabled spec
    (``enabled == False``, DESIGN.md §9) makes the node a pure relay:
    every ingest forwards its real records verbatim and the flush is
    empty — how an unplaced fat-tree switch behaves.

    Telemetry mirrors :class:`LevelStats`: ``n_in`` real pairs ingested,
    ``n_evict`` FPE evictions, ``n_out`` pairs forwarded downstream
    (per-batch BPE-combined evictions when ``spec.bpe``, plus the flush);
    ``n_slots`` counts the padded input slots the FPE and BPE dispatches
    walked, real or empty.  ``level`` is the node's place in its cascade,
    the ``level`` arg of its ``dataplane.fpe`` / ``bpe`` / ``fetch`` spans
    (DESIGN.md §11).
    """

    #: pending-row count above which the capacity-0 node compacts its
    #: buffer with one bulk sorted_combine (keeps memory ~O(variety))
    COMPACT_THRESHOLD = 8192

    #: smallest shape-stable ingest pad (no batch_pad): packets shorter
    #: than this share one trace instead of one per tiny length
    MIN_PAD = 8

    def __init__(self, spec: LevelSpec, op: str, *,
                 batch_pad: int | None = None, exact_stream: bool = True,
                 level: int = 0):
        self.spec = spec
        self.level = level
        self.op = op
        self._aggop = aggops.get(op)
        self.batch_pad = batch_pad
        self.exact_stream = exact_stream
        self._tk: jnp.ndarray | None = None
        self._tv: jnp.ndarray | None = None
        # capacity == 0: buffered rows, bulk-combined lazily — per-record
        # combine() calls would pay a jax dispatch per record for jnp ops
        self._exact: list[tuple[np.ndarray, np.ndarray]] | None = (
            [] if spec.capacity == 0 and spec.enabled else None)
        self._exact_rows = 0
        self._value_sample: np.ndarray | None = None  # dtype/lane template
        self.n_in = 0
        self.n_evict = 0
        self.n_out = 0
        self.n_slots = 0
        self._flushed = False

    def _empty_out(self) -> tuple[np.ndarray, np.ndarray]:
        if self._value_sample is None:
            return (np.zeros((0,), np.int32), np.zeros((0,), np.float32))
        v = self._value_sample
        return (np.zeros((0,), np.int32),
                np.zeros((0,) + v.shape, v.dtype))

    def ingest(self, keys, values) -> tuple[np.ndarray, np.ndarray]:
        """Feed one batch of carried-representation records; returns the
        packed (keys, values) this node forwards downstream right now."""
        if self._flushed:
            raise RuntimeError("LevelState already flushed")
        keys = np.asarray(keys, np.int32)
        values = np.asarray(values)
        if keys.shape[0] != values.shape[0]:
            raise ValueError("keys/values leading dims differ")
        if self._value_sample is None and values.shape[0]:
            self._value_sample = np.zeros(values.shape[1:], values.dtype)
        real = keys != _EMPTY
        n_real = int(real.sum())
        self.n_in += n_real
        if not n_real:
            return self._empty_out()
        if not self.spec.enabled:  # forward-only hop (DESIGN.md §9)
            fk, fv = keys[real].astype(np.int32), values[real]
            self.n_out += int(fk.shape[0])
            return fk, fv
        if self._exact is not None:  # capacity == 0: exact unbounded node
            self._exact.append((keys[real], values[real]))
            self._exact_rows += n_real
            if self._exact_rows > self.COMPACT_THRESHOLD:
                self._compact_exact()
            return self._empty_out()
        if self.batch_pad:
            pad = self.batch_pad
        else:
            # shape-stable size bucket: next pow2 >= len (min MIN_PAD), so
            # varying packet lengths reuse O(log n) compiled traces
            pad = max(self.MIN_PAD,
                      1 << (int(keys.shape[0]) - 1).bit_length())
        out_k, out_v = [], []
        for lo in range(0, keys.shape[0], pad):
            chunk_real = (n_real if keys.shape[0] <= pad
                          else int(real[lo:lo + pad].sum()))
            ek, ev = self._ingest_chunk(keys[lo:lo + pad],
                                        values[lo:lo + pad], pad, chunk_real)
            if ek.size:
                out_k.append(ek)
                out_v.append(ev)
        if not out_k:
            return self._empty_out()
        fk, fv = np.concatenate(out_k), np.concatenate(out_v)
        self.n_out += fk.shape[0]
        return fk, fv

    def _ingest_chunk(self, keys: np.ndarray, values: np.ndarray,
                      pad: int, n_real: int) -> tuple[np.ndarray, np.ndarray]:
        tracer = obs_trace.get_tracer()
        level = self.level
        if keys.shape[0] < pad:
            fill = pad - keys.shape[0]
            keys = np.concatenate(
                [keys, np.full((fill,), _EMPTY, np.int32)])
            values = np.concatenate(
                [values, np.zeros((fill,) + values.shape[1:], values.dtype)])
        with tracer.span("dataplane.fpe", cat="dataplane",
                         args={"level": level, "slots": pad, "real": n_real}):
            res = kvagg.fpe_aggregate(
                jnp.asarray(keys), jnp.asarray(values),
                capacity=self.spec.capacity, ways=self.spec.ways, op=self.op,
                exact_stream=self.exact_stream,
                table_keys=self._tk, table_values=self._tv)
        self._tk, self._tv = res.table_keys, res.table_values
        self.n_slots += pad
        with tracer.span("dataplane.fetch", cat="dataplane",
                         args={"level": level, "what": "evict_count"}):
            evicted = np.asarray(res.evict_keys)
        n_evict = int(np.sum(evicted != _EMPTY))
        self.n_evict += n_evict
        ek, ev = res.evict_keys, res.evict_values
        if self.spec.bpe:  # combine this packet's evictions (fixed shape)
            slots = int(ek.shape[0])
            with tracer.span("dataplane.bpe", cat="dataplane",
                             args={"level": level, "slots": slots,
                                   "real": n_evict}):
                c = kvagg.sorted_combine(ek, ev, op=self.op)
            ek, ev = c.unique_keys, c.combined_values
            self.n_slots += slots
        with tracer.span("dataplane.fetch", cat="dataplane",
                         args={"level": level, "what": "evictions"}):
            ek, ev = np.asarray(ek), np.asarray(ev)
        mask = ek != _EMPTY
        return ek[mask], ev[mask]

    def _compact_exact(self) -> None:
        """Collapse the capacity-0 buffer to its unique-key combine (one
        bulk sorted_combine instead of per-record combine dispatches).
        Input is padded to a power-of-two length so the jitted combine
        compiles once per size bucket, not once per compaction."""
        k = np.concatenate([k for k, _ in self._exact])
        v = np.concatenate([v for _, v in self._exact])
        pad = max(1, 1 << (int(k.shape[0]) - 1).bit_length()) - k.shape[0]
        if pad:
            k = np.concatenate([k, np.full((pad,), _EMPTY, np.int32)])
            v = np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
        c = kvagg.sorted_combine(jnp.asarray(k), jnp.asarray(v), op=self.op)
        with obs_trace.get_tracer().span(
                "dataplane.fetch", cat="dataplane",
                args={"level": self.level, "what": "compact"}):
            nu = int(c.n_unique)
            ck = np.asarray(c.unique_keys)[:nu]
            cv = np.asarray(c.combined_values)[:nu]
        self._exact = [(ck, cv)]
        self._exact_rows = nu

    def flush(self) -> tuple[np.ndarray, np.ndarray]:
        """End-of-task flush: pack and emit every resident pair."""
        self._flushed = True
        if self._exact is not None:
            if not self._exact_rows:
                return self._empty_out()
            self._compact_exact()
            fk, fv = self._exact[0]
        elif self._tk is None:
            return self._empty_out()
        else:
            with obs_trace.get_tracer().span(
                    "dataplane.fetch", cat="dataplane",
                    args={"level": self.level, "what": "table"}):
                tk, tv = np.asarray(self._tk), np.asarray(self._tv)
            mask = tk != _EMPTY
            fk, fv = tk[mask].astype(np.int32), tv[mask]
        self.n_out += fk.shape[0]
        return fk, fv


def run_cascade_stream(
    batches: Iterable[tuple[jnp.ndarray, jnp.ndarray]],
    plan: CascadePlan,
    *,
    batch_pad: int | None = None,
    final_combine: bool = True,
    prepare: bool = True,
    finalize: bool = True,
    exact_stream: bool = True,
) -> CascadeResult:
    """Packet-batched counterpart of :func:`run_cascade` (DESIGN.md §7).

    ``batches`` is an iterator of (keys, values) ingests — packets off the
    wire instead of one monolithic array.  Per-level node state persists
    across batches and each batch's evictions cascade downstream
    immediately (the paper's streamline, batch- rather than task-clocked);
    the end-of-stream flush then drains the tables leaf to root.  Grouping
    the root stream by key equals :func:`run_cascade`'s exact result for
    every registered op — packetization changes *traffic* (what ``n_out``
    measures), never totals.

    Ingest is shape-stable: without ``batch_pad`` every packet is padded
    to a pow2 size bucket (``LevelState.MIN_PAD`` floor), so streaming
    arbitrary packet lengths compiles O(log max_len) FPE traces, not one
    per distinct length (DESIGN.md §8).  ``exact_stream=False`` runs all
    node FPEs on the batched-block fast path.

    Spans (DESIGN.md §11): ``dataplane.ingest`` per batch (args ``batch``,
    ``records``), inside it ``dataplane.level`` per node ingest and each
    blocking device-to-host read as ``dataplane.fetch``; then
    ``dataplane.flush`` per level and ``dataplane.root_combine``.
    """
    op = aggops.get(plan.op)
    tracer = obs_trace.get_tracer()
    states = [LevelState(spec, plan.op, batch_pad=batch_pad,
                         exact_stream=exact_stream, level=i)
              for i, spec in enumerate(plan.levels)]
    root_k: list[np.ndarray] = []
    root_v: list[np.ndarray] = []

    def push(i: int, k, v) -> None:
        if np.asarray(k).shape[0] == 0:
            return
        if i == len(states):
            root_k.append(np.asarray(k, np.int32))
            root_v.append(np.asarray(v))
            return
        with tracer.span("dataplane.level", cat="dataplane",
                         args={"level": i}):
            ek, ev = states[i].ingest(k, v)
        push(i + 1, ek, ev)

    with tracer.span("dataplane.run_cascade_stream", cat="dataplane"):
        for b, (k, v) in enumerate(batches):
            with tracer.span("dataplane.ingest", cat="dataplane",
                             args={"batch": b, "records": len(k)}):
                if prepare:
                    with tracer.span("dataplane.fetch", cat="dataplane",
                                     args={"what": "prepare"}):
                        v = np.asarray(op.prepare_values(jnp.asarray(v)))
                else:
                    v = np.asarray(v)
                push(0, np.asarray(k, np.int32), v)
        for i, st in enumerate(states):
            with tracer.span("dataplane.flush", cat="dataplane",
                             args={"level": i}):
                fk, fv = st.flush()
            push(i + 1, fk, fv)

    if root_k:
        rk = np.concatenate(root_k)
        rv = np.concatenate(root_v)
    else:
        rk = np.zeros((0,), np.int32)
        # empty root still needs the op's carried lane shape (mean carries
        # (sum, count)) or finalize below would index a missing lane axis
        tmpl = states[0]._value_sample
        if tmpl is not None:
            rv = np.zeros((0,) + tmpl.shape, tmpl.dtype)
        elif prepare:
            rv = np.asarray(op.prepare_values(jnp.zeros((0,), jnp.float32)))
        else:
            rv = np.zeros((0,), np.float32)
    with tracer.span("dataplane.root_combine", cat="dataplane",
                     args={"records": int(rk.shape[0])}):
        k_out, v_out = jnp.asarray(rk), jnp.asarray(rv)
        if final_combine and rk.size:
            c = kvagg.sorted_combine(k_out, v_out, op=plan.op)
            k_out, v_out = c.unique_keys, c.combined_values
    if finalize:
        v_out = op.finalize_values(v_out)
    i32 = lambda xs: jnp.asarray(np.asarray(xs, np.int32))  # noqa: E731
    _publish_levels(
        plan.op,
        [{"level": i, "records_in": int(s.n_in),
          "records_out": int(s.n_out), "evictions": int(s.n_evict),
          "slots": int(s.n_slots),
          "reduction": round(1.0 - int(s.n_out) / max(int(s.n_in), 1), 4)}
         for i, s in enumerate(states)],
        source="stream")
    return CascadeResult(
        keys=k_out, values=v_out,
        n_in=i32(states[0].n_in), n_out=i32(states[-1].n_out),
        level_in=i32([s.n_in for s in states]),
        level_out=i32([s.n_out for s in states]),
        level_evict=i32([s.n_evict for s in states]),
    )


def level_reductions(res: CascadeResult) -> jnp.ndarray:
    """Per-hop measured reduction ratio R_i = 1 - out_i/in_i (paper's R)."""
    return 1.0 - res.level_out / jnp.maximum(res.level_in, 1)


def end_to_end_reduction(res: CascadeResult) -> jnp.ndarray:
    """Whole-cascade reduction: traffic leaving the root vs entering leaf."""
    return 1.0 - res.n_out / jnp.maximum(res.n_in, 1)


def predicted_level_reductions(
    plan: CascadePlan, data_amount: int, key_variety: int
) -> list[float]:
    """Eq. 3 applied hop by hop: level *i* sees the (modeled) survivor
    stream of level *i-1*; key variety is preserved by aggregation."""
    preds = []
    m = float(max(1, data_amount))
    n = float(max(1, min(key_variety, data_amount)))
    for spec in plan.levels:
        if spec.capacity == 0:  # exact node: ideal reduction
            r = 1.0 - min(n, m) / m
        else:
            r = rm.reduction_ratio(m, min(n, m), spec.capacity)
        preds.append(r)
        m = max(n, m * (1.0 - r))
    return preds


def _publish_levels(op_name: str, levels: list, *, source: str) -> None:
    """Per-level cascade telemetry into the obs registry (DESIGN.md §11).

    ``run_cascade`` is jitted, so publishing happens at its observation
    points — :func:`telemetry` (post device_get, ``source="cascade"``)
    and the eager :func:`run_cascade_stream` (``source="stream"``).
    """
    reg = obs_metrics.get_registry()
    base = {"op": op_name, "source": source}
    for lvl in levels:
        lbl = dict(base, level=lvl["level"])
        reg.counter("dataplane.level.records_in_total",
                    **lbl).inc(lvl["records_in"])
        reg.counter("dataplane.level.records_out_total",
                    **lbl).inc(lvl["records_out"])
        reg.counter("dataplane.level.evictions_total",
                    **lbl).inc(lvl["evictions"])
        if "slots" in lvl:
            reg.counter("dataplane.level.slots_total",
                        **lbl).inc(lvl["slots"])
        reg.gauge("dataplane.level.reduction", **lbl).set(lvl["reduction"])
        if "predicted_reduction" in lvl:
            reg.gauge("dataplane.level.predicted_reduction",
                      **lbl).set(lvl["predicted_reduction"])


def telemetry(res: CascadeResult, plan: CascadePlan) -> dict:
    """JSON-able per-level report (the dry-run / bench record)."""
    li = [int(x) for x in jax.device_get(res.level_in)]
    lo = [int(x) for x in jax.device_get(res.level_out)]
    le = [int(x) for x in jax.device_get(res.level_evict)]
    levels = []
    for i, spec in enumerate(plan.levels):
        levels.append({
            "level": i,
            "capacity": spec.capacity,
            "records_in": li[i],
            "records_out": lo[i],
            "evictions": le[i],
            "reduction": round(1.0 - lo[i] / max(li[i], 1), 4),
        })
    report = {
        "op": plan.op,
        "levels": levels,
        "n_in": int(res.n_in),
        "n_out": int(res.n_out),
        "end_to_end_reduction": round(float(end_to_end_reduction(res)), 4),
    }
    _publish_levels(plan.op, levels, source="cascade")
    return report


def simulate_plan(
    plan: CascadePlan,
    *,
    data_amount: int = 4096,
    key_variety: int = 512,
    dist: str = "uniform",
    seed: int = 0,
    backend: str = "jnp",
    interpret: bool = False,
) -> dict:
    """Run a synthetic stream through the cascade and report per-level
    predicted (Eq. 3) vs simulated reduction — the dry-run's dataplane
    validation record.
    """
    gen = rm.uniform_keys if dist == "uniform" else rm.zipf_keys
    keys = jnp.asarray(gen(data_amount, key_variety, seed=seed).astype("int32"))
    values = jnp.ones((data_amount,), jnp.float32)
    res = run_cascade(keys, values, plan, backend=backend, interpret=interpret)
    report = telemetry(res, plan)
    preds = predicted_level_reductions(plan, data_amount, key_variety)
    reg = obs_metrics.get_registry()
    for lvl, p in zip(report["levels"], preds):
        lvl["predicted_reduction"] = round(p, 4)
        # same label set telemetry() used, so the dashboard can join the
        # Eq.3 prediction against the measured reduction per level
        reg.gauge("dataplane.level.predicted_reduction", op=plan.op,
                  source="cascade",
                  level=lvl["level"]).set(lvl["predicted_reduction"])
    report["dist"] = dist
    report["data_amount"] = data_amount
    report["key_variety"] = key_variety
    return report
