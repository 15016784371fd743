"""Discrete-event job simulator: mappers -> switch cascade -> reducer
(DESIGN.md §7; paper §6 Figs. 9-10).

The missing layer between the planner and the dataplane: the planner
*models* per-level bytes and drain times, the dataplane *computes* exact
aggregation — this module runs a whole job over an emulated network and
measures what the paper measures: job completion time, per-link wire
bytes, and drain time, with or without in-network aggregation.

Topology: ``fanins`` leaf->root (e.g. ``(4, 2)`` = 8 mappers, two level-0
switches of fan-in 4, one root of fan-in 2).  Every tree edge is its own
FIFO :class:`~repro.net.links.Link`; every edge runs a reliable go-back-N
flow (``net.transport``) whose receiver dedupes on PSN before the records
touch aggregation state.  Each switch owns one ``dataplane.LevelState``
node (its slice of the job's ``CascadePlan``), charges line-rate
processing per packet, re-packs its eviction stream into MTU frames
(``net.wire``) as it goes, and flushes downstream once every child has
sent end-of-task.  The root's stream crosses the reducer in-link — the
paper testbed's 10 GbE bottleneck — and JCT is the arrival of the final
end-of-task byte at the reducer.

Because links are FIFO and flows are per-edge, the engine runs level by
level: a node's full arrival schedule is known once its children finished,
so no global event heap is needed — arrivals are merged in time order and
ingested sequentially, which keeps the hash-table dynamics honest.

Concurrency: :func:`simulate_jobs` steps a whole batch of independent
jobs level by level in lockstep, so tiers at the same depth that share a
kernel-static signature run as ONE batched ``vsim.tier_ingest`` call
(multi-job tier batching, DESIGN.md §10) — results are bit-identical to
running each job alone.

``aggregate=False`` is the host-only baseline: switches forward records
unaggregated and the reducer in-link carries the entire map output — the
configuration the paper's Fig. 10 JCT comparison is measured against.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Callable, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import aggops, dataplane, kvagg
from repro.obs import trace as obs_trace
from . import links as links_lib
from . import schema as schema_lib
from . import transport, vsim, wire

_EMPTY = int(kvagg.EMPTY_KEY)

#: paper-testbed defaults, in the planner's 1e9-bytes/s unit
TEN_GBE = 1.25  # 10 GbE link
LINE_RATE = 5.0  # 40 Gb/s processing engine


@dataclasses.dataclass(frozen=True)
class NetConfig:
    """Knobs of the emulated network (defaults: the paper's testbed)."""

    link_gbps: tuple[float, ...] | None = None  # per tree level, leaf->root
    reducer_gbps: float | None = None  # reducer in-link; default root level
    processing_gbps: float = LINE_RATE  # switch line-rate processing charge
    propagation_s: float = 1e-6
    loss_rate: float = 0.0
    seed: int = 0
    window: int = 16  # go-back-N window
    timeout_s: float | None = None  # None: per-link conservative RTO
    records_per_packet: int = wire.RECORDS_PER_PACKET
    #: False runs every switch FPE on the batched-block fast path
    #: (DESIGN.md §8): same delivered totals, eviction traffic not
    #: paper-faithful — keep True for Fig. 9/10 reproductions
    exact_stream: bool = True
    #: "node" steps one Python node per switch (the oracle);
    #: "vectorized" batches each tier's per-packet FPE work into one
    #: jitted call (DESIGN.md §10) — bit-identical results at any loss
    #: rate, orders of magnitude more simulated switch-steps per second
    engine: str = "node"
    #: optional ``transport.LossModel`` override (e.g. an explicit
    #: drop-mask model in the property tests); ``None`` builds
    #: ``LossModel(loss_rate, seed)``.  The model's ``rate`` must be > 0
    #: for the lossy transport path to engage, and its ``drop`` /
    #: ``drop_array`` must stay elementwise-consistent so both engines
    #: see the same loss pattern.
    loss_model: transport.LossModel | None = None
    #: restart incarnation stamped on every packet header (DESIGN.md §12).
    #: 0 everywhere outside the fault driver; ``simulate_job_with_faults``
    #: bumps it per epoch so receivers dedupe across incarnations.
    epoch: int = 0

    def __post_init__(self):
        if self.engine not in ("node", "vectorized"):
            raise ValueError(f"unknown sim engine {self.engine!r} "
                             "(expected 'node' or 'vectorized')")
        if not 0.0 <= self.loss_rate < 1.0:
            raise ValueError(f"loss_rate {self.loss_rate!r} outside [0, 1)")


class _Node:
    """One switch: PSN-dedupe gate + one cascade level + output packetizer."""

    def __init__(self, *, level: int, n_children: int,
                 spec: dataplane.LevelSpec | None, op: str, aggregate: bool,
                 cfg: NetConfig, job_id: int, flow_id: int):
        self.level = level
        self.n_children = n_children
        # a disabled spec (placement left this tier out, DESIGN.md §9) is a
        # forward-only switch — same path as the host-only baseline
        self.aggregate = aggregate and (spec is None or spec.enabled)
        self.state = (dataplane.LevelState(
            spec, op, batch_pad=cfg.records_per_packet,
            exact_stream=cfg.exact_stream, level=level)
            if self.aggregate else None)
        self.receiver = transport.Receiver()
        self.proc_free = 0.0
        self.proc_rate = cfg.processing_gbps * 1e9
        self.rpp = cfg.records_per_packet
        self.job_id = job_id
        self.epoch = int(cfg.epoch)
        self.flow_id = flow_id  # of the uplink flow this node sends
        self.out: list[tuple[float, wire.Packet]] = []  # (t_ready, pkt)
        self._psn = 0
        self._pend_k: np.ndarray | None = None
        self._pend_v: np.ndarray | None = None
        self._eot_seen = 0
        self.records_in = 0
        self.records_out = 0
        self.bytes_out = 0  # wire bytes of every packet this node emits
        self.agg_proc_s = 0.0  # aggregation-engine busy seconds (0 if relay)
        self.queue_peak = 0  # deepest the output pending queue ever got
        self.finished = False
        # fault-plane timing: when the table first held state and when the
        # EoT flush completed — the window a table_wipe can corrupt (§12)
        self.t_first_ingest = math.inf
        self.t_finish = math.inf

    def _append(self, keys: np.ndarray, values: np.ndarray) -> None:
        if self._pend_k is None:
            self._pend_k, self._pend_v = keys, values
        else:
            self._pend_k = np.concatenate([self._pend_k, keys])
            self._pend_v = np.concatenate([self._pend_v, values])
        self.queue_peak = max(self.queue_peak, int(self._pend_k.shape[0]))

    def _emit_packet(self, t: float, keys: np.ndarray, values: np.ndarray,
                     eot: bool) -> None:
        hdr = wire.PacketHeader(
            job_id=self.job_id, flow_id=self.flow_id, level=self.level + 1,
            psn=self._psn, n_records=int(keys.shape[0]), eot=eot,
            epoch=self.epoch)
        self._psn += 1
        self.records_out += int(keys.shape[0])
        pkt = wire.Packet(header=hdr, keys=keys, values=values)
        self.bytes_out += pkt.wire_bytes
        self.out.append((t, pkt))

    def _emit_full(self, t: float) -> None:
        while self._pend_k is not None and self._pend_k.shape[0] >= self.rpp:
            k, self._pend_k = self._pend_k[:self.rpp], self._pend_k[self.rpp:]
            v, self._pend_v = self._pend_v[:self.rpp], self._pend_v[self.rpp:]
            self._emit_packet(t, k, v, eot=False)

    def receive(self, pkt: wire.Packet, t_arrive: float) -> None:
        """Ingest one arrival: dedupe on PSN, charge line-rate processing,
        cascade the records, and re-frame whatever leaves the node."""
        if not self.receiver.accept(pkt.header):
            return  # gap or duplicate: discarded before aggregation state
        t = t_arrive
        if pkt.header.n_records:
            start = max(t_arrive, self.proc_free)
            busy = pkt.wire_bytes / self.proc_rate
            self.proc_free = start + busy
            t = self.proc_free
            if self.aggregate:  # a relay's charge is store-and-forward,
                self.agg_proc_s += busy  # not aggregation-engine work
            self.records_in += pkt.header.n_records
            self.t_first_ingest = min(self.t_first_ingest, t_arrive)
            if self.aggregate:
                ek, ev = self.state.ingest(pkt.keys, pkt.values)
            else:  # host-only baseline: forward unaggregated
                ek = np.asarray(pkt.keys, np.int32)
                ev = np.asarray(pkt.values)
            if ek.shape[0]:
                self._append(ek, ev)
                self._emit_full(t)
        if pkt.header.eot:
            self._eot_seen += 1
            if self._eot_seen == self.n_children:
                self._finish(max(t, self.proc_free))

    def _finish(self, t: float) -> None:
        if self.aggregate:
            fk, fv = self.state.flush()
            if fk.shape[0]:
                # EoT flush streams out at the processing line rate too
                busy = fk.shape[0] * wire.PAIR_BYTES / self.proc_rate
                self.agg_proc_s += busy
                self.proc_free = max(t, self.proc_free) + busy
                t = self.proc_free
                self._append(fk, fv)
        self._emit_full(t)
        if self._pend_k is not None and self._pend_k.shape[0]:
            self._emit_packet(t, self._pend_k, self._pend_v, eot=True)
            self._pend_k = self._pend_v = None
        else:  # the flush trigger must cross the wire even when empty
            self._emit_packet(
                t, np.zeros((0,), np.int32),
                np.zeros((0,), np.float32), eot=True)
        self.t_finish = t
        self.finished = True


@dataclasses.dataclass
class SimResult:
    """Everything one simulated job run measured."""

    jct_s: float
    aggregate: bool
    op: str
    fanins: tuple[int, ...]
    axes: tuple[str, ...]
    delivered_keys: np.ndarray  # reducer's final table, packed + finalized
    delivered_values: np.ndarray
    delivered_records: int  # records the reducer hands the application
    delivered_bytes: int  # wire bytes of the delivered stream
    arrived_records: int  # records arriving at the reducer pre-merge
    link_stats: dict[str, dict]  # per axis (+ "reducer"), links.stats_by_axis
    per_level: list[dict]
    retransmissions: int
    timeouts: int
    packets_dropped: int
    gap_discards: int
    duplicate_discards: int
    mapper_finish_s: list[float]

    def delivered_table(self) -> dict[int, float]:
        return {int(k): np.asarray(v).tolist() if np.ndim(v) else float(v)
                for k, v in zip(self.delivered_keys, self.delivered_values)}

    def report(self) -> dict:
        """JSON-able record in the unified schema (``net.schema``) —
        identical keys from both engines, bench/dry-run/dashboard shape."""
        return schema_lib.report_dict(self)


def _default_axes(n: int) -> tuple[str, ...]:
    return tuple(f"lvl{i}" for i in range(n))


@dataclasses.dataclass(frozen=True)
class JobSpec:
    """One job's inputs to :func:`simulate_jobs` — exactly
    :func:`simulate_job`'s signature as data, so a batch of concurrent
    jobs can run through the level-lockstep engine together."""

    keys: object
    values: object
    fanins: Sequence[int]
    plan: dataplane.CascadePlan | None = None
    op: str = "sum"
    aggregate: bool = True
    cfg: NetConfig | None = None
    axes: Sequence[str] | None = None
    mapper_delay: Callable[[int], float] | None = None
    job_id: int = 0
    #: telemetry tag: labels this job's metric series and names its trace
    #: track (placement policy, comparison leg, ...); default "job<id>"
    tag: str = ""

    def __post_init__(self):
        fanins = tuple(int(f) for f in self.fanins)
        if not fanins or any(f < 1 for f in fanins):
            raise ValueError(f"bad fanins {fanins}: every level needs a "
                             "positive mapper/child count")


class _FaultCtx:
    """One restart epoch's view of the failure plane (DESIGN.md §12).

    Built by the epoch driver (:func:`simulate_job_with_faults`) and
    threaded through ``_JobRun``: maps the injector's *absolute*-time
    events onto this epoch's relative timeline (``rel = t_s - t_start_s``,
    clamped at 0 for failures that predate the epoch), carries the
    positions already known dead (``bypass`` — forward-only relays), the
    persistent per-position :class:`transport.Receiver` gates that survive
    restarts, and collects the epoch's :class:`FailureVerdict`s.
    """

    def __init__(self, *, injector, policy, epoch: int, t_start_s: float,
                 bypass: frozenset, fired_wipes: set, receivers: dict):
        self.injector = injector
        self.policy = policy
        self.epoch = int(epoch)
        self.t_start_s = float(t_start_s)
        self.bypass = bypass  # {(level, switch)} dead -> relay positions
        self.fired_wipes = fired_wipes  # indices into injector.events
        self.receivers = receivers  # {(level, switch) | ("reducer", 0)}
        self.verdicts: list = []
        self.retry = transport.RetryPolicy(
            backoff=policy.backoff, max_timeouts=policy.max_timeouts,
            max_timeout_s=policy.max_timeout_s)
        self._at: dict[tuple[int, int], list] = {}
        for i, e in enumerate(injector.events):
            self._at.setdefault((e.level, e.switch), []).append((i, e))

    def _events_at(self, level: int, switch: int) -> list:
        return self._at.get((level, switch), [])

    def _level_active(self, level: int, kinds=None) -> bool:
        for (l, s), evs in self._at.items():
            if l != level or (l, s) in self.bypass:
                continue
            for i, e in evs:
                if kinds is not None and e.kind not in kinds:
                    continue
                if e.kind == "table_wipe" and i in self.fired_wipes:
                    continue
                if (e.kind == "link_down"
                        and e.t_s + e.duration_s <= self.t_start_s):
                    continue  # window fully in a previous incarnation
                return True
        return False

    def tier_faulted(self, level: int) -> bool:
        """Does tier ``level`` need the fault-aware node path?  Yes when a
        switch of the tier is a bypass relay or has a pending event, or
        when the tier below has a pending crash (this tier is the parent
        that must liveness-detect the truncated child stream)."""
        if any(p[0] == level for p in self.bypass):
            return True
        if self._level_active(level):
            return True
        return level >= 1 and self._level_active(
            level - 1, kinds=("switch_crash",))

    def crash_rel(self, level: int, switch: int) -> float | None:
        """This epoch's crash instant of (level, switch), relative to the
        epoch start — 0 if the (undetected) crash predates it."""
        if (level, switch) in self.bypass:
            return None
        ts = [e.t_s for _, e in self._events_at(level, switch)
              if e.kind == "switch_crash"]
        if not ts:
            return None
        return max(0.0, min(ts) - self.t_start_s)

    def edge_fault(self, level: int, switch: int, child: int, *,
                   crash_rel: float | None,
                   bypassed: bool) -> transport.EdgeFault | None:
        if bypassed:
            return None  # the recovery re-route is assumed healthy
        windows = []
        for _, e in self._events_at(level, switch):
            if e.kind != "link_down" or (e.child is not None
                                         and e.child != child):
                continue
            t0 = e.t_s - self.t_start_s
            t1 = t0 + e.duration_s
            if t1 > 0:
                windows.append((max(0.0, t0), t1))
        if crash_rel is None and not windows:
            return None
        return transport.EdgeFault(dead_from_s=crash_rel,
                                   down_windows=tuple(sorted(windows)))

    def wipe_rel(self, level: int, switch: int, t_first_ingest: float,
                 t_finish: float) -> float | None:
        """A pending table wipe that lands while the switch's table holds
        state (first ingest <= t < EoT flush) — locally visible, so the
        switch self-reports the instant it happens.  Wipes outside the
        state window are harmless and fire silently."""
        for i, e in self._events_at(level, switch):
            if e.kind != "table_wipe" or i in self.fired_wipes:
                continue
            rel = e.t_s - self.t_start_s
            if rel >= 0 and t_first_ingest <= rel < t_finish:
                return rel
        return None

    def liveness_s(self, link, window: int, timeout_s: float | None) -> float:
        """Parent-side liveness timeout: how long a node waits past its
        last arrival before declaring an EoT-less child dead.  Default:
        the time a sender needs to exhaust its own retry budget on this
        link (base RTO through the full backoff ladder), so both
        detection paths date verdicts comparably."""
        if self.policy.liveness_timeout_s is not None:
            return self.policy.liveness_timeout_s
        if timeout_s is None:
            timeout_s = 2.0 * (window * link.serialize_s(wire.MTU_BYTES)
                               + 2.0 * link.propagation_s)
        return sum(self.retry.rto(timeout_s, i)
                   for i in range(self.policy.max_timeouts + 1))

    def attach_receiver(self, pos) -> transport.Receiver:
        """The persistent PSN/epoch gate of one position; created on first
        use, reused across epochs (discard counters are per-epoch)."""
        rcv = self.receivers.get(pos)
        if rcv is None:
            rcv = transport.Receiver()
            self.receivers[pos] = rcv
        rcv.gap_discards = 0
        rcv.duplicate_discards = 0
        rcv.stale_epoch_discards = 0
        return rcv

    def add_verdict(self, kind: str, level: int, switch: int, *,
                    t_detect_rel: float, detected_by: str) -> None:
        from repro.runtime.fault_tolerance import FailureVerdict

        self.verdicts.append(FailureVerdict(
            kind=kind, level=level, switch=switch, epoch=self.epoch,
            t_detect_s=self.t_start_s + t_detect_rel,
            detected_by=detected_by))


class _JobRun:
    """Mutable per-job state while :func:`simulate_jobs` steps the batch
    level by level.  Jobs never interact — each owns its links, flows,
    and streams; the lockstep exists only so same-depth tiers can share
    batched kernel calls."""

    def __init__(self, spec: JobSpec, faults: _FaultCtx | None = None):
        cfg = spec.cfg or NetConfig()
        # engine/loss-rate/fanin validity is a dataclass invariant now:
        # NetConfig.__post_init__ and JobSpec.__post_init__ raise at
        # construction, before any simulation state exists
        fanins = tuple(int(f) for f in spec.fanins)
        n_levels = len(fanins)
        axes = (tuple(spec.axes) if spec.axes is not None
                else _default_axes(n_levels))
        if len(axes) != n_levels:
            raise ValueError("axes must match fanins")
        op, plan, aggregate = spec.op, spec.plan, spec.aggregate
        if plan is not None:
            op = plan.op  # the plan owns the op even for the baseline
        if aggregate:
            if plan is None:
                plan = dataplane.CascadePlan(op=op, levels=tuple(
                    dataplane.LevelSpec(capacity=0) for _ in fanins))
            if len(plan.levels) != n_levels:
                raise ValueError(
                    f"plan has {len(plan.levels)} levels, tree has "
                    f"{n_levels}")
        link_gbps = (tuple(cfg.link_gbps) if cfg.link_gbps is not None
                     else (TEN_GBE,) * n_levels)
        if len(link_gbps) != n_levels:
            raise ValueError("link_gbps must match fanins")
        self.cfg = cfg
        self.fanins = fanins
        self.n_levels = n_levels
        self.axes = axes
        self.op = op
        self.plan = plan
        self.aggregate = aggregate
        self.aggop = aggops.get(op)
        self.link_gbps = link_gbps
        self.reducer_gbps = (cfg.reducer_gbps if cfg.reducer_gbps is not None
                             else link_gbps[-1])
        self.job_id = spec.job_id
        self.tag = spec.tag or f"job{spec.job_id}"
        self.faults = faults
        # one virtual-time trace track per run (DESIGN.md §11): per-level
        # ingest/transport lanes on their own pid so repeated runs and
        # concurrent jobs never interleave on one lane
        tracer = obs_trace.get_tracer()
        self._pid: int | None = None
        if tracer.enabled:
            leg = "" if spec.aggregate else " (host-only)"
            if faults is not None:
                leg += f" e{faults.epoch}"
            self._pid = tracer.new_track(f"sim {self.tag}{leg}")

        n_mappers = math.prod(fanins)
        self.keys = np.asarray(spec.keys, np.int32)
        self.carried = np.asarray(self.aggop.prepare_values(
            jnp.asarray(np.asarray(spec.values))))
        self.loss = (cfg.loss_model if cfg.loss_model is not None
                     else transport.LossModel(cfg.loss_rate, cfg.seed))
        self.all_links: list[links_lib.Link] = []
        self.flows = transport.FlowStats()
        self.mapper_finish = [0.0] * n_mappers
        self.fast_engine = cfg.engine == "vectorized"
        self.next_flow_id = n_mappers
        self.per_level_nodes: list[list] = []
        self.reducer_gap = 0
        self.reducer_dup = 0

        # mapper output flows (flow ids 0..n_mappers-1); streams live as
        # Packet lists (node path) or array-form PacketStreams (fast path)
        t0s = [float(spec.mapper_delay(m)) if spec.mapper_delay is not None
               else 0.0 for m in range(n_mappers)]
        if self.fast_engine:
            self.current: list = vsim.streams_from_mapper_records(
                self.keys, self.carried, t0s, n_mappers=n_mappers,
                job_id=self.job_id, level=0, rpp=cfg.records_per_packet,
                epoch=int(cfg.epoch))
        else:
            key_chunks = np.array_split(self.keys, n_mappers)
            val_chunks = np.array_split(self.carried, n_mappers)
            self.current = []
            for m in range(n_mappers):
                pkts = wire.pack_records(
                    key_chunks[m], val_chunks[m], job_id=self.job_id,
                    flow_id=m, level=0, eot=True,
                    records_per_packet=cfg.records_per_packet,
                    epoch=int(cfg.epoch))
                self.current.append([(t0s[m], p) for p in pkts])

    def _note_tier(self, l: int, *, t0: float, t1: float,
                   kind: str) -> None:
        """Replay one tier interval onto this run's virtual-time track
        (span taxonomy: ``sim.transport`` = child flows draining,
        ``sim.ingest`` = switch accept/aggregate/re-frame window)."""
        tracer = obs_trace.get_tracer()
        if not tracer.enabled or self._pid is None:
            return
        tid = 2 * l + (1 if kind == "ingest" else 0)
        name = f"L{l} {self.axes[l]} {kind}"
        tracer.name_thread(self._pid, tid, name)
        tracer.add_span(name, t0, t1, cat=f"sim.{kind}", pid=self._pid,
                        tid=tid, args={"level": l, "axis": self.axes[l]})

    def _add_flow(self, st: transport.FlowStats) -> None:
        self.flows.packets_sent += st.packets_sent
        self.flows.packets_dropped += st.packets_dropped
        self.flows.retransmissions += st.retransmissions
        self.flows.timeouts += st.timeouts
        self.flows.wire_bytes += st.wire_bytes

    def _run_flow(self, stream, link, sink) -> float:
        arrivals: list[tuple[float, wire.Packet]] = []
        fid = stream[0][1].header.flow_id
        t_done, st = transport.send_stream(
            stream, link, self.loss, flow_id=fid, window=self.cfg.window,
            timeout_s=self.cfg.timeout_s,
            deliver=lambda p, t: arrivals.append((t, p)))
        self._add_flow(st)
        sink.extend(arrivals)
        return t_done

    def start_tier(self, l: int) -> vsim.TierWork | None:
        """Run tier *l*'s front half.  Fast-path tiers return a
        ``TierWork`` for the shared kernel dispatch; node-path tiers
        (host-only engine, or capacity-0 exact levels) run to completion
        here and return ``None``."""
        if self.faults is not None and self.faults.tier_faulted(l):
            # fault-affected tiers walk the node path: per-edge faults,
            # backoff senders, and persistent receivers have no array
            # form — clean tiers keep the fast path, so the vectorized
            # engine stays bit-identical where nothing is broken (§12)
            self._run_tier_node_faulted(l)
            return None
        spec = self.plan.levels[l] if self.aggregate else None
        # forward-only tiers (host-only baseline, placement-disabled hops)
        # have no aggregation state at all, so the fast path covers them
        # with pure array re-framing — no kernel call
        fast_forward = self.fast_engine and (
            not self.aggregate or (spec is not None and not spec.enabled))
        if fast_forward or (self.fast_engine and self.aggregate
                            and vsim.supports(spec)):
            # fast path (DESIGN.md §10): the whole tier — transport (any
            # loss rate), acceptance, processing, re-framing, telemetry —
            # as array passes plus at most one jitted kernel call,
            # bit-identical to the node walk
            streams = [
                s if isinstance(s, vsim.PacketStream)
                else vsim.stream_from_packets(
                    s, value_template=self.carried[:0])
                for s in self.current]
            return vsim.tier_start(
                streams, level=l, fanin=self.fanins[l],
                spec=None if fast_forward else spec, op=self.op,
                cfg=self.cfg, axis=self.axes[l], gbps=self.link_gbps[l],
                job_id=self.job_id, first_flow_id=self.next_flow_id,
                value_template=self.carried[:0], loss=self.loss)
        self._run_tier_node(l)
        return None

    def finish_tier(self, l: int, work: vsim.TierWork) -> None:
        """Consume tier *l*'s dispatched kernel slice and advance."""
        nodes, out_streams, tier_links, tier_flow, t_done = \
            vsim.tier_finish(work)
        self.next_flow_id += work.n_switches
        self.all_links.extend(tier_links)
        self._add_flow(tier_flow)
        if l == 0:
            self.mapper_finish = list(t_done)
        self.per_level_nodes.append(nodes)
        self.current = out_streams
        if self._pid is not None and obs_trace.get_tracer().enabled:
            t0 = float(work.t_m.min()) if work.t_m.size else 0.0
            t_tx = max(t_done, default=t0)
            self._note_tier(l, t0=t0, t1=t_tx, kind="transport")
            t_out = max((float(s.times[-1]) for s in out_streams
                         if s.times.size), default=t_tx)
            self._note_tier(l, t0=t0, t1=t_out, kind="ingest")

    def _run_tier_node(self, l: int) -> None:
        # node path tiers (host-only engine, capacity-0 exact levels)
        # walk materialized packets
        fanin = self.fanins[l]
        n_switches = math.prod(self.fanins[l + 1:])
        spec = self.plan.levels[l] if self.aggregate else None
        current = [
            vsim.stream_to_packets(s) if isinstance(s, vsim.PacketStream)
            else s for s in self.current]
        nodes: list[_Node] = []
        nxt: list[list[tuple[float, wire.Packet]]] = []
        t_first, t_tx, t_out = math.inf, 0.0, 0.0
        for s in range(n_switches):
            # phase A — transport: run every child-edge flow; links are
            # FIFO and flows per-edge, so the switch's full arrival
            # schedule is known before its node steps
            arrivals: list[tuple[float, wire.Packet]] = []
            for c in range(fanin):
                ci = s * fanin + c
                link = links_lib.Link(
                    name=f"{self.axes[l]}.s{s}.c{c}", axis=self.axes[l],
                    gbps=self.link_gbps[l],
                    propagation_s=self.cfg.propagation_s)
                self.all_links.append(link)
                t_done = self._run_flow(current[ci], link, arrivals)
                t_tx = max(t_tx, t_done)
                if l == 0:
                    self.mapper_finish[ci] = t_done
            arrivals.sort(key=lambda a: (a[0], a[1].header.flow_id,
                                         a[1].header.psn))
            # phase B — host walk: acceptance, aggregation, timing,
            # packetization, and telemetry through the node code
            node = _Node(level=l, n_children=fanin, spec=spec, op=self.op,
                         aggregate=self.aggregate, cfg=self.cfg,
                         job_id=self.job_id, flow_id=self.next_flow_id)
            self.next_flow_id += 1
            for t, p in arrivals:
                node.receive(p, t)
            assert node.finished, "reliable transport must complete the node"
            nodes.append(node)
            nxt.append(node.out)
            if arrivals:
                t_first = min(t_first, arrivals[0][0])
            if node.out:
                t_out = max(t_out, max(t for t, _ in node.out))
        self.per_level_nodes.append(nodes)
        self.current = nxt
        if self._pid is not None and obs_trace.get_tracer().enabled:
            t0 = 0.0 if math.isinf(t_first) else t_first
            self._note_tier(l, t0=t0, t1=max(t_tx, t0), kind="transport")
            self._note_tier(l, t0=t0, t1=max(t_out, t0), kind="ingest")

    def _run_tier_node_faulted(self, l: int) -> None:
        """Tier *l* under the fault plane (DESIGN.md §12): per-edge
        ``EdgeFault``s with the armed backoff/verdict retry policy on
        faulted edges (clean edges keep the legacy constant-RTO sender,
        bit for bit), persistent receivers across restart epochs, crash
        truncation of arrivals and in-flight output, and all three
        detection paths — sender retry exhaustion, parent liveness on an
        EoT-less child stream, and self-reported table wipes."""
        fx = self.faults
        cfg = self.cfg
        fanin = self.fanins[l]
        n_switches = math.prod(self.fanins[l + 1:])
        spec = self.plan.levels[l] if self.aggregate else None
        current = [
            vsim.stream_to_packets(s) if isinstance(s, vsim.PacketStream)
            else s for s in self.current]
        nodes: list[_Node] = []
        nxt: list[list[tuple[float, wire.Packet]]] = []
        t_first, t_tx, t_out = math.inf, 0.0, 0.0
        for s in range(n_switches):
            pos = (l, s)
            bypassed = pos in fx.bypass
            crash_rel = fx.crash_rel(l, s)
            arrivals: list[tuple[float, wire.Packet]] = []
            silent: list[int] = []  # child edges whose stream was cut short
            link = None
            for c in range(fanin):
                ci = s * fanin + c
                link = links_lib.Link(
                    name=f"{self.axes[l]}.s{s}.c{c}", axis=self.axes[l],
                    gbps=self.link_gbps[l],
                    propagation_s=cfg.propagation_s)
                self.all_links.append(link)
                stream = current[ci]
                if not stream:  # the child died before emitting anything
                    silent.append(c)
                    continue
                fault = fx.edge_fault(l, s, c, crash_rel=crash_rel,
                                      bypassed=bypassed)
                retry = (fx.retry if fault is not None
                         else transport.DEFAULT_RETRY)
                try:
                    t_done, st = transport.send_stream(
                        stream, link, self.loss,
                        flow_id=stream[0][1].header.flow_id,
                        window=cfg.window, timeout_s=cfg.timeout_s,
                        deliver=lambda p, t: arrivals.append((t, p)),
                        retry=retry, fault=fault)
                    self._add_flow(st)
                    if not stream[-1][1].header.eot:
                        silent.append(c)  # truncated upstream: no EoT to send
                except transport.PeerDeadError as e:
                    # sender-side verdict: this switch is declared dead
                    # (really dead, or a link-down window outlived the
                    # retry budget — the false-positive the bypass must
                    # also survive)
                    t_done = e.t_s
                    if e.stats is not None:
                        self._add_flow(e.stats)
                    fx.add_verdict(
                        "switch_crash" if crash_rel is not None
                        else "link_down",
                        l, s, t_detect_rel=e.t_s, detected_by="sender")
                t_tx = max(t_tx, t_done)
                if l == 0:
                    self.mapper_finish[ci] = t_done
            arrivals.sort(key=lambda a: (a[0], a[1].header.flow_id,
                                         a[1].header.psn))
            node = _Node(level=l, n_children=fanin, spec=spec, op=self.op,
                         aggregate=self.aggregate and not bypassed, cfg=cfg,
                         job_id=self.job_id, flow_id=self.next_flow_id)
            self.next_flow_id += 1
            node.receiver = fx.attach_receiver(pos)
            for t, p in arrivals:
                node.receive(p, t)
            if crash_rel is not None:
                # the crash loses the in-flight table: output the switch
                # would have produced at or after the instant never made
                # the wire, and the EoT it owed its parent dies with it
                node.out = [(t, p) for t, p in node.out if t < crash_rel]
            elif not node.finished:
                # a child went silent (dead switch below, or a sender that
                # gave this node up): declare EoT-less children dead by
                # liveness timeout, then flush what did arrive so the
                # epoch's timeline completes without cascading false
                # verdicts up the tree
                t_last = arrivals[-1][0] if arrivals else 0.0
                t_detect = t_last + fx.liveness_s(link, cfg.window,
                                                  cfg.timeout_s)
                if l >= 1:
                    for c in silent:
                        fx.add_verdict(
                            "switch_crash", l - 1, s * fanin + c,
                            t_detect_rel=t_detect, detected_by="parent")
                node._finish(max(t_detect, node.proc_free))
            if crash_rel is None and not bypassed and node.aggregate:
                w_rel = fx.wipe_rel(l, s, node.t_first_ingest,
                                    node.t_finish)
                if w_rel is not None:
                    fx.add_verdict("table_wipe", l, s, t_detect_rel=w_rel,
                                   detected_by="self")
            nodes.append(node)
            nxt.append(node.out)
            if arrivals:
                t_first = min(t_first, arrivals[0][0])
            if node.out:
                t_out = max(t_out, max(t for t, _ in node.out))
        self.per_level_nodes.append(nodes)
        self.current = nxt
        if self._pid is not None and obs_trace.get_tracer().enabled:
            t0 = 0.0 if math.isinf(t_first) else t_first
            self._note_tier(l, t0=t0, t1=max(t_tx, t0), kind="transport")
            self._note_tier(l, t0=t0, t1=max(t_out, t0), kind="ingest")

    def finalize(self) -> SimResult:
        """Root -> reducer over the reducer in-link, then assemble."""
        cfg = self.cfg
        red_link = links_lib.Link(name="reducer", axis="reducer",
                                  gbps=self.reducer_gbps,
                                  propagation_s=cfg.propagation_s)
        self.all_links.append(red_link)
        root = self.current[0]
        if self.faults is not None:
            # fault mode: the reducer is a real host that survives every
            # epoch — its PSN/epoch gate persists across incarnations, and
            # a root that went silent without EoT is liveness-detected
            # here (the reducer is the root's "parent")
            fx = self.faults
            pkts = (vsim.stream_to_packets(root)
                    if isinstance(root, vsim.PacketStream) else root)
            recv = fx.attach_receiver(("reducer", 0))
            arrivals = []
            if pkts:
                _, st = transport.send_stream(
                    pkts, red_link, self.loss,
                    flow_id=pkts[0][1].header.flow_id, window=cfg.window,
                    timeout_s=cfg.timeout_s,
                    deliver=lambda p, t: arrivals.append((t, p)))
                self._add_flow(st)
            arrivals.sort(key=lambda a: (a[0], a[1].header.psn))
            jct = 0.0
            got_eot = False
            rec_k, rec_v = [], []
            for t, p in arrivals:
                if recv.accept(p.header):
                    jct = max(jct, t)
                    got_eot = got_eot or p.header.eot
                    if p.header.n_records:
                        rec_k.append(np.asarray(p.keys, np.int32))
                        rec_v.append(np.asarray(p.values))
            if not got_eot:
                t_last = arrivals[-1][0] if arrivals else 0.0
                fx.add_verdict(
                    "switch_crash", self.n_levels - 1, 0,
                    t_detect_rel=t_last + fx.liveness_s(
                        red_link, cfg.window, cfg.timeout_s),
                    detected_by="parent")
            arrived_k = (np.concatenate(rec_k) if rec_k
                         else np.zeros((0,), np.int32))
            arrived_v = (np.concatenate(rec_v) if rec_v
                         else np.zeros((0,) + self.carried.shape[1:],
                                       self.carried.dtype))
            self.reducer_gap = recv.gap_discards
            self.reducer_dup = recv.duplicate_discards
        elif isinstance(root, vsim.PacketStream):
            # fast path: acceptance falls out of the window algebra, so
            # the reducer's pre-merge stream is the root stream verbatim
            # and the JCT is the last accepted arrival
            if self.loss.rate > 0.0:
                arrive, _, st, self.reducer_gap = vsim.transmit_stream_lossy(
                    root, red_link, self.loss, window=cfg.window,
                    timeout_s=cfg.timeout_s)
                self._add_flow(st)
            else:
                arrive, _ = vsim.transmit_stream(root, red_link)
                self.flows.packets_sent += root.n_packets
                self.flows.wire_bytes += (
                    wire.HEADER_BYTES * root.n_packets
                    + wire.PAIR_BYTES * int(root.sizes.sum()))
            jct = max(0.0, float(arrive.max()))
            arrived_k, arrived_v = root.keys, root.values
        else:
            recv = transport.Receiver()
            arrivals: list[tuple[float, wire.Packet]] = []
            self._run_flow(root, red_link, arrivals)
            arrivals.sort(key=lambda a: (a[0], a[1].header.psn))
            jct = 0.0
            rec_k: list[np.ndarray] = []
            rec_v: list[np.ndarray] = []
            for t, p in arrivals:
                if recv.accept(p.header):
                    jct = max(jct, t)
                    if p.header.n_records:
                        rec_k.append(np.asarray(p.keys, np.int32))
                        rec_v.append(np.asarray(p.values))
            arrived_k = (np.concatenate(rec_k) if rec_k
                         else np.zeros((0,), np.int32))
            arrived_v = (np.concatenate(rec_v) if rec_v
                         else np.zeros((0,) + self.carried.shape[1:],
                                       self.carried.dtype))
            self.reducer_gap = recv.gap_discards
            self.reducer_dup = recv.duplicate_discards
        if arrived_k.size:  # the reducer host's final exact merge
            c = kvagg.sorted_combine(jnp.asarray(arrived_k),
                                     jnp.asarray(arrived_v), op=self.op)
            n_unique = int(c.n_unique)
            dk = np.asarray(c.unique_keys)[:n_unique]
            dv = np.asarray(self.aggop.finalize_values(
                c.combined_values))[:n_unique]
        else:
            n_unique, dk = 0, np.zeros((0,), np.int32)
            dv = np.zeros((0,), np.float32)

        gap = sum(n.receiver.gap_discards
                  for lvl in self.per_level_nodes for n in lvl) \
            + self.reducer_gap
        dup = sum(n.receiver.duplicate_discards
                  for lvl in self.per_level_nodes for n in lvl) \
            + self.reducer_dup
        per_level = [schema_lib.level_report(l, self.axes[l], nodes)
                     for l, nodes in enumerate(self.per_level_nodes)]
        result = SimResult(
            jct_s=jct,
            aggregate=self.aggregate,
            op=self.op,
            fanins=self.fanins,
            axes=self.axes,
            delivered_keys=dk,
            delivered_values=dv,
            delivered_records=n_unique,
            delivered_bytes=wire.stream_wire_bytes(
                n_unique, cfg.records_per_packet),
            arrived_records=int(arrived_k.shape[0]),
            link_stats=links_lib.stats_by_axis(self.all_links),
            per_level=per_level,
            retransmissions=self.flows.retransmissions,
            timeouts=self.flows.timeouts,
            packets_dropped=self.flows.packets_dropped,
            gap_discards=gap,
            duplicate_discards=dup,
            mapper_finish_s=self.mapper_finish,
        )
        # telemetry out (DESIGN.md §11): both engines publish through the
        # one schema path, so their metric series are comparable 1:1.
        # Under the fault driver an epoch that dies is discarded — the
        # driver publishes the surviving epoch's report itself.
        if self.faults is None:
            schema_lib.publish_report(result.report(), job=self.tag,
                                      engine=self.cfg.engine)
        tracer = obs_trace.get_tracer()
        if tracer.enabled and self._pid is not None:
            root_t0 = 0.0
            if isinstance(root, vsim.PacketStream):
                if root.times.size:
                    root_t0 = float(root.times[0])
            elif root:
                root_t0 = float(root[0][0])
            tid = 2 * self.n_levels
            tracer.name_thread(self._pid, tid, "reducer drain")
            tracer.add_span("reducer drain", root_t0, max(jct, root_t0),
                            cat="sim.transport", pid=self._pid, tid=tid,
                            args={"axis": "reducer"})
        return result


def _warn_deprecated(old: str) -> None:
    """Shim-emitted deprecation pointing at the unified facade
    (DESIGN.md §13).  ``stacklevel=3`` attributes the warning to the
    shim's caller, not the shim."""
    warnings.warn(
        f"{old} is deprecated; use repro.net.simulate() — the unified "
        "facade over every sim entry point (DESIGN.md §13)",
        DeprecationWarning, stacklevel=3)


def _simulate_jobs(
    specs: Sequence[JobSpec],
    admissions: Sequence[tuple[int, JobSpec]] = (),
) -> list[SimResult]:
    """The level-lockstep batch engine, with event-driven mid-run
    admission.  ``specs`` start at lockstep step 0; each ``(step, spec)``
    in ``admissions`` joins the running batch at that lockstep step —
    i.e. between tier levels of the jobs already in flight — and a job
    leaves the batch the step its last tier completes.  Jobs never
    interact (each owns its links, flows, and streams), so every result
    is bit-identical to running that spec alone on either engine: the
    batching — and therefore mid-run admission — changes kernel dispatch
    count, never results.  Results come back in ``specs`` order followed
    by ``admissions`` order."""
    entries: list[tuple[int, JobSpec]] = [(0, s) for s in specs]
    for step, s in admissions:
        step = int(step)
        if step < 0:
            raise ValueError(f"admission step {step} must be >= 0")
        entries.append((step, s))
    runs: list[_JobRun | None] = [None] * len(entries)
    results: list[SimResult | None] = [None] * len(entries)
    n_done = 0
    step = 0
    while n_done < len(entries):
        pending = []
        for i, (t0, spec) in enumerate(entries):
            if results[i] is not None or step < t0:
                continue
            if runs[i] is None:  # this step's arrivals enter the batch
                runs[i] = _JobRun(spec)
            r = runs[i]
            pending.append((i, r, step - t0, r.start_tier(step - t0)))
        works = [w for _, _, _, w in pending if w is not None]
        if works:
            vsim.dispatch_tier_ingest(works)
        for i, r, l, w in pending:
            if w is not None:
                r.finish_tier(l, w)
            if l == r.n_levels - 1:  # departure: finalize and free the slot
                results[i] = r.finalize()
                runs[i] = None
                n_done += 1
        step += 1
    return list(results)


def simulate_jobs(specs: Sequence[JobSpec]) -> list[SimResult]:
    """Deprecated: use :func:`repro.net.simulate` with a list of
    :class:`JobSpec` (DESIGN.md §13).

    Runs a batch of independent jobs, tiers stepped level by level in
    lockstep so same-depth fast-path tiers share batched kernel calls
    (``vsim.dispatch_tier_ingest``; ``planner.batch_tier_groups``
    predicts the packing).  Returns one :class:`SimResult` per spec,
    bit-identical to running each spec alone — the batching changes
    kernel dispatch count, never results.
    """
    _warn_deprecated("simulate_jobs")
    return _simulate_jobs(specs)


def simulate_job(
    keys,
    values,
    *,
    fanins: Sequence[int],
    plan: dataplane.CascadePlan | None = None,
    op: str = "sum",
    aggregate: bool = True,
    cfg: NetConfig | None = None,
    axes: Sequence[str] | None = None,
    mapper_delay: Callable[[int], float] | None = None,
    job_id: int = 0,
    tag: str = "",
) -> SimResult:
    """Deprecated: use :func:`repro.net.simulate` with a single
    :class:`JobSpec` (DESIGN.md §13).

    Runs one job end to end over the emulated network.  ``keys``/
    ``values`` are the global mapper output (split contiguously among
    ``prod(fanins)`` mappers); ``plan`` gives each tree level its node
    geometry (default: exact capacity-0 nodes).  ``mapper_delay(m)``
    adds per-mapper start delay — the straggler-injection hook shared with
    ``runtime.fault_tolerance``.  ``tag`` names the run's metric series
    and trace track (DESIGN.md §11; default ``job<job_id>``).
    """
    _warn_deprecated("simulate_job")
    return _simulate_jobs([JobSpec(
        keys=keys, values=values, fanins=fanins, plan=plan, op=op,
        aggregate=aggregate, cfg=cfg, axes=axes, mapper_delay=mapper_delay,
        job_id=job_id, tag=tag)])[0]


# ---------------------------------------------------------------------------
# Failure-recovery runtime: epoch-restart driver (DESIGN.md §12).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FaultSimResult:
    """One job survived its failure schedule: the clean final epoch plus
    the whole recovery history.

    ``result`` is the surviving incarnation's :class:`SimResult` — its
    delivered table is THE job output, and the exactly-once invariant
    says it equals the no-failure grouped-combine.  ``jct_s`` is absolute:
    every aborted epoch's detection latency, every restart delay, and the
    final epoch's completion — the recovery JCT penalty is
    ``jct_s - <no-failure jct>``.
    """

    result: SimResult  # the final (clean) epoch's run
    jct_s: float  # absolute completion time across all epochs
    epochs: int  # incarnations run (1 = no restart was needed)
    verdicts: list  # every FailureVerdict, in detection order
    applied: list  # the verdicts that actually triggered restarts
    bypass: tuple[tuple[int, int], ...]  # positions degraded to relays
    epoch_log: list[dict]  # per epoch: start, detect/jct, verdict counts
    repair: object | None = None  # planner.PlacementRepair (fat-tree runs)

    def delivered_table(self) -> dict[int, float]:
        return self.result.delivered_table()


def _run_fault_epochs(spec: JobSpec, injector, policy,
                      on_restart=None) -> FaultSimResult:
    """The epoch-restart loop (DESIGN.md §12).  Runs the job; when any
    failure verdict lands, dates the restart from the *earliest* verdict
    (later ones had not been detected yet — they re-detect in the next
    incarnation), turns crash/link verdicts into forward-only bypass
    relays, bumps the epoch, and replays every mapper (the pipeline is a
    pure function of the mapper index).  Surviving switches keep their
    PSN gates across epochs; the packet epoch tag is what lets them
    accept the replay instead of discarding it as duplicates.  Terminates
    because every applied verdict removes a failure from play and clean
    epochs return — ``policy.max_epochs`` is the storm backstop."""
    from repro.runtime import fault_tolerance as ft_lib

    if policy is None:
        policy = ft_lib.FaultPolicy()
    fanins = tuple(int(f) for f in spec.fanins)
    for e in getattr(injector, "events", ()):
        if not 0 <= e.level < len(fanins):
            raise ValueError(f"failure event targets level {e.level}; the "
                             f"tree has levels 0..{len(fanins) - 1}")
        n_sw = int(np.prod(fanins[e.level + 1:], dtype=np.int64))
        if not 0 <= e.switch < n_sw:
            raise ValueError(
                f"failure event targets switch {e.switch} at level "
                f"{e.level}, which has {n_sw} switch(es) — an out-of-range "
                f"event would silently never fire")
        if e.child is not None and not 0 <= e.child < fanins[e.level]:
            raise ValueError(f"failure event child {e.child} out of range "
                             f"for fan-in {fanins[e.level]} at level "
                             f"{e.level}")
    base_cfg = spec.cfg or NetConfig()
    tag = spec.tag or f"job{spec.job_id}"
    receivers: dict = {}
    bypass: set = set()
    fired_wipes: set = set()
    t_start = 0.0
    all_verdicts: list = []
    applied: list = []
    epoch_log: list[dict] = []
    for epoch in range(policy.max_epochs + 1):
        ctx = _FaultCtx(
            injector=injector, policy=policy, epoch=epoch,
            t_start_s=t_start, bypass=frozenset(bypass),
            fired_wipes=fired_wipes, receivers=receivers)
        run = _JobRun(dataclasses.replace(
            spec, cfg=dataclasses.replace(base_cfg, epoch=epoch), tag=tag),
            faults=ctx)
        for l in range(run.n_levels):
            w = run.start_tier(l)
            if w is not None:
                vsim.dispatch_tier_ingest([w])
                run.finish_tier(l, w)
        result = run.finalize()
        if not ctx.verdicts:
            epoch_log.append({"epoch": epoch, "t_start_s": t_start,
                              "jct_s": result.jct_s, "n_verdicts": 0,
                              "n_applied": 0})
            schema_lib.publish_report(result.report(), job=tag,
                                      engine=base_cfg.engine)
            fsr = FaultSimResult(
                result=result, jct_s=t_start + result.jct_s,
                epochs=epoch + 1, verdicts=all_verdicts, applied=applied,
                bypass=tuple(sorted(bypass)), epoch_log=epoch_log)
            schema_lib.publish_fault_report(
                schema_lib.fault_report_dict(fsr), job=tag,
                engine=base_cfg.engine)
            _trace_fault_timeline(tag, fsr)
            return fsr
        vs = sorted(ctx.verdicts, key=lambda v: v.t_detect_s)
        all_verdicts.extend(vs)
        t_detect = vs[0].t_detect_s  # absolute
        now = [v for v in vs if v.t_detect_s <= t_detect]
        for v in now:
            applied.append(v)
            if v.kind in ("switch_crash", "link_down"):
                # dead (or unreachable) position: re-route its subtree
                # forward-only; the replacement relay is a new incarnation
                bypass.add((v.level, v.switch))
                receivers.pop((v.level, v.switch), None)
        epoch_log.append({"epoch": epoch, "t_start_s": t_start,
                          "t_detect_s": t_detect,
                          "n_verdicts": len(vs), "n_applied": len(now)})
        t_start = t_detect + policy.restart_delay_s
        # wipes scheduled before the restart boundary corrupted state the
        # replay rebuilds from scratch anyway — they have fired
        for i, e in enumerate(injector.events):
            if (e.kind == "table_wipe" and i not in fired_wipes
                    and e.t_s < t_start):
                fired_wipes.add(i)
        if on_restart is not None:
            new_plan = on_restart(tuple(sorted(bypass)), epoch)
            if new_plan is not None:
                spec = dataclasses.replace(spec, plan=new_plan)
    raise RuntimeError(
        f"failure schedule did not quiesce within {policy.max_epochs} "
        f"restarts ({len(all_verdicts)} verdicts); raise max_epochs or "
        f"thin the schedule")


def _trace_fault_timeline(tag: str, fsr: FaultSimResult) -> None:
    """The failure/recovery timeline as virtual-time trace spans: one
    lane of epochs, one lane of verdicts (detection -> restart)."""
    tracer = obs_trace.get_tracer()
    if not tracer.enabled:
        return
    pid = tracer.new_track(f"faults {tag}")
    tracer.name_thread(pid, 0, "epochs")
    tracer.name_thread(pid, 1, "verdicts")
    for rec in fsr.epoch_log:
        t0 = rec["t_start_s"]
        t1 = rec.get("t_detect_s", t0 + rec.get("jct_s", 0.0))
        tracer.add_span(f"epoch {rec['epoch']}", t0, max(t1, t0),
                        cat="sim.fault", pid=pid, tid=0, args=dict(rec))
    for v in fsr.verdicts:
        end = next((r.get("t_detect_s", v.t_detect_s)
                    for r in fsr.epoch_log if r["epoch"] == v.epoch),
                   v.t_detect_s)
        tracer.add_span(
            f"{v.kind} L{v.level}.s{v.switch} ({v.detected_by})",
            v.t_detect_s, max(end, v.t_detect_s), cat="sim.fault",
            pid=pid, tid=1,
            args={"kind": v.kind, "level": v.level, "switch": v.switch,
                  "epoch": v.epoch, "detected_by": v.detected_by})


def _simulate_spec_with_faults(spec: JobSpec, injector,
                               policy=None) -> FaultSimResult:
    """One :class:`JobSpec` under a failure schedule: epoch-restart
    driver with the injector's own straggler delays as the default
    ``mapper_delay`` and ``"faulted"`` as the default telemetry tag."""
    if spec.mapper_delay is None and getattr(injector, "delays", None):
        spec = dataclasses.replace(spec, mapper_delay=injector)
    if not spec.tag:
        spec = dataclasses.replace(spec, tag="faulted")
    return _run_fault_epochs(spec, injector, policy)


def simulate_job_with_faults(
    keys,
    values,
    *,
    fanins: Sequence[int],
    injector,
    policy=None,
    plan: dataplane.CascadePlan | None = None,
    op: str = "sum",
    aggregate: bool = True,
    cfg: NetConfig | None = None,
    axes: Sequence[str] | None = None,
    mapper_delay: Callable[[int], float] | None = None,
    job_id: int = 0,
    tag: str = "",
) -> FaultSimResult:
    """Deprecated: use :func:`repro.net.simulate` with ``faults=``
    (DESIGN.md §13).

    One job under a failure schedule (DESIGN.md §12).  ``injector`` is a
    ``runtime.fault_tolerance.FailureInjector`` — switch crashes,
    link-down windows, and table wipes at absolute simulated times;
    ``policy`` a ``FaultPolicy`` (detection backoff / retry budget /
    liveness / restart delay).  The job restarts as epochs until an
    incarnation completes clean; the returned :class:`FaultSimResult`
    carries that incarnation's delivered table (exactly-once: equal to
    the no-failure grouped-combine), the total absolute JCT, and the
    full verdict history.  ``mapper_delay`` defaults to the injector's
    own straggler delays."""
    _warn_deprecated("simulate_job_with_faults")
    return _simulate_spec_with_faults(
        JobSpec(keys=keys, values=values, fanins=fanins, plan=plan, op=op,
                aggregate=aggregate, cfg=cfg, axes=axes,
                mapper_delay=mapper_delay, job_id=job_id, tag=tag),
        injector, policy)


def _job_plan_spec(
    job_plan,
    keys,
    values,
    *,
    cfg: NetConfig | None,
    aggregate: bool,
    mapper_delay: Callable[[int], float] | None,
) -> JobSpec:
    """A controller-admitted job (``planner.JobPlan``) as a
    :class:`JobSpec`: cascade geometry from its ``ConfigureMsg``, link
    rates from its ``AggregationTree`` levels."""
    cfg = cfg or NetConfig()
    cascade = dataplane.plan_from_configure(job_plan.configure)
    tree = job_plan.tree
    cfg = dataclasses.replace(
        cfg, link_gbps=tuple(l.link_gbps for l in tree.levels))
    return JobSpec(
        keys=keys, values=values, fanins=job_plan.configure.fanins,
        plan=cascade, op=job_plan.configure.op, aggregate=aggregate,
        cfg=cfg, axes=tree.axes, mapper_delay=mapper_delay,
        job_id=job_plan.configure.tree_id)


def _job_plan_specs(
    job_plans: Sequence,
    keys_list: Sequence,
    values_list: Sequence,
    *,
    cfg: NetConfig | None = None,
    aggregate: bool = True,
    mapper_delays: Sequence[Callable[[int], float] | None] | None = None,
) -> list[JobSpec]:
    """An admitted batch (``JobScheduler.plan_all`` output) as specs."""
    if not len(job_plans) == len(keys_list) == len(values_list):
        raise ValueError("job_plans, keys_list, values_list must align")
    if mapper_delays is not None and len(mapper_delays) != len(job_plans):
        raise ValueError("mapper_delays must align with job_plans")
    return [
        _job_plan_spec(
            jp, keys_list[i], values_list[i], cfg=cfg, aggregate=aggregate,
            mapper_delay=mapper_delays[i] if mapper_delays is not None
            else None)
        for i, jp in enumerate(job_plans)]


def simulate_job_plan(
    job_plan,
    keys,
    values,
    *,
    cfg: NetConfig | None = None,
    aggregate: bool = True,
    mapper_delay: Callable[[int], float] | None = None,
) -> SimResult:
    """Deprecated: use :func:`repro.net.simulate` with a
    ``planner.JobPlan`` (DESIGN.md §13).

    Runs a controller-admitted job (``planner.JobPlan``) end to end.
    The cascade geometry comes from the plan's ``ConfigureMsg`` (the §4.2.2
    per-tree memory partition split across levels), the link rates from its
    ``AggregationTree`` levels — the simulator consuming exactly what the
    ``JobScheduler`` emitted, so measured drain can be fed back via
    :func:`drain_calibration` + ``JobScheduler.calibrate``.
    """
    _warn_deprecated("simulate_job_plan")
    return _simulate_jobs([_job_plan_spec(
        job_plan, keys, values, cfg=cfg, aggregate=aggregate,
        mapper_delay=mapper_delay)])[0]


def simulate_job_plans(
    job_plans: Sequence,
    keys_list: Sequence,
    values_list: Sequence,
    *,
    cfg: NetConfig | None = None,
    aggregate: bool = True,
    mapper_delays: Sequence[Callable[[int], float] | None] | None = None,
) -> list[SimResult]:
    """Deprecated: use :func:`repro.net.simulate` with a list of
    ``planner.JobPlan`` (DESIGN.md §13).

    Runs a whole admitted batch (``JobScheduler.plan_all`` output)
    concurrently in one lockstep batch, so tiers of different jobs that
    share a kernel-static signature ride ONE batched ``tier_ingest``
    dispatch under the vectorized engine.  Results are bit-identical to
    per-job :func:`simulate_job_plan` runs.
    """
    _warn_deprecated("simulate_job_plans")
    return _simulate_jobs(_job_plan_specs(
        job_plans, keys_list, values_list, cfg=cfg, aggregate=aggregate,
        mapper_delays=mapper_delays))


def drain_calibration(result: SimResult) -> dict[str, float]:
    """Measured-vs-modeled drain factors for ``JobScheduler.calibrate``.

    The planner's drain model charges payload bytes at line rate; the wire
    also carries headers and retransmissions.  The factor per axis is
    ``wire_bytes / payload_bytes`` (>= 1), i.e. how much longer the level
    really takes to drain than the payload-only model claims.
    """
    out = {}
    for axis, s in result.link_stats.items():
        if axis == "reducer":
            continue
        payload = s["payload_bytes"]
        out[axis] = (s["bytes"] / payload) if payload > 0 else 1.0
    return out


def jct_comparison(
    keys,
    values,
    *,
    fanins: Sequence[int],
    plan: dataplane.CascadePlan | None = None,
    op: str = "sum",
    cfg: NetConfig | None = None,
    axes: Sequence[str] | None = None,
) -> dict:
    """The Fig. 10 measurement: JCT with in-network aggregation vs the
    host-only baseline on the same network, same loss pattern.

    The returned dict is JSON-able except for ``_results``, the raw
    ``(switchagg, host_only)`` SimResult pair for callers (the JCT bench)
    that need more than the report scalars — drop the key before dumping.
    """
    sw, host = _simulate_jobs([
        JobSpec(keys=keys, values=values, fanins=fanins, plan=plan, op=op,
                aggregate=True, cfg=cfg, axes=axes, tag="switchagg"),
        JobSpec(keys=keys, values=values, fanins=fanins, plan=plan, op=op,
                aggregate=False, cfg=cfg, axes=axes, tag="host_only")])
    return {
        "switchagg": sw.report(),
        "host_only": host.report(),
        "jct_switchagg_s": sw.jct_s,
        "jct_host_only_s": host.jct_s,
        "jct_saved": 1.0 - sw.jct_s / host.jct_s if host.jct_s > 0 else 0.0,
        "reduction": 1.0 - (sw.arrived_records
                            / max(1, host.arrived_records)),
        "_results": (sw, host),
    }


def _fat_tree_spec(
    ft,
    keys,
    values,
    *,
    placement,
    op: str,
    cfg: NetConfig | None,
    mapper_delay: Callable[[int], float] | None = None,
    job_id: int = 0,
    tag: str = "",
) -> JobSpec:
    """One fat-tree incast as a :class:`JobSpec`: the topology's own
    per-tier links, aggregation only where ``placement`` put nodes."""
    plan = dataplane.plan_from_placement(placement, op=op)
    topo_links = ft.link_tiers()
    cfg = cfg or NetConfig()
    cfg = dataclasses.replace(
        cfg, link_gbps=tuple(l.gbps for l in topo_links),
        reducer_gbps=(cfg.reducer_gbps if cfg.reducer_gbps is not None
                      else ft.edge_gbps))
    return JobSpec(
        keys=keys, values=values,
        fanins=tuple(l.fanin for l in topo_links), plan=plan, op=op,
        aggregate=True, cfg=cfg, axes=tuple(l.axis for l in topo_links),
        mapper_delay=mapper_delay, job_id=job_id, tag=tag)


def _fat_tree_job(
    ft,
    keys,
    values,
    *,
    placement=None,
    policy: str = "auto",
    op: str = "sum",
    cfg: NetConfig | None = None,
    mapper_delay: Callable[[int], float] | None = None,
    job_id: int = 0,
    tag: str = "",
) -> SimResult:
    """One multi-rack incast over a ``planner.FatTreeTopology``."""
    from repro.core import planner  # local import: core.planner is upstream

    if placement is None:
        n_mappers = ft.n_hosts
        keys_arr = np.asarray(keys)
        per_host = -(-keys_arr.shape[0] // max(1, n_mappers))
        placement = planner.place_aggregation_tree(
            ft, per_host_pairs=per_host,
            key_variety=int(keys_arr.max(initial=0)) + 1, policy=policy)
    return _simulate_jobs([_fat_tree_spec(
        ft, keys, values, placement=placement, op=op, cfg=cfg,
        mapper_delay=mapper_delay, job_id=job_id, tag=tag)])[0]


def simulate_fat_tree_job(
    ft,
    keys,
    values,
    *,
    placement=None,
    policy: str = "auto",
    op: str = "sum",
    cfg: NetConfig | None = None,
    mapper_delay: Callable[[int], float] | None = None,
    job_id: int = 0,
) -> SimResult:
    """Deprecated: use :func:`repro.net.simulate` with a
    ``planner.FatTreeTopology`` (DESIGN.md §13).

    Runs one multi-rack incast over a ``planner.FatTreeTopology``.  The
    emulated network is the fat-tree's own per-tier links — host "edge"
    links at ``edge_gbps``, oversubscribed ToR "aggr" uplinks, pod
    "core" uplinks — with the reducer in-link at the host rate (the
    reducer is just another host).  Each tier's switches run aggregation
    only where the ``placement`` (or a fresh ``policy`` search) put nodes;
    unplaced tiers forward, so host-only / ToR-only / full-tree deployments
    are all the same simulation with different `LevelSpec.enabled` rows.
    """
    _warn_deprecated("simulate_fat_tree_job")
    return _fat_tree_job(
        ft, keys, values, placement=placement, policy=policy, op=op,
        cfg=cfg, mapper_delay=mapper_delay, job_id=job_id)


def _fat_tree_job_with_faults(
    ft,
    keys,
    values,
    *,
    injector,
    fault_policy=None,
    placement=None,
    policy: str = "auto",
    op: str = "sum",
    cfg: NetConfig | None = None,
    mapper_delay: Callable[[int], float] | None = None,
    job_id: int = 0,
    tag: str = "",
) -> FaultSimResult:
    """Fat-tree incast under a failure schedule with the control plane in
    the recovery loop (``planner.repair_placement`` per restart)."""
    from repro.core import planner  # local import: core.planner is upstream

    keys_arr = np.asarray(keys)
    per_host = -(-keys_arr.shape[0] // max(1, ft.n_hosts))
    key_variety = int(keys_arr.max(initial=0)) + 1
    if placement is None:
        placement = planner.place_aggregation_tree(
            ft, per_host_pairs=per_host, key_variety=key_variety,
            policy=policy)
    spec = _fat_tree_spec(
        ft, keys, values, placement=placement, op=op, cfg=cfg,
        mapper_delay=mapper_delay, job_id=job_id, tag=tag or "faulted")
    state: dict = {"repair": None}

    def on_restart(bypass, epoch):
        rep = planner.repair_placement(
            ft, placement, failed=bypass, per_host_pairs=per_host,
            key_variety=key_variety)
        state["repair"] = rep
        return dataplane.plan_from_placement(rep.placement, op=op)

    fsr = _run_fault_epochs(spec, injector, fault_policy,
                            on_restart=on_restart)
    fsr.repair = state["repair"]
    return fsr


def simulate_fat_tree_job_with_faults(
    ft,
    keys,
    values,
    *,
    injector,
    fault_policy=None,
    placement=None,
    policy: str = "auto",
    op: str = "sum",
    cfg: NetConfig | None = None,
    mapper_delay: Callable[[int], float] | None = None,
    job_id: int = 0,
    tag: str = "",
) -> FaultSimResult:
    """Deprecated: use :func:`repro.net.simulate` with a
    ``planner.FatTreeTopology`` and ``faults=`` (DESIGN.md §13).

    The fat-tree incast under a failure schedule, with the control plane
    in the recovery loop: after each restart the driver calls
    ``planner.repair_placement`` on the positions declared dead, and
    the next epoch runs the *repaired* placement — dead switches become
    forward-only relays, and a tier that lost every switch is re-placed
    around entirely (DESIGN.md §12).  The final ``PlacementRepair`` (its
    degraded byte model is the modeled JCT-penalty source) rides on
    ``FaultSimResult.repair``."""
    _warn_deprecated("simulate_fat_tree_job_with_faults")
    return _fat_tree_job_with_faults(
        ft, keys, values, injector=injector, fault_policy=fault_policy,
        placement=placement, policy=policy, op=op, cfg=cfg,
        mapper_delay=mapper_delay, job_id=job_id, tag=tag)


def fat_tree_jct_comparison(
    ft,
    keys,
    values,
    *,
    per_host_pairs: int | None = None,
    key_variety: int | None = None,
    op: str = "sum",
    policies: Sequence[str] = ("host_only", "tor_only", "full"),
    cfg: NetConfig | None = None,
) -> dict:
    """The rack-scale Fig. 10: one mapper stream, one fat-tree network,
    JCT and per-tier wire bytes for each placement policy side by side.

    All policies run as ONE :func:`simulate_jobs` batch, so under the
    vectorized engine their same-depth aggregating tiers share kernel
    dispatches (e.g. full's ToR tier batches with tor_only's).  The
    returned dict maps each policy to its report plus a ``placement``
    record (placed tiers, modeled scarce bytes); ``jct_s`` collects the
    headline JCTs.  ``_results`` holds the raw SimResults (drop before
    JSON-dumping).  For any aggregating placement the delivered table is
    exact, so host-only vs ToR-only vs full-tree differ only in where
    bytes die — what the placement search optimizes.
    """
    from repro.core import planner  # local import: core.planner is upstream

    keys_arr = np.asarray(keys)
    if per_host_pairs is None:
        per_host_pairs = -(-keys_arr.shape[0] // max(1, ft.n_hosts))
    if key_variety is None:
        key_variety = int(keys_arr.max(initial=0)) + 1
    out: dict = {"policies": list(policies), "jct_s": {},
                 "scarce_axis": ft.scarce_uplink_axis(), "_results": {}}
    placements = {
        pol: planner.place_aggregation_tree(
            ft, per_host_pairs=per_host_pairs, key_variety=key_variety,
            policy=pol)
        for pol in policies}
    results = _simulate_jobs([
        _fat_tree_spec(ft, keys, values, placement=placements[pol], op=op,
                       cfg=cfg, tag=pol)
        for pol in policies])
    for pol, res in zip(policies, results):
        placement = placements[pol]
        rep = res.report()
        rep["placement"] = {
            "policy": pol,
            "tiers": list(placement.tiers),
            "n_agg_switches": placement.n_agg_switches,
            "modeled_scarce_bytes": placement.scarce_uplink_bytes,
            "modeled_reducer_bytes": placement.reducer_bytes,
        }
        out[pol] = rep
        out["jct_s"][pol] = res.jct_s
        out["_results"][pol] = res
    return out
