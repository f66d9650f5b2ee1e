"""TpuGraphBackend — live mirror of a FusionHub's dependency graph on device.

The bridge between the authoritative host graph (ComputedRegistry + per-node
edge sets) and the device CSR mirror (DeviceGraph): registry/edge/invalidate
events stream in through the hub hooks, batch up host-side, and flush to
device before each wave. ``invalidate_cascade`` then offloads the transitive
invalidation closure to the TPU kernel and applies the result back to host
nodes via ``Computed.invalidate_local`` (no host cascade — the device already
walked the graph).

Host↔device coherence (SURVEY.md "hard parts"): every mutation is buffered
with a monotonically growing pending list and flushed under a single lock
before any wave runs, so a wave never observes half an edge batch. Epoch
bumps happen at node *registration* (compute start), matching the host rule
that edges captured during a compute belong to the new version.

Applying a device wave back to host (r2 redesign, VERDICT.md weak #2): the
device returns the newly-invalidated ids COMPACTED (O(wave) readback, not
two O(graph) mask snapshots), and the host materializes invalidation in two
tiers:

- **watched nodes** (anything with an invalidation handler — states, RPC
  push subscriptions, ``when_invalidated`` waiters) are invalidated EAGERLY
  so observers fire promptly;
- **unwatched nodes** get a bit in a host-side ``pending`` mask; the read
  path (FunctionBase via ``hub.graph_read_filter``) materializes the
  invalidation lazily on next access. An unread cached value burns zero
  host time per wave — the host cost of a wave is O(watched ∩ wave), not
  O(wave).

A recompute (epoch bump) clears the node's pending bit: the wave targeted
the previous version, and on device the new epoch's edges never matched —
the same version-match rule the reference applies per-edge
(Computed.cs:213-215).
"""
from __future__ import annotations

import logging
import threading
import time
import weakref
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..diagnostics.flight_recorder import RECORDER
from ..diagnostics.metrics import WaveProfiler, global_metrics, next_wave_seq
from ..diagnostics.tracing import CAUSE_PREFIX, current_span, hot_span, span_cause_id
from .device_graph import DeviceGraph, array_placement, run_on_device

if TYPE_CHECKING:
    from ..core.computed import Computed
    from ..core.hub import FusionHub
    from ..core.inputs import ComputedInput

log = logging.getLogger("stl_fusion_tpu")

__all__ = ["TpuGraphBackend", "RowBlock"]

#: process-unique cause-id prefix: two hosts minting "wave#1" must not
#: collide when their frames meet in one client's telemetry. SHARED with
#: tracing (span_cause_id / find_span_by_cause key on byte-identical
#: prefixes) — never mint a diverging local copy.
_CAUSE_PREFIX = CAUSE_PREFIX

#: hot-span name of each batched journal run flush() replays
_REPLAY_SPAN = {
    kind: "flush.replay." + kind
    for kind in ("bump", "edge", "epack", "icasc", "cpack", "invalid")
}


class RowBlock:
    """A MemoTable bound to a contiguous block of graph node ids — the
    columnar registration unit (VERDICT r3 #2: vectorized live ingest).

    The reference's registry absorbs nodes one ``Register`` call at a time
    (src/Stl.Fusion/ComputedRegistry.cs:72-105) because every node is an
    object; here a table-backed service registers its whole dense key space
    in ONE allocation (``bind_table_rows``) and declares dependency edges in
    bulk numpy (``declare_row_edges``) — graph construction runs at array
    speed, not at Python-object speed. Row ``r`` of the table IS graph node
    ``base + r``; scalar ``@compute_method`` nodes for the same keys adopt
    the row's node id on registration, so the scalar and columnar views
    cascade as ONE logical node."""

    __slots__ = (
        "table", "base", "n_rows", "_decl_src", "_decl_dst", "_csr",
        "_dev_refresh",
    )

    def __init__(self, table, base: int, n_rows: int):
        self.table = table
        self.base = base
        self.n_rows = n_rows
        # declared topology, kept so a scalar recompute (epoch bump) of a
        # row can re-declare that row's in-edges at the new epoch — the
        # declared-edge contract is "every version until redeclared"
        self._decl_src: List[np.ndarray] = []
        self._decl_dst: List[np.ndarray] = []
        self._csr: Optional[Tuple[np.ndarray, np.ndarray]] = None
        # jitted device-refresh programs, keyed by update_valid (see
        # TpuGraphBackend.refresh_block_on_device)
        self._dev_refresh: Dict[bool, object] = {}

    def end(self) -> int:
        return self.base + self.n_rows

    def _declared_csr(self) -> Tuple[np.ndarray, np.ndarray, int]:
        """CSR (starts, src_nids, declarations_included) of declared edges
        by LOCAL dst row. Built lazily and NOT rebuilt per declaration —
        per-row queries scan the post-build declaration tail instead
        (see :meth:`declared_in_srcs`): a full rebuild sorts EVERY declared
        edge (~seconds per churn round at 10M), while realistic churn only
        appends a few thousand."""
        if self._csr is not None:
            # refold once the post-build tail outgrows the amortization
            # budget: a long-lived service declaring forever must not make
            # every per-row query scan an unbounded tail (r5 review)
            starts, src, included = self._csr
            tail_edges = sum(len(a) for a in self._decl_src[included:])
            if tail_edges > max(len(src), 4096):
                self._csr = None
        if self._csr is None:
            if self._decl_src:
                src = np.concatenate(self._decl_src)
                dst = np.concatenate(self._decl_dst)
                local = dst - self.base
                order = np.argsort(local, kind="stable")
                src, local = src[order], local[order]
                starts = np.zeros(self.n_rows + 1, dtype=np.int64)
                np.add.at(starts[1:], local, 1)
                starts = np.cumsum(starts)
            else:
                src = np.empty(0, dtype=np.int32)
                starts = np.zeros(self.n_rows + 1, dtype=np.int64)
            self._csr = (starts, src, len(self._decl_src))
        return self._csr

    def declared_in_srcs(self, nid: int) -> np.ndarray:
        """Declared in-edge sources of graph node ``nid`` (base CSR slice +
        a linear scan of declarations made after the CSR was built)."""
        starts, src, included = self._declared_csr()
        r = nid - self.base
        s, e = int(starts[r]), int(starts[r + 1])
        parts = [src[s:e]]
        for s_arr, d_arr in zip(
            self._decl_src[included:], self._decl_dst[included:]
        ):
            sel = d_arr == nid
            if sel.any():
                parts.append(s_arr[sel])
        return parts[0] if len(parts) == 1 else np.concatenate(parts)


def _finish_block_refresh_bookkeeping(table, cleared: np.ndarray) -> None:
    """THE shared host bookkeeping tail of a columnar device refresh —
    stale accounting for the rows the device recomputed, the table version
    bump, and the non-backend ``on_refresh`` fan-out. Used by BOTH the
    sequential path (``refresh_block_on_device``) and the fused chain
    ticket, so the two can never drift. ``cleared`` is a bool mask over
    the table's rows."""
    was_stale = table._stale_host & cleared
    table._stale_count -= int(np.count_nonzero(was_stale))
    table._stale_host &= ~cleared
    table._bump()
    extern = [
        h for h in table.on_refresh if not getattr(h, "_backend_hook", False)
    ]
    if extern and cleared.any():
        ids_np = np.nonzero(cleared)[0].astype(np.int32)
        for h in extern:
            h(ids_np)


class _RefreshChainTicket:
    """In-flight burst→refresh chain (what ``SuperRoundProgram`` enqueues
    for a lanes super-round): the dispatches are enqueued; ``harvest()``
    blocks on the results and runs the two-tier host apply per logical
    wave. ``dispatched_at`` lets the caller account the overlap window
    (host work done between dispatch and harvest ran concurrently with the
    chain's device execution)."""

    __slots__ = (
        "backend", "block", "n_bursts", "stage_burst", "stages", "refresh",
        "pending", "cause", "seqs", "pre_block_invalid", "dispatched_at",
        "update_valid", "done", "cleared_total", "kind",
    )

    def __init__(self, backend, block, n_bursts, stage_burst, stages, refresh,
                 pending, cause, seqs, pre_block_invalid, dispatched_at,
                 update_valid, kind: str):
        self.backend = backend
        self.block = block
        self.n_bursts = n_bursts
        self.stage_burst = stage_burst
        self.stages = stages
        self.refresh = refresh
        self.pending = pending
        self.cause = cause
        self.seqs = seqs
        self.pre_block_invalid = pre_block_invalid
        self.dispatched_at = dispatched_at
        self.update_valid = update_valid
        self.kind = kind
        self.done = False
        #: filled at harvest: total block rows the chained refreshes
        #: recomputed (the churn-recompute accounting of the fused loop)
        self.cleared_total = 0

    def harvest(self) -> list:
        """Block on the chain, apply every stage's newly-mask under its own
        wave seq, and finish the refresh bookkeeping. Returns one int64
        newly-count array per burst. Idempotent-guarded (a second harvest
        raises — the state was already consumed)."""
        if self.done:
            raise RuntimeError("refresh chain already harvested")
        self.done = True
        backend = self.backend
        block, table = self.block, self.block.table
        seqs, stages = self.seqs, self.stages
        dg = backend.graph
        stage_counts, stage_masks = dg.harvest_waves_lanes_chain(self.pending)
        t1 = time.perf_counter()
        # commit the chained table state (same contract as
        # refresh_block_on_device: values recomputed, validity caught up)
        table._values = self.refresh["values"]
        if self.update_valid:
            table._valid_dev = self.refresh["valid_dev"]
        # two-tier host apply PER STAGE, each under its own wave seq — the
        # recorder/fanout events of one logical wave never blur into its
        # chain siblings; overlap_active is visible to the fan-out index
        # when another chain is already executing
        backend.last_cause_id = self.cause
        per_burst = [np.empty(0, dtype=np.int64) for _ in range(self.n_bursts)]
        cleared_rows = self.pre_block_invalid.copy()
        total_newly = 0
        for i, (cnts, mask) in enumerate(zip(stage_counts, stage_masks)):
            backend.last_wave_seq = seqs[i]
            backend._apply_newly(mask)
            sub = mask[block.base : block.end()]
            cleared_rows |= sub
            self.cleared_total += int(sub.sum())
            bi = self.stage_burst[i]
            per_burst[bi] = np.concatenate([per_burst[bi], cnts])
            total_newly += int(mask.sum())
        backend.last_wave_seq = seqs[0]
        # refresh bookkeeping once, at the end state: the device refreshed
        # every block row that was invalid at ANY stage
        _finish_block_refresh_bookkeeping(table, cleared_rows)
        total_counts = sum(int(c.sum()) for c in stage_counts)
        backend.waves_run += sum(len(s) for s in stages)
        backend.device_invalidations += total_counts
        backend._profile_wave(
            self.kind,
            sum(len(g) for s in stages for g in s), self.cause,
            self.dispatched_at, t1, total_newly, seqs[0],
            groups=sum(len(s) for s in stages),
            fused_depth=len(stages), seq_span=(seqs[0], seqs[-1]),
            dispatches=self.pending["dispatches"],
        )
        return per_burst


class TpuGraphBackend:
    """The hub's graph on a device (module docstring). ``device`` is where
    everything this backend owns lives: the :class:`DeviceGraph`'s arrays
    and mirrors, every table bound with :meth:`bind_table_rows` (its values,
    its validity mask, the loader arguments its refresh makes) and every
    argument a wave, a patch or a refresh stages. ``None`` (the one-chip
    deployments) names no device anywhere: arrays lie where JAX's default
    placement puts them, ``jax.devices()[0]``. With a ``jax.Device``, one
    process can hold one backend per chip, each hub's waves on its own."""

    def __init__(self, hub: "FusionHub", node_capacity: int = 4096,
                 edge_capacity: int = 16384, device=None):
        self.hub = hub
        self.device = device
        self.graph = DeviceGraph(node_capacity, edge_capacity, device=device)
        if device is not None:
            # the entries that reach JAX without going through the graph's
            # own (placed) methods: the table programs and the loader's args
            run_on_device(self, device, (
                "refresh_block_on_device", "warm_block_on_device",
                "_block_refresh_state", "refresh_rows_on_device",
            ))
        self._lock = threading.Lock()
        self._id_by_input: Dict["ComputedInput", int] = {}
        self._computed_by_id: Dict[int, "weakref.ref[Computed]"] = {}
        # ordered event journal: ("bump", nid) | ("edge", (src, dst)) |
        # ("invalid", nid). Order preserves causality — an invalidation mark
        # buffered before a node's recompute-bump must not survive it.
        self._journal: List[Tuple[str, object]] = []
        # host-side wave-application state (see module docstring):
        # pending = device-invalidated, not yet materialized on host;
        # watched = has invalidation observers → apply eagerly
        self._pending = np.zeros(self.graph.n_cap + 1, dtype=bool)
        self._watched = np.zeros(self.graph.n_cap + 1, dtype=bool)
        # nids whose invalidation is CURRENTLY being applied from a device
        # wave — only those skip the journal echo (is_wave_echo); a handler
        # that host-led invalidates some OTHER node during application must
        # still journal (a global flag here would silently desync the device
        # mask)
        self._applying_ids: set = set()
        #: table marks dropped as wave echoes (ids, not calls): a watched
        #: scalar twin's ``mark_row_stale`` handler firing while the wave
        #: that invalidated it is applied. See :meth:`is_wave_echo`.
        self.wave_echo_marks_dropped = 0
        # columnar row blocks (bind_table_rows): sorted by base, with flat
        # base/end arrays for O(log blocks) wave partitioning
        self._row_blocks: List[RowBlock] = []
        self._block_bases = np.empty(0, dtype=np.int64)
        self._block_ends = np.empty(0, dtype=np.int64)
        self._block_by_table: Dict[int, RowBlock] = {}
        #: the blocks of tables declared ``hot`` (kept fresh after every
        #: wave: :meth:`refresh_hot`), in refresh order: a block that is the
        #: source of a declared cross-block edge before the edge's target.
        #: Empty unless a bound table says so: no path then gains a call
        self._hot_blocks: List[RowBlock] = []
        #: declared cross-block edges, as (source block base, target block
        #: base) pairs: what orders the hot blocks
        self._block_edges: set = set()
        self.hot_refresh_rows = 0  # rows the sparse refresh recomputed
        self.hot_refresh_dispatches = 0  # refresh_rows programs dispatched
        #: waves whose hot blocks the whole-block program refreshed: the
        #: wave arrived as a mask, or with more rows than the sparse widths
        self.hot_refresh_block_fallbacks = 0
        #: blocks refresh_block_on_device refreshed by the refresh_rows
        #: program on their few invalid rows, not by the whole-block one
        self.block_refresh_sparse = 0
        self._sharded_mirror: Optional[dict] = None  # see sharded_mirror
        self._packed_mirror: Optional[dict] = None  # see packed_mirror
        self._routed_mirror: Optional[dict] = None  # see routed_mirror
        self._routed_config: Optional[dict] = None  # see enable_mesh_routing
        #: optional resilience.WaveWatchdog: when attached, union/lane burst
        #: dispatches route through it (deadline + fault containment with a
        #: split-host-loop fallback); None = direct dispatch, zero overhead
        self.watchdog = None
        #: optional graph.nonblocking.WavePipeline (ISSUE 7): the lazy seed
        #: accumulator + fused-chain dispatcher; Computed.invalidate_eventually
        #: and FusionHub.enable_nonblocking route here
        self.pipeline = None
        #: optional graph.superround.SuperRoundProgram (ISSUE 14): the
        #: resident whole-live-loop device program with double-buffered
        #: host I/O; enable_super_rounds installs it and
        #: WavePipeline.drain() covers its in-flight work
        self.super_rounds = None
        #: True while a pipeline harvest applies wave N-1's newly-mask WITH
        #: wave N still executing on device — the fan-out index reads it to
        #: count fences drained in the overlap window (ISSUE 7 stage c)
        self.overlap_active = False
        self.waves_run = 0
        self.device_invalidations = 0
        #: fired on every wave application with the newly-invalid set AS
        #: THE DEVICE SHIPPED IT — an id array (small waves) or a bool mask
        #: over node ids (lane bursts, 1 bit/node). The RPC fan-out index
        #: (rpc/fanout.py) drains subscribed keys straight from here into
        #: per-peer invalidation batches — no per-subscription watch-task
        #: wakeup on the burst path. Hooks must be cheap and non-reentrant
        #: (they run inside wave application).
        self.newly_hooks: List = []
        #: per-wave timeline recorder (ISSUE 3): every wave dispatch records
        #: seeds / newly / device-vs-host ms / journal depth / cause id into
        #: a ring buffer surfaced by FusionMonitor.report()["waves"] and the
        #: bench telemetry section. ``profiler.enabled = False`` reduces the
        #: instrumentation to attribute checks.
        self.profiler = WaveProfiler()
        #: cause id of the wave currently being applied (stamped into
        #: $sys-c frames by the fan-out index) + the host timestamp the
        #: apply started at — the origin end of the end-to-end delivery
        #: histogram
        self.last_cause_id: Optional[str] = None
        self.last_wave_applied_ts: Optional[float] = None
        #: seq of the last wave begun — minted at _begin_wave so recorder
        #: events during application join the profiler record they belong to
        self.last_wave_seq: Optional[int] = None
        hub.registry.on_register.append(self._on_register)
        hub.edge_added_hooks.append(self._on_edge_added)
        hub.invalidated_hooks.append(self._on_invalidated)
        hub.attach_graph_backend(self)
        global_metrics().register_collector(self, TpuGraphBackend._collect_metrics)

    def device_layout(self) -> dict:
        """Where each resident array of this backend lies
        (:meth:`DeviceGraph.device_layout`'s form): the graph's and, as
        ``table<i>.values`` / ``table<i>.valid``, every bound table's."""
        layout = self.graph.device_layout()
        for i, blk in enumerate(self._row_blocks):
            layout[f"table{i}.values"] = array_placement(blk.table._values)
            layout[f"table{i}.valid"] = array_placement(blk.table._valid_dev)
        return layout

    def _collect_metrics(self) -> dict:
        """Pull-time gauges for /metrics (weak-registered — a dead backend
        drops out of the scrape on its own)."""
        return {
            "fusion_graph_nodes": self.graph.n_nodes,
            "fusion_graph_edges": self.graph.n_edges,
            "fusion_graph_journal_depth": len(self._journal),
            "fusion_waves_run_total": self.waves_run,
            "fusion_device_invalidations_total": self.device_invalidations,
            "fusion_wave_echo_marks_dropped_total": self.wave_echo_marks_dropped,
            "fusion_sweep_packed_dispatches_total": self.graph.sweep_packed_dispatches,
            "fusion_mirror_recaptures_in_place_total": self.graph.mirror_rows_kept,
            "fusion_mirror_slots_revived_total": self.graph.mirror_slots_revived,
            "fusion_hot_refresh_rows_total": self.hot_refresh_rows,
            "fusion_hot_refresh_dispatches_total": self.hot_refresh_dispatches,
            "fusion_hot_refresh_block_fallbacks_total": self.hot_refresh_block_fallbacks,
            "fusion_refresh_block_sparse_total": self.block_refresh_sparse,
        }

    def _begin_wave(self) -> str:
        """Mint this wave's cause id: the active tracing span when one is
        open (a command/mutation running under CommandTracer — the wave
        then links back to its originating span, SURVEY §5.1's activity
        propagation), else a process-unique sequence id. The id rides the
        fan-out into ``$sys-c`` frame entries so a client fence can name
        the server-side wave that caused it. Also mints the wave SEQ here
        (not at record time) and publishes it to the flight recorder, so
        lifecycle events recorded DURING this wave's application carry the
        wave they belong to (ISSUE 4). Wave-shaped causes carry the SAME
        seq as the profiler/journal records — one numbering, so an
        operator grepping for "wave#7" lands on wave 7's record.

        Returns ``(cause, seq)``: call sites hold BOTH and pass the seq to
        :meth:`_profile_wave` — a nested wave (an invalidation handler
        triggering another cascade mid-apply) overwrites ``last_wave_seq``,
        and recording the outer wave from the attribute would stamp it
        with the inner wave's number."""
        self.last_wave_seq = next_wave_seq()
        span = current_span()
        if span is not None:
            cause = span_cause_id(span)
        else:
            cause = f"{_CAUSE_PREFIX}/wave#{self.last_wave_seq}"
        self.last_cause_id = cause
        return cause, self.last_wave_seq

    def _begin_wave_span(self, n: int):
        """Mint ``n`` logical-wave seqs for ONE physically-fused dispatch
        (ISSUE 7): every logical wave fused into a chain keeps its own seq
        — the recorder stamps per-stage events with the stage's seq, the
        profiler record carries the whole span, and explain() resolves any
        seq in the span back to the fused record. The chain's cause id
        names the span (``wave#s0-s1``) unless a tracing span is open —
        same precedence as :meth:`_begin_wave`.

        Returns ``(cause, seqs)`` with ``seqs`` a list of n ints
        (contiguous absent concurrent minters — the span bounds in the
        profiler record are [seqs[0], seqs[-1]])."""
        seqs = [next_wave_seq() for _ in range(max(n, 1))]
        self.last_wave_seq = seqs[0]
        span = current_span()
        if span is not None:
            cause = span_cause_id(span)
        elif len(seqs) == 1:
            cause = f"{_CAUSE_PREFIX}/wave#{seqs[0]}"
        else:
            cause = f"{_CAUSE_PREFIX}/wave#{seqs[0]}-{seqs[-1]}"
        self.last_cause_id = cause
        return cause, seqs

    def _profile_wave(
        self, kind, seeds, cause, t0, t1, newly, seq, groups=None,
        fused_depth=None, seq_span=None, dispatches=None, mesh=None,
    ) -> None:
        """``t0``/``t1`` are the host clock around the blocking dispatch:
        ``device_ms`` is dispatch until the readback returned, so device
        time, transfer and host wait together (see WaveProfiler)."""
        with hot_span("wave.profile", seq):
            if self.profiler.enabled:
                self.profiler.record_wave(
                    kind,
                    seeds=seeds,
                    newly=newly,
                    device_ms=(t1 - t0) * 1e3,
                    apply_ms=(time.perf_counter() - t1) * 1e3,
                    cause=cause,
                    groups=groups,
                    seq=seq,
                    fused_depth=fused_depth,
                    seq_span=seq_span,
                    dispatches=dispatches,
                    mesh=mesh,
                )
                if fused_depth is not None and dispatches:
                    # per-dispatch depth samples feed the engagement histogram
                    per = max(int(round(fused_depth / dispatches)), 1)
                    for _ in range(int(dispatches)):
                        self.profiler.note_fused_dispatch(per)
            if RECORDER.enabled:
                detail = f"{kind}: seeds={seeds} newly={newly}"
                if fused_depth is not None:
                    detail += f" fused_depth={fused_depth}"
                RECORDER.note(
                    "wave",
                    cause=cause,
                    wave=seq,
                    detail=detail,
                )

    # ------------------------------------------------------------------ event feed
    def _on_register(self, computed: "Computed") -> None:
        input = computed.input
        with self._lock:
            nid = self._id_by_input.get(input)
            old = None
            if nid is None:
                nid = self._row_nid_for_input(input)
                if nid is not None:
                    # ADOPTION: the scalar node materializes an EXISTING
                    # columnar row node — row r of a bound table IS graph
                    # node base+r, so the two views cascade as one logical
                    # node. No epoch bump (the block's declared in-edges
                    # belong to every version until redeclared), but a
                    # fresh consistent value supersedes any device invalid
                    # bit — leaving it set would stop future cascades at
                    # this node (silent under-invalidation).
                    self._journal.append(("cpack", np.array([nid], np.int32)))
                    self._id_by_input[input] = nid
                    if self._pending[nid]:
                        self._pending[nid] = False
                        old_ref = self._computed_by_id.get(nid)
                        old = old_ref() if old_ref is not None else None
                else:
                    nid = int(self.graph.add_nodes(1)[0])
                    self._id_by_input[input] = nid
                    self._ensure_host_masks()
            else:
                # recompute: next epoch; stale in-edges die, invalid clears.
                # A pending device invalidation of the PREVIOUS version must
                # be materialized on ITS Computed before the bit clears —
                # otherwise the displaced node would read as consistent
                # again (zombie) once the bit is gone.
                self._journal.append(("bump", nid))
                blk = self._block_of_nid(nid)
                if blk is not None:
                    # a row node's declared in-edges survive the bump:
                    # re-declare them at the new epoch (the bump's edge kill
                    # is the body-capture rule; declared topology has its
                    # own lifetime — "until redeclared")
                    ins = blk.declared_in_srcs(nid)
                    if len(ins):
                        self._journal.append(
                            ("epack", (ins.copy(), np.full(len(ins), nid, np.int32)))
                        )
                if self._pending[nid]:
                    self._pending[nid] = False
                    old_ref = self._computed_by_id.get(nid)
                    old = old_ref() if old_ref is not None else None
            self._computed_by_id[nid] = weakref.ref(computed)
            computed._backend_nid = nid
        if RECORDER.enabled:
            RECORDER.note("registered", key=repr(input), detail=f"nid={nid}")
        if old is not None:
            from ..core.computed import LAZY_WAVE_DETAIL

            self._applying_ids.add(nid)
            try:
                # the displaced node's pending device invalidation
                # materializes as it is superseded — journal it as the
                # device-wave mechanism it is, not as host-led
                old.invalidate_local(_detail=LAZY_WAVE_DETAIL)
            finally:
                self._applying_ids.discard(nid)

    def _row_nid_for_input(self, input) -> Optional[int]:
        """The columnar node id these call args map to, if the input's
        method is table-backed AND its table is bound to a row block."""
        if not self._block_by_table:
            return None
        md = getattr(input, "method_def", None)
        service = getattr(input, "service", None)
        if md is None or service is None or md.table is None:
            return None
        table = md.peek_table(service)
        if table is None:
            return None
        blk = self._block_by_table.get(id(table))
        if blk is None:
            return None
        row = md.row_for_args(input.args, table)
        if row is None or not (0 <= row < blk.n_rows):
            return None
        return blk.base + int(row)

    def _block_of_nid(self, nid: int) -> Optional[RowBlock]:
        if not self._block_bases.size:
            return None
        i = int(np.searchsorted(self._block_bases, nid, side="right")) - 1
        if i >= 0 and nid < self._block_ends[i]:
            return self._row_blocks[i]
        return None

    def _on_edge_added(self, dependent: "Computed", used: "Computed") -> None:
        with self._lock:
            did = self._id_by_input.get(dependent.input)
            uid = self._id_by_input.get(used.input)
            if did is None or uid is None:
                return  # nodes born before the backend attached
            self._journal.append(("edge", (uid, did)))

    def is_wave_echo(self, nids):
        """Whether THIS backend is right now applying to a node what the
        device already computed (``_eager_invalidate`` from
        ``_apply_newly_ids`` / ``_apply_newly_mask``, ``_on_register``'s
        displaced computed): a bool for one node id, a mask for an id
        array. An invalidation that arrives for such a node, through
        whichever hook (``_on_invalidated`` for the computed; for its
        ``mark_row_stale`` handler the table's ``on_invalidate`` hooks, the
        bound block's ``on_inv`` and the service's table → scalar probe),
        is the wave's own echo: it journals nothing and reaches no node.

        Dropping it is the same work, not less of it: (1) a node handed to
        ``_eager_invalidate`` is in the wave's newly set, so the wave
        marked it on the device and expanded through it; (2) non-seed
        invalid nodes block, so a union wave seeded at it reaches nothing
        the first wave did not mark or find invalid; (3) ``run_icasc``
        re-applies no seed and restores ``was_clear``, so the echo's wave
        changed no bit, no count (its ``total`` was 0), no table row, and
        fired no handler. The one exception the echo had: rows declared
        (``declare_row_edges``) as dependents of a row AFTER a wave
        invalidated it and BEFORE the next flush were reached by the echo
        when, and only when, that row had a watched twin; for any other
        invalid row they never were, and now they are not for any row.
        The displaced computed's echo did harm besides: it arrives when the
        registry already holds the NEW version, so the table → scalar probe
        told that one to invalidate itself once computed, and the ``icasc``
        landed behind the new version's ``bump`` and ``epack``: a twin
        re-read locally after a wave came back invalid (ROADMAP D11)."""
        applying = self._applying_ids
        if isinstance(nids, np.ndarray):
            if not applying:
                return np.zeros(nids.shape, dtype=bool)
            return np.isin(nids, list(applying))
        return nids in applying

    def _on_invalidated(self, computed: "Computed") -> None:
        nid = getattr(computed, "_backend_nid", None)
        if nid is not None and self.is_wave_echo(nid):
            return  # the device already knows — this IS a wave application
        with self._lock:
            nid = self._id_by_input.get(computed.input)
            if nid is not None:
                self._journal.append(("invalid", nid))
                self._pending[nid] = False  # host led; nothing left to materialize

    def attach_watchdog(self, watchdog):
        """Route wave dispatches through a resilience.WaveWatchdog: a fused
        burst that raises or blows its deadline degrades to the split host
        loop; the first fused wave after recovery is oracle-verified."""
        self.watchdog = watchdog
        return watchdog

    def _wave_union(self, seed_lists):
        if self.watchdog is not None:
            return self.watchdog.run_union(self.graph, seed_lists)
        return self.graph.run_waves_union(seed_lists)

    def _wave_lanes(self, seed_lists):
        if self.watchdog is not None:
            return self.watchdog.run_lanes(self.graph, seed_lists)
        return self.graph.run_waves_lanes(seed_lists)

    def _wave_union_seq(self, seed_lists):
        if self.watchdog is not None:
            return self.watchdog.run_seq(self.graph, seed_lists)
        return self.graph.run_waves_union_seq(seed_lists)

    def mark_watched(self, computed: "Computed") -> None:
        """An invalidation observer attached: device waves must apply this
        node EAGERLY (hub routes ``Computed.on_invalidated`` here)."""
        nid = getattr(computed, "_backend_nid", None)
        if nid is not None:
            self._watched[nid] = True

    def _ensure_host_masks(self) -> None:
        need = self.graph.n_cap + 1
        if len(self._pending) < need:
            for name in ("_pending", "_watched"):
                old = getattr(self, name)
                arr = np.zeros(need, dtype=bool)
                arr[: len(old)] = old
                setattr(self, name, arr)


    # ------------------------------------------------------------------ flush
    def flush(self) -> None:
        """Replay the event journal against the device mirror IN ORDER,
        coalescing consecutive same-type runs into batches. Ordered replay is
        what keeps the mirror coherent: a stale invalid-mark buffered before
        a node's recompute-bump dies with the bump instead of resurrecting."""
        with self._lock:
            journal, self._journal = self._journal, []
        if not journal:
            return
        t_flush0 = time.perf_counter()
        with hot_span("flush", start=t_flush0):
            self._replay_journal(journal, t_flush0)

    def _replay_journal(self, journal: List[Tuple[str, object]], t_flush0: float) -> None:
        """:meth:`flush`'s body, for a non-empty journal taken at ``t_flush0``."""
        journal_pre = len(journal)
        with hot_span("flush.coalesce"):
            journal = self._group_commuting_entries(journal)
        journal_post = len(journal)
        icasc_parts: List[np.ndarray] = []
        icasc_s = 0.0  # embedded wave time: reported on the wave records,
        # subtracted from flush_ms so the two never double-count

        def run_icasc() -> None:
            nonlocal icasc_s
            # Union expansion for the accumulated table marks (seeds
            # conduct even while already invalid — ops/wave.py). The seeds
            # themselves are NOT re-applied: each table marked its own rows
            # stale and probed their scalar twins at mark time
            # (MemoTable.invalidate → on_invalidate hooks), and a seed
            # refreshed after its mark must not be re-staled — the union
            # re-marks every seed, so refreshed ones are restored after.
            # _apply_newly never journals (quiet table marks +
            # invalidate_local under _applying_ids): no flush re-entry.
            nids = np.unique(np.concatenate(icasc_parts))
            icasc_parts.clear()
            cause, wave_seq = self._begin_wave()
            t0 = time.perf_counter()
            with hot_span("flush.icasc", wave_seq, t0):
                was_clear = nids[~self.graph._h_invalid[nids]]
                with hot_span("wave.union"):
                    total, newly_ids = self._wave_union([nids.tolist()])
                newly_ids = newly_ids[~np.isin(newly_ids, nids)]
                if was_clear.size:
                    self.graph.clear_invalid_ids(was_clear)
                t1 = time.perf_counter()
                self._apply_newly(newly_ids)
                self.device_invalidations += total
                self._profile_wave("icasc", len(nids), cause, t0, t1, len(newly_ids), wave_seq)
            icasc_s += time.perf_counter() - t0

        i, n = 0, len(journal)
        while i < n:
            kind = journal[i][0]
            j = i
            while j < n and journal[j][0] == kind:
                j += 1
            # (after _group_commuting_entries, an N-recompute storm's
            # alternating entries arrive here as a few long same-kind runs)
            batch = [payload for _, payload in journal[i:j]]
            if kind in ("cpack", "bump") and icasc_parts:
                # a refresh/recompute of an ALREADY-ACCUMULATED mark must
                # not be clobbered by (or clobber) the deferred expansion:
                # expand NOW, in journal order, before clearing those bits.
                # Non-intersecting batches (the common case) keep deferring
                # — one union per flush.
                touched = (
                    np.concatenate(batch) if kind == "cpack"
                    else np.asarray(batch, dtype=np.int32)
                )
                acc = np.concatenate(icasc_parts)
                if np.isin(touched, acc).any():
                    run_icasc()
            with hot_span(_REPLAY_SPAN[kind]):
                self._replay_run(kind, batch, icasc_parts)
            i = j
        if icasc_parts:
            run_icasc()
        if self.profiler.enabled:
            self.profiler.note_flush(
                journal_pre,
                journal_post,
                (time.perf_counter() - t_flush0 - icasc_s) * 1e3,
            )

    def _replay_run(self, kind: str, batch: list, icasc_parts: List[np.ndarray]) -> None:
        """One batched same-kind run of the journal against the mirror."""
        if kind == "bump":
            self.graph.bump_epochs(np.asarray(batch, dtype=np.int32))
        elif kind == "edge":
            arr = np.asarray(batch, dtype=np.int32)
            # dst_epoch defaults to the dependent's CURRENT epoch, which
            # is correct exactly because earlier bumps already applied
            self.graph.add_edges(arr[:, 0], arr[:, 1])
        elif kind == "epack":  # bulk-declared row edges (already nids)
            self.graph.add_edges(
                np.concatenate([p[0] for p in batch]),
                np.concatenate([p[1] for p in batch]),
            )
        elif kind == "icasc":
            # host-led table invalidations CASCADE — but interleaved
            # scalar churn would split them into many batches, and a
            # union wave per batch is the one per-flush device cost
            # that matters. All icasc marks of this flush mark their
            # bits NOW (order vs bumps/refreshes preserved) and expand
            # in ONE union wave at the END: expansion against the
            # final structural state is safe — an edge only dies when
            # its dependent recomputed, and a recomputed dependent is
            # fresh by construction.
            nids = np.concatenate(batch)
            self.graph.mark_invalid(nids)
            icasc_parts.append(nids)
        elif kind == "cpack":  # bulk refreshes: consistent again, no bump
            self.graph.clear_invalid_ids(np.concatenate(batch))
        else:  # invalid
            self.graph.mark_invalid(np.asarray(batch, dtype=np.int32))

    @staticmethod
    def _group_commuting_entries(journal: List[Tuple[str, object]]) -> List[Tuple[str, object]]:
        """Regroup every stretch of ``bump``, ``cpack``, ``edge`` and
        ``epack`` entries by kind (bumps, then cpacks, then edges, then
        epacks, each kind in its own order), so that the batcher replays the
        stretch as four runs and not as one run an entry.

        Scalar reads journal alternations. A recompute of a row node is
        ``bump x, epack(-> x)`` and then one captured ``edge`` per awaited
        dependency, some of which recompute in their turn; N recomputes a
        flush were 2N device dispatches and more (~0.5 s/op at 10M: the r5
        'scalar churn' phase; five re-read totals of ten products each
        twenty-five runs a command). A FIRST read of a row whose body awaits
        other rows is adoption ``cpack``, captured ``edge``, the next
        dependency's ``cpack``, its ``edge``, ...: 1,600 subscribed totals
        were 38,000 runs of one entry.

        Why the regrouping is sound: a ``cpack`` clears invalid bits and
        touches neither epochs nor edges; an edge append (``edge``,
        ``epack``) reads its DEPENDENT's current epoch and nothing else, so
        it commutes with everything but a ``bump`` of that dependent; a
        ``bump`` commutes with the bumps and cpacks of other nodes. So a
        bump may move to the front of its stretch unless an entry before it
        in the stretch adds an edge INTO its node (that edge was captured at
        the old epoch and must stay dead) or bumps the same node again (the
        second bump must see the first recapture applied): such a bump
        starts a new stretch where it stands. ``icasc`` and ``invalid`` set
        invalid bits, which bumps and cpacks clear: they end a stretch and
        nothing crosses them."""
        out: List[Tuple[str, object]] = []
        i, n = 0, len(journal)
        while i < n:
            kinds: Dict[str, list] = {"bump": [], "cpack": [], "edge": [], "epack": []}
            bumped, into, into_packs = set(), set(), []
            while i < n and journal[i][0] in kinds:
                kind, payload = journal[i]
                if kind == "bump":
                    if (
                        payload in bumped
                        or payload in into
                        or any((dsts == payload).any() for dsts in into_packs)
                    ):
                        break  # stays behind what it must follow
                    bumped.add(payload)
                elif kind == "edge":
                    into.add(payload[1])
                elif kind == "epack":
                    into_packs.append(payload[1])
                kinds[kind].append(journal[i])
                i += 1
            for kind in ("bump", "cpack", "edge", "epack"):
                out.extend(kinds[kind])
            if i < n and journal[i][0] not in kinds:
                out.append(journal[i])  # icasc / invalid: where it stood
                i += 1
        return out

    # ------------------------------------------------------------------ columnar ingest
    def bind_table_rows(self, table, n_rows: Optional[int] = None) -> RowBlock:
        """Register a MemoTable's dense key space as ONE contiguous block of
        graph nodes (row ``r`` ⇔ node ``base+r``) — the vectorized live
        ingest path (VERDICT r3 #2). Bind at service setup, BEFORE scalar
        reads of the method create standalone nodes (a scalar node created
        pre-bind keeps its own node id and will not cascade as the row).

        After binding:
        - ``declare_row_edges`` declares dependency topology in bulk numpy;
        - host-led ``table.invalidate(ids)`` mirrors to the device graph as
          bulk invalid marks; ``table.refresh`` (or a ``read_batch`` that
          refreshes) clears the rows' invalid bits — consistent again with
          NO epoch bump, so declared topology survives value churn;
        - device waves mark hit rows stale vectorized (``_apply_newly``
          partitions the wave by block — no per-row Python);
        - scalar ``@compute_method`` nodes for the same keys ADOPT the
          row's node id on registration (see ``_on_register``)."""
        n = int(n_rows if n_rows is not None else table.n_rows)
        if n > table.n_rows:
            raise ValueError(f"n_rows {n} exceeds table rows {table.n_rows}")
        if table.hot and (table.device_compute_fn is None or n != table.n_rows):
            raise ValueError(
                "a hot table is refreshed on the device after every wave: it "
                "needs a device loader (TableBacking(device_batch=...)) and a "
                "FULL bind"
            )
        with self._lock:
            existing = self._block_by_table.get(id(table))
            if existing is not None:
                if existing.n_rows != n:
                    raise ValueError(
                        f"table already bound with {existing.n_rows} rows"
                    )
                return existing
            if self.device is not None:
                table.place_on(self.device)
            base = self.graph.n_nodes
            self.graph.add_nodes(n)
            self._ensure_host_masks()
            blk = RowBlock(table, base, n)
            self._row_blocks.append(blk)
            self._row_blocks.sort(key=lambda b: b.base)
            self._block_bases = np.array(
                [b.base for b in self._row_blocks], dtype=np.int64
            )
            self._block_ends = np.array(
                [b.end() for b in self._row_blocks], dtype=np.int64
            )
            self._block_by_table[id(table)] = blk
            if table.hot:
                self._hot_blocks = self._ordered_hot_blocks(self._block_edges)

        def on_inv(ids_np, _blk=blk):
            ids64 = np.asarray(ids_np, np.int64)
            if n < table.n_rows:  # partial bind: rows past the block are unmapped
                ids64 = ids64[ids64 < _blk.n_rows]
            if ids64.size == 0:
                return
            nids = (_blk.base + ids64).astype(np.int32)
            echo = self.is_wave_echo(nids)
            if echo.any():
                # the wave's own application, arriving through the twin's
                # mark_row_stale handler: the table has marked its row, the
                # device has nothing to learn
                self.wave_echo_marks_dropped += int(echo.sum())
                nids = nids[~echo]
                if nids.size == 0:
                    return
            with self._lock:
                # icasc, not a bare mark: a host-led table invalidation must
                # CASCADE through the declared row topology (which exists
                # only on device — the reference's rule that invalidation
                # always walks dependents, Computed.cs Invalidate). flush
                # runs the expansion wave in journal order, so a refresh
                # that follows still clears exactly its own rows.
                self._journal.append(("icasc", nids))

        def on_ref(ids_np, _blk=blk):
            ids64 = np.asarray(ids_np, np.int64)
            if n < table.n_rows:
                ids64 = ids64[ids64 < _blk.n_rows]
            if ids64.size == 0:
                return
            with self._lock:
                self._journal.append(("cpack", (_blk.base + ids64).astype(np.int32)))

        on_ref._backend_hook = True  # refresh_block_on_device subsumes it
        table.on_invalidate.append(on_inv)
        table.on_refresh.append(on_ref)
        return blk

    def declare_row_edges(self, src_block: RowBlock, src_rows, dst_block: RowBlock, dst_rows) -> int:
        """Declare dependency edges used(src row) → dependent(dst row) in
        bulk — the columnar analogue of per-``await`` edge capture. One
        journal entry per call regardless of edge count; flush appends them
        to the device CSR in one numpy splice. Declared edges persist
        across value churn (columnar refresh never bumps epochs) and are
        re-declared automatically when a row's scalar twin recomputes.
        Declarations ACCUMULATE — to change a row's dependency set, call
        :meth:`clear_declared_row_edges` first, then declare the new
        topology."""
        src_rows = self._check_rows(src_block, src_rows).astype(np.int64)
        dst_rows = self._check_rows(dst_block, dst_rows).astype(np.int64)
        if src_rows.shape != dst_rows.shape:
            raise ValueError("src_rows and dst_rows must have the same shape")
        if src_rows.size == 0:
            return 0
        src_nids = (src_block.base + src_rows).astype(np.int32)
        dst_nids = (dst_block.base + dst_rows).astype(np.int32)
        with self._lock:
            pair = (src_block.base, dst_block.base)
            if src_block is not dst_block and pair not in self._block_edges:
                # raises on a cycle between hot blocks, before anything is
                # declared
                self._hot_blocks = self._ordered_hot_blocks(self._block_edges | {pair})
                self._block_edges.add(pair)
            self._journal.append(("epack", (src_nids, dst_nids)))
            dst_block._decl_src.append(src_nids)
            dst_block._decl_dst.append(dst_nids)
            # the cached CSR stays: per-row queries scan the new tail
            # (declared_in_srcs); only clear_declared_row_edges rebuilds
        return int(src_nids.size)

    def _ordered_hot_blocks(self, block_edges: set) -> List[RowBlock]:
        """The hot blocks in refresh order under the declared cross-block
        edges ``block_edges``: a block whose rows others are computed from
        (through any chain of blocks) comes before them. Two hot blocks
        that each reach the other have no order: ValueError."""
        hot = [b for b in self._row_blocks if b.table.hot]
        below: Dict[int, set] = {}
        for blk in hot:  # what each hot block reaches (a handful of blocks)
            seen, stack = set(), [blk.base]
            while stack:
                at = stack.pop()
                for s_base, d_base in block_edges:
                    if s_base == at and d_base not in seen:
                        seen.add(d_base)
                        stack.append(d_base)
            below[blk.base] = seen
        for a in hot:
            for b in hot:
                if a is not b and b.base in below[a.base] and a.base in below[b.base]:
                    raise ValueError(
                        "declared edges make a cycle between two hot tables "
                        f"(blocks at {a.base} and {b.base}): neither can be "
                        "refreshed before the other"
                    )
        # fewer hot blocks below = later in a chain; ties keep bind order
        return sorted(
            hot, key=lambda b: -sum(1 for o in hot if o.base in below[b.base])
        )

    @staticmethod
    def _check_rows(block: RowBlock, rows) -> np.ndarray:
        """Rows → int32 array, validated against the block: a silent
        out-of-range row would seed a cascade at a FOREIGN node id."""
        rows = np.asarray(rows, dtype=np.int64)
        if rows.size and (int(rows.min()) < 0 or int(rows.max()) >= block.n_rows):
            raise ValueError(f"rows out of range [0, {block.n_rows})")
        return rows.astype(np.int32)

    def clear_declared_row_edges(self, block: RowBlock, rows) -> None:
        """The 'redeclare' half of the declared-edge lifetime: drop declared
        edges INTO these rows from the declaration log AND kill their live
        in-edges (an epoch bump — the recompute rule: dependencies changed).
        Follow with :meth:`declare_row_edges` for the new topology; without
        this, repeated declarations into the same rows would only
        accumulate."""
        rows = self._check_rows(block, rows)
        nids = (block.base + rows.astype(np.int64)).astype(np.int32)
        drop = set(int(x) for x in nids)
        with self._lock:
            new_src, new_dst = [], []
            for s_arr, d_arr in zip(block._decl_src, block._decl_dst):
                keep = ~np.isin(d_arr, nids)
                if keep.all():
                    new_src.append(s_arr)
                    new_dst.append(d_arr)
                elif keep.any():
                    new_src.append(s_arr[keep])
                    new_dst.append(d_arr[keep])
            block._decl_src, block._decl_dst = new_src, new_dst
            block._csr = None
            for nid in drop:
                self._journal.append(("bump", nid))

    def cascade_rows_batch(self, block: RowBlock, rows) -> int:
        """Invalidate + cascade table rows in ONE union device wave (the
        command-completion shape for table-backed services: a bulk mutation
        lands, its rows and their transitive dependents go stale). The wave
        application marks hit rows stale in bulk and runs the two-tier
        host apply for scalar twins. Returns total newly invalidated."""
        with hot_span("cascade") as span:
            self.flush()
            nids = block.base + self._check_rows(block, rows)
            cause, wave_seq = self._begin_wave()
            # NOTE: routing small seeds through the dense frontier BFS
            # (run_wave_collect) was measured SLOWER at 10M (2.2 s vs 0.77 s)
            # — per-level full-edge gathers over the pow2-padded edge arrays
            # lose to one depth-free mirror sweep. The mirror union is the
            # lone-wave path too.
            t0 = time.perf_counter()
            with hot_span("wave.union", wave_seq, t0):  # its event names the wave
                total, newly_ids = self._wave_union([nids.tolist()])
            span.set_wave(wave_seq)
            t1 = time.perf_counter()
            self._apply_newly(newly_ids)
            self.waves_run += 1
            self.device_invalidations += total
            self._profile_wave("union", len(nids), cause, t0, t1, len(newly_ids), wave_seq)
            return total

    @staticmethod
    def _loader_args(table) -> tuple:
        """The device loader's runtime arguments, made now (the loader's
        state may have changed since the last refresh)."""
        return tuple(table.device_loader_args()) if table.device_loader_args is not None else ()

    def _block_refresh_state(self, block: RowBlock) -> dict:
        """The device-refresh runtime state the resident super-round
        program (``graph/superround.py``) threads through its loop carry
        (memo values, validity, loader args). Raises for tables without a
        device loader or partial binds (callers fall back to the
        sequential pair)."""
        table = block.table
        fn = table.device_compute_fn
        if fn is None:
            raise TypeError(
                "table has no device loader — declare "
                "TableBacking(device_batch=...) or run the sequential "
                "cascade_rows_lanes + table.refresh() pair"
            )
        if block.n_rows != table.n_rows:
            raise ValueError(
                "the fused burst→refresh composition requires a FULL table bind"
            )
        update_valid = not table._valid_dev_dirty
        loader_args = self._loader_args(table)
        return {
            "base": block.base,
            "n_rows": block.n_rows,
            "fn": fn,
            "largs": loader_args,
            "values": table._values,
            "valid_dev": table.valid_mask if update_valid else table._valid_dev,
            "update_valid": update_valid,
            "cache": block._dev_refresh,
        }

    def enable_super_rounds(
        self, block: RowBlock, depth: int = 4, max_words: int = 16
    ):
        """Install the resident super-round program (ISSUE 14): K live
        rounds of (seed accumulate → fused wave chain → columnar refresh
        through the memo-table loader → two-tier memo apply → packed
        fence-mask extraction) compile into ONE device program, and the
        host's only per-super-round work is staging a seed buffer and
        draining a packed fence buffer — double-buffered, so staging for
        super-round N+1 and the fence drain of N−1 both overlap N's device
        execution. Returns the :class:`~stl_fusion_tpu.graph.superround.
        SuperRoundProgram`; ``backend.super_rounds`` holds it and
        ``WavePipeline.drain()`` covers its in-flight work."""
        from .superround import SuperRoundProgram

        if self.super_rounds is not None and not self.super_rounds._disposed:
            raise ValueError("backend already has a SuperRoundProgram attached")
        self.super_rounds = SuperRoundProgram(
            self, block, depth=depth, max_words=max_words
        )
        return self.super_rounds

    def refresh_block_on_device(self, block: RowBlock) -> int:
        """Recompute ALL stale rows of a bound table ON DEVICE, from the
        device-resident invalid state, through the table's DEVICE loader
        (``TableBacking(device_batch=...)``) — one dispatch, zero host
        value traffic. This is the churn-recompute path at scale: r4's
        host refresh of a 10M-row stale set moved ~70 MB over PCIe
        per round (ids up + values up) at ~1.1 M rows/s; here values never
        leave HBM. Host bookkeeping (stale counts, versions) updates from
        the host invalid mirror — no readback. Returns rows refreshed.

        Semantics = ``table.refresh(stale_rows)`` for every row the graph
        holds invalid in this block: values recomputed, rows valid again
        with NO epoch bump (declared topology survives), scalar twins stay
        pending-invalid until their next read — identical to the host
        path. A row re-read through its scalar twin after a wave (a client's
        re-read of a written row) leaves the graph's invalid set but stays
        stale on the table, by design: the scalar recompute never writes
        the columnar cache, and this refresh recomputes exactly the rows
        the graph holds invalid, so ``valid_mask`` shows that row stale
        until the table's own next read of it (``read_batch``,
        ``table.refresh``) recomputes it.

        The program is chosen by how many rows the graph holds invalid in
        the block. From 1 to :data:`HOT_REFRESH_MAX_ROWS` of them, the
        ``refresh_rows`` program of :meth:`refresh_rows_on_device` runs on
        those rows alone (span ``refresh.sparse``, counted in
        ``block_refresh_sparse``), with the same host bookkeeping by the
        ids; its ids are padded to the cap itself, so a block has one such
        program, compiled by its first sparse refresh, and no width can
        first appear (and compile) later. With more rows, or none, the
        whole-block program runs: the loader for EVERY row of the table,
        the result masked. Every graph-invalid row of a bound block is
        stale on its table, so the table's exact O(1) stale count above the
        cap takes the whole program with no scan of the host mirror; a
        scan that finds more rows than the cap takes it too. A table
        declared ``hot`` gets one refresh or the other after every wave
        (:meth:`refresh_hot`)."""
        with hot_span("refresh"):
            self.flush()
            table = block.table
            fn = table.device_compute_fn
            if fn is None:
                raise TypeError(
                    "table has no device loader — declare "
                    "TableBacking(device_batch=...) or use table.refresh()"
                )
            if block.n_rows != table.n_rows:
                raise ValueError(
                    "refresh_block_on_device requires a FULL table bind "
                    f"(block covers {block.n_rows} of {table.n_rows} rows); "
                    "partially bound tables refresh through table.refresh()"
                )
            cap = self.HOT_REFRESH_MAX_ROWS
            if table._stale_count <= cap:
                rows = np.flatnonzero(self.graph._h_invalid[block.base : block.end()])
                if 0 < rows.size <= cap:
                    with hot_span("refresh.sparse"):
                        self._refresh_block_rows(block, rows.astype(np.int32), cap)
                    self.block_refresh_sparse += 1
                    return int(rows.size)
            g = self.graph.device_arrays()
            update_valid = not table._valid_dev_dirty
            loader_args = self._loader_args(table)
            prog = block._dev_refresh.get(update_valid)
            if prog is None:
                import jax
                import jax.numpy as jnp
                from jax import lax

                base, n_rows = block.base, block.n_rows

                @jax.jit
                def prog(values, valid_dev, g_invalid, *largs):
                    stale = lax.slice_in_dim(g_invalid, base, base + n_rows)
                    ids = jnp.arange(n_rows, dtype=jnp.int32)
                    fresh = fn(ids, *largs)
                    mask = stale.reshape((n_rows,) + (1,) * (values.ndim - 1))
                    values2 = jnp.where(mask, fresh, values)
                    inv2 = lax.dynamic_update_slice_in_dim(
                        g_invalid, jnp.zeros(n_rows, dtype=g_invalid.dtype), base, 0
                    )
                    valid2 = (valid_dev | stale) if update_valid else valid_dev
                    return values2, valid2, inv2

                block._dev_refresh[update_valid] = prog
            # valid_mask (not the raw array) applies any deferred small
            # updates first; the update_valid=False variant ignores validity
            valid_in = table.valid_mask if update_valid else table._valid_dev
            with hot_span("refresh.dispatch"):
                values2, valid2, inv2 = prog(
                    table._values, valid_in, g.invalid, *loader_args
                )
            table._values = values2
            if update_valid:
                table._valid_dev = valid2
            self.graph._g = g._replace(invalid=inv2)
            # host bookkeeping from the host invalid mirror — no device readback
            dg = self.graph
            cleared = dg._h_invalid[block.base : block.end()].copy()
            n_cleared = int(np.count_nonzero(cleared))
            if n_cleared == 0:
                return 0
            dg._h_invalid[block.base : block.end()] = False
            dg.invalid_version += 1
            # non-backend on_refresh subscribers still get the refreshed ids
            # inside the shared tail; the backend's own hook is skipped — its
            # job (clearing the device invalid bits) was just done in-program
            _finish_block_refresh_bookkeeping(table, cleared)
            return n_cleared

    #: a sparse refresh pads its ids to a power of two, no narrower than
    #: this: every distinct width is a compile, and a wave of a few hundred
    #: rows costs the device the same as one of one row
    HOT_REFRESH_MIN_WIDTH = 512
    #: a wave with more rows than this in one hot block refreshes the block
    #: by the whole-block program (the lat kernel's own cap on a wave's ids:
    #: the sparse widths stay a handful of programs); so does
    #: refresh_block_on_device for a block with more invalid rows, and pads
    #: the ids of one with fewer to this width (a power of two)
    HOT_REFRESH_MAX_ROWS = 8192

    def refresh_hot(self, newly) -> int:
        """Make fresh again, on the device, what one applied wave
        invalidated in the hot tables: called by the wave pipeline after
        the wave's apply (marks, fan-out, ticket), before the next wave is
        dispatched. The form ``newly`` has decides the program: an id array
        (the small-wave path) refreshes exactly those rows
        (:meth:`refresh_rows_on_device`); a bool mask over node ids (a
        fused chain, a lane burst) refreshes every hot block it touches by
        :meth:`refresh_block_on_device`, in the same block order, and is
        counted in ``hot_refresh_block_fallbacks``. Dispatch only: nothing
        here waits for the device. Returns the rows made fresh."""
        if not self._hot_blocks or len(newly) == 0:
            return 0
        if not (isinstance(newly, np.ndarray) and newly.dtype == np.bool_):
            return self.refresh_rows_on_device(newly)
        touched = [b for b in self._hot_blocks if newly[b.base : b.end()].any()]
        self.hot_refresh_block_fallbacks += bool(touched)
        return sum(self.refresh_block_on_device(blk) for blk in touched)

    def refresh_rows_on_device(self, nids) -> int:
        """The sparse twin of :meth:`refresh_block_on_device`: recompute on
        the device the rows of the HOT tables among the node ids ``nids``
        (one applied wave's newly invalid set), through each table's device
        loader called on those ids alone, and make them valid again in the
        graph. Per hot block that holds some of them ONE program,
        ``refresh_rows``: ``fn(ids, *loader_args)``, a scatter into the
        table's values (donated: in place), the rows' bits cleared in the
        device ``invalid`` array; ids padded to a power of two by repeating
        the first (:data:`HOT_REFRESH_MIN_WIDTH`). Blocks go in the order of
        the declared cross-block edges, and each block's loader arguments
        are made when its turn comes: a derived row is computed from source
        rows that are already fresh. Host bookkeeping from the ids, no
        readback, exactly what the whole-block refresh does for a block:
        ``_h_invalid``, ``invalid_version``, the table's stale mask and
        count, its version, the non-backend ``on_refresh`` hooks. Scalar
        twins stay pending-invalid until their next read. A block with more
        than :data:`HOT_REFRESH_MAX_ROWS` of the ids takes the whole-block
        program instead (counted as a fallback). Rows of tables that are not
        hot, and every other row of the hot ones, are not touched. Returns
        the rows refreshed."""
        nids = np.asarray(nids, dtype=np.int64)
        if not self._hot_blocks or nids.size == 0:
            return 0
        with hot_span("refresh.rows"):
            self.flush()
            sparse, whole = 0, []  # rows by refresh_rows; by the block program
            for blk in self._hot_blocks:
                rows = nids[(nids >= blk.base) & (nids < blk.end())] - blk.base
                if rows.size == 0:
                    continue
                if rows.size > self.HOT_REFRESH_MAX_ROWS:
                    whole.append(self.refresh_block_on_device(blk))
                    continue
                rows = np.unique(rows).astype(np.int32)
                self._refresh_block_rows(blk, rows, self.HOT_REFRESH_MIN_WIDTH)
                self.hot_refresh_dispatches += 1
                sparse += len(rows)
            self.hot_refresh_rows += sparse
            self.hot_refresh_block_fallbacks += bool(whole)
            return sparse + sum(whole)

    def _refresh_block_rows(self, blk: RowBlock, rows: np.ndarray, floor: int) -> None:
        """One ``refresh_rows`` dispatch on the rows ``rows`` (unique int32
        row ids of ``blk``), padded to a power of two no narrower than
        ``floor``, and the host bookkeeping from the ids: ``_h_invalid``,
        ``invalid_version``, the table's stale mask and count, its deferred
        validity, its version, the non-backend ``on_refresh`` hooks."""
        dg, table = self.graph, blk.table
        padded = dg._pad_ids_pow2(rows, floor)
        loader_args = self._loader_args(table)
        g = dg.device_arrays()
        with hot_span("refresh.rows.dispatch"):
            table._values, inv2 = self._refresh_rows_program(blk)(
                table._values, g.invalid, table._put(padded), *loader_args
            )
        dg._g = g._replace(invalid=inv2)
        dg._h_invalid[blk.base + rows] = False
        dg.invalid_version += 1
        table._stale_count -= int(np.count_nonzero(table._stale_host[rows]))
        table._stale_host[rows] = False
        table._defer_valid(rows, True)
        table._bump()
        for h in table.on_refresh:
            if not getattr(h, "_backend_hook", False):
                h(rows)

    @staticmethod
    def _refresh_rows_program(block: RowBlock):
        """The jitted ``refresh_rows`` of one block (one trace a width)."""
        prog = block._dev_refresh.get("rows")
        if prog is None:
            import functools

            import jax

            fn, base = block.table.device_compute_fn, block.base

            @functools.partial(jax.jit, donate_argnums=(0,))
            def refresh_rows(values, g_invalid, ids, *largs):
                return (
                    values.at[ids].set(fn(ids, *largs)),
                    g_invalid.at[base + ids].set(False),
                )

            prog = block._dev_refresh["rows"] = refresh_rows
        return prog

    def warm_hot_refresh(self) -> None:
        """Compile (or load) every hot block's ``refresh_rows`` program at
        the narrowest width, recorded as ``refresh_rows`` in
        ``program_warm_report()``: each block refreshes its row 0, which
        changes nothing on a table that holds nothing stale."""
        from .program_cache import time_program_warm

        key = tuple((b.base, b.n_rows) for b in self._hot_blocks)
        with time_program_warm("refresh_rows", key=(key, self.HOT_REFRESH_MIN_WIDTH)):
            self.refresh_rows_on_device([b.base for b in self._hot_blocks])
            for blk in self._hot_blocks:
                blk.table._values.block_until_ready()

    def warm_block_on_device(self, block: RowBlock) -> int:
        """Load EVERY row of a bound table through its DEVICE loader in one
        dispatch — the cold-start warm. The host-loader alternative
        (chunked ``read_batch``) computes on host and ships all values
        host→device (~40 MB at 10M rows). Graph invalid state is
        untouched (a fresh table has nothing invalid to clear)."""
        table = block.table
        fn = table.device_compute_fn
        if fn is None:
            raise TypeError(
                "table has no device loader — declare "
                "TableBacking(device_batch=...) or warm via read_batch()"
            )
        if block.n_rows != table.n_rows:
            raise ValueError("warm_block_on_device requires a FULL table bind")
        if self.graph._h_invalid[block.base : block.end()].any():
            # outstanding graph invalid marks: warming would zero table
            # staleness while the dense/device invalid bits stayed set,
            # silently pre-blocking those rows in later bursts (r5 review)
            raise RuntimeError(
                "block has outstanding invalid marks — use "
                "refresh_block_on_device() (warm is for cold tables)"
            )
        loader_args = self._loader_args(table)
        prog = block._dev_refresh.get("warm")
        if prog is None:
            import jax
            import jax.numpy as jnp

            n_rows = block.n_rows

            @jax.jit
            def prog(*largs):
                ids = jnp.arange(n_rows, dtype=jnp.int32)
                return fn(ids, *largs), jnp.ones(n_rows, dtype=jnp.bool_)

            block._dev_refresh["warm"] = prog
        # the loader's arguments are staged, not resident: commit the outputs
        table._values, table._valid_dev = self.graph.commit(prog(*loader_args))
        table._valid_dev_dirty = False
        table._valid_pending.clear()
        table._valid_pending_n = 0
        n_stale = table._stale_count
        table._stale_host[:] = False
        table._stale_count = 0
        table._bump()
        extern = [h for h in table.on_refresh if not getattr(h, "_backend_hook", False)]
        if extern:
            all_ids = np.arange(block.n_rows, dtype=np.int32)
            for h in extern:
                h(all_ids)
        return n_stale

    def cascade_rows_batch_seq(self, block: RowBlock, row_batches) -> np.ndarray:
        """M :meth:`cascade_rows_batch` calls in ONE device dispatch, each
        batch cascading against the state the previous batches left
        (sequential semantics — identical final state and counts). The
        burst-of-independent-invalidations shape: M commands complete,
        each invalidating its own row set, one dispatch + one readback
        total via the lat mirror (host loop fallback otherwise). Returns
        per-batch newly counts int64[M].

        This IS the wave chain (ISSUE 7): M logical waves physically fused
        — each keeps its own seq, the profiler record carries the span +
        ``fused_depth=M``."""
        self.flush()
        seed_lists = [
            (block.base + self._check_rows(block, rows)).tolist()
            for rows in row_batches
        ]
        cause, seqs = self._begin_wave_span(len(seed_lists))
        lat_before = self.graph.lat_waves
        t0 = time.perf_counter()
        counts, union_ids = self._wave_union_seq(seed_lists)
        t1 = time.perf_counter()
        self._apply_newly(union_ids)
        self.waves_run += len(seed_lists)
        self.device_invalidations += int(counts.sum())
        fused = self.graph.lat_waves > lat_before  # lat chain vs host loop
        self._profile_wave(
            "seq", sum(len(s) for s in seed_lists), cause, t0, t1,
            int(counts.sum()), seqs[0], groups=len(seed_lists),
            fused_depth=len(seed_lists), seq_span=(seqs[0], seqs[-1]),
            dispatches=1 if fused else len(seed_lists),
        )
        return counts

    #: groups per lane chunk at the default word width (32 * max_words=16)
    _LANES_CHUNK = 512

    def cascade_rows_lanes(self, block: RowBlock, row_groups) -> np.ndarray:
        """Lane-packed columnar burst: each row group cascades independently
        in its own bit lane (32 groups per packed word, one topo-mirror
        sweep per chunk) seeded DIRECTLY by table rows — no per-seed
        Computed capture. Multi-chunk bursts fuse into the loop-carried
        chain (one dispatch per FUSE_CHAIN_MAX chunks — ISSUE 7). Returns
        per-group newly counts."""
        self.flush()
        seed_lists = [
            (block.base + self._check_rows(block, g)).tolist() for g in row_groups
        ]
        n_stages = max(-(-len(seed_lists) // self._LANES_CHUNK), 1)
        cause, seqs = self._begin_wave_span(n_stages)
        # cleared first: a watchdog-degraded burst runs the host loop and
        # never touches it — stamping the PREVIOUS burst's fused identity
        # on a host-loop wave would fake engagement during the exact
        # regime the CI gate exists to expose
        self.graph.last_lanes_info = None
        t0 = time.perf_counter()
        counts, union_ids = self._wave_lanes(seed_lists)
        t1 = time.perf_counter()
        self._apply_newly(union_ids)
        self.waves_run += len(seed_lists)
        self.device_invalidations += int(counts.sum())
        info = self.graph.last_lanes_info or {}
        self._profile_wave(
            "lanes", sum(len(s) for s in seed_lists), cause, t0, t1,
            int(counts.sum()), seqs[0], groups=len(seed_lists),
            fused_depth=info.get("depth"), seq_span=(seqs[0], seqs[-1]),
            dispatches=info.get("dispatches"),
        )
        return counts

    # ------------------------------------------------------------------ offload
    def invalidate_cascade(self, computed: "Computed", collect_cap: int = 8192) -> int:
        """Run the invalidation wave for ``computed`` ON DEVICE, then apply
        the closure to host state. Returns nodes the device invalidated.

        The device compacts the newly-invalid ids (O(wave) readback);
        host application is two-tier — eager for watched nodes, a pending
        bit for the rest (materialized on next read). See module docstring."""
        self.flush()
        nid = self._id_by_input.get(computed.input)
        if nid is None:
            computed.invalidate(immediately=True)
            return 1
        cause, wave_seq = self._begin_wave()
        t0 = time.perf_counter()
        count, newly_ids = self.graph.run_wave_collect([nid], cap=collect_cap)
        t1 = time.perf_counter()
        self._apply_newly(newly_ids)
        self.waves_run += 1
        self.device_invalidations += count
        self._profile_wave("collect", 1, cause, t0, t1, len(newly_ids), wave_seq)
        return count

    def invalidate_cascade_batch(self, computeds: Sequence["Computed"]) -> int:
        """Cascade MANY seed invalidations in one device dispatch + one
        readback (the burst shape: a batch of commands completing together).
        All seeds expand in ONE union BFS — identical final state to
        running them sequentially (invalidation is idempotent, and the host
        applies only the union of newly-invalid nodes), at O(edges × depth)
        instead of O(edges × depth × batch). Returns the total
        newly-invalidated count."""
        self.flush()
        seeds: List[List[int]] = []
        fallback = 0
        for c in computeds:
            nid = self._id_by_input.get(c.input)
            if nid is None:
                c.invalidate(immediately=True)
                fallback += 1
            else:
                seeds.append([nid])
        if not seeds:
            return fallback
        cause, wave_seq = self._begin_wave()
        t0 = time.perf_counter()
        total, newly_ids = self._wave_union(seeds)
        t1 = time.perf_counter()
        self._apply_newly(newly_ids)
        self.waves_run += len(seeds)
        self.device_invalidations += total
        self._profile_wave("union", len(seeds), cause, t0, t1, len(newly_ids), wave_seq)
        return total + fallback

    def invalidate_cascade_batch_lanes(
        self, groups: Sequence[Sequence["Computed"]]
    ) -> np.ndarray:
        """Lane-packed live burst: each group (the computeds one command's
        completion invalidates) cascades INDEPENDENTLY in its own bit lane,
        32 groups per packed word, all in one topo-mirror sweep — the live
        path running at the static kernel's lane occupancy instead of one
        union lane per dispatch (VERDICT r2 #1).

        Per-group semantics = a dense BFS from the pre-burst invalid state
        (snapshot-independent groups, the static bench's accounting); the
        UNION of the closures is applied to the hub once, two-tier like
        every other wave path. Returns per-group newly-invalidated counts
        (int64[len(groups)]; a computed not in the graph falls back to an
        immediate host invalidation and counts 1 in its group)."""
        self.flush()
        seed_lists: List[List[int]] = []
        fallback = np.zeros(len(groups), dtype=np.int64)
        for gi, group in enumerate(groups):
            ids: List[int] = []
            for c in group:
                nid = self._id_by_input.get(c.input)
                if nid is None:
                    c.invalidate(immediately=True)
                    fallback[gi] += 1
                else:
                    ids.append(nid)
            seed_lists.append(ids)
        n_stages = max(-(-len(seed_lists) // self._LANES_CHUNK), 1)
        cause, seqs = self._begin_wave_span(n_stages)
        self.graph.last_lanes_info = None  # see cascade_rows_lanes
        t0 = time.perf_counter()
        counts, union_ids = self._wave_lanes(seed_lists)
        t1 = time.perf_counter()
        self._apply_newly(union_ids)
        self.waves_run += len(groups)
        self.device_invalidations += int(counts.sum())
        info = self.graph.last_lanes_info or {}
        self._profile_wave(
            "lanes", sum(len(s) for s in seed_lists), cause, t0, t1,
            int(counts.sum()), seqs[0], groups=len(groups),
            fused_depth=info.get("depth"), seq_span=(seqs[0], seqs[-1]),
            dispatches=info.get("dispatches"),
        )
        return counts + fallback

    def build_topo_mirror(self, k: int = 4, cap: int = 65536) -> dict:
        """Build/refresh the packed topo mirror of the live graph: while
        topology stays stable, ``invalidate_cascade_batch`` bursts run ONE
        depth-free level-ordered sweep (the flagship kernel) instead of a
        level-by-level BFS — the difference between O(edges·depth) and
        O(edges) on deep graphs. Any live-edge change routes bursts back to
        the dense path until this is called again (fingerprint check)."""
        self.flush()
        return self.graph.build_topo_mirror(k=k, cap=cap)

    def _apply_newly(self, newly) -> None:
        """Two-tier host application of a device wave's newly-invalid set.
        ``newly`` is either an id array (small waves — lone unions) or a
        BOOL MASK over node ids (lane bursts: millions of rows travel as
        1 bit/node and apply as vectorized mask ops — materializing ids
        was ~a third of r4's per-burst cost at 10M)."""
        self.last_wave_applied_ts = time.perf_counter()
        # recorder events emitted DURING application (eager invalidations,
        # fanout fence posts) auto-stamp this wave; the finally RESTORES
        # the prior stamp (not None) so a nested wave triggered by an
        # invalidation handler doesn't strip the outer wave's remaining
        # events — and a throwing handler never leaks the stamp
        prev_wave = RECORDER.current_wave
        RECORDER.current_wave = self.last_wave_seq
        try:
            with hot_span("wave.apply", self.last_wave_seq, self.last_wave_applied_ts):
                if isinstance(newly, np.ndarray) and newly.dtype == np.bool_:
                    return self._apply_newly_mask(newly)
                self._apply_newly_ids(newly)
        finally:
            RECORDER.current_wave = prev_wave

    def _apply_newly_ids(self, newly_ids) -> None:
        if len(newly_ids) == 0:
            return
        if self._block_bases.size:
            # columnar tier: rows of bound tables go stale VECTORIZED —
            # the host cost of a wave over row blocks is O(wave) numpy,
            # not O(wave) Python objects. Scalar twins (if any) still ride
            # the pending/watched tiers below via the shared node id.
            idx = np.searchsorted(self._block_bases, newly_ids, side="right") - 1
            in_block = (idx >= 0) & (newly_ids < self._block_ends[np.maximum(idx, 0)])
            if in_block.any():
                for bi in np.unique(idx[in_block]):
                    blk = self._row_blocks[int(bi)]
                    sel = in_block & (idx == bi)
                    local = newly_ids[sel] - blk.base
                    blk.table._mark_stale_from_wave(local)
                    for h in blk.table.on_wave_invalidate:
                        h(np.asarray(local, dtype=np.int32))
        watched = newly_ids[self._watched[newly_ids]]
        self._pending[newly_ids] = True
        for hook in self.newly_hooks:
            hook(newly_ids)
        self._eager_invalidate(watched)

    def _apply_newly_mask(self, newly: np.ndarray) -> None:
        """Mask twin of the id path: same tiers, all-vectorized."""
        n = len(newly)
        for blk in self._row_blocks:
            if blk.base >= n:
                continue
            sub = newly[blk.base : min(blk.end(), n)]
            if sub.any():
                blk.table._mark_stale_from_wave_mask(sub)
                if blk.table.on_wave_invalidate:
                    local = np.nonzero(sub)[0].astype(np.int32)
                    for h in blk.table.on_wave_invalidate:
                        h(local)
        self._pending[:n] |= newly
        watched = np.nonzero(newly & self._watched[:n])[0]
        for hook in self.newly_hooks:
            hook(newly)
        self._eager_invalidate(watched)

    def _eager_invalidate(self, watched_ids) -> None:
        for node_id in watched_ids:
            node_id = int(node_id)
            self._pending[node_id] = False
            self._watched[node_id] = False
            c = self.computed_for(node_id)
            if c is None:
                continue
            # cause propagation: the sync invalidation handlers this fires
            # (RpcInboundComputeCall._on_computed_invalidated) read the
            # stamp to tag their $sys-c push with the originating wave
            c._invalidation_cause = self.last_cause_id
            self._applying_ids.add(node_id)
            try:
                c.invalidate_local()
            finally:
                self._applying_ids.discard(node_id)

    # ------------------------------------------------------------------ export
    def to_sharded(self, mesh=None, exchange: str = "packed"):
        """Snapshot the LIVE mirrored graph as a mesh-sharded wave graph
        (node epochs, invalid marks, version-carrying edges) — the bridge
        from the incremental single-chip mirror to the multi-chip path
        (parallel/sharded_wave.py). Structure-only snapshot: waves run on
        it must be applied back through the caller (ids are the backend's
        node ids; resolve via ``computed_for``)."""
        from ..parallel.sharded_wave import ShardedDeviceGraph

        self.flush()
        dg = self.graph
        m = dg.n_edges
        return ShardedDeviceGraph(
            dg._h_edge_src[:m].copy(),
            dg._h_edge_dst[:m].copy(),
            dg.n_nodes,
            mesh=mesh,
            edge_dst_epoch=dg._h_edge_dst_epoch[:m].copy(),
            exchange=exchange,
            node_epoch=dg._h_node_epoch,
            # device-authoritative: the host _h_invalid may trail the device
            # lane; invalid_mask() reads the device copy
            invalid=dg.invalid_mask(),
        )

    def sharded_mirror(self, mesh=None, exchange: str = "packed"):
        """Fingerprint-cached :meth:`to_sharded` — the LIVE bridge to the
        multi-chip path. Cached by the full structural state (edges, edge
        epochs, node epochs, n_nodes) using the same struct-version
        shortcut as the topo mirror, so stable-topology calls are O(1);
        ANY bump/append rebuilds on next use. Between mesh bursts the
        single-chip dense state stays authoritative — callers sync invalid
        state through ``invalidate_cascade_batch_sharded``."""
        import hashlib

        from .device_graph import check_structure_cache

        self.flush()
        dg = self.graph
        sv = dg._struct_version

        def fingerprint() -> bytes:
            m = dg.n_edges
            h = hashlib.blake2b(digest_size=16)
            h.update(np.int64(dg.n_nodes).tobytes())
            h.update(dg._h_edge_src[:m].tobytes())
            h.update(dg._h_edge_dst[:m].tobytes())
            h.update(dg._h_edge_dst_epoch[:m].tobytes())
            h.update(dg._h_node_epoch[: dg.n_nodes].tobytes())
            return h.digest()

        cached = self._sharded_mirror
        # the mesh is compared by IDENTITY via a weakref — keying on a bare
        # id(mesh) would alias a new mesh that reuses a collected mesh's id
        # (ADVICE r2), and a strong reference would pin a discarded mesh
        # (plus its derived graph) for the backend's lifetime; a dead ref
        # simply misses and rebuilds
        if cached is not None:
            cached_ref = cached["mesh"]
            same_mesh = (
                cached_ref is None if mesh is None
                else cached_ref is not None and cached_ref() is mesh
            )
            if (
                same_mesh
                and cached["exchange"] == exchange
                and check_structure_cache(cached, sv, fingerprint)
            ):
                return cached["graph"]
        sharded = self.to_sharded(mesh=mesh, exchange=exchange)
        self._sharded_mirror = {
            "fp": fingerprint(),
            "mesh": weakref.ref(mesh) if mesh is not None else None,
            "exchange": exchange,
            "validated_at": sv,
            "graph": sharded,
        }
        return sharded

    def invalidate_cascade_batch_sharded(self, computeds: Sequence["Computed"], mesh=None) -> int:
        """The live multi-chip burst: expand ALL seeds in one union wave on
        the MESH (frontier all-gather over ICI — parallel/sharded_wave.py),
        then apply the newly-invalidated set back to the live hub exactly
        like the single-chip path (dense mirror + two-tier host
        application).

        Per-burst host traffic is O(wave), not O(n) (VERDICT r2 #2): the
        mesh's invalid state stays RESIDENT between bursts — seed ids go
        up, compacted newly ids come back, and the dense mirror catches up
        via ``mark_invalid``. The dense invalid_version tracks whether a
        host-led change (mark_invalid, epoch bump, a single-chip wave)
        touched the invalid state since the last burst; only then does the
        bridge pay a full O(n) re-sync. Validated on the virtual CPU mesh
        (tests + dryrun)."""
        seeds: List[int] = []
        fallback = 0
        for c in computeds:
            nid = self._id_by_input.get(c.input)
            if nid is None:
                c.invalidate(immediately=True)
                fallback += 1
            else:
                seeds.append(nid)
        if not seeds:
            return fallback
        return self._union_sharded_nids(seeds, mesh) + fallback

    def cascade_rows_batch_sharded(self, block: RowBlock, rows, mesh=None) -> int:
        """:meth:`cascade_rows_batch` ON THE MESH: table rows seed a union
        wave expanded over the device mesh (frontier all-gather over ICI),
        applied back to the live hub and tables like the single-chip path."""
        nids = block.base + self._check_rows(block, rows)
        return self._union_sharded_nids(nids.tolist(), mesh)

    def _union_sharded_nids(self, seeds: List[int], mesh=None) -> int:
        sharded = self.sharded_mirror(mesh=mesh)
        entry = self._sharded_mirror
        dg = self.graph
        if entry.get("invalid_version") != dg.invalid_version:
            # host-led change since the last burst (or first burst on this
            # mirror): dense state is authoritative — full sync, once. The
            # host mirror catches up from the same device read, so the
            # overflow mask-diff below never compares against a stale
            # _h_invalid (whatever left it stale also bumped
            # invalid_version → lands here)
            mask = dg.invalid_mask()
            dg._h_invalid[: dg.n_nodes] = mask
            sharded.set_invalid(mask)
        # the mesh state is about to advance; until the dense apply below
        # COMPLETES, the entry must read as out-of-sync — otherwise a
        # failure between the wave and the apply would leave the mesh
        # permanently ahead and a retry of the same seeds would find
        # nothing newly-invalid (a silently dropped cascade)
        entry.pop("invalid_version", None)
        cause, wave_seq = self._begin_wave()
        t0 = time.perf_counter()
        count, newly_ids, overflow = sharded.run_wave_collect(seeds)
        if overflow:
            # wave larger than the collect buffer: one mask-diff readback
            # (1 byte/node) against the still-pre-burst dense host mirror
            newly = sharded.invalid_mask() & ~dg._h_invalid[: sharded.n_nodes]
            newly_ids = np.nonzero(newly)[0].astype(np.int32)
        dg.mark_invalid(newly_ids)  # dense device + host mirror catch up
        entry["invalid_version"] = dg.invalid_version  # in sync again
        t1 = time.perf_counter()
        self._apply_newly(newly_ids)
        self.waves_run += 1
        self.device_invalidations += count
        self._profile_wave("sharded_union", len(seeds), cause, t0, t1, len(newly_ids), wave_seq)
        return count

    def packed_mirror(self, mesh=None) -> dict:
        """Packed mesh mirror of the LIVE edge set — the multi-chip
        lane-burst bridge (PackedShardedGraph over the currently live,
        epoch-matched edges + a device-resident blocked mask mirroring the
        invalid state). Structural churn PATCHES the mesh tables in place
        from the graph's ordered delta stream (VERDICT r4 #4 — the r4
        mirror rebuilt on ANY bump/append): bumps scatter the mesh's
        rebased epochs (the pull kernel has no level order, so no
        violations exist), adds splice into slack slots; only slot
        overflow, unknown nodes, or a broken log rebuild. The blocked mask
        re-syncs from the dense state only after host-led invalid-state
        changes (same invalid_version protocol as the union bridge)."""
        from ..parallel.packed_wave import PackedShardedGraph
        from .device_graph import check_structure_cache

        self.flush()
        dg = self.graph
        sv = dg._struct_version
        cached = self._packed_mirror
        if cached is not None:
            cached_ref = cached["mesh_ref"]
            same_mesh = (
                cached_ref is None if mesh is None
                else cached_ref is not None and cached_ref() is mesh
            )
            if same_mesh:
                if cached["validated_at"] == sv:
                    return cached
                aux = cached["aux_log"]
                if not aux["broken"] and self._try_patch_packed(cached, aux):
                    cached["validated_at"] = sv
                    return cached
                if cached["fp"] is not None and check_structure_cache(
                    cached, sv, lambda: dg._live_edge_fingerprint()[2]
                ):
                    return cached
        if cached is not None:
            dg.drop_aux_delta_log(cached["aux_log"])
        src, dst, fp = dg._live_edge_fingerprint()
        pg = PackedShardedGraph(
            src, dst, dg.n_nodes, mesh=mesh, slack=dg.PATCH_SLACK
        )
        self._packed_mirror = {
            "fp": fp,
            "validated_at": sv,
            "mesh_ref": weakref.ref(mesh) if mesh is not None else None,
            "graph": pg,
            "blocked": pg.put_blocked(),
            # epochs on the mesh are REBASED to 0 at build; deltas carry
            # absolute epochs and translate through this base
            "epoch_base": dg._h_node_epoch[: dg.n_nodes].copy(),
            "aux_log": dg.register_aux_delta_log(),
            # absent invalid_version ⇒ next burst full-syncs from dense
        }
        return self._packed_mirror

    def _try_patch_packed(self, entry: dict, aux: dict) -> bool:
        """Replay the recorded structural deltas onto the mesh mirror —
        the WHOLE stream coalesced into one fused device dispatch
        (``PackedShardedGraph.patch_batch``; ISSUE 9 satellite: the last
        chip record had 1090.7 ms for 6 patches, ~all of it per-patch
        dispatch overhead). The packed mirror's epochs are REBASED to 0 at build,
        so the shared coalescer's absolute epochs translate through the
        build base here. Returns False (and breaks the log) on anything
        the in-place path can't absorb — the caller rebuilds."""
        pg = entry["graph"]
        base = entry["epoch_base"]
        coalesced = self._coalesce_mirror_deltas(aux["deltas"], pg.n_nodes)
        if coalesced is None:
            aux["broken"] = True  # nodes born after the build
            return False
        bumps, u, v, ep = coalesced
        if not len(bumps) and not len(u):
            aux["deltas"] = []
            return True
        # the first in-place mutation invalidates the BUILD fingerprint
        # forever: a later failed replay must never let the fp path
        # revalidate half-patched tables (r5 review)
        entry["fp"] = None
        if not pg.patch_batch(bumps, u, v, ep - base[v]):
            aux["broken"] = True  # slot overflow / unknown nodes
            return False
        aux["deltas"] = []
        global_metrics().counter(
            "fusion_mirror_patch_batches_total",
            help="structural churn bursts applied to the packed mesh mirror in one fused dispatch",
        ).inc()
        return True

    @staticmethod
    def _coalesce_mirror_deltas(deltas, n: int):
        """Collect a recorded structural-delta stream into concatenated
        ``(bumps, u, v, ep_abs)`` for a ONE-dispatch patch batch — the one
        coalescer both mesh-mirror flavors (packed/rebased and
        routed/absolute) replay through. Coalescing is final-state-safe:
        bumps are epoch increments and adds carry captured epochs, so the
        result is order-independent; bump payloads arrive UNIQUIFIED
        (device_graph.bump_epochs dedups before recording), so plain
        concatenation preserves the sequential replay's semantics — once
        per id per payload, accumulating across payloads. Returns None
        when an add references nodes born after the mirror's build (the
        rebuild signal)."""
        bumps: List[np.ndarray] = []
        us: List[np.ndarray] = []
        vs: List[np.ndarray] = []
        eps: List[np.ndarray] = []
        for kind, payload in deltas:
            if kind == "bump":
                ids = np.asarray(payload, dtype=np.int64)
                ids = ids[ids < n]
                if ids.size:
                    bumps.append(ids)
            else:
                u, v, ep = payload
                u64 = np.asarray(u, dtype=np.int64)
                v64 = np.asarray(v, dtype=np.int64)
                if u64.size and (int(u64.max()) >= n or int(v64.max()) >= n):
                    return None
                us.append(u64)
                vs.append(v64)
                eps.append(np.asarray(ep, dtype=np.int64))

        def cat(parts):
            return np.concatenate(parts) if parts else np.empty(0, np.int64)

        return cat(bumps), cat(us), cat(vs), cat(eps)

    def invalidate_cascade_batch_lanes_sharded(
        self, groups: Sequence[Sequence["Computed"]], mesh=None
    ) -> np.ndarray:
        """Lane-packed live burst ON THE MESH: each command group cascades
        independently in its own bit lane over the device mesh (packed
        frontier words ride one all-gather per level —
        parallel/packed_wave.py), gated by the live graph's invalid state,
        with the union applied back to the hub exactly like the
        single-chip lane path. The blocked mask stays device-resident
        between bursts (invalid_version protocol, exception-safe: the
        entry reads out-of-sync until the dense apply completes).
        Returns per-group newly counts (missing computeds fall back to
        immediate host invalidation, counting 1)."""
        seed_lists: List[List[int]] = []
        fallback = np.zeros(len(groups), dtype=np.int64)
        for gi, group in enumerate(groups):
            ids: List[int] = []
            for c in group:
                nid = self._id_by_input.get(c.input)
                if nid is None:
                    c.invalidate(immediately=True)
                    fallback[gi] += 1
                else:
                    ids.append(nid)
            seed_lists.append(ids)
        return self._lanes_sharded_nids(seed_lists, mesh) + fallback

    def cascade_rows_lanes_sharded(self, block: RowBlock, row_groups, mesh=None) -> np.ndarray:
        """:meth:`cascade_rows_lanes` ON THE MESH: each row group cascades
        independently in its own bit lane over the device mesh (packed
        frontier words, one all-gather per level), union applied back to
        the hub and tables like the single-chip path."""
        seed_lists = [
            (block.base + self._check_rows(block, g)).tolist() for g in row_groups
        ]
        return self._lanes_sharded_nids(seed_lists, mesh)

    def _lanes_sharded_nids(self, seed_lists: List[List[int]], mesh=None) -> np.ndarray:
        entry = self.packed_mirror(mesh=mesh)
        pg = entry["graph"]
        dg = self.graph
        if entry.get("invalid_version") != dg.invalid_version:
            mask = dg.invalid_mask()
            dg._h_invalid[: dg.n_nodes] = mask
            entry["blocked"] = pg.put_blocked(mask)
        entry.pop("invalid_version", None)  # out-of-sync until apply completes
        cause, wave_seq = self._begin_wave()
        t0 = time.perf_counter()
        counts, union_ids, blocked2, overflow = pg.run_gated_lanes(
            seed_lists, entry["blocked"]
        )
        entry["blocked"] = blocked2
        if overflow:
            newly = np.asarray(blocked2)[: dg.n_nodes] & ~dg._h_invalid[: dg.n_nodes]
            union_ids = np.nonzero(newly)[0].astype(np.int32)
        dg.mark_invalid(union_ids)
        entry["invalid_version"] = dg.invalid_version
        t1 = time.perf_counter()
        self._apply_newly(union_ids)
        self.waves_run += len(seed_lists)
        self.device_invalidations += int(counts.sum())
        self._profile_wave(
            "sharded_lanes", sum(len(s) for s in seed_lists), cause, t0, t1,
            int(counts.sum()), wave_seq, groups=len(seed_lists),
        )
        return counts

    # ------------------------------------------------------------------ routed mesh
    def enable_mesh_routing(
        self,
        shard_map,
        mesh=None,
        mesh_members=None,
        exchange: str = "a2a",
        devices_per_host: Optional[int] = None,
        exchange_async: bool = False,
        async_depth: int = 4,
    ) -> None:
        """Pin the live graph's CSR shards onto mesh devices per the
        CLUSTER shard map (ISSUE 9 tentpole): each member's shard-map
        assignment also places its slice of the mirror on its mesh
        devices, and cross-shard invalidation frontiers thereafter resolve
        via collectives inside the wave (``_union_routed_nids`` /
        the WavePipeline's routed chain) instead of surfacing to the host
        and re-entering through per-key RPC. ``mesh_members`` names the
        members co-located on THIS mesh (default: all map members — the
        single-host cluster); shards owned by off-mesh members stay on the
        DCN relay path (rpc/fanout.py counts it). ``devices_per_host``
        declares the placement's host axis (ISSUE 15) — with
        ``exchange="hier"`` each BFS level then resolves as an intra-host
        collective plus an inter-host exchange of the reduced per-host
        frontier words, inside the same fused chain the super-rounds ride.
        ``exchange_async=True`` (ISSUE 17) runs the routed waves in
        asynchronous mode: each shard expands its LOCAL frontier
        speculatively for up to ``async_depth`` levels between global
        merge epochs, and the level fence becomes a counted quiescence
        vote — the phase-end invalid mask stays bit-identical to sync by
        the idempotent-OR argument (tier1-gated). The mirror itself
        builds lazily on first routed wave."""
        self._routed_config = {
            "shard_map": shard_map,
            "mesh": mesh,
            "mesh_members": tuple(mesh_members) if mesh_members is not None else None,
            "exchange": exchange,
            "devices_per_host": devices_per_host,
            "exchange_async": exchange_async,
            "async_depth": async_depth,
        }
        self._routed_mirror = None  # rebuild under the new config

    def mesh_routing_active(self) -> bool:
        return self._routed_config is not None

    def routed_mirror(self) -> dict:
        """Fingerprint-cached routed mesh mirror of the live graph.
        Structural churn since the last wave PATCHES the resident shards in
        place from the graph's ordered delta stream — the whole batch
        coalesced into ONE fused device dispatch (ISSUE 9 satellite: the
        per-patch dispatch overhead, not the per-edge cost, dominated
        mirror_patch_ms). Anything the in-place path can't
        absorb (new nodes, slot/bucket overflow) rebuilds, counted."""
        from ..cluster.placement import DevicePlacement, PlacementError
        from ..parallel.routed_wave import RoutedShardedGraph
        from .device_graph import check_structure_cache

        cfg = self._routed_config
        if cfg is None:
            raise RuntimeError("mesh routing not enabled (enable_mesh_routing)")
        self.flush()
        dg = self.graph
        sv = dg._struct_version
        cached = self._routed_mirror
        if cached is not None:
            if cached["validated_at"] == sv:
                return cached
            aux = cached["aux_log"]
            if not aux["broken"] and self._try_patch_routed(cached, aux):
                cached["validated_at"] = sv
                return cached
            if cached["fp"] is not None and check_structure_cache(
                cached, sv, lambda: self._routed_fingerprint()
            ):
                return cached
        if cached is not None:
            dg.drop_aux_delta_log(cached["aux_log"])
            global_metrics().counter(
                "fusion_mesh_rebuilds_total",
                help="routed mesh mirrors rebuilt (patch path could not absorb the churn)",
            ).inc()
        mesh = cfg["mesh"]
        import jax as _jax

        n_dev = mesh.devices.size if mesh is not None else len(_jax.devices())
        smap = cfg["shard_map"]
        members = cfg["mesh_members"] or smap.members
        placement = DevicePlacement.build(
            smap, n_dev, dg.n_nodes, mesh_members=members,
            devices_per_host=cfg.get("devices_per_host"),
        )
        m = dg.n_edges
        graph = RoutedShardedGraph(
            dg._h_edge_src[:m].copy(),
            dg._h_edge_dst[:m].copy(),
            dg.n_nodes,
            placement,
            mesh=mesh,
            exchange=cfg["exchange"],
            edge_dst_epoch=dg._h_edge_dst_epoch[:m].copy(),
            node_epoch=dg._h_node_epoch[: dg.n_nodes],
            exchange_async=cfg.get("exchange_async", False),
            async_depth=cfg.get("async_depth", 4),
        )
        self._routed_mirror = {
            "fp": self._routed_fingerprint(),
            "validated_at": sv,
            "graph": graph,
            "aux_log": dg.register_aux_delta_log(),
            # absent invalid_version ⇒ next wave full-syncs from dense
        }
        return self._routed_mirror

    def _routed_fingerprint(self) -> bytes:
        import hashlib

        dg = self.graph
        m = dg.n_edges
        h = hashlib.blake2b(digest_size=16)
        h.update(np.int64(dg.n_nodes).tobytes())
        h.update(dg._h_edge_src[:m].tobytes())
        h.update(dg._h_edge_dst[:m].tobytes())
        h.update(dg._h_edge_dst_epoch[:m].tobytes())
        h.update(dg._h_node_epoch[: dg.n_nodes].tobytes())
        return h.digest()

    def _try_patch_routed(self, entry: dict, aux: dict) -> bool:
        """Coalesce the WHOLE recorded delta stream into one batched patch
        (bumps are epoch increments and adds carry absolute captured
        epochs, so the final device state is order-independent — the
        property that makes same-burst batching safe) and apply it in ONE
        fused dispatch. False ⇒ rebuild."""
        graph = entry["graph"]
        coalesced = self._coalesce_mirror_deltas(aux["deltas"], graph.n_nodes)
        if coalesced is None:
            aux["broken"] = True  # nodes born after the build
            return False
        bumps, u, v, ep = coalesced
        if not len(bumps) and not len(u):
            aux["deltas"] = []
            return True
        entry["fp"] = None  # in-place mutation: the build fp never revalidates
        # the routed mirror keeps ABSOLUTE epochs — no rebase translation
        if not graph.patch_batch(bumps, u, v, ep.astype(np.int32)):
            aux["broken"] = True
            return False
        aux["deltas"] = []
        global_metrics().counter(
            "fusion_mesh_patch_batches_total",
            help="structural churn batches applied to the routed mesh mirror in one fused dispatch",
        ).inc()
        return True

    def apply_mesh_reshard(self, new_map, mesh_members=None) -> int:
        """MOVE the resident device shards the new epoch reassigns (the
        rebalancer's device half): state blocks transfer on-device, edge
        slices + exchange buckets re-pack for the touched devices only.
        Returns the number of shard moves (0 when no mirror is live yet —
        the next build derives placement from the new map directly).
        A move the placement can't absorb drops the mirror (rebuild on
        next use) — counted, never silent."""
        from ..cluster.placement import PlacementError

        cfg = self._routed_config
        if cfg is None:
            return 0
        cfg["shard_map"] = new_map
        if mesh_members is not None:
            cfg["mesh_members"] = tuple(mesh_members)
        entry = self._routed_mirror
        if entry is None:
            return 0
        if entry.get("inflight", 0):
            # a fused chain mid-flight references the CURRENT row layout;
            # moving shards under it would make its harvest map rows
            # through the new permutation (dropped invalidations). Drain
            # first — the reshard then applies to a quiesced mirror.
            self._drain_nonblocking()
            entry = self._routed_mirror
            if entry is None:
                return 0
        graph = entry["graph"]
        members = cfg["mesh_members"] or new_map.members
        try:
            placement, moves = graph.placement.moved_to(new_map, mesh_members=members)
            graph.apply_placement(placement, moves)
        except PlacementError as e:
            log.warning("mesh reshard forced a rebuild: %s", e)
            self.graph.drop_aux_delta_log(entry["aux_log"])
            self._routed_mirror = None
            global_metrics().counter("fusion_mesh_rebuilds_total").inc()
            return 0
        global_metrics().counter(
            "fusion_mesh_shard_moves_total",
            help="device shards moved between mesh devices by reshards",
        ).inc(len(moves))
        global_metrics().counter("fusion_mesh_reshards_total").inc()
        if RECORDER.enabled:
            RECORDER.note(
                "mesh_reshard",
                key=None,
                cause=f"reshard:{new_map.epoch}",
                count=len(moves),
                detail=(
                    f"epoch {new_map.epoch}: moved {len(moves)} device "
                    f"shard(s) on-mesh (placement epoch {placement.epoch})"
                ),
            )
        return len(moves)

    def invalidate_cascade_batch_routed(self, computeds: Sequence["Computed"]) -> int:
        """The live routed burst: one union wave whose cross-shard frontier
        resolves via mesh collectives (a2a buckets / reduction tree —
        parallel/routed_wave.py), applied back to the hub exactly like the
        single-chip path. Missing computeds fall back to immediate host
        invalidation, counted."""
        seeds: List[int] = []
        fallback = 0
        for c in computeds:
            nid = self._id_by_input.get(c.input)
            if nid is None:
                c.invalidate(immediately=True)
                fallback += 1
            else:
                seeds.append(nid)
        if not seeds:
            return fallback
        return self._union_routed_nids(seeds) + fallback

    def cascade_rows_batch_routed(self, block: RowBlock, rows) -> int:
        nids = block.base + self._check_rows(block, rows)
        return self._union_routed_nids(nids.tolist())

    def _routed_sync(self, entry: dict) -> None:
        dg = self.graph
        if entry.get("invalid_version") != dg.invalid_version:
            mask = dg.invalid_mask()
            dg._h_invalid[: dg.n_nodes] = mask
            entry["graph"].set_invalid(mask)
        # out-of-sync until the dense apply completes (same failure
        # containment as the sharded union bridge)
        entry.pop("invalid_version", None)

    def _drain_nonblocking(self) -> None:
        """Harvest every in-flight nonblocking plane — the WavePipeline's
        fused chains AND the SuperRoundProgram's resident super-rounds —
        so blocking paths (reshards, routed unions) act on a quiesced
        device state."""
        if self.pipeline is not None:
            self.pipeline.drain()  # also drains super_rounds
        elif self.super_rounds is not None and not self.super_rounds._disposed:
            self.super_rounds.drain()

    def _union_routed_nids(self, seeds: List[int]) -> int:
        entry = self.routed_mirror()
        if entry.get("inflight", 0):
            # a fused chain is mid-flight: its device advance must land
            # before a blocking union syncs from the dense mirror (drain
            # is the nonblocking-mode barrier — same rule as flush)
            self._drain_nonblocking()
            entry = self.routed_mirror()
        graph = entry["graph"]
        dg = self.graph
        with hot_span("routed.sync"):
            self._routed_sync(entry)
        cause, wave_seq = self._begin_wave()
        t0 = time.perf_counter()
        levels0 = graph.levels_total
        count, newly, overflow = graph.run_wave_collect(seeds)
        if overflow:
            # the closure outgrew the compacted id buffers: the whole mask
            # comes back instead (40 MB at 40 M nodes), counted, and stays
            # a mask to the last hook: no id array of its length is built
            global_metrics().counter(
                "fusion_mesh_routed_overflows_total",
                help="routed union waves whose closure overflowed the "
                "compacted newly-id buffers and read the whole mask back",
            ).inc()
            with hot_span("routed.mask_fetch"):
                newly = graph.invalid_mask() & ~dg._h_invalid[: graph.n_nodes]
        with hot_span("routed.mark"):
            if overflow:
                dg.mark_invalid_mask(newly)
            else:
                dg.mark_invalid(newly)
        entry["invalid_version"] = dg.invalid_version
        t1 = time.perf_counter()
        levels = graph.levels_total - levels0
        self._apply_newly(newly)
        self.waves_run += 1
        self.device_invalidations += count
        global_metrics().counter(
            "fusion_mesh_routed_waves_total",
            help="union waves whose cross-shard frontier resolved via mesh collectives",
        ).inc()
        global_metrics().counter(
            "fusion_mesh_exchange_levels_total",
            help="collective frontier-exchange rounds run on the mesh",
        ).inc(levels)
        self._profile_wave(
            "routed_union", len(seeds), cause, t0, t1,
            int(np.count_nonzero(newly)) if overflow else len(newly), wave_seq,
            mesh={
                "exchange": graph.exchange,
                "levels": int(levels),
                "epoch": graph.placement.epoch,
                "n_dev": graph.n_dev,
            },
        )
        return count

    def dispatch_waves_routed_chain(
        self, stage_seed_lists: Sequence[Sequence[int]], staged: Optional[dict] = None
    ) -> dict:
        """K logical waves in ONE routed lax.scan dispatch with NO readback
        — the frontier exchange composed into the nonblocking loop-carried
        chain (graph/nonblocking.py rides this when mesh routing is on).
        Raises RuntimeError for contract violations the pipeline treats as
        the eager fallback (out-of-range seeds).

        With a chain already IN FLIGHT the device state is AHEAD of the
        dense mirror by exactly that chain's work — the dense full-sync
        must be SKIPPED (it would overwrite the in-flight advance with
        pre-chain state and double-count its cascade at harvest); the
        loop-carried device state is the consistent one. Host-led invalid
        changes between overlapped dispatches are covered by the
        pipeline's journal guard + ``drain()`` barrier, same contract as
        the single-chip lanes chain."""
        if any(len(s) == 0 for s in stage_seed_lists):
            raise RuntimeError("routed chain stages need non-empty seed sets")
        entry = self.routed_mirror()
        graph = entry["graph"]
        if entry.get("inflight", 0) == 0:
            self._routed_sync(entry)
        levels0 = graph.levels_total
        # a pre-packed seed buffer (SuperRoundProgram's back buffer) skips
        # the host pack; dispatch_union_chain rejects a stale token
        pending = graph.dispatch_union_chain(stage_seed_lists, staged=staged)
        entry["inflight"] = entry.get("inflight", 0) + 1  # after dispatch succeeds
        pending["entry"] = entry
        pending["levels0"] = levels0
        return pending

    def harvest_waves_routed_chain(self, pending: dict):
        """Block on a routed chain ticket: (per-stage counts, per-stage
        newly id arrays). An overflowed stage's ids are recovered from one
        mask diff against the pre-chain dense mirror and attributed to the
        FIRST overflowed stage — containment preserves the SET (the counts
        stay device-exact); invalidation is idempotent."""
        entry = pending["entry"]
        graph = entry["graph"]
        dg = self.graph
        try:
            counts, stage_ids, info = graph.harvest_union_chain(pending)
        except Exception:
            # a failed harvest leaves the device state unknowable: clear
            # the in-flight accounting and stay out-of-sync so the next
            # wave full-syncs from the dense truth (the pipeline's fault
            # containment re-runs the waves on the split host loop)
            entry["inflight"] = 0
            entry.pop("invalid_version", None)
            raise
        if info["overflowed"]:
            newly = graph.invalid_mask() & ~dg._h_invalid[: graph.n_nodes]
            all_ids = np.nonzero(newly)[0].astype(np.int64)
            attributed = [i for i in stage_ids if i is not None]
            seen = (
                np.concatenate(attributed) if attributed else np.empty(0, np.int64)
            )
            leftover = np.setdiff1d(all_ids, seen)
            first = True
            for i, ids in enumerate(stage_ids):
                if ids is None:
                    stage_ids[i] = leftover if first else np.empty(0, np.int64)
                    first = False
        union = (
            np.concatenate(stage_ids) if stage_ids else np.empty(0, np.int64)
        )
        dg.mark_invalid(union)
        entry["inflight"] = max(entry.get("inflight", 1) - 1, 0)
        if entry["inflight"] == 0:
            # only a FULLY-drained mirror reads in-sync: with another chain
            # still executing, the device state is ahead of the dense
            # mirror until that chain harvests too
            entry["invalid_version"] = dg.invalid_version
        levels = graph.levels_total - pending["levels0"]
        global_metrics().counter("fusion_mesh_routed_waves_total").inc(len(stage_ids))
        global_metrics().counter("fusion_mesh_exchange_levels_total").inc(levels)
        return counts, stage_ids

    def computed_for(self, node_id: int):
        """The live Computed for a backend node id (None if collected)."""
        ref = self._computed_by_id.get(int(node_id))
        return ref() if ref is not None else None

    def id_for(self, computed: "Computed") -> Optional[int]:
        """The backend node id for a live Computed (None if unmirrored) —
        the seed-id side of the ``to_sharded`` bridge."""
        with self._lock:
            return self._id_by_input.get(computed.input)

    # ------------------------------------------------------------------ stats
    @property
    def node_count(self) -> int:
        return self.graph.n_nodes

    @property
    def edge_count(self) -> int:
        return self.graph.n_edges
