"""Device-resident super-round tests (ISSUE 14 tentpole).

The super-round(depth K) ≡ sequential-rounds ORACLE suite: for each depth
the resident program's result must be identical to K sequential
(lane burst → device refresh) pairs — invalid masks, memo value columns,
fence sets (the ``newly_hooks`` drain the fan-out rides), per-group newly
counts, and per-logical-wave seq identity — plus double-buffered staging
across an in-flight super-round, the journal-guard forced harvest, a
mirror re-level between stage and dispatch (counted re-stage, never a
stale-id dispatch), mid-super-round fault injection
(``inject_fault_next``) falling back to the COUNTED eager path with the
block's memo values still truth, ``drain()`` barrier semantics (including
through ``WavePipeline.drain``), metric export, and a routed-mesh
super-round asserting the rounds rode the collective chain with zero
host-relay re-entries.
"""
import asyncio

import numpy as np
import pytest

from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    compute_method,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.diagnostics import global_metrics
from stl_fusion_tpu.graph import TpuGraphBackend, WavePipeline
from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.resilience import WaveWatchdog

N = 800
SRC, DST = power_law_dag(N, avg_degree=3, seed=7)


class Dag(ComputeService):
    """Table-backed service with a DEVICE loader — the super-round's
    in-program refresh recomputes through it."""

    def __init__(self, hub=None):
        super().__init__(hub)
        self.base = np.arange(N, dtype=np.float32)
        self._base_dev = None

    def load(self, ids):
        return self.base[np.asarray(ids, dtype=np.int64)]

    def load_dev(self, ids, base_dev):
        return base_dev[ids]

    def load_dev_args(self):
        if self._base_dev is None:
            import jax.numpy as jnp

            self._base_dev = jnp.asarray(self.base)
        return (self._base_dev,)

    @compute_method(
        table=TableBacking(
            rows=N, batch="load",
            device_batch="load_dev", device_args="load_dev_args",
        )
    )
    async def node(self, i: int) -> float:
        return float(self.base[i])


def make_stack():
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=N + 8, edge_capacity=len(SRC) + 512)
    svc = Dag(hub)
    hub.add_service(svc, "dag")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    backend.declare_row_edges(block, SRC, block, DST)
    backend.warm_block_on_device(block)
    backend.flush()
    backend.graph.build_topo_mirror()
    return hub, backend, svc, table, block


def round_bursts(k, groups=4, seeds=3, rng=None):
    rng = rng if rng is not None else np.random.default_rng(20260804)
    return [
        [rng.choice(N, size=seeds, replace=False).tolist() for _ in range(groups)]
        for _ in range(k)
    ]


def fence_collector(backend):
    """Record every wave application's (seq, newly-set) — the stream the
    RPC fan-out index drains from the same hook."""
    seen = []

    def hook(newly):
        if isinstance(newly, np.ndarray) and newly.dtype == np.bool_:
            ids = frozenset(np.nonzero(newly)[0].tolist())
        else:
            ids = frozenset(int(i) for i in newly)
        if ids:
            seen.append((backend.last_wave_seq, ids))

    backend.newly_hooks.append(hook)
    return seen


# ---------------------------------------------------------------- oracle


@pytest.mark.parametrize("k", [1, 2, 4])
async def test_superround_matches_sequential_rounds(k):
    """super-round(depth K) ≡ K sequential (burst → refresh) pairs:
    invalid masks, memo columns, fence sets, per-group counts, and each
    round keeps its own wave seq (contiguous span, fences stamped)."""
    bursts = round_bursts(k)

    hub_a, b_a, _s, table_a, blk_a = make_stack()
    old = set_default_hub(hub_a)
    try:
        fences_a = fence_collector(b_a)
        prog = b_a.enable_super_rounds(blk_a, depth=k)
        ticket = prog.dispatch(prog.stage(bursts))
        per_burst = ticket.harvest()
        assert prog.superrounds_dispatched == 1
        assert prog.eager_rounds == 0 and prog.faults == 0

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        fences_b = fence_collector(b_b)
        seq_counts = []
        for groups in bursts:
            seq_counts.append(b_b.cascade_rows_lanes(blk_b, groups))
            b_b.refresh_block_on_device(blk_b)

        for i in range(k):
            assert per_burst[i].tolist() == seq_counts[i].tolist(), i
        assert np.array_equal(
            b_a.graph.invalid_mask(), b_b.graph.invalid_mask()
        )
        assert np.array_equal(
            np.asarray(table_a._values), np.asarray(table_b._values)
        )
        assert table_a.stale_count() == table_b.stale_count()
        # fence sets identical round for round, each under its OWN seq
        assert [ids for _seq, ids in fences_a] == [ids for _seq, ids in fences_b]
        seqs_a = [seq for seq, _ids in fences_a]
        assert seqs_a == sorted(seqs_a)
        nonempty = sum(1 for c in per_burst if int(c.sum()))
        assert len(set(seqs_a)) == nonempty  # one seq per fencing round
        # the profiler record carries the fused identity for explain()
        rec = [r for r in b_a.profiler._ring if r["kind"] == "superround"][-1]
        assert rec["fused_depth"] == k and rec["dispatches"] == 1
        assert rec["seq_span"][1] - rec["seq_span"][0] == k - 1
    finally:
        set_default_hub(old)


async def test_double_buffered_staging_overlaps_inflight_superround():
    """stage() for super-round N+1 runs while N is in flight (back
    buffer); dispatch(N+1) harvests N — state identical to the sequential
    twin across both super-rounds."""
    r1 = round_bursts(2, rng=np.random.default_rng(1))
    r2 = round_bursts(2, rng=np.random.default_rng(2))

    hub_a, b_a, _s, table_a, blk_a = make_stack()
    old = set_default_hub(hub_a)
    try:
        prog = b_a.enable_super_rounds(blk_a, depth=2)
        t1 = prog.dispatch(prog.stage(r1))
        assert len(prog._inflight) == 1 and not t1.done
        staged2 = prog.stage(r2)  # packed with t1 still in flight
        t2 = prog.dispatch(staged2)  # harvests t1 (MAX_INFLIGHT=1)
        assert t1.done and not t2.done
        prog.drain()
        assert t2.done and prog.harvests == 2
        assert prog.occupancy() >= 0.0 and prog.stats()["wall_s"] > 0
        # the chain ticket under a super-round is consumed by its harvest
        assert t2.inner.done and t2.inner.cleared_total > 0
        with pytest.raises(RuntimeError):
            t2.inner.harvest()

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        want = []
        for groups in r1 + r2:
            want.append(b_b.cascade_rows_lanes(blk_b, groups))
            b_b.refresh_block_on_device(blk_b)
        got = [c for t in (t1, t2) for c in t.per_burst]
        assert [c.tolist() for c in got] == [c.tolist() for c in want]
        assert np.array_equal(
            np.asarray(table_a._values), np.asarray(table_b._values)
        )
    finally:
        set_default_hub(old)


async def test_journal_entry_with_inflight_superround_forces_harvest():
    """A journal entry between dispatches forces the in-flight harvest
    BEFORE flush (the WavePipeline hazard guard) — counted, and the
    host-led invalidation still lands correctly."""
    hub, b, svc, table, blk = make_stack()
    old = set_default_hub(hub)
    try:
        prog = b.enable_super_rounds(blk, depth=2)
        t1 = prog.dispatch(prog.stage(round_bursts(2)))
        table.invalidate([int(N - 1)])  # journals an icasc while in flight
        t2 = prog.dispatch(prog.stage(round_bursts(2, rng=np.random.default_rng(9))))
        assert prog.journal_forced_harvests == 1
        assert t1.done  # the guard harvested it before flush
        prog.drain()
        assert t2.done
        # the host-led invalidation cascaded at flush and the second
        # super-round's in-program refresh re-consistented the block —
        # nothing left stale, values truth
        assert not b.graph._h_invalid[blk.base : blk.end()].any()
        assert table.stale_count() == 0
        assert float(np.asarray(table._values)[N - 1]) == float(N - 1)
    finally:
        set_default_hub(old)


async def test_relevel_between_stage_and_dispatch_restages():
    """A mirror rebuild after stage() re-permutes NEW ids — dispatch must
    re-pack the buffer (counted), never dispatch the stale ids."""
    hub, b, svc, table, blk = make_stack()
    old = set_default_hub(hub)
    try:
        prog = b.enable_super_rounds(blk, depth=1)
        bursts = round_bursts(1)
        staged = prog.stage(bursts)
        b.graph.build_topo_mirror(force=True)  # re-level: new inv_perm
        ticket = prog.dispatch(staged)
        per_burst = ticket.harvest()
        assert prog.restages == 1 and prog.eager_rounds == 0

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        want = b_b.cascade_rows_lanes(blk_b, bursts[0])
        assert per_burst[0].tolist() == want.tolist()
    finally:
        set_default_hub(old)


async def test_rebuild_inside_dispatch_restages():
    """Churn the patcher cannot absorb (one row's in-degree overflowing its
    mirror slots) rebuilds the mirror while dispatch() brings it up to
    date: a buffer staged BEFORE that must be re-packed (counted), not
    enqueued in the old id order as seeds at the wrong nodes."""
    hub, b, svc, table, blk = make_stack()
    old = set_default_hub(hub)
    try:
        prog = b.enable_super_rounds(blk, depth=1)
        bursts = round_bursts(1)
        staged = prog.stage(bursts)
        srcs, dsts = np.arange(16), np.full(16, N - 1)
        b.declare_row_edges(blk, srcs, blk, dsts)
        rebuilds0 = b.graph.mirror_rebuilds
        per_burst = prog.dispatch(staged).harvest()
        assert b.graph.mirror_rebuilds == rebuilds0 + 1
        assert prog.restages == 1 and prog.eager_rounds == 0

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        b_b.declare_row_edges(blk_b, srcs, blk_b, dsts)
        want = b_b.cascade_rows_lanes(blk_b, bursts[0])
        assert per_burst[0].tolist() == want.tolist()
    finally:
        set_default_hub(old)


# ---------------------------------------------------------------- faults


async def test_mid_superround_fault_falls_back_to_counted_eager_path():
    """``inject_fault_next`` at dispatch: the fault is contained — the
    block conservatively re-stales + refreshes (values stay truth), the
    rounds re-run on the COUNTED eager path under the pre-minted seqs,
    and the final state matches the sequential twin."""
    bursts = round_bursts(3, rng=np.random.default_rng(5))

    hub_a, b_a, _s, table_a, blk_a = make_stack()
    old = set_default_hub(hub_a)
    try:
        wd = b_a.attach_watchdog(WaveWatchdog(recovery_bursts=1))
        prog = b_a.enable_super_rounds(blk_a, depth=3)
        wd.inject_fault_next()
        ticket = prog.dispatch(prog.stage(bursts))
        assert ticket.done and ticket.fallback
        assert prog.faults == 1 and prog.eager_rounds == 3
        assert wd.faults == 1

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        for groups in bursts:
            b_b.cascade_rows_lanes(blk_b, groups)
            b_b.refresh_block_on_device(blk_b)
        # containment preserves the SET and the VALUES (the counts of the
        # eager re-run reflect its own execution order)
        assert np.array_equal(
            b_a.graph.invalid_mask(), b_b.graph.invalid_mask()
        )
        assert np.array_equal(
            np.asarray(table_a._values), np.asarray(table_b._values)
        )
        assert table_a.stale_count() == table_b.stale_count()
    finally:
        set_default_hub(old)


async def test_harvest_fault_contained_and_values_stay_truth(monkeypatch):
    """A fault in the readback half: the half-run chain's device refresh
    cleared block bits but its values were never committed — containment
    must re-stale + refresh so no row reads consistent-with-stale."""
    bursts = round_bursts(2, rng=np.random.default_rng(6))
    hub, b, svc, table, blk = make_stack()
    old = set_default_hub(hub)
    try:
        prog = b.enable_super_rounds(blk, depth=2)
        import jax

        real = jax.device_get
        state = {"arm": False}

        def flaky(x):
            if state.pop("arm", None):
                raise RuntimeError("injected harvest fault")
            return real(x)

        ticket = prog.dispatch(prog.stage(bursts))
        state["arm"] = True
        monkeypatch.setattr(jax, "device_get", flaky)
        per_burst = ticket.harvest()  # contained, never raises
        monkeypatch.setattr(jax, "device_get", real)
        assert ticket.fallback and prog.faults == 1

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        for groups in bursts:
            b_b.cascade_rows_lanes(blk_b, groups)
            b_b.refresh_block_on_device(blk_b)
        assert np.array_equal(
            np.asarray(table._values), np.asarray(table_b._values)
        )
        assert np.array_equal(b.graph.invalid_mask(), b_b.graph.invalid_mask())
        assert len(per_burst) == 2
    finally:
        set_default_hub(old)


# ---------------------------------------------------------------- barrier


async def test_drain_barrier_and_pipeline_drain_cover_superrounds():
    """drain() resolves everything in flight; WavePipeline.drain() — the
    nonblocking-mode barrier — covers the super-round plane too."""
    hub, b, svc, table, blk = make_stack()
    old = set_default_hub(hub)
    try:
        prog = b.enable_super_rounds(blk, depth=2)
        t = prog.dispatch(prog.stage(round_bursts(2)))
        assert not t.done
        assert prog.drain() == 1 and t.done and len(prog._inflight) == 0

        pipe = WavePipeline(b, fuse_depth=4)
        t2 = prog.dispatch(prog.stage(round_bursts(2, rng=np.random.default_rng(3))))
        assert not t2.done
        pipe.drain()  # the one barrier covers both planes
        assert t2.done and len(prog._inflight) == 0
        pipe.dispose()
    finally:
        set_default_hub(old)


async def test_superround_metrics_exported():
    import gc

    gc.collect()  # drop other tests' weak-registered collectors
    hub, b, svc, table, blk = make_stack()
    old = set_default_hub(hub)
    try:
        prog = b.enable_super_rounds(blk, depth=2)
        before = dict(global_metrics()._collect())
        prog.dispatch(prog.stage(round_bursts(2)))
        prog.drain()
        collected = global_metrics()._collect()

        def delta(name):
            return collected.get(name, 0) - before.get(name, 0)

        assert delta("fusion_superround_dispatches_total") == 1
        assert delta("fusion_superround_rounds_total") == 2
        assert delta("fusion_superround_eager_rounds_total") == 0
        assert delta("fusion_superround_faults_total") == 0
        assert "fusion_superround_occupancy" in collected
        assert "fusion_superround_host_stall_ms" in collected
        prog.dispose()
        assert b.super_rounds is None
    finally:
        set_default_hub(old)


# ---------------------------------------------------------------- routed mesh


async def test_routed_superround_zero_host_relay_reentries():
    """Mesh mode: the super-round rides the routed union chain — K rounds
    in ONE collective scan dispatch, per-super-round refresh at harvest,
    oracle-identical to the single-chip twin, and ZERO rounds re-entering
    through the host relay (no eager fallback, one dispatch)."""
    from stl_fusion_tpu.cluster import ShardMap
    from stl_fusion_tpu.parallel import graph_mesh

    bursts = round_bursts(2, groups=2, rng=np.random.default_rng(11))

    hub_a, b_a, _s, table_a, blk_a = make_stack()
    old = set_default_hub(hub_a)
    try:
        smap = ShardMap.initial(["m0", "m1"], n_shards=32)
        b_a.enable_mesh_routing(smap, mesh=graph_mesh())
        prog = b_a.enable_super_rounds(blk_a, depth=2)
        ticket = prog.dispatch(prog.stage(bursts))
        prog.drain()
        got = [int(c.sum()) for c in ticket.per_burst]
        assert prog.superrounds_dispatched == 1
        assert prog.eager_rounds == 0 and prog.faults == 0
        routed_graph = b_a._routed_mirror["graph"]
        # every round resolved INSIDE the routed chain (waves_run counts
        # chain stages) — none re-entered via the dense host path
        assert routed_graph.waves_run >= 2
        assert ticket.routed_pending["dispatches"] == 1

        # single-chip twin: one union wave per round, refresh at the end
        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        want = []
        for groups in bursts:
            seeds = sorted({x for g in groups for x in g})
            want.append(b_b.cascade_rows_batch(blk_b, seeds))
        b_b.refresh_block_on_device(blk_b)
        assert got == want
        assert np.array_equal(
            b_a.graph.invalid_mask(), b_b.graph.invalid_mask()
        )
        assert np.array_equal(
            np.asarray(table_a._values), np.asarray(table_b._values)
        )
        assert table_a.stale_count() == 0
    finally:
        set_default_hub(old)


# ------------------------------------------------------- lane-dense sweep state
def wide_bursts(k, groups=260, seeds=2):
    """Rounds of more than 256 groups: 16 words a node, 8 nodes a state row."""
    rng = np.random.default_rng(20261001)
    return [
        [rng.choice(N, size=seeds, replace=False).tolist() for _ in range(groups)]
        for _ in range(k)
    ]


async def test_depth3_superround_at_16_words_matches_sequential_pairs():
    """A depth-3 super-round at ``words=16`` (the benchmark's geometry: the
    sweep state packs 8 nodes into each 128-lane row) ≡ three sequential
    (lane burst → refresh) pairs: per-group counts, invalid mask, memo
    columns, fence sets."""
    bursts = wide_bursts(3)

    hub_a, b_a, _s, table_a, blk_a = make_stack()
    old = set_default_hub(hub_a)
    try:
        fences_a = fence_collector(b_a)
        prog = b_a.enable_super_rounds(blk_a, depth=3, max_words=16)
        staged = prog.stage(bursts)
        assert staged.words == 16
        per_burst = prog.dispatch(staged).harvest()
        assert prog.superrounds_dispatched == 1
        assert prog.eager_rounds == 0 and prog.faults == 0
        assert b_a.graph.sweep_packed_dispatches == 1

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        fences_b = fence_collector(b_b)
        for i, groups in enumerate(bursts):
            counts = b_b.cascade_rows_lanes(blk_b, groups)
            assert per_burst[i].tolist() == counts.tolist(), i
            assert int(counts.sum()) > 0
            b_b.refresh_block_on_device(blk_b)
        assert b_b.graph.sweep_packed_dispatches == 3

        assert np.array_equal(b_a.graph.invalid_mask(), b_b.graph.invalid_mask())
        assert np.array_equal(
            np.asarray(table_a._values), np.asarray(table_b._values)
        )
        assert table_a.stale_count() == table_b.stale_count()
        assert [ids for _seq, ids in fences_a] == [ids for _seq, ids in fences_b]
    finally:
        set_default_hub(old)


async def test_sweep_packed_dispatches_counts_every_sweep_program_call():
    """``DeviceGraph.sweep_packed_dispatches`` = sweep programs dispatched:
    one per fused union, lane burst, chain batch or super-round, one per pass
    on the split pipeline, none for a lat-served union; exported as
    ``fusion_sweep_packed_dispatches_total`` and named in the warm report by
    its ``nodes_per_row``."""
    from stl_fusion_tpu.graph.program_cache import (
        program_warm_report,
        time_program_warm,
    )

    hub, backend, _s, _table, block = make_stack()
    old = set_default_hub(hub)
    try:
        g = backend.graph
        assert g.sweep_packed_dispatches == 0
        rng = np.random.default_rng(5)
        g.run_waves_union([[int(rng.integers(N))]])  # lat-served: no sweep
        assert g.lat_waves == 1 and g.sweep_packed_dispatches == 0
        with time_program_warm("sweep-test-union"):
            g.run_waves_union([rng.choice(N, size=300, replace=False).tolist()])
        assert g.sweep_packed_dispatches == 1  # past LAT_SEED_MAX: fused union
        g.run_waves_lanes(round_bursts(1)[0])
        assert g.sweep_packed_dispatches == 2
        with time_program_warm("sweep-test-chain"):
            g.run_waves_lanes_chain(wide_bursts(g.FUSE_CHAIN_MAX + 2))
        assert g.sweep_packed_dispatches == 4  # ceil(10 / FUSE_CHAIN_MAX) batches
        prog = backend.enable_super_rounds(block, depth=2)
        prog.dispatch(prog.stage(round_bursts(2))).harvest()
        assert g.sweep_packed_dispatches == 5
        # a mirror carrying more passes than the fused programs serve runs
        # the split pipeline: one sweep program call per pass
        g._topo_mirror["passes"] = g.FUSED_PASS_MAX + 1
        g.run_waves_lanes(round_bursts(1)[0])
        assert g.sweep_packed_dispatches == 5 + g.FUSED_PASS_MAX + 1
        dispatched = g.sweep_packed_dispatches
        assert (
            backend._collect_metrics()["fusion_sweep_packed_dispatches_total"]
            == dispatched
        )
        report = program_warm_report()
        assert report["sweep-test-union"]["nodes_per_row"] == [1]  # one word
        assert report["sweep-test-chain"]["nodes_per_row"] == [8]  # 16 words
    finally:
        set_default_hub(old)
