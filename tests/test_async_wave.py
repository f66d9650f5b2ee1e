"""Async frontier execution (ISSUE 17 tentpole): speculative local wave
levels between counted-quiescence merge epochs must converge to the
BIT-IDENTICAL invalid mask as the bulk-synchronous exchange AND the host
BFS — at every depth, on every exchange geometry, through chains,
patches, stragglers and faults.

Covers: async ≡ sync ≡ host BFS at depths 1/2/4 over seeded random
graphs and deep chains (where the barrier reclaim is strict); the hier
plane; the 3-host counted gather-fallback geometry (non-pow2 hosts —
async exact THROUGH the fallback); the counted tree→gather construction
fallback; an adversarial straggler shard (one shard's frontier runs many
levels deeper than the rest); fault injection mid-async super-round
(contained, counted, state stays truth); and the adaptive sweep passes
the live loop rides (fixed-point ≡ fixed worst-case pass count, counted
stages, rebuilds keep the mode)."""
import numpy as np
import pytest

from stl_fusion_tpu.cluster import DevicePlacement, ShardMap
from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.parallel import RoutedShardedGraph, graph_mesh
from test_routed_wave import (
    EXCHANGES, LEVEL_RULE_CASES, check_level_rules, check_worklist_chain,
)


def bfs_closure(adj, seeds):
    seen, stack = set(), list(seeds)
    while stack:
        u = stack.pop()
        if u in seen:
            continue
        seen.add(u)
        stack.extend(adj.get(u, ()))
    return seen


def make_graph(n=4000, seed=3):
    src, dst = power_law_dag(n, avg_degree=3.0, seed=seed)
    adj = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        adj.setdefault(s, []).append(d)
    return src, dst, adj


def mask_of(seen, n):
    m = np.zeros(n, dtype=bool)
    if seen:
        m[np.fromiter(seen, dtype=np.int64, count=len(seen))] = True
    return m


def pair(src, dst, n, *, exchange="a2a", depth=2, pl=None, mesh=None):
    """A sync twin and an async graph over the same placement."""
    if pl is None:
        smap = ShardMap.initial(["a", "b"], n_shards=32)
        pl = DevicePlacement.build(smap, 8, n)
    mesh = mesh or graph_mesh()
    g_s = RoutedShardedGraph(src, dst, n, pl, mesh=mesh, exchange=exchange)
    g_a = RoutedShardedGraph(
        src, dst, n, pl, mesh=mesh, exchange=exchange,
        exchange_async=True, async_depth=depth,
    )
    return g_s, g_a


# ---------------------------------------------------------- depth sweep
@pytest.mark.parametrize("depth", [1, 2, 4])
def test_async_matches_sync_and_host_bfs(depth):
    n = 4000
    src, dst, adj = make_graph(n=n)
    g_s, g_a = pair(src, dst, n, depth=depth)
    rng = np.random.default_rng(7)
    seen = set()
    for _ in range(3):
        seeds = rng.choice(n, size=16, replace=False).tolist()
        cs, _ids, _ = g_s.run_wave_collect(seeds)
        ca, _ids, _ = g_a.run_wave_collect(seeds)
        assert int(cs) == int(ca)
        seen |= bfs_closure(adj, seeds)
        want = mask_of(seen, n)
        assert np.array_equal(g_a.invalid_mask(), g_s.invalid_mask())
        assert np.array_equal(g_a.invalid_mask(), want)
    # the quiescence protocol actually ran (counted merge epochs), and
    # the async schedule never needs MORE barriers than per-level sync
    assert g_a.quiescence_checks > 0
    assert g_a.levels_total <= g_s.levels_total
    st = g_a.stats()
    assert st["exchange_async"] is True and st["async_depth"] == depth
    assert st["quiescence_checks"] == g_a.quiescence_checks


@pytest.mark.parametrize("case", LEVEL_RULE_CASES)
@pytest.mark.parametrize("exchange", EXCHANGES)
def test_async_level_rules_match_host_levels(exchange, case):
    """ISSUE 33: the speculative levels and the merge epoch share the
    hoisted version check and the node-row invalid mask."""
    check_level_rules(exchange, case, async_depth=2)


@pytest.mark.parametrize("case", ["bump_between_waves", "second_wave_no_restore"])
@pytest.mark.parametrize("depth", [1, 4])
def test_async_merge_fires_the_worklist(depth, case):
    """ISSUE 37: a merge epoch fires the wave's worklist against the
    ever-lit rows; a slot that fired there is done for the wave, at the
    shallowest and at the default depth."""
    check_level_rules("a2a", case, async_depth=depth)


@pytest.mark.parametrize("depth", [1, 4])
def test_async_chain_builds_a_worklist_per_stage(depth):
    check_worklist_chain("a2a", async_depth=depth)


def test_async_deep_chain_reclaims_barriers_strictly():
    """A deep chain is the worst case for per-level exchange (one barrier
    per hop) and the best case for speculation: async at depth 4 must
    stay exact while retiring STRICTLY fewer merge epochs."""
    n = 512
    src = np.arange(n - 1, dtype=np.int64)
    dst = np.arange(1, n, dtype=np.int64)
    g_s, g_a = pair(src, dst, n, depth=4)
    cs, _ids, _ = g_s.run_wave_collect([0])
    ca, _ids, _ = g_a.run_wave_collect([0])
    assert int(cs) == int(ca) == n
    assert np.array_equal(g_a.invalid_mask(), g_s.invalid_mask())
    assert g_a.invalid_mask().all()
    assert g_a.levels_total < g_s.levels_total
    assert g_a.spec_levels_total > 0  # speculation did real work


def test_async_hier_plane_matches_host_bfs():
    n = 4000
    src, dst, adj = make_graph(n=n, seed=11)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n, devices_per_host=4)
    g_s, g_a = pair(src, dst, n, exchange="hier", depth=2, pl=pl)
    assert g_a.exchange == "hier" and g_a.hier_fallbacks == 0
    seeds = [0, 17, 901, 2048]
    cs, _ids, _ = g_s.run_wave_collect(seeds)
    ca, _ids, _ = g_a.run_wave_collect(seeds)
    assert int(cs) == int(ca)
    want = mask_of(bfs_closure(adj, seeds), n)
    assert np.array_equal(g_a.invalid_mask(), g_s.invalid_mask())
    assert np.array_equal(g_a.invalid_mask(), want)
    assert g_a.cross_host_words > 0  # the host plane really exchanged


def test_async_chain_dispatch_and_patch_stay_exact():
    """The fused union chain and a live patch_batch both ride the async
    program: stage counts, masks and the post-patch closure must match
    the sync twin exactly."""
    n = 4000
    src, dst, adj = make_graph(n=n, seed=5)
    g_s, g_a = pair(src, dst, n, depth=2)
    stages = [[1, 2], [700, 1500], [3999]]
    for g in (g_s, g_a):
        pending = g.dispatch_union_chain(stages)
        g.harvest_union_chain(pending)
    assert np.array_equal(g_a.invalid_mask(), g_s.invalid_mask())
    # live edges grafted mid-flight: same batch to both graphs
    new_src = np.asarray([10, 20, 30], dtype=np.int64)
    new_dst = np.asarray([2000, 2500, 3000], dtype=np.int64)
    ep = np.zeros(3, dtype=np.int32)
    for g in (g_s, g_a):
        g.clear_invalid()
        assert g.patch_batch(np.empty(0, np.int64), new_src, new_dst, ep)
    for s, d in zip(new_src.tolist(), new_dst.tolist()):
        adj.setdefault(s, []).append(d)
    cs, _ids, _ = g_s.run_wave_collect([10, 20, 30])
    ca, _ids, _ = g_a.run_wave_collect([10, 20, 30])
    assert int(cs) == int(ca)
    want = mask_of(bfs_closure(adj, [10, 20, 30]), n)
    assert np.array_equal(g_a.invalid_mask(), g_s.invalid_mask())
    assert np.array_equal(g_a.invalid_mask(), want)


# ------------------------------------------------- fallback geometries
def test_three_host_gather_fallback_keeps_async_exact():
    """3 emulated hosts (6 devices x dph 2): hier's xor trees need pow2
    hosts, so construction falls back to gather — COUNTED — and the
    async wave must be exact straight through the fallback plane."""
    n = 3000
    src, dst, adj = make_graph(n=n, seed=13)
    smap = ShardMap.initial(["a", "b", "c"], n_shards=30)
    pl = DevicePlacement.build(smap, 6, n, devices_per_host=2)
    mesh = graph_mesh(n_devices=6)
    g_s, g_a = pair(src, dst, n, exchange="hier", depth=2, pl=pl, mesh=mesh)
    for g in (g_s, g_a):
        assert g.exchange == "gather" and g.hier_fallbacks == 1
    seeds = [0, 5, 1234]
    cs, _ids, _ = g_s.run_wave_collect(seeds)
    ca, _ids, _ = g_a.run_wave_collect(seeds)
    assert int(cs) == int(ca)
    want = mask_of(bfs_closure(adj, seeds), n)
    assert np.array_equal(g_a.invalid_mask(), g_s.invalid_mask())
    assert np.array_equal(g_a.invalid_mask(), want)
    assert g_a.quiescence_checks > 0


def test_tree_fallback_is_counted_not_silent():
    """tree on a non-pow2 device count: resolved via gather with a
    counter bump AND a recorder event — the ISSUE 17 satellite retiring
    the silent downgrade."""
    from stl_fusion_tpu.diagnostics.metrics import global_metrics
    from stl_fusion_tpu.resilience.events import global_events

    n = 2000
    src, dst, adj = make_graph(n=n, seed=17)
    smap = ShardMap.initial(["a", "b"], n_shards=30)
    pl = DevicePlacement.build(smap, 6, n)
    before = global_metrics().snapshot().get("fusion_mesh_tree_fallback_total", 0)
    ev_before = global_events().count("tree_fallback")
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(n_devices=6), exchange="tree",
        exchange_async=True, async_depth=2,
    )
    assert g.exchange == "gather" and g.tree_fallbacks == 1
    assert g.stats()["tree_fallbacks"] == 1
    snap = global_metrics().snapshot()
    assert snap.get("fusion_mesh_tree_fallback_total", 0) == before + 1
    assert global_events().count("tree_fallback") == ev_before + 1
    # and the fallback plane stays exact under async
    c, _ids, _ = g.run_wave_collect([0, 9])
    want = mask_of(bfs_closure(adj, [0, 9]), n)
    assert np.array_equal(g.invalid_mask(), want) and int(c) == int(want.sum())


def test_pow2_tree_does_not_count_a_fallback():
    n = 1000
    src, dst, _adj = make_graph(n=n, seed=19)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=graph_mesh(), exchange="tree")
    assert g.exchange == "tree" and g.tree_fallbacks == 0


# ------------------------------------------------- adversarial straggler
def test_straggler_shard_deep_chain_converges_exactly():
    """One shard owns a deep local chain (the straggler — its frontier
    keeps producing for many levels) while every other shard's frontier
    dies immediately. Quiescence must wait for the straggler: the merged
    mask is exact at every depth and the chain is fully closed."""
    n = 4096  # 8 devices x 512 local rows; ids 0..599 sit on device 0
    chain = 600
    src = list(range(chain - 1))
    dst = list(range(1, chain))
    # shallow far-side fan: a hub high in the id space with leaf children
    hub = n - 100
    for leaf in range(n - 99, n - 50):
        src.append(hub)
        dst.append(leaf)
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    adj = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        adj.setdefault(s, []).append(d)
    for depth in (1, 2, 4):
        g_s, g_a = pair(src, dst, n, depth=depth)
        seeds = [0, hub]
        cs, _ids, _ = g_s.run_wave_collect(seeds)
        ca, _ids, _ = g_a.run_wave_collect(seeds)
        assert int(cs) == int(ca) == chain + 50
        want = mask_of(bfs_closure(adj, seeds), n)
        assert np.array_equal(g_a.invalid_mask(), g_s.invalid_mask())
        assert np.array_equal(g_a.invalid_mask(), want)
        if depth > 1:
            assert g_a.levels_total < g_s.levels_total


# ------------------------------------------------------ fault containment
N_SR = 800
SR_SRC, SR_DST = power_law_dag(N_SR, avg_degree=3, seed=7)


def make_sr_stack():
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        TableBacking,
        compute_method,
        memo_table_of,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    class Dag(ComputeService):
        def __init__(self, hub=None):
            super().__init__(hub)
            self.base = np.arange(N_SR, dtype=np.float32)
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
                rows=N_SR, batch="load",
                device_batch="load_dev", device_args="load_dev_args",
            )
        )
        async def node(self, i: int) -> float:
            return float(self.base[i])

    hub = FusionHub()
    backend = TpuGraphBackend(
        hub, node_capacity=N_SR + 8, edge_capacity=len(SR_SRC) + 512
    )
    svc = Dag(hub)
    hub.add_service(svc, "dag")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    backend.declare_row_edges(block, SR_SRC, block, SR_DST)
    backend.warm_block_on_device(block)
    backend.flush()
    backend.graph.build_topo_mirror()
    return hub, backend, table, block


async def test_fault_mid_async_superround_is_contained():
    """inject_fault_next with the routed mirror in ASYNC mode: the fused
    super-round faults mid-async-wave, falls back to the COUNTED eager
    path, and the final state still matches a clean sequential twin —
    containment is mode-independent."""
    from stl_fusion_tpu.core import set_default_hub
    from stl_fusion_tpu.resilience import WaveWatchdog

    rng = np.random.default_rng(20260806)
    bursts = [
        [rng.choice(N_SR, size=3, replace=False).tolist() for _ in range(3)]
        for _ in range(2)
    ]
    hub_a, b_a, table_a, blk_a = make_sr_stack()
    old = set_default_hub(hub_a)
    try:
        smap = ShardMap.initial(["m0", "m1"], n_shards=32)
        b_a.enable_mesh_routing(
            smap, mesh=graph_mesh(), exchange_async=True, async_depth=2
        )
        prog = b_a.enable_super_rounds(blk_a, depth=2)
        wd = b_a.attach_watchdog(WaveWatchdog(recovery_bursts=1))
        wd.inject_fault_next()
        ticket = prog.dispatch(prog.stage(bursts))
        assert ticket.done and ticket.fallback
        assert prog.faults == 1 and wd.faults == 1

        hub_b, b_b, table_b, blk_b = make_sr_stack()
        set_default_hub(hub_b)
        for groups in bursts:
            b_b.cascade_rows_lanes(blk_b, groups)
            b_b.refresh_block_on_device(blk_b)
        assert np.array_equal(
            b_a.graph.invalid_mask(), b_b.graph.invalid_mask()
        )
        assert np.array_equal(
            np.asarray(table_a._values), np.asarray(table_b._values)
        )
    finally:
        set_default_hub(old)


async def test_clean_async_superround_matches_sync_superround():
    """No fault: an async-mode routed super-round's final state is
    bit-identical to the same super-round over the sync exchange."""
    from stl_fusion_tpu.core import set_default_hub

    rng = np.random.default_rng(99)
    bursts = [
        [rng.choice(N_SR, size=3, replace=False).tolist() for _ in range(2)]
        for _ in range(2)
    ]
    masks, values = [], []
    for async_mode in (False, True):
        hub, b, table, blk = make_sr_stack()
        old = set_default_hub(hub)
        try:
            smap = ShardMap.initial(["m0", "m1"], n_shards=32)
            b.enable_mesh_routing(
                smap, mesh=graph_mesh(),
                exchange_async=async_mode, async_depth=2,
            )
            prog = b.enable_super_rounds(blk, depth=2)
            prog.dispatch(prog.stage(bursts))
            prog.drain()
            assert prog.faults == 0 and prog.eager_rounds == 0
            if async_mode:
                # the super-round stats expose the routed async mode
                # (satellite; the mirror builds lazily — probe after
                # the dispatch resolved through the routed chain)
                st = prog.stats()
                assert st["exchange_async"] is True
                assert st["async_depth"] == 2
                assert st["quiescence_checks"] > 0
            masks.append(b.graph.invalid_mask().copy())
            values.append(np.asarray(table._values).copy())
        finally:
            set_default_hub(old)
    assert np.array_equal(masks[0], masks[1])
    assert np.array_equal(values[0], values[1])


# ------------------------------------------------- adaptive sweep passes
def two_chain_graph():
    """Two parallel chains + a later cross edge that violates the frozen
    level order — the patched mirror needs 2 sweep passes."""
    from stl_fusion_tpu.graph import DeviceGraph

    g = DeviceGraph(node_capacity=128, edge_capacity=256)
    g.add_nodes(64)
    g.add_edges(np.arange(31), np.arange(1, 32))
    g.add_edges(np.arange(32, 63), np.arange(33, 64))
    g.build_topo_mirror()
    g.add_edges([31], [33])  # level-order violation -> passes = 2
    g.run_waves_union([[0]])  # applies the patch to the mirror
    g.clear_invalid()
    g._topo_mirror["lat"] = None  # force the fused sweep path
    return g


def test_adaptive_passes_match_fixed_and_are_counted():
    g = two_chain_graph()
    m = g._topo_mirror
    assert m["passes"] == 2  # 1 + n_viol
    c_fixed, ids_fixed = g.run_waves_union([[0]])
    g.clear_invalid()
    g.set_adaptive_passes(True)
    assert m["passes"] == 0  # the fixed-point sentinel
    stages0 = g.adaptive_stages
    c_ad, ids_ad = g.run_waves_union([[0]])
    assert int(c_ad) == int(c_fixed) == 63
    assert sorted(ids_ad.tolist()) == sorted(ids_fixed.tolist())
    assert g.adaptive_stages > stages0
    from stl_fusion_tpu.diagnostics.metrics import global_metrics

    assert global_metrics().snapshot().get(
        "fusion_wave_adaptive_stages_total", 0
    ) > 0
    # turning it off restores the worst-case count in place
    g.set_adaptive_passes(False)
    assert m["passes"] == 2


def test_adaptive_survives_mirror_rebuild():
    """A mid-loop re-level installs a FRESH mirror dict: the adaptive
    mode must carry over (a rebuild silently reverting to fixed passes
    is exactly the uncounted downgrade this PR retires)."""
    g = two_chain_graph()
    g.set_adaptive_passes(True)
    g.build_topo_mirror(force=True)
    assert g._topo_mirror["passes"] == 0
    g.set_adaptive_passes(False)
    g.build_topo_mirror(force=True)
    assert g._topo_mirror["passes"] == 1


def test_adaptive_lanes_chain_matches_fixed():
    g = two_chain_graph()
    c_fixed, _ = g.run_waves_lanes_chain([[[0]], [[32]]])
    mask_fixed = g.invalid_mask().copy()
    g.clear_invalid()
    g.set_adaptive_passes(True)
    c_ad, _ = g.run_waves_lanes_chain([[[0]], [[32]]])
    assert np.array_equal(g.invalid_mask(), mask_fixed)
    assert np.asarray(c_ad).tolist() == np.asarray(c_fixed).tolist()


# ------------------------------------------------------------- telemetry
def test_level_stall_gauge_is_max_aggregated():
    from stl_fusion_tpu.diagnostics.metrics import global_metrics
    from stl_fusion_tpu.parallel.routed_wave import record_level_stall_ms

    record_level_stall_ms(12.5)
    snap = global_metrics().snapshot()
    assert snap.get("fusion_mesh_level_stall_ms") == 12.5
    # non-additive gauge: the registry must combine collector values for
    # this name with MAX, or N hubs would scrape N x the stall
    assert global_metrics()._agg.get("fusion_mesh_level_stall_ms") == "max"


def test_quiescence_counter_tracks_merge_epochs():
    from stl_fusion_tpu.diagnostics.metrics import global_metrics

    n = 512
    src = np.arange(n - 1, dtype=np.int64)
    dst = np.arange(1, n, dtype=np.int64)
    before = global_metrics().snapshot().get(
        "fusion_mesh_quiescence_checks_total", 0
    )
    _g_s, g_a = pair(src, dst, n, depth=4)
    g_a.run_wave_collect([0])
    snap = global_metrics().snapshot()
    assert (
        snap.get("fusion_mesh_quiescence_checks_total", 0)
        == before + g_a.quiescence_checks
    )
    assert g_a.quiescence_checks > 0
