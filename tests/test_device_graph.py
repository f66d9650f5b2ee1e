"""Device invalidation-wave tests: python BFS oracle equivalence on random
DAGs (the SURVEY §7 step-3 gate), churn/epoch semantics, and the live
hub-mirror offload path."""
import numpy as np
import pytest

from stl_fusion_tpu.graph import DeviceGraph


# ------------------------------------------------------------------ oracle

def python_wave_oracle(n, edges, edge_epochs, node_epochs, invalid, seeds):
    """Reference BFS with version matching — mirrors the C# cascade rule
    (Computed.cs:210-217): fire only if dependent's current epoch matches the
    edge's captured epoch and it isn't already invalidated."""
    from collections import defaultdict, deque

    adj = defaultdict(list)
    for (s, d), ep in zip(edges, edge_epochs):
        adj[s].append((d, ep))
    invalid = dict(enumerate(invalid))
    q = deque()
    for s in seeds:
        if not invalid[s]:
            invalid[s] = True
            q.append(s)
    while q:
        u = q.popleft()
        for d, ep in adj[u]:
            if not invalid[d] and node_epochs[d] == ep:
                invalid[d] = True
                q.append(d)
    return np.array([invalid[i] for i in range(n)], dtype=bool)


def random_dag(rng, n, avg_deg=3.0):
    """Random DAG edges src→dst with src < dst (dependents have higher id)."""
    edges = []
    for d in range(1, n):
        k = rng.poisson(avg_deg)
        k = min(k, d)
        if k > 0:
            srcs = rng.choice(d, size=k, replace=False)
            edges.extend((int(s), d) for s in srcs)
    return edges


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_wave_matches_python_oracle(seed):
    rng = np.random.default_rng(seed)
    n = 300
    edges = random_dag(rng, n)
    g = DeviceGraph(node_capacity=n, edge_capacity=len(edges) + 1)
    g.add_nodes(n)
    arr = np.asarray(edges, dtype=np.int32)
    g.add_edges(arr[:, 0], arr[:, 1])

    # random epoch churn: bump some nodes AFTER edges were captured,
    # killing their stale in-edges
    bumped = rng.choice(n, size=n // 10, replace=False)
    g.bump_epochs(bumped)

    seeds = rng.choice(n, size=5, replace=False).tolist()
    count = g.run_wave(seeds)
    got = g.invalid_mask()

    node_epochs = g._h_node_epoch[:n]
    edge_epochs = [0] * len(edges)  # captured at epoch 0
    want = python_wave_oracle(n, edges, edge_epochs, node_epochs, np.zeros(n, bool), seeds)
    np.testing.assert_array_equal(got, want)
    assert count == int(want.sum())


def test_wave_depth_and_counts():
    # chain 0 -> 1 -> 2 -> 3 -> 4
    g = DeviceGraph()
    g.add_nodes(5)
    g.add_edges(np.arange(4), np.arange(1, 5))
    count, depth = g.run_wave([0], with_stats=True)
    assert count == 5
    assert depth == 4
    assert g.invalid_mask().all()


def test_wave_idempotent_and_incremental():
    g = DeviceGraph()
    g.add_nodes(4)
    g.add_edges([0, 1], [1, 2])  # 0->1->2, 3 isolated
    assert g.run_wave([0]) == 3
    assert g.run_wave([0]) == 0  # already invalid: no re-invalidation
    assert g.run_wave([3]) == 1
    assert g.invalid_mask().all()


def test_epoch_bump_kills_stale_edges_and_revives_node():
    g = DeviceGraph()
    g.add_nodes(3)
    g.add_edges([0, 1], [1, 2])
    g.run_wave([0])
    assert g.invalid_mask().all()
    # "recompute" node 1 and 2: epoch bump clears invalid, old edges die
    g.bump_epochs([1, 2])
    mask = g.invalid_mask()
    assert mask[0] and not mask[1] and not mask[2]
    # invalidating 0 again does NOT cascade: 0 already invalid
    assert g.run_wave([0]) == 0
    # re-adding the edge at the new epoch reconnects the graph
    g.bump_epochs([0])  # 0 recomputed too
    g.add_edges([0], [1])
    assert g.run_wave([0]) == 2  # 0 and 1 (no live 1->2 edge)
    mask = g.invalid_mask()
    assert mask[0] and mask[1] and not mask[2]


@pytest.mark.parametrize("case", ["sparse", "dense", "no_device_copy", "dirty", "empty"])
def test_mark_invalid_mask_equals_mark_invalid_of_its_ids(case, monkeypatch):
    """A closure marked as a bool mask leaves the graph where marking its
    ids leaves it: host mask, device mask and ``invalid_version``. Few rows
    go the id scatter's way, many the bit-packed upload's, and no device
    copy (none yet, or a stale one) gets none."""
    from stl_fusion_tpu.graph import device_graph as dg_mod

    rng = np.random.default_rng(4)
    n = 300  # n_cap 512: four bytes an id outweigh the mask from 129 rows on
    g, twin = (DeviceGraph(node_capacity=n, edge_capacity=8) for _ in range(2))
    before = rng.choice(n, size=20, replace=False)
    for graph in (g, twin):
        graph.add_nodes(n)
        if case != "no_device_copy":
            graph.device_arrays()
        graph.mark_invalid(before)
        if case == "dirty":
            graph.add_nodes(400)  # grows past n_cap: the device copy is stale
            assert graph._dirty
    hits = {"sparse": 5, "dense": 200, "no_device_copy": 200, "dirty": 200, "empty": 0}
    mask = np.zeros(n, dtype=bool)
    # half of the rows are invalid already: the mask is OR-ed in
    mask[np.concatenate([before[:hits[case] // 2],
                         rng.choice(n, size=hits[case], replace=False)])] = True
    ids = np.flatnonzero(mask)

    uploads, scatters = [], []
    monkeypatch.setattr(dg_mod, "_pack_mask_host",
                        lambda m, f=dg_mod._pack_mask_host: uploads.append(1) or f(m))
    monkeypatch.setattr(g, "_pad_ids_pow2",
                        lambda i, f=g._pad_ids_pow2: scatters.append(len(i)) or f(i))
    monkeypatch.setattr(g, "mark_invalid", None)  # not by way of the id method
    version = g.invalid_version
    g.mark_invalid_mask(mask)
    assert (len(uploads), scatters) == {
        "sparse": (0, [len(ids)]), "dense": (1, []),
    }.get(case, (0, []))
    twin.mark_invalid(ids)

    assert g.invalid_version == twin.invalid_version == version + (case != "empty")
    np.testing.assert_array_equal(g._h_invalid, twin._h_invalid)
    assert g._h_invalid[:n].sum() == len(np.union1d(before, ids))
    np.testing.assert_array_equal(g.invalid_mask(), twin.invalid_mask())
    np.testing.assert_array_equal(g.invalid_mask(), g._h_invalid[: g.n_nodes])


def test_capacity_growth():
    g = DeviceGraph(node_capacity=16, edge_capacity=16)
    ids = g.add_nodes(100)
    g.add_edges(ids[:-1], ids[1:])
    assert g.run_wave([0]) == 100
    assert g.invalid_mask().sum() == 100


def test_compact_drops_dead_edges():
    g = DeviceGraph()
    g.add_nodes(3)
    g.add_edges([0, 0], [1, 2])
    g.bump_epochs([1])  # edge 0->1 now dead
    assert g.compact() == 1
    assert g.n_edges == 1
    assert g.run_wave([0]) == 2  # 0 + 2 only


# ------------------------------------------------------------------ live hub mirror

async def test_backend_offload_matches_host_semantics():
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        capture,
        compute_method,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub)

        class S(ComputeService):
            def __init__(self):
                super().__init__()
                self.data = {"a": 1, "b": 2}

            @compute_method
            async def get(self, k: str) -> int:
                return self.data[k]

            @compute_method
            async def total(self) -> int:
                return await self.get("a") + await self.get("b")

            @compute_method
            async def doubled(self) -> int:
                return 2 * await self.total()

        svc = S()
        assert await svc.doubled() == 6
        c_a = await capture(lambda: svc.get("a"))
        c_total = await capture(lambda: svc.total())
        c_doubled = await capture(lambda: svc.doubled())
        assert backend.node_count == 4  # get(a), get(b), total, doubled

        # offload the cascade: device wave computes the closure
        svc.data["a"] = 10
        applied = backend.invalidate_cascade(c_a)
        assert applied == 3  # a, total, doubled
        assert c_a.is_invalidated and c_total.is_invalidated and c_doubled.is_invalidated
        b_node = await capture(lambda: svc.get("b"))
        assert b_node.is_consistent  # untouched branch stays consistent

        # recompute rebuilds edges at new epochs; a second offload wave works
        assert await svc.doubled() == 24
        c_a2 = await capture(lambda: svc.get("a"))
        svc.data["a"] = 0
        backend.invalidate_cascade(c_a2)
        assert await svc.doubled() == 4
    finally:
        set_default_hub(old)


async def test_backend_sharded_export_cascades_on_mesh():
    """to_sharded bridges the LIVE incremental graph to the multi-chip wave:
    the mesh cascade must equal the single-chip backend cascade."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        capture,
        compute_method,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub)

        class S(ComputeService):
            def __init__(self):
                super().__init__()
                self.data = {"a": 1, "b": 2}

            @compute_method
            async def get(self, k: str) -> int:
                return self.data[k]

            @compute_method
            async def total(self) -> int:
                return await self.get("a") + await self.get("b")

            @compute_method
            async def doubled(self) -> int:
                return 2 * await self.total()

        svc = S()
        assert await svc.doubled() == 6
        c_a = await capture(lambda: svc.get("a"))
        c_b = await capture(lambda: svc.get("b"))
        c_total = await capture(lambda: svc.total())
        c_doubled = await capture(lambda: svc.doubled())

        sharded = backend.to_sharded()  # 8-device CPU mesh (conftest)
        ids = {name: backend.id_for(c) for name, c in
               [("a", c_a), ("b", c_b), ("total", c_total), ("doubled", c_doubled)]}
        count = sharded.run_wave([ids["a"]])
        assert count == 3  # a, total, doubled — b untouched
        mask = sharded.invalid_mask()
        assert mask[ids["a"]] and mask[ids["total"]] and mask[ids["doubled"]]
        assert not mask[ids["b"]]
        # the live nodes map back through computed_for
        assert backend.computed_for(ids["total"]) is c_total

        # stale edges (old epochs) must not fire after a recompute bump:
        # recompute everything, export again, wave from the NEW a-node
        svc.data["a"] = 10
        backend.invalidate_cascade(c_a)
        assert await svc.doubled() == 24
        c_a2 = await capture(lambda: svc.get("a"))
        sharded2 = backend.to_sharded()
        count2 = sharded2.run_wave([backend.id_for(c_a2)])
        assert count2 == 3  # fresh epoch edges cascade; dead ones don't refire
    finally:
        set_default_hub(old)


def test_run_wave_collect_and_chained_match_oracle():
    """run_wave_collect returns exactly the newly-invalidated ids (O(wave)
    readback path); run_waves_chained equals running the waves one at a
    time."""
    rng = np.random.default_rng(11)
    n = 400
    edges = random_dag(rng, n)
    arr = np.asarray(edges, dtype=np.int32)

    def fresh():
        g = DeviceGraph(node_capacity=n, edge_capacity=len(edges) + 1)
        g.add_nodes(n)
        g.add_edges(arr[:, 0], arr[:, 1])
        return g

    seeds1 = rng.choice(n, size=5, replace=False).tolist()
    seeds2 = rng.choice(n, size=5, replace=False).tolist()

    g = fresh()
    count, ids = g.run_wave_collect(seeds1, cap=8)  # tiny cap → overflow path
    want1 = python_wave_oracle(
        n, edges, [0] * len(edges), np.zeros(n, np.int32), np.zeros(n, bool), seeds1
    )
    assert count == int(want1.sum())
    np.testing.assert_array_equal(np.sort(ids), np.nonzero(want1)[0])

    g2 = fresh()
    count2, ids2 = g2.run_wave_collect(seeds1, cap=1024)  # compacted path
    assert count2 == count
    np.testing.assert_array_equal(np.sort(ids2), np.sort(ids))
    # incremental second wave only reports NEW ids
    count3, ids3 = g2.run_wave_collect(seeds2, cap=1024)
    want_u = python_wave_oracle(
        n, edges, [0] * len(edges), np.zeros(n, np.int32), want1.copy(), seeds2
    )
    newly = want_u & ~want1
    assert count3 == int(newly.sum())
    np.testing.assert_array_equal(np.sort(ids3), np.nonzero(newly)[0])

    # chained = sequential
    g3 = fresh()
    counts, union_ids = g3.run_waves_chained([seeds1, seeds2])
    assert counts.tolist() == [count, count3]
    np.testing.assert_array_equal(np.sort(union_ids), np.nonzero(want_u)[0])

    # union = one BFS from all seeds, same final state + total (the live
    # batch path: O(edges x depth), not x batch size)
    g4 = fresh()
    total, union_ids2 = g4.run_waves_union([seeds1, seeds2])
    assert total == count + count3
    np.testing.assert_array_equal(np.sort(union_ids2), np.nonzero(want_u)[0])
    # a second union call reports nothing new (idempotent)
    total2, ids_again = g4.run_waves_union([seeds1, seeds2])
    assert total2 == 0 and len(ids_again) == 0


async def test_backend_two_tier_application():
    """Watched nodes (invalidation observers) apply EAGERLY after a device
    wave; unwatched nodes go pending and materialize on next touch — both
    read as invalidated through the public API the whole time."""
    from stl_fusion_tpu.core import (
        ComputeService,
        ConsistencyState,
        FusionHub,
        capture,
        compute_method,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub)

        class S(ComputeService):
            def __init__(self):
                super().__init__()
                self.data = {"a": 1, "b": 2}

            @compute_method
            async def get(self, k: str) -> int:
                return self.data[k]

            @compute_method
            async def total(self) -> int:
                return await self.get("a") + await self.get("b")

            @compute_method
            async def doubled(self) -> int:
                return 2 * await self.total()

        svc = S()
        assert await svc.doubled() == 6
        c_a = await capture(lambda: svc.get("a"))
        c_total = await capture(lambda: svc.total())
        c_doubled = await capture(lambda: svc.doubled())

        fired = []
        c_doubled.on_invalidated(lambda c: fired.append(c))  # → watched

        svc.data["a"] = 10
        backend.invalidate_cascade(c_a)
        # watched: materialized eagerly, handler fired
        assert fired == [c_doubled]
        assert c_doubled._state == int(ConsistencyState.INVALIDATED)
        # unwatched: pending (raw state untouched) but the public API is
        # already truthful
        assert c_total._state == int(ConsistencyState.CONSISTENT)
        assert c_total.is_invalidated and not c_total.is_consistent
        assert c_total.consistency_state == ConsistencyState.INVALIDATED

        # a read sees the miss and recomputes; the displaced node is
        # materialized by the register-time bump (no zombies)
        assert await svc.total() == 12
        assert c_total._state == int(ConsistencyState.INVALIDATED)
        assert await svc.doubled() == 24

        # direct invalidate() on a pending node materializes locally
        c_a2 = await capture(lambda: svc.get("a"))
        backend.invalidate_cascade(c_a2)
        assert c_a2.invalidate() is True
        assert c_a2._state == int(ConsistencyState.INVALIDATED)
    finally:
        set_default_hub(old)


async def test_backend_batch_cascade():
    """invalidate_cascade_batch: many seeds, one dispatch, sequential
    semantics."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        capture,
        compute_method,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub)

        class S(ComputeService):
            def __init__(self):
                super().__init__()
                self.data = {k: i for i, k in enumerate("abcd")}

            @compute_method
            async def get(self, k: str) -> int:
                return self.data[k]

            @compute_method
            async def pair(self, a: str, b: str) -> int:
                return await self.get(a) + await self.get(b)

        svc = S()
        assert await svc.pair("a", "b") == 1
        assert await svc.pair("c", "d") == 5
        c_a = await capture(lambda: svc.get("a"))
        c_c = await capture(lambda: svc.get("c"))
        c_ab = await capture(lambda: svc.pair("a", "b"))
        c_cd = await capture(lambda: svc.pair("c", "d"))

        total = backend.invalidate_cascade_batch([c_a, c_c])
        assert total == 4  # a, pair(a,b), c, pair(c,d)
        assert c_ab.is_invalidated and c_cd.is_invalidated
        svc.data["a"] = 100
        assert await svc.pair("a", "b") == 101
    finally:
        set_default_hub(old)


def test_topo_mirror_burst_matches_dense_union():
    """The packed topo mirror (depth-free burst path) produces the SAME
    newly-invalidated set, host state, and device state as the dense union
    BFS — including across epoch churn (recomputes kill the fingerprint and
    route bursts back to the dense path) and already-invalid seeds."""
    rng = np.random.default_rng(17)
    n = 500
    edges = random_dag(rng, n, avg_deg=3.0)
    arr = np.asarray(edges, dtype=np.int32)

    def fresh():
        g = DeviceGraph(node_capacity=n, edge_capacity=len(edges) * 2)
        g.add_nodes(n)
        g.add_edges(arr[:, 0], arr[:, 1])
        return g

    seeds1 = rng.choice(n, size=6, replace=False).tolist()
    seeds2 = rng.choice(n, size=6, replace=False).tolist()

    dense = fresh()
    c1, ids1 = dense.run_waves_union([seeds1], mirror="off")

    mirrored = fresh()
    info = mirrored.build_topo_mirror(k=4, cap=1024)
    assert info["levels"] >= 1
    c1m, ids1m = mirrored.run_waves_union([seeds1])  # auto → mirror path
    assert mirrored.mirror_bursts == 1  # the mirror actually served it
    assert c1m == c1
    np.testing.assert_array_equal(np.sort(ids1m), np.sort(ids1))
    np.testing.assert_array_equal(mirrored._h_invalid, dense._h_invalid)
    np.testing.assert_array_equal(  # device states agree too
        np.asarray(mirrored.device_arrays().invalid),
        np.asarray(dense.device_arrays().invalid),
    )

    # second burst over the SAME mirror: incremental (already-invalid nodes
    # don't recount), still equals the dense path
    c2, ids2 = dense.run_waves_union([seeds2], mirror="off")
    c2m, ids2m = mirrored.run_waves_union([seeds2])
    assert c2m == c2
    np.testing.assert_array_equal(np.sort(ids2m), np.sort(ids2))

    # re-running the same seeds: nothing new on either path
    assert mirrored.run_waves_union([seeds1])[0] == 0
    assert dense.run_waves_union([seeds1], mirror="off")[0] == 0
    assert mirrored.mirror_bursts == 3 and dense.mirror_bursts == 0


def test_topo_mirror_patches_bump_and_breaks_on_untracked_delta():
    """r4: an epoch bump no longer drops bursts to the dense path — the
    delta PATCHES the mirror in place (tests/test_mirror_patch.py covers
    the patch matrix). A delta the log cannot express (here: simulated by
    severing the log) falls back to the dense path and is remembered
    (missed_at), and a rebuild restores the mirror route."""
    rng = np.random.default_rng(23)
    n = 200
    edges = random_dag(rng, n, avg_deg=2.5)
    arr = np.asarray(edges, dtype=np.int32)

    g = DeviceGraph(node_capacity=n, edge_capacity=len(edges) * 4)
    g.add_nodes(n)
    g.add_edges(arr[:, 0], arr[:, 1])
    g.build_topo_mirror(k=4, cap=512)
    fp0 = g._topo_mirror["fp"]

    # a recompute: epoch bump kills that node's in-edges → fp changes,
    # but the delta log patches the mirror and the burst stays on it
    victim = int(arr[:, 1][len(arr) // 2])
    g.bump_epochs([victim])
    _, _, fp1 = g._live_edge_fingerprint()
    assert fp1 != fp0

    seeds = rng.choice(n, size=4, replace=False).tolist()
    twin = DeviceGraph(node_capacity=n, edge_capacity=len(edges) * 4)
    twin.add_nodes(n)
    twin.add_edges(arr[:, 0], arr[:, 1])
    twin.bump_epochs([victim])
    c_auto, ids_auto = g.run_waves_union([seeds])
    c_dense, ids_dense = twin.run_waves_union([seeds], mirror="off")
    assert c_auto == c_dense
    np.testing.assert_array_equal(np.sort(ids_auto), np.sort(ids_dense))
    assert g.mirror_bursts == 1 and g.mirror_patches == 1  # patched, served

    # now an untracked structural change (broken delta log): dense fallback
    victim2 = int(arr[:, 1][len(arr) // 3])
    g.bump_epochs([victim2])
    twin.bump_epochs([victim2])
    g._mirror_deltas = None  # sever the log (an unpatchable delta does this)
    seeds2 = rng.choice(n, size=4, replace=False).tolist()
    c2_auto, ids2_auto = g.run_waves_union([seeds2])
    c2_dense, ids2_dense = twin.run_waves_union([seeds2], mirror="off")
    assert c2_auto == c2_dense
    np.testing.assert_array_equal(np.sort(ids2_auto), np.sort(ids2_dense))
    assert g.mirror_bursts == 1  # dense fallback served this one
    # ...and the failed validation is remembered: another burst on the same
    # (unchanged) topology must not re-validate (missed_at == struct_version)
    assert g._topo_mirror["missed_at"] == g._struct_version

    # rebuild picks up the new topology; mirror route is correct again
    g.clear_invalid()
    twin.clear_invalid()
    info = g.build_topo_mirror(k=4, cap=512)
    assert info["fp"] != fp0
    seeds3 = rng.choice(n, size=4, replace=False).tolist()
    c_m, ids_m = g.run_waves_union([seeds3])
    c_d, ids_d = twin.run_waves_union([seeds3], mirror="off")
    assert c_m == c_d and g.mirror_bursts == 2
    np.testing.assert_array_equal(np.sort(ids_m), np.sort(ids_d))


def test_topo_mirror_overflow_falls_back_to_mask_diff():
    """A burst bigger than the id buffer still applies fully (full-mask
    diff fallback), identical to the dense path."""
    rng = np.random.default_rng(29)
    n = 300
    edges = random_dag(rng, n, avg_deg=3.0)
    arr = np.asarray(edges, dtype=np.int32)

    g = DeviceGraph(node_capacity=n, edge_capacity=len(edges) + 1)
    g.add_nodes(n)
    g.add_edges(arr[:, 0], arr[:, 1])
    g.build_topo_mirror(k=4, cap=4)  # tiny buffer → overflow path
    twin = DeviceGraph(node_capacity=n, edge_capacity=len(edges) + 1)
    twin.add_nodes(n)
    twin.add_edges(arr[:, 0], arr[:, 1])

    seeds = list(range(0, 20))
    c_m, ids_m = g.run_waves_union([seeds])
    c_d, ids_d = twin.run_waves_union([seeds], mirror="off")
    assert c_m == c_d and c_m > 4 and g.mirror_bursts == 1
    np.testing.assert_array_equal(np.sort(ids_m), np.sort(ids_d))
    np.testing.assert_array_equal(g._h_invalid, twin._h_invalid)


def test_topo_mirror_random_interleaving_stress():
    """Randomized interleavings of structural mutations, host-led
    invalidations, lone waves, bursts, and mirror rebuilds: a mirror-auto
    graph must remain state-identical to a dense-only twin at every step.
    This is the guard for the staleness machinery — any missed
    struct-version bump or fingerprint shortcut shows up as divergence."""
    rng = np.random.default_rng(41)
    n = 160

    g = DeviceGraph(node_capacity=n, edge_capacity=4096)
    twin = DeviceGraph(node_capacity=n, edge_capacity=4096)
    for d in (g, twin):
        d.add_nodes(n)
    g.build_topo_mirror(k=4, cap=256)

    mirror_served = 0
    for step in range(60):
        op = rng.choice(["edge", "bump", "mark", "wave", "burst", "rebuild"],
                        p=[0.25, 0.15, 0.1, 0.15, 0.25, 0.1])
        if op == "edge":
            k = int(rng.integers(1, 6))
            dst = rng.integers(1, n, size=k)
            src = np.array([rng.integers(0, d) for d in dst])  # src < dst: stays a DAG
            g.add_edges(src, dst)
            twin.add_edges(src, dst)
        elif op == "bump":
            ids = rng.choice(n, size=int(rng.integers(1, 5)), replace=False)
            g.bump_epochs(ids)
            twin.bump_epochs(ids)
        elif op == "mark":
            ids = rng.choice(n, size=int(rng.integers(1, 4)), replace=False)
            g.mark_invalid(ids)
            twin.mark_invalid(ids)
        elif op == "wave":
            seeds = rng.choice(n, size=2, replace=False).tolist()
            assert g.run_wave(seeds) == twin.run_wave(seeds)
        elif op == "burst":
            lists = [rng.choice(n, size=2, replace=False).tolist()
                     for _ in range(int(rng.integers(1, 4)))]
            before = g.mirror_bursts
            c_g, ids_g = g.run_waves_union(lists)            # auto
            c_t, ids_t = twin.run_waves_union(lists, mirror="off")
            mirror_served += g.mirror_bursts - before
            assert c_g == c_t, f"step {step}: {c_g} != {c_t}"
            np.testing.assert_array_equal(np.sort(ids_g), np.sort(ids_t))
        else:  # rebuild
            g.build_topo_mirror(k=4, cap=256)
        np.testing.assert_array_equal(
            g._h_invalid, twin._h_invalid, err_msg=f"step {step} ({op})"
        )
    # final deep check: device states agree and the mirror path was exercised
    np.testing.assert_array_equal(
        np.asarray(g.device_arrays().invalid), np.asarray(twin.device_arrays().invalid)
    )
    assert mirror_served >= 3, f"mirror served only {mirror_served} bursts"


async def test_live_sharded_burst_applies_to_hub():
    """The LIVE multi-chip bridge end to end: a burst expanded on the
    8-device mesh invalidates real Computeds in the hub, the dense
    single-chip mirror stays coherent, and the sharded export is
    fingerprint-cached (rebuilt only when topology changes)."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        capture,
        compute_method,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub)

        class S(ComputeService):
            def __init__(self):
                super().__init__()
                self.data = {k: i for i, k in enumerate("abcdef")}

            @compute_method
            async def get(self, k: str) -> int:
                return self.data[k]

            @compute_method
            async def pair_sum(self, a: str, b: str) -> int:
                return await self.get(a) + await self.get(b)

        svc = S()
        assert await svc.pair_sum("a", "b") == 1
        assert await svc.pair_sum("c", "d") == 5
        c_a = await capture(lambda: svc.get("a"))
        c_c = await capture(lambda: svc.get("c"))
        c_ab = await capture(lambda: svc.pair_sum("a", "b"))
        c_cd = await capture(lambda: svc.pair_sum("c", "d"))

        svc.data["a"] = 10
        svc.data["c"] = 20
        applied = backend.invalidate_cascade_batch_sharded([c_a, c_c])
        assert applied == 4  # a, c, and both pair sums
        assert c_a.is_invalidated and c_c.is_invalidated
        assert c_ab.is_invalidated and c_cd.is_invalidated
        b_node = await capture(lambda: svc.get("b"))
        assert b_node.is_consistent  # untouched branch unaffected

        # the dense mirror saw the mesh burst too: a follow-up single-chip
        # wave from the same seed finds nothing new to invalidate
        assert backend.invalidate_cascade(c_a) == 0
        assert await svc.pair_sum("a", "b") == 11

        # export caching: same topology+epochs → same object; a different
        # mesh/exchange request or a structural change (a NEW node enters
        # the graph) rebuilds
        m1 = backend.sharded_mirror()
        assert backend.sharded_mirror() is m1
        assert backend.sharded_mirror(exchange="bool") is not m1
        await svc.get("e")  # first read: new node + journal entry
        m2 = backend.sharded_mirror()
        assert m2 is not m1
        c_a2 = await capture(lambda: svc.get("a"))
        svc.data["a"] = 0
        applied = backend.invalidate_cascade_batch_sharded([c_a2])
        assert applied >= 2  # a + pair_sum(a,b) again at the new epochs
        assert await svc.pair_sum("a", "b") == 1
    finally:
        set_default_hub(old)


# ------------------------------------------------------------------ lane bursts

@pytest.mark.parametrize("seed,n_groups", [(0, 7), (1, 40), (2, 70)])
def test_lane_burst_matches_per_group_dense(seed, n_groups):
    """run_waves_lanes: every group's count and the applied union must match
    INDEPENDENT dense BFS runs from the same pre-burst state — including
    multi-word packing (>32 groups) and epoch-churned dead edges."""
    rng = np.random.default_rng(seed)
    n = 240
    edges = random_dag(rng, n)
    arr = np.asarray(edges, dtype=np.int32)
    bumped = rng.choice(n, size=n // 10, replace=False)
    pre_invalid = rng.choice(n, size=n // 8, replace=False)

    def fresh():
        g = DeviceGraph(node_capacity=n, edge_capacity=len(edges) + 1)
        g.add_nodes(n)
        g.add_edges(arr[:, 0], arr[:, 1])
        g.bump_epochs(bumped)
        g.mark_invalid(pre_invalid)
        return g

    groups = [
        rng.choice(n, size=int(rng.integers(1, 6)), replace=False).tolist()
        for _ in range(n_groups)
    ]
    groups[0] = []  # an empty group is a 0-count no-op lane

    lanes = fresh()
    counts, union_mask = lanes.run_waves_lanes(groups)
    assert lanes.mirror_bursts >= 1

    union_expected = np.zeros(n, dtype=bool)
    for gi, group in enumerate(groups):
        dense = fresh()
        before = dense.invalid_mask().copy()
        c, ids = dense.run_waves_union([group], mirror="off") if group else (0, [])
        assert counts[gi] == c, (gi, counts[gi], c)
        newly = dense.invalid_mask() & ~before
        union_expected |= newly
    # the applied state is pre | union of independent closures
    base = fresh()
    np.testing.assert_array_equal(
        lanes.invalid_mask(), base.invalid_mask() | union_expected
    )
    np.testing.assert_array_equal(union_mask[:n], union_expected)
    # host mirror stayed coherent with device state
    np.testing.assert_array_equal(lanes._h_invalid[:n], lanes.invalid_mask())


def test_lane_burst_chunking_applies_sequentially():
    """Groups beyond 32*max_words are dispatched in chunks; later chunks see
    earlier chunks' invalidations as pre-existing (documented semantics)."""
    rng = np.random.default_rng(3)
    n = 120
    edges = random_dag(rng, n)
    arr = np.asarray(edges, dtype=np.int32)
    g = DeviceGraph(node_capacity=n, edge_capacity=len(edges) + 1)
    g.add_nodes(n)
    g.add_edges(arr[:, 0], arr[:, 1])

    groups = [[int(i % n)] for i in rng.integers(0, n, size=80)]
    counts, union_mask = g.run_waves_lanes(groups, max_words=1)  # 3 chunks of ≤32

    # oracle: chunks of 32, independent inside a chunk, sequential between
    oracle_invalid = np.zeros(n, dtype=bool)
    expected = []
    for c0 in range(0, len(groups), 32):
        chunk_newly = np.zeros(n, dtype=bool)
        for group in groups[c0 : c0 + 32]:
            closure = python_wave_oracle(
                n, edges, [0] * len(edges), np.zeros(n, np.int32),
                oracle_invalid.copy(), group,
            ) & ~oracle_invalid
            expected.append(int(closure.sum()))
            chunk_newly |= closure
        oracle_invalid |= chunk_newly
    np.testing.assert_array_equal(counts, expected)
    np.testing.assert_array_equal(g.invalid_mask(), oracle_invalid)
    np.testing.assert_array_equal(union_mask[:n], oracle_invalid)


def test_lane_burst_rejects_out_of_range_seeds():
    g = DeviceGraph(node_capacity=16, edge_capacity=16)
    g.add_nodes(8)
    with pytest.raises(ValueError, match="seed ids"):
        g.run_waves_lanes([[0], [99]])


async def test_backend_lane_burst_applies_to_hub():
    """invalidate_cascade_batch_lanes through a REAL hub: per-group counts
    match dense per-group runs, watched nodes invalidate eagerly, unwatched
    lazily, and a missing computed falls back to host invalidation."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        capture,
        compute_method,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub)

        class Chain(ComputeService):
            @compute_method
            async def base(self, i: int) -> int:
                return i

            @compute_method
            async def mid(self, i: int) -> int:
                return await self.base(i) + 1

            @compute_method
            async def top(self, i: int) -> int:
                return await self.mid(i) + 1

        svc = Chain(hub=hub)
        tops = [await capture(lambda i=i: svc.top(i)) for i in range(8)]
        bases = [await capture(lambda i=i: svc.base(i)) for i in range(8)]
        mids = [await capture(lambda i=i: svc.mid(i)) for i in range(8)]

        # group g invalidates base(g) → chain of 3 (base, mid, top)
        groups = [[bases[i]] for i in range(6)]
        counts = backend.invalidate_cascade_batch_lanes(groups)
        np.testing.assert_array_equal(counts, [3] * 6)
        for i in range(6):
            # unwatched nodes are pending (lazy) until read; either way the
            # invalidation must be visible through the read path: a fresh
            # capture yields a NEW computed, not the stale cached one
            assert (
                bases[i].is_invalidated
                or backend._pending[backend.id_for(bases[i])]
            )
            fresh_top = await capture(lambda i=i: svc.top(i))
            assert fresh_top is not tops[i]
        # untouched groups stay consistent and cached
        assert not tops[7].is_invalidated and not bases[7].is_invalidated
        assert (await capture(lambda: svc.top(7))) is tops[7]

        # overlapping groups are snapshot-independent: both count the shared
        # node even though it is applied once
        await svc.top(7)  # ensure consistent
        c2 = backend.invalidate_cascade_batch_lanes([[mids[7]], [bases[7]]])
        assert c2[0] == 2  # mid, top
        assert c2[1] == 3  # base, mid, top (counts mid+top again)
    finally:
        set_default_hub(old)


# ------------------------------------------------- recaptures with fan-in (PR 38)

def fan_in_graph(indeg: int, fanout: int, spare: int = 2):
    """``indeg`` sources, ``fanout`` dependents that each depend on ALL of
    them (in-degree ``indeg``, every source with ``fanout`` dependents), and
    ``spare`` sources beside them with one dependent each (for in-sets that
    differ). Both mirrors built. Returns (graph, sources, dependents,
    spare sources)."""
    srcs = np.arange(indeg, dtype=np.int32)
    deps = np.arange(indeg, indeg + fanout, dtype=np.int32)
    extra = np.arange(indeg + fanout, indeg + fanout + spare, dtype=np.int32)
    sink = indeg + fanout + spare
    src = np.concatenate([np.repeat(srcs, fanout), extra])
    dst = np.concatenate([np.tile(deps, indeg), np.full(spare, sink, np.int32)])
    g = DeviceGraph(node_capacity=sink + 8, edge_capacity=len(src) + 4096)
    g.add_nodes(sink + 1)
    g.add_edges(src, dst)
    g.build_topo_mirror()
    return g, srcs, deps, extra


def live_closure(g, seeds):
    """Host BFS over the graph's own live edges (captured-at-epoch rule)."""
    m = g.n_edges
    live = g._h_node_epoch[g._h_edge_dst[:m]] == g._h_edge_dst_epoch[:m]
    adj = {}
    for u, v in zip(g._h_edge_src[:m][live].tolist(), g._h_edge_dst[:m][live].tolist()):
        adj.setdefault(u, set()).add(v)
    seen, frontier = set(seeds), list(seeds)
    while frontier:
        frontier = [v for u in frontier for v in adj.get(u, ()) if v not in seen
                    and not seen.add(v)]
    return seen


def recapture(g, v, sources):
    """What a scalar twin's recompute journals: bump, then the in-edges."""
    g.bump_epochs(np.array([v], np.int32))
    if len(sources):
        g.add_edges(np.asarray(sources, np.int32), np.full(len(sources), v, np.int32))


def assert_waves_equal_bfs(g, seed):
    want = live_closure(g, [seed])
    lat0 = g.lat_waves
    _count, ids = g.run_waves_union([[seed]])
    assert set(ids.tolist()) == want
    g.clear_invalid()
    _count, ids = g._run_mirror_union([[seed]])
    assert set(ids.tolist()) == want
    g.clear_invalid()
    return g.lat_waves - lat0


@pytest.mark.parametrize("indeg,fanout", [(6, 3), (7, 7), (16, 500)])
def test_recapture_survives_fan_in_and_hubs(indeg, fanout):
    """A row behind collectors (in-degree past the in-ELL's 4 + 2) under
    sources behind forwarding trees (out-degree past the out-ELL's): a
    recapture that restores the in-set keeps both mirrors, moves no slot,
    and the waves after it equal the host BFS."""
    g, srcs, deps, _extra = fan_in_graph(indeg, fanout)
    m = g._topo_mirror
    h0, d0 = m["h_in_src"].copy(), m["lat"]["h_ell_dst"].copy()
    v = int(deps[len(deps) // 2])
    recapture(g, v, srcs)
    assert g._mirror_valid() and g._mirror_deltas == []
    assert m["lat"] is not None and g.mirror_patches == 1
    assert g.mirror_rows_kept == 1 and g.mirror_slots_revived == indeg
    np.testing.assert_array_equal(m["h_in_src"], h0)
    np.testing.assert_array_equal(m["lat"]["h_ell_dst"], d0)
    assert assert_waves_equal_bfs(g, int(srcs[0])) == 1  # the lat mirror served


@pytest.mark.parametrize("indeg,fanout", [(6, 3), (16, 40)])
def test_ten_recaptures_in_a_row_leak_no_slot(indeg, fanout):
    g, srcs, deps, _extra = fan_in_graph(indeg, fanout)
    m = g._topo_mirror
    h0, d0 = m["h_in_src"].copy(), m["lat"]["h_ell_dst"].copy()
    for i in range(10):
        recapture(g, int(deps[i % 3]), srcs)
        # the same edges captured once more, as a scalar body's awaits do
        g.add_edges(srcs, np.full(len(srcs), int(deps[i % 3]), np.int32))
        assert g._mirror_valid() and m["lat"] is not None
    assert g.mirror_rows_kept == 10 and g.mirror_slots_revived == 10 * indeg
    np.testing.assert_array_equal(m["h_in_src"], h0)
    np.testing.assert_array_equal(m["lat"]["h_ell_dst"], d0)
    assert g.mirror_rebuilds == 1
    assert assert_waves_equal_bfs(g, int(srcs[1])) == 1


@pytest.mark.parametrize("change", ["one_more", "one_less", "another"])
@pytest.mark.parametrize("indeg", [3, 16])
def test_a_recapture_with_another_in_set_takes_the_old_road(indeg, change):
    """The row is cleared and its new in-set spliced into its free slots:
    patched where they suffice (a row of 3, which has no collectors), broken
    where they do not (a row of 16 has 6 slots for 15-17 sources)."""
    g, srcs, deps, extra = fan_in_graph(indeg, 5)
    v = int(deps[0])
    new = {
        "one_more": np.concatenate([srcs, extra[:1]]),
        "one_less": srcs[:-1],
        "another": np.concatenate([srcs[:-1], extra[:1]]),
    }[change]
    recapture(g, v, new)
    assert g._mirror_valid() == (indeg == 3)
    assert g.mirror_rows_kept == 0
    if indeg == 3:
        assert g._mirror_deltas == [] and g.mirror_patches == 1
        row = g._topo_mirror["h_in_src"][g._topo_mirror["inv_perm"][v]]
        assert sorted(row[row != g._topo_mirror["n_tot"]].tolist()) == sorted(
            g._topo_mirror["inv_perm"][new].tolist()
        )
        for seed in (int(srcs[0]), int(srcs[-1]), int(extra[0])):
            assert_waves_equal_bfs(g, seed)
    else:
        assert g._mirror_deltas is None  # in-degree overflow: a rebuild's
        want = live_closure(g, [int(srcs[0])])
        assert set(g.run_waves_union([[int(srcs[0])]])[1].tolist()) == want


def test_a_bump_with_no_add_clears_the_row():
    g, srcs, deps, _extra = fan_in_graph(7, 7)
    v = int(deps[2])
    g.bump_epochs(np.array([v], np.int32))
    assert g._mirror_valid() and g.mirror_rows_kept == 0
    m = g._topo_mirror
    assert (m["h_in_src"][m["inv_perm"][v]] == m["n_tot"]).all()
    for seed in srcs[:2].tolist():
        assert v not in live_closure(g, [seed])
        assert_waves_equal_bfs(g, seed)


def test_plawdag_recaptures_patch_as_before():
    """In-degree <= 3, no collectors: every recapture is served by one patch
    batch, both mirrors stay, no rebuild, the in-sets and the waves are the
    host's. (Rows whose in-set is restored are kept where they lie; the
    device tables are then not rewritten for them.)"""
    from stl_fusion_tpu.graph.synthetic import power_law_dag

    n = 400
    src, dst = power_law_dag(n, avg_degree=3, seed=38)
    g = DeviceGraph(node_capacity=n, edge_capacity=len(src) * 4)
    g.add_nodes(n)
    g.add_edges(src, dst)
    g.build_topo_mirror()
    rng = np.random.default_rng(38)
    for i, v in enumerate(rng.choice(np.unique(dst), size=12, replace=False).tolist()):
        recapture(g, v, src[dst == v])
        assert g._mirror_valid() and g._mirror_deltas == []
        assert g.mirror_patches == i + 1 and g._topo_mirror["lat"] is not None
        assert assert_waves_equal_bfs(g, int(src[dst == v][0])) == 1
    assert g.mirror_rebuilds == 1 and g._topo_mirror.get("n_viol", 0) == 0
    assert g.mirror_rows_kept == 12
