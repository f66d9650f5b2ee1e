"""Incremental topo-mirror maintenance (VERDICT r3 #1): level-preserving
edge/epoch deltas patch the mirror tables in place — churn keeps bursts on
the depth-free mirror lane path instead of dropping to the dense BFS until
a multi-second rebuild. Unpatchable deltas (level violations, in-degree
overflow past k, post-build nodes) break the delta log and fall back to the
dense path; a rebuild restarts the log. Reference bar: the registry mutates
concurrently with reads (src/Stl.Fusion/ComputedRegistry.cs:72-105)."""
import numpy as np
import pytest

from stl_fusion_tpu.graph.device_graph import DeviceGraph


def chain_graph(n=64, build_mirror=True):
    g = DeviceGraph(node_capacity=n, edge_capacity=8 * n)
    g.add_nodes(n)
    g.add_edges(np.arange(n - 1), np.arange(1, n))
    if build_mirror:
        g.build_topo_mirror()
    return g


def dense_closure(edges_src, edges_dst, n, seeds, invalid0=None):
    """Numpy BFS oracle over live edges."""
    seen = np.zeros(n, dtype=bool) if invalid0 is None else invalid0.copy()
    newly = np.zeros(n, dtype=bool)
    frontier = [s for s in seeds if not seen[s]]
    for s in frontier:
        seen[s] = True
        newly[s] = True
    adj = {}
    for u, v in zip(edges_src, edges_dst):
        adj.setdefault(int(u), []).append(int(v))
    while frontier:
        nxt = []
        for u in frontier:
            for v in adj.get(u, ()):
                if not seen[v]:
                    seen[v] = True
                    newly[v] = True
                    nxt.append(v)
        frontier = nxt
    return int(newly.sum()), newly


def test_level_preserving_edge_add_patches_in_place():
    g = chain_graph()
    assert g.mirror_rebuilds == 1
    # new edge 10 -> 50: level(10)=10 < level(50)=50 — patchable
    g.add_edges(np.array([10]), np.array([50]))
    count, _ = g.run_waves_union([[10]])
    assert g.mirror_patches == 1 and g.mirror_rebuilds == 1
    assert g.mirror_bursts == 1  # served by the PATCHED mirror
    # oracle: chain from 10 plus the shortcut (same closure: 10..63)
    assert count == 54
    # the patched edge is real: seeding 49 reaches 50 via chain anyway;
    # check the shortcut alone by clearing and seeding node 10's new child
    g.clear_invalid()
    count2, _ = g.run_waves_union([[50]])
    assert count2 == 14  # 50..63


def test_bump_and_recapture_patches_in_place():
    g = chain_graph()
    # recompute node 30: in-edge 29->30 dies, then re-captured at new epoch
    g.bump_epochs(np.array([30]))
    g.add_edges(np.array([29]), np.array([30]))
    count, _ = g.run_waves_union([[0]])
    assert g.mirror_patches == 1 and g.mirror_rebuilds == 1
    assert g.mirror_bursts == 1
    assert count == 64  # full chain intact through the recomputed node


def test_bump_without_recapture_severs_edge():
    g = chain_graph()
    g.bump_epochs(np.array([30]))  # 29->30 dies; nothing re-captured
    count, _ = g.run_waves_union([[0]])
    assert g.mirror_patches == 1
    assert count == 30  # 0..29 — the cascade stops at the severed edge


def test_level_violating_edge_patches_with_extra_pass():
    # two parallel chains: 0..31 and 32..63
    g = DeviceGraph(node_capacity=64, edge_capacity=512)
    g.add_nodes(64)
    g.add_edges(np.arange(31), np.arange(1, 32))
    g.add_edges(np.arange(32, 63), np.arange(33, 64))
    g.build_topo_mirror()
    # 31 -> 33: acyclic, but level(31)=31 >= level(33)=1 in the frozen
    # order — patched with ONE extra sweep pass (monotone OR stays exact)
    g.add_edges(np.array([31]), np.array([33]))
    count, _ = g.run_waves_union([[0]])
    assert g.mirror_bursts == 1 and g.mirror_patches == 1
    assert g._topo_mirror["passes"] == 2
    assert count == 63  # 0..31, then 33..63 through the cross edge
    # a FORCED rebuild re-levels and resets to single-pass sweeps (the
    # maintenance move once violations accumulate; an unforced call keeps
    # returning the still-valid patched mirror)
    g.clear_invalid()
    assert g.build_topo_mirror() is g._topo_mirror and g.mirror_rebuilds == 1
    g.build_topo_mirror(force=True)
    assert g.mirror_rebuilds == 2
    assert g._topo_mirror.get("passes", 1) == 1
    count2, _ = g.run_waves_union([[0]])
    assert g.mirror_bursts == 2
    assert count2 == 63


def test_violation_chain_needs_passes_and_self_maintains():
    """A dependency path through V violating edges needs 1+V passes; past
    3 violations the mirror SELF-MAINTAINS (auto-starts the async
    re-level, keeps serving with extra passes as the bridge); past the
    hard cap of 8 the log breaks to the dense path."""
    # four parallel chains of 16 (+ a DISCONNECTED fifth, 64..79, for the
    # hard-cap leg); cross edges wire the four tail -> head
    g = DeviceGraph(node_capacity=128, edge_capacity=512)
    g.add_nodes(80)
    for c in range(5):
        b = 16 * c
        g.add_edges(np.arange(b, b + 15), np.arange(b + 1, b + 16))
    g.build_topo_mirror()
    # tail(chain c) -> head+1(chain c+1): level(tail)=15 >= level(head+1)=1
    g.add_edges(np.array([15]), np.array([17]))
    g.add_edges(np.array([31]), np.array([33]))
    count, _ = g.run_waves_union([[0]])
    assert g._topo_mirror["passes"] == 3 and g.mirror_bursts == 1
    # chain0 (16) + 17..31 (15) + 33..47 (15); heads 32/48 unreached
    assert count == 16 + 15 + 15
    # third violation still patches...
    g.clear_invalid()
    g.add_edges(np.array([47]), np.array([49]))
    c2, _ = g.run_waves_union([[0]])
    assert g._topo_mirror["passes"] == 4 and g.mirror_bursts == 2
    assert c2 == 16 + 15 + 15 + 15  # ...now 49..63 reachable via 47->49
    assert g._async_rebuild is None  # 3 violations: no maintenance yet
    # fourth STILL patches (passes=5) and auto-starts the async re-level
    # (15 -> 34: violating but acyclic — 34 is already downstream of 15)
    g.clear_invalid()
    g.add_edges(np.array([15]), np.array([34]))
    c3, _ = g.run_waves_union([[0]])
    assert g.mirror_bursts == 3 and g._topo_mirror["passes"] == 5
    assert c3 == 16 + 15 + 15 + 15  # 34 was already reached
    assert g._async_rebuild is not None, "self-maintenance did not start"
    g._async_rebuild["thread"].join(30)
    assert g.poll_topo_mirror_rebuild()
    assert g._topo_mirror.get("passes", 1) == 1  # violations dissolved
    g.clear_invalid()
    c4, _ = g.run_waves_union([[0]])
    assert c4 == c3 and g.mirror_bursts == 4
    # hard cap: 9 violating edges into the disconnected fifth chain
    # (63 -> 64..72: acyclic, and level(63) >= level(64+i) in ANY order
    # that keeps the fifth chain at its own levels) break the log
    g.clear_invalid()
    for i in range(9):
        g.add_edges(np.array([63]), np.array([64 + i]))
    c5, _ = g.run_waves_union([[0]])
    assert g.mirror_bursts == 4  # dense served it (log broke past 8)
    assert c5 == c3 + 16  # the fifth chain is reachable now


def test_in_degree_overflow_breaks():
    g = DeviceGraph(node_capacity=32, edge_capacity=256)
    g.add_nodes(8)
    g.add_edges(np.array([0, 1, 2, 3]), np.array([7, 7, 7, 7]))  # k=4 full
    g.build_topo_mirror()
    # the PATCH_SLACK free columns absorb the next two in-edges in place
    g.add_edges(np.array([4, 5]), np.array([7, 7]))
    count, _ = g.run_waves_union([[4]])
    assert g.mirror_patches == 1 and g.mirror_bursts == 1
    assert count == 2  # 4 and 7
    # the (k + slack + 1)-th in-edge finds no free slot: the log breaks
    g.clear_invalid()
    g.add_edges(np.array([6]), np.array([7]))
    count2, _ = g.run_waves_union([[6]])
    assert g.mirror_bursts == 1  # dense fallback served it
    assert count2 == 2  # 6 and 7


def test_post_build_node_edge_breaks():
    g = chain_graph(16)
    g.add_nodes(1)  # node 16 born after the build
    g.add_edges(np.array([15]), np.array([16]))
    count, _ = g.run_waves_union([[0]])
    assert g.mirror_bursts == 0  # dense path
    assert count == 17


def chain_backbone_graph(n, rng, extras, cap=4):
    """Chain 0→1→…→n-1 (so longest-path level(v) == v: ANY u<v edge is
    level-preserving for the frozen mirror) + tracked random forward edges
    keeping in-degree < cap (so patches always find a free ELL slot)."""
    g = DeviceGraph(node_capacity=n, edge_capacity=16 * n)
    g.add_nodes(n)
    g.add_edges(np.arange(n - 1), np.arange(1, n))
    indeg = np.ones(n, dtype=np.int64)
    indeg[0] = 0
    added = 0
    while added < extras:
        v = int(rng.integers(1, n))
        if indeg[v] >= cap:
            continue
        u = int(rng.integers(0, v))
        g.add_edges(np.array([u]), np.array([v]))
        indeg[v] += 1
        added += 1
    return g, indeg


def patchable_churn(g, indeg, rng, n, adds, bumps, cap=4):
    """Churn that stays on the patch path: forward edge adds under the
    in-degree cap, plus bump/recapture cycles (the scalar-recompute shape)."""
    for _ in range(adds):
        v = int(rng.integers(1, n))
        if indeg[v] >= cap:
            continue
        u = int(rng.integers(0, v))
        g.add_edges(np.array([u]), np.array([v]))
        indeg[v] += 1
    for _ in range(bumps):
        v = int(rng.integers(1, n))
        g.bump_epochs(np.array([v]))  # ALL of v's live in-edges die
        u = int(rng.integers(0, v))
        g.add_edges(np.array([u, v - 1] if u != v - 1 else [v - 1]), np.full(2 if u != v - 1 else 1, v))
        indeg[v] = 2 if u != v - 1 else 1


def test_patch_then_lane_burst_matches_oracle():
    """run_waves_lanes goes through build_topo_mirror: a patched mirror must
    serve lane bursts with per-group counts equal to the dense oracle."""
    rng = np.random.default_rng(11)
    n = 120
    g, indeg = chain_backbone_graph(n, rng, extras=100)
    g.build_topo_mirror()
    patchable_churn(g, indeg, rng, n, adds=10, bumps=5)
    groups = [rng.choice(n, size=3, replace=False).tolist() for _ in range(33)]
    counts, union_mask = g.run_waves_lanes(groups)
    assert g.mirror_patches >= 1 and g.mirror_rebuilds == 1

    # oracle over the CURRENT live edge set
    m = g.n_edges
    live = g._h_node_epoch[g._h_edge_dst[:m]] == g._h_edge_dst_epoch[:m]
    ls, ld = g._h_edge_src[:m][live], g._h_edge_dst[:m][live]
    union = np.zeros(n, dtype=bool)
    for gi, seeds in enumerate(groups):
        c, newly = dense_closure(ls, ld, n, seeds)
        assert counts[gi] == c, (gi, counts[gi], c)
        union |= newly
    np.testing.assert_array_equal(union_mask[:n], union)


def test_randomized_patch_equivalence_with_gated_state():
    """Interleave patchable churn with bursts from a DIRTY invalid state:
    the patched mirror's gated sweep must equal the dense BFS oracle that
    respects pre-existing invalidity."""
    rng = np.random.default_rng(7)
    n = 80
    g, indeg = chain_backbone_graph(n, rng, extras=80)
    g.build_topo_mirror()
    for round_ in range(6):
        patchable_churn(g, indeg, rng, n, adds=3, bumps=2)
        # oracle state BEFORE the burst
        invalid0 = g.invalid_mask().copy()
        m = g.n_edges
        live = g._h_node_epoch[g._h_edge_dst[:m]] == g._h_edge_dst_epoch[:m]
        ls, ld = g._h_edge_src[:m][live], g._h_edge_dst[:m][live]
        seeds = rng.choice(n, size=4, replace=False).tolist()
        count, newly_ids = g.run_waves_union([seeds])
        c_oracle, newly_oracle = dense_closure(ls, ld, n, seeds, invalid0)
        assert count == c_oracle, (round_, count, c_oracle)
        got = np.zeros(n, dtype=bool)
        got[newly_ids] = True
        np.testing.assert_array_equal(got, newly_oracle)
    assert g.mirror_rebuilds == 1  # every round patched, never rebuilt
    assert g.mirror_bursts == 6


def test_async_rebuild_dissolves_violations_and_catches_up():
    """The maintenance loop: violations accumulate on the patched mirror
    (multi-pass sweeps), a BACKGROUND re-level dissolves them, and deltas
    recorded while it ran catch the fresh mirror up at install."""
    # three parallel chains: 0..31, 32..63, and a DISCONNECTED 64..79
    g = DeviceGraph(node_capacity=128, edge_capacity=512)
    g.add_nodes(80)
    g.add_edges(np.arange(31), np.arange(1, 32))
    g.add_edges(np.arange(32, 63), np.arange(33, 64))
    g.add_edges(np.arange(64, 79), np.arange(65, 80))
    g.build_topo_mirror()
    g.add_edges(np.array([31]), np.array([33]))  # violating cross edge
    count, _ = g.run_waves_union([[0]])
    assert count == 63 and g._topo_mirror["passes"] == 2

    assert g.start_topo_mirror_rebuild()
    assert not g.start_topo_mirror_rebuild()  # one in flight
    # churn WHILE the rebuild runs: a bridge into the third chain (recorded
    # in the catch-up log — the rebuild's snapshot does not contain it).
    # Target 68 (level 4 in the fresh order) from 2 (level 2): patchable
    # without a violation.
    g.add_edges(np.array([2]), np.array([68]))
    g._async_rebuild["thread"].join(30)
    assert g.poll_topo_mirror_rebuild()
    assert g.mirror_rebuilds == 2
    # fresh levels dissolve the violation: single-pass sweeps again...
    g.clear_invalid()
    count2, _ = g.run_waves_union([[0]])
    assert g._topo_mirror.get("n_viol", 0) == 0
    assert g._topo_mirror.get("passes", 1) == 1
    # 0..31 + 33..63 via cross + 68..79 via the caught-up bridge
    assert count2 == 63 + 12 and g.mirror_bursts == 2
    # closure through ONLY the caught-up bridge
    g.clear_invalid()
    c3, _ = g.run_waves_union([[70]])
    assert g.mirror_bursts == 3
    assert c3 == 10  # 70..79 — third chain tail, mirrored correctly


def test_async_rebuild_superseded_by_forced_rebuild_is_discarded():
    g = chain_graph(32)
    assert g.start_topo_mirror_rebuild()
    g._async_rebuild["thread"].join(30)
    g.build_topo_mirror(force=True)  # sync rebuild wins the race
    rebuilds = g.mirror_rebuilds
    assert not g.poll_topo_mirror_rebuild()  # stale snapshot discarded
    assert g.mirror_rebuilds == rebuilds
    count, _ = g.run_waves_union([[0]])
    assert count == 32 and g.mirror_bursts == 1


def test_bump_recapture_retires_and_recounts_violations():
    """Review r4: recomputing a row with a violating in-edge must not
    accumulate n_viol forever — the bump retires the row's violations and
    the re-add counts them fresh, so passes stays at 2 and the mirror never
    breaks under sustained recompute churn of that one row."""
    g = DeviceGraph(node_capacity=64, edge_capacity=512)
    g.add_nodes(64)
    g.add_edges(np.arange(31), np.arange(1, 32))
    g.add_edges(np.arange(32, 63), np.arange(33, 64))
    g.build_topo_mirror()
    g.add_edges(np.array([31]), np.array([33]))  # violating cross edge
    assert g.run_waves_union([[0]])[0] == 63
    assert g._topo_mirror["passes"] == 2
    for cycle in range(6):  # recompute row 33 over and over
        g.clear_invalid()
        g.bump_epochs(np.array([33]))
        g.add_edges(np.array([32, 31]), np.array([33, 33]))  # recapture both
        count, _ = g.run_waves_union([[0]])
        assert count == 63, cycle
        assert g._topo_mirror["n_viol"] == 1, cycle
        assert g._topo_mirror["passes"] == 2, cycle
    assert g.mirror_rebuilds == 1 and g.mirror_bursts == 7


def test_add_edges_delta_records_unpadded_batch():
    """ADVICE r4: the incremental device-append branch pow2-pads src/dst in
    place; the mirror delta must record the REAL batch, not the padded one
    (pad repeats inflate the log toward its break thresholds)."""
    g = chain_graph()
    g.device_arrays()  # materialize: the padded incremental append path runs
    assert g._mirror_deltas == []
    g.add_edges(np.array([1, 2, 3]), np.array([10, 20, 30]))  # pads to 4
    assert len(g._mirror_deltas) == 1
    kind, (src, dst, eps) = g._mirror_deltas[0]
    assert kind == "add"
    assert src.tolist() == [1, 2, 3] and dst.tolist() == [10, 20, 30]
    assert eps.tolist() == [0, 0, 0]  # captured epochs ride the delta


def test_mirror_disk_cache_roundtrip(tmp_path, monkeypatch):
    """r5: the fingerprint-keyed mirror disk cache (restart warmth) — a
    second DeviceGraph over the same live edge set loads the built tables
    and serves oracle-identical waves, and stays patchable."""
    import time

    monkeypatch.setenv("FUSION_MIRROR_CACHE", str(tmp_path))
    n = 96
    src = np.arange(n - 1)
    dst = np.arange(1, n)

    def fresh():
        g = DeviceGraph(node_capacity=n, edge_capacity=8 * n)
        g.add_nodes(n)
        g.add_edges(src, dst)
        return g

    g1 = fresh()
    g1.build_topo_mirror()
    def landed():
        # the writer's in-progress file is <entry>.tmp.npz: not an entry yet
        return [p for p in tmp_path.glob("*.npz") if ".tmp" not in p.name]

    deadline = time.time() + 20
    while not landed() and time.time() < deadline:
        time.sleep(0.1)
    assert landed(), "background cache save did not land"

    g2 = fresh()
    g2.build_topo_mirror()
    assert g2._topo_mirror["lat"] is not None
    assert g2.mirror_cache_hits == 1
    # an entry laid out by other builder code (changed source hash) misses
    from stl_fusion_tpu.graph import device_graph

    with monkeypatch.context() as mp:
        mp.setattr(device_graph, "_mirror_builder_hash", lambda: "0" * 16)
        g3 = fresh()
        g3.build_topo_mirror()
    assert g3.mirror_cache_hits == 0 and g3.mirror_cache_misses == 1
    c1, _ = g1.run_waves_union([[10]])
    c2, _ = g2.run_waves_union([[10]])
    assert c1 == c2 == n - 10
    # a cache-loaded mirror still patches in place
    g2.add_edges(np.array([5]), np.array([60]))
    g2.clear_invalid()
    c3, _ = g2.run_waves_union([[5]])
    assert g2.mirror_patches == 1 and g2.mirror_rebuilds == 1
    assert c3 == n - 5
