"""Sharded-wave tests on the virtual 8-device CPU mesh: equivalence with the
single-device kernel and the python oracle."""
import numpy as np
import pytest

import jax

from stl_fusion_tpu.graph import DeviceGraph
from stl_fusion_tpu.parallel import ShardedDeviceGraph, graph_mesh

from test_device_graph import python_wave_oracle, random_dag


def test_mesh_has_8_virtual_devices():
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("seed", [0, 7])
def test_sharded_wave_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    n = 500
    edges = random_dag(rng, n, avg_deg=3.0)
    arr = np.asarray(edges, dtype=np.int32)

    sg = ShardedDeviceGraph(arr[:, 0], arr[:, 1], n, mesh=graph_mesh())
    seeds = rng.choice(n, size=7, replace=False).tolist()
    count = sg.run_wave(seeds)
    got = sg.invalid_mask()

    want = python_wave_oracle(
        n, edges, [0] * len(edges), np.zeros(n, np.int32), np.zeros(n, bool), seeds
    )
    np.testing.assert_array_equal(got, want)
    assert count == int(want.sum())


def test_sharded_matches_single_device():
    rng = np.random.default_rng(42)
    n = 400
    edges = random_dag(rng, n, avg_deg=4.0)
    arr = np.asarray(edges, dtype=np.int32)

    single = DeviceGraph(node_capacity=n, edge_capacity=len(edges) + 1)
    single.add_nodes(n)
    single.add_edges(arr[:, 0], arr[:, 1])

    sharded = ShardedDeviceGraph(arr[:, 0], arr[:, 1], n)

    for wave_seed in (3, 11, 200):
        seeds = rng.choice(n, size=wave_seed % 13 + 1, replace=False).tolist()
        c1 = single.run_wave(seeds)
        c2 = sharded.run_wave(seeds)
        assert c1 == c2
        np.testing.assert_array_equal(single.invalid_mask(), sharded.invalid_mask())


def test_sharded_wave_idempotent():
    edges = np.array([[0, 1], [1, 2], [2, 3]], dtype=np.int32)
    sg = ShardedDeviceGraph(edges[:, 0], edges[:, 1], 4)
    assert sg.run_wave([0]) == 4
    assert sg.run_wave([0]) == 0
    sg.clear_invalid()
    assert sg.run_wave([2]) == 2  # 2 and 3 only


@pytest.mark.parametrize("seed", [1, 13])
def test_packed_exchange_matches_bool(seed):
    rng = np.random.default_rng(seed)
    n = 613  # deliberately not a multiple of 32*n_dev
    edges = random_dag(rng, n, avg_deg=3.0)
    arr = np.asarray(edges, dtype=np.int32)
    packed = ShardedDeviceGraph(arr[:, 0], arr[:, 1], n, exchange="packed")
    plain = ShardedDeviceGraph(arr[:, 0], arr[:, 1], n, exchange="bool")
    for _ in range(3):
        seeds = rng.choice(n, size=5, replace=False).tolist()
        c1 = packed.run_wave(seeds)
        c2 = plain.run_wave(seeds)
        assert c1 == c2
        np.testing.assert_array_equal(packed.invalid_mask(), plain.invalid_mask())


def test_chained_waves_match_per_wave_runs():
    """run_waves_chained == W separate run_wave calls with resets."""
    from stl_fusion_tpu.graph.synthetic import power_law_dag

    n = 512
    (src, dst) = power_law_dag(n, avg_degree=3.0, seed=3)
    rng = np.random.default_rng(5)
    seed_mat = np.zeros((4, n), dtype=bool)
    for i in range(4):
        seed_mat[i, rng.choice(n, size=16, replace=False)] = True

    a = ShardedDeviceGraph(src, dst, n, mesh=graph_mesh())
    per_wave = []
    for i in range(4):
        a.clear_invalid()
        per_wave.append(a.run_wave(np.flatnonzero(seed_mat[i]).tolist()))

    b = ShardedDeviceGraph(src, dst, n, mesh=graph_mesh())
    total, counts = b.run_waves_chained(seed_mat)
    assert counts.tolist() == per_wave
    assert total == sum(per_wave)
    # final invalid mask equals the last per-wave run's mask
    np.testing.assert_array_equal(b.invalid_mask(), a.invalid_mask())


@pytest.mark.parametrize("seed", [0, 9])
def test_packed_sharded_wave_matches_oracle(seed):
    """32 packed waves in one mesh pass: every lane's closure equals the
    host oracle, and the totals match per-wave ShardedDeviceGraph runs."""
    from stl_fusion_tpu.parallel import PackedShardedGraph

    rng = np.random.default_rng(seed)
    n = 400
    edges = random_dag(rng, n, avg_deg=3.0)
    arr = np.asarray(edges, dtype=np.int32)
    src, dst = arr[:, 0], arr[:, 1]

    seed_lists = [rng.choice(n, size=5, replace=False).tolist() for _ in range(32)]
    pg = PackedShardedGraph(src, dst, n, mesh=graph_mesh())
    total = pg.run_waves(seed_lists)

    expected_total = 0
    for w, seeds in enumerate(seed_lists):
        want = python_wave_oracle(
            n,
            list(zip(src.tolist(), dst.tolist())),
            [0] * len(src),
            np.zeros(n, np.int32),
            np.zeros(n, bool),
            seeds,
        )
        got = pg.invalid_mask(wave=w)
        np.testing.assert_array_equal(got, want, err_msg=f"wave {w}")
        expected_total += int(want.sum())
    assert total == expected_total


def test_packed_sharded_wave_idempotent_and_incremental():
    from stl_fusion_tpu.parallel import PackedShardedGraph

    src = np.array([0, 0, 1], dtype=np.int32)
    dst = np.array([1, 2, 3], dtype=np.int32)
    pg = PackedShardedGraph(src, dst, 4, mesh=graph_mesh())
    assert pg.run_waves([[0]]) == 4
    # idempotent AND newly-lit counting: the second run lights nothing new,
    # so it reports 0 (cumulative bits are not re-counted — ADVICE r1)
    assert pg.run_waves([[0]]) == 0
    assert pg.invalid_mask().sum() == 4  # the cumulative mask is unchanged
    pg.clear_invalid()
    assert pg.run_waves([[1]]) == 2  # 1 and 3 only
    assert not pg.invalid_mask()[0] and not pg.invalid_mask()[2]


def test_packed_sharded_multiword_and_chained():
    """words=2 packs 64 waves per pass; chained batches equal separate runs."""
    from stl_fusion_tpu.parallel import PackedShardedGraph

    rng = np.random.default_rng(21)
    n = 300
    edges = random_dag(rng, n, avg_deg=3.0)
    arr = np.asarray(edges, dtype=np.int32)
    src, dst = arr[:, 0], arr[:, 1]
    seed_lists = [rng.choice(n, size=4, replace=False).tolist() for _ in range(64)]

    pg = PackedShardedGraph(src, dst, n, mesh=graph_mesh(), words=2)
    total = pg.run_waves(seed_lists)
    expected = 0
    for i, seeds in enumerate(seed_lists):
        want = python_wave_oracle(
            n, list(zip(src.tolist(), dst.tolist())), [0] * len(src),
            np.zeros(n, np.int32), np.zeros(n, bool), seeds,
        )
        np.testing.assert_array_equal(pg.invalid_mask(wave=i), want, err_msg=f"wave {i}")
        expected += int(want.sum())
    assert total == expected

    # chained batches: 2 batches of 64 == two separate cleared runs
    pg2 = PackedShardedGraph(src, dst, n, mesh=graph_mesh(), words=2)
    batch2_lists = [rng.choice(n, size=4, replace=False).tolist() for _ in range(64)]
    stacked = np.stack(
        [np.asarray(pg2.seeds_to_bits(seed_lists)), np.asarray(pg2.seeds_to_bits(batch2_lists))]
    )
    chained_total, per_batch = pg2.run_wave_batches(stacked)
    pg3 = PackedShardedGraph(src, dst, n, mesh=graph_mesh(), words=2)
    t1 = pg3.run_waves(seed_lists)
    pg3.clear_invalid()
    t2 = pg3.run_waves(batch2_lists)
    assert per_batch.tolist() == [t1, t2]
    assert chained_total == t1 + t2


# ------------------------------------------------------------------ O(wave) collect

def test_sharded_collect_matches_wave_and_mask_diff():
    """run_wave_collect returns exactly the newly-invalidated ids of the
    equivalent run_wave, with the invalid state carried RESIDENT between
    calls (the second collect sees the first one's state)."""
    import numpy as np

    from stl_fusion_tpu.parallel import ShardedDeviceGraph

    rng = np.random.default_rng(11)
    n = 500
    edges = []
    for d in range(1, n):
        for s in rng.choice(d, size=min(int(rng.integers(0, 4)), d), replace=False):
            edges.append((int(s), d))
    arr = np.asarray(edges, dtype=np.int32)

    a = ShardedDeviceGraph(arr[:, 0], arr[:, 1], n)
    b = ShardedDeviceGraph(arr[:, 0], arr[:, 1], n)

    seeds1 = rng.choice(n, size=6, replace=False).tolist()
    seeds2 = rng.choice(n, size=6, replace=False).tolist()

    before = a.invalid_mask().copy()
    c1, ids1, over1 = a.run_wave_collect(seeds1)
    assert not over1
    b.run_wave(seeds1)
    np.testing.assert_array_equal(a.invalid_mask(), b.invalid_mask())
    want1 = np.nonzero(b.invalid_mask() & ~before)[0]
    np.testing.assert_array_equal(np.sort(ids1), want1)
    assert c1 == len(want1)

    # second collect from the RESIDENT state: only genuinely-new ids return
    before2 = b.invalid_mask().copy()
    c2, ids2, over2 = a.run_wave_collect(seeds2)
    b.run_wave(seeds2)
    want2 = np.nonzero(b.invalid_mask() & ~before2)[0]
    np.testing.assert_array_equal(np.sort(ids2), want2)
    assert c2 == len(want2) and not over2


def test_sharded_collect_overflow_flag():
    """count > cap sets overflow; the caller falls back to a mask diff."""
    import numpy as np

    from stl_fusion_tpu.parallel import ShardedDeviceGraph

    n = 200
    # a chain: one seed cascades everywhere
    src = np.arange(n - 1, dtype=np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    g = ShardedDeviceGraph(src, dst, n)
    count, ids, overflow = g.run_wave_collect([0], cap=16)
    assert count == n and overflow
    assert g.invalid_mask().all()


async def test_sharded_bridge_resident_state_skips_full_sync():
    """VERDICT r2 #2: consecutive mesh bursts pay NO full invalid-state
    sync — set_invalid fires only on the first burst and after a host-led
    invalid-state change; burst results stay equal to the dense path."""
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
        tops = [await capture(lambda i=i: svc.top(i)) for i in range(6)]
        bases = [await capture(lambda i=i: svc.base(i)) for i in range(6)]

        sharded = backend.sharded_mirror()
        sync_calls = []
        orig_set_invalid = sharded.set_invalid
        sharded.set_invalid = lambda mask: (sync_calls.append(1), orig_set_invalid(mask))[1]

        assert backend.invalidate_cascade_batch_sharded([bases[0]]) == 3
        assert backend.invalidate_cascade_batch_sharded([bases[1]]) == 3
        assert backend.invalidate_cascade_batch_sharded([bases[2]]) == 3
        assert len(sync_calls) == 1  # only the FIRST burst synced

        assert bases[0].is_invalidated or backend._pending[backend.id_for(bases[0])]
        assert tops[1].is_invalidated or backend._pending[backend.id_for(tops[1])]

        # idempotence across the resident state: re-bursting an already
        # invalid seed finds nothing new
        assert backend.invalidate_cascade_batch_sharded([bases[0]]) == 0
        assert len(sync_calls) == 1

        # a NO-OP dense wave (already-invalid seed, nothing newly invalid)
        # must not force a full re-sync either (review r3)
        assert backend.invalidate_cascade_batch([bases[0]]) == 0
        assert backend.invalidate_cascade_batch_sharded([bases[5]]) == 3
        assert len(sync_calls) == 1

        # a HOST-led invalid-state change → exactly one full re-sync
        backend.graph.mark_invalid(
            np.asarray([backend.id_for(bases[3])], dtype=np.int32)
        )
        assert backend.invalidate_cascade_batch_sharded([bases[4]]) == 3
        assert len(sync_calls) == 2
        # the host-led mark was honored: base(3) reads as already invalid
        # and doesn't COUNT — but (r4 conduct-all union rule) a marked seed
        # still fires its dependents (a columnar mark's declared dependents
        # exist only in the graph): top(3)+agg re-invalidate (safe
        # over-invalidation, 2 newly), then the expansion is idempotent
        assert backend.invalidate_cascade_batch_sharded([bases[3]]) == 2
        assert backend.invalidate_cascade_batch_sharded([bases[3]]) == 0
        assert len(sync_calls) == 2
    finally:
        set_default_hub(old)


@pytest.mark.parametrize("chaos_seed", [1234, 99, 7])
async def test_sharded_bridge_chaos_interleaving(chaos_seed):
    """VERDICT r2 #8: randomized interleaving of live mutations (reads that
    recompute, host-led invalidations), mirror rebuilds, single-chip bursts,
    and mesh bursts — with a python BFS oracle asserting EXACT dense-BFS
    equivalence of every mesh burst, plus failure injection between the
    mesh wave and the host apply (the bridge must recover by re-syncing
    from the authoritative dense state)."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        capture,
        compute_method,
        invalidating,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    rng = np.random.default_rng(chaos_seed)
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub)
        K = 24

        class Chain(ComputeService):
            def __init__(self, hub=None):
                super().__init__(hub)
                self.data = {i: i for i in range(K)}

            @compute_method
            async def base(self, i: int) -> int:
                return self.data[i]

            @compute_method
            async def mid(self, i: int) -> int:
                return await self.base(i) + await self.base((i + 1) % K)

            @compute_method
            async def top(self, i: int) -> int:
                return await self.mid(i) + 1

        svc = Chain(hub)
        for i in range(K):
            await svc.top(i)

        def oracle_burst(seed_nids):
            """Expected (count, final invalid mask) of a dense union BFS
            from the CURRENT live state (post-flush host arrays)."""
            dg = backend.graph
            n, m = dg.n_nodes, dg.n_edges
            edges = list(zip(dg._h_edge_src[:m].tolist(), dg._h_edge_dst[:m].tolist()))
            final = python_wave_oracle(
                n, edges, dg._h_edge_dst_epoch[:m].tolist(),
                dg._h_node_epoch[:n], dg._h_invalid[:n].copy(), seed_nids,
            )
            count = int((final & ~dg._h_invalid[:n]).sum())
            return count, final

        async def live_computed(kind, i):
            fn = {"base": svc.base, "mid": svc.mid, "top": svc.top}[kind]
            return await capture(lambda: fn(i))

        injected = [0]
        for step in range(70):
            action = rng.choice(["burst", "read", "write", "mark", "mirror", "fail"])
            i = int(rng.integers(0, K))
            if action == "read":
                await svc.top(i)  # recomputes anything invalid → epoch bumps
            elif action == "write":
                svc.data[i] += 1
                with invalidating():
                    await svc.base(i)  # host-led journal invalidation
            elif action == "mark":
                c = await live_computed(str(rng.choice(["base", "mid"])), i)
                c.invalidate()  # host-led, outside any device wave
            elif action == "mirror":
                backend.sharded_mirror()
            elif action == "fail":
                # failure INJECTION between mesh wave and host apply: the
                # mesh state advances but the dense apply never happens;
                # the bridge must self-heal on the next burst (dense state
                # is authoritative; the entry version was never updated)
                c = await live_computed("base", i)
                sharded = backend.sharded_mirror()
                orig = sharded.run_wave_collect

                def boom(*a, **k):
                    sharded.run_wave_collect = orig
                    orig(*a, **k)  # the mesh wave RUNS...
                    raise ConnectionError("injected between wave and apply")

                sharded.run_wave_collect = boom
                with pytest.raises(ConnectionError):
                    backend.invalidate_cascade_batch_sharded([c])
                injected[0] += 1
                # DETERMINISTIC self-heal check (review r3: with the wrong
                # protocol this only passed when an unrelated action bumped
                # the version first): retrying the SAME seed immediately
                # must still produce the oracle cascade — the entry was
                # marked stale before the wave, so the retry re-syncs from
                # the authoritative dense state instead of finding the
                # mesh already-invalid and dropping the cascade
                backend.flush()
                want_count, want_mask = oracle_burst([backend.id_for(c)])
                got = backend.invalidate_cascade_batch_sharded([c])
                assert got == want_count, (step, "post-injection", got, want_count)
                np.testing.assert_array_equal(
                    backend.graph._h_invalid[: backend.graph.n_nodes], want_mask
                )
            else:  # burst — the assertion step
                kinds = rng.choice(["base", "mid", "top"], size=int(rng.integers(1, 4)))
                cs = [await live_computed(str(k), int(rng.integers(0, K))) for k in kinds]
                backend.flush()
                seed_nids = [backend.id_for(c) for c in cs]
                assert all(s is not None for s in seed_nids)
                want_count, want_mask = oracle_burst(seed_nids)
                if rng.random() < 0.5:
                    got = backend.invalidate_cascade_batch_sharded(cs)
                else:
                    got = backend.invalidate_cascade_batch(cs)
                assert got == want_count, (step, action, got, want_count)
                dg = backend.graph
                np.testing.assert_array_equal(
                    dg._h_invalid[: dg.n_nodes], want_mask, err_msg=f"step {step}"
                )
                np.testing.assert_array_equal(
                    dg.invalid_mask(), want_mask, err_msg=f"step {step} (device)"
                )
        assert injected[0] > 0, "chaos run never exercised the failure injection"
    finally:
        set_default_hub(old)


# ------------------------------------------------------------ mesh lane bursts

async def test_mesh_lane_burst_matches_single_chip_lanes():
    """invalidate_cascade_batch_lanes_sharded ≡ the single-chip lane path:
    same per-group counts and same applied state, from the same pre-state,
    including pre-existing invalidations and a recompute in between."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        capture,
        compute_method,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend

    async def build():
        hub = FusionHub()
        old = set_default_hub(hub)
        backend = TpuGraphBackend(hub)

        class Chain(ComputeService):
            def __init__(self, hub=None):
                super().__init__(hub)
                self.data = {i: i for i in range(16)}

            @compute_method
            async def base(self, i: int) -> int:
                return self.data[i]

            @compute_method
            async def mid(self, i: int) -> int:
                return await self.base(i) + await self.base((i + 1) % 16)

            @compute_method
            async def top(self, i: int) -> int:
                return await self.mid(i) + 1

        svc = Chain(hub)
        for i in range(16):
            await svc.top(i)
        bases = [await capture(lambda i=i: svc.base(i)) for i in range(16)]
        # a pre-existing invalidation the lanes must treat as blocked
        bases[3].invalidate()
        return hub, old, backend, svc, bases

    hub_m, old, backend_m, svc_m, bases_m = await build()
    try:
        groups = [[bases_m[0]], [bases_m[3], bases_m[5]], [], [bases_m[0], bases_m[7]]]
        counts_m = backend_m.invalidate_cascade_batch_lanes_sharded(groups)
        state_m = backend_m.graph._h_invalid[: backend_m.graph.n_nodes].copy()
    finally:
        set_default_hub(old)

    hub_s, old, backend_s, svc_s, bases_s = await build()
    try:
        groups = [[bases_s[0]], [bases_s[3], bases_s[5]], [], [bases_s[0], bases_s[7]]]
        counts_s = backend_s.invalidate_cascade_batch_lanes(groups)
        state_s = backend_s.graph._h_invalid[: backend_s.graph.n_nodes].copy()
    finally:
        set_default_hub(old)

    np.testing.assert_array_equal(counts_m, counts_s)
    np.testing.assert_array_equal(state_m, state_s)


async def test_mesh_lane_burst_resident_blocked_state():
    """Consecutive mesh lane bursts ride the resident blocked mask (no full
    sync), a host-led change forces exactly one re-sync, and idempotence
    holds across the resident state."""
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
            async def top(self, i: int) -> int:
                return await self.base(i) + 1

        svc = Chain(hub=hub)
        tops = [await capture(lambda i=i: svc.top(i)) for i in range(8)]
        bases = [await capture(lambda i=i: svc.base(i)) for i in range(8)]

        assert backend.invalidate_cascade_batch_lanes_sharded([[bases[0]]]).tolist() == [2]
        entry = backend._packed_mirror
        assert "invalid_version" in entry
        v = entry["invalid_version"]
        # second burst: resident state, no rebuild, version advances in step
        assert backend.invalidate_cascade_batch_lanes_sharded([[bases[1]]]).tolist() == [2]
        assert backend._packed_mirror is entry
        assert entry["invalid_version"] != v
        # idempotence: blocked seeds produce empty lanes
        assert backend.invalidate_cascade_batch_lanes_sharded([[bases[0]]]).tolist() == [0]
        assert tops[0].is_invalidated or backend._pending[backend.id_for(tops[0])]

        # host-led mark → resync; burst on ANOTHER seed still exact
        backend.graph.mark_invalid(
            np.asarray([backend.id_for(bases[2])], dtype=np.int32)
        )
        assert backend.invalidate_cascade_batch_lanes_sharded([[bases[3]]]).tolist() == [2]
        # the host-led mark is honored: the marked seed doesn't count, but
        # (r4 conduct-all) it still fires its dependent chain — top(2)
        # re-invalidates (safe over-invalidation), then idempotence holds
        assert backend.invalidate_cascade_batch_lanes_sharded([[bases[2]]]).tolist() == [1]
        assert backend.invalidate_cascade_batch_lanes_sharded([[bases[2]]]).tolist() == [0]
    finally:
        set_default_hub(old)


async def test_packed_mirror_patches_structural_churn():
    """VERDICT r4 #4: structural churn must PATCH the packed mesh mirror
    in place (bump epochs scattered, adds spliced into slack slots) —
    lane bursts keep serving oracle-exact counts on the churned topology
    with no rebuild; only slot overflow breaks to a rebuild."""
    from stl_fusion_tpu.core import FusionHub, set_default_hub
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        n = 400
        backend = TpuGraphBackend(hub, node_capacity=n, edge_capacity=16 * n)
        dg = backend.graph
        dg.add_nodes(n)
        dg.add_edges(np.arange(n - 1), np.arange(1, n))  # chain
        mesh = graph_mesh()

        def lanes(groups):
            seed_lists = [list(g) for g in groups]
            return backend._lanes_sharded_nids(seed_lists, mesh)

        counts = lanes([[0], [n // 2]])
        assert counts.tolist() == [n, n - n // 2]
        entry0 = backend._packed_mirror
        pg = entry0["graph"]
        dg.clear_invalid()

        # add: a shortcut patches in place
        dg.add_edges(np.array([10]), np.array([300]))
        counts = lanes([[10]])
        assert backend._packed_mirror is entry0 and pg.patches >= 1
        assert counts.tolist() == [n - 10]  # 10..n-1 via chain + shortcut
        dg.clear_invalid()

        # bump: severs 150's chain in-edge on the mesh (epoch scatter)
        dg.bump_epochs(np.array([150]))
        counts = lanes([[20]])
        assert backend._packed_mirror is entry0
        # 20..149 via the chain; the severed edge stops the wave (the
        # 10→300 shortcut is upstream of this seed and can't fire)
        assert counts.tolist() == [130]
        dg.clear_invalid()

        # bump + recapture at the new epoch: chain restored
        dg.add_edges(np.array([149]), np.array([150]))
        counts = lanes([[20]])
        assert backend._packed_mirror is entry0
        assert counts.tolist() == [n - 20]
        dg.clear_invalid()

        # slot overflow (k + slack new in-edges on one row) → rebuild
        width = pg.k
        srcs = np.arange(width + 1, dtype=np.int64)
        dg.add_edges(srcs, np.full(width + 1, 399, dtype=np.int64))
        counts = lanes([[399]])
        assert counts.tolist() == [1]  # 399 is terminal either way
        assert backend._packed_mirror is not entry0  # rebuilt
        # and the rebuilt mirror serves the full churned topology
        dg.clear_invalid()
        counts = lanes([[0]])
        assert counts.tolist() == [n]
    finally:
        set_default_hub(old)
