"""Cluster-routed CSR shards (ISSUE 9): placement geometry, collective
frontier-exchange equivalence, device-shard moves, batched patches,
per-shard snapshots, and the live backend/pipeline composition — all on
the virtual 8-device CPU mesh."""
import asyncio

import numpy as np
import pytest

from stl_fusion_tpu.cluster import DevicePlacement, ShardMap
from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.parallel import RoutedShardedGraph, graph_mesh


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


# ---------------------------------------------------------------- placement
def test_placement_geometry_and_determinism():
    smap = ShardMap.initial(["a", "b"], n_shards=64)
    p1 = DevicePlacement.build(smap, 8, 10_000)
    p2 = DevicePlacement.build(smap, 8, 10_000)
    assert np.array_equal(p1.shard_dev, p2.shard_dev)
    assert np.array_equal(p1.shard_slot, p2.shard_slot)
    assert p1.slot_rows % 32 == 0
    # every shard on-mesh, each on one of its owner's devices
    assignment = smap.assignment
    for s in range(64):
        d = int(p1.shard_dev[s])
        assert d >= 0
        assert p1.member_of_device(d) == assignment[s]
    perm, inv = p1.permutation()
    assert (perm >= 0).all()
    # perm/inv are mutual inverses over real nodes
    assert np.array_equal(inv[perm], np.arange(10_000))


def test_placement_move_keeps_unmoved_slots_and_same_dev_shards():
    smap = ShardMap.initial(["a", "b"], n_shards=64)
    p1 = DevicePlacement.build(smap, 8, 10_000)
    new_map = smap.with_members(["a"])
    p2, moves = p1.moved_to(new_map, mesh_members=["a"])
    moved = set(ShardMap.diff(smap, new_map))
    assert moves  # a kill moves the departed member's shards
    moved_in_list = {m[0] for m in moves}
    for s in range(64):
        if s not in moved:
            # unmoved shards NEVER relocate
            assert p2.shard_dev[s] == p1.shard_dev[s]
            assert p2.shard_slot[s] == p1.shard_slot[s]
        elif s not in moved_in_list:
            # a moved shard whose rendezvous device is unchanged keeps its
            # slot outright (the silent-slot-reassignment regression)
            assert p2.shard_dev[s] == p1.shard_dev[s]
            assert p2.shard_slot[s] == p1.shard_slot[s]
    # no two shards share a (dev, slot)
    pairs = {(int(d), int(k)) for d, k in zip(p2.shard_dev, p2.shard_slot) if d >= 0}
    assert len(pairs) == int((p2.shard_dev >= 0).sum())


def test_placement_off_mesh_members_have_no_slots():
    smap = ShardMap.initial(["a", "b", "c", "d"], n_shards=64)
    p = DevicePlacement.build(smap, 8, 5_000, mesh_members=["a", "b"])
    assignment = smap.assignment
    for s in range(64):
        on = assignment[s] in ("a", "b")
        assert p.on_mesh(s) == on
    perm, _inv = p.permutation()
    # nodes of off-mesh shards have no device row
    off = [s for s in range(64) if not p.on_mesh(s)]
    if off:
        s = off[0]
        lo = s * p.ids_per_shard
        assert perm[lo] == -1


# ---------------------------------------------------------------- host axis
def test_placement_host_axis_geometry():
    smap = ShardMap.initial(["a", "b"], n_shards=64)
    p = DevicePlacement.build(smap, 8, 10_000, devices_per_host=4)
    assert p.n_hosts == 2 and p.devices_per_host == 4
    assert p.host_of_device(0) == 0 and p.host_of_device(3) == 0
    assert p.host_of_device(4) == 1 and p.host_of_device(7) == 1
    snap = p.snapshot()
    assert snap["hosts"] == 2 and snap["devices_per_host"] == 4
    # default: every device one host (the pre-multihost shape)
    p1 = DevicePlacement.build(smap, 8, 10_000)
    assert p1.n_hosts == 1 and p1.host_of_device(7) == 0
    with pytest.raises(Exception):
        DevicePlacement.build(smap, 8, 10_000, devices_per_host=3)


def test_placement_host_aware_moves_prefer_same_host_and_are_deterministic():
    """ISSUE 15 satellite: a reshard must not needlessly turn an
    intra-host slot reassignment into a cross-host DCN transfer — moved
    shards land on a same-host device of the new owner whenever one has a
    free slot, deterministically."""
    smap = ShardMap.initial(["a", "b"], n_shards=64)
    pl = DevicePlacement.build(smap, 8, 10_000, devices_per_host=4, slot_headroom=3.0)
    # kill b: member a absorbs every device range, so b's shards (resident
    # on host-1 devices 4-7) have same-host candidates under the new owner
    m2 = smap.with_members(["a"])
    p2a, moves_a = pl.moved_to(m2, mesh_members=["a"])
    p2b, moves_b = pl.moved_to(m2, mesh_members=["a"])
    # determinism: identical placements + move lists across derivations
    assert moves_a == moves_b
    assert np.array_equal(p2a.shard_dev, p2b.shard_dev)
    assert np.array_equal(p2a.shard_slot, p2b.shard_slot)
    # host preference: with generous slot headroom NO move crosses hosts
    assert pl.cross_host_moves(moves_a) == 0
    for s, old, new in moves_a:
        assert pl.host_of_device(old) == pl.host_of_device(new)
    assert p2a.devices_per_host == 4  # the host axis survives the epoch


# ---------------------------------------------------------------- waves
@pytest.mark.parametrize("exchange", ["a2a", "tree", "gather"])
def test_routed_wave_matches_bfs_oracle(exchange):
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=graph_mesh(), exchange=exchange)
    rng = np.random.default_rng(1)
    seeds = rng.choice(n, size=5, replace=False).tolist()
    count, ids, over = g.run_wave_collect(seeds)
    assert not over
    want = bfs_closure(adj, seeds)
    assert set(ids.tolist()) == want
    assert count == len(want)
    # idempotence: the union is resident on device
    c2, _ids2, _ = g.run_wave_collect(seeds[:2])
    assert c2 == 0
    assert g.levels_total > 0  # collective exchange rounds were counted


# ------------------------------------------------- the level's two rules
EXCHANGES = ["a2a", "tree", "gather", "hier"]
LEVEL_RULE_CASES = [
    "stale_and_invalid", "invalid_seed_conducts", "bump_between_waves",
    "second_wave_no_restore",
]


class HostLevels:
    """The routed wave's rules on the host, level by level: an edge
    conducts only while its captured epoch equals its destination's node
    epoch; an already-invalid destination is neither counted nor re-lit;
    a seed conducts even when it is already invalid (the union rule)."""

    def __init__(self, src, dst, n, invalid=None):
        self.src = np.asarray(src, dtype=np.int64)
        self.dst = np.asarray(dst, dtype=np.int64)
        self.eep = np.zeros(len(self.src), dtype=np.int32)
        self.nepoch = np.zeros(n, dtype=np.int32)
        self.inv = np.zeros(n, dtype=bool) if invalid is None else invalid.copy()

    def patch(self, bump_ids, add_u, add_v, add_ep):
        np.add.at(self.nepoch, np.asarray(bump_ids, dtype=np.int64), 1)
        self.src = np.concatenate([self.src, np.asarray(add_u, dtype=np.int64)])
        self.dst = np.concatenate([self.dst, np.asarray(add_v, dtype=np.int64)])
        self.eep = np.concatenate([self.eep, np.asarray(add_ep, dtype=np.int32)])

    def wave(self, seeds, versions=True, skip_invalid=True, commit=True):
        """(count, levels, newly ids ascending, mask after). ``slot_visits``
        is then what the levels read: at each level the live slots whose
        source has been in no earlier frontier (ISSUE 37: a slot leaves
        the wave once it has fired)."""
        live = self.nepoch[self.dst] == self.eep if versions else slice(None)
        src, dst = self.src[live], self.dst[live]
        blocked = self.inv if skip_invalid else np.zeros_like(self.inv)
        frontier = np.zeros_like(self.inv)
        frontier[np.asarray(seeds, dtype=np.int64)] = True
        lit = frontier.copy()
        fired = np.zeros_like(lit)  # rows that have been in a frontier
        levels = 0
        self.slot_visits = 0
        while frontier.any():
            self.slot_visits += int(np.count_nonzero(~fired[src]))
            fired |= frontier
            hit = np.zeros_like(lit)
            hit[dst[frontier[src]]] = True
            frontier = hit & ~lit & ~blocked
            lit |= frontier
            levels += 1
        newly = lit & ~self.inv
        mask = self.inv | lit
        if commit:
            self.inv = mask
        return int(newly.sum()), levels, np.flatnonzero(newly), mask


def live_slots(g):
    """Per device, the slots of its edge slice that a wave's worklist
    starts with: neither pads nor behind their destination's version."""
    nepoch = np.asarray(g.g_node_epoch).reshape(g.n_dev, g.n_local)
    edst = g._h_edst.reshape(g.n_dev, g.e_cap)
    eep = g._h_eep.reshape(g.n_dev, g.e_cap)
    rows = np.minimum(edst, g.n_local - 1)  # a pad's gather clamps; -1 never matches
    return (np.take_along_axis(nepoch, rows, axis=1) == eep).sum(axis=1)


def check_level_rules(exchange, case, async_depth=0):
    """One case of the level's rules on the device against
    :class:`HostLevels`: count, compacted newly ids, the mask and (sync
    mode: an async merge epoch is no BFS level) the number of levels and
    the slots they read."""
    n = 4000
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(
        smap, 8, n, devices_per_host=4 if exchange == "hier" else None
    )
    rng = np.random.default_rng(33)
    pre = np.zeros(n, dtype=bool)
    if case == "invalid_seed_conducts":
        # a chain over five shards and a pair; 2005 and 700 invalid before
        src = np.array([5, 1005, 2005, 3005, 700], dtype=np.int64)
        dst = np.array([1005, 2005, 3005, 3905, 2700], dtype=np.int64)
        pre[[2005, 700]] = True
    else:
        src, dst, _adj = make_graph(n, seed=7)
        pre[rng.choice(n, size=300, replace=False)] = True
    host = HostLevels(src, dst, n, invalid=pre)
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), exchange=exchange, invalid=pre,
        exchange_async=async_depth > 0, async_depth=async_depth,
    )
    assert g.exchange == exchange

    def patch(n_bump, n_redeclare):
        """Bump rows (every in-edge of theirs goes stale) and declare one
        in-edge of some of them again at the new epoch."""
        has_in = np.unique(host.dst)
        bumped = rng.choice(has_in, size=n_bump, replace=False)
        again = bumped[:n_redeclare]
        first_in = dict(zip(host.dst[::-1].tolist(), host.src[::-1].tolist()))
        u = np.array([first_in[v] for v in again.tolist()], dtype=np.int64)
        ep = host.nepoch[again] + 1
        host.patch(bumped, u, again, ep)
        assert g.patch_batch(bumped, u, again, ep.astype(np.int32))

    def wave(seeds):
        levels0, visits0 = g.levels_total, g.slot_visits_total
        want_count, want_levels, want_ids, want_mask = host.wave(seeds)
        count, ids, over = g.run_wave_collect(seeds)
        assert not over
        assert count == want_count
        assert np.array_equal(np.sort(ids), want_ids)
        assert np.array_equal(g.invalid_mask(), want_mask)
        levels, visits = g.levels_total - levels0, g.slot_visits_total - visits0
        if async_depth:
            # a merge epoch fires the ever-lit rows: no BFS level, so its
            # reads are bounded, not the host's
            assert 1 <= levels <= want_levels
            assert 0 < visits <= levels * int(live_slots(g).sum())
        else:
            assert levels == want_levels
            assert visits == host.slot_visits
        return want_count, want_ids, want_mask

    if case == "stale_and_invalid":
        patch(n_bump=400, n_redeclare=100)
        seeds = rng.choice(n // 10, size=5, replace=False).tolist()
        # the scenario tells the rules apart: dropping either changes the mask
        for off in ("versions", "skip_invalid"):
            other = host.wave(seeds, commit=False, **{off: False})[3]
            assert not np.array_equal(other, host.wave(seeds, commit=False)[3])
        wave(seeds)
    elif case == "invalid_seed_conducts":
        # 700 is invalid and conducts to 2700; 2005 is invalid already:
        # not counted, not lit again, so 3005 and 3905 stay valid
        count, ids, mask = wave([5, 700])
        assert count == 3 and ids.tolist() == [5, 1005, 2700]
        assert np.flatnonzero(mask).tolist() == [5, 700, 1005, 2005, 2700]
        count, ids, mask = wave([2005])  # as a seed it conducts
        assert count == 2 and ids.tolist() == [3005, 3905]
    elif case == "second_wave_no_restore":
        # ISSUE 37: the worklist is a wave's own value. The first wave's
        # closure (a seed of it invalid before) is the second's ``inv`` at
        # entry: slots that fired then start in the worklist again, their
        # destinations stay dark, and the counts add up
        patch(n_bump=200, n_redeclare=50)
        first = rng.choice(n // 10, size=4, replace=False).tolist()
        wave(first + np.flatnonzero(pre)[:1].tolist())
        wave(rng.choice(n // 10, size=4, replace=False).tolist() + first[:2])
    else:
        wave(rng.choice(np.arange(n // 2, n), size=5, replace=False).tolist())
        seeds = rng.choice(n // 10, size=5, replace=False).tolist()
        before = host.wave(seeds, commit=False)[3]
        patch(n_bump=400, n_redeclare=100)  # between the waves
        assert not np.array_equal(before, host.wave(seeds, commit=False)[3])
        wave(seeds)
        patch(n_bump=200, n_redeclare=200)  # every bumped row declared again
        wave(rng.choice(n // 10, size=5, replace=False).tolist())


@pytest.mark.parametrize("case", LEVEL_RULE_CASES)
@pytest.mark.parametrize("exchange", EXCHANGES)
def test_level_rules_match_host_levels(exchange, case):
    """ISSUE 33: the version check is hoisted out of the level loop (once
    a wave, not once a graph) and the already-invalid mask is applied on
    node rows after the scatter; both rules hold on every exchange."""
    check_level_rules(exchange, case)


def check_worklist_chain(exchange, async_depth=0):
    """ISSUE 37: the worklist spans several chunks with a partial last one
    (pads behind it, slots behind their destination's version out of it),
    and the chain builds one per stage: three stages in ONE dispatch, the
    first two sharing seeds, against :class:`HostLevels` stage by stage,
    then a single wave on top with no restore between."""
    from stl_fusion_tpu.parallel.routed_wave import _chunk_width

    n = 4000
    pl = DevicePlacement.build(
        ShardMap.initial(["a", "b"], n_shards=32), 8, n,
        devices_per_host=4 if exchange == "hier" else None,
    )
    rng = np.random.default_rng(37)
    src, dst, _adj = make_graph(n, seed=5)
    pre = np.zeros(n, dtype=bool)
    pre[rng.choice(n, size=200, replace=False)] = True
    host = HostLevels(src, dst, n, invalid=pre)
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), exchange=exchange, invalid=pre,
        exchange_async=async_depth > 0, async_depth=async_depth,
    )
    bumped = rng.choice(np.unique(dst), size=300, replace=False)
    host.patch(bumped, [], [], [])
    assert g.patch_batch(bumped, np.empty(0, np.int64), np.empty(0, np.int64),
                         np.empty(0, np.int32))
    chunk, live = _chunk_width(g.e_cap), live_slots(g)
    # every chip's worklist spans chunks and ends inside one
    assert (live > chunk).all() and (live.max() > 4 * chunk) and (live % chunk != 0).all()
    assert (live < (g._h_eep.reshape(g.n_dev, -1) >= 0).sum(axis=1)).all()  # stale
    assert live.max() < g.e_cap  # pads

    low = rng.choice(n // 10, size=6, replace=False).tolist()
    stages = [low[:3], low[2:], rng.choice(n, size=3, replace=False).tolist()]
    want = [host.wave(st) + (host.slot_visits,) for st in stages]
    counts, stage_ids, info = g.harvest_union_chain(g.dispatch_union_chain(stages))
    assert not info["overflowed"]
    for (count, levels, ids, _mask, _v), c, got, lv in zip(
        want, counts, stage_ids, info["levels"].ravel()
    ):
        assert int(c) == count and np.array_equal(np.sort(got), ids)
        assert int(lv) == levels if not async_depth else 1 <= int(lv) <= max(levels, 1)
    assert np.array_equal(g.invalid_mask(), want[-1][3])
    levels = sum(w[1] for w in want)
    if not async_depth:
        assert g.levels_total == levels
        assert g.slot_visits_total == sum(w[4] for w in want)
    assert 0 < g.slot_visits_total < g.levels_total * g.e_cap * g.n_dev  # dense
    assert g.stats()["slot_visits_total"] == g.slot_visits_total
    seeds = rng.choice(n // 10, size=4, replace=False).tolist()
    count, _levels, ids, mask = host.wave(seeds)
    got_count, got, over = g.run_wave_collect(seeds)
    assert not over and got_count == count and np.array_equal(np.sort(got), ids)
    assert np.array_equal(g.invalid_mask(), mask)


@pytest.mark.parametrize("exchange", EXCHANGES)
def test_worklist_spans_chunks_and_chain_counts_visits(exchange):
    check_worklist_chain(exchange)


WORKLIST_WRITERS = ["patch", "patch_resize", "placement", "snapshot"]


@pytest.mark.parametrize("writer", WORKLIST_WRITERS)
@pytest.mark.parametrize("exchange", EXCHANGES)
def test_resident_worklist_follows_every_writer(exchange, writer):
    """A wave starts from the RESIDENT worklist, built by the
    first wave and again only after an array it is computed from was
    replaced. After construction and after each writer (``patch_batch``'s
    bumps and adds; a ``patch_batch`` whose adds overflow the edge slack,
    which grows in place; a kill and a join moved by ``apply_placement``;
    ``import_shard_state``) the worklist the next wave used equals a fresh
    build exactly, and ``n_live`` counts the live slots on the host; a
    wave between two writers builds nothing; every wave matches
    :class:`HostLevels`, its levels and slot visits included."""
    n = 4000
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(
        smap, 8, n, slot_headroom=3.0,
        devices_per_host=4 if exchange == "hier" else None,
    )
    rng = np.random.default_rng(43)
    src, dst, _adj = make_graph(n, seed=13)
    pre = np.zeros(n, dtype=bool)
    pre[rng.choice(n, size=150, replace=False)] = True
    host = HostLevels(src, dst, n, invalid=pre)
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), exchange=exchange, invalid=pre,
        edge_headroom=1.3 if writer == "patch_resize" else 2.5,
        bucket_headroom=2.5,
    )
    assert g.exchange == exchange

    def waves(builds):
        """Two waves: the first rebuilt the worklist when a writer dropped
        it, the second reuses it."""
        for _ in range(2):
            seeds = rng.choice(n // 10, size=4, replace=False).tolist()
            levels0, visits0 = g.levels_total, g.slot_visits_total
            want_count, want_levels, want_ids, want_mask = host.wave(seeds)
            count, ids, over = g.run_wave_collect(seeds)
            assert not over and count == want_count
            assert np.array_equal(np.sort(ids), want_ids)
            assert np.array_equal(g.invalid_mask(), want_mask)
            assert g.levels_total - levels0 == want_levels
            assert g.slot_visits_total - visits0 == host.slot_visits
            assert g.worklist_builds == builds == g.stats()["worklist_builds"]
            fresh = g._worklist(
                g.g_send, g.g_hsend, g.g_eprod, g.g_ebslot, g.g_ebit, g.g_edst,
                g.g_eep, g.g_node_epoch,
            )
            for got, want in zip(g.g_work, fresh):
                assert np.array_equal(np.asarray(got), np.asarray(want))
            assert np.array_equal(np.asarray(g.g_work[2]), live_slots(g))

    def bump(n_bump, n_redeclare):
        """Bump rows (their in-edges go stale) and declare one in-edge of
        some of them again at the new epoch."""
        bumped = rng.choice(np.unique(host.dst), size=n_bump, replace=False)
        again = bumped[:n_redeclare]
        first_in = dict(zip(host.dst[::-1].tolist(), host.src[::-1].tolist()))
        u = np.array([first_in[v] for v in again.tolist()], dtype=np.int64)
        ep = host.nepoch[again] + 1
        host.patch(bumped, u, again, ep)
        assert g.patch_batch(bumped, u, again, ep.astype(np.int32))

    assert g.worklist_builds == 0 and g.g_work is None
    waves(builds=1)
    if writer == "patch":
        bump(n_bump=200, n_redeclare=50)
        assert g.g_work is None
        waves(builds=2)
    elif writer == "patch_resize":
        # adds into one row, more than its device's edge slack holds
        v = n - 1
        d = int(g.perm[v]) // g.n_local
        k = g.e_cap - int(g._dev_edge_count[d]) + 1
        u = rng.integers(0, v, size=k)
        ep = np.full(k, host.nepoch[v], dtype=np.int32)
        host.patch([], u, np.full(k, v), ep)
        e_cap = g.e_cap
        assert g.patch_batch(np.empty(0, np.int64), u, np.full(k, v), ep)
        assert g.resize_detail["edge"] >= 1 and g.e_cap > e_cap
        assert g.g_work is None
        waves(builds=2)
    elif writer == "placement":
        m2 = smap.with_members(["a"])
        pl2, moves = pl.moved_to(m2, mesh_members=["a"])
        assert moves
        g.apply_placement(pl2, moves)
        assert g.g_work is None
        waves(builds=2)
        pl3, moves3 = pl2.moved_to(m2.with_members(["a", "c"]), mesh_members=["a", "c"])
        assert moves3
        g.apply_placement(pl3, moves3)
        assert g.g_work is None
        waves(builds=3)
    else:
        snap = g.export_shard_state()
        saved = host.nepoch.copy(), host.inv.copy()
        bump(n_bump=200, n_redeclare=0)
        waves(builds=2)
        assert g.import_shard_state(snap) == 32
        host.nepoch, host.inv = saved
        assert g.g_work is None
        waves(builds=3)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _named(jaxpr, *prefixes):
    return [e for e in _eqns(jaxpr) if e.primitive.name.startswith(prefixes)]


def test_level_loop_reads_each_edge_slot_once():
    """Structural: the level loop (the a2a sync
    wave's outer ``while``) holds ONE inner loop, over the chunks of the
    worklist; a chunk is exactly one indexed read of the exchanged words
    and one scatter, both of chunk width. Nothing of the width of the
    edge slice is read or scattered inside a level, and no node-row table
    is read per slot. Nor once a wave: the version check's gather and the
    worklist's sort are the worklist program's, and the wave program
    holds no gather from a node-row table at edge width and no sort of
    edge width outside its level loop. A per-edge lookup put back inside
    the level, or the worklist's build put back into the wave, fails here,
    not in a benchmark."""
    import jax

    from stl_fusion_tpu.parallel.routed_wave import _chunk_width

    n = 4000
    src, dst, _adj = make_graph(n)
    pl = DevicePlacement.build(ShardMap.initial(["a", "b"], n_shards=32), 8, n)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=graph_mesh(), exchange="a2a")
    chunk = _chunk_width(g.e_cap)
    assert g.e_cap not in (g.n_local, g.w_local, g.g_send.shape[-1], chunk)
    assert chunk not in (g.n_local, g.w_local, g.g_send.shape[-1])
    g._ensure_worklist()
    jaxpr = jax.make_jaxpr(g._wave)(
        g.g_invalid, g.g_send, g.g_hsend, g.g_work, g.g_invalid,
    )
    # two loops in the program: the level loop and, in its body, the chunks'
    whiles = _named(jaxpr.jaxpr, "while")
    assert len(whiles) == 2
    (level,) = [
        w.params["body_jaxpr"].jaxpr for w in whiles
        if _named(w.params["body_jaxpr"].jaxpr, "while")
    ]
    (chunk_loop,) = _named(level, "while")  # its trip count a carried value
    chunk_body = chunk_loop.params["body_jaxpr"].jaxpr
    # the level outside its chunk loop: the pack and the send buckets,
    # nothing per edge slot and nothing that looks a node row up
    in_chunk = {id(e) for e in _eqns(chunk_body)}
    outside = [e for e in _named(level, "gather", "scatter") if id(e) not in in_chunk]
    assert [e.primitive.name for e in outside] == ["gather"]  # words_p[send_idx]
    assert outside[0].invars[1].aval.shape[:2] == (g.n_dev, g.g_send.shape[-1])
    # a chunk: one read of the exchanged words, one scatter, chunk wide
    (read,) = _named(chunk_body, "gather")
    assert read.invars[1].aval.shape[0] == chunk
    assert read.invars[0].aval.dtype == np.uint32
    assert read.invars[0].aval.shape == (g.n_dev * g.g_send.shape[-1],)
    (scatter,) = _named(chunk_body, "scatter")
    assert scatter.invars[1].aval.shape[0] == chunk
    assert scatter.invars[0].aval.shape == (g.n_local,)
    # one sort a chunk serves the scatter (its indices arrive sorted, so the
    # compiler adds none of its own) and the partition
    (sort,) = _named(chunk_body, "sort")
    assert sort.invars[0].aval.shape == (chunk,) and len(sort.invars) == 2
    assert scatter.params["indices_are_sorted"]
    # and nothing as wide as the edge slice is read, scattered or sorted
    padded = -(-g.e_cap // chunk) * chunk
    for e in _named(level, "gather", "scatter", "sort"):
        for v in e.invars:
            assert g.e_cap not in v.aval.shape and padded not in v.aval.shape, e
    # outside the level loop, the wave neither reads a node row per edge
    # slot nor sorts the edge slice: it starts from the resident worklist
    in_level = {id(e) for e in _eqns(level)}
    once = [e for e in _named(jaxpr.jaxpr, "gather", "sort") if id(e) not in in_level]
    for e in once:
        widths = {d for v in e.invars for d in v.aval.shape}
        assert not widths & {g.e_cap, padded}, e
    # both are the worklist program's: ONE gather of the node epochs at
    # edge width (the version check) and ONE sort of the edge slice
    wl = jax.make_jaxpr(g._worklist)(
        g.g_send, g.g_hsend, g.g_eprod, g.g_ebslot, g.g_ebit, g.g_edst, g.g_eep,
        g.g_node_epoch,
    )
    (check,) = _named(wl.jaxpr, "gather")
    assert check.invars[0].aval.shape == (g.n_local,)
    assert check.invars[1].aval.shape[0] == g.e_cap
    (sort,) = _named(wl.jaxpr, "sort")
    assert sort.invars[0].aval.shape == (g.e_cap,) and len(sort.invars) == 2


@pytest.mark.parametrize("dph", [2, 4])
def test_hier_exchange_matches_bfs_oracle_and_counts_cross_words(dph):
    """ISSUE 15 tentpole: the hierarchical two-stage exchange (intra-host
    subgroup a2a + inter-host host-bucket ppermute tree) is oracle-exact
    on an emulated host axis, and the cross-host word telemetry counts."""
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n, devices_per_host=dph)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=graph_mesh(), exchange="hier")
    assert g.exchange == "hier" and g.n_hosts == 8 // dph
    rng = np.random.default_rng(1)
    seeds = rng.choice(n, size=5, replace=False).tolist()
    count, ids, over = g.run_wave_collect(seeds)
    assert not over
    want = bfs_closure(adj, seeds)
    assert set(ids.tolist()) == want
    assert count == len(want)
    # a frontier spanning shards on distinct hosts must ship words across
    # the host boundary — exercised, not merely counted
    assert g.cross_words_per_level > 0
    assert g.cross_host_words > 0
    st = g.stats()
    assert st["hosts"] == 8 // dph and st["cross_host_words"] == g.cross_host_words


def test_hier_chain_equals_sequential_and_patches_apply():
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n, devices_per_host=4)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=graph_mesh(), exchange="hier")
    rng = np.random.default_rng(2)
    stages = [rng.choice(n, size=3, replace=False).tolist() for _ in range(3)]
    pending = g.dispatch_union_chain(stages)
    counts, stage_ids, info = g.harvest_union_chain(pending)
    assert not info["overflowed"] and pending["dispatches"] == 1
    seen = set()
    for st, c, ids in zip(stages, counts, stage_ids):
        want = {x for x in bfs_closure(adj, st) if x not in seen}
        seen |= want
        assert int(c) == len(want)
        assert set(ids.tolist()) == want
    # live patching on the hier layout: a bump stops the cascade, a
    # re-declare at the bumped epoch resumes it — and a CROSS-HOST added
    # edge routes through the host buckets
    g.clear_invalid()
    # pick u on host 0's id range, v on host 1's (contiguous shard ids →
    # find one pair via the placement)
    def host_of_node(i):
        return g.placement.host_of_device(
            int(g.placement.shard_dev[g.placement.shard_of_node(i)])
        )

    # a SINK on host 0 (closure = itself) so the asserted cascade can only
    # come from the patched cross-host edge
    u_node = next(i for i in range(n) if host_of_node(i) == 0 and i not in adj)
    v_node = next(i for i in range(n) if host_of_node(i) == 1 and i != u_node)
    before = g.cross_words_per_level
    ok = g.patch_batch(
        np.empty(0, np.int64), np.array([u_node]), np.array([v_node]),
        np.zeros(1, np.int32),
    )
    assert ok
    assert g.cross_words_per_level >= before  # host buckets absorbed the word
    c, ids, _ = g.run_wave_collect([u_node])
    got = set(ids.tolist())
    assert v_node in got  # the cross-host patched edge conducts


def test_hier_kill_join_moves_shards_preserving_state():
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n, devices_per_host=4, slot_headroom=3.0)
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), exchange="hier",
        edge_headroom=2.5, bucket_headroom=2.5,
    )
    rng = np.random.default_rng(3)
    seeds = rng.choice(n, size=4, replace=False).tolist()
    g.run_wave_collect(seeds)
    mask0 = g.invalid_mask().copy()
    m2 = smap.with_members(["a"])
    pl2, moves = pl.moved_to(m2, mesh_members=["a"])
    assert moves
    g.apply_placement(pl2, moves)
    assert np.array_equal(g.invalid_mask(), mask0)
    # host-aware ranking: the generous headroom means zero DCN transfers
    assert g.cross_host_moves == 0
    # waves stay oracle-exact on the churned hier layout
    s2 = rng.choice(n, size=3, replace=False).tolist()
    c, ids, _ = g.run_wave_collect(s2)
    already = bfs_closure(adj, seeds)
    want = {x for x in bfs_closure(adj, s2) if x not in already}
    assert set(ids.tolist()) == want and c == len(want)


def test_routed_chain_equals_sequential_waves():
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n)
    mesh = graph_mesh()
    g = RoutedShardedGraph(src, dst, n, pl, mesh=mesh)
    rng = np.random.default_rng(2)
    stages = [rng.choice(n, size=3, replace=False).tolist() for _ in range(3)]
    pending = g.dispatch_union_chain(stages)
    counts, stage_ids, info = g.harvest_union_chain(pending)
    assert not info["overflowed"] and pending["dispatches"] == 1
    seen = set()
    for st, c, ids in zip(stages, counts, stage_ids):
        want = {x for x in bfs_closure(adj, st) if x not in seen}
        seen |= want
        assert int(c) == len(want)
        assert set(ids.tolist()) == want


def test_routed_kill_join_moves_shards_preserving_state():
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    # generous slot headroom: the kill parks ALL shards on one member, and
    # the join must then first-fit c's shards into still-free slots (a
    # tight headroom makes that a legitimate REBUILD instead of a move)
    pl = DevicePlacement.build(smap, 8, n, slot_headroom=3.0)
    # edge slack likewise: kill+join concentrates both eras' shards on the
    # shared devices; undersized slack is a legitimate rebuild, but this
    # test wants the MOVE path
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), edge_headroom=2.5, bucket_headroom=2.5
    )
    rng = np.random.default_rng(3)
    seeds = rng.choice(n, size=4, replace=False).tolist()
    g.run_wave_collect(seeds)
    mask0 = g.invalid_mask().copy()
    # kill b
    m2 = smap.with_members(["a"])
    pl2, moves = pl.moved_to(m2, mesh_members=["a"])
    assert moves
    g.apply_placement(pl2, moves)
    assert np.array_equal(g.invalid_mask(), mask0)
    # join c
    m3 = m2.with_members(["a", "c"])
    pl3, moves3 = pl2.moved_to(m3, mesh_members=["a", "c"])
    assert moves3
    g.apply_placement(pl3, moves3)
    assert np.array_equal(g.invalid_mask(), mask0)
    # waves stay oracle-exact on the twice-churned placement
    s2 = rng.choice(n, size=3, replace=False).tolist()
    c, ids, _ = g.run_wave_collect(s2)
    already = bfs_closure(adj, seeds)
    want = {x for x in bfs_closure(adj, s2) if x not in already}
    assert set(ids.tolist()) == want and c == len(want)
    assert g.shard_moves == len(moves) + len(moves3)


def test_routed_patch_batch_is_one_dispatch_and_oracle_exact():
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=graph_mesh())
    # bumps + adds of one burst, applied together
    u = np.array([n - 5, n - 4, n - 3], dtype=np.int64)
    v = np.array([n - 4, n - 3, n - 2], dtype=np.int64)
    ep = np.zeros(3, dtype=np.int32)
    ok = g.patch_batch(np.array([n - 2], dtype=np.int64), u, v, ep)
    assert ok and g.patch_dispatches == 1
    # n-2 was bumped: the chain stops there (its in-edge epoch no longer
    # matches), exactly the dense-mirror bump semantics
    c, ids, _ = g.run_wave_collect([n - 5])
    got = set(ids.tolist())
    assert {n - 5, n - 4, n - 3} <= got and n - 2 not in got
    # re-declare at the bumped epoch in a second batch: now it cascades
    g.clear_invalid()
    ok = g.patch_batch(
        np.empty(0, np.int64), np.array([n - 3]), np.array([n - 2]),
        np.array([1], dtype=np.int32),
    )
    assert ok and g.patch_dispatches == 2
    c, ids, _ = g.run_wave_collect([n - 5])
    assert n - 2 in set(ids.tolist())


def test_routed_patch_overflow_reports_rebuild_when_resizes_exhausted():
    n = 2000
    src, dst, _adj = make_graph(n, seed=5)
    smap = ShardMap.initial(["a"], n_shards=16)
    pl = DevicePlacement.build(smap, 8, n)
    # max_resizes=0: the pre-ISSUE-15 ladder — overflow goes straight to
    # the rebuild rung (False), and the exhaustion is COUNTED
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), edge_headroom=1.01, max_resizes=0
    )
    from stl_fusion_tpu.diagnostics.metrics import global_metrics

    before = global_metrics().snapshot().get("fusion_mesh_resize_exhausted_total", 0)
    # flood one destination's device with more edges than the slack holds
    k = g.e_cap  # definitely over the per-device free slots
    u = np.random.default_rng(0).integers(0, n - 1, size=k)
    v = np.full(k, n - 1, dtype=np.int64)
    ep = np.zeros(k, dtype=np.int32)
    assert g.patch_batch(np.empty(0, np.int64), u, v, ep) is False
    assert g.bucket_resizes == 0
    after = global_metrics().snapshot().get("fusion_mesh_resize_exhausted_total", 0)
    assert after == before + 1


def test_routed_patch_overflow_resizes_in_place_and_stays_oracle_exact():
    """ISSUE 15 satellite: an overflowed edge-slack slot / exchange bucket
    under live patching GROWS in place (counted), the patched wave stays
    oracle-exact, and zero rebuild-grade failures are reported."""
    n = 2000
    src, dst, adj = make_graph(n, seed=5)
    smap = ShardMap.initial(["a", "b"], n_shards=16)
    pl = DevicePlacement.build(smap, 8, n)
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), edge_headroom=1.01, bucket_headroom=1.01
    )
    from stl_fusion_tpu.diagnostics.metrics import global_metrics

    before = global_metrics().snapshot().get("fusion_mesh_bucket_resizes_total", 0)
    # flood one destination device's slack well past e_cap AND mint many
    # new (producer, word) bucket entries
    rng = np.random.default_rng(0)
    k = g.e_cap + 64
    u = rng.integers(0, n - 1, size=k)
    v = np.full(k, n - 1, dtype=np.int64)
    ep = np.zeros(k, dtype=np.int32)
    assert g.patch_batch(np.empty(0, np.int64), u, v, ep) is True
    assert g.bucket_resizes >= 1
    assert g.resize_detail["edge"] >= 1
    after = global_metrics().snapshot().get("fusion_mesh_bucket_resizes_total", 0)
    assert after == before + g.bucket_resizes
    # the grown layout serves oracle-exact waves: every new edge conducts
    for s, d_ in zip(u.tolist(), v.tolist()):
        adj.setdefault(s, []).append(d_)
    seeds = [int(u[0]), int(u[k // 2])]
    c, ids, over = g.run_wave_collect(seeds)
    assert not over
    want = bfs_closure(adj, seeds)
    assert set(ids.tolist()) == want and c == len(want)
    # a second overflow within the remaining budget also resizes in place
    u2 = rng.integers(0, n - 1, size=g.e_cap)
    v2 = np.full(len(u2), n - 2, dtype=np.int64)
    assert g.patch_batch(
        np.empty(0, np.int64), u2, v2, np.zeros(len(u2), np.int32)
    ) is True
    assert g.resize_detail["edge"] >= 2


def test_mesh_shard_snapshot_survives_reshard():
    from stl_fusion_tpu.checkpoint import restore_mesh_shards, save_mesh_shards

    n = 3000
    src, dst, _adj = make_graph(n, seed=9)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n)
    mesh = graph_mesh()
    g = RoutedShardedGraph(src, dst, n, pl, mesh=mesh)
    g.run_wave_collect([0, 1, 2])
    import tempfile, os

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "mesh.npz")
        n_written = save_mesh_shards(g, path)
        assert n_written == 32
        # restore under the POST-KILL placement: every shard re-pins
        m2 = smap.with_members(["a"])
        pl2, _moves = pl.moved_to(m2, mesh_members=["a"])
        g2 = RoutedShardedGraph(src, dst, n, pl2, mesh=mesh)
        r = restore_mesh_shards(g2, path)
        assert r["restored"] == 32 and r["map_epoch"] == 0
        assert np.array_equal(g2.invalid_mask(), g.invalid_mask())
        # a snapshot from DIFFERENT geometry must refuse, not silently
        # overwrite the neighbouring slot's rows (ids_per_shard differs)
        pl3 = DevicePlacement.build(smap, 8, n // 2)
        g3 = RoutedShardedGraph(src[src < n // 2][:0], dst[:0], n // 2, pl3, mesh=mesh)
        with pytest.raises(ValueError):
            restore_mesh_shards(g3, path)


# ---------------------------------------------------------------- packed batch
def test_packed_patch_batch_equals_sequential():
    from stl_fusion_tpu.parallel import PackedShardedGraph

    n = 2000
    src, dst, _adj = make_graph(n, seed=11)
    mesh = graph_mesh()
    a = PackedShardedGraph(src, dst, n, mesh=mesh, slack=4)
    b = PackedShardedGraph(src, dst, n, mesh=mesh, slack=4)
    rng = np.random.default_rng(4)
    bumps1 = rng.choice(n, size=8, replace=False)
    bumps2 = rng.choice(n, size=8, replace=False)  # may overlap bumps1
    u = rng.integers(0, n - 1, size=12)
    v = u + 1
    ep = np.zeros(12, dtype=np.int64)
    # sequential: two bump payloads + one add payload
    a.patch_bumps(bumps1)
    a.patch_bumps(bumps2)
    assert a.patch_adds(u, v, ep)
    # batched: one fused dispatch (per-payload unique, cross-payload concat
    # — the exact coalescing backend._try_patch_packed performs)
    merged = np.concatenate([np.unique(bumps1), np.unique(bumps2)])
    assert b.patch_batch(merged, u, v, ep)
    assert np.array_equal(np.asarray(a.node_epoch), np.asarray(b.node_epoch))
    assert np.array_equal(np.asarray(a.in_src), np.asarray(b.in_src))
    assert np.array_equal(np.asarray(a.edge_epoch), np.asarray(b.edge_epoch))
    assert np.array_equal(a.h_node_epoch, b.h_node_epoch)
    assert b.patches == 1 and a.patches == 3


# ---------------------------------------------------------------- live backend
@pytest.mark.parametrize("exchange", ["a2a", "hier"])
async def test_backend_mesh_routing_pipeline_and_reshard_chaos(exchange):
    """The ISSUE 9 acceptance scenario at test scale: a live hub's fused
    wave chains ride the routed mesh path, a mid-burst reshard MOVES
    device shards, and the consistency auditor sees zero oracle-divergent
    reads on the churned topology. Parametrized over the hierarchical
    exchange (ISSUE 15): the two-stage intra-host + inter-host protocol
    must ride the SAME pipeline with zero eager fallbacks."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        TableBacking,
        compute_method,
        memo_table_of,
        set_default_hub,
    )
    from stl_fusion_tpu.diagnostics.invariants import validate_hub, validate_mirror
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.graph.nonblocking import WavePipeline

    ns = 3000
    src, dst, adj = make_graph(ns, seed=23)
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=len(src) + 2048)

        class RowSvc(ComputeService):
            def load(self, ids):
                return np.asarray(ids, dtype=np.float32)

            @compute_method(table=TableBacking(rows=ns, batch="load"))
            async def row(self, i: int) -> float:
                return float(i)

        svc = RowSvc(hub)
        hub.add_service(svc)
        table = memo_table_of(svc.row)
        blk = backend.bind_table_rows(table)
        backend.declare_row_edges(blk, src, blk, dst)
        table.read_batch(np.arange(ns))
        backend.flush()

        smap = ShardMap.initial(["m0", "m1"], n_shards=32)
        backend.enable_mesh_routing(
            smap, mesh=graph_mesh(), exchange=exchange,
            devices_per_host=4 if exchange == "hier" else None,
        )
        pipe = WavePipeline(backend, fuse_depth=2)
        rng = np.random.default_rng(7)
        seen = set()

        def check(groups, tickets):
            nonlocal seen
            for g_, t in zip(groups, tickets):
                want = {x for x in bfs_closure(adj, g_) if x not in seen}
                seen |= want
                assert t.count == len(want), (t.count, len(want))

        groups = [rng.choice(ns, size=3, replace=False).tolist() for _ in range(2)]
        tickets = [pipe.submit_rows(blk, g_) for g_ in groups]
        pipe.drain()
        check(groups, tickets)
        assert pipe.eager_waves == 0 and pipe.fused_dispatches >= 1

        # MID-BURST reshard: submit, reshard while the chain is pending
        groups2 = [rng.choice(ns, size=3, replace=False).tolist() for _ in range(2)]
        t0 = pipe.submit_rows(blk, groups2[0])
        moves = backend.apply_mesh_reshard(smap.with_members(["m0"]))
        assert moves > 0
        t1 = pipe.submit_rows(blk, groups2[1])
        pipe.drain()
        check(groups2, [t0, t1])
        assert pipe.chain_faults == 0
        pipe.dispose()

        # zero oracle-divergent reads on the churned topology: the stale
        # set must equal the union of all closures, and the auditor's
        # invariant sweeps must be clean
        assert table.stale_count() == len(seen)
        assert np.array_equal(
            np.sort(np.nonzero(backend.graph.invalid_mask())[0]),
            np.sort(np.fromiter(seen, dtype=np.int64)),
        )
        rep = validate_hub(hub)
        assert not rep.violations, rep.violations
        rep = validate_mirror(backend)
        assert not rep.violations, rep.violations
    finally:
        set_default_hub(old)


async def test_rebalancer_moves_device_shards_on_epoch():
    """attach_backend: an applied epoch moves the mesh's device shards in
    the same change that fences moved client keys."""
    from stl_fusion_tpu.cluster import ClusterRebalancer, ShardMapRouter
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        TableBacking,
        compute_method,
        memo_table_of,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.rpc import RpcHub

    ns = 2000
    src, dst, adj = make_graph(ns, seed=31)
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=len(src) + 256)

        class RowSvc(ComputeService):
            def load(self, ids):
                return np.asarray(ids, dtype=np.float32)

            @compute_method(table=TableBacking(rows=ns, batch="load"))
            async def row(self, i: int) -> float:
                return float(i)

        svc = RowSvc(hub)
        hub.add_service(svc)
        table = memo_table_of(svc.row)
        blk = backend.bind_table_rows(table)
        backend.declare_row_edges(blk, src, blk, dst)
        table.read_batch(np.arange(ns))
        backend.flush()

        smap = ShardMap.initial(["m0", "m1"], n_shards=32)
        backend.enable_mesh_routing(smap, mesh=graph_mesh())
        # build + warm the routed mirror
        c0 = backend.cascade_rows_batch_routed(blk, [0])
        assert c0 == len(bfs_closure(adj, [0]))

        rpc = RpcHub("member")
        router = ShardMapRouter(rpc, shard_map=smap)
        reb = ClusterRebalancer(rpc, router).attach_backend(backend)
        router.apply_map(smap.with_members(["m0"]))
        assert reb.device_shards_moved > 0
        assert reb.snapshot()["device_shards_moved"] == reb.device_shards_moved
        # post-epoch waves stay exact on the moved shards
        seen = bfs_closure(adj, [0])
        want = {x for x in bfs_closure(adj, [1]) if x not in seen} | ({1} - seen)
        c1 = backend.cascade_rows_batch_routed(blk, [1])
        assert c1 == len(want)
        reb.dispose()
        await rpc.stop()
    finally:
        set_default_hub(old)


def test_explain_names_the_shard_hop():
    from stl_fusion_tpu.diagnostics.explain import explain
    from stl_fusion_tpu.core import FusionHub, set_default_hub
    from stl_fusion_tpu.core import ComputeService, TableBacking, compute_method, memo_table_of
    from stl_fusion_tpu.graph import TpuGraphBackend

    ns = 2000
    src, dst, adj = make_graph(ns, seed=41)
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=len(src) + 256)

        class RowSvc(ComputeService):
            def load(self, ids):
                return np.asarray(ids, dtype=np.float32)

            @compute_method(table=TableBacking(rows=ns, batch="load"))
            async def row(self, i: int) -> float:
                return float(i)

        svc = RowSvc(hub)
        hub.add_service(svc)
        table = memo_table_of(svc.row)
        blk = backend.bind_table_rows(table)
        backend.declare_row_edges(blk, src, blk, dst)
        table.read_batch(np.arange(ns))
        backend.flush()
        smap = ShardMap.initial(["m0", "m1"], n_shards=32)
        backend.enable_mesh_routing(smap, mesh=graph_mesh())

        from stl_fusion_tpu.core import capture

        holder = {}

        async def drive():
            holder["c"] = await capture(lambda: svc.row(int(dst[0])))
            # watched → the wave applies EAGERLY and journals the wave seq
            # on the node (the lazy tier records no per-node identity, so
            # the shard hop would have nothing to attach to)
            backend.mark_watched(holder["c"])
            backend.cascade_rows_batch_routed(blk, [int(src[0])])

        asyncio.run(drive())
        out = explain(holder["c"], hub=hub, backend=backend)
        text = " ".join(out["chain"])
        assert "frontier exchanged on-mesh" in text, out["chain"]
        assert "a2a" in text and "no host-relay hop" in text
        assert "device shard #" in text  # the key's own hop is named
    finally:
        set_default_hub(old)


# ---------------------------------------------------------------- clock sync
def test_clocksync_offset_estimation_and_fallback():
    from stl_fusion_tpu.diagnostics.clocksync import ClockSync

    cs = ClockSync()
    cs.note_sample("p", 100.0, 105.005, 100.010)  # remote = local + 5s
    assert abs(cs.offset("p") - 5.0) < 1e-9
    assert abs(cs.to_local("p", 105.005) - 100.005) < 1e-9
    # a worse (higher-RTT) sample never replaces the best
    cs.note_sample("p", 200.0, 205.4, 200.5)
    assert abs(cs.offset("p") - 5.0) < 1e-9
    # never-probed peers keep the identity mapping (same-clock stacks)
    assert cs.to_local(None, 7.0) == 7.0
    assert cs.to_local("unknown", 7.0) == 7.0
    cs.forget("p")
    assert cs.offset("p") is None


async def test_clock_probe_rides_connect_and_corrects_delivery():
    """A connect fires one $sys.clock probe in each direction; the client's
    delivery histogram then maps the server's origin_ts through the
    measured offset (≈0 in-process, so the corrected sample stays sane)."""
    from stl_fusion_tpu.client import compute_client, install_compute_call_type
    from stl_fusion_tpu.core import ComputeService, FusionHub, compute_method
    from stl_fusion_tpu.diagnostics.clocksync import global_clock_sync
    from stl_fusion_tpu.rpc import RpcHub
    from stl_fusion_tpu.rpc.testing import RpcTestTransport

    class Svc(ComputeService):
        @compute_method
        async def get(self, k: str) -> int:
            return 1

    server_fusion = FusionHub()
    server_rpc = RpcHub("server")
    client_rpc = RpcHub("client")
    install_compute_call_type(server_rpc)
    install_compute_call_type(client_rpc)
    svc = Svc(server_fusion)
    server_rpc.add_service("s", svc)
    RpcTestTransport(client_rpc, server_rpc)
    client = compute_client("s", client_rpc, FusionHub())
    before = global_clock_sync().probes
    await client.get("a")
    await asyncio.sleep(0.05)
    cs = global_clock_sync()
    assert cs.probes > before
    off = cs.offset("default")
    assert off is not None and abs(off) < 0.05  # same process ≈ zero
    await client_rpc.stop()
    await server_rpc.stop()


async def test_overlapped_routed_chains_keep_device_state():
    """Two routed chains in flight at once (fuse_depth=1, no drain between
    submits): dispatch N must NOT full-sync the mirror from the pre-chain
    dense state — that would erase chain N-1's in-flight device advance
    and double-count its cascade at harvest (the in-flight counter
    regression)."""
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        TableBacking,
        compute_method,
        memo_table_of,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.graph.nonblocking import WavePipeline

    ns = 2000
    src, dst, adj = make_graph(ns, seed=51)
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=len(src) + 256)

        class RowSvc(ComputeService):
            def load(self, ids):
                return np.asarray(ids, dtype=np.float32)

            @compute_method(table=TableBacking(rows=ns, batch="load"))
            async def row(self, i: int) -> float:
                return float(i)

        svc = RowSvc(hub)
        hub.add_service(svc)
        table = memo_table_of(svc.row)
        blk = backend.bind_table_rows(table)
        backend.declare_row_edges(blk, src, blk, dst)
        table.read_batch(np.arange(ns))
        backend.flush()
        smap = ShardMap.initial(["m0"], n_shards=16)
        backend.enable_mesh_routing(smap, mesh=graph_mesh())

        # fuse_depth=1: every submit dispatches its own chain; three
        # submits put chain 2 in flight while chain 1 is unharvested
        pipe = WavePipeline(backend, fuse_depth=1)
        rng = np.random.default_rng(8)
        groups = [rng.choice(ns, size=2, replace=False).tolist() for _ in range(3)]
        tickets = [pipe.submit_rows(blk, g_) for g_ in groups]
        pipe.drain()
        seen = set()
        for g_, t in zip(groups, tickets):
            want = {x for x in bfs_closure(adj, g_) if x not in seen}
            seen |= want
            assert t.count == len(want), (t.count, len(want))
        assert pipe.eager_waves == 0 and pipe.chain_faults == 0
        # after the drain the mirror reads in-sync again
        entry = backend._routed_mirror
        assert entry["inflight"] == 0
        assert entry["invalid_version"] == backend.graph.invalid_version
        pipe.dispose()
    finally:
        set_default_hub(old)


def test_single_shard_move_repacks_remote_consumers():
    """The partial-repack regression (review): moving ONE shard must also
    re-route every consumer device whose edges SOURCE from it — their
    exchange buckets reference the vacated rows, and a kill-style reshard
    (which touches all devices) masked the loss. A hub shard's move must
    leave every cross-device cascade intact."""
    n = 4000
    src, dst, adj = make_graph(n)
    smap = ShardMap.initial(["a", "b"], n_shards=32)
    pl = DevicePlacement.build(smap, 8, n)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=graph_mesh())
    # shard 0 holds the power-law hubs: its nodes source edges into
    # destinations spread across every device
    s = 0
    old_dev = int(pl.shard_dev[s])
    new_dev = (old_dev + 3) % 8
    used = {int(k) for d, k in zip(pl.shard_dev, pl.shard_slot) if int(d) == new_dev}
    free = next(k for k in range(pl.slots_per_dev) if k not in used)
    pl2 = DevicePlacement(
        shard_map=pl.shard_map, n_dev=pl.n_dev, n_nodes=pl.n_nodes,
        mesh_members=pl.mesh_members, ids_per_shard=pl.ids_per_shard,
        slot_rows=pl.slot_rows, slots_per_dev=pl.slots_per_dev,
        shard_dev=pl.shard_dev.copy(), shard_slot=pl.shard_slot.copy(),
        moves=pl.moves,
    )
    pl2.shard_dev[s] = new_dev
    pl2.shard_slot[s] = free
    g.apply_placement(pl2, [(s, old_dev, new_dev)])
    seeds = [0, 1]  # hub nodes inside the moved shard
    count, ids, over = g.run_wave_collect(seeds)
    want = bfs_closure(adj, seeds)
    assert set(ids.tolist()) == want, (
        f"single-shard move lost {len(want) - count} cascaded invalidations"
    )
    assert count == len(want)


# ------------------------------------------------- a mask moves by shard blocks
def _block_perm_case(case):
    """(graph, n) on a placement whose node <-> row permutation has the
    shape the case names; edges only between on-mesh nodes."""
    n = 3990 if case == "short_last_shard" else 4000
    src, dst, _adj = make_graph(n)
    members = ["a", "b", "c", "d"] if case == "off_mesh_shard" else ["a", "b"]
    smap = ShardMap.initial(members, n_shards=32)
    on_mesh = ["a", "b"]
    pl = DevicePlacement.build(smap, 8, n, mesh_members=on_mesh, slot_headroom=3.0)
    if case == "off_mesh_shard":
        perm, _inv = pl.permutation()
        keep = (perm[src] >= 0) & (perm[dst] >= 0)
        src, dst = src[keep], dst[keep]
    built_with = np.random.default_rng(8).random(n) < 0.3
    g = RoutedShardedGraph(
        src, dst, n, pl, mesh=graph_mesh(), edge_headroom=2.5, bucket_headroom=2.5,
        invalid=built_with,
    )
    if case == "after_moves":
        pl2, moves = pl.moved_to(smap.with_members(["a"]), mesh_members=["a"])
        assert moves
        g.apply_placement(pl2, moves)
        assert not np.array_equal(g.perm, pl.permutation()[0])
    return g, n, built_with


@pytest.mark.parametrize(
    "case", ["initial", "after_moves", "off_mesh_shard", "short_last_shard"]
)
def test_mask_permutes_by_shard_blocks_as_by_index(case):
    g, n, built_with = _block_perm_case(case)
    perm, inv_perm = g.placement.permutation()
    assert np.array_equal(g.perm, perm) and np.array_equal(g.inv_perm, inv_perm)
    on = perm >= 0
    assert on.all() == (case != "off_mesh_shard")
    if case == "short_last_shard":
        lo, hi, _base = g._runs[-1]
        assert hi == n and hi - lo < g.placement.ids_per_shard
    # the mask the graph was built with, carried through the moves
    assert np.array_equal(g.invalid_mask(), built_with & on)
    m = np.random.default_rng(9).random(n) < 0.4
    assert m[~on].any() or on.all()

    g.set_invalid(m)
    rows = np.asarray(g.g_invalid)
    by_index = np.zeros(g.n_global, dtype=bool)
    by_index[perm[on]] = m[on]
    assert np.array_equal(rows, by_index)  # pad and off-mesh rows stay False

    got = g.invalid_mask()
    real = inv_perm >= 0
    by_index = np.zeros(n, dtype=bool)
    by_index[inv_perm[real]] = rows[real]
    assert np.array_equal(got, by_index)
    assert np.array_equal(got, m & on)  # the round trip; off-mesh nodes read False
    assert np.array_equal(np.asarray(g.g_is_real), real)


# ------------------------------------- a routed closure on the host (ISSUE 35)
@pytest.mark.parametrize("kind", ["overflow", "no_overflow"])
async def test_routed_closure_applies_by_mask_on_overflow_and_by_ids_otherwise(kind):
    """``cascade_rows_batch_routed`` on a closure that outgrows the compacted
    id buffers (it comes back as the mesh's mask, and stays one to the last
    hook) and on one that fits (ids, as ever): either way the hub ends where
    applying the host BFS closure's ids would leave it."""
    from stl_fusion_tpu.core import (
        ComputeService, FusionHub, TableBacking, capture, compute_method,
        memo_table_of, set_default_hub,
    )
    from stl_fusion_tpu.diagnostics.metrics import global_metrics
    from stl_fusion_tpu.graph import TpuGraphBackend

    ns = 100_000  # 25 k rows a device against id buffers of 16,384
    src, dst, adj = make_graph(ns, seed=5)
    rng = np.random.default_rng(6)
    earlier = [int(ns // 2 + rng.integers(ns // 4))]
    if kind == "overflow":
        seeds = rng.choice(ns // 10, size=200, replace=False).tolist()
    else:
        seeds = (ns // 2 + rng.choice(ns // 2, size=40, replace=False)).tolist()
    already = np.zeros(ns, dtype=bool)
    already[list(bfs_closure(adj, earlier))] = True
    closure = np.zeros(ns, dtype=bool)
    closure[list(bfs_closure(adj, seeds))] = True
    want_newly = closure & ~already
    newly_ids = np.flatnonzero(want_newly)

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=len(src) + 256)

        class RowSvc(ComputeService):
            def load(self, ids):
                return np.asarray(ids, dtype=np.float32)

            @compute_method(table=TableBacking(rows=ns, batch="load"))
            async def row(self, i: int) -> float:
                return float(i)

        svc = RowSvc(hub)
        hub.add_service(svc)
        table = memo_table_of(svc.row)
        blk = backend.bind_table_rows(table)
        backend.declare_row_edges(blk, src, blk, dst)
        table.read_batch(np.arange(ns))
        # two scalar twins inside the closure: one watched (the eager tier),
        # one not (it stays pending)
        watched_row, lazy_row = (int(r) for r in newly_ids[[len(newly_ids) // 2, -1]])
        hits: list = []
        watched = await capture(lambda: svc.row(watched_row))
        watched.on_invalidated(hits.append)
        lazy = await capture(lambda: svc.row(lazy_row))
        backend.flush()
        backend.enable_mesh_routing(
            ShardMap.initial(["m0", "m1", "m2", "m3"], n_shards=32),
            mesh=graph_mesh(n_devices=4),
        )
        dg = backend.graph
        assert backend.cascade_rows_batch_routed(blk, earlier) == np.count_nonzero(already)

        hooked: list = []
        backend.newly_hooks.append(hooked.append)
        calls = {"mark_invalid": 0, "mark_invalid_mask": 0}
        for name in calls:
            def spy(arg, _f=getattr(dg, name), _name=name):
                calls[_name] += 1
                return _f(arg)
            setattr(dg, name, spy)
        def overflows() -> int:
            return int(
                global_metrics().snapshot().get("fusion_mesh_routed_overflows_total", 0)
            )

        overflows0, version0 = overflows(), dg.invalid_version
        pending0 = backend._pending.copy()

        count = backend.cascade_rows_batch_routed(blk, seeds)

        assert count == len(newly_ids)
        overflowed = kind == "overflow"
        assert overflows() - overflows0 == int(overflowed)
        assert calls == {"mark_invalid": int(not overflowed),
                         "mark_invalid_mask": int(overflowed)}
        assert dg.invalid_version == version0 + 1
        # the table, the dense graph on host and device, the mesh's own mask
        stale = already | closure
        assert np.array_equal(table._stale_host[:ns], stale)
        assert np.array_equal(~np.asarray(table.valid_mask), stale)
        assert table.stale_count() == np.count_nonzero(stale)
        assert np.array_equal(dg._h_invalid[:ns], stale)
        assert not dg._h_invalid[ns:].any()
        assert np.array_equal(np.asarray(dg.invalid_mask()), stale)
        assert np.array_equal(backend.routed_mirror()["graph"].invalid_mask(), stale)
        # the lazy tier: every newly row pending, but the one applied eagerly
        want_pending = pending0.copy()
        want_pending[newly_ids] = True
        want_pending[watched_row] = False
        assert np.array_equal(backend._pending, want_pending)
        assert hits == [watched] and watched._invalidation_cause == backend.last_cause_id
        assert backend.last_cause_id is not None
        assert backend._pending[lazy_row] and not lazy.is_consistent  # materialized on read
        # the hooks get the closure in the form it came back in
        (got,) = hooked
        if overflowed:
            assert got.dtype == np.bool_ and got.shape == (dg.n_nodes,)
            assert np.array_equal(got, want_newly)
        else:
            assert got.dtype != np.bool_
            assert np.array_equal(np.sort(got), newly_ids)
        assert backend.profiler.recent()[-1]["newly"] == len(newly_ids)
    finally:
        set_default_hub(old)
