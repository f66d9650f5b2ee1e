"""The row scatters patch their tables IN PLACE (PR 29): ``fused_pair_scatter``,
``fused_quad_scatter`` (ops/bitops.py) and ``_fused_triple_scatter``
(graph/device_graph.py) donate the tables they update, so a mirror patch or an
edge append writes its rows instead of copying whole tables.

What donation demands, pinned here on the CPU backend:

- the handle held before the call is deleted, and the donation is USED (no
  "donated buffers were not usable" warning: a silent copy would pass every
  other test);
- nothing writes through into host memory: the patcher's own ``h_*`` tables
  hold exactly what the host patched, and the arrays the mirror was built
  from (which the disk-cache writer may still be serialising, and which
  ``jnp.asarray`` may alias zero-copy) stay as built;
- no holder keeps a donated handle: a patch and an ``add_edges`` while an
  async rebuild, a non-blocking chain or a super-round is in flight, every
  wave path with a patch pending, the packed mesh mirror. Each exact against
  a host BFS, each leaving only live handles installed.
"""
import contextlib
import threading
import time
import warnings

import numpy as np
import pytest

from stl_fusion_tpu.graph.device_graph import DeviceGraph, _fused_triple_scatter
from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.ops.bitops import fused_pair_scatter, fused_quad_scatter

N = 240
SRC, DST = power_law_dag(N, avg_degree=3, seed=29)

EDGE_TABLES = ("edge_src", "edge_dst", "edge_dst_epoch")
TOPO_TABLES = ("in_src", "edge_epoch")
LAT_TABLES = ("ell_dst", "ell_epoch")


class HostRef:
    """The live edge set as the host knows it, and a BFS over it. A bump
    kills the node's in-edges (captured-at-epoch rule)."""

    def __init__(self, src, dst):
        self.edges = set(zip(src.tolist(), dst.tolist()))

    def add(self, src, dst):
        self.edges |= set(zip(np.asarray(src).tolist(), np.asarray(dst).tolist()))

    def bump(self, ids):
        dead = set(np.asarray(ids).tolist())
        self.edges = {(u, v) for (u, v) in self.edges if v not in dead}

    def closure(self, seeds, invalid=None):
        seen = np.zeros(N, dtype=bool) if invalid is None else invalid.copy()
        newly = np.zeros(N, dtype=bool)
        adj = {}
        for u, v in self.edges:
            adj.setdefault(u, []).append(v)
        frontier = []
        for s in seeds:
            if not seen[s]:
                seen[s] = newly[s] = True
                frontier.append(s)
        while frontier:
            nxt = []
            for u in frontier:
                for v in adj.get(u, ()):
                    if not seen[v]:
                        seen[v] = newly[v] = True
                        nxt.append(v)
            frontier = nxt
        return newly


def make_graph():
    dg = DeviceGraph(node_capacity=N, edge_capacity=8 * N)
    dg.add_nodes(N)
    dg.add_edges(SRC, DST)
    dg.build_topo_mirror()
    return dg, HostRef(SRC, DST)


def handles(dg):
    """Every device array the graph has installed right now, by name."""
    out = dict(dg.device_arrays()._asdict())
    m = dg._topo_mirror
    if m is not None:
        out.update(m["garrays"]._asdict())
        lat = m.get("lat")
        if lat is not None:
            out.update({name: lat[name] for name in LAT_TABLES})
    return out


def assert_all_live(dg):
    for name, arr in handles(dg).items():
        assert not arr.is_deleted(), f"{name} is installed but deleted"


def assert_tables_match_host(dg):
    """Device tables equal the patcher's host truth, row for row."""
    g = dg.device_arrays()
    e = dg.n_edges
    np.testing.assert_array_equal(np.asarray(g.edge_src)[:e], dg._h_edge_src[:e])
    np.testing.assert_array_equal(np.asarray(g.edge_dst)[:e], dg._h_edge_dst[:e])
    np.testing.assert_array_equal(
        np.asarray(g.edge_dst_epoch)[:e], dg._h_edge_dst_epoch[:e]
    )
    m = dg._topo_mirror
    h = m["h_in_src"]
    np.testing.assert_array_equal(np.asarray(m["garrays"].in_src), h)
    np.testing.assert_array_equal(
        np.asarray(m["garrays"].edge_epoch), np.where(h != m["n_tot"], 0, -1)
    )
    lat = m["lat"]
    np.testing.assert_array_equal(np.asarray(lat["ell_dst"]), lat["h_ell_dst"])
    np.testing.assert_array_equal(np.asarray(lat["ell_epoch"]), lat["h_ell_epoch"])


@contextlib.contextmanager
def _NoUnusedDonation():
    """Fails on JAX's "Some donated buffers were not usable" (it then copies
    silently, which is the cost this change removes)."""
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        yield
    unused = [str(w.message) for w in seen if "donated" in str(w.message)]
    assert not unused, unused


def free_pair(dg, ref, lo=N // 2):
    """(u, v): no edge u -> v yet, level(u) < level(v), and v's in-row and
    (where the mirror has its lat half) u's out-row both have a free slot: a
    patchable add that changes every mirror there is."""
    m = dg._topo_mirror
    lat = m["lat"]
    levels = dg.mirror_levels(np.arange(N))
    for v in range(N - 1, lo, -1):
        if not (m["h_in_src"][m["inv_perm"][v]] == m["n_tot"]).any():
            continue
        for u in range(lo, v):
            if (u, v) in ref.edges or levels[u] >= levels[v]:
                continue
            if lat is None or (lat["h_ell_dst"][u] == lat["n_tot"]).any():
                return u, v
    raise AssertionError("no patchable pair in the test graph")


def first_free(row, pad):
    return int(np.argmax(row == pad))


# ------------------------------------------------------------------ the programs


@pytest.mark.parametrize("shape", ["triple", "pair", "quad", "lat_only"])
def test_patch_donates_its_tables_and_leaves_host_arrays_alone(
    shape, monkeypatch, tmp_path
):
    """An edge append (triple), a topo-only patch (pair), a patch of both
    mirrors (quad) and a lat-only patch (pair): the old handles of exactly
    the patched tables are deleted, the donation is used, the device tables
    equal the host's, ``h_in_src`` / ``h_ell_dst`` hold what the host
    patched and nothing else, and the arrays the build handed to the
    disk-cache writer are as built."""
    built = {}
    monkeypatch.setenv("FUSION_MIRROR_CACHE", str(tmp_path))
    monkeypatch.setattr(
        DeviceGraph, "_save_mirror_cache_async",
        lambda self, path, topo, lat: built.update(topo=topo, lat=lat),
    )
    dg, ref = make_graph()
    topo, lat_built = built["topo"], built["lat"]
    as_built = {
        "in_src": topo.in_src.copy(), "edge_epoch": topo.edge_epoch.copy(),
        "ell_dst": lat_built.ell_dst.copy(),
    }
    m = dg._topo_mirror
    lat = m["lat"]
    n_tot, ln_tot = m["n_tot"], lat["n_tot"]
    u, v = free_pair(dg, ref)
    want_h_in = m["h_in_src"].copy()
    want_h_ell = lat["h_ell_dst"].copy()
    before = handles(dg)
    with _NoUnusedDonation():
        if shape == "triple":
            dg.add_edges(np.array([u]), np.array([v]))
            donated = set(EDGE_TABLES)
        elif shape == "pair":
            dg.bump_epochs(np.array([v]))  # clears v's in-row; lat untouched
            assert dg._mirror_valid() and dg.mirror_patches == 1
            want_h_in[m["inv_perm"][v], :] = n_tot
            donated = set(TOPO_TABLES)
        elif shape == "quad":
            dg.add_edges(np.array([u]), np.array([v]))
            assert dg._mirror_valid() and dg.mirror_patches == 1
            rv, ru = m["inv_perm"][v], m["inv_perm"][u]
            want_h_in[rv, first_free(want_h_in[rv], n_tot)] = ru
            want_h_ell[u, first_free(want_h_ell[u], ln_tot)] = v
            donated = set(EDGE_TABLES + TOPO_TABLES + LAT_TABLES)
        else:
            slot = first_free(lat["h_ell_dst"][u], ln_tot)
            lat["h_ell_dst"][u, slot] = v
            lat["h_ell_epoch"][u, slot] = dg._h_node_epoch[v]
            want_h_ell[u, slot] = v
            dg._scatter_lat_rows(lat, np.array([u]))
            donated = set(LAT_TABLES)
    for name, arr in before.items():
        assert arr.is_deleted() == (name in donated), name
    assert_all_live(dg)
    if shape != "triple":
        assert_tables_match_host(dg)
    else:
        e = dg.n_edges
        g = dg.device_arrays()
        assert np.asarray(g.edge_src)[e - 1] == u and np.asarray(g.edge_dst)[e - 1] == v
        np.testing.assert_array_equal(np.asarray(g.edge_src)[:e], dg._h_edge_src[:e])
    # no write-through: the host tables hold the host's patch, bit for bit
    np.testing.assert_array_equal(m["h_in_src"], want_h_in)
    np.testing.assert_array_equal(lat["h_ell_dst"], want_h_ell)
    np.testing.assert_array_equal(topo.in_src, as_built["in_src"])
    np.testing.assert_array_equal(topo.edge_epoch, as_built["edge_epoch"])
    np.testing.assert_array_equal(lat_built.ell_dst, as_built["ell_dst"])


def _aligned(shape, fill):
    """A C-contiguous int32 array on a 64-byte boundary: what the CPU
    backend's ``jnp.asarray`` takes zero-copy."""
    count = int(np.prod(shape))
    raw = np.empty(count * 4 + 64, dtype=np.uint8)
    off = (-raw.ctypes.data) % 64
    arr = raw[off : off + count * 4].view(np.int32).reshape(shape)
    arr[...] = fill
    return arr


@pytest.mark.parametrize("program", ["pair", "quad", "triple"])
def test_donating_a_zero_copy_upload_never_writes_into_the_numpy_array(program):
    """``jnp.asarray`` of an aligned numpy array aliases its memory on the
    CPU backend. Donating such a buffer must not turn the scatter into a
    write into host memory: the runtime does not own the buffer and copies
    it first. The mirror uploads (``topo_graph_arrays(topo)``,
    ``jnp.asarray(lat.ell_dst)``) rest on this."""
    import jax.numpy as jnp

    wide = program != "triple"
    shape = (4096, 6) if wide else (4096,)
    n_tables = {"pair": 2, "quad": 4, "triple": 3}[program]
    hosts = [_aligned(shape, 7 + i) for i in range(n_tables)]
    tables = [jnp.asarray(h) for h in hosts]
    if not all(t.unsafe_buffer_pointer() == h.ctypes.data for t, h in zip(tables, hosts)):
        pytest.skip("this backend copied the upload: nothing aliased to protect")
    rows = jnp.asarray(np.array([3, 5], dtype=np.int32))
    vals = jnp.asarray(np.full((2, 6) if wide else (2,), -1, dtype=np.int32))
    with _NoUnusedDonation():
        if program == "pair":
            outs = fused_pair_scatter()(tables[0], tables[1], rows, vals, vals)
        elif program == "quad":
            outs = fused_quad_scatter()(
                tables[0], tables[1], rows, vals, vals,
                tables[2], tables[3], rows, vals, vals,
            )
        else:
            outs = _fused_triple_scatter()(*tables, rows, vals, vals, vals)
    for i, (out, host, table) in enumerate(zip(outs, hosts, tables)):
        got = np.asarray(out)
        assert table.is_deleted()
        assert (host == 7 + i).all()  # host memory as it was
        assert (got[[3, 5]] == -1).all() and (np.delete(got, [3, 5], 0) == 7 + i).all()


@pytest.mark.parametrize("churn", ["add", "bump", "bump_recapture"])
def test_patched_mirror_waves_equal_host_bfs_and_a_fresh_rebuild(churn):
    """Waves over tables patched in place, lat-served and topo-swept, equal
    the host BFS; a forced rebuild of the same live edges gives the same."""
    dg, ref = make_graph()
    u, v = free_pair(dg, ref)
    if churn == "add":
        dg.add_edges(np.array([u]), np.array([v]))
        ref.add([u], [v])
    else:
        dg.bump_epochs(np.array([v]))
        ref.bump([v])
        if churn == "bump_recapture":
            parents = np.array([u, v - 1 if v - 1 != u else v - 2])
            dg.add_edges(parents, np.full(2, v))
            ref.add(parents, [v, v])
    seeds = [u, int(SRC[DST == v][0]) if (DST == v).any() else 0, 3, N // 3]
    want = [ref.closure([s]) for s in seeds]

    def read(dg):
        lat_counts, lat_ids = [], []
        for s in seeds:
            waves0 = dg.lat_waves
            count, ids = dg.run_waves_union([[s]])
            assert dg.lat_waves == waves0 + 1  # lat-served, not a fallback
            lat_counts.append(count)
            lat_ids.append(sorted(ids.tolist()))
            dg.clear_invalid()
        lane_counts, _mask = dg.run_waves_lanes([[s] for s in seeds])
        dg.clear_invalid()
        return lat_counts, lat_ids, lane_counts.tolist()

    with _NoUnusedDonation():
        patched = read(dg)
    assert dg.mirror_patches == 1 and dg.mirror_rebuilds == 1
    assert patched[0] == [int(w.sum()) for w in want]
    assert patched[1] == [np.nonzero(w)[0].tolist() for w in want]
    assert patched[2] == patched[0]
    assert_all_live(dg)
    assert_tables_match_host(dg)
    dg.build_topo_mirror(force=True)
    assert dg.mirror_rebuilds == 2
    assert read(dg) == patched


# ------------------------------------------------------------------ the holders


def _hold_rebuild(monkeypatch):
    """Make the async rebuild's worker wait for ``release.set()`` (a sync
    build calls the same function: build the graph first)."""
    from stl_fusion_tpu.ops import topo_wave

    release = threading.Event()
    real = topo_wave.build_topo_graph

    def held(*args, **kwargs):
        assert release.wait(30)
        return real(*args, **kwargs)

    monkeypatch.setattr(topo_wave, "build_topo_graph", held)
    return release


def _install(dg, release):
    release.set()
    deadline = time.monotonic() + 30
    while not dg.poll_topo_mirror_rebuild():
        assert time.monotonic() < deadline and dg._async_rebuild is not None
        time.sleep(0.005)


@pytest.mark.parametrize("lat", ["carried", "rebuilt"])
def test_patch_and_add_edges_while_a_rebuild_is_in_flight(lat, monkeypatch):
    """The async rebuild keeps no table of the live mirror: edges appended
    and patched in while its worker runs delete the old handles, and the
    install (fresh topo tables; the lat carried over, or built from the
    rebuild's own epoch snapshot) then serves exact waves."""
    dg, ref = make_graph()
    release = _hold_rebuild(monkeypatch)
    if lat == "rebuilt":
        dg._topo_mirror["lat"] = None
    assert dg.start_topo_mirror_rebuild()
    assert dg._async_rebuild["need_lat"] == (lat == "rebuilt")
    u, v = free_pair(dg, ref)
    with _NoUnusedDonation():
        before = handles(dg)
        dg.add_edges(np.array([u]), np.array([v]))
        ref.add([u], [v])
        dg.bump_epochs(np.array([N - 1]))
        ref.bump([N - 1])
        count, ids = dg.run_waves_union([[u]])  # patches the old mirror first
        want = ref.closure([u])
        assert count == int(want.sum()) and sorted(ids.tolist()) == np.nonzero(want)[0].tolist()
        assert all(before[name].is_deleted() for name in EDGE_TABLES + TOPO_TABLES)
        dg.clear_invalid()
        rebuilds0 = dg.mirror_rebuilds
        _install(dg, release)
        assert dg.mirror_rebuilds == rebuilds0 + 1
        assert_all_live(dg)
        for seed in (u, 5, int(SRC[-1])):
            waves0 = dg.lat_waves
            count, ids = dg.run_waves_union([[seed]])
            assert dg.lat_waves == waves0 + 1
            want = ref.closure([seed])
            assert count == int(want.sum()), seed
            assert sorted(ids.tolist()) == np.nonzero(want)[0].tolist()
            dg.clear_invalid()
    assert_all_live(dg)
    assert_tables_match_host(dg)


def test_dense_wave_during_a_rebuild_leaves_the_epoch_snapshot_alive(monkeypatch):
    """The dense BFS programs (ops/wave.py) donate the whole GraphArrays,
    ``node_epoch`` included. The rebuild's epoch snapshot is a copy of its
    own, so a wave that fell to the dense path mid-rebuild does not delete
    it under the install."""
    dg, ref = make_graph()
    release = _hold_rebuild(monkeypatch)
    dg._topo_mirror["lat"] = None
    assert dg.start_topo_mirror_rebuild()
    snapshot = dg._async_rebuild["node_epoch_dev"]
    count, _ids = dg.run_waves_union([[7]], mirror="off")
    assert count == int(ref.closure([7]).sum())
    assert not snapshot.is_deleted()
    dg.clear_invalid()
    _install(dg, release)
    assert dg._topo_mirror["lat"] is not None
    count, _ids = dg.run_waves_union([[7]])
    assert dg.lat_waves == 1 and count == int(ref.closure([7]).sum())


def test_patch_and_add_edges_between_a_chain_dispatch_and_its_harvest():
    """A dispatched chain reads the tables it was given. Edges appended and
    patched in before its harvest wait for it (the runtime orders a donation
    after the reads already enqueued): the chain's counts are those of the
    topology at its dispatch, the next chain's those of the patched one."""
    dg, ref = make_graph()
    stages = [[[3], [N // 4]], [[N // 3], [9, 11]]]
    want, inv = [], np.zeros(N, dtype=bool)
    for stage in stages:
        masks = [ref.closure(g, inv) for g in stage]
        want.append([int(mk.sum()) for mk in masks])
        for mk in masks:
            inv |= mk
    u, v = free_pair(dg, ref)
    with _NoUnusedDonation():
        before = handles(dg)
        pending = dg.dispatch_waves_lanes_chain(stages)
        dg.add_edges(np.array([u]), np.array([v]))
        ref.add([u], [v])
        assert dg._mirror_valid() and dg.mirror_patches == 1
        for name in EDGE_TABLES + TOPO_TABLES + LAT_TABLES:
            assert before[name].is_deleted(), name
        counts, masks = dg.harvest_waves_lanes_chain(pending)
        assert [c.tolist() for c in counts] == want
        got_inv = np.zeros(N, dtype=bool)
        for mk in masks:
            got_inv |= mk[:N]
        np.testing.assert_array_equal(got_inv, inv)
        dg.clear_invalid()
        counts2, _ = dg.run_waves_lanes_chain([[[u]], [[3]]])
        first = ref.closure([u])
        assert [c.tolist() for c in counts2] == [
            [int(first.sum())], [int(ref.closure([3], first).sum())]
        ]
    assert_all_live(dg)
    assert_tables_match_host(dg)


WAVE_PATHS = {
    "lat": lambda dg, s: dg.run_waves_union([[s]])[0],
    "lat_seq": lambda dg, s: int(dg.run_waves_union_seq([[s]])[0][0]),
    "topo_union": lambda dg, s: dg._mirror_valid() and dg._run_mirror_union([[s]])[0],
    "lanes": lambda dg, s: int(dg.run_waves_lanes([[s]])[0][0]),
    "lanes_chain": lambda dg, s: int(dg.run_waves_lanes_chain([[[s]]])[0][0][0]),
    "dense": lambda dg, s: dg.run_waves_union([[s]], mirror="off")[0],
}


@pytest.mark.parametrize("path", sorted(WAVE_PATHS))
def test_no_wave_path_reinstalls_a_handle_the_patch_deleted(path):
    """Every wave path reads ``device_arrays()`` and writes back
    ``g._replace(invalid=...)``. None may span the ``add_edges`` or the
    patch that a pending delta triggers inside it: the old ``g`` would put
    deleted edge arrays back. With an append pending, each path serves the
    exact closure and leaves only live handles; a second append and wave
    after it still find them."""
    dg, ref = make_graph()
    with _NoUnusedDonation():
        for _ in range(2):
            u, v = free_pair(dg, ref)
            dg.add_edges(np.array([u]), np.array([v]))
            ref.add([u], [v])
            assert WAVE_PATHS[path](dg, u) == int(ref.closure([u]).sum())
            assert_all_live(dg)
            dg.clear_invalid()
    # the dense path leaves the mirror alone: its two appends patch in now
    assert dg._mirror_valid() and dg.mirror_rebuilds == 1
    assert dg.mirror_patches == (1 if path == "dense" else 2)
    assert_all_live(dg)
    assert_tables_match_host(dg)


async def test_patch_and_add_edges_between_a_superround_dispatch_and_its_harvest():
    """The resident super-round program is handed ``m["garrays"]`` at its
    dispatch. Edges appended and patched into the mirror while it is in
    flight do not disturb it (its counts equal a twin's that never saw the
    edges), and the next super-round and a device refresh run on the
    patched tables, exact against a twin that declared the same edges in
    between. ``refresh_block_on_device``'s own device_arrays/_replace pair
    is the one in ``backend.py``."""
    from test_superround import make_stack, round_bursts

    from stl_fusion_tpu.core import set_default_hub

    r1 = round_bursts(2, rng=np.random.default_rng(291))
    r2 = round_bursts(2, rng=np.random.default_rng(292))
    hub_a, b_a, _s, table_a, blk_a = make_stack()
    old = set_default_hub(hub_a)
    try:
        dg = b_a.graph
        levels = dg.mirror_levels(np.arange(dg.n_nodes))
        order = np.argsort(levels, kind="stable")
        src = order[:6].astype(np.int32)
        dst = order[-6:].astype(np.int32)  # lowest level -> highest: patchable
        with _NoUnusedDonation():
            prog = b_a.enable_super_rounds(blk_a, depth=2)
            before = handles(dg)
            t1 = prog.dispatch(prog.stage(r1))
            assert not t1.done
            patches0 = dg.mirror_patches
            dg.add_edges(src, dst)
            assert dg._mirror_valid() and dg.mirror_patches == patches0 + 1
            for name in EDGE_TABLES + TOPO_TABLES + LAT_TABLES:
                assert before[name].is_deleted(), name
            got1 = t1.harvest()
            got2 = prog.dispatch(prog.stage(r2)).harvest()
            b_a.refresh_block_on_device(blk_a)
            assert prog.eager_rounds == 0 and prog.faults == 0
            assert_all_live(dg)

        hub_b, b_b, _s2, table_b, blk_b = make_stack()
        set_default_hub(hub_b)
        want = []
        for i, groups in enumerate(r1 + r2):
            if i == len(r1):
                b_b.graph.add_edges(src, dst)
            want.append(b_b.cascade_rows_lanes(blk_b, groups))
            b_b.refresh_block_on_device(blk_b)
        assert [c.tolist() for c in got1 + got2] == [c.tolist() for c in want]
        np.testing.assert_array_equal(
            dg.invalid_mask(), b_b.graph.invalid_mask()
        )
        np.testing.assert_array_equal(
            np.asarray(table_a._values), np.asarray(table_b._values)
        )
    finally:
        set_default_hub(old)


def test_packed_mesh_mirror_patches_its_sharded_tables_in_place():
    """``PackedShardedGraph.patch_adds`` runs the same pair scatter on the
    mesh-sharded tables (8-device CPU mesh): the old shards are deleted, the
    donation is usable under the mesh's sharding, the tables equal the
    host's, and the packed waves see the new edges."""
    from stl_fusion_tpu.parallel import PackedShardedGraph
    from stl_fusion_tpu.parallel.mesh import graph_mesh

    mesh = graph_mesh()
    assert mesh.devices.size == 8
    pg = PackedShardedGraph(SRC, DST, N, mesh=mesh, k=4, slack=2)
    ref = HostRef(SRC, DST)
    adds = [(2, N - 1), (5, N - 2), (N // 2, N - 1)]
    adds = [(u, v) for (u, v) in adds if (u, v) not in ref.edges]
    u64 = np.array([a[0] for a in adds], dtype=np.int64)
    v64 = np.array([a[1] for a in adds], dtype=np.int64)
    old_in_src, old_epoch = pg.in_src, pg.edge_epoch
    sharding0 = pg.in_src.sharding
    with _NoUnusedDonation():
        assert pg.patch_adds(u64, v64, np.zeros(len(adds), dtype=np.int64))
    assert old_in_src.is_deleted() and old_epoch.is_deleted()
    assert not pg.in_src.is_deleted() and not pg.edge_epoch.is_deleted()
    assert pg.in_src.sharding == sharding0 and pg.edge_epoch.sharding == sharding0
    np.testing.assert_array_equal(np.asarray(pg.in_src), pg.h_in_src)
    np.testing.assert_array_equal(np.asarray(pg.edge_epoch), pg.h_edge_epoch)
    ref.add(u64, v64)
    seeds = [[int(u64[0])], [int(u64[-1])], [3]]
    total = pg.run_waves(seeds)
    want = [ref.closure(s) for s in seeds]
    for w, mask in enumerate(want):
        np.testing.assert_array_equal(pg.invalid_mask(wave=w), mask, err_msg=f"wave {w}")
    assert total == sum(int(mk.sum()) for mk in want)
