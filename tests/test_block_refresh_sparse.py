"""A block's restore refreshes what is stale, not the whole table:
``TpuGraphBackend.refresh_block_on_device`` runs the ``refresh_rows`` program
on a block's invalid rows when there are from 1 to ``HOT_REFRESH_MAX_ROWS``
of them, and the whole-block program otherwise.

Pinned here on the CPU backend: the sparse branch against the whole program
(the same backend with the cap set to 0) on values, validity, the host and
device invalid masks, the stale mask and count, both versions and the
``on_refresh`` hooks' ids, for 0, 1, 23, the cap and the cap + 1 invalid
rows, on a plain and on a hot block, with the device validity read and left
dirty; which branch ran; the O(1) proxy's premise (every graph-invalid row
of a bound block is stale on its table) and that a scan past the cap falls
back whatever the proxy said; and, on the four-device CPU mesh, a routed
wave restored sparsely and then run again.
"""
import numpy as np
import pytest

from stl_fusion_tpu.core import FusionHub, set_default_hub
from stl_fusion_tpu.graph import TpuGraphBackend
from stl_fusion_tpu.ops.memo_table import MemoTable

CAP = TpuGraphBackend.HOT_REFRESH_MAX_ROWS
N = CAP + 64


class World:
    """A backend with one bound table whose device loader reads a store;
    ``whole`` sets the backend's cap to 0, so every refresh takes the
    whole-block program."""

    def __init__(self, hot: bool, whole: bool):
        import jax.numpy as jnp

        self.be = TpuGraphBackend(FusionHub(), node_capacity=N + 64, edge_capacity=64)
        if whole:
            self.be.HOT_REFRESH_MAX_ROWS = 0  # instance override
        self.store = np.arange(N, dtype=np.float32)
        table = self.table = MemoTable(N, lambda ids: self.store[np.asarray(ids)] * 2 + 1)
        table.device_compute_fn = lambda ids, store: store[ids] * 2 + 1
        # a fresh device copy each refresh: the store is replaced, never edited
        table.device_loader_args = lambda: (jnp.array(self.store),)
        table.hot = hot
        self.blk = self.be.bind_table_rows(table)
        self.be.warm_block_on_device(self.blk)
        self.hooked: list = []
        table.on_refresh.append(lambda rows: self.hooked.append(np.asarray(rows).copy()))

    def invalidate(self, rows) -> None:
        """The store changes under ``rows``; a wave from them (no edges: the
        closure is the rows) marks them invalid in the graph and stale."""
        self.store = self.store + 100.0
        if len(rows):
            assert self.be.cascade_rows_batch(self.blk, rows) == len(rows)

    def state(self) -> dict:
        g, t = self.be.graph, self.table
        return {
            "values": np.asarray(t.values).copy(),
            "valid": np.asarray(t.valid_mask).copy(),
            "h_invalid": g._h_invalid.copy(),
            "d_invalid": np.asarray(g.device_arrays().invalid).copy(),
            "stale_host": t._stale_host.copy(),
            "stale_count": t._stale_count,
            "version": t.version,
            "invalid_version": g.invalid_version,
            "hooked": [h.tolist() for h in self.hooked],
        }


def graph_invalid_rows_are_stale(be, blk) -> bool:
    """The proxy's premise: no row of the block is invalid in the graph and
    fresh on its table."""
    inv = be.graph._h_invalid[blk.base : blk.end()]
    return not (inv & ~blk.table._stale_host).any()


@pytest.mark.parametrize("valid", ["read", "dirty"])
@pytest.mark.parametrize("hot", [False, True], ids=["plain", "hot"])
@pytest.mark.parametrize("k", [0, 1, 23, CAP, CAP + 1])
def test_sparse_block_refresh_equals_the_whole_program(k, hot, valid):
    rows = np.sort(np.random.default_rng(40 + k).choice(N, size=k, replace=False))
    sparse, whole = World(hot, whole=False), World(hot, whole=True)
    for w in (sparse, whole):
        w.invalidate(rows)
        assert graph_invalid_rows_are_stale(w.be, w.blk)
        assert w.table._valid_dev_dirty == bool(k)  # a wave defers the device mask
        if valid == "read":
            w.table.valid_mask  # noqa: B018 — materializes it: the whole program keeps it
            assert not w.table._valid_dev_dirty
    values0 = np.asarray(sparse.table.values).copy()  # no valid_mask read: it materializes
    version0 = sparse.be.graph.invalid_version
    for w in (sparse, whole):
        assert w.table._valid_dev_dirty == (valid == "dirty" and k > 0)
        assert w.be.refresh_block_on_device(w.blk) == k
    assert sparse.be.block_refresh_sparse == int(1 <= k <= CAP)
    assert whole.be.block_refresh_sparse == 0
    sa, sb = sparse.state(), whole.state()
    for key in sa:
        np.testing.assert_array_equal(sa[key], sb[key], err_msg=key)
    # nothing left stale, the refreshed rows from the store, the others kept
    assert sa["stale_count"] == 0 and not sa["h_invalid"].any() and not sa["d_invalid"].any()
    assert sa["valid"].all()
    np.testing.assert_array_equal(sa["values"][rows], sparse.store[rows] * 2 + 1)
    kept = np.ones(N, dtype=bool)
    kept[rows] = False
    np.testing.assert_array_equal(sa["values"][kept], values0[kept])
    assert sa["invalid_version"] == version0 + int(k > 0)
    assert sa["hooked"] == ([rows.tolist()] if k else [])


def test_one_sparse_program_a_block_whatever_the_row_count():
    """The sparse branch pads to the cap itself: 1, 23 and the cap rows run
    one trace of ``refresh_rows``."""
    w = World(hot=False, whole=False)
    prog = w.be._refresh_rows_program(w.blk)
    rng = np.random.default_rng(3)
    for k in (1, 23, CAP):
        w.invalidate(rng.choice(N, size=k, replace=False))
        assert w.be.refresh_block_on_device(w.blk) == k
    assert prog._cache_size() == 1 and w.be.block_refresh_sparse == 3


def test_a_scan_past_the_cap_takes_the_whole_program_whatever_the_proxy_says():
    """Graph marks the table does not count as stale (the proxy's premise
    broken on purpose): the table's count is under the cap, the scan finds
    more than the cap, and the whole program clears them all."""
    w = World(hot=False, whole=False)
    rows = np.arange(CAP + 1, dtype=np.int32)
    w.be.graph.mark_invalid(w.blk.base + rows)
    assert w.table.stale_count() == 0 and not graph_invalid_rows_are_stale(w.be, w.blk)
    assert w.be.refresh_block_on_device(w.blk) == CAP + 1
    assert w.be.block_refresh_sparse == 0
    assert not w.be.graph._h_invalid.any()
    assert not np.asarray(w.be.graph.device_arrays().invalid).any()


def test_the_counter_is_exported():
    from stl_fusion_tpu.diagnostics.metrics import global_metrics

    w = World(hot=False, whole=False)
    w.invalidate([5, 6])
    w.be.refresh_block_on_device(w.blk)
    assert w.be._collect_metrics()["fusion_refresh_block_sparse_total"] == 1
    assert global_metrics().snapshot()["fusion_refresh_block_sparse_total"] >= 1


# ----------------------------------------------- the routed wave and its restore
def _closure(src, dst, n, seeds) -> np.ndarray:
    """bool[n]: the seeds and every transitive dependent, by host BFS."""
    order = np.argsort(src, kind="stable")
    starts = np.searchsorted(src[order], np.arange(n + 1))
    out = np.zeros(n, dtype=bool)
    stack = list(seeds)
    while stack:
        u = stack.pop()
        if out[u]:
            continue
        out[u] = True
        stack.extend(dst[order[starts[u] : starts[u + 1]]].tolist())
    return out


def test_a_routed_wave_restores_sparsely_and_runs_again_in_full():
    """On the CPU four-device mesh: an 8-seed routed wave, the restore (the
    sparse branch: nothing stale after it), the same wave again (its count
    is the whole closure again, so the routed sync read the restored mask);
    then a wave whose closure passes the cap restores by the whole program."""
    import jax.numpy as jnp

    from stl_fusion_tpu.cluster import ShardMap
    from stl_fusion_tpu.graph.synthetic import power_law_dag
    from stl_fusion_tpu.parallel import graph_mesh

    ns = 40_000
    src, dst = power_law_dag(ns, avg_degree=3.0, seed=5)
    rng = np.random.default_rng(8)
    small = (ns // 2 + rng.choice(ns // 2, size=8, replace=False)).tolist()
    large = rng.choice(ns // 10, size=200, replace=False).tolist()
    want_small = _closure(src, dst, ns, small)
    want_large = _closure(src, dst, ns, large)
    assert 8 <= want_small.sum() <= CAP < want_large.sum()

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        be = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=len(src) + 256)
        store = np.arange(ns, dtype=np.float32)
        table = MemoTable(ns, lambda ids: store[np.asarray(ids)])
        table.device_compute_fn = lambda ids, s: s[ids]
        table.device_loader_args = lambda: (jnp.array(store),)
        blk = be.bind_table_rows(table)
        be.declare_row_edges(blk, src, blk, dst)
        be.warm_block_on_device(blk)
        be.flush()
        be.enable_mesh_routing(
            ShardMap.initial(["m0", "m1", "m2", "m3"], n_shards=32),
            mesh=graph_mesh(n_devices=4),
        )
        dg = be.graph

        def restore() -> None:
            be.refresh_block_on_device(blk)
            be.flush()
            assert table.stale_count() == 0 and not dg._h_invalid.any()
            assert not np.asarray(dg.invalid_mask()).any()

        for i in range(2):
            assert be.cascade_rows_batch_routed(blk, small) == want_small.sum()
            assert np.array_equal(table._stale_host, want_small)
            assert graph_invalid_rows_are_stale(be, blk)
            restore()
            assert be.block_refresh_sparse == i + 1
        assert be.cascade_rows_batch_routed(blk, large) == want_large.sum()
        assert graph_invalid_rows_are_stale(be, blk)
        restore()
        assert be.block_refresh_sparse == 2  # the whole program ran
        np.testing.assert_array_equal(np.asarray(table.values), store)
    finally:
        set_default_hub(old)
