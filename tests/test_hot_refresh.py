"""A table kept HOT (``TableBacking(hot=True)``, PR 38): after every wave the
pipeline applies, the rows the wave invalidated are recomputed on the device
through the table's device loader, those rows alone
(``TpuGraphBackend.refresh_rows_on_device``), sources before the rows derived
from them, and are valid again in the graph before the next wave.

Pinned here on the CPU backend: the sparse refresh against the whole-block
one (``refresh_block_on_device``) and against ``MemoTable.refresh(ids)`` on
two- and three-block graphs (values, valid masks, the host and the device
invalid state, ``invalid_version``, the ``on_refresh`` hooks, scalar twins,
rows not in the wave); block order and the cycle; one program a power-of-two
width; a second wave on the same seed counting in full; the declaration's
demands; and that a backend with no hot table gains no call anywhere.
"""
import asyncio

import numpy as np
import pytest

from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    capture,
    compute_method,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.graph import TpuGraphBackend

P, C, L = 12, 60, 4
RNG = np.random.default_rng(38)
LINES = np.stack([RNG.choice(P, L, replace=False) for _ in range(C)]).astype(np.int32)
QTY = RNG.integers(1, 10, (C, L)).astype(np.float32)


def make_shop(hot: bool, with_cart: bool):
    """Products (stored), optionally carts (stored, never hot), totals
    (derived from the product table's device values)."""
    import jax.numpy as jnp

    class Shop(ComputeService):
        def __init__(self, hub):
            super().__init__(hub)
            self.price = np.arange(1, P + 1, dtype=np.float32)

        def load_p(self, ids):
            return self.price[np.asarray(ids)]

        def load_p_dev(self, ids, price):
            return price[ids]

        def p_args(self):
            return (jnp.asarray(self.price.copy()),)

        def load_c(self, ids):
            return np.full(len(ids), L, np.float32)

        def load_c_dev(self, ids):
            return jnp.full(ids.shape, L, jnp.float32)

        def load_t(self, ids):
            ids = np.asarray(ids)
            return (self.price[LINES[ids]] * QTY[ids]).sum(axis=1)

        def load_t_dev(self, ids, prices, lines, qty):
            return (prices[lines[ids]] * qty[ids]).sum(axis=1)

        def t_args(self):
            return (memo_table_of(self.product).values, jnp.asarray(LINES), jnp.asarray(QTY))

        @compute_method(table=TableBacking(
            rows=P, batch="load_p", device_batch="load_p_dev", device_args="p_args", hot=hot))
        async def product(self, i: int) -> float:
            return float(self.price[i])

        @compute_method(table=TableBacking(rows=C, batch="load_c", device_batch="load_c_dev"))
        async def cart(self, c: int) -> float:
            return float(L)

        @compute_method(table=TableBacking(
            rows=C, batch="load_t", device_batch="load_t_dev", device_args="t_args", hot=hot))
        async def total(self, c: int) -> float:
            if with_cart:
                await self.cart(c)
            return float(sum(
                [(await self.product(int(p))) * float(q) for p, q in zip(LINES[c], QTY[c])]
            ))

    return Shop


class World:
    """One hub, backend and shop; blocks bound product, [cart,] total."""

    def __init__(self, hot=True, with_cart=False, pipeline=True):
        self.hub = FusionHub()
        self.old = set_default_hub(self.hub)
        self.be = TpuGraphBackend(self.hub, node_capacity=256, edge_capacity=4096)
        self.svc = make_shop(hot, with_cart)(self.hub)
        self.hub.add_service(self.svc, "shop")
        names = ["product"] + (["cart"] if with_cart else []) + ["total"]
        self.tables = {n: memo_table_of(getattr(self.svc, n)) for n in names}
        self.blocks = {n: self.be.bind_table_rows(t) for n, t in self.tables.items()}
        carts = np.repeat(np.arange(C), L)
        self.be.declare_row_edges(
            self.blocks["product"], LINES.reshape(-1), self.blocks["total"], carts)
        if with_cart:
            self.be.declare_row_edges(
                self.blocks["cart"], np.arange(C), self.blocks["total"], np.arange(C))
        for n in names:
            self.be.warm_block_on_device(self.blocks[n])
        self.be.flush()
        self.be.build_topo_mirror()
        self.pipe = self.hub.enable_nonblocking(8) if pipeline else None

    def close(self):
        if self.pipe is not None:
            self.pipe.dispose()
        set_default_hub(self.old)

    def edit(self, p: int, delta: float = 5.0):
        self.svc.price[p] += np.float32(delta)

    def wave(self, p: int):
        """An edit's wave through the blocking entry: (count, newly ids)."""
        blk = self.blocks["product"]
        count, ids = self.be._wave_union([[blk.base + p]])
        self.be._apply_newly(ids)
        return int(count), ids

    def want_totals(self):
        return (self.svc.price[LINES] * QTY).sum(axis=1)

    def state(self):
        g = self.be.graph
        return {
            "values": {n: np.asarray(t.values).copy() for n, t in self.tables.items()},
            "valid": {n: np.asarray(t.valid_mask).copy() for n, t in self.tables.items()},
            "stale": {n: t.stale_count() for n, t in self.tables.items()},
            "h_invalid": g._h_invalid.copy(),
            "d_invalid": np.asarray(g.device_arrays().invalid).copy(),
        }


@pytest.fixture
def worlds():
    made = []

    def make(**kw):
        made.append(World(**kw))
        return made[-1]

    yield make
    for w in reversed(made):
        w.close()


def fanout(p: int) -> int:
    return int((LINES == p).any(axis=1).sum())


@pytest.mark.parametrize("with_cart", [False, True], ids=["two_blocks", "three_blocks"])
def test_rows_refresh_equals_block_refresh_on_the_waves_rows(worlds, with_cart):
    a, b = worlds(with_cart=with_cart, pipeline=False), worlds(with_cart=with_cart, pipeline=False)
    for w in (a, b):
        w.edit(3)
        count, ids = w.wave(3)
        assert count == 1 + fanout(3)
    before = a.state()
    hooks = {n: [] for n in a.tables}
    for n, t in a.tables.items():
        t.on_refresh.append(lambda rows, n=n: hooks[n].append(np.asarray(rows).copy()))
    v0 = a.be.graph.invalid_version
    assert a.be.refresh_rows_on_device(ids) == len(ids)
    b.be.HOT_REFRESH_MAX_ROWS = 0  # instance override: b's blocks take the whole program
    for blk in b.be._hot_blocks:
        b.be.refresh_block_on_device(blk)
    sa, sb = a.state(), b.state()
    for key in ("values", "valid", "stale"):
        for n in a.tables:
            np.testing.assert_array_equal(sa[key][n], sb[key][n], err_msg=f"{key} {n}")
    np.testing.assert_array_equal(sa["h_invalid"], sb["h_invalid"])
    np.testing.assert_array_equal(sa["d_invalid"], sb["d_invalid"])
    assert not sa["h_invalid"].any() and not sa["d_invalid"].any()
    np.testing.assert_array_equal(sa["values"]["total"], a.want_totals())
    # one bump of invalid_version a refreshed block, the hooks with the ids
    assert a.be.graph.invalid_version == v0 + 2
    tb = a.blocks["total"]
    np.testing.assert_array_equal(
        np.sort(hooks["total"][0]), np.sort(ids[ids >= tb.base] - tb.base))
    np.testing.assert_array_equal(hooks["product"][0], [3])
    assert "cart" not in hooks or hooks["cart"] == []
    # no row outside the wave was touched
    untouched = np.ones(C, bool)
    untouched[ids[ids >= tb.base] - tb.base] = False
    np.testing.assert_array_equal(
        sa["values"]["total"][untouched], before["values"]["total"][untouched])
    assert a.be.hot_refresh_rows == len(ids) and a.be.hot_refresh_dispatches == 2
    assert a.be.hot_refresh_block_fallbacks == 0


def test_rows_refresh_equals_the_host_refresh(worlds):
    a, b = worlds(pipeline=False), worlds(pipeline=False)
    for w in (a, b):
        w.edit(5, 2.0)
        _count, ids = w.wave(5)
    a.be.refresh_rows_on_device(ids)
    for name in ("product", "total"):  # the host twin, sources first
        blk = b.blocks[name]
        b.tables[name].refresh(ids[(ids >= blk.base) & (ids < blk.end())] - blk.base)
    b.be.flush()
    sa, sb = a.state(), b.state()
    for n in a.tables:
        np.testing.assert_array_equal(sa["values"][n], sb["values"][n])
        np.testing.assert_array_equal(sa["valid"][n], sb["valid"][n])
    np.testing.assert_array_equal(sa["h_invalid"], sb["h_invalid"])
    np.testing.assert_array_equal(sa["d_invalid"], sb["d_invalid"])


def test_a_second_wave_on_the_same_seed_counts_in_full(worlds):
    w = worlds()
    blk = w.blocks["product"]
    for delta in (1.0, 2.0, 3.0):
        w.edit(7, delta)
        ticket = w.pipe.submit_rows(blk, [7])
        w.pipe.drain()
        assert ticket.count == 1 + fanout(7)
        assert w.pipe.lat_waves >= 1 and w.pipe.eager_waves == 0
        np.testing.assert_array_equal(np.asarray(w.tables["total"].values), w.want_totals())
        assert not w.be.graph._h_invalid.any()
        assert all(t.stale_count() == 0 for t in w.tables.values())
    assert w.be.hot_refresh_rows == 3 * (1 + fanout(7))
    assert w.be.hot_refresh_block_fallbacks == 0


def test_scalar_twins_stay_pending_until_their_next_read(worlds):
    w = worlds()

    async def run():
        cart = int(np.flatnonzero((LINES == 2).any(axis=1))[0])
        before = await capture(lambda: w.svc.total(cart))
        w.be.flush()
        w.edit(2, 4.0)
        ticket = w.pipe.submit_rows(w.blocks["product"], [2])
        w.pipe.drain()
        assert ticket.count == 1 + fanout(2)
        nid = w.blocks["total"].base + cart
        assert w.be._pending[nid] and not w.be.graph._h_invalid[nid]
        assert not before.is_consistent  # pending-aware
        after = await capture(lambda: w.svc.total(cart))
        assert after is not before and after.value == float(w.want_totals()[cart])
        w.be.flush()
        # the displaced twin's echo marks nothing stale on a hot table
        assert all(t.stale_count() == 0 for t in w.tables.values())
        assert not w.be.graph._h_invalid.any()

    asyncio.run(run())


def test_blocks_refresh_sources_first_whatever_the_bind_order():
    """Totals bound BEFORE products: the declared edge still puts the
    product block first, and a total is computed from the fresh price."""
    import jax.numpy as jnp

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        be = TpuGraphBackend(hub, node_capacity=256, edge_capacity=4096)
        svc = make_shop(True, False)(hub)
        hub.add_service(svc, "shop")
        tt, tp = memo_table_of(svc.total), memo_table_of(svc.product)
        bt, bp = be.bind_table_rows(tt), be.bind_table_rows(tp)
        assert [b.base for b in be._hot_blocks] == [bt.base, bp.base]
        be.declare_row_edges(bp, LINES.reshape(-1), bt, np.repeat(np.arange(C), L))
        assert [b.base for b in be._hot_blocks] == [bp.base, bt.base]
        be.warm_block_on_device(bp)
        be.warm_block_on_device(bt)
        be.flush()
        svc.price[1] += 9
        count, ids = be._wave_union([[bp.base + 1]])
        be._apply_newly(ids)
        be.refresh_rows_on_device(ids)
        np.testing.assert_array_equal(
            np.asarray(tt.values), (svc.price[LINES] * QTY).sum(axis=1))
        with pytest.raises(ValueError, match="cycle"):
            be.declare_row_edges(bt, [0], bp, [0])
        assert (bt.base, bp.base) not in be._block_edges  # nothing was declared
        assert jnp is not None
    finally:
        set_default_hub(old)


@pytest.mark.parametrize("rows,width", [(1, 512), (300, 512), (513, 1024), (700, 1024)])
def test_id_widths_share_a_program_by_power_of_two(rows, width):
    """One trace of ``refresh_rows`` a padded width, floor 512."""
    import jax.numpy as jnp

    from stl_fusion_tpu.ops.memo_table import MemoTable

    n = 2048
    hub = FusionHub()
    be = TpuGraphBackend(hub, node_capacity=n + 64, edge_capacity=64)
    table = MemoTable(n, lambda ids: np.asarray(ids, np.float32))
    table.device_compute_fn = lambda ids: ids.astype(jnp.float32) * 2
    table.hot = True
    blk = be.bind_table_rows(table)
    be.warm_block_on_device(blk)
    prog = be._refresh_rows_program(blk)
    for k in (rows, rows + 1):  # two id counts, one padded width
        ids = np.arange(k, dtype=np.int64)
        be.graph.mark_invalid(ids.astype(np.int32))
        table._mark_stale_from_wave(ids)
        assert be.refresh_rows_on_device(blk.base + ids) == k
    assert prog._cache_size() == 1
    assert (1 << (max(rows, 512) - 1).bit_length()) == width
    assert table.stale_count() == 0 and not be.graph._h_invalid.any()
    np.testing.assert_array_equal(np.asarray(table.values)[:rows], 2.0 * np.arange(rows))


def test_a_wave_too_wide_or_a_mask_takes_the_block_program(worlds):
    w = worlds(pipeline=False)
    w.be.HOT_REFRESH_MAX_ROWS = 4  # instance override: the product's closure is wider
    w.edit(3)
    _count, ids = w.wave(3)
    assert w.be.refresh_hot(ids) == len(ids)
    assert w.be.hot_refresh_block_fallbacks == 1
    np.testing.assert_array_equal(np.asarray(w.tables["total"].values), w.want_totals())
    w.edit(4)
    _count, ids = w.wave(4)
    mask = np.zeros(w.be.graph.n_nodes, dtype=bool)
    mask[ids] = True
    assert w.be.refresh_hot(mask) == len(ids)
    assert w.be.hot_refresh_block_fallbacks == 2
    np.testing.assert_array_equal(np.asarray(w.tables["total"].values), w.want_totals())
    assert not w.be.graph._h_invalid.any()
    assert not np.asarray(w.be.graph.device_arrays().invalid).any()


def test_a_hot_table_needs_a_device_loader_and_a_full_bind():
    from stl_fusion_tpu.ops.memo_table import MemoTable

    with pytest.raises(ValueError, match="device loader"):
        TableBacking(rows=4, batch="load", hot=True)
    be = TpuGraphBackend(FusionHub(), node_capacity=64, edge_capacity=64)
    table = MemoTable(8, lambda ids: np.zeros(len(ids), np.float32))
    table.hot = True
    with pytest.raises(ValueError, match="hot table"):
        be.bind_table_rows(table)  # no device loader
    table.device_compute_fn = lambda ids: ids * 0.0
    with pytest.raises(ValueError, match="hot table"):
        be.bind_table_rows(table, n_rows=4)  # a partial bind
    assert be.bind_table_rows(table).n_rows == 8 and len(be._hot_blocks) == 1


def test_a_backend_without_a_hot_table_gains_no_call(worlds, monkeypatch):
    """The accepted cells declare none: the lone cascade, the pipeline's
    small-wave and chain paths and the super-round run as before, without a
    call, a transfer or a dispatch of the refresh."""
    w = worlds(hot=False)
    assert w.be._hot_blocks == []

    def boom(*_a, **_k):
        raise AssertionError("a backend with no hot table reached the hot refresh")

    for name in ("refresh_hot", "refresh_rows_on_device", "_refresh_rows_program"):
        monkeypatch.setattr(TpuGraphBackend, name, boom)
    blk = w.blocks["product"]
    assert w.be.cascade_rows_batch(blk, [3]) == 1 + fanout(3)  # the lone edit's entry
    ticket = w.pipe.submit_rows(blk, [4])  # the small-wave path
    w.pipe.drain()
    assert ticket.count == 1 + fanout(4) - len(
        set(np.flatnonzero((LINES == 3).any(axis=1))) & set(np.flatnonzero((LINES == 4).any(axis=1))))
    tickets = [w.pipe.submit_rows(blk, list(range(5, 5 + 3))) for _ in range(2)]
    w.be.graph.LAT_SEED_MAX = 1  # instance override: the accumulation rides the chain
    w.pipe.drain()
    assert all(t.done for t in tickets) and w.pipe.fused_dispatches >= 1
    # what went stale stays stale: nothing refreshed behind the caller's back
    assert w.tables["total"].stale_count() > 0
    sr = w.be.enable_super_rounds(w.blocks["total"], depth=2, max_words=1)
    try:
        sr.dispatch(sr.stage([[[0], [1]], [[2]]]))  # two rounds of row groups
        sr.drain()
    finally:
        sr.dispose()
    assert w.be.hot_refresh_rows == w.be.hot_refresh_dispatches == 0
    assert w.be.hot_refresh_block_fallbacks == 0
    assert w.tables["product"].stale_count() > 0  # the super-round's own block is its own


# ------------------------------------------- the journal's commuting entries
def test_commuting_journal_entries_are_grouped_by_kind():
    """Bumps, cpacks, edges and epacks of a stretch regroup by kind; a bump
    stays behind an earlier add into its node and behind an earlier bump of
    it; ``icasc`` and ``invalid`` end a stretch and nothing crosses them."""
    group = TpuGraphBackend._group_commuting_entries

    def ep(dst, srcs=(5,)):
        return ("epack", (np.asarray(srcs, np.int32), np.full(len(srcs), dst, np.int32)))

    journal = [
        ("cpack", 1), ("edge", (2, 9)), ("cpack", 3), ("edge", (4, 9)), ("bump", 5),
        ("edge", (7, 5)), ("cpack", 8), ("icasc", 9), ("edge", (1, 2)), ("cpack", 11),
    ]
    assert group(journal) == [
        ("bump", 5), ("cpack", 1), ("cpack", 3), ("cpack", 8), ("edge", (2, 9)),
        ("edge", (4, 9)), ("edge", (7, 5)), ("icasc", 9), ("cpack", 11), ("edge", (1, 2)),
    ]
    # a command's re-reads: two totals recomputed, the product inside the first
    t1, t2 = ep(20, (1, 2)), ep(21, (1, 3))
    journal = [("bump", 20), t1, ("edge", (30, 20)), ("bump", 1), ("edge", (1, 20)),
               ("edge", (2, 20)), ("bump", 21), t2, ("edge", (1, 21)), ("edge", (3, 21))]
    out = group(journal)
    assert [k for k, _ in out] == ["bump"] * 3 + ["edge"] * 5 + ["epack"] * 2
    assert [p for k, p in out if k == "bump"] == [20, 1, 21]
    # an edge captured BEFORE its dependent's bump stays dead: the bump does not pass it
    assert [k for k, _ in group([("edge", (1, 7)), ("bump", 7), ("edge", (1, 7))])] == [
        "edge", "bump", "edge"]
    assert [k for k, _ in group([ep(7), ("cpack", 2), ("bump", 7), ep(7)])] == [
        "cpack", "epack", "bump", "epack"]
    assert group([]) == [] and group([("invalid", 1)]) == [("invalid", 1)]


def test_first_reads_of_derived_rows_flush_in_a_few_runs(worlds, monkeypatch):
    """Twenty first reads of totals journal an alternation of adoptions and
    captured edges; the flush replays it in a handful of runs, and the
    graph it leaves is the one an entry-by-entry replay leaves."""
    runs = {}

    def states(grouped: bool):
        w = worlds()
        if not grouped:
            monkeypatch.setattr(
                TpuGraphBackend, "_group_commuting_entries", staticmethod(lambda j: j))
        replay = TpuGraphBackend._replay_run
        runs[grouped] = 0

        def counted(self, kind, batch, icasc_parts):
            runs[grouped] += 1
            return replay(self, kind, batch, icasc_parts)

        monkeypatch.setattr(TpuGraphBackend, "_replay_run", counted)

        async def read():
            for c in range(20):
                await w.svc.total(c)

        asyncio.run(read())
        w.be.flush()
        monkeypatch.undo()
        g = w.be.graph
        m = g.n_edges
        return sorted(zip(g._h_edge_src[:m].tolist(), g._h_edge_dst[:m].tolist(),
                          g._h_edge_dst_epoch[:m].tolist())), g._h_invalid.copy()

    (edges_a, inv_a), (edges_b, inv_b) = states(True), states(False)
    assert edges_a == edges_b
    np.testing.assert_array_equal(inv_a, inv_b)
    assert runs[True] <= 4 < 40 <= runs[False]
