"""The HelloCart deployment (``hellocart-w100-1c``, cell
``hellocart-w100-1c-edits``) on the CPU at its rehearsal size: the generator
and the plain reference (``benchmarks/lib/cartgraph.py``, ``cartref.py``)
against hand-made cases; the system against the reference on seeded traffic,
with all three controls incorrect; each planted fault seen by the driver's
``check``; and the deployment's exit 3 on a program without the hot-table
declaration. Tiny sizes: no number here is a device number.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
CELL = "hellocart-w100-1c-edits"
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


# ------------------------------------------------------ generator and reference
def hand_made():
    """Three products, four carts: cart 0 holds products 0 and 1, cart 1
    holds 1 and 2, cart 2 holds 0 alone, cart 3 holds 2 alone."""
    from lib.cartgraph import MAX_LINES, CartData

    product = np.zeros((4, MAX_LINES), np.int32)
    qty = np.zeros((4, MAX_LINES), np.int32)
    for cart, lines in enumerate([[(0, 2), (1, 3)], [(1, 1), (2, 4)], [(0, 5)], [(2, 1)]]):
        for j, (p, q) in enumerate(lines):
            product[cart, j], qty[cart, j] = p, q
    return CartData(
        np.array([10, 20, 30], np.int64), np.array([2, 2, 1, 1], np.int32), product, qty, 0
    )


def test_cartref_totals_and_closures_by_hand():
    from lib.cartref import CartRef

    ref = CartRef(hand_made())
    assert ref.all_totals().tolist() == [80, 140, 50, 30]
    assert [ref.total(c) for c in range(4)] == [80, 140, 50, 30]
    assert [ref.carts_of(p).tolist() for p in range(3)] == [[0, 2], [0, 1], [1, 3]]
    assert [ref.fanout(p) for p in range(3)] == [2, 2, 2]


def test_cartref_replay_by_hand():
    from lib.cartref import replay

    subs = [(0, 0), (1, 0), (2, 1), (3, 3)]  # cart 0 twice, cart 1, cart 3
    events = [
        ("cmd", "a", 1, 5),  # product 1: carts 0 and 1
        ("reread", 0, 0, None), ("reread", 1, 0, None), ("reread", 2, 1, None),
        ("cmd", "b", 0, 1),  # product 0: carts 0 and 2 (cart 2 unwatched)
        ("reread", 1, 0, None), ("reread", 0, 0, None),
        ("cmd", "c", 1, 2),
    ]
    got = replay(hand_made(), subs, events)
    assert got.journal == ["a", "b", "c"]
    assert got.price.tolist() == [11, 27, 30]
    assert got.newly_counts == [3, 3, 3]  # 1 + fan-out, every time
    assert got.observers == [
        frozenset({(0, 0), (1, 0), (2, 1)}), frozenset({(0, 0), (1, 0)}),
        frozenset({(0, 0), (1, 0), (2, 1)}),
    ]
    assert got.reread_values == [95, 95, 145, 97, 97]
    assert got.totals.tolist() == [2 * 11 + 3 * 27, 27 + 120, 55, 30]


def test_cartref_controls_differ_from_the_reference():
    from lib.cartref import replay

    subs = [(0, 0), (2, 1)]
    events = [
        ("cmd", "a", 1, 5), ("reread", 0, 0, None),  # cart 1's watcher does not re-read
        ("cmd", "b", 1, 2), ("reread", 0, 0, None), ("reread", 2, 1, None),
    ]
    want = replay(hand_made(), subs, events)
    assert want.newly_counts == [3, 3] and want.reread_values == [95, 101, 147]
    stale = replay(hand_made(), subs, events, stale_total=True)
    assert stale.reread_values == [80, 95, 145] and stale.newly_counts == want.newly_counts
    cold = replay(hand_made(), subs, events, no_refresh=True)
    # the second edit: product 1 (recomputed by cart 0's re-read) and cart 0
    # count again, cart 1 is still invalid and its watcher is not told again
    assert cold.newly_counts == [3, 2]
    assert cold.observers[1] == frozenset({(0, 0)}) != want.observers[1]
    lost = replay(hand_made(), subs, events, drop_op="b")
    assert lost.journal == ["a"] and lost.price.tolist() == [10, 25, 30]
    assert lost.totals.tolist() != want.totals.tolist()


@pytest.mark.parametrize("products,carts", [(200, 1500), (2000, 20000)])
def test_cartgraph_draws_tpcc_shapes(products, carts):
    from lib import cartgraph

    data = cartgraph.generate(products, carts, 0)
    again = cartgraph.generate(products, carts, 0)
    np.testing.assert_array_equal(data.line_product, again.line_product)
    assert data.ol_cnt.min() == 5 and data.ol_cnt.max() == 15
    assert data.price.min() >= 1 and data.price.max() <= 100
    cart, product, qty = data.lines()
    assert len(cart) == int(data.ol_cnt.sum())  # a product drawn twice is drawn again
    assert len(np.unique(cart * products + product)) == len(cart)
    assert qty.min() == 1 and qty.max() == 10 and 0 <= product.min() and product.max() < products
    assert (data.line_qty[~data.live] == 0).all() and (data.line_product[~data.live] == 0).all()
    fan = np.bincount(product, minlength=products)
    assert fan.max() > 3 * fan.mean() and fan.max() > 4 * np.median(fan)  # NURand's skew
    src, dst = cartgraph.edges(data, 0, products, products + carts)
    assert len(src) == len(cart) + carts
    indeg = np.bincount(dst - products - carts, minlength=carts)
    assert indeg.min() == 6 and indeg.max() == 16
    lo, hi = int(np.percentile(fan, 60)), int(np.percentile(fan, 95))
    pool, watched, fanout = cartgraph.choose_pool(data, 8, 0, lo, hi, 4)
    assert ((fanout >= lo) & (fanout <= hi)).all() and len(set(pool.tolist())) == 8
    for p, carts4 in zip(pool.tolist(), watched.tolist()):
        assert carts4 == np.sort(cart[product == p])[:4].tolist()


# ----------------------------------------------------------- the whole harness
def run_harness(script, *args):
    return subprocess.run(
        [sys.executable, script, *args, "--workload", CELL, "--cpu-rehearsal",
         "--seconds", "0.5", "--seed", "11", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )


def test_the_system_equals_the_reference_and_every_control_is_incorrect():
    proc = run_harness(os.path.join(BENCH, "run.py"), "--control", "1")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    counters, notes = line["notes"]["counters"], line["notes"]
    assert counters["lat_waves"] == counters["commands"] > 0
    # the mechanism engaged: a command refreshes exactly its closure
    assert counters["hot_refresh_rows"] == pytest.approx(
        counters["commands"] * notes["edited_closure_mean"])
    assert counters["hot_refresh_dispatches"] == 2 * counters["commands"]
    assert counters["recaptures_in_place"] >= 4 * counters["commands"]
    assert counters["slots_revived"] > counters["recaptures_in_place"]
    assert notes["stale_rows_at_end"] == {"product": 0, "cart": 0, "total": 0}
    assert set(line["control"]) == {"stale_total", "no_refresh", "lost_write"}
    over = {
        kind: {n for n, (value, limit) in got["compared"].items() if value > limit}
        for kind, got in line["control"].items()
    }
    assert all(got["correct"] is False for got in line["control"].values())
    assert over["stale_total"] == {"reread_mismatches"}
    assert over["no_refresh"] == {"newly_count_mismatches"}
    assert {"journal_mismatches", "store_mismatches", "device_total_mismatches"} <= over["lost_write"]


@pytest.mark.parametrize("fault, seen_by", [
    ("refresh_skipped", {"newly_count_mismatches", "device_total_mismatches",
                         "stale_rows_at_end", "invalid_nodes_at_end"}),
    ("wrong_block_order", {"device_total_mismatches"}),
    ("stale_total_served", {"reread_mismatches"}),
    ("lost_write", {"journal_mismatches", "store_mismatches", "device_total_mismatches"}),
])
def test_fault_underneath_makes_the_run_incorrect(fault, seen_by):
    proc = run_harness(os.path.join(BENCH, "tests", "cart_fault_run.py"), fault)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["compared"]
    over = {name for name, (value, limit) in line["compared"].items() if value > limit}
    assert seen_by <= over, over


def test_a_program_without_the_declaration_exits_3_at_once():
    proc = run_harness(os.path.join(BENCH, "tests", "cart_fault_run.py"), "no_declaration")
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert "hot declaration" in proc.stderr and "generating" not in proc.stderr
    assert not proc.stdout.strip()  # no result line
