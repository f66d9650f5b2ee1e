"""The HelloCart deployment's data, made by the benchmark itself from a seed.

Nothing here imports the program. The shape is upstream's first sample
(``samples/HelloCart``: products with a price, carts of lines, a total per
cart); the cardinalities and the skew are TPC-C's (rev. 5.11): the initial
population of clause 4.3.3.1 at ``W`` warehouses (``100,000`` ITEM rows;
``W x 10`` districts ``x 3,000`` orders, each of 5-15 order lines) with every
line drawn as the New-Order transaction draws it (clause 2.4.1.5: the item by
``NURand(8191, 1, 100000)``, the quantity uniform 1-10).

``NURand(A, x, y) = (((random(0, A) | random(x, y)) + C) % (y - x + 1)) + x``
(clause 2.1.6), ``C`` a constant drawn once. The bitwise OR is what skews
it: an item whose low 13 bits hold k ones is ``3^k / 8192`` times as popular
as the mean, so a few products sit in tens of thousands of carts and the
median product in a few dozen.

A product drawn twice for one cart is drawn again (a cart holds a product
once: an order line names a distinct item). Prices and quantities are whole
numbers, so every total is exact in float32 far beyond what a run reaches.
"""
from __future__ import annotations

import numpy as np

MAX_LINES = 15  # TPC-C: ol_cnt is uniform in 5..15
NURAND_A = 8191  # the constant TPC-C gives for item ids


class CartData:
    """``products`` prices, ``carts`` carts of ``ol_cnt`` lines each; line j
    of cart c is ``(line_product[c, j], line_qty[c, j])`` for ``j <
    ol_cnt[c]``, pad lines hold product 0 and quantity 0."""

    def __init__(self, price, ol_cnt, line_product, line_qty, nurand_c):
        self.price = price  # int64[products], 1..100
        self.ol_cnt = ol_cnt  # int32[carts], 5..15
        self.line_product = line_product  # int32[carts, 15]
        self.line_qty = line_qty  # int32[carts, 15], 1..10 (pads 0)
        self.nurand_c = nurand_c
        self.products = len(price)
        self.carts = len(ol_cnt)

    @property
    def live(self) -> np.ndarray:
        """bool[carts, 15]: the slots that are lines."""
        return np.arange(MAX_LINES)[None, :] < self.ol_cnt[:, None]

    def lines(self):
        """(cart, product, quantity) of every line, int64, in cart order."""
        live = self.live
        cart = np.broadcast_to(np.arange(self.carts)[:, None], live.shape)[live]
        return (cart.astype(np.int64), self.line_product[live].astype(np.int64),
                self.line_qty[live].astype(np.int64))


def nurand(rng, a: int, x: int, y: int, c: int, size) -> np.ndarray:
    """TPC-C clause 2.1.6, vectorized."""
    return ((rng.integers(0, a + 1, size) | rng.integers(x, y + 1, size)) + c) % (y - x + 1) + x


def generate(products: int, carts: int, seed: int) -> CartData:
    rng = np.random.default_rng(seed)
    c = int(rng.integers(0, NURAND_A + 1))
    price = rng.integers(1, 101, products).astype(np.int64)
    ol_cnt = rng.integers(5, MAX_LINES + 1, carts).astype(np.int32)
    product = (nurand(rng, NURAND_A, 1, products, c, (carts, MAX_LINES)) - 1).astype(np.int32)
    qty = rng.integers(1, 11, (carts, MAX_LINES)).astype(np.int32)
    live = np.arange(MAX_LINES)[None, :] < ol_cnt[:, None]
    # a product a cart already holds is drawn again, until no cart holds one
    # twice (later columns give way to earlier ones; pads never clash)
    todo = np.arange(carts)
    while todo.size:
        sub = np.where(live[todo], product[todo], -1 - np.arange(MAX_LINES, dtype=np.int32))
        order = np.argsort(sub, axis=1, kind="stable")
        ranked = np.take_along_axis(sub, order, axis=1)
        again = np.zeros(sub.shape, dtype=bool)
        np.put_along_axis(
            again, order[:, 1:], ranked[:, 1:] == ranked[:, :-1], axis=1
        )
        rows, cols = np.nonzero(again)
        if rows.size == 0:
            break
        product[todo[rows], cols] = nurand(rng, NURAND_A, 1, products, c, rows.size) - 1
        todo = todo[np.unique(rows)]
    product[~live] = 0
    qty[~live] = 0
    return CartData(price, ol_cnt, product, qty, c)


def edges(data: CartData, product_base: int, cart_base: int, total_base: int):
    """The dependency edges as (src, dst) node ids: ``product -> total`` per
    line, then ``cart -> total`` per cart."""
    cart, product, _qty = data.lines()
    carts = np.arange(data.carts, dtype=np.int64)
    return (
        np.concatenate([product_base + product, cart_base + carts]),
        np.concatenate([total_base + cart, total_base + carts]),
    )


def choose_pool(data: CartData, size: int, seed: int, lo: int, hi: int, watched: int):
    """``size`` products whose fan-out (carts that hold them) lies in
    ``lo..hi``, taken in an order shuffled by ``seed``, and for each its
    first ``watched`` carts by id. Returns (products int64[size], watched
    carts int64[size, watched], fan-out int64[size])."""
    cart, product, _qty = data.lines()
    fanout = np.bincount(product, minlength=data.products)
    candidates = np.flatnonzero((fanout >= max(lo, watched)) & (fanout <= hi))
    if len(candidates) < size:
        raise RuntimeError(f"only {len(candidates)} of {size} pool products found")
    picked = candidates[np.random.default_rng([seed, 0x9001]).permutation(len(candidates))[:size]]
    member = np.zeros(data.products, dtype=bool)
    member[picked] = True
    held = np.flatnonzero(member[product])  # the picked products' lines alone
    held = held[np.lexsort((cart[held], product[held]))]  # by product, carts ascending
    starts = np.searchsorted(product[held], picked)
    first = cart[held][starts[:, None] + np.arange(watched)[None, :]]
    return picked.astype(np.int64), first.astype(np.int64), fanout[picked].astype(np.int64)
