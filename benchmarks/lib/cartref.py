"""The plain reference of the HelloCart deployment: prices, the lines both
ways, and a replay of a run's own events.

It imports ``cartgraph`` only, nothing of the program. Prices are int64, the
lines a CSR by product (which carts hold it) beside the cart-major table the
generator made. A run's driver records what it did, in order, as events:

    ("cmd", op_id, product, delta)     an edit the system acknowledged
    ("reread", client, cart, value)    a client's re-read of a total and
                                       what it returned

and :func:`replay` says what a correct system shows for them. The rules,
each one of the configuration's guarantees:

- *exactly once*: a price is its generated value plus the sum of the
  acknowledged deltas of that product;
- *journal, then complete*: the journal is the list of acknowledged
  operation ids, in order;
- *the cascade, on state kept hot*: an edit's wave newly invalidates the
  product and the total of every cart that holds it, ``1 + fan-out`` nodes,
  the first time and every time after: whatever the last wave invalidated is
  valid again before the next one;
- *who observes*: every subscription on a total in that closure, and no
  other (a subscription that observed re-reads at once, which arms it again
  before the next command);
- *a re-read* returns the total computed from the prices at that moment:
  ``sum(price[product] * quantity)`` over the cart's lines;
- *at the end* every total is that sum over the final prices (one
  ``bincount``), and nothing is stale or invalid anywhere.

The controls break one rule each and the reference never does:
``stale_total`` (re-reads do not see the edit that caused them),
``no_refresh`` (an invalidated node stays invalid until a re-read recomputes
it: a total by its own re-read, a product by the re-read of a total that
holds it; a node still invalid neither counts again nor is observed),
``drop_op`` (one acknowledged edit is missing from the prices and the
journal).
"""
from __future__ import annotations

import numpy as np


class CartRef:
    """Prices and the lines both ways."""

    def __init__(self, data, lines_of=None):
        """``lines_of``: another ``CartRef`` of the same data, whose line
        tables (never written) this one shares: only the prices are its own."""
        self.data = data
        self.price = data.price.astype(np.int64).copy()
        if lines_of is not None:
            self._cart, self._product, self._qty = lines_of._cart, lines_of._product, lines_of._qty
            self._carts_by_product, self._starts = lines_of._carts_by_product, lines_of._starts
            return
        cart, product, qty = data.lines()
        self._cart, self._product, self._qty = cart, product, qty
        order = np.argsort(product, kind="stable")  # carts ascending within a product
        self._carts_by_product = cart[order]
        self._starts = np.concatenate(
            [[0], np.cumsum(np.bincount(product, minlength=data.products))]
        )

    def carts_of(self, product: int) -> np.ndarray:
        """The carts that hold ``product``, ascending."""
        return self._carts_by_product[self._starts[product]:self._starts[product + 1]]

    def fanout(self, product: int) -> int:
        return int(self._starts[product + 1] - self._starts[product])

    def total(self, cart: int, price=None) -> int:
        price = self.price if price is None else price
        n = int(self.data.ol_cnt[cart])
        return int((price[self.data.line_product[cart, :n]] * self.data.line_qty[cart, :n]).sum())

    def all_totals(self) -> np.ndarray:
        """int64[carts]: every cart's total at the current prices."""
        return np.bincount(
            self._cart, weights=(self.price[self._product] * self._qty).astype(np.float64),
            minlength=self.data.carts,
        ).astype(np.int64)


class Expected:
    """What a correct system shows for one run's events."""

    def __init__(self):
        self.journal: list = []  # acknowledged operation ids, in order
        self.newly_counts: list = []  # per command
        self.observers: list = []  # per command: frozenset of (client, cart)
        self.reread_values: list = []  # per re-read, in order
        self.price = None  # int64[products] after the last event
        self.totals = None  # int64[carts] after the last event


def replay(data, subscriptions, events, stale_total=False, no_refresh=False,
           drop_op=None, lines_of=None) -> Expected:
    """``data``: a ``cartgraph.CartData``. ``subscriptions``: iterable of
    ``(client, cart)``. ``events``: see the module docstring. ``lines_of``:
    a ``CartRef`` of ``data`` to share the line tables with (a 30 M-line
    sort saved a replay)."""
    ref = CartRef(data, lines_of)
    out = Expected()
    watchers: dict = {}  # cart -> [(client, cart)]
    for client, cart in subscriptions:
        watchers.setdefault(int(cart), []).append((client, int(cart)))
    watched = np.fromiter(watchers, dtype=np.int64, count=len(watchers))
    invalid_products: set = set()  # only a run without the refresh keeps any
    invalid_totals: set = set()
    seen_price = ref.price  # the prices a re-read sees
    for event in events:
        if event[0] == "cmd":
            _kind, op_id, product, delta = event
            product = int(product)
            if stale_total:
                seen_price = ref.price.copy()  # as they were before this edit
            if op_id != drop_op:
                out.journal.append(op_id)
                ref.price[product] += int(delta)
            carts = ref.carts_of(product)
            if no_refresh:
                fresh = [c for c in carts.tolist() if c not in invalid_totals]
                out.newly_counts.append((product not in invalid_products) + len(fresh))
                invalid_products.add(product)
                invalid_totals.update(fresh)
                hit = np.intersect1d(np.asarray(fresh, dtype=np.int64), watched)
            else:
                out.newly_counts.append(1 + len(carts))
                hit = np.intersect1d(carts, watched)
            out.observers.append(frozenset(
                sub for cart in hit.tolist() for sub in watchers[cart]
            ))
        elif event[0] == "reread":
            _kind, _client, cart, _value = event
            cart = int(cart)
            out.reread_values.append(ref.total(cart, seen_price))
            if no_refresh:
                invalid_totals.discard(cart)
                n = int(data.ol_cnt[cart])
                invalid_products.difference_update(data.line_product[cart, :n].tolist())
        else:
            raise ValueError(f"cartref: no event kind {event[0]!r}")
        if not stale_total:
            seen_price = ref.price
    out.price = ref.price
    out.totals = ref.all_totals()
    return out
