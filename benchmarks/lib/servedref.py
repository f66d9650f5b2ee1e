"""The plain reference of the served write path: a store, a journal, a
subscription table and the stale state carried from command to command.

Nothing here imports the program. The graph is ``lib/hostgraph.py``'s
``HostGraph`` (its CSR BFS gives a command's closure); everything else is
sets and a dict. A run's driver records what it did, in order, as events:

    ("cmd", op_id, row, delta)      a command the system acknowledged
    ("reread", client, row, value)  a client's re-read and what it returned

and :func:`replay` says what a correct system shows for them.

The rules, each one of the configuration's guarantees:

- *exactly once*: the store is ``float32(row)`` plus the sum of the
  acknowledged deltas of that row (whole numbers, so float32 is exact);
- *journal, then complete*: the journal is the list of acknowledged
  operation ids, in order;
- *the cascade*: a command's wave starts at its row and follows the edges.
  The row itself always conducts. A node other than the row that is still
  invalid from an earlier wave neither counts nor conducts (its dependents
  went stale with it). The wave's *newly* set is what it reached that was
  not invalid before;
- *who observes*: every armed subscription on a newly invalid row, and no
  other. Observing disarms a subscription; a re-read arms it again;
- *written rows* (the rule PR 28 settled, ``refresh_block_on_device``'s
  docstring): a re-read makes the row valid again in the GRAPH (the next
  wave counts it again) and returns the store's value, but leaves it stale
  on the TABLE: only the table's own read recomputes the columnar row. So
  the table's stale set only grows here.

``max_depth`` cuts the cascade after that many hops and ``drop_op`` leaves
one acknowledged command out of the store and the journal: the controls
use them, the reference never does.
"""
from __future__ import annotations

import numpy as np


class Expected:
    """What a correct system shows for one run's events."""

    def __init__(self):
        self.journal: list = []  # acknowledged operation ids, in order
        self.store: dict = {}  # row -> float32, rows written or re-read
        self.newly_counts: list = []  # per command
        self.observers: list = []  # per command: frozenset of (client, row)
        self.reread_values: list = []  # per re-read, in order
        self.table_stale: set = set()  # rows valid_mask shows stale at the end


def initial_value(row: int) -> np.float32:
    return np.float32(row)


def replay(graph, subscriptions, events, max_depth=None, drop_op=None) -> Expected:
    """``graph``: a ``HostGraph``. ``subscriptions``: iterable of
    ``(client, row)``, all armed at the start. ``events``: see above."""
    out = Expected()
    armed: dict = {}  # row -> set of clients
    for client, row in subscriptions:
        armed.setdefault(int(row), set()).add(client)
    invalid: set = set()  # the graph's invalid rows

    def value(row: int) -> np.float32:
        return out.store.get(row, initial_value(row))

    for event in events:
        if event[0] == "cmd":
            _kind, op_id, row, delta = event
            row = int(row)
            if op_id != drop_op:
                out.journal.append(op_id)
                out.store[row] = np.float32(value(row) + np.float32(delta))
            newly = _wave(graph, row, invalid, max_depth)
            invalid |= newly
            out.table_stale |= newly
            out.newly_counts.append(len(newly))
            seen = set()
            for r in newly:
                for client in armed.pop(r, ()):
                    seen.add((client, r))
            out.observers.append(frozenset(seen))
        elif event[0] == "reread":
            _kind, client, row, _value = event
            row = int(row)
            invalid.discard(row)
            armed.setdefault(row, set()).add(client)
            out.reread_values.append(value(row))
        else:
            raise ValueError(f"servedref: no event kind {event[0]!r}")
    return out


def _wave(graph, seed: int, invalid: set, max_depth) -> set:
    """The newly invalid rows of one wave seeded at ``seed``."""
    newly = set() if seed in invalid else {seed}
    seen = {seed}
    frontier = [seed]
    depth = 0
    while frontier and (max_depth is None or depth < max_depth):
        nxt = []
        for d in graph.out_neighbors(frontier).tolist():
            if d in seen:
                continue
            seen.add(d)
            if d in invalid:
                continue  # blocked: neither counts nor conducts
            newly.add(d)
            nxt.append(d)
        frontier = nxt
        depth += 1
    return newly
