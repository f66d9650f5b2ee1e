"""The plain reference of the multi-host write path: several served members,
each a replica of the whole graph with its own subscribers, over ONE store
and ONE operation log; a command is applied by the member that owns its key
and reaches the others through the log.

Nothing here imports the program. The graph is ``lib/hostgraph.py``'s, a
member's view of a run is ``lib/servedref.py``'s replay, and the shard map is
computed here from the member names and the shard count alone (``CLUSTER.md``
"Shard map": key -> sha1 -> virtual shard, shard -> the member with the
highest sha1(member|shard) score). A run's driver records what it did, in
order, as events:

    ("cmd", op_id, row, delta, owner)        a command the system acknowledged,
                                             and the member that applied it
    ("reread", member, client, row, value)   a client's re-read from its own
                                             member, and what it returned

and :func:`replay` says what a correct system shows for them:

- *the journal*: every acknowledged operation id exactly once, in order,
  each under the member the shard map names for its key (``owner`` in the
  event is what the system did; the reference's is its own);
- *the store*: exactly once (``servedref``'s rule), one store for all;
- *per member*: every command's wave runs on EVERY member (the owner's by
  its own completion, the others' by replaying the log), so each member
  shows ``servedref.replay`` of all the commands with its own subscriptions
  and its own clients' re-reads: the newly invalid count of each command,
  who observed it there, every re-read's value and the table's stale mask
  at the end. A re-read on one member makes the row valid again on THAT
  member only;
- *replays*: every member replays every operation it does not own exactly
  once and none of its own.

Controls (the reference never uses them): ``max_depth`` cuts every cascade
after that many hops; ``lost_replay=(member, op_id)`` leaves one member
ignorant of one acknowledged operation: no wave there, nobody there observes.
"""
from __future__ import annotations

import hashlib

from lib import servedref


def shard_of(key: str, n_shards: int) -> int:
    digest = hashlib.sha1(str(key).encode()).digest()
    return int.from_bytes(digest[:8], "big") % n_shards


def owner_of_shard(shard: int, members) -> str:
    def score(member: str):
        digest = hashlib.sha1(f"{member}|{shard}".encode()).digest()
        return int.from_bytes(digest[:8], "big"), member

    return max(members, key=score)


def owner_of_row(row: int, members, n_shards: int) -> str:
    """The member that owns ``Bump(row, ...)``: the command's shard key is
    ``"row-<row>"`` and the router keys on its ``repr``."""
    return owner_of_shard(shard_of(repr(f"row-{int(row)}"), n_shards), members)


class Expected:
    """What a correct system shows for one run's events."""

    def __init__(self):
        self.journal: list = []  # (operation id, owner), in order
        self.store: dict = {}  # row -> float32
        self.members: dict = {}  # member -> servedref.Expected
        self.replays: dict = {}  # member -> external operations it replays


def replay(graph, members, n_shards: int, subscriptions: dict, events,
           max_depth=None, lost_replay=None) -> Expected:
    """``members``: the member names. ``subscriptions``: member ->
    iterable of ``(client, row)``, all armed at the start. ``events``: see
    the module docstring."""
    members = sorted(members)
    out = Expected()
    commands = [e for e in events if e[0] == "cmd"]
    for _kind, op_id, row, _delta, _owner in commands:
        out.journal.append((op_id, owner_of_row(row, members, n_shards)))
    for member in members:
        lost = lost_replay[1] if lost_replay and lost_replay[0] == member else None
        view, lost_at, seen = [], None, 0
        for e in events:
            if e[0] == "cmd":
                if e[1] == lost:
                    lost_at = seen  # this member never hears of it
                else:
                    view.append(("cmd", e[1], e[2], e[3]))
                seen += 1
            elif e[0] == "reread":
                if e[1] == member:
                    view.append(("reread", e[2], e[3], e[4]))
            else:
                raise ValueError(f"mhref: no event kind {e[0]!r}")
        got = servedref.replay(graph, subscriptions[member], view, max_depth=max_depth)
        if lost_at is not None:
            got.newly_counts.insert(lost_at, 0)
            got.observers.insert(lost_at, frozenset())
        out.members[member] = got
        out.replays[member] = sum(
            1 for op_id, owner in out.journal if owner != member and op_id != lost
        )
    # one store: any member's replay that saw every command holds it
    whole = next(m for m in members if not (lost_replay and lost_replay[0] == m))
    out.store = out.members[whole].store
    return out
