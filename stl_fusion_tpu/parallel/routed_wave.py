"""Cluster-routed CSR shards with collective frontier exchange (ISSUE 9 + 15).

The unification of the cluster control plane with the mesh path: node rows
live on the device that owns their cluster shard (:class:`~..cluster.
placement.DevicePlacement` — the shard map's device half), edges shard by
DESTINATION owner device, and each BFS level exchanges the invalidation
frontier with mesh collectives instead of surfacing to the host:

- ``exchange="a2a"`` (single-host default): each device bit-packs its
  newly-lit frontier into uint32 words and sends each consumer device
  ONLY the words that consumer's edges actually reference — static
  per-(producer, consumer) word buckets delivered by one ``lax.all_to_all``
  per level. Exchange volume is O(cut words), not O(n): a frontier bit
  travels only to device shards whose edges need it (the "cluster-routed"
  step PAPER.md's collectives thesis asks for).
- ``exchange="hier"`` (ISSUE 15, the multi-host protocol): each level
  resolves in TWO stages over a 2-D ``(host, ldev)`` mesh — an intra-host
  packed-word a2a over the local device group (the ICI leg: same bucket
  routing as ``a2a``, restricted to same-host pairs), then an inter-host
  exchange of the REDUCED per-host frontier words: every device gathers
  its owned words of the per-(producer-host, consumer-host) buckets, the
  host group OR-assembles them in log2(dph) ``ppermute`` rounds, and the
  assembled host payloads travel a recursive-doubling ``ppermute`` tree
  across hosts (log2(n_hosts) rounds — the Tascade reduction-tree shape,
  PAPERS.md #1). Only bucket words cross the host boundary (the DCN leg),
  and the whole two-stage exchange stays INSIDE the fused wave/chain scan
  — super-rounds ride it with zero host-relay hops. Under
  ``jax.distributed`` (cluster/multihost.py) the host axis spans REAL OS
  processes and the inter-host ppermute moves bytes between them.
- ``exchange="tree"``: the full packed frontier replicates through a
  log2(n_dev)-round recursive-doubling ``ppermute`` reduction tree,
  each round OR-combining block pairs at doubling distance; the
  explicit-tree alternative to ``lax.all_gather``.
- ``exchange="gather"``: plain ``lax.all_gather`` of packed words — the
  reference for equivalence tests.

The WORKLIST is resident: the slots that pass the version check (one local
row gather, ``node_epoch[dst] == edge epoch`` — device-local by
construction, the reason edges shard by destination; no pad, none behind
its destination's version) as (source key, destination row) pairs, sorted
to a prefix by a program of its own. It is a fact of the graph, not of the
wave: the graph builds it again only after one of the arrays it derives
from was replaced (a patch, a resize, a reshard, a snapshot import), so a
static graph builds it once. A wave starts from a copy of it. Per level,
after the exchange: the worklist fires in chunks — per
slot ONE indexed read (the source word) and one place in the chunk's local
scatter — and a slot that fired leaves it (a node is in a frontier at most
once a wave, so the slot can never change the result again): a wave's
reads are the live slots whose source is still to come, not every slot at
every level. Then the already-invalid mask on the node rows and a ``psum``
for the continuation flag. The while_loop carries the flag, so no
collective runs in ``cond``; the chunk loop's trip count is the worklist's
length, read on the device.

The **chain faces** (:meth:`RoutedShardedGraph.dispatch_union_chain` /
:meth:`harvest_union_chain`) run K logical waves in ONE ``lax.scan`` with
per-stage compacted newly-id readback — the frontier exchange composed
into the nonblocking loop-carried chain (graph/nonblocking.py rides them
when mesh routing is enabled), so a cross-shard frontier resolves inside
the fused dispatch instead of re-entering through per-key host RPC.

A live reshard MOVES a device shard (:meth:`apply_placement`): the moved
shard's fixed-width row block transfers on-device to its new owner's free
slot, the affected consumer devices' edge slices + exchange buckets
re-pack host-side, and everything else stays resident. Structural churn
patches route by owner (:meth:`patch_batch` — bumps scatter absolute
epochs, adds splice into per-device slack slots) and apply in ONE fused
dispatch per batch.

**Dynamic bucket growth (ISSUE 15).** Edge routing is CAP-INDEPENDENT:
per-edge arrays carry ``(eprod, ebslot)`` — the producer family and the
slot WITHIN its bucket — and the kernel computes the flat exchange index
from the (trace-time) bucket capacities. An overflowed exchange bucket,
host bucket, or edge-slack slot therefore GROWS IN PLACE: the host-side
table re-allocates with the new capacity, re-uploads, and the next
dispatch recompiles against the new shape — no consumer's slot
assignments change (slots are append-only between rebuilds). Every grow
counts in ``fusion_mesh_bucket_resizes_total``; a graph that exhausts its
``max_resizes`` budget reports the overflow exactly like the old code
(``False`` / :class:`PlacementError`) and the caller takes the REBUILD
rung — the last rung of the counted ladder
(resize → resize-exhausted → rebuild), never a silent fallback.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..cluster.placement import DevicePlacement, PlacementError
from ..diagnostics.mesh_telemetry import current_dispatch_cause, global_mesh_trace
from ..diagnostics.metrics import global_metrics, next_wave_seq
from ..diagnostics.tracing import hot_span, wave_shaped_cause
from ..graph.program_cache import time_program_warm
from .mesh import GRAPH_AXIS, graph_mesh, shard_map_compat

__all__ = ["RoutedShardedGraph", "build_routed_wave", "record_level_stall_ms"]

_EXCHANGES = ("a2a", "tree", "gather", "hier")
HOST_AXIS = "host"
LDEV_AXIS = "ldev"


def record_level_stall_ms(ms: float, cause: Optional[str] = None) -> None:
    """Record the level-barrier stall time an async A/B leg reclaimed
    (sync wall − async wall over the same wave schedule, clamped at 0) as
    the ``fusion_mesh_level_stall_ms`` MAX-gauge. Lives here — next to the
    kernel whose barrier it measures — so the perf legs share one minting
    site and the catalog row has a package anchor. ``cause`` (the leg's
    last traced wave) additionally records the sample into the
    ``fusion_mesh_stall_reclaim_ms`` histogram, whose exemplar ring keeps
    the wave id — an operator reading the reclaim number can jump to
    ``GET /trace?cause=`` in one hop (ISSUE 19)."""
    g = global_metrics().gauge(
        "fusion_mesh_level_stall_ms",
        help="level-barrier stall time reclaimed by the async frontier "
        "mode over an identical wave schedule (sync wall minus async "
        "wall, ms; MAX across recordings)",
    )
    g.set(float(ms))
    global_metrics().set_aggregation("fusion_mesh_level_stall_ms", "max")
    if cause is not None:
        global_metrics().histogram(
            "fusion_mesh_stall_reclaim_ms",
            help="per-recording async stall-reclaim samples; exemplars "
            "carry the reclaiming leg's wave cause id",
        ).record(float(ms), cause=cause)


def _flat_spec(mesh: Mesh) -> P:
    """The node/edge partition spec for a routed mesh: 1-D graph axis, or
    the flattened (host, ldev) product for the hierarchical exchange."""
    names = mesh.axis_names
    return P(names[0]) if len(names) == 1 else P(tuple(names))


def _psum_axes(mesh: Mesh):
    names = mesh.axis_names
    return names[0] if len(names) == 1 else tuple(names)


#: a worklist key is ``_WAITING | word index << 5 | bit``: a slot still to
#: fire. Under that bit lie the destination rows of the slots that fire in
#: the chunk being sorted; above every key, the entry that is no slot
_WAITING = np.uint32(1 << 31)
_DEAD_KEY = np.uint32(0xFFFFFFFF)


def _chunk_width(e_cap: int) -> int:
    """Slots in one chunk of a level's worklist, from the edge capacity
    alone: a power of two, 2**20 at most (on the v5e a wave at 10 M nodes
    a chip took 2.92 / 2.44 / 2.35 / 2.06 s at 2**14 / 2**16 / 2**18 /
    2**20 and no less above: CHANGES.md, PR 37), and an eighth of the
    shard's slots at most, so that a small graph's worklist still spans
    chunks."""
    return min(1 << 20, max(64, 1 << ((e_cap // 8).bit_length() - 1)))


def build_routed_wave(
    mesh: Mesh, n_global: int, n_dev: int, exchange: str, async_depth: int = 0
):
    """Compile the routed union wave for a mesh + geometry. Returns the
    pair ``(worklist, wave)``:

    - ``worklist(send_idx, hsend_idx, eprod, ebslot, ebit, edst, eepoch,
      nepoch) -> (wkey, wdst, n_live)``: the live slots of each device's
      edge slice (none behind its destination's version, no pad) as
      (source key, destination row) pairs sorted to a prefix, and their
      number per device. A fact of the graph: it changes only with what it
      is computed from.
    - ``wave(frontier, send_idx, hsend_idx, (wkey, wdst, n_live), invalid,
      *local) -> (invalid', count, levels, spec_levels, visits)``, starting
      its level loop from that worklist; ``local`` is ``(elsrc, edst,
      eepoch, nepoch)`` in the async mode and empty otherwise.

    All arrays are sharded over the mesh's flat device axis; seeds conduct
    even when already invalid (the r4 union rule); ``levels`` is the number
    of frontier exchanges the wave ran (the collective-rounds telemetry
    ``fusion_mesh_exchange_levels`` aggregates); ``visits`` the worklist
    slots its levels read, per device as uint32 ``[lo, hi]``. For
    ``exchange="hier"`` the mesh must be the 2-D ``(host, ldev)`` mesh;
    bucket capacities are read from the (trace-time) table shapes, which is
    what lets an in-place bucket resize recompile instead of re-pack.

    ``async_depth >= 1`` compiles the ASYNCHRONOUS execution mode (ISSUE
    17): between global merges each shard advances its LOCAL frontier
    speculatively for up to ``async_depth`` levels through the per-edge
    ``elsrc`` table (same-device source row, pad for remote sources —
    local CSR expansion never waits on remote words). A merge then
    exchanges the cumulative EVER-LIT accumulator through the unchanged
    OR-accumulation collectives (atomic-free by construction — packed-word
    OR is idempotent and order-independent, the Tascade reduction-tree
    property) and fires the worklist against it, which both completes the
    remote frontier and picks up local rows the bounded speculation left
    unexpanded. The per-level barrier becomes a counted QUIESCENCE vote:
    one psum of "did any shard's merge fire a row" per merge epoch —
    merge firing nothing anywhere proves no ever-lit→eligible edge
    remains, i.e. the closure is complete (monotone idempotent
    OR-accumulation makes the final mask schedule-independent, so the
    async mask is bit-identical to the sync exchange and the host BFS).
    ``levels`` then counts MERGE epochs (each runs exactly one full
    exchange — the cross-host-words accounting stays honest) and
    ``spec_levels`` the deepest shard's productive speculative levels."""
    if exchange not in _EXCHANGES:
        raise ValueError(f"unknown exchange {exchange!r}")
    n_local = n_global // n_dev
    assert n_local % 32 == 0
    w_local = n_local // 32
    if exchange == "tree" and (n_dev & (n_dev - 1)):
        raise ValueError("tree exchange needs a power-of-two device count")
    if exchange == "hier":
        n_hosts, dph = mesh.devices.shape
        assert n_hosts * dph == n_dev
    else:
        n_hosts, dph = 1, n_dev

    spec = _flat_spec(mesh)
    ax = _psum_axes(mesh)
    node_spec = spec
    edge_spec = spec
    send_spec = P(*(spec + (None,)))

    def _pack_words(f_l):
        lanes = jnp.arange(32, dtype=jnp.uint32)[None, :]
        return jnp.sum(
            f_l.reshape(-1, 32).astype(jnp.uint32) << lanes, axis=1, dtype=jnp.uint32
        )

    def _exchange_words(f_l, send_idx_l, hsend_idx_l):
        """One frontier exchange: local packed words → (intra_flat,
        cross_flat) word vectors the per-edge (eprod, ebslot) routing
        indexes into (layout differs per mode; cross_flat exists only for
        hier)."""
        words = _pack_words(f_l)
        if exchange == "gather":
            return lax.all_gather(words, ax, tiled=True), None
        if exchange == "a2a":
            words_p = jnp.concatenate([words, jnp.zeros(1, jnp.uint32)])  # pad word
            send = words_p[send_idx_l]  # [n_dev, icap] — bucket per consumer
            recv = lax.all_to_all(
                send, ax, split_axis=0, concat_axis=0, tiled=True
            )
            return recv.reshape(-1), None  # row p = words from producer p
        if exchange == "tree":
            # recursive-doubling ppermute — log2(n_dev) OR-merge rounds
            acc = words
            idx = lax.axis_index(ax)
            step = 1
            while step < n_dev:
                perm = [(i, i ^ step) for i in range(n_dev)]
                recv = lax.ppermute(acc, ax, perm)
                low = (idx & step) == 0  # my block sits in the lower half
                acc = jnp.where(
                    low,
                    jnp.concatenate([acc, recv]),
                    jnp.concatenate([recv, acc]),
                )
                step *= 2
            return acc, None  # full packed frontier, device order
        # hier — ISSUE 15: two stages, intra-host then inter-host
        words_p = jnp.concatenate([words, jnp.zeros(1, jnp.uint32)])
        # stage 1: intra-host packed-word a2a over the local device group
        # (same bucket protocol as a2a, subgroup = this host's devices;
        # nothing crosses the host boundary here)
        send = words_p[send_idx_l]  # [dph, icap]
        intra = lax.all_to_all(
            send, LDEV_AXIS, split_axis=0, concat_axis=0, tiled=True
        ).reshape(-1)  # row p_l = words from local producer p_l
        # stage 2a: host-bucket contribution gather + intra-host OR
        # assembly — each device owns a disjoint word range, so OR over
        # the host group assembles the host's complete outgoing buckets
        contrib = words_p[hsend_idx_l]  # [n_hosts(G), hcap]
        step = 1
        while step < dph:
            perm = [(i, i ^ step) for i in range(dph)]
            contrib = contrib | lax.ppermute(contrib, LDEV_AXIS, perm)
            step *= 2
        # stage 2b: recursive-doubling ppermute TREE across hosts (the
        # Tascade reduction-tree shape) shipping the reduced per-host
        # frontier BUCKETS — only bucket payloads cross the host boundary
        # (never full frontiers), though each tree round re-ships the
        # accumulated blocks, so wire cost ~ n_hosts x bucket capacity
        acc = contrib[None]  # [1, n_hosts(G), hcap] — my host's payload
        h = lax.axis_index(HOST_AXIS)
        hstep = 1
        while hstep < n_hosts:
            perm = [(i, i ^ hstep) for i in range(n_hosts)]
            recv = lax.ppermute(acc, HOST_AXIS, perm)
            low = (h & hstep) == 0
            acc = jnp.where(
                low,
                jnp.concatenate([acc, recv]),
                jnp.concatenate([recv, acc]),
            )
            hstep *= 2
        return intra, acc.reshape(-1)  # [n_hosts(H) * n_hosts(G) * hcap]

    def _word_index(send_idx_l, hsend_idx_l, eprod_l, ebslot_l):
        """Per edge slot the index of its source word in :func:`_words`,
        ONE vector over what :func:`_exchange_words` returns, via the
        cap-independent (eprod, ebslot) routing. The index arithmetic runs
        with the worklist's build; a level pays the indexed read alone.
        Capacities come from trace-time table shapes — the hook dynamic
        bucket growth hangs off."""
        if exchange in ("tree", "gather"):
            n_words, idx = n_dev * w_local, ebslot_l
        elif exchange == "a2a":
            icap = send_idx_l.shape[-1]
            n_words, idx = n_dev * icap, eprod_l * icap + ebslot_l
        else:
            # hier: intra edges read the subgroup-a2a rows; cross edges the
            # (producer host, consumer host) bucket of the host tree, laid
            # behind them so that a visit is one read
            icap, hcap = send_idx_l.shape[-1], hsend_idx_l.shape[-1]
            n_intra = dph * icap
            n_words = n_intra + n_hosts * n_hosts * hcap
            g = lax.axis_index(HOST_AXIS)
            idx = jnp.where(
                eprod_l >= n_dev,
                n_intra + ((eprod_l - n_dev) * n_hosts + g) * hcap + ebslot_l,
                (eprod_l % dph) * icap + ebslot_l,
            )
        if n_words >= 1 << 26:
            # a worklist key is (word index << 5 | bit) under _WAITING
            raise ValueError(f"{n_words} exchanged words a device: over 2**26")
        return idx

    def _words(intra_flat, cross_flat):
        """The exchanged words as the one vector :func:`_word_index`
        points into: hier's host-tree buckets lie behind its intra rows."""
        if exchange == "hier":
            return jnp.concatenate([intra_flat, cross_flat])
        return intra_flat

    @shard_map_compat(
        mesh=mesh,
        in_specs=(
            send_spec, send_spec, edge_spec, edge_spec, edge_spec, edge_spec,
            edge_spec, node_spec,
        ),
        out_specs=(edge_spec, edge_spec, edge_spec),
    )
    def _worklist(send_idx_l, hsend_idx_l, eprod_l, ebslot_l, ebit_l, edst_l,
                  eepoch_l, nepoch_l):
        # A slot whose captured epoch is behind its destination's (or a pad:
        # eepoch -1 never matches, the gather clamps) never conducts: it
        # stays out of the worklist, so no level reads it.
        live = nepoch_l[edst_l] == eepoch_l
        idx = _word_index(send_idx_l, hsend_idx_l, eprod_l, ebslot_l)
        # the live slots as (source key = word index and bit, destination
        # row), in source order, compacted to a prefix by one sort (an
        # order of magnitude cheaper an element than an indexed op); what
        # is no live slot sorts behind them
        key = _WAITING | (idx.astype(jnp.uint32) << 5) | ebit_l.astype(jnp.uint32)
        wkey, wdst = lax.sort(
            (jnp.where(live, key, _DEAD_KEY), edst_l), num_keys=1, is_stable=False
        )
        return wkey, wdst, live.sum(dtype=jnp.int32)[None]

    # the async mode's local speculation reads the edge slice itself
    local_specs = (edge_spec, edge_spec, edge_spec, node_spec) if async_depth else ()

    @shard_map_compat(
        mesh=mesh,
        in_specs=(
            node_spec, send_spec, send_spec, (edge_spec, edge_spec, edge_spec),
            node_spec, *local_specs,
        ),
        out_specs=(node_spec, P(), P(), P(), node_spec),
    )
    def _wave(seeds_l, send_idx_l, hsend_idx_l, work_l, inv_l, *local_l):
        fresh = seeds_l & ~inv_l
        inv_l = inv_l | seeds_l
        count0 = lax.psum(fresh.sum(dtype=jnp.int32), ax)
        go0 = lax.psum(seeds_l.any().astype(jnp.int32), ax) > 0

        # the wave's own copy of the worklist, in whole chunks (a slice
        # never clamps): a slot leaves it once it has fired, because a node
        # is in a frontier at most once a wave
        wkey, wdst, n_live = work_l
        e_cap = wkey.shape[0]
        chunk = _chunk_width(e_cap)
        pad = -e_cap % chunk
        work0 = (
            jnp.concatenate([wkey, jnp.full(pad, _DEAD_KEY, jnp.uint32)]),
            jnp.concatenate([wdst, jnp.full(pad, n_local, jnp.int32)]),
            n_live[0],
        )
        lane = jnp.arange(chunk, dtype=jnp.int32)

        def merge_fire(frontier, inv, work):
            """One global exchange of ``frontier`` + a fire of the
            worklist against it, chunk by chunk: per slot ONE indexed read
            (the source word) and one scatter; then the slots that did not
            fire close up, in place (the write offset never passes the
            read position). The already-invalid rule is a fact of the
            destination ROW, so it is applied on the node rows after the
            scatter — ``max(a & ~inv[dst]) == max(a) & ~inv``, bit for
            bit. Shared by the sync per-level step and the async merge
            epoch. Returns ``(newly lit rows, the worklist left)``."""
            wkey, wdst, n_work = work
            recv = _words(*_exchange_words(frontier, send_idx_l, hsend_idx_l))

            def fire(hit, k, d, valid, fired, n_fired):
                """ONE sort serves the scatter and the partition: the slots
                that fired come first, keyed by their destination row, so
                the scatter's indices are sorted and XLA sorts nothing
                itself; the waiting slots follow in source order (keys
                from ``_WAITING`` up clamp to the dropped row) and move
                to the front."""
                key = jnp.where(
                    fired, d.astype(jnp.uint32), jnp.where(valid, k, _DEAD_KEY)
                )
                key, d = lax.sort((key, d), num_keys=1, is_stable=False)
                hit = hit.at[jnp.minimum(key, n_local).astype(jnp.int32)].set(
                    True, mode="drop", indices_are_sorted=True
                )
                return hit, jnp.roll(key, -n_fired), jnp.roll(d, -n_fired)

            def fire_chunk(i, st):
                wkey, wdst, hit, kept = st
                base = i * chunk
                k = lax.dynamic_slice(wkey, (base,), (chunk,))
                d = lax.dynamic_slice(wdst, (base,), (chunk,))
                valid = lane < n_work - base
                word = recv[(k & ~_WAITING) >> 5]
                fired = valid & ((word >> (k & 31)) & 1).astype(bool)
                n_fired = fired.sum(dtype=jnp.int32)
                # a chunk in which nothing fired moves as it is: a wave
                # with a tiny frontier pays its reads alone
                hit, k, d = lax.cond(
                    n_fired > 0, fire, lambda hit, k, d, *_: (hit, k, d),
                    hit, k, d, valid, fired, n_fired,
                )
                wkey = lax.dynamic_update_slice(wkey, k, (kept,))
                wdst = lax.dynamic_update_slice(wdst, d, (kept,))
                n_valid = jnp.clip(n_work - base, 0, chunk)
                return wkey, wdst, hit, kept + n_valid - n_fired

            wkey, wdst, hit, kept = lax.fori_loop(
                0, (n_work + chunk - 1) // chunk, fire_chunk,
                (wkey, wdst, jnp.zeros_like(frontier), jnp.int32(0)),
            )
            return hit & ~inv, (wkey, wdst, kept)

        def visited(visits, work):
            """``visits`` (uint32 [lo, hi]) plus the slots the level about
            to run reads: 64 bits, a deep wave over a full shard passes
            2**31."""
            lo = visits[0] + work[2].astype(jnp.uint32)
            return jnp.stack([lo, visits[1] + (lo < visits[0])])

        visits0 = jnp.zeros(2, jnp.uint32)

        if async_depth and async_depth > 0:
            # ---- asynchronous mode: speculative local levels between
            # counted-quiescence merges (ISSUE 17) ----
            elsrc_l, edst_l, eepoch_l, nepoch_l = local_l
            live = nepoch_l[edst_l] == eepoch_l
            edst_live = jnp.where(live, edst_l, n_local)

            def spec_body(_i, st):
                f, inv, acc, newly_l, spec = st
                # local-only expansion: a remote-sourced edge's elsrc is
                # the pad row → fill False, so it simply waits for a merge
                src_active = f.at[elsrc_l].get(mode="fill", fill_value=False)
                nxt = jnp.zeros_like(f).at[edst_live].max(src_active) & ~inv
                return (
                    nxt, inv | nxt, acc | nxt,
                    newly_l + nxt.sum(dtype=jnp.int32),
                    spec + nxt.any().astype(jnp.int32),
                )

            def cond(carry):
                return carry[6]

            def body(carry):
                f, inv, acc, count, merges, spec, _go, work, visits = carry
                f, inv, acc, newly_l, spec = lax.fori_loop(
                    0, async_depth, spec_body,
                    (f, inv, acc, jnp.int32(0), spec),
                )
                # merge epoch: exchange the EVER-LIT accumulator and fire
                # the worklist against it — completes remote frontiers AND
                # local rows the bounded speculation left unexpanded. A
                # slot that fired is done here too: its source stays lit,
                # its destination invalid
                visits = visited(visits, work)
                nxt_m, work = merge_fire(acc, inv, work)
                inv = inv | nxt_m
                acc = acc | nxt_m
                newly = lax.psum(newly_l + nxt_m.sum(dtype=jnp.int32), ax)
                # quiescence vote: the merge covers ALL edges against all
                # ever-lit rows — firing nothing anywhere proves closure
                go = lax.psum(nxt_m.any().astype(jnp.int32), ax) > 0
                return (nxt_m, inv, acc, count + newly, merges + 1, spec, go,
                        work, visits)

            _f, inv_l, _acc, count, levels, spec, _go, _work, visits = lax.while_loop(
                cond, body,
                (seeds_l, inv_l, seeds_l, count0, jnp.int32(0), jnp.int32(0), go0,
                 work0, visits0),
            )
            return inv_l, count, levels, lax.pmax(spec, ax), visits

        def cond(carry):
            return carry[4]

        def body(carry):
            f_l, inv_l, count, levels, _go, work, visits = carry
            visits = visited(visits, work)
            nxt_l, work = merge_fire(f_l, inv_l, work)
            inv_l = inv_l | nxt_l
            newly = lax.psum(nxt_l.sum(dtype=jnp.int32), ax)
            return nxt_l, inv_l, count + newly, levels + 1, newly > 0, work, visits

        _f, inv_l, count, levels, _go, _work, visits = lax.while_loop(
            cond, body, (seeds_l, inv_l, count0, jnp.int32(0), go0, work0, visits0)
        )
        return inv_l, count, levels, jnp.int32(0), visits

    return jax.jit(_worklist), jax.jit(_wave)


def build_routed_compact(mesh: Mesh, n_global: int, n_dev: int, capd: int):
    """Per-device LOCAL newly-id compaction (ISSUE 9): each device cumsums
    its own shard rows into a ``capd``-sized buffer — no cross-device
    cumsum/scatter (the global compaction was super-linear on the mesh:
    XLA lowered it to collective permutes that dominated the wave itself
    past ~100K rows). Returns ``(counts int32[n_dev], bufs
    int32[n_dev*capd])``; device d's newly GLOBAL rows are
    ``bufs[d*capd : d*capd + counts[d]]``; ``counts[d] > capd`` = that
    device overflowed (caller mask-diffs)."""
    n_local = n_global // n_dev
    spec = _flat_spec(mesh)
    names = mesh.axis_names
    if len(names) == 1:
        dev_index = lambda: lax.axis_index(names[0])  # noqa: E731
    else:
        dph = mesh.devices.shape[1]
        dev_index = lambda: (  # noqa: E731
            lax.axis_index(names[0]) * dph + lax.axis_index(names[1])
        )

    @shard_map_compat(
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec),
    )
    def _compact(inv2_l, inv_l, real_l):
        newly_l = inv2_l & ~inv_l & real_l
        count = newly_l.sum(dtype=jnp.int32)
        pos = jnp.cumsum(newly_l.astype(jnp.int32)) - 1
        base = (dev_index() * n_local).astype(jnp.int32)
        rows = base + jnp.arange(n_local, dtype=jnp.int32)
        scatter_pos = jnp.where(newly_l & (pos < capd), pos, capd)
        buf = jnp.full(capd, -1, jnp.int32).at[scatter_pos].set(rows, mode="drop")
        return count[None], buf

    return _compact


class _WorklistSource:
    """A resident array the wave's worklist is computed from. Assigning it
    drops the worklist, so the next wave builds it again: no writer can
    forget to. It defines no ``__get__``, so a read is the plain instance
    attribute."""

    def __set_name__(self, owner, name):
        self.name = name

    def __set__(self, graph, value):
        graph.__dict__[self.name] = value
        graph.__dict__["g_work"] = None


class RoutedShardedGraph:
    """Mesh-sharded device graph whose layout IS the cluster shard map."""

    g_send = _WorklistSource()
    g_hsend = _WorklistSource()
    g_eprod = _WorklistSource()
    g_ebslot = _WorklistSource()
    g_ebit = _WorklistSource()
    g_edst = _WorklistSource()
    g_eep = _WorklistSource()
    g_node_epoch = _WorklistSource()

    def __init__(
        self,
        edges_src: np.ndarray,
        edges_dst: np.ndarray,
        n_nodes: int,
        placement: DevicePlacement,
        mesh: Optional[Mesh] = None,
        exchange: str = "a2a",
        edge_dst_epoch: Optional[np.ndarray] = None,
        node_epoch: Optional[np.ndarray] = None,
        invalid: Optional[np.ndarray] = None,
        bucket_headroom: float = 1.3,
        edge_headroom: float = 1.3,
        max_resizes: int = 8,
        resize_growth: float = 1.5,
        exchange_async: bool = False,
        async_depth: int = 4,
    ):
        base_mesh = mesh or graph_mesh()
        if base_mesh.devices.size != placement.n_dev:
            raise PlacementError(
                f"placement spans {placement.n_dev} devices, mesh has "
                f"{base_mesh.devices.size}"
            )
        if exchange not in _EXCHANGES:
            raise ValueError(f"unknown exchange {exchange!r}")
        #: tree requested but n_dev is not a power of two — resolved via
        #: gather, COUNTED (FL002: no silent mode swaps; same contract as
        #: the hier fallback below)
        self.tree_fallbacks = 0
        if exchange == "tree" and (placement.n_dev & (placement.n_dev - 1)):
            exchange = "gather"  # tree's xor rounds need 2^k devices
            self.tree_fallbacks = 1
            global_metrics().counter(
                "fusion_mesh_tree_fallback_total",
                help="tree exchanges resolved via gather on a non-power-of-2 "
                "device count (counted fallback, never a decline)",
            ).inc()
            from ..resilience.events import global_events

            global_events().record(
                "tree_fallback", f"n_dev={placement.n_dev}"
            )
        self.dph = placement.devices_per_host or placement.n_dev
        self.n_hosts = placement.n_dev // self.dph
        #: hier requested but the geometry can't ride the xor trees —
        #: resolved via gather instead of declining (ISSUE 16), COUNTED:
        #: a non-power-of-2 mesh silently losing its hierarchical exchange
        #: would misread as a perf regression with no telemetry trail
        self.hier_fallbacks = 0
        if exchange == "hier" and (
            (self.dph & (self.dph - 1)) or (self.n_hosts & (self.n_hosts - 1))
        ):
            exchange = "gather"  # hier's xor trees need 2^k hosts AND dph
            self.hier_fallbacks = 1
            global_metrics().counter(
                "fusion_mesh_hier_fallback_total",
                help="hier exchanges resolved via gather on a non-power-of-2 "
                "host/device geometry (counted fallback, never a decline)",
            ).inc()
            from ..resilience.events import global_events

            global_events().record(
                "hier_fallback", f"hosts={self.n_hosts} dph={self.dph}"
            )
        if exchange == "hier":
            devs = np.asarray(base_mesh.devices).reshape(-1)
            self.mesh = Mesh(
                devs.reshape(self.n_hosts, self.dph), (HOST_AXIS, LDEV_AXIS)
            )
        else:
            self.mesh = base_mesh
        self.placement = placement
        self.exchange = exchange
        self.n_nodes = n_nodes
        self.n_dev = placement.n_dev
        self.n_local = placement.n_local
        self.n_global = placement.n_global
        self.w_local = self.n_local // 32
        #: set when a failed in-place reshard left device/host layout
        #: inconsistent — every wave entry point then refuses (rebuild)
        self.broken = False
        #: in-place capacity growth budget: once spent, an overflow falls
        #: to the REBUILD rung of the ladder exactly like the pre-resize
        #: code (counted, never silent)
        self.max_resizes = max_resizes
        self.resize_growth = resize_growth
        self.bucket_resizes = 0
        self.resize_detail = {"bucket": 0, "hbucket": 0, "edge": 0}
        #: async frontier execution (ISSUE 17): speculative local levels
        #: between counted-quiescence merge epochs
        self.exchange_async = bool(exchange_async)
        self.async_depth = int(async_depth) if self.exchange_async else 0
        # -- telemetry --
        self.waves_run = 0
        self.worklist_builds = 0  # the resident worklist built (anew)
        self.levels_total = 0  # frontier exchanges (collective rounds)
        self.slot_visits_total = 0  # worklist slots the levels read, all chips
        self.quiescence_checks = 0  # async merge epochs (each = one vote)
        self.spec_levels_total = 0  # deepest shard's productive spec levels
        self.shard_moves = 0
        self.cross_host_moves = 0
        self.patches = 0
        self.patch_dispatches = 0
        self.cross_host_words = 0  # cumulative words shipped across hosts
        self.cross_words_per_level = 0  # static per-exchange-level payload
        self._procs = jax.process_count()
        #: mesh trace identity (ISSUE 18): segments recorded at the host
        #: boundaries carry this host label; ``trace_cause`` lets a driver
        #: pin a mesh-wide cause (every host running the same deterministic
        #: schedule names the wave identically, so the stitch can join
        #: their segments); the super-round threads the backend's cause via
        #: the dispatch contextvar instead
        self.trace_host = f"h{jax.process_index()}"
        self.trace_cause: Optional[str] = None
        self.last_trace_cause: Optional[str] = None

        # int32 host truth: node ids always fit (n_global is int32-bound),
        # and at 240M edges the int64 sorted copies alone were ~5 GB
        src = np.asarray(edges_src, dtype=np.int32)
        dst = np.asarray(edges_dst, dtype=np.int32)
        ep = (
            np.zeros(len(dst), dtype=np.int32)
            if edge_dst_epoch is None
            else np.asarray(edge_dst_epoch, dtype=np.int32)
        )
        # host truth: per-DST-SHARD edge lists (absolute node ids + absolute
        # captured epochs) — the unit a reshard re-partitions by owner
        ips = placement.ids_per_shard
        shard_of_dst = dst.astype(np.int64) // ips
        order = np.argsort(shard_of_dst, kind="stable")
        src, dst, ep, sh = src[order], dst[order], ep[order], shard_of_dst[order]
        self._shard_edges: Dict[int, List[np.ndarray]] = {}
        if len(sh):
            bounds = np.flatnonzero(np.diff(sh)) + 1
            starts = np.concatenate([[0], bounds])
            ends = np.concatenate([bounds, [len(sh)]])
            for a, b in zip(starts, ends):
                self._shard_edges[int(sh[a])] = [src[a:b], dst[a:b], ep[a:b]]

        # capacities sized from the initial partition + headroom
        dev_edges = np.zeros(self.n_dev, dtype=np.int64)
        for s, (es, _ed, _ee) in self._shard_edges.items():
            d = int(placement.shard_dev[s])
            if d >= 0:
                dev_edges[d] += len(es)
        self.e_cap = max(int(dev_edges.max() * edge_headroom) + 32, 64)
        self.bucket_headroom = bucket_headroom
        spec = _flat_spec(self.mesh)
        self._node_sh = NamedSharding(self.mesh, spec)
        self._edge_sh = NamedSharding(self.mesh, spec)
        self._send_sh = NamedSharding(self.mesh, P(*(spec + (None,))))
        self._rep_sh = NamedSharding(self.mesh, P())
        self._replicator = None  # lazy jit identity → replicated (multihost fetch)

        self._set_permutation(placement)
        perm = self.perm  # node id → global row (-1 off-mesh)

        # node state, absolute epochs (no rebase: patches translate nothing)
        nep = np.zeros(self.n_global, dtype=np.int32)
        if node_epoch is not None:
            nep[perm[: len(node_epoch)][perm[: len(node_epoch)] >= 0]] = np.asarray(
                node_epoch, dtype=np.int32
            )[perm[: len(node_epoch)] >= 0]

        self._build_exchange_and_edges()
        self.g_node_epoch = self._put(nep, self._node_sh)
        if invalid is None:
            self.clear_invalid()
        else:
            self.set_invalid(invalid)
        self.g_is_real = self._put(self._h_is_real, self._node_sh)
        self._worklist, self._wave = build_routed_wave(
            self.mesh, self.n_global, self.n_dev, self.exchange,
            async_depth=self.async_depth,
        )
        #: the resident worklist ``(wkey, wdst, n_live)``; None until the
        #: next wave builds it (assigning a ``_WorklistSource`` drops it)
        self.g_work = None
        self._worklist_warmed: set = set()
        self._collect_cache: dict = {}
        self._chain_cache: dict = {}
        self._patch_cache: dict = {}
        self._move_cache: dict = {}
        if self.n_hosts > 1:
            g = global_metrics().gauge(
                "fusion_mesh_hosts",
                help="host processes joined into the global device mesh",
            )
            g.set(self.n_hosts)
            global_metrics().set_aggregation("fusion_mesh_hosts", "max")

    # ---------------------------------------------------------------- helpers
    def _set_permutation(self, placement: DevicePlacement) -> None:
        """The node <-> row maps of ``placement``: the index arrays for
        callers that map ids, and the shard runs (at most one per shard)
        by which a whole mask moves as block copies."""
        self.perm, self.inv_perm = placement.permutation()
        self._runs = placement.shard_runs()
        self._h_is_real = self.inv_perm >= 0

    def _host_of_dev(self, d) -> np.ndarray:
        return np.asarray(d) // self.dph

    def _put(self, a: np.ndarray, sharding):
        """Host array → global device array. Multi-process: via
        ``make_array_from_callback`` — each process materializes ONLY its
        addressable shards from the (identical, SPMD-contract) host
        truth, so an upload NEVER touches the wire. A cross-process
        ``device_put`` lowers to an SPMD program whose collectives can
        interleave with an in-flight compute module's on the shared gloo
        pairs (chunked large messages mispair → transport abort; found
        at the 5M build, nondeterministic). Single-process: plain
        device_put, unchanged."""
        if self._procs == 1:
            return jax.device_put(a, sharding)
        a = np.asarray(a)
        return jax.make_array_from_callback(a.shape, sharding, lambda idx: a[idx])

    def _host_arg(self, a: np.ndarray):
        """A host index/seed array as a jit argument: replicated global
        array under multi-process (every host passes identical data —
        the SPMD contract), plain local array otherwise."""
        if self._procs == 1:
            return jnp.asarray(a)
        return self._put(np.asarray(a), self._rep_sh)

    def _sync(self, *arrays) -> None:
        """Multi-process collective-module serialization: block until the
        dispatched module's outputs are ready before dispatching the NEXT
        module that carries collectives. Two concurrently-executing
        modules reuse XLA channel ids on the gloo CPU transport and their
        chunked messages mispair (the same abort class the _put docstring
        names) — on the real accelerator fabric this is a no-op concern,
        so single-process keeps the async dispatch overlap."""
        if self._procs > 1:
            jax.block_until_ready(arrays)

    def _fetch(self, x) -> np.ndarray:
        """A device array's FULL value on every host. Single-process:
        plain device_get. Multi-process: one jitted replication (an
        all-gather over the mesh) then read the local copy — a global
        array spans non-addressable devices and cannot be fetched
        directly."""
        if self._procs == 1:
            return np.asarray(jax.device_get(x))
        if self._replicator is None:
            self._replicator = jax.jit(lambda a: a, out_shardings=self._rep_sh)
        rep = self._replicator(x)
        out = np.asarray(rep.addressable_shards[0].data)
        self._sync(rep)
        return out

    # ------------------------------------------------------------------ build
    def _consumer_pack(self, d: int) -> dict:
        """Pack consumer device ``d``'s edge slice (UNPADDED) + its word
        buckets from the host per-shard edge lists. Intra buckets cover
        every producer for ``a2a`` and same-host producers for ``hier``;
        hier's cross-host edges come back as (producer host, global word)
        pairs — their ``ebslot`` is assigned against the shared host
        buckets by the caller (build: vectorized union; repack/patch:
        append-only against the live tables)."""
        pl = self.placement
        srcs: List[np.ndarray] = []
        dsts: List[np.ndarray] = []
        eps: List[np.ndarray] = []
        for s in range(pl.shard_map.n_shards):
            if int(pl.shard_dev[s]) != d:
                continue
            ent = self._shard_edges.get(s)
            if ent is None:
                continue
            srcs.append(ent[0])
            dsts.append(ent[1])
            eps.append(ent[2])
        if srcs:
            src = np.concatenate(srcs)
            dst = np.concatenate(dsts)
            ep = np.concatenate(eps)
        else:
            src = dst = np.empty(0, np.int64)
            ep = np.empty(0, np.int32)
        src_rows = self.perm[src] if len(src) else src
        dst_rows = self.perm[dst] if len(dst) else dst
        if len(src) and (src_rows.min() < 0 or dst_rows.min() < 0):
            raise PlacementError("edge endpoints land on off-mesh shards")
        n_e = len(src)
        words = src_rows >> 5
        eprod = np.zeros(n_e, dtype=np.int32)
        ebslot = np.zeros(n_e, dtype=np.int32)
        ebit = (src_rows & 31).astype(np.int32) if n_e else np.empty(0, np.int32)
        edst = (
            (dst_rows - d * self.n_local).astype(np.int32)
            if n_e
            else np.empty(0, np.int32)
        )
        # async speculation operates on LOCAL sources only: same-device
        # producers get their local row, remote ones the pad row (they
        # wait for a merge epoch)
        elsrc = (
            np.where(
                src_rows // self.n_local == d,
                src_rows - d * self.n_local,
                self.n_local,
            ).astype(np.int32)
            if n_e
            else np.empty(0, np.int32)
        )
        buckets: Dict[int, np.ndarray] = {}
        cross = None
        if self.exchange in ("tree", "gather"):
            if n_e:
                ebslot[:] = words.astype(np.int32)
        else:
            prod = (src_rows // self.n_local).astype(np.int64)
            my_host = d // self.dph
            if self.exchange == "hier":
                intra_sel = self._host_of_dev(prod) == my_host if n_e else np.empty(0, bool)
            else:
                intra_sel = np.ones(n_e, dtype=bool)
            for p in range(self.n_dev):
                if self.exchange == "hier" and p // self.dph != my_host:
                    continue
                sel = intra_sel & (prod == p)
                if not sel.any():
                    buckets[p] = np.empty(0, np.int64)
                    continue
                wl = words[sel] - p * self.w_local
                uniq = np.unique(wl)
                buckets[p] = uniq
                eprod[sel] = p
                ebslot[sel] = np.searchsorted(uniq, wl)
            if self.exchange == "hier":
                csel = ~intra_sel
                if csel.any():
                    ch = self._host_of_dev(prod[csel]).astype(np.int64)
                    eprod[csel] = (self.n_dev + ch).astype(np.int32)
                    cross = (ch, words[csel], np.flatnonzero(csel))
        return {
            "n_e": n_e,
            "eprod": eprod,
            "ebslot": ebslot,
            "ebit": ebit,
            "edst": edst,
            "elsrc": elsrc,
            "eep": ep,
            "buckets": buckets,
            "cross": cross,
        }

    def _register_pack_buckets(self, d: int, pack: dict) -> None:
        """Adopt a pack's build-time intra buckets as device ``d``'s live
        bucket truth (sorted build-time buckets: slot lookup at patch time
        is a searchsorted, never a V×words Python dict at 100M-node
        scale); patch-added slots restart empty."""
        self._buckets[d] = pack["buckets"]
        self._patch_slots[d] = {}
        self._bucket_fill[d] = {p: len(b) for p, b in pack["buckets"].items()}
        self._dev_edge_count[d] = pack["n_e"]

    def _assign_cross_slots(self, d: int, pack: dict, append: bool) -> int:
        """Resolve a pack's cross-host edges to host-bucket slots. With
        ``append=True`` (repack after a reshard) new words APPEND to the
        live buckets — existing consumers' slots never shift, which is
        what makes a re-pack touch only the affected consumer's slices.
        Returns the peak fill the assignment needed (the caller grows
        ``hbucket_cap`` when it exceeds it)."""
        peak = 0
        if pack["cross"] is None:
            return peak
        g = d // self.dph
        ch, cw, pos = pack["cross"]
        for h in np.unique(ch).tolist():
            key = (int(h), g)
            sel = ch == h
            wsel = cw[sel]
            hb = self._hbuckets.setdefault(key, np.empty(0, np.int64))
            pslots = self._hpatch_slots.setdefault(key, {})
            fill = self._hbucket_fill.get(key, len(hb))
            base = np.searchsorted(hb, wsel)
            base_cl = np.minimum(base, max(len(hb) - 1, 0))
            hit = (len(hb) > 0) & (hb[base_cl] == wsel) if len(hb) else np.zeros(len(wsel), bool)
            slots = np.where(hit, base_cl, -1).astype(np.int64)
            miss = np.flatnonzero(~hit)
            if len(miss):
                if not append:
                    raise PlacementError(
                        f"cross-host word missing from host bucket {key}"
                    )
                for i in miss.tolist():
                    w = int(wsel[i])
                    j = pslots.get(w)
                    if j is None:
                        j = fill
                        pslots[w] = j
                        fill += 1
                        p = w // self.w_local
                        self._hsend_writes.append((p, g, j, w - p * self.w_local))
                    slots[i] = j
            self._hbucket_fill[key] = fill
            peak = max(peak, fill)
            pack["ebslot"][pos[sel]] = slots.astype(np.int32)
        return peak

    def _build_exchange_and_edges(self) -> None:
        """(Re)build the full host-side edge partition + exchange tables and
        upload. Called at construction and on a rebuild-grade change."""
        n_dev = self.n_dev
        #: consumer dev → {producer dev → sorted build-time word bucket}
        self._buckets: Dict[int, Dict[int, np.ndarray]] = {}
        #: consumer dev → {(producer, word) → slot} for PATCH-added words
        #: only (build-time slots resolve by searchsorted in _buckets)
        self._patch_slots: Dict[int, Dict[Tuple[int, int], int]] = {}
        self._bucket_fill: Dict[int, Dict[int, int]] = {}
        #: hier cross-host buckets: (producer host, consumer host) →
        #: sorted build-time GLOBAL word ids (+ append-only patch slots)
        self._hbuckets: Dict[Tuple[int, int], np.ndarray] = {}
        self._hpatch_slots: Dict[Tuple[int, int], Dict[int, int]] = {}
        self._hbucket_fill: Dict[Tuple[int, int], int] = {}
        self._hsend_writes: List[Tuple[int, int, int, int]] = []
        self._dev_edge_count = np.zeros(n_dev, dtype=np.int64)
        packs = [self._consumer_pack(d) for d in range(n_dev)]
        need_e = max((p["n_e"] for p in packs), default=0)
        if need_e > self.e_cap:
            # construction sizes e_cap itself; this only triggers on a
            # geometry edge case — size up front, it is not a "resize"
            self.e_cap = need_e + 32
        for d, pack in enumerate(packs):
            self._register_pack_buckets(d, pack)
        peak = max(
            (max(f.values(), default=0) for f in self._bucket_fill.values()),
            default=0,
        )
        self.bucket_cap = max(int(peak * self.bucket_headroom) + 8, 16)
        if self.exchange == "hier":
            # host buckets: vectorized union over every consumer's cross
            # word lists, one sorted array per (producer host, consumer
            # host) pair
            per_pair: Dict[Tuple[int, int], List[np.ndarray]] = {}
            for d, pack in enumerate(packs):
                if pack["cross"] is None:
                    continue
                g = d // self.dph
                ch, cw, _pos = pack["cross"]
                for h in np.unique(ch).tolist():
                    per_pair.setdefault((int(h), g), []).append(cw[ch == h])
            for key, parts in per_pair.items():
                hb = np.unique(np.concatenate(parts))
                self._hbuckets[key] = hb
                self._hpatch_slots[key] = {}
                self._hbucket_fill[key] = len(hb)
            hpeak = max(self._hbucket_fill.values(), default=0)
            self.hbucket_cap = max(int(hpeak * self.bucket_headroom) + 8, 16)
            for d, pack in enumerate(packs):
                self._assign_cross_slots(d, pack, append=False)
            self._hsend_writes = []
        else:
            self.hbucket_cap = 1
        self._rebuild_send_tables(packs)
        self._write_edge_slices({d: p for d, p in enumerate(packs)})
        self._recount_cross_words()
        self._upload_edges()

    def _rebuild_send_tables(self, packs: Sequence[dict]) -> None:
        """Materialize the send-index tables from the live bucket truth."""
        n_dev = self.n_dev
        if self.exchange == "a2a":
            send = np.full(
                (n_dev, n_dev, self.bucket_cap), self.w_local, np.int32
            )
            for d in range(n_dev):
                for p, wl in self._buckets[d].items():
                    send[p, d, : len(wl)] = wl
                for (p, w), j in self._patch_slots[d].items():
                    send[p, d, j] = w
            self._h_send = send.reshape(n_dev * n_dev, self.bucket_cap)
        elif self.exchange == "hier":
            # intra: producer p's rows are its same-host consumers by
            # LOCAL index — [n_dev * dph, icap], each device holds [dph, icap]
            send = np.full(
                (n_dev, self.dph, self.bucket_cap), self.w_local, np.int32
            )
            for d in range(n_dev):
                c_l = d % self.dph
                for p, wl in self._buckets[d].items():
                    send[p, c_l, : len(wl)] = wl
                for (p, w), j in self._patch_slots[d].items():
                    send[p, c_l, j] = w
            self._h_send = send.reshape(n_dev * self.dph, self.bucket_cap)
        else:
            self.bucket_cap = 16  # unused; kernel signature stays uniform
            self._h_send = np.zeros((n_dev, 1), np.int32)
        if self.exchange == "hier":
            # cross: device p's [n_hosts, hcap] block marks the local word
            # index of every host-bucket word IT owns (pad elsewhere; the
            # host group OR-assembles the full bucket on device)
            hs = np.full(
                (n_dev, self.n_hosts, self.hbucket_cap), self.w_local, np.int32
            )
            for (h, g), hb in self._hbuckets.items():
                if len(hb):
                    p = hb // self.w_local
                    hs[p, g, np.arange(len(hb))] = (hb - p * self.w_local).astype(
                        np.int32
                    )
                for w, j in self._hpatch_slots[(h, g)].items():
                    p = w // self.w_local
                    hs[p, g, j] = w - p * self.w_local
            self._h_hsend = hs.reshape(n_dev * self.n_hosts, self.hbucket_cap)
        else:
            self._h_hsend = np.zeros((self.n_dev, 1), np.int32)

    def _write_edge_slices(self, packs: Dict[int, dict]) -> None:
        """(Re)write the listed devices' fixed-width edge slices into the
        host mirrors (allocating them first when absent)."""
        if not hasattr(self, "_h_eprod") or len(self._h_eprod) != self.n_dev * self.e_cap:
            self._h_eprod = np.zeros(self.n_dev * self.e_cap, dtype=np.int32)
            self._h_ebslot = np.zeros(self.n_dev * self.e_cap, dtype=np.int32)
            self._h_ebit = np.zeros(self.n_dev * self.e_cap, dtype=np.int32)
            self._h_edst = np.full(
                self.n_dev * self.e_cap, self.n_local, dtype=np.int32
            )  # pad: dropped
            self._h_elsrc = np.full(
                self.n_dev * self.e_cap, self.n_local, dtype=np.int32
            )  # pad: fill-False on speculative gather
            self._h_eep = np.full(self.n_dev * self.e_cap, -1, dtype=np.int32)
        for d, pack in packs.items():
            sl = slice(d * self.e_cap, (d + 1) * self.e_cap)
            n_e = pack["n_e"]
            self._h_eprod[sl] = 0
            self._h_ebslot[sl] = 0
            self._h_ebit[sl] = 0
            self._h_edst[sl] = self.n_local
            self._h_elsrc[sl] = self.n_local
            self._h_eep[sl] = -1
            if n_e:
                self._h_eprod[sl][:n_e] = pack["eprod"]
                self._h_ebslot[sl][:n_e] = pack["ebslot"]
                self._h_ebit[sl][:n_e] = pack["ebit"]
                self._h_edst[sl][:n_e] = pack["edst"]
                self._h_elsrc[sl][:n_e] = pack["elsrc"]
                self._h_eep[sl][:n_e] = pack["eep"]

    def _recount_cross_words(self) -> None:
        """Static per-exchange-level cross-host payload (words), per mode —
        the ``fusion_mesh_cross_host_words_total`` increment unit. Zero on
        a single-host mesh by construction. For ``hier`` this counts the
        DISTINCT reduced host-bucket words (fill) — the frontier
        information that must cross; the recursive-doubling tree's wire
        traffic is larger (each round ships the accumulated payload incl.
        capacity padding, ~n_hosts x the fill at full depth)."""
        if self.n_hosts <= 1:
            self.cross_words_per_level = 0
            return
        if self.exchange == "hier":
            self.cross_words_per_level = int(sum(self._hbucket_fill.values()))
        elif self.exchange == "a2a":
            total = 0
            for d, by_p in self._bucket_fill.items():
                for p, fill in by_p.items():
                    if p // self.dph != d // self.dph:
                        total += fill
            self.cross_words_per_level = total
        else:  # tree/gather replicate the full frontier to every host
            self.cross_words_per_level = (
                (self.n_hosts - 1) * self.n_hosts * self.dph * self.w_local
            )

    def _upload_edges(self) -> None:
        self.g_send = self._put(self._h_send, self._send_sh)
        self.g_hsend = self._put(self._h_hsend, self._send_sh)
        self.g_eprod = self._put(self._h_eprod, self._edge_sh)
        self.g_ebslot = self._put(self._h_ebslot, self._edge_sh)
        self.g_ebit = self._put(self._h_ebit, self._edge_sh)
        self.g_edst = self._put(self._h_edst, self._edge_sh)
        self.g_elsrc = self._put(self._h_elsrc, self._edge_sh)
        self.g_eep = self._put(self._h_eep, self._edge_sh)

    # ------------------------------------------------------------------ resize
    def _try_grow(self, kind: str, needed: int, upload: bool = True) -> bool:
        """Grow an overflowed capacity IN PLACE (ISSUE 15): re-allocate the
        host table with headroom, re-upload, and let the next dispatch
        recompile against the new shape — slot assignments are
        cap-independent so NOTHING re-packs. Counted; a spent budget
        returns False and the caller takes the rebuild rung.

        ``upload=False`` defers the device re-upload to the caller — a
        mutation that may grow several capacities (or that ends with its
        own :meth:`_upload_edges`) pays ONE full-table transfer instead of
        one per grow; the caller must upload before the next dispatch."""
        if self.bucket_resizes >= self.max_resizes:
            global_metrics().counter(
                "fusion_mesh_resize_exhausted_total",
                help="bucket/edge-slack overflows that exhausted the in-place "
                "resize budget and fell to the rebuild rung",
            ).inc()
            return False
        if kind == "bucket":
            old = self.bucket_cap
            new = max(needed + 8, int(old * self.resize_growth) + 1)
            rows = self._h_send.shape[0]
            grown = np.full((rows, new), self.w_local, np.int32)
            grown[:, :old] = self._h_send
            self._h_send = grown
            self.bucket_cap = new
        elif kind == "hbucket":
            old = self.hbucket_cap
            new = max(needed + 8, int(old * self.resize_growth) + 1)
            rows = self._h_hsend.shape[0]
            grown = np.full((rows, new), self.w_local, np.int32)
            grown[:, :old] = self._h_hsend
            self._h_hsend = grown
            self.hbucket_cap = new
        elif kind == "edge":
            old = self.e_cap
            new = max(needed + 32, int(old * self.resize_growth) + 1)
            for name, pad in (
                ("_h_eprod", 0),
                ("_h_ebslot", 0),
                ("_h_ebit", 0),
                ("_h_edst", self.n_local),
                ("_h_elsrc", self.n_local),
                ("_h_eep", -1),
            ):
                arr = getattr(self, name)
                grown = np.full(self.n_dev * new, pad, dtype=np.int32)
                grown.reshape(self.n_dev, new)[:, :old] = arr.reshape(
                    self.n_dev, old
                )
                setattr(self, name, grown)
            self.e_cap = new
        else:  # pragma: no cover — internal misuse
            raise ValueError(kind)
        self.bucket_resizes += 1
        self.resize_detail[kind] += 1
        global_metrics().counter(
            "fusion_mesh_bucket_resizes_total",
            help="exchange-bucket / host-bucket / edge-slack capacities grown "
            "in place instead of rebuilding the routed mirror (ISSUE 15)",
        ).inc()
        if upload:
            self._upload_edges()
        return True

    # ------------------------------------------------------------------ waves
    def _count_exchange(self, levels: int, spec_levels: int, visits) -> None:
        """``visits``: uint32 [lo, hi] pairs, one a chip and wave."""
        self.levels_total += levels
        lo_hi = np.asarray(visits, dtype=np.uint64).reshape(-1, 2)
        visited = int(lo_hi[:, 0].sum() + (lo_hi[:, 1].sum() << np.uint64(32)))
        self.slot_visits_total += visited
        global_metrics().counter(
            "fusion_mesh_routed_slot_visits_total",
            help="edge slots the routed level loops read, summed over "
            "levels and chips: a slot leaves its wave's worklist once it "
            "has fired (a dense level loop reads levels x e_cap x n_dev)",
        ).inc(visited)
        if self.exchange_async and levels:
            # async mode: each merge epoch ends in exactly one counted
            # quiescence vote over the psum plane (the level fence that
            # replaced the per-level barrier)
            self.quiescence_checks += levels
            self.spec_levels_total += spec_levels
            global_metrics().counter(
                "fusion_mesh_quiescence_checks_total",
                help="async-mode counted quiescence votes (one per merge "
                "epoch — the fence that replaced the per-level exchange "
                "barrier, ISSUE 17)",
            ).inc(levels)
        if self.cross_words_per_level and levels:
            shipped = levels * self.cross_words_per_level
            self.cross_host_words += shipped
            global_metrics().counter(
                "fusion_mesh_cross_host_words_total",
                help="distinct reduced host-bucket frontier words per exchange "
                "level (the DCN leg's information content — what the bucket "
                "protocol exists to minimize). Wire cost runs higher: the "
                "recursive-doubling tree replicates the assembled payload "
                "~n_hosts x and ships capacity padding",
            ).inc(shipped)

    # -------------------------------------------------------- trace hooks
    #: derived per-level segments are capped per stage (coarsened by
    #: grouping, window preserved) so a deep wave cannot flood the store
    _TRACE_MAX_LEVELS = 64

    def _trace_cause_for_dispatch(self) -> Optional[str]:
        """The cause this dispatch's segments key under: the super-round's
        wave cause (contextvar) > a driver-pinned mesh-wide cause > a
        freshly minted wave-shaped cause. None when tracing is off."""
        if not global_mesh_trace().enabled:
            return None
        cause = current_dispatch_cause() or self.trace_cause
        if cause is None:
            cause = wave_shaped_cause(next_wave_seq())
        self.last_trace_cause = cause
        return cause

    def _pacing_shard(self, newly_node_ids) -> int:
        """The shard that carried most of this window's newly-invalid
        frontier — the per-host pacing attribution (the per-level split
        inside the jit'd kernel is not host-visible; the dominant shard
        of the harvested frontier is, and it is what a rebalance acts on)."""
        if newly_node_ids is None or len(newly_node_ids) == 0:
            return -1
        ips = self.placement.ids_per_shard
        counts = np.bincount(np.asarray(newly_node_ids, dtype=np.int64) // ips)
        return int(counts.argmax())

    def _trace_slice(self, store, cause, t0, t1, levels, spec, shard, level_base) -> int:
        """Record one stage's host-visible window as per-level segments.

        The wave kernel runs inside ONE jit dispatch — per-level host
        timestamps do not exist — so the measured window is divided across
        the counted levels (totals and ordering preserved; the derivation
        is documented in OBSERVABILITY.md, never passed off as measured).
        Async mode: the speculative share first (spec_expand), then one
        quiescence_vote per merge epoch; hier sync: each level splits into
        a2a (intra-host) + tree_round (cross-host); other sync modes: one
        exchange/tree_round segment per level. Returns the next wave-wide
        level index (chains keep level numbering cumulative)."""
        window = max(t1 - t0, 0.0)
        if levels <= 0:
            store.record(cause, "spec_expand" if spec else "exchange",
                         t0, t0 + window, host=self.trace_host, shard=shard)
            return level_base
        cursor = t0
        if self.exchange_async and spec > 0:
            cut = t0 + window * (spec / (spec + levels))
            store.record(cause, "spec_expand", cursor, cut,
                         host=self.trace_host, shard=shard)
            cursor = cut
        per = max(t1 - cursor, 0.0) / levels
        step = max(1, -(-levels // self._TRACE_MAX_LEVELS))
        for first in range(0, levels, step):
            n = min(step, levels - first)
            seg0 = cursor + first * per
            seg1 = seg0 + n * per
            lvl = level_base + first
            if self.exchange_async:
                store.record(cause, "quiescence_vote", seg0, seg1,
                             host=self.trace_host, level=lvl, shard=shard)
            elif self.exchange == "hier":
                mid = (seg0 + seg1) / 2.0
                store.record(cause, "a2a", seg0, mid,
                             host=self.trace_host, level=lvl, shard=shard)
                store.record(cause, "tree_round", mid, seg1,
                             host=self.trace_host, level=lvl, shard=shard)
            else:
                phase = "tree_round" if self.exchange == "tree" else "exchange"
                store.record(cause, phase, seg0, seg1,
                             host=self.trace_host, level=lvl, shard=shard)
        return level_base + levels

    def run_wave_collect(
        self, seed_node_ids: Sequence[int], cap: int = 65536
    ) -> Tuple[int, np.ndarray, bool]:
        """Union wave from node ids with O(wave) host exchange: seed ids up,
        compacted newly NODE ids back, one dispatch. Returns (count, newly
        node ids, overflow)."""
        self._check_usable()
        k = len(seed_node_ids)
        width = 1
        while width < max(k, 1):
            width <<= 1
        rows = np.full(width, self.n_global, dtype=np.int64)  # pad: dropped
        if k:
            r = self.perm[np.asarray(seed_node_ids, dtype=np.int64)]
            if r.min() < 0:
                raise PlacementError("seed node lands on an off-mesh shard")
            rows[:k] = r
        capd = max(cap // self.n_dev, 1024)
        self._ensure_worklist()
        cause = self._trace_cause_for_dispatch()
        t0 = time.perf_counter()
        with hot_span("routed.dispatch"):
            args = (self._host_arg(rows), self.g_is_real, *self._wave_args())
            fn = self._collect_cache.get((capd, width))
            if fn is None:
                fn = self._build_collect(capd)
                self._collect_cache[(capd, width)] = fn
                # the compile alone, apart from the wave that follows it:
                # program_warm_report() then says what a cold start owes it
                with time_program_warm(
                    "routed_collect",
                    key=(self.n_global, self.n_dev, self.exchange, capd, width),
                ):
                    fn.lower(*args).compile()
            self.g_invalid, counts, levels, spec, bufs, visits = fn(*args)
        with hot_span("routed.readback"):
            self._sync(self.g_invalid, counts, levels, spec, bufs, visits)
            counts = self._fetch(counts)
            levels = self._fetch(levels)
            spec = self._fetch(spec)
            bufs = self._fetch(bufs)
            visits = self._fetch(visits)
        self.waves_run += 1
        self._count_exchange(int(levels), int(spec), visits)
        count = int(counts.sum())
        overflow = bool((counts > capd).any())
        node_ids: Optional[np.ndarray] = None
        if not overflow:
            ids = np.concatenate(
                [bufs[d * capd : d * capd + int(counts[d])] for d in range(self.n_dev)]
            )
            node_ids = self.inv_perm[ids]
        if cause is not None:
            self._trace_slice(
                global_mesh_trace(), cause, t0, time.perf_counter(),
                int(levels), int(spec), self._pacing_shard(node_ids), 0,
            )
        if overflow:
            return count, np.empty(0, np.int64), True
        return count, node_ids, False

    def _build_collect(self, capd: int):
        wave = self._wave
        compact = build_routed_compact(self.mesh, self.n_global, self.n_dev, capd)
        node_sh = self._node_sh
        n_global = self.n_global

        @jax.jit
        def collect(seed_rows, is_real, send, hsend, work, inv, *local):
            frontier = lax.with_sharding_constraint(
                jnp.zeros(n_global, bool).at[seed_rows].set(True, mode="drop"),
                node_sh,
            )
            inv2, _count, levels, spec, visits = wave(
                frontier, send, hsend, work, inv, *local
            )
            counts, bufs = compact(inv2, inv, is_real)
            return inv2, counts, levels, spec, bufs, visits

        return collect

    def _wave_args(self) -> tuple:
        """What a wave reads besides its seeds and ``g_is_real``, in
        ``wave``'s order: the send tables, the resident worklist, the
        invalid state and, in the async mode, the edge slice its local
        speculation reads."""
        local = (
            (self.g_elsrc, self.g_edst, self.g_eep, self.g_node_epoch)
            if self.async_depth else ()
        )
        return (self.g_send, self.g_hsend, self.g_work, self.g_invalid, *local)

    def _ensure_worklist(self) -> None:
        """Build the resident worklist where there is none: at the first
        wave, and after an array it is computed from was replaced. In a
        graph whose topology and epochs stay put, every later wave reuses
        it. Counted in ``worklist_builds``."""
        if self.g_work is not None:
            return
        args = (
            self.g_send, self.g_hsend, self.g_eprod, self.g_ebslot, self.g_ebit,
            self.g_edst, self.g_eep, self.g_node_epoch,
        )
        with hot_span("routed.worklist"):
            key = (self.n_global, self.n_dev, self.exchange, self.e_cap,
                   self.bucket_cap, self.hbucket_cap)
            if key not in self._worklist_warmed:
                self._worklist_warmed.add(key)
                with time_program_warm("routed_worklist", key=key):
                    self._worklist.lower(*args).compile()
            self.g_work = self._worklist(*args)
        self.worklist_builds += 1
        global_metrics().counter(
            "fusion_mesh_routed_worklist_builds_total",
            help="routed worklists built: at a graph's first wave and after "
            "a patch, resize, reshard or snapshot import replaced an array "
            "it is computed from (a static graph builds one)",
        ).inc()

    # ------------------------------------------------------------------ chain
    def stage_union_chain(
        self, stage_seed_lists: Sequence[Sequence[int]], cap: int = 65536
    ) -> dict:
        """Host-side pack of a union chain's seed tensor — the super-round
        BACK BUFFER (ISSUE 14): perm-map and pad WITHOUT dispatching, so
        the pack runs while the previous chain executes on device. The
        staged dict carries a (graph identity, placement epoch) token;
        :meth:`dispatch_union_chain` refuses a buffer staged against a
        permutation a reshard/rebuild has since retired (PlacementError —
        the caller re-stages, counted, never silently dispatches stale
        row ids)."""
        K = len(stage_seed_lists)
        if K == 0:
            raise ValueError("empty chain")
        width = 1
        kmax = max((len(s) for s in stage_seed_lists), default=1)
        while width < max(kmax, 1):
            width <<= 1
        mat = np.full((K, width), self.n_global, dtype=np.int64)
        for i, seeds in enumerate(stage_seed_lists):
            if seeds:
                r = self.perm[np.asarray(seeds, dtype=np.int64)]
                if r.min() < 0:
                    raise PlacementError("seed node lands on an off-mesh shard")
                mat[i, : len(seeds)] = r
        capd = max(cap // self.n_dev, 1024)
        return {
            "mat": mat, "stages": K, "width": width, "capd": capd,
            "token": (id(self), self.placement.epoch),
        }

    def dispatch_union_chain(
        self,
        stage_seed_lists: Optional[Sequence[Sequence[int]]] = None,
        cap: int = 65536,
        staged: Optional[dict] = None,
    ) -> dict:
        """K logical union waves in ONE lax.scan dispatch, NO readback:
        stage i cascades against the invalid state stages < i left (each
        result equals a sequential per-stage dispatch). ``staged`` (from
        :meth:`stage_union_chain`) skips the host pack — the double-
        buffered super-round path. Returns a pending ticket for
        :meth:`harvest_union_chain`; the device invalid state advances
        immediately (futures)."""
        self._check_usable()
        if staged is None:
            staged = self.stage_union_chain(stage_seed_lists, cap)
        elif staged["token"] != (id(self), self.placement.epoch):
            raise PlacementError(
                "staged seed buffer predates a reshard/rebuild — re-stage"
            )
        K, width, capd = staged["stages"], staged["width"], staged["capd"]
        mat = staged["mat"]
        fn = self._chain_cache.get((K, width, capd))
        if fn is None:
            fn = self._build_chain(capd)
            self._chain_cache[(K, width, capd)] = fn
        self._ensure_worklist()
        trace_cause = self._trace_cause_for_dispatch()
        trace_t0 = time.perf_counter()
        self.g_invalid, counts, levels, spec, bufs, visits = fn(
            self._host_arg(mat), self.g_is_real, *self._wave_args()
        )
        # multi-process: the chain's collectives must fully drain before
        # any later module's (harvest fetch, patch) hit the gloo pairs —
        # the dispatch stays nonblocking on a single-process mesh
        self._sync(self.g_invalid, counts, levels, spec, bufs, visits)
        return {"counts": counts, "levels": levels, "spec": spec, "bufs": bufs,
                "visits": visits,
                "stages": K, "capd": capd, "dispatches": 1,
                "trace_cause": trace_cause, "trace_t0": trace_t0}

    def _build_chain(self, capd: int):
        wave = self._wave
        compact = build_routed_compact(self.mesh, self.n_global, self.n_dev, capd)
        node_sh = self._node_sh
        n_global = self.n_global

        @jax.jit
        def chain(seed_mat, is_real, send, hsend, work, inv0, *local):
            # the graph cannot change inside the scan: every stage starts
            # from the one resident worklist
            def body(inv, seed_rows):
                frontier = lax.with_sharding_constraint(
                    jnp.zeros(n_global, bool).at[seed_rows].set(True, mode="drop"),
                    node_sh,
                )
                inv2, _c, levels, spec, visits = wave(
                    frontier, send, hsend, work, inv, *local
                )
                counts, bufs = compact(inv2, inv, is_real)
                return inv2, (counts, levels, spec, bufs, visits)

            inv, (counts, levels, spec, bufs, visits) = lax.scan(body, inv0, seed_mat)
            return inv, counts, levels, spec, bufs, visits

        return chain

    def harvest_union_chain(self, pending: dict) -> Tuple[np.ndarray, List[np.ndarray], dict]:
        """Block on a chain ticket: (per-stage counts, per-stage newly NODE
        id arrays, info). An overflowed stage returns ``None`` in its slot —
        the caller mask-diffs against its dense mirror; every overflow is
        COUNTED (``fusion_mesh_chain_overflows_total``), the containment
        path is never silent."""
        counts_dev = self._fetch(pending["counts"])
        levels = self._fetch(pending["levels"])
        spec = self._fetch(pending["spec"])
        bufs = self._fetch(pending["bufs"])
        capd = pending["capd"]
        self.waves_run += pending["stages"]
        self._count_exchange(
            int(levels.sum()), int(spec.sum()), self._fetch(pending["visits"])
        )
        counts = counts_dev.astype(np.int64).sum(axis=1)
        stage_ids: List[Optional[np.ndarray]] = []
        overflowed = False
        for i in range(pending["stages"]):
            if (counts_dev[i] > capd).any():
                stage_ids.append(None)
                overflowed = True
            else:
                stage_ids.append(
                    self.inv_perm[
                        np.concatenate(
                            [
                                bufs[i, d * capd : d * capd + int(counts_dev[i, d])]
                                for d in range(self.n_dev)
                            ]
                        )
                    ]
                )
        if overflowed:
            global_metrics().counter(
                "fusion_mesh_chain_overflows_total",
                help="fused-chain stages whose compacted newly-id buffer "
                "overflowed (recovered by one dense mask diff — counted, "
                "never silent)",
            ).inc(sum(1 for i in stage_ids if i is None))
        cause = pending.get("trace_cause")
        store = global_mesh_trace()
        if cause is not None and store.enabled:
            # the chain's dispatch→harvest window, split across stages
            # proportionally to their counted levels, then per-level within
            # each stage (_trace_slice); level numbering runs cumulatively
            # so the stitched timeline's merge epochs stay distinct
            t1 = time.perf_counter()
            t0 = float(pending.get("trace_t0", t1))
            lv = levels.astype(np.int64).ravel()
            sp = spec.astype(np.int64).ravel()
            weights = np.maximum(lv + sp, 1).astype(np.float64)
            edges = np.concatenate([[0.0], np.cumsum(weights)])
            scale = max(t1 - t0, 0.0) / edges[-1] if edges[-1] else 0.0
            level_base = 0
            for i in range(pending["stages"]):
                level_base = self._trace_slice(
                    store, cause, t0 + edges[i] * scale, t0 + edges[i + 1] * scale,
                    int(lv[i]), int(sp[i]), self._pacing_shard(stage_ids[i]),
                    level_base,
                )
        info = {"levels": levels.astype(np.int64), "overflowed": overflowed,
                "trace_cause": cause}
        return counts, stage_ids, info

    # ------------------------------------------------------------------ state
    def device_layout(self) -> dict:
        """Where each resident array's shards actually live: ``{array name:
        [device id of block 0, block 1, ...]}`` over this process's
        addressable shards, in block order. Block ``d`` belongs on
        ``mesh.devices.flat[d]`` (the device ``DevicePlacement`` assigns
        shard group ``d``): a bring-up check that nothing collapsed onto
        device 0."""
        layout = {}
        for name in (
            "g_node_epoch", "g_invalid", "g_is_real", "g_send", "g_hsend",
            "g_eprod", "g_ebslot", "g_ebit", "g_edst", "g_elsrc", "g_eep",
        ):
            shards = sorted(
                getattr(self, name).addressable_shards,
                key=lambda sh: sh.index[0].start or 0,
            )
            layout[name] = [sh.device.id for sh in shards]
        return layout

    def invalid_mask(self) -> np.ndarray:
        """bool[n_nodes] in NODE space (reads the device state once)."""
        arr = self._fetch(self.g_invalid)
        out = np.zeros(self.n_nodes, dtype=bool)
        for lo, hi, base in self._runs:
            out[lo:hi] = arr[base : base + hi - lo]
        return out

    def set_invalid(self, mask: np.ndarray) -> None:
        inv = np.zeros(self.n_global, dtype=bool)
        m = np.asarray(mask[: self.n_nodes], dtype=bool)
        for lo, hi, base in self._runs:
            hi = min(hi, len(m))
            if hi > lo:
                inv[base : base + hi - lo] = m[lo:hi]
        self.g_invalid = self._put(inv, self._node_sh)

    def clear_invalid(self) -> None:
        self.g_invalid = self._put(
            np.zeros(self.n_global, dtype=bool), self._node_sh
        )

    # ------------------------------------------------------------------ reshard
    def apply_placement(self, new_placement: DevicePlacement, moves) -> None:
        """MOVE the listed device shards to their new owners: each moved
        shard's fixed-width row block transfers on-device (one fused
        gather/scatter dispatch for node state), and the affected consumer
        devices' edge slices + exchange buckets re-pack — affected means
        the old/new OWNER devices plus every consumer whose edges SOURCE
        from a moved shard (their slot/bucket routes reference the
        vacated rows; missing them loses invalidations silently — caught
        in review with a single-shard-move repro). State for unmoved
        shards never leaves its device. An overflow the in-place resize
        ladder cannot absorb raises :class:`PlacementError`, after which
        the graph is BROKEN (every wave entry point refuses) — the caller
        rebuilds. Cross-host row moves (the DCN transfers the host-aware
        placement ranking minimizes) are counted separately."""
        if not moves:
            self.placement = new_placement
            return
        old_rows_l: List[np.ndarray] = []
        new_rows_l: List[np.ndarray] = []
        affected_devs: set = set()
        ips = self.placement.ids_per_shard
        for s, old_dev, new_dev in moves:
            if old_dev >= 0:
                affected_devs.add(old_dev)
            if new_dev >= 0:
                affected_devs.add(new_dev)
            if old_dev < 0 or new_dev < 0:
                # shard entering/leaving the mesh changes real-row coverage:
                # that is a rebuild-grade change, not an in-place move
                raise PlacementError(f"shard {s} crossed the mesh boundary")
            base_old = old_dev * self.n_local + int(self.placement.shard_slot[s]) * self.placement.slot_rows
            base_new = new_dev * self.n_local + int(new_placement.shard_slot[s]) * new_placement.slot_rows
            n = min(ips, self.n_nodes - s * ips)
            if n <= 0:
                continue
            old_rows_l.append(np.arange(base_old, base_old + n, dtype=np.int64))
            new_rows_l.append(np.arange(base_new, base_new + n, dtype=np.int64))
        # consumers whose edge SOURCES moved: their exchange routes (a2a
        # buckets / host buckets / global word slots) point at the old rows
        moved_shards = np.fromiter((m[0] for m in moves), dtype=np.int64)
        for shard, ent in self._shard_edges.items():
            d = int(new_placement.shard_dev[shard])
            if d < 0 or d in affected_devs:
                continue
            if len(ent[0]) and np.isin(ent[0] // ips, moved_shards).any():
                affected_devs.add(d)
        cross = new_placement.cross_host_moves(moves) if self.n_hosts > 1 else 0
        self.placement = new_placement
        self._set_permutation(new_placement)
        self.g_is_real = self._put(self._h_is_real, self._node_sh)
        if old_rows_l:
            old_rows = np.concatenate(old_rows_l)
            new_rows = np.concatenate(new_rows_l)
            width = 1 << int(len(old_rows) - 1).bit_length()
            po = np.full(width, self.n_global, dtype=np.int64)
            pn = np.full(width, self.n_global, dtype=np.int64)
            po[: len(old_rows)] = old_rows
            pn[: len(new_rows)] = new_rows
            fn = self._move_cache.get(width)
            if fn is None:
                fn = self._build_move()
                self._move_cache[width] = fn
            self.g_node_epoch, self.g_invalid = fn(
                self.g_node_epoch, self.g_invalid,
                self._host_arg(po), self._host_arg(pn),
            )
            self._sync(self.g_node_epoch, self.g_invalid)
        # re-pack edges + buckets for the touched consumer devices only
        try:
            self._repack_devices(sorted(affected_devs))
        except PlacementError:
            # the state blocks already moved and some devices may be half
            # repacked — a partial rollback would LOOK usable while being
            # wrong (review finding). Mark broken; every wave entry point
            # refuses until the caller rebuilds.
            self.broken = True
            raise
        self.shard_moves += len(moves)
        if cross:
            self.cross_host_moves += cross
            global_metrics().counter(
                "fusion_mesh_cross_host_moves_total",
                help="moved device-shard row blocks that crossed a host "
                "boundary during a reshard (the DCN transfers the "
                "host-aware placement ranking minimizes)",
            ).inc(cross)

    def _build_move(self):
        node_sh = self._node_sh

        @jax.jit
        def move(ep, inv, old_rows, new_rows):
            mep = ep.at[old_rows].get(mode="fill", fill_value=0)
            minv = inv.at[old_rows].get(mode="fill", fill_value=False)
            ep = ep.at[old_rows].set(0, mode="drop").at[new_rows].set(mep, mode="drop")
            inv = (
                inv.at[old_rows].set(False, mode="drop")
                .at[new_rows].set(minv, mode="drop")
            )
            return (
                lax.with_sharding_constraint(ep, node_sh),
                lax.with_sharding_constraint(inv, node_sh),
            )

        return move

    def _repack_devices(self, devs: Sequence[int]) -> None:
        """Host-side re-pack of the listed consumer devices' edge slices
        and their bucket columns, then one upload. Overflow climbs the
        resize ladder first (edge slack and bucket/host-bucket capacities
        grow in place, counted); only a spent budget raises."""
        packs = {d: self._consumer_pack(d) for d in devs}
        need_e = max((p["n_e"] for p in packs.values()), default=0)
        if need_e > self.e_cap and not self._try_grow("edge", need_e, upload=False):
            raise PlacementError(
                f"edge slice {need_e} exceeds capacity {self.e_cap} and the "
                f"resize budget is spent"
            )
        for d, pack in packs.items():
            self._register_pack_buckets(d, pack)
        peak = max(
            (max(f.values(), default=0) for f in self._bucket_fill.values()),
            default=0,
        )
        if peak > self.bucket_cap and not self._try_grow("bucket", peak, upload=False):
            raise PlacementError(
                f"exchange bucket fill {peak} exceeds cap {self.bucket_cap} "
                f"and the resize budget is spent"
            )
        if self.exchange == "hier":
            self._hsend_writes = []
            hpeak = 0
            for d, pack in packs.items():
                hpeak = max(hpeak, self._assign_cross_slots(d, pack, append=True))
            if hpeak > self.hbucket_cap and not self._try_grow(
                "hbucket", hpeak, upload=False
            ):
                raise PlacementError(
                    f"host bucket fill {hpeak} exceeds cap {self.hbucket_cap} "
                    f"and the resize budget is spent"
                )
            for p, g, j, wloc in self._hsend_writes:
                self._h_hsend[p * self.n_hosts + g, j] = wloc
            self._hsend_writes = []
        # repacked consumers rewrite their send columns from bucket truth
        if self.exchange == "a2a":
            for d, pack in packs.items():
                send3 = self._h_send.reshape(self.n_dev, self.n_dev, self.bucket_cap)
                for p in range(self.n_dev):
                    col = np.full(self.bucket_cap, self.w_local, np.int32)
                    wl = self._buckets[d].get(p)
                    if wl is not None and len(wl):
                        col[: len(wl)] = wl
                    send3[p, d] = col
        elif self.exchange == "hier":
            send3 = self._h_send.reshape(self.n_dev, self.dph, self.bucket_cap)
            for d, pack in packs.items():
                c_l = d % self.dph
                my_host = d // self.dph
                for p in range(my_host * self.dph, (my_host + 1) * self.dph):
                    col = np.full(self.bucket_cap, self.w_local, np.int32)
                    wl = self._buckets[d].get(p)
                    if wl is not None and len(wl):
                        col[: len(wl)] = wl
                    send3[p, c_l] = col
        self._write_edge_slices(packs)
        self._recount_cross_words()
        self._upload_edges()

    # ------------------------------------------------------------------ patches
    def patch_batch(
        self,
        bump_ids: np.ndarray,
        add_u: np.ndarray,
        add_v: np.ndarray,
        add_ep: np.ndarray,
    ) -> bool:
        """Apply a WHOLE burst's structural patches in one fused device
        dispatch: epoch bumps scatter-add (+k for k bumps of one row —
        final state is order-independent because bumps are increments and
        adds carry absolute captured epochs), new edges splice into
        per-device slack slots routed by their destination's OWNER.
        Exhausted slack GROWS IN PLACE first (edge slots, exchange
        buckets, host buckets — each counted in
        ``fusion_mesh_bucket_resizes_total``); returns False only for
        rebuild-grade shapes (new nodes, off-mesh endpoints) or a spent
        resize budget — after False the caller MUST rebuild (host truth
        may be partially advanced, same contract as before)."""
        self._check_usable()
        bump_rows = np.empty(0, np.int64)
        bump_counts = np.empty(0, np.int32)
        if len(bump_ids):
            ids = np.asarray(bump_ids, dtype=np.int64)
            uniq, counts = np.unique(ids, return_counts=True)
            rows = self.perm[uniq]
            if rows.min() < 0:
                return False
            bump_rows, bump_counts = rows, counts.astype(np.int32)
            # host truth for future repacks: nothing — node epochs live only
            # on device + dense mirror; shard edge lists carry captured
            # epochs, which bumps do not rewrite
        e_rows = np.empty(0, np.int64)
        e_prod = np.empty(0, np.int32)
        e_bslot = np.empty(0, np.int32)
        e_bit = np.empty(0, np.int32)
        e_dst = np.empty(0, np.int32)
        e_lsrc = np.empty(0, np.int32)
        e_ep = np.empty(0, np.int32)
        send_writes: List[Tuple[int, int, int, int]] = []  # (p, c, j, wl) intra
        self._hsend_writes = []
        grew = False  # defer the grow re-uploads to ONE transfer pre-dispatch
        if len(add_u):
            u = np.asarray(add_u, dtype=np.int64)
            v = np.asarray(add_v, dtype=np.int64)
            ep = np.asarray(add_ep, dtype=np.int32)
            if (u >= self.n_nodes).any() or (v >= self.n_nodes).any():
                return False  # nodes born after the build: rebuild
            ips = self.placement.ids_per_shard
            u_rows = self.perm[u]
            v_rows = self.perm[v]
            if len(u_rows) and (u_rows.min() < 0 or v_rows.min() < 0):
                return False
            shards = v // ips
            devs = (v_rows // self.n_local).astype(np.int64)
            # pre-scan the edge slack so e_rows are computed against ONE
            # final e_cap (a mid-batch grow would mix two layouts)
            uds, ucounts = np.unique(devs, return_counts=True)
            need_e = int(
                max(
                    self._dev_edge_count[d] + k
                    for d, k in zip(uds.tolist(), ucounts.tolist())
                )
            )
            if need_e > self.e_cap:
                if not self._try_grow("edge", need_e, upload=False):
                    return False  # edge slack exhausted: rebuild rung
                grew = True
            er, eP, eS, eb, ed, el, ee = [], [], [], [], [], [], []
            bucket_need = 0
            hbucket_need = 0
            for d in uds.tolist():
                sel = devs == d
                k = int(sel.sum())
                base = int(self._dev_edge_count[d])
                self._dev_edge_count[d] = base + k
                rows = d * self.e_cap + base + np.arange(k, dtype=np.int64)
                ur, vr = u_rows[sel], v_rows[sel]
                er.append(rows)
                eb.append((ur & 31).astype(np.int32))
                ed.append((vr - d * self.n_local).astype(np.int32))
                el.append(
                    np.where(
                        ur // self.n_local == d, ur - d * self.n_local, self.n_local
                    ).astype(np.int32)
                )
                ee.append(ep[sel])
                if self.exchange in ("tree", "gather"):
                    eP.append(np.zeros(k, np.int32))
                    eS.append((ur >> 5).astype(np.int32))
                else:
                    prod = (ur // self.n_local).astype(np.int64)
                    wl = (ur >> 5) - prod * self.w_local
                    my_host = d // self.dph
                    prods = np.empty(k, dtype=np.int64)
                    slots = np.empty(k, dtype=np.int64)
                    built = self._buckets[d]
                    patch_slots = self._patch_slots[d]
                    fill = self._bucket_fill[d]
                    for i, (p, w) in enumerate(zip(prod.tolist(), wl.tolist())):
                        if self.exchange == "hier" and p // self.dph != my_host:
                            # cross-host edge: slot in the (H, G) host
                            # bucket, append-only (other consumers' slots
                            # never shift)
                            h = p // self.dph
                            key = (h, my_host)
                            wg = p * self.w_local + w
                            hb = self._hbuckets.get(key)
                            j = None
                            if hb is not None and len(hb):
                                pos = int(np.searchsorted(hb, wg))
                                if pos < len(hb) and hb[pos] == wg:
                                    j = pos
                            if j is None:
                                pslots = self._hpatch_slots.setdefault(key, {})
                                j = pslots.get(wg)
                                if j is None:
                                    j = self._hbucket_fill.get(
                                        key, len(hb) if hb is not None else 0
                                    )
                                    pslots[wg] = j
                                    self._hbucket_fill[key] = j + 1
                                    self._hsend_writes.append((p, my_host, j, w))
                            hbucket_need = max(hbucket_need, j + 1)
                            prods[i] = self.n_dev + h
                            slots[i] = j
                            continue
                        bucket = built.get(p)
                        j = None
                        if bucket is not None and len(bucket):
                            pos = int(np.searchsorted(bucket, w))
                            if pos < len(bucket) and bucket[pos] == w:
                                j = pos
                        if j is None:
                            j = patch_slots.get((p, w))
                        if j is None:
                            j = fill.get(p, 0)
                            patch_slots[(p, w)] = j
                            fill[p] = j + 1
                            send_writes.append((p, d, j, w))
                        bucket_need = max(bucket_need, j + 1)
                        prods[i] = p
                        slots[i] = j
                    eP.append(prods.astype(np.int32))
                    eS.append(slots.astype(np.int32))
                # host truth for future repacks
                for s in np.unique(shards[sel]).tolist():
                    ss = sel & (shards == s)
                    ent = self._shard_edges.setdefault(
                        int(s),
                        [np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0, np.int32)],
                    )
                    ent[0] = np.concatenate([ent[0], u[ss]])
                    ent[1] = np.concatenate([ent[1], v[ss]])
                    ent[2] = np.concatenate([ent[2], ep[ss]])
                # mirror into host edge arrays
                self._h_eprod[rows] = eP[-1]
                self._h_ebslot[rows] = eS[-1]
                self._h_ebit[rows] = eb[-1]
                self._h_edst[rows] = ed[-1]
                self._h_elsrc[rows] = el[-1]
                self._h_eep[rows] = ee[-1]
            # bucket growth AFTER slot assignment (slots are cap-independent
            # — only the flat table rows below depend on the final caps)
            if bucket_need > self.bucket_cap:
                if not self._try_grow("bucket", bucket_need, upload=False):
                    return False
                grew = True
            if hbucket_need > self.hbucket_cap:
                if not self._try_grow("hbucket", hbucket_need, upload=False):
                    return False
                grew = True
            if grew:
                # one transfer for every grow this batch: the fused dispatch
                # below scatters into device tables of the FINAL shapes
                self._upload_edges()
            e_rows = np.concatenate(er) if er else e_rows
            e_prod = np.concatenate(eP) if eP else e_prod
            e_bslot = np.concatenate(eS) if eS else e_bslot
            e_bit = np.concatenate(eb) if eb else e_bit
            e_dst = np.concatenate(ed) if ed else e_dst
            e_lsrc = np.concatenate(el) if el else e_lsrc
            e_ep = np.concatenate(ee) if ee else e_ep
        # materialize the send-table writes with the FINAL capacities
        s_rows = np.empty(0, np.int64)
        s_vals = np.empty(0, np.int32)
        if send_writes:
            if self.exchange == "a2a":
                s_rows = np.asarray(
                    [(p * self.n_dev + c) * self.bucket_cap + j for p, c, j, _w in send_writes],
                    dtype=np.int64,
                )
            else:  # hier intra: row p*dph + local consumer index
                s_rows = np.asarray(
                    [
                        (p * self.dph + (c % self.dph)) * self.bucket_cap + j
                        for p, c, j, _w in send_writes
                    ],
                    dtype=np.int64,
                )
            s_vals = np.asarray([w for _p, _c, _j, w in send_writes], dtype=np.int32)
            flat = self._h_send.reshape(-1)
            flat[s_rows] = s_vals
        hs_rows = np.empty(0, np.int64)
        hs_vals = np.empty(0, np.int32)
        if self._hsend_writes:
            hs_rows = np.asarray(
                [
                    (p * self.n_hosts + g) * self.hbucket_cap + j
                    for p, g, j, _w in self._hsend_writes
                ],
                dtype=np.int64,
            )
            hs_vals = np.asarray(
                [w for _p, _g, _j, w in self._hsend_writes], dtype=np.int32
            )
            hflat = self._h_hsend.reshape(-1)
            hflat[hs_rows] = hs_vals
            self._hsend_writes = []
        if send_writes or len(hs_rows):
            # new bucket words may be cross-host in EITHER mode (a2a routes
            # cross-host pairs through the same per-(p, c) buckets) — keep
            # fusion_mesh_cross_host_words_total's per-level unit honest
            self._recount_cross_words()
        if not len(bump_rows) and not len(e_rows):
            return True
        # ONE fused dispatch for the whole batch — pad each index family to
        # a pow2 width (OOB pads dropped) so program shapes cache
        def _pad(a, fill, dtype=np.int64):
            w = max(64, 1 << int(max(len(a), 1) - 1).bit_length())
            out = np.full(w, fill, dtype=dtype)
            out[: len(a)] = a
            return out

        pb = _pad(bump_rows, self.n_global)
        pbc = _pad(bump_counts, 0, np.int32)
        pe = _pad(e_rows, self.n_dev * self.e_cap)
        pep = _pad(e_prod, 0, np.int32)
        pes = _pad(e_bslot, 0, np.int32)
        peb = _pad(e_bit, 0, np.int32)
        ped = _pad(e_dst, self.n_local, np.int32)
        pel = _pad(e_lsrc, self.n_local, np.int32)
        pee = _pad(e_ep, -1, np.int32)
        ps = _pad(s_rows, self._h_send.size)
        psv = _pad(s_vals, self.w_local, np.int32)
        ph = _pad(hs_rows, self._h_hsend.size)
        phv = _pad(hs_vals, self.w_local, np.int32)
        key = (len(pb), len(pe), len(ps), len(ph))
        fn = self._patch_cache.get(key)
        if fn is None:
            fn = self._build_patch()
            self._patch_cache[key] = fn
        (
            self.g_node_epoch, self.g_eprod, self.g_ebslot, self.g_ebit,
            self.g_edst, self.g_elsrc, self.g_eep, self.g_send, self.g_hsend,
        ) = fn(
            self.g_node_epoch, self.g_eprod, self.g_ebslot, self.g_ebit,
            self.g_edst, self.g_elsrc, self.g_eep, self.g_send, self.g_hsend,
            self._host_arg(pb), self._host_arg(pbc), self._host_arg(pe),
            self._host_arg(pep), self._host_arg(pes), self._host_arg(peb),
            self._host_arg(ped), self._host_arg(pel), self._host_arg(pee),
            self._host_arg(ps), self._host_arg(psv), self._host_arg(ph),
            self._host_arg(phv),
        )
        self._sync(self.g_node_epoch, self.g_send)
        self.patches += 1
        self.patch_dispatches += 1
        return True

    def _build_patch(self):
        node_sh, edge_sh, send_sh = self._node_sh, self._edge_sh, self._send_sh

        @jax.jit
        def patch(nep, eprod, ebslot, ebit, edst, elsrc, eep, send, hsend,
                  b_rows, b_counts, e_rows, e_prod, e_bslot, e_bit, e_dst,
                  e_lsrc, e_ep, s_rows, s_vals, h_rows, h_vals):
            nep = nep.at[b_rows].add(b_counts, mode="drop")
            eprod = eprod.at[e_rows].set(e_prod, mode="drop")
            ebslot = ebslot.at[e_rows].set(e_bslot, mode="drop")
            ebit = ebit.at[e_rows].set(e_bit, mode="drop")
            edst = edst.at[e_rows].set(e_dst, mode="drop")
            elsrc = elsrc.at[e_rows].set(e_lsrc, mode="drop")
            eep = eep.at[e_rows].set(e_ep, mode="drop")
            flat = send.reshape(-1).at[s_rows].set(s_vals, mode="drop")
            hflat = hsend.reshape(-1).at[h_rows].set(h_vals, mode="drop")
            return (
                lax.with_sharding_constraint(nep, node_sh),
                lax.with_sharding_constraint(eprod, edge_sh),
                lax.with_sharding_constraint(ebslot, edge_sh),
                lax.with_sharding_constraint(ebit, edge_sh),
                lax.with_sharding_constraint(edst, edge_sh),
                lax.with_sharding_constraint(elsrc, edge_sh),
                lax.with_sharding_constraint(eep, edge_sh),
                lax.with_sharding_constraint(flat.reshape(send.shape), send_sh),
                lax.with_sharding_constraint(hflat.reshape(hsend.shape), send_sh),
            )

        return patch

    # ------------------------------------------------------------------ snapshots
    def export_shard_state(self, local_only: bool = False) -> dict:
        """Per-device-shard node state keyed by VIRTUAL SHARD id (the unit
        that survives a reshard): checkpoint/durable.py stores this so a
        warm restart re-pins each shard under whatever placement the
        restarting process derives — layout-independent by construction.
        ``local_only=True`` exports only the shards whose owner device is
        on THIS host process (the per-host snapshot unit of the multihost
        chaos ladder)."""
        ep = self._fetch(self.g_node_epoch)
        inv = self._fetch(self.g_invalid)
        pl = self.placement
        my_host = None
        if local_only and self._procs > 1:
            import jax as _jax

            my_host = _jax.process_index()
        shards: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for s in range(pl.shard_map.n_shards):
            if pl.shard_dev[s] < 0:
                continue
            if my_host is not None and int(pl.shard_dev[s]) // self.dph != my_host:
                continue
            lo = s * pl.ids_per_shard
            n = min(pl.ids_per_shard, self.n_nodes - lo)
            if n <= 0:
                continue
            base = pl.row_of_shard(s)
            shards[s] = (ep[base : base + n].copy(), inv[base : base + n].copy())
        return {
            "epoch": pl.epoch,
            "n_nodes": self.n_nodes,
            "n_shards": pl.shard_map.n_shards,
            "shards": shards,
        }

    def import_shard_state(self, snap: dict) -> int:
        """Re-pin snapshotted shard states under THIS graph's placement.
        Returns the number of shards restored (shards the snapshot lacks
        keep their built state)."""
        pl = self.placement
        if snap.get("n_nodes") != self.n_nodes or snap.get("n_shards") != pl.shard_map.n_shards:
            # shard keying is only meaningful under the SAME (n_nodes, V)
            # geometry — ids_per_shard derives from both, and restoring a
            # wider snapshot would write past a shard's slot into its
            # neighbour's rows (silent cross-shard corruption). Refuse.
            raise ValueError(
                f"mesh shard snapshot geometry (n_nodes={snap.get('n_nodes')}, "
                f"n_shards={snap.get('n_shards')}) does not match this graph "
                f"({self.n_nodes}, {pl.shard_map.n_shards}); cold-build instead"
            )
        ep = self._fetch(self.g_node_epoch).copy()
        inv = self._fetch(self.g_invalid).copy()
        restored = 0
        for s, (sep, sinv) in snap["shards"].items():
            s = int(s)
            if s >= pl.shard_map.n_shards or pl.shard_dev[s] < 0:
                continue
            base = pl.row_of_shard(s)
            # belt on top of the geometry check: never write past the
            # shard's real-id extent
            n = min(len(sep), max(self.n_nodes - s * pl.ids_per_shard, 0), pl.slot_rows)
            ep[base : base + n] = sep[:n]
            inv[base : base + n] = sinv[:n]
            restored += 1
        self.g_node_epoch = self._put(ep, self._node_sh)
        self.g_invalid = self._put(inv, self._node_sh)
        return restored

    def _check_usable(self) -> None:
        if self.broken:
            raise PlacementError(
                "routed graph broken by a failed in-place reshard; rebuild"
            )

    # ------------------------------------------------------------------ stats
    def stats(self) -> dict:
        return {
            "exchange": self.exchange,
            "n_dev": self.n_dev,
            "hosts": self.n_hosts,
            "devices_per_host": self.dph,
            "n_nodes": self.n_nodes,
            "n_global": self.n_global,
            "e_cap": self.e_cap,
            "bucket_cap": self.bucket_cap,
            "hbucket_cap": self.hbucket_cap,
            "placement_epoch": self.placement.epoch,
            "waves_run": self.waves_run,
            "worklist_builds": self.worklist_builds,
            "exchange_levels_total": self.levels_total,
            "slot_visits_total": self.slot_visits_total,
            "exchange_async": self.exchange_async,
            "async_depth": self.async_depth,
            "quiescence_checks": self.quiescence_checks,
            "spec_levels_total": self.spec_levels_total,
            "tree_fallbacks": self.tree_fallbacks,
            "shard_moves": self.shard_moves,
            "cross_host_moves": self.cross_host_moves,
            "patches": self.patches,
            "patch_dispatches": self.patch_dispatches,
            "bucket_resizes": self.bucket_resizes,
            "hier_fallbacks": self.hier_fallbacks,
            "resize_detail": dict(self.resize_detail),
            "cross_host_words": self.cross_host_words,
            "cross_words_per_level": self.cross_words_per_level,
        }
