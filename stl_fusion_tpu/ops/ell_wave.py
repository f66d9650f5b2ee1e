"""Work-efficient invalidation waves: ELL adjacency + bucketed frontiers.

The dense edge-parallel kernel (wave.py) costs O(total edges) per BFS level
— the right shape for huge frontiers, hopeless for the common case where a
wave touches 0.1-10% of a 10M-node graph. This module is the work-efficient
path: per level it reads only the out-edges of the ACTIVE frontier.

Two TPU-specific problems and their solutions:

1. **Power-law out-degree vs static shapes.** A hub node (a config value
   ten thousand views depend on) has out-degree ~10⁴; padding every node's
   edge list to the max is unusable. The graph is therefore rewritten into
   **ELL form with virtual forwarding trees**: every node keeps at most
   ``k`` out-slots; a node with more dependents fans out through a k-ary
   tree of virtual nodes (built statically, `build_ell`). This bounds the
   per-level row width at the cost of +log_k(degree) wave depth for hub
   cascades — latency for bandwidth, the right trade on a machine that
   hates gathers and loves dense rows.

2. **Frontier sizes vary wildly** (SURVEY.md §7 hard parts). Static shapes
   would force every level to pay the worst-case frontier. Instead the
   kernel compiles a ladder of frontier **buckets** (1k → … → F_max) and
   `lax.switch`es per level into the smallest bucket that fits — so a
   1k-node level costs a 1k-slot program, not a 10M-slot one.

Dedup inside a level picks its strategy per bucket at build time: small
buckets sort the fired destinations (touches only O(frontier·k) elements —
the lone-wave latency path), wide buckets use a claim-by-scatter-max trick
(first edge slot to claim a destination wins; one O(n_tot) fill costs less
than sorting a near-graph-sized frontier). No host round trips anywhere in
the wave.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "EllGraph",
    "EllWaveState",
    "advance_epoch",
    "build_ell",
    "build_ell_lat_wave",
    "build_ell_wave",
    "ell_live_epoch_init",
    "ell_live_union_chain_step",
    "ell_live_union_step",
    "invalid_mask",
    "widen_ell",
]


class EllGraph(NamedTuple):
    """Host-built ELL graph (device arrays created by the wave builder)."""

    ell_dst: np.ndarray  # int32[n_tot+1, k] — out-slot targets; pad = n_tot
    ell_epoch: np.ndarray  # int32[n_tot+1, k] — captured target epochs; pad -1
    is_real: np.ndarray  # bool[n_tot+1] — False for virtual forwarding nodes
    n_real: int
    n_tot: int
    k: int


def build_ell(
    src: np.ndarray, dst: np.ndarray, n_nodes: int, k: int = 4, use_native: bool = True
) -> EllGraph:
    """Rewrite an edge list into ELL(k) with virtual forwarding trees.

    Native counting-sort packer when available (~1 s at 10M nodes vs ~28 s
    for the numpy path below); virtual-id NUMBERING may differ between the
    two, reachability semantics are identical (tests cross-check both).

    Numpy path: layered construction, fully vectorized — in each round,
    nodes whose current out-list exceeds ``k`` get their list chunked into
    groups of ``k`` hung under fresh virtual nodes; the virtual ids become
    the node's new out-list. Rounds ≈ log_k(max_degree).
    """
    if use_native:
        from ..native import native_build_ell

        res = native_build_ell(src, dst, n_nodes, k)
        if res is not None:
            ell_dst, n_tot = res
            ell_epoch = np.where(ell_dst != n_tot, 0, -1).astype(np.int32)
            is_real = np.zeros(n_tot + 1, dtype=bool)
            is_real[:n_nodes] = True
            return EllGraph(ell_dst, ell_epoch, is_real, n_nodes, n_tot, k)

    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    next_virtual = n_nodes
    final_src: List[np.ndarray] = []
    final_dst: List[np.ndarray] = []

    cur_src, cur_dst = src, dst
    while len(cur_src):
        order = np.argsort(cur_src, kind="stable")
        s, d = cur_src[order], cur_dst[order]
        # rank of each edge within its source group
        uniq, starts, counts = np.unique(s, return_index=True, return_counts=True)
        rank = np.arange(len(s)) - np.repeat(starts, counts)
        deg = np.repeat(counts, counts)
        small = deg <= k
        final_src.append(s[small])
        final_dst.append(d[small])
        # big groups: chunk into virtual nodes of k
        bs, bd, brank = s[~small], d[~small], rank[~small]
        if len(bs) == 0:
            break
        # chunk index within the big group
        chunk = brank // k
        # assign one virtual id per (source, chunk)
        grp_key = np.stack([bs, chunk], axis=1)
        _, grp_first, grp_inv = np.unique(
            grp_key[:, 0] * (chunk.max() + 1) + grp_key[:, 1],
            return_index=True,
            return_inverse=True,
        )
        n_virtual = len(grp_first)
        virtual_ids = next_virtual + np.arange(n_virtual)
        next_virtual += n_virtual
        # edges virtual → original dst (these are ≤ k per virtual by chunking)
        final_src.append(virtual_ids[grp_inv])
        final_dst.append(bd)
        # next round: source → its virtual children (dedup (src, chunk))
        cur_src = bs[grp_first]
        cur_dst = virtual_ids

    n_tot = next_virtual
    ell_dst = np.full((n_tot + 1, k), n_tot, dtype=np.int32)
    ell_epoch = np.full((n_tot + 1, k), -1, dtype=np.int32)
    fs = np.concatenate(final_src) if final_src else np.empty(0, np.int64)
    fd = np.concatenate(final_dst) if final_dst else np.empty(0, np.int64)
    if len(fs):
        order = np.argsort(fs, kind="stable")
        fs, fd = fs[order], fd[order]
        uniq, starts, counts = np.unique(fs, return_index=True, return_counts=True)
        slot = np.arange(len(fs)) - np.repeat(starts, counts)
        assert slot.max() < k, "ELL transform failed to bound out-degree"
        ell_dst[fs, slot] = fd
        ell_epoch[fs, slot] = 0  # all targets start at epoch 0
    is_real = np.zeros(n_tot + 1, dtype=bool)
    is_real[:n_nodes] = True
    return EllGraph(ell_dst, ell_epoch, is_real, n_nodes, n_tot, k)


def widen_ell(graph: EllGraph, extra: int) -> EllGraph:
    """Append ``extra`` guaranteed-free pad columns to every row — slot
    headroom for in-place patching (a packed row would otherwise break the
    live mirror's patch log on the first new edge landing on it)."""
    if extra <= 0:
        return graph
    rows = graph.ell_dst.shape[0]
    return graph._replace(
        ell_dst=np.hstack(
            [graph.ell_dst, np.full((rows, extra), graph.n_tot, dtype=np.int32)]
        ),
        ell_epoch=np.hstack(
            [graph.ell_epoch, np.full((rows, extra), -1, dtype=np.int32)]
        ),
        k=graph.k + extra,
    )


class EllWaveState(NamedTuple):
    """Persistent wave state. ``invalid`` is epoch-stamped rather than a
    bool mask: node x is invalid iff ``inv_stamp[x] == epoch``. Marking the
    whole graph consistent again (the churn model between waves, or a bulk
    recompute) is then ``epoch + 1`` — O(1) instead of an O(n) device fill,
    which WAS the 10M lone-wave latency floor (PERF.md r1). ``frontier`` is
    the persistent scratch frontier buffer: levels only ever read slots
    below the live count (masked in-kernel), so it is never cleared — the
    other O(f_max) per-wave fill the r1 kernel paid."""

    node_epoch: "object"  # int32[n_tot+1]
    inv_stamp: "object"  # int32[n_tot+1] — last epoch each node was invalidated in
    epoch: "object"  # int32 scalar — current consistency epoch (≥ 1)
    frontier: "object"  # int32[f_max] scratch; slots ≥ live count are stale


def advance_epoch(state: EllWaveState) -> EllWaveState:
    """All nodes consistent again (bulk 'recompute') in O(1): stale stamps
    from earlier epochs can never equal the new epoch."""
    return state._replace(epoch=state.epoch + 1)


def invalid_mask(state: EllWaveState) -> np.ndarray:
    """bool[n_tot+1] — the materialized invalid set (readback helper)."""
    return np.asarray(state.inv_stamp) == int(state.epoch)


class EllGraphArrays(NamedTuple):
    """Device-resident ELL adjacency, passed to the kernel as runtime args
    (never jit-closure captures — a 10M-node table embedded as an HLO
    constant makes the compile payload hundreds of MB; see pull_wave.py)."""

    ell_dst: "object"  # int32[n_tot+1, k]
    ell_epoch: "object"  # int32[n_tot+1, k]
    is_real: "object"  # bool[n_tot+1]


def build_ell_wave(
    graph: EllGraph,
    f_max: Optional[int] = None,
    buckets: Optional[Sequence[int]] = None,
):
    """Compile the bucketed work-efficient wave for an ELL graph.

    Returns (initial_state, wave_fn) where
    ``wave_fn(seed_ids_padded, state) -> (state, real_invalidated_count)``;
    ``seed_ids_padded`` is int32[seed_cap] padded with -1. The whole wave —
    all levels, bucket switching, dedup — runs in one XLA program. The
    device adjacency is exposed as ``wave_fn.garrays`` / raw jitted kernel
    as ``wave_fn.step`` for callers composing a larger jitted program.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_tot, k = graph.n_tot, graph.k
    if f_max is None:
        # must bound the widest possible level (worst case: the whole graph)
        f_max = 1 << int(np.ceil(np.log2(max(n_tot, 1 << 14))))
    if buckets is None:
        buckets = []
        b = 1 << 10  # small head buckets keep shallow lone waves on the
        while b < f_max:  # sort-dedup path (µs-scale levels)
            buckets.append(b)
            b <<= 3
        buckets.append(f_max)
    buckets = [min(b, f_max) for b in buckets]

    garrays = EllGraphArrays(
        ell_dst=jnp.asarray(graph.ell_dst),
        ell_epoch=jnp.asarray(graph.ell_epoch),
        is_real=jnp.asarray(graph.is_real),
    )

    def init_state() -> EllWaveState:
        node_epoch = jnp.zeros(n_tot + 1, dtype=jnp.int32).at[n_tot].set(-2)
        inv_stamp = jnp.zeros(n_tot + 1, dtype=jnp.int32)
        # epoch starts at 1 so the zero-initialized stamps mean "consistent"
        return EllWaveState(
            node_epoch,
            inv_stamp,
            jnp.asarray(1, dtype=jnp.int32),
            jnp.full(f_max, n_tot, dtype=jnp.int32),  # the ONLY f_max fill, ever
        )

    def _sort_dedup(mask, ids):
        """(winners, isnew): sort ``ids`` (masked-out → null), keep the
        first of each run of equal ids. Touches only O(len(ids)) elements —
        the small-bucket / seed-stage dedup."""
        skeys = jnp.sort(jnp.where(mask, ids, n_tot).astype(jnp.int32))
        isnew = (skeys < n_tot) & jnp.concatenate(
            [jnp.ones(1, dtype=bool), skeys[1:] != skeys[:-1]]
        )
        return skeys, isnew

    NEVER = jnp.asarray(np.int32(-(2**31)), dtype=jnp.int32)  # stamp scatter filler

    def _level(bsize: int, F, nF, inv_stamp, epoch, node_epoch, ell_dst, ell_epoch, is_real):
        """Expand F[:bsize] one level; returns (F_next, nF_next, inv_stamp, newly_real).

        Dedup strategy is picked per bucket at build time:
        - small buckets SORT the fired dsts (O(m log² m), m = bsize*k) — no
          full-graph array is touched, so a shallow lone wave costs µs, not
          an O(n_tot) zero-fill per level;
        - wide buckets use the claim scatter (O(n_tot)) where the sort
          would cost more than the fill.
        F persists across levels AND waves: slots ≥ nF hold stale ids from
        earlier frontiers, and the slot mask below keeps them from firing —
        so F never needs an O(f_max) re-fill, whatever happens to the
        invalid set between waves (epoch bumps included).
        """
        Fb = lax.slice(F, (0,), (bsize,))
        slot_live = jnp.arange(bsize, dtype=jnp.int32) < nF
        rows = ell_dst[Fb]  # (bsize, k) row gather; pad rows → n_tot
        eps = ell_epoch[Fb]
        cur = node_epoch[rows]
        inv = inv_stamp[rows] == epoch
        fire = slot_live[:, None] & (cur == eps) & ~inv & (rows < n_tot)
        flat_dst = rows.reshape(-1)
        flat_fire = fire.reshape(-1)
        inv_stamp = inv_stamp.at[flat_dst].max(jnp.where(flat_fire, epoch, NEVER))
        m = bsize * k
        if m * max(int(np.log2(m)), 1) < n_tot:
            winners, isnew = _sort_dedup(flat_fire, flat_dst)
        else:
            # claim dedup: first firing slot per destination wins
            slot_id = jnp.arange(m, dtype=jnp.int32) + 1
            claim = (
                jnp.zeros(n_tot + 1, dtype=jnp.int32)
                .at[flat_dst]
                .max(jnp.where(flat_fire, slot_id, 0))
            )
            isnew = flat_fire & (claim[flat_dst] == slot_id)
            winners = flat_dst.astype(jnp.int32)
        pos = jnp.cumsum(isnew.astype(jnp.int32)) - 1
        nF_next = isnew.sum(dtype=jnp.int32)
        scatter_pos = jnp.where(isnew, pos, f_max + 1)  # OOB → dropped
        F_next = F.at[scatter_pos].set(winners, mode="drop")
        newly_real = (isnew & is_real[winners]).sum(dtype=jnp.int32)
        return F_next, nF_next, inv_stamp, newly_real

    branches = [
        functools.partial(_level, b) for b in buckets
    ]

    def level_switch(F, nF, inv_stamp, epoch, node_epoch, ell_dst, ell_epoch, is_real):
        # smallest bucket that fits nF
        bidx = jnp.searchsorted(jnp.asarray(buckets, dtype=jnp.int32), nF, side="left")
        bidx = jnp.minimum(bidx, len(buckets) - 1)
        return lax.switch(
            bidx, branches, F, nF, inv_stamp, epoch, node_epoch, ell_dst, ell_epoch, is_real
        )

    @jax.jit
    def step(g: EllGraphArrays, seed_ids: "jax.Array", state: EllWaveState):
        ell_dst, ell_epoch, is_real = g
        node_epoch, inv_stamp, epoch, F = state
        # seed frontier: pad -1 → n_tot slot; only fresh (not-invalid)
        # seeds, deduped by sorting the (small) seed vector — a claim
        # scatter here would cost an O(n_tot) zero-fill per wave, the
        # dominant term of a shallow lone wave's latency at 10M nodes
        safe = jnp.where(seed_ids >= 0, seed_ids, n_tot).astype(jnp.int32)
        candidate = (safe < n_tot) & (inv_stamp[safe] != epoch)
        skeys, fresh = _sort_dedup(candidate, safe)
        inv_stamp = inv_stamp.at[skeys].max(jnp.where(fresh, epoch, NEVER))
        count0 = (fresh & is_real[skeys]).sum(dtype=jnp.int32)
        pos = jnp.cumsum(fresh.astype(jnp.int32)) - 1
        F0 = F.at[jnp.where(fresh, pos, f_max + 1)].set(skeys, mode="drop")
        nF0 = fresh.sum(dtype=jnp.int32)

        def cond(carry):
            _F, nF, _inv, _cnt = carry
            return nF > 0

        def body(carry):
            F, nF, inv_stamp, cnt = carry
            F2, nF2, inv_stamp, newly = level_switch(
                F, nF, inv_stamp, epoch, node_epoch, ell_dst, ell_epoch, is_real
            )
            return F2, nF2, inv_stamp, cnt + newly

        F, _nF, inv_stamp, count = lax.while_loop(cond, body, (F0, nF0, inv_stamp, count0))
        return EllWaveState(node_epoch, inv_stamp, epoch, F), count

    def wave(seed_ids, state):
        return step(garrays, seed_ids, state)

    wave.garrays = garrays
    wave.step = step
    return init_state(), wave


def build_ell_lat_wave(
    graph: EllGraph,
    lcap: int = 1024,
    cap: int = 16384,
    assume_static_epochs: bool = False,
):
    """The LONE-WAVE latency kernel: a shallow edit's cascade in O(wave)
    device work with NO scatters inside the level loop.

    Measured on v5e (op_probe, r2): a scatter of even 256 lanes into a
    16M-element array costs ~31 µs and grows with lane count (~276 µs at
    4096), while sorts of ≤64K elements cost 12-55 µs and small gathers
    ~21 µs — so the general kernel's per-level scatter pair (stamp mark +
    frontier compaction) IS the 10M lone-wave latency floor (~1.2 ms per
    level). This kernel therefore:

    - keeps the level frontier COMPACT (int32[lcap] ids, not a mask);
    - dedups and tests membership by TAGGED MERGE-SORT against the sorted
      accumulated-wave id list (int32[cap]) — a sort replaces both the
      stamp scatter and the claim scatter;
    - compacts the next frontier by sorting candidate ids (new ids first,
      pads last) and slicing — a sort replaces the position scatter;
    - commits ``inv_stamp`` ONCE at wave end (a single scatter).

    Capacity overflow (wave wider than ``lcap`` per level or ``cap`` total)
    aborts WITHOUT touching state and reports ``overflow=True``; the caller
    re-runs the wave on the general bucketed kernel. Shares ``EllWaveState``
    with ``build_ell_wave`` (the persistent ``frontier`` scratch is unused
    here).

    ``assume_static_epochs=True`` additionally elides the per-level epoch
    gathers — valid ONLY for graphs whose topology never mutates after
    build (all captured edge epochs stay equal to their node epochs, e.g.
    the synthetic bench graphs); the builder verifies the precondition.

    Returns (initial_state, lat_wave) with
    ``lat_wave(seed_ids, state) -> (state, count, overflow)``; the raw
    jitted kernel is ``lat_wave.step``, device adjacency ``lat_wave.garrays``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_tot, k = graph.n_tot, graph.k
    if 2 * (n_tot + 1) >= 2**31:
        raise ValueError("tagged-sort keys need 2*(n_tot+1) < 2^31")
    if assume_static_epochs:
        live_slots = graph.ell_dst != n_tot
        if not (graph.ell_epoch[live_slots] == 0).all():
            raise ValueError(
                "assume_static_epochs requires all captured edge epochs == 0"
            )

    garrays = EllGraphArrays(
        ell_dst=jnp.asarray(graph.ell_dst),
        ell_epoch=jnp.asarray(graph.ell_epoch),
        is_real=jnp.asarray(graph.is_real),
    )
    NEVER = jnp.asarray(np.int32(-(2**31)), dtype=jnp.int32)

    def init_state() -> EllWaveState:
        node_epoch = jnp.zeros(n_tot + 1, dtype=jnp.int32).at[n_tot].set(-2)
        return EllWaveState(
            node_epoch,
            jnp.zeros(n_tot + 1, dtype=jnp.int32),
            jnp.asarray(1, dtype=jnp.int32),
            jnp.zeros(0, dtype=jnp.int32),  # frontier scratch unused
        )

    def _dedup_first(sorted_ids):
        prev = jnp.concatenate([jnp.full(1, -1, jnp.int32), sorted_ids[:-1]])
        return sorted_ids != prev

    @jax.jit
    def step(g: EllGraphArrays, seed_ids: "jax.Array", state: EllWaveState):
        ell_dst, ell_epoch, is_real = g
        node_epoch, inv_stamp, epoch, scratch = state

        # ---- seed stage: dedup by sort, no graph-sized work
        safe = jnp.where(seed_ids >= 0, seed_ids, n_tot).astype(jnp.int32)
        ok = (safe < n_tot) & (inv_stamp[safe] != epoch)
        skeys = jnp.sort(jnp.where(ok, safe, n_tot))
        fresh = _dedup_first(skeys) & (skeys < n_tot)
        nF0 = fresh.sum(dtype=jnp.int32)
        F0 = lax.dynamic_slice_in_dim(
            jnp.sort(jnp.where(fresh, skeys, n_tot)), 0, min(lcap, skeys.shape[0])
        )
        if F0.shape[0] < lcap:
            F0 = jnp.concatenate([F0, jnp.full(lcap - F0.shape[0], n_tot, jnp.int32)])
        acc0 = jnp.full(cap, n_tot, dtype=jnp.int32).at[: skeys.shape[0]].set(
            jnp.where(fresh, skeys, n_tot)
        )
        acc0 = jnp.sort(acc0)
        over0 = nF0 > lcap

        def cond(carry):
            _F, nF, _acc, _nacc, over = carry
            return (nF > 0) & ~over

        def body(carry):
            F, nF, acc, n_acc, over = carry
            slot_live = jnp.arange(lcap, dtype=jnp.int32) < nF
            rows = ell_dst[F]  # [lcap, k]
            stamp = inv_stamp[rows]
            live = (stamp != epoch) & (rows < n_tot)
            if not assume_static_epochs:
                # live-graph version matching; on an immutable-topology
                # graph every slot's captured epoch equals the node epoch,
                # so both gathers are elided (two fewer gathers per level —
                # the gathers are the level cost floor, see op_probe r2)
                eps = ell_epoch[F]
                cur = node_epoch[rows]
                live = live & (cur == eps)
            cand_ok = slot_live[:, None] & live
            cand = jnp.where(cand_ok, rows, n_tot).reshape(-1)
            # tagged merge: acc entries (even) sort before candidates (odd)
            keys = jnp.sort(jnp.concatenate([acc * 2, cand * 2 + 1]))
            ids = keys >> 1
            first = _dedup_first(ids) & (ids < n_tot)
            isnew = first & ((keys & 1) == 1)
            nF_next = isnew.sum(dtype=jnp.int32)
            F_next = jnp.sort(jnp.where(isnew, ids, n_tot))[:lcap]
            n_all = first.sum(dtype=jnp.int32)
            acc_next = jnp.sort(jnp.where(first, ids, n_tot))[:cap]
            over = over | (nF_next > lcap) | (n_all > cap)
            return F_next, nF_next, acc_next, n_all, over

        _F, _nF, acc, _nacc, over = lax.while_loop(
            cond, body, (F0, nF0, acc0, nF0, over0)
        )

        # ---- single commit: stamp the whole wave at once (masked on overflow)
        valid = (acc < n_tot) & ~over
        inv_stamp = inv_stamp.at[jnp.where(valid, acc, n_tot)].max(
            jnp.where(valid, epoch, NEVER), mode="drop"
        )
        count = jnp.where(over, 0, (valid & is_real[acc]).sum(dtype=jnp.int32))
        return EllWaveState(node_epoch, inv_stamp, epoch, scratch), count, over

    def lat_wave(seed_ids, state):
        return step(garrays, seed_ids, state)

    lat_wave.garrays = garrays
    lat_wave.step = step
    return init_state(), lat_wave


@functools.lru_cache(maxsize=8)
def ell_live_epoch_init(n_nodes: int, n_cap: int):
    """Jitted derivation of the lat mirror's per-slot captured epochs from
    the ALREADY-RESIDENT dense epoch array — the mirror's second big table
    costs one device op instead of a second multi-hundred-MB upload.
    Slot dst real → its current epoch; virtual/pad → 0 (virtual
    forwarding nodes never version)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def derive(ell_dst, node_epoch):
        real = ell_dst < n_nodes
        return jnp.where(real, node_epoch[jnp.clip(ell_dst, 0, n_cap)], 0)

    return derive


@functools.lru_cache(maxsize=8)
def ell_live_union_step(
    n_tot: int, n_nodes: int, n_cap: int, lcap: int, cap: int
):
    """The LIVE lone-wave kernel (VERDICT r4 #1): O(closure) union expansion
    over the lat mirror's out-ELL, gated by the LIVE dense state, in ONE
    dispatch — the bridge that routes ``cascade_rows_batch``'s small seed
    sets through the scatter-free small-wave machinery instead of a full
    topo-table sweep (718 ms p99 at 10 M in an early chip record; the reference's
    invalidation cost is ∝ dependents, Computed.cs:162-230).

    Mechanics = :func:`build_ell_lat_wave` (compact sorted frontier, tagged
    merge-sort dedup against the accumulated wave, one commit) with the
    static kernel's epoch-stamp state replaced by the live graph's own
    arrays, both resident:

    - liveness: slot (u→d) fires iff d is virtual (forwarding trees never
      version) or ``node_epoch[d] == ell_epoch[u,slot]`` — the captured-at-
      epoch rule, so a bumped dependent's old in-edges are dead without any
      mirror maintenance, and a patched re-capture carries its new epoch;
    - blocking: an already-invalid REAL node neither counts, re-fires, nor
      conducts (the dense-BFS union rule); seeds conduct even when already
      invalid but never count;
    - commit: newly-invalid real ids scatter straight into the dense
      ``g_invalid`` array (device-resident result state — the same array
      every other wave path updates) and come back compacted (≤ ``cap``).

    Frontier > ``lcap`` per level or wave > ``cap`` total aborts WITHOUT
    touching state (``overflow=True``); the caller re-runs on the topo
    sweep. Returns jitted ``step(ell_dst, ell_epoch, node_epoch, g_invalid,
    seed_ids) -> (g_invalid2, count, acc_ids, overflow)``; ``acc_ids`` is
    the sorted wave id list (real + virtual, pads ``n_tot``) — the host
    filters ``< n_nodes``."""
    import jax

    return jax.jit(_live_union_core(n_tot, n_nodes, n_cap, lcap, cap))


def _live_union_core(n_tot: int, n_nodes: int, n_cap: int, lcap: int, cap: int):
    """Traceable single-wave core shared by the lone-wave step and the
    chained variant: ``core(ell_dst, ell_epoch, node_epoch, g_invalid,
    seed_ids) -> (g_invalid2, count, acc, over)``."""
    import jax.numpy as jnp
    from jax import lax

    if 2 * (n_tot + 1) >= 2**31:
        raise ValueError("tagged-sort keys need 2*(n_tot+1) < 2^31")

    def _dedup_first(sorted_ids):
        prev = jnp.concatenate([jnp.full(1, -1, jnp.int32), sorted_ids[:-1]])
        return sorted_ids != prev

    def core(ell_dst, ell_epoch, node_epoch, g_invalid, seed_ids):
        oob = g_invalid.shape[0]

        # ---- seed stage: dedup by sort; pre-invalid seeds CONDUCT (enter
        # the frontier) but are never newly (never enter acc)
        safe = jnp.where(
            (seed_ids >= 0) & (seed_ids < n_tot), seed_ids, n_tot
        ).astype(jnp.int32)
        skeys = jnp.sort(safe)
        uniq = _dedup_first(skeys) & (skeys < n_tot)
        pre_inv = g_invalid[jnp.clip(skeys, 0, n_cap)]
        fresh = uniq & ~pre_inv
        nF0 = uniq.sum(dtype=jnp.int32)
        F0 = jnp.sort(jnp.where(uniq, skeys, n_tot))[: min(lcap, skeys.shape[0])]
        if F0.shape[0] < lcap:
            F0 = jnp.concatenate([F0, jnp.full(lcap - F0.shape[0], n_tot, jnp.int32)])
        m0 = min(cap, skeys.shape[0])
        acc0 = jnp.full(cap, n_tot, dtype=jnp.int32).at[:m0].set(
            jnp.sort(jnp.where(fresh, skeys, n_tot))[:m0]
        )
        over0 = (nF0 > lcap) | (fresh.sum(dtype=jnp.int32) > cap)

        def cond(carry):
            _F, nF, _acc, over = carry
            return (nF > 0) & ~over

        def body(carry):
            F, nF, acc, over = carry
            rows = ell_dst[F]  # [lcap, k]; pad F entries read the null row
            eps = ell_epoch[F]
            d = rows.reshape(-1)
            e = eps.reshape(-1)
            is_pad = d >= n_tot
            is_virtual = (d >= n_nodes) & ~is_pad
            dc = jnp.clip(d, 0, n_cap)
            epoch_ok = is_virtual | (node_epoch[dc] == e)
            unblocked = is_virtual | ~g_invalid[dc]
            cand = jnp.where(~is_pad & epoch_ok & unblocked, d, n_tot)
            # tagged merge: acc entries (even) sort before candidates (odd)
            keys = jnp.sort(jnp.concatenate([acc * 2, cand * 2 + 1]))
            ids = keys >> 1
            first = _dedup_first(ids) & (ids < n_tot)
            isnew = first & ((keys & 1) == 1)
            nF_next = isnew.sum(dtype=jnp.int32)
            F_next = jnp.sort(jnp.where(isnew, ids, n_tot))[:lcap]
            n_all = first.sum(dtype=jnp.int32)
            acc_next = jnp.sort(jnp.where(first, ids, n_tot))[:cap]
            over = over | (nF_next > lcap) | (n_all > cap)
            return F_next, nF_next, acc_next, over

        _F, _nF, acc, over = lax.while_loop(cond, body, (F0, nF0, acc0, over0))

        # ---- single commit into the LIVE dense invalid array (masked out
        # entirely on overflow — state untouched, caller re-runs elsewhere)
        newly = (acc < n_nodes) & ~over
        count = newly.sum(dtype=jnp.int32)
        g_invalid2 = g_invalid.at[jnp.where(newly, acc, oob)].set(True, mode="drop")
        acc_out = jnp.where(over, jnp.full_like(acc, n_tot), acc)
        return g_invalid2, count, acc_out, over

    return core


@functools.lru_cache(maxsize=8)
def ell_live_union_chain_step(
    n_tot: int, n_nodes: int, n_cap: int, lcap: int, cap: int, out_cap: int
):
    """M INDEPENDENT lone waves SEQUENCED in one program against the live
    state: wave ``i`` sees waves ``< i``'s commits (identical final state
    and per-wave counts to M separate :func:`ell_live_union_step` calls) —
    the burst-of-single-row-invalidations API, and the shape that lets the
    live bench measure per-wave latency by CHAIN DIFFERENCE (the
    per-dispatch cost cancels, as in the static kernel's
    methodology). A wave that overflows commits nothing and flags its slot
    (the caller re-runs it on the topo sweep); the union readback compacts
    the combined newly set to ``out_cap``.

    Returns jitted ``step(ell_dst, ell_epoch, node_epoch, g_invalid,
    seed_mat[M, S]) -> (g_invalid2, counts[M], overs[M], out_ids[out_cap],
    out_count, out_over)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    core = _live_union_core(n_tot, n_nodes, n_cap, lcap, cap)

    @jax.jit
    def step(ell_dst, ell_epoch, node_epoch, g_invalid, seed_mat):
        g_invalid0 = g_invalid

        def body(g_inv, seeds):
            g_inv2, count, _acc, over = core(
                ell_dst, ell_epoch, node_epoch, g_inv, seeds
            )
            return g_inv2, (count, over)

        g_invalid2, (counts, overs) = lax.scan(body, g_invalid, seed_mat)
        newly = g_invalid2 & ~g_invalid0
        out_count = newly.sum(dtype=jnp.int32)
        pos = jnp.cumsum(newly.astype(jnp.int32)) - 1
        scatter_pos = jnp.where(newly & (pos < out_cap), pos, out_cap)
        out_ids = (
            jnp.full(out_cap, -1, dtype=jnp.int32)
            .at[scatter_pos]
            .set(jnp.arange(newly.shape[0], dtype=jnp.int32), mode="drop")
        )
        return g_invalid2, counts, overs, out_ids, out_count, out_count > out_cap

    return step
