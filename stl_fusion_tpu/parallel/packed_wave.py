"""Bit-packed sharded waves — ``32*words`` independent waves per mesh pass.

The multi-chip counterpart of the single-chip pull kernel
(ops/pull_wave.py): node rows block-shard over the mesh's ``graph`` axis,
each row's ≤ k in-edges live beside it (in-ELL with virtual OR-collector
trees bounding fan-in, built by the native packer), and each BFS level is:

  1. ONE ``all_gather`` of the newly-lit frontier WORDS over ICI —
     32 waves ride each uint32 lane, so the per-wave exchange cost is
     1 bit/node/level;
  2. a local row gather + epoch-masked OR-fold (the pull pattern: a row
     pulls from its dependencies, so the scatter-OR that JAX lacks is
     never needed);
  3. ``psum`` of the newly-lit count for the loop-continuation flag.

``words`` packs W uint32 lanes per row — the same transaction-width lever
that took the single-chip topo sweep from 1B to 7.7B inv/s (PERF.md);
``run_wave_batches`` chains batches in one compiled program with a single
readback (per-batch host dispatch pays a host round trip each).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.pull_wave import pack_seed_words
from .mesh import GRAPH_AXIS, graph_mesh, shard_map_compat

__all__ = ["PackedShardedGraph", "build_packed_sharded_wave"]


@functools.lru_cache(maxsize=1)
def _patch_scatter_add():
    @jax.jit
    def f(arr, ids):
        return arr.at[ids].add(1, mode="drop")  # pads index OOB → dropped

    return f


@functools.lru_cache(maxsize=1)
def _fused_patch_apply():
    """ONE dispatch for a whole burst's patches (ISSUE 9 satellite —
    the mirror_patch bill was per-PATCH dispatch overhead, not per-edge
    cost): epoch bumps scatter-add (+1 per
    occurrence, so concatenated bump payloads keep their cumulative
    effect) and spliced rows pair-scatter, all OOB pads dropped."""

    @jax.jit
    def f(nep, in_src, eep, bump_ids, rows, rows_src, rows_ep):
        nep = nep.at[bump_ids].add(1, mode="drop")
        in_src = in_src.at[rows].set(rows_src, mode="drop")
        eep = eep.at[rows].set(rows_ep, mode="drop")
        return nep, in_src, eep

    return f


def build_packed_sharded_wave(mesh: Mesh):
    """Compile the packed sharded kernel for a mesh.

    Returns ``wave(seed_bits, in_src, edge_epoch, node_epoch, is_real,
    invalid) -> (invalid, counts)`` — row-sharded arrays (row count must
    divide evenly over the mesh); seeds/invalid are int32 words
    [rows, W] (32 packed waves per lane); ``counts`` is int32[W] per-word
    (one word's count is ≤ 32·rows, int32-safe — totals are summed in
    int64 host-side). k and W come from array shapes at trace time."""
    node_spec = P(GRAPH_AXIS)
    word_spec = P(GRAPH_AXIS, None)

    @shard_map_compat(
        mesh=mesh,
        in_specs=(word_spec, word_spec, word_spec, node_spec, node_spec, word_spec),
        out_specs=(word_spec, P()),
    )
    def _wave(seeds_l, in_src_l, eepoch_l, nepoch_l, is_real_l, inv_l):
        inv_in_l = inv_l  # counts report only bits newly lit by THIS call
        live = eepoch_l == nepoch_l[:, None]  # dead/pad slots never match
        frontier_l = seeds_l & ~inv_l
        inv_l = inv_l | frontier_l
        go0 = lax.psum((frontier_l != 0).any().astype(jnp.int32), GRAPH_AXIS) > 0

        def cond(carry):
            _f, _inv, go = carry
            return go

        def body(carry):
            f_l, inv_l, _go = carry
            # the ONE collective: newly-lit words, 32 waves per lane
            f_full = lax.all_gather(f_l, GRAPH_AXIS, tiled=True)
            f = f_full[in_src_l]  # (n_local, k, W); pad rows clamp, masked by live
            contrib = jnp.where(live[:, :, None], f, 0)
            fire = contrib[:, 0]
            for j in range(1, contrib.shape[1]):
                fire = fire | contrib[:, j]
            fire = fire & ~inv_l
            inv_l = inv_l | fire
            go = lax.psum((fire != 0).any().astype(jnp.int32), GRAPH_AXIS) > 0
            return fire, inv_l, go

        _f, inv_l, _go = lax.while_loop(cond, body, (frontier_l, inv_l, go0))
        counts = lax.psum(
            lax.population_count(
                jnp.where(is_real_l[:, None], inv_l & ~inv_in_l, 0)
            ).sum(axis=0, dtype=jnp.int32),
            GRAPH_AXIS,
        )
        return inv_l, counts

    @jax.jit
    def wave(seed_bits, in_src, edge_epoch, node_epoch, is_real, invalid):
        return _wave(seed_bits, in_src, edge_epoch, node_epoch, is_real, invalid)

    return wave


def _build_gated_lane_burst(mesh, cap: int, n_global: int, n_nodes: int, words: int):
    """Jitted LIVE lane burst on the mesh (the multi-chip analogue of
    ops/topo_wave.py::topo_mirror_burst_lanes_step): ``32*words``
    independent command groups cascade over the mesh in one pass, gated by
    a RESIDENT blocked mask (the live graph's invalid state) — blocked
    rows neither fire, count, nor conduct, expressed through the kernel's
    own epoch machinery (epoch -3 never matches a live edge's 0).

    Cached PER PackedShardedGraph instance (not a module lru_cache): the
    program's lifetime then matches the graph that owns the mesh, instead
    of pinning discarded meshes process-wide.
    Returns ``burst(seed_ids, in_src, edge_epoch, node_epoch0, is_real,
    blocked) -> (blocked2, lane_counts int32[32*words], union_count,
    compacted union ids, overflow)`` with the union folded back into the
    blocked mask (device-resident between bursts)."""
    wave = build_packed_sharded_wave(mesh)
    W = words
    L = 32 * W
    node_sh = NamedSharding(mesh, P(GRAPH_AXIS))
    word_sh = NamedSharding(mesh, P(GRAPH_AXIS, None))

    @jax.jit
    def burst(seed_ids, in_src, edge_epoch, node_epoch0, is_real, blocked):
        lanes = jnp.arange(L, dtype=jnp.int32)
        word_of = lanes // 32
        bit_of = jnp.left_shift(jnp.int32(1), lanes % 32)
        flat = seed_ids * W + word_of[:, None]  # pad id = n_global → dropped
        vals = jnp.broadcast_to(bit_of[:, None], seed_ids.shape)
        seeds = (
            jnp.zeros(n_global * W, jnp.int32)
            .at[flat.ravel()]
            .add(vals.ravel(), mode="drop")
            .reshape(n_global, W)
        )
        # seeds CONDUCT even when already blocked (r4, the union rule —
        # ops/wave.py::run_waves_union): a blocked row still can't RECEIVE
        # (epoch -3), and the newly mask below excludes pre-blocked rows
        # from counts, union, and writeback
        seeds = lax.with_sharding_constraint(seeds, word_sh)
        node_epoch = lax.with_sharding_constraint(
            jnp.where(blocked, -3, node_epoch0), node_sh
        )
        inv, _word_counts = wave(
            seeds, in_src, edge_epoch, node_epoch, is_real,
            lax.with_sharding_constraint(jnp.zeros_like(seeds), word_sh),
        )
        newly = jnp.where(is_real[:, None] & ~blocked[:, None], inv, 0)
        lane_counts = jnp.stack(
            [
                ((newly[:, w] >> b) & 1).sum(dtype=jnp.int32)
                for w in range(W)
                for b in range(32)
            ]
        )
        union = (newly != 0).any(axis=1) & (
            jnp.arange(n_global, dtype=jnp.int32) < n_nodes
        )
        union_count = union.sum(dtype=jnp.int32)
        pos = jnp.cumsum(union.astype(jnp.int32)) - 1
        scatter_pos = jnp.where(union & (pos < cap), pos, cap)
        ids = (
            jnp.full(cap, -1, dtype=jnp.int32)
            .at[scatter_pos]
            .set(jnp.arange(n_global, dtype=jnp.int32), mode="drop")
        )
        blocked2 = lax.with_sharding_constraint(blocked | union, node_sh)
        return blocked2, lane_counts, union_count, ids, union_count > cap

    return burst


class PackedShardedGraph:
    """Static mesh-sharded graph running ``32*words`` packed waves per pass."""

    def __init__(
        self,
        edges_src: np.ndarray,
        edges_dst: np.ndarray,
        n_nodes: int,
        mesh: Optional[Mesh] = None,
        k: int = 8,
        words: int = 1,
        slack: int = 0,
    ):
        # build_pull_graph = build_ell on reversed edges, which routes
        # through the native packer itself — one packer path to maintain
        from ..ops.ell_wave import widen_ell
        from ..ops.pull_wave import build_pull_graph

        self.mesh = mesh or graph_mesh()
        n_dev = self.mesh.devices.size

        ell = build_pull_graph(edges_src, edges_dst, n_nodes, k=k)
        if slack:
            # guaranteed-free in-slots per row: the LIVE mesh mirror
            # patches structural churn in place (VERDICT r4 #4), and a
            # packed row would break the patch on its first new in-edge
            ell = widen_ell(ell, slack)
        in_src, n_tot = ell.ell_dst, ell.n_tot
        self.n_nodes = n_nodes
        self.n_tot = n_tot
        self.k = ell.k
        self.words = words
        self.patches = 0  # in-place structural patches absorbed
        # pad rows to the mesh grid; pads are inert (epoch -1 slots)
        self.n_local = max(-(-(n_tot + 1) // n_dev), 1)
        self.n_global = self.n_local * n_dev
        if 32 * self.n_global >= 2**31:
            # per-word counts popcount-sum 32 lanes in int32 on device
            # before the psum (jax x64 off); beyond ~67M global rows one
            # word's count could silently wrap — same guard as
            # topo_init_state (ops/topo_wave.py)
            raise ValueError(
                f"packed sharded count tracking is int32-limited to "
                f"<{2**31 // 32} global rows; got {self.n_global} — "
                f"use ShardedDeviceGraph (one wave per pass) at this scale"
            )

        k = self.k
        rows = np.full((self.n_global, k), n_tot, dtype=np.int32)
        rows[: n_tot + 1] = in_src
        edge_epoch = np.full((self.n_global, k), -1, dtype=np.int32)
        edge_epoch[: n_tot + 1][in_src != n_tot] = 0
        node_epoch = np.zeros(self.n_global, dtype=np.int32)
        node_epoch[n_tot:] = -2  # null + pad rows never match any edge epoch
        is_real = np.zeros(self.n_global, dtype=bool)
        is_real[:n_nodes] = True

        sh = NamedSharding(self.mesh, P(GRAPH_AXIS))
        sh2 = NamedSharding(self.mesh, P(GRAPH_AXIS, None))
        self.in_src = jax.device_put(rows, sh2)
        self.edge_epoch = jax.device_put(edge_epoch, sh2)
        self.node_epoch = jax.device_put(node_epoch, sh)
        self.is_real = jax.device_put(is_real, sh)
        # host patch-truth copies (REAL copies — the device_put above may
        # alias the numpy buffers zero-copy on the CPU backend, and these
        # mutate in place during patching)
        self.h_in_src = rows.copy()
        self.h_edge_epoch = edge_epoch.copy()
        self.h_node_epoch = node_epoch.copy()
        self._word_sharding = sh2
        self._zero_words = jax.device_put(
            np.zeros((self.n_global, words), dtype=np.int32), sh2
        )
        self.invalid = self._zero_words
        self._wave = build_packed_sharded_wave(self.mesh)
        self._chain = None  # compiled lazily per batch shape
        self._gated_lanes: dict = {}  # (cap, words) → jitted gated burst

    # ------------------------------------------------------------------ patching
    def patch_bumps(self, node_ids: np.ndarray) -> None:
        """Recomputed nodes (RELATIVE epoch convention: the mesh mirror
        rebases epochs to 0 at build; the owner translates): +1 kills all
        live in-edges of those rows — the mesh pull kernel has NO level
        order, so a bump is just an epoch scatter, never a re-level."""
        ids = np.unique(np.asarray(node_ids, dtype=np.int64))
        if ids.size == 0:
            return
        self.h_node_epoch[ids] += 1
        width = max(256, 1 << int(len(ids) - 1).bit_length())
        padded = np.full(width, self.n_global, dtype=np.int64)  # OOB → drop
        padded[: len(ids)] = ids
        self.node_epoch = _patch_scatter_add()(
            self.node_epoch, jnp.asarray(padded)
        )
        self.patches += 1

    def patch_adds(
        self, u64: np.ndarray, v64: np.ndarray, ep_rel: np.ndarray
    ) -> bool:
        """Splice new in-edges (u → v at RELATIVE captured epoch) into free
        row slots, vectorized like the single-chip mirror's patcher. The
        mesh kernel iterates BFS to fixpoint, so there are no level
        violations — only slot overflow (returns False: caller rebuilds).
        """
        if u64.size == 0:
            return True
        hd, he = self.h_in_src, self.h_edge_epoch
        pad = self.n_tot
        dup = ((hd[v64] == u64[:, None]) & (he[v64] == ep_rel[:, None])).any(axis=1)
        u, v, e = u64[~dup], v64[~dup], ep_rel[~dup]
        if u.size == 0:
            return True
        order = np.lexsort((e, u, v))
        u, v, e = u[order], v[order], e[order]
        first = np.ones(len(u), dtype=bool)
        first[1:] = (v[1:] != v[:-1]) | (u[1:] != u[:-1]) | (e[1:] != e[:-1])
        u, v, e = u[first], v[first], e[first]
        idx = np.arange(len(v))
        grp_start = np.ones(len(v), dtype=bool)
        grp_start[1:] = v[1:] != v[:-1]
        rank = idx - np.maximum.accumulate(np.where(grp_start, idx, 0))
        free_cum = (hd[v] == pad).cumsum(axis=1)
        need = rank + 1
        if (free_cum[:, -1] < need).any():
            return False  # in-row overflow: cheaper to rebuild
        slot = (free_cum == need[:, None]).argmax(axis=1)
        hd[v, slot] = u
        he[v, slot] = e
        rows = np.unique(v)
        width = max(256, 1 << int(len(rows) - 1).bit_length())
        q = np.full(width, self.n_global - 1, dtype=np.int64)
        q[: len(rows)] = rows  # pad rows rewrite their own current contents
        from ..ops.bitops import fused_pair_scatter

        self.in_src, self.edge_epoch = fused_pair_scatter()(
            self.in_src, self.edge_epoch, jnp.asarray(q),
            jnp.asarray(hd[q]), jnp.asarray(he[q]),
        )
        self.patches += 1
        return True

    def patch_batch(
        self, bump_ids: np.ndarray, u64: np.ndarray, v64: np.ndarray, ep_rel: np.ndarray
    ) -> bool:
        """A whole burst's structural patches in ONE fused device dispatch
        (vs one per :meth:`patch_bumps`/:meth:`patch_adds` call — the
        ISSUE 9 amortization satellite). Safe to coalesce because the
        final state is order-independent: bumps are epoch INCREMENTS
        (``bump_ids`` may repeat — each occurrence adds 1) and adds carry
        their captured epochs; dup detection matches the sequential
        path's (within-batch dups collapse exactly like a later call
        seeing the earlier call's splice). Returns False on slot overflow
        or unknown nodes — caller rebuilds, same contract as patch_adds."""
        bump_ids = np.asarray(bump_ids, dtype=np.int64)
        u64 = np.asarray(u64, dtype=np.int64)
        v64 = np.asarray(v64, dtype=np.int64)
        ep_rel = np.asarray(ep_rel, dtype=np.int64)
        n = self.n_nodes
        if bump_ids.size and int(bump_ids.max()) >= self.n_global:
            return False
        if u64.size and (int(u64.max()) >= n or int(v64.max()) >= n):
            return False
        rows = np.empty(0, np.int64)
        hd, he = self.h_in_src, self.h_edge_epoch
        if u64.size:
            pad = self.n_tot
            dup = ((hd[v64] == u64[:, None]) & (he[v64] == ep_rel[:, None])).any(axis=1)
            u, v, e = u64[~dup], v64[~dup], ep_rel[~dup]
            if u.size:
                order = np.lexsort((e, u, v))
                u, v, e = u[order], v[order], e[order]
                first = np.ones(len(u), dtype=bool)
                first[1:] = (v[1:] != v[:-1]) | (u[1:] != u[:-1]) | (e[1:] != e[:-1])
                u, v, e = u[first], v[first], e[first]
                idx = np.arange(len(v))
                grp_start = np.ones(len(v), dtype=bool)
                grp_start[1:] = v[1:] != v[:-1]
                rank = idx - np.maximum.accumulate(np.where(grp_start, idx, 0))
                free_cum = (hd[v] == pad).cumsum(axis=1)
                need = rank + 1
                if (free_cum[:, -1] < need).any():
                    return False  # in-row overflow: cheaper to rebuild
                slot = (free_cum == need[:, None]).argmax(axis=1)
                hd[v, slot] = u
                he[v, slot] = e
                rows = np.unique(v)
        if bump_ids.size:
            uniq, counts = np.unique(bump_ids, return_counts=True)
            live = uniq < self.n_global
            np.add.at(self.h_node_epoch, uniq[live], counts[live].astype(np.int32))
        if not bump_ids.size and not rows.size:
            return True

        def _pad(a, fill):
            w = max(256, 1 << int(max(len(a), 1) - 1).bit_length())
            out = np.full(w, fill, dtype=np.int64)
            out[: len(a)] = a
            return out

        pb = _pad(bump_ids, self.n_global)  # OOB pad → dropped by scatter
        pr = _pad(rows, self.n_global)
        gather_rows = np.minimum(pr, self.n_global - 1)  # values for dropped
        self.node_epoch, self.in_src, self.edge_epoch = _fused_patch_apply()(
            self.node_epoch, self.in_src, self.edge_epoch,
            jnp.asarray(pb), jnp.asarray(pr),
            jnp.asarray(hd[gather_rows]), jnp.asarray(he[gather_rows]),
        )
        self.patches += 1
        return True

    # ------------------------------------------------------------------ waves
    def seeds_to_bits(self, seed_ids_per_wave: Sequence[Sequence[int]]) -> np.ndarray:
        bits = pack_seed_words(self.n_global, seed_ids_per_wave, words=self.words)
        return bits[:, None] if self.words == 1 else bits

    def prepare_seeds(self, seed_ids_per_wave: Sequence[Sequence[int]]):
        """Pack + upload seed words once, outside any timed region."""
        return jax.device_put(self.seeds_to_bits(seed_ids_per_wave), self._word_sharding)

    def run_waves(self, seeds) -> int:
        """Run ≤``32*words`` packed waves; ``seeds`` is a list of per-wave id
        lists or a device array from ``prepare_seeds``. Returns the real
        invalidations NEWLY lit by this call (bits already set in the
        persistent cumulative mask are not re-counted — same semantics as
        ``ShardedDeviceGraph.run_wave``; int64-summed over lanes)."""
        if isinstance(seeds, (list, tuple)):
            seeds = self.prepare_seeds(seeds)
        self.invalid, counts = self._wave(
            seeds, self.in_src, self.edge_epoch, self.node_epoch, self.is_real, self.invalid
        )
        return int(np.asarray(counts, dtype=np.int64).sum())

    def prepare_seed_batches(self, seed_batches: np.ndarray):
        """Upload stacked seed batches [B, n_global, W] sharded — call once,
        outside any timed region."""
        return jax.device_put(
            seed_batches, NamedSharding(self.mesh, P(None, GRAPH_AXIS, None))
        )

    def run_wave_batches(self, seed_batches) -> Tuple[int, np.ndarray]:
        """Chain B batches (each ``32*words`` waves, invalid reset between —
        the bench churn model) in ONE compiled program with a single
        readback. ``seed_batches``: [B, n_global, W] numpy (uploaded per
        call) or a device array from ``prepare_seed_batches``. Returns
        (total, per-batch counts int64[B])."""
        if isinstance(seed_batches, np.ndarray):
            seed_batches = self.prepare_seed_batches(seed_batches)
        if self._chain is None:
            wave = self._wave

            @jax.jit
            def chain(seed_batches, in_src, edge_epoch, node_epoch, is_real, invalid):
                def body(inv, seeds):
                    inv = jnp.zeros_like(inv)
                    inv, counts = wave(seeds, in_src, edge_epoch, node_epoch, is_real, inv)
                    return inv, counts

                inv, counts = lax.scan(body, invalid, seed_batches)
                return inv, counts

            self._chain = chain
        self.invalid, counts = self._chain(
            seed_batches, self.in_src, self.edge_epoch, self.node_epoch,
            self.is_real, self.invalid,
        )
        counts = np.asarray(counts, dtype=np.int64)
        return int(counts.sum()), counts.sum(axis=1)

    def run_gated_lanes(
        self,
        seed_id_lists: Sequence[Sequence[int]],
        blocked,
        cap: int = 65536,
        max_words: int = 16,
    ):
        """INDEPENDENT per-group cascades over the mesh, gated by a
        device-resident ``blocked`` mask (bool[n_global] — the live graph's
        invalid state): the multi-chip face of
        ``DeviceGraph.run_waves_lanes``. Chunks of ≤``32*max_words`` groups
        per dispatch (later chunks see earlier chunks' union as blocked).
        Returns (per-group counts int64[B], union newly ids or None on
        overflow, updated blocked mask, overflow flag).

        Chunk dispatches are SOFTWARE-PIPELINED (ISSUE 7: the mesh burst's
        share of the nonblocking work): chunk ``c+1`` is enqueued — chained
        device-side through the carried blocked mask — before chunk ``c``'s
        results are read back, so the host-side unpack of one chunk
        overlaps the next chunk's collective execution."""
        from ..ops.pull_wave import pack_lane_matrix

        B = len(seed_id_lists)
        counts = np.zeros(B, dtype=np.int64)
        union_parts: list = []
        any_overflow = False
        chunk_size = 32 * max_words
        pending = None  # (device handles, chunk slice) awaiting readback

        def harvest(p) -> None:
            nonlocal any_overflow
            handles, c0_h, n_h = p
            lane_counts, count, ids, overflow = jax.device_get(handles)
            counts[c0_h : c0_h + n_h] = lane_counts[:n_h].astype(np.int64)
            if overflow:
                any_overflow = True
            else:
                union_parts.append(ids[: int(count)])

        for c0 in range(0, B, chunk_size):
            chunk = seed_id_lists[c0 : c0 + chunk_size]
            mat, words = pack_lane_matrix(
                chunk, pad_id=self.n_global, n_valid=self.n_nodes, base_index=c0
            )
            burst = self._gated_lanes.get((cap, words))
            if burst is None:
                burst = _build_gated_lane_burst(
                    self.mesh, cap, self.n_global, self.n_nodes, words
                )
                self._gated_lanes[(cap, words)] = burst
            blocked, lane_counts, count, ids, overflow = burst(
                jnp.asarray(mat), self.in_src, self.edge_epoch, self.node_epoch,
                self.is_real, blocked,
            )
            if pending is not None:
                harvest(pending)
            pending = ((lane_counts, count, ids, overflow), c0, len(chunk))
        if pending is not None:
            harvest(pending)
        union_ids = (
            None
            if any_overflow
            else (
                np.concatenate(union_parts)
                if union_parts
                else np.empty(0, np.int32)
            )
        )
        return counts, union_ids, blocked, any_overflow

    def put_blocked(self, mask: Optional[np.ndarray] = None):
        """The gated-lane blocked mask in ITS layout (bool[n_global],
        GRAPH_AXIS-sharded) from a host mask over [0, n_nodes) — one place
        owns the layout contract (mirror sync + initial state)."""
        padded = np.zeros(self.n_global, dtype=bool)
        if mask is not None:
            padded[: len(mask)] = np.asarray(mask[: self.n_global], dtype=bool)
        return jax.device_put(padded, NamedSharding(self.mesh, P(GRAPH_AXIS)))

    def clear_invalid(self) -> None:
        # a cached device-zero array: no per-clear H2D transfer
        self.invalid = self._zero_words

    def invalid_mask(self, wave: int = 0) -> np.ndarray:
        """bool[n_nodes] for one packed wave lane."""
        w, lane = divmod(wave, 32)
        col = np.asarray(self.invalid[: self.n_nodes, w]).astype(np.int64)
        return (col & (np.int64(1) << lane)) != 0
