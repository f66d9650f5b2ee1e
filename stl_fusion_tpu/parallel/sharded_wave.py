"""Sharded invalidation waves — the multi-chip execution of the hot path.

This is the TPU-native replacement for the reference's cross-host
invalidation fan-out (per-peer WebSocket pub/sub + DB op-log readers,
SURVEY.md §3.5, §5.8), re-designed per the BASELINE north star: the
dependency graph's nodes AND edges are sharded over a device mesh, and each
BFS level exchanges the invalidation frontier with ONE ``all_gather`` over
ICI instead of N point-to-point messages.

Sharding layout (1-D mesh, axis ``graph``):
- nodes: block-sharded — device d owns ids [d*n_local, (d+1)*n_local);
  ``node_epoch`` / ``invalid`` live sharded, never replicated;
- edges: sharded by DESTINATION owner, so the version-match gather
  (``node_epoch[dst]``) and the invalidation scatter are device-local;
  only the frontier read (``frontier[src]``) needs remote data — hence the
  all-gather;
- per level: local fire-mask → local scatter → ``psum`` of the newly-lit
  count decides continuation (the while_loop carries the flag so no
  collective runs in ``cond``).

Out-of-range padding uses JAX's gather-clamps/scatter-drops semantics:
padded edges point at ``dst = n_local`` (dropped on scatter) with epoch -1
(never matches on gather).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import GRAPH_AXIS, graph_mesh, shard_map_compat

__all__ = ["ShardedGraphArrays", "ShardedDeviceGraph", "build_sharded_wave"]


class ShardedGraphArrays(NamedTuple):
    edge_src: jax.Array  # int32[n_dev * e_shard] — GLOBAL source ids
    edge_dst_local: jax.Array  # int32[n_dev * e_shard] — LOCAL dest ids (pad = n_local)
    edge_dst_epoch: jax.Array  # int32[n_dev * e_shard] — pad = -1
    node_epoch: jax.Array  # int32[n_global] — sharded by node block
    invalid: jax.Array  # bool[n_global] — sharded by node block


def build_sharded_wave(mesh: Mesh, n_global: int, exchange: str = "packed"):
    """Compile the sharded wave for a mesh + node capacity.

    Returns a ``(wave, wave_chain)`` pair:
    - ``wave(seed_frontier, g) -> (g, newly_invalidated_count)`` — one wave;
    - ``wave_chain(seed_mat, g, reset_between) -> (g, total, counts)`` —
      ``seed_mat.shape[0]`` waves in one compiled program (single readback).

    ``exchange`` selects the per-level frontier collective:
    - ``"packed"`` (default): the local frontier bit-packs into uint32 words
      before the all-gather — 8x fewer bytes over ICI than gathering the
      bool lane (XLA bools travel as one byte each); sources then test
      ``word >> (id & 31)`` instead of gathering bools.
    - ``"bool"``: the plain boolean all-gather (reference for equivalence
      tests and as a fallback).
    """
    n_dev = mesh.devices.size
    n_local = n_global // n_dev
    assert n_global % n_dev == 0, "node capacity must divide evenly over the mesh"
    if exchange not in ("packed", "bool"):
        raise ValueError(f"unknown exchange {exchange!r}")
    if exchange == "packed":
        assert n_local % 32 == 0, "packed exchange needs n_local % 32 == 0"

    node_spec = P(GRAPH_AXIS)
    edge_spec = P(GRAPH_AXIS)

    def _pack_words(f_l):
        lanes = jnp.arange(32, dtype=jnp.uint32)[None, :]
        return jnp.sum(
            f_l.reshape(-1, 32).astype(jnp.uint32) << lanes, axis=1, dtype=jnp.uint32
        )

    def _gather_src_active(f_l, esrc_l):
        """frontier exchange + per-edge source-activity test (ONE collective)."""
        if exchange == "bool":
            f_full = lax.all_gather(f_l, GRAPH_AXIS, tiled=True)
            return f_full[esrc_l]
        f_full_w = lax.all_gather(_pack_words(f_l), GRAPH_AXIS, tiled=True)
        word = f_full_w[esrc_l >> 5]
        return ((word >> (esrc_l & 31).astype(jnp.uint32)) & 1).astype(bool)

    @shard_map_compat(
        mesh=mesh,
        in_specs=(node_spec, edge_spec, edge_spec, edge_spec, node_spec, node_spec),
        out_specs=(node_spec, node_spec, P()),
    )
    def _wave(seeds_l, esrc_l, edst_l, eepoch_l, nepoch_l, inv_l):
        # seeds CONDUCT even when already invalid (r4, same rule as the
        # single-chip union — ops/wave.py::run_waves_union: an uncascaded
        # columnar mark's declared dependents live only in the graph);
        # pre-invalid seeds don't count, invalid NON-seeds still block
        fresh = seeds_l & ~inv_l
        inv_l = inv_l | seeds_l
        count0 = lax.psum(fresh.sum(dtype=jnp.int32), GRAPH_AXIS)
        go0 = lax.psum(seeds_l.any().astype(jnp.int32), GRAPH_AXIS) > 0

        def cond(carry):
            _f, _inv, _count, go = carry
            return go

        def body(carry):
            f_l, inv_l, count, _go = carry
            src_active = _gather_src_active(f_l, esrc_l)
            ver_ok = nepoch_l[edst_l] == eepoch_l  # gather clamps; -1 never matches
            fire = src_active & ver_ok & ~inv_l[edst_l]
            nxt_l = jnp.zeros_like(f_l).at[edst_l].max(fire)  # OOB pads dropped
            inv_l = inv_l | nxt_l
            newly = lax.psum(nxt_l.sum(dtype=jnp.int32), GRAPH_AXIS)
            return nxt_l, inv_l, count + newly, newly > 0

        _f, inv_l, count, _go = lax.while_loop(cond, body, (seeds_l, inv_l, count0, go0))
        return inv_l, nepoch_l, count

    @jax.jit
    def wave(seed_frontier: jax.Array, g: ShardedGraphArrays):
        invalid, node_epoch, count = _wave(
            seed_frontier, g.edge_src, g.edge_dst_local, g.edge_dst_epoch, g.node_epoch, g.invalid
        )
        return g._replace(invalid=invalid, node_epoch=node_epoch), count

    @functools.partial(jax.jit, static_argnums=2)
    def wave_chain(seed_mat: jax.Array, g: ShardedGraphArrays, reset_between: bool):
        """W waves in ONE compiled program with a single readback — the
        multi-chip analogue of the single-chip bench's lax.scan batching
        (per-wave host dispatch pays a host round trip each; the
        chain pays it once). ``reset_between`` clears ``invalid`` before
        each wave (the bench's churn model: the graph is re-consistent
        between waves)."""

        def body(carry, seeds):
            g, total = carry
            if reset_between:
                g = g._replace(invalid=jnp.zeros_like(g.invalid))
            g, count = wave(seeds, g)
            return (g, total + count), count

        (g, total), counts = lax.scan(body, (g, jnp.int32(0)), seed_mat)
        return g, total, counts

    return wave, wave_chain


class ShardedDeviceGraph:
    """Static sharded graph for multi-chip waves (bench + dry-run scale
    path; the incremental host mirror is DeviceGraph on one chip)."""

    def __init__(
        self,
        edges_src: np.ndarray,
        edges_dst: np.ndarray,
        n_nodes: int,
        mesh: Optional[Mesh] = None,
        edge_dst_epoch: Optional[np.ndarray] = None,
        exchange: str = "packed",
        node_epoch: Optional[np.ndarray] = None,
        invalid: Optional[np.ndarray] = None,
    ):
        self.mesh = mesh or graph_mesh()
        n_dev = self.mesh.devices.size
        # n_local rounds up to a multiple of 32 so the packed exchange's
        # uint32 words tile evenly per device (floor 32: an empty graph
        # still needs one valid row block per device to compile)
        self.n_local = max(((n_nodes + n_dev - 1) // n_dev + 31) // 32 * 32, 32)
        self.n_global = self.n_local * n_dev
        self.n_nodes = n_nodes
        self.n_dev = n_dev
        self.exchange = exchange

        src = np.asarray(edges_src, dtype=np.int32)
        dst = np.asarray(edges_dst, dtype=np.int32)
        epoch = (
            np.zeros_like(dst)
            if edge_dst_epoch is None
            else np.asarray(edge_dst_epoch, dtype=np.int32)
        )
        # partition edges by destination owner; pad shards to equal length
        owner = dst // self.n_local
        order = np.argsort(owner, kind="stable")
        src, dst, epoch, owner = src[order], dst[order], epoch[order], owner[order]
        counts = np.bincount(owner, minlength=n_dev)
        e_shard = max(int(counts.max()), 1)
        E = n_dev * e_shard
        esrc = np.zeros(E, dtype=np.int32)
        edst_local = np.full(E, self.n_local, dtype=np.int32)  # pad: OOB → dropped
        eepoch = np.full(E, -1, dtype=np.int32)  # pad: never version-matches
        start = 0
        for d in range(n_dev):
            k = counts[d]
            if k:
                sl = slice(d * e_shard, d * e_shard + k)
                esrc[sl] = src[start : start + k]
                edst_local[sl] = dst[start : start + k] - d * self.n_local
                eepoch[sl] = epoch[start : start + k]
                start += k
        self.e_shard = e_shard

        node_sh = NamedSharding(self.mesh, P(GRAPH_AXIS))
        edge_sh = NamedSharding(self.mesh, P(GRAPH_AXIS))
        # optional state import (live-graph snapshots): pad rows beyond
        # n_nodes keep epoch 0 / not-invalid — they have no edges to fire
        nep = np.zeros(self.n_global, dtype=np.int32)
        inv = np.zeros(self.n_global, dtype=bool)
        if node_epoch is not None:
            nep[:n_nodes] = np.asarray(node_epoch[:n_nodes], dtype=np.int32)
        if invalid is not None:
            inv[:n_nodes] = np.asarray(invalid[:n_nodes], dtype=bool)
        self.g = ShardedGraphArrays(
            edge_src=jax.device_put(esrc, edge_sh),
            edge_dst_local=jax.device_put(edst_local, edge_sh),
            edge_dst_epoch=jax.device_put(eepoch, edge_sh),
            node_epoch=jax.device_put(nep, node_sh),
            invalid=jax.device_put(inv, node_sh),
        )
        self._node_sharding = node_sh
        self._wave, self._wave_chain = build_sharded_wave(
            self.mesh, self.n_global, exchange=exchange
        )
        self._collect_cache: dict = {}  # (cap, seed_width) → jitted program

    # ------------------------------------------------------------------ waves
    def seeds_to_frontier(self, seed_ids: Sequence[int]) -> jax.Array:
        frontier = np.zeros(self.n_global, dtype=bool)
        frontier[np.asarray(seed_ids, dtype=np.int64)] = True
        return jax.device_put(frontier, self._node_sharding)

    def run_wave(self, seed_ids: Sequence[int]) -> int:
        self.g, count = self._wave(self.seeds_to_frontier(seed_ids), self.g)
        return int(count)

    def run_wave_collect(
        self, seed_ids: Sequence[int], cap: int = 65536
    ) -> Tuple[int, np.ndarray, bool]:
        """Union wave from ``seed_ids`` with an O(wave) host exchange
        (VERDICT r2 #2): seed IDS travel up (never an O(n) frontier mask),
        the newly-invalidated GLOBAL ids come back compacted into a
        ``cap``-sized buffer, all in one dispatch. Returns (count, newly
        ids, overflow) — on overflow (count > cap) the id buffer is
        partial and the caller falls back to a mask diff."""
        k = len(seed_ids)
        width = 1
        while width < max(k, 1):
            width <<= 1
        # pad = n_global: dropped as OOB by the scatter (-1 would WRAP to
        # the last row and invalidate a padding slot)
        ids = np.full(width, self.n_global, dtype=np.int32)
        ids[:k] = np.asarray(seed_ids, dtype=np.int32)
        key = (cap, width)
        fn = self._collect_cache.get(key)
        if fn is None:
            fn = self._build_collect(cap)
            self._collect_cache[key] = fn
        self.g, count, out_ids, overflow = fn(jnp.asarray(ids), self.g)
        count, out_ids, overflow = jax.device_get((count, out_ids, overflow))
        count = int(count)
        return count, out_ids[:count] if count <= cap else out_ids, bool(overflow)

    def _build_collect(self, cap: int):
        node_sh = self._node_sharding
        n_global = self.n_global
        n_nodes = self.n_nodes
        wave = self._wave

        @jax.jit
        def collect(seed_ids: jax.Array, g: ShardedGraphArrays):
            frontier = lax.with_sharding_constraint(
                jnp.zeros(n_global, bool).at[seed_ids].set(True, mode="drop"),
                node_sh,
            )
            inv_before = g.invalid
            g2, _count = wave(frontier, g)
            # only REAL rows count/compact — padding rows [n_nodes, n_global)
            # exist for the mesh tiling, never for the caller
            newly = (
                g2.invalid
                & ~inv_before
                & (jnp.arange(n_global, dtype=jnp.int32) < n_nodes)
            )
            count = newly.sum(dtype=jnp.int32)
            # global compaction over the sharded mask: XLA lowers the
            # cumsum/scatter to mesh collectives; host traffic stays O(cap)
            pos = jnp.cumsum(newly.astype(jnp.int32)) - 1
            scatter_pos = jnp.where(newly & (pos < cap), pos, cap)
            out = (
                jnp.full(cap, -1, dtype=jnp.int32)
                .at[scatter_pos]
                .set(jnp.arange(n_global, dtype=jnp.int32), mode="drop")
            )
            return g2, count, out, count > cap

        return collect

    def prepare_seed_mat(self, seed_mat: np.ndarray) -> jax.Array:
        """Pad a bool[W, n_nodes] seed matrix to the mesh capacity and
        upload it sharded — call once, outside any timed region."""
        W, n = seed_mat.shape
        if n < self.n_global:
            seed_mat = np.pad(seed_mat, ((0, 0), (0, self.n_global - n)))
        sharding = NamedSharding(self.mesh, P(None, GRAPH_AXIS))
        return jax.device_put(np.asarray(seed_mat, dtype=bool), sharding)

    def run_waves_chained(
        self, seed_mat, reset_between: bool = True
    ) -> Tuple[int, np.ndarray]:
        """Run ``seed_mat.shape[0]`` waves in one compiled program; returns
        (total, per-wave counts). ``seed_mat`` is bool[W, n_nodes-or-global]
        (numpy, uploaded per call) or a device array from
        ``prepare_seed_mat`` (no transfer cost)."""
        if isinstance(seed_mat, np.ndarray):
            seed_mat = self.prepare_seed_mat(seed_mat)
        self.g, total, counts = self._wave_chain(seed_mat, self.g, reset_between)
        return int(total), np.asarray(counts)

    # ------------------------------------------------------------------ readback
    def invalid_mask(self) -> np.ndarray:
        return np.asarray(self.g.invalid)[: self.n_nodes]

    def set_invalid(self, mask: np.ndarray) -> None:
        """Replace the sharded invalid state from a host mask[n_nodes-or-
        global] (the live-mirror sync path: the single-chip dense state is
        authoritative between mesh bursts)."""
        inv = np.zeros(self.n_global, dtype=bool)
        inv[: len(mask)] = np.asarray(mask[: self.n_global], dtype=bool)
        self.g = self.g._replace(
            invalid=jax.device_put(inv, self._node_sharding)
        )

    def clear_invalid(self) -> None:
        self.g = self.g._replace(
            invalid=jax.device_put(np.zeros(self.n_global, dtype=bool), self._node_sharding)
        )
