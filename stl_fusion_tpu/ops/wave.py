"""The invalidation wave — batched sparse-BFS frontier expansion, jitted.

This is the TPU-native replacement for the reference's invalidation hot path:
``Computed.Invalidate()``'s synchronous, lock-per-node, pointer-chasing DFS
over ``_usedBy`` edge sets (src/Stl.Fusion/Computed.cs:162-230, cascade at
210-217). Instead of chasing pointers, the dependency graph lives in HBM as
an edge-parallel CSR-style structure and a whole *batch* of seed
invalidations expands level-by-level:

    frontier_{k+1}[d] = OR over edges (s→d): frontier_k[s]
                        AND node_epoch[d] == edge_dst_epoch   (version match)
                        AND NOT invalid[d]

Version-consistent edges: the reference stores ``(input, version)`` in
_usedBy and only fires on version match (Computed.cs:213-215). On device the
version is an int32 per-node *epoch* bumped on every recompute; an edge
carries the dependent's epoch at capture time, so stale edges (left by the
pruner-tolerant design) never re-invalidate a recomputed node.

Shapes are static (padded capacities) so XLA compiles one program: gathers +
scatter-max per level inside ``lax.while_loop``. Every op maps onto TPU VPU
lanes + HBM streaming; no host round-trips inside a wave.

Layout (all int32, device-resident):
- ``edge_src[e]``   — the used node (invalidation source); padding = n_cap
- ``edge_dst[e]``   — the dependent; padding = n_cap (a dummy slot)
- ``edge_dst_epoch[e]`` — dependent's epoch at edge-capture; padding = -1
- ``node_epoch[i]`` — current epoch per node; the dummy slot holds -2
- ``invalid[i]``    — invalidated flag (bool)

The arrays are sized (n_cap+1,) so the dummy slot absorbs padded-edge
gathers/scatters without branches.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "GraphArrays",
    "wave_step",
    "run_wave",
    "run_wave_collect",
    "run_wave_with_stats",
    "run_waves_chained",
    "run_waves_union",
    "seeds_to_frontier",
]


class GraphArrays(NamedTuple):
    """Device-resident dependency-graph mirror (see module docstring)."""

    edge_src: jax.Array  # int32[e_cap]
    edge_dst: jax.Array  # int32[e_cap]
    edge_dst_epoch: jax.Array  # int32[e_cap]
    node_epoch: jax.Array  # int32[n_cap+1]
    invalid: jax.Array  # bool[n_cap+1]

    @property
    def n_cap(self) -> int:
        return self.node_epoch.shape[0] - 1

    @property
    def e_cap(self) -> int:
        return self.edge_src.shape[0]


def seeds_to_frontier(n_cap: int, seed_ids: jax.Array) -> jax.Array:
    """Seed id list (padded with -1) → boolean frontier of size n_cap+1."""
    frontier = jnp.zeros(n_cap + 1, dtype=jnp.bool_)
    safe = jnp.where(seed_ids >= 0, seed_ids, n_cap)
    return frontier.at[safe].set(True).at[n_cap].set(False)


def wave_step(
    frontier: jax.Array, g: GraphArrays
) -> Tuple[jax.Array, GraphArrays]:
    """One BFS level: expand ``frontier`` across all version-matched edges."""
    src_active = frontier[g.edge_src]  # gather
    dst_epoch_now = g.node_epoch[g.edge_dst]  # gather
    fire = src_active & (dst_epoch_now == g.edge_dst_epoch) & ~g.invalid[g.edge_dst]
    next_frontier = (
        jnp.zeros_like(frontier).at[g.edge_dst].max(fire).at[g.n_cap].set(False)
    )
    invalid = g.invalid | next_frontier
    return next_frontier, g._replace(invalid=invalid)


@functools.partial(jax.jit, donate_argnums=(1,))
def run_wave(seed_frontier: jax.Array, g: GraphArrays) -> Tuple[GraphArrays, jax.Array]:
    """Full cascading-invalidation wave from a seed frontier.

    Returns (updated graph, newly-invalidated count). The while_loop runs
    entirely on device; levels continue until the frontier empties.
    """
    # seeds invalidate unconditionally (they're the nodes invalidate() was
    # called on), but already-invalid seeds don't re-expand
    fresh_seeds = seed_frontier & ~g.invalid
    g = g._replace(invalid=g.invalid | fresh_seeds)
    return _expand_to_fixpoint(fresh_seeds, g)


def _expand_to_fixpoint(fresh_seeds: jax.Array, g: GraphArrays):
    """Shared wave loop: expand fresh (already-marked) seeds until empty.
    Returns (g, newly-invalidated count incl. the seeds)."""

    def cond(carry):
        frontier, _g, _count = carry
        return frontier.any()

    def body(carry):
        frontier, g, count = carry
        nxt, g = wave_step(frontier, g)
        return nxt, g, count + nxt.sum(dtype=jnp.int32)

    _f, g, count = lax.while_loop(
        cond, body, (fresh_seeds, g, fresh_seeds.sum(dtype=jnp.int32))
    )
    return g, count


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(1,))
def run_wave_collect(
    seed_frontier: jax.Array, g: GraphArrays, cap: int
) -> Tuple[GraphArrays, jax.Array, jax.Array, jax.Array]:
    """run_wave that also COMPACTS the newly-invalidated node ids on device.

    Returns (g, count, ids: int32[cap] padded with -1, overflow: bool).
    The live path (graph/backend.py) reads back only ``count`` and the id
    buffer — O(wave size), not O(graph size) — instead of diffing two full
    invalid-mask snapshots on host (the r1 design VERDICT.md weak #2).
    When ``count > cap`` the buffer holds the first ``cap`` ids by node id
    and ``overflow`` is set; the caller falls back to a mask readback.
    """
    inv_before = g.invalid
    fresh_seeds = seed_frontier & ~g.invalid
    g = g._replace(invalid=g.invalid | fresh_seeds)
    g, count = _expand_to_fixpoint(fresh_seeds, g)
    newly = g.invalid & ~inv_before
    pos = jnp.cumsum(newly.astype(jnp.int32)) - 1
    scatter_pos = jnp.where(newly & (pos < cap), pos, cap)  # OOB → dropped
    ids = (
        jnp.full(cap, -1, dtype=jnp.int32)
        .at[scatter_pos]
        .set(jnp.arange(newly.shape[0], dtype=jnp.int32), mode="drop")
    )
    return g, count, ids, count > cap


@functools.partial(jax.jit, donate_argnums=(1,))
def run_waves_chained(
    seed_ids_mat: jax.Array, g: GraphArrays
) -> Tuple[GraphArrays, jax.Array, jax.Array]:
    """Chain W seed-id waves (int32[W, S], -1-padded) in ONE program.

    Each wave cascades over the state the previous one left (the live
    burst shape: many commands completing back-to-back get ONE dispatch +
    ONE readback instead of W host round trips). Returns
    (g, per-wave newly-invalidated counts int32[W], union newly mask).
    """
    inv_before = g.invalid
    n_cap = g.n_cap

    def body(g, seed_ids):
        fresh = seeds_to_frontier(n_cap, seed_ids) & ~g.invalid
        g = g._replace(invalid=g.invalid | fresh)
        g, count = _expand_to_fixpoint(fresh, g)
        return g, count

    g, counts = lax.scan(body, g, seed_ids_mat)
    return g, counts, g.invalid & ~inv_before


@functools.partial(jax.jit, donate_argnums=(1,))
def run_waves_union(
    seed_ids: jax.Array, g: GraphArrays
) -> Tuple[GraphArrays, jax.Array, jax.Array]:
    """Union cascade: ALL seeds (int32[...], -1-padded) expand in ONE BFS.

    Invalidation is idempotent and the live batch path applies only the
    UNION of newly-invalid nodes (graph/backend.py::invalidate_cascade_batch
    reads counts.sum() + the union mask) — so chaining W sequential waves
    (O(edges × depth × W), which at 1M nodes × 64 waves ran long enough to
    get the TPU worker killed mid-program) collapses to one expansion,
    O(edges × depth) total. Returns (g, newly count, union newly mask).

    Seeds CONDUCT even when already invalid (r4): a host-led columnar mark
    (``table.invalidate`` → icasc journal entry) sets a row's invalid bit
    without the host having walked its DEVICE-ONLY declared dependents, so
    the expansion from such a seed must still fire them. Already-invalid
    NON-seed nodes keep blocking propagation — they were either cascaded
    when they were invalidated, or they are seeds of this same batch.
    Pre-invalid seeds don't count as newly (mask diff vs inv_before).
    """
    inv_before = g.invalid
    frontier = seeds_to_frontier(g.n_cap, seed_ids.reshape(-1))
    g = g._replace(invalid=g.invalid | frontier)
    g, _ = _expand_to_fixpoint(frontier, g)
    newly = g.invalid & ~inv_before
    return g, newly.sum(dtype=jnp.int32), newly


@functools.partial(jax.jit, donate_argnums=(1,))
def run_wave_with_stats(
    seed_frontier: jax.Array, g: GraphArrays
) -> Tuple[GraphArrays, jax.Array, jax.Array]:
    """run_wave + BFS depth (levels executed) for latency analysis."""
    fresh_seeds = seed_frontier & ~g.invalid
    g = g._replace(invalid=g.invalid | fresh_seeds)

    def cond(carry):
        frontier, _g, _count, _depth = carry
        return frontier.any()

    def body(carry):
        frontier, g, count, depth = carry
        nxt, g = wave_step(frontier, g)
        # depth = productive levels (the final empty expansion doesn't count)
        return nxt, g, count + nxt.sum(dtype=jnp.int32), depth + nxt.any().astype(jnp.int32)

    frontier, g, count, depth = lax.while_loop(
        cond,
        body,
        (fresh_seeds, g, fresh_seeds.sum(dtype=jnp.int32), jnp.int32(0)),
    )
    return g, count, depth
