"""Bit-packed pull-mode waves: 32 invalidation cascades per pass.

The throughput endgame of the wave kernel family (see ell_wave.py for the
work-efficient single-wave path). Two ideas compose:

1. **Pull mode.** Level expansion reads each node's IN-list ("which nodes do
   I depend on — did any of them just fire?"). In-degree is naturally small
   (a compute method uses a handful of others; the synthetic DAG uses ~3),
   and `build_ell` on the REVERSED edge list bounds it at k with virtual
   OR-collector nodes. Per level the ONLY arbitrary-indexed access is
   ``frontier[in_src]``; the version check (edge epoch vs own epoch),
   fire combination, and invalid update are all contiguous vector ops —
   exactly what the TPU VPU streams at full HBM bandwidth.

2. **Bit-packing.** Invalidation is idempotent and commutative, so 32
   INDEPENDENT waves (32 command completions, in reference terms — the
   OperationCompletionNotifier queue processed SIMD instead of serially)
   ride one int32 lane: bit w = "wave w reached this node". The per-index
   gather cost — the TPU's weak spot — is amortized 32×.

Wave depth becomes max over the batch, and all 32 waves share one epoch
snapshot (graph consistent at batch start) — the batching contract.

The graph arrays travel as RUNTIME ARGUMENTS (``PullGraphArrays``), never
as jit closure captures: at 10M nodes the in-edge table is ~320MB, and a
closure capture would embed it as an HLO constant — blowing up the compile
payload.
Passing them as device-resident args keeps the compiled program
shape-parameterized and the upload a one-time ``device_put``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

from .ell_wave import EllGraph, build_ell

__all__ = [
    "pack_lane_matrix",
    "PullGraphArrays",
    "PullState",
    "build_pull_graph",
    "build_pull_wave32",
    "pull_wave32_step",
    "pull_graph_arrays",
    "pull_init_state",
    "seeds_to_bits",
]


def build_pull_graph(src: np.ndarray, dst: np.ndarray, n_nodes: int, k: int = 8) -> EllGraph:
    """In-edge ELL: row d lists the nodes d depends on (virtual OR-collectors
    bound fan-in at k). Just build_ell on the reversed edges."""
    return build_ell(dst, src, n_nodes, k=k)


def pack_seed_words(
    n_rows: int, seed_ids_per_wave, words: int = 1, id_map: "np.ndarray" = None
) -> np.ndarray:
    """≤``32*words`` seed-id lists → int32 bit words (host-side prep):
    1-D [n_rows] for ``words=1``, else [n_rows, words]. ``id_map`` remaps
    seed ids first (e.g. topo's original→level-order permutation). The
    shared packer behind every bit-packed kernel's seed path."""
    bits = np.zeros((n_rows, words), dtype=np.int32)
    for i, ids in enumerate(seed_ids_per_wave[: 32 * words]):
        w, lane = divmod(i, 32)
        ids = np.asarray(ids, dtype=np.int64)
        if id_map is not None:
            ids = id_map[ids]
        bits[ids, w] |= np.int32(1 << lane) if lane < 31 else np.int32(-(1 << 31))
    return bits[:, 0] if words == 1 else bits


def pack_lane_matrix(groups, pad_id: int, n_valid: int, id_map=None, base_index: int = 0):
    """Per-group seed ids → (int32[32*words, width] lane matrix, words):
    row i holds group i's UNIQUE ids (uniqueness matters — lane bits are
    scatter-ADDed on device), padded with ``pad_id``; words and width round
    up to powers of two so varying burst shapes reuse compiled programs.
    ``id_map`` optionally remaps ids (e.g. topo original→level-order); ids
    must lie in [0, n_valid). THE shared packer behind both lane-burst
    faces (DeviceGraph.run_waves_lanes, PackedShardedGraph.run_gated_lanes)."""
    words = 1
    while words < (len(groups) + 31) // 32:
        words <<= 1
    width = 1
    while width < max((len(s) for s in groups), default=1):
        width <<= 1
    mat = np.full((32 * words, width), pad_id, dtype=np.int32)
    for i, s in enumerate(groups):
        ids = np.unique(np.asarray(s, dtype=np.int64))
        if len(ids) and (ids[0] < 0 or ids[-1] >= n_valid):
            raise ValueError(
                f"group {base_index + i}: seed ids must be in [0, {n_valid})"
            )
        if id_map is not None:
            ids = id_map[ids]
        mat[i, : len(ids)] = ids.astype(np.int32)
    return mat, words


def seeds_to_bits(n_tot: int, seed_ids_per_wave) -> np.ndarray:
    """List of ≤32 seed-id arrays → int32 bitmask vector (host-side prep)."""
    bits = pack_seed_words(n_tot + 1, seed_ids_per_wave)
    bits[n_tot] = 0
    return bits


class PullGraphArrays(NamedTuple):
    """Device-resident graph structure, passed to the kernel per call."""

    in_src: "object"  # int32[n_tot+1, k]: row d's dependencies
    edge_epoch: "object"  # int32[n_tot+1, k]: captured dependency epochs
    is_real: "object"  # bool[n_tot+1]: False for virtual OR-collectors


class PullState(NamedTuple):
    node_epoch: "object"  # int32[n_tot+1]
    invalid_bits: "object"  # int32[n_tot+1]


def pull_graph_arrays(graph: EllGraph) -> PullGraphArrays:
    """One-time upload of the packed in-edge table to device HBM."""
    import jax.numpy as jnp

    return PullGraphArrays(
        in_src=jnp.asarray(graph.ell_dst),
        edge_epoch=jnp.asarray(graph.ell_epoch),
        is_real=jnp.asarray(graph.is_real),
    )


def pull_init_state(n_tot: int) -> PullState:
    import jax.numpy as jnp

    return PullState(
        jnp.zeros(n_tot + 1, dtype=jnp.int32).at[n_tot].set(-2),
        jnp.zeros(n_tot + 1, dtype=jnp.int32),
    )


def _pull_wave32_impl(garrays: PullGraphArrays, seed_bits, state: PullState):
    import jax.numpy as jnp
    from jax import lax

    in_src, edge_epoch, is_real = garrays
    n_tot = in_src.shape[0] - 1
    k = in_src.shape[1]

    node_epoch, invalid = state.node_epoch, state.invalid_bits
    live = edge_epoch == node_epoch[:, None]  # (n_tot+1, k) contiguous
    frontier = seed_bits & ~invalid
    invalid = invalid | frontier

    def cond(carry):
        _frontier, _inv, go = carry
        return go

    def body(carry):
        frontier, invalid, _go = carry
        f = frontier[in_src]  # (n_tot+1, k) — the one arbitrary gather
        contrib = jnp.where(live, f, 0)
        fire = contrib[:, 0]
        for j in range(1, k):  # static small k: unrolled OR-fold
            fire = fire | contrib[:, j]
        fire = (fire & ~invalid).at[n_tot].set(0)
        invalid = invalid | fire
        return fire, invalid, (fire != 0).any()

    _f, invalid, _go = lax.while_loop(cond, body, (frontier, invalid, (frontier != 0).any()))
    counts = lax.population_count(jnp.where(is_real, invalid, 0))
    return PullState(node_epoch, invalid), counts.sum(dtype=jnp.int32)


@functools.lru_cache(maxsize=1)
def pull_wave32_step():
    """The jitted 32-wave kernel: ``step(garrays, seed_bits, state)``.

    Module-level (cached) so composing programs — e.g. the benchmark's
    lax.scan over seed batches — can call it inside their own jit while
    threading ``garrays`` through as parameters.
    """
    import jax

    return jax.jit(_pull_wave32_impl)


def build_pull_wave32(graph: EllGraph):
    """Compile the 32-wave bit-packed cascade for one graph.

    Returns (state0, wave32) where
    ``wave32(seed_bits, state) -> (state, real_invalidation_count)``:
    ``seed_bits`` is int32[n_tot+1]; the count sums popcounts over REAL nodes
    (virtual collectors excluded) across all 32 waves. The device graph is
    exposed as ``wave32.garrays`` (and the raw kernel as ``wave32.step``)
    for callers that fuse the wave into a larger jitted program.
    """
    garrays = pull_graph_arrays(graph)
    step = pull_wave32_step()

    def wave32(seed_bits, state):
        return step(garrays, seed_bits, state)

    wave32.garrays = garrays
    wave32.step = step
    wave32.impl = _pull_wave32_impl
    return pull_init_state(graph.n_tot), wave32
