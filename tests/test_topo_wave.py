"""Topo-ordered single-sweep 32-wave kernel vs host BFS oracle (ops/topo_wave.py).

Same oracle strategy as test_pull_wave, plus checks that
the level renumbering round-trips ids and that the native Kahn level pass
agrees with the numpy relaxation.
"""
import numpy as np
import pytest

from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.ops.topo_wave import (
    _levels_numpy,
    build_topo_graph,
    build_topo_wave32,
    topo_seeds_to_bits,
)


def host_reachable(src, dst, n, seeds):
    adj = {}
    for s, d in zip(src, dst):
        adj.setdefault(int(s), []).append(int(d))
    seen = set(int(s) for s in seeds)
    stack = list(seen)
    while stack:
        u = stack.pop()
        for v in adj.get(u, ()):
            if v not in seen:
                seen.add(v)
                stack.append(v)
    return seen


def run_waves(graph, seed_lists):
    import jax.numpy as jnp

    state0, wave32 = build_topo_wave32(graph)
    seed_bits = jnp.asarray(topo_seeds_to_bits(graph, seed_lists))
    state, count = wave32(seed_bits, state0)
    return np.asarray(state.invalid_bits), int(count)


def check_against_oracle(src, dst, n, seed_lists, k=4, use_native=True):
    graph = build_topo_graph(src, dst, n, k=k, use_native=use_native)
    invalid_bits, count = run_waves(graph, seed_lists)
    # results live in new-id space: row i is original node graph.perm[i]
    total = 0
    for w, seeds in enumerate(seed_lists):
        expected = host_reachable(src, dst, n, seeds)
        bit = np.int64(1) << w
        got = {
            int(graph.perm[i])
            for i in range(graph.n_tot)
            if (invalid_bits[i] & bit) and graph.is_real[i]
        }
        assert got == expected, f"wave {w}: {len(got)} vs {len(expected)} nodes"
        total += len(expected)
    assert count == total
    return graph


def test_matches_oracle_on_power_law_dag():
    src, dst = power_law_dag(3000, avg_degree=3.0, seed=11)
    rng = np.random.default_rng(0)
    seed_lists = [rng.choice(3000, size=5, replace=False) for _ in range(32)]
    check_against_oracle(src, dst, 3000, seed_lists)


def test_levels_are_topological():
    src, dst = power_law_dag(2000, avg_degree=3.0, seed=4)
    g = build_topo_graph(src, dst, 2000, k=4)
    # every live in-edge must point at a strictly earlier row
    live = g.in_src < g.n_tot
    rows = np.arange(g.n_tot + 1)[:, None]
    assert (g.in_src[live] < np.broadcast_to(rows, g.in_src.shape)[live]).all()
    # level slices are contiguous; the tail past the last level is pure
    # capacity padding (null rows — r4 total quantization for program-key
    # stability across rebuilds)
    assert g.level_starts[0] == 0 and g.level_starts[-1] <= g.n_tot
    tail = slice(g.level_starts[-1], g.n_tot)
    assert not g.is_real[tail].any()
    assert (g.in_src[tail] == g.n_tot).all()


def test_high_fan_in_through_collector_trees():
    """500 sources feeding one sink ≫ k: the collector tree must be placed
    on correct (deeper) levels so every source's signal arrives in one sweep."""
    n = 502
    edges = [(i, 500) for i in range(500)] + [(500, 501)]
    src = np.array([e[0] for e in edges])
    dst = np.array([e[1] for e in edges])
    graph = build_topo_graph(src, dst, n, k=4)
    assert graph.n_tot > n  # collector nodes exist
    for probe in (0, 1, 250, 499):
        inv, _ = run_waves(graph, [[probe]])
        new_sink = int(graph.inv_perm[500])
        new_tail = int(graph.inv_perm[501])
        assert inv[new_sink] & 1, f"source {probe} lost through collectors"
        assert inv[new_tail] & 1


def test_deep_chain_single_sweep():
    """A 900-deep chain completes in ONE sweep (the level-synchronized
    kernels would need 900 iterations)."""
    n = 900
    src = np.arange(n - 1)
    dst = np.arange(1, n)
    graph = check_against_oracle(src, dst, n, [[0]] + [[i] for i in range(1, 32)])
    assert len(graph.level_starts) - 1 == n  # one level per chain link


def test_idempotent_and_epoch_gating():
    import jax.numpy as jnp

    src, dst = power_law_dag(500, avg_degree=3.0, seed=3)
    graph = build_topo_graph(src, dst, 500)
    state0, wave32 = build_topo_wave32(graph)
    seed_bits = jnp.asarray(topo_seeds_to_bits(graph, [[1, 2, 3]]))
    state1, c1 = wave32(seed_bits, state0)
    assert int(c1) > 0
    state2, c2 = wave32(seed_bits, state1)
    assert int(c2) == 0  # already invalid: nothing new

    # bump a node's epoch: its in-edges (captured at epoch 0) go dead, so
    # the cascade can't pass through it (version-consistent edges,
    # Computed.cs:213-215)
    reach = host_reachable(src, dst, 500, [1])
    blocked = sorted(reach - {1})
    if blocked:
        b_new = int(graph.inv_perm[blocked[0]])
        bumped = state0._replace(node_epoch=state0.node_epoch.at[b_new].set(1))
        state3, _ = wave32(jnp.asarray(topo_seeds_to_bits(graph, [[1]])), bumped)
        assert not (np.asarray(state3.invalid_bits)[b_new] & 1)


def test_native_levels_match_numpy():
    from stl_fusion_tpu.native import native_topo_levels
    from stl_fusion_tpu.ops.ell_wave import build_ell

    src, dst = power_law_dag(4000, avg_degree=3.0, seed=17)
    ell = build_ell(dst, src, 4000, k=4)
    lv_nat = native_topo_levels(ell.ell_dst, ell.n_tot, 4)
    assert lv_nat is not None
    lv_np = _levels_numpy(ell.ell_dst, ell.n_tot, 4)
    assert np.array_equal(lv_nat, lv_np)


def test_agrees_with_dense_kernel():
    """The sweep's 32 lanes against 32 waves of the plain dense BFS
    (ops/wave.py, the level-synchronous reference kernel) on the same graph
    and seeds: every lane's closure and the total count are equal."""
    import jax.numpy as jnp

    from stl_fusion_tpu.ops.wave import GraphArrays, run_wave, seeds_to_frontier

    n = 2500
    src, dst = power_law_dag(n, avg_degree=3.0, seed=8)
    rng = np.random.default_rng(5)
    seed_lists = [rng.choice(n, size=10, replace=False) for _ in range(32)]

    tg = build_topo_graph(src, dst, n)
    inv_t, c_t = run_waves(tg, seed_lists)
    lanes = inv_t[: tg.n_tot].astype(np.int64)
    real = np.asarray(tg.is_real[: tg.n_tot], dtype=bool)

    total = 0
    for w, seeds in enumerate(seed_lists):
        g = GraphArrays(  # run_wave donates its graph: a fresh one per wave
            edge_src=jnp.asarray(src, dtype=jnp.int32),
            edge_dst=jnp.asarray(dst, dtype=jnp.int32),
            edge_dst_epoch=jnp.zeros(len(src), dtype=jnp.int32),
            node_epoch=jnp.zeros(n + 1, dtype=jnp.int32).at[n].set(-2),
            invalid=jnp.zeros(n + 1, dtype=jnp.bool_),
        )
        frontier = seeds_to_frontier(n, jnp.asarray(seeds, dtype=jnp.int32))
        g, count = run_wave(frontier, g)
        got = np.zeros(n, dtype=bool)
        got[tg.perm[np.nonzero((lanes & (1 << w)).astype(bool) & real)[0]]] = True
        assert np.array_equal(got, np.asarray(g.invalid[:n])), w
        total += int(count)
    assert c_t == total


def test_multiword_packing_matches_oracle():
    """words=2 packs 64 waves in one sweep; every lane's closure must equal
    the host oracle, and the count must sum across all lanes."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    n = 400
    edges = sorted({(int(a), int(b)) for a, b in zip(
        rng.integers(0, n - 1, 1200), rng.integers(1, n, 1200)) if a < b})
    src = np.array([e[0] for e in edges], dtype=np.int32)
    dst = np.array([e[1] for e in edges], dtype=np.int32)

    seed_lists = [rng.choice(n, size=4, replace=False).tolist() for _ in range(64)]
    graph = build_topo_graph(src, dst, n, k=4)
    state0, wave = build_topo_wave32(graph, words=2)
    seed_bits = jnp.asarray(topo_seeds_to_bits(graph, seed_lists, words=2))
    state, count = wave(seed_bits, state0)
    invalid = np.asarray(state.invalid_bits)
    assert invalid.shape == (graph.n_tot + 1, 2)
    assert np.asarray(count).shape == (2,)  # per-word counts (int32-safe)
    count = int(np.asarray(count, dtype=np.int64).sum())

    total = 0
    for i, seeds in enumerate(seed_lists):
        w, lane = divmod(i, 32)
        expected = host_reachable(src, dst, n, seeds)
        bit = np.int64(1) << lane
        got = {
            int(graph.perm[r])
            for r in range(graph.n_tot)
            if (np.int64(invalid[r, w]) & bit) and graph.is_real[r]
        }
        assert got == expected, f"wave {i}: {len(got)} vs {len(expected)}"
        total += len(expected)
    assert count == total


def test_empty_graph_builds_trivially():
    """ADVICE r4: n_tot == 0 hit a negative shift in the total-quantization;
    an empty backend (build_topo_mirror before any nodes) must get the
    trivial graph, not a ValueError."""
    g = build_topo_graph(np.empty(0, np.int32), np.empty(0, np.int32), 0)
    assert g.n_tot == 0 and g.n_real == 0
    assert g.level_starts == (0,) or g.level_starts == (0, 0)

    from stl_fusion_tpu.graph.device_graph import DeviceGraph

    dg = DeviceGraph()
    dg.build_topo_mirror()  # no nodes yet: must not raise
    counts, union_mask = dg.run_waves_lanes([[]])
    assert counts.tolist() == [0] and not union_mask.any()


# ------------------------------------------------- lane-dense (packed) sweep
def _gated_closure(adj, seeds, pre_invalid):
    """Dense-BFS closure of one lane group: seeds conduct even when already
    invalid, any other already-invalid node neither fires nor conducts."""
    seen = set(int(s) for s in seeds)
    stack = list(seen)
    while stack:
        u = stack.pop()
        for v in adj.get(u, ()):
            if v not in seen and v not in pre_invalid:
                seen.add(v)
                stack.append(v)
    return seen - pre_invalid


def _patched_mirror(n, quantize, n_late, rng):
    """A mirror built from a random DAG less ``n_late`` edges, which are then
    spliced into free slack slots the way the live patcher does. Every late
    edge runs from a node of a level at or above its target's level (a level
    violation: one extra sweep pass each), and no two of them chain."""
    pairs = sorted({(int(a), int(b)) for a, b in zip(
        rng.integers(0, n - 1, 3 * n), rng.integers(1, n, 3 * n)) if a < b})
    src = np.array([p[0] for p in pairs], dtype=np.int32)
    dst = np.array([p[1] for p in pairs], dtype=np.int32)
    graph = build_topo_graph(src, dst, n, k=4, quantize=quantize, slack=2)
    starts = np.asarray(graph.level_starts)
    level_of = lambda old: int(np.searchsorted(starts, graph.inv_perm[old], "right")) - 1
    in_src = graph.in_src.copy()
    late, used = [], set()
    for u in rng.permutation(n):
        if len(late) == n_late:
            break
        u = int(u)
        # a LATER node (so the edge keeps the DAG acyclic: ids ascend along
        # every edge) that sits in a level no higher than u's
        cands = [v for v in range(u + 1, n)
                 if level_of(v) <= level_of(u) and v not in used and u not in used]
        if not cands:
            continue
        v = int(cands[0])
        row = int(graph.inv_perm[v])
        free = np.nonzero(in_src[row] == graph.n_tot)[0]
        in_src[row, free[0]] = graph.inv_perm[u]
        late.append((u, v))
        used.update((u, v))
    assert len(late) == n_late
    all_src = np.concatenate([src, np.array([p[0] for p in late], dtype=np.int32)])
    all_dst = np.concatenate([dst, np.array([p[1] for p in late], dtype=np.int32)])
    return graph, in_src, all_src, all_dst


@pytest.mark.parametrize("passes", [1, 2, 0])
@pytest.mark.parametrize("quantize", [True, False])
@pytest.mark.parametrize("words", [1, 2, 8, 16, 32])
def test_packed_lane_burst_matches_oracle(words, quantize, passes):
    """The lane-dense sweep (``128 // Wp`` nodes to a state row from 8 words
    on, one node a row below) against the host closure of every lane group:
    at every width, with level boundaries
    that are and are not multiples of the nodes per row, on a patched mirror
    at a fixed pass count and adaptive, with pre-invalid nodes gating, and
    with the null row inside a state row it shares with real nodes. The split
    pipeline (bits packed on entry, unpacked on exit) must agree."""
    import jax.numpy as jnp

    from stl_fusion_tpu.ops.topo_wave import (
        TopoGraphArrays,
        _row_geometry,
        run_topo_sweep_passes,
        topo_mirror_finish_lanes_step,
        topo_mirror_fused_lanes_step,
        topo_mirror_gate_lanes_step,
    )

    rng = np.random.default_rng([words, quantize, passes])
    n_late = {1: 0, 2: 1, 0: 3}[passes]
    for n in range(301, 340):
        graph, in_src, src, dst = _patched_mirror(n, quantize, n_late, rng)
        if quantize or graph.n_tot % 2:
            break  # an odd row count: the null row shares its state row
    n_tot = graph.n_tot
    P = _row_geometry(words)[0]
    if not quantize and P > 1:
        assert n_tot % P, "the null row must share a state row with real nodes"
        assert any(s % P for s in graph.level_starts), "no unaligned boundary"

    adj = {}
    for s, d in zip(src, dst):
        adj.setdefault(int(s), []).append(int(d))
    pre_invalid = set(rng.choice(n, size=6, replace=False).tolist())
    n_groups = 32 * words - 3  # the last lanes stay empty
    groups = [rng.choice(n, size=2, replace=False).tolist() for _ in range(n_groups)]
    seed_mat = np.full((32 * words, 2), n_tot, dtype=np.int32)
    for g, seeds in enumerate(groups):
        seed_mat[g] = graph.inv_perm[seeds]

    garrays = TopoGraphArrays(
        jnp.asarray(in_src),
        jnp.where(jnp.asarray(in_src) != n_tot, 0, -1).astype(jnp.int32),
        jnp.asarray(graph.is_real),
    )
    node_epoch0 = jnp.zeros(n_tot + 1, dtype=jnp.int32).at[n_tot].set(-2)
    perm_clipped = jnp.asarray(np.clip(graph.perm, 0, n).astype(np.int32))
    g_invalid = np.zeros(n + 1, dtype=bool)
    g_invalid[list(pre_invalid)] = True
    g_invalid = jnp.asarray(g_invalid)

    g_invalid2, lane_counts, union_count, packed = topo_mirror_fused_lanes_step(
        graph.level_starts, n_tot, words, passes
    )(garrays, node_epoch0, perm_clipped, g_invalid, jnp.asarray(seed_mat))

    closures = [_gated_closure(adj, seeds, pre_invalid) for seeds in groups]
    lane_counts = np.asarray(lane_counts)
    assert lane_counts.shape == (32 * words,)
    assert lane_counts[:n_groups].tolist() == [len(c) for c in closures]
    assert not lane_counts[n_groups:].any()
    union = set().union(*closures)
    assert int(union_count) == len(union)
    got = np.unpackbits(
        np.asarray(packed).view(np.uint8), count=n + 1, bitorder="little"
    ).astype(bool)
    assert set(np.nonzero(got)[0].tolist()) == union
    assert set(np.nonzero(np.asarray(g_invalid2))[0].tolist()) == union | pre_invalid

    if passes == 2:  # the split pipeline: [n_tot+1, W] bits between programs
        node_epoch, seed_bits = topo_mirror_gate_lanes_step(n_tot, words)(
            garrays.is_real, node_epoch0, perm_clipped, g_invalid, jnp.asarray(seed_mat)
        )
        assert seed_bits.shape == (n_tot + 1, words)
        state = run_topo_sweep_passes(
            graph.level_starts, garrays, seed_bits, node_epoch, passes
        )
        assert state.invalid_bits.shape == (n_tot + 1, words)
        split = topo_mirror_finish_lanes_step(n_tot, words)(
            garrays.is_real, perm_clipped, g_invalid, state.invalid_bits
        )
        assert np.array_equal(np.asarray(split[1]), lane_counts)
        assert np.array_equal(np.asarray(split[0]), np.asarray(g_invalid2))
        assert np.array_equal(np.asarray(split[3]), np.asarray(packed))
