"""Topo-ordered single-sweep 32-wave kernel: the whole cascade in ONE pass.

The level-synchronized kernels (pull_wave.py, hybrid_wave.py) pay a gather
over the in-edge table EVERY BFS level — O(n·k · depth) gathered words per
32-wave batch. But the dependency graph is a DAG (a computed value can only
depend on values that existed when it was computed — Computed.cs:347-363
"dependencies that didn't finish aren't dependencies"), so there is a
strictly better schedule:

1. **Topological level ordering** (host/native, once per graph build).
   level[d] = 1 + max(level of d's dependencies); renumber nodes so each
   level occupies a contiguous id range. All in-edges then point to strictly
   LOWER levels.
2. **Single sweep.** Process levels in ascending order inside one jitted
   program: level l's rows gather ``invalid`` at their in-slots — which are
   all in already-finalized earlier levels — OR-fold, and write the level's
   contiguous slice. After one pass over the table, ``invalid`` holds the
   full transitive closure of all 32 packed waves, no matter where their
   seeds sat. Total gathered rows = n·k, not n·k·depth: depth× fewer
   fetches than the dense pull kernel (the 10 M-node bench DAG runs 86
   levels).
3. **Lane-dense state.** The chip is bound by the NUMBER of fetches, not
   by their bytes (v5e, PERF.md §5: 7.7 ns for a 4-byte fetch, 15 ns for
   a whole 512-byte row, 36 ns for sixteen words strided across a state
   laid out with the nodes along the lanes, which is how XLA:TPU lays
   ``[n, 16]`` out). So inside the sweep, from 8 words a node on, the
   bit state is ``int32[ceil(n / P), 128]``, ``P = 128 // words`` nodes to
   a 128-lane row (:func:`_row_geometry`): every in-edge fetch is one
   whole-row gather, and no array in the sweep has a minor dimension the
   tiling would pad.

Level boundaries are STATIC (baked into the compiled program — they only
change when the graph's level structure changes), while the table contents
remain runtime args, so edge/epoch updates that preserve the level layout
need no recompile and the compile payload stays shape-only (see
pull_wave.py on why the arrays must not ride the payload).

Pull-mode bonus (see pull_wave.py): hub fan-OUT never matters — only
in-degree is bounded (avg ~3 in the bench DAG) — so the augmented graph has
few or no virtual collector nodes and real depth stays shallow.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import numpy as np

from .ell_wave import EllGraph, build_ell
from .pull_wave import pack_seed_words

__all__ = [
    "TopoGraph",
    "TopoGraphArrays",
    "TopoState",
    "build_topo_graph",
    "topo_graph_arrays",
    "topo_init_state",
    "build_topo_wave32",
    "topo_mirror_gate_step",
    "topo_mirror_finish_step",
    "topo_mirror_fused_union_step",
    "topo_mirror_fused_lanes_step",
    "topo_mirror_fused_lanes_chain_step",
    "topo_mirror_superround_step",
    "topo_mirror_gate_lanes_step",
    "topo_mirror_finish_lanes_step",
    "run_topo_sweep_passes",
    "topo_seeds_to_bits",
]


class TopoGraph(NamedTuple):
    """Host-built in-ELL in topological level order.

    Row ids are NEW (level-ordered) ids; ``perm`` maps new→old augmented
    ids, ``inv_perm`` old→new (both length n_tot+1, fixed point at the null
    row n_tot).
    """

    in_src: np.ndarray  # int32[n_tot+1, k] — NEW-id in-neighbors; pad n_tot
    edge_epoch: np.ndarray  # int32[n_tot+1, k] — captured epochs; pad -1
    is_real: np.ndarray  # bool[n_tot+1] (new order)
    level_starts: Tuple[int, ...]  # len L+1; level l = rows [starts[l], starts[l+1])
    perm: np.ndarray  # int64[n_tot+1]: new id -> old id
    inv_perm: np.ndarray  # int64[n_tot+1]: old id -> new id
    n_real: int
    n_tot: int
    k: int


def _levels_numpy(in_src: np.ndarray, n: int, k: int) -> np.ndarray:
    """Longest-path levels by vectorized relaxation (fallback; the native
    Kahn pass in graphpack.cpp::gp_topo_levels is the fast path)."""
    level = np.zeros(n, dtype=np.int32)
    table = in_src[:n].astype(np.int64)
    live = table < n
    safe = np.where(live, table, 0)
    for _ in range(4 * n + 4):  # depth is bounded by n
        cand = np.where(live, level[safe] + 1, 0).max(axis=1).astype(np.int32)
        if (cand <= level).all():
            return level
        level = np.maximum(level, cand)
    raise ValueError("level relaxation failed to converge (cycle?)")


def _quantize_level(s: int) -> int:
    """Pad a level's row count up to a coarse size bucket (≤12.5% overhead
    past 128 rows, minimum grid 16). Level sizes — and therefore the
    ``level_starts`` tuple the sweep program is keyed on — become STABLE
    under small structural drift: a mirror rebuild after churn usually
    produces the SAME tuple and reuses the already-compiled sweep (in-
    process lru + persistent cache) instead of paying a full XLA compile
    (~3 min at 1M nodes) inside the serving path."""
    if s <= 0:
        return 0
    if s <= 16:
        return 16
    grid = max(16, 1 << (int(s - 1).bit_length() - 3))
    return -(-s // grid) * grid


def build_topo_graph(
    src: np.ndarray, dst: np.ndarray, n_nodes: int, k: int = 4, use_native: bool = True,
    quantize: bool = True, slack: int = 0,
) -> TopoGraph:
    """In-ELL (build_ell on reversed edges, bounding in-degree at k with
    virtual OR-collectors) renumbered into topological level order, each
    level padded to a quantized size (null rows: no in-edges, not real) so
    the compiled sweep survives rebuilds — see :func:`_quantize_level`.

    ``slack`` appends that many GUARANTEED-FREE pad columns to every row:
    the live mirror's patch path needs a free slot to splice a new in-edge
    in place, and a packed row (in-degree ≡ k) would otherwise break the
    patch log on the first realistic-churn edge landing on it. Slack
    widens the sweep's row gathers by slack/k — the live mirror pays it,
    the static bench (slack=0) does not."""
    ell: EllGraph = build_ell(dst, src, n_nodes, k=k, use_native=use_native)
    n_tot_o = ell.n_tot
    level = None
    if use_native:
        from ..native import native_topo_levels

        level = native_topo_levels(ell.ell_dst, n_tot_o, k)
    if level is None:
        level = _levels_numpy(ell.ell_dst, n_tot_o, k)

    order = np.argsort(level, kind="stable")  # levels ascending over old ids
    sizes = np.bincount(level, minlength=int(level.max()) + 1 if n_tot_o else 1)
    padded = [(_quantize_level(int(s)) if quantize else int(s)) for s in sizes]
    n_tot = int(sum(padded))  # padded row-space size; null row at index n_tot
    if quantize and n_tot:
        # quantize the TOTAL too (≤ ~3% tail of pure null rows): programs
        # keyed on n_tot (gate/finish/lane epilogues) survive rebuilds whose
        # level structure drifted — the expensive 512-lane popcount epilogue
        # would otherwise recompile on every re-level. (n_tot == 0 — an
        # empty backend mirror — would shift by -1 here; the trivial graph
        # needs no padding at all.)
        grain = max(256, (1 << (n_tot.bit_length() - 1)) // 32)
        n_tot = -(-n_tot // grain) * grain

    # perm: new row -> old augmented id; pad rows map to the OLD null row
    # (their in-rows read as all-pad, epoch -1 — they can never fire)
    perm = np.full(n_tot + 1, n_tot_o, dtype=np.int64)
    starts = [0]
    pos = oi = 0
    for s, ps in zip(sizes, padded):
        s, ps = int(s), int(ps)
        perm[pos : pos + s] = order[oi : oi + s]
        oi += s
        pos += ps
        starts.append(pos)
    inv_perm = np.full(n_tot_o + 1, n_tot, dtype=np.int64)
    real = perm[:n_tot] != n_tot_o
    inv_perm[perm[:n_tot][real]] = np.nonzero(real)[0]
    inv_perm[n_tot_o] = n_tot

    # remap rows into new order and entries into new ids (the old pad row
    # maps to the new null row n_tot, so pad entries stay pads)
    in_src = inv_perm[ell.ell_dst[perm]].astype(np.int32)
    edge_epoch = ell.ell_epoch[perm]
    is_real = ell.is_real[perm] & (perm != n_tot_o)
    if slack:
        in_src = np.hstack(
            [in_src, np.full((in_src.shape[0], slack), n_tot, dtype=np.int32)]
        )
        edge_epoch = np.hstack(
            [edge_epoch, np.full((in_src.shape[0], slack), -1, dtype=np.int32)]
        )

    return TopoGraph(
        in_src, edge_epoch, is_real, tuple(starts), perm, inv_perm, n_nodes, n_tot,
        k + slack,
    )


class TopoGraphArrays(NamedTuple):
    in_src: "object"
    edge_epoch: "object"
    is_real: "object"


class TopoState(NamedTuple):
    node_epoch: "object"  # int32[n_tot+1] (new order)
    #: int32[n_tot+1] (words=1) or int32[n_tot+1, words] — each uint32 lane
    #: packs 32 independent waves; see topo_init_state(words=...)
    invalid_bits: "object"


@functools.lru_cache(maxsize=4)
def _derive_topo_epoch_kernel(n_tot: int):
    """Slot live ⇔ epoch 0, pad ⇔ -1: fully derivable from the id table —
    deriving ON DEVICE halves a mirror install's upload (the epoch table
    is as big as the structure table, ~264 MB at 10M)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def derive(in_src):
        return jnp.where(in_src != n_tot, 0, -1).astype(jnp.int32)

    return derive


def topo_graph_arrays(graph: TopoGraph) -> TopoGraphArrays:
    import jax.numpy as jnp

    in_src = jnp.asarray(graph.in_src)
    return TopoGraphArrays(
        in_src=in_src,
        edge_epoch=_derive_topo_epoch_kernel(graph.n_tot)(in_src),
        is_real=jnp.asarray(graph.is_real),
    )


def topo_init_state(n_tot: int, words: int = 1) -> TopoState:
    """``words`` packs ``32*words`` independent waves per sweep. The sweep
    pays per fetched index, not per byte, once its state is lane-dense
    (:func:`_row_geometry`): 16 words cost 19.5 ns an index on the v5e
    against 7.7 ns for one word, for sixteen times the waves (PERF.md §5).
    This is the state as it crosses a program boundary; the sweep packs it
    on entry."""
    import jax.numpy as jnp

    if 32 * (n_tot + 1) >= 2**31:
        # per-word counts are summed in int32 on device (jax x64 is off);
        # beyond ~67M rows one word's count could silently wrap
        raise ValueError(
            f"topo sweep count tracking is int32-limited to <{2**31 // 32} rows; "
            f"got {n_tot + 1} — shard the graph (parallel/sharded_wave.py) instead"
        )
    shape = (n_tot + 1,) if words == 1 else (n_tot + 1, words)
    return TopoState(
        jnp.zeros(n_tot + 1, dtype=jnp.int32).at[n_tot].set(-2),
        jnp.zeros(shape, dtype=jnp.int32),
    )


def topo_seeds_to_bits(graph: TopoGraph, seed_ids_per_wave, words: int = 1) -> np.ndarray:
    """≤``32*words`` seed-id arrays (ORIGINAL node ids) → int32 bit
    vector[s] in NEW id space, ready for the sweep (1-D for ``words=1``,
    else [n_tot+1, words])."""
    bits = pack_seed_words(
        graph.n_tot + 1, seed_ids_per_wave, words=words, id_map=graph.inv_perm
    )
    bits[graph.n_tot] = 0
    return bits


#: lanes of one vector row of the chip: the sweep state's minor dimension
_LANES = 128
#: rows of one level fetched at a time: the fetched block is
#: ``rows x k x 512`` bytes before the fold (under 1 GB at k = 6)
_FETCH_ROWS = 1 << 18


def _row_geometry(words: int) -> Tuple[int, int]:
    """``(P, Wp)``: nodes per state row and words per node slot. The sweep
    state is ``int32[ceil((n_tot + 1) / P), P * Wp]``: node ``i`` owns lanes
    ``(i % P) * Wp ... + Wp`` of row ``i // P``. From 8 words on a row is
    the chip's 128 lanes, ``P = 128 // Wp`` nodes to it; ``words`` that does
    not divide 128 rounds up to the next power of two. 128 words or more
    are whole rows already, and under 8 words a node stays a row of its own
    (``P`` = 1, ``Wp = words``)."""
    if words >= _LANES or words < 8:
        # XLA:TPU lays [n, W] out with the nodes along the lanes, and
        # fetching W strided words costs 7.7 / 7.0 / 16.0 ns an index at
        # W = 1 / 2 / 4 on the v5e, against 12.2 / 11.3 / 20.4 ns for the
        # 512-byte row that would hold them; from 8 words on the row wins
        # (22.8 -> 19.5 ns, and 36.2 -> 19.5 at 16). PERF.md §5.
        return 1, words
    wp = 1 << (words - 1).bit_length()
    return _LANES // wp, wp


def _or_over(x, axis: int):
    import jax.numpy as jnp
    from jax import lax

    return lax.reduce(x, jnp.int32(0), lax.bitwise_or, (axis,))


def _pack_rows(bits):
    """``int32[n_tot+1]`` or ``[n_tot+1, W]`` -> the lane-dense
    ``[R, P * Wp]`` sweep state (:func:`_row_geometry`)."""
    import jax.numpy as jnp

    if bits.ndim == 1:
        bits = bits[:, None]
    n, words = bits.shape
    P, wp = _row_geometry(words)
    rows = -(-n // P)
    return jnp.pad(bits, ((0, rows * P - n), (0, wp - words))).reshape(rows, P * wp)


def _unpack_rows(packed, n_tot: int, words: int):
    """The inverse of :func:`_pack_rows`: ``[n_tot+1, words]``."""
    wp = _row_geometry(words)[1]
    return packed.reshape(-1, wp)[: n_tot + 1, :words]


def _clear_null_row(packed, n_tot: int, words: int):
    """The null row's words are always 0 (dead edges and pads read it)."""
    import jax.numpy as jnp
    from jax import lax

    P, wp = _row_geometry(words)
    return lax.dynamic_update_slice(
        packed, jnp.zeros((1, wp), packed.dtype), (n_tot // P, (n_tot % P) * wp)
    )


def _sweep_packed(
    level_starts, garrays: TopoGraphArrays, node_epoch, packed, words: int,
    start_level: int,
):
    """One pass over the levels, ascending, on the lane-dense state: every
    fetch reads only finalized rows. A level's in-edge fetch is a gather of
    WHOLE 512-byte state rows at ``eff // P`` (the embedding-lookup form);
    the ``Wp`` lanes of group ``eff % P`` are then selected by a mask, OR-ed
    over the ``k`` slots, folded over the groups by lane rotations and
    placed in the reading node's own group. Nothing in it has a minor
    dimension other than the row's lanes. With ``P`` = 1 the fetched row
    is the node's own words and the select and the fold fall away.

    ``start_level=1`` skips level 0 (no in-edges at build time by
    definition); multi-pass sweeps over PATCHED mirrors start at 0 — a
    patched edge into a level-0 row (any edge into level 0 is a level
    violation) fires from the previous pass's finalized state."""
    import jax.numpy as jnp
    from jax import lax

    from ..graph.program_cache import note_program_shape

    in_src, edge_epoch, _is_real = garrays
    n_tot = in_src.shape[0] - 1
    k = in_src.shape[1]
    P, wp = _row_geometry(words)
    lanes = P * wp
    note_program_shape(nodes_per_row=P)
    shift = P.bit_length() - 1
    lane_group = jnp.arange(lanes, dtype=jnp.int32) // wp
    own_group = jnp.arange(P, dtype=jnp.int32)[:, None] == lane_group[None, :]

    for l in range(start_level, len(level_starts) - 1):
        a, b = level_starts[l], level_starts[l + 1]
        # static sub-slices bound the fetched block; Python slices, not a
        # fori_loop: a loop's view of in_src[n_tot+1, k] is padded to 128
        # lanes (see topo_mirror_superround_step)
        for c in range(a, b, _FETCH_ROWS):
            d = min(c + _FETCH_ROWS, b)
            rows = lax.slice(in_src, (c, 0), (d, k))
            epochs = lax.slice(edge_epoch, (c, 0), (d, k))
            own = lax.slice(node_epoch, (c,), (d,))
            # dead edges (captured epoch != dependent's current epoch) read
            # the null row, whose words are always 0 (version-consistent
            # edges, Computed.cs:213-215). Slot-major: [k, d-c] is how the
            # tables lie on the chip (the nodes along the lanes), and the
            # fetched block then folds over its MAJOR axis, slab by slab
            eff = jnp.where(epochs == own[:, None], rows, n_tot).T
            f = packed[eff >> shift]  # (k, d-c, lanes): whole-row gather
            if P > 1:
                hit = (eff & (P - 1))[:, :, None] == lane_group
                f = jnp.where(hit, f, 0)
            fire = _or_over(f, 0)  # (d-c, lanes)
            if P > 1:
                # every group <- OR of all groups, then keep the own one
                s = lanes // 2
                while s >= wp:
                    fire = fire | jnp.roll(fire, s, axis=1)
                    s //= 2
                # a boundary that is no multiple of P: widen to whole rows,
                # the lanes outside [c, d) fire nothing
                fire = jnp.pad(fire, ((c % P, -d % P), (0, 0)))
                fire = fire.reshape(-1, P, lanes)
                fire = _or_over(jnp.where(own_group, fire, 0), 1)
            r0 = c // P
            cur = lax.slice(packed, (r0, 0), (r0 + fire.shape[0], lanes))
            packed = lax.dynamic_update_slice(packed, cur | fire, (r0, 0))
    return packed


def _topo_sweep_impl(
    level_starts, garrays: TopoGraphArrays, seed_bits, state: TopoState,
    start_level: int = 1,
):
    """The sweep as a program of its own: ``[n_tot+1]`` / ``[n_tot+1, W]``
    bits cross the program boundary, packed on entry and unpacked on exit
    (the fused programs below never leave the packed form)."""
    import jax.numpy as jnp
    from jax import lax

    n_tot = garrays.in_src.shape[0] - 1
    node_epoch, invalid = state.node_epoch, state.invalid_bits
    squeeze = invalid.ndim == 1
    if squeeze:
        invalid = invalid[:, None]
    if seed_bits.ndim == 1:
        seed_bits = seed_bits[:, None]
    W = invalid.shape[1]
    if seed_bits.shape[1] != W:
        # broadcasting a mismatched width would silently duplicate seeds
        # into every lane (or drop lanes on the squeeze path)
        raise ValueError(
            f"seed_bits width {seed_bits.shape[1]} != state width {W}; "
            f"pass words= consistently to topo_seeds_to_bits/build_topo_wave32"
        )
    packed = _clear_null_row(_pack_rows(invalid | seed_bits), n_tot, W)
    packed = _sweep_packed(level_starts, garrays, node_epoch, packed, W, start_level)
    after = _unpack_rows(packed, n_tot, W)
    newly = lax.population_count(
        jnp.where(garrays.is_real[:, None], after & ~invalid, 0)
    )
    # per-WORD counts: one word's count is ≤ 32*n (int32-safe); the total
    # across many packed waves can exceed int32, so callers sum in int64
    counts = newly.sum(axis=0, dtype=jnp.int32)
    if squeeze:
        return TopoState(node_epoch, after[:, 0]), counts[0]
    return TopoState(node_epoch, after), counts


@functools.lru_cache(maxsize=8)
def topo_mirror_gate_step(n_tot: int):
    """Jitted burst PROLOGUE over a topo mirror: project the dense live
    invalid state into topo order (device gather — no host upload) and gate
    the seeds with dense-BFS semantics — an already-invalid node neither
    re-fires, counts, nor conducts (ops/wave.py::wave_step rule; a plain
    closure sweep over ``invalid | seeds`` would also propagate PRE-EXISTING
    invalidity, diverging from the dense path). The gate is expressed
    THROUGH the sweep's own epoch machinery so _topo_sweep_impl is reused
    verbatim: a blocked row gets epoch -3, so none of its in-edges (captured
    at epoch 0) version-match — it can never fire; its bit starts 0 and is
    never seeded, so nothing propagates THROUGH it either.

    Split from the sweep and the epilogue (:func:`topo_mirror_finish_step`)
    so the PASS COUNT of a patched mirror is a host loop over the jitted
    sweep — violations accumulating on a patched mirror never recompile
    anything (r4; the monolithic burst program re-traced per pass count)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gate(is_real, node_epoch0, perm_clipped, g_invalid, seed_new_ids):
        node_epoch = _gate_epochs(n_tot, is_real, node_epoch0, perm_clipped, g_invalid)
        # union seeds CONDUCT even when already invalid (see ops/wave.py
        # run_waves_union: an uncascaded columnar mark's declared dependents
        # exist only on device); blocked rows still can't RECEIVE (epoch -3)
        # and pre-invalid seeds are excluded from newly by the finish step
        seed_bits = (
            jnp.zeros(n_tot + 1, jnp.int32).at[seed_new_ids].set(1).at[n_tot].set(0)
        )
        return node_epoch, seed_bits

    return gate


@functools.lru_cache(maxsize=8)
def topo_mirror_finish_step(cap: int, n_tot: int):
    """Jitted burst EPILOGUE: count the newly-invalidated real rows from the
    final sweep bits, compact their ORIGINAL ids to ``cap`` (O(cap)
    readback), and scatter them back into the dense invalid array."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def finish(is_real, perm_clipped, g_invalid, final_bits):
        # ~pre-invalid: a conducting already-invalid seed is not NEWLY
        newly = final_bits.astype(bool) & is_real & ~g_invalid[perm_clipped]
        count = newly.sum(dtype=jnp.int32)
        pos = jnp.cumsum(newly.astype(jnp.int32)) - 1
        scatter_pos = jnp.where(newly & (pos < cap), pos, cap)  # OOB → dropped
        ids = (
            jnp.full(cap, -1, dtype=jnp.int32)
            .at[scatter_pos]
            .set(perm_clipped, mode="drop")
        )
        oob = g_invalid.shape[0]
        g_invalid2 = g_invalid.at[jnp.where(newly, perm_clipped, oob)].set(
            True, mode="drop"
        )
        return g_invalid2, count, ids, count > cap

    return finish


def run_topo_sweep_passes(level_starts, garrays, seed_bits, node_epoch, passes: int):
    """HOST loop over jitted sweep passes, chaining device state — the
    multi-pass execution of a patched mirror (level-violating edges need
    one extra pass each; see _try_patch_mirror). The sweep program is keyed
    only on level_starts: ANY pass count reuses it (start_level is pinned
    to 0 — level 0 is sources-only, so the extra slice is near-free, and a
    passes 1→2 transition must not re-key the program mid-serving: the
    compile would land inside a timed burst)."""
    import jax.numpy as jnp

    step = topo_sweep_step(level_starts, 0)
    state = TopoState(node_epoch, jnp.zeros_like(seed_bits))
    sb = seed_bits
    for _ in range(passes):
        state, _ = step(garrays, sb, state)
        sb = jnp.zeros_like(seed_bits)  # only the first pass seeds
    return state


def _sweep_schedule(
    level_starts, garrays, node_epoch, packed, words: int, passes: int
):
    """The fused programs' pass schedule over a SEEDED packed state:
    ``passes`` sweeps (a patched mirror's level-violating edges need one
    extra pass each), or, for ``passes <= 0`` (adaptive, ISSUE 17), one
    sweep and then extra sweeps under a device-side ``lax.while_loop``
    until the bits reach a FIXED POINT. The bits are monotone under OR, so
    termination is guaranteed and the fixed point equals what any fixed
    pass count ≥ the true violation depth computes — the burst stops
    exactly when quiescent instead of paying a worst-case pass schedule
    on every dispatch (the fused-chain analogue of the routed plane's
    counted quiescence check). The state is lane-dense, so the loop's
    carry has no padding to grow."""
    import jax.numpy as jnp
    from jax import lax

    def sweep(st):
        return _sweep_packed(level_starts, garrays, node_epoch, st, words, 0)

    if passes > 0:
        for _ in range(passes):
            packed = sweep(packed)
        return packed

    def body(carry):
        st2 = sweep(carry[0])
        return st2, (st2 != carry[0]).any()

    packed, _ = lax.while_loop(
        lambda carry: carry[1], body, (sweep(packed), jnp.array(True))
    )
    return packed


def _pack_bool_bits(mask):
    """Burst epilogues ship the newly-union as 1 bit/node to the host
    instead of capped id buffers + a separate pack
    dispatch (VERDICT r4 #2/#6); one shared definition in ops/bitops."""
    from .bitops import pack_bool_bits

    return pack_bool_bits(mask)


def _lane_counts_blocked(newly_packed, words: int, block: int = 1 << 12):
    """Per-lane popcounts of the packed ``[R, P * Wp]`` bits in ONE pass
    over HBM.

    The obvious ``stack([((bits[:, w] >> b) & 1).sum() ...])`` emits 32·W
    separate strided reductions which XLA does NOT fuse at scale — at 10M
    rows × W=16 that re-reads the 700 MB bit array hundreds of times
    (~30 s/burst measured). Here a fori_loop unpacks one [32, block, 128]
    tile at a time (the bit index leads, so every vector is a full 128-lane
    row) and accumulates [32, 128] partials; the ``P`` node groups of a row
    fold at the end: total traffic = one read of the bits + a 64 MB
    transient."""
    import jax.numpy as jnp
    from jax import lax

    rows, lanes = newly_packed.shape
    P, wp = _row_geometry(words)
    nb = -(-rows // block)
    padded = jnp.pad(newly_packed, ((0, nb * block - rows), (0, 0)))
    shifts = jnp.arange(32, dtype=jnp.int32)[:, None, None]

    def body(i, acc):
        blk = lax.dynamic_slice(padded, (i * block, 0), (block, lanes))
        bits = (blk[None, :, :] >> shifts) & 1
        return acc + bits.sum(axis=1, dtype=jnp.int32)

    acc = lax.fori_loop(0, nb, body, jnp.zeros((32, lanes), jnp.int32))
    per_word = acc.reshape(32, P, wp).sum(axis=1)[:, :words]  # [bit, word]
    return per_word.T.reshape(words * 32)  # lane l = word l//32, bit l%32


def _lanes_finish(packed, n_tot: int, words: int, is_real, perm_clipped, g_invalid):
    """The lane bursts' shared epilogue on the packed final bits: per-lane
    closure popcounts and the newly-union scattered back into the dense
    invalid array. A conducting already-invalid seed is not NEWLY in any
    lane (same rule as the union finish). Returns ``(g_invalid2,
    lane_counts int32[32*words], newly_dense bool[dense])``."""
    import jax.numpy as jnp

    P, wp = _row_geometry(words)
    rows = packed.shape[0]
    keep = is_real & ~g_invalid[perm_clipped]
    keep_rows = jnp.pad(keep, (0, rows * P - n_tot - 1)).reshape(rows, P)
    newly_bits = jnp.where(jnp.repeat(keep_rows, wp, axis=1), packed, 0)
    lane_counts = _lane_counts_blocked(newly_bits, words)
    union = (newly_bits != 0).reshape(rows, P, wp).any(axis=2)
    union = union.reshape(rows * P)[: n_tot + 1]
    oob = g_invalid.shape[0]
    newly_dense = (
        jnp.zeros_like(g_invalid)
        .at[jnp.where(union, perm_clipped, oob)]
        .set(True, mode="drop")
    )
    return g_invalid | newly_dense, lane_counts, newly_dense


def _gate_epochs(n_tot: int, is_real, node_epoch0, perm_clipped, g_invalid):
    """Dense-BFS gate through the sweep's own epoch machinery (see
    :func:`topo_mirror_gate_step`): an already-invalid row gets epoch -3,
    so none of its in-edges version-match."""
    import jax.numpy as jnp

    blocked = jnp.where(is_real, g_invalid[perm_clipped], False).at[n_tot].set(False)
    return jnp.where(blocked, -3, node_epoch0)


def _lane_seed_words(seed_new_ids, words: int, slot_words: int):
    """``(flat, vals)`` of the lane seeds' scatter-add: group g seeds word
    ``g // 32`` bit ``g % 32`` of its ids; ``flat`` is the row-major offset
    ``id * slot_words + word`` (of ``[n_tot+1, W]`` with ``slot_words = W``,
    of the packed state with ``slot_words = Wp``)."""
    import jax.numpy as jnp

    lanes = jnp.arange(32 * words, dtype=jnp.int32)
    # lane 31 wraps negative: same bit pattern
    bit_of = jnp.left_shift(jnp.int32(1), lanes % 32)
    flat = seed_new_ids * slot_words + (lanes // 32)[:, None]
    vals = jnp.broadcast_to(bit_of[:, None], seed_new_ids.shape)
    return flat.ravel(), vals.ravel()


@functools.lru_cache(maxsize=8)
def topo_mirror_fused_union_step(
    level_starts: Tuple[int, ...], cap: int, n_tot: int, passes: int = 1
):
    """ONE-dispatch union burst (gate + sweep×passes + finish fused).

    Every blocking dispatch costs a host round trip, so the split
    gate/sweep/finish pipeline pays 3-4 of them per lone wave. Small pass counts (a patched mirror carrying a few
    level violations — r5: one fused program per pass count ≤ 3, each
    compiled once per level layout and persisted) stay on the one-dispatch
    path; beyond that the split pipeline's host loop takes over so pass
    growth never recompiles anything."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def burst(garrays: TopoGraphArrays, node_epoch0, perm_clipped, g_invalid, seed_new_ids):
        is_real = garrays.is_real
        node_epoch = _gate_epochs(n_tot, is_real, node_epoch0, perm_clipped, g_invalid)
        seed_bits = (
            jnp.zeros(n_tot + 1, jnp.int32).at[seed_new_ids].set(1).at[n_tot].set(0)
        )
        packed = _sweep_schedule(
            level_starts, garrays, node_epoch, _pack_rows(seed_bits), 1, passes
        )
        final_bits = _unpack_rows(packed, n_tot, 1)[:, 0]
        newly = final_bits.astype(bool) & is_real & ~g_invalid[perm_clipped]
        count = newly.sum(dtype=jnp.int32)
        pos = jnp.cumsum(newly.astype(jnp.int32)) - 1
        scatter_pos = jnp.where(newly & (pos < cap), pos, cap)
        ids = (
            jnp.full(cap, -1, dtype=jnp.int32)
            .at[scatter_pos]
            .set(perm_clipped, mode="drop")
        )
        oob = g_invalid.shape[0]
        g_invalid2 = g_invalid.at[jnp.where(newly, perm_clipped, oob)].set(
            True, mode="drop"
        )
        return g_invalid2, count, ids, count > cap

    return burst


@functools.lru_cache(maxsize=8)
def topo_mirror_fused_lanes_step(
    level_starts: Tuple[int, ...], n_tot: int, words: int, passes: int = 1
):
    """ONE-dispatch lane burst (gate + sweep×``passes`` + finish fused) —
    see :func:`topo_mirror_fused_union_step` for the pass-count policy:
    small counts each get their own fused program (saving 2-3 host round
    trips per burst), heavier violation loads fall to the split
    pipeline's host loop. The newly-union comes back as a
    device-packed DENSE bitmask (1 bit/node): burst unions at stress scale
    are millions of rows, so a capped id compaction overflowed every burst
    and cost a separate pack dispatch + mask diff (VERDICT r4 #2/#6)."""
    import jax
    import jax.numpy as jnp

    W = words

    @jax.jit
    def burst(garrays: TopoGraphArrays, node_epoch0, perm_clipped, g_invalid, seed_new_ids):
        g_invalid2, lane_counts, newly_dense = _lanes_stage_body(
            level_starts, n_tot, W, passes,
            garrays, node_epoch0, perm_clipped, g_invalid, seed_new_ids,
        )
        union_count = newly_dense.sum(dtype=jnp.int32)
        return g_invalid2, lane_counts, union_count, _pack_bool_bits(newly_dense)

    return burst


def _lanes_stage_body(
    level_starts, n_tot: int, W: int, passes: int,
    garrays: TopoGraphArrays, node_epoch0, perm_clipped, g_invalid, seed_new_ids,
):
    """One lane-burst stage against ``g_invalid`` (the shared body of the
    single-burst program and the chained scan below): gate → sweep×passes →
    newly accounting. Returns (g_invalid2, lane_counts, newly_dense)."""
    import jax.numpy as jnp

    is_real = garrays.is_real
    node_epoch = _gate_epochs(n_tot, is_real, node_epoch0, perm_clipped, g_invalid)
    # the seeds scatter straight into the packed state: id * Wp + word is
    # its row-major offset, so the reshape is a bitcast
    P, wp = _row_geometry(W)
    rows = -(-(n_tot + 1) // P)
    flat, vals = _lane_seed_words(seed_new_ids, W, wp)
    packed = jnp.zeros(rows * P * wp, jnp.int32).at[flat].add(vals)
    # pad seeds name the null row
    packed = _clear_null_row(packed.reshape(rows, P * wp), n_tot, W)
    packed = _sweep_schedule(level_starts, garrays, node_epoch, packed, W, passes)
    return _lanes_finish(packed, n_tot, W, is_real, perm_clipped, g_invalid)


@functools.lru_cache(maxsize=8)
def topo_mirror_fused_lanes_chain_step(
    level_starts: Tuple[int, ...], n_tot: int, words: int, passes: int,
    depth: int,
):
    """``depth`` consecutive lane bursts in ONE dispatch — the loop-carried-
    dependence composition of the wave chain (PAPERS.md "Julia GraphBLAS
    with Nonblocking Execution"): a ``lax.scan`` carries the dense invalid
    state from stage to stage, so stage ``i`` sees exactly the state stages
    ``< i`` left, with NO host round trip between them. Semantics per stage
    = :func:`topo_mirror_fused_lanes_step` (groups within a stage are
    snapshot-independent; stages apply sequentially) — a fused chain of K
    stages is oracle-identical to K sequential burst dispatches.

    Takes ``seed_mats`` int32[depth, 32*words, S] (NEW-id seed rows, padded
    with ``n_tot``) and returns ``(g_invalid2, lane_counts
    int32[depth, 32*words], packed_stages uint32[depth, ceil(dense/32)])``
    — per-STAGE newly masks, so the host can apply (and fence) each
    logical wave under its own identity while the next chain runs."""
    import jax
    from jax import lax

    W = words

    @jax.jit
    def chain(garrays: TopoGraphArrays, node_epoch0, perm_clipped, g_invalid, seed_mats):
        def stage(g_inv, seed_new_ids):
            g_inv2, lane_counts, newly_dense = _lanes_stage_body(
                level_starts, n_tot, W, passes,
                garrays, node_epoch0, perm_clipped, g_inv, seed_new_ids,
            )
            return g_inv2, (lane_counts, _pack_bool_bits(newly_dense))

        g_invalid2, (lane_counts, packed_stages) = lax.scan(
            stage, g_invalid, seed_mats
        )
        return g_invalid2, lane_counts, packed_stages

    return chain


def topo_mirror_superround_step(
    level_starts, n_tot: int, words: int, passes: int,
    base: int, n_rows: int, fn, update_valid: bool,
):
    """K live rounds of (lane-burst sweep → columnar refresh through the
    memo-table device loader → packed fence-mask extraction) as ONE jitted
    loop-carried ``lax.scan`` — the resident super-round program (ISSUE 14,
    the FuseFlow-style fusion ACROSS pipeline-stage boundaries). The carry
    holds the dense invalid state AND the memo columns (values + validity),
    so round ``i+1`` cascades against exactly the state round ``i`` left —
    burst, refresh, and fence extraction for the whole super-round run with
    zero host round trips between rounds.

    Per-round semantics = :func:`topo_mirror_fused_lanes_step` followed by
    the block's device refresh (``TpuGraphBackend.refresh_block_on_device``)
    — a super-round of K rounds is oracle-identical to K sequential
    (burst → refresh) pairs. The depth comes from ``seed_mats.shape[0]`` at
    trace time, so ONE returned program object serves every pinned depth
    (jit re-traces per shape; the persistent XLA cache keeps each compiled
    executable across restarts). Returns ``(g_invalid2, values2, valid2,
    lane_counts int32[K, 32*words], packed uint32[K, ceil(dense/32)])`` —
    per-ROUND packed fence masks, so the host drain applies (and fences)
    each logical wave under its own identity while the next super-round
    executes.

    ``fn`` is the memo table's device loader ``(ids, *largs) -> rows``;
    its state rides as trailing runtime args, never closure constants."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .bitops import pack_bool_bits

    W = words

    @jax.jit
    def superround(values, valid_dev, garrays, node_epoch0, perm_clipped,
                   g_invalid, seed_mats, *largs):
        def round_step(carry, seed_new_ids):
            g_inv, values, valid_dev = carry
            g_inv2, lane_counts, newly_dense = _lanes_stage_body(
                level_starts, n_tot, W, passes,
                garrays, node_epoch0, perm_clipped, g_inv, seed_new_ids,
            )
            # columnar refresh: the block's invalid rows recompute through
            # the table's device loader and their invalid bits clear, so
            # the NEXT round cascades against a consistent block
            stale = lax.slice_in_dim(g_inv2, base, base + n_rows)
            ids = jnp.arange(n_rows, dtype=jnp.int32)
            fresh = fn(ids, *largs)
            mask = stale.reshape((n_rows,) + (1,) * (values.ndim - 1))
            values2 = jnp.where(mask, fresh, values)
            inv3 = lax.dynamic_update_slice_in_dim(
                g_inv2, jnp.zeros(n_rows, dtype=g_inv2.dtype), base, 0,
            )
            valid2 = (valid_dev | stale) if update_valid else valid_dev
            return (inv3, values2, valid2), (
                lane_counts, pack_bool_bits(newly_dense)
            )

        # unroll=True: as a while loop, XLA:TPU lays the loop's view of
        # the [n_tot, k] mirror tables out row-major with the minor dim
        # padded to 128 lanes (21x: 5.25 GB temps at 10 M nodes, a
        # compile-time HBM OOM on a 16 GB v5e at any depth > 1, PR 21).
        # Unrolled, the rounds compile as straight-line code and the
        # tables keep their compact layout, the nodes along the lanes.
        # The sweep state no longer has a layout to lose: it is lane-dense
        # (:func:`_row_geometry`) in or out of a loop.
        (inv_f, values_f, valid_f), (lane_counts, packed) = lax.scan(
            round_step, (g_invalid, values, valid_dev), seed_mats,
            unroll=True,
        )
        return inv_f, values_f, valid_f, lane_counts, packed

    return superround


@functools.lru_cache(maxsize=8)
def topo_mirror_gate_lanes_step(n_tot: int, words: int):
    """Lane-packed gate: ``32*words`` INDEPENDENT command groups, group g
    seeding word ``g//32`` bit ``g%32``. Each lane gets dense-BFS semantics
    from the graph's CURRENT invalid state (same gate as the union burst);
    groups are snapshot-independent, exactly like the static bench's packed
    waves. ``seed_new_ids`` is int32[32*words, S] of NEW (topo-order) ids,
    padded with ``n_tot``; ids must be UNIQUE within a lane (seed bits
    accumulate by scatter-add — the caller dedups, which it does anyway to
    define a group). The device-side seed scatter keeps the upload O(total
    seeds), never the O(n·W) bit matrix (16 MB/burst at 1M nodes)."""
    import jax
    import jax.numpy as jnp

    W = words

    @jax.jit
    def gate(is_real, node_epoch0, perm_clipped, g_invalid, seed_new_ids):
        node_epoch = _gate_epochs(n_tot, is_real, node_epoch0, perm_clipped, g_invalid)
        # within-lane unique ⇒ add ≡ or (disjoint bits across lanes)
        flat, vals = _lane_seed_words(seed_new_ids, W, W)
        seed_bits = (
            jnp.zeros((n_tot + 1) * W, jnp.int32)
            .at[flat]
            .add(vals)
            .reshape(n_tot + 1, W)
            .at[n_tot]
            .set(0)
        )
        # seeds CONDUCT even when already invalid (same rule as the union
        # gate / ops/wave.py run_waves_union); blocked rows still can't
        # receive, and the finish step excludes pre-invalid rows from counts
        return node_epoch, seed_bits

    return gate


@functools.lru_cache(maxsize=8)
def topo_mirror_finish_lanes_step(n_tot: int, words: int):
    """Lane-packed epilogue: per-lane closure popcounts + the newly-union
    as a device-packed DENSE bitmask in one readback, dense-state writeback
    on device (see :func:`topo_mirror_fused_lanes_step` on why packed).
    Returns (g_invalid2, lane_counts int32[32*words], union count,
    packed_newly uint32[ceil(dense/32)])."""
    import jax
    import jax.numpy as jnp

    W = words

    @jax.jit
    def finish(is_real, perm_clipped, g_invalid, final_bits):
        g_invalid2, lane_counts, newly_dense = _lanes_finish(
            _pack_rows(final_bits), n_tot, W, is_real, perm_clipped, g_invalid
        )
        union_count = newly_dense.sum(dtype=jnp.int32)
        return g_invalid2, lane_counts, union_count, _pack_bool_bits(newly_dense)

    return finish


@functools.lru_cache(maxsize=8)
def topo_sweep_step(level_starts: Tuple[int, ...], start_level: int = 1):
    """Jitted sweep for one level layout: ``step(garrays, seed_bits, state)``.

    Level boundaries are compile-time (they shape the program); the graph
    arrays stay runtime args so content updates never recompile.
    ``start_level=0`` includes level 0 — needed only by multi-pass sweeps
    over patched mirrors (an edge into a level-0 row)."""
    import jax

    return jax.jit(
        functools.partial(_topo_sweep_impl, level_starts, start_level=start_level)
    )


def build_topo_wave32(graph: TopoGraph, words: int = 1):
    """(state0, wave32) — same contract as build_pull_wave32, but the whole
    ``32*words``-wave cascade costs one table pass. ``wave32(seed_bits,
    state)`` → (state, newly-invalidated count over real nodes)."""
    garrays = topo_graph_arrays(graph)
    step = topo_sweep_step(graph.level_starts)

    def wave32(seed_bits, state):
        return step(garrays, seed_bits, state)

    wave32.garrays = garrays
    wave32.step = step
    wave32.impl = functools.partial(_topo_sweep_impl, graph.level_starts)
    return topo_init_state(graph.n_tot, words), wave32
