"""DeviceGraph — host-managed container around the device CSR mirror.

The management half of the TPU graph backend: capacity-padded device arrays
(see stl_fusion_tpu.ops.wave for the layout), batched edge ingestion, epoch
bumps on recompute, and the wave API. This is what the reference implements
as ComputedRegistry + per-node edge sets (src/Stl.Fusion/ComputedRegistry.cs,
Computed.cs:347-419) — re-shaped so the invalidation hot path runs on TPU.

Capacities are static per compiled program; growth doubles capacity and
re-pads (one recompile per doubling, amortized like a vector push_back).
Edge ingestion is append-only with tombstoning-by-epoch: edges whose
``edge_dst_epoch`` no longer matches are dead weight until ``compact()``
rebuilds the arrays (the device analogue of the reference's
ComputedGraphPruner edge sweep).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..diagnostics.tracing import hot_span
from ..ops.wave import (
    GraphArrays,
    run_wave,
    run_wave_collect,
    run_wave_with_stats,
    run_waves_chained,
    run_waves_union,
    seeds_to_frontier,
)

__all__ = ["DeviceGraph"]


def _round_up_pow2(x: int) -> int:
    n = 1
    while n < x:
        n <<= 1
    return n


@functools.lru_cache(maxsize=1)
def _mirror_builder_hash() -> str:
    """Hash of the modules that lay out a topo mirror (build_topo_graph,
    build_ell/widen_ell): part of the disk-cache key, so an entry built by
    other code is a miss, not a mirror of the wrong layout (the same rule
    native/__init__.py keys its compiled ``.so`` on)."""
    import hashlib

    from ..ops import ell_wave, topo_wave

    digest = hashlib.sha256()
    for module in (topo_wave, ell_wave):
        with open(module.__file__, "rb") as f:
            digest.update(f.read())
    return digest.hexdigest()[:16]


@functools.lru_cache(maxsize=4)
def _unpack_mask_kernel(n: int):
    """uint32[ceil(n/32)] little-endian words → bool[n] ON DEVICE: host-led
    bulk invalid updates (a 10M-row refresh flush) upload 1 bit/node
    over PCIe instead of the 8x bool array."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def unpack(packed):
        bits = (packed[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
        return bits.reshape(-1)[:n].astype(bool)

    return unpack


def _pack_mask_host(mask: np.ndarray) -> np.ndarray:
    """Host-side little-endian bit pack matching :func:`_unpack_mask_kernel`
    (pad to whole uint32 words)."""
    packed8 = np.packbits(mask, bitorder="little")
    pad = (-len(packed8)) % 4
    if pad:
        packed8 = np.concatenate([packed8, np.zeros(pad, dtype=np.uint8)])
    return packed8.view(np.uint32)


@functools.lru_cache(maxsize=1)
def _fused_bump():
    """One jitted op for an epoch bump (+1 on unique ids, invalid cleared):
    pads repeat the first id, so add lanes past ``n_live`` are masked to 0
    (the invalid clear is idempotent and needs no mask)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bump(node_epoch, invalid, ids, n_live):
        live = jnp.arange(ids.shape[0], dtype=jnp.int32) < n_live
        return (
            node_epoch.at[ids].add(jnp.where(live, 1, 0)),
            invalid.at[ids].set(False),
        )

    return bump


@functools.lru_cache(maxsize=1)
def _fused_triple_scatter():
    """One jitted scatter updating the three edge arrays of an incremental
    append (src, dst, epoch) IN PLACE: the arrays are donated (see
    ops/bitops.py::fused_pair_scatter), so an append writes its slots
    instead of copying three ``e_cap``-long arrays (~0.4 GB at 2^25). One
    dispatch instead of three eager ones (paid per scalar-churn flush)."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def scat(t1, t2, t3, rows, v1, v2, v3):
        return t1.at[rows].set(v1), t2.at[rows].set(v2), t3.at[rows].set(v3)

    return scat


def _fused_pair_scatter():
    """Shared paired-table row scatter (ops/bitops)."""
    from ..ops.bitops import fused_pair_scatter

    return fused_pair_scatter()


def _fused_quad_scatter():
    """Shared double-mirror row scatter (ops/bitops): topo + lat patch
    applications in ONE dispatch instead of two."""
    from ..ops.bitops import fused_quad_scatter

    return fused_quad_scatter()


def _pack_mask_kernel():
    """Jitted bool→uint32 bit pack (overflow readbacks ship 1 bit/node
    to the host); one shared definition in ops/bitops."""
    from ..ops.bitops import pack_bool_bits_jit

    return pack_bool_bits_jit()


def check_structure_cache(entry: dict, struct_version: int, fp_fn) -> bool:
    """THE shared freshness check for structure-fingerprint caches (the topo
    mirror here, the sharded mirror in graph/backend.py): O(1) when the
    entry was already validated — or already known stale — at this
    struct_version, at most one O(edges) fingerprint hash per structural
    mutation otherwise. Mutates ``entry['validated_at']``/``['missed_at']``."""
    if entry["validated_at"] == struct_version:
        return True
    if entry.get("missed_at") == struct_version:
        return False
    if fp_fn() == entry["fp"]:
        entry["validated_at"] = struct_version
        return True
    entry["missed_at"] = struct_version
    return False


def run_on_device(obj, device, names) -> None:
    """Rebind ``obj``'s named SYNCHRONOUS methods so that each runs under
    ``jax.default_device(device)``: every array a call stages
    (``jnp.asarray`` of a host buffer, ``zeros``, ``full``, the loader
    arguments user code makes) then goes to that device directly, never by
    way of ``jax.devices()[0]``. The scope is thread-local and closes when
    the call returns, so it is never held across an ``await``. Called only
    where a device was given: an object built without one keeps its class's
    methods untouched (no added call on any path)."""
    import jax

    def placed(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.default_device(device):
                return fn(*args, **kwargs)

        return run

    for name in names:
        setattr(obj, name, placed(getattr(obj, name)))


def array_placement(array) -> dict:
    """``{"devices": [device ids], "committed": bool}`` of one device array:
    an entry of a ``device_layout()``."""
    return {
        "devices": sorted(d.id for d in array.devices()),
        "committed": bool(array.committed),
    }


class DeviceGraph:
    """The device CSR mirror and its topo/lat mirrors. With ``device=None``
    every resident array lives where JAX puts uncommitted arrays (the
    process's default device, ``jax.devices()[0]``) and nothing here names a
    device. With a ``device``, every resident array (the five graph arrays,
    both mirrors' tables, the epoch snapshots) is committed to it and every
    public method runs under :func:`run_on_device`, so staged arguments go
    to it directly: one process can then hold one graph per chip."""

    def __init__(self, node_capacity: int = 1024, edge_capacity: int = 4096,
                 device=None):
        import jax.numpy as jnp

        self._jnp = jnp
        #: the ``jax.Device`` this graph's arrays are committed to; None =
        #: wherever JAX's default placement puts them
        self.device = device
        self.n_cap = _round_up_pow2(max(node_capacity, 16))
        self.e_cap = _round_up_pow2(max(edge_capacity, 16))
        self.n_nodes = 0  # dense ids [0, n_nodes)
        self.n_edges = 0  # live prefix of edge arrays
        # host staging (authoritative for structure)
        self._h_edge_src = np.full(self.e_cap, self.n_cap, dtype=np.int32)
        self._h_edge_dst = np.full(self.e_cap, self.n_cap, dtype=np.int32)
        self._h_edge_dst_epoch = np.full(self.e_cap, -1, dtype=np.int32)
        self._h_node_epoch = np.zeros(self.n_cap + 1, dtype=np.int32)
        self._h_node_epoch[self.n_cap] = -2  # dummy slot never version-matches
        self._h_invalid = np.zeros(self.n_cap + 1, dtype=bool)  # host-authoritative
        self._g: Optional[GraphArrays] = None  # device copy, built lazily
        self._dirty = True
        self._topo_mirror: Optional[dict] = None  # see build_topo_mirror
        # bumped on every structural mutation; the mirror remembers both the
        # version it was last VALIDATED at and the version it last MISSED
        # at, so stable-topology bursts pay O(1) and a stale mirror pays the
        # O(edges) fingerprint re-check at most once per mutation
        self._struct_version = 0
        # bumped on every change to the INVALID state (waves, marks, epoch
        # bumps, clears) — lets the sharded live bridge know whether its
        # device-resident mirror of the invalid state is still current or a
        # host-led change forces a full re-sync (VERDICT r2 #2)
        self.invalid_version = 0
        self.mirror_bursts = 0  # observability: bursts served by the mirror
        self.lat_waves = 0  # observability: unions served by the lat mirror
        #: sweep programs dispatched over the lane-dense topo state (a fused
        #: burst, chain batch or super-round is one; the split pipeline one
        #: per pass)
        self.sweep_packed_dispatches = 0
        #: shape of the last lane-burst execution: {"depth": logical
        #: stages, "dispatches": physical device dispatches} — the backend
        #: reads it to stamp fused-depth identity on profiler records
        self.last_lanes_info: Optional[dict] = None
        self.mirror_cache_hits = 0  # disk-cache loads (build_topo_mirror)
        self.mirror_cache_misses = 0  # full host builds with a cache root set
        # incremental topo-mirror maintenance (VERDICT r3 #1): structural
        # deltas since the mirror was last coherent. None = no delta log
        # (no mirror, or an unpatchable delta broke it — next mirror use
        # falls back to fingerprint/rebuild). Patching keeps churn on the
        # mirror lane path instead of dropping every burst to the dense BFS
        # until a 5+ second rebuild.
        self._mirror_deltas: Optional[list] = None
        # async re-level (VERDICT r3 #1): a background thread rebuilds the
        # topo levels while bursts keep riding the patched mirror; deltas
        # recorded since the snapshot catch the fresh mirror up at install
        self._async_rebuild: Optional[dict] = None
        self._rebuild_deltas: Optional[list] = None
        self.mirror_patches = 0  # patch applications (batches, not deltas)
        #: recaptures the patcher served IN PLACE: topo in-rows (with their
        #: collectors) kept because the batch's adds restored exactly the
        #: in-set the mirror held, and lat slots whose epoch was rewritten
        #: where they lay (the source's own row or its forwarding tree)
        self.mirror_rows_kept = 0
        self.mirror_slots_revived = 0
        self.mirror_rebuilds = 0  # full topo rebuilds
        # adaptive sweep passes (ISSUE 17): a patched mirror runs sweeps
        # under a device-side fixed-point loop (passes=0 sentinel) instead
        # of a worst-case 1+n_viol schedule; counted per adaptive dispatch
        self.adaptive_passes = False
        self.adaptive_stages = 0
        self.mirror_patch_s = 0.0  # cumulative patch time
        # auxiliary structural-delta subscribers (the backend's MESH
        # mirrors, VERDICT r4 #4): each gets the same ordered delta stream
        # the topo mirror consumes; an overflowing or broken log marks
        # itself and its owner falls back to a rebuild
        self._aux_delta_logs: list = []
        if device is not None:
            run_on_device(self, device, [
                name for name in dir(type(self))
                if not name.startswith("_") and callable(getattr(type(self), name))
            ])

    def commit(self, tree):
        """``tree`` with every ``jax.Array`` leaf COMMITTED to this graph's
        device (no copy where it already lies there); as given where there
        is no device. For the sites that MAKE a resident array: an
        uncommitted one follows the process's default device the first time
        something outside :func:`run_on_device`'s scope touches it."""
        if self.device is None:
            return tree
        import jax

        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self.device) if isinstance(x, jax.Array) else x,
            tree,
        )

    def device_layout(self) -> dict:
        """Where each resident array lies: ``{name: {"devices": [device
        ids], "committed": bool}}`` over the graph arrays and both mirrors'
        device tables, as far as they are built. A graph given a device
        shows every entry committed to that device alone; a bring-up check
        that nothing of a member's state sits on another member's chip."""
        arrays: dict = {}
        if self._g is not None:
            arrays.update((f"g.{k}", v) for k, v in self._g._asdict().items())
        m = self._topo_mirror
        if m is not None:
            arrays.update(
                (f"topo.{k}", v) for k, v in m["garrays"]._asdict().items()
            )
            arrays["topo.node_epoch0"] = m["node_epoch0"]
            arrays["topo.perm_clipped"] = m["perm_clipped"]
            if m.get("lat") is not None:
                arrays["lat.ell_dst"] = m["lat"]["ell_dst"]
                arrays["lat.ell_epoch"] = m["lat"]["ell_epoch"]
        return {name: array_placement(a) for name, a in arrays.items()}

    MAX_MIRROR_DELTAS = 65536

    def register_aux_delta_log(self, cap: int = MAX_MIRROR_DELTAS) -> dict:
        """Subscribe to the ordered structural-delta stream (mesh mirror
        maintenance). Returns the log dict: {"deltas", "broken", "cap"}."""
        log = {"deltas": [], "broken": False, "cap": cap}
        self._aux_delta_logs.append(log)
        return log

    def drop_aux_delta_log(self, log: dict) -> None:
        try:
            self._aux_delta_logs.remove(log)
        except ValueError:
            pass

    def _record_mirror_delta(self, kind: str, payload) -> None:
        for log in self._aux_delta_logs:
            if log["broken"]:
                continue
            if len(log["deltas"]) >= log["cap"]:
                log["broken"] = True
                log["deltas"] = []
            else:
                log["deltas"].append((kind, payload))
        if self._rebuild_deltas is not None:
            # catch-up log for the in-flight async rebuild (its own break
            # rule: only overflow — patchability is judged at install
            # against the NEW levels, where old violations dissolve)
            if len(self._rebuild_deltas) >= self.MAX_MIRROR_DELTAS:
                self._rebuild_deltas = None
            else:
                self._rebuild_deltas.append((kind, payload))
        if self._topo_mirror is None:
            return
        d = self._mirror_deltas
        if d is None:
            return  # already broken — rebuild will restart the log
        if len(d) >= self.MAX_MIRROR_DELTAS:
            self._mirror_deltas = None  # unbounded churn: cheaper to rebuild
            return
        d.append((kind, payload))

    # ------------------------------------------------------------------ build
    def add_nodes(self, count: int) -> np.ndarray:
        """Allocate ``count`` dense node ids."""
        start = self.n_nodes
        self.n_nodes += count
        self._struct_version += 1  # n_nodes is part of the fingerprint
        if self.n_nodes > self.n_cap:
            self._grow_nodes(self.n_nodes)
        return np.arange(start, self.n_nodes, dtype=np.int32)

    def add_edges(
        self,
        src: np.ndarray,
        dst: np.ndarray,
        dst_epoch: Optional[np.ndarray] = None,
    ) -> None:
        """Append dependency edges src(used) → dst(dependent) in batch.

        ``dst_epoch`` defaults to each dependent's CURRENT epoch — the
        "edge is valid for this version" capture rule."""
        src = np.asarray(src, dtype=np.int32)
        dst = np.asarray(dst, dtype=np.int32)
        k = len(src)
        if self.n_edges + k > self.e_cap:
            self._grow_edges(self.n_edges + k)
        if dst_epoch is None:
            dst_epoch = self._h_node_epoch[dst]
        dst_epoch = np.broadcast_to(
            np.asarray(dst_epoch, dtype=np.int32), dst.shape
        )
        start = self.n_edges
        sl = slice(start, start + k)
        self._h_edge_src[sl] = src
        self._h_edge_dst[sl] = dst
        self._h_edge_dst_epoch[sl] = dst_epoch
        self.n_edges += k
        if self._g is not None and not self._dirty:
            # incremental device append: an edge batch lands in the padded
            # slots by scatter instead of dirtying the mirror — a full
            # dense-array re-upload (~130 MB at 1M nodes)
            # inside the next burst is exactly the cost live churn can't pay
            jnp = self._jnp
            idx = np.arange(start, start + k, dtype=np.int32)
            # repeats idx[0]: same values rewrite
            pad = self._pad_ids_pow2(idx, self.SCATTER_MIN_WIDTH)
            if len(pad) != k:
                src = np.concatenate([src, np.full(len(pad) - k, src[0], np.int32)])
                dst = np.concatenate([dst, np.full(len(pad) - k, dst[0], np.int32)])
                dst_epoch = np.concatenate(
                    [dst_epoch, np.full(len(pad) - k, dst_epoch[0], np.int32)]
                )
            es, ed, ee = _fused_triple_scatter()(
                self._g.edge_src, self._g.edge_dst, self._g.edge_dst_epoch,
                jnp.asarray(pad), jnp.asarray(src), jnp.asarray(dst),
                jnp.asarray(np.asarray(dst_epoch)),
            )
            self._g = self._g._replace(
                edge_src=es, edge_dst=ed, edge_dst_epoch=ee
            )
        else:
            self._dirty = True
        self._struct_version += 1
        if (
            (self._topo_mirror is not None and self._mirror_deltas is not None)
            or self._rebuild_deltas is not None
            or self._aux_delta_logs
        ):
            # only LIVE-at-append edges exist for the mirror; dead-on-arrival
            # edges (checkpoint loads with stale epochs) are invisible to it.
            # Slice to the REAL batch [:k]: the incremental device-append
            # branch above pow2-pads src/dst in place, and recording the pad
            # repeats would inflate the delta log ~2x toward its break
            # thresholds (duplicates are patch-time no-ops, but the log
            # budget is what keeps churn on the patch path).
            src_r, dst_r = src[:k], dst[:k]
            # dst_epoch is already broadcast to dst.shape above (and the pad
            # branch concatenates matching shapes), so a plain slice works.
            # The delta carries the CAPTURED epoch: the lat mirror patches
            # slots with it, so an edge whose dependent bumps between
            # record and patch time stays dead (captured-at-epoch rule)
            # instead of resurrecting with a current-epoch stamp.
            ep_r = np.asarray(dst_epoch[:k], dtype=np.int32)
            live = ep_r == self._h_node_epoch[dst_r]
            if live.all():
                self._record_mirror_delta(
                    "add", (src_r.copy(), dst_r.copy(), ep_r.copy())
                )
            elif live.any():
                self._record_mirror_delta(
                    "add",
                    (src_r[live].copy(), dst_r[live].copy(), ep_r[live].copy()),
                )

    def bump_epochs(self, node_ids: np.ndarray) -> None:
        """Nodes recomputed: new epoch ⇒ their stale in-edges go dead, and
        their invalid flag clears (a recomputed node is consistent again).
        Ids are UNIQUE-ified first: the host fancy ``+=`` applies once per
        unique id (numpy buffering) while a device ``.at[].add`` would
        accumulate per occurrence — a duplicated batch would silently
        diverge the two epoch copies."""
        node_ids = np.unique(np.asarray(node_ids, dtype=np.int32))
        if node_ids.size == 0:
            return
        self._h_node_epoch[node_ids] += 1
        self._h_invalid[node_ids] = False
        self._struct_version += 1
        self.invalid_version += 1
        if (
            (self._topo_mirror is not None and self._mirror_deltas is not None)
            or self._rebuild_deltas is not None
            or self._aux_delta_logs
        ):
            self._record_mirror_delta("bump", node_ids.copy())
        if self._g is not None and not self._dirty:
            jnp = self._jnp
            ids = jnp.asarray(self._pad_ids_pow2(node_ids, self.SCATTER_MIN_WIDTH))
            # pads repeat the first id: the epoch bump must NOT double-
            # apply, so the fused op masks pad lanes via a length scalar
            ne, inv = _fused_bump()(
                self._g.node_epoch, self._g.invalid, ids,
                jnp.asarray(len(node_ids), dtype=jnp.int32),
            )
            self._g = self._g._replace(node_epoch=ne, invalid=inv)
        else:
            self._dirty = True

    #: narrowest scatter of an edge append or an epoch bump: a served
    #: command's re-reads journal a few dozen edges and a handful of bumps,
    #: a different count every command, and each power of two below this
    #: would be a program of its own, first met (and compiled) whenever
    #: traffic happens to produce it
    SCATTER_MIN_WIDTH = 256

    @staticmethod
    def _pad_ids_pow2(node_ids: np.ndarray, floor: int = 1) -> np.ndarray:
        """Pow2-pad an id batch (no narrower than ``floor``) by REPEATING
        the first id (idempotent for set-style scatters) so the device
        scatter's shape quantizes: live batches vary per call, and every
        fresh shape is a fresh executable (~seconds of compile)."""
        width = max(_round_up_pow2(len(node_ids)), floor)
        if width == len(node_ids):
            return node_ids
        out = np.full(width, node_ids[0], dtype=np.int32)
        out[: len(node_ids)] = node_ids
        return out

    def mark_invalid(self, node_ids: np.ndarray) -> None:
        """Externally-observed invalidations (host-led waves) → mirror state."""
        node_ids = np.asarray(node_ids, dtype=np.int32)
        if node_ids.size == 0:
            return
        self._h_invalid[node_ids] = True
        self.invalid_version += 1
        self._device_invalid_update(node_ids, True)

    def mark_invalid_mask(self, mask: np.ndarray) -> None:
        """:meth:`mark_invalid` for a closure that arrives as a bool mask
        over node ids (a routed wave that overflowed its id buffers: 40 M
        rows): OR-ed into the host mask, and the device copy takes the
        bit-packed upload without the ids ever being built. A mask of few
        rows goes the id way, as a batch of that size does."""
        mask = np.asarray(mask, dtype=bool)
        hits = int(np.count_nonzero(mask))
        if hits == 0:
            return
        self._h_invalid[: len(mask)] |= mask
        self.invalid_version += 1
        if hits * 4 <= self.n_cap + 1:
            self._device_invalid_update(np.flatnonzero(mask).astype(np.int32), True)
        elif self._g is not None and not self._dirty:
            # _device_invalid_update's bulk upload, with no ids to size it by
            packed = self._jnp.asarray(_pack_mask_host(self._h_invalid))
            self._g = self._g._replace(
                invalid=self.commit(_unpack_mask_kernel(len(self._h_invalid))(packed))
            )

    def _device_invalid_update(self, node_ids: np.ndarray, value: bool) -> None:
        """Apply a host-side invalid-state change to the device copy. Small
        batches scatter by (pow2-padded) ids; batches whose id payload
        exceeds the full bool mask (ids are 4 B/entry, the mask 1 B/node)
        upload the host-authoritative mask instead — a 10M-row refresh costs
        11 MB, not 40 MB, host→device."""
        if self._g is None or self._dirty:
            return
        if node_ids.size * 4 > self.n_cap + 1:
            # bulk path: ship the host-authoritative mask BIT-PACKED
            # (1 bit/node host→device — an 11 MB bool upload per
            # 10M-row refresh flush was a dominant per-round cost) and
            # unpack on device. The packed temp is fresh, so no aliasing.
            n = len(self._h_invalid)
            packed = self._jnp.asarray(_pack_mask_host(self._h_invalid))
            self._g = self._g._replace(
                invalid=self.commit(_unpack_mask_kernel(n)(packed))
            )
            return
        ids = self._jnp.asarray(self._pad_ids_pow2(node_ids))
        self._g = self._g._replace(invalid=self._g.invalid.at[ids].set(value))

    def clear_invalid_ids(self, node_ids: np.ndarray) -> None:
        """Refreshed rows are consistent again WITHOUT an epoch bump — the
        columnar refresh recomputes VALUES, not edges, so declared row
        topology must survive (an epoch bump would kill the block's declared
        in-edges). The scalar path keeps using :meth:`bump_epochs`."""
        node_ids = np.asarray(node_ids, dtype=np.int32)
        if node_ids.size == 0:
            return
        self._h_invalid[node_ids] = False
        self.invalid_version += 1
        self._device_invalid_update(node_ids, False)

    def _grow_nodes(self, need: int) -> None:
        new_cap = _round_up_pow2(need)
        node_epoch = np.zeros(new_cap + 1, dtype=np.int32)
        node_epoch[: self.n_cap] = self._h_node_epoch[: self.n_cap]
        node_epoch[new_cap] = -2
        invalid = np.zeros(new_cap + 1, dtype=bool)
        invalid[: self.n_cap] = self._h_invalid[: self.n_cap]
        # re-point padded edges at the new dummy slot
        pad_mask = self._h_edge_src == self.n_cap
        self._h_edge_src[pad_mask] = new_cap
        self._h_edge_dst[self._h_edge_dst == self.n_cap] = new_cap
        self._h_node_epoch = node_epoch
        self._h_invalid = invalid
        self.n_cap = new_cap
        self._dirty = True

    def _grow_edges(self, need: int) -> None:
        new_cap = _round_up_pow2(need)
        for name in ("_h_edge_src", "_h_edge_dst"):
            arr = np.full(new_cap, self.n_cap, dtype=np.int32)
            arr[: self.n_edges] = getattr(self, name)[: self.n_edges]
            setattr(self, name, arr)
        epoch = np.full(new_cap, -1, dtype=np.int32)
        epoch[: self.n_edges] = self._h_edge_dst_epoch[: self.n_edges]
        self._h_edge_dst_epoch = epoch
        self.e_cap = new_cap
        self._dirty = True

    # ------------------------------------------------------------------ device sync
    def device_arrays(self) -> GraphArrays:
        """Materialize (or reuse) the device copy; host staging is
        authoritative for structure AND invalid state at rebuild time.

        The host arrays are COPIED before jnp.asarray: on the CPU backend
        asarray may alias the numpy buffer zero-copy, and every one of
        these staging arrays is later mutated IN PLACE (epoch +=, edge
        splices, invalid marks) — an aliased device array would absorb
        those host writes nondeterministically on top of its own
        functional updates (observed as double-applied epoch bumps,
        timing-dependent). One memcpy per rebuild buys determinism."""
        if self._g is None or self._dirty:
            jnp = self._jnp
            self._g = self.commit(GraphArrays(
                edge_src=jnp.asarray(self._h_edge_src.copy()),
                edge_dst=jnp.asarray(self._h_edge_dst.copy()),
                edge_dst_epoch=jnp.asarray(self._h_edge_dst_epoch.copy()),
                node_epoch=jnp.asarray(self._h_node_epoch.copy()),
                invalid=jnp.asarray(self._h_invalid.copy()),
            ))
            self._dirty = False
        return self._g

    # ------------------------------------------------------------------ waves
    def run_wave(self, seed_ids: Sequence[int], with_stats: bool = False):
        """Cascade from ``seed_ids``; returns newly-invalidated count
        (+ BFS depth with stats). The device arrays keep the result state."""
        jnp = self._jnp
        g = self.device_arrays()
        seeds = seeds_to_frontier(self.n_cap, jnp.asarray(np.asarray(seed_ids, dtype=np.int32)))
        if with_stats:
            self._g, count, depth = run_wave_with_stats(seeds, g)
            self._sync_invalid_back()
            return int(count), int(depth)
        self._g, count = run_wave(seeds, g)
        self._sync_invalid_back()
        return int(count)

    def run_wave_collect(
        self, seed_ids: Sequence[int], cap: int = 8192
    ) -> Tuple[int, np.ndarray]:
        """Cascade from ``seed_ids`` and return (count, newly-invalidated
        node ids) with an O(wave) readback: ids are compacted ON DEVICE into
        a ``cap``-sized buffer; only on overflow (count > cap, rare wide
        waves) does this fall back to one full-mask readback. The host
        ``_h_invalid`` copy is patched from the ids — never re-fetched."""
        import jax

        jnp = self._jnp
        g = self.device_arrays()
        seeds = seeds_to_frontier(
            self.n_cap, jnp.asarray(np.asarray(seed_ids, dtype=np.int32))
        )
        self._g, count, ids, overflow = run_wave_collect(seeds, g, cap)
        # ONE batched transfer — three sequential readbacks would pay the
        # device→host round trip three times on the lone-wave path
        count, ids, overflow = jax.device_get((count, ids, overflow))
        count = int(count)
        return count, self._patch_host_invalid(count, ids, bool(overflow))

    def _patch_host_invalid(self, count: int, ids: np.ndarray, overflow: bool) -> np.ndarray:
        """Apply a compacted-wave readback to ``_h_invalid``: the id buffer
        when it fit, otherwise a full mask diff against the (already
        updated) device invalid state — read back BIT-PACKED (1 bit/node,
        ~1.4 MB at 10M instead of the 11 MB bool array over PCIe).
        Returns the newly-invalid ids."""
        if count or overflow:
            self.invalid_version += 1
        if overflow:
            # the pack runs as its own dispatch (one extra RTT) — folding it
            # into the wave/finish kernels' batched transfer would save it,
            # at the cost of re-keying every compiled burst program; at
            # ~0.1 s against a multi-second overflow round it stays separate
            packed = np.asarray(_pack_mask_kernel()(self._g.invalid))
            dev_mask = np.unpackbits(
                packed.view(np.uint8), count=len(self._h_invalid), bitorder="little"
            ).astype(bool)
            newly = dev_mask & ~self._h_invalid
            newly_ids = np.nonzero(newly)[0].astype(np.int32)
            self._h_invalid |= newly
        else:
            newly_ids = ids[:count] if count else np.empty(0, np.int32)
            self._h_invalid[newly_ids] = True
        return newly_ids

    def run_waves_chained(self, seed_id_lists: Sequence[Sequence[int]]):
        """Chain many seed waves in ONE dispatch (the live burst path).
        Returns (per-wave counts int64[W], union newly ids). W and the seed
        width are padded to powers of two (a -1 row is a no-op wave, count
        0) so bursts of varying size reuse one compiled program instead of
        retracing the full-graph scan per shape; counts + the union mask
        come back in one batched transfer."""
        import jax

        jnp = self._jnp
        g = self.device_arrays()
        n_real_waves = len(seed_id_lists)
        width = _round_up_pow2(max((len(s) for s in seed_id_lists), default=1))
        n_rows = _round_up_pow2(max(n_real_waves, 1))
        mat = np.full((n_rows, width), -1, dtype=np.int32)
        for i, s in enumerate(seed_id_lists):
            mat[i, : len(s)] = np.asarray(s, dtype=np.int32)
        self._g, counts, newly = run_waves_chained(jnp.asarray(mat), g)
        counts, newly = jax.device_get((counts, newly))
        if newly.any():
            self.invalid_version += 1
        self._h_invalid |= newly
        return (
            counts[:n_real_waves].astype(np.int64),
            np.nonzero(newly)[0].astype(np.int32),
        )

    def run_waves_union(self, seed_id_lists: Sequence[Sequence[int]], mirror: str = "auto"):
        """Union cascade for a burst of seed waves: ONE BFS expansion from
        all seeds together (the live batch path applies only the union, and
        invalidation is idempotent — see ops/wave.py::run_waves_union).
        Returns (total newly count, union newly ids). Seed count is padded
        to a power of two so varying burst sizes reuse one program.

        ``mirror``: "auto" rides the packed topo mirror when one was built
        with :meth:`build_topo_mirror` and the live topology still matches
        its fingerprint (depth-free: one level-ordered sweep instead of a
        level-by-level BFS — the difference between O(edges·depth) and
        O(edges) on deep graphs); "off" forces the dense BFS path."""
        if mirror == "auto" and self._mirror_valid():
            m = self._topo_mirror
            m_nodes = m["n_nodes"]
            flat_ids = [int(i) for s in seed_id_lists for i in s]
            if all(0 <= i < m_nodes for i in flat_ids):
                lat = m.get("lat")
                if lat is not None and 0 < len(flat_ids) <= self.LAT_SEED_MAX:
                    # the O(closure) small-wave path: one dispatch over the
                    # lat mirror instead of a full topo-table sweep — THE
                    # live lone-wave latency fix (VERDICT r4 #1). Overflow
                    # (deep/wide closure) falls through to the sweep.
                    res = self._run_lat_union(lat, flat_ids)
                    if res is not None:
                        return res
                return self._run_mirror_union(seed_id_lists)
            # out-of-contract seed ids (unallocated slots): the dense
            # path can represent them, the mirror cannot — fall through
        import jax

        jnp = self._jnp
        g = self.device_arrays()
        flat = [int(i) for s in seed_id_lists for i in s]
        # width floor 256: small cascades (lone waves, scalar-churn icasc
        # batches) share ONE compiled program instead of one per pow2 width
        width = max(256, _round_up_pow2(max(len(flat), 1)))
        ids = np.full(width, -1, dtype=np.int32)
        ids[: len(flat)] = np.asarray(flat, dtype=np.int32)
        self._g, count, newly = run_waves_union(jnp.asarray(ids), g)
        count, newly = jax.device_get((count, newly))
        if newly.any():
            self.invalid_version += 1
        self._h_invalid |= newly
        return int(count), np.nonzero(newly)[0].astype(np.int32)

    def lat_serves(self, seed_id_lists: Sequence[Sequence[int]]) -> bool:
        """Would :meth:`run_waves_union` send each of these waves, alone, to
        the lat mirror right now? The mirror valid (a pending structural
        delta is patched in here, as any wave would), a lat mirror built,
        every wave of 1..``LAT_SEED_MAX`` seeds, every seed an id the mirror
        knows. A wave so admitted may still overflow the lat caps; it then
        runs on the topo union, as a lone edit does."""
        if not self._mirror_valid():
            return False
        m = self._topo_mirror
        if m.get("lat") is None:
            return False
        m_nodes = m["n_nodes"]
        return all(
            0 < len(s) <= self.LAT_SEED_MAX
            and all(0 <= int(i) < m_nodes for i in s)
            for s in seed_id_lists
        )

    # ------------------------------------------------------------------ topo mirror
    def _mirror_valid(self) -> bool:
        """Is the cached mirror usable RIGHT NOW? O(1) on a topology the
        mirror has already been validated (or known stale) against. A
        structural delta first tries the INCREMENTAL PATCH path (level-
        preserving edge/epoch changes splice into the mirror tables in
        place — no recompile, the program is keyed on level_starts only);
        only an unpatchable delta falls back to the O(edges) fingerprint
        check and, on mismatch, the dense path until a rebuild."""
        m = self._topo_mirror
        if m is None:
            return False
        if m["validated_at"] == self._struct_version:
            return True
        # past the O(1) answers there is work to time: a patch or an
        # O(edges) fingerprint
        with hot_span("mirror.validate"):
            if self._mirror_deltas is not None:
                with hot_span("mirror.patch"):
                    return self._try_patch_mirror(m)
            if m["fp"] is None:
                # patched mirrors shed their fingerprint (it describes the
                # build-time edge sequence, not the patched state): once the
                # delta log broke, only a rebuild revalidates
                m["missed_at"] = self._struct_version
                return False
            return check_structure_cache(
                m, self._struct_version, lambda: self._live_edge_fingerprint()[2]
            )

    def _break_mirror_deltas(self) -> bool:
        self._mirror_deltas = None
        m = self._topo_mirror
        if m is not None:
            m["missed_at"] = self._struct_version
            # a broken log may have been PARTIALLY applied to the lat
            # mirror (host tables mutated, device scatter skipped) — and a
            # carried-across-rebuild lat would then serve lone waves from
            # tables missing live edges (silent under-invalidation, r5
            # review). A broken log costs a lat rebuild, full stop.
            m["lat"] = None
        return False

    MAX_PATCH_EDGES = 65536  # per add-delta; beyond this a rebuild wins

    def _try_patch_mirror(self, m: dict) -> bool:
        """Apply the recorded structural deltas to the topo mirror (and its
        companion lat mirror) IN PLACE, VECTORIZED per delta payload —
        thousands of churn edges per round patch in numpy, not per-edge
        Python (VERDICT r4 #5: the interpreted loop cost ~1.4 s per 1-2
        edge patch and bailed at 4096 edges).

        Patchable deltas (the churn shapes, VERDICT r3 #1):
        - ``bump v``: v's in-edges die → clear v's mirror in-row (levels
          only lose constraints — still a valid topological order); the
          lat mirror needs nothing (its slot epochs stop matching). A
          RECAPTURE is kept instead: where the adds that follow the bump in
          this batch (up to v's next bump) restore exactly the in-set the
          mirror holds for v — the real sources under v's in-row and under
          its collectors — nothing is cleared, no slot moves and no
          violation is counted (``mirror_rows_kept``). A row with fan-in
          past ``k`` could not be re-spliced at all: its sources sit under
          collectors, and the row itself has only the slack slots free;
        - ``add u→v`` where both are mirror-known, the mirror does not hold
          the edge yet (in v's row or under its collectors) and v's row has
          a free slot. A LEVEL-VIOLATING add (``level(u) >= level(v)`` in the
          frozen order — a genuinely new dependency direction) is still
          patchable: each such edge needs one extra sweep pass to
          propagate, so the mirror runs ``1 + n_viol`` passes (monotone OR
          — exact, see ops/topo_wave.py). Capped at 3 violations; beyond
          that a rebuild (which re-levels and resets to 1 pass) is cheaper
          than the extra sweep passes.

        Anything else — an edge from a node born after the build, an
        in-degree overflow past k, too many violations — breaks the log:
        bursts take the dense path until ``build_topo_mirror`` rebuilds.
        Host tables patch per-delta; the device tables get ONE fused
        width-quantized row scatter per mirror per patch call (floor 1024
        rows: each distinct scatter width is a compile, so widths bucket
        coarsely and the programs persist in the cache)."""
        import time as _time

        deltas = self._mirror_deltas
        if not deltas:
            # struct_version advanced without mirror-visible changes
            # (add_nodes, compact): the mirror simply doesn't know the new
            # nodes — seeds there fall back per-burst (bounds check)
            m["validated_at"] = self._struct_version
            return True
        t0 = _time.perf_counter()
        h = m["h_in_src"]
        inv_perm = m["inv_perm"]
        n_tot = m["n_tot"]
        n_known = m["n_nodes"]
        ls = m["level_starts_arr"]
        changed_parts: list = []
        lat = m.get("lat")
        lat_changed_parts: list = []
        # per-row violating sources: a bump that clears a row RETIRES the
        # violations that row contributed (review r4: recounting the same
        # violating edge on every bump+recapture cycle would monotonically
        # accumulate n_viol until the log broke for good)
        viol_by_row: Dict[int, set] = m.setdefault("viol_by_row", {})
        n_viol = int(m.get("n_viol", 0))
        mutated = False
        stride = np.int64(n_tot + 1)  # (in-row, source row) -> one int64 key
        restored = self._restored_in_keys(deltas, inv_perm, n_known, stride)

        def _break_patched():
            if mutated:
                # host tables diverged from the (untouched) device tables:
                # the build fingerprint must never revalidate them
                m["fp"] = None
            return self._break_mirror_deltas()

        for seq, (kind, payload) in enumerate(deltas):
            if kind == "bump":
                v = np.asarray(payload, dtype=np.int64)
                v = v[v < n_known]  # born after build: no mirrored in-edges
                if v.size == 0:
                    continue
                rows = inv_perm[v]
                # a recapture: the adds this bump governs restore the very
                # in-set the mirror holds → the row and its collectors stay
                held = self._mirror_in_keys(m, rows, stride)
                differ = np.setxor1d(held, restored.get(seq, held[:0])) // stride
                rows = rows[np.isin(rows, differ)]
                self.mirror_rows_kept += len(np.setdiff1d(held // stride, differ))
                if rows.size == 0:
                    continue
                h[rows, :] = n_tot
                changed_parts.append(rows)
                mutated = True
                if viol_by_row:
                    for row in np.intersect1d(
                        rows,
                        np.fromiter(viol_by_row.keys(), dtype=np.int64,
                                    count=len(viol_by_row)),
                    ):
                        n_viol -= len(viol_by_row.pop(int(row)))
            else:  # "add"
                src_a, dst_a, ep_a = payload
                if len(src_a) > self.MAX_PATCH_EDGES:
                    return _break_patched()
                u64 = np.asarray(src_a, dtype=np.int64)
                v64 = np.asarray(dst_a, dtype=np.int64)
                if u64.size and (
                    int(u64.max()) >= n_known or int(v64.max()) >= n_known
                ):
                    return _break_patched()
                if lat is not None:
                    lat = self._patch_lat_add_batch(
                        m, lat, u64, v64, np.asarray(ep_a), lat_changed_parts
                    )
                ru = inv_perm[u64]
                rv = inv_perm[v64]
                # drop edges already present (duplicates: closure-identical),
                # in the row itself or under one of its collectors
                present = np.isin(
                    rv * stride + ru,
                    self._mirror_in_keys(m, np.unique(rv), stride),
                )
                ru, rv = ru[~present], rv[~present]
                if ru.size == 0:
                    continue
                # in-batch dedup by (rv, ru); sort groups edges by row
                key = rv * np.int64(n_tot + 1) + ru
                order = np.argsort(key, kind="stable")
                ku = key[order]
                first = np.ones(len(ku), dtype=bool)
                first[1:] = ku[1:] != ku[:-1]
                ru, rv = ru[order][first], rv[order][first]
                # rank within each rv group → the rank-th free slot
                idx = np.arange(len(rv))
                grp_start = np.ones(len(rv), dtype=bool)
                grp_start[1:] = rv[1:] != rv[:-1]
                rank = idx - np.maximum.accumulate(np.where(grp_start, idx, 0))
                free_cum = (h[rv] == n_tot).cumsum(axis=1)
                need = rank + 1
                if (free_cum[:, -1] < need).any():
                    return _break_patched()  # in-degree overflow past k
                slot = (free_cum == need[:, None]).argmax(axis=1)
                # level check: violations pay extra passes, capped
                lu_l = np.searchsorted(ls, ru, side="right") - 1
                lv_l = np.searchsorted(ls, rv, side="right") - 1
                viol = lu_l >= lv_l
                nv = int(viol.sum())
                if nv:
                    n_viol += nv
                    if n_viol > 3 and self._async_rebuild is None:
                        self.start_topo_mirror_rebuild(k=m["k"], cap=m["cap"])
                    if n_viol > 8:
                        return _break_patched()
                    for r_, u_ in zip(rv[viol], ru[viol]):
                        viol_by_row.setdefault(int(r_), set()).add(int(u_))
                h[rv, slot] = ru
                changed_parts.append(rv)
                mutated = True
        if changed_parts and lat is not None and lat_changed_parts:
            # BOTH mirrors changed (the common churn shape: every added
            # edge touches a topo in-row and a lat out-row): ONE fused
            # dispatch instead of two. Each scatter patches its donated
            # tables in place; what the host pays here is the staging of
            # the rows and the program call
            self._scatter_mirror_and_lat_rows(
                m, np.unique(np.concatenate(changed_parts)), n_tot,
                lat, np.unique(np.concatenate(lat_changed_parts)),
            )
        elif changed_parts:
            self._scatter_mirror_rows(
                m, np.unique(np.concatenate(changed_parts)), n_tot
            )
        elif lat is not None and lat_changed_parts:
            self._scatter_lat_rows(
                lat, np.unique(np.concatenate(lat_changed_parts))
            )
        if n_viol != int(m.get("n_viol", 0)):
            # pass counts ≤ FUSED_PASS_MAX each key one fused one-dispatch
            # program (compiled once per level layout, persisted — the
            # bench warms them); beyond that the split pipeline's HOST
            # loop over the jitted sweep serves any count with no
            # recompiles at all
            m["n_viol"] = n_viol
            # adaptive mode replaces the worst-case 1+n_viol schedule with
            # the sweep fixed-point loop (passes=0 sentinel, ISSUE 17)
            m["passes"] = 0 if self.adaptive_passes else 1 + n_viol
        self._mirror_deltas = []
        m["validated_at"] = self._struct_version
        m["fp"] = None  # build-time fingerprint no longer describes the tables
        self.mirror_patches += 1
        self.mirror_patch_s += _time.perf_counter() - t0
        return True

    @staticmethod
    def _mirror_in_keys(m: dict, rows: np.ndarray, stride) -> np.ndarray:
        """The in-set the topo mirror holds for each of ``rows`` (mirror row
        ids): every REAL source in the row itself and under the collectors
        below it, as sorted unique keys ``row * stride + source row``. A
        walk of the collector tree, one gather a level: its depth is
        log_k of the row's fan-in."""
        h, real, n_tot = m["h_in_src"], m["h_row_real"], m["n_tot"]
        owner = cur = np.asarray(rows, dtype=np.int64)
        parts = []
        while cur.size:
            ent = h[cur].astype(np.int64)
            own = np.broadcast_to(owner[:, None], ent.shape)
            is_src = real[ent]  # the null row is not real: pads drop out
            parts.append(own[is_src] * stride + ent[is_src])
            below = (ent != n_tot) & ~is_src  # collectors: one level down
            cur, owner = ent[below], own[below]
        return np.unique(np.concatenate(parts)) if parts else np.empty(0, np.int64)

    @staticmethod
    def _restored_in_keys(deltas, inv_perm, n_known: int, stride) -> Dict[int, np.ndarray]:
        """Per ``bump`` delta of a patch batch (by its index in the batch),
        the in-edges the batch re-adds under it: the adds into a bumped node
        that follow that bump and precede the node's next one, as sorted
        unique :meth:`_mirror_in_keys` keys. A bump with no such add has no
        entry."""
        b_seq, b_v, a_seq, a_u, a_v = [], [], [], [], []
        for seq, (kind, payload) in enumerate(deltas):
            if kind == "bump":
                v = np.asarray(payload, dtype=np.int64)
                b_v.append(v)
                b_seq.append(np.full(len(v), seq, dtype=np.int64))
            else:
                a_u.append(np.asarray(payload[0], dtype=np.int64))
                a_v.append(np.asarray(payload[1], dtype=np.int64))
                a_seq.append(np.full(len(a_u[-1]), seq, dtype=np.int64))
        if not b_v or not a_v:
            return {}
        b_seq, b_v = np.concatenate(b_seq), np.concatenate(b_v)
        a_seq, a_u, a_v = (np.concatenate(x) for x in (a_seq, a_u, a_v))
        known = (a_u < n_known) & (a_v < n_known)
        a_seq, a_u, a_v = a_seq[known], a_u[known], a_v[known]
        # the bump that governs an add: the last bump of its target before it
        n_seq = np.int64(len(deltas) + 1)
        order = np.argsort(b_v * n_seq + b_seq)
        b_key, b_seq = (b_v * n_seq + b_seq)[order], b_seq[order]
        at = np.searchsorted(b_key, a_v * n_seq + a_seq) - 1
        governed = (at >= 0) & (b_key[np.maximum(at, 0)] // n_seq == a_v)
        gov = b_seq[np.maximum(at, 0)][governed]
        keys = inv_perm[a_v[governed]] * stride + inv_perm[a_u[governed]]
        return {int(seq): np.unique(keys[gov == seq]) for seq in np.unique(gov)}

    @staticmethod
    def _quantize_scatter_rows(rows: np.ndarray, null_row: int) -> np.ndarray:
        """Pad a changed-row batch to a coarse width bucket (pow2, floor
        1024) with the null row: every distinct scatter width is a fresh
        compile, so widths bucket coarsely."""
        width = max(1024, _round_up_pow2(len(rows)))
        out = np.full(width, null_row, dtype=np.int64)
        out[: len(rows)] = rows
        return out

    def _scatter_mirror_rows(self, m, rows: np.ndarray, n_tot: int) -> None:
        jnp = self._jnp
        q = self._quantize_scatter_rows(rows, n_tot)
        new_rows = m["h_in_src"][q]  # null-row pads rewrite their own pads
        # mirror epoch convention: slot live ⇔ epoch 0 (matches
        # node_epoch0); pad slots -1 never version-match
        epoch_rows = np.where(new_rows != n_tot, 0, -1).astype(np.int32)
        g = m["garrays"]
        in_src2, epoch2 = _fused_pair_scatter()(
            g.in_src, g.edge_epoch, jnp.asarray(q),
            jnp.asarray(new_rows), jnp.asarray(epoch_rows),
        )
        m["garrays"] = g._replace(in_src=in_src2, edge_epoch=epoch2)

    def _scatter_mirror_and_lat_rows(
        self, m, rows: np.ndarray, n_tot: int, lat: dict, lat_rows: np.ndarray
    ) -> None:
        """Both mirrors' patched rows in ONE device dispatch (see
        ops/bitops.fused_quad_scatter) — identical per-table semantics to
        :meth:`_scatter_mirror_rows` + :meth:`_scatter_lat_rows`."""
        jnp = self._jnp
        q = self._quantize_scatter_rows(rows, n_tot)
        new_rows = m["h_in_src"][q]
        epoch_rows = np.where(new_rows != n_tot, 0, -1).astype(np.int32)
        ql = self._quantize_scatter_rows(lat_rows, lat["n_tot"])
        g = m["garrays"]
        in_src2, epoch2, ell_dst2, ell_epoch2 = _fused_quad_scatter()(
            g.in_src, g.edge_epoch, jnp.asarray(q),
            jnp.asarray(new_rows), jnp.asarray(epoch_rows),
            lat["ell_dst"], lat["ell_epoch"], jnp.asarray(ql),
            jnp.asarray(lat["h_ell_dst"][ql]),
            jnp.asarray(lat["h_ell_epoch"][ql]),
        )
        m["garrays"] = g._replace(in_src=in_src2, edge_epoch=epoch2)
        lat["ell_dst"], lat["ell_epoch"] = ell_dst2, ell_epoch2

    def _scatter_lat_rows(self, lat: dict, rows: np.ndarray) -> None:
        jnp = self._jnp
        q = self._quantize_scatter_rows(rows, lat["n_tot"])
        lat["ell_dst"], lat["ell_epoch"] = _fused_pair_scatter()(
            lat["ell_dst"], lat["ell_epoch"], jnp.asarray(q),
            jnp.asarray(lat["h_ell_dst"][q]),
            jnp.asarray(lat["h_ell_epoch"][q]),
        )

    def _patch_lat_add_batch(
        self, m: dict, lat: dict, u64, v64, ep_a, lat_changed_parts: list
    ):
        """Vectorized lat-mirror half of an add-delta, per (u, v, epoch)
        triple (duplicates dropped, of one edge the newest capture kept).

        An edge the mirror already holds a slot for is REVIVED where the
        slot lies: in ``u``'s own row, or in a virtual row of ``u``'s
        forwarding tree (found through the tree index built with the
        mirror, never by walking a hub's tree). A slot at the add's epoch
        is a duplicate; any other is dead, because its dependent has been
        bumped past its captured epoch, and takes the new epoch
        (``mirror_slots_revived``). A recaptured dependent so keeps its one
        slot under every source, however many dependents the source has.

        Only an edge the mirror does not hold looks for a free slot in
        ``u``'s own row, by within-row rank. A slot is free when it is a pad
        or DEAD (a bump leaves the lat tables alone). A full out-row (or
        unknown node) breaks ONLY the lat mirror — lone waves fall back to
        the topo sweep while lane bursts keep patching. Returns the lat
        dict, or None once broken."""
        if u64.size == 0:
            return lat
        if int(u64.max()) >= lat["n_real"] or int(v64.max()) >= lat["n_real"]:
            m["lat"] = None
            return None
        hd, he = lat["h_ell_dst"], lat["h_ell_epoch"]
        ln_tot, k = lat["n_tot"], hd.shape[1]
        # of one edge's captures the newest: an older one is dead on arrival
        order = np.lexsort((np.asarray(ep_a, dtype=np.int64), v64, u64))
        u, v, e = u64[order], v64[order], np.asarray(ep_a, dtype=np.int64)[order]
        last = np.ones(len(u), dtype=bool)
        last[:-1] = (u[1:] != u[:-1]) | (v[1:] != v[:-1])
        u, v, e = u[last], v[last], e[last]
        # where the mirror already holds u→v: the flat slot, or -1
        own = hd[u] == v[:, None]
        at = np.where(own.any(axis=1), u * k + own.argmax(axis=1), -1)
        miss = np.flatnonzero(at < 0)
        if miss.size and len(lat["tree_slots"]):
            at[miss] = self._lat_tree_slots(lat, u[miss], v[miss])
        held = at >= 0
        if held.any():
            flat_e = he.reshape(-1)
            stale = flat_e[at[held]] != e[held]
            if stale.any():
                flat_e[at[held][stale]] = e[held][stale]
                self.mirror_slots_revived += int(stale.sum())
                lat_changed_parts.append(at[held][stale] // k)
            u, v, e = u[~held], v[~held], e[~held]
            if u.size == 0:
                return lat
        idx = np.arange(len(u))
        grp_start = np.ones(len(u), dtype=bool)
        grp_start[1:] = u[1:] != u[:-1]
        rank = idx - np.maximum.accumulate(np.where(grp_start, idx, 0))
        hd_u = hd[u]
        real = hd_u < lat["n_real"]
        dead = real & (self._h_node_epoch[np.where(real, hd_u, 0)] != he[u])
        free_cum = ((hd_u == ln_tot) | dead).cumsum(axis=1)
        need = rank + 1
        if (free_cum[:, -1] < need).any():
            m["lat"] = None  # out-row full: lone waves fall back to the sweep
            return None
        slot = (free_cum == need[:, None]).argmax(axis=1)
        hd[u, slot] = v
        he[u, slot] = e
        lat_changed_parts.append(u)
        return lat

    @staticmethod
    def _lat_tree_index(ell_dst: np.ndarray, n_real: int, n_tot: int):
        """Where each real dependent sits below a forwarding tree of the
        lat out-ELL: ``(tree_slots, tree_root)``. ``tree_slots`` is every
        slot of a VIRTUAL row that holds a real target, as sorted int64
        keys ``target << 32 | flat slot``; ``tree_root[row]`` is the real
        source at the top of a virtual row's tree (a real row is its own).
        A dependent's slots are one ``searchsorted`` range, and the root
        says which of them is under the source asked for."""
        k = ell_dst.shape[1]
        root = np.arange(n_tot + 1, dtype=np.int32)
        cur = np.flatnonzero(
            ((ell_dst[:n_real] >= n_real) & (ell_dst[:n_real] < n_tot)).any(axis=1)
        )
        while cur.size:  # one round a tree level
            ent = ell_dst[cur]
            below = (ent >= n_real) & (ent < n_tot)
            children = ent[below]
            root[children] = np.broadcast_to(root[cur][:, None], ent.shape)[below]
            cur = children
        flat = ell_dst[n_real:n_tot].reshape(-1)
        sel = np.flatnonzero(flat < n_real)
        slots = np.sort((flat[sel].astype(np.int64) << 32) | (sel + n_real * k))
        return slots, root

    @staticmethod
    def _lat_tree_slots(lat: dict, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """The flat slot that holds ``u[i] → v[i]`` in a virtual row of
        ``u[i]``'s forwarding tree, or -1: ``v``'s range of the tree index,
        filtered by the tree's root."""
        slots, root, k = lat["tree_slots"], lat["tree_root"], lat["h_ell_dst"].shape[1]
        lo = np.searchsorted(slots, v << 32)
        n = np.searchsorted(slots, (v + 1) << 32) - lo
        pair = np.repeat(np.arange(len(v)), n)
        flat = slots[np.repeat(lo, n) + np.arange(n.sum()) - np.repeat(n.cumsum() - n, n)]
        flat &= 0xFFFFFFFF
        under = root[flat // k] == u[pair]
        out = np.full(len(v), -1, dtype=np.int64)
        out[pair[under]] = flat[under]
        return out

    def _live_edge_fingerprint(self):
        """(live src, live dst, fingerprint) of the CURRENT live edge set
        (epoch-matched edges only). Order-sensitive by design: any append,
        epoch bump that kills an in-edge, or compact changes it — a
        mismatch just means the mirror falls back to the dense path."""
        import hashlib

        m = self.n_edges
        live = (
            self._h_node_epoch[self._h_edge_dst[:m]] == self._h_edge_dst_epoch[:m]
        )
        src = self._h_edge_src[:m][live]
        dst = self._h_edge_dst[:m][live]
        h = hashlib.blake2b(digest_size=16)
        h.update(np.int64(self.n_nodes).tobytes())
        h.update(src.tobytes())
        h.update(dst.tobytes())
        return src, dst, h.digest()

    FUSED_PASS_MAX = 3  # ≤ this many sweep passes ride the fused one-
    # dispatch burst programs (one compile per count, persisted); beyond,
    # the split pipeline's host loop serves any count with no recompiles
    # (passes=0 — the adaptive fixed-point sentinel — always fuses)

    def mirror_levels(self, node_ids) -> Optional[np.ndarray]:
        """The topo mirror's level of each node id (None without a mirror).
        An edge from a lower level to a higher one PATCHES the mirror in
        place; a same-level or downward edge costs an extra sweep pass —
        what a churn generator needs to know to shape realistic,
        order-respecting structural churn."""
        m = self._topo_mirror
        if m is None:
            return None
        pos = m["inv_perm"][np.asarray(node_ids, dtype=np.int64)]
        return np.searchsorted(m["level_starts_arr"], pos, side="right") - 1

    def set_adaptive_passes(self, on: bool = True) -> None:
        """Switch the mirror sweep schedule to adaptive fixed-point mode
        (ISSUE 17): bursts run sweeps under a device-side quiescence loop
        (``passes=0``) instead of the worst-case ``1 + n_viol`` count a
        patched mirror carries. Takes effect on the next patch/burst; an
        already-built mirror's pinned pass count updates in place."""
        self.adaptive_passes = bool(on)
        m = self._topo_mirror
        if m is not None:
            n_viol = int(m.get("n_viol", 0))
            m["passes"] = 0 if on else 1 + n_viol

    def _count_adaptive(self, passes: int) -> None:
        """Count one adaptive-mode burst dispatch (``passes <= 0``)."""
        if passes > 0:
            return
        self.adaptive_stages += 1
        from ..diagnostics.metrics import global_metrics

        global_metrics().counter(
            "fusion_wave_adaptive_stages_total",
            help="mirror burst dispatches that ran their sweeps under the "
            "adaptive device-side fixed-point loop instead of a pinned "
            "worst-case pass count (ISSUE 17)",
        ).inc()
    LAT_SEED_MAX = 256  # ≤ this many union seeds routes via the lat mirror
    LAT_K = 4  # lat out-ELL build width (virtual trees bound fan-out)
    LAT_LCAP = 512
    LAT_CAP = 8192
    # guaranteed-free slots per mirror row (topo in-rows AND lat out-rows):
    # realistic churn lands edges on arbitrary rows, and any PACKED row
    # would break the patch log — slack makes overflow a rare collision
    # (≥ slack+1 new edges on ONE row between rebuilds) instead of a
    # certainty at volume, at slack/k extra sweep gather width
    PATCH_SLACK = 2

    def build_topo_mirror(self, k: int = 4, cap: int = 65536, force: bool = False) -> dict:
        """Build (or refresh) the packed topo mirror of the LIVE edge set:
        the level-ordered in-ELL (ops/topo_wave.py) that runs a whole burst
        in ONE depth-free sweep. Rebuilt only when the live-edge fingerprint
        changes; per-burst the mirror reads the dense device invalid state
        directly (no host upload) and writes newly bits back into it, so
        the two device states never diverge. Epoch checks are unnecessary
        inside the mirror — it contains exactly the currently-live edges,
        and any change to the LIVE edge sequence (an append, an epoch bump
        that kills an in-edge) changes the fingerprint, routing bursts back
        to the dense path until the mirror is rebuilt. Operations that
        preserve the live set — compact() drops only dead edges — keep the
        fingerprint, and the mirror stays valid because the semantics are
        unchanged."""
        from ..ops.topo_wave import build_topo_graph

        jnp = self._jnp
        cached = self._topo_mirror
        if not force and cached is not None and cached["cap"] == cap and cached["k"] == k:
            # patch-or-validate first: a level-preserving delta splices in
            # place and the existing compiled program keeps serving bursts.
            # ``force`` skips this — the maintenance rebuild that re-levels
            # a patched mirror back to single-pass sweeps (n_viol → 0)
            if self._mirror_valid():
                return cached
        src, dst, fp = self._live_edge_fingerprint()
        if (
            not force
            and cached is not None
            and cached["fp"] == fp
            and cached["cap"] == cap
            and cached["k"] == k
        ):
            cached["validated_at"] = self._struct_version
            self._mirror_deltas = []
            return cached
        cache_path = self._mirror_cache_path(fp, k)
        if cache_path is not None:
            loaded = self._load_mirror_cache(cache_path)
            if loaded is not None:
                topo_c, lat_c = loaded
                from ..ops.topo_wave import topo_graph_arrays

                import logging

                self.mirror_cache_hits += 1
                logging.getLogger("stl_fusion_tpu").info(
                    "topo mirror loaded from disk cache (%s)", cache_path
                )
                garrays_c = topo_graph_arrays(topo_c)  # async upload starts
                self._install_topo_mirror(
                    topo_c, k, cap, fp, self._struct_version, self.n_nodes,
                    lat=lat_c, garrays=garrays_c,
                )
                self._mirror_deltas = []
                return self._topo_mirror
            self.mirror_cache_misses += 1
        from ..ops.ell_wave import build_ell, widen_ell

        # the lat mirror is LEVEL-INDEPENDENT (out-ELL by original ids):
        # a re-level rebuild can carry a still-live patched lat across —
        # skipping its build + upload (~264 MB at 10M).
        # Only carry when the delta chain is unbroken (a broken log means
        # lat missed deltas) and the node count matches the new snapshot.
        carried_lat = None
        if (
            cached is not None
            and self._mirror_deltas == []  # no pending-unapplied deltas:
            # a delta recorded but not yet patched is IN the new edge
            # snapshot — a carried lat would be missing it (r5 review)
            and cached.get("lat") is not None
            and cached["lat"]["n_real"] == self.n_nodes
        ):
            carried_lat = cached["lat"]
        topo = build_topo_graph(src, dst, self.n_nodes, k=k, slack=self.PATCH_SLACK)
        # start the topo upload NOW: transfers are async, so the lat
        # mirror's host build below overlaps the in-ELL's trip to HBM
        # (hundreds of MB at 10M — a serial build-then-upload-both cold
        # start pays the full sum)
        from ..ops.topo_wave import topo_graph_arrays

        garrays = topo_graph_arrays(topo)
        lat = carried_lat if carried_lat is not None else widen_ell(
            build_ell(src, dst, self.n_nodes, k=self.LAT_K), self.PATCH_SLACK
        )
        self._install_topo_mirror(
            topo, k, cap, fp, self._struct_version, self.n_nodes, lat=lat,
            garrays=garrays,
        )
        if cache_path is not None and not isinstance(lat, dict):
            self._save_mirror_cache_async(cache_path, topo, lat)
        self._mirror_deltas = []  # fresh log: the mirror is coherent NOW
        return self._topo_mirror

    # ------------------------------------------------------------------ mirror disk cache
    # keep 3: the reusable pre-churn entry + this run's rebuild saves;
    # loads LRU-touch their entry so the reusable one can never be the
    # prune victim of a run's own churned-rebuild writes
    MIRROR_CACHE_KEEP = 3

    def _mirror_cache_path(self, fp, k: int):
        """Fingerprint-keyed on-disk mirror cache (FUSION_MIRROR_CACHE env
        root; unset = disabled): a process restart on the same live edge
        set loads the built topo+lat tables (~seconds of disk read) instead
        of re-deriving them (~40 s of 1-core host work at 10M) — the
        restart-warmth analogue of the reference's persistent client cache
        (Client/Caching/ClientComputedCache.cs:35-49)."""
        import os

        root = os.environ.get("FUSION_MIRROR_CACHE")
        if not root:
            return None
        key = (
            f"{fp.hex()}-k{k}s{self.PATCH_SLACK}l{self.LAT_K}"
            f"-{_mirror_builder_hash()}"
        )
        return os.path.join(root, key + ".npz")

    def _load_mirror_cache(self, path: str):
        """(TopoGraph, EllGraph) from a cache entry, or None. Derivable
        tables (epoch patterns, is_real flags) rebuild from the id tables
        — the entry stores only what cannot be derived."""
        import os

        from ..ops.ell_wave import EllGraph
        from ..ops.topo_wave import TopoGraph

        if not os.path.exists(path):
            return None
        try:
            # LRU-touch BEFORE reading: pruning is by mtime, and without
            # the touch a run's churned-rebuild saves (useless next run —
            # churn-dependent fingerprints) evicted the one REUSABLE
            # pre-churn entry after two runs, so every later canonical run
            # missed the cache it was supposed to hit (VERDICT r5 missing
            # #2: ~121 s cold start with the cache sitting right there)
            try:
                os.utime(path)
            except OSError:
                pass
            z = np.load(path)
            in_src = z["in_src"]
            n_tot = int(z["n_tot"])
            n_real = int(z["n_real"])
            if n_real != self.n_nodes:
                return None
            perm = z["perm"]
            is_real = z["is_real"]
            topo = TopoGraph(
                in_src,
                np.where(in_src != n_tot, 0, -1).astype(np.int32),
                is_real,
                tuple(z["level_starts"].tolist()),
                perm,
                z["inv_perm"],
                n_real,
                n_tot,
                int(z["k"]),
            )
            lat_dst = z["lat_dst"]
            lat_n_tot = int(z["lat_n_tot"])
            lat_is_real = np.zeros(lat_n_tot + 1, dtype=bool)
            lat_is_real[:n_real] = True
            lat = EllGraph(
                lat_dst,
                np.where(lat_dst != lat_n_tot, 0, -1).astype(np.int32),
                lat_is_real,
                n_real,
                lat_n_tot,
                int(z["lat_k"]),
            )
            return topo, lat
        except Exception:  # noqa: BLE001 — a corrupt entry is a cache miss
            try:
                os.remove(path)
            except OSError:
                pass
            return None

    def _save_mirror_cache_async(self, path: str, topo, lat) -> None:
        """Persist a freshly built mirror in a background thread (the write
        is ~1 GB at 10M — never on the serving path), pruning old entries."""
        import os
        import threading

        def work():
            tmp = path + ".tmp"
            try:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                np.savez(
                    tmp,
                    in_src=topo.in_src,
                    level_starts=np.asarray(topo.level_starts, dtype=np.int64),
                    perm=topo.perm,
                    inv_perm=topo.inv_perm,
                    is_real=topo.is_real,
                    n_tot=topo.n_tot,
                    n_real=topo.n_real,
                    k=topo.k,
                    lat_dst=lat.ell_dst,
                    lat_n_tot=lat.n_tot,
                    lat_k=lat.k,
                )
                os.replace(tmp + ".npz", path)
            except Exception:  # noqa: BLE001 — cache writes are best-effort
                try:
                    os.remove(tmp + ".npz")
                except OSError:
                    pass
                return
            try:
                import time as _time

                dirname = os.path.dirname(path)
                entries = []
                for f in os.listdir(dirname):
                    full = os.path.join(dirname, f)
                    if f.endswith(".tmp.npz"):
                        # an orphan from a killed writer: stale after an
                        # hour (each is ~1 GB at 10M — r5 review)
                        if _time.time() - os.path.getmtime(full) > 3600:
                            os.remove(full)
                    elif f.endswith(".npz"):
                        entries.append(full)
                entries.sort(key=os.path.getmtime)
                for old in entries[: -self.MIRROR_CACHE_KEEP]:
                    os.remove(old)
            except Exception:  # noqa: BLE001 — pruning is best-effort
                pass

        threading.Thread(
            target=work, name="mirror-cache-save", daemon=True
        ).start()

    def _install_topo_mirror(
        self, topo, k: int, cap: int, fp, validated_at: int, n_nodes: int,
        lat=None, garrays=None,
    ) -> dict:
        """Materialize a built TopoGraph as the active mirror (device
        transfers happen HERE, on the calling thread — the async rebuild
        worker only does host work). ``lat`` is the companion out-ELL of
        the same live edge snapshot (the lone-wave lat mirror); its per-
        slot epochs are derived ON DEVICE from the resident epoch array
        (one op instead of a second hundreds-of-MB upload)."""
        from ..ops.topo_wave import topo_graph_arrays

        jnp = self._jnp
        self.mirror_rebuilds += 1
        n_tot = topo.n_tot
        node_epoch0 = jnp.zeros(n_tot + 1, dtype=jnp.int32).at[n_tot].set(-2)
        # original id per topo row, clipped into the dense arrays (virtual
        # rows would index past n_cap; is_real masks them in the program)
        perm_clipped = jnp.asarray(
            np.clip(topo.perm, 0, self.n_cap).astype(np.int32)
        )
        self._topo_mirror = {
            "fp": fp,
            "cap": cap,
            "k": k,
            # freshness is judged against the structure the build SAW —
            # for a sync build that is the current version (the first burst
            # must not re-hash to learn what we already know); for an async
            # install it is the snapshot version, and the catch-up deltas
            # bring it forward
            "validated_at": validated_at,
            "n_nodes": n_nodes,
            "n_tot": n_tot,
            "inv_perm": topo.inv_perm,
            "garrays": self.commit(
                garrays if garrays is not None else topo_graph_arrays(topo)
            ),
            "node_epoch0": self.commit(node_epoch0),
            "perm_clipped": self.commit(perm_clipped),
            "level_starts": topo.level_starts,
            "levels": len(topo.level_starts) - 1,
            # incremental-patch state: host copy of the in-ELL (slot
            # occupancy truth) + level boundaries as an array for row→level
            "h_in_src": topo.in_src.copy(),
            # mirror rows of real nodes (a collector or a pad row is not):
            # the walk of a row's in-set tells sources from collectors by it
            "h_row_real": np.asarray(topo.is_real, dtype=bool),
            "level_starts_arr": np.asarray(topo.level_starts, dtype=np.int64),
            # a fresh install honors the adaptive-sweep mode (ISSUE 17): a
            # mid-loop re-level must not silently revert to fixed passes
            "passes": 0 if self.adaptive_passes else 1,
            # a dict is an already-materialized lat CARRIED across a
            # re-level (level-independent); an EllGraph materializes fresh
            "lat": (
                lat if isinstance(lat, dict)
                else self._materialize_lat(lat) if lat is not None
                else None
            ),
        }
        return self._topo_mirror

    def _materialize_lat(
        self, lat, node_epoch_dev=None, h_node_epoch=None
    ) -> dict:
        """Device-side half of the lat mirror: upload the out-ELL id table,
        derive slot epochs on device, keep host copies for patching.

        Epochs must come from the SAME moment as the edge snapshot the ELL
        was built from — for a sync build that is the live state; an async
        install passes the device/host epoch snapshots captured at rebuild
        start, each a copy. Holding a jax array object is a snapshot only
        while no program donates it. The mirror paths leave ``node_epoch``
        and ``invalid`` alone (an epoch bump and a mirror wave return new
        arrays); the dense BFS programs (ops/wave.py) donate the whole
        GraphArrays; and the row scatters donate the tables they patch (the
        mirror's ``in_src`` / ``edge_epoch``, the lat ``ell_dst`` /
        ``ell_epoch``, the three edge arrays), so nothing may keep one of
        those across a patch or an ``add_edges``. Nodes bumped after the
        snapshot then show
        an epoch mismatch at kernel time — exactly the captured-at-epoch
        death rule, with no catch-up patching needed for bumps."""
        from ..ops.ell_wave import ell_live_epoch_init

        jnp = self._jnp
        g = self.device_arrays()
        if node_epoch_dev is None:
            node_epoch_dev = g.node_epoch
        if h_node_epoch is None:
            h_node_epoch = self._h_node_epoch
        ell_dst_dev = self.commit(jnp.asarray(lat.ell_dst))
        if node_epoch_dev.shape[0] == self.n_cap + 1:
            ell_epoch_dev = ell_live_epoch_init(lat.n_real, self.n_cap)(
                ell_dst_dev, node_epoch_dev
            )
        else:
            # capacity grew between snapshot and install: derive on host
            # from the snapshot epochs and pay the upload (rare — a grow
            # implies new nodes, whose edges break the delta log anyway)
            ell_epoch_dev = self.commit(jnp.asarray(
                np.where(
                    lat.ell_dst < lat.n_real,
                    h_node_epoch[np.clip(lat.ell_dst, 0, len(h_node_epoch) - 1)],
                    0,
                ).astype(np.int32)
            ))
        tree_slots, tree_root = self._lat_tree_index(lat.ell_dst, lat.n_real, lat.n_tot)
        return {
            "n_tot": lat.n_tot,
            "n_real": lat.n_real,
            "k": lat.k,
            "ell_dst": ell_dst_dev,
            "ell_epoch": ell_epoch_dev,
            # the build-time slots below forwarding trees, for the patcher's
            # revive-in-place (a slot the patcher adds lies in a real row)
            "tree_slots": tree_slots,
            "tree_root": tree_root,
            # slot-occupancy truth for patching — a REAL copy: jnp.asarray
            # above may be zero-copy on the CPU backend, and patching this
            # table in place would race the async kernel reads of the
            # "device" buffer (same rule as the topo mirror's h_in_src)
            "h_ell_dst": lat.ell_dst.copy(),
            "h_ell_epoch": np.where(
                lat.ell_dst < lat.n_real,
                h_node_epoch[np.clip(lat.ell_dst, 0, len(h_node_epoch) - 1)],
                0,
            ).astype(np.int32),
        }

    def start_topo_mirror_rebuild(self, k: int = 4, cap: int = 65536) -> bool:
        """Begin re-leveling the mirror in a BACKGROUND thread (VERDICT r3
        #1: rebuild asynchronously while bursts keep flowing). The worker
        does only host work (in-ELL pack + Kahn levels — the native pass
        releases the GIL); device transfers happen at install time on the
        polling thread. While it runs, bursts keep using the current
        (patched, possibly multi-pass) mirror; deltas since the snapshot
        are recorded separately and catch the fresh mirror up at install.
        The maintenance move once patched violations accumulate: a fresh
        level order dissolves them back to single-pass sweeps. Returns
        False if a rebuild is already in flight."""
        import threading

        from ..ops.topo_wave import build_topo_graph

        if self._async_rebuild is not None:
            return False
        src, dst, fp = self._live_edge_fingerprint()
        state = {
            "k": k,
            "cap": cap,
            "fp": fp,
            "snap_version": self._struct_version,
            "n_nodes": self.n_nodes,
            "rebuilds_at_start": self.mirror_rebuilds,
            "result": None,
            "result_lat": None,
            # the lat mirror is level-independent: when the current one is
            # alive and patched-current, the re-level carries it instead of
            # rebuilding + re-uploading it (the catch-up replay is dup-safe)
            "need_lat": not (
                self._topo_mirror is not None
                and self._topo_mirror.get("lat") is not None
                # == [] : pending-unapplied deltas are in the snapshot the
                # rebuild sees but NOT in the lat we would carry
                and self._mirror_deltas == []
                and self._topo_mirror["lat"]["n_real"] == self.n_nodes
            ),
            "error": None,
            # epoch snapshots for the lat mirror, each a copy of its own:
            # the host array mutates in place, and the dense BFS programs
            # (ops/wave.py), which serve the waves whenever the patch log
            # breaks mid-rebuild, donate the live device array
            "node_epoch_dev": self._jnp.array(
                self.device_arrays().node_epoch, copy=True
            ),
            "h_node_epoch": self._h_node_epoch.copy(),
        }

        def work():
            try:
                from ..ops.ell_wave import build_ell, widen_ell

                state["result"] = build_topo_graph(
                    src, dst, state["n_nodes"], k=k, slack=self.PATCH_SLACK
                )
                if state["need_lat"]:
                    state["result_lat"] = widen_ell(
                        build_ell(src, dst, state["n_nodes"], k=self.LAT_K),
                        self.PATCH_SLACK,
                    )
            except Exception as e:  # noqa: BLE001 — surfaced at poll
                state["error"] = e

        self._rebuild_deltas = []
        t = threading.Thread(target=work, name="topo-mirror-rebuild", daemon=True)
        state["thread"] = t
        self._async_rebuild = state
        t.start()
        return True

    def poll_topo_mirror_rebuild(self) -> bool:
        """Install a finished async rebuild (no-op while it runs). Returns
        True when a fresh mirror was installed this call."""
        st = self._async_rebuild
        if st is None or st["thread"].is_alive():
            return False
        self._async_rebuild = None
        catchup, self._rebuild_deltas = self._rebuild_deltas, None
        if st["error"] is not None:
            import logging

            logging.getLogger("stl_fusion_tpu").warning(
                "async mirror rebuild failed: %s", st["error"]
            )
            return False
        if self.mirror_rebuilds != st["rebuilds_at_start"]:
            return False  # a sync/forced rebuild superseded this snapshot
        old_m = self._topo_mirror
        old_lat = old_m.get("lat") if old_m is not None else None
        self._install_topo_mirror(
            st["result"], st["k"], st["cap"], st["fp"],
            st["snap_version"], st["n_nodes"],
        )
        if st["result_lat"] is not None:
            self._topo_mirror["lat"] = self._materialize_lat(
                st["result_lat"], st["node_epoch_dev"], st["h_node_epoch"]
            )
        elif (
            old_lat is not None
            and catchup is not None
            and old_lat["n_real"] == st["n_nodes"]
        ):
            # carry the live patched lat across the re-level (the catch-up
            # replay below double-applies its deltas — dup-safe)
            self._topo_mirror["lat"] = old_lat
        # deltas since the snapshot bring the fresh mirror forward; a broken
        # catch-up log (overflow) leaves it stale → dense until next rebuild
        self._mirror_deltas = catchup
        return True

    def _run_lat_union(self, lat: dict, flat_ids):
        """Small union wave on the lat mirror: ONE fused dispatch (seed
        gate + O(closure) expansion + dense-invalid commit) and one O(cap)
        readback. Returns (count, newly real ids) or None on capacity
        overflow (the caller re-runs on the topo sweep; overflow leaves
        all state untouched)."""
        import jax

        from ..ops.ell_wave import ell_live_union_step

        jnp = self._jnp
        # two spans, not four (the topo path's stage and commit have their
        # own): a lone edit is a couple of milliseconds and passes here every
        # time, and a recorded span costs microseconds (PERF.md §5)
        with hot_span("lat.dispatch"):
            g = self.device_arrays()
            ids = np.full(self.LAT_SEED_MAX, lat["n_tot"], dtype=np.int32)
            ids[: len(flat_ids)] = np.asarray(flat_ids, dtype=np.int32)
            step = ell_live_union_step(
                lat["n_tot"], lat["n_real"], self.n_cap, self.LAT_LCAP, self.LAT_CAP
            )
            g_invalid2, count, acc, over = step(
                lat["ell_dst"], lat["ell_epoch"], g.node_epoch, g.invalid,
                jnp.asarray(ids),
            )
        with hot_span("lat.readback"):
            # here the host waits on the program; the host mirror's commit
            # after it is a hundredth of the wait
            count, acc, over = jax.device_get((count, acc, over))
            if bool(over):
                return None
            self._g = g._replace(invalid=g_invalid2)
            self.mirror_bursts += 1
            self.lat_waves += 1
            count = int(count)
            # acc is sorted ascending: real ids (< n_real) form the prefix
            newly = acc[:count].astype(np.int32)
            if count:
                self.invalid_version += 1
                self._h_invalid[newly] = True
        return count, newly

    LAT_CHAIN_OUT_CAP = 65536

    def run_waves_union_seq(self, seed_id_lists: Sequence[Sequence[int]]):
        """M independent union waves SEQUENCED in one dispatch on the lat
        mirror — wave ``i`` sees waves ``< i``'s commits, so final state
        and per-wave counts equal M :meth:`run_waves_union` calls (the
        burst-of-lone-invalidations shape). Per-wave capacity
        overflows re-run on the topo sweep AFTER the chain (their counts
        then reflect that execution order). Without a valid lat mirror the
        whole call degrades to a host loop. Returns (counts int64[M],
        union newly ids int32[])."""
        M = len(seed_id_lists)
        if M == 0:
            return np.zeros(0, dtype=np.int64), np.empty(0, np.int32)

        def _loop_fallback():
            counts = np.zeros(M, dtype=np.int64)
            parts = []
            for i, s in enumerate(seed_id_lists):
                c, ids = self.run_waves_union([s])
                counts[i] = c
                parts.append(ids)
            return counts, (
                np.concatenate(parts) if parts else np.empty(0, np.int32)
            )

        if not self._mirror_valid():
            return _loop_fallback()
        m = self._topo_mirror
        lat = m.get("lat")
        m_nodes = m["n_nodes"]
        if (
            lat is None
            or any(len(s) == 0 or len(s) > self.LAT_SEED_MAX for s in seed_id_lists)
            or any(not (0 <= int(i) < m_nodes) for s in seed_id_lists for i in s)
        ):
            return _loop_fallback()
        import jax

        from ..ops.ell_wave import ell_live_union_chain_step

        jnp = self._jnp
        n_tot = lat["n_tot"]
        n_rows = _round_up_pow2(M)  # pad waves with empty seed rows
        mat = np.full((n_rows, self.LAT_SEED_MAX), n_tot, dtype=np.int32)
        for i, s in enumerate(seed_id_lists):
            mat[i, : len(s)] = np.asarray(s, dtype=np.int32)
        g = self.device_arrays()
        step = ell_live_union_chain_step(
            n_tot, lat["n_real"], self.n_cap, self.LAT_LCAP, self.LAT_CAP,
            self.LAT_CHAIN_OUT_CAP,
        )
        g_invalid2, counts, overs, out_ids, out_count, out_over = step(
            lat["ell_dst"], lat["ell_epoch"], g.node_epoch, g.invalid,
            jnp.asarray(mat),
        )
        counts, overs, out_ids, out_count, out_over = jax.device_get(
            (counts, overs, out_ids, out_count, out_over)
        )
        self._g = g._replace(invalid=g_invalid2)
        self.mirror_bursts += 1
        self.lat_waves += M
        newly_ids = self._patch_host_invalid(
            int(out_count), out_ids[: int(out_count)], bool(out_over)
        )
        counts = counts[:M].astype(np.int64)
        if overs[:M].any():
            # overflowed waves committed nothing in-chain: re-run each on
            # the general path now (counts reflect this execution order)
            extra_parts = []
            for i in np.nonzero(overs[:M])[0]:
                c, ids = self.run_waves_union([seed_id_lists[int(i)]])
                counts[int(i)] = c
                extra_parts.append(ids)
            if extra_parts:
                newly_ids = np.concatenate([newly_ids, *extra_parts])
        return counts, newly_ids

    def _run_mirror_union(self, seed_id_lists: Sequence[Sequence[int]]):
        import jax

        from ..ops.topo_wave import (
            run_topo_sweep_passes,
            topo_mirror_finish_step,
            topo_mirror_gate_step,
        )

        jnp = self._jnp
        m = self._topo_mirror
        n_tot = m["n_tot"]
        with hot_span("topo.stage"):
            flat = np.asarray(
                [int(i) for s in seed_id_lists for i in s], dtype=np.int64
            )
            new_ids = m["inv_perm"][flat] if len(flat) else np.empty(0, np.int64)
            width = max(256, _round_up_pow2(max(len(new_ids), 1)))  # shared program
            ids = np.full(width, n_tot, dtype=np.int32)  # pad = null row
            ids[: len(new_ids)] = new_ids.astype(np.int32)
            g = self.device_arrays()
            garrays = m["garrays"]
            passes = m.get("passes", 1)
            ids_dev = jnp.asarray(ids)
        with hot_span("topo.dispatch"):
            if passes <= self.FUSED_PASS_MAX:
                # steady state AND lightly patched mirrors: ONE dispatch + one
                # readback (fewer dispatches, fewer host round trips);
                # one fused program per pass count ≤ FUSED_PASS_MAX,
                # each compiled once per level layout and persisted — heavier
                # violation loads fall to the split pipeline's host loop,
                # which never recompiles at any pass count
                from ..ops.topo_wave import topo_mirror_fused_union_step

                self._count_adaptive(passes)
                self.sweep_packed_dispatches += 1
                g_invalid2, count, out_ids, overflow = topo_mirror_fused_union_step(
                    m["level_starts"], m["cap"], n_tot, passes
                )(garrays, m["node_epoch0"], m["perm_clipped"], g.invalid, ids_dev)
            else:
                node_epoch, seed_bits = topo_mirror_gate_step(n_tot)(
                    garrays.is_real, m["node_epoch0"], m["perm_clipped"], g.invalid,
                    ids_dev,
                )
                self.sweep_packed_dispatches += passes
                state = run_topo_sweep_passes(
                    m["level_starts"], garrays, seed_bits, node_epoch, passes
                )
                g_invalid2, count, out_ids, overflow = topo_mirror_finish_step(
                    m["cap"], n_tot
                )(garrays.is_real, m["perm_clipped"], g.invalid, state.invalid_bits)
        with hot_span("topo.readback"):  # here the host waits on the sweep
            count, out_ids, overflow = jax.device_get((count, out_ids, overflow))
        with hot_span("topo.commit"):
            self._g = g._replace(invalid=g_invalid2)
            self.mirror_bursts += 1
            count = int(count)
            return count, self._patch_host_invalid(count, out_ids, bool(overflow))

    #: chain stages fused per dispatch (run_waves_lanes_chain): deep chains
    #: split into this many stages per compiled scan — a bounded program
    #: set (one per depth ≤ the cap) while still collapsing K dispatches
    #: into ceil(K/8)
    FUSE_CHAIN_MAX = 8

    def dispatch_waves_lanes_chain(
        self,
        stage_groups: Sequence[Sequence[Sequence[int]]],
        max_words: int = 16,
    ) -> dict:
        """ENQUEUE ``depth`` consecutive lane bursts as
        ``ceil(depth/FUSE_CHAIN_MAX)`` chained device dispatches WITHOUT
        reading anything back — the nonblocking half of the wave chain
        (ISSUE 7). The dispatches chain device-side through the carried
        invalid array (jax enqueues them immediately), so the caller can
        do host work — or enqueue the NEXT chain — while the device runs;
        :meth:`harvest_waves_lanes_chain` blocks on the results and applies
        them to the host mirror.

        Requires a fusible mirror (valid, ``passes <= FUSED_PASS_MAX``);
        raises RuntimeError otherwise — callers fall back to the split
        per-burst path. Returns the pending-handles dict for harvest."""
        from ..ops.pull_wave import pack_lane_matrix
        from ..ops.topo_wave import topo_mirror_fused_lanes_chain_step

        jnp = self._jnp
        m = self.build_topo_mirror()
        if not self._mirror_valid():
            raise RuntimeError("topo mirror unavailable — chain needs the fused path")
        passes = m.get("passes", 1)
        if passes > self.FUSED_PASS_MAX:
            raise RuntimeError(
                f"mirror carries {passes} sweep passes > FUSED_PASS_MAX — "
                "chain fusion serves only the fused one-dispatch regime"
            )
        self._count_adaptive(passes)
        n_tot = m["n_tot"]
        # common lane geometry for the whole chain (scan stages must share
        # one shape): words covers the widest stage, width the widest group
        words = 1
        max_groups = max((len(s) for s in stage_groups), default=1)
        while 32 * words < max_groups:
            words <<= 1
        if words > max_words:
            raise ValueError(
                f"a stage carries {max_groups} groups > 32*max_words="
                f"{32 * max_words}; chunk stages before chaining"
            )
        width = 1
        max_seeds = max(
            (len(g) for s in stage_groups for g in s), default=1
        )
        while width < max_seeds:
            width <<= 1
        L = 32 * words

        def pack_stage(stage, base_index):
            mat, _w = pack_lane_matrix(
                stage, pad_id=n_tot, n_valid=m["n_nodes"],
                id_map=m["inv_perm"], base_index=base_index,
            )
            if mat.shape == (L, width):
                return mat
            out = np.full((L, width), n_tot, dtype=np.int32)
            out[: mat.shape[0], : mat.shape[1]] = mat
            return out

        batches: list = []
        group_base = 0
        depth_cap = self.FUSE_CHAIN_MAX
        for b0 in range(0, len(stage_groups), depth_cap):
            batch = stage_groups[b0 : b0 + depth_cap]
            parts = []
            for s in batch:
                parts.append(pack_stage(s, group_base))
                group_base += len(s)
            mats = np.stack(parts)
            g = self.device_arrays()
            self.sweep_packed_dispatches += 1
            chain = topo_mirror_fused_lanes_chain_step(
                m["level_starts"], n_tot, words, passes, len(batch)
            )
            g_inv2, lane_counts_d, packed_d = chain(
                m["garrays"], m["node_epoch0"], m["perm_clipped"],
                g.invalid, jnp.asarray(mats),
            )
            # commit the device handle NOW so the next batch (or the next
            # chain the caller enqueues) chains device-side
            self._g = g._replace(invalid=g_inv2)
            self.mirror_bursts += len(batch)
            batches.append((lane_counts_d, packed_d, [len(s) for s in batch]))
        self.last_lanes_info = {
            "depth": len(stage_groups),
            "dispatches": len(batches),
        }
        return {
            "batches": batches,
            "refresh": None,
            "depth": len(stage_groups),
            "dispatches": len(batches),
        }

    def _refresh_chain_program(self, m, refresh: dict, words: int, passes: int):
        """Build (or reuse) the jitted burst→refresh scan for one block —
        the loop-carried composition of ``run_waves_lanes`` +
        ``refresh_block_on_device`` (ops/topo_wave.py::
        topo_mirror_superround_step), the resident super-round's program.
        Cached in the caller-owned ``refresh["cache"]`` dict keyed
        on everything that shapes the program (level layout included: a
        re-level must never serve a stale chain; depth is NOT a key — jit
        re-traces per seed-tensor shape, one program object per
        geometry)."""
        key = (
            "lanes_refresh_chain", words, passes,
            refresh["update_valid"], m["n_tot"], m["level_starts"],
            refresh["base"], refresh["n_rows"],
        )
        cache = refresh["cache"]
        prog = cache.get(key)
        if prog is not None:
            return prog
        from ..ops.topo_wave import topo_mirror_superround_step

        prog = topo_mirror_superround_step(
            m["level_starts"], m["n_tot"], words, passes,
            refresh["base"], refresh["n_rows"], refresh["fn"],
            refresh["update_valid"],
        )
        cache[key] = prog
        return prog

    #: rounds per resident super-round dispatch: one lax.scan covers the
    #: whole depth (no FUSE_CHAIN_MAX batching — the program is resident
    #: and reused every super-round, so a deep scan amortizes rather than
    #: re-keys); the cap bounds trace/compile time for a runaway depth
    SUPER_DEPTH_MAX = 64

    def dispatch_waves_superround(
        self, mats: np.ndarray, sizes: Sequence[int], refresh: dict,
        words: int,
    ) -> dict:
        """ONE resident dispatch for a whole super-round (ISSUE 14):
        ``mats`` is the PRE-PACKED ``int32[K, 32*words, S]`` NEW-id seed
        tensor — staged by the host while the PREVIOUS super-round executed
        (graph/superround.py owns the double buffering), so dispatch does
        no per-stage pack work and no geometry recomputation. Unlike
        :meth:`dispatch_waves_lanes_chain` there is no chunking: the whole
        depth runs as one ``lax.scan`` through the shared
        burst→refresh→fence program, and same geometry ⇒ the SAME compiled
        executable every super-round. Requires a fusible mirror; raises
        RuntimeError otherwise (callers count the eager fallback — never
        silent). Returns a pending dict for
        :meth:`harvest_waves_lanes_chain`."""
        jnp = self._jnp
        m = self.build_topo_mirror()
        if not self._mirror_valid():
            raise RuntimeError(
                "topo mirror unavailable — super-round needs the fused path"
            )
        passes = m.get("passes", 1)
        if passes > self.FUSED_PASS_MAX:
            raise RuntimeError(
                f"mirror carries {passes} sweep passes > FUSED_PASS_MAX — "
                "super-rounds serve only the fused one-dispatch regime"
            )
        self._count_adaptive(passes)
        K = int(mats.shape[0])
        if K > self.SUPER_DEPTH_MAX:
            raise ValueError(
                f"super-round depth {K} > SUPER_DEPTH_MAX={self.SUPER_DEPTH_MAX}"
            )
        g = self.device_arrays()
        prog = self._refresh_chain_program(m, refresh, words, passes)
        self.sweep_packed_dispatches += 1
        (
            g_inv2, values2, valid2, lane_counts_d, packed_d,
        ) = prog(
            refresh["values"], refresh["valid_dev"],
            m["garrays"], m["node_epoch0"], m["perm_clipped"],
            g.invalid, jnp.asarray(mats), *refresh["largs"],
        )
        refresh["values"] = values2
        refresh["valid_dev"] = valid2
        # commit the device handle NOW so a next super-round the caller
        # enqueues chains device-side off this one's final state
        self._g = g._replace(invalid=g_inv2)
        self.mirror_bursts += K
        self.last_lanes_info = {"depth": K, "dispatches": 1}
        return {
            "batches": [(lane_counts_d, packed_d, list(sizes))],
            "refresh": refresh,
            "depth": K,
            "dispatches": 1,
        }

    def harvest_waves_lanes_chain(self, pending: dict) -> Tuple[list, list]:
        """Block on a :meth:`dispatch_waves_lanes_chain` ticket and fold the
        results into the host mirror. Returns ``(stage_counts,
        stage_masks)``: per-stage int64 newly counts and per-stage dense
        newly BOOL masks over node ids (the mask a stage's fence fan-out
        drains). For a refresh chain the block's rows read consistent
        afterwards (host mirror cleared to match the device state)."""
        import jax

        stage_counts: list = []
        stage_masks: list = []
        any_newly = False
        for lane_counts_d, packed_d, sizes in pending["batches"]:
            lane_counts, packed = jax.device_get((lane_counts_d, packed_d))
            for d, size in enumerate(sizes):
                stage_counts.append(lane_counts[d, :size].astype(np.int64))
                mask = np.unpackbits(
                    packed[d].view(np.uint8),
                    count=len(self._h_invalid),
                    bitorder="little",
                ).astype(bool)
                stage_masks.append(mask)
                if mask.any():
                    any_newly = True
                    self._h_invalid |= mask
        refresh = pending["refresh"]
        if refresh is not None:
            # the device cleared the block's invalid bits at every stage;
            # the host mirror catches up once, at the end state
            base, n_rows = refresh["base"], refresh["n_rows"]
            self._h_invalid[base : base + n_rows] = False
            any_newly = True
        if any_newly:
            self.invalid_version += 1
        return stage_counts, stage_masks

    def run_waves_lanes_chain(
        self,
        stage_groups: Sequence[Sequence[Sequence[int]]],
        max_words: int = 16,
    ) -> Tuple[list, list]:
        """``depth`` CONSECUTIVE lane bursts — stage ``i`` cascades against
        the invalid state stages ``< i`` left — fused into
        ``ceil(depth/FUSE_CHAIN_MAX)`` device dispatches via the loop-
        carried ``lax.scan`` chain. Oracle-identical to calling
        :meth:`run_waves_lanes` once per stage; the dispatch count is the
        only difference. Dispatch + harvest in one call — the nonblocking
        halves are :meth:`dispatch_waves_lanes_chain` /
        :meth:`harvest_waves_lanes_chain` (what the WavePipeline overlaps).
        """
        return self.harvest_waves_lanes_chain(
            self.dispatch_waves_lanes_chain(stage_groups, max_words=max_words)
        )

    def run_waves_lanes(
        self, seed_id_lists: Sequence[Sequence[int]], max_words: int = 16
    ) -> Tuple[np.ndarray, np.ndarray]:
        """INDEPENDENT per-group cascades, 32 groups per packed word, one
        topo-mirror sweep per ≤``32*max_words`` groups (the lane-packed live
        burst — ops/topo_wave.py::topo_mirror_burst_lanes_step). Builds or
        revalidates the mirror itself.

        Per-group semantics = a dense BFS from the graph's invalid state at
        the chunk boundary (groups inside a chunk are snapshot-independent:
        two groups may both count a node; chunks apply sequentially).
        Returns (per-group newly counts int64[B], union newly-invalid BOOL
        MASK over node ids) — burst unions at stress scale are millions of
        rows, so the union travels and applies as a dense bitmask end to
        end (1 bit/node on the wire, vectorized mask ops on the host; the
        id materialization every burst was ~a third of r4's burst cost).

        Multi-chunk bursts FUSE: the sequential chunk walk (each chunk one
        dispatch + one readback) is replaced by the loop-carried chain —
        same semantics, ``ceil(chunks/FUSE_CHAIN_MAX)`` dispatches
        (ISSUE 7); a mirror needing the split multi-pass pipeline keeps the
        per-chunk walk.
        """
        import jax

        from ..ops.pull_wave import pack_lane_matrix
        from ..ops.topo_wave import (
            run_topo_sweep_passes,
            topo_mirror_finish_lanes_step,
            topo_mirror_gate_lanes_step,
        )

        jnp = self._jnp
        m = self.build_topo_mirror()
        n_tot = m["n_tot"]
        B = len(seed_id_lists)
        counts = np.zeros(B, dtype=np.int64)
        union_mask = np.zeros(self.n_cap + 1, dtype=bool)
        any_newly = False
        chunk_size = 32 * max_words
        if (
            B > chunk_size
            and self._mirror_valid()
            and m.get("passes", 1) <= self.FUSED_PASS_MAX
        ):
            stages = [
                seed_id_lists[c0 : c0 + chunk_size]
                for c0 in range(0, B, chunk_size)
            ]
            stage_counts, stage_masks = self.run_waves_lanes_chain(
                stages, max_words=max_words
            )
            counts = np.concatenate(stage_counts)
            for mask in stage_masks:
                union_mask |= mask
            return counts, union_mask
        for c0 in range(0, B, chunk_size):
            chunk = seed_id_lists[c0 : c0 + chunk_size]
            mat, words = pack_lane_matrix(
                chunk, pad_id=n_tot, n_valid=m["n_nodes"],
                id_map=m["inv_perm"], base_index=c0,
            )
            g = self.device_arrays()
            garrays = m["garrays"]
            passes = m.get("passes", 1)
            if passes <= self.FUSED_PASS_MAX:
                from ..ops.topo_wave import topo_mirror_fused_lanes_step

                self._count_adaptive(passes)
                self.sweep_packed_dispatches += 1
                g_invalid2, lane_counts, union_count, packed = (
                    topo_mirror_fused_lanes_step(
                        m["level_starts"], n_tot, words, passes
                    )(garrays, m["node_epoch0"], m["perm_clipped"], g.invalid,
                      jnp.asarray(mat))
                )
            else:
                node_epoch, seed_bits = topo_mirror_gate_lanes_step(n_tot, words)(
                    garrays.is_real, m["node_epoch0"], m["perm_clipped"], g.invalid,
                    jnp.asarray(mat),
                )
                self.sweep_packed_dispatches += passes
                state = run_topo_sweep_passes(
                    m["level_starts"], garrays, seed_bits, node_epoch, passes
                )
                g_invalid2, lane_counts, union_count, packed = (
                    topo_mirror_finish_lanes_step(n_tot, words)(
                        garrays.is_real, m["perm_clipped"], g.invalid,
                        state.invalid_bits,
                    )
                )
            lane_counts, union_count, packed = jax.device_get(
                (lane_counts, union_count, packed)
            )
            self._g = g._replace(invalid=g_invalid2)
            self.mirror_bursts += 1
            counts[c0 : c0 + len(chunk)] = lane_counts[: len(chunk)].astype(np.int64)
            if int(union_count):
                any_newly = True
                newly = np.unpackbits(
                    packed.view(np.uint8),
                    count=len(self._h_invalid),
                    bitorder="little",
                ).astype(bool)
                self._h_invalid |= newly
                union_mask |= newly
        if any_newly:
            self.invalid_version += 1
        n_chunks = max(-(-B // chunk_size), 1)
        self.last_lanes_info = {"depth": n_chunks, "dispatches": n_chunks}
        return counts, union_mask

    def _sync_invalid_back(self) -> None:
        """After a device wave, the device invalid lane is newer — pull it
        BIT-PACKED (1 bit/node over PCIe, same as the overflow readback
        path)."""
        self.invalid_version += 1
        packed = np.asarray(_pack_mask_kernel()(self._g.invalid))
        self._h_invalid = np.unpackbits(
            packed.view(np.uint8), count=self.n_cap + 1, bitorder="little"
        ).astype(bool)

    # ------------------------------------------------------------------ readback
    def invalid_mask(self) -> np.ndarray:
        g = self.device_arrays()
        return np.asarray(g.invalid[: self.n_nodes])

    def invalid_ids(self) -> np.ndarray:
        return np.nonzero(self.invalid_mask())[0].astype(np.int32)

    def clear_invalid(self) -> None:
        jnp = self._jnp
        self.invalid_version += 1
        g = self.device_arrays()
        self._g = g._replace(invalid=jnp.zeros_like(g.invalid))
        self._h_invalid = np.zeros(self.n_cap + 1, dtype=bool)

    def compact(self) -> int:
        """Drop dead edges (epoch-mismatched) — the pruner sweep. Returns
        removed count."""
        live = (
            self._h_node_epoch[self._h_edge_dst[: self.n_edges]]
            == self._h_edge_dst_epoch[: self.n_edges]
        )
        removed = int((~live).sum())
        if removed == 0:
            return 0
        k = int(live.sum())
        for name in ("_h_edge_src", "_h_edge_dst", "_h_edge_dst_epoch"):
            arr = getattr(self, name)
            kept = arr[: self.n_edges][live]
            pad_val = self.n_cap if name != "_h_edge_dst_epoch" else -1
            arr[:k] = kept
            arr[k : self.n_edges] = pad_val
        self.n_edges = k
        self._dirty = True
        # compact preserves the live edge sequence (fp unchanged), but one
        # cheap re-validation beats reasoning about it here
        self._struct_version += 1
        return removed
