"""MemoTable — vectorized reactive memoization over a dense key space.

The TPU-first re-design of the reference's hot READ path
(Function.cs:56, ComputedRegistry.cs:57-70) for the case its benchmark
actually measures: millions of `users.Get(id)` reads over a dense integer
key space (tests/Stl.Fusion.Tests/PerformanceTest.cs:32-144). The scalar
`@compute_method` path keeps one Python node per key — the right shape for
heterogeneous dependency graphs, ~2.8 µs per memoized hit. When the key
space is dense and the read pattern is bulk, the TPU-native shape is
columnar instead:

- values live in device HBM as one array (pytree of arrays) with a row per
  key — the "registry" is a gather index, not a hash map;
- a batch of reads is ONE jitted gather (amortized cost: nanoseconds/read);
- consistency is a per-row validity bit: `invalidate(ids)` clears bits,
  the next read of a stale row triggers a vectorized recompute
  (`compute_fn(ids) -> rows`) and scatter — single-flight per refresh call,
  read-your-writes within a table;
- staleness bookkeeping is mirrored host-side (numpy) so `read_batch`
  never pays a device→host sync to decide whether to refresh (a blocking
  readback per call is what a hot loop cannot afford), while
  the packed device bitmask stays available to on-device consumers (wave
  kernels, masked matmuls).

Scalar-graph bridge: `on_invalidate` callbacks fire with the invalidated
ids, so a host `Computed` (e.g. an aggregate over the table) can subscribe
and cascade through the object graph; `changed` is an AsyncEvent stream of
table versions for reactive `ComputedState`-style consumers.
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Union

import numpy as np

from ..diagnostics.flight_recorder import RECORDER
from ..utils.async_utils import AsyncEvent

__all__ = ["MemoTable"]

Ids = Union[Sequence[int], np.ndarray]


def _pad_repeat_pow2(ids_np: np.ndarray) -> np.ndarray:
    """Pow2-pad an id batch by repeating the first id — shape-quantizes the
    jitted kernels so varying batch sizes don't each compile a fresh device
    executable (set-style scatters are duplicate-safe)."""
    n = len(ids_np)
    if n == 0:
        return ids_np  # empty gathers/scatters stay empty (no [0] to repeat)
    width = 1
    while width < n:
        width <<= 1
    if width == n:
        return ids_np
    out = np.full(width, ids_np[0], dtype=np.int32)
    out[:n] = ids_np
    return out


class MemoTable:
    def __init__(
        self,
        n_rows: int,
        compute_fn: Callable[[np.ndarray], "np.ndarray"],
        row_shape: tuple = (),
        dtype=None,
        eager: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        #: host buffer -> device array, for every upload this table makes.
        #: ``jnp.asarray`` (JAX's default placement) until a graph backend
        #: that was given a device binds the table (:meth:`place_on`)
        self._put = jnp.asarray
        self.n_rows = int(n_rows)
        self.compute_fn = compute_fn
        self.version = 0
        dtype = dtype or jnp.float32
        self._values = jnp.zeros((self.n_rows, *row_shape), dtype=dtype)
        # host-authoritative staleness (True = stale); device mirror is an
        # unpacked bool row mask (scatter of 0/1 is duplicate-safe, unlike a
        # packed-word RMW which loses bits when two ids share a word)
        self._stale_host = np.ones(self.n_rows, dtype=bool)
        self._stale_count = self.n_rows  # exact count, O(batch) to maintain
        self._valid_dev = jnp.zeros(self.n_rows, dtype=jnp.bool_)
        # True = the device mask lags _stale_host (wave application defers
        # the scatter — a 10M-row wave would upload 40 MB of ids per
        # burst); valid_mask/valid_bits materialize lazily
        self._valid_dev_dirty = False
        # small invalidate/refresh batches defer their device-mask scatter
        # here (applied in order at materialization): every eager scatter
        # is a dispatch, and a scalar write loop paid one per op (r5 — the
        # live bench's dominant non-burst phase)
        self._valid_pending: List[np.ndarray] = []
        self._valid_pending_n = 0
        self._packed_cache: Optional[tuple] = None  # (version, packed bits)
        self.on_invalidate: List[Callable[[np.ndarray], None]] = []
        #: fired BY THE GRAPH BACKEND with the local row ids a DEVICE WAVE
        #: marked stale (``_mark_stale_from_wave*`` itself stays silent —
        #: the wave owns the cascade; these hooks are for EXTERNAL
        #: observers such as the RPC fence push, which would otherwise
        #: never learn of burst-driven staleness). Only fired when
        #: non-empty, so unobserved tables pay nothing per wave.
        self.on_wave_invalidate: List[Callable[[np.ndarray], None]] = []
        #: fired with the refreshed ids after a vectorized recompute — the
        #: columnar analogue of a recompute's consistency restoration (the
        #: graph backend subscribes to clear device invalid bits in bulk)
        self.on_refresh: List[Callable[[np.ndarray], None]] = []
        #: optional DEVICE loader (set by TableBacking(device_batch=...)):
        #: jax-traceable ``(ids: int32[k] device, *args) -> rows`` — lets
        #: the graph backend refresh stale rows entirely on device
        #: (TpuGraphBackend.refresh_block_on_device), zero host traffic.
        #: ``device_loader_args()`` returns the loader's device-array state
        #: (threaded as runtime args, never closure constants).
        self.device_compute_fn = None
        self.device_loader_args = None
        #: kept hot (set by TableBacking(hot=True)): a graph backend this
        #: table is bound to recomputes on the device, after every wave,
        #: the rows the wave invalidated (TpuGraphBackend.refresh_hot)
        self.hot = False
        #: optional key codec (set by TableBacking wiring): arbitrary
        #: hashable keys ⇄ dense rows — see read_keys/invalidate_keys
        self.key_codec = None
        #: declared key arity (set by TableBacking wiring): disambiguates a
        #: single-arg method whose KEY VALUES are tuples from a multi-arg
        #: method — a runtime isinstance(key, tuple) check cannot
        self.key_arity: Optional[int] = None
        self.changed: AsyncEvent = AsyncEvent(0)
        self._jit_cache = _kernels()  # shared: tables reuse one compile cache
        # /metrics exposure (ISSUE 3): stale backlog + version, summed over
        # live tables at scrape time — weak-registered, a collected table
        # drops out on its own; read_batch/invalidate never pay a registry hop
        from ..diagnostics.metrics import global_metrics

        global_metrics().register_collector(self, MemoTable._collect_metrics)
        if eager:
            self.refresh(np.arange(self.n_rows))

    def _collect_metrics(self) -> dict:
        return {
            "fusion_memo_tables": 1,
            "fusion_memo_rows": self.n_rows,
            "fusion_memo_stale_rows": self._stale_count,
            "fusion_memo_versions_total": self.version,
        }

    # ------------------------------------------------------------------ reads
    def read_batch(self, ids: Ids):
        """Values for ``ids`` (device array [k, ...]); refreshes stale rows
        first. The all-fresh fast path is one gather — no host↔device sync.

        ``ids`` may be a DEVICE array (jax): then the batch never crosses
        the host boundary — instead of gathering per-id staleness on the
        host (which would force a device→host readback), the ENTIRE current
        stale set (host-known, typically a handful of mutator-invalidated
        rows) is refreshed before the gather. Correct for any stale-set
        size, and the right trade when invalidations are sparse: the hot
        read loop stays pure async device dispatch, which is what lets
        batched reads pipeline at the kernel rate instead of the
        host-transfer rate."""
        if isinstance(ids, self._jax.Array):
            # device-resident ids (positive detection — every other
            # sequence type keeps the original np.asarray host contract):
            # refresh-all-stale, then one pure gather
            if self._stale_count:
                self.refresh(np.nonzero(self._stale_host)[0])
            return self._jit_cache["gather"](self._values, ids)
        ids_np = np.asarray(ids, dtype=np.int32)
        stale = self._stale_host[ids_np]
        if stale.any():
            self.refresh(np.unique(ids_np[stale]))
        k = len(ids_np)
        padded = _pad_repeat_pow2(ids_np)
        out = self._jit_cache["gather"](self._values, self._put(padded))
        return out if len(padded) == k else out[:k]

    def encode_keys(self, keys, allocate: bool = True) -> np.ndarray:
        """Dense row ids for arbitrary keys via the attached codec (a key is
        the call-args tuple, or the bare value for single-arg methods).
        ``allocate=False`` maps only already-interned keys (-1 otherwise)."""
        codec = self._require_codec()
        rows = np.empty(len(keys), dtype=np.int32)
        for j, k in enumerate(keys):
            args = self._key_to_args(k)
            row = codec.acquire(args) if allocate else codec.peek(args)
            rows[j] = -1 if row is None else row
        return rows

    def _key_to_args(self, k) -> tuple:
        """Canonical call-args tuple for a key, by DECLARED arity: a
        single-arg method's tuple-valued key must intern as ((1, 2),),
        never be mistaken for two args."""
        if self.key_arity == 1:
            return (k,)
        if self.key_arity is not None:
            if not isinstance(k, tuple) or len(k) != self.key_arity:
                raise TypeError(
                    f"key {k!r} does not match the method's arity "
                    f"({self.key_arity}): pass an args tuple"
                )
            return k
        return k if isinstance(k, tuple) else (k,)  # standalone-table heuristic

    def read_keys(self, keys):
        """``read_batch`` for codec-backed tables: keys are interned to rows
        (first read allocates), stale rows refresh through the service's
        batch method with the DECODED keys, one gather returns the values."""
        return self.read_batch(self.encode_keys(keys))

    def invalidate_keys(self, keys) -> None:
        """Mark the rows of already-interned ``keys`` stale (never-read keys
        have no row and are a no-op, not an allocation)."""
        rows = self.encode_keys(keys, allocate=False)
        rows = rows[rows >= 0]
        if rows.size:
            self.invalidate(rows)

    def _require_codec(self):
        if self.key_codec is None:
            raise TypeError(
                "this MemoTable has no key codec — declare "
                "TableBacking(keys=True) or read by integer row ids"
            )
        return self.key_codec

    @property
    def values(self):
        """The raw device value table (rows for stale ids may be outdated)."""
        return self._values

    MAX_VALID_PENDING = 4096  # total deferred ids before a full rebuild wins

    def _defer_valid(self, ids_np: np.ndarray, value: bool) -> None:
        """Queue a small device-mask update instead of dispatching it
        eagerly; past the budget the full lazy materialization is cheaper.
        The queue stores only the TOUCHED ids — at flush time the
        authoritative host staleness supplies each id's final value, so
        any number of deferred batches coalesce into ONE scatter."""
        if self._valid_dev_dirty:
            return  # full materialization already pending
        if self._valid_pending_n + len(ids_np) > self.MAX_VALID_PENDING:
            self._valid_dev_dirty = True
            self._valid_pending.clear()
            self._valid_pending_n = 0
        else:
            self._valid_pending.append(ids_np)
            self._valid_pending_n += len(ids_np)

    @property
    def valid_mask(self):
        """Per-row device validity mask (bool[n_rows]); materialized from
        the host-authoritative staleness if a wave application deferred it.
        Deferred small updates flush as ONE value-scatter: the final value
        of every touched id is just ``~stale_host[id]`` (host truth), so
        per-batch replay — and its one dispatch per batch — is
        unnecessary."""
        if self._valid_dev_dirty:
            self._valid_dev = self._put(~self._stale_host)
            self._valid_dev_dirty = False
            self._valid_pending.clear()
            self._valid_pending_n = 0
        elif self._valid_pending:
            ids = np.unique(np.concatenate(self._valid_pending))
            padded = _pad_repeat_pow2(ids)
            self._valid_dev = self._jit_cache["set_mask_vals"](
                self._valid_dev,
                self._put(padded),
                self._put(~self._stale_host[padded]),
            )
            self._valid_pending.clear()
            self._valid_pending_n = 0
        return self._valid_dev

    def valid_bits(self):
        """Packed per-row validity (uint32 lanes) for on-device bit-kernel
        consumers; packed on demand and cached per table version."""
        if self._packed_cache is None or self._packed_cache[0] != self.version:
            self._packed_cache = (self.version, self._jit_cache["pack"](self.valid_mask))
        return self._packed_cache[1]

    # ------------------------------------------------------------------ writes
    def refresh(self, ids: Ids) -> None:
        """Vectorized recompute + scatter for ``ids`` (marks them fresh).
        Ids are deduped: compute_fn sees each row once."""
        ids_np = np.unique(np.asarray(ids, dtype=np.int32))
        if ids_np.size == 0:
            return
        rows = self.compute_fn(ids_np)
        # pow2-pad by repeating the first row (duplicate scatter of the SAME
        # value is deterministic): refresh batch sizes vary per call, and a
        # fresh shape is a fresh device executable (~seconds of compile)
        padded = _pad_repeat_pow2(ids_np)
        if len(padded) != len(ids_np):
            rows = np.asarray(rows)
            pad_rows = np.broadcast_to(
                rows[:1], (len(padded) - len(ids_np), *rows.shape[1:])
            )
            rows = np.concatenate([rows, pad_rows])
        jids = self._put(padded)
        self._values = self._jit_cache["scatter"](self._values, jids, self._put(rows))
        self._defer_valid(ids_np, True)  # dirty: lazy materialization covers it
        self._stale_count -= int(np.count_nonzero(self._stale_host[ids_np]))
        self._stale_host[ids_np] = False
        self._bump()
        if RECORDER.enabled:
            RECORDER.note(
                "table_refreshed",
                key=f"table:{id(self):x}",
                detail=f"{len(ids_np)} rows",
            )
        for handler in self.on_refresh:
            handler(ids_np)

    def invalidate(self, ids: Ids) -> None:
        """Mark rows stale; notifies subscribers (the cascade entry point).
        Ids are deduped: on_invalidate handlers see each row once."""
        ids_np = self._mark_stale(ids)
        if ids_np is not None:
            if RECORDER.enabled:
                # one event per CALL (never per row): host-led bulk marks
                # show up in the flight journal; wave-driven staleness is
                # already journaled by the backend's wave event
                RECORDER.note(
                    "table_invalidated",
                    key=f"table:{id(self):x}",
                    detail=f"{len(ids_np)} rows",
                )
            for handler in self.on_invalidate:
                handler(ids_np)

    def _mark_stale_from_wave_mask(self, rows_mask: np.ndarray) -> None:
        """Mask twin of :meth:`_mark_stale_from_wave` for lane bursts: the
        wave's newly-rows arrive as bool[rows] (possibly a prefix slice)
        and apply as two vectorized mask ops — no id materialization."""
        if not rows_mask.any():
            return
        sub = self._stale_host[: len(rows_mask)]
        self._stale_count += int(np.count_nonzero(rows_mask & ~sub))
        sub |= rows_mask
        self._valid_dev_dirty = True
        self._bump()

    def _mark_stale_from_wave(self, ids: Ids) -> None:
        """Device-wave application path (graph backend): mark rows stale
        WITHOUT firing ``on_invalidate`` — the wave already owns the cascade
        and the scalar-twin application (two-tier, graph/backend.py), so the
        table→scalar hook firing here would re-walk the whole wave in
        per-row Python. The device mask update is DEFERRED (dirty flag;
        wave ids are already unique, and a 10M-row id scatter would upload
        40 MB per burst). ``changed`` still advances."""
        ids_np = np.asarray(ids, dtype=np.int32)
        if ids_np.size == 0:
            return
        self._stale_count += int(np.count_nonzero(~self._stale_host[ids_np]))
        self._stale_host[ids_np] = True
        self._valid_dev_dirty = True
        self._bump()

    def _mark_stale(self, ids: Ids) -> Optional[np.ndarray]:
        """Shared staleness bookkeeping; returns the deduped ids (None when
        empty) so :meth:`invalidate` can notify with exactly what changed."""
        ids_np = np.unique(np.asarray(ids, dtype=np.int32))
        if ids_np.size == 0:
            return None
        self._stale_count += int(np.count_nonzero(~self._stale_host[ids_np]))
        self._stale_host[ids_np] = True
        self._defer_valid(ids_np, False)
        self._bump()
        return ids_np

    def invalidate_all(self) -> None:
        self._stale_host[:] = True
        self._stale_count = self.n_rows
        self._valid_dev = self._jnp.zeros_like(self._valid_dev)
        self._valid_dev_dirty = False
        self._valid_pending.clear()
        self._valid_pending_n = 0
        self._bump()
        if self.on_invalidate:
            all_ids = np.arange(self.n_rows, dtype=np.int32)
            for handler in self.on_invalidate:
                handler(all_ids)

    def _bump(self) -> None:
        self.version += 1
        self.changed = self.changed.create_next(self.version)

    # ------------------------------------------------------------------ checkpoint
    def export_state(self) -> dict:
        """Snapshot of the columnar state (values + per-row validity +
        version) for checkpoint/resume — the restart-surviving analogue of
        the reference's persistent client cache
        (Client/Caching/ClientComputedCache.cs:35-49)."""
        return {
            "values": np.asarray(self._values),
            "valid": (~self._stale_host).copy(),
            "version": int(self.version),
        }

    def import_state(self, state: dict) -> None:
        """Restore :meth:`export_state` output: valid rows read as warm
        hits immediately; stale rows refresh on first touch. Invalidation
        wiring (on_invalidate, codec) is the LIVE table's — import only
        replaces the row data, so post-restore invalidations propagate
        exactly like pre-snapshot ones."""
        values = np.asarray(state["values"])
        if values.shape != tuple(np.asarray(self._values).shape):
            raise ValueError(
                f"checkpoint shape {values.shape} != table shape "
                f"{tuple(np.asarray(self._values).shape)}"
            )
        valid = np.asarray(state["valid"], dtype=bool)
        self._values = self._put(values)
        self._stale_host = ~valid
        self._stale_count = int((~valid).sum())
        self._valid_dev = self._put(valid)
        self._valid_dev_dirty = False
        self._valid_pending.clear()
        self._valid_pending_n = 0
        self._packed_cache = None
        self.version = int(state["version"])
        self._bump()

    # ------------------------------------------------------------------ misc
    def place_on(self, device) -> None:
        """Commit this table's device state to ``device`` and send every
        later upload there directly (``TpuGraphBackend.bind_table_rows`` of
        a backend that was given one: the table then lives beside the graph
        its rows are nodes of). A table is made before it is bound; one
        that has computed nothing yet is made anew on the device (its zeros
        never cross from the default device), any other is moved, once."""
        jax, jnp = self._jax, self._jnp
        self._put = functools.partial(jax.device_put, device=device)
        if self._stale_count == self.n_rows and self.version == 0:
            with jax.default_device(device):
                values = jnp.zeros(self._values.shape, dtype=self._values.dtype)
                valid = jnp.zeros(self.n_rows, dtype=jnp.bool_)
        else:
            values, valid = self._values, self.valid_mask
        self._values = jax.device_put(values, device)
        self._valid_dev = jax.device_put(valid, device)
        self._packed_cache = None

    def stale_count(self) -> int:
        return self._stale_count

    def __repr__(self) -> str:
        return f"MemoTable({self.n_rows} rows, {self.stale_count()} stale, v{self.version})"


@functools.lru_cache(maxsize=1)
def _kernels():
    """Module-level jitted kernels: per-instance closures would give every
    MemoTable its own compile cache and recompile identical programs."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gather(values, ids):
        return values[ids]

    @jax.jit
    def scatter(values, ids, rows):
        return values.at[ids].set(rows)

    from functools import partial

    @partial(jax.jit, static_argnums=2)
    def set_mask(mask, ids, on):
        return mask.at[ids].set(on)

    @jax.jit
    def set_mask_vals(mask, ids, vals):
        return mask.at[ids].set(vals)

    from .bitops import pack_bool_bits_jit

    pack = pack_bool_bits_jit()  # shared wrapper: one trace cache repo-wide

    return {
        "gather": gather, "scatter": scatter, "set_mask": set_mask,
        "set_mask_vals": set_mask_vals, "pack": pack,
    }
