"""``routed_waves``: closed loop, one caller, back-to-back routed waves
through the blocking ``TpuGraphBackend.cascade_rows_batch_routed`` (it
returns the closure's count once the wave has been applied to the hub), each
followed by the restore (``refresh_block_on_device`` + ``flush``, waited
for). Wave and restore are both inside the window.

Every wave is the same wave: ONE set of seed rows, fixed by the traffic
file (``groups_seed``), because a set's BFS depth decides what a wave costs.
The window runs whole waves until ``--seconds`` have passed and
``min_waves`` are harvested, and closes after the last wave's restore.
``inv_per_s`` is the waves' returned counts summed over the time from the
first dispatch to the last restore's end, less the seconds of the one mask
read that the check makes between the last wave and its restore.

``correct``: every wave's count against the size of the host BFS closure of
the seeds; the table's stale mask and the graph's invalid mask on the device
after the window's last wave against the closure's mask; nothing stale after
the last restore, on the table or in the graph; the table's values on rows
drawn from ``--seed`` against the store; the routed graph's arrays each laid
out block ``d`` on device ``d`` of the mesh; no counted fallback.
"""
from __future__ import annotations

import time

import numpy as np


class Driver:
    CONTROLS = ("direct_only",)

    def __init__(self, ctx, dep):
        self.ctx, self.dep, self.m = ctx, dep, ctx.m
        self.rng = np.random.default_rng([ctx.seed, 0x40D7ED])
        self.counts: list = []  # per wave of the window, as returned
        self.wave_s: list = []
        self.restore_s: list = []
        self.final_stale = None  # _read_stale() after the window's last wave
        self.mask_read_s = 0.0
        self.warm_wave_s = 0.0
        self.attempted = 0
        self.failed = 0
        self.elapsed = 0.0
        self._fallbacks: dict = {}

    # ------------------------------------------------------------------ set-up
    async def setup(self) -> None:
        from lib.result import note
        from stl_fusion_tpu.graph.program_cache import time_program_warm

        ctx, dep, n = self.ctx, self.dep, self.dep.n
        if int(ctx.param("seed_sets")) != 1:
            raise ValueError("routed_waves runs one fixed seed set")
        k = int(ctx.param("seeds_per_wave"))
        lo, hi = ctx.param("seed_id_range")
        lo, hi = int(lo * n), max(int(hi * n), k)
        grng = np.random.default_rng(int(ctx.param("groups_seed")))
        self.seeds = np.sort(lo + grng.choice(hi - lo, size=k, replace=False))
        self.min_waves = int(ctx.param("min_waves"))
        rows = min(int(ctx.param("check_value_rows")), n)
        self.value_rows = np.sort(self.rng.choice(n, size=rows, replace=False))
        note(f"routed waves: {k:,} seed rows of [{lo:,}, {hi:,}), one fixed set; "
             "one untimed wave and restore")
        # the routed program's own compile is recorded by the program
        # (routed_collect); the wave that follows it is not a warm's time
        t0 = time.perf_counter()
        self._wave()
        self.warm_wave_s = time.perf_counter() - t0
        with time_program_warm("refresh", key=(n,)):
            dep.restore()
            # the window's last restore follows the check's read of
            # valid_mask, which makes the refresh keep the device's validity
            # mask too: another program. Compile it here, on a clean table
            self._read_stale()
            dep.backend.refresh_block_on_device(dep.block)
            dep.restore()

    def _wave(self) -> int:
        return int(self.dep.backend.cascade_rows_batch_routed(self.dep.block, self.seeds))

    def _read_stale(self) -> tuple:
        """(the table's stale rows, the graph's invalid nodes on the device),
        bool[n] each: after a wave both are its closure."""
        dep = self.dep
        return ~np.asarray(dep.table.valid_mask), dep.gdev.invalid_mask()

    # ------------------------------------------------------------------ window
    async def window(self, seconds: float) -> None:
        dep, m = self.dep, self.m
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with m.span("wave"):
                self.counts.append(self._wave())
            t1 = time.perf_counter()
            self.wave_s.append(t1 - t)
            last = t1 - t0 >= seconds and len(self.counts) >= self.min_waves
            if last:
                # the answer the entry exposes besides the count, read
                # before the restore clears it; not the product's time
                with m.span("mask_read"):
                    self.final_stale = self._read_stale()
                self.mask_read_s = time.perf_counter() - t1
            t = time.perf_counter()
            with m.span("restore"):
                dep.restore()
            self.restore_s.append(time.perf_counter() - t)
            if last:
                break
        self.elapsed = time.perf_counter() - t0 - self.mask_read_s
        self.attempted = len(self.counts)

    def counters(self) -> dict:
        dep = self.dep
        return {
            "waves": len(self.counts),
            "exchange_levels": dep.routed.levels_total,
            "routed_overflows": dep.metric("fusion_mesh_routed_overflows_total"),
        }

    def end_to_end(self) -> dict:
        return {"inv_per_s": sum(self.counts) / self.elapsed}

    # ----------------------------------------------------------------- correct
    def _reference(self, direct_only: bool = False) -> np.ndarray:
        """bool[n]: the seeds and every transitive dependent, by the host
        BFS (the control stops after the direct dependents)."""
        oracle = self.dep.oracle
        if not direct_only:
            return oracle.closure(self.seeds)
        mask = np.zeros(self.dep.n, dtype=bool)
        mask[self.seeds] = True
        mask[oracle.out_neighbors(self.seeds)] = True
        return mask

    def _compare(self, want: np.ndarray) -> list:
        size = int(np.count_nonzero(want))
        wrong = sum(1 for c in self.counts if c != size)
        table_stale, graph_invalid = self.final_stale
        return [
            {"name": "wave_count_mismatches", "value": wrong, "limit": 0},
            {"name": "stale_mask_mismatches",
             "value": int(np.count_nonzero(want != table_stale)), "limit": 0},
            {"name": "graph_mask_mismatches",
             "value": int(np.count_nonzero(want != graph_invalid)), "limit": 0},
        ]

    async def check(self) -> list:
        dep = self.dep
        # after the window's last restore: nothing stale anywhere, and the
        # refreshed rows hold the store's values
        still_stale = int(dep.table.stale_count()) + int(
            np.count_nonzero(dep.gdev.invalid_mask())
        )
        rows = self.value_rows
        got = np.asarray(dep.table.values[rows])
        self._want = self._reference()
        out = self._compare(self._want)
        out.append({"name": "stale_after_restore", "value": still_stale, "limit": 0})
        out.append({"name": "value_mismatches",
                    "value": int(np.count_nonzero(got != dep.svc.base[rows])),
                    "limit": 0})
        out.append(dep.layout_compared())
        self._fallbacks, compared = dep.fallbacks_compared()
        out.append(compared)
        return out

    def control(self, kind: str) -> list:
        """``direct_only``: the reference in the program's place with the
        guarantee 'every transitive dependent' broken: the cascade stops at
        the direct dependents."""
        return self._compare(self._reference(direct_only=True))

    def notes(self) -> dict:
        import jax

        chips = self.ctx.cell["chips"]
        return {
            "waves": len(self.counts), "window_s": self.elapsed,
            "mask_read_s": self.mask_read_s,
            "seeds_per_wave": int(len(self.seeds)),
            "closure": int(np.count_nonzero(self._want)),
            "counts": self.counts, "wave_s": self.wave_s, "restore_s": self.restore_s,
            "warm_wave_s": self.warm_wave_s,
            "build_s": self.dep.build_s,
            "layout": getattr(self.dep, "layout", None),
            "peak_bytes_by_chip": [
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.devices()[:chips]
            ],
            "program_warms": _warms(),
            "collect_ops_s_per_chip": _collect_ops(self.m.trace),
            "fallbacks": self._fallbacks,
            "counters": self.m.counters,
        }

    async def close(self) -> None:
        pass


def _warms() -> dict:
    from stl_fusion_tpu.graph.program_cache import program_warm_report

    return {k: [v["warm_s"], v["cache_hit"]] for k, v in program_warm_report().items()}


def _collect_ops(trace):
    """Every device operation family of ``jit_collect`` with its seconds in
    the window, mean over the chips that ran (the result's ``breakdown``
    keeps only the ten largest of all programs); None in an untraced run."""
    if trace is None:
        return None
    return {
        key.partition(":")[2]: seconds / trace.devices_busy
        for key, seconds in trace.device_ops if key.startswith("jit_collect:")
    }
