"""``lone_wave``: closed loop, one caller, single-row edits through the
blocking ``TpuGraphBackend.cascade_rows_batch`` (it returns the closure's
count, so the device has finished when it returns).

The rows come in blocks drawn from the seed: every block holds the same
number of rows of each class of the mix (the traffic file's ``classes``), in
shuffled order, so that every seed does the same work in another order.
After each block the stale rows are restored (``refresh_block_on_device``,
then wait), outside every timed sample. The latency statistics are taken
over ALL timed edits of the window.

``correct``: every edit's count against the host BFS (the newly stale rows:
the closure less what earlier edits of the block left stale), the stale mask
read back after the window's last block against the union of its closures,
and no counted fallback.
"""
from __future__ import annotations

import time

import numpy as np


class Driver:
    CONTROLS = ("direct_only",)

    def __init__(self, ctx, dep):
        self.ctx, self.dep, self.m = ctx, dep, ctx.m
        self.rng = np.random.default_rng([ctx.seed, 0x10AE])
        self.waves: list = []  # (block index, row, class index, count, ms)
        self.final_stale = None
        self.attempted = 0
        self.failed = 0
        self.elapsed = 0.0
        self._fallbacks: dict = {}
        self._block = 0

    # ------------------------------------------------------------------ set-up
    async def setup(self) -> None:
        from lib.result import note
        from stl_fusion_tpu.graph.program_cache import time_program_warm

        ctx, dep, n = self.ctx, self.dep, self.dep.n
        self.classes = ctx.param("classes")
        self.block_rows = int(ctx.param("rows_per_block"))
        per_class = [int(round(c["share"] * self.block_rows)) for c in self.classes]
        per_class[0] += self.block_rows - sum(per_class)
        self.per_class = per_class
        note(f"lone edits: blocks of {self.block_rows} rows, "
             + ", ".join(f"{k} {c['name']}" for k, c in zip(per_class, self.classes)))
        with time_program_warm("union", key=(n, "lat+topo")):
            # a shallow wave compiles the lat kernel; a deep one overflows it
            # into the fused topo union, which serves the rare mid-range row
            # whose closure does not fit the lat mirror
            dep.backend.cascade_rows_batch(dep.block, [n - 1])
            dep.backend.cascade_rows_batch(dep.block, [n // 20])
        with time_program_warm("refresh", key=(n,)):
            dep.restore()
        # one whole block, untimed, through the window's own path
        self._run_block(record=False, deadline=None)
        self._read_stale()
        dep.restore()

    def _draw_block(self):
        n = self.dep.n
        rows, classes = [], []
        for ci, (k, c) in enumerate(zip(self.per_class, self.classes)):
            lo, hi = int(c["id_range"][0] * n), int(c["id_range"][1] * n)
            rows.append(lo + self.rng.choice(hi - lo, size=k, replace=False))
            classes.append(np.full(k, ci))
        rows, classes = np.concatenate(rows), np.concatenate(classes)
        order = self.rng.permutation(len(rows))
        return rows[order].tolist(), classes[order].tolist()

    def _run_block(self, record: bool, deadline) -> bool:
        """One block of timed edits. Returns False when the deadline passed
        before the block was through (the block is then left unrestored)."""
        dep, m = self.dep, self.m
        rows, classes = self._draw_block()
        cascade, block = dep.backend.cascade_rows_batch, dep.block
        for row, ci in zip(rows, classes):
            if deadline is not None and time.perf_counter() >= deadline:
                return False
            with m.span("lone_wave"):
                t0 = time.perf_counter()
                count = cascade(block, [row])
                ms = (time.perf_counter() - t0) * 1e3
            if record:
                self.waves.append((self._block, row, ci, int(count), ms))
        return True

    def _read_stale(self) -> np.ndarray:
        return np.flatnonzero(~np.asarray(self.dep.table.valid_mask))

    # ------------------------------------------------------------------ window
    async def window(self, seconds: float) -> None:
        dep, m = self.dep, self.m
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            done = self._run_block(record=True, deadline=deadline)
            if not done or time.perf_counter() >= deadline:
                break
            with m.span("restore"):
                dep.restore()
            self._block += 1
        self.elapsed = time.perf_counter() - t0
        self.attempted = len(self.waves)
        # the answer the entry exposes besides the count: the stale mask the
        # window's last block left, read once the window has closed
        self.final_stale = self._read_stale()

    def counters(self) -> dict:
        gdev = self.dep.gdev
        return {
            "waves": len(self.waves),
            "lat_waves": gdev.lat_waves,
            "restores": self._block,
        }

    def end_to_end(self) -> dict:
        from lib.measure import percentile

        ms = [w[4] for w in self.waves]
        return {
            "lone_wave_p50_ms": percentile(ms, 50),
            "lone_wave_p95_ms": percentile(ms, 95),
        }

    # ----------------------------------------------------------------- correct
    def _reference(self, max_depth=None):
        """Per edit the count of newly stale rows, and the last block's
        stale set, by the host BFS (``max_depth`` only for the control)."""
        oracle = self.dep.oracle
        counts, stale, block = [], set(), -1
        for b, row, _ci, _count, _ms in self.waves:
            if b != block:
                stale, block = set(), b
            closure = oracle.closure_ids([row], max_depth=max_depth)
            counts.append(len(closure - stale))
            stale |= closure
        if block != self._block:  # the last block on record was restored
            stale = set()
        return counts, stale

    def _compare(self, counts, final_stale) -> list:
        want_counts, want_stale = self._reference()
        wrong = sum(1 for a, b in zip(counts, want_counts) if a != b)
        mask_wrong = len(want_stale.symmetric_difference(set(final_stale)))
        return [
            {"name": "wave_count_mismatches", "value": wrong, "limit": 0},
            {"name": "stale_mask_mismatches", "value": mask_wrong, "limit": 0},
        ]

    async def check(self) -> list:
        out = self._compare([w[3] for w in self.waves], self.final_stale.tolist())
        self._fallbacks, compared = self.dep.fallbacks_compared()
        out.append(compared)
        return out

    def control(self, kind: str) -> list:
        """``direct_only``: the reference in the program's place with the
        guarantee 'every transitive dependent' broken: the cascade stops at
        the direct dependents."""
        counts, stale = self._reference(max_depth=1)
        return self._compare(counts, sorted(stale))

    def notes(self) -> dict:
        """What PERF.md needs to show where the percentiles lie: per class
        the count, the time percentiles and the closure sizes, and the class
        make-up of the samples around the overall median and 95th."""
        from lib.measure import percentile

        ms_all = [w[4] for w in self.waves]
        p50, p95 = percentile(ms_all, 50), percentile(ms_all, 95)
        per_class = {}
        for ci, c in enumerate(self.classes):
            ms = [w[4] for w in self.waves if w[2] == ci]
            sizes = [w[3] for w in self.waves if w[2] == ci]
            if not ms:
                continue
            per_class[c["name"]] = {
                "n": len(ms),
                "ms_p10": percentile(ms, 10), "ms_p50": percentile(ms, 50),
                "ms_p90": percentile(ms, 90), "ms_p99": percentile(ms, 99),
                "closure_p50": percentile(sizes, 50),
                "closure_p90": percentile(sizes, 90), "closure_max": max(sizes),
                "single_node_share": sum(1 for s in sizes if s <= 1) / len(sizes),
            }

        def share_of(ci, lo, hi):
            near = [w for w in self.waves if lo <= w[4] <= hi]
            return sum(1 for w in near if w[2] == ci) / max(len(near), 1)

        return {
            "waves": len(self.waves), "blocks_restored": self._block,
            "window_s": self.elapsed,
            "classes": per_class,
            "first_class_share_within_2pct_of_p50": share_of(0, p50 * 0.98, p50 * 1.02),
            "last_class_share_within_5pct_of_p95": share_of(
                len(self.classes) - 1, p95 * 0.95, p95 * 1.05),
            "ms_p99": percentile(ms_all, 99), "ms_max": max(ms_all),
            "fallbacks": self._fallbacks,
            "counters": self.m.counters,
        }

    async def close(self) -> None:
        pass
