"""``superround_burst``: closed loop, one driver, resident super-rounds with
churn between them (``chip_smoke.py``'s leg (b), run for a window).

One super-round: churn prep (declared edges that follow the DAG's level
order, scalar recaptures) → ``stage`` → ``flush`` →
``refresh_block_on_device`` → ``dispatch`` → ``harvest``. The window runs
whole super-rounds until ``--seconds`` have passed; ``inv_per_s`` is every
invalidation of every harvested round over the whole window.

``correct``: lane counts of groups drawn from the seed in EVERY super-round
of the window against the host CSR BFS over the topology as churned when
that super-round was dispatched; every round of a
super-round against its first (all lanes, all super-rounds); no counted
fallback; every super-round served by the resident program.
"""
from __future__ import annotations

import time

import numpy as np


class Driver:
    CONTROLS = ("stale_topology",)

    def __init__(self, ctx, dep):
        self.ctx, self.dep, self.m = ctx, dep, ctx.m
        self.rng = np.random.default_rng([ctx.seed, 0xB0257])
        self.depth = int(ctx.size("super_round_depth"))
        self.history: list = []  # one record per super-round of the window
        self.attempted = 0  # rounds harvested in the window
        self.failed = 0
        self.invalidations = 0
        self.elapsed = 0.0
        self._scalar_cursor = 0
        self._sample = None
        self._checked = 0
        self._fallbacks: dict = {}

    # ------------------------------------------------------------------ set-up
    async def setup(self) -> None:
        from lib.result import note
        from stl_fusion_tpu.graph.program_cache import time_program_warm

        ctx, dep, n = self.ctx, self.dep, self.dep.n
        groups = int(ctx.param("lane_groups"))
        per_group = int(ctx.param("seeds_per_group"))
        lo, hi = ctx.param("seed_id_range")
        lo, hi = int(lo * n), max(int(hi * n), per_group + 1)
        # the SET of lane groups is the mix's own (drawn from its
        # groups_seed), so that every --seed sweeps the same closures; the
        # seed deals them to the lanes in another order
        grng = np.random.default_rng(int(ctx.param("groups_seed")))
        fixed = [
            (lo + grng.choice(hi - lo, size=per_group, replace=False)).tolist()
            for _ in range(groups)
        ]
        self.group_ids = [fixed[i] for i in self.rng.permutation(groups)]
        # a toy graph takes churn in proportion (chip_smoke.py's rule:
        # 2,000 edges a round on 20 k nodes is a rebuild a round, not churn)
        self.edge_churn = int(ctx.param("edge_churn_per_round"))
        if ctx.rehearsal:
            self.edge_churn = max(n // 250, 8)
        self.scalar_churn = int(ctx.param("scalar_churn_per_round"))
        indeg = dep.oracle.in_degree()
        low_indeg = np.nonzero(indeg[: n // 2] <= 4)[0]
        pool = min(int(ctx.param("scalar_row_pool")), len(low_indeg))
        # the recaptured rows are the mix's own sequence too: a row with a
        # deep closure sends its cascade through a full sweep inside flush
        # (0.15 s, 1.0 s or 2.7 s a flush on the chip), so rows drawn anew
        # per seed made the window's work a coin toss per super-round
        self.scalar_rows = grng.choice(low_indeg, size=pool, replace=False)
        # the benchmark's own level table (the generated DAG's longest-path
        # levels): churn is oriented by it, with no call into the program
        self.level = dep.oracle.levels()
        warm = int(ctx.param("warm_super_rounds"))
        note(f"warming the resident super-round program ({warm} super-rounds)")
        with time_program_warm("superround", key=(n, groups, self.depth)):
            # the resident program has two variants (memo validity mask folded
            # in-program or deferred), so a second super-round can compile
            for _ in range(warm):
                await self.super_round(record=False)

    # ---------------------------------------------------------------- one unit
    async def prep_churn(self) -> None:
        """One super-round's churn: per round, random pairs declared as edges
        from the lower level of the DAG to the higher (a dependency on
        something computed earlier: acyclic, and no node's level changes;
        same-level pairs are dropped), plus scalar recaptures of
        low-in-degree rows. The flush before the next dispatch applies it."""
        from stl_fusion_tpu.core import invalidating

        dep, n = self.dep, self.dep.n
        for _ in range(self.depth):
            a = self.rng.integers(0, n, size=self.edge_churn)
            b = self.rng.integers(0, n, size=self.edge_churn)
            la, lb = self.level[a], self.level[b]
            keep = la != lb
            u = np.where(la < lb, a, b)[keep]
            v = np.where(la < lb, b, a)[keep]
            dep.backend.declare_row_edges(dep.block, u, dep.block, v)
            dep.oracle.add_edges(u, v)
        for _ in range(self.depth * self.scalar_churn):
            row = int(self.scalar_rows[self._scalar_cursor % len(self.scalar_rows)])
            self._scalar_cursor += 1
            with invalidating():
                await dep.svc.node(row)
            await dep.svc.node(row)

    async def super_round(self, record: bool = True) -> int:
        dep, m = self.dep, self.m
        with m.span("churn_prep"):
            await self.prep_churn()
        with m.span("stage"):
            staged = dep.sr.stage([self.group_ids] * self.depth)
        with m.span("flush"):
            dep.backend.flush()
        with m.span("refresh"):
            dep.backend.refresh_block_on_device(dep.block)
        with m.span("dispatch"):
            ticket = dep.sr.dispatch(staged)
        with m.span("harvest"):
            per_burst = ticket.harvest()
        counts = [np.asarray(c, dtype=np.int64) for c in per_burst]
        inv = int(sum(int(c.sum()) for c in counts))
        if record:
            spans = {k: m.spans[k][-1][1] - m.spans[k][-1][0]
                     for k in ("churn_prep", "stage", "flush", "refresh", "dispatch", "harvest")}
            self.history.append({"chunks": dep.oracle.chunks, "counts": counts,
                                 "inv": inv, "spans": spans})
            self.attempted += len(counts)
            self.invalidations += inv
        return inv

    # ------------------------------------------------------------------ window
    async def window(self, seconds: float) -> None:
        from lib.result import note

        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            t1 = time.perf_counter()
            inv = await self.super_round()
            note(f"super-round {len(self.history)}: {inv:,} invalidations in "
                 f"{time.perf_counter() - t1:.2f}s")
        self.elapsed = time.perf_counter() - t0

    def counters(self) -> dict:
        sr, gdev = self.dep.sr.stats(), self.dep.gdev
        return {
            "superrounds": sr["superrounds_dispatched"],
            "rounds": sr["rounds_total"],
            "mirror_patch_s": gdev.mirror_patch_s,
            "mirror_patches": gdev.mirror_patches,
            "mirror_rebuilds": gdev.mirror_rebuilds,
            "superround_stall_s": sr["stall_s"],
            "superround_stage_s": sr["stage_s"],
        }

    def end_to_end(self) -> dict:
        return {"inv_per_s": self.invalidations / self.elapsed}

    # ----------------------------------------------------------------- correct
    def _sampled(self):
        """(super-round index, group index) pairs drawn from the seed: some
        groups of every super-round of the window."""
        if self._sample is None:
            rng = np.random.default_rng([self.ctx.seed, 0x5A3B1E])
            k = int(self.ctx.param("check_groups_per_superround"))
            self._sample = [
                (sr_i, int(g))
                for sr_i in range(len(self.history))
                for g in rng.choice(len(self.group_ids), size=k, replace=False)
            ]
        return self._sample

    def _compare(self, answers: dict, with_rounds: bool = True) -> list:
        """``answers[(sr, round, group)]`` = lane count given, against the
        reference's closure over the topology of that super-round."""
        oracle = self.dep.oracle
        wrong = checked = 0
        for sr_i, g in self._sampled():
            rec = self.history[sr_i]
            want = int(oracle.closure(self.group_ids[g], chunks=rec["chunks"]).sum())
            for rnd in (0, self.depth - 1):
                checked += 1
                if int(answers[(sr_i, rnd, g)]) != want:
                    wrong += 1
        out = [{"name": "lane_count_mismatches", "value": wrong, "limit": 0}]
        self._checked = checked
        if with_rounds:
            disagree = sum(
                int((rec["counts"][r] != rec["counts"][0]).sum())
                for rec in self.history for r in range(1, len(rec["counts"]))
            )
            self._fallbacks, fallbacks = self.dep.fallbacks_compared()
            not_resident = len(self.history) - self.m.counters.get("superrounds", 0)
            out += [
                {"name": "round_disagreements", "value": disagree, "limit": 0},
                fallbacks,
                {"name": "superrounds_not_resident", "value": abs(not_resident), "limit": 0},
            ]
        return out

    async def check(self) -> list:
        answers = {
            (sr_i, rnd, g): self.history[sr_i]["counts"][rnd][g]
            for sr_i, g in self._sampled() for rnd in (0, self.depth - 1)
        }
        return self._compare(answers)

    def control(self, kind: str) -> list:
        """``stale_topology``: the reference in the program's place, with the
        guarantee 'closures over the topology as churned so far' broken: its
        lane counts are those of the generated DAG, the declared edges never
        applied (a mirror that is never patched)."""
        oracle = self.dep.oracle
        answers = {}
        for sr_i, g in self._sampled():
            stale = int(oracle.closure(self.group_ids[g], chunks=1).sum())
            for rnd in (0, self.depth - 1):
                answers[(sr_i, rnd, g)] = stale
        return self._compare(answers, with_rounds=False)

    def notes(self) -> dict:
        return {
            "superrounds": len(self.history),
            "rounds": self.attempted,
            "invalidations": self.invalidations,
            "window_s": self.elapsed,
            "edges_declared": self.dep.oracle.edges_declared(),
            "lane_counts_checked": self._checked,
            "per_superround": [dict(rec["spans"], inv=rec["inv"]) for rec in self.history],
            "fallbacks": self._fallbacks,
            "counters": self.m.counters,
        }

    async def close(self) -> None:
        pass
