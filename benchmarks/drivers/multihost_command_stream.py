"""``multihost_command_stream``: ``command_stream``'s closed loop over a
``multihost_dag`` deployment: ONE writer, one command in flight, no think
time; ``Bump(row, delta)`` through the writer's routed
``ClusterCommander.call`` (forwarded to the member that owns the row's key),
watched on EVERY member by that member's subscribed clients, which re-read
from their own member when they observe.

Rows, deltas and the Zipf deal are ``command_stream``'s, drawn from
``--seed``. On each member every pool row is watched by ``subs_per_key``
clients on the row itself and as many on its first direct dependent; which
client holds which is dealt from ``--seed`` member by member (a
``command_stream.Driver`` per member does the dealing, subscribing, reading
and observing: that code is shared, not copied). A sample is the host clock
from the writer's entry into ``ClusterCommander.call`` to the moment the
LAST of the command's subscriptions, over all members, saw its invalidation
at its client: the forward, the journal, the owner's wave, the other
readers' wake-up, read and replay, their waves and every member's fan-out
lie inside; the re-reads that follow lie outside. The next command goes when
the last re-read has returned. Nothing is reset. ``warm_commands`` untimed
commands go first through the same loop, and more until every member has
owned at least one.

``correct``, every comparison exact against ``lib/mhref.py``'s replay of the
run's own events: every acknowledged id in the log; the log's records of this
run equal to the reference's journal, id by id in order, each under the agent
of the member the reference's own shard map names; the one store; on every
member, per command, who observed and the newly invalid count, every re-read
value, and the table's stale mask after the window; every member replayed
every operation of the others once and none of its own, its watermark at the
log's last index; every resident array of member ``k`` committed to device
``k`` alone; no counted fallback on any member, no retry, dedup or error on
the routed hop, no lane burst from a reader; every wave of every member
served by the small-wave path (lat + overflow = members x commands).
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from drivers import command_stream


class Driver:
    CONTROLS = ("direct_only", "lost_replay")

    def __init__(self, ctx, dep):
        self.ctx, self.dep, self.m = ctx, dep, ctx.m
        self.rng = np.random.default_rng([ctx.seed, 0xC0DE])
        self.observe_timeout_s = float(ctx.param("observe_timeout_s"))
        #: one per member: its subscriptions, its clients' reads, what its
        #: clients observed of the command in flight
        self.views = []
        for k, mem in enumerate(dep.members):
            view = command_stream.Driver(ctx, mem)
            view.rng = np.random.default_rng([ctx.seed, 0xC0DE, k + 1])
            self.views.append(view)
        self.events: list = []  # lib.mhref events, warm-up included
        #: per command, warm-up included: dict(op, row, timed, t0, t_call,
        #: seen: per member what its view recorded, drain: per member its
        #: drain ticks, rereads: per member the values)
        self.commands: list = []
        self.first_timed = 0
        self.final_stale: list = []  # per member
        self.attempted = 0
        self.failed = 0
        self.elapsed = 0.0
        self._fallbacks: dict = {}
        self._journal: list = []  # _logged(), read once the window closed
        self._misplaced: dict = {}
        self.subscribe_s = 0.0

    # ------------------------------------------------------------------ set-up
    async def setup(self) -> None:
        from lib.result import note
        from stl_fusion_tpu.graph.program_cache import time_program_warm

        ctx, dep = self.ctx, self.dep
        pool = len(dep.pool_rows)
        per_key = int(ctx.param("subs_per_key"))
        self.deal = self.rng.permutation(pool)
        ranks = np.arange(1, pool + 1, dtype=np.float64)
        weights = ranks ** -float(ctx.param("zipf_s"))
        self.cdf = np.cumsum(weights / weights.sum())
        for view, mem in zip(self.views, dep.members):
            view._deal_subscriptions(per_key, len(mem.clients))
            note(f"{mem.name}: subscribing {len(view.subscriptions)} $sys-c "
                 f"subscriptions ({len(mem.clients)} clients)")
            with self.m.span("subscribe"):
                await asyncio.gather(*(
                    view._subscribe(ci, [r for c, r in view.subscriptions if c == ci])
                    for ci in range(len(mem.clients))
                ))
            if mem.server_rpc.compute_fanout.stats()["subscriptions"] != len(view.subscriptions):
                raise RuntimeError(f"{mem.name}: the fan-out index does not hold "
                                   "every subscription")
        with time_program_warm("cmd_wave", key=(dep.n, "lat", len(dep.members))):
            # the window's own loop, untimed: every member compiles its lat
            # program for its own chip and takes every link through a frame
            warm, most = int(ctx.param("warm_commands")), 10 * int(ctx.param("warm_commands"))
            while len(self.commands) < warm or (
                len(self._local_counts()) < len(dep.members) and len(self.commands) < most
            ):
                if not await self._command(timed=False):
                    raise RuntimeError("a warm-up command was never observed everywhere")
        self.subscribe_s = self.m.span_seconds("subscribe")
        owned = self._local_counts()
        if len(owned) < len(dep.members):
            raise RuntimeError(f"the warm-up reached only {sorted(owned)}")
        note(f"warm-up: {len(self.commands)} commands, applied by {owned}")
        self.first_timed = len(self.commands)

    def _local_counts(self) -> dict:
        """member -> operations of this run it journaled, by the log."""
        out: dict = {}
        for _op, name in self._logged():
            out[name] = out.get(name, 0) + 1
        return out

    # ------------------------------------------------------------------ window
    async def _command(self, timed: bool) -> bool:
        dep, m = self.dep, self.m
        rank = int(np.searchsorted(self.cdf, self.rng.random(), side="right"))
        row = int(dep.pool_rows[self.deal[min(rank, len(self.deal) - 1)]])
        delta = float(self.rng.integers(1, 10))
        op = f"mh-{self.ctx.seed}-{len(self.commands)}"
        seen = []
        for view in self.views:
            view._current = {"observed": [], "expect": len(view.watchers[row]),
                             "t_seen": None}
            view._seen.clear()
            seen.append(view._current)
        cmd = {"op": op, "row": row, "timed": timed, "seen": seen,
               "drains": [len(mem.drains) for mem in dep.members]}
        self.commands.append(cmd)
        with m.span("cmd"):
            cmd["t0"] = time.perf_counter()
            await dep.writer.call(dep.Bump(row, delta), operation_id=op)
            cmd["t_call"] = time.perf_counter()
            self.events.append(["cmd", op, row, delta, None])
            try:
                await asyncio.wait_for(
                    asyncio.gather(*(view._seen.wait() for view in self.views)),
                    self.observe_timeout_s,
                )
            except asyncio.TimeoutError:
                self.failed += 1
                return False
        cmd["drain"] = [mem.drains[start:] for mem, start in zip(dep.members, cmd["drains"])]
        reads = [
            (k, ci, r) for k, view in enumerate(self.views)
            for ci, r in view.watchers[row]
        ]
        with m.span("reread"):
            values = await asyncio.gather(*(
                self.views[k]._read(ci, r) for k, ci, r in reads
            ))
        cmd["rereads"] = [[] for _ in self.views]
        for (k, ci, r), value in zip(reads, values):
            self.events.append(("reread", dep.members[k].name, ci, r, value))
            cmd["rereads"][k].append(value)
        for view in self.views:
            view._current = None
        return True

    async def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            if not await self._command(timed=True):
                break
        self.elapsed = time.perf_counter() - t0
        timed = self.commands[self.first_timed:]
        self.attempted = len(timed)
        done = [c for c in timed if "rereads" in c]
        pairs = [  # (command, member index) with exactly one dispatching tick
            (c, k) for c in done for k in range(len(self.views))
            if len(c["drain"][k]) == 1
        ]
        if pairs:
            self.m.values["tick_wait_ms_per_cmd"] = 1e3 * float(np.mean(
                [c["drain"][k][0][0] - c["t_call"] for c, k in pairs]))
            self.m.values["deliver_ms_per_cmd"] = 1e3 * float(np.mean(
                [c["seen"][k]["t_seen"] - c["drain"][k][0][1] for c, k in pairs]))
        if done:
            self.m.values["mh_visible_skew_ms_per_cmd"] = 1e3 * float(np.mean(
                [self._skew(c) for c in done]))
        self.final_stale = [
            np.flatnonzero(~np.asarray(mem.table.valid_mask)) for mem in self.dep.members
        ]

    @staticmethod
    def _skew(cmd) -> float:
        times = [s["t_seen"] for s in cmd["seen"]]
        return max(times) - min(times)

    def counters(self) -> dict:
        members = self.dep.members
        pipes = [mem.pipe.stats() for mem in members]
        boxes = [mem.outbox_totals() for mem in members]
        return {
            "commands": max(len(self.commands) - self.first_timed, 0),
            "lat_waves": sum(p["lat_waves"] for p in pipes),
            "lat_overflow_waves": sum(p["lat_overflow_waves"] for p in pipes),
            "fused_dispatches": sum(p["fused_dispatches"] for p in pipes),
            "batch_frames": sum(b["batch_frames"] for b in boxes),
            "batch_keys": sum(b["batch_keys"] for b in boxes),
            "external_seen": sum(mem.reader.external_seen for mem in members),
            "replay_submitted": sum(mem.reader.replay_submitted for mem in members),
            "reader_lane_bursts": sum(mem.reader.replay_lane_bursts for mem in members),
        }

    def _samples(self) -> list:
        return [
            (max(s["t_seen"] for s in c["seen"]) - c["t0"]) * 1e3
            for c in self.commands[self.first_timed:] if "rereads" in c
        ]

    def end_to_end(self) -> dict:
        from lib.measure import percentile

        ms = self._samples()
        return {
            "lone_wave_p50_ms": percentile(ms, 50),
            "lone_wave_p95_ms": percentile(ms, 95),
        }

    # ----------------------------------------------------------------- correct
    def _logged(self) -> list:
        """(operation id, member that journaled it) of this run, in the
        log's order."""
        names = {mem.agent_id: mem.name for mem in self.dep.members}
        prefix = f"mh-{self.ctx.seed}-"
        return [
            (str(r.id), names.get(r.agent_id, r.agent_id))
            for r in self.dep.log_store.read_after(0, limit=1 << 30)
            if str(r.id).startswith(prefix)
        ]

    def _compare(self, expected) -> list:
        dep, first = self.dep, self.first_timed
        timed = self.commands[first:]
        logged = self._journal
        ids = {op for op, _owner in logged}
        acked = [c["op"] for c in self.commands if "t_call" in c]
        unjournaled = sum(1 for op in acked if op not in ids)
        journal_wrong = abs(len(logged) - len(expected.journal)) + sum(
            1 for got, want in zip(logged, expected.journal) if got != want
        )
        rows = set(dep.pool_rows.tolist()) | set(dep.pool_deps.tolist())
        store_wrong = sum(
            1 for r in rows
            if np.float32(dep.store[r]) != expected.store.get(r, np.float32(r))
        )
        observers_wrong = reread_wrong = counts_wrong = stale_wrong = 0
        for k, mem in enumerate(dep.members):
            want = expected.members[mem.name]
            observers_wrong += sum(
                1 for c, w in zip(timed, want.observers[first:])
                if frozenset(c["seen"][k]["observed"]) != w
                or len(c["seen"][k]["observed"]) != len(w)
            )
            before = sum(len(c["rereads"][k]) for c in self.commands[:first])
            got_values = [v for c in timed if "rereads" in c for v in c["rereads"][k]]
            want_values = want.reread_values[before:]
            reread_wrong += abs(len(got_values) - len(want_values)) + sum(
                1 for a, b in zip(got_values, want_values) if np.float32(a) != b
            )
            counts_wrong += sum(
                1 for c, w in zip(timed, want.newly_counts[first:])
                if [d[2] for d in c.get("drain", [()] * len(dep.members))[k]] != [w]
            )
            stale_wrong += len(
                want.table_stale.symmetric_difference(self.final_stale[k].tolist())
            )
        last = dep.log_store.last_index()
        replays_wrong = sum(
            (mem.reader.external_seen != expected.replays[mem.name])
            + (mem.reader.watermark != last)
            for mem in dep.members
        )
        return [
            {"name": "unjournaled_acks", "value": unjournaled, "limit": 0},
            {"name": "journal_mismatches", "value": journal_wrong, "limit": 0},
            {"name": "store_mismatches", "value": store_wrong, "limit": 0},
            {"name": "observer_mismatches", "value": observers_wrong, "limit": 0},
            {"name": "reread_mismatches", "value": reread_wrong, "limit": 0},
            {"name": "newly_count_mismatches", "value": counts_wrong, "limit": 0},
            {"name": "stale_mask_mismatches", "value": stale_wrong, "limit": 0},
            {"name": "replay_mismatches", "value": replays_wrong, "limit": 0},
        ]

    def _replay(self, **broken):
        from lib.mhref import replay

        dep = self.dep
        owners = dict(self._journal)
        for event in self.events:
            if event[0] == "cmd":
                event[4] = owners.get(event[1])  # what the system did
        return replay(
            dep.oracle, [mem.name for mem in dep.members], dep.shards,
            {mem.name: view.subscriptions for mem, view in zip(dep.members, self.views)},
            self.events, **broken,
        )

    async def check(self) -> list:
        dep = self.dep
        self.failed += sum(view.failed for view in self.views)
        self._journal = self._logged()
        out = self._compare(self._replay())
        self._misplaced = {mem.name: mem.layout_misplaced() for mem in dep.members}
        out.append({"name": "layout_misplaced_arrays",
                    "value": sum(len(v) for v in self._misplaced.values()), "limit": 0})
        self._fallbacks, compared = dep.fallbacks_compared()
        out.append(compared)
        c = self.m.counters
        routed = c.get("lat_waves", 0) + c.get("lat_overflow_waves", 0)
        out.append({"name": "waves_not_small_routed",
                    "value": abs(len(dep.members) * c.get("commands", 0) - routed),
                    "limit": 0})
        out.append({"name": "subscriptions_never_fired", "value": self.failed, "limit": 0})
        return out

    def control(self, kind: str) -> list:
        """The reference under one broken guarantee. ``direct_only``: every
        cascade stops at the direct dependents. ``lost_replay``: one member,
        not its owner, never learns of one acknowledged command of the
        window (both drawn from the seed)."""
        if kind == "direct_only":
            return self._compare(self._replay(max_depth=1))
        rng = np.random.default_rng([self.ctx.seed, 0x1057])
        timed = [c["op"] for c in self.commands[self.first_timed:] if "t_call" in c]
        op = timed[int(rng.integers(len(timed)))]
        owner = dict(self._journal).get(op)
        others = [mem.name for mem in self.dep.members if mem.name != owner]
        return self._compare(self._replay(
            lost_replay=(others[int(rng.integers(len(others)))], op)
        ))

    def notes(self) -> dict:
        import jax

        from lib.measure import percentile
        from stl_fusion_tpu.graph.program_cache import program_warm_report

        dep = self.dep
        timed = [c for c in self.commands[self.first_timed:] if "rereads" in c]
        ms = self._samples()
        owners = dict(self._journal)
        applied: dict = {}
        for c in timed:
            applied[owners.get(c["op"])] = applied.get(owners.get(c["op"]), 0) + 1

        def per_member(f):
            out = []
            for k in range(len(dep.members)):
                xs = [1e3 * f(c, k) for c in timed if len(c["drain"][k]) == 1]
                out.append(float(np.mean(xs)) if xs else None)
            return out

        return {
            "commands": len(timed), "warm_commands": self.first_timed,
            "window_s": self.elapsed,
            "members": [mem.name for mem in dep.members],
            "devices": [str(mem.device) for mem in dep.members],
            "applied_by": applied,
            "subscriptions": sum(len(v.subscriptions) for v in self.views),
            "distinct_rows_written": len({c["row"] for c in timed}),
            "ms_mean": float(np.mean(ms)) if ms else None,
            "ms_p99": percentile(ms, 99) if ms else None,
            "ms_max": max(ms, default=None),
            "call_ms_mean": 1e3 * float(np.mean([c["t_call"] - c["t0"] for c in timed]))
            if timed else None,
            "visible_ms_mean_by_member": [
                1e3 * float(np.mean([c["seen"][k]["t_seen"] - c["t0"] for c in timed]))
                if timed else None for k in range(len(dep.members))
            ],
            "tick_wait_ms_mean_by_member": per_member(
                lambda c, k: c["drain"][k][0][0] - c["t_call"]),
            "drain_ms_mean_by_member": per_member(
                lambda c, k: c["drain"][k][0][1] - c["drain"][k][0][0]),
            "deliver_ms_mean_by_member": per_member(
                lambda c, k: c["seen"][k]["t_seen"] - c["drain"][k][0][1]),
            "skew_ms_p50": percentile([1e3 * self._skew(c) for c in timed], 50)
            if timed else None,
            "cycle_ms_p50": percentile(
                [1e3 * (b["t0"] - a["t0"]) for a, b in zip(timed, timed[1:])], 50)
            if len(timed) > 1 else None,
            "stale_rows_at_end": [int(len(s)) for s in self.final_stale],
            "peak_bytes_by_chip": [
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.devices()[:self.ctx.cell["chips"]]
            ],
            "misplaced": self._misplaced,
            "program_warms": {
                k: [v["warm_s"], v["cache_hit"]] for k, v in program_warm_report().items()
            },
            "build_s": dict(dep.build_s, subscribe=self.subscribe_s),
            "fallbacks": self._fallbacks,
            "counters": self.m.counters,
        }

    async def close(self) -> None:
        pass
