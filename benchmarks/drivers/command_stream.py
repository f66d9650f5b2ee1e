"""``command_stream``: closed loop, ``writers`` writer(s), no think time:
``Bump(row, delta)`` through ``ClusterCommander.call`` on a ``served_dag``
deployment, watched by subscribed clients that re-read on invalidation.

Each command's row is drawn by Zipf(``zipf_s``) over the deployment's key
pool (the rank-to-row deal drawn from the seed), its ``delta`` a whole
number in 1..9. Every pool row is watched by ``subs_per_key`` clients on the
row itself and as many on its first direct dependent; which client holds
which is dealt from the seed (no client holds one key twice). A sample is
the host clock from the writer's entry into ``ClusterCommander.call`` to the
moment the last of those subscriptions saw its invalidation at its client.
Then the clients re-read (inside the window, outside the sample), and the
writer issues the next command when the last re-read has returned. Nothing
is reset between commands, inside or outside the window: ``warm_commands``
untimed commands go first through the same loop, and their state stays.

``correct``, of what the timed window itself produced, every comparison
exact against ``lib/servedref.py``'s replay of the run's own events: every
acknowledged operation id in the op-log and the op-log's ids of this run
equal to the reference's journal; the store on every pool row and every
subscribed row; per command the subscriptions that observed; every re-read
value; per command the newly invalid count; the table's stale mask after
the window; no counted fallback; every command wave served by the small-wave
path (lat + overflow = commands).
"""
from __future__ import annotations

import asyncio
import time

import numpy as np


class Driver:
    CONTROLS = ("direct_only", "lost_write")

    def __init__(self, ctx, dep):
        self.ctx, self.dep, self.m = ctx, dep, ctx.m
        self.rng = np.random.default_rng([ctx.seed, 0xC0DE])
        #: a subscription that has not fired by then never will: the run
        #: stops and is incorrect
        self.observe_timeout_s = float(ctx.param("observe_timeout_s"))
        self.events: list = []  # lib.servedref events, warm-up included
        #: per command, warm-up included: dict(op, row, timed, t0, t_call,
        #: t_seen, observed, drain, rereads)
        self.commands: list = []
        self.first_timed = 0
        self.subscriptions: list = []  # (client index, row)
        self.watchers: dict = {}  # row -> [(client index, row)] armed there
        self.final_stale = None
        self.attempted = 0
        self.failed = 0
        self.elapsed = 0.0
        self._fallbacks: dict = {}
        self._current = None
        self._seen = asyncio.Event()

    # ------------------------------------------------------------------ set-up
    async def setup(self) -> None:
        from lib.result import note
        from stl_fusion_tpu.graph.program_cache import time_program_warm

        ctx, dep = self.ctx, self.dep
        pool = len(dep.pool_rows)
        per_key = int(ctx.param("subs_per_key"))
        n_clients = len(dep.clients)
        # rank r of the Zipf law writes pool row deal[r]
        self.deal = self.rng.permutation(pool)
        ranks = np.arange(1, pool + 1, dtype=np.float64)
        weights = ranks ** -float(ctx.param("zipf_s"))
        self.cdf = np.cumsum(weights / weights.sum())
        self._deal_subscriptions(per_key, n_clients)
        note(f"subscribing {len(self.subscriptions)} $sys-c subscriptions "
             f"({n_clients} clients)")
        with self.m.span("subscribe"):
            await asyncio.gather(*(
                self._subscribe(ci, [r for c, r in self.subscriptions if c == ci])
                for ci in range(n_clients)
            ))
        if dep.server_rpc.compute_fanout.stats()["subscriptions"] != len(self.subscriptions):
            raise RuntimeError("the fan-out index does not hold every subscription")
        with time_program_warm("cmd_wave", key=(dep.n, "lat")):
            # the window's own loop, untimed: compiles the lat program and
            # takes every client link through a first invalidation frame
            for _ in range(int(ctx.param("warm_commands"))):
                await self._command(timed=False)
        self.first_timed = len(self.commands)

    def _deal_subscriptions(self, per_key: int, n_clients: int) -> None:
        """Every pool row and every pool dependent gets ``per_key``
        subscriptions; client slots are dealt at random, each client the
        same number, and a client that drew one key twice swaps a slot."""
        dep = self.dep
        keys = np.concatenate([dep.pool_rows, dep.pool_deps]).tolist()
        slots = [k for _ in range(per_key) for k in keys]
        if len(slots) % n_clients:
            raise ValueError("subscriptions do not divide among the clients")
        owner = self.rng.permutation(np.repeat(np.arange(n_clients), len(slots) // n_clients))
        held = [set() for _ in range(n_clients)]
        clash = []
        for i, (c, k) in enumerate(zip(owner.tolist(), slots)):
            if k in held[c]:
                clash.append(i)
            else:
                held[c].add(k)
        for i in clash:
            k = slots[i]
            while True:  # swap with a slot whose client can take this key
                j = int(self.rng.integers(len(slots)))
                ci, cj, kj = int(owner[i]), int(owner[j]), slots[j]
                if j in clash or k in held[cj] or kj in held[ci]:
                    continue
                held[cj].discard(kj)
                held[cj].add(k)
                held[ci].add(kj)
                owner[i], owner[j] = cj, ci
                break
        self.subscriptions = list(zip(owner.tolist(), slots))
        dep_of = dict(zip(dep.pool_rows.tolist(), dep.pool_deps.tolist()))
        by_key: dict = {}
        for sub in self.subscriptions:
            by_key.setdefault(sub[1], []).append(sub)
        self.watchers = {
            row: by_key[row] + by_key[dep_row] for row, dep_row in dep_of.items()
        }

    async def _subscribe(self, ci: int, rows) -> None:
        for row in rows:
            await self._read(ci, row)

    async def _read(self, ci: int, row: int):
        value, computed = await self.dep.clients[ci].read(row)
        computed.on_invalidated(lambda _c, sub=(ci, row): self._hit(sub))
        return value

    def _hit(self, sub) -> None:
        now = time.perf_counter()
        cmd = self._current
        if cmd is None:
            self.failed += 1  # an invalidation no command explains
            return
        cmd["observed"].append(sub)
        cmd["t_seen"] = now
        if len(cmd["observed"]) >= cmd["expect"]:
            self._seen.set()

    # ------------------------------------------------------------------ window
    async def _command(self, timed: bool) -> bool:
        """One command, its observation and its re-reads. False when a
        subscription never fired (the run is then incorrect)."""
        dep, m = self.dep, self.m
        rank = int(np.searchsorted(self.cdf, self.rng.random(), side="right"))
        row = int(dep.pool_rows[self.deal[min(rank, len(self.deal) - 1)]])
        delta = float(self.rng.integers(1, 10))
        index = len(self.commands)
        op = f"cs-{self.ctx.seed}-{index}"
        watchers = self.watchers[row]
        cmd = {"op": op, "row": row, "timed": timed, "observed": [],
               "expect": len(watchers), "t_seen": None, "drains": len(dep.drains)}
        self.commands.append(cmd)
        self._current = cmd
        self._seen.clear()
        with m.span("cmd"):
            cmd["t0"] = time.perf_counter()
            await dep.commander.call(dep.Bump(row, delta), operation_id=op)
            cmd["t_call"] = time.perf_counter()
            self.events.append(("cmd", op, row, delta))
            try:
                await asyncio.wait_for(self._seen.wait(), self.observe_timeout_s)
            except asyncio.TimeoutError:
                self.failed += 1
                return False
        cmd["drain"] = dep.drains[cmd["drains"]:]
        with m.span("reread"):
            values = await asyncio.gather(*(self._read(ci, r) for ci, r in watchers))
        for (ci, r), value in zip(watchers, values):
            self.events.append(("reread", ci, r, value))
        cmd["rereads"] = values
        self._current = None
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
        done = [c for c in timed if "rereads" in c and len(c["drain"]) == 1]
        if done:
            # where a sample's time goes by the driver's own clock: the
            # call, the wait for the drain tick, the tick (dispatch, wave,
            # apply, fan-out posts), then frames out and clients notified
            self.m.values["tick_wait_ms_per_cmd"] = 1e3 * float(np.mean(
                [c["drain"][0][0] - c["t_call"] for c in done]))
            self.m.values["deliver_ms_per_cmd"] = 1e3 * float(np.mean(
                [c["t_seen"] - c["drain"][0][1] for c in done]))
        self.final_stale = np.flatnonzero(~np.asarray(self.dep.table.valid_mask))

    def counters(self) -> dict:
        pipe = self.dep.pipe.stats()
        out = {
            "commands": max(len(self.commands) - self.first_timed, 0),
            "lat_waves": pipe["lat_waves"],
            "lat_overflow_waves": pipe["lat_overflow_waves"],
            "fused_dispatches": pipe["fused_dispatches"],
        }
        out.update(self.dep.outbox_totals())
        return out

    def _samples(self) -> list:
        return [
            (c["t_seen"] - c["t0"]) * 1e3
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
    def _compare(self, expected) -> list:
        """The window's commands against a replay of the run's events."""
        dep, first = self.dep, self.first_timed
        timed = self.commands[first:]
        acked = [c["op"] for c in self.commands if "t_call" in c]
        unjournaled = sum(1 for op in acked if not dep.log_store.contains(op))
        prefix = f"cs-{self.ctx.seed}-"
        logged = {
            r.id for r in dep.log_store.read_after(0, limit=1 << 30)
            if str(r.id).startswith(prefix)
        }
        journal_wrong = len(logged.symmetric_difference(expected.journal))
        rows = set(dep.pool_rows.tolist()) | {r for _c, r in self.subscriptions}
        store_wrong = sum(
            1 for r in rows
            if np.float32(dep.svc.base[r]) != expected.store.get(r, np.float32(r))
        )
        observers_wrong = sum(
            1 for c, want in zip(timed, expected.observers[first:])
            if frozenset(c["observed"]) != want or len(c["observed"]) != len(want)
        )
        n_rereads_before = sum(len(c.get("rereads", ())) for c in self.commands[:first])
        got_values = [v for c in timed for v in c.get("rereads", ())]
        want_values = expected.reread_values[n_rereads_before:]
        reread_wrong = abs(len(got_values) - len(want_values)) + sum(
            1 for a, b in zip(got_values, want_values) if np.float32(a) != b
        )
        counts_wrong = sum(
            1 for c, want in zip(timed, expected.newly_counts[first:])
            if [d[2] for d in c.get("drain", ())] != [want]
        )
        stale_wrong = len(expected.table_stale.symmetric_difference(self.final_stale.tolist()))
        return [
            {"name": "unjournaled_acks", "value": unjournaled, "limit": 0},
            {"name": "journal_mismatches", "value": journal_wrong, "limit": 0},
            {"name": "store_mismatches", "value": store_wrong, "limit": 0},
            {"name": "observer_mismatches", "value": observers_wrong, "limit": 0},
            {"name": "reread_mismatches", "value": reread_wrong, "limit": 0},
            {"name": "newly_count_mismatches", "value": counts_wrong, "limit": 0},
            {"name": "stale_mask_mismatches", "value": stale_wrong, "limit": 0},
        ]

    def _replay(self, **broken):
        from lib.servedref import replay

        return replay(self.dep.oracle, self.subscriptions, self.events, **broken)

    async def check(self) -> list:
        out = self._compare(self._replay())
        self._fallbacks, compared = self.dep.fallbacks_compared()
        out.append(compared)
        c = self.m.counters
        routed = c.get("lat_waves", 0) + c.get("lat_overflow_waves", 0)
        out.append({"name": "waves_not_small_routed",
                    "value": abs(c.get("commands", 0) - routed), "limit": 0})
        out.append({"name": "subscriptions_never_fired", "value": self.failed, "limit": 0})
        return out

    def control(self, kind: str) -> list:
        """The reference under one broken guarantee. ``direct_only``: the
        cascade stops at the direct dependents (as in the lone cell).
        ``lost_write``: the store and the journal leave out one acknowledged
        command of the window, drawn from the seed."""
        if kind == "direct_only":
            return self._compare(self._replay(max_depth=1))
        timed = [c["op"] for c in self.commands[self.first_timed:] if "t_call" in c]
        pick = int(np.random.default_rng([self.ctx.seed, 0x1057]).integers(len(timed)))
        return self._compare(self._replay(drop_op=timed[pick]))

    def notes(self) -> dict:
        from lib.measure import percentile

        timed = [c for c in self.commands[self.first_timed:] if "rereads" in c]
        ms = self._samples()
        rows = [c["row"] for c in timed]
        closure_of = dict(zip(self.dep.pool_rows.tolist(), self.dep.pool_closures))
        one = [c for c in timed if len(c["drain"]) == 1]

        def mean_ms(f):
            return 1e3 * float(np.mean([f(c) for c in one])) if one else None

        def spread_ms(f):
            xs = [1e3 * f(c) for c in one]
            return [percentile(xs, q) for q in (10, 50, 90)] if xs else None

        return {
            "commands": len(timed), "warm_commands": self.first_timed,
            "window_s": self.elapsed,
            "subscriptions": len(self.subscriptions),
            "distinct_rows_written": len(set(rows)),
            "pool_closure_min": min(self.dep.pool_closures),
            "pool_closure_max": max(self.dep.pool_closures),
            "written_closure_p50": percentile([closure_of[r] for r in rows], 50) if rows else None,
            "newly_p50": percentile([c["drain"][0][2] for c in one], 50) if one else None,
            "newly_max": max((c["drain"][0][2] for c in one), default=None),
            "ms_mean": float(np.mean(ms)) if ms else None,
            "ms_p99": percentile(ms, 99) if ms else None,
            "ms_max": max(ms, default=None),
            "call_ms_mean": mean_ms(lambda c: c["t_call"] - c["t0"]),
            "tick_wait_ms_mean": mean_ms(lambda c: c["drain"][0][0] - c["t_call"]),
            "drain_ms_mean": mean_ms(lambda c: c["drain"][0][1] - c["drain"][0][0]),
            "deliver_ms_mean": mean_ms(lambda c: c["t_seen"] - c["drain"][0][1]),
            "tick_wait_ms_p10_p50_p90": spread_ms(lambda c: c["drain"][0][0] - c["t_call"]),
            "drain_ms_p10_p50_p90": spread_ms(lambda c: c["drain"][0][1] - c["drain"][0][0]),
            "deliver_ms_p10_p50_p90": spread_ms(lambda c: c["t_seen"] - c["drain"][0][1]),
            "cycle_ms_p10_p50_p90": [
                percentile([1e3 * (b["t0"] - a["t0"]) for a, b in zip(timed, timed[1:])], q)
                for q in (10, 50, 90)] if len(timed) > 1 else None,
            "stale_rows_at_end": int(len(self.final_stale)) if self.final_stale is not None else None,
            "fallbacks": self._fallbacks,
            "counters": self.m.counters,
        }

    async def close(self) -> None:
        pass
