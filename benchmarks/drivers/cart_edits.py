"""``cart_edits``: closed loop, ONE writer, no think time:
``Edit(product, delta)`` through ``ClusterCommander.call`` on a
``cart_served`` deployment, watched by subscribed clients that re-read a
total the moment they see its invalidation.

Each command's product is drawn by Zipf(``zipf_s``) over the deployment's
key pool (the rank-to-product deal drawn from the seed), its ``delta`` a
whole number in 1..9. Every pool product has ``watched_totals_per_product``
watched carts (its first by id), and on each of their totals
``subs_per_total`` clients hold a ``$sys-c`` subscription; which client
holds which is dealt from the seed by ``command_stream.Driver``'s dealing
(shared, as its subscribing, reading and observing are: this driver is that
one with another command and another sample). Closures may overlap: a
command must be seen by every subscription on a total of ANY cart that holds
its product, which the benchmark's own record of the lines says.

A sample is the host clock from the writer's entry into
``ClusterCommander.call`` to the moment the LAST subscription that must see
the command holds the new total at its client: its re-read, started when its
invalidation arrived, has returned. The writer issues the next command then.
``warm_commands`` untimed commands go first through the same loop.

``correct``, of what the timed window itself produced, every comparison
exact against ``lib/cartref.py``'s replay of the run's own events: every
acknowledged operation id in the op-log and the op-log's ids of this run
equal to the reference's journal; the price store on every product; per
command the subscriptions that observed; every re-read's value; per command
the newly invalid count (1 + the product's fan-out, every time); after the
window nothing stale on any of the three tables and nothing invalid in the
graph (host mirror and device array); all total rows read back from the
device against the reference's; no counted fallback, the hot refresh's
whole-block fallback among them; every command wave lat-served.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from drivers import command_stream


class _Keys:
    """What ``command_stream``'s dealing reads of a deployment: the
    subscribed keys (here the watched totals; no dependents beside them)."""

    def __init__(self, keys):
        self.pool_rows, self.pool_deps = keys, keys[:0]


class Driver(command_stream.Driver):
    CONTROLS = ("stale_total", "no_refresh", "lost_write")

    def __init__(self, ctx, dep):
        super().__init__(ctx, dep)
        from lib.cartref import CartRef

        self.ref = CartRef(dep.data)  # the lines both ways; prices untouched
        self.by_cart: dict = {}  # cart -> its subscriptions
        self.final: dict = {}  # what the tables and the graph held at the end
        self._rereads: list = []  # tasks of the command in flight
        self.build_s: dict = {}

    # ------------------------------------------------------------------ set-up
    async def setup(self) -> None:
        from lib.result import note
        from stl_fusion_tpu.graph.program_cache import time_program_warm

        ctx, dep = self.ctx, self.dep
        pool = len(dep.pool_products)
        n_clients = len(dep.clients)
        watched = dep.pool_watched[:, : int(ctx.param("watched_totals_per_product"))]
        # rank r of the Zipf law edits pool product deal[r]
        self.deal = self.rng.permutation(pool)
        ranks = np.arange(1, pool + 1, dtype=np.float64)
        weights = ranks ** -float(ctx.param("zipf_s"))
        self.cdf = np.cumsum(weights / weights.sum())
        # a cart that is among the first of two pool products is watched twice
        keys = watched.reshape(-1)
        real, self.dep = self.dep, _Keys(keys)
        try:
            self._deal_subscriptions(int(ctx.param("subs_per_total")), n_clients)
        finally:
            self.dep = real
        for sub in self.subscriptions:
            self.by_cart.setdefault(sub[1], []).append(sub)
        self.watched_carts = np.unique(keys)
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
            # the patch scatters, takes every client link through a frame
            for _ in range(int(ctx.param("warm_commands"))):
                if not await self._command(timed=False):
                    cmd = self.commands[-1]
                    raise RuntimeError(
                        f"warm-up command {len(self.commands)} (product {cmd['row']}) "
                        f"was not seen by all: {cmd['expect']} subscriptions must, "
                        f"{len(cmd['observed'])} observed, {len(cmd['rereads'])} "
                        f"re-reads returned; drain ticks "
                        f"{[(round(e - s, 3), n) for s, e, n in dep.drains[cmd['drains']:]]}"
                    )
        self.first_timed = len(self.commands)
        self.build_s = {  # the window clears the spans: keep the set-up's
            k: self.m.span_seconds(k)
            for k in ("graph_generate", "columnar_build", "mirror_build", "pool",
                      "clients", "subscribe")
        }

    def _expected(self, product: int) -> list:
        """The subscriptions that must see an edit of ``product``: those on
        the total of a cart that holds it (the benchmark's own lines)."""
        carts = np.intersect1d(self.ref.carts_of(product), self.watched_carts)
        return [sub for cart in carts.tolist() for sub in self.by_cart[cart]]

    def _hit(self, sub) -> None:
        """A subscription saw its invalidation: it re-reads at once."""
        now = time.perf_counter()
        cmd = self._current
        if cmd is None:
            self.failed += 1  # an invalidation no command explains
            return
        cmd["observed"].append(sub)
        cmd["t_seen"] = now
        self._rereads.append(asyncio.ensure_future(self._reread(cmd, sub)))

    async def _reread(self, cmd, sub) -> None:
        value = await self._read(*sub)  # arms the subscription again
        cmd["t_held"] = time.perf_counter()
        cmd["rereads"].append((sub, value))
        self.events.append(("reread", sub[0], sub[1], value))
        if len(cmd["rereads"]) >= cmd["expect"]:
            self._seen.set()

    # ------------------------------------------------------------------ window
    async def _command(self, timed: bool) -> bool:
        """One command, seen and re-read by everyone who must. False when
        that never happened (the run is then incorrect)."""
        dep, m = self.dep, self.m
        rank = int(np.searchsorted(self.cdf, self.rng.random(), side="right"))
        product = int(dep.pool_products[self.deal[min(rank, len(self.deal) - 1)]])
        delta = float(self.rng.integers(1, 10))
        op = f"ce-{self.ctx.seed}-{len(self.commands)}"
        cmd = {"op": op, "row": product, "timed": timed, "observed": [],
               "rereads": [], "expect": len(self._expected(product)),
               "t_seen": None, "t_held": None, "drains": len(dep.drains)}
        self.commands.append(cmd)
        self._current = cmd
        self._seen.clear()
        self._rereads = []
        with m.span("cmd"):
            cmd["t0"] = time.perf_counter()
            await dep.commander.call(dep.Edit(product, delta), operation_id=op)
            cmd["t_call"] = time.perf_counter()
            self.events.append(("cmd", op, product, delta))
            try:
                await asyncio.wait_for(self._seen.wait(), self.observe_timeout_s)
            except asyncio.TimeoutError:
                self.failed += 1
                return False
        await asyncio.gather(*self._rereads)  # a surplus observer's, if any
        cmd["drain"] = dep.drains[cmd["drains"]:]
        cmd["done"] = True
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
        done = [c for c in timed if c.get("done") and len(c["drain"]) == 1]
        if done:
            self.m.values["tick_wait_ms_per_cmd"] = 1e3 * float(np.mean(
                [c["drain"][0][0] - c["t_call"] for c in done]))
            self.m.values["deliver_ms_per_cmd"] = 1e3 * float(np.mean(
                [c["t_seen"] - c["drain"][0][1] for c in done]))
        self._read_final()

    def _read_final(self) -> None:
        """What the three tables and the graph hold once the window closed."""
        dep = self.dep
        dep.backend.flush()  # the last re-reads' journal
        g = dep.gdev
        self.final = {
            "stale": {
                name: int(np.count_nonzero(~np.asarray(t.valid_mask))) + t.stale_count()
                for name, t in dep.tables.items()
            },
            "invalid": int(np.count_nonzero(g._h_invalid))
            + int(np.count_nonzero(np.asarray(g.device_arrays().invalid))),
            "totals": np.asarray(dep.tables["total"].values),
        }

    def counters(self) -> dict:
        out = super().counters()
        backend = self.dep.backend
        out.update({
            "hot_refresh_rows": backend.hot_refresh_rows,
            "hot_refresh_dispatches": backend.hot_refresh_dispatches,
            "recaptures_in_place": backend.graph.mirror_rows_kept,
            "slots_revived": backend.graph.mirror_slots_revived,
        })
        return out

    def _samples(self) -> list:
        return [
            (c["t_held"] - c["t0"]) * 1e3
            for c in self.commands[self.first_timed:] if c.get("done")
        ]

    # ----------------------------------------------------------------- correct
    def _compare(self, expected) -> list:
        """The window's commands against a replay of the run's events."""
        dep, first = self.dep, self.first_timed
        timed = self.commands[first:]
        acked = [c["op"] for c in self.commands if "t_call" in c]
        unjournaled = sum(1 for op in acked if not dep.log_store.contains(op))
        prefix = f"ce-{self.ctx.seed}-"
        logged = {
            r.id for r in dep.log_store.read_after(0, limit=1 << 30)
            if str(r.id).startswith(prefix)
        }
        journal_wrong = len(logged.symmetric_difference(expected.journal))
        store_wrong = int(np.count_nonzero(
            dep.svc.price.astype(np.int64) != expected.price
        ))
        observers_wrong = sum(
            1 for c, want in zip(timed, expected.observers[first:])
            if frozenset(c["observed"]) != want or len(c["observed"]) != len(want)
        )
        n_before = sum(len(c["rereads"]) for c in self.commands[:first])
        got_values = [v for c in timed for _sub, v in c["rereads"]]
        want_values = expected.reread_values[n_before:]
        reread_wrong = abs(len(got_values) - len(want_values)) + sum(
            1 for a, b in zip(got_values, want_values) if float(a) != float(b)
        )
        counts_wrong = sum(
            1 for c, want in zip(timed, expected.newly_counts[first:])
            if [d[2] for d in c.get("drain", ())] != [want]
        )
        totals_wrong = int(np.count_nonzero(
            self.final["totals"].astype(np.int64) != expected.totals
        )) + int(np.count_nonzero(self.final["totals"] % 1))
        return [
            {"name": "unjournaled_acks", "value": unjournaled, "limit": 0},
            {"name": "journal_mismatches", "value": journal_wrong, "limit": 0},
            {"name": "store_mismatches", "value": store_wrong, "limit": 0},
            {"name": "observer_mismatches", "value": observers_wrong, "limit": 0},
            {"name": "reread_mismatches", "value": reread_wrong, "limit": 0},
            {"name": "newly_count_mismatches", "value": counts_wrong, "limit": 0},
            {"name": "device_total_mismatches", "value": totals_wrong, "limit": 0},
        ]

    def _replay(self, **broken):
        from lib.cartref import replay

        return replay(
            self.dep.data, self.subscriptions, self.events, lines_of=self.ref, **broken
        )

    async def check(self) -> list:
        out = self._compare(self._replay())
        out.append({"name": "stale_rows_at_end",
                    "value": sum(self.final["stale"].values()), "limit": 0})
        out.append({"name": "invalid_nodes_at_end",
                    "value": self.final["invalid"], "limit": 0})
        self._fallbacks, compared = self.dep.fallbacks_compared()
        out.append(compared)
        c = self.m.counters
        out.append({"name": "waves_not_lat_served",
                    "value": abs(c.get("commands", 0) - c.get("lat_waves", 0)),
                    "limit": 0})
        out.append({"name": "subscriptions_never_fired", "value": self.failed, "limit": 0})
        return out

    def control(self, kind: str) -> list:
        """The reference under one broken guarantee. ``stale_total``: a
        re-read does not see the edit that caused it. ``no_refresh``: what a
        wave invalidated stays invalid until a re-read recomputes it, so a
        product's second edit counts the re-read totals alone.
        ``lost_write``: the prices and the journal leave out one
        acknowledged command of the window, drawn from the seed."""
        if kind == "stale_total":
            return self._compare(self._replay(stale_total=True))
        if kind == "no_refresh":
            return self._compare(self._replay(no_refresh=True))
        timed = [c["op"] for c in self.commands[self.first_timed:] if "t_call" in c]
        pick = int(np.random.default_rng([self.ctx.seed, 0x1057]).integers(len(timed)))
        return self._compare(self._replay(drop_op=timed[pick]))

    def notes(self) -> dict:
        from lib.measure import percentile
        from stl_fusion_tpu.graph.program_cache import program_warm_report

        timed = [c for c in self.commands[self.first_timed:] if c.get("done")]
        ms = self._samples()
        one = [c for c in timed if len(c["drain"]) == 1]
        fanout = dict(zip(self.dep.pool_products.tolist(), self.dep.pool_fanout.tolist()))

        def mean_ms(f):
            return 1e3 * float(np.mean([f(c) for c in one])) if one else None

        return {
            "commands": len(timed), "warm_commands": self.first_timed,
            "window_s": self.elapsed,
            "subscriptions": len(self.subscriptions),
            "distinct_products_edited": len({c["row"] for c in timed}),
            "pool_fanout_min": int(self.dep.pool_fanout.min()),
            "pool_fanout_max": int(self.dep.pool_fanout.max()),
            "pool_closure_mean": 1.0 + float(self.dep.pool_fanout.mean()),
            "edited_closure_mean": float(np.mean([1 + fanout[c["row"]] for c in timed]))
            if timed else None,
            "observers_per_cmd_mean": float(np.mean([len(c["observed"]) for c in timed]))
            if timed else None,
            "observers_per_cmd_max": max((len(c["observed"]) for c in timed), default=None),
            "newly_p50": percentile([c["drain"][0][2] for c in one], 50) if one else None,
            "newly_max": max((c["drain"][0][2] for c in one), default=None),
            "ms_mean": float(np.mean(ms)) if ms else None,
            "ms_p99": percentile(ms, 99) if ms else None,
            "ms_max": max(ms, default=None),
            "call_ms_mean": mean_ms(lambda c: c["t_call"] - c["t0"]),
            "tick_wait_ms_mean": mean_ms(lambda c: c["drain"][0][0] - c["t_call"]),
            "drain_ms_mean": mean_ms(lambda c: c["drain"][0][1] - c["drain"][0][0]),
            "deliver_ms_mean": mean_ms(lambda c: c["t_seen"] - c["drain"][0][1]),
            "reread_ms_mean": mean_ms(lambda c: c["t_held"] - c["t_seen"]),
            "stale_rows_at_end": self.final.get("stale"),
            "edges": self.m.values.get("edges"),
            "mirror_rows": self.m.values.get("mirror_rows"),
            "lat_rows": self.m.values.get("lat_rows"),
            "program_warms": {
                k: [v["warm_s"], v["cache_hit"]] for k, v in program_warm_report().items()
            },
            "build_s": self.build_s,
            "fallbacks": self._fallbacks,
            "counters": self.m.counters,
        }
