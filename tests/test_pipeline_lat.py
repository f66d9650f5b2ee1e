"""The wave pipeline's small-wave path (ISSUE 28): an accumulation the lat
mirror can serve rides the lat kernel wave by wave instead of one topo sweep
per wave, and nobody can tell from the answers.

For every shape of submission the pipeline with a lat mirror must resolve
its tickets with the same counts, the same seq order and the same stale
state as the fused topo chain (the same stack with the lat mirror dropped)
and as one blocking ``cascade_rows_batch`` per wave; the counters say which
route served. Also here: the lat patcher reuses dead slots (a row whose
dependent is recaptured again and again must not fill up), the written-row
rule ``refresh_block_on_device``'s docstring states, the span sites of the
write path, and ``ClusterCommander`` end to end against the benchmark's
plain reference (``benchmarks/lib/servedref.py``: op-log, store, who
observed what). Since ISSUE 31 also: a wave's own application journals
nothing on either apply path (ids, mask), so a served command is ONE lat
wave, and a twin displaced by a local re-read leaves its successor alone.
"""
import asyncio
import dataclasses
import os
import sys

import numpy as np
import pytest

from stl_fusion_tpu.client import compute_client, install_compute_call_type
from stl_fusion_tpu.commands import ClusterCommander, command_handler
from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    capture,
    compute_method,
    is_invalidating,
    memo_table_of,
)
from stl_fusion_tpu.diagnostics import tracing
from stl_fusion_tpu.graph import TpuGraphBackend
from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.oplog import (
    InMemoryOperationLog,
    LocalChangeNotifier,
    attach_operation_log,
)
from stl_fusion_tpu.resilience import WaveWatchdog
from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport, install_compute_fanout
from stl_fusion_tpu.utils.serialization import wire_type

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
)
from lib.hostgraph import HostGraph  # noqa: E402
from lib.servedref import _wave as reference_wave, replay  # noqa: E402

N = 3000
SRC, DST = power_law_dag(N, avg_degree=3, seed=28)


@wire_type("PipelineLatBump")
@dataclasses.dataclass(frozen=True)
class Bump:
    row: int
    delta: float

    def shard_key(self):
        return f"row-{self.row}"


class Dag(ComputeService):
    def __init__(self, hub=None):
        super().__init__(hub)
        self.base = np.arange(N, dtype=np.float32)
        self._base_dev = None

    def load(self, ids):
        return self.base[np.asarray(ids, dtype=np.int64)]

    def load_dev(self, ids, base_dev):
        return base_dev[ids]

    def load_dev_args(self):
        if self._base_dev is None:
            import jax.numpy as jnp

            self._base_dev = jnp.asarray(self.base)
        return (self._base_dev,)

    @compute_method(
        table=TableBacking(
            rows=N, batch="load", device_batch="load_dev", device_args="load_dev_args",
        )
    )
    async def node(self, i: int) -> float:
        return float(self.base[i])

    @command_handler
    async def bump(self, command: Bump):
        if is_invalidating():
            await self.node(command.row)
            return
        self.base[command.row] += np.float32(command.delta)
        self._base_dev = None
        return float(self.base[command.row])


def make_stack(lat=True, watchdog=False):
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=N + 8, edge_capacity=len(SRC) + 4096)
    if watchdog:
        backend.attach_watchdog(WaveWatchdog(deadline_s=600.0))
    svc = Dag(hub)
    hub.add_service(svc, "dag")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    backend.declare_row_edges(block, SRC, block, DST)
    backend.warm_block_on_device(block)
    backend.flush()
    backend.graph.build_topo_mirror()
    if not lat:
        backend.graph._topo_mirror["lat"] = None
    return hub, backend, svc, table, block


def shallow_rows(k, seed=1):
    """Rows of the upper half of ids: closures of a few rows."""
    rng = np.random.default_rng(seed)
    return (N // 2 + rng.choice(N // 2, size=k, replace=False)).tolist()


def run_pipeline(waves, lat, before_drain=None, tweak=None):
    hub, backend, _svc, table, block = make_stack(lat=lat)
    if tweak is not None:
        tweak(backend)
    pipe = hub.enable_nonblocking(fuse_depth=len(waves) + 1)
    tickets = [pipe.submit_rows(block, w) for w in waves]
    if before_drain is not None:
        before_drain(backend, block)
    pipe.drain()
    assert all(t.done for t in tickets)
    return {
        "counts": [t.count for t in tickets],
        "seq_order": [t.seq for t in tickets] == sorted(t.seq for t in tickets),
        "seq_gaps": [b.seq - a.seq for a, b in zip(tickets, tickets[1:])],
        "invalid": backend.graph._h_invalid.copy(),
        "device_invalid": np.asarray(backend.graph.invalid_mask()),
        "stale": np.flatnonzero(~np.asarray(table.valid_mask)),
        "causes": {t.cause for t in tickets},
        "stats": pipe.stats(),
    }


def small_caps(backend):
    backend.graph.LAT_LCAP = 4
    backend.graph.LAT_CAP = 8


def break_mirror(backend):
    """The state an unpatchable delta leaves: the delta log broken and the
    lat mirror dropped. With the edges ``declare_before_drain`` adds, the
    mirror is invalid at dispatch and the chain's entry rebuilds it."""
    backend.graph._break_mirror_deltas()


def declare_before_drain(backend, block):
    """A non-empty journal at dispatch: two more edges, level-respecting."""
    lv = backend.graph.mirror_levels(np.arange(N))
    lo, hi = int(np.argmin(lv)), int(np.argmax(lv))
    backend.declare_row_edges(block, np.array([lo]), block, np.array([hi]))
    assert backend._journal


DEEP = [int(np.argmax(np.bincount(SRC, minlength=N)))]  # the widest hub: overflows small caps
CASES = {
    "small_waves": dict(waves=[[r] for r in shallow_rows(5)], route="lat"),
    "several_seeds_a_wave": dict(waves=[shallow_rows(7, seed=s) for s in (2, 3, 4)], route="lat"),
    "repeated_row": dict(waves=[[N - 5], [N - 5], [N - 9], [N - 5]], route="lat"),
    "overflows_the_lat_caps": dict(
        waves=[[N - 3], DEEP, [N - 7]], route="lat+overflow", tweak=small_caps),
    "more_than_lat_seed_max_seeds": dict(
        waves=[shallow_rows(300, seed=5), [N - 3]], route="chain"),
    "invalid_mirror": dict(waves=[[r] for r in shallow_rows(3, seed=6)], route="chain",
                           tweak=break_mirror, before_drain=declare_before_drain),
    "journal_at_dispatch": dict(waves=[[r] for r in shallow_rows(4, seed=7)], route="lat",
                                before_drain=declare_before_drain),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_small_wave_path_equals_the_topo_chain(case):
    spec = CASES[case]
    kw = {k: spec[k] for k in ("before_drain", "tweak") if k in spec}
    with_lat = run_pipeline(spec["waves"], lat=True, **kw)
    chain = run_pipeline(spec["waves"], lat=False, **kw)
    assert with_lat["counts"] == chain["counts"]
    assert with_lat["seq_order"] and chain["seq_order"]
    assert with_lat["seq_gaps"] == chain["seq_gaps"] == [1] * (len(spec["waves"]) - 1)
    assert np.array_equal(with_lat["invalid"], chain["invalid"])
    assert np.array_equal(with_lat["device_invalid"], chain["device_invalid"])
    assert np.array_equal(with_lat["invalid"][:N], with_lat["device_invalid"][:N])
    assert np.array_equal(with_lat["stale"], chain["stale"])
    assert len(with_lat["causes"]) == 1 and None not in with_lat["causes"]
    # and both equal one blocking lone wave per submission
    _hub, backend, _svc, _table, block = make_stack(lat=True)
    if "tweak" in spec:
        spec["tweak"](backend)
    lone = []
    for i, w in enumerate(spec["waves"]):
        if i == 0 and "before_drain" in spec:
            spec["before_drain"](backend, block)
        lone.append(backend.cascade_rows_batch(block, w))
    assert with_lat["counts"] == lone
    assert np.array_equal(with_lat["invalid"], backend.graph._h_invalid)
    # which route served is counted, never guessed
    stats, n = with_lat["stats"], len(spec["waves"])
    if spec["route"] == "lat":
        assert stats["lat_waves"] == n and stats["lat_overflow_waves"] == 0
        assert stats["fused_dispatches"] == 0 and stats["eager_waves"] == 0
    elif spec["route"] == "lat+overflow":
        assert stats["lat_waves"] == n - 1 and stats["lat_overflow_waves"] == 1
        assert stats["fused_dispatches"] == 0 and stats["eager_waves"] == 0
    else:
        assert spec["route"] == "chain"
        assert stats["lat_waves"] == 0 and stats["fused_dispatches"] >= 1
        assert stats["eager_waves"] == 0
    assert chain["stats"]["lat_waves"] == chain["stats"]["lat_overflow_waves"] == 0


def test_small_waves_decline_while_a_super_round_is_in_flight():
    """The blocking lat readback would stall the host behind the resident
    program: such an accumulation keeps the chain and queues behind it."""
    hub, backend, _svc, _table, block = make_stack(lat=True)
    sr = backend.enable_super_rounds(block, depth=2, max_words=1)
    pipe = hub.enable_nonblocking(fuse_depth=8, max_words=1)
    ticket = sr.dispatch(sr.stage([[[5], [9]]] * 2))
    wave = pipe.submit_rows(block, [N - 3])
    pipe.dispatch()
    assert pipe.stats()["lat_waves"] == 0
    pipe.drain()
    assert ticket.done and wave.done
    assert pipe.stats()["fused_dispatches"] == 1 and pipe.stats()["eager_waves"] == 0
    pipe.dispose()
    sr.dispose()


def test_watchdog_in_host_mode_keeps_small_waves_off_the_device():
    hub, backend, _svc, _table, block = make_stack(lat=True, watchdog=True)
    pipe = hub.enable_nonblocking(fuse_depth=4)
    backend.watchdog.mode = WaveWatchdog.MODE_HOST
    backend.watchdog._host_bursts_left = 5
    wave = pipe.submit_rows(block, [N - 3])
    pipe.drain()
    assert wave.done and wave.count >= 1
    assert pipe.stats()["lat_waves"] == 0 and pipe.stats()["eager_waves"] == 1


def test_a_fault_on_the_small_wave_path_is_contained_like_a_chain_fault():
    """No watchdog to contain it: the wave that raised and those after it
    re-run on the split host loop, counted, and the set is the same."""
    waves = [[r] for r in shallow_rows(4, seed=9)]
    want = run_pipeline(waves, lat=True)
    hub, backend, _svc, _table, block = make_stack(lat=True)
    pipe = hub.enable_nonblocking(fuse_depth=8)
    union, calls = backend._wave_union, {"n": 0}

    def flaky(seed_lists):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("device runtime fault")
        return union(seed_lists)

    backend._wave_union = flaky
    tickets = [pipe.submit_rows(block, w) for w in waves]
    pipe.drain()
    assert all(t.done for t in tickets) and [t.count for t in tickets] == want["counts"]
    assert [t.seq for t in tickets] == sorted(t.seq for t in tickets)
    assert np.array_equal(backend.graph._h_invalid, want["invalid"])
    stats = pipe.stats()
    assert stats["chain_faults"] == 1 and stats["lat_waves"] == 1


def test_counters_reach_the_metrics_collector():
    hub, _backend, _svc, _table, block = make_stack(lat=True)
    pipe = hub.enable_nonblocking(fuse_depth=2)
    pipe.submit_rows(block, [N - 3])
    pipe.drain()
    got = pipe._collect_metrics()
    assert got["fusion_pipeline_lat_waves_total"] == 1
    assert got["fusion_pipeline_lat_overflow_waves_total"] == 0


# ------------------------------------------------- the wave's echo (ISSUE 31)
@pytest.mark.parametrize("route", ["lat_ids", "chain_mask"])
async def test_applying_a_wave_journals_nothing_on_either_apply_path(route):
    """Watched twins on a wave's rows, the wave through the pipeline's lat
    route (``_apply_newly_ids``) and through the fused chain
    (``_apply_newly_mask``): the eager tier fires, the table marks of its
    twins are dropped and counted, the journal stays empty, and the flush
    that follows runs no wave and changes no bit."""
    hub, backend, svc, table, block = make_stack(lat=route == "lat_ids")
    row = int(np.flatnonzero(np.bincount(SRC, minlength=N)[N // 2:] >= 2)[0] + N // 2)
    closure = sorted(HostGraph(SRC, DST, N).closure_ids([row]))
    watched_rows = closure[:3]
    hits: list = []
    for r in watched_rows:
        twin = await capture(lambda r=r: svc.node(r))
        twin.on_invalidated(lambda c: hits.append(c.input.args[0]))
    backend.flush()
    pipe = hub.enable_nonblocking(fuse_depth=2)
    ticket = pipe.submit_rows(block, [row])
    pipe.drain()
    assert ticket.count == len(closure)
    assert sorted(hits) == sorted(watched_rows)
    assert backend._journal == []
    assert backend.wave_echo_marks_dropped == len(watched_rows)
    stats = pipe.stats()
    assert (stats["lat_waves"], stats["fused_dispatches"]) == ((1, 0) if route == "lat_ids" else (0, 1))
    assert np.flatnonzero(~np.asarray(table.valid_mask)).tolist() == closure
    mask = np.asarray(backend.graph.invalid_mask()).copy()
    waves, total = backend.graph.lat_waves, backend.device_invalidations
    backend.flush()
    assert np.array_equal(np.asarray(backend.graph.invalid_mask()), mask)
    assert (backend.graph.lat_waves, backend.device_invalidations) == (waves, total)
    assert not [r for r in backend.profiler.recent() if r["kind"] == "icasc"]
    pipe.dispose()


async def test_a_twin_displaced_by_a_local_reread_leaves_its_successor_alone():
    """ROADMAP D11's recipe: capture, cascade, capture again, flush. The
    first twin is unwatched, so the wave leaves it pending and the re-read's
    registration materializes it AFTER the registry holds the new version.
    Its ``mark_row_stale`` is the wave's echo: it must neither tell the new
    version to invalidate itself once computed (the table -> scalar probe)
    nor journal an ``icasc`` behind the new version's ``bump`` and
    ``epack``."""
    _hub, backend, svc, table, block = make_stack(lat=True)
    row = shallow_rows(1, seed=11)[0]
    nid = block.base + row
    first = await capture(lambda: svc.node(row))
    backend.flush()
    assert backend.cascade_rows_batch(block, [row]) >= 1
    assert not first.is_consistent and backend._journal == []
    second = await capture(lambda: svc.node(row))
    assert [kind for kind, _p in backend._journal] == ["bump", "epack"]
    assert backend.wave_echo_marks_dropped == 1
    backend.flush()
    assert first.is_invalidated and second.is_consistent and second is not first
    assert not backend.graph._h_invalid[nid]
    assert not np.asarray(backend.graph.invalid_mask())[nid]
    assert not np.asarray(table.valid_mask)[row]  # the written-row rule: stale on the table
    assert backend.cascade_rows_batch(block, [row]) >= 1  # ... and the next wave counts it again
    assert not second.is_consistent


# ------------------------------------------------------------ lat dead slots
async def test_lat_patcher_reuses_dead_slots():
    """A row whose dependent's scalar twin is recaptured over and over (a
    written row its subscribers keep re-reading) adds one out-slot a
    recapture; the slots of the epochs gone by are dead and must be taken
    again, or the out-row fills and the lat mirror is dropped."""
    s = await served_stack(n_clients=1)
    backend, block, proxy = s["backend"], s["block"], s["clients"][0][1]
    outdeg = np.bincount(SRC, minlength=N)
    row = int(np.flatnonzero(outdeg[N // 2:] >= 2)[0] + N // 2)
    dep = int(DST[SRC == row][0])
    k = backend.graph._topo_mirror["lat"]["h_ell_dst"].shape[1]
    for i in range(3 * k + 2):
        held = [await capture(lambda key=key: proxy.node(key)) for key in (row, dep)]
        assert backend.cascade_rows_batch(block, [row]) >= 2, i
        await until(lambda: all(c.is_invalidated for c in held))
        assert backend.graph._topo_mirror["lat"] is not None, i
    assert backend.graph.lat_waves >= 3 * k + 2
    await s["close"]()


# ------------------------------------------------------- the written-row rule
async def test_a_reread_row_stays_stale_on_the_table_by_design():
    s = await served_stack(n_clients=1)
    backend, table, block, cc = s["backend"], s["table"], s["block"], s["cc"]
    proxy = s["clients"][0][1]
    row = shallow_rows(1, seed=11)[0]
    nid = block.base + row
    seen = await capture(lambda: proxy.node(row))
    await cc.call(Bump(row, 3.0))
    assert cc.drain() >= 1
    await until(lambda: seen.is_invalidated)
    assert not np.asarray(table.valid_mask)[row] and backend.graph._h_invalid[nid]
    # the subscriber's re-read: the row's scalar twin recomputes from the store
    seen = await capture(lambda: proxy.node(row))
    assert seen.value == float(row) + 3.0
    backend.flush()
    assert not backend.graph._h_invalid[nid]  # valid again in the graph
    backend.refresh_block_on_device(block)
    backend.flush()
    # ... and still stale on the table, as the docstring says, in one sentence
    assert not np.asarray(table.valid_mask)[row]
    assert table.stale_count() == 1
    doc = " ".join(TpuGraphBackend.refresh_block_on_device.__doc__.split())
    assert (
        "A row re-read through its scalar twin after a wave (a client's re-read of a "
        "written row) leaves the graph's invalid set but stays stale on the table, by design"
    ) in doc
    # the table's own read recomputes it, from the store
    assert float(np.asarray(table.read_batch(np.array([row])))[0]) == float(row) + 3.0
    assert np.asarray(table.valid_mask)[row] and table.stale_count() == 0
    # the next command counts the row again
    await cc.call(Bump(row, 1.0))
    assert cc.drain() >= 1
    assert s["pipe"].stats()["lat_waves"] == 2
    await s["close"]()


# ------------------------------------------------------------------- the spans
WRITE_PATH_SPANS = {
    "cmd.call", "cmd.execute", "cmd.journal", "cmd.complete", "cmd.submit",
    "cmd.drain", "pipeline.dispatch", "pipeline.harvest", "lat.dispatch",
    "lat.readback", "fanout.newly", "outbox.wait", "outbox.drain", "rpc.compute_call",
}


async def served_stack(n_clients=3):
    hub, backend, svc, table, block = make_stack(lat=True)
    hub.commander.add_service(svc)
    log = InMemoryOperationLog()
    reader = attach_operation_log(hub.commander, log, LocalChangeNotifier())
    pipe = hub.enable_nonblocking(fuse_depth=8)
    cc = ClusterCommander(hub.commander, member_id="m0", log_store=log)
    server = RpcHub("server")
    install_compute_call_type(server)
    server.add_service("dag", svc)
    install_compute_fanout(server, backend)
    clients = []
    for i in range(n_clients):
        rpc = RpcHub(f"client-{i}")
        install_compute_call_type(rpc)
        RpcTestTransport(rpc, server, wire_codec=True)
        clients.append((rpc, compute_client("dag", rpc, FusionHub(), peer_ref=f"c{i}")))

    async def close():
        for rpc, _proxy in clients:
            await rpc.stop()
        await server.stop()
        await reader.stop()
        pipe.dispose()

    return dict(hub=hub, backend=backend, svc=svc, table=table, block=block, log=log,
                pipe=pipe, cc=cc, clients=clients, close=close)


async def until(predicate, timeout_s=10.0):
    deadline = asyncio.get_running_loop().time() + timeout_s
    while not predicate():
        assert asyncio.get_running_loop().time() < deadline, "timed out"
        await asyncio.sleep(0.002)


async def test_write_path_spans_record_when_on_and_cost_nothing_off():
    s = await served_stack(n_clients=1)
    row = shallow_rows(1, seed=12)[0]
    _rpc, proxy = s["clients"][0]
    hits = []

    async def subscribe():
        computed = await capture(lambda: proxy.node(row))
        computed.on_invalidated(hits.append)

    await subscribe()
    tracing.clear_hot_spans()
    await s["cc"].call(Bump(row, 1.0))
    s["cc"].drain()
    await until(lambda: len(hits) == 1)
    assert tracing.hot_spans() == []  # gated off: every site is the shared no-op
    assert tracing.hot_span("cmd.call") is tracing.hot_span("outbox.drain")
    await subscribe()
    tracing.enable_hot_spans()
    try:
        await s["cc"].call(Bump(row, 1.0))
        s["cc"].drain()
        await until(lambda: len(hits) == 2)
        await subscribe()
        record = tracing.hot_spans()
    finally:
        tracing.disable_hot_spans()
        tracing.clear_hot_spans()
    names = {r.name for r in record}
    assert WRITE_PATH_SPANS <= names, WRITE_PATH_SPANS - names
    by_id = {r.span_id: r for r in record}

    def parent(name):
        return {by_id[r.parent_id].name for r in record
                if r.name == name and r.parent_id in by_id}

    assert parent("cmd.execute") == {"cmd.call"} and parent("cmd.submit") == {"cmd.call"}
    assert parent("cmd.journal") == {"cmd.execute"}
    assert parent("pipeline.dispatch") == {"cmd.drain"}
    assert "pipeline.dispatch" in parent("lat.dispatch")
    assert parent("fanout.newly") <= {"pipeline.harvest", "wave.apply"}
    wait = next(r for r in record if r.name == "outbox.wait")
    drain = next(r for r in record if r.name == "outbox.drain")
    assert wait.start < wait.end <= drain.start + 1e-3  # posted, then taken by a tick
    await s["close"]()


# ------------------------------------- ClusterCommander against the reference
@pytest.mark.parametrize("seed", [28, 2**31 + 28])
async def test_commander_end_to_end_equals_the_plain_reference(seed):
    """Commands through ``ClusterCommander.call``, watched over RPC,
    re-read on invalidation, nothing reset: op-log, store, who observed
    what, every re-read value, every wave's newly count and the table's
    stale mask equal ``lib/servedref.py``'s replay of the same events, the
    device's invalid mask equals the reference's wave rule replayed over
    them, and a command is ONE lat wave: the eager tier's table marks are
    dropped, the re-reads journal ``bump`` and ``epack`` alone."""
    s = await served_stack(n_clients=3)
    backend, svc, table, cc = s["backend"], s["svc"], s["table"], s["cc"]
    graph = HostGraph(SRC, DST, N)
    rng = np.random.default_rng(seed)
    outdeg = np.bincount(SRC, minlength=N)
    pool = (np.flatnonzero(outdeg[N // 2:] >= 2) + N // 2)[:6].tolist()
    keys = sorted(set(pool) | {int(graph.out_neighbors([r])[0]) for r in pool}
                  | {int(x) for r in pool[:2] for x in graph.closure_ids([r])})
    subs = [(ci, k) for k in keys for ci in rng.choice(3, size=2, replace=False).tolist()]
    observed: list = []

    async def read(ci, key):
        computed = await capture(lambda: s["clients"][ci][1].node(key))
        computed.on_invalidated(lambda _c, sub=(ci, key): observed.append(sub))
        return computed.value

    for ci, key in subs:
        await read(ci, key)
    events, got_observers, got_counts, got_values = [], [], [], []
    for i in range(24):
        row = int(rng.choice(pool))
        delta = float(rng.integers(1, 10))
        op = f"t-{seed}-{i}"
        del observed[:]
        await cc.call(Bump(row, delta), operation_id=op)
        events.append(("cmd", op, row, delta))
        assert s["log"].contains(op)  # journaled before anything fans out
        assert not observed
        assert "icasc" not in {kind for kind, _p in backend._journal}
        got_counts.append(cc.drain())
        want_now = replay(graph, subs, events).observers[-1]
        await until(lambda: len(observed) >= len(want_now))
        await asyncio.sleep(0.01)  # room for an invalidation nobody should get
        got_observers.append(frozenset(observed))
        assert len(observed) == len(set(observed))
        for ci, key in sorted(want_now):
            if rng.random() < 0.8:  # some subscribers never come back
                value = await read(ci, key)
                events.append(("reread", ci, key, value))
                got_values.append(np.float32(value))
    want = replay(graph, subs, events)
    assert got_observers == want.observers
    assert got_counts == want.newly_counts
    assert got_values == want.reread_values
    logged = [r.id for r in s["log"].read_after(0, limit=10_000)]
    assert logged == want.journal
    for key in keys:
        assert np.float32(svc.base[key]) == want.store.get(key, np.float32(key))
    stale = set(np.flatnonzero(~np.asarray(table.valid_mask)).tolist())
    assert stale == want.table_stale
    stats = s["pipe"].stats()
    assert stats["lat_waves"] == 24 and stats["eager_waves"] == 0
    assert stats["fused_dispatches"] == 0 and stats["chain_faults"] == 0
    assert backend.graph._topo_mirror["lat"] is not None
    # one wave a command, none for the flush of its re-reads
    backend.flush()
    assert backend.graph.lat_waves == 24 and backend.waves_run == 24
    assert not [r for r in backend.profiler.recent() if r["kind"] == "icasc"]
    assert backend.wave_echo_marks_dropped == sum(
        len({key for _ci, key in seen}) for seen in want.observers)
    invalid: set = set()
    for event in events:
        if event[0] == "cmd":
            invalid |= reference_wave(graph, int(event[2]), invalid, None)
        else:
            invalid.discard(int(event[2]))
    base = s["block"].base
    for mask in (backend.graph._h_invalid, np.asarray(backend.graph.invalid_mask())):
        assert set(np.flatnonzero(mask[base:base + N]).tolist()) == invalid
    await s["close"]()
