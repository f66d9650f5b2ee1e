"""Tracing spans (the always-on kind and the live loop's hot-path kind),
CommandTracer filter, and batched EntityResolver tests (SURVEY §5.1 tracing;
§2.3 CommandTracer; §2.6 DbEntityResolver)."""
import asyncio
import glob
import time
from collections import Counter
from dataclasses import dataclass

import numpy as np
import pytest

from stl_fusion_tpu.commands import attach_command_tracer, command_handler
from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    compute_method,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.diagnostics import (
    add_listener,
    current_span,
    get_activity_source,
    recent_spans,
    remove_listener,
    tracing,
)
from stl_fusion_tpu.diagnostics.tracing import (
    enable_hot_spans,
    find_span_by_cause,
    hot_span,
    hot_spans,
    hot_spans_on,
    span_cause_id,
)
from stl_fusion_tpu.graph import TpuGraphBackend
from stl_fusion_tpu.graph.device_graph import DeviceGraph
from stl_fusion_tpu.graph.program_cache import compile_report, reset_program_warms
from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.oplog import EntityResolver


@pytest.fixture(autouse=True)
def fresh_hub():
    hub = FusionHub()
    hub.commander.attach_operations_pipeline()
    old = set_default_hub(hub)
    yield hub
    set_default_hub(old)


class TestTracing:
    def test_span_records_duration_and_tags(self):
        src = get_activity_source("test.src")
        with src.span("work", key=1) as span:
            assert current_span() is span
        assert span.duration is not None and span.duration >= 0
        assert span.tags == {"key": 1}
        assert current_span() is None

    def test_span_nesting_builds_parent_chain(self):
        src = get_activity_source("test.src")
        with src.span("outer") as outer:
            with src.span("inner") as inner:
                assert inner.parent_id == outer.span_id
        assert outer.parent_id is None

    def test_listener_and_error_capture(self):
        seen = []
        add_listener(seen.append)
        try:
            src = get_activity_source("test.src")
            with pytest.raises(ValueError):
                with src.span("boom"):
                    raise ValueError("x")
        finally:
            remove_listener(seen.append)
        assert any(s.name == "boom" and s.error_type == "ValueError" and s.error_message == "x" for s in seen)

    def test_recent_spans_filter(self):
        src = get_activity_source("test.filter")
        with src.span("alpha"):
            pass
        spans = recent_spans(source="test.filter", name="alpha")
        assert spans and spans[-1].name == "alpha"


@dataclass(frozen=True)
class Ping:
    n: int


class AnnotationSpy:
    """Stands in for ``jax.profiler.TraceAnnotation``: what was opened and
    what was closed, by name."""

    opened: list = []
    closed: list = []

    def __init__(self, name, **metadata):
        self.name = name
        self.metadata = metadata

    def __enter__(self):
        AnnotationSpy.opened.append((self.name, self.metadata))
        return self

    def __exit__(self, exc_type, exc, tb):
        AnnotationSpy.closed.append(self.name)

    @staticmethod
    def is_enabled():
        return False


@pytest.fixture
def annotations(monkeypatch):
    hot_spans_on()  # binds the profiler's predicate and class; then swap
    AnnotationSpy.opened, AnnotationSpy.closed = [], []
    monkeypatch.setattr(tracing, "_TraceAnnotation", AnnotationSpy)
    return AnnotationSpy


class TestHotSpans:
    def test_gate_off_is_one_shared_noop_and_records_nothing(self, annotations):
        assert not hot_spans_on()
        a, b = hot_span("cascade"), hot_span("lat.dispatch", 7, 1.0)
        assert a is b
        with a as span:
            span.set_wave(3)
            with b:
                pass
        assert hot_spans() == [] and annotations.opened == []

    def test_gate_on_nests_with_parent_and_wave_seq(self, annotations):
        enable_hot_spans()
        assert hot_spans_on()
        with hot_span("cascade") as outer:
            with hot_span("flush") as early:
                early.set_wave(41)
            outer.set_wave(41)
            with hot_span("wave.union", start=123.5):
                with hot_span("lat.dispatch"):
                    pass
            with hot_span("wave.apply", 42):
                pass
        rec = {r.name: r for r in hot_spans()}
        assert [r.name for r in hot_spans()] == [
            "flush", "lat.dispatch", "wave.union", "wave.apply", "cascade"]
        assert rec["cascade"].parent_id is None
        assert rec["wave.union"].parent_id == rec["cascade"].span_id
        assert rec["lat.dispatch"].parent_id == rec["wave.union"].span_id
        # a child inherits the wave its parent holds when it opens; its own wins
        assert [rec[n].wave for n in ("cascade", "flush", "wave.union",
                                      "lat.dispatch", "wave.apply")] == [41, 41, 41, 41, 42]
        assert rec["wave.union"].start == 123.5  # the caller's own reading
        assert all(r.end >= r.start for r in rec.values() if r.name != "wave.union")
        assert rec["cascade"].start <= rec["flush"].start
        assert rec["wave.apply"].end <= rec["cascade"].end
        # the event carries the wave only where the enclosing event does not
        assert dict(annotations.opened) == {
            "fusion:cascade": {}, "fusion:flush": {}, "fusion:wave.union": {},
            "fusion:lat.dispatch": {}, "fusion:wave.apply": {"wave": 42}}
        assert sorted(annotations.closed) == sorted(n for n, _ in annotations.opened)
        tracing.disable_hot_spans()
        assert hot_span("cascade") is hot_span("flush")  # the no-op again

    def test_raising_body_still_closes_span_and_annotation(self, annotations):
        enable_hot_spans()
        with pytest.raises(ValueError):
            with hot_span("cascade"):
                with hot_span("lat.readback", 5):
                    raise ValueError("device lost")
        assert [r.name for r in hot_spans()] == ["lat.readback", "cascade"]
        assert annotations.closed == ["fusion:lat.readback", "fusion:cascade"]
        with hot_span("flush"):
            pass
        assert hot_spans()[-1].parent_id is None  # nothing left open

    @pytest.mark.parametrize("hot", [False, True])
    @pytest.mark.parametrize("source,name,tags", [
        ("stl_fusion_tpu.commands", "run:Ping", {"top_level": True}),
        ("oplog", "replay", {"index": 12, "agent": "a1"}),
    ])
    def test_always_on_spans_are_as_before(self, annotations, hot, source, name, tags):
        """Ring, listeners, ``current_span`` and cause ids of the spans the
        commander and the op-log reader open do not depend on the gate, and
        a hot span never stands in for one."""
        if hot:
            enable_hot_spans()
        seen = []
        add_listener(seen.append)
        try:
            with get_activity_source(source).span(name, **tags) as span:
                with hot_span("cascade"):
                    assert current_span() is span
                    assert tracing.current_cause_id() == span_cause_id(span)
        finally:
            remove_listener(seen.append)
        assert seen == [span] and recent_spans(source=source, name=name) == [span]
        assert span.tags == tags and span.parent_id is None
        assert find_span_by_cause(span_cause_id(span)) is span
        assert [r.parent_id for r in hot_spans()] == ([span.span_id] if hot else [])


N_ROWS = 600
DAG_SRC, DAG_DST = power_law_dag(N_ROWS, avg_degree=3, seed=7)


class SpanDag(ComputeService):
    def __init__(self, hub=None):
        super().__init__(hub)
        self.base = np.arange(N_ROWS, dtype=np.float32)

    def load(self, ids):
        return self.base[np.asarray(ids, dtype=np.int64)]

    def load_dev(self, ids):
        import jax.numpy as jnp

        return jnp.asarray(self.base)[ids]

    @compute_method(table=TableBacking(rows=N_ROWS, batch="load", device_batch="load_dev"))
    async def node(self, i: int) -> float:
        return float(self.base[i])


def make_stack():
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=N_ROWS + 8, edge_capacity=len(DAG_SRC) + 512)
    svc = SpanDag(hub)
    hub.add_service(svc, "dag")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    backend.declare_row_edges(block, DAG_SRC, block, DAG_DST)
    backend.warm_block_on_device(block)
    backend.flush()
    backend.graph.build_topo_mirror()
    return backend, table, block


def act_lat(backend, table, block):
    backend.cascade_rows_batch(block, [N_ROWS - 1])
    return backend.last_wave_seq


def act_overflow(backend, table, block):
    backend.cascade_rows_batch(block, [0])  # a closure beyond LAT_CAP
    return backend.last_wave_seq


def act_flush_icasc(backend, table, block):
    table.invalidate([N_ROWS - 2])  # journals an icasc entry
    backend.flush()
    return backend.last_wave_seq


def act_superround(backend, table, block):
    program = backend.enable_super_rounds(block, depth=2)
    staged = program.stage([[[5, 6], [7]], [[8], [9, 10]]])
    ticket = program.dispatch(staged)
    ticket.harvest()
    program.dispose()
    return ticket.seqs[0]


def act_refresh(backend, table, block):
    backend.refresh_block_on_device(block)  # a lone wave's rows: the sparse branch
    return None


def act_refresh_whole(backend, table, block):
    backend.HOT_REFRESH_MAX_ROWS = 0  # instance override: the whole-block program
    backend.refresh_block_on_device(block)
    return None


def act_patch(backend, table, block):
    # a declared edge from a lower level to a higher: the mirror patches
    levels = backend.graph.mirror_levels(np.arange(N_ROWS))
    u, v = int(np.argmin(levels)), int(np.argmax(levels))
    backend.declare_row_edges(block, [u], block, [v])
    backend.cascade_rows_batch(block, [N_ROWS - 1])
    return backend.last_wave_seq


SPAN_SITES = {
    # act, spans recorded exactly once under the act's wave seq, parent of each
    "lat": (act_lat, {
        "cascade": None, "wave.union": "cascade", "lat.dispatch": "wave.union",
        "lat.readback": "wave.union", "wave.apply": "cascade",
        "wave.profile": "cascade"}),
    "overflow": (act_overflow, {
        "cascade": None, "wave.union": "cascade", "lat.dispatch": "wave.union",
        "lat.readback": "wave.union", "topo.stage": "wave.union",
        "topo.dispatch": "wave.union", "topo.readback": "wave.union",
        "topo.commit": "wave.union", "wave.apply": "cascade"}),
    "flush_icasc": (act_flush_icasc, {
        "flush.icasc": "flush", "wave.union": "flush.icasc",
        "lat.dispatch": "wave.union", "lat.readback": "wave.union",
        "wave.apply": "flush.icasc", "wave.profile": "flush.icasc"}),
    "superround": (act_superround, {
        "superround.dispatch": None, "superround.wait": None,
        "superround.apply": None, "wave.profile": "superround.apply"}),
    "refresh": (act_refresh, {}),
    "refresh_whole": (act_refresh_whole, {}),
    "patch": (act_patch, {
        "cascade": None, "wave.union": "cascade",
        "mirror.validate": "wave.union", "mirror.patch": "mirror.validate",
        "lat.dispatch": "wave.union"}),
}
# recorded once with no wave of their own (they open before a seq is minted)
SEQLESS = {
    "flush_icasc": {"flush": None, "flush.coalesce": "flush", "flush.replay.icasc": "flush"},
    "superround": {"superround.stage": None},
    "refresh": {"refresh": None, "refresh.sparse": "refresh",
                "refresh.rows.dispatch": "refresh.sparse"},
    "refresh_whole": {"refresh": None, "refresh.dispatch": "refresh"},
    "patch": {"flush": "cascade", "flush.coalesce": "flush", "flush.replay.epack": "flush"},
}


@pytest.mark.parametrize("gate", ["on", "off"])
@pytest.mark.parametrize("site", sorted(SPAN_SITES))
def test_span_sites_of_the_live_loop(site, gate, monkeypatch, annotations):
    if site == "overflow":
        monkeypatch.setattr(DeviceGraph, "LAT_CAP", 32)
    backend, table, block = make_stack()
    if site.startswith("refresh"):
        backend.cascade_rows_batch(block, [N_ROWS - 1])  # something to refresh
    act, with_seq = SPAN_SITES[site]
    if gate == "off":
        # an untraced run: the record stays empty, no annotation is opened
        act(backend, table, block)
        assert hot_spans() == [] and annotations.opened == []
        return
    enable_hot_spans()
    seq = act(backend, table, block)
    record = hot_spans()
    by_id = {r.span_id: r for r in record}
    names = Counter(r.name for r in record)
    for expected, waves in ((with_seq, {seq}), (SEQLESS.get(site, {}), {None})):
        for name, parent in expected.items():
            assert names[name] == 1, (name, names)
            r = next(r for r in record if r.name == name)
            assert r.wave in waves, (name, r.wave, seq)
            assert (by_id[r.parent_id].name if r.parent_id else None) == parent, name
    if site == "lat":
        # nothing else on a lone edit's path, but for what JAX compiled on
        # this first call (the listener's spans: TestJitSpans)
        assert {n for n in names if not n.startswith("jit.")} == set(with_seq)
        # one event of the edit names its wave: the first to know it
        assert [(n, m) for n, m in annotations.opened if m] == [
            ("fusion:wave.union", {"wave": seq})]
    if site == "superround":
        assert names["wave.apply"] == 2  # one per round, inside superround.apply
        apply = next(r for r in record if r.name == "superround.apply")
        assert all(by_id[r.parent_id] is apply for r in record if r.name == "wave.apply")
    assert {n for n, _ in annotations.opened} == {"fusion:" + n for n in names}
    assert len(annotations.closed) == len(annotations.opened) == len(record)


def test_profiler_trace_turns_the_spans_on_and_holds_them(tmp_path):
    """Taking a ``jax.profiler`` trace is the switch: no call, no option.
    The ``fusion:*`` events sit on a host line of the trace, nested as the
    record has them, with the wave seq as a stat."""
    import jax
    from jax.profiler import ProfileData

    backend, table, block = make_stack()
    backend.cascade_rows_batch(block, [N_ROWS - 3])
    assert hot_spans() == [] and not hot_spans_on()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert hot_spans_on()
        backend.cascade_rows_batch(block, [N_ROWS - 1])
    finally:
        jax.profiler.stop_trace()
    assert not hot_spans_on()
    seq = backend.last_wave_seq
    assert {r.wave for r in hot_spans() if r.name == "lat.dispatch"} == {seq}
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [e for e in line.events if e.name.startswith("fusion:")]
            if events:
                assert plane.name.startswith("/host:")
                found.update({e.name: e for e in events})
    assert {"fusion:" + r.name for r in hot_spans()} == set(found)
    outer, inner = found["fusion:cascade"], found["fusion:lat.dispatch"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    assert dict(found["fusion:wave.union"].stats)["wave"] == seq


JIT_EVENTS = {
    "trace": "/jax/core/compile/jaxpr_trace_duration",
    "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "compile": "/jax/core/compile/backend_compile_duration",
}
JIT_PHASES = [f"jit.{phase}.probe" for phase in JIT_EVENTS]


def build_probe(body=None):
    """What an ``lru_cache`` program builder hands out on a miss: a NEW
    function under a new ``jax.jit``. (A new ``jax.jit`` over the SAME
    function object hits JAX's own caches and traces nothing.)"""
    import jax

    def probe(x):
        if body is not None:
            body()
        return jax.lax.add(x, x)  # a primitive: no jitted function inside

    return jax.jit(probe)


class TimedAnnotationSpy(AnnotationSpy):
    """Also when each annotation was open, on the record's clock."""

    intervals: dict = {}

    def __enter__(self):
        self._t0 = time.perf_counter()
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb):
        TimedAnnotationSpy.intervals[self.name] = (self._t0, time.perf_counter())
        return super().__exit__(exc_type, exc, tb)


class TestJitSpans:
    """JAX's trace, lower and compile phases as hot spans: no site opens
    them, ``graph/program_cache.py``'s ``jax.monitoring`` listeners do."""

    @pytest.mark.parametrize("call", ["first", "second", "new_jit"])
    def test_phases_nest_in_the_hot_span_that_caused_them(self, annotations, call):
        import jax.numpy as jnp

        enable_hot_spans()
        x = jnp.arange(8)
        probe = build_probe()
        if call != "first":
            probe(x)
        tracing.clear_hot_spans()  # of what made ``x``, too
        annotations.opened.clear()
        with hot_span("topo.dispatch", 17):
            (build_probe() if call == "new_jit" else probe)(x)
        record = hot_spans()
        outer = record[-1]
        assert outer.name == "topo.dispatch"
        if call == "second":  # every cache of JAX's hits: nothing to report
            assert [r.name for r in record] == ["topo.dispatch"]
            return
        # "new_jit" is the retrace shape: the same program, traced again
        assert [r.name for r in record] == JIT_PHASES + ["topo.dispatch"]
        edges = [outer.start]
        for r in record[:-1]:
            assert r.parent_id == outer.span_id and r.wave == 17
            edges += [r.start, r.end]
        edges.append(outer.end)
        assert edges == sorted(edges)  # inside its interval, none overlapping
        assert [n for n, _ in annotations.opened] == [
            "fusion:topo.dispatch"] + ["fusion:" + n for n in JIT_PHASES]

    def test_a_trace_inside_a_trace_is_counted_not_recorded(self, annotations):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def outer_fn(x):  # every jnp function is a jitted function of its own
            return jnp.where(x > 0, jnp.bitwise_or(x, 1), x).sum()

        enable_hot_spans()
        reset_program_warms()
        with hot_span("lat.dispatch"):
            outer_fn(jnp.arange(8))
        names = Counter(r.name for r in hot_spans())
        assert names == {"lat.dispatch": 1, "jit.trace.outer_fn": 1,
                         "jit.lower.outer_fn": 1, "jit.compile.outer_fn": 1}
        row = compile_report()["functions"]["outer_fn"]
        assert (row["traces"], row["lowers"], row["compiles"]) == (1, 1, 1)
        assert row["nested"] >= 4  # greater, bitwise_or, where, sum
        assert "bitwise_or" not in compile_report()["functions"]

    def test_spans_off_the_account_counts_the_same_phases(self, annotations):
        import jax.numpy as jnp

        assert not hot_spans_on()
        reset_program_warms()
        with hot_span("topo.dispatch"):
            build_probe()(jnp.arange(8))
        assert hot_spans() == [] and annotations.opened == []
        report = compile_report()
        row = report["functions"]["probe"]
        assert (row["traces"], row["lowers"], row["compiles"], row["nested"]) == (1, 1, 1, 0)
        assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
        assert report["totals"] == row and set(report["functions"]) == {"probe"}

    @pytest.mark.parametrize("phase", sorted(JIT_EVENTS))
    def test_each_phase_is_open_for_as_long_as_it_lasts(self, monkeypatch, annotations, phase):
        """The pinned form: the annotation opens at the phase's entry event
        and closes at its end event, so the trace viewer shows the phase's
        own extent (the closed-at-once form would open it at the end)."""
        import jax.numpy as jnp

        TimedAnnotationSpy.intervals = {}
        monkeypatch.setattr(tracing, "_TraceAnnotation", TimedAnnotationSpy)
        enable_hot_spans()
        build_probe()(jnp.arange(8))
        r = next(r for r in hot_spans() if r.name == f"jit.{phase}.probe")
        opened, closed = TimedAnnotationSpy.intervals[f"fusion:jit.{phase}.probe"]
        assert opened <= r.start <= r.end <= closed

    @pytest.mark.parametrize("entry", ["none", "other_context"])
    @pytest.mark.parametrize("phase", sorted(JIT_EVENTS))
    def test_an_end_with_no_entry_of_its_own_is_closed_at_once(
        self, annotations, phase, entry
    ):
        """Listeners registered mid-phase (no entry event), or an entry that
        ran in another context (its contextvar token is not this one's to
        reset): one span, closed as soon as opened, the event's start mapped
        onto the span clock."""
        import contextvars

        from jax import monitoring

        enable_hot_spans()
        reset_program_warms()
        t1 = time.time()
        t0 = t1 - 0.25
        with hot_span("wave.apply") as outer:
            if entry == "other_context":
                contextvars.copy_context().run(
                    monitoring.record_scalar, JIT_EVENTS[phase], t0, fun_name="jit(late)")
            before = time.perf_counter()
            monitoring.record_event_time_span(JIT_EVENTS[phase], t0, t1, fun_name="jit(late)")
            after = time.perf_counter()
        (r,) = [r for r in hot_spans() if r.name.startswith("jit.")]
        assert r.name == f"jit.{phase}.late" and r.parent_id == outer.span_id
        assert before <= r.end <= after
        assert 0.25 <= r.end - r.start <= 0.25 + (after - before) + 0.05
        row = compile_report()["functions"]["late"]
        assert row[phase + "s"] == 1 and row[phase + "_s"] == pytest.approx(0.25, abs=1e-3)
        with hot_span("flush"):
            pass
        assert hot_spans()[-1].parent_id is None  # nothing left open here

    def test_a_compile_on_another_thread_leaves_this_threads_chain(self, annotations):
        import threading

        import jax.numpy as jnp

        enable_hot_spans()
        reset_program_warms()
        x = jnp.arange(8)

        def elsewhere():
            with hot_span("lat.dispatch"):
                build_probe()(x)

        def inside_the_trace():
            worker = threading.Thread(target=elsewhere)
            worker.start()
            worker.join()

        slow = build_probe(inside_the_trace)
        with hot_span("topo.dispatch") as outer:
            slow(x)
        record = hot_spans()
        by_id = {r.span_id: r for r in record}
        parents = Counter(
            (r.name, by_id[r.parent_id].name) for r in record if r.name.startswith("jit."))
        assert parents == {(n, p): 1 for n in JIT_PHASES for p in ("topo.dispatch", "lat.dispatch")}
        mine = next(r for r in record if r.name == "jit.trace.probe"
                    and r.parent_id == outer.span_id)
        theirs = [r for r in record if r.name.startswith("jit.") and r.parent_id != outer.span_id]
        assert all(mine.start <= r.start and r.end <= mine.end for r in theirs)
        assert next(r for r in record if r.name == "lat.dispatch").parent_id is None
        # the other thread's phases were outermost on ITS stack: counted in
        # full, and not as nested in this thread's trace
        row = compile_report()["functions"]["probe"]
        assert (row["traces"], row["lowers"], row["compiles"], row["nested"]) == (2, 2, 2, 0)

    def test_the_topo_program_is_not_traced_again_on_the_cpu(self, monkeypatch, annotations):
        """``_run_mirror_union`` twice with the same shapes: the second
        ``topo.dispatch`` has no ``jit.*`` child. (On the chip it looked
        traced again on every call: PERF.md §7. A retrace HERE would be a
        fault of the program.)"""
        monkeypatch.setattr(DeviceGraph, "LAT_CAP", 32)
        reset_program_warms()
        backend, table, block = make_stack()
        enable_hot_spans()
        children = []
        for _ in range(2):
            tracing.clear_hot_spans()
            backend.graph._run_mirror_union([[0]])
            record = hot_spans()
            (dispatch,) = [r for r in record if r.name == "topo.dispatch"]
            children.append([r.name for r in record if r.parent_id == dispatch.span_id])
            backend.refresh_block_on_device(block)
            backend.flush()
        # the first call compiles it, unless an earlier test's stack did
        assert children[0] in ([], ["jit.trace.burst", "jit.lower.burst", "jit.compile.burst"])
        assert children[1] == []
        traced = compile_report()["functions"].get("burst", {"traces": 0})["traces"]
        assert traced == len(children[0]) // 3  # and nowhere else in the stack's life


class TestCommandTracer:
    async def test_traces_commands(self, fresh_hub):
        class Svc:
            @command_handler
            async def ping(self, command: Ping) -> int:
                return command.n + 1

        fresh_hub.commander.add_service(Svc())
        attach_command_tracer(fresh_hub.commander)
        assert await fresh_hub.commander.call(Ping(1)) == 2
        spans = recent_spans(source="stl_fusion_tpu.commands", name="run:Ping")
        assert spans and not spans[-1].failed

    async def test_traces_errors(self, fresh_hub):
        @dataclass(frozen=True)
        class Fail:
            pass

        class Svc:
            @command_handler
            async def fail(self, command: Fail):
                raise RuntimeError("nope")

        fresh_hub.commander.add_service(Svc())
        attach_command_tracer(fresh_hub.commander)
        with pytest.raises(RuntimeError):
            await fresh_hub.commander.call(Fail())
        spans = [s for s in recent_spans(source="stl_fusion_tpu.commands") if s.name == "run:Fail"]
        assert spans and spans[-1].tags.get("error_type") == "RuntimeError"


class TestEntityResolver:
    async def test_concurrent_resolves_coalesce_into_one_batch(self):
        backend_calls = []

        async def fetch_many(keys):
            backend_calls.append(sorted(keys))
            return {k: f"user-{k}" for k in keys}

        resolver = EntityResolver(fetch_many)
        results = await asyncio.gather(*(resolver.resolve(i) for i in range(8)))
        assert results == [f"user-{i}" for i in range(8)]
        assert resolver.batches == 1
        assert backend_calls == [list(range(8))]

    async def test_same_key_shares_one_fetch(self):
        count = [0]

        async def fetch_many(keys):
            count[0] += len(keys)
            return {k: k for k in keys}

        resolver = EntityResolver(fetch_many)
        results = await asyncio.gather(*(resolver.resolve("a") for _ in range(5)))
        assert results == ["a"] * 5
        assert count[0] == 1

    async def test_missing_keys_resolve_none(self):
        async def fetch_many(keys):
            return {}

        resolver = EntityResolver(fetch_many)
        assert await resolver.resolve("ghost") is None

    async def test_batch_size_cap(self):
        sizes = []

        async def fetch_many(keys):
            sizes.append(len(keys))
            return {k: k for k in keys}

        resolver = EntityResolver(fetch_many, max_batch_size=3)
        await asyncio.gather(*(resolver.resolve(i) for i in range(8)))
        assert all(s <= 3 for s in sizes)
        assert sum(sizes) == 8

    async def test_backend_error_propagates_to_all_waiters(self):
        async def fetch_many(keys):
            raise TimeoutError("db down")

        resolver = EntityResolver(fetch_many)
        results = await asyncio.gather(
            *(resolver.resolve(i) for i in range(3)), return_exceptions=True
        )
        assert all(isinstance(r, TimeoutError) for r in results)

    async def test_resolve_many(self):
        async def fetch_many(keys):
            return {k: k * 2 for k in keys if k != 3}

        resolver = EntityResolver(fetch_many)
        out = await resolver.resolve_many([1, 2, 3])
        assert out == {1: 2, 2: 4, 3: None}


class TestOperationLogTrimmer:
    async def test_trims_old_records(self):
        import time as _time

        from stl_fusion_tpu.oplog import InMemoryOperationLog, OperationRecord
        from stl_fusion_tpu.oplog.trimmer import OperationLogTrimmer

        store = InMemoryOperationLog()
        now = _time.time()
        for i in range(5):
            store.append(OperationRecord(f"op{i}", "agent", now - 1000 + i, None, ()))
        store.append(OperationRecord("fresh", "agent", now, None, ()))
        trimmer = OperationLogTrimmer(store, max_age=600.0)
        removed = trimmer.trim_once()
        assert removed == 5
        assert trimmer.trimmed_total == 5
        remaining = store.read_after(-1)
        assert [r.id for r in remaining] == ["fresh"]
