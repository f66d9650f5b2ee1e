"""Persistent program cache — compiled lane/burst programs survive restarts.

The cold-start budget's biggest line items are compiles, not data. XLA
already ships a persistent compilation cache; this module is the ONE place
the project wires it (benchmarks/run.py, chip_smoke.py, every perf/*.py
script and any serving process call :func:`enable_program_cache`; nothing else
touches ``jax_compilation_cache_dir``), plus the restart-warmth telemetry:
:func:`program_cache_stats` counts cached executables so the warm-rejoin
path (cluster/rejoin.py, DURABILITY.md) can report whether a restart
actually pre-warmed from disk or recompiled cold.

Where the cache lives is decided from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it stands and
this module sets no directory; when it is not, the directory is
``<checkout>/.jax_cache``, a FIXED path (the path is part of the cache
key, so a directory that moves never hits). The topo-mirror disk cache
(device_graph.py) sits beside it at ``<checkout>/.fusion_mirror_cache``
unless ``FUSION_MIRROR_CACHE`` names another directory.

It also keeps the compile ACCOUNT (:func:`watch_compiles`,
:func:`compile_report`): JAX reports every trace, lowering and backend
compile itself through ``jax.monitoring``, on the thread that called the
jitted function, so which function recompiled, in which phase, for how
long and under which hot span is read from the program and not guessed
from outside. The listeners run only when JAX traces, lowers or compiles,
which a steady state never does: no hot path gains a line.
"""
from __future__ import annotations

import os
import sys
import threading
import time

from ..diagnostics import tracing

__all__ = [
    "enable_program_cache",
    "program_cache_dir",
    "program_cache_stats",
    "time_program_warm",
    "note_program_shape",
    "program_warm_report",
    "reset_program_warms",
    "watch_compiles",
    "compile_report",
]

#: JAX's own variable; when set, this module leaves the directory alone
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
MIRROR_CACHE_ENV = "FUSION_MIRROR_CACHE"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_program_cache() -> dict:
    """Turn on XLA's persistent compilation cache and the topo-mirror disk
    cache (module docstring: where each lives). Idempotent. Raises when
    the cache directory cannot be created — a run that silently compiles
    cold is a different run, so callers hear about it. Returns
    ``{jax_cache_dir, mirror_cache_dir, from_env}``."""
    import jax

    from_env = bool(os.environ.get(JAX_CACHE_ENV))
    if not from_env:
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_CHECKOUT, ".jax_cache")
        )
    jax_dir = program_cache_dir()
    os.makedirs(jax_dir, exist_ok=True)
    mirror_dir = os.environ.setdefault(
        MIRROR_CACHE_ENV, os.path.join(_CHECKOUT, ".fusion_mirror_cache")
    )
    from ..diagnostics.metrics import global_metrics

    global_metrics().gauge(
        "fusion_program_cache_enabled",
        help="1 when the persistent XLA compilation cache is active",
    ).set(1)
    watch_compiles()
    return {
        "jax_cache_dir": jax_dir,
        "mirror_cache_dir": mirror_dir,
        "from_env": from_env,
    }


def program_cache_dir():
    """The EFFECTIVE compilation-cache directory, as ``jax.config`` holds
    it (from ``JAX_COMPILATION_CACHE_DIR`` or :func:`enable_program_cache`);
    None while no cache is configured."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


#: per-program warm records: name -> {"key", "warm_s", "cache_hit",
#: "new_entries"} plus the shape facts noted while the warm traced its
#: programs (insertion-ordered; the bench cold_start block reports it)
_PROGRAM_WARMS: dict = {}
#: fact -> distinct values, in the order programs noted them
_SHAPE_NOTES: dict = {}


def note_program_shape(**facts) -> None:
    """Called from a program's body while it is TRACED, with what its
    shapes decided and no argument chose (the topo sweep's
    ``nodes_per_row``). A :class:`time_program_warm` record carries the
    distinct values noted inside its window: ``"nodes_per_row": [8, 1]``
    for a warm that traced a 16-word sweep and a one-word one."""
    for fact, value in facts.items():
        seen = _SHAPE_NOTES.setdefault(fact, [])
        if value not in seen:
            seen.append(value)


class time_program_warm:
    """Context manager timing ONE program family's warm-up, attributing it
    to the persistent cache (a lane-program warm of a minute used to be
    recorded with no way to tell a cache-served warm from a cold compile).
    ``key`` names what the program is keyed on
    — geometry, depth, exchange — so two runs with different keys never
    read as the same warm. ``cache_hit`` is JAX's own answer where the
    persistent cache answered at all during the warm (hits and no miss);
    where it did not, it is judged from the cache dir: a warm that added NO
    new executables (and the cache is enabled) was served from
    disk/in-process. ``trace_s``, ``lower_s``, ``compile_s``, ``cache_hits``
    and ``cache_misses`` are the compile account's change over the warm
    (:func:`compile_report`). Records land in :func:`program_warm_report`;
    the benchmark's ``program_warm_s`` is their sum.

    Usage::

        with time_program_warm("lane", key=(n_tot, words, passes)):
            backend.cascade_rows_lanes(block, group_ids)
    """

    def __init__(self, name: str, key=None):
        self.name = name
        self.key = key
        self._t0 = 0.0
        self._entries0 = 0
        self._account0: dict = {}

    def _entries(self) -> int:
        try:
            return program_cache_stats()["entries"]
        except OSError:  # an unreadable cache dir reads as empty
            return 0

    def __enter__(self):
        self._entries0 = self._entries()
        _SHAPE_NOTES.clear()
        self._account0 = _ACCOUNT.total()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        totals = _ACCOUNT.total()
        account = {
            key: totals[key] - self._account0[key]
            for key in ("trace_s", "lower_s", "compile_s", "cache_hits", "cache_misses")
        }
        new = self._entries() - self._entries0
        # with no cache dir on disk the entry delta proves nothing — a
        # cold 60 s compile must never be recorded as cache-served
        # (cache_hit=None = unattributable, the honest answer)
        jax_dir = program_cache_dir()
        cache_present = jax_dir is not None and os.path.isdir(jax_dir)
        if account["cache_hits"] or account["cache_misses"]:
            cache_hit = account["cache_misses"] == 0
        else:
            # the cache said nothing: no new persisted executables ⇒ the
            # warm was served from the persistent cache (or was cheap
            # enough to fall under the min-compile-time persistence floor
            # — either way, not a cold multi-second XLA compile)
            cache_hit = (new <= 0) if cache_present else None
        _PROGRAM_WARMS[self.name] = {
            "key": repr(self.key) if self.key is not None else None,
            "warm_s": round(dt, 3),
            "new_entries": int(new),
            "cache_hit": cache_hit,
            **{k: round(v, 6) if k.endswith("_s") else v for k, v in account.items()},
            **{fact: list(seen) for fact, seen in _SHAPE_NOTES.items()},
        }
        return False


def program_warm_report() -> dict:
    """Everything :class:`time_program_warm` recorded this process — the
    bench ``cold_start.programs`` block (per-program warm seconds + warm
    vs. cache-hit attribution)."""
    return {k: dict(v) for k, v in _PROGRAM_WARMS.items()}


def reset_program_warms() -> None:
    _PROGRAM_WARMS.clear()
    _ACCOUNT.clear()


def program_cache_stats() -> dict:
    """Count cached executables + bytes under the effective cache dir —
    the restart-warmth signal (``entries > 0`` before first compile of a
    new process means the restart pre-warms from disk)."""
    jax_dir = program_cache_dir()
    entries = 0
    size = 0
    if jax_dir is not None and os.path.isdir(jax_dir):
        for dirpath, _dirnames, filenames in os.walk(jax_dir):
            for name in filenames:
                entries += 1
                try:
                    size += os.path.getsize(os.path.join(dirpath, name))
                except OSError:
                    pass
    return {"dir": jax_dir, "entries": entries, "bytes": size}


# ---------------------------------------------------------------- the compile account
#: ``jax.monitoring``'s phase events -> (phase, count key, seconds key). JAX
#: sends a scalar when a phase is ENTERED and a time span when it ENDS, both
#: with ``fun_name``, from inside the call that caused the phase
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace", "traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower", "lowers", "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("compile", "compiles", "compile_s"),
}
#: the persistent cache's own answers; they arrive inside a ``compile`` phase
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: distinct function names the account keeps; the rest share ``other``
COMPILE_ACCOUNT_CAP = 256
#: name prefixes of the loaded modules whose ``functools.lru_cache`` program
#: builders :func:`compile_report` lists
_BUILDER_MODULES = (
    "stl_fusion_tpu.ops.", "stl_fusion_tpu.graph.device_graph",
    "stl_fusion_tpu.parallel.",
)


def _new_row() -> dict:
    return {
        "traces": 0, "trace_s": 0.0, "lowers": 0, "lower_s": 0.0,
        "compiles": 0, "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
        "cache_load_s": 0.0, "nested": 0,
    }


class _CompileAccount:
    """What JAX traced, lowered and compiled in this process, by function.
    One per process (the module's), so the registry exports its totals once
    however many backends the process holds."""

    def __init__(self):
        self._lock = threading.Lock()
        self.clear()

    def clear(self) -> None:
        with self._lock:
            self.functions: dict = {}
            self.other = _new_row()
            self.totals = _new_row()

    def add(self, fun, **amounts) -> None:
        """``fun`` None: an event outside any phase, kept under ``other``."""
        with self._lock:
            row = self.functions.get(fun)
            if row is None:
                if fun is None or len(self.functions) >= COMPILE_ACCOUNT_CAP:
                    row = self.other
                else:
                    row = self.functions[fun] = _new_row()
            for key, amount in amounts.items():
                row[key] += amount
                self.totals[key] += amount

    def total(self) -> dict:
        with self._lock:
            return dict(self.totals)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "functions": {k: dict(v) for k, v in self.functions.items()},
                "other": dict(self.other),
                "totals": dict(self.totals),
            }

    def _collect_metrics(self) -> dict:
        t = self.total()
        return {
            "fusion_jit_traces_total": t["traces"],
            "fusion_jit_trace_seconds_total": t["trace_s"],
            "fusion_jit_lowers_total": t["lowers"],
            "fusion_jit_lower_seconds_total": t["lower_s"],
            "fusion_jit_compiles_total": t["compiles"],
            "fusion_jit_compile_seconds_total": t["compile_s"],
            "fusion_jit_cache_hits_total": t["cache_hits"],
            "fusion_jit_cache_misses_total": t["cache_misses"],
        }


_ACCOUNT = _CompileAccount()
#: per thread, the phases JAX has entered and not ended, outermost first:
#: ``(event, fun_name as sent, bare function, the open jit.* span or None)``
_OPEN = threading.local()
_watch_lock = threading.Lock()
_watching = False


def _open_phases() -> list:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


def _outermost_fun():
    """The function whose phase this thread is in (None: in none)."""
    stack = _open_phases()
    return stack[0][2] if stack else None


def _bare(fun_name: str) -> str:
    """``jit(burst)`` -> ``burst``: the trace phase names the function, the
    other two the module; one name lines a span up with its device program
    (``jit_burst``)."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _closed_at_once(name: str, start_wall: float) -> None:
    """A span for a phase that was not open as one: closed as soon as
    opened, its start the event's own (wall clock) mapped onto the span
    clock by the two clocks' difference now (``oplog.lag``'s form)."""
    now = time.perf_counter()
    with tracing.hot_span(name, start=now - max(time.time() - start_wall, 0.0)):
        pass


def _phase_entered(event: str, _value, fun_name: str = "", **_kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    stack = _open_phases()
    fun, span = _bare(fun_name), None
    if stack:
        # inside another phase (the jnp functions a trace traces, an eager
        # op compiled while tracing): counted, never a span of its own
        _ACCOUNT.add(stack[0][2], nested=1)
    elif tracing.hot_spans_on():
        span = tracing.hot_span(f"jit.{phase[0]}.{fun}")
        span.__enter__()
    stack.append((event, fun_name, fun, span))


def _phase_ended(event: str, start: float, end: float, fun_name: str = "", **_kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    stack = _open_phases()
    depth = next(
        (d for d in range(len(stack) - 1, -1, -1) if stack[d][:2] == (event, fun_name)),
        None,
    )
    span = None
    if depth is not None:
        span = stack[depth][3]
        del stack[depth:]  # with it, entries above whose end never came
        if depth:
            return  # nested: counted when it was entered
    elif stack:
        # no entry of its own (the listeners were registered mid-phase, or
        # this JAX sends no entry scalar), inside a phase that has one
        _ACCOUNT.add(stack[0][2], nested=1)
        return
    _name, count_key, seconds_key = phase
    fun = _bare(fun_name)
    _ACCOUNT.add(fun, **{count_key: 1, seconds_key: end - start})
    if span is not None:
        try:
            span.__exit__(None, None, None)
            return
        except ValueError:
            # entered in another context: its token is not this one's to
            # reset, and nothing was recorded
            pass
    if tracing.hot_spans_on():
        _closed_at_once(f"jit.{_name}.{fun}", start)


def _cache_answered(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        _ACCOUNT.add(_outermost_fun(), **{key: 1})


def _cache_loaded(event: str, seconds: float, **_kw) -> None:
    if event == _CACHE_LOAD_EVENT:
        _ACCOUNT.add(_outermost_fun(), cache_load_s=seconds)


def watch_compiles() -> None:
    """Register the account's listeners with ``jax.monitoring``, once a
    process, and its collector with the metrics registry (again on every
    call: a registry that was cleared gets it back). Called by
    :func:`enable_program_cache` and by the hot-span gate's first
    evaluation, so every process that reaches a span site has it."""
    global _watching
    from ..diagnostics.metrics import global_metrics

    reg = global_metrics()
    with _watch_lock:
        reg.unregister_collector(_ACCOUNT)
        reg.register_collector(_ACCOUNT, _CompileAccount._collect_metrics)
        if _watching:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_phase_entered)
        monitoring.register_event_time_span_listener(_phase_ended)
        monitoring.register_event_listener(_cache_answered)
        monitoring.register_event_duration_secs_listener(_cache_loaded)
        _watching = True


def _builder_caches() -> dict:
    """``cache_info()`` of every ``functools.lru_cache`` program builder of
    the loaded modules of :data:`_BUILDER_MODULES`: a builder that misses
    hands out a NEW function under a new ``jax.jit``, which JAX traces,
    lowers and compiles again whatever its own caches hold (they are keyed
    on the function object)."""
    found = {}
    for mod_name, mod in sorted(sys.modules.items()):
        if mod is None or not mod_name.startswith(_BUILDER_MODULES):
            continue
        for attr, obj in sorted(vars(mod).items()):
            info = getattr(obj, "cache_info", None)
            if info is None or getattr(obj, "__module__", None) != mod_name:
                continue
            found[f"{mod_name.rpartition('.')[2]}.{attr}"] = info()._asdict()
    return found


def compile_report() -> dict:
    """The compile account of this process, on or off any trace:
    ``functions`` (per bare function name: how often and for how many
    seconds JAX traced, lowered and compiled it as an OUTERMOST phase, the
    persistent cache's hits, misses and load seconds inside those compiles,
    and ``nested``, the phases entered inside them), ``other`` (names past
    the first :data:`COMPILE_ACCOUNT_CAP`, cache events outside any phase),
    ``totals`` (what the ``fusion_jit_*`` counters export) and ``builders``
    (:func:`_builder_caches`)."""
    report = _ACCOUNT.snapshot()
    report["builders"] = _builder_caches()
    return report
