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
"""
from __future__ import annotations

import os

__all__ = [
    "enable_program_cache",
    "program_cache_dir",
    "program_cache_stats",
    "time_program_warm",
    "note_program_shape",
    "program_warm_report",
    "reset_program_warms",
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
    read as the same warm. ``cache_hit`` is judged from the persistent
    cache dir: a warm that added NO new executables (and the cache is
    enabled) was served from disk/in-process. Records land in
    :func:`program_warm_report`; the benchmark's ``program_warm_s`` is
    their sum.

    Usage::

        with time_program_warm("lane", key=(n_tot, words, passes)):
            backend.cascade_rows_lanes(block, group_ids)
    """

    def __init__(self, name: str, key=None):
        self.name = name
        self.key = key
        self._t0 = 0.0
        self._entries0 = 0

    def _entries(self) -> int:
        try:
            return program_cache_stats()["entries"]
        except OSError:  # an unreadable cache dir reads as empty
            return 0

    def __enter__(self):
        import time

        self._entries0 = self._entries()
        _SHAPE_NOTES.clear()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        import time

        dt = time.perf_counter() - self._t0
        new = self._entries() - self._entries0
        # with no cache dir on disk the entry delta proves nothing — a
        # cold 60 s compile must never be recorded as cache-served
        # (cache_hit=None = unattributable, the honest answer)
        jax_dir = program_cache_dir()
        cache_present = jax_dir is not None and os.path.isdir(jax_dir)
        _PROGRAM_WARMS[self.name] = {
            "key": repr(self.key) if self.key is not None else None,
            "warm_s": round(dt, 3),
            "new_entries": int(new),
            # no new persisted executables ⇒ the warm was served from the
            # persistent cache (or was cheap enough to fall under the
            # min-compile-time persistence floor — either way, not a cold
            # multi-second XLA compile)
            "cache_hit": (new <= 0) if cache_present else None,
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
