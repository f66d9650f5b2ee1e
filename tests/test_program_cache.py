"""The compile cache is placed from OUTSIDE the program: with
``JAX_COMPILATION_CACHE_DIR`` set, ``enable_program_cache`` leaves JAX's own
reading of it alone; unset, the cache is the fixed ``<checkout>/.jax_cache``.
Subprocesses, because the setting is process-global jax config."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRIVER = """
import json, jax
updates = []
real_update = jax.config.update
def spy(name, value):
    updates.append(name)
    real_update(name, value)
jax.config.update = spy
from stl_fusion_tpu.graph.program_cache import (
    enable_program_cache, program_cache_dir, program_cache_stats,
)
info = enable_program_cache()
print(json.dumps({
    "info": info, "updates": updates,
    "config": jax.config.jax_compilation_cache_dir,
    "effective": program_cache_dir(), "stats_dir": program_cache_stats()["dir"],
}))
"""


def _run(env_dir):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("FUSION_MIRROR_CACHE", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_placed_cache_is_left_alone(tmp_path):
    placed = str(tmp_path / "placed")
    d = _run(placed)
    assert "jax_compilation_cache_dir" not in d["updates"]
    assert d["config"] == d["effective"] == d["stats_dir"] == placed
    assert d["info"]["from_env"] is True and d["info"]["jax_cache_dir"] == placed
    assert os.path.isdir(placed)


def test_default_cache_is_inside_the_checkout():
    d = _run(None)
    want = os.path.join(REPO, ".jax_cache")
    assert d["config"] == d["effective"] == d["stats_dir"] == want
    assert d["info"]["from_env"] is False
    assert d["info"]["mirror_cache_dir"] == os.path.join(REPO, ".fusion_mirror_cache")


# ------------------------------------------------------------ the compile account
import jax  # noqa: E402
import pytest  # noqa: E402
from jax import monitoring  # noqa: E402

from stl_fusion_tpu.diagnostics.metrics import global_metrics  # noqa: E402
from stl_fusion_tpu.graph import program_cache  # noqa: E402
from stl_fusion_tpu.graph.program_cache import (  # noqa: E402
    COMPILE_ACCOUNT_CAP,
    compile_report,
    program_warm_report,
    reset_program_warms,
    time_program_warm,
    watch_compiles,
)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
COUNTERS = {
    "fusion_jit_traces_total": "traces", "fusion_jit_trace_seconds_total": "trace_s",
    "fusion_jit_lowers_total": "lowers", "fusion_jit_lower_seconds_total": "lower_s",
    "fusion_jit_compiles_total": "compiles", "fusion_jit_compile_seconds_total": "compile_s",
    "fusion_jit_cache_hits_total": "cache_hits", "fusion_jit_cache_misses_total": "cache_misses",
}


@pytest.fixture
def account():
    watch_compiles()
    reset_program_warms()
    yield
    reset_program_warms()


def fresh_program():
    """A new function under a new ``jax.jit``: what a builder miss hands out."""

    def prog(x):
        return jax.lax.mul(x, x)

    return jax.jit(prog)


@pytest.mark.parametrize("fun_name,bare", [
    ("jit(burst)", "burst"), ("burst", "burst"), ("pmap(step)", "step"),
    ("jit(<lambda>)", "<lambda>"), ("jit(<unknown>)", "<unknown>"),
])
def test_a_phase_is_booked_under_the_bare_function(account, fun_name, bare):
    monitoring.record_scalar(TRACE, 0.0, fun_name=fun_name)
    monitoring.record_event_time_span(TRACE, 10.0, 10.5, fun_name=fun_name)
    assert compile_report()["functions"] == {bare: {
        "traces": 1, "trace_s": 0.5, "lowers": 0, "lower_s": 0.0, "compiles": 0,
        "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0, "cache_load_s": 0.0,
        "nested": 0}}


def test_reset_program_warms_clears_the_account(account):
    fresh_program()(jax.numpy.arange(4))
    with time_program_warm("p"):
        pass
    assert compile_report()["totals"]["compiles"] >= 1 and program_warm_report()
    reset_program_warms()
    report = compile_report()
    assert report["functions"] == {} and not any(report["totals"].values())
    assert not any(report["other"].values()) and program_warm_report() == {}


def test_watching_twice_counts_once_and_exports_once(account):
    watch_compiles()
    watch_compiles()
    fresh_program()(jax.numpy.arange(4))
    totals = compile_report()["totals"]
    assert (totals["traces"], totals["lowers"], totals["compiles"]) == (1, 1, 1)
    samples = global_metrics().flat_samples()
    assert {name: samples[name] for name in COUNTERS} == {
        name: totals[key] for name, key in COUNTERS.items()}


def test_the_account_keeps_a_bounded_number_of_names(account):
    for i in range(COMPILE_ACCOUNT_CAP + 40):
        monitoring.record_event_time_span(TRACE, 0.0, 1.0, fun_name=f"f{i}")
    monitoring.record_event("/jax/compilation_cache/cache_hits")  # outside any phase
    report = compile_report()
    assert len(report["functions"]) == COMPILE_ACCOUNT_CAP
    assert report["other"]["traces"] == 40 and report["other"]["cache_hits"] == 1
    assert report["totals"]["traces"] == COMPILE_ACCOUNT_CAP + 40


@pytest.mark.parametrize("cache", ["hit", "miss", "hit_and_miss", "silent"])
def test_a_warm_carries_the_accounts_change_and_jaxs_own_answer(account, cache, monkeypatch):
    """``cache_hit`` is the persistent cache's own word where it spoke
    during the warm; the directory count only where it did not."""
    monkeypatch.setattr(program_cache, "program_cache_dir", lambda: None)
    events = {"hit": ["cache_hits"], "miss": ["cache_misses"],
              "hit_and_miss": ["cache_hits", "cache_misses"], "silent": []}[cache]
    with time_program_warm("lane", key=(8, 1)):
        fresh_program()(jax.numpy.arange(4))
        monitoring.record_scalar(COMPILE, 0.0, fun_name="jit(lane)")
        for name in events:  # as JAX sends them: inside the compile phase
            monitoring.record_event("/jax/compilation_cache/" + name)
        monitoring.record_event_time_span(COMPILE, 5.0, 7.0, fun_name="jit(lane)")
    warm = program_warm_report()["lane"]
    assert warm["key"] == "(8, 1)" and warm["warm_s"] >= 0 and warm["new_entries"] == 0
    assert warm["trace_s"] > 0 and warm["lower_s"] > 0 and warm["compile_s"] >= 2.0
    assert (warm["cache_hits"], warm["cache_misses"]) == (
        events.count("cache_hits"), events.count("cache_misses"))
    # no cache directory here, so the fallback's answer is "cannot tell"
    assert warm["cache_hit"] is {"hit": True, "miss": False, "hit_and_miss": False,
                                 "silent": None}[cache]
    row = compile_report()["functions"]["lane"]
    assert (row["cache_hits"], row["cache_misses"]) == (warm["cache_hits"], warm["cache_misses"])


def test_the_report_lists_the_program_builders_caches(account):
    from stl_fusion_tpu.ops import bitops, ell_wave, topo_wave  # noqa: F401

    import stl_fusion_tpu.graph.device_graph  # noqa: F401

    builders = compile_report()["builders"]
    per_module = {m: sum(1 for k in builders if k.startswith(m + "."))
                  for m in ("topo_wave", "ell_wave", "bitops", "device_graph")}
    assert per_module["topo_wave"] == 9 and per_module["ell_wave"] == 3
    assert per_module["bitops"] == 3 and per_module["device_graph"] >= 3
    before = builders["topo_wave.topo_mirror_fused_union_step"]
    assert set(before) == {"hits", "misses", "maxsize", "currsize"} and before["maxsize"] == 8
    topo_wave.topo_mirror_fused_union_step((0, 3, 5), 7, 5, 1)  # a key no test uses
    after = compile_report()["builders"]["topo_wave.topo_mirror_fused_union_step"]
    assert after["misses"] == before["misses"] + 1


_CACHE_DRIVER = """
import json, jax
from stl_fusion_tpu.graph.program_cache import (
    enable_program_cache, compile_report, program_warm_report, time_program_warm,
)
enable_program_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
def build():
    def lane(x):
        return jax.lax.mul(x, x)
    return jax.jit(lane)
x = jax.numpy.arange(4)
for name in ("cold", "again"):
    with time_program_warm(name):
        build()(x)
print(json.dumps({"warms": program_warm_report(), "lane": compile_report()["functions"]["lane"]}))
"""


def test_the_persistent_cache_answers_for_itself(tmp_path):
    """A real miss, then a real hit (a new ``jax.jit`` of the same program
    compiles again and finds it on disk), each booked to the function."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_DRIVER], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    d = json.loads(proc.stdout.strip().splitlines()[-1])
    cold, again = d["warms"]["cold"], d["warms"]["again"]
    assert (cold["cache_hit"], cold["cache_hits"], cold["cache_misses"]) == (False, 0, 1)
    assert (again["cache_hit"], again["cache_hits"], again["cache_misses"]) == (True, 1, 0)
    assert cold["new_entries"] > 0 and again["new_entries"] == 0
    lane = d["lane"]
    assert (lane["traces"], lane["lowers"], lane["compiles"]) == (2, 2, 2)
    assert (lane["cache_hits"], lane["cache_misses"]) == (1, 1) and lane["cache_load_s"] > 0


def test_threads_book_their_own_phases_and_lose_none(account):
    """Each thread has its own stack of open phases; the account is one,
    under a lock: no update lost, nothing counted as nested in a phase that
    another thread has open."""
    import threading

    n_threads, n_each = 16, 400
    start = threading.Barrier(n_threads)

    def compiles(i):
        start.wait(timeout=30)
        for _ in range(n_each):
            monitoring.record_scalar(TRACE, 0.0, fun_name=f"f{i % 4}")
            monitoring.record_event("/jax/compilation_cache/cache_hits")
            monitoring.record_event_time_span(TRACE, 1.0, 1.5, fun_name=f"f{i % 4}")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=compiles, args=(i,)) for i in range(n_threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    report = compile_report()
    assert set(report["functions"]) == {"f0", "f1", "f2", "f3"}
    for row in report["functions"].values():
        assert (row["traces"], row["cache_hits"], row["nested"]) == (
            n_threads // 4 * n_each, n_threads // 4 * n_each, 0)
        assert row["trace_s"] == pytest.approx(0.5 * n_threads // 4 * n_each)
    assert report["totals"]["traces"] == n_threads * n_each
