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
