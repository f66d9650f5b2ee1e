"""Multi-host bring-up (ISSUE 15): launcher env contract, single-host
context shortcut, and the real 2-OS-process mesh self-check (slow: the
tier1 `multihost` CI job runs the full perf gate; the spawn test here is
the library-level smoke)."""
import os
import subprocess
import sys

import pytest

from stl_fusion_tpu.cluster.multihost import (
    ENV_COORDINATOR,
    ENV_DEVICES_PER_HOST,
    ENV_NUM_HOSTS,
    ENV_PROCESS_ID,
    MultiHostContext,
    host_env,
    init_multihost,
    pick_coordinator,
)


def test_host_env_sets_mesh_vars_and_replaces_device_count():
    base = {
        "PYTHONPATH": "/keep/this:/and/this",
        "XLA_FLAGS": "--xla_foo=1 --xla_force_host_platform_device_count=8",
        "SOMETHING": "else",
    }
    env = host_env(2, 1, "127.0.0.1:9999", 4, base_env=base)
    # the parent env survives
    assert env["PYTHONPATH"] == "/keep/this:/and/this"
    assert env["SOMETHING"] == "else"
    # the device-count flag is REPLACED, other XLA flags kept
    assert "--xla_foo=1" in env["XLA_FLAGS"]
    assert env["XLA_FLAGS"].count("xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env[ENV_NUM_HOSTS] == "2" and env[ENV_PROCESS_ID] == "1"
    assert env[ENV_COORDINATOR] == "127.0.0.1:9999"
    assert env[ENV_DEVICES_PER_HOST] == "4"


def test_pick_coordinator_returns_bindable_address():
    addr = pick_coordinator()
    host, port = addr.rsplit(":", 1)
    assert host == "127.0.0.1" and 0 < int(port) < 65536


def test_context_geometry_helpers():
    ctx = MultiHostContext(process_id=1, n_hosts=2, devices_per_host=4)
    assert ctx.n_dev == 8 and ctx.is_multiprocess
    assert ctx.host_of_device(3) == 0 and ctx.host_of_device(4) == 1
    assert ctx.member_names() == ["h0", "h1"]
    assert ctx.member_names("m") == ["m0", "m1"]


def test_init_single_host_shortcut_no_distributed_runtime():
    """n_hosts=1 must not touch jax.distributed (a lone survivor phase
    and every pre-ISSUE-15 caller run this path)."""
    import jax

    ctx = init_multihost(n_hosts=1, devices_per_host=jax.local_device_count())
    assert not ctx.is_multiprocess
    assert ctx.n_dev == jax.local_device_count()
    ctx.sync()  # no-op
    ctx.shutdown()  # no-op
    # a wrong local device expectation must refuse loudly
    with pytest.raises(RuntimeError):
        init_multihost(n_hosts=1, devices_per_host=jax.local_device_count() + 1)


def test_world_guards_without_distributed_runtime():
    """Single-host library guards: no client installed, detach is a no-op,
    teardown is safe to call on an unformed world (the degrade path calls
    it unconditionally)."""
    from stl_fusion_tpu.cluster.multihost import detach_world, world_is_formed

    assert not world_is_formed()
    assert detach_world() is False


_ELASTIC_WORKER = r"""
import os, sys, time
import numpy as np
import jax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from stl_fusion_tpu.cluster.multihost import (
    detach_world, form_world, pick_coordinator, teardown_world,
    world_is_formed,
)
from stl_fusion_tpu.parallel.mesh import GRAPH_AXIS, graph_mesh, shard_map_compat

DIR = os.environ["ELASTIC_DIR"]
pid = int(os.environ["FUSION_MH_PROCESS_ID"])
n = int(os.environ["FUSION_MH_NUM_HOSTS"])

def put(name):
    open(os.path.join(DIR, name), "w").write("1")

def wait(name, t=90):
    t0 = time.time()
    while not os.path.exists(os.path.join(DIR, name)):
        assert time.time() - t0 < t, name
        time.sleep(0.05)

form_world(n, pid, os.environ["FUSION_MH_COORDINATOR"])
assert world_is_formed()
mesh = graph_mesh()
sh = NamedSharding(mesh, P(GRAPH_AXIS))

@jax.jit
def f(x):
    @shard_map_compat(mesh=mesh, in_specs=(P(GRAPH_AXIS),), out_specs=P(GRAPH_AXIS))
    def inner(xl):
        return xl + lax.psum(xl.sum(), GRAPH_AXIS)
    return inner(x)

x = jax.device_put(np.arange(jax.device_count() * 4, dtype=np.int32), sh)
np.asarray(f(x).addressable_shards[0].data)
put(f"ready-{pid}")
for i in range(n):
    wait(f"ready-{i}")
assert detach_world() and not world_is_formed()
np.asarray(f(x).addressable_shards[0].data)  # collectives outlive the agent
print("DETACHED_OK", flush=True)
if pid == 1:
    put("h1-parked")
    time.sleep(120)  # parked until the orchestrator SIGKILLs us
    sys.exit(0)
wait("h1-dead")
# the survivor arc, all in THIS process: abandon the dead world, serve
# local, then re-form a fresh 1-host world on a new coordinator port
teardown_world(rebuild_local=True)
z = np.asarray(jax.jit(lambda a: a * 2)(np.arange(8)))
assert int(z[3]) == 6
form_world(1, 0, pick_coordinator())
assert world_is_formed()
teardown_world(rebuild_local=True)
print("SURVIVOR_OK", flush=True)
"""


@pytest.mark.slow
def test_survivor_outlives_peer_kill_without_restart(tmp_path):
    """THE elastic-world mechanics (ISSUE 16), library level: two real
    host processes form a world, both detach the coordination agent, the
    orchestrator SIGKILLs h1 — and h0 (the SAME process, never restarted)
    tears the dead world down, computes locally, and re-forms a fresh
    world. Without detach_world the kill aborts h0 with rc=-6 (measured)."""
    from stl_fusion_tpu.cluster.multihost import launch_hosts

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = tmp_path / "elastic_worker.py"
    worker.write_text(_ELASTIC_WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["ELASTIC_DIR"] = str(tmp_path)
    procs = launch_hosts(
        [sys.executable, str(worker)],
        n_hosts=2,
        devices_per_host=2,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
    )
    try:
        deadline = 90
        import time as _time

        t0 = _time.time()
        while not (tmp_path / "h1-parked").exists():
            assert _time.time() - t0 < deadline, "h1 never parked"
            assert procs[1].poll() is None, procs[1].communicate()[0].decode()
            _time.sleep(0.1)
        procs[1].kill()  # the host-kill chaos primitive
        procs[1].wait(timeout=30)
        (tmp_path / "h1-dead").write_text("1")
        out0, _ = procs[0].communicate(timeout=120)
        text = out0.decode()
        assert procs[0].returncode == 0, text
        assert "DETACHED_OK" in text and "SURVIVOR_OK" in text, text
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


@pytest.mark.slow
def test_two_real_host_processes_join_one_mesh():
    """The zero-to-aha spawn: 2 OS processes x 2 emulated devices form ONE
    4-device global mesh and a cross-process psum agrees on both."""
    from stl_fusion_tpu.cluster.multihost import launch_hosts

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = launch_hosts(
        [sys.executable, "-m", "stl_fusion_tpu.cluster.multihost"],
        n_hosts=2,
        devices_per_host=2,
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
    )
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out.decode())
    assert all(p.returncode == 0 for p in procs), outs
    for i, out in enumerate(outs):
        assert f"host={i}/2" in out and "psum_ok=True" in out, out
