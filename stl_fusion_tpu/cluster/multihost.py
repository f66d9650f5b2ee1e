"""Multi-host mesh bring-up over REAL process boundaries (ISSUE 15).

PR 9's routed mesh was oracle-exact at 80M nodes, but on 8 virtual devices
in ONE process — the "cross-host" leg never crossed a process boundary.
This module stands up the honest version: each emulated host is a separate
OS process owning its own XLA CPU device pool
(``--xla_force_host_platform_device_count``), joined into ONE global device
mesh through ``jax.distributed.initialize`` with the gloo CPU collectives
backend. A ``ppermute``/``all_to_all`` issued inside the routed wave then
moves bytes between processes — the DCN leg is exercised, not merely
counted (the MULTICHIP protocol's standing complaint).

Layout contract (what :class:`~.placement.DevicePlacement`'s host axis
leans on): ``jax.devices()`` orders the global pool process 0 first, so
host ``h`` owns the contiguous device range ``[h*dph, (h+1)*dph)`` —
:func:`init_multihost` VERIFIES this against each device's
``process_index`` instead of assuming it.

Three pieces:

- :func:`init_multihost` — called by a HOST process after import, before
  any jax computation. Reads the ``FUSION_MH_*`` env the launcher set (or
  explicit args), configures gloo + ``jax.distributed``, validates the
  device/process layout, and returns a :class:`MultiHostContext`.
  ``n_hosts=1`` short-circuits to a single-process context (no
  distributed runtime) so the same worker script runs both shapes — the
  chaos ladder's "survivor serves alone" phase is exactly that.
- :func:`launch_hosts` — called by an ORCHESTRATOR (perf driver, CI
  smoke): spawns one OS process per host with the right env
  (``XLA_FLAGS`` device emulation, coordinator address, process id) and
  returns the Popen handles. Killing one of them IS the host-kill chaos
  primitive.
- :class:`MultiHostContext` — the bring-up facts (process id, host count,
  devices per host) + helpers the routed graph and the perf workers use:
  the global mesh, member naming, host-of-device math, and a collective
  barrier for phase sequencing.

Gotcha (measured, not theoretical): setting
``jax_cpu_collectives_implementation=gloo`` WITHOUT then initializing
``jax.distributed`` breaks single-process CPU client creation on this
jax — so the gloo config is applied only on the genuinely multi-process
path.

Elastic world mechanics (ISSUE 16): ``jax.distributed.initialize`` can
run exactly once per process (it refuses after backends exist), and the
coordination service it installs is all-or-nothing — any task death
propagates a fatal error that ABORTS every survivor from inside the
error-polling agent (measured: SIGKILL a peer and the survivor dies
rc=-6 in ``PollForError`` with no Python frame on the stack). Both
properties are wrong for a mesh that must outlive its members, so this
module owns the world lifecycle directly:

- :func:`form_world` builds the coordination service (process 0) and
  client through ``xla_extension`` and installs them into jax's
  ``global_state`` — repeatable any number of times per process.
- :func:`detach_world` gracefully retires the coordination agent AFTER
  backend formation (``client.shutdown()`` is itself the cross-host
  barrier). The gloo pairs are already established peer-to-peer, so
  collectives keep running — but with no agent left polling, a later
  peer death can no longer abort the survivor. Failure detection moves
  where it belongs: :class:`~.mesh_controller.MeshController`.
- :func:`teardown_world` abandons a (possibly wedged) world in-process:
  drop the service/client refs, clear backends + jit caches, reset the
  collectives config. A dispatch thread blocked inside a wedged gloo
  collective keeps the OLD backend alive as a zombie (C++ offers no
  cancellation); the fresh world forms on new ports regardless — that
  leaked thread is the measured cost of surviving without a restart.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

__all__ = [
    "MultiHostContext",
    "init_multihost",
    "launch_hosts",
    "host_env",
    "pick_coordinator",
    "form_world",
    "detach_world",
    "teardown_world",
    "world_is_formed",
    "ENV_NUM_HOSTS",
    "ENV_PROCESS_ID",
    "ENV_COORDINATOR",
    "ENV_DEVICES_PER_HOST",
    "ENV_ASYNC_DEPTH",
    "async_depth_env",
]

ENV_NUM_HOSTS = "FUSION_MH_NUM_HOSTS"
ENV_PROCESS_ID = "FUSION_MH_PROCESS_ID"
ENV_COORDINATOR = "FUSION_MH_COORDINATOR"
ENV_DEVICES_PER_HOST = "FUSION_MH_DEVICES_PER_HOST"
#: asynchronous frontier execution across real host processes (ISSUE 17):
#: > 0 switches every routed wave a worker builds to async mode at that
#: speculation depth; 0 (default) keeps the bulk-synchronous exchange.
#: One shared parsing site so the scale / geometry / elastic workers and
#: the orchestrator can never disagree on the mode under test.
ENV_ASYNC_DEPTH = "FUSION_MH_ASYNC_DEPTH"


def async_depth_env(default: int = 0) -> int:
    """The async speculation depth this process should run routed waves
    at (``FUSION_MH_ASYNC_DEPTH``; 0 = synchronous per-level exchange).
    Every host process of a mesh must agree — the wave program is SPMD —
    which is why workers read the env rather than taking a per-call
    argument."""
    try:
        depth = int(os.environ.get(ENV_ASYNC_DEPTH, str(default)))
    except ValueError:
        return default
    return max(depth, 0)

_DEVCOUNT_FLAG = "--xla_force_host_platform_device_count"


@dataclass
class MultiHostContext:
    """One host process's view of the multi-host mesh."""

    process_id: int
    n_hosts: int
    devices_per_host: int
    coordinator: Optional[str] = None
    #: the coordination agent has been retired (detach_world): collectives
    #: still run over the established gloo pairs, but cross-host phase
    #: sequencing must come from the caller's own machinery, and shutdown
    #: is a local drop instead of a coordinated barrier
    detached: bool = False

    @property
    def n_dev(self) -> int:
        return self.n_hosts * self.devices_per_host

    @property
    def is_multiprocess(self) -> bool:
        return self.n_hosts > 1

    def host_of_device(self, dev: int) -> int:
        return dev // self.devices_per_host

    def member_names(self, prefix: str = "h") -> List[str]:
        """One cluster member per host process — the natural mapping the
        perf workers and the placement's ``mesh_members`` use."""
        return [f"{prefix}{i}" for i in range(self.n_hosts)]

    def mesh(self):
        """1-D global graph mesh over every device of every host."""
        from ..parallel.mesh import graph_mesh

        return graph_mesh()

    def sync(self, tag: str = "fusion-mh") -> None:
        """Collective barrier across every host process (no-op single
        host). Used between worker phases so asymmetric host work (the
        DCN leg's server/client split) never interleaves with a phase
        that dispatches collectives."""
        if not self.is_multiprocess:
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)

    def detach(self) -> bool:
        """Retire this host's coordination agent (see :func:`detach_world`).
        Blocks until every host calls it — the agent's shutdown barrier IS
        the cross-host synchronization point."""
        if not self.is_multiprocess or self.detached:
            return False
        self.detached = detach_world()
        return self.detached

    def shutdown(self) -> None:
        if not self.is_multiprocess:
            return
        if self.detached:
            # no agent left to coordinate a barrier through — local drop
            teardown_world(rebuild_local=False)
            return
        import jax

        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — already torn down / peer gone
            # best-effort: a chaos-killed peer can leave the coordinator
            # unreachable, and shutdown-on-exit must not mask the run's
            # real result; counted by the caller's exit path, not here
            pass


def _global_state():
    from jax._src import distributed as jdist

    return jdist.global_state


def world_is_formed() -> bool:
    """Whether a coordination client is currently installed (a DETACHED
    world reports False — its agent is gone by design)."""
    return _global_state().client is not None


def form_world(
    n_hosts: int,
    process_id: int,
    coordinator: str,
    *,
    heartbeat_interval_s: int = 2,
    max_missing_heartbeats: int = 10,
    init_timeout_s: int = 60,
    shutdown_timeout_s: int = 30,
) -> None:
    """Bring up the ``jax.distributed`` world directly (service on process
    0 + client everywhere), installing the handles into jax's
    ``global_state`` exactly as ``jax.distributed.initialize`` would —
    minus its once-per-process restriction, so a surviving process can
    re-form over a new member set after :func:`teardown_world`.

    Idempotence guard: refuses when a client is already installed —
    tear the old world down first, don't stack worlds."""
    import jax
    from jax._src.lib import xla_extension

    state = _global_state()
    if state.client is not None:
        raise RuntimeError("a coordination client is already installed; "
                           "teardown_world() before re-forming")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if process_id == 0 and state.service is None:
        port = coordinator.rsplit(":", 1)[1]
        state.service = xla_extension.get_distributed_runtime_service(
            f"[::]:{port}",
            n_hosts,
            heartbeat_interval=heartbeat_interval_s,
            max_missing_heartbeats=max_missing_heartbeats,
        )
    client = xla_extension.get_distributed_runtime_client(
        coordinator,
        process_id,
        init_timeout=init_timeout_s,
        shutdown_timeout=shutdown_timeout_s,
        heartbeat_interval=heartbeat_interval_s,
        max_missing_heartbeats=max_missing_heartbeats,
        # destruction must NEVER imply a barrier: teardown_world drops the
        # ref with the peer possibly dead, and a destructor that dials the
        # coordinator would wedge the survivor right back
        shutdown_on_destruction=False,
        use_compression=True,
    )
    client.connect()
    state.client = client
    state.process_id = process_id
    state.num_processes = n_hosts
    state.coordinator_address = coordinator


def detach_world() -> bool:
    """Gracefully retire the coordination agent AFTER world formation.

    ``client.shutdown()`` runs the coordination service's own shutdown
    barrier, so every host blocks here until all of them detach — a free
    synchronization point. Afterwards the established gloo communicators
    keep serving collectives, but no agent is left error-polling: a peer
    SIGKILL surfaces as a wedged collective (detectable, survivable)
    instead of a process abort (measured rc=-6 without this). Returns
    False when no client is installed (single-host or already detached)."""
    state = _global_state()
    if state.client is None:
        return False
    state.client.shutdown()
    state.client = None
    return True


def teardown_world(*, rebuild_local: bool = True) -> None:
    """Abandon the current world in-process: drop the coordination
    handles, clear backends and jit caches, and (by default) reset the
    collectives config so the next backend is a plain local CPU pool.

    Safe with a collective wedged on another thread: that thread keeps
    the old backend alive as an abandoned zombie (no cancellation exists
    for an in-flight gloo op), while new backends form independently on
    fresh ports. Callers re-enter :func:`form_world` afterwards — or just
    compute locally when ``rebuild_local`` left the config at ``none``."""
    import jax

    state = _global_state()
    # the dead-peer case: no graceful shutdown is possible; dropping the
    # refs is the teardown (shutdown_on_destruction=False by contract)
    state.client = None
    if state.service is not None:
        try:
            state.service.shutdown()
        except Exception:  # noqa: BLE001 — peers gone mid-barrier; the
            # service is being abandoned either way
            pass
        state.service = None
    state.preemption_sync_manager = None
    state.process_id = 0
    state.num_processes = 1  # the pristine default — the CPU backend
    # factory passes this straight through as num_nodes and rejects None
    state.coordinator_address = None
    if rebuild_local:
        # 'none' (string) is the real local implementation — Python None
        # is rejected by this jax's config validator
        jax.config.update("jax_cpu_collectives_implementation", "none")
    from jax.extend import backend as _jeb

    _jeb.clear_backends()
    jax.clear_caches()


def init_multihost(
    n_hosts: Optional[int] = None,
    process_id: Optional[int] = None,
    coordinator: Optional[str] = None,
    devices_per_host: Optional[int] = None,
) -> MultiHostContext:
    """Join (or short-circuit) the multi-host mesh from a host process.

    Arguments default from the ``FUSION_MH_*`` env :func:`launch_hosts`
    exports. Must run before the first jax computation; the XLA device
    count itself comes from ``XLA_FLAGS`` which the LAUNCHER set (it is
    baked at backend creation and cannot be set here)."""
    n_hosts = int(os.environ.get(ENV_NUM_HOSTS, "1")) if n_hosts is None else n_hosts
    process_id = (
        int(os.environ.get(ENV_PROCESS_ID, "0")) if process_id is None else process_id
    )
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    import jax

    if n_hosts > 1:
        if not coordinator:
            raise ValueError(f"multi-host init needs a coordinator ({ENV_COORDINATOR})")
        # gloo ONLY on the real multi-process path: configuring it without
        # a distributed world breaks CPU client creation outright.
        # form_world (not jax.distributed.initialize) so the SAME process
        # can tear down and re-form after a member change — the elastic
        # mesh's whole point (ISSUE 16)
        form_world(n_hosts, process_id, coordinator)
    local = jax.local_device_count()
    if devices_per_host is None:
        devices_per_host = int(os.environ.get(ENV_DEVICES_PER_HOST, str(local)))
    if local != devices_per_host:
        raise RuntimeError(
            f"host {process_id} has {local} local devices, expected "
            f"{devices_per_host} (launcher XLA_FLAGS mismatch)"
        )
    if jax.process_count() != n_hosts:
        raise RuntimeError(
            f"distributed runtime spans {jax.process_count()} processes, "
            f"expected {n_hosts}"
        )
    # the placement's host axis assumes host h == the contiguous device
    # block [h*dph, (h+1)*dph) — verify against the real process layout
    for i, d in enumerate(jax.devices()):
        if d.process_index != i // devices_per_host:
            raise RuntimeError(
                f"global device {i} belongs to process {d.process_index}, "
                f"host-axis contract expects {i // devices_per_host}"
            )
    from ..diagnostics.metrics import global_metrics

    reg = global_metrics()
    g = reg.gauge(
        "fusion_mesh_hosts",
        help="host processes joined into the global device mesh",
    )
    g.set(n_hosts)
    reg.set_aggregation("fusion_mesh_hosts", "max")
    return MultiHostContext(
        process_id=process_id,
        n_hosts=n_hosts,
        devices_per_host=devices_per_host,
        coordinator=coordinator,
    )


def pick_coordinator(host: str = "127.0.0.1") -> str:
    """A free coordinator address on this machine (bind-then-release; the
    distributed service binds it again moments later)."""
    with socket.socket() as s:
        s.bind((host, 0))
        return f"{host}:{s.getsockname()[1]}"


def _with_device_count(xla_flags: str, devices_per_host: int) -> str:
    kept = [
        f for f in xla_flags.split() if not f.startswith(_DEVCOUNT_FLAG + "=")
    ]
    kept.append(f"{_DEVCOUNT_FLAG}={devices_per_host}")
    return " ".join(kept)


def host_env(
    n_hosts: int,
    process_id: int,
    coordinator: str,
    devices_per_host: int,
    base_env: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The child env for one emulated host process: the parent environment
    with the mesh vars overridden and the platform pinned to the CPU (the
    emulated hosts are CPU pools by contract; JAX_PLATFORMS is what makes
    them so)."""
    env = dict(base_env if base_env is not None else os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _with_device_count(env.get("XLA_FLAGS", ""), devices_per_host)
    env[ENV_NUM_HOSTS] = str(n_hosts)
    env[ENV_PROCESS_ID] = str(process_id)
    env[ENV_COORDINATOR] = coordinator
    env[ENV_DEVICES_PER_HOST] = str(devices_per_host)
    env.setdefault("PYTHONUNBUFFERED", "1")
    return env


def launch_hosts(
    argv: Sequence[str],
    n_hosts: int,
    devices_per_host: int,
    coordinator: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
    stdout=None,
    stderr=None,
) -> List[subprocess.Popen]:
    """Spawn ``n_hosts`` OS processes running ``argv`` (typically
    ``[sys.executable, worker_script, ...]``), each configured as one
    emulated host of the shared mesh. The caller owns the handles —
    ``procs[i].kill()`` is the host-kill chaos primitive, ``wait()`` the
    join. ``stdout``/``stderr`` apply to every child (default: inherit,
    so worker gate output lands in the orchestrator's log)."""
    coordinator = coordinator or pick_coordinator()
    procs: List[subprocess.Popen] = []
    for i in range(n_hosts):
        procs.append(
            subprocess.Popen(
                list(argv),
                env=host_env(n_hosts, i, coordinator, devices_per_host, base_env=env),
                stdout=stdout,
                stderr=stderr,
            )
        )
    return procs


if __name__ == "__main__":  # tiny self-check harness (used by tests)
    ctx = init_multihost()
    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import GRAPH_AXIS, shard_map_compat

    mesh = ctx.mesh()
    sh = NamedSharding(mesh, P(GRAPH_AXIS))
    x = jax.device_put(np.arange(ctx.n_dev * 8, dtype=np.int32), sh)

    @jax.jit
    def f(x):
        @shard_map_compat(mesh=mesh, in_specs=(P(GRAPH_AXIS),), out_specs=P(GRAPH_AXIS))
        def inner(xl):
            return xl + lax.psum(xl.sum(), GRAPH_AXIS)

        return inner(x)

    y = f(x)
    total = int(np.asarray(ctx.n_dev * 8 * (ctx.n_dev * 8 - 1) // 2))
    got = np.asarray(y.addressable_shards[0].data)
    want = np.asarray(x.addressable_shards[0].data) + total
    ok = bool(np.array_equal(got, want))
    print(
        f"multihost-selfcheck host={ctx.process_id}/{ctx.n_hosts} "
        f"dph={ctx.devices_per_host} psum_ok={ok}",
        flush=True,
    )
    ctx.shutdown()
    sys.exit(0 if ok else 1)
