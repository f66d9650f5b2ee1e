#!/usr/bin/env python
"""ComputedPerformanceTest port — memoized read throughput, Fusion on/off.

Mirrors the reference's only published benchmark
(tests/Stl.Fusion.Tests/PerformanceTest.cs:32-144, results in
docs/performance-test-results/): N concurrent readers issue random
`users.get(id)` calls over 1000 users against a sqlite DAL while one mutator
does a read-modify-write every 10 ms. Three modes:

- ``fusion``     — the scalar `@compute_method` path (one node per key);
- ``none``       — no memoization, every read hits sqlite (the reference's
                   "without Stl.Fusion" rows);
- ``vectorized`` — the TPU-first path through the PUBLIC service API: the
                   service declares ``@compute_method(table=TableBacking)``
                   and readers call ``memo_table_of(users.get).read_batch``;
                   the mutator is the ordinary scalar command path, whose
                   ``invalidating()`` replay transparently marks table rows
                   stale. Each element read counts as one op, matching the
                   reference's per-read accounting.

Run: python perf/read_throughput.py [--quick] [--workers N]
``--workers N`` additionally runs the scalar bench as N OS processes
sharing the sqlite DAL — the thread-parity comparison to the reference's
multi-threaded runs (one asyncio loop ≈ one thread).
Prints one line per mode + a JSON summary; committed numbers live in PERF.md.
"""
import argparse
import asyncio
import json
import os
import random
import sqlite3
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    compute_method,
    invalidating,
    memo_table_of,
)

USER_COUNT = 1000


def make_db(path: str) -> None:
    db = sqlite3.connect(path)
    db.execute("CREATE TABLE users (id INTEGER PRIMARY KEY, name TEXT, email TEXT)")
    db.executemany(
        "INSERT INTO users VALUES (?, ?, ?)",
        [(i, f"user{i}", f"{i}@example.com") for i in range(USER_COUNT)],
    )
    db.commit()
    db.close()


class UserDal:
    """The sqlite DAL both services share (≈ the EF DbContext)."""

    def __init__(self, path: str):
        self.db = sqlite3.connect(path)
        self.reads = 0

    def get(self, uid: int):
        self.reads += 1
        row = self.db.execute("SELECT id, name, email FROM users WHERE id=?", (uid,)).fetchone()
        return {"id": row[0], "name": row[1], "email": row[2]} if row else None

    def get_many(self, ids: np.ndarray):
        self.reads += len(ids)
        marks = ",".join("?" * len(ids))
        rows = self.db.execute(
            f"SELECT id, email FROM users WHERE id IN ({marks})", [int(i) for i in ids]
        ).fetchall()
        by_id = {r[0]: r for r in rows}
        # numeric projection for the device table: (id, len(email)) per row
        return np.array([[i, len(by_id[int(i)][1])] for i in ids], dtype=np.float32)

    def update_email(self, uid: int, email: str) -> None:
        self.db.execute("UPDATE users SET email=? WHERE id=?", (email, uid))
        self.db.commit()


class FusionUserService(ComputeService):
    """≈ UserService with [ComputeMethod] Get (the "with Stl.Fusion" rows).
    The ``table=`` backing adds the columnar read path WITHOUT changing the
    service's API: scalar gets keep per-key nodes, bulk reads ride
    ``memo_table_of(svc.get).read_batch`` refreshed through ``get_rows``."""

    def __init__(self, dal: UserDal, hub=None):
        super().__init__(hub)
        self.dal = dal

    def get_rows(self, ids: np.ndarray) -> np.ndarray:
        return self.dal.get_many(ids)

    @compute_method(table=TableBacking(rows=USER_COUNT, batch="get_rows", row_shape=(2,)))
    async def get(self, uid: int):
        return self.dal.get(uid)

    async def update_email(self, uid: int, email: str) -> None:
        self.dal.update_email(uid, email)
        with invalidating():
            await self.get(uid)


class PlainUserService:
    """No memoization — every read is a DB hit."""

    def __init__(self, dal: UserDal):
        self.dal = dal

    async def get(self, uid: int):
        return self.dal.get(uid)

    async def update_email(self, uid: int, email: str) -> None:
        self.dal.update_email(uid, email)


def make_mutator(service, stop, read_first: bool = False):
    """The shared 10 ms read-modify-write mutator every mode churns with
    (one definition: the fence cadence PERF keys off must not diverge)."""

    async def mutator():
        rnd = random.Random(1)
        count = 0
        while not stop.is_set():
            uid = rnd.randrange(USER_COUNT)
            if read_first:
                user = await service.get(uid)
                assert user is not None
            count += 1
            await service.update_email(uid, f"{count}@counter.org")
            try:
                await asyncio.wait_for(stop.wait(), 0.01)
            except asyncio.TimeoutError:
                pass

    return mutator


async def run_scalar_hot(service, readers: int, iterations: int):
    """Harness-minimal scalar loop: PRECOMPUTED uid sequence (no per-op
    randrange — ~0.6 µs/op of pure-python harness in the parity loop above
    masks the framework's own hit cost), mutator still churning. This row
    measures the FRAMEWORK's memoized-hit path; the parity row keeps the
    reference's loop shape for comparability."""
    stop = asyncio.Event()
    ids = [(i * 7919) % USER_COUNT for i in range(min(iterations, 100_000))]
    mutator = make_mutator(service, stop)

    async def reader(count: int) -> int:
        ok = 0
        loops = count // len(ids)
        for _ in range(max(loops, 1)):
            for uid in ids:
                user = await service.get(uid)
                if user is not None:
                    ok += 1
        return ok

    for i in range(USER_COUNT):  # warm every key
        await service.get(i)
    m = asyncio.ensure_future(mutator())
    t0 = time.perf_counter()
    counts = await asyncio.gather(*[reader(iterations) for _ in range(readers)])
    dt = time.perf_counter() - t0
    stop.set()
    await m
    return sum(counts), dt


async def run_scalar(service, readers: int, iterations: int, mutate: bool,
                     mutator_service=None):
    """The reference's Test() body: N readers + 1 mutator.
    ``mutator_service`` lets the mutator run against a different surface
    than the readers (the RPC-client mode reads through the client proxy
    while writes land on the server service)."""
    mut_svc = mutator_service or service
    stop = asyncio.Event()
    mutator = make_mutator(mut_svc, stop, read_first=True)

    async def reader(n: int, count: int) -> int:
        rnd = random.Random(n)
        ok = 0
        for _ in range(count):
            uid = rnd.randrange(USER_COUNT)
            user = await service.get(uid)
            if user is not None and user["id"] == uid:
                ok += 1
        return ok

    # warmup (the reference runs iterations/4 first)
    warm = max(iterations // 4, 1)
    await asyncio.gather(*(reader(100 + i, warm) for i in range(readers)))

    mut = asyncio.ensure_future(mutator()) if mutate else None
    t0 = time.perf_counter()
    results = await asyncio.gather(*(reader(i, iterations) for i in range(readers)))
    elapsed = time.perf_counter() - t0
    stop.set()
    if mut:
        await mut
    assert all(r == iterations for r in results)
    return readers * iterations, elapsed


async def run_vectorized(service: FusionUserService, readers: int, iterations: int,
                         batch: int, mutate: bool, device_ids: bool = False):
    """Same workload, columnar — ALL through the public service API: bulk
    reads via the table behind ``@compute_method(table=...)``; the mutator
    is the ordinary scalar write path, whose ``invalidating()`` replay
    transparently marks the stale table row.

    ``device_ids=True`` is the TPU-native reader shape: id batches are
    drawn ON DEVICE (jax PRNG) and never cross the host boundary, so the
    read loop is pure async dispatch (host-id batches pay a ~1 MB
    host→device upload per call — transfer-bound, not read-bound)."""
    table = memo_table_of(service.get)
    table.read_batch(np.arange(USER_COUNT))  # warm table + compile
    stop = asyncio.Event()

    async def mutator():
        rnd = random.Random(1)
        count = 0
        while not stop.is_set():
            uid = rnd.randrange(USER_COUNT)
            count += 1
            await service.update_email(uid, f"{count}@counter.org")
            try:
                await asyncio.wait_for(stop.wait(), 0.01)
            except asyncio.TimeoutError:
                pass

    async def reader_host(n: int) -> int:
        rng = np.random.default_rng(n)
        ok = 0
        for i in range(iterations):
            ids = rng.integers(0, USER_COUNT, size=batch).astype(np.int32)
            out = table.read_batch(ids)
            ok += out.shape[0]
            if i % 8 == 0:
                await asyncio.sleep(0)  # yield so the mutator runs
        return ok

    async def reader_device(n: int) -> int:
        import jax
        import jax.numpy as jnp

        draw = jax.jit(
            lambda key: jax.random.randint(key, (batch,), 0, USER_COUNT, dtype=jnp.int32)
        )
        key = jax.random.PRNGKey(n)
        keys = jax.random.split(key, iterations)
        ok = 0
        for i in range(iterations):
            ids = draw(keys[i])          # device-resident batch
            out = table.read_batch(ids)  # public API, pure dispatch
            ok += out.shape[0]
            if i % 8 == 0:
                await asyncio.sleep(0)  # yield so the mutator runs
        return ok

    reader = reader_device if device_ids else reader_host
    await reader(100)  # warmup
    mut = asyncio.ensure_future(mutator()) if mutate else None
    t0 = time.perf_counter()
    results = await asyncio.gather(*(reader(i) for i in range(readers)))
    # one device sync so queued gathers are actually done
    np.asarray(table.read_batch([0]))
    elapsed = time.perf_counter() - t0
    stop.set()
    if mut:
        await mut
    assert all(r == iterations * batch for r in results)
    return readers * iterations * batch, elapsed


def run_device_chained(table, n_chained: int, batch: int):
    """The kernel ceiling: ``n_chained`` random-id gathers chained in ONE
    jit with a single readback — what batched reads cost once dispatch
    overhead (one host round trip per call)
    is amortized away, i.e. the reference's "Single reader, no mutators"
    row executed as a device loop."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(7)
    id_mat = jnp.asarray(rng.integers(0, table.n_rows, size=(n_chained, batch)).astype(np.int32))

    @jax.jit
    def run_all(values, id_mat):
        def body(acc, ids):
            rows = values[ids]
            return acc + rows.sum(), None

        acc, _ = lax.scan(body, jnp.float32(0), id_mat)
        return acc

    float(run_all(table.values, id_mat))  # compile + warm
    t0 = time.perf_counter()
    float(run_all(table.values, id_mat))
    elapsed = time.perf_counter() - t0
    return n_chained * batch, elapsed


async def run_rpc_client(path: str, readers: int, iterations: int, mutate: bool):
    """The distributed read path (≈ the reference's 'Fusion + serialization
    per read' row): a compute CLIENT reads users.get over the in-memory RPC
    transport. First read of a key pays the wire round trip; repeats are
    CLIENT-CACHE hits (ClientComputed stays bound until the server pushes
    an invalidation), so steady-state throughput shows what remote readers
    actually see — local-hit speed, not wire speed."""
    from stl_fusion_tpu.client import compute_client, install_compute_call_type
    from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport

    server_fusion = FusionHub()
    dal = UserDal(path)
    service = FusionUserService(dal, server_fusion)
    server_rpc = RpcHub("perf-server")
    install_compute_call_type(server_rpc)
    server_rpc.add_service("users", service)

    client_rpc = RpcHub("perf-client")
    install_compute_call_type(client_rpc)
    RpcTestTransport(client_rpc, server_rpc)
    users = compute_client("users", client_rpc, FusionHub())

    try:
        return await run_scalar(
            users, readers, iterations, mutate, mutator_service=service
        )
    finally:
        await client_rpc.stop()
        await server_rpc.stop()


async def run_rpc_vectorized(
    path: str, readers: int, iterations: int, batch: int, mutate: bool
):
    """Vectorized reads ACROSS the process boundary (VERDICT r2 #4): a
    RemoteTable client reads id batches from the served MemoTable — one RPC
    per stale batch, local gathers after that — while the ordinary scalar
    mutator invalidates rows server-side (TableBacking replay → row fence
    pushed to the client). Steady-state throughput is the remote analogue of
    the in-process vectorized row: cache-local gathers punctuated by one
    row-sized refetch per mutation."""
    from stl_fusion_tpu.client import RemoteTable, RemoteTableHost
    from stl_fusion_tpu.rpc import RpcHub
    from stl_fusion_tpu.rpc.testing import RpcTestTransport

    server_fusion = FusionHub()
    dal = UserDal(path)
    service = FusionUserService(dal, server_fusion)
    table = memo_table_of(service.get)
    server_rpc = RpcHub("perf-table-server")
    RemoteTableHost(server_rpc).expose("users", table)
    client_rpc = RpcHub("perf-table-client")
    RpcTestTransport(client_rpc, server_rpc)
    remote = RemoteTable(client_rpc, "default", "users")

    stop = asyncio.Event()

    async def mutator():
        uid = 0
        while not stop.is_set():
            await service.update_email(uid % USER_COUNT, f"m{uid}@x.com")
            uid += 1
            await asyncio.sleep(0.01)

    async def reader(n: int) -> int:
        rng = np.random.default_rng(n)
        ops = 0
        for _ in range(iterations):
            ids = rng.integers(0, USER_COUNT, size=batch)
            await remote.read_batch(ids)
            ops += batch
        return ops

    try:
        mut = asyncio.ensure_future(mutator()) if mutate else None
        t0 = time.perf_counter()
        counts = await asyncio.gather(*(reader(n) for n in range(readers)))
        dt = time.perf_counter() - t0
        if mut is not None:
            stop.set()
            await mut
        return sum(counts), dt, remote.remote_reads
    finally:
        remote.dispose()
        await client_rpc.stop()
        await server_rpc.stop()


async def run_scalar_worker(path: str, iterations: int, seed: int) -> None:
    """One OS-process worker of the multi-process scalar run: its own hub,
    its own memo cache, 4 readers + 1 mutator over the SHARED sqlite file —
    process-parity with one of the reference's reader threads."""
    random.seed(seed)
    hub = FusionHub()
    dal = UserDal(path)
    service = FusionUserService(dal, hub)
    ops, dt = await run_scalar(service, readers=4, iterations=iterations, mutate=True)
    print(json.dumps({"ops": ops, "elapsed": dt, "db_reads": dal.reads}))


def run_multi_worker_scalar(path: str, workers: int, iterations: int):
    """Spawn N scalar workers as OS processes against one sqlite DAL (the
    fair thread-parity shape: one asyncio loop ≈ one reference thread).
    Throughput = total ops / the SLOWEST worker's own measured loop time —
    interpreter startup, imports, and finish skew are not benchmark work."""
    import subprocess

    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--scalar-worker", path,
             str(iterations), str(w)],
            stdout=subprocess.PIPE, text=True,
        )
        for w in range(workers)
    ]
    total_ops, slowest = 0, 0.0
    for p in procs:
        out, _ = p.communicate(timeout=600)
        assert p.returncode == 0
        stats = json.loads(out.strip().splitlines()[-1])
        total_ops += stats["ops"]
        slowest = max(slowest, stats["elapsed"])
    return total_ops, slowest


async def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true", help="~10x fewer iterations")
    parser.add_argument("--workers", type=int, default=0,
                        help="also run the scalar bench as N OS processes")
    parser.add_argument("--scalar-worker", nargs=3, metavar=("PATH", "ITERS", "SEED"),
                        help="internal: one multi-process scalar worker")
    args = parser.parse_args()
    if args.scalar_worker:
        path, iters, seed = args.scalar_worker
        await run_scalar_worker(path, int(iters), int(seed))
        return
    scale = 10 if args.quick else 1
    from stl_fusion_tpu.graph import require_accelerator

    device = require_accelerator("perf/read_throughput.py")

    path = os.path.join(tempfile.mkdtemp(), "perf-users.sqlite")
    make_db(path)
    results = {}

    hub = FusionHub()
    dal = UserDal(path)
    fusion_users = FusionUserService(dal, hub)
    ops, dt = await run_scalar(fusion_users, readers=4, iterations=250_000 // scale, mutate=True)
    results["fusion_scalar"] = ops / dt
    print(f"fusion (scalar):        {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s, {dal.reads} DB reads)")

    ops, dt = await run_scalar_hot(fusion_users, readers=4, iterations=250_000 // scale)
    results["fusion_scalar_hot"] = ops / dt
    print(f"fusion (scalar, hot):   {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s — precomputed ids, mutator churning)")

    if args.workers:
        ops, dt = run_multi_worker_scalar(path, args.workers, 250_000 // scale)
        results["fusion_scalar_multiworker"] = ops / dt
        print(f"fusion (scalar, {args.workers} procs): {ops / dt / 1e3:10,.1f} K ops/sec  ({ops} ops, {dt:.2f}s slowest worker loop)")

    ops, dt = await run_rpc_client(path, readers=4, iterations=100_000 // scale, mutate=True)
    results["fusion_rpc_client"] = ops / dt
    print(f"fusion (rpc client):    {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s)")

    # max-churn shape: the 10ms mutator invalidates a row between ANY two
    # 65K-id batches over 1000 users, so every call pays one RPC refetch
    # (plus the server-side refresh+gather dispatches), so the row is a floor
    ops, dt, rpc_reads = await run_rpc_vectorized(
        path, readers=4, iterations=200 // scale or 1, batch=65_536, mutate=True
    )
    results["fusion_rpc_vectorized"] = ops / dt
    print(f"fusion (rpc vec):       {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s, {rpc_reads} RPC round trips)")

    # steady state between mutations: every row cached client-side, reads
    # are pure local gathers — the remote reader's hit-path ceiling
    ops, dt, rpc_reads = await run_rpc_vectorized(
        path, readers=4, iterations=400 // scale or 1, batch=65_536, mutate=False
    )
    results["fusion_rpc_vectorized_hits"] = ops / dt
    print(f"fusion (rpc vec, hits): {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s, {rpc_reads} RPC round trips)")

    dal2 = UserDal(path)
    plain_users = PlainUserService(dal2)
    ops, dt = await run_scalar(plain_users, readers=4, iterations=20_000 // scale, mutate=True)
    results["no_fusion"] = ops / dt
    print(f"without fusion:         {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s)")

    dal3 = UserDal(path)
    vec_users = FusionUserService(dal3, FusionHub())
    ops, dt = await run_vectorized(
        vec_users, readers=4, iterations=100 // scale, batch=262_144 // scale, mutate=True
    )
    results["fusion_vectorized"] = ops / dt
    print(f"fusion (vectorized):    {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s, {dal3.reads} DB reads)")

    dal4 = UserDal(path)
    dev_users = FusionUserService(dal4, FusionHub())
    ops, dt = await run_vectorized(
        dev_users, readers=4, iterations=64 // scale, batch=1_048_576 // scale,
        mutate=True, device_ids=True,
    )
    results["fusion_vectorized_device_ids"] = ops / dt
    print(f"fusion (vec, dev ids):  {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.2f}s, {dal4.reads} DB reads)")

    table = memo_table_of(vec_users.get)
    table.read_batch(np.arange(USER_COUNT))
    ops, dt = run_device_chained(table, n_chained=64, batch=1_048_576 // scale)
    results["fusion_device_chained"] = ops / dt
    print(f"fusion (device chain):  {ops / dt / 1e3:12,.1f} K ops/sec  ({ops} ops, {dt:.4f}s)")

    results["speedup_scalar_vs_none"] = results["fusion_scalar"] / results["no_fusion"]
    results["speedup_vectorized_vs_none"] = results["fusion_vectorized"] / results["no_fusion"]
    print(json.dumps(
        {**device, **{k: round(v, 1) for k, v in results.items()}}
    ))


if __name__ == "__main__":
    asyncio.run(main())
