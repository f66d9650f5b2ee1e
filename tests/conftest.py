"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh BEFORE jax is imported anywhere,
so sharding/collective tests exercise real SPMD partitioning without TPU
hardware (the bench + driver run on the real chip separately).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests always run the virtual CPU mesh
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _isolate_span_state():
    """Tracing and the flight recorder keep module-level state (the
    recent-span ring + listener list, the lifecycle-event ring) that would
    otherwise LEAK across tests: a span recorded by one test shows up in
    the next test's ``recent_spans()``, a listener a test forgot to remove
    fires forever, and one test's invalidation events pollute the next
    test's ``explain()``. Clear both rings and snapshot/restore the
    listeners + recorder gate around every test (ISSUE 3/4 satellites); the
    hot-path span record and its switch likewise."""
    from stl_fusion_tpu.diagnostics import tracing
    from stl_fusion_tpu.diagnostics.flight_recorder import RECORDER
    from stl_fusion_tpu.diagnostics.mesh_telemetry import global_mesh_trace

    trace_store = global_mesh_trace()
    tracing.clear_recent()
    tracing.clear_hot_spans()
    RECORDER.clear()
    trace_store.clear()
    listeners_before = list(tracing._listeners)
    recorder_enabled_before = RECORDER.enabled
    trace_enabled_before = trace_store.enabled
    yield
    tracing._listeners[:] = listeners_before
    tracing.clear_recent()
    tracing.disable_hot_spans()
    tracing.clear_hot_spans()
    RECORDER.enabled = recorder_enabled_before
    RECORDER.clear()
    trace_store.enabled = trace_enabled_before
    trace_store.clear()


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on a fresh event loop (no pytest-asyncio here)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None
