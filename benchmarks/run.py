#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. It finds everything by name from ``BENCHMARK.json``
at the root of the checkout: the cell's configuration
(``configs/<config>.json``, which names a builder under ``deployments/``), its
traffic mix (``traffic/<mix>.json``, which names a driver under ``drivers/``)
and, in a traced run, each per-layer metric (``layer_metrics/<metric>.json``,
which names a reader under ``readers/``). A new configuration, a new mix for
an existing driver or a new metric over an existing reader is new files plus
manifest entries; no file that is there is edited.

A run: build the deployment from ``--seed`` and warm every program the
window will use (``setup_s``: process start to the first measured operation),
measure for ``--seconds``, read the device's memory peak, then hold what the
window produced against the plain reference (``lib/hostgraph.py``). The last
line of standard output is the result. Without a TPU, or with fewer chips than
the cell asks for, the run exits 2 and prints no result. ``--cpu-rehearsal``
is the explicit tiny CPU run (it says ``"platform": "cpu"``; its numbers are
no device numbers). ``--control 1`` also reads the cell's control, the
reference under one broken guarantee, and says whether it came out incorrect.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")


class Ctx:
    """What one run hands to its deployment, driver and readers."""

    def __init__(self, args, manifest, cell, config, traffic):
        from lib.measure import Measurements

        self.args = args
        self.manifest = manifest
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.traced = bool(args.trace)
        self.rehearsal = bool(args.cpu_rehearsal)
        self.m = Measurements()
        self.device: dict = {}
        self.peaks: dict = {}

    def size(self, key: str):
        """A size of the configuration; the rehearsal's own where it has one."""
        if self.rehearsal and key in self.config.get("rehearsal", {}):
            return self.config["rehearsal"][key]
        return self.config["sizes"][key]

    def param(self, key: str, default=None):
        """A parameter of the traffic mix; the rehearsal's own where it has one."""
        if self.rehearsal and key in self.traffic.get("rehearsal", {}):
            return self.traffic["rehearsal"][key]
        return self.traffic["params"].get(key, default)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    return importlib.import_module(f"{kind}.{name}")


def applies(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="explicit tiny CPU run; prints \"platform\": \"cpu\"")
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="also read the cell's control (never part of a check)")
    return p.parse_args(argv)


async def run(ctx: Ctx, driver_mod, deployment_mod):
    from lib import device as devlib
    from lib.result import compared_ok, note

    m = ctx.m
    dep = await deployment_mod.build(ctx)
    driver = driver_mod.Driver(ctx, dep)
    try:
        await driver.setup()
        from stl_fusion_tpu.graph.program_cache import program_warm_report

        m.values["program_warm_s"] = float(
            sum(w["warm_s"] for w in program_warm_report().values())
        )
        gc.collect()
        gc.freeze()  # the window allocates little; what set-up left is not rescanned
        m.clear_window()
        trace_dir = None
        if ctx.traced:
            import jax

            trace_dir = os.path.join(TRACE_ROOT, f"{ctx.cell['name']}-{ctx.seed}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            os.makedirs(trace_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        before = driver.counters()
        setup_s = time.perf_counter() - T_START
        note(f"set-up took {setup_s:.1f}s; measuring for {ctx.seconds:g}s")
        t0 = time.perf_counter()
        try:
            with m.span("window"):
                await driver.window(ctx.seconds)
        finally:
            m.window = (t0, time.perf_counter())
            if trace_dir is not None:
                import jax

                jax.profiler.stop_trace()
        after = driver.counters()
        m.counters = {k: after[k] - before.get(k, 0) for k in after}
        m._recording = False
        ctx.device["memory_peak_bytes"] = devlib.memory_peak_bytes(ctx.cell["chips"])
        note(f"window closed after {m.window[1] - m.window[0]:.2f}s; comparing "
             "with the plain reference")
        t0 = time.perf_counter()
        compared = await driver.check()
        m.values["reference_s"] = time.perf_counter() - t0
        control = None
        if ctx.args.control:
            control = {}
            for kind in driver.CONTROLS:
                got = driver.control(kind)
                control[kind] = {
                    "correct": compared_ok(got),
                    "compared": {c["name"]: [c["value"], c["limit"]] for c in got},
                }
                note(f"control {kind}: {control[kind]}")
        if trace_dir is not None:
            from lib.trace import summarize

            t0 = time.perf_counter()
            try:
                m.trace = summarize(trace_dir)
            except ValueError:
                if not ctx.rehearsal:  # a CPU trace has no device plane
                    raise
            m.values["trace_read_s"] = time.perf_counter() - t0
            shutil.rmtree(trace_dir, ignore_errors=True)
        return driver, setup_s, compared, control
    finally:
        await driver.close()
        await deployment_mod.close(dep)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"# benchmark: no workload {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 3
    cell = cells[args.workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the flag is the explicit request
    from lib import device as devlib
    from lib.result import compared_ok, note, print_result

    ctx = Ctx(args, manifest, cell, config, traffic)
    ctx.device = devlib.device_record(cell["chips"], ctx.rehearsal)
    if not ctx.rehearsal:
        ctx.peaks = devlib.peaks_for(ctx.device["kind"])
    sys.path.insert(0, ROOT)
    try:
        import jax
        from stl_fusion_tpu.graph import enable_program_cache
    except ImportError as e:
        print(f"# benchmark: the system under test is not in this checkout ({e})",
              file=sys.stderr)
        return 3
    cache = enable_program_cache()  # JAX_COMPILATION_CACHE_DIR, else <root>/.jax_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    note(f"{cell['name']} seed {ctx.seed} on {ctx.device}; compile cache "
         f"{cache['jax_cache_dir']}")

    deployment_mod = load_module("deployments", config["deployment"])
    driver_mod = load_module("drivers", traffic["driver"])
    driver, setup_s, compared, control = asyncio.run(
        run(ctx, driver_mod, deployment_mod)
    )

    m = ctx.m
    e2e = dict(driver.end_to_end())
    e2e["setup_s"] = setup_s
    metrics = {}
    if ctx.traced:
        for entry in manifest["per_layer"]:
            if entry["moves"] not in e2e or not applies(entry, cell["name"]):
                continue
            spec = load_json("layer_metrics", entry["name"] + ".json")
            value = load_module("readers", spec["reader"]).read(spec.get("args", {}), ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        if m.trace is not None:
            ctx.device["busy_s"] = m.trace.busy_s
            ctx.device["window_s"] = m.trace.window_s
    else:
        for entry in manifest["end_to_end"]:
            if applies(entry, cell["name"]) and entry["name"] in e2e:
                metrics[entry["name"]] = {"value": e2e[entry["name"]], "unit": entry["unit"]}
    extra = {
        "workload": cell["name"], "seed": ctx.seed,
        "window_s": m.window[1] - m.window[0],
        "reference_s": m.values.get("reference_s"),
        "notes": driver.notes(),
    }
    if ctx.traced:
        extra["traced_end_to_end"] = e2e
        extra["trace_read_s"] = m.values.get("trace_read_s")
    if control is not None:
        extra["control"] = control
    print_result(
        correct=compared_ok(compared),
        attempted=driver.attempted, failed=driver.failed,
        metrics=metrics, device=ctx.device, compared=compared,
        breakdown=m.trace.breakdown() if m.trace is not None else None, extra=extra,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
