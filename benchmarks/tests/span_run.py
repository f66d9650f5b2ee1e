"""Support for ``test_program_span.py`` and for checking a chip run by hand:
a whole run of ``run.py`` (same arguments), after whose result line one more
line says what the result line does not: how many spans the program's record
holds and, by name, how many of them and how many seconds lie inside the
window; the wall time of the benchmark's own spans (``bench:*``, for the sums the program's spans have to add up to)
and, in a traced run, where the program's ``fusion:*`` events lie in the
profiler's trace: on which planes, how many inside the ``bench:*`` event that
should enclose them on the same line, how many executions of a device
program start inside a ``fusion:`` span that should hold them (one clock),
and which of the runtime's own host events take the time inside the spans
of the blocking calls.

    python benchmarks/tests/span_run.py --workload <cell> --seed <n> --seconds <s> --trace 1
"""
import bisect
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

# outermost program span -> the benchmark span whose call opens it
ENCLOSED_BY = {
    "fusion:cascade": ("bench:lone_wave",),
    "fusion:flush": ("bench:flush",),
    "fusion:refresh": ("bench:refresh", "bench:restore"),
    "fusion:superround.stage": ("bench:stage",),
    "fusion:superround.dispatch": ("bench:dispatch",),
    "fusion:superround.apply": ("bench:harvest",),
}
# device program -> the program spans between whose start and end it runs
RUNS_INSIDE = {"jit_core": "fusion:cascade", "jit_burst": "fusion:flush.icasc"}
# program spans of one blocking call each: what the runtime's own host events
# (PjRt, XLA: the profiler records them with no Python tracer) do inside them
LOOK_INSIDE = ("fusion:lat.dispatch", "fusion:lat.readback", "fusion:topo.dispatch",
               "fusion:topo.readback")


def _inside(intervals, t) -> bool:
    """Is ``t`` inside one of the sorted, disjoint ``(start, end)``?"""
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t <= intervals[i][1]


def trace_report(path: str) -> dict:
    from jax.profiler import ProfileData

    from lib.trace import DEVICE_PLANE_PREFIX, MODULES_LINE, program_name

    host_lines, programs, inside_of = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PLANE_PREFIX):
                if line.name == MODULES_LINE:
                    for e in line.events:
                        programs.setdefault(program_name(e.name), []).append(e.start_ns)
                continue
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events if e.name.startswith(("fusion:", "bench:"))]
            if not any(n.startswith("fusion:") for n, _s, _e in events):
                continue
            host_lines.append((plane.name, line.name, events))
            targets = {t: sorted((s, e) for n, s, e in events if n == t) for t in LOOK_INSIDE}
            targets = {t: spans for t, spans in targets.items() if spans}
            for e in line.events:  # the runtime's own events on this thread's line
                if e.name.startswith("fusion:"):
                    continue
                for target, spans in targets.items():
                    if _inside(spans, e.start_ns):
                        slot = inside_of.setdefault(target, {}).setdefault(e.name, [0, 0])
                        slot[0] += 1
                        slot[1] += e.duration_ns
    report = {"planes": sorted({p for p, _l, _e in host_lines}), "events": 0,
              "enclosed": {}, "programs_inside": {}, "host_events_inside": {
                  target: [[n, c, ns / 1e9] for n, (c, ns) in
                           sorted(got.items(), key=lambda kv: -kv[1][1])[:10]]
                  for target, got in inside_of.items()}}
    spans_of: dict = {}
    for _plane, _line, events in host_lines:
        report["events"] += sum(1 for n, _s, _e in events if n.startswith("fusion:"))
        by_name: dict = {}
        for n, s, e in events:
            by_name.setdefault(n, []).append((s, e))
        for name, intervals in by_name.items():
            spans_of.setdefault(name, []).extend(intervals)
        for inner, outer in ENCLOSED_BY.items():
            if inner not in by_name:
                continue
            outers = sorted(iv for name in outer for iv in by_name.get(name, ()))
            got = report["enclosed"].setdefault(
                inner, {"in": "|".join(outer), "n": 0, "inside": 0})
            for s, e in by_name[inner]:
                got["n"] += 1
                got["inside"] += _inside(outers, s) and _inside(outers, e)
    for program, span in RUNS_INSIDE.items():
        intervals = sorted(spans_of.get(span, ()))
        starts = programs.get(program, ())
        report["programs_inside"][program] = {
            "in": span, "n": len(starts),
            "inside": sum(_inside(intervals, t) for t in starts),
        }
    return report


def main(argv) -> int:
    import run
    from lib import trace

    made, traced = [], {}

    class Ctx(run.Ctx):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    summarize = trace.summarize

    def summarize_and_report(log_dir):
        traced["report"] = trace_report(trace.find_xplane(log_dir))
        return summarize(log_dir)

    run.Ctx, trace.summarize = Ctx, summarize_and_report
    rc = run.main(argv)
    if rc == 0:
        from stl_fusion_tpu.diagnostics import tracing

        m = made[0].m
        record = getattr(tracing, "hot_spans", list)()
        lo, hi = m.window
        by_name: dict = {}
        for r in record:
            if r.start >= lo and r.end <= hi:
                got = by_name.setdefault(r.name, [0, 0.0])
                got[0] += 1
                got[1] += r.end - r.start
        out = {"program_spans": len(record), "program_span_totals": {
            name: {"n": n, "seconds": seconds} for name, (n, seconds) in by_name.items()
        }, "bench_spans": {
            name: {"n": len(iv), "seconds": sum(e - s for s, e in iv)}
            for name, iv in m.spans.items()
        }, "counters": m.counters}
        if "report" in traced:
            out["trace"] = traced["report"]
        print(json.dumps(out, separators=(",", ":")), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
