"""``trace_idle``: the seconds, from the profiler trace, in which the device
ran nothing while the host was inside the named benchmark spans (the
innermost span wins where they nest), divided by a window counter, times
``scale``. What a span holds of device work is so left out: only the time
the chip waited on the host is counted. No trace: no value.

args: ``{"spans": [names], "per": counter name, "scale": number}``.
"""


def read(args, ctx):
    trace = ctx.m.trace
    per = ctx.m.counters.get(args["per"], 0)
    if trace is None or per <= 0:
        return None
    gaps = dict(trace.idle_gaps)
    idle = sum(gaps.get(s, 0.0) for s in args["spans"])
    return idle / per * args.get("scale", 1.0)
