"""``trace_program_time``: device time, from the profiler trace, of the
jitted programs whose name matches ``program`` (a regular expression),
divided by a window counter (or by the program's own executions in the
traced window where ``per`` is ``"runs"``), times ``scale``. The traced
window is the measured window, so the counter and the trace cover the same
work. Nothing to read (no trace, or the program never ran): no value.

args: ``{"program": regex, "per": counter name or "runs", "scale": number}``.
"""


def read(args, ctx):
    trace = ctx.m.trace
    if trace is None:
        return None
    seconds, runs = trace.program_time(args["program"])
    if seconds <= 0:
        return None
    per = runs if args["per"] == "runs" else ctx.m.counters.get(args["per"], 0)
    if per <= 0:
        return None
    return seconds / per * args.get("scale", 1.0)
