"""``counter_delta``: a program counter's change over the window, divided by
another's (or by 1), times ``scale``.

args: ``{"counter": name, "per": name or null, "scale": number}``.
"""


def read(args, ctx):
    counters = ctx.m.counters
    if args["counter"] not in counters:
        return None
    value = counters[args["counter"]]
    if args.get("per"):
        per = counters.get(args["per"], 0)
        if per <= 0:
            return None
        value = value / per
    return value * args.get("scale", 1.0)
