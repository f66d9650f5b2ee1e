"""``value``: a reading the set-up took (``Measurements.values``): the spans
of the build, or ``program_warm_s``, the sum of the program's
``program_warm_report()`` warm seconds.

args: ``{"value": name}``.
"""


def read(args, ctx):
    value = ctx.m.values.get(args["value"])
    return float(value) if value is not None else None
