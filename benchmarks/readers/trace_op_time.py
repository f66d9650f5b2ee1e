"""``trace_op_time``: device time, from the profiler trace, of the device
operations of the programs matching ``program`` whose family (the HLO
instruction's name without its numbering, ``lib/trace.py::op_family``)
matches ``ops``, both regular expressions; self time, so a loop's own event
does not count its body again. ``TraceSummary.device_ops`` sums over the
device planes, so the sum is divided by the chips that ran (as
``busy_s`` and a program's time are means over them), then by a window
counter, times ``scale``. No trace, no such operation in it, or a counter
at zero: no value.

args: ``{"program": regex, "ops": regex, "per": counter name, "scale": number}``.
"""
import re


def op_seconds(device_ops, program: str, ops: str) -> float:
    """Seconds of the ``("<program>:<family>", seconds)`` entries whose two
    halves match the two expressions."""
    prog_rx, ops_rx = re.compile(program), re.compile(ops)
    total = 0.0
    for key, seconds in device_ops:
        owner, _, family = key.partition(":")
        if prog_rx.search(owner) and ops_rx.search(family):
            total += seconds
    return total


def read(args, ctx):
    trace = ctx.m.trace
    if trace is None:
        return None
    seconds = op_seconds(trace.device_ops, args["program"], args["ops"])
    per = ctx.m.counters.get(args["per"], 0)
    if seconds <= 0 or per <= 0 or trace.devices_busy <= 0:
        return None
    return seconds / trace.devices_busy / per * args.get("scale", 1.0)
