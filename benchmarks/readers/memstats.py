"""``memstats``: the device's peak bytes in use, read after the window.

args: none.
"""


def read(args, ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return float(peak) if peak else None
