"""``roofline``: the least time the chip could take for one unit of work,
over the device time the trace shows for it, in percent.

The work is counted from the configuration's shapes only, never from the
implementation: ``bytes`` lists terms ``{"count": "nodes"|"edges",
"row_words_times": a, "plus": b}``, each ``count * (row_words * a + b)``
bytes per unit (``edges`` is what the benchmark's generator made for this
seed). With ``flops`` absent the bound is memory: bytes over the chip's
published HBM bandwidth (``lib/device.py``).

args: ``{"bytes": [...], "program": regex, "per": counter name}``.
"""
from readers import trace_program_time


def work_bytes(terms, sizes: dict) -> float:
    return float(sum(
        sizes[t["count"]] * (sizes["row_words"] * t["row_words_times"] + t["plus"])
        for t in terms
    ))


def read(args, ctx):
    if not ctx.peaks:
        return None  # a rehearsal has no chip and so no peak
    seconds = trace_program_time.read(
        {"program": args["program"], "per": args["per"]}, ctx
    )
    if seconds is None:
        return None
    sizes = {
        "nodes": ctx.size("nodes"),
        "edges": ctx.m.values["edges"],
        "row_words": ctx.size("row_words"),
    }
    least = work_bytes(args["bytes"], sizes) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
