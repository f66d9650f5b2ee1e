"""``roofline_mesh``: ``roofline`` for a program that runs across the cell's
chips: the least time is the unit's bytes over ``chips x`` the chip's
published HBM bandwidth (every chip streams its own shard at once), against
the program's device time per unit, which ``trace_program_time`` gives as
the mean over the chips that ran. The bytes are counted from the
configuration's shapes only (``readers/roofline.py::work_bytes``), never
from the implementation.

args: ``{"bytes": [...], "program": regex, "per": counter name}``.
"""
from readers import roofline, trace_program_time


def least_seconds(terms, sizes: dict, chips: int, hbm_bytes_per_s: float) -> float:
    return roofline.work_bytes(terms, sizes) / (chips * hbm_bytes_per_s)


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
    least = least_seconds(
        args["bytes"], sizes, int(ctx.cell["chips"]), ctx.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds
