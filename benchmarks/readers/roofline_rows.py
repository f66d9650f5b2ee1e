"""``roofline_rows``: the least time the chip could take for the rows a
sparse refresh recomputed in the window, over the device time the trace shows
for its program, in percent.

The work is counted from the configuration's shapes and a counter of rows,
never from the implementation. A DERIVED row reads ``lines`` line slots of
``per_line`` bytes each and moves ``plus`` bytes more (its value written, its
word of the invalid array); a WRITTEN row (``written_rows_per`` names the
counter that says how many the window refreshed: one a command) moves
``written_row_bytes``. The bound is memory: bytes over the chip's published
HBM bandwidth (``lib/device.py``). Nothing to read (no trace, a program
without the refresh, no rows): no value.

args: ``{"program": regex, "rows": counter, "written_rows_per": counter,
"derived_row_bytes": {"lines", "per_line", "plus"}, "written_row_bytes": n}``.
"""


def work_bytes(args: dict, rows: float, written: float) -> float:
    d = args["derived_row_bytes"]
    derived = max(rows - written, 0.0)
    return derived * (d["lines"] * d["per_line"] + d["plus"]) + written * args["written_row_bytes"]


def read(args, ctx):
    trace = ctx.m.trace
    if trace is None or not ctx.peaks:
        return None  # a rehearsal has no chip and so no peak
    seconds, _runs = trace.program_time(args["program"])
    rows = ctx.m.counters.get(args["rows"], 0)
    if seconds <= 0 or rows <= 0:
        return None
    written = ctx.m.counters.get(args["written_rows_per"], 0)
    least = work_bytes(args, rows, written) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
