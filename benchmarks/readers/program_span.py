"""``program_span``: seconds or counts of the program's own hot-path spans
(``stl_fusion_tpu/diagnostics/tracing.py``: ``hot_span`` sites in the live
loop, recording only while a profiler trace is taken), inside the measured
window, divided by a window counter, times ``scale``.

The program's record comes through its one accessor (``hot_spans()``); span
times and ``ctx.m.window`` are both ``time.perf_counter``. A term sums, over
the spans it names that lie inside the window, their seconds (``"seconds"``),
their self seconds (``"self"``: duration less what their child spans cover)
or their number (``"count"``); ``within`` keeps only spans with an ancestor
of that name, ``outside`` only those with none. A name ending in ``*``
matches by prefix. The value is the ``add`` terms less the ``subtract``
terms. Nothing recorded (an untraced run, or a program with no such spans):
no value. A record that filled up may have lost the window's start: no value.

args: ``{"add": [term], "subtract": [term], "per": counter name,
"scale": number}``, term: ``{"spans": [names], "stat": "seconds" | "self" |
"count", "within": name, "outside": name}``.
"""


class SpanIndex:
    """The spans of one record that lie inside ``window``, with what each
    one's children cover and its chain of ancestors' names."""

    def __init__(self, record, window):
        lo, hi = window
        self.spans = [r for r in record if r.start >= lo and r.end <= hi]
        self._by_id = {r.span_id: r for r in self.spans}
        self._covered: dict = {}
        for r in self.spans:
            if r.parent_id in self._by_id:
                self._covered[r.parent_id] = (
                    self._covered.get(r.parent_id, 0.0) + (r.end - r.start)
                )
        self._ancestors: dict = {}

    def ancestors(self, r) -> frozenset:
        got = self._ancestors.get(r.span_id)
        if got is None:
            parent = self._by_id.get(r.parent_id)
            got = (
                frozenset() if parent is None
                else self.ancestors(parent) | {parent.name}
            )
            self._ancestors[r.span_id] = got
        return got

    def term(self, term: dict) -> float:
        exact = {n for n in term["spans"] if not n.endswith("*")}
        prefixes = tuple(n[:-1] for n in term["spans"] if n.endswith("*"))
        stat = term.get("stat", "seconds")
        if stat not in ("seconds", "self", "count"):
            raise ValueError(f"program_span: no stat {stat!r}")
        within, outside = term.get("within"), term.get("outside")
        total = 0.0
        for r in self.spans:
            if r.name not in exact and not (prefixes and r.name.startswith(prefixes)):
                continue
            if within is not None or outside is not None:
                up = self.ancestors(r)
                if (within is not None and within not in up) or outside in up:
                    continue
            if stat == "count":
                total += 1
            elif stat == "self":
                total += (r.end - r.start) - self._covered.get(r.span_id, 0.0)
            else:
                total += r.end - r.start
        return total


def compute(args: dict, index: SpanIndex, per: float):
    if not index.spans or per <= 0:
        return None
    value = sum(index.term(t) for t in args["add"])
    value -= sum(index.term(t) for t in args.get("subtract", ()))
    return value / per * args.get("scale", 1.0)


def _index(ctx):
    """The window's index, made once a run (``False``: nothing to read)."""
    index = getattr(ctx, "_program_span_index", None)
    if index is None:
        index = False
        try:
            from stl_fusion_tpu.diagnostics import tracing
        except ImportError:
            tracing = None
        accessor = getattr(tracing, "hot_spans", None)
        if accessor is not None:
            record = accessor()
            if record and len(record) < tracing.HOT_RECORD_CAP:
                index = SpanIndex(record, ctx.m.window)
        ctx._program_span_index = index
    return index


def read(args, ctx):
    index = _index(ctx)
    if not index:
        return None
    return compute(args, index, ctx.m.counters.get(args["per"], 0))
