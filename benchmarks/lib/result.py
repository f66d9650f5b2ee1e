"""The last lines of a run: each number compared beside its limit on standard
error, then the one JSON object the driver reads on standard output."""
from __future__ import annotations

import json
import sys


def note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def compared_ok(compared: list) -> bool:
    """Every number compared is a count of divergences or a gap: it has to
    be at or under its limit."""
    return all(c["value"] <= c["limit"] for c in compared)


def print_result(correct, attempted, failed, metrics, device, compared,
                 breakdown=None, extra=None) -> None:
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if extra:
        line.update(extra)
    line["compared"] = {c["name"]: [c["value"], c["limit"]] for c in compared}
    sys.stdout.flush()
    for c in compared:
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {c['name']}: {c['value']} limit {c['limit']} {verdict}",
              file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line, separators=(",", ":")), flush=True)
