"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

What it computes, per traced window:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the device planes that show any;
- ``window_s``: the length of the window, which is the ``bench:window``
  annotation's interval where the trace has one;
- per-program device time and executions (the ``XLA Modules`` line: one
  event per execution of a jitted program, named ``jit_<name>(<hash>)``);
- the device operations that took most time, named ``<program>:<op>``;
- the idle gaps of the device, each attributed to the benchmark's own
  annotation (``bench:<name>``) that the host was inside at the time.

The reduction works on a plain form (``load_xplane`` makes it from the file
with nothing but JAX), so that it can be checked on a recorded trace.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench:"
WINDOW_ANNOTATION = "bench:window"
_HASH_SUFFIX = re.compile(r"\(\d+\)$")
_DIGITS = re.compile(r"[.\d]+")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str, device_prefix: str = DEVICE_PLANE_PREFIX) -> list:
    """``[{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]``
    holding the device planes' op and module lines and the host events that
    are benchmark annotations: all the reduction reads."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_device = plane.name.startswith(device_prefix)
        lines = []
        for line in plane.lines:
            if is_device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                ]
            else:
                events = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)
                ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def program_name(module_event_name: str) -> str:
    """``jit_prog(1234567)`` -> ``jit_prog``."""
    return _HASH_SUFFIX.sub("", module_event_name)


def op_family(op_name: str) -> str:
    """``fusion.123`` -> ``fusion``, ``%copy.4 = ...`` -> ``copy``: the op's
    name without its numbering, so that one kernel's instances add up."""
    head = op_name.strip().lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    parts = [p for p in head.split(".") if p and not _DIGITS.fullmatch(p)]
    return ".".join(parts) or head


def _innermost_segments(annotations) -> list:
    """Sorted, non-overlapping ``(start, end, name)`` segments, each named by
    the innermost annotation open at that time."""
    out = []
    stack = []  # (end, name)
    cursor = None

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for name, s, e in sorted(annotations, key=lambda a: (a[1], -a[2])):
        if cursor is None:
            cursor = s
        close_until(s)
        if stack and s > cursor:
            out.append((cursor, s, stack[-1][1]))
        cursor = max(cursor, s)
        stack.append((e, name))
    close_until(float("inf"))
    return out


class TraceSummary:
    def __init__(self, planes: list, device_prefix: str = DEVICE_PLANE_PREFIX):
        device = [p for p in planes if p["name"].startswith(device_prefix)]
        host = [p for p in planes if not p["name"].startswith(device_prefix)]
        if not device:
            raise ValueError(
                f"the trace has no plane named {device_prefix}*: no operation "
                "ran on a device while it was taken"
            )
        annotations = []  # (name, start, end) of bench:* host spans
        for plane in host:
            for line in plane["lines"]:
                for name, s, d in line["events"]:
                    if name.startswith(ANNOTATION_PREFIX):
                        annotations.append((name, s, s + d))
        windows = [a for a in annotations if a[0] == WINDOW_ANNOTATION]
        ops_all = [
            (s, s + d)
            for p in device for ln in p["lines"] if ln["name"] == OPS_LINE
            for _n, s, d in ln["events"]
        ]
        if windows:
            lo = min(w[1] for w in windows)
            hi = max(w[2] for w in windows)
        elif ops_all:
            lo = min(s for s, _ in ops_all)
            hi = max(e for _, e in ops_all)
        else:
            raise ValueError("the trace holds no device operation")
        self.window_ns = (lo, hi)
        self.window_s = (hi - lo) / 1e9
        self.annotations = [a for a in annotations if a[0] != WINDOW_ANNOTATION]

        busy_by_plane = []
        self.program_seconds: dict = {}
        self.program_runs: dict = {}
        op_seconds: dict = {}
        gaps: dict = {}
        for plane in device:
            modules, ops = [], []
            for line in plane["lines"]:
                if line["name"] == MODULES_LINE:
                    modules = sorted(
                        (s, s + d, program_name(n)) for n, s, d in line["events"]
                    )
                elif line["name"] == OPS_LINE:
                    ops = line["events"]
            for s, e, name in modules:
                cs, ce = _clip(s, e, lo, hi)
                if ce > cs:
                    self.program_seconds[name] = (
                        self.program_seconds.get(name, 0.0) + (ce - cs) / 1e9
                    )
                    if s >= lo:
                        self.program_runs[name] = self.program_runs.get(name, 0) + 1
            starts = [m[0] for m in modules]
            clipped = []
            open_ops = []  # [end, key] of the ops this one is nested in
            for name, s, d in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
                cs, ce = _clip(s, s + d, lo, hi)
                if ce <= cs:
                    continue
                clipped.append((cs, ce))
                i = bisect.bisect_right(starts, s) - 1
                owner = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
                key = f"{owner}:{op_family(name)}"
                # self time: a loop's own event spans its body's events, and
                # what the body took is the body's, not the loop's again
                while open_ops and open_ops[-1][0] <= cs:
                    open_ops.pop()
                if open_ops:
                    parent = open_ops[-1][1]
                    op_seconds[parent] -= (min(ce, open_ops[-1][0]) - cs) / 1e9
                op_seconds[key] = op_seconds.get(key, 0.0) + (ce - cs) / 1e9
                open_ops.append([ce, key])
            if not clipped:
                continue
            busy = _union(clipped)
            busy_by_plane.append(sum(e - s for s, e in busy) / 1e9)
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
            self._label_gaps(idle, gaps)
        if not busy_by_plane:
            raise ValueError("no device operation ran inside the traced window")
        self.busy_s = sum(busy_by_plane) / len(busy_by_plane)
        self.devices_busy = len(busy_by_plane)
        # like busy_s, a program's time is the mean over the chips that ran
        for name in self.program_seconds:
            self.program_seconds[name] /= self.devices_busy
            self.program_runs[name] = self.program_runs.get(name, 0) // self.devices_busy
        self.device_ops = sorted(op_seconds.items(), key=lambda kv: -kv[1])
        self.idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])

    def _label_gaps(self, idle: list, gaps: dict) -> None:
        """Split the device's idle intervals (sorted) among the benchmark
        annotations the host was inside at the time: the innermost wins
        where they nest, and what none covers is ``unannotated``."""
        segments = _innermost_segments(self.annotations)
        i = 0
        for gs, ge in idle:
            covered = 0.0
            while i < len(segments) and segments[i][1] <= gs:
                i += 1
            j = i
            while j < len(segments) and segments[j][0] < ge:
                s, e, name = segments[j]
                overlap = min(e, ge) - max(s, gs)
                if overlap > 0:
                    key = name[len(ANNOTATION_PREFIX):]
                    gaps[key] = gaps.get(key, 0.0) + overlap / 1e9
                    covered += overlap
                j += 1
            rest = (ge - gs) - covered
            if rest > 0:
                gaps["unannotated"] = gaps.get("unannotated", 0.0) + rest / 1e9

    def program_time(self, pattern: str):
        """(seconds, executions) of the programs whose name matches the
        regular expression, inside the window; (0.0, 0) where none ran."""
        rx = re.compile(pattern)
        names = [n for n in self.program_seconds if rx.search(n)]
        return (
            sum(self.program_seconds[n] for n in names),
            sum(self.program_runs.get(n, 0) for n in names),
        )

    def breakdown(self, top: int = 10) -> dict:
        return {
            "device_ops": [[k, v] for k, v in self.device_ops[:top]],
            "idle_gaps": [[k, v] for k, v in self.idle_gaps[:top]],
        }


def summarize(log_dir: str) -> TraceSummary:
    return TraceSummary(load_xplane(find_xplane(log_dir)))
