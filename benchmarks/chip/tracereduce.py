"""Reductions from a profiler trace and from host spans to numbers.

:func:`reduce_xplane` reads one ``.xplane.pb`` (``jax.profiler``'s
format) with nothing but JAX:

* device busy time: the union of the intervals in which an operation
  ran on a device plane (a ``/device:`` plane with an ``XLA Ops``
  line), averaged over the device planes, inside the window;
* per program: the summed device time and the number of executions of
  each ``XLA Modules`` event name (``jit_local_step``, ...);
* idle gaps: the stretches of the window with no device operation,
  each charged to the innermost host annotation open at its midpoint
  on the thread that opened the window's rounds.

The window is the span from the start of the first to the end of the
last ``round`` annotation in the trace.

:func:`span_totals` and :func:`self_time` reduce the program's own
spans (Chrome trace events of ``repro.obs.trace.Tracer``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Any, Iterable

ROUND = "round"
SKEW_NS = 5e6


def _module_name(name: str) -> str:
    """``jit_local_step(1234)`` -> ``jit_local_step``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def union_length(intervals: Iterable[tuple[float, float]]) -> tuple[float, list]:
    """Total length of the union, and the merged intervals, sorted."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def innermost_segments(spans: list[tuple[float, float, str]]) -> list[tuple[float, str]]:
    """Spans of one thread (properly nested) flattened into a sorted
    list of (start, label): from each start on, the innermost open span
    is ``label`` ("none" where no span is open)."""
    bounds = []
    for s, e, name in spans:
        bounds.append((s, 1, -(e - s), name))
        bounds.append((e, 0, 0.0, name))
    bounds.sort()
    stack: list[str] = []
    out: list[tuple[float, str]] = []
    for t, is_start, _, name in bounds:
        if is_start:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        out.append((t, stack[-1] if stack else "none"))
    return out


def label_at(segments: list[tuple[float, str]], starts: list[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    return segments[i][1] if i >= 0 else "none"


def reduce_xplane(path: str, host_names: set[str], top: int = 10) -> dict[str, Any]:
    """Window, busy time, per-program device time, breakdown lists.

    ``host_names`` are the annotation names that may label idle gaps
    (the program's span names). Times in the result are seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: list[list[tuple[float, float]]] = []
    mod_time: dict[str, float] = defaultdict(float)
    mod_calls: dict[str, int] = defaultdict(int)
    mod_events: list[tuple[float, float, str]] = []
    host: dict[str, list[tuple[float, float, str]]] = defaultdict(list)
    for plane in pd.planes:
        lines = {line.name for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            ops: list[tuple[float, float]] = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.end_ns) for e in line.events)
                elif line.name == "XLA Modules":
                    for e in line.events:
                        mod_events.append((e.start_ns, e.end_ns, _module_name(e.name)))
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        host[line.name].append((e.start_ns, e.end_ns, e.name))
    rounds = [(s, e) for spans in host.values() for s, e, n in spans if n == ROUND]
    if not rounds:
        raise ValueError(f"{path}: no '{ROUND}' annotation, so no window")
    w0 = min(s for s, _ in rounds)
    w1 = max(e for _, e in rounds)
    window_ns = w1 - w0
    busy = []
    merged_all: list = []
    for ops in device_ops:
        length, merged = union_length(_clip(ops, w0, w1))
        busy.append(length)
        merged_all.extend(merged)
    # the device's timestamps run about a millisecond behind the host's
    # in v5e traces, so a program counts when it overlaps the window
    # widened by SKEW_NS
    for s, e, name in mod_events:
        if s < w1 + SKEW_NS and e > w0 - SKEW_NS:
            mod_time[name] += (e - s) / 1e9
            mod_calls[name] += 1
    # idle gaps over all device planes together, charged to the host
    # span open on the thread that ran the rounds
    _, busy_union = union_length(merged_all)
    main = max(host, key=lambda ln: sum(1 for *_, n in host[ln] if n == ROUND))
    segments = innermost_segments(host[main])
    starts = [t for t, _ in segments]
    idle: dict[str, float] = defaultdict(float)
    cursor = w0
    for s, e in busy_union + [[w1, w1]]:
        if s > cursor:
            idle[label_at(segments, starts, (cursor + s) / 2)] += (s - cursor) / 1e9
        cursor = max(cursor, e)
    n_dev = max(len(device_ops), 1)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": len(device_ops),
        "module_s": dict(mod_time),
        "module_calls": dict(mod_calls),
        "device_ops": sorted(([k, v] for k, v in mod_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def module_seconds(summary: dict, patterns: Iterable[str]) -> tuple[float, int]:
    """Device seconds and executions of the programs whose name
    contains any of ``patterns``."""
    pats = tuple(patterns)
    secs = sum(v for k, v in summary["module_s"].items() if any(p in k for p in pats))
    calls = sum(v for k, v in summary["module_calls"].items() if any(p in k for p in pats))
    return secs, calls


# ---------------------------------------------------------------------------
# host spans (Chrome trace events: ts/dur in microseconds)
# ---------------------------------------------------------------------------

def window_events(events: list[dict], first_round: int) -> tuple[list[dict], int]:
    """The complete spans inside the window — from the start of round
    ``first_round`` to the end of the last round — and the number of
    rounds in it."""
    rounds = [e for e in events if e.get("ph") == "X" and e["name"] == ROUND
              and e["args"].get("round", -1) >= first_round]
    if not rounds:
        return [], 0
    lo = min(e["ts"] for e in rounds)
    hi = max(e["ts"] + e["dur"] for e in rounds)
    inside = [e for e in events if e.get("ph") == "X"
              and lo <= e["ts"] and e["ts"] + e["dur"] <= hi]
    return inside, len(rounds)


def span_totals(events: list[dict], names: Iterable[str]) -> float:
    """Summed duration (seconds) of the spans named ``names``."""
    wanted = set(names)
    return sum(e["dur"] for e in events if e["name"] in wanted) / 1e6


def self_time(events: list[dict], name: str,
              child_prefixes: tuple[str, ...] = ("kernel.", "agg.")) -> float:
    """Summed duration (seconds) of the spans named ``name``, less the
    time of spans nested in them (same thread) whose name starts with
    one of ``child_prefixes``."""
    by_tid: dict[Any, list[dict]] = defaultdict(list)
    for e in events:
        by_tid[e["tid"]].append(e)
    total = 0.0
    for evs in by_tid.values():
        kids = sorted((e["ts"], e["ts"] + e["dur"]) for e in evs
                      if e["name"].startswith(child_prefixes))
        for e in evs:
            if e["name"] != name:
                continue
            s, t = e["ts"], e["ts"] + e["dur"]
            inner, _ = union_length(_clip(kids, s, t))
            total += e["dur"] - inner
    return total / 1e6
