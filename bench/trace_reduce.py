"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

``trace_scopes.read_events`` reads the trace with
``jax.profiler.ProfileData``. Device operations are the events of the ``XLA
Ops`` line of each chip's plane (``/device:TPU:<n>``; other ``/device:``
planes hold no chip); host spans are the ``jax.profiler.TraceAnnotation``
events of the ``/host:CPU`` plane. All times are in the trace's own clock,
in nanoseconds, and every quantity is taken inside one window: the span
named ``window`` (the benchmark opens it around its measured window).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|send|recv", re.IGNORECASE)


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def merge(intervals, lo: float, hi: float):
    """Union of ``(start, end, ...)`` intervals clipped to ``[lo, hi]``, as a
    sorted list of disjoint ``(start, end)``."""
    out = []
    for iv in sorted((max(a, lo), min(b, hi)) for a, b, *_ in intervals):
        a, b = iv
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(merged) -> float:
    return float(sum(b - a for a, b in merged))


def gaps(merged, lo: float, hi: float):
    """The complement of ``merged`` inside ``[lo, hi]``."""
    out, t = [], lo
    for a, b in merged:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def subtract(merged, other):
    """``merged`` minus ``other``; both sorted and disjoint."""
    out, j = [], 0
    for a, b in merged:
        t = a
        while j < len(other) and other[j][1] <= t:
            j += 1
        k = j
        while k < len(other) and other[k][0] < b:
            if other[k][0] > t:
                out.append((t, other[k][0]))
            t = max(t, other[k][1])
            k += 1
        if t < b:
            out.append((t, b))
    return out


def exposed_collective_ns(events, lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` in which a collective runs on a device and no
    other operation does. An event ``(start, end, name, scope,
    collective)`` whose fifth field is set (``trace_scopes.hlo_ops``, from
    the program's HLO) is a collective by it, a fusion that XLA built
    around a collective among them; any other by its own name (an event's
    text also names its operands, and an op that reads a gathered weight
    is no collective). A control-flow op (``CONTAINERS``) is an event that
    spans the ops of its body, a collective among them, so it counts as no
    other operation."""
    def is_coll(e):
        if len(e) > 4 and e[4] is not None:
            return e[4]
        return COLLECTIVE.match(op_name(e[2])) is not None

    coll = merge([e for e in events if is_coll(e)], lo, hi)
    rest = merge([e for e in events if not is_coll(e)
                  and not op_name(e[2]).startswith(CONTAINERS)], lo, hi)
    return length(subtract(coll, rest))


def window_of(spans, name: str):
    hits = [(a, b) for a, b, n in spans if n == name]
    if not hits:
        raise ValueError(f"the trace holds no {name!r} span")
    return hits[0]


def span_label(spans, a: float, b: float, skip: str) -> str:
    """The host span that overlaps ``[a, b]`` the most (``"none"`` if no
    span other than ``skip`` does)."""
    best, label = 0.0, "none"
    for s0, s1, name in spans:
        if name == skip:
            continue
        ov = min(b, s1) - max(a, s0)
        if ov > best:
            best, label = ov, name
    return label


def overlap(merged, a: float, b: float) -> float:
    """Length of ``merged`` (sorted, disjoint) inside ``[a, b]``."""
    i = max(bisect.bisect_right(merged, (a, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        total += max(0.0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return total


CONTAINERS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """``%fusion.12 = (f32[...]) fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ")[0].lstrip("%")


def reduce_events(ev: dict, window: str, top: int = 10) -> dict:
    """Busy time, idle share, span durations and the device's idle time
    inside each span, exposed collectives and the breakdown, all inside the
    ``window`` span. ``ev``: ``{"devices": {plane: [(start, end, name,
    ...)]}, "spans": [(start, end, name)]}``. Control-flow ops (a loop and
    the ops in its body are both events) count towards busy time but not
    in ``device_ops``."""
    lo, hi = window_of(ev["spans"], window)
    win = hi - lo
    busy, exposed, gap_list, merged_all = [], [], [], []
    op_time = defaultdict(float)
    for plane, events in sorted(ev["devices"].items()):
        merged = merge(events, lo, hi)
        merged_all.append(merged)
        busy.append(length(merged))
        exposed.append(exposed_collective_ns(events, lo, hi))
        for a, b, name, *_ in events:
            name = op_name(name)
            d = min(b, hi) - max(a, lo)
            if d > 0 and not name.startswith(CONTAINERS):
                op_time[name] += d
        gap_list.extend(gaps(merged, lo, hi))
    n_dev = max(len(busy), 1)
    spans, span_idle = defaultdict(list), defaultdict(list)
    for a, b, name in ev["spans"]:
        if name != window and a >= lo and b <= hi:
            spans[name].append((b - a) / 1e9)
            if merged_all:
                inside = sum(overlap(m, a, b) for m in merged_all) / n_dev
                span_idle[name].append((b - a - inside) / 1e9)
    gap_list.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": win / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "idle_share": 1.0 - sum(busy) / n_dev / win if busy else None,
        "exposed_collective_share": (sum(exposed) / n_dev / win
                                     if busy else None),
        "spans": dict(spans),
        "span_idle": dict(span_idle),
        "device_ops": [[name, t / n_dev / 1e9] for name, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[span_label(ev["spans"], a, b, window), (b - a) / 1e9]
                      for a, b in gap_list[:top]],
        "devices": n_dev if busy else 0,
    }

