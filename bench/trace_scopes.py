"""Device time per layer of the training step, from a profiler trace.

The program names the layers of its step with ``jax.named_scope``
(``repro/train/step.py``: ``fwd_bwd``, ``grab_balance``, ``grad_accum``,
``optimizer``; ``repro/train/loop.py``: ``grab_rollover``). The names reach
each HLO instruction's ``op_name`` metadata. A TPU trace's op events carry
only the instruction's name, and the trace carries each program's HLO
(``hlo_op_names``): an op's program is the ``XLA Modules`` event it runs
in, and its ``op_name`` that program's instruction's. An op belongs to the
first of the five names in its ``op_name``, as a path component or inside a
wrapper such as ``transpose(jvp(fwd_bwd))`` or ``jit(fwd_bwd)``; else to
the program it runs in, where the program is named after a scope
(``jit_grab_rollover``); else it is unscoped. A fusion counts under its
own metadata.

The loop also nests host spans in ``epoch_reorder`` (``sign_fetch``,
``reorder``, ``rollover``) and opens ``epoch_hook``; an idle gap here is
labelled ``parent/child`` when a span nested in the span that overlaps the
gap most covers at least half of it.

This builds on ``trace_reduce`` and changes none of its numbers.

    python3 bench/trace_scopes.py TRACE [--window bench_window]

``TRACE`` is an ``.xplane.pb`` file or a directory holding one, such as
the ``--profile-dir`` of ``examples/train_lm.py --profile-steps A:B``.
Without ``--window`` the whole trace is the window. Prints one JSON
object: the window, busy time, device seconds per scope and unscoped,
the steps (``dispatch`` spans) in the window, the top ops named
``<scope>/<op>`` and the idle gaps with their labels.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce as tr  # noqa: E402

SCOPES = ("fwd_bwd", "grab_balance", "grad_accum", "optimizer",
          "grab_rollover")
SPANS = ("loader_wait", "dispatch", "epoch_reorder", "ckpt_save",
         "sign_fetch", "reorder", "rollover", "epoch_hook")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
_WORD = re.compile(r"[A-Za-z0-9_]+")
_PROGRAM = re.compile(r"^jit_(\w+)\(")


def scope_of(op_name) -> str | None:
    """The first of ``SCOPES`` in ``op_name``, or None."""
    for part in (op_name or "").split("/"):
        for word in _WORD.findall(part):
            if word in SCOPES:
                return word
    return None


def program_scope(program: str) -> str | None:
    """The scope a whole program is named after: ``jit_grab_rollover(…)``
    -> ``grab_rollover``. Its ops that carry no ``op_name`` (zero fills and
    copies the compiler makes) belong to it."""
    m = _PROGRAM.match(program or "")
    return m.group(1) if m and m.group(1) in SCOPES else None


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of a protobuf message's fields; a
    length-delimited value is a ``memoryview``."""
    buf, i = memoryview(buf), 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _sub(msg, number: int):
    return [v for n, v in _fields(msg) if n == number]


def hlo_op_names(raw: bytes) -> dict:
    """``{program: {instruction: op_name}}`` from the HLO of each program
    that a trace (serialized ``XSpace``) carries: the ``Hlo Proto`` stats of
    its ``/host:metadata`` plane, keyed by the program's name as the
    device's ``XLA Modules`` line names it. The device op events carry
    only the instruction's name; its ``op_name`` is here."""
    out = {}
    for plane in _sub(raw, 1):                      # XSpace.planes
        if bytes(next(iter(_sub(plane, 2)), b"")) != METADATA_PLANE.encode():
            continue
        stat_names = {}
        for entry in _sub(plane, 5):                # XPlane.stat_metadata
            for md in _sub(entry, 2):
                f = dict(_fields(md))
                stat_names[f.get(1)] = bytes(f.get(2, b"")).decode()
        for entry in _sub(plane, 4):                # XPlane.event_metadata
            for md in _sub(entry, 2):
                program = bytes(next(iter(_sub(md, 2)), b"")).decode()
                for stat in _sub(md, 5):            # XEventMetadata.stats
                    f = dict(_fields(stat))
                    if stat_names.get(f.get(1)) != "Hlo Proto" or 6 not in f:
                        continue
                    names = out.setdefault(program, {})
                    for module in _sub(f[6], 1):    # HloProto.hlo_module
                        for comp in _sub(module, 3):
                            for ins in _sub(comp, 2):
                                f_ins = dict(_fields(ins))
                                op = dict(_fields(f_ins.get(7, b"")))
                                names[bytes(f_ins[1]).decode()] = (
                                    bytes(op[2]).decode() if 2 in op
                                    else None)
    return out


def scope_events(ops, programs, op_names):
    """``(start, end, name, scope)`` of each device op ``(start, end,
    name)``: the op's program is the ``XLA Modules`` event ``(start, end,
    program)`` it runs in, its ``op_name`` that program's, and its scope the
    first of ``SCOPES`` there, else the program's own (``program_scope``)."""
    programs = sorted(programs)
    starts = [p[0] for p in programs]
    out = []
    for a, b, name in ops:
        i = bisect.bisect_right(starts, a) - 1
        program = programs[i][2] if i >= 0 and a < programs[i][1] else None
        op_name = op_names.get(program, {}).get(tr.op_name(name))
        out.append((a, b, name,
                    scope_of(op_name) or program_scope(program)))
    return out


def read_events(path: str, span_names) -> dict:
    """``trace_reduce.read_events``, with each device op's scope as a fourth
    field: ``{"devices": {plane: [(start, end, name, scope)]}, "spans":
    [(start, end, name)]}``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    op_names = hlo_op_names(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices, spans = {}, []
    wanted = set(span_names)
    for plane in data.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            ops, programs = [], []
            for line in plane.lines:
                if line.name in (tr.OPS_LINE, MODULES_LINE):
                    evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                    (ops if line.name == tr.OPS_LINE else programs).extend(evs)
            devices[plane.name] = sorted(scope_events(ops, programs,
                                                      op_names))
        elif plane.name == tr.HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events if e.name in wanted)
    return {"devices": devices, "spans": sorted(spans)}


def nested_label(spans, a: float, b: float, skip: str) -> str:
    """``trace_reduce.span_label`` of the gap ``[a, b]``, as
    ``parent/child`` when a span nested in the chosen one covers at least
    half of the gap."""
    label = tr.span_label(spans, a, b, skip)
    if label == "none":
        return label
    ov = lambda s0, s1: min(b, s1) - max(a, s0)
    p0, p1 = max(((s0, s1) for s0, s1, n in spans if n == label),
                 key=lambda s: ov(*s))
    best, child = 0.5 * (b - a), None
    for s0, s1, name in spans:
        if (name not in (label, skip) and p0 <= s0 and s1 <= p1
                and ov(s0, s1) >= best):
            best, child = ov(s0, s1), name
    return f"{label}/{child}" if child else label


def reduce_events(ev: dict, window, top: int = 10) -> dict:
    """Device seconds per scope inside the ``window`` span (the whole
    trace when ``window`` is None), averaged over the chips, with
    control-flow containers left out as ``trace_reduce.reduce_events``
    leaves them out of ``device_ops``."""
    if window is None:
        ends = [e[:2] for evs in ev["devices"].values() for e in evs]
        ends += [s[:2] for s in ev["spans"]]
        lo, hi = min(a for a, _ in ends), max(b for _, b in ends)
    else:
        lo, hi = tr.window_of(ev["spans"], window)
    scope_t, op_t = defaultdict(float), defaultdict(float)
    unscoped, busy, gap_list = 0.0, 0.0, []
    for plane, events in sorted(ev["devices"].items()):
        merged = tr.merge(events, lo, hi)
        busy += tr.length(merged)
        gap_list.extend(tr.gaps(merged, lo, hi))
        for a, b, name, scope in events:
            name = tr.op_name(name)
            d = min(b, hi) - max(a, lo)
            if d <= 0 or name.startswith(tr.CONTAINERS):
                continue
            if scope is None:
                unscoped += d
                op_t[name] += d
            else:
                scope_t[scope] += d
                op_t[f"{scope}/{name}"] += d
    n_dev = max(len(ev["devices"]), 1)
    gap_list.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n_dev / 1e9,
        "steps": sum(1 for a, b, n in ev["spans"]
                     if n == "dispatch" and a >= lo and b <= hi),
        "scope_s": {s: t / n_dev / 1e9 for s, t in sorted(scope_t.items())},
        "unscoped_s": unscoped / n_dev / 1e9,
        "device_ops": [[name, t / n_dev / 1e9] for name, t in sorted(
            op_t.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[nested_label(ev["spans"], a, b, window), (b - a) / 1e9]
                      for a, b in gap_list[:top]],
    }


def reduce_file(path: str, window=None, top: int = 10) -> dict:
    return reduce_events(read_events(path, SPANS + (window,)), window, top)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--window", default=None,
                    help="the span to reduce inside (default: the whole "
                         "trace), e.g. bench_window")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    path = (args.trace if os.path.isfile(args.trace)
            else tr.find_trace(args.trace))
    print(json.dumps(reduce_file(path, args.window, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
