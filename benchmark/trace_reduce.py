"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

``reduce_trace(path)`` reads the file with ``jax.profiler.ProfileData`` and
returns, for the traced window:

``window_s``   length of the window: the benchmark's own ``bench:window``
               annotation where the trace has it, else first device event to
               last;
``busy_s``     seconds in which an operation ran on the device: the union of
               the device-op intervals, clipped to the window, averaged over
               the device planes that ran anything;
``modules``    device seconds per jit module (the ``XLA Modules`` line), most
               first, under the names the trace gives;
``ops``        device seconds per operation (the ``XLA Ops`` line), most first;
               an op that holds others (a ``while`` and its body) counts
               their time too, so these do not add up to ``busy_s``;
``gaps``       the longest intervals in which no operation ran, each labelled
               by the benchmark's own span that covers most of it
               (``bench:<label>`` annotations), else ``unattributed``;
``spans``      how many of each ``bench:`` annotation the window holds.

Nothing here knows a cell, a query or a kernel by name.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
BENCH = "bench:"
#: on the CPU backend (rehearsal only) XLA's client threads stand in for a device
HOST_XLA_LINE = "tf_XLAPjRtCpuClient"
#: shorter pauses between two ops are the device's own, not a gap to explain
MIN_GAP_S = 1e-6


def union(intervals) -> list:
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(merged, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in merged if e > lo and s < hi]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def gaps(merged, lo, hi) -> list:
    """The intervals of [lo, hi] that ``merged`` (sorted, disjoint) leaves."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label_gap(gap, spans) -> str:
    """Label of the span that covers most of ``gap``; ``spans`` is
    ``[(start, end, label), ...]``."""
    best, best_cover = "unattributed", 0.0
    for s, e, label in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = label, cover
    return best


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """The trace names an op by its whole HLO line; keep what stands before
    the ``=``: ``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _ranked(seconds: dict, n: int) -> list:
    return [[k, v] for k, v in sorted(seconds.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_trace(path: str, host_xla_as_device: bool = False, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_lines = {}  # plane name -> {line name: [(start_s, end_s, name)]}
    spans = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device:
                keep = line.name in (OPS_LINE, MODULES_LINE)
                as_line = line.name
            else:
                keep = host_xla_as_device and line.name.startswith(HOST_XLA_LINE)
                as_line = OPS_LINE
            events = []
            for ev in line.events:
                name = ev.name
                if not is_device and name.startswith(BENCH):
                    s = ev.start_ns / 1e9
                    spans.append((s, s + ev.duration_ns / 1e9, name[len(BENCH):]))
                elif keep and ev.duration_ns > 0 and not name.startswith("ThreadpoolListener"):
                    s = ev.start_ns / 1e9
                    events.append((s, s + ev.duration_ns / 1e9, name))
            if events:
                plane_key = plane.name if is_device else "host-xla"
                device_lines.setdefault(plane_key, {}).setdefault(as_line, []).extend(events)

    window = [(s, e) for s, e, label in spans if label == "window"]
    all_ops = [ev for lines in device_lines.values() for ev in lines.get(OPS_LINE, [])]
    if window:
        lo, hi = window[0]
    elif all_ops:
        lo, hi = min(e[0] for e in all_ops), max(e[1] for e in all_ops)
    else:
        return {"window_s": None, "busy_s": None, "devices": 0, "modules": [],
                "ops": [], "gaps": [], "spans": {}}

    busy, module_s, op_s, merged_any = [], {}, {}, []
    for lines in device_lines.values():
        ops = lines.get(OPS_LINE, [])
        merged = clip(union((s, e) for s, e, _ in ops), lo, hi)
        if merged:
            busy.append(length(merged))
            merged_any = merged_any or merged
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                name = _op_name(name)
                op_s[name] = op_s.get(name, 0.0) + d
        for s, e, name in lines.get(MODULES_LINE, []):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                name = _module_name(name)
                module_s[name] = module_s.get(name, 0.0) + d

    inner = [sp for sp in spans if sp[2] != "window"]
    idle = sorted(
        (g for g in gaps(merged_any, lo, hi) if g[1] - g[0] >= MIN_GAP_S),
        key=lambda g: g[0] - g[1],
    )[:top]
    counts = {}
    for s, e, label in inner:
        if e > lo and s < hi:
            counts[label] = counts.get(label, 0) + 1
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy) if busy else None,
        "devices": len(busy),
        "modules": _ranked(module_s, top),
        "ops": _ranked(op_s, top),
        "gaps": [[label_gap(g, inner), g[1] - g[0]] for g in idle],
        "spans": counts,
    }


def outline(path: str) -> None:
    """Print what a trace holds: planes, lines, event counts and a few names."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = []
            for ev in events:
                if ev.name not in names:
                    names.append(ev.name)
                if len(names) == 6:
                    break
            span = (
                (min(e.start_ns for e in events), max(e.start_ns + e.duration_ns for e in events))
                if events else None
            )
            print("  line", line.name, len(events), span, names)


if __name__ == "__main__":
    import json
    import sys

    outline(sys.argv[1])
    print(json.dumps(reduce_trace(sys.argv[1]), indent=1))
