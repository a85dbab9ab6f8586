"""From a profiler trace to device busy time, launches, the heaviest
device operations and the idle gaps labelled by the benchmark's spans.

``load`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into plain tuples; everything after it is
arithmetic on ``(name, start_ns, duration_ns)`` and is what the tests
check. A device plane is ``/device:TPU:<n>`` (or GPU); its "XLA Ops"
line holds one event per operation that ran, its "XLA Modules" line one
per program execution (a launch). The benchmark's own spans are the
``TraceAnnotation`` events whose names start with ``SPAN_PREFIX``, one
round each statement; the traced window runs from the first one's start
to the last one's end.
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: a device operation is named by its HLO text; the breakdown keeps this much
NAME_CHARS = 160


def newest_trace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [...]}``, each list of ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData

    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: [(e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in ln.events]
                     for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            devices[plane.name] = {"ops": lines.get(OPS_LINE, []),
                                   "modules": lines.get(MODULES_LINE, [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend((e.name, float(e.start_ns),
                              float(e.duration_ns)) for e in ln.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def union(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same instants."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(events, lo: float, hi: float) -> list:
    """Events cut to the window, those outside it dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def window_of(spans) -> tuple:
    if not spans:
        raise ValueError(f"the trace holds no {SPAN_PREFIX}* span")
    return (min(s for _, s, _ in spans),
            max(s + d for _, s, d in spans))


def label_gaps(busy, spans, lo: float, hi: float) -> dict:
    """Idle nanoseconds by what the benchmark's spans say the host was
    doing: ``<span> in flight`` or ``between statements``."""
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    inner = sorted((s, s + d, name[len(SPAN_PREFIX):])
                   for name, s, d in spans)
    out = {}
    for a, b in gaps:
        covered = 0.0
        for s, e, name in inner:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                key = f"{name} in flight"
                out[key] = out.get(key, 0.0) + ov
                covered += ov
        if b - a - covered > 0:
            out["between statements"] = (out.get("between statements", 0.0)
                                         + b - a - covered)
    return out


def reduce(loaded: dict) -> dict:
    """The traced window's numbers. ``busy_s`` is the union of the
    intervals in which an operation ran on a device, averaged over the
    devices; ``launches`` counts program executions on all of them."""
    lo, hi = window_of(loaded["spans"])
    spans = clip(loaded["spans"], lo, hi)
    busy_ns, launches, by_op, gaps = [], 0, {}, {}
    for plane in loaded["devices"].values():
        ops = clip(plane["ops"] or plane["modules"], lo, hi)
        busy = union((s, s + d) for _, s, d in ops)
        busy_ns.append(sum(e - s for s, e in busy))
        launches += len(clip(plane["modules"], lo, hi))
        for name, _, d in ops:
            by_op[name] = by_op.get(name, 0.0) + d
        for k, v in label_gaps(busy, spans, lo, hi).items():
            gaps[k] = gaps.get(k, 0.0) + v
    n = max(len(busy_ns), 1)

    def top(ns_by_name: dict) -> list:
        return [[k[:NAME_CHARS], v / n / 1e9] for k, v in sorted(
            ns_by_name.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "devices": len(busy_ns),
        "launches": launches,
        "statements": len(spans),
        "device_ops": top(by_op),
        "idle_gaps": top(gaps),
    }
