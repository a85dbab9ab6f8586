#!/usr/bin/env python3
"""One traced run of a benchmark cell, read below the benchmark's own
reduction: device-idle seconds by the innermost ``ydb.*`` host span
that covers each gap, and device time by ``ydb.*`` named scope.

    python scripts/trace_breakdown.py --workload tpch-sf1.join --seed 7
    python scripts/trace_breakdown.py --workload tpch-sf3.scan --seed 7 \
        --background

``--background`` also asks the other host threads (conveyor workers: a
staging producer, a ``ydb.resident.promote``; a ``ydb.compact``) what
they were doing during an idle gap that the statement's thread spent
under ``dispatch`` / ``device.wait`` / ``device.get``.

It is ``bench/run.py --trace 1`` with one more reader on the same
``.xplane.pb`` (the benchmark deletes the trace once it has reduced
it). PERF.md section 5 is written from its output; a ``benchmark`` PR
can lift the two functions into ``bench/trace_reduce.py``. The result
goes to stdout and, as JSON, under ``chiprun_out/``.
"""

from __future__ import annotations

import argparse
import heapq
import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "bench")]

PREFIX = "ydb."
#: spans that cover everything beneath them: a gap put down to one of
#: these is not explained
COVERING = {"ydb.query", "ydb.execute", "ydb.dq", "ydb.scan",
            "ydb.transform", "ydb.analyze", "ydb.mesh",
            "ydb.mesh.shuffle", "ydb.mesh.join"}
#: spans in which the statement's thread enqueues a program or waits
#: for the device: with ``--background`` an idle gap under one of them
#: also names what any other host thread was doing
WAITING = {"ydb.dispatch", "ydb.device.wait", "ydb.device.get"}
#: spans of the newest statement's profile reported with their attrs
#: (an exchange's bucket sizes, worst count, attempts and bytes; a local
#: join's capacities and attempts; a host concatenation's blocks, rows
#: and bytes; a Transform's capacity, group layout, key words and tier;
#: a DQ graph's channel rows by path)
REPORTED_SPANS = ("mesh.shuffle", "mesh.join", "host.concat", "transform",
                  "dq")


def reported_span(sp: dict) -> bool:
    """One of ``REPORTED_SPANS``, or a DQ stage's dispatch that says
    something: a join stage's rows and kind, a group-by's or a sort's
    tiers."""
    return sp["name"] in REPORTED_SPANS or (
        sp["name"] == "dispatch" and sp["attrs"].get("program") == "dq_stage"
        and len(set(sp["attrs"]) - {"program", "compile_built",
                                     "compile_fetched",
                                     "compile_seconds"}) > 0)


#: a device operation that crosses devices, by its HLO name
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter")


def host_lines(profile) -> list:
    """Per host thread line, its ``ydb.*`` events as (start, end, name)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in ln.events if e.name.startswith(PREFIX))
            if evs:
                out.append(evs)
    return out


def _innermost(events, bounds) -> list:
    """For each piece ``[bounds[i], bounds[i + 1])`` the name of the
    shortest of ``events`` (sorted) open over it, ``(none)`` where none
    is: one sweep, a heap by duration, ended events dropped as they
    surface. ``bounds`` holds every start and end of ``events``."""
    out, heap, nxt = [], [], 0
    for x in bounds[:-1]:
        while nxt < len(events) and events[nxt][0] <= x:
            s, e, n = events[nxt]
            heapq.heappush(heap, (e - s, n, e))
            nxt += 1
        while heap and heap[0][2] <= x:
            heapq.heappop(heap)
        out.append(heap[0][1] if heap else "(none)")
    return out


def idle_by_span(busy, lines, lo, hi, background: bool = False) -> dict:
    """Idle nanoseconds of [lo, hi) outside ``busy`` (sorted disjoint
    intervals), by the innermost event of the statement threads' lines
    (those that hold a ``ydb.query``) covering each instant; ``(none)``
    where no ``ydb.*`` event covers it. With ``background``, an instant
    the statement's thread spends in one of ``WAITING`` is put down to
    that span AND the innermost event of any other host thread then
    (``ydb.dispatch + ydb.resident.promote.put``): a promotion, a
    compaction or a staging producer that held the interpreter while
    the statement's thread was to enqueue or to be woken."""
    gaps, at = [], lo
    for s, e in busy:
        if at < hi and s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    statement = [any(n == "ydb.query" for _, _, n in evs) for evs in lines]
    events = sorted(ev for evs, st in zip(lines, statement) if st
                    for ev in evs)
    others = sorted(ev for evs, st in zip(lines, statement)
                    if background and not st for ev in evs)
    # one sweep over [lo, hi) cut at every event boundary, and each
    # piece meets the gaps it overlaps. A window of hundreds of
    # statements holds 10^5 gaps and 10^4 events; gap by gap over all
    # events is 10^9 steps.
    bounds = sorted({lo, hi} | {t for s, e, _ in events + others
                                for t in (s, e) if lo < t < hi})
    names = _innermost(events, bounds)
    behind = _innermost(others, bounds) if others else None
    out, g = {}, 0
    for i, (x, y) in enumerate(zip(bounds, bounds[1:])):
        name = names[i]
        if behind and name in WAITING and behind[i] != "(none)":
            name = f"{name} + {behind[i]}"
        while g < len(gaps) and gaps[g][1] <= x:
            g += 1
        k = g
        while k < len(gaps) and gaps[k][0] < y:
            cut = min(gaps[k][1], y) - max(gaps[k][0], x)
            if cut > 0:
                out[name] = out.get(name, 0.0) + cut
            k += 1
    return out


def self_by_span(lines, lo, hi) -> dict:
    """Self nanoseconds by event name on the statement threads' lines
    over [lo, hi): each instant goes to the innermost event covering
    it, so the names sum to the time some statement was in flight."""
    out = idle_by_span([], lines, lo, hi)
    out.pop("(none)", None)
    return out


def device_time_by_scope(path: str, lo: float, hi: float) -> dict:
    """Device seconds of [lo, hi) by ``ydb.<kernel>`` scope and the
    heaviest operations with scope and source line. The scope is not
    among an event's own stats (all that ``ProfileData`` hands out): on
    a v5e it is in the ``tf_op`` stat of the event's *metadata*, the
    HLO ``op_name`` (``jit(run)/ydb.compact/gather:``), beside
    ``source`` (file:line). Reading those takes the xplane proto, which
    TensorFlow ships; without it there is nothing to read."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return {}
    import trace_reduce

    space = xplane_pb2.XSpace()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    by_scope, by_op, carrier = {}, {}, {}
    collective = {"events": 0, "seconds": 0.0, "exposed_seconds": 0.0,
                  "by_scope": {}}
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        crossing, local = [], []
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        described = {}
        for mid, md in plane.event_metadata.items():
            strings = {stat_name.get(st.metadata_id): st.str_value
                       for st in md.stats
                       if st.WhichOneof("value") == "str_value"}
            scope = "(none)"
            for key, value in strings.items():
                parts = [x for x in value.split("/")
                         if x.startswith(PREFIX)]
                if parts:
                    scope = parts[-1].rstrip(":")   # the innermost
                    carrier[key] = carrier.get(key, 0) + 1
                    break
            described[mid] = (scope, md.display_name or md.name[:40],
                              strings.get("source", "").replace(
                                  str(ROOT) + "/", ""))
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1e3
                dur = ev.duration_ps / 1e3
                if start + dur <= lo or start >= hi:
                    continue
                scope, name, source = described[ev.metadata_id]
                by_scope[scope] = by_scope.get(scope, 0.0) + dur
                key = f"{scope} | {name} | {source}"
                by_op[key] = by_op.get(key, 0.0) + dur
                if COLLECTIVE.search(name):
                    crossing.append((start, start + dur))
                    collective["events"] += 1
                    collective["by_scope"][scope] = (
                        collective["by_scope"].get(scope, 0.0) + dur / 1e9)
                else:
                    local.append((start, start + dur))
        # a collective is exposed while nothing else runs on its device
        crossing = trace_reduce.union(crossing)
        hidden = overlap_ns(crossing, trace_reduce.union(local))
        total = sum(e - s for s, e in crossing)
        collective["seconds"] += total / 1e9
        collective["exposed_seconds"] += (total - hidden) / 1e9
    return {"by_scope": by_scope, "by_op": by_op, "scope_stat": carrier,
            "collective": collective}


def overlap_ns(a, b) -> float:
    """Nanoseconds covered by both of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def breakdown(path: str, background: bool = False) -> dict:
    from jax.profiler import ProfileData

    import trace_reduce

    profile = ProfileData.from_file(path)
    loaded = trace_reduce.load(path)
    lo, hi = trace_reduce.window_of(loaded["spans"])
    lines = host_lines(profile)
    idle = {}
    busy_of = []        # each device's busy intervals, merged
    for plane in profile.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for ln in plane.lines:
            if ln.name != trace_reduce.OPS_LINE:
                continue
            busy = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                    for e in ln.events
                    if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
            busy_of.append(trace_reduce.union(busy))
            for k, v in idle_by_span(busy_of[-1], lines, lo, hi,
                                     background).items():
                idle[k] = idle.get(k, 0.0) + v
    scopes = device_time_by_scope(path, lo, hi)

    def top(d, n=12):
        return [[k, round(v / 1e9, 6)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:n]]

    total_idle = sum(idle.values())
    named = sum(v for k, v in idle.items()
                if k != "(none)" and k not in COVERING)
    threads = {}
    for evs in lines:
        if not any(n == "ydb.query" for _, _, n in evs):
            for s, e, n in evs:
                if e > lo and s < hi:
                    threads[n] = threads.get(n, 0.0) + min(e, hi) - max(s, lo)
    per_statement = {}
    for evs in lines:
        for _, _, n in evs:
            per_statement[n] = per_statement.get(n, 0) + 1
    statements = max(per_statement.get("ydb.query", 0), 1)
    busy_s = [sum(e - s for s, e in b) / 1e9 for b in busy_of]
    any_busy = trace_reduce.union([iv for b in busy_of for iv in b])
    return {
        "window_s": (hi - lo) / 1e9,
        # do the devices of a mesh work side by side? each device's
        # busy seconds, and the seconds in which any of them is busy:
        # the sum of the first equals the second where they take turns
        "device_busy_s": [round(v, 6) for v in busy_s],
        "any_device_busy_s": round(
            sum(e - s for s, e in any_busy) / 1e9, 6),
        "idle_s": total_idle / 1e9,
        "idle_named_leaf_share": named / total_idle if total_idle else None,
        "idle_by_span": top(idle, 20),
        # what the other host threads (conveyor workers, a background
        # compaction) spent inside the window, by event name: seconds of
        # events, nested ones counted in their parents' too
        "background_s_by_span": top(threads, 12),
        "device_s_by_scope": top(scopes.get("by_scope", {}), 20),
        "device_s_top_ops": top(scopes.get("by_op", {}), 10),
        "scope_stat": scopes.get("scope_stat", {}),
        # summed over the devices: a collective runs on each of them
        "collective": scopes.get("collective", {}),
        "host_self_s_by_span": top(self_by_span(lines, lo, hi), 20),
        "host_events_per_statement": round(
            sum(per_statement.values()) / statements, 1),
        "spans_per_statement": round(
            sum(v for k, v in per_statement.items()
                if not k.startswith(PREFIX + "stage.")) / statements, 1),
        "statements_traced": statements,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--background", action="store_true",
                    help="put an idle gap under dispatch / device.wait / "
                         "device.get down to the innermost ydb.* event of "
                         "any other host thread too")
    args = ap.parse_args(argv)

    import run
    import trace_reduce

    found = {}
    newest = trace_reduce.newest_trace

    def newest_and_read(trace_dir):
        path = newest(trace_dir)
        found.update(breakdown(path, args.background))
        return path

    trace_reduce.newest_trace = newest_and_read
    peak = run.memory_peak_bytes

    def peak_of_each_device():
        """Every device's peak and live bytes as the window closes (the
        benchmark reports the fullest device's peak alone)."""
        import jax

        found["device_memory"] = [
            {k: int((d.memory_stats() or {}).get(k, 0))
             for k in ("peak_bytes_in_use", "bytes_in_use")}
            for d in jax.local_devices()]
        return peak()

    run.memory_peak_bytes = peak_of_each_device
    totals = run.deploy.resident_totals

    def totals_and_mesh_report(cluster):
        """What each mesh device holds resident, where the program has
        the report (``Cluster.mesh_report``, since PR 31)."""
        report = getattr(cluster, "mesh_report", lambda: [])()
        if report:
            found["mesh_report"] = report
        # the newest execution of each statement text of the round
        by_sql = {p.sql: p for p in cluster.profiles.recent()[-8:]}
        reported = [
            {"sql": p.sql, "seconds": p.seconds, "stages": dict(p.stages),
             "spans": [dict(sp["attrs"], name=sp["name"],
                            seconds=sp["seconds"])
                       for sp in p.spans if reported_span(sp)]}
            for p in by_sql.values()]
        reported = [r for r in reported if r["spans"]]
        if reported:
            found["newest_statement"] = reported[-1]
            found["newest_statements"] = reported
        # the process's DQ channel and join counters (set-up included),
        # and the HBM a graph's channel blocks may hold, where the
        # program has that budget (``engine/hbm.channel_budget``)
        from ydb_tpu.engine import hbm
        from ydb_tpu.obs.counters import root_counters

        found["counters"] = {
            c: root_counters().group(component=c).snapshot()
            for c in ("dq", "join", "rollup", "window")}
        found["channel_budget"] = getattr(hbm, "channel_budget",
                                          lambda: None)()
        return totals(cluster)

    run.deploy.resident_totals = totals_and_mesh_report
    cell = run.load_cell(args.workload)
    run.check_device(cell["chips"])
    result = run.run_cell(cell, args.seed, args.seconds, True)
    found["result"] = {k: result[k] for k in ("correct", "metrics")}
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"breakdown_{args.workload}_{args.seed}.json").write_text(
        json.dumps(found, indent=1))
    print(json.dumps(found, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
