#!/usr/bin/env python3
"""The deploy of a benchmark cell and nothing after it, read from the
write path's own counters: rows/s, the `write` spans' seconds by stage,
the promotions' rate, lag and refusals.

    python scripts/write_breakdown.py --workload clickbench-hits.topk --seed 7
    YDB_TPU_PROFILE=0 python scripts/write_breakdown.py --workload ... --seed 7

It is ``bench/run.py`` up to the end of ``deploy.build`` (generate, create,
load, count check, upsert probe, promotions drained; ``enable_mesh()`` for
a mesh configuration), then the process counters ``component=write |
resident | compact`` (``ydb_tpu/obs/README.md``, "The span tree of a
write") instead of the warm-up and the window: a loader change is read in
a third of a run's time, and with ``YDB_TPU_PROFILE=0`` what the spans
cost (the harness itself needs the profiles that setting turns off).
PERF.md section 5's set-up paragraph is written from its output; the
result goes to stdout and, as JSON, under ``chiprun_out/``.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "bench")]


def read_counters() -> dict:
    from ydb_tpu.obs import tracing
    from ydb_tpu.obs.counters import root_counters
    from ydb_tpu.obs.profile import WRITE_SPAN_STAGE

    stages = sorted(set(WRITE_SPAN_STAGE.values()))

    w = root_counters().group(component="write")
    r = root_counters().group(component="resident")
    c = root_counters().group(component="compact")
    rows, seconds = w.counter("rows").value, w.counter("seconds").value
    stage = {s: w.group(stage=s).counter("stage_seconds").value
             for s in stages}
    lag = r.histogram("resident_lag_seconds")
    visible = w.histogram("visible_seconds")
    put = r.group(stage="put").counter("promote_seconds").value
    return {
        "write": {k: w.counter(k).value for k in (
            "inserts", "rows", "bytes_in", "portions", "blob_bytes",
            "rows_deduped", "seconds", "failed")},
        "write_rows_per_s": rows / seconds if seconds else None,
        "stage_seconds": stage,
        "stage_ms_per_mrow": {s: 1e9 * v / rows for s, v in stage.items()}
        if rows else {},
        "stage_share": {s: v / seconds for s, v in stage.items()}
        if seconds else {},
        "stages_cover": sum(stage.values()) / seconds if seconds else None,
        "visible_s": {"count": visible.count, "p50": visible.percentile(0.5),
                      "p95": visible.percentile(0.95)},
        "resident": {k: r.counter(k).value for k in (
            "promotions", "promote_bytes", "spills", "errors")},
        "promote_seconds": {s: r.group(stage=s).counter(
            "promote_seconds").value for s in ("load", "put", "admit")},
        "promote_gb_per_s": r.counter("promote_bytes").value / put / 1e9
        if put else None,
        "promote_declined": {x: r.group(reason=x).counter(
            "promote_declined").value
            for x in ("inflight_full", "in_flight", "disabled")},
        "resident_lag_s": {"count": lag.count,
                           "mean": lag.total / lag.count if lag.count
                           else None,
                           "p50": lag.percentile(0.5),
                           "p95": lag.percentile(0.95)},
        "compact": {k: c.counter(k).value
                    for k in ("runs", "rows_in", "rows_out", "seconds")},
        "compile": tracing.compile_counts(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import run
    from ydb_tpu.kqp.session import Cluster

    cell = run.load_cell(args.workload)
    run.check_device(cell["chips"])
    run.configure_compile_cache()
    config = cell["config"]
    split = {"import": time.perf_counter() - T0}
    t0 = time.perf_counter()
    data = run.load_module(cell["dir"], "", config["generator"]).make(
        config["scale_factor"], args.seed,
        **config.get("generator_options", {}))
    split["generate"] = time.perf_counter() - t0
    cluster = Cluster()
    try:
        t0 = time.perf_counter()
        readings = run.deploy.build(cluster, cluster.session(), data,
                                    config, run.say)
        if config.get("mesh"):
            cluster.enable_mesh()
            run.deploy.drain_promotions(cluster)
        split["deploy"] = time.perf_counter() - t0
        resident = run.deploy.resident_totals(cluster)
    finally:
        cluster.stop()
    found = dict(read_counters(), workload=args.workload, seed=args.seed,
                 profile=os.environ.get("YDB_TPU_PROFILE", "1"),
                 split=split, readings=readings, resident_totals=resident)
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"write_{args.workload}_{args.seed}_p{found['profile']}_"
           f"{os.getpid()}.json").write_text(json.dumps(found, indent=1))
    print(json.dumps(found, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
