#!/usr/bin/env python3
"""bench/run.py: one cell of the benchmark, on the chip, through pgwire.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children. It asserts a TPU whose kind is in
``peaks.json``, builds the cell's deployment from ``--seed`` through the
program's normal path (``deploy.py``), serves it over a PostgreSQL-wire
socket from a thread of this process, warms each statement of the
cell's traffic, then drives the window through that socket: SQL text in,
every row fetched and decoded by the client. After the window it frees
the deployment, computes the plain numpy references from the generated
arrays and compares every answer the window (and the warm-up) returned.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``statements/<id>.sql`` with ``refs/<id>.py``, and one
``layer_metrics/<name>.py`` per per-layer metric. This file holds no
cell's, statement's or metric's name.

The last line of stdout is the result object; every other line is for
the reader.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()     # process start, as near as Python gets

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import threading

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare
import deploy
import pgclient
import trace_reduce
import work

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(RuntimeError):
    """The run cannot be made as asked; exits non-zero, no result."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------- the cell, from files found by name ------------------


def load_module(base: pathlib.Path, kind: str, name: str):
    path = base / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_"), path)
    if spec is None or not path.exists():
        raise BenchError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entries of BENCHMARK.json with its configuration and
    traffic files read, and the metrics it has to report."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    here = lambda m: workload in m.get("workloads", [workload])
    base = root / bench["paths"][0]
    return {
        "name": workload,
        "dir": base,
        "chips": cell["chips"],
        "config": json.loads((root / entry["file"]).read_text()),
        "traffic": json.loads(
            (base / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


def load_statements(base: pathlib.Path, ids) -> dict:
    return {sid: {"sql": (base / "statements" / f"{sid}.sql").read_text()
                  .strip(),
                  "ref": load_module(base, "refs", sid)}
            for sid in dict.fromkeys(ids)}


# ---------------- the device ------------------------------------------


def check_device(chips: int) -> dict:
    """A TPU with the chips the cell asks for and a kind whose peaks are
    known, or no run: there is no CPU fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    work.peaks_for(devs[0].device_kind)     # an unknown kind raises
    return device_info()


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """The peak on the fullest chip (0 where the backend reports none)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def configure_compile_cache() -> str:
    """JAX's persistent cache at a path that never moves: the one
    ``JAX_COMPILATION_CACHE_DIR`` names, else inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # a statement is many small programs: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs XLA built or fetched from the persistent cache (JAX fires
    the event round both, never on a hit of the in-process cache). No
    metric: a run whose window built or fetched one is not ``correct``
    (``compiles_inside_the_window`` is held at 0)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _seconds, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def executor_of(profile) -> str:
    spans = {s["name"]: s for s in profile.spans}
    if spans.get("mesh", {}).get("attrs", {}).get("answered"):
        return "mesh-fused" if "plan.fuse" in spans else "mesh-walk"
    if "plan.fuse" in spans:
        return "fused"
    return "dq" if "dq" in spans else "walk"


def unexpected_executors(profiles, statements: dict, expected: dict) -> int:
    """Statements answered by another executor than the one the traffic
    file expects of them (a cell's ``why`` names the path it times);
    a statement the file says nothing of is not held to any."""
    by_sql = {st["sql"]: sid for sid, st in statements.items()}
    wrong = 0
    for prof in profiles:
        sid = by_sql.get(prof.sql.strip())
        if sid in expected and executor_of(prof) != expected[sid]:
            wrong += 1
    return wrong


# ---------------- the window ------------------------------------------


def drive(port: int, traffic: dict, statements: dict, seconds: float,
          after_each=None, annotate: bool = False) -> tuple:
    """The cell's traffic against the wire for ``seconds``; returns the
    records of every statement started and the window's whole time. A
    closed loop: each client sends its next statement when the last has
    answered, the statements in their listed order round after round
    (client i starts i places on); at ``seconds`` the round in flight
    finishes and the window ends at its completion, so that every
    window holds the same mix whatever a statement takes."""
    if traffic["loop"] != "closed":
        raise NotImplementedError(
            f"loop {traffic['loop']!r} with a rate: not built yet")
    import jax

    order = traffic["statements"]
    clients = [pgclient.PgClient(port) for _ in range(traffic["clients"])]
    records, errors = [], []
    go = threading.Barrier(len(clients) + 1)

    def client_loop(i: int, client) -> None:
        try:
            go.wait()
            k = i
            while True:
                sid = order[k % len(order)]
                k += 1
                rec = {"id": sid, "client": i, "error": None}
                with (jax.profiler.TraceAnnotation(
                        trace_reduce.SPAN_PREFIX + sid) if annotate
                        else contextlib.nullcontext()):
                    rec["t0"] = time.perf_counter()
                    try:
                        rec["names"], rec["rows"] = client.query(
                            statements[sid]["sql"])
                    except pgclient.PgError as e:
                        rec["error"] = repr(e)
                    rec["t1"] = time.perf_counter()
                if after_each:
                    after_each(rec)
                records.append(rec)
                if ((k - i) % len(order) == 0
                        and rec["t1"] - t_start >= seconds):
                    return
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            errors.append(e)
            go.abort()

    threads = [threading.Thread(target=client_loop, args=(i, c),
                                name=f"bench-client-{i}")
               for i, c in enumerate(clients)]
    for t in threads:
        t.start()
    t_start = time.perf_counter()
    try:
        go.wait()
    except threading.BrokenBarrierError:
        pass
    for t in threads:
        t.join()
    for c in clients:
        c.close()
    if errors:
        raise errors[0]
    return records, max(r["t1"] for r in records) - t_start


def end_to_end(records, elapsed: float, statements: dict, data) -> dict:
    """``rows_per_s``: rows of the base tables each completed statement
    reads, over the window's whole time. ``query_geomean_ms``: per
    statement class all the client-side time over its completions, then
    the geometric mean over classes."""
    done = [r for r in records if r["error"] is None]
    rows = sum(work.statement_rows(statements[r["id"]]["ref"].TABLES, data)
               for r in done)
    by_class = {}
    for r in done:
        by_class.setdefault(r["id"], []).append(r["t1"] - r["t0"])
    mean_ms = {k: 1000.0 * sum(v) / len(v) for k, v in by_class.items()}
    return {"rows_per_s": rows / elapsed,
            "query_geomean_ms": math.exp(
                sum(map(math.log, mean_ms.values())) / len(mean_ms)),
            "per_class_ms": mean_ms,
            "completed": {k: len(v) for k, v in by_class.items()}}


# ---------------- correct ----------------------------------------------


def check_answers(records, statements: dict, data) -> dict:
    """Every answer against its statement's reference, each distinct
    answer compared once. Returns the numbers compared."""
    refs, verdicts = {}, {}
    out = {"wrong_cells": 0, "missing_answers": 0}
    for r in records:
        if r["error"] is not None:
            out["missing_answers"] += 1
            continue
        ref = statements[r["id"]]["ref"]
        key = (r["id"], tuple(r["names"]), tuple(map(tuple, r["rows"])))
        if key not in verdicts:
            if r["id"] not in refs:
                refs[r["id"]] = ref.reference(data)
            got = compare.decode(r["names"], r["rows"], ref.COLUMNS,
                                 data.dicts)
            verdicts[key] = compare.compare(got, refs[r["id"]],
                                            ref.COLUMNS)
        v = verdicts[key]
        out["wrong_cells"] += v["wrong_cells"]
        if hasattr(ref, "RATIO_REL_GAP_LIMIT"):
            out["ratio_rel_gap"] = max(out.get("ratio_rel_gap", 0.0),
                                       v["ratio_rel_gap"])
    return out


def limits_for(statements: dict) -> dict:
    """Each number compared beside its limit: exact comparisons hold at
    0, a quotient's gap at the tightest limit a statement's reference
    file states."""
    limits = dict.fromkeys(("wrong_cells", "missing_answers",
                            "count_mismatch_tables", "upsert_extra_rows",
                            "upsert_stale_rows", "resident_errors",
                            "compiles_inside_the_window",
                            "unexpected_executor_statements"), 0)
    ratio = [s["ref"].RATIO_REL_GAP_LIMIT for s in statements.values()
             if hasattr(s["ref"], "RATIO_REL_GAP_LIMIT")]
    if ratio:
        limits["ratio_rel_gap"] = min(ratio)
    return limits


# ---------------- one run ----------------------------------------------


def per_layer(cell: dict, run: dict) -> dict:
    """The cell's per-layer metrics, each from its own reader; one that
    finds nothing to read is left out of the line."""
    metrics = {}
    for m in cell["per_layer"]:
        v = load_module(cell["dir"], "layer_metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics


def run_cell(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Everything after the look for a chip; returns the result object."""
    import jax

    from ydb_tpu.api.pgwire import PgWireServer
    from ydb_tpu.kqp.session import Cluster

    config, traffic = cell["config"], cell["traffic"]
    cache_dir = configure_compile_cache()
    compiles = CompileCounter()
    info = device_info()
    say(f"device: platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    say(f"host: cpus={os.cpu_count()} loadavg={os.getloadavg()}")
    split = {"import": time.perf_counter() - T0}
    base = cell["dir"]
    statements = load_statements(base, traffic["statements"])

    t0 = time.perf_counter()
    data = load_module(base, "", config["generator"]).make(
        config["scale_factor"], seed, **config.get("generator_options", {}))
    split["generate"] = time.perf_counter() - t0
    say(f"generate: scale_factor={config['scale_factor']} seed={seed} "
        f"seconds={split['generate']:.2f}")

    cluster = Cluster()
    pg = None
    try:
        session = cluster.session()
        t0 = time.perf_counter()
        readings = deploy.build(cluster, session, data, config, say)
        if config.get("mesh"):      # as chip_smoke.py does: after the load
            cluster.enable_mesh()
            deploy.drain_promotions(cluster)
        split["deploy"] = time.perf_counter() - t0
        pg = PgWireServer(cluster, port=0).start()

        # warm every statement over the wire; its answers are checked too
        t0 = time.perf_counter()
        warm_records = []
        client = pgclient.PgClient(pg.port)
        for sid in statements:
            for i in range(traffic["warm_rounds"]):
                c0, w0 = compiles.n, time.perf_counter()
                names, rows = client.query(statements[sid]["sql"])
                warm_records.append({"id": sid, "names": names,
                                     "rows": rows, "error": None})
                prof = cluster.profiles.recent()[-1]
                say(f"warm {sid} #{i + 1}: executor={executor_of(prof)} "
                    f"seconds={time.perf_counter() - w0:.3f} "
                    f"built_or_fetched={compiles.n - c0} "
                    f"compile_cache={prof.compile_cache or '-'} "
                    f"rows={len(rows)}")
        client.close()
        deploy.drain_promotions(cluster)
        split["warm"] = time.perf_counter() - t0

        last_seq = [cluster.profiles.recent()[-1].seq]

        def server_side(rec) -> None:
            """The statement's own QueryProfile from the cluster's ring
            (traced runs only: a lock and a copy between statements)."""
            new = [p for p in cluster.profiles.recent()
                   if p.seq > last_seq[0]]
            if new:
                last_seq[0] = new[-1].seq
                rec["server_s"] = new[-1].seconds
                rec["stages"] = dict(new[-1].stages)

        resident_before = deploy.resident_totals(cluster)
        c_before = compiles.n
        if trace:
            seconds = min(seconds, traffic["trace_seconds"])
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        setup_s = time.perf_counter() - T0
        try:
            records, elapsed = drive(
                pg.port, traffic, statements, seconds,
                after_each=server_side if trace else None, annotate=trace)
        finally:
            if trace:
                jax.profiler.stop_trace()
        readings["compiles_inside_the_window"] = compiles.n - c_before
        # the warm-up's and the window's statements, as far as the ring
        # still holds them
        readings["unexpected_executor_statements"] = unexpected_executors(
            cluster.profiles.recent(), statements,
            traffic.get("executors", {}))
        peak = memory_peak_bytes()
        resident_after = deploy.resident_totals(cluster)
        bc = cluster.scan_block_cache
        say(f"window: seconds={elapsed:.3f} statements={len(records)} "
            f"compiles_inside_the_window="
            f"{readings['compiles_inside_the_window']} "
            f"memory_peak_bytes={peak}")
        say(f"resident after the window: {resident_after}")
        say(f"block cache: entries={len(bc)} hits={bc.hits} "
            f"misses={bc.misses}")
        readings["resident_errors"] = resident_after["errors"]
    finally:
        if pg is not None:
            pg.stop()
        cluster.stop()
    del cluster, session, pg
    gc.collect()

    # the reference runs once the window has closed, the peak has been
    # read and the deployment is freed; it is not part of setup_s
    t0 = time.perf_counter()
    readings.update(check_answers(warm_records + records, statements, data))
    say(f"references and comparison: seconds="
        f"{time.perf_counter() - t0:.2f}")
    limits = limits_for(statements)
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in readings}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    e2e = end_to_end(records, elapsed, statements, data)
    e2e["setup_s"] = setup_s
    say("setup split: " + " ".join(f"{k}={v:.2f}" for k, v in split.items())
        + f" setup_s={setup_s:.2f}")
    say("per statement ms: " + " ".join(
        f"{r['id']}={1000 * (r['t1'] - r['t0']):.1f}" for r in records))
    say(f"per class: mean_ms={ {k: round(v, 3) for k, v in e2e['per_class_ms'].items()} } "
        f"completed={e2e['completed']}")

    result = {"correct": correct, "attempted": len(records),
              "failed": sum(r["error"] is not None for r in records),
              "device": dict(info, memory_peak_bytes=peak)}
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.newest_trace(str(TRACE_DIR))))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        say("trace: " + json.dumps({k: reduced[k] for k in (
            "window_s", "busy_s", "devices", "launches", "statements")}))
        done = [r for r in records if r["error"] is None]
        n_bytes = sum(work.statement_bytes(
            statements[r["id"]]["ref"].TABLES, data, data.widths)
            for r in done)
        result["metrics"] = per_layer(cell, {
            "statements": [dict(r, client_s=r["t1"] - r["t0"])
                           for r in done],
            "trace": reduced,
            "resident_delta": {k: resident_after[k] - resident_before[k]
                               for k in resident_after},
            "memory_peak_bytes": peak,
            # peaks exist for a TPU only; the tests drive this on a CPU
            "least_seconds": work.least_seconds(
                n_bytes, work.peaks_for(info["kind"]))
            if info["platform"] == "tpu" else None,
        })
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        check_device(cell["chips"])
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (BenchError, work.UnknownDevice, ImportError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: value={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(f"correct={result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
