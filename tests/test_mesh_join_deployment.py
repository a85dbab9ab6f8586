"""Q3 over the four-chip join deployment of the benchmark
(``tpch-sf1-4chip``'s files: TPC-H hash-sharded into 4 shards, one a
mesh device) at SF 0.01 on four of the virtual CPU devices, answered by
the mesh walk, whose every equi-join hash-repartitions both sides with
``all_to_all`` and joins device-locally; held to the benchmark's plain
numpy reference and to the same statement on one shard, with the
``mesh.shuffle`` / ``mesh.join`` spans, the ``mesh_shuffle`` /
``mesh_join`` statement keys and the counters that say where a
statement's time and bytes went. The cell that times it on four chips
is ``tpch-sf1-4chip.join-mesh`` (PERF.md section 4)."""

import json
import time

import jax
import numpy as np
import pytest

from test_mesh_deployment import (
    BENCH,
    DEVICES,
    SCALE_FACTOR,
    SEED,
    answer,
    bench_module,
    by_name,
    deployed,
    under,
)
from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.obs import profile as profile_mod
from ydb_tpu.obs import tracing
from ydb_tpu.obs.profile import MESH_KEY, MESH_SPAN_KEYS, STATEMENT_KEYS
from ydb_tpu.obs.timeline import movement_snapshot as moved
from ydb_tpu.parallel import mesh_exec
from ydb_tpu.parallel.mesh import make_mesh
from ydb_tpu.parallel.shuffle import exchange_bytes_per_device
from ydb_tpu.plan import execute_plan, to_host
from ydb_tpu.plan.nodes import LookupJoin, TableScan, Transform
from ydb_tpu.ssa import plan_fuse
from ydb_tpu.ssa.ops import Agg
from ydb_tpu.ssa.program import AggSpec, GroupByStep, Program, ProjectStep

#: what a cell that times Q3 over the mesh would hold it to
EXPECTED = {"q3": "mesh-walk"}
BLOCK_ROWS = 4096       # ~15K lineitem rows a shard: 4 blocks a scan
NINE = STATEMENT_KEYS + tuple(MESH_SPAN_KEYS.values())
SHUFFLE_ATTRS = {"keys", "capacity", "bucket_rows", "bucket_rows_final",
                 "worst", "attempts", "bytes_per_device"}
JOIN_ATTRS = {"kind", "expand", "probe_capacity", "build_capacity",
              "out_capacity", "attempts"}
JOIN_PROGRAMS = ("mesh_repartition", "mesh_lookup", "mesh_match",
                 "mesh_expand")


@pytest.fixture(scope="module")
def run():
    """``bench/run.py`` as a module (it puts ``bench/`` on the path)."""
    return bench_module("run.py")


@pytest.fixture(scope="module")
def deployment(run):
    """``(data, Q3, sharded cluster on a 4-device mesh, the same data on
    one shard)`` from the four-chip configuration's own files, the
    tables too large for the fused executors, resident as on the chip."""
    mp = pytest.MonkeyPatch()
    mp.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    mp.setattr(resident_mod, "RESIDENT_FORCE", True)
    config = json.loads(
        (BENCH / "configs" / "tpch-sf1-4chip.json").read_text())
    assert config["mesh"] is True and config["chips"] == DEVICES
    data = bench_module(config["generator"] + ".py").make(
        SCALE_FACTOR, SEED, **config["generator_options"])
    q3 = run.load_statements(BENCH, ["q3"])["q3"]
    sharded = deployed(data, config, DEVICES)
    sharded.enable_mesh(make_mesh(DEVICES, devices=jax.devices()))
    for t in sharded.tables.values():
        for sh in t.shards:
            sh.resident.drain()
    one = deployed(data, config, 1)
    try:
        yield data, q3, sharded, one
    finally:
        sharded.stop()
        one.stop()
        mp.undo()


@pytest.fixture
def several_blocks(monkeypatch):
    """Each side's shard in several blocks, as ``lineitem`` is at SF 1:
    a shard's scan compacts each and concatenates them."""
    monkeypatch.setattr(mesh_exec, "DEFAULT_BLOCK_ROWS", BLOCK_ROWS)
    return BLOCK_ROWS


def warm_profile(cluster, sql: str):
    s = cluster.session()
    s.execute(sql)          # compiles
    s.execute(sql)
    return s.last_profile


@pytest.mark.parametrize("blocks", ("one_block", "several_blocks"))
def test_q3_equals_the_plain_reference_and_one_shard(deployment, blocks,
                                                     request):
    if blocks == "several_blocks":
        request.getfixturevalue(blocks)
    data, q3, sharded, one = deployment
    s = sharded.session()
    got = answer(s.execute(q3["sql"]))
    if blocks == "several_blocks":
        # the sides' scans met more than one block a shard
        assert len(by_name(s.last_profile, "host.concat")) >= DEVICES
    want = q3["ref"].reference(data)
    assert list(got) == list(want) == list(q3["ref"].COLUMNS)
    assert len(got["l_orderkey"]) == 10
    for name in want:
        assert np.array_equal(got[name], want[name]), name
    alone = answer(one.session().execute(q3["sql"]))
    for name in want:
        assert got[name].dtype == alone[name].dtype
        assert got[name].tobytes() == alone[name].tobytes(), name


def test_the_cells_files_load_and_differ_from_the_scan_mesh_cells_in_scale(
        run):
    """``bench/run.py`` finds the cell by name; its configuration is
    ``tpch-sf3-4chip.json`` but for the scale factor and its reason, its
    traffic Q3 alone in a closed loop, held to the mesh walk."""
    cell = run.load_cell("tpch-sf1-4chip.join-mesh")
    assert cell["chips"] == DEVICES
    # its own four, then the nine of the write path that every cell
    # lists since PR 39 (they read the set-up's writes)
    assert [m["name"] for m in cell["per_layer"]] == [
        "mesh_shuffle_ms", "mesh_join_ms", "mesh_join_scan_ms",
        "mesh_join_roofline_share", "write_rows_per_s", "write_sort_ms",
        "write_blob_ms", "write_index_ms", "write_route_ms",
        "promote_gb_per_s", "resident_lag_ms", "promote_declined",
        "compile_built_s"]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "rows_per_s", "query_geomean_ms", "setup_s"}
    assert cell["traffic"] == {
        "loop": "closed", "clients": 1, "statements": ["q3"],
        "warm_rounds": 1, "trace_seconds": 12, "executors": EXPECTED}
    four, scan = cell["config"], json.loads(
        (BENCH / "configs" / "tpch-sf3-4chip.json").read_text())
    assert {k for k in set(four) | set(scan)
            if four.get(k) != scan.get(k)} == {"scale_factor", "assumed"}
    assert (four["scale_factor"], scan["scale_factor"]) == (1, 3)
    assert four["published"] == {"scale_factor": 50}
    reason = {"why_scale_factor_1", "why_scale_factor_3"}
    assert set(four["assumed"]) ^ set(scan["assumed"]) == reason
    assert all(four["assumed"][k] == scan["assumed"][k]
               for k in set(four["assumed"]) - reason)
    entry = {c["name"]: c for c in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["configs"]}
    assert entry["tpch-sf1-4chip"]["reduced"] == ["scale_factor"]
    assert entry["tpch-sf1-4chip"]["file"] == \
        "bench/configs/tpch-sf1-4chip.json"


def test_q3_is_the_mesh_walk_by_the_benchmarks_rule(deployment, run):
    _, q3, sharded, one = deployment
    p = warm_profile(sharded, q3["sql"])
    (mesh,) = by_name(p, "mesh")
    assert mesh["attrs"]["answered"] == 1
    assert mesh["attrs"]["devices"] == DEVICES
    assert run.executor_of(p) == "mesh-walk"
    assert run.unexpected_executors([p], {"q3": q3}, EXPECTED) == 0
    alone = warm_profile(one, q3["sql"])
    assert run.executor_of(alone) != "mesh-walk"
    assert run.unexpected_executors([alone], {"q3": q3}, EXPECTED) == 1


def test_four_exchanges_and_two_local_joins_with_their_attrs(deployment):
    _, q3, sharded, _ = deployment
    p = warm_profile(sharded, q3["sql"])
    (mesh,) = by_name(p, "mesh")
    shuffles, joins = by_name(p, "mesh.shuffle"), by_name(p, "mesh.join")
    assert [sp["attrs"]["keys"] for sp in shuffles] == [
        "c_custkey", "o_custkey", "o_orderkey", "l_orderkey"]
    assert len(joins) == 2
    assert all(sp["parent_id"] == mesh["span_id"]
               for sp in shuffles + joins)
    for sp in shuffles:
        a = sp["attrs"]
        assert set(a) == SHUFFLE_ATTRS
        assert 1 <= a["bucket_rows"] <= a["bucket_rows_final"]
        assert 0 < a["worst"] <= a["bucket_rows_final"]
        assert a["attempts"] == 1 + (a["bucket_rows"]
                                     < a["bucket_rows_final"])
        # rows a device holds, not the stacked block's device axis
        assert a["capacity"] >= 1024 and a["capacity"] % 1024 == 0
    for sp in joins:
        a = sp["attrs"]
        assert set(a) == JOIN_ATTRS
        assert a["kind"] == "inner" and a["expand"] == 1
        # the match is sized by its sides alone and the emit by the
        # totals read back: nothing is guessed, nothing runs twice
        assert a["attempts"] == 1
        assert min(a["probe_capacity"], a["build_capacity"],
                   a["out_capacity"]) >= 1024
    # every exchange and local join is enqueued beneath its own span,
    # and so are the waits for its counts: the mesh span keeps the
    # placements, the collective step and the answer's copy out
    owners = {sp["span_id"]: sp for sp in shuffles + joins}
    dispatched = [sp for sp in by_name(p, "dispatch")
                  if sp["attrs"]["program"] in JOIN_PROGRAMS]
    assert all(sp["parent_id"] in owners for sp in dispatched)
    per_owner = {i: 0 for i in owners}
    for sp in dispatched:
        per_owner[sp["parent_id"]] += 1
    assert all(per_owner[sp["span_id"]] == sp["attrs"]["attempts"]
               for sp in shuffles)
    for sp in joins:    # the sort-bearing match, then the emit
        assert [d["attrs"]["program"] for d in dispatched
                if d["parent_id"] == sp["span_id"]] == [
            "mesh_match", "mesh_expand"]
    beneath = under(p, set(owners))
    assert {sp["name"] for sp in beneath} == {"dispatch", "device.wait"}
    own = [sp["attrs"]["program"] for sp in by_name(p, "dispatch")
           if sp["parent_id"] == mesh["span_id"]]
    assert own == ["mesh_place"] * 4 + ["mesh_step"]


def test_the_nine_keys_sum_to_seconds_and_hold_their_own_spans(deployment):
    _, q3, sharded, _ = deployment
    p = warm_profile(sharded, q3["sql"])
    assert set(NINE) <= set(p.stages)
    assert sum(p.stages[k] for k in NINE) == pytest.approx(
        p.seconds, abs=max(0.01 * p.seconds, 2e-4))
    # spans nest on one thread here: a leaf's seconds are its self time
    leaves = ("dispatch", "device.wait", "device.get")
    for name, key in (("mesh.shuffle", "mesh_shuffle"),
                      ("mesh.join", "mesh_join")):
        ids = {sp["span_id"] for sp in by_name(p, name)}
        own = sum(sp["seconds"] for sp in under(p, ids)
                  if sp["name"] in leaves)
        assert p.stages[key] == pytest.approx(own, abs=1e-4)
        assert p.stages[key] > 0
    (mesh,) = by_name(p, "mesh")
    scans = {sp["span_id"] for sp in by_name(p, "scan")}
    carved = {sp["span_id"] for sp in under(
        p, scans | {sp["span_id"] for sp in by_name(p, "mesh.shuffle")
                    + by_name(p, "mesh.join")})}
    rest = sum(sp["seconds"] for sp in under(p, {mesh["span_id"]})
               if sp["name"] in leaves and sp["span_id"] not in carved)
    assert p.stages[MESH_KEY] == pytest.approx(rest, abs=1e-4)
    assert p.stages[MESH_KEY] > 0


@pytest.mark.parametrize("sid", ("q1", "q6"))
def test_a_statement_that_joins_nothing_has_neither_key(deployment, run,
                                                        sid):
    """Q1 and Q6 on the same deployment: the ``mesh`` key by the rule it
    had before the two were carved out of it, and no more keys."""
    _, _, sharded, _ = deployment
    st = run.load_statements(BENCH, [sid])[sid]
    p = warm_profile(sharded, st["sql"])
    assert not by_name(p, "mesh.shuffle") and not by_name(p, "mesh.join")
    assert "mesh_shuffle" not in p.stages and "mesh_join" not in p.stages
    seven = STATEMENT_KEYS + (MESH_KEY,)
    assert sum(p.stages[k] for k in seven) == pytest.approx(
        p.seconds, abs=max(0.01 * p.seconds, 2e-4))
    (mesh,) = by_name(p, "mesh")
    scans = {sp["span_id"] for sp in by_name(p, "scan")}
    beneath = {sp["span_id"] for sp in under(p, scans)}
    own = sum(sp["seconds"] for sp in under(p, {mesh["span_id"]})
              if sp["name"] in ("dispatch", "device.wait", "device.get")
              and sp["span_id"] not in beneath)
    assert p.stages[MESH_KEY] == pytest.approx(own, abs=1e-4)
    text = sharded.session().execute("EXPLAIN ANALYZE " + st["sql"])
    line = next(ln for ln in text.splitlines()
                if ln.startswith("statement: "))
    assert [kv.split("=")[0] for kv in line.split()[1:]] == list(seven)


def test_a_statement_off_the_mesh_has_none_of_the_three(deployment):
    _, q3, _, one = deployment
    p = warm_profile(one, q3["sql"])
    assert not set(MESH_SPAN_KEYS.values()) & set(p.stages)


def test_the_counters_rise_by_what_the_spans_say(deployment):
    _, q3, sharded, _ = deployment
    s = sharded.session()
    s.execute(q3["sql"])
    before, report0 = moved(), sharded.mesh_report()
    s.execute(q3["sql"])
    after, report1 = moved(), sharded.mesh_report()
    shuffles = by_name(s.last_profile, "mesh.shuffle")
    sent = sum(sp["attrs"]["bytes_per_device"] for sp in shuffles)
    again = sum(sp["attrs"]["attempts"] - 1 for sp in shuffles)
    assert sent > 0
    for d in range(DEVICES):
        key = f"shuffle_bytes_dev{d}"
        assert after[key] - before.get(key, 0) == sent
        assert report1[d]["shuffle_bytes"] - report0[d]["shuffle_bytes"] \
            == sent
        assert report1[d]["shuffle_grows"] - report0[d]["shuffle_grows"] \
            == again
    assert after.get("shuffle_grows", 0) - before.get("shuffle_grows", 0) \
        == again


def test_explain_analyze_shows_each_exchange_and_local_join(deployment):
    _, q3, sharded, _ = deployment
    text = sharded.session().execute("EXPLAIN ANALYZE " + q3["sql"])
    line = next(ln for ln in text.splitlines()
                if ln.startswith("statement: "))
    got = dict(kv.split("=") for kv in line.split()[1:])
    assert list(got) == list(NINE)
    assert float(got["mesh_shuffle"]) > 0 and float(got["mesh_join"]) > 0
    shuffles = [ln for ln in text.splitlines()
                if ln.startswith("  mesh.shuffle: ")]
    assert len(shuffles) == 4
    for ln in shuffles:
        kv = dict(b.split("=") for b in ln.split()[1:])
        assert {"keys", "attempts", "bytes_per_device", "worst",
                "bucket_rows", "bucket_rows_final"} <= set(kv)
        assert int(kv["attempts"]) >= 1
        assert int(kv["bytes_per_device"]) > 0
    joins = [ln for ln in text.splitlines()
             if ln.startswith("  mesh.join: ")]
    assert len(joins) == 2 and all("expand=1" in ln for ln in joins)


def _skewed(n: int = 24000):
    """``n`` fact rows of which every second carries ONE join key, and a
    dimension row a distinct key."""
    ids = np.arange(n, dtype=np.int64)
    keys = np.where(ids % 2 == 0, 7, 1000 + ids)
    return ({"f_id": ids, "f_key": keys, "f_val": ids % 13},
            {"d_key": np.unique(keys), "d_w": np.unique(keys) % 5 + 1})


def test_the_first_bucket_is_sized_from_the_rows_a_device_holds(deployment):
    """A stacked block's ``capacity`` is its device axis: sized from it,
    every first bucket was 4 rows and every exchange ran twice (PR 35's
    chip trace). Q3's keys spread evenly, so each exchange is made once,
    in a bucket of at least the mean load, and comes out no wider than
    the shape class of what a device received."""
    _, q3, sharded, _ = deployment
    grows = moved().get("shuffle_grows", 0)
    p = warm_profile(sharded, q3["sql"])
    shuffles = [sp["attrs"] for sp in by_name(p, "mesh.shuffle")]
    assert len(shuffles) == 4
    for a in shuffles:
        assert a["attempts"] == 1
        assert a["capacity"] / DEVICES <= a["bucket_rows"] <= a["capacity"]
        assert a["worst"] <= a["bucket_rows"] == a["bucket_rows_final"]
    assert moved().get("shuffle_grows", 0) == grows
    for sp in by_name(p, "mesh.join"):
        a = sp["attrs"]
        # a side is its exchange's output, sliced to what was received
        assert a["probe_capacity"] < DEVICES * max(
            x["bucket_rows"] for x in shuffles)
        assert a["out_capacity"] == plan_fuse.shape_class(a["out_capacity"])


def test_a_key_on_half_the_rows_overflows_and_answers_what_one_shard_does(
        deployment):
    _, _, sharded, one = deployment
    fact, dim = _skewed()
    for c in (sharded, one):
        s = c.session()
        shards = len(next(iter(c.tables.values())).shards)
        for ddl in (
                "CREATE TABLE fact (f_id int64 NOT NULL, f_key int64 NOT "
                "NULL, f_val int64 NOT NULL, PRIMARY KEY (f_id))",
                "CREATE TABLE dim (d_key int64 NOT NULL, d_w int64 NOT "
                "NULL, PRIMARY KEY (d_key))"):
            s.execute(f"{ddl} WITH (store = column, shards = {shards})")
        assert c.tables["fact"].insert(dict(fact)).committed
        assert c.tables["dim"].insert(dict(dim)).committed
    sql = ("SELECT f_val, count(*) AS c, sum(f_val * d_w) AS x FROM fact "
           "JOIN dim ON f_key = d_key GROUP BY f_val ORDER BY f_val")
    s = sharded.session()
    s.execute(sql)
    grows = moved().get("shuffle_grows", 0)
    got = answer(s.execute(sql))
    p = s.last_profile
    (mesh,) = by_name(p, "mesh")
    assert mesh["attrs"]["answered"] == 1 and not by_name(p, "plan.fuse")
    (probe,) = [sp["attrs"] for sp in by_name(p, "mesh.shuffle")
                if sp["attrs"]["keys"] == "f_key"]
    # half of every device's rows go to one destination: far above the
    # mean load x margin a uniform key would need
    held = max(d["tables"]["fact"]["rows"] for d in sharded.mesh_report())
    assert probe["worst"] >= held // 2 > 1.5 * held / DEVICES
    assert probe["attempts"] == 2
    assert probe["bucket_rows"] < probe["worst"] \
        <= probe["bucket_rows_final"]
    again = sum(sp["attrs"]["attempts"] - 1
                for sp in by_name(p, "mesh.shuffle"))
    assert again >= 1
    assert moved()["shuffle_grows"] - grows == again
    want = answer(one.session().execute(sql))
    assert list(got) == list(want)
    for name in want:
        assert got[name].tobytes() == want[name].tobytes(), name
    assert got["c"].sum() == len(fact["f_id"])


def _semi_join_of_orders_with_themselves():
    """A ``LookupJoin`` (the planner makes Q3's joins expanding ones):
    every order matches itself, under an aggregating root."""
    scan = TableScan("orders", Program((
        ProjectStep(("o_orderkey", "o_totalprice")),)))
    return Transform(
        LookupJoin(scan, scan, ("o_orderkey",), ("o_orderkey",),
                   kind="semi"),
        Program((GroupByStep(
            (), (AggSpec(Agg.SUM, "o_totalprice", "x"),
                 AggSpec(Agg.COUNT_ALL, None, "c"))),)))


def test_a_lookup_join_has_its_span_too(deployment):
    data, _, sharded, one = deployment
    with profile_mod.profiled() as held:
        got = to_host(execute_plan(_semi_join_of_orders_with_themselves(),
                                   sharded.snapshot_db()))
    p = held.profile
    (join,) = by_name(p, "mesh.join")
    assert set(join["attrs"]) == JOIN_ATTRS
    assert join["attrs"]["kind"] == "semi"
    assert join["attrs"]["expand"] == 0 and join["attrs"]["attempts"] == 1
    (lookup,) = [sp for sp in by_name(p, "dispatch")
                 if sp["attrs"]["program"] == "mesh_lookup"]
    assert lookup["parent_id"] == join["span_id"]
    assert len(by_name(p, "mesh.shuffle")) == 2
    assert p.stages["mesh_join"] > 0 and p.stages["mesh_shuffle"] > 0
    assert int(np.asarray(got.cols["c"][0])[0]) == data.rows("orders")
    want = to_host(execute_plan(_semi_join_of_orders_with_themselves(),
                                one.snapshot_db()))
    assert np.asarray(got.cols["x"][0]).tobytes() == \
        np.asarray(want.cols["x"][0]).tobytes()


def test_the_span_bytes_are_the_exchanges_static_shape(deployment):
    """``bytes_per_device`` is what ``exchange_bytes_per_device`` gives
    for each attempt's bucket size: the shape of the ``all_to_all``,
    not the live rows."""
    _, q3, sharded, _ = deployment
    p = warm_profile(sharded, q3["sql"])
    schema = sharded.tables["customer"].schema.select(("c_custkey",))
    a = by_name(p, "mesh.shuffle")[0]["attrs"]
    sizes = [a["bucket_rows"], a["bucket_rows_final"]][:a["attempts"]]
    assert a["bytes_per_device"] == sum(
        exchange_bytes_per_device(schema, DEVICES, b) for b in sizes)


def test_the_nearest_enclosing_span_decides_a_leafs_key():
    """The rule on a hand-made tree: a leaf beneath a ``scan`` beneath
    the mesh stays the scan's, one beneath ``mesh.shuffle`` /
    ``mesh.join`` goes to that key, the rest beneath ``mesh`` to
    ``mesh``, one outside the mesh to no mesh key."""
    tracer = tracing.Tracer()
    root = tracer.trace("query")

    def leaf(name: str, seconds: float) -> float:
        with tracing.span(name) as sp:
            time.sleep(seconds)
        return sp.seconds       # a leaf: its own time, as measured

    with tracing.activate(root):
        outside = leaf("dispatch", 0.002)
        with tracing.span("mesh"):
            with tracing.span("scan"):
                scanned = leaf("dispatch", 0.003) + leaf("device.wait",
                                                         0.002)
            own = leaf("dispatch", 0.004)
            with tracing.span("mesh.shuffle"):
                shuffled = leaf("dispatch", 0.005) + leaf("device.wait",
                                                          0.003)
            with tracing.span("mesh.join"):
                joined = leaf("dispatch", 0.006)
            own += leaf("device.get", 0.002)
    root.finish()
    spans = tracer.spans_for(root.trace_id)
    got = profile_mod.statement_stages(spans, root.seconds)
    assert list(got) == list(NINE)
    near = lambda want: pytest.approx(want, abs=1e-6)
    assert got["mesh"] == near(own)
    assert got["mesh_shuffle"] == near(shuffled)
    assert got["mesh_join"] == near(joined)
    assert got["dispatch"] + got["device_wait"] == near(outside + scanned)
    assert sum(got.values()) == pytest.approx(root.seconds, abs=1e-6)
    # without the two spans the keys are the seven of before
    seven = profile_mod.statement_stages(
        [sp for sp in spans if not sp.name.startswith("mesh.")],
        root.seconds)
    assert list(seven) == list(STATEMENT_KEYS) + [MESH_KEY]
