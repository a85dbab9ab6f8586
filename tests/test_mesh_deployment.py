"""The four-chip deployment of the benchmark (``tpch-sf3-4chip``: TPC-H
hash-sharded into 4 shards, one shard and its resident slice a mesh
device) at SF 0.01 on four of the virtual CPU devices: created and
loaded as the benchmark does it (``bench/deploy.py``), answered by the
mesh walk, held to the benchmark's plain numpy references and to the
same statements on one shard, with the spans, the ``mesh`` statement key
and the per-device report that say where a statement's time went."""

import collections
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from ydb_tpu.analysis import syncsan
from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs import profile as profile_mod
from ydb_tpu.obs.profile import MESH_KEY, STATEMENT_KEYS
from ydb_tpu.parallel import mesh_exec
from ydb_tpu.parallel.mesh import make_mesh
from ydb_tpu.plan import execute_plan, to_host
from ydb_tpu.plan.nodes import LookupJoin, TableScan, Transform
from ydb_tpu.ssa import plan_fuse
from ydb_tpu.ssa.ops import Agg, Op
from ydb_tpu.ssa.program import (
    AggSpec,
    AssignStep,
    Call,
    Col,
    GroupByStep,
    Program,
    ProjectStep,
    lit,
)

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
SCALE_FACTOR = 0.01
SEED = 2147483999       # the driver's seeds pass 2**31
DEVICES = 4
STATEMENTS = ("q1", "q6")
BLOCK_ROWS = 4096       # ~15K lineitem rows a shard: 4 blocks, 3 folds


def bench_module(relative: str):
    """A file of ``bench/`` loaded by path: the benchmark is no package
    and the program imports nothing of it."""
    path = BENCH / relative
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deployed(data, config: dict, shards: int) -> Cluster:
    deploy = bench_module("deploy.py")
    config = dict(config, table_options=dict(config["table_options"],
                                             shards=shards))
    cluster = Cluster()
    readings = deploy.build(cluster, cluster.session(), data, config,
                            lambda line: None)
    assert readings == {"count_mismatch_tables": 0,
                        "upsert_extra_rows": 0, "upsert_stale_rows": 0}
    return cluster


@pytest.fixture(scope="module")
def deployment():
    """``(data, statements, sharded cluster on a 4-device mesh, the same
    data on one shard)``; the tables too large for the fused executors,
    resident in the device tier as on the chip."""
    mp = pytest.MonkeyPatch()
    mp.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    mp.setattr(resident_mod, "RESIDENT_FORCE", True)
    config = json.loads(
        (BENCH / "configs" / "tpch-sf3-4chip.json").read_text())
    assert config["mesh"] is True and config["chips"] == DEVICES
    assert config["table_options"]["shards"] == DEVICES
    data = bench_module(config["generator"] + ".py").make(
        SCALE_FACTOR, SEED, **config["generator_options"])
    statements = {
        sid: {"sql": (BENCH / "statements" / f"{sid}.sql").read_text()
              .strip(),
              "ref": bench_module(f"refs/{sid}.py")}
        for sid in STATEMENTS}
    sharded = deployed(data, config, DEVICES)
    sharded.enable_mesh(make_mesh(DEVICES, devices=jax.devices()))
    for t in sharded.tables.values():
        for sh in t.shards:
            sh.resident.drain()
    one = deployed(data, config, 1)
    try:
        yield data, statements, sharded, one
    finally:
        sharded.stop()
        one.stop()
        mp.undo()


@pytest.fixture
def several_blocks(monkeypatch):
    """A shard's slice in several blocks, as at SF 3 (at SF 0.01 the
    mesh walk's 2^20-row blocks hold a shard each): every shard folds."""
    monkeypatch.setattr(mesh_exec, "DEFAULT_BLOCK_ROWS", BLOCK_ROWS)
    return BLOCK_ROWS


def answer(res) -> dict:
    return {name: np.asarray(res.cols[name][0])
            for name in res.schema.names}


def mesh_walk_profile(cluster, sql: str):
    s = cluster.session()
    s.execute(sql)          # compiles
    s.execute(sql)
    return s.last_profile


def by_name(profile, name: str) -> list:
    return [sp for sp in profile.spans if sp["name"] == name]


def under(profile, ancestors: set) -> list:
    """The spans of ``profile`` beneath any span of ``ancestors``."""
    by_id = {sp["span_id"]: sp for sp in profile.spans}

    def beneath(sp) -> bool:
        sp = by_id.get(sp["parent_id"])
        while sp is not None:
            if sp["span_id"] in ancestors:
                return True
            sp = by_id.get(sp["parent_id"])
        return False

    return [sp for sp in profile.spans if beneath(sp)]


@pytest.mark.parametrize("blocks", ("one_block", "several_blocks"))
@pytest.mark.parametrize("sid", STATEMENTS)
def test_answers_equal_the_plain_reference_and_one_shard(
        deployment, sid, blocks, request):
    if blocks == "several_blocks":
        request.getfixturevalue(blocks)
    data, statements, sharded, one = deployment
    sql = statements[sid]["sql"]
    got = answer(sharded.session().execute(sql))
    want = statements[sid]["ref"].reference(data)       # exact arithmetic
    assert list(got) == list(want)
    for name, kind in statements[sid]["ref"].COLUMNS.items():
        if kind[0] == "ratio":
            assert got[name] == pytest.approx(want[name], rel=1e-12)
        else:
            assert np.array_equal(got[name], want[name]), name
    # bit for bit what one shard answers, the averages too
    alone = answer(one.session().execute(sql))
    for name in want:
        assert got[name].dtype == alone[name].dtype
        assert got[name].tobytes() == alone[name].tobytes(), name


@pytest.mark.parametrize("sid", STATEMENTS)
def test_the_mesh_walk_answers_and_its_keys_sum_to_seconds(deployment,
                                                            sid):
    _, statements, sharded, _ = deployment
    p = mesh_walk_profile(sharded, statements[sid]["sql"])
    (mesh,) = by_name(p, "mesh")
    assert mesh["attrs"]["answered"] == 1
    assert mesh["attrs"]["devices"] == DEVICES
    assert not by_name(p, "plan.fuse")         # the walk, not mesh-fused
    seven = STATEMENT_KEYS + (MESH_KEY,)
    assert set(seven) <= set(p.stages)
    assert sum(p.stages[k] for k in seven) == pytest.approx(
        p.seconds, abs=max(0.01 * p.seconds, 2e-4))
    assert p.stages[MESH_KEY] > 0
    # what no named leaf covers is the bookkeeping of the mesh span and
    # its four scan spans, 1-2 ms whatever the size: a tenth of the
    # statement at most, once the statement is longer than this one.
    # The best of a few runs: a worker of a loaded machine is preempted
    # between spans as well as inside them
    s = sharded.session()
    least = p.stages["unattributed"]
    for _ in range(4):
        s.execute(statements[sid]["sql"])
        least = min(least, s.last_profile.stages["unattributed"])
    assert least < max(0.10 * p.seconds, 3e-3)


def test_the_mesh_key_holds_the_mesh_spans_apart_from_the_scans(
        deployment):
    _, statements, sharded, _ = deployment
    p = mesh_walk_profile(sharded, statements["q1"]["sql"])
    (mesh,) = by_name(p, "mesh")
    scans = {sp["span_id"] for sp in by_name(p, "scan")}
    assert all(sp["parent_id"] == mesh["span_id"]
               for sp in by_name(p, "scan"))
    programs = {sp["attrs"].get("program"): sp["parent_id"]
                for sp in by_name(p, "dispatch")}
    assert {"mesh_place", "mesh_step", "scan_partial"} <= set(programs)
    assert programs["scan_partial"] in scans
    assert programs["mesh_place"] not in scans
    assert programs["mesh_step"] not in scans
    # the wait for the collective step and the answer's copy out are
    # the mesh's own, and a statement's only ones: the shards' row
    # counts are not waited for, their rows never leave the devices
    own = [sp for sp in p.spans
           if sp["name"] in ("device.wait", "device.get")]
    assert sorted(sp["name"] for sp in own) == ["device.get",
                                                "device.wait"]
    assert all(sp["parent_id"] == mesh["span_id"] for sp in own)
    # spans nest on one thread here: a leaf's seconds are its self time
    beneath = {sp["span_id"] for sp in under(p, scans)}
    leaves = [sp for sp in p.spans
              if sp["name"] in ("dispatch", "device.wait", "device.get")]
    own_s = sum(sp["seconds"] for sp in leaves
                if sp["span_id"] not in beneath)
    scan_s = sum(sp["seconds"] for sp in leaves
                 if sp["span_id"] in beneath)
    # (each rounded to the microsecond in the profile)
    assert p.stages[MESH_KEY] == pytest.approx(own_s, abs=1e-4)
    assert p.stages["dispatch"] + p.stages["device_wait"] == \
        pytest.approx(scan_s, abs=1e-4)


@pytest.mark.parametrize("sid", STATEMENTS)
def test_every_shard_aggregates_its_blocks_on_its_own_device(
        deployment, several_blocks, sid):
    """The aggregate pushdown on the mesh walk: one program a resident
    block, nothing waited for or fetched beneath a shard's scan, no
    program built or fetched by a second run."""
    _, statements, sharded, _ = deployment
    sql = statements[sid]["sql"]
    s = sharded.session()
    s.execute(sql)
    with syncsan.activate():
        s.execute(sql)
        p = s.last_profile
        text = s.execute("EXPLAIN ANALYZE " + sql)
        again = s.last_profile
    (mesh,) = by_name(p, "mesh")
    scans = by_name(p, "scan")
    assert len(scans) == DEVICES
    assert all(sp["attrs"]["agg_pushdown"] == 1 for sp in scans)
    assert mesh["attrs"]["agg_pushdown"] == DEVICES
    assert mesh["attrs"]["answered"] == 1 and not by_name(p, "plan.fuse")
    assert p.agg_pushdown == DEVICES
    beneath = under(p, {sp["span_id"] for sp in scans})
    assert not [sp["name"] for sp in beneath
                if sp["name"] in ("host.concat", "device.get",
                                  "device.wait")]
    assert not by_name(p, "host.concat") and not by_name(p, "transform")
    # one program a block, the fold inside it; then the placement and
    # the collective step
    blocks = sum(-(-d["tables"]["lineitem"]["rows"] // several_blocks)
                 for d in sharded.mesh_report())
    assert blocks >= 3 * DEVICES        # every shard folds
    partials = [sp for sp in by_name(p, "dispatch")
                if sp["attrs"]["program"] == "scan_partial"]
    assert len(partials) == blocks
    assert len(by_name(p, "dispatch")) <= blocks + 2 * DEVICES + 2
    # the CPU stand-in for compiles_inside_the_window
    assert p.syncsan["compiles"] == 0 and again.syncsan["compiles"] == 0
    assert p.compile_cache == "hit"
    # EXPLAIN ANALYZE shows both: a line a shard's scan, one the mesh's
    assert text.count("agg_pushdown=1") == DEVICES
    (line,) = [ln for ln in text.splitlines()
               if ln.startswith("  mesh: ")]
    assert f"devices={DEVICES} answered=1" in line
    assert line.endswith(f"agg_pushdown={DEVICES}")


@pytest.mark.parametrize("sid", STATEMENTS)
def test_every_shard_assembles_its_blocks_on_its_own_device(
        deployment, several_blocks, monkeypatch, sid):
    """The resident tier cuts a shard's blocks by one program each
    (``resident._assemble``) that runs where the shard's columns lie:
    every output of every call is on the device of its pieces, the four
    shards use the four devices, and each shard's ``scan`` span counts
    its own calls."""
    _, statements, sharded, _ = deployment
    calls = collections.Counter()
    real = resident_mod._assemble

    def placed(datas, valids, bounds, *, cap):
        out_d, out_v, length = real(datas, valids, bounds, cap=cap)
        (dev,) = datas[0][0].devices()
        assert all(a.devices() == {dev} for piece in datas + valids
                   for a in piece)
        assert all(a.devices() == {dev}
                   for a in out_d + out_v + (length,))
        assert cap == several_blocks
        calls[dev] += 1
        return out_d, out_v, length

    monkeypatch.setattr(resident_mod, "_assemble", placed)
    s = sharded.session()
    s.execute(statements[sid]["sql"])
    calls.clear()
    s.execute(statements[sid]["sql"])
    scans = by_name(s.last_profile, "scan")
    assert len(scans) == DEVICES
    devices = jax.devices()[:DEVICES]
    assert set(calls) == set(devices)
    for sp, report in zip(scans, sharded.mesh_report()):
        blocks = -(-report["tables"]["lineitem"]["rows"] // several_blocks)
        attrs = sp["attrs"]
        assert attrs["resident_blocks_assembled"] == blocks
        assert attrs["resident_blocks_whole"] == 0
        assert calls[devices[attrs["device"]]] == blocks


AGGS = ("sum(l_extendedprice) AS sv, count(l_tax) AS cn, count(*) AS c, "
        "avg(l_discount) AS av, min(l_quantity) AS mn, "
        "max(l_shipdate) AS mx")
#: aggregating SELECTs over one table that take the pushdown on the
#: mesh, beside the benchmark's Q1 and Q6: the shapes of
#: tests/test_agg_pushdown.py's PUSHED over the deployment's tables
PUSHED = {
    "keyed": (f"SELECT l_returnflag, l_linestatus, {AGGS} FROM lineitem "
              "WHERE l_quantity >= 10 GROUP BY l_returnflag, l_linestatus"),
    "keyless": f"SELECT {AGGS} FROM lineitem WHERE l_quantity >= 10",
    "nothing_selected_keyless": (f"SELECT {AGGS} FROM lineitem "
                                 "WHERE l_quantity < 0"),
    "nothing_selected_keyed": (f"SELECT l_shipmode, {AGGS} FROM lineitem "
                               "WHERE l_quantity < 0 GROUP BY l_shipmode"),
    "having": ("SELECT l_shipmode, l_linestatus, sum(l_tax) AS st "
               "FROM lineitem GROUP BY l_shipmode, l_linestatus "
               "HAVING sum(l_tax) > 100"),
    "order_limit": ("SELECT l_shipmode, l_returnflag, count(*) AS c "
                    "FROM lineitem GROUP BY l_shipmode, l_returnflag "
                    "ORDER BY c DESC, l_shipmode, l_returnflag LIMIT 3"),
    "string_min": ("SELECT l_returnflag, min(l_shipmode) AS lo, "
                   "max(l_shipinstruct) AS hi FROM lineitem "
                   "GROUP BY l_returnflag ORDER BY lo, l_returnflag"),
    "distinct_dense": "SELECT DISTINCT o_orderstatus, o_orderpriority "
                      "FROM orders",
}


@pytest.mark.parametrize("case", sorted(PUSHED))
def test_pushed_down_shapes_answer_what_one_shard_answers(
        deployment, several_blocks, case):
    _, _, sharded, one = deployment
    s = sharded.session()
    got = s.execute(PUSHED[case])
    p = s.last_profile
    (mesh,) = by_name(p, "mesh")
    assert mesh["attrs"]["answered"] == 1
    assert mesh["attrs"]["agg_pushdown"] == DEVICES
    assert p.agg_pushdown == DEVICES and not by_name(p, "host.concat")
    want = one.session().execute(PUSHED[case])
    # names, types and scales (the mesh's slot-aligned states make
    # every column nullable in the schema; the masks are compared below)
    assert [(f.name, f.type) for f in got.schema.fields] == \
        [(f.name, f.type) for f in want.schema.fields]
    ordered = "ORDER BY" in PUSHED[case]
    got_cols = answer(got) if ordered else _rows_in_order(got)
    want_cols = answer(want) if ordered else _rows_in_order(want)
    for name in want_cols:
        assert got_cols[name].dtype == want_cols[name].dtype
        assert got_cols[name].tobytes() == want_cols[name].tobytes(), name
    for name in got.schema.names:       # the validity masks too
        assert np.array_equal(np.sort(got.cols[name][1]),
                              np.sort(want.cols[name][1])), name
    rows = len(next(iter(want_cols.values())))
    assert rows == {"nothing_selected_keyless": 1,
                    "nothing_selected_keyed": 0}.get(case, rows)
    if case == "string_min":
        # the aggregate's output decodes through its input's dictionary
        assert got.strings("lo") == want.strings("lo")
        assert got.strings("lo") == sorted(got.strings("lo"))


def _sum_by_flag(column: str) -> Program:
    return Program((GroupByStep(
        ("l_returnflag",), (AggSpec(Agg.SUM, column, "x"),
                            AggSpec(Agg.COUNT_ALL, None, "c"))),))


def _scan_without_a_program():
    return Transform(
        TableScan("lineitem", None, ("l_quantity", "l_returnflag")),
        _sum_by_flag("l_quantity"))


def _scan_read_twice():
    """One TableScan node read by both sides of a join (every order
    matches itself), under an aggregating root."""
    scan = TableScan("orders", Program((
        ProjectStep(("o_orderkey", "o_totalprice", "o_shippriority")),)))
    return Transform(
        LookupJoin(scan, scan, ("o_orderkey",), ("o_orderkey",),
                   kind="semi"),
        Program((GroupByStep(
            (), (AggSpec(Agg.SUM, "o_totalprice", "x"),
                 AggSpec(Agg.COUNT_ALL, None, "c"))),)))


def _elementwise_below_the_root():
    """An elementwise Transform that stays sharded (``mesh_xform``)
    between the scan and the aggregating root."""
    return Transform(
        Transform(
            TableScan("lineitem", Program((ProjectStep(
                ("l_quantity", "l_returnflag")),))),
            Program((AssignStep("twice", Call(Op.MUL, Col("l_quantity"),
                                              lit(2))),))),
        _sum_by_flag("twice"))


#: the shapes that keep the mesh walk's old path (the shards' rows are
#: scanned out, placed over the mesh and aggregated in the collective
#: step), as tests/test_agg_pushdown.py's KEPT are the one-chip walk's:
#: SQL text, or a hand-made plan where the planner makes no such shape
KEPT = {
    "sort_derived_layout": (
        "SELECT l_orderkey, sum(l_quantity) AS q, count(*) AS c "
        "FROM lineitem GROUP BY l_orderkey"),
    "scan_read_twice": _scan_read_twice,
    "scan_without_a_program": _scan_without_a_program,
    "aggregate_over_a_join": (
        "SELECT o_orderpriority, count(*) AS c, sum(l_quantity) AS q "
        "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
        "WHERE o_orderdate < date '1995-01-01' GROUP BY o_orderpriority"),
    "elementwise_transform": _elementwise_below_the_root,
}


def _run(cluster, source):
    if callable(source):
        with profile_mod.profiled() as held:
            out = to_host(execute_plan(source(), cluster.snapshot_db()))
        return out, held.profile
    s = cluster.session()
    return s.execute(source), s.last_profile


def _rows_in_order(res) -> dict:
    """The answer's columns, its rows in the order of their values (a
    group-by without ORDER BY promises no order)."""
    cols = answer(res)
    order = np.lexsort(tuple(cols[n] for n in reversed(list(cols))))
    return {n: v[order] for n, v in cols.items()}


@pytest.mark.parametrize("case", sorted(KEPT))
def test_other_shapes_keep_the_old_path_on_the_mesh(deployment, case):
    _, _, sharded, one = deployment
    got, p = _run(sharded, KEPT[case])
    (mesh,) = by_name(p, "mesh")
    assert mesh["attrs"]["answered"] == 1 and not by_name(p, "plan.fuse")
    assert p.agg_pushdown == 0
    assert not [sp for sp in p.spans if "agg_pushdown" in sp["attrs"]]
    scans = collections.Counter(sp["attrs"]["table"]
                                for sp in by_name(p, "scan"))
    # one scan a shard of each table; the memo ran the shared one once
    assert set(scans.values()) == {DEVICES}
    # the shards' row counts are waited for, as before
    assert [sp for sp in by_name(p, "device.wait")
            if sp["parent_id"] == mesh["span_id"]]
    want, _ = _run(one, KEPT[case])
    got, want = _rows_in_order(got), _rows_in_order(want)
    assert list(got) == list(want)
    for name in want:
        assert got[name].dtype == want[name].dtype
        assert got[name].tobytes() == want[name].tobytes(), name
    assert len(next(iter(got.values()))) > 0


@pytest.mark.parametrize("sid", STATEMENTS)
def test_the_shard_scans_charge_stages_and_portions(deployment, sid):
    _, statements, sharded, _ = deployment
    p = mesh_walk_profile(sharded, statements[sid]["sql"])
    scans = by_name(p, "scan")
    assert [sp["attrs"]["device"] for sp in scans] == list(range(DEVICES))
    assert {sp["attrs"]["table"] for sp in scans} == {"lineitem"}
    assert {sp["attrs"]["compile_cache"] for sp in scans} == {"hit"}
    assert any(p.stages[k] > 0
               for k in ("read", "merge", "stage", "compute"))
    assert p.stages["compute"] > 0
    portions = sum(len(sh.visible_portions(None))
                   for sh in sharded.tables["lineitem"].shards)
    assert portions >= DEVICES
    assert p.pruning["portions_total"] == portions
    assert p.pruning["resident_portions"] == portions
    assert p.pruning["resident_rows"] == \
        sum(sp["attrs"]["resident_rows"] for sp in scans)


def test_the_report_shows_a_quarter_of_lineitem_on_each_device(
        deployment):
    data, _, sharded, one = deployment
    report = sharded.mesh_report()
    assert [d["device"] for d in report] == list(range(DEVICES))
    rows = data.rows("lineitem")
    for d in report:
        held = d["tables"]["lineitem"]
        assert abs(100.0 * held["rows"] / rows - 25.0) <= 2.0
        assert held["bytes"] > 0
        assert d["stores"] == len(sharded.tables)
        assert d["bytes"] == sum(t["bytes"] for t in d["tables"].values())
    assert sum(d["tables"]["lineitem"]["rows"] for d in report) == rows
    assert sum(d["bytes"] for d in report) == sum(
        sh.resident.snapshot()["bytes"]
        for t in sharded.tables.values() for sh in t.shards)
    assert one.mesh_report() == []      # no mesh, nothing bound


def test_a_one_chip_statement_opens_no_mesh_key(deployment):
    _, statements, _, one = deployment
    s = one.session()
    s.execute(statements["q6"]["sql"])
    p = s.last_profile
    assert MESH_KEY not in p.stages
    assert not by_name(p, "mesh")
    assert set(STATEMENT_KEYS) <= set(p.stages)
    assert "mesh=" not in s.execute("EXPLAIN ANALYZE "
                                    + statements["q6"]["sql"])


def test_explain_analyze_prints_the_mesh_key_and_the_shard_scans(
        deployment):
    _, statements, sharded, _ = deployment
    txt = sharded.session().execute("EXPLAIN ANALYZE "
                                    + statements["q6"]["sql"])
    line = next(ln for ln in txt.splitlines()
                if ln.startswith("statement: "))
    got = dict(kv.split("=") for kv in line.split()[1:])
    assert list(got) == list(STATEMENT_KEYS) + [MESH_KEY]
    assert float(got[MESH_KEY]) > 0
    assert sum(ln.startswith("  scan: ") and "table=lineitem" in ln
               for ln in txt.splitlines()) == DEVICES
