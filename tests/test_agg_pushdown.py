"""Aggregate pushdown on the walk (plan/executor.py): an aggregating
Transform over a TableScan runs as ONE scan whose program ends in the
Transform's. Each case is held against the path it replaces, the
Transform compiled over the scan's concatenated output, bit for bit;
every plan shape that keeps that path is shown to keep it."""

import collections
import pathlib

import numpy as np
import pytest

from ydb_tpu.analysis import syncsan
from ydb_tpu.config import AppConfig
from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs import profile as profile_mod
from ydb_tpu.plan import executor as plan_executor
from ydb_tpu.plan import execute_plan, to_host
from ydb_tpu.plan.nodes import Concat, TableScan, Transform
from ydb_tpu.sql.parser import parse
from ydb_tpu.sql.planner import plan_select_full
from ydb_tpu.ssa import kernels, plan_fuse
from ydb_tpu.ssa.ops import Agg
from ydb_tpu.ssa.program import (
    AggSpec,
    GroupByStep,
    Program,
    ProjectStep,
    SortStep,
)
from ydb_tpu.workload import tpch

STATEMENTS = pathlib.Path(__file__).resolve().parents[1] / "bench" / \
    "statements"
BLOCK_ROWS = 4096   # 60K lineitem rows = 15 blocks: the combine runs
EV_ROWS = 40_000    # 2 shards x 5 blocks


def _ev_columns():
    rng = np.random.default_rng(3)
    n = EV_ROWS
    cols = {
        "id": np.arange(n, dtype=np.int64),
        "g": [b"g%d" % i for i in rng.integers(0, 5, n)],
        "h": [b"h%d" % i for i in rng.integers(0, 3, n)],
        "v": rng.integers(-10 ** 6, 10 ** 6, n).astype(np.int64),
        "n": rng.integers(-1000, 1000, n).astype(np.int64),
        "s": [b"s%03d" % i for i in rng.integers(0, 100, n)],
        "o": rng.permutation(n).astype(np.int64),
    }
    validity = {"n": rng.random(n) < 0.8, "s": rng.random(n) < 0.9}
    return cols, validity


@pytest.fixture(scope="module")
def cluster():
    """Column tables over two shards each, resident in the device tier
    as on the chip, too large for the fused executor, in blocks small
    enough that every scan folds its partials at least once."""
    mp = pytest.MonkeyPatch()
    mp.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    mp.setattr(resident_mod, "RESIDENT_FORCE", True)
    c = Cluster(config=AppConfig(scan_block_rows=BLOCK_ROWS))
    s = c.session()
    s.execute(
        "CREATE TABLE ev (id int64 NOT NULL, g string NOT NULL, "
        "h string NOT NULL, v decimal(12,2) NOT NULL, n int64, s string, "
        "o int64 NOT NULL, PRIMARY KEY (id)) "
        "WITH (store = column, shards = 2)")
    cols, validity = _ev_columns()
    assert c.tables["ev"].insert(cols, validity).committed
    data = tpch.TpchData(sf=0.01, seed=11)
    sch = data.schema("lineitem")
    s.execute(
        "CREATE TABLE lineitem ("
        + ", ".join(f"{f.name} {_sql_type(f.type)} NOT NULL"
                    for f in sch.fields)
        + ", PRIMARY KEY (l_orderkey, l_linenumber)) "
        "WITH (store = column, shards = 2)")
    for name in data.dicts.columns():
        if name in sch.names:
            d = c.dicts.for_column(name)
            for v in data.dicts[name].values:
                d.add(v)
    assert c.tables["lineitem"].insert(
        dict(data.tables["lineitem"])).committed
    for t in c.tables.values():
        for sh in t.shards:
            sh.resident.drain()
    c._invalidate_plans()
    try:
        yield c
    finally:
        c.stop()
        mp.undo()


def _sql_type(t) -> str:
    if t.is_decimal:
        return f"decimal(15, {t.scale})"
    return str(t)


def _plan(cluster, sql):
    return plan_select_full(parse(sql), cluster.catalog(), None).plan


def _old_path(plan, db):
    """What the walk did before the pushdown: the scan's own program
    over the blocks, its output concatenated, the Transform over that."""
    return plan_executor._transform_node(
        plan, execute_plan(plan.input, db), db)


def _assert_same(new, old):
    assert new.schema == old.schema     # names, types, scales, nullable
    for name in new.schema.names:
        for got, want in zip(new.cols[name], old.cols[name]):
            assert got.dtype == want.dtype
            assert np.array_equal(got, want), name


def _span_names(profile):
    return collections.Counter(
        sp["name"] + (":" + sp["attrs"]["program"]
                      if "program" in sp["attrs"] else "")
        for sp in profile.spans)


AGGS = ("sum(v) AS sv, count(n) AS cn, count(*) AS c, avg(v) AS av, "
        "min(v) AS mn, max(o) AS mx")
PUSHED = {
    "tpch_q1": (STATEMENTS / "q1.sql").read_text(),
    "tpch_q6": (STATEMENTS / "q6.sql").read_text(),
    "keyed": f"SELECT g, h, {AGGS} FROM ev WHERE o >= 100 GROUP BY g, h",
    "keyless": f"SELECT {AGGS} FROM ev WHERE o >= 100",
    "null_bearing": ("SELECT g, sum(n) AS sn, avg(n) AS an, min(n) AS "
                     "lo, max(n) AS hi, count(n) AS cn FROM ev GROUP BY g"),
    "null_bearing_keyless": ("SELECT sum(n) AS sn, avg(n) AS an, "
                             "min(n) AS lo, count(n) AS cn FROM ev"),
    "nothing_selected_keyless": f"SELECT {AGGS} FROM ev WHERE o < 0",
    "nothing_selected_keyed": (f"SELECT g, {AGGS} FROM ev WHERE o < 0 "
                               "GROUP BY g"),
    "having": ("SELECT g, h, sum(v) AS sv FROM ev GROUP BY g, h "
               "HAVING sum(v) > 0"),
    "order_limit": ("SELECT g, h, count(*) AS c FROM ev GROUP BY g, h "
                    "ORDER BY c DESC, g, h LIMIT 3"),
    "string_min": ("SELECT g, min(s) AS lo, max(s) AS hi FROM ev "
                   "GROUP BY g ORDER BY lo"),
    "distinct_dense": "SELECT DISTINCT g, h FROM ev",
}


@pytest.mark.parametrize("case", sorted(PUSHED))
def test_pushdown_equals_the_transform_over_the_scan(cluster, case):
    plan = _plan(cluster, PUSHED[case])
    assert isinstance(plan, Transform) \
        and isinstance(plan.input, TableScan)
    db = cluster.snapshot_db()
    memo = plan_executor._Memo(plan)
    assert plan_executor._pushdown_scan(plan, memo.shared) is not None
    new = plan_executor._scan_aggregated(plan, db, memo.shared)
    assert new is not None, "the shape pushes down"
    new, old = to_host(new), to_host(_old_path(plan, db))
    _assert_same(new, old)
    rows = len(new.cols[new.schema.names[0]][0])
    if case == "nothing_selected_keyless":
        assert rows == 1
    if case == "nothing_selected_keyed":
        assert rows == 0
    if case == "string_min":
        # the aggregate's output decodes through its input's dictionary
        lo = new.cols["lo"][0]
        d = cluster.dicts["s"]
        assert [d.values[i] for i in lo] == sorted(
            d.values[i] for i in lo)


def _ev_numpy():
    return {k: np.asarray(v) for k, v in _ev_columns()[0].items()}


def _sorted_scan_plan():
    """A sort before the group-by in one program: only a hand-made plan
    has it (the planner puts such a sort into a Transform of its own)."""
    return Transform(
        TableScan("ev", Program((ProjectStep(("g", "o")),))),
        Program((
            SortStep(("o",), (False,), 100),
            GroupByStep(("g",), (AggSpec(Agg.COUNT_ALL, None, "c"),)),
        )))


def _shared_scan_plan():
    """One TableScan node read by two aggregating Transforms."""
    scan = TableScan("ev", Program((ProjectStep(("g", "v")),)))
    return Concat((
        Transform(scan, Program((
            GroupByStep((), (AggSpec(Agg.SUM, "v", "x"),)),))),
        Transform(scan, Program((
            GroupByStep((), (AggSpec(Agg.MAX, "v", "x"),)),))),
    ))


def _check_window(out, ev):
    sel = ev["o"] < 500
    assert sorted(out.cols["id"][0].tolist()) == sorted(
        ev["id"][sel].tolist())
    order = np.argsort(out.cols["id"][0])
    want = {}
    for g in np.unique(ev["g"][sel]):
        ids = ev["id"][sel & (ev["g"] == g)]
        rank = np.argsort(np.argsort(ev["o"][ids])) + 1
        want.update(zip(ids.tolist(), rank.tolist()))
    ids = out.cols["id"][0][order].tolist()
    assert out.cols["r"][0][order].tolist() == [want[i] for i in ids]


def _check_sorted_scan(out, ev):
    first = np.argsort(ev["o"])[:100]
    want = collections.Counter(ev["g"][first].tolist())
    assert sorted(out.cols["c"][0].tolist()) == sorted(want.values())


def _check_sort_layout(out, ev):
    order = np.argsort(out.cols["o"][0])
    assert np.array_equal(out.cols["o"][0][order], np.sort(ev["o"]))
    assert np.array_equal(out.cols["sv"][0][order],
                          ev["v"][np.argsort(ev["o"])])


def _check_shared(out, ev):
    assert out.cols["x"][0].tolist() == [int(ev["v"].sum()),
                                         int(ev["v"].max())]


def _check_cte(out, ev):
    sel = ev["o"] > 10
    assert out.cols["x"][0].tolist() == [int(ev["v"][sel].sum()),
                                         int(ev["v"][sel].max())]


def _check_plain(out, ev):
    assert out.cols["id"][0].tolist() == sorted(
        ev["id"][ev["o"] < 50].tolist())[:5]


KEPT = {
    "window": ("SELECT id, rank() OVER (PARTITION BY g ORDER BY o) AS r "
               "FROM ev WHERE o < 500", _check_window),
    "sort_before_group_by": (_sorted_scan_plan, _check_sorted_scan),
    "sort_derived_layout": ("SELECT o, sum(v) AS sv FROM ev GROUP BY o",
                            _check_sort_layout),
    "scan_read_twice": (_shared_scan_plan, _check_shared),
    "cte_read_twice": (
        "WITH c AS (SELECT g, v FROM ev WHERE o > 10) SELECT sum(v) AS x "
        "FROM c UNION ALL SELECT max(v) AS x FROM c", _check_cte),
    "plain_select": ("SELECT id, v FROM ev WHERE o < 50 ORDER BY id "
                     "LIMIT 5", _check_plain),
}


@pytest.mark.parametrize("case", sorted(KEPT))
def test_other_shapes_keep_the_old_path(cluster, case):
    source, check = KEPT[case]
    plan = source() if callable(source) else _plan(cluster, source)
    db = cluster.snapshot_db()
    with profile_mod.profiled() as held:
        out = to_host(execute_plan(plan, db))
    names = _span_names(held.profile)
    assert held.profile.agg_pushdown == 0
    assert names["transform"] >= 1 and names["dispatch:transform"] >= 1
    assert names["dispatch:scan_combine"] == 0
    if case == "scan_read_twice":
        assert names["scan"] == 1    # the memo ran the shared scan once
    check(out, _ev_numpy())


@pytest.mark.parametrize("case", ("tpch_q1", "order_limit"))
def test_the_transform_span_says_which_way_its_sort_went(cluster, case):
    """Q1 orders without a limit and keeps the whole sort; so does a
    LIMIT over a block of a few thousand slots (``kernels.sort_tier``)."""
    plan = _plan(cluster, PUSHED[case])
    with profile_mod.profiled() as held:
        out = _old_path(plan, cluster.snapshot_db())
    (transform,) = [sp for sp in held.profile.spans
                    if sp["name"] == "transform"]
    attrs = transform["attrs"]
    assert attrs["sort_tier"] == "whole"
    assert attrs.get("sort_limit") == (3 if case == "order_limit" else None)
    assert 3 * kernels.TOPK_ROOM > out.capacity


def test_pushed_down_statement_profile(cluster):
    s = cluster.session()
    sql = PUSHED["tpch_q1"]
    s.execute(sql)
    with syncsan.activate():
        s.execute(sql)
        warm = s.last_profile
        text = s.execute("EXPLAIN ANALYZE " + sql)
    assert warm.agg_pushdown == 1
    scans = [sp for sp in warm.spans if sp["name"] == "scan"]
    assert len(scans) == 1 and scans[0]["attrs"]["agg_pushdown"] == 1
    assert scans[0]["attrs"]["rows"] == 4
    names = _span_names(warm)
    assert names["host.concat"] == 0 and names["transform"] == 0
    assert names["dispatch:scan_partial"] >= 8
    assert names["dispatch:scan_combine"] >= 1
    assert names["dispatch:scan_finalize"] == 1
    # nothing leaves the device before the result does
    fetch = next(sp for sp in warm.spans if sp["name"] == "fetch")
    gets = [sp for sp in warm.spans if sp["name"] == "device.get"]
    assert gets and all(sp["parent_id"] == fetch["span_id"]
                        for sp in gets)
    assert warm.compile_cache == "hit"
    assert warm.syncsan["compiles"] == 0
    assert "agg_pushdown=1" in text
    assert s.last_profile.syncsan["compiles"] == 0
