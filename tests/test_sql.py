"""SQL frontend tests: parser, planner, end-to-end SQL execution.

TPC-H Q1/Q6/Q3/Q5 in actual SQL against the engine, cross-checked with
the hand-built programs/oracle — the KQP compile+execute suite shape
(ydb/core/kqp/ut/query) for the supported dialect."""

import numpy as np
import pytest

from ydb_tpu.engine.oracle import OracleTable, run_oracle
from ydb_tpu.engine.scan import ColumnSource
from ydb_tpu.kqp import Cluster
from ydb_tpu.plan import Database, execute_plan, to_host
from ydb_tpu.sql import parse
from ydb_tpu.sql.planner import Catalog, PlanError, plan_select
from ydb_tpu.workload import tpch

Q1_SQL = """
select
  l_returnflag, l_linestatus,
  sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1.00 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1.00 - l_discount) * (1.00 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty,
  avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc,
  count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q6_SQL = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07
  and l_quantity < 24
"""

Q3_SQL = """
select l_orderkey,
       sum(l_extendedprice * (1.00 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING'
  and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate, l_orderkey
limit 10
"""

Q5_SQL = """
select n_name,
       sum(l_extendedprice * (1.00 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey
  and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey
  and n_regionkey = r_regionkey
  and r_name = 'ASIA'
  and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1995-01-01'
group by n_name
order by revenue desc
"""


@pytest.fixture(scope="module")
def data():
    return tpch.TpchData(sf=0.005, seed=31)


@pytest.fixture(scope="module")
def db(data):
    return Database(
        sources={
            t: ColumnSource(cols, data.schema(t), data.dicts)
            for t, cols in data.tables.items()
        },
        dicts=data.dicts,
    )


@pytest.fixture(scope="module")
def catalog(data):
    return Catalog(
        schemas={t: data.schema(t) for t in data.tables},
        primary_keys={
            "orders": ("o_orderkey",), "customer": ("c_custkey",),
            "supplier": ("s_suppkey",), "nation": ("n_nationkey",),
            "region": ("r_regionkey",),
            "lineitem": ("l_orderkey", "l_linenumber"),
        },
        dicts=data.dicts,
    )


def _oracle(data, table):
    cols = {
        n: (v, np.ones(len(v), dtype=bool))
        for n, v in data.tables[table].items()
    }
    return OracleTable(cols, data.schema(table))


def _sql(sql, catalog, db):
    return to_host(execute_plan(plan_select(parse(sql), catalog), db))


def test_parser_roundtrip_shapes():
    s = parse(Q1_SQL)
    assert len(s.items) == 10
    assert s.group_by and s.order_by
    s3 = parse(Q3_SQL)
    assert s3.limit == 10
    assert len(_flatten(s3.from_)) == 3


def _flatten(f):
    from ydb_tpu.sql.planner import _flatten_from

    return _flatten_from(f)[0]


def test_q1_sql_matches_program(data, db, catalog):
    res = _sql(Q1_SQL, catalog, db)
    ora = run_oracle(tpch.q1_program(), _oracle(data, "lineitem"),
                     data.dicts)
    assert res.num_rows == ora.num_rows
    for name in ("sum_qty", "sum_disc_price", "avg_price", "count_order"):
        np.testing.assert_allclose(
            np.asarray(res.cols[name][0], dtype=np.float64),
            np.asarray(ora.cols[name][0], dtype=np.float64),
            rtol=1e-9, err_msg=name,
        )


def test_q6_sql_matches_program(data, db, catalog):
    res = _sql(Q6_SQL, catalog, db)
    ora = run_oracle(tpch.q6_program(), _oracle(data, "lineitem"),
                     data.dicts)
    assert int(res.cols["revenue"][0][0]) == int(ora.cols["revenue"][0][0])


def test_q3_sql_matches_plan(data, db, catalog):
    res = _sql(Q3_SQL, catalog, db)
    ref = to_host(execute_plan(tpch.q3_plan(), db))
    np.testing.assert_array_equal(
        res.cols["revenue"][0], ref.cols["revenue"][0]
    )
    np.testing.assert_array_equal(
        res.cols["l_orderkey"][0], ref.cols["l_orderkey"][0]
    )


def test_q5_sql_matches_plan(data, db, catalog):
    res = _sql(Q5_SQL, catalog, db)
    ref = to_host(execute_plan(tpch.q5_plan(), db))
    np.testing.assert_array_equal(
        res.cols["revenue"][0], ref.cols["revenue"][0]
    )
    np.testing.assert_array_equal(res.cols["n_name"][0],
                                  ref.cols["n_name"][0])


def test_sql_misc_features(data, db, catalog):
    # IN over strings, LIKE, HAVING, expression select, year()
    res = _sql(
        """
        select l_shipmode, count(*) as n,
               sum(l_extendedprice) / 100 as total
        from lineitem
        where l_shipmode in ('AIR', 'MAIL') and l_quantity >= 10
        group by l_shipmode
        having count(*) > 1
        order by l_shipmode
        """,
        catalog, db,
    )
    assert res.num_rows == 2
    d = data.dicts["l_shipmode"]
    names = [d.values[int(i)] for i in res.cols["l_shipmode"][0]]
    assert names == sorted(names)  # ordered lexicographically via ranks
    assert set(names) == {b"AIR", b"MAIL"}

    res2 = _sql(
        """
        select year(o_orderdate) as y, count(*) as n
        from orders where o_orderpriority like '1%'
        group by year(o_orderdate) order by y
        """,
        catalog, db,
    )
    ys = res2.cols["y"][0]
    assert list(ys) == sorted(ys) and len(ys) >= 5


def test_error_cases(catalog):
    with pytest.raises(PlanError):
        plan_select(parse("select nope from lineitem"), catalog)
    with pytest.raises(PlanError):
        plan_select(
            parse("select l_orderkey from lineitem group by l_shipmode"),
            catalog,
        )
    with pytest.raises(SyntaxError):
        parse("select from")
    with pytest.raises(PlanError):
        # cross join without equi condition
        plan_select(parse(
            "select l_orderkey from lineitem, orders"), catalog)


def test_cluster_end_to_end_sql():
    c = Cluster(n_shards=3)
    s = c.session()
    s.execute("""
        create table events (
            id bigint not null,
            ts date not null,
            kind string,
            amount decimal(10, 2),
            primary key (id)
        )
    """)
    r = s.execute("""
        insert into events (id, ts, kind, amount) values
        (1, date '2024-01-01', 'buy', 10.50),
        (2, date '2024-01-02', 'sell', 3.25),
        (3, date '2024-01-02', 'buy', 1.00),
        (4, date '2024-02-01', 'buy', null)
    """)
    assert r.committed
    res = s.execute("""
        select kind, count(*) as n, sum(amount) as total
        from events group by kind order by kind
    """)
    assert res.num_rows == 2
    kinds = [c.dicts["kind"].values[int(i)] for i in res.cols["kind"][0]]
    assert kinds == [b"buy", b"sell"]
    np.testing.assert_array_equal(res.cols["n"][0], [3, 1])
    np.testing.assert_array_equal(res.cols["total"][0], [1150, 325])

    # second insert + repeated query (plan cache path)
    s.execute("insert into events values (5, date '2024-03-01', 'sell', 2.00)")
    res2 = s.execute("""
        select kind, count(*) as n, sum(amount) as total
        from events group by kind order by kind
    """)
    np.testing.assert_array_equal(res2.cols["n"][0], [3, 2])


def test_select_distinct(data, db, catalog):
    res = _sql("select distinct l_shipmode from lineitem order by l_shipmode",
               catalog, db)
    assert res.num_rows == 7  # all ship modes, deduplicated
    d = data.dicts["l_shipmode"]
    names = [d.values[int(i)] for i in res.cols["l_shipmode"][0]]
    assert names == sorted(names)


def test_on_condition_orientation(data, db, catalog):
    # reversed operand order in ON must plan identically
    a = _sql("""select count(*) n from lineitem l
                join orders o on o_orderkey = l_orderkey
                where o_orderdate < date '1995-01-01'""", catalog, db)
    b = _sql("""select count(*) n from lineitem l
                join orders o on l_orderkey = o_orderkey
                where o_orderdate < date '1995-01-01'""", catalog, db)
    assert int(a.cols["n"][0][0]) == int(b.cols["n"][0][0]) > 0


def test_no_payload_join_preserves_multiplicity(data, db, catalog):
    # lineitem joined to itself-shaped non-unique side must not collapse
    # multiplicity: count(*) over orders x lineitem on orderkey equals
    # lineitem rows with matching order (orders unique -> semi fine),
    # but joining the non-unique direction must expand
    res = _sql("""select count(*) n from orders, lineitem
                  where o_orderkey = l_orderkey""", catalog, db)
    n_li = len(data.tables["lineitem"]["l_orderkey"])
    assert int(res.cols["n"][0][0]) == n_li  # every lineitem has its order


def test_left_join_where_equi_cond_stays_post_join():
    """WHERE a.ya = b.yb on a LEFT JOIN must filter AFTER the join (drop
    NULL-extended rows), not fold into the ON condition."""
    c = Cluster(n_shards=1)
    s = c.session()
    s.execute("""create table a (k bigint not null, ya bigint,
                 primary key (k))""")
    s.execute("""create table b (k bigint not null, yb bigint,
                 primary key (k))""")
    s.execute("insert into a values (1, 10), (2, 20), (3, 30)")
    s.execute("insert into b values (1, 10), (2, 99)")
    # matches: k=1 (ya=yb=10 kept), k=2 (20!=99 dropped),
    # k=3 (no match -> NULL yb -> dropped by WHERE)
    res = s.execute("""select a.k as k, yb from a
                       left join b on a.k = b.k
                       where ya = yb order by k""")
    assert res.num_rows == 1
    assert int(res.cols["k"][0][0]) == 1
    assert int(res.cols["yb"][0][0]) == 10
    # sanity: without the WHERE all three left rows survive
    res2 = s.execute("""select a.k as k from a
                        left join b on a.k = b.k order by k""")
    assert res2.num_rows == 3


def test_left_join_residual_on_colliding_name_raises():
    """A residual predicate referencing a build-side column shadowed by a
    probe-side column of the same name must raise, not silently resolve
    to the probe side."""
    c = Cluster(n_shards=1)
    s = c.session()
    s.execute("create table a (k bigint not null, ya bigint, primary key (k))")
    s.execute("create table b (k bigint not null, yb bigint, primary key (k))")
    s.execute("insert into a values (1, 1), (2, 20)")
    s.execute("insert into b values (2, 99)")
    with pytest.raises(PlanError, match="not carried through the join"):
        s.execute("""select a.k from a left join b on a.k = b.k
                     where a.ya = b.k""")


def test_explain_renders_the_physical_plan(data, db, catalog):
    from ydb_tpu.kqp.session import Cluster

    c = Cluster()
    s = c.session()
    s.execute("create table kv (k bigint not null, v bigint, "
              "primary key (k))")
    s.execute("insert into kv (k, v) values (1, 2), (3, 4)")
    text = s.execute("explain select k, sum(v) as t from kv "
                     "where k > 0 group by k order by t desc limit 5")
    assert "Transform" in text and "TableScan kv" in text
    assert "group_by[keys=['k']" in text
    assert "limit=5" in text
    # joins show probe/build structure
    text2 = s.execute(
        "explain select a.k from kv a, kv b where a.k = b.k")
    assert "Join" in text2


# ---------------- UNION [ALL] ----------------


def test_union_all_with_rename_order_limit(data, db, catalog):
    """Branch outputs align by position (second branch's alias differs),
    trailing ORDER BY/LIMIT bind to the whole union."""
    li = data.tables["lineitem"]
    sql = """
    select l_orderkey, l_quantity from lineitem where l_quantity < 3
    union all
    select l_orderkey, l_quantity * 2 as q2 from lineitem
    where l_quantity > 48
    order by l_quantity desc limit 5"""
    from ydb_tpu.sql.planner import plan_select_full

    pq = plan_select_full(parse(sql), catalog)
    assert pq.out_names == ("l_orderkey", "l_quantity")
    out = to_host(execute_plan(pq.plan, db))
    got = np.asarray(out.cols["l_quantity"][0])
    # l_quantity is decimal(2)-scaled: SQL "< 3" means 300 cents
    lo = li["l_quantity"][li["l_quantity"] < 300]
    hi = li["l_quantity"][li["l_quantity"] > 4800] * 2
    assert len(lo) and len(hi), "both branches must select rows"
    want = np.sort(np.concatenate([lo, hi]))[::-1][:5]
    assert np.array_equal(got, want)


def test_union_all_in_from_groups_across_branches(data, db, catalog):
    """The TPC-DS channel-union shape: union in a derived table, one
    aggregation over all branches, string key decodes via the shared
    dictionary."""
    li = data.tables["lineitem"]
    sql = """
    select l_returnflag, sum(amt) as total from (
      select l_returnflag, l_extendedprice as amt from lineitem
      where l_quantity < 25
      union all
      select l_returnflag, l_extendedprice as amt from lineitem
      where l_quantity >= 25
    ) u group by l_returnflag order by l_returnflag"""
    from ydb_tpu.sql.planner import plan_select_full

    pq = plan_select_full(parse(sql), catalog)
    out = to_host(execute_plan(pq.plan, db))
    rf = li["l_returnflag"]
    want = {int(k): int(li["l_extendedprice"][rf == k].sum())
            for k in np.unique(rf)}
    got_k = np.asarray(out.cols["l_returnflag"][0])
    got_v = np.asarray(out.cols["total"][0])
    assert {int(k): int(v) for k, v in zip(got_k, got_v)} == want


def test_union_distinct_dedups(data, db, catalog):
    sql = ("select l_returnflag from lineitem "
           "union select l_returnflag from lineitem")
    from ydb_tpu.sql.planner import plan_select_full

    pq = plan_select_full(parse(sql), catalog)
    out = to_host(execute_plan(pq.plan, db))
    got = np.sort(np.asarray(out.cols["l_returnflag"][0]))
    want = np.unique(data.tables["lineitem"]["l_returnflag"])
    assert np.array_equal(got, want)


def test_union_arity_mismatch_raises(data, db, catalog):
    from ydb_tpu.sql.planner import plan_select_full

    with pytest.raises(PlanError, match="columns"):
        plan_select_full(parse(
            "select l_orderkey, l_quantity from lineitem "
            "union all select l_orderkey from lineitem"), catalog)


def test_union_mixed_chain_rejected():
    with pytest.raises(SyntaxError, match="mixed UNION"):
        parse("select 1 as a from t union all select 2 as a from t "
              "union select 3 as a from t")


def test_union_all_permuted_columns(data, db, catalog):
    """A later branch whose output names PERMUTE the first branch's must
    remap by position without corrupting either column (code-review
    regression: sequential renames through one shared env)."""
    li = data.tables["lineitem"]
    sql = """
    select l_orderkey, l_partkey from lineitem where l_quantity < 2
    union all
    select l_partkey, l_orderkey from lineitem where l_quantity > 49"""
    from ydb_tpu.sql.planner import plan_select_full

    pq = plan_select_full(parse(sql), catalog)
    assert pq.out_names == ("l_orderkey", "l_partkey")
    out = to_host(execute_plan(pq.plan, db))
    lo = li["l_quantity"] < 200
    hi = li["l_quantity"] > 4900
    want_ok = np.concatenate([li["l_orderkey"][lo], li["l_partkey"][hi]])
    want_pk = np.concatenate([li["l_partkey"][lo], li["l_orderkey"][hi]])
    assert np.array_equal(np.asarray(out.cols["l_orderkey"][0]), want_ok)
    assert np.array_equal(np.asarray(out.cols["l_partkey"][0]), want_pk)


def test_union_cte_scoping(data, db, catalog):
    """A statement-level WITH scopes over every branch; a later branch's
    own WITH shadows locally without rewriting sibling branches
    (code-review regression: shared cte dict registered all branches'
    CTEs before planning any)."""
    from ydb_tpu.sql.planner import plan_select_full

    li = data.tables["lineitem"]
    sql = """
    with base as (select l_orderkey as v from lineitem
                  where l_quantity < 2)
    select v from base
    union all
    with base as (select l_partkey as v from lineitem
                  where l_quantity < 2)
    select v from base"""
    pq = plan_select_full(parse(sql), catalog)
    out = to_host(execute_plan(pq.plan, db))
    m = li["l_quantity"] < 200
    want = np.concatenate([li["l_orderkey"][m], li["l_partkey"][m]])
    assert np.array_equal(np.asarray(out.cols["v"][0]), want)


def test_union_interior_order_by_rejected():
    with pytest.raises(SyntaxError, match="non-final UNION branch"):
        parse("select a from t order by a limit 3 "
              "union all select a from t")


def test_sql_path_device_block_cache(monkeypatch):
    """The cluster-owned block cache serves warm SQL scans and every
    mutation (INSERT/UPDATE/DELETE) is immediately visible — the cache
    keys on per-shard visible-portion ids, so a commit changes the key
    (shared_sausagecache analog on the SQL path)."""
    monkeypatch.setenv("YDB_TPU_SCAN_CACHE_BYTES", str(64 << 20))
    c = Cluster(n_shards=2)
    s = c.session()
    s.execute("create table kv (k bigint not null, v bigint, "
              "primary key (k))")
    s.execute("insert into kv values (1, 10), (2, 20), (3, 30)")

    def total():
        r = s.execute("select sum(v) as s from kv")
        return int(np.asarray(r.cols["s"][0])[0])

    assert total() == 60
    assert total() == 60
    assert c.scan_block_cache.hits > 0
    s.execute("insert into kv values (4, 40)")
    assert total() == 100
    assert total() == 100
    # a row-store table (UPDATE/DELETE surface) keeps exact semantics
    # alongside the cache (its sources are not portion-backed)
    s.execute("create table rt (k bigint not null, v bigint, "
              "primary key (k)) with (store = row)")
    s.execute("insert into rt values (1, 1), (2, 2)")
    s.execute("update rt set v = 9 where k = 1")
    s.execute("delete from rt where k = 2")
    r = s.execute("select sum(v) as s from rt")
    assert int(np.asarray(r.cols["s"][0])[0]) == 9


def test_block_cache_cleared_on_drop_table(monkeypatch):
    """A re-created same-name table reuses shard ids and restarts
    portion ids, so DROP TABLE must clear the cluster block cache or a
    warm SELECT would serve the dropped table's rows (code-review
    finding)."""
    monkeypatch.setenv("YDB_TPU_SCAN_CACHE_BYTES", str(64 << 20))
    c = Cluster(n_shards=1)
    s = c.session()
    s.execute("create table t (k bigint not null, v bigint, "
              "primary key (k))")
    s.execute("insert into t values (1, 111)")
    r = s.execute("select sum(v) as s from t")
    assert int(np.asarray(r.cols["s"][0])[0]) == 111
    s.execute("drop table t")
    s.execute("create table t (k bigint not null, v bigint, "
              "primary key (k))")
    s.execute("insert into t values (1, 222)")
    r = s.execute("select sum(v) as s from t")
    assert int(np.asarray(r.cols["s"][0])[0]) == 222


def test_block_cache_pruned_for_gcd_portions(monkeypatch):
    """Compaction/TTL churn must not leave cluster-cache entries keyed
    by GC'd portion ids pinning HBM budget until LRU pressure: the
    per-statement Database snapshot prunes against the live portion
    sets, mirroring ColumnShard.scan's per-shard prune (ADVICE r5)."""
    monkeypatch.setenv("YDB_TPU_SCAN_CACHE_BYTES", str(64 << 20))
    c = Cluster(n_shards=1)
    s = c.session()
    s.execute("create table t (k bigint not null, v bigint, "
              "primary key (k))")
    s.execute("insert into t values (1, 10)")
    s.execute("insert into t values (2, 20)")  # second portion
    r = s.execute("select sum(v) as s from t")  # warm: keys current set
    assert int(np.asarray(r.cols["s"][0])[0]) == 30
    assert len(c.scan_block_cache) >= 1
    shard = c.tables["t"].shards[0]
    shard.compact()
    shard.gc_blobs(keep_snap=shard.snap)  # pre-compaction portions die
    live = set(shard.portions)
    # the warm entry references dead portion ids until the next
    # statement snapshot prunes it
    assert any(
        not live.issuperset(pids)
        for key in c.scan_block_cache for _, pids in key[0])
    r = s.execute("select sum(v) as s from t")
    assert int(np.asarray(r.cols["s"][0])[0]) == 30
    for key in c.scan_block_cache:
        for _sid, pids in key[0]:
            assert live.issuperset(pids), key
    # the emergency valve (budget -> 0 mid-process) frees everything:
    # entries cached under the old budget can never be served again
    assert len(c.scan_block_cache) >= 1
    monkeypatch.setenv("YDB_TPU_SCAN_CACHE_BYTES", "0")
    s.execute("select sum(v) as s from t")
    assert len(c.scan_block_cache) == 0


# ---------------- window functions ----------------


def test_window_rank_through_sql_and_dq(data, db, catalog):
    """rank() over a JOIN-bearing plan: the DQ stage graph must treat
    the WindowStep as a merge barrier (per-task evaluation would rank
    within partitions of the data, not the data)."""
    from ydb_tpu.sql.planner import plan_select_full

    li = data.tables["lineitem"]
    ords = data.tables["orders"]
    sql = """
    select l_orderkey, revenue, rank() over (order by revenue desc)
           as rnk
    from (select l_orderkey,
                 sum(l_extendedprice * (1.00 - l_discount)) as revenue
          from lineitem, orders
          where l_orderkey = o_orderkey
            and o_orderdate < date '1995-03-15'
          group by l_orderkey) r
    order by rnk, l_orderkey
    limit 10"""
    pq = plan_select_full(parse(sql), catalog)
    out = to_host(execute_plan(pq.plan, db))
    # independent numpy reference
    cutoff = (np.datetime64("1995-03-15", "D")
              - np.datetime64("1970-01-01", "D")).astype(int)
    omap = {k: d for k, d in zip(ords["o_orderkey"].tolist(),
                                 ords["o_orderdate"].tolist())}
    import collections
    rev = collections.defaultdict(int)
    for k, p, dsc in zip(li["l_orderkey"].tolist(),
                         li["l_extendedprice"].tolist(),
                         li["l_discount"].tolist()):
        if omap[k] < cutoff:
            rev[k] += p * (100 - dsc)
    ranked = sorted(rev.items(), key=lambda kv: (-kv[1], kv[0]))
    want = []
    rnk = 0
    prev = None
    for i, (k, v) in enumerate(ranked[:10]):
        if v != prev:
            rnk = i + 1
        want.append((k, rnk))
        prev = v
    got = list(zip(np.asarray(out.cols["l_orderkey"][0]).tolist(),
                   np.asarray(out.cols["rnk"][0]).tolist()))
    assert got == want


def test_window_mixed_with_aggregate_rejected(data, db, catalog):
    with pytest.raises(PlanError, match="window functions cannot mix"):
        from ydb_tpu.sql.planner import plan_select_full

        plan_select_full(parse(
            "select sum(l_quantity) as s, "
            "rank() over (order by l_orderkey) as r from lineitem"),
            catalog)


def test_ranking_window_with_args_is_a_syntax_error():
    """rank(x) OVER (...) used to silently DROP the argument list; it
    must fail at parse time instead of rewriting the query's meaning."""
    with pytest.raises(SyntaxError, match="no arguments"):
        parse("select rank(l_quantity) over (order by l_orderkey) as r"
              " from lineitem")
    with pytest.raises(SyntaxError, match="no arguments"):
        parse("select dense_rank(distinct l_tax) over"
              " (order by l_orderkey) as r from lineitem")
    # argument-free ranking calls still parse
    parse("select row_number() over (order by l_orderkey) as r"
          " from lineitem")


def test_nested_window_rejected_with_targeted_error(catalog):
    """Windows hidden inside expressions or WHERE/HAVING used to fall
    through to a generic late PlanError; they must fail with the
    targeted top-level-select-items message."""
    with pytest.raises(PlanError, match="top-level select items"):
        plan_select(parse(
            "select rank() over (order by l_orderkey) + 1 as r"
            " from lineitem"), catalog)
    with pytest.raises(PlanError, match="not allowed in WHERE"):
        plan_select(parse(
            "select l_orderkey from lineitem"
            " where rank() over (order by l_orderkey) < 5"), catalog)
    with pytest.raises(PlanError, match="not allowed in HAVING"):
        plan_select(parse(
            "select l_orderkey, sum(l_quantity) as s from lineitem"
            " group by l_orderkey"
            " having rank() over (order by l_orderkey) < 5"), catalog)
    # nested windows inside a DERIVED TABLE get the same treatment
    with pytest.raises(PlanError, match="top-level select items"):
        plan_select(parse(
            "select r from (select rank() over (order by l_orderkey)"
            " * 2 as r from lineitem) t"), catalog)


def test_or_of_exists_decorrelates():
    """EXISTS(A) OR EXISTS(B) (and mixed with plain predicates) lowers
    through the counting scalar-join rewrite (TPC-DS q10/q35 shape)."""
    c = Cluster(n_shards=1)
    s = c.session()
    s.execute("create table cu (id bigint not null, nm string, "
              "primary key (id))")
    s.execute("create table w (k bigint not null, cid bigint, "
              "primary key (k))")
    s.execute("create table ct (k bigint not null, cid bigint, "
              "primary key (k))")
    s.execute("insert into cu values (1,'a'),(2,'b'),(3,'c'),(4,'d')")
    s.execute("insert into w values (10, 1), (11, 3)")
    s.execute("insert into ct values (20, 2), (21, 3)")
    r = s.execute(
        "select id from cu c where "
        "exists (select * from w where c.id = cid) "
        "or exists (select * from ct where c.id = cid) order by id")
    assert np.asarray(r.cols["id"][0]).tolist() == [1, 2, 3]
    r2 = s.execute(
        "select id from cu c where nm = 'd' "
        "or not exists (select * from w where c.id = cid) "
        "order by id")
    assert np.asarray(r2.cols["id"][0]).tolist() == [2, 4]


def test_composite_primary_key_upsert_and_join():
    """A column table's key may span columns (TPC-H lineitem:
    (l_orderkey, l_linenumber)): rows route and sort on the first, but
    upsert dedup compares the WHOLE key — across commits, inside one
    commit and after compaction — and the planner sees the whole key,
    so a join on the first column keeps every line."""
    import numpy as np

    from ydb_tpu.kqp.session import Cluster

    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE li (ok int64, ln int64, v int64, "
              "PRIMARY KEY (ok, ln)) WITH (shards = 2, upsert = on)")
    s.execute("CREATE TABLE od (ok int64, w int64, PRIMARY KEY (ok)) "
              "WITH (shards = 2, upsert = on)")
    assert c.catalog().primary_keys["li"] == ("ok", "ln")
    ok = np.repeat(np.arange(1, 501), 4)
    ln = np.tile(np.arange(1, 5), 500)
    v = np.arange(2000)
    t = c.tables["li"]
    # two batches that split an order between them
    assert t.insert({"ok": ok[:1002], "ln": ln[:1002],
                     "v": v[:1002]}).committed
    assert t.insert({"ok": ok[1002:], "ln": ln[1002:],
                     "v": v[1002:]}).committed
    c.tables["od"].insert({"ok": np.arange(1, 501),
                           "w": np.ones(500, dtype=np.int64)})
    c._invalidate_plans()

    def totals():
        r = s.execute("SELECT COUNT(*) AS n, SUM(v) AS sv FROM li")
        return int(r.cols["n"][0][0]), int(r.cols["sv"][0][0])

    assert totals() == (2000, int(v.sum()))
    # the two batches touch on order 251 (lines 1-2 | 3-4) without
    # overlapping on the whole key: no host-merged cluster joins them
    from ydb_tpu.engine.reader import plan_clusters

    for sh in t.shards:
        metas = sh.visible_portions()
        assert len(metas) == 2
        assert [len(cl) for cl in plan_clusters(metas, dedup=True)] \
            == [1, 1]
    # same key twice in ONE statement: the last wins; other lines stay
    s.execute("UPSERT INTO li (ok, ln, v) VALUES "
              "(251, 2, -1), (251, 3, -7), (7, 1, -9), (251, 2, -5)")
    want = v.copy()
    want[(ok == 251) & (ln == 2)] = -5
    want[(ok == 251) & (ln == 3)] = -7
    want[(ok == 7) & (ln == 1)] = -9
    assert totals() == (2000, int(want.sum()))
    r = s.execute("SELECT ln, v FROM li WHERE ok = 251 ORDER BY ln")
    np.testing.assert_array_equal(r.cols["ln"][0], [1, 2, 3, 4])
    np.testing.assert_array_equal(r.cols["v"][0], want[ok == 251])
    j = s.execute("SELECT COUNT(*) AS n, SUM(l.v * o.w) AS sv "
                  "FROM li l JOIN od o ON l.ok = o.ok")
    assert (int(j.cols["n"][0][0]), int(j.cols["sv"][0][0])) == \
        (2000, int(want.sum()))
    for sh in t.shards:
        sh.compact()
    assert totals() == (2000, int(want.sum()))
