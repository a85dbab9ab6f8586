"""Distributed plan execution on the 8-device CPU mesh: grace-style
hash-repartition joins (VERDICT r4 item 3) and the portion store feeding
the mesh (item 4). Results must match the single-chip executor / oracle
bit-for-bit on integers."""

import numpy as np
import pytest

from ydb_tpu.engine.scan import ColumnSource
from ydb_tpu.parallel.dist import MeshScan
from ydb_tpu.parallel.mesh import make_mesh
from ydb_tpu.parallel.mesh_exec import MeshDatabase, MeshPlanExecutor
from ydb_tpu.plan import Database, execute_plan, to_host
from ydb_tpu.sql.parser import parse
from ydb_tpu.sql.planner import Catalog, plan_select_full
from ydb_tpu.workload import tpch
from ydb_tpu.workload.queries import TPCH

N_DEV = 8


@pytest.fixture(scope="module")
def data():
    return tpch.TpchData(sf=0.005, seed=23)


@pytest.fixture(scope="module")
def catalog(data):
    return Catalog(
        schemas={t: data.schema(t) for t in data.tables},
        primary_keys=dict(tpch.PRIMARY_KEYS),
        dicts=data.dicts,
    )


def _shard_source(data, table, s, n):
    """Round-robin row partition s of n for a table."""
    cols = data.tables[table]
    return ColumnSource(
        {k: v[s::n] for k, v in cols.items()},
        data.schema(table), data.dicts,
    )


@pytest.fixture(scope="module")
def mesh_db(data):
    return MeshDatabase(
        sources={
            t: [_shard_source(data, t, s, N_DEV) for s in range(N_DEV)]
            for t in data.tables
        },
        dicts=data.dicts,
    )


@pytest.fixture(scope="module")
def single_db(data):
    return Database(
        sources={
            t: ColumnSource(cols, data.schema(t), data.dicts)
            for t, cols in data.tables.items()
        },
        dicts=data.dicts,
    )


def _match(mesh_res, ref_res, int_cols, float_cols=()):
    assert mesh_res.num_rows == ref_res.num_rows
    for c in int_cols:
        np.testing.assert_array_equal(
            np.asarray(mesh_res.cols[c][0]), np.asarray(ref_res.cols[c][0]),
            err_msg=c)
    for c in float_cols:
        np.testing.assert_allclose(
            np.asarray(mesh_res.cols[c][0], dtype=np.float64),
            np.asarray(ref_res.cols[c][0], dtype=np.float64),
            rtol=1e-9, err_msg=c)


@pytest.mark.slow  # per-stage 8-dev traces dominate single-core CI
def test_q3_mesh_join_matches_single_chip(data, catalog, mesh_db,
                                          single_db):
    plan = plan_select_full(parse(TPCH["q3"]), catalog).plan
    mesh = make_mesh(N_DEV)
    ex = MeshPlanExecutor(mesh_db, mesh)
    res = ex.execute(plan)
    ref = to_host(execute_plan(plan, single_db))
    _match(res, ref, ("l_orderkey", "revenue", "o_orderdate",
                      "o_shippriority"))


@pytest.mark.slow  # per-stage 8-dev traces dominate single-core CI
def test_q5_mesh_join_matches_single_chip(data, catalog, mesh_db,
                                          single_db):
    plan = plan_select_full(parse(TPCH["q5"]), catalog).plan
    mesh = make_mesh(N_DEV)
    ex = MeshPlanExecutor(mesh_db, mesh)
    res = ex.execute(plan)
    ref = to_host(execute_plan(plan, single_db))
    _match(res, ref, ("n_name", "revenue"))


def test_mesh_scan_from_portion_store(tmp_path, data):
    """Sharded ON-DISK table scanned via per-shard portion streams on the
    mesh: out-of-core and multi-chip compose (VERDICT r4 item 4)."""
    from ydb_tpu.engine.blobs import DirBlobStore
    from ydb_tpu.engine.reader import PortionStreamSource
    from ydb_tpu.engine.shard import ColumnShard, ShardConfig
    from ydb_tpu.engine.oracle import OracleTable, run_oracle

    li = data.tables["lineitem"]
    n = len(li["l_orderkey"])
    shards = []
    for s in range(N_DEV):
        store = DirBlobStore(str(tmp_path / f"s{s}"))
        shard = ColumnShard(
            f"s{s}", tpch.LINEITEM_SCHEMA, store, dicts=data.dicts,
            config=ShardConfig(compact_portion_threshold=10 ** 9,
                               portion_chunk_rows=1 << 10),
        )
        # several portions per shard so the stream really streams
        idx = np.arange(s, n, N_DEV)
        for piece in np.array_split(idx, 3):
            wid = shard.write({k: v[piece] for k, v in li.items()})
            shard.commit([wid])
        shards.append(shard)

    mesh = make_mesh(N_DEV)
    prog = tpch.q1_program()
    scan = MeshScan(prog, tpch.LINEITEM_SCHEMA, data.dicts, mesh=mesh)
    assert scan.partial.group_layout[0] == "dense_slots"

    def fresh_sources(of):
        return [PortionStreamSource(sh, sh.visible_portions(),
                                    columns=of.read_cols)
                for sh in shards]

    res = scan.execute_sources(fresh_sources(scan), block_rows=1 << 12)

    table = OracleTable(
        {k: (v, np.ones(len(v), dtype=bool)) for k, v in li.items()},
        tpch.LINEITEM_SCHEMA)
    ora = run_oracle(prog, table, data.dicts)
    assert res.num_rows == ora.num_rows
    for name in ("sum_qty", "sum_charge", "count_order"):
        np.testing.assert_allclose(
            np.asarray(res.cols[name][0], dtype=np.float64),
            np.asarray(ora.cols[name][0], dtype=np.float64), rtol=1e-9,
            err_msg=name)

    # past INFLIGHT_BLOCKS programs in a device's queue the driver
    # waits for the oldest (each pins its input block): the same answer
    from ydb_tpu.obs import profile as profile_mod
    from ydb_tpu.parallel import dist

    def waits(limit):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(dist, "INFLIGHT_BLOCKS", limit)
            with profile_mod.profiled() as held:
                out = scan.execute_sources(fresh_sources(scan),
                                           block_rows=1 << 9)
        for name in res.cols:
            np.testing.assert_array_equal(out.cols[name][0],
                                          res.cols[name][0])
        spans = held.profile.spans
        blocks = sum(sp["name"] == "dispatch"
                     and sp["attrs"].get("program") == "scan_partial"
                     for sp in spans)
        # the driver's own waits sit right under a shard's scan span (a
        # new shape's one-off timing sync sits under its dispatch)
        scans = {sp["span_id"] for sp in spans if sp["name"] == "scan"}
        return blocks, sum(sp["name"] == "device.wait"
                           and sp["parent_id"] in scans for sp in spans)

    blocks, waited = waits(1)
    assert blocks > N_DEV and waited == blocks - N_DEV
    assert waits(blocks) == (blocks, 0)

    # a compact layout (unbounded keys) has no slot-aligned state to
    # fold: the streaming driver refuses it, MeshScan.execute takes it
    from ydb_tpu.ssa import Agg, AggSpec, GroupByStep, Program, SortStep

    prog2 = Program((
        GroupByStep(keys=("l_orderkey",), aggs=(
            AggSpec(Agg.SUM, "l_extendedprice", "total"),
            AggSpec(Agg.COUNT_ALL, None, "cnt"),
        )),
        SortStep(keys=("l_orderkey",)),
    ))
    scan2 = MeshScan(prog2, tpch.LINEITEM_SCHEMA, data.dicts, mesh=mesh)
    assert scan2.partial.group_layout[0] == "compact"
    assert not scan2.folds_partials
    with pytest.raises(ValueError, match="compact"):
        scan2.execute_sources(fresh_sources(scan2), block_rows=1 << 12)


def test_mesh_from_sql_session():
    """Cluster.enable_mesh routes session SELECTs (join AND scan+agg)
    SPMD over the mesh, with shard counts != device count grouped via
    device_partitions — results identical to the non-mesh path
    (VERDICT r4 item 4: the mesh reachable from SQL text)."""
    import numpy as np

    from ydb_tpu.kqp.session import Cluster
    from ydb_tpu.plan import executor as ex

    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE musers (id int64, grp int64, "
              "PRIMARY KEY (id)) WITH (shards = 3)")
    s.execute("CREATE TABLE morders (oid int64, uid int64, amount int64,"
              " PRIMARY KEY (oid)) WITH (shards = 5)")
    for i in range(0, 120, 30):
        s.execute("INSERT INTO musers VALUES " + ", ".join(
            f"({j}, {j % 4})" for j in range(i, i + 30)))
    for i in range(0, 600, 100):
        s.execute("INSERT INTO morders VALUES " + ", ".join(
            f"({j}, {j % 120}, {j % 13})" for j in range(i, i + 100)))
    q = ("SELECT u.grp AS g, SUM(o.amount) AS total, COUNT(*) AS n "
         "FROM morders o JOIN musers u ON o.uid = u.id "
         "GROUP BY u.grp ORDER BY g")
    q2 = ("SELECT o.uid AS u2, SUM(o.amount) AS t FROM morders o "
          "GROUP BY o.uid ORDER BY t DESC, u2 LIMIT 5")
    ref, ref2 = s.execute(q), s.execute(q2)
    c.enable_mesh()
    calls = []
    orig = ex._execute_plan_mesh

    def spy(p, d):
        r = orig(p, d)
        calls.append(r)
        return r

    ex._execute_plan_mesh = spy
    try:
        res, res2 = s.execute(q), s.execute(q2)
    finally:
        ex._execute_plan_mesh = orig
    # invoked AND succeeded (a None would mean a silent fallback to
    # DQ/recursive produced the matching rows, not the mesh)
    assert len(calls) == 2 and all(r is not None for r in calls), calls
    for col in ("g", "total", "n"):
        np.testing.assert_array_equal(
            np.asarray(res.cols[col][0]), np.asarray(ref.cols[col][0]),
            err_msg=col)
    for col in ("u2", "t"):
        np.testing.assert_array_equal(
            np.asarray(res2.cols[col][0]), np.asarray(ref2.cols[col][0]),
            err_msg=col)
