"""SQL through the DQ stage graph: planned SELECTs lower to scan ->
hash-partition channels -> grace-bucket join stages -> final aggregate,
executed by the credit-flow compute actors on the simulated multi-node
runtime — and match the single-chip executor (VERDICT r4 item 6)."""

import numpy as np
import pytest

from ydb_tpu.engine.scan import ColumnSource
from ydb_tpu.kqp.dq_lower import (
    execute_plan_dq,
    partition_source,
    plan_to_stages,
)
from ydb_tpu.plan import Database, execute_plan, to_host
from ydb_tpu.runtime.test_runtime import SimRuntime
from ydb_tpu.sql.parser import parse
from ydb_tpu.sql.planner import Catalog, plan_select_full
from ydb_tpu.workload import tpch
from ydb_tpu.workload.queries import TPCH

N_TASKS = 3


@pytest.fixture(scope="module")
def data():
    return tpch.TpchData(sf=0.004, seed=17)


@pytest.fixture(scope="module")
def catalog(data):
    return Catalog(
        schemas={t: data.schema(t) for t in data.tables},
        primary_keys=dict(tpch.PRIMARY_KEYS),
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


@pytest.fixture(scope="module")
def dq_sources(data):
    return {
        t: partition_source(
            ColumnSource(cols, data.schema(t), data.dicts), N_TASKS)
        for t, cols in data.tables.items()
    }


def _run_both(name, catalog, single_db, dq_sources, data):
    plan = plan_select_full(parse(TPCH[name]), catalog).plan
    ref = to_host(execute_plan(plan, single_db))
    rt = SimRuntime(n_nodes=2)
    res = execute_plan_dq(plan, dq_sources, rt, dicts=data.dicts,
                          n_tasks=N_TASKS, block_rows=1 << 12)
    return res, ref


def _match(res, ref, cols):
    assert res.num_rows == ref.num_rows
    for c in cols:
        np.testing.assert_array_equal(
            np.asarray(res.cols[c][0]), np.asarray(ref.cols[c][0]),
            err_msg=c)


def test_q1_through_dq(data, catalog, single_db, dq_sources):
    res, ref = _run_both("q1", catalog, single_db, dq_sources, data)
    _match(res, ref, ("l_returnflag", "l_linestatus", "sum_qty",
                      "sum_charge", "count_order"))


def test_q3_join_through_dq(data, catalog, single_db, dq_sources):
    res, ref = _run_both("q3", catalog, single_db, dq_sources, data)
    _match(res, ref, ("l_orderkey", "revenue", "o_orderdate",
                      "o_shippriority"))


def test_q5_join_chain_through_dq(data, catalog, single_db, dq_sources):
    res, ref = _run_both("q5", catalog, single_db, dq_sources, data)
    _match(res, ref, ("n_name", "revenue"))


def test_q12_case_agg_through_dq(data, catalog, single_db, dq_sources):
    res, ref = _run_both("q12", catalog, single_db, dq_sources, data)
    _match(res, ref, ("l_shipmode", "high_line_count", "low_line_count"))


def test_orderby_no_groupby_through_dq(data, catalog, single_db,
                                       dq_sources):
    """A group-less ORDER BY (and its LIMIT top-k) must apply ONCE over
    the merged inputs, not per block — the per-block sort + arrival-order
    concat regression (SortStep split in kqp/dq_lower._split_at_sort)."""
    sql = ("SELECT l.l_orderkey AS k, l.l_extendedprice AS p "
           "FROM lineitem l JOIN orders o ON l.l_orderkey = o.o_orderkey "
           "ORDER BY p DESC, k LIMIT 50")
    plan = plan_select_full(parse(sql), catalog).plan
    ref = to_host(execute_plan(plan, single_db, use_dq=False))
    rt = SimRuntime(n_nodes=2)
    res = execute_plan_dq(plan, dq_sources, rt, dicts=data.dicts,
                          n_tasks=N_TASKS, block_rows=1 << 10)
    _match(res, ref, ("k", "p"))


def test_default_executor_routes_joins_to_dq(catalog, single_db):
    """execute_plan (the production entry) runs join plans on the DQ
    stage graph by default; YDB_TPU_DQ=0 (use_dq=False) is the only way
    back to the recursive walk."""
    from ydb_tpu.plan import executor as ex

    plan = plan_select_full(parse(TPCH["q3"]), catalog).plan
    called = []
    orig = ex._execute_plan_dq
    ex._execute_plan_dq = lambda p, d: (called.append(1), orig(p, d))[1]
    try:
        out = to_host(execute_plan(plan, single_db))
    finally:
        ex._execute_plan_dq = orig
    assert called, "join plan bypassed the DQ executor"
    ref = to_host(execute_plan(plan, single_db, use_dq=False))
    _match(out, ref, ("l_orderkey", "revenue"))


def test_stage_graph_shape(catalog):
    """q3 lowers to scan stages -> hash-partitioned join stages -> one
    result transform; joins never get a whole-table UnionAll input."""
    from ydb_tpu.dq.graph import HashPartition, ResultOutput

    plan = plan_select_full(parse(TPCH["q3"]), catalog).plan
    stages = plan_to_stages(plan, n_tasks=4)
    joins = [s for s in stages if s.join is not None]
    assert len(joins) >= 2
    for s in joins:
        assert s.tasks == 4
        for inp in s.inputs:
            up = stages[inp.from_stage]
            assert isinstance(up.output, HashPartition)
    assert isinstance(stages[-1].output, ResultOutput)
    assert stages[-1].tasks == 1


@pytest.mark.parametrize("name", ["q3", "q5", "q12"])
def test_device_channels_match_host_channels(name, data, catalog,
                                             dq_sources):
    """The same plan on one node with its channels on the chip and (a
    budget of 0) through the host: the same rows in the same order,
    every column and its validity."""
    from ydb_tpu.engine.hbm import ChannelBudget
    from ydb_tpu.obs.counters import root_counters
    from ydb_tpu.runtime.actors import ActorSystem

    def channel_rows():
        g = root_counters().group(component="dq")
        return {p: g.group(path=p).counter("channel_rows").value
                for p in ("device", "host")}

    plan = plan_select_full(parse(TPCH[name]), catalog).plan
    runs = []
    for budget in (ChannelBudget(None), ChannelBudget(0)):
        before = channel_rows()
        res = execute_plan_dq(plan, dq_sources, ActorSystem(),
                              dicts=data.dicts, n_tasks=N_TASKS,
                              block_rows=1 << 12, channel_budget=budget)
        runs.append((res, {p: n - before[p]
                           for p, n in channel_rows().items()}))
    (dev, dev_rows), (host, host_rows) = runs
    assert dev.num_rows == host.num_rows > 0
    assert dev.schema.names == host.schema.names
    for c in host.schema.names:
        for i in (0, 1):
            np.testing.assert_array_equal(
                np.asarray(dev.cols[c][i]), np.asarray(host.cols[c][i]),
                err_msg=c)
    assert dev_rows["device"] > 0
    assert dev_rows["host"] == dev.num_rows  # the result's alone
    assert host_rows["device"] == 0


def test_the_dq_span_counts_channel_rows_by_path(catalog, single_db):
    """The session path's one-process graph: the ``dq`` span says how
    many channel rows rode the chip and how many the host carried, the
    result's alone."""
    from ydb_tpu.obs import tracing

    plan = plan_select_full(parse(TPCH["q3"]), catalog).plan
    tracer = tracing.Tracer()
    root = tracer.trace("query")
    with tracing.activate(root):
        out = to_host(execute_plan(plan, single_db))
    root.finish()
    dq = [s.attrs for s in tracer.spans_for(root.trace_id)
          if s.name == "dq"]
    assert len(dq) == 1
    assert dq[0]["device_channel_rows"] > 0
    assert dq[0]["host_channel_rows"] == out.num_rows
