"""``correct`` has been shown to fail: the control (the reference in
float32 in the program's place) reads as not correct, a sound run reads
as correct, and a run with an answer altered where it is produced, or
with a stated guarantee broken underneath, comes out false."""

import numpy as np
import pytest
import compare
import control
import run

CELLS = ("tpch-sf3.scan", "tpch-sf1.join")


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", (1, 2147483999, 4000000123))
def test_the_control_fails_a_number_of_each_cell(small_cell, workload, seed):
    cell = small_cell(workload, scale_factor=0.05)
    limits = run.limits_for(run.load_statements(
        cell["dir"], cell["traffic"]["statements"]))
    for sid, v in control.control_readings(cell, seed).items():
        assert v["wrong_cells"] > limits["wrong_cells"], (sid, v)
        if "ratio_rel_gap" in limits and v["ratio_rel_gap"]:
            assert v["ratio_rel_gap"] > 3 * limits["ratio_rel_gap"]


def test_decode_and_compare():
    cols = {"k": ("int",), "d": ("decimal", 2), "day": ("date",),
            "s": ("dict", "s"), "avg": ("ratio",)}

    class D:
        def get(self, v):
            return {b"A": 0, b"B": 1}.get(v)

    got = compare.decode(
        ["k", "d", "day", "s", "avg"],
        [["7", "12.50", "1970-01-11", "B", "0.5"],
         ["8", "0.125", None, "Z", None]], cols, {"s": D()})
    assert got["k"].tolist() == [7, 8]
    assert got["d"].tolist() == [1250, compare.BAD]    # 0.125: not scale 2
    assert got["day"].tolist() == [10, compare.BAD]
    assert got["s"].tolist() == [1, compare.BAD]
    want = {"k": np.array([7, 8]), "d": np.array([1250, 12]),
            "day": np.array([10, 11]), "s": np.array([1, 0]),
            "avg": np.array([0.5, 0.25])}
    v = compare.compare(got, want, cols)
    assert v["wrong_cells"] == 3 and v["ratio_rel_gap"] == float("inf")
    same = compare.compare(want, want, cols)
    assert same == {"wrong_cells": 0, "ratio_rel_gap": 0.0}
    short = {k: a[:1] for k, a in want.items()}
    assert compare.compare(short, want, cols)["wrong_cells"] == 10


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(small_cell, workload):
    res = run.run_cell(small_cell(workload), seed=2147483999, seconds=0.5,
                       trace=False)
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_it_is_produced(small_cell, monkeypatch,
                                                workload):
    """The timed path broken underneath: one decimal of every result
    table comes out of Session.execute one unit of its last place off."""
    from ydb_tpu.engine.oracle import OracleTable
    from ydb_tpu.kqp.session import Session

    real = Session.execute

    def altered(self, sql, *a, **kw):
        out = real(self, sql, *a, **kw)
        if isinstance(out, OracleTable) and not sql.startswith(
                "SELECT COUNT(*)"):       # deploy.py's own count checks
            for f in out.schema.fields:
                if f.type.is_decimal:
                    vals, ok = out.cols[f.name]
                    vals = np.array(vals)
                    vals[-1] += 1
                    out.cols[f.name] = (vals, ok)
                    break
        return out

    monkeypatch.setattr(Session, "execute", altered)
    res = run.run_cell(small_cell(workload), seed=7, seconds=0.5, trace=False)
    assert not res["correct"]
    assert res["checks"]["wrong_cells"]["value"] > 0


def test_a_broken_upsert_guarantee_comes_out_false(small_cell):
    """``upsert = off`` underneath: a row written again under its key
    is appended, and the probe counts the rows left over."""
    cell = small_cell("tpch-sf3.scan")
    cell["config"]["table_options"]["upsert"] = "off"
    res = run.run_cell(cell, seed=3, seconds=0.3, trace=False)
    assert not res["correct"]
    assert res["checks"]["upsert_extra_rows"]["value"] > 0


def test_a_second_write_that_is_dropped_comes_out_false(small_cell,
                                                        monkeypatch):
    """The write is acknowledged and the old value kept: every insert
    into the probe table after the load writes the rows as they were
    loaded. The count stays right; the value read back by key is stale."""
    from ydb_tpu.tx.sharded import ShardedTable

    real, first = ShardedTable.insert, {}

    def keeps_the_old_value(self, cols, *a, **kw):
        if self.name == "region":
            cols = first.setdefault("region", cols)
        return real(self, cols, *a, **kw)

    monkeypatch.setattr(ShardedTable, "insert", keeps_the_old_value)
    res = run.run_cell(small_cell("tpch-sf3.scan"), seed=3, seconds=0.3,
                       trace=False)
    assert not res["correct"]
    assert res["checks"]["upsert_extra_rows"]["value"] == 0
    assert res["checks"]["upsert_stale_rows"]["value"] == 5


def test_a_compile_inside_the_window_comes_out_false(small_cell,
                                                     monkeypatch):
    import jax

    real = run.drive

    def compiles(*a, **kw):
        out = real(*a, **kw)
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.1)
        return out

    monkeypatch.setattr(run, "drive", compiles)
    res = run.run_cell(small_cell("tpch-sf3.scan"), seed=3, seconds=0.3,
                       trace=False)
    assert not res["correct"]
    assert res["checks"]["compiles_inside_the_window"]["value"] == 1


def test_another_executor_than_the_traffic_expects_comes_out_false(
        small_cell):
    """The cell's ``why`` names the path it times: a statement the
    program answers by another executor fails the run."""
    cell = small_cell("tpch-sf3.scan")
    assert run.load_cell("tpch-sf3.scan")["traffic"]["executors"] == {
        "q1": "walk", "q6": "walk"}
    cell["traffic"]["executors"] = {"q1": "fused", "q6": "walk"}
    res = run.run_cell(cell, seed=3, seconds=0.3, trace=False)
    assert not res["correct"]
    # q6 once in the warm-up and once in every round of the window
    assert (res["checks"]["unexpected_executor_statements"]["value"]
            == 1 + res["attempted"] // 2)


def test_a_statement_the_server_rejects_ends_the_run(small_cell,
                                                      monkeypatch):
    """No result line for a cell whose statement does not run at all:
    the warm-up raises what the wire returned."""
    import pgclient

    real = run.load_statements

    def broken(base, ids):
        st = real(base, ids)
        st["q6"]["sql"] = "select no_such_column from lineitem"
        return st

    monkeypatch.setattr(run, "load_statements", broken)
    with pytest.raises(pgclient.PgError, match="no_such_column"):
        run.run_cell(small_cell("tpch-sf3.scan"), seed=3, seconds=0.3,
                     trace=False)


def test_an_answer_that_never_comes_is_missing():
    st = {"q": {"ref": None}}
    out = run.check_answers([{"id": "q", "error": "PgError(...)"}], st, None)
    assert out == {"wrong_cells": 0, "missing_answers": 1}
