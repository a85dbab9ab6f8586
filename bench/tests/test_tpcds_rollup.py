"""The cell ``tpcds-store.rollup``: its traffic and entries as they were
asked for, its five metrics listed for it alone, and its reference: the
float32 control fails ``wrong_cells``, a NULL cell decodes to ``BAD`` and
equals the reference's, and at this size the rollup has its nine levels
and the rank its eleven partitions."""

import json

import compare
import numpy as np
import pytest
import run
import tpcds_gen

CELL = "tpcds-store.rollup"
METRICS = ("tpcds_rollup_ms", "tpcds_window_ms",
           "tpcds_rollup_roofline_share", "tpcds_window_roofline_share",
           "tpcds_rollup_idle_share")
SEEDS = (2147483999, 4400000017)


def ref():
    return run.load_module(run.HERE, "refs", "tpcds_q67")


@pytest.fixture(scope="module", params=SEEDS)
def data(request):
    """The configuration's eighth of SF 0.5: 180,000 fact rows."""
    return tpcds_gen.make(0.5, request.param, fact_share=0.125)


def test_the_traffic_and_the_entries_are_as_asked():
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert cell == dict(cell, config="tpcds-q67-1chip",
                        traffic="tpcds-rollup", chips=1)
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    traffic = json.loads(
        (run.HERE / "traffic" / "tpcds-rollup.json").read_text())
    assert traffic == {"loop": "closed", "clients": 1,
                       "statements": ["tpcds_q67"], "warm_rounds": 2,
                       "trace_seconds": 12,
                       "executors": {"tpcds_q67": "dq"}}
    loaded = run.load_cell(CELL)
    assert loaded["config"]["generator"] == "tpcds_gen"
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "rows_per_s", "query_geomean_ms", "setup_s"]
    sql = (run.HERE / "statements" / "tpcds_q67.sql").read_text()
    assert "rollup(i_category, i_class, i_brand, i_product_name" in sql
    assert "between 1200 and 1200+11" in sql
    for table, cols in ref().TABLES.items():
        assert table in sql and all(c in sql for c in cols), table


def test_the_five_metrics_list_the_cell_alone():
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == [CELL], name
    assert [m["name"] for m in bench["per_layer"][-len(METRICS):]] == list(
        METRICS)
    # besides them, two accepted metrics list the cell too: the joins'
    # time and the HBM peak
    assert {m["name"] for m in run.load_cell(CELL)["per_layer"]} == set(
        METRICS) | {"tpcds_join_ms", "hbm_peak_gb"}


def test_the_float32_control_fails_wrong_cells(data):
    r = ref()
    got = compare.compare(r.reference(data, "float32"), r.reference(data),
                          r.COLUMNS)
    assert got["wrong_cells"] > 0


def test_a_null_cell_decodes_to_bad_and_equals_the_reference(data):
    r = ref()
    want = r.reference(data)
    names = list(want)
    # the answer as the wire carries it: a NULL is None, a text its text
    rows = []
    for i in range(len(want["rk"])):
        row = []
        for name in names:
            v = int(want[name][i])
            kind = r.COLUMNS[name]
            if v == compare.BAD:
                row.append(None)
            elif kind[0] == "dict":
                row.append(data.dicts[kind[1]].values[v].decode())
            elif kind[0] == "decimal":
                row.append(f"{v // 100}.{v % 100:02d}")
            else:
                row.append(str(v))
        rows.append(row)
    assert any(None in row for row in rows)
    got = compare.decode(names, rows, r.COLUMNS, data.dicts)
    assert compare.compare(got, want, r.COLUMNS)["wrong_cells"] == 0
    assert (got["i_product_name"] == compare.BAD).any()


def test_nine_levels_and_eleven_partitions(data):
    levels = ref().levels(data)
    assert len(levels) == 9
    sizes = [len(sums) for _, sums in levels]
    assert sizes == sorted(sizes, reverse=True) and sizes[-1] == 1
    for kept, (keys, _) in zip(range(8, -1, -1), levels):
        assert (keys[:, :kept] >= 0).all() and (keys[:, kept:] < 0).all()
    categories = np.concatenate([keys[:, 0] for keys, _ in levels])
    assert len(np.unique(categories)) == 11     # ten and the grand total
    total = levels[-1][1][0]
    assert all(sums.sum() == total for _, sums in levels)
