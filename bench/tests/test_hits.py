"""The cell ``clickbench-hits.topk``: its files found by name, the rows
and bytes ``work.py`` reckons for its three statements, its four readers
over a hand-made run (nothing, and no raise, where the source is
missing, as under a program without the ``concat`` / ``transform``
keys), and the cell rehearsed on the CPU through the harness's own
``run_cell``."""

import clickbench_gen
import pytest
import run
import work

CELL = "clickbench-hits.topk"
HITS_METRICS = ("hits_concat_ms", "hits_transform_ms", "hits_roofline_share",
                "hits_device_idle_share")
STATEMENTS = ("hits_q12", "hits_q15", "hits_q16")
SIX = {"plan": 0.001, "pull": 0.010, "dispatch": 0.020,
       "device_wait": 0.100, "fetch": 0.002, "unattributed": 0.05}


def reader(name: str):
    return run.load_module(run.HERE, "layer_metrics", name).read


def test_the_cell_lists_its_four_metrics_and_its_traffic():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert tuple(m["name"] for m in cell["per_layer"]) == HITS_METRICS
    assert [m["name"] for m in cell["end_to_end"]] == [
        "rows_per_s", "query_geomean_ms", "setup_s"]
    assert cell["traffic"] == {
        "loop": "closed", "clients": 1, "statements": list(STATEMENTS),
        "warm_rounds": 1, "trace_seconds": 20,
        "executors": dict.fromkeys(STATEMENTS, "walk")}
    config = cell["config"]
    assert config["scale_factor"] == config["published"]["scale_factor"] \
        == 12.5
    assert config["tables"] == ["hits_probe", "hits"]
    assert config["guarantees"]["upsert_probe_table"] == "hits_probe"
    assert config["table_options"] == {"store": "column", "shards": 1,
                                       "upsert": "on"}
    assert config["mesh"] is False and config["generator_options"] == {}


class Rows:
    """Row counts only: the widths are the schema's."""

    def rows(self, t):
        return {"hits": 12_500_000, "hits_probe": 4096}[t]

    def schema(self, t):
        return clickbench_gen.SCHEMA


# hand-counted: a dictionary id 4, an int64 8
@pytest.mark.parametrize("sid,row_bytes", (("hits_q12", 4), ("hits_q15", 8),
                                           ("hits_q16", 12)))
def test_rows_and_bytes_of_a_statement_over_hits(sid, row_bytes):
    ref = run.load_module(run.HERE, "refs", sid)
    assert work.statement_rows(ref.TABLES, Rows()) == 12_500_000
    assert work.statement_bytes(ref.TABLES, Rows(), clickbench_gen.WIDTHS) \
        == 12_500_000 * row_bytes
    sql = (run.HERE / "statements" / f"{sid}.sql").read_text()
    assert all(c in sql for c in ref.TABLES["hits"])
    assert ref.PARAMS == {}


def test_a_row_of_hits_is_360_bytes_on_the_device():
    assert len(clickbench_gen.SCHEMA) == 105
    assert sum(clickbench_gen.WIDTHS[t]
               for _, t in clickbench_gen.SCHEMA) == 360


@pytest.mark.parametrize("name,key", (("hits_concat_ms", "concat"),
                                      ("hits_transform_ms", "transform")))
def test_a_span_reader_means_its_key_over_the_statements_that_have_it(
        name, key):
    run_ = {"statements": [
        {"id": "hits_q15", "stages": dict(SIX, **{key: 0.5})},
        {"id": "hits_q16", "stages": dict(SIX, **{key: 1.5})},
        {"id": "pushed_down", "stages": dict(SIX)},
        {"id": "untraced"}]}
    assert reader(name)(run_) == pytest.approx(1000.0)
    # a program without the key (the parent), or an untraced run
    assert reader(name)({"statements": [{"id": "q", "stages": SIX},
                                        {"id": "q"}]}) is None
    assert reader(name)({"statements": []}) is None


def test_the_trace_readers_use_the_scan_cells_formulas():
    run_ = {"least_seconds": 0.001,
            "trace": {"busy_s": 4.0, "window_s": 5.0, "devices": 1}}
    assert reader("hits_roofline_share")(run_) == pytest.approx(0.025)
    assert reader("hits_device_idle_share")(run_) == pytest.approx(20.0)
    for name, accepted in (("hits_roofline_share", "device_roofline_share"),
                           ("hits_device_idle_share", "device_idle_share")):
        assert reader(name)(run_) == reader(accepted)(run_)
        assert reader(name)({"trace": None, "least_seconds": None}) is None
        assert reader(name)({"least_seconds": 0.001, "trace": {
            "busy_s": 0.0, "window_s": 5.0}}) is None
    # on a CPU there are no peaks, so no least time
    assert reader("hits_roofline_share")(dict(run_, least_seconds=None)) \
        is None


def test_the_cell_rehearsed_on_the_cpu(small_cell, monkeypatch):
    """60,000 rows, with what the chip's size settles brought down: the
    table past the fused executor's reach, every dictionary past the
    dense group layout's (tests/test_hits_deployment.py)."""
    from ydb_tpu.ssa import compiler, plan_fuse

    monkeypatch.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    monkeypatch.setattr(compiler, "_DENSE_GROUP_LIMIT", 512)
    cell = small_cell(CELL, scale_factor=0.06)
    cell["traffic"]["executors"] = dict.fromkeys(STATEMENTS, "walk")
    res = run.run_cell(cell, seed=3700000007, seconds=1.0, trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] % 3 == 0
    # one 2^20-row block holds the table here, so nothing is concatenated
    # (12 blocks on the chip; tests/test_hits_deployment.py cuts 15)
    assert set(res["metrics"]) == {"hits_transform_ms"}
    assert res["metrics"]["hits_transform_ms"]["value"] > 0
