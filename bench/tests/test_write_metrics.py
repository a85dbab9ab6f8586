"""The readers of the write path's process counters, over hand-made
counters: each computes what its file says, and returns nothing, without
raising, where the program under it counts none of it (the parent
commit: the counters module is there, the groups are empty)."""

import json

import pytest
import run

from ydb_tpu.obs import counters, tracing

WRITE_METRICS = ("write_rows_per_s", "write_sort_ms", "write_blob_ms",
                 "write_index_ms", "write_route_ms", "promote_gb_per_s",
                 "resident_lag_ms", "promote_declined", "compile_built_s")


def reader(name: str):
    return run.load_module(run.HERE, "layer_metrics", name).read


@pytest.fixture
def root(monkeypatch):
    """A process root of the test's own."""
    fresh = counters.CounterGroup()
    monkeypatch.setattr(counters, "_root", fresh)
    return fresh


@pytest.fixture
def loaded(root):
    """2M rows written in 4 s: 3.6 s in stages, 0.4 s the span's own."""
    w = root.group(component="write")
    w.counter("rows").inc(2_000_000)
    w.counter("seconds").inc(4.0)
    for stage, s in {"encode": 0.1, "route": 0.5, "buffer": 0.2,
                     "commit": 0.2, "concat": 0.3, "sort": 0.9,
                     "blob": 1.0, "log": 0.1, "index": 0.3}.items():
        w.group(stage=stage).counter("stage_seconds").inc(s)
    r = root.group(component="resident")
    r.counter("promotions").inc(10)
    r.counter("promote_bytes").inc(3_000_000_000)
    r.group(stage="put").counter("promote_seconds").inc(1.5)
    for lag in (0.010, 0.030):
        r.histogram("resident_lag_seconds").observe(lag)
    return root


def test_each_reader_computes_what_its_file_says(loaded):
    assert reader("write_rows_per_s")({}) == pytest.approx(500_000.0)
    assert reader("write_sort_ms")({}) == pytest.approx(600.0)
    assert reader("write_blob_ms")({}) == pytest.approx(550.0)
    assert reader("write_index_ms")({}) == pytest.approx(150.0)
    assert reader("write_route_ms")({}) == pytest.approx(500.0)
    assert reader("promote_gb_per_s")({}) == pytest.approx(2.0)
    assert reader("resident_lag_ms")({}) == pytest.approx(20.0)
    assert reader("promote_declined")({}) == 0.0
    # the four stage metrics sum to no more than the write's own time a
    # 10^6 rows: what is missing is the `write` span's self time
    stages = sum(reader(n)({}) for n in WRITE_METRICS[1:5])
    assert stages == pytest.approx(1800.0)
    assert stages <= 1e9 / reader("write_rows_per_s")({})


def test_declined_promotions_sum_over_the_reasons(loaded):
    r = loaded.group(component="resident")
    r.group(reason="inflight_full").counter("promote_declined").inc(3)
    r.group(reason="in_flight").counter("promote_declined").inc(1)
    assert reader("promote_declined")({}) == 4.0


def test_compile_built_s_reads_the_listeners_seconds(monkeypatch):
    monkeypatch.setattr(tracing, "compile_counts", lambda: {
        "built": 3, "fetched": 9, "seconds": 7.0, "built_seconds": 6.5,
        "fetched_seconds": 0.5})
    assert reader("compile_built_s")({}) == 6.5


@pytest.mark.parametrize("name", WRITE_METRICS)
def test_a_program_that_counts_none_of_it_has_nothing_to_read(
        root, monkeypatch, name):
    monkeypatch.setattr(tracing, "compile_counts", lambda: {
        "built": 3, "fetched": 9, "seconds": 7.0})
    assert reader(name)({}) is None


def test_profiling_off_leaves_the_rate_and_no_stage(root):
    w = root.group(component="write")
    w.counter("rows").inc(1000)
    w.counter("seconds").inc(0.5)
    assert reader("write_rows_per_s")({}) == pytest.approx(2000.0)
    for name in WRITE_METRICS[1:5]:
        assert reader(name)({}) is None


def test_all_five_cells_list_the_nine_metrics_and_nothing_else_moved():
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    new = bench["per_layer"][-len(WRITE_METRICS):]
    assert [m["name"] for m in new] == list(WRITE_METRICS)
    for m in new:
        assert m["workloads"] == cells and m["moves"] == "setup_s"
        assert m["source"] == "program_counter"
        assert m["layer"] == ("kernels" if m["name"] == "compile_built_s"
                              else "write path")
