"""The readers of the program's span tree: each returns a number on a
traced run of a tiny cell (CPU), and nothing, without raising, where the
program under it has no such stage or counter (the parent commit)."""

import pytest
import run

SPAN_METRICS = ("plan_ms", "stage_wait_ms", "dispatch_host_ms",
                "device_wait_ms", "span_coverage")


def reader(name: str):
    return run.load_module(run.HERE, "layer_metrics", name).read


@pytest.mark.parametrize("workload", ["tpch-sf3.scan", "tpch-sf1.join"])
def test_every_new_reader_reads_a_traced_run(small_cell, workload):
    cell = small_cell(workload)
    assert {m["name"] for m in cell["per_layer"]} >= set(
        SPAN_METRICS) | {"programs_built"}
    res = run.run_cell(cell, seed=2147483999, seconds=0.5, trace=True)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in SPAN_METRICS:
        assert got[name]["value"] > 0, name
    assert 50.0 < got["span_coverage"]["value"] <= 100.0
    # a cache directory of the test's own: every program was built
    assert got["programs_built"]["value"] >= 1


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_stage_has_nothing_to_read(name):
    old = {"read": 0.1, "merge": 0.0, "stage": 0.2, "compute": 0.3}
    for statements in ([], [{"server_s": 1.0}],
                       [{"server_s": 1.0, "stages": None}],
                       [{"server_s": 1.0, "stages": old}]):
        assert reader(name)({"statements": statements}) is None


def test_the_stage_readers_take_the_mean_per_statement():
    stages = {"plan": 0.001, "pull": 0.010, "dispatch": 0.020,
              "device_wait": 0.100, "fetch": 0.001, "unattributed": 0.05}
    run_ = {"statements": [
        {"server_s": 1.0, "stages": stages},
        {"server_s": 0.5, "stages": dict(stages, pull=0.030)},
        {"server_s": 2.0},                      # an untraced statement
    ]}
    assert reader("plan_ms")(run_) == pytest.approx(1.0)
    assert reader("stage_wait_ms")(run_) == pytest.approx(20.0)
    assert reader("dispatch_host_ms")(run_) == pytest.approx(20.0)
    assert reader("device_wait_ms")(run_) == pytest.approx(100.0)
    assert reader("span_coverage")(run_) == pytest.approx(
        100.0 * (0.95 + 0.90) / 2)


def test_programs_built_reads_the_programs_counter(monkeypatch):
    from ydb_tpu.obs import tracing

    monkeypatch.setattr(tracing, "compile_counts",
                        lambda: {"built": 3, "fetched": 9, "seconds": 1.0})
    assert reader("programs_built")({}) == 3.0
    monkeypatch.delattr(tracing, "compile_counts")   # the parent commit
    assert reader("programs_built")({}) is None
