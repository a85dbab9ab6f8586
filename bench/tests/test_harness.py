"""The harness refuses what it cannot measure, and finds a cell made
only of new files by name."""

import json
import types

import pytest
import run
import work


def test_a_platform_that_is_no_tpu_ends_the_run_nonzero(capsys):
    with pytest.raises(run.BenchError, match="needs a TPU"):
        run.check_device(1)
    rc = run.main(["--workload", "tpch-sf3.scan", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "needs a TPU" in out.err


def fake_devices(kind: str, n: int = 1):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind)] * n


def test_an_unknown_device_kind_ends_the_run_nonzero(monkeypatch, capsys):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: fake_devices("TPU v9"))
    with pytest.raises(work.UnknownDevice, match="TPU v9"):
        run.check_device(1)
    rc = run.main(["--workload", "tpch-sf3.scan", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "TPU v9" in out.err


def test_fewer_chips_than_the_cell_asks_for(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: fake_devices("TPU v5 lite"))
    assert run.check_device(1)["count"] == 1
    with pytest.raises(run.BenchError, match="4 chips"):
        run.check_device(4)


def test_an_unknown_workload_is_refused():
    with pytest.raises(run.BenchError, match="no workload"):
        run.load_cell("no-such-cell")


def test_an_open_loop_is_accepted_as_data_and_not_built_yet():
    with pytest.raises(NotImplementedError, match="not built yet"):
        run.drive(0, {"loop": "open", "rate_per_s": 5, "clients": 1,
                      "statements": ["q1"]}, {}, 1.0)


def test_every_committed_cell_resolves_to_its_files():
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        run.load_statements(cell["dir"], cell["traffic"]["statements"])
        run.load_module(cell["dir"], "", cell["config"]["generator"])
        assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}
        for m in cell["per_layer"]:
            assert callable(run.load_module(
                cell["dir"], "layer_metrics", m["name"]).read)


# ---- a cell made only of new files, found by name ---------------------

GENERATOR = '''
import numpy as np

class Dict:
    values = []
    def get(self, v): return None

class Data:
    widths = {"int64": 8}
    def __init__(self, sf, seed):
        n = int(1000 * sf)
        rng = np.random.default_rng(seed)
        self.tables = {"t": {"k": np.arange(n, dtype=np.int64),
                             "v": rng.integers(0, 100, n, dtype=np.int64)}}
        self.dicts = self
    def columns(self): return []
    def rows(self, t): return len(self.tables[t]["k"])
    def schema(self, t): return (("k", "int64"), ("v", "int64"))
    def primary_key(self, t): return ("k",)

def make(sf, seed): return Data(sf, seed)
'''
REF = '''
import numpy as np
TABLES = {"t": ("v",)}
COLUMNS = {"total": ("int",)}
def reference(data, arith="exact"):
    return {"total": np.array([int(data.tables["t"]["v"].sum())])}
'''
METRIC = '''
def read(run):
    return float(len(run["statements"]))
'''


@pytest.fixture
def new_files_only(tmp_path):
    b = tmp_path / "newbench"
    for d in ("configs", "traffic", "statements", "refs", "layer_metrics"):
        (b / d).mkdir(parents=True)
    (b / "dummy_gen.py").write_text(GENERATOR)
    (b / "configs" / "dummy.json").write_text(json.dumps({
        "scale_factor": 2, "chips": 1, "generator": "dummy_gen",
        "tables": ["t"],
        "table_options": {"store": "column", "shards": 1, "upsert": "on"},
        "guarantees": {"upsert_probe_table": "t"}}))
    (b / "traffic" / "sums.json").write_text(json.dumps({
        "loop": "closed", "clients": 2, "statements": ["total"],
        "warm_rounds": 1, "trace_seconds": 1}))
    (b / "statements" / "total.sql").write_text(
        "select sum(v) as total from t\n")
    (b / "refs" / "total.py").write_text(REF)
    (b / "layer_metrics" / "stmts_seen.py").write_text(METRIC)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["newbench"],
        "configs": [{"name": "dummy", "file": "newbench/configs/dummy.json"}],
        "workloads": [{"name": "dummy.sums", "config": "dummy",
                       "traffic": "sums", "chips": 1}],
        "end_to_end": [
            {"name": "rows_per_s", "unit": "rows/s"},
            {"name": "setup_s", "unit": "s"},
            {"name": "query_geomean_ms", "unit": "ms",
             "workloads": ["some-other-cell"]}],
        "per_layer": [
            {"name": "stmts_seen", "unit": "count",
             "workloads": ["dummy.sums"]},
            {"name": "elsewhere", "unit": "ms", "workloads": ["other"]}]}))
    return tmp_path


def test_a_cell_of_new_files_is_found_by_name_and_runs(
        new_files_only, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    cell = run.load_cell("dummy.sums", root=new_files_only)
    assert [m["name"] for m in cell["end_to_end"]] == ["rows_per_s",
                                                       "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == ["stmts_seen"]
    res = run.run_cell(cell, seed=5, seconds=0.3, trace=False)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 2
    assert set(res["metrics"]) == {"rows_per_s", "setup_s"}
    assert res["metrics"]["rows_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    traced = run.run_cell(cell, seed=6, seconds=0.3, trace=True)
    assert traced["correct"]
    assert traced["metrics"]["stmts_seen"]["value"] == traced["attempted"]
    assert {"busy_s", "window_s"} <= set(traced["device"])
