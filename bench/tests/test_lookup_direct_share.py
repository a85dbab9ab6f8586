"""``tpcds_lookup_direct_share``: listed for both TPC-DS cells (a subset
check: later PRs may list more metrics), and its reader over hand-made
counters."""

import pytest
import run
import write_counters

CELLS = ("tpcds-store.star", "tpcds-store.rollup")
NAME = "tpcds_lookup_direct_share"


@pytest.mark.parametrize("workload", CELLS)
def test_both_tpcds_cells_list_it(workload):
    cell = run.load_cell(workload)
    assert {NAME} <= {m["name"] for m in cell["per_layer"]}
    (m,) = [m for m in cell["per_layer"] if m["name"] == NAME]
    assert (m["unit"], m["layer"], m["moves"]) == (
        "%", "joins", "query_geomean_ms")


@pytest.mark.parametrize("direct, search, want", (
    (7, 2, 700 / 9), (3, 0, 100.0), (0, 4, 0.0), (0, 0, None)))
def test_the_share_of_lookups_that_took_the_table(monkeypatch, direct,
                                                  search, want):
    counts = {("join", "lookups", "direct"): direct,
              ("join", "lookups", "search"): search}
    monkeypatch.setattr(
        write_counters, "count",
        lambda component, name, path: float(counts[component, name, path]))
    read = run.load_module(run.HERE, "layer_metrics", NAME).read
    got = read({"statements": []})
    assert got == (None if want is None else pytest.approx(want))
