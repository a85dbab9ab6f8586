"""The four readers of the four-chip join cell
``tpch-sf1-4chip.join-mesh``: each over a hand-made run, nothing (and no
raise) where its source is missing, as under a program without the
``mesh_shuffle`` / ``mesh_join`` keys, and the cell rehearsed on four
virtual CPU devices through the harness's own ``run_cell``."""

import os

# before JAX starts its backend: the rehearsal needs a mesh of four
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest
import run

CELL = "tpch-sf1-4chip.join-mesh"
JOIN_MESH_METRICS = ("mesh_shuffle_ms", "mesh_join_ms",
                     "mesh_join_scan_ms", "mesh_join_roofline_share")
SPAN_METRICS = JOIN_MESH_METRICS[:3]
SIX = {"plan": 0.001, "pull": 0.010, "dispatch": 0.020,
       "device_wait": 0.100, "fetch": 0.002, "unattributed": 0.05}


def reader(name: str):
    return run.load_module(run.HERE, "layer_metrics", name).read


def test_the_cell_lists_its_four_metrics_and_asks_for_four_chips():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 4
    assert cell["config"]["mesh"] is True
    assert cell["config"]["table_options"]["shards"] == 4
    assert tuple(m["name"] for m in cell["per_layer"]) == JOIN_MESH_METRICS
    assert [m["name"] for m in cell["end_to_end"]] == [
        "rows_per_s", "query_geomean_ms", "setup_s"]
    assert cell["traffic"] == {
        "loop": "closed", "clients": 1, "statements": ["q3"],
        "warm_rounds": 1, "trace_seconds": 12,
        "executors": {"q3": "mesh-walk"}}


def test_the_deployment_is_the_scan_mesh_cells_at_scale_factor_1():
    """``tpch-sf3-4chip``'s file but for the scale and its reason, and
    the one-chip join cell's data: the pair differs in the cluster
    alone."""
    four = run.load_cell(CELL)["config"]
    scan = run.load_cell("tpch-sf3-4chip.scan-mesh")["config"]
    one = run.load_cell("tpch-sf1.join")["config"]
    assert {k for k in set(four) | set(scan) if four.get(k) != scan.get(k)} \
        == {"scale_factor", "assumed"}
    assert four["scale_factor"] == one["scale_factor"] == 1
    assert four["published"] == {"scale_factor": 50}
    assert set(four["assumed"]) ^ set(scan["assumed"]) == {
        "why_scale_factor_1", "why_scale_factor_3"}
    assert {k: v for k, v in four["assumed"].items()
            if k != "why_scale_factor_1"} == {
        k: v for k, v in scan["assumed"].items()
        if k != "why_scale_factor_3"}
    assert four["generator_options"] == one["generator_options"]
    assert four["tables"] == one["tables"]


def test_mesh_join_roofline_share_divides_by_every_chips_bandwidth():
    # one chip's least time for all the bytes is 4 s; each of the four
    # devices was busy 1 s, a quarter of the bytes at its peak: all
    # roofline
    at_peak = {"least_seconds": 4.0,
               "trace": {"busy_s": 1.0, "window_s": 1.0, "devices": 4}}
    assert reader("mesh_join_roofline_share")(at_peak) == pytest.approx(
        100.0)
    slower = dict(at_peak, trace={"busy_s": 8.0, "window_s": 40.0,
                                  "devices": 4})
    assert reader("mesh_join_roofline_share")(slower) == pytest.approx(12.5)


def test_a_run_without_a_trace_has_no_roofline_share():
    read = reader("mesh_join_roofline_share")
    for nothing in ({}, {"trace": None, "least_seconds": 1.0},
                    {"trace": {"busy_s": 0.0, "window_s": 1.0,
                               "devices": 4}, "least_seconds": 1.0},
                    {"trace": {"busy_s": 1.0, "window_s": 1.0,
                               "devices": 4}, "least_seconds": None},
                    {"trace": {"busy_s": 1.0, "window_s": 1.0},
                     "least_seconds": 1.0}):
        assert read(nothing) is None


def test_the_span_readers_take_the_mean_of_the_joining_statements():
    joined = dict(SIX, mesh=0.002, mesh_shuffle=0.300, mesh_join=0.040)
    run_ = {"statements": [
        {"server_s": 1.0, "stages": joined},
        {"server_s": 1.0, "stages": dict(joined, mesh_shuffle=0.500,
                                         mesh_join=0.060, pull=0.030)},
        {"server_s": 1.0, "stages": dict(SIX, mesh=0.004)},  # Q1 or Q6
        {"server_s": 1.0, "stages": SIX},       # answered off the mesh
        {"server_s": 2.0},                      # an untraced statement
    ]}
    assert reader("mesh_shuffle_ms")(run_) == pytest.approx(400.0)
    assert reader("mesh_join_ms")(run_) == pytest.approx(50.0)
    assert reader("mesh_join_scan_ms")(run_) == pytest.approx(
        1000.0 * (0.132 + 0.152) / 2)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_keys_has_nothing_to_read(name):
    # the parent commit, or a statement that exchanged nothing
    for statements in ([], [{"server_s": 1.0}],
                       [{"server_s": 1.0, "stages": None}],
                       [{"server_s": 1.0, "stages": SIX}],
                       [{"server_s": 1.0, "stages": dict(SIX, mesh=0.1)}]):
        assert reader(name)({"statements": statements}) is None


def test_a_rehearsal_of_the_cell_on_four_virtual_devices(
        small_cell, monkeypatch):
    import jax

    from ydb_tpu.ssa import plan_fuse

    if len(jax.devices()) < 4:
        pytest.skip("JAX started before this file asked for 4 devices")
    # SF 1 has 1.5M lineitem rows a device, far above the fusion cutoff
    monkeypatch.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    expected = run.load_cell(CELL)["traffic"]["executors"]
    cell = small_cell(CELL)
    cell["traffic"]["executors"] = expected
    res = run.run_cell(cell, seed=2147483999, seconds=0.5, trace=True)
    assert res["correct"], res["checks"]
    assert res["checks"]["unexpected_executor_statements"]["value"] == 0
    assert res["checks"]["wrong_cells"]["value"] == 0
    assert res["device"]["count"] >= 4
    got = res["metrics"]
    for name in SPAN_METRICS:
        assert got[name]["value"] > 0, name
    # peaks exist for a TPU only, and the CPU's trace has no device
    # plane: nothing to divide
    assert "mesh_join_roofline_share" not in got
