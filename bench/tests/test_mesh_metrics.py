"""The four readers of the four-chip cell ``tpch-sf3-4chip.scan-mesh``:
each over a hand-made run, nothing (and no raise) where its source is
missing, as under the parent commit, and the cell rehearsed on four
virtual CPU devices through the harness's own ``run_cell``."""

import os

# before JAX starts its backend: the rehearsal needs a mesh of four
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest
import run

CELL = "tpch-sf3-4chip.scan-mesh"
MESH_METRICS = ("mesh_roofline_share", "mesh_device_idle_share",
                "mesh_step_ms", "mesh_scan_host_ms")
SIX = {"plan": 0.001, "pull": 0.010, "dispatch": 0.020,
       "device_wait": 0.100, "fetch": 0.001, "unattributed": 0.05}


def reader(name: str):
    return run.load_module(run.HERE, "layer_metrics", name).read


def test_the_cell_lists_its_four_metrics_and_asks_for_four_chips():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 4
    assert cell["config"]["mesh"] is True
    assert cell["config"]["table_options"]["shards"] == 4
    assert tuple(m["name"] for m in cell["per_layer"]) == MESH_METRICS
    assert [m["name"] for m in cell["end_to_end"]] == [
        "rows_per_s", "query_geomean_ms", "setup_s"]
    assert cell["traffic"]["executors"] == {"q1": "mesh-walk",
                                            "q6": "mesh-walk"}


def test_the_deployment_is_the_one_chip_twin_but_for_the_cluster():
    """The same generator, options, tables and text pools: no column,
    width, key distribution or parameter differs."""
    four = run.load_cell(CELL)["config"]
    one = run.load_cell("tpch-sf3.scan")["config"]
    assert {k for k in set(four) | set(one) if four.get(k) != one.get(k)} \
        == {"source", "deployment", "published", "chips", "mesh",
            "table_options", "guarantees", "assumed"}
    assert four["scale_factor"] == one["scale_factor"] == 3
    assert four["published"] == {"scale_factor": 50}
    assert dict(four["table_options"], shards=1) == one["table_options"]
    assert {k: v for k, v in four["guarantees"].items()
            if k != "sharding"} == one["guarantees"]
    assert {k: v for k, v in four["assumed"].items()
            if k != "why_scale_factor_3"} == {
        k: v for k, v in one["assumed"].items()
        if k != "why_scale_factor_3"}


def test_mesh_roofline_share_divides_by_every_chips_bandwidth():
    # one chip's least time for all the bytes is 4 s; each of the four
    # devices was busy 1 s, a quarter of the bytes at its peak: all
    # roofline
    at_peak = {"least_seconds": 4.0,
               "trace": {"busy_s": 1.0, "window_s": 1.0, "devices": 4}}
    assert reader("mesh_roofline_share")(at_peak) == pytest.approx(100.0)
    # the one-chip reader over the same run reads four times too high
    assert reader("device_roofline_share")(at_peak) == pytest.approx(400.0)
    slower = dict(at_peak, trace={"busy_s": 8.0, "window_s": 40.0,
                                  "devices": 4})
    assert reader("mesh_roofline_share")(slower) == pytest.approx(12.5)


def test_mesh_device_idle_share_is_the_mean_devices_idle_time():
    run_ = {"trace": {"busy_s": 2.5, "window_s": 10.0, "devices": 4}}
    assert reader("mesh_device_idle_share")(run_) == pytest.approx(75.0)


@pytest.mark.parametrize("name", ("mesh_roofline_share",
                                  "mesh_device_idle_share"))
def test_a_run_without_a_trace_has_nothing_to_read(name):
    for nothing in ({}, {"trace": None, "least_seconds": 1.0},
                    {"trace": {"busy_s": 0.0, "window_s": 1.0,
                               "devices": 4}, "least_seconds": 1.0}):
        assert reader(name)(nothing) is None
    assert reader("mesh_roofline_share")(
        {"trace": {"busy_s": 1.0, "window_s": 1.0, "devices": 4},
         "least_seconds": None}) is None    # no peaks: not a TPU
    assert reader("mesh_roofline_share")(
        {"trace": {"busy_s": 1.0, "window_s": 1.0},
         "least_seconds": 1.0}) is None


def test_the_span_readers_take_the_mean_of_the_mesh_statements():
    run_ = {"statements": [
        {"server_s": 1.0, "stages": dict(SIX, mesh=0.002)},
        {"server_s": 1.0, "stages": dict(SIX, mesh=0.004, pull=0.030)},
        {"server_s": 1.0, "stages": SIX},       # answered off the mesh
        {"server_s": 2.0},                      # an untraced statement
    ]}
    assert reader("mesh_step_ms")(run_) == pytest.approx(3.0)
    assert reader("mesh_scan_host_ms")(run_) == pytest.approx(
        1000.0 * (0.130 + 0.150) / 2)


@pytest.mark.parametrize("name", ("mesh_step_ms", "mesh_scan_host_ms"))
def test_a_program_without_the_mesh_key_has_nothing_to_read(name):
    # the parent commit: no statement's stages hold the key
    for statements in ([], [{"server_s": 1.0}],
                       [{"server_s": 1.0, "stages": None}],
                       [{"server_s": 1.0, "stages": SIX}]):
        assert reader(name)({"statements": statements}) is None


def test_a_rehearsal_of_the_cell_on_four_virtual_devices(
        small_cell, monkeypatch):
    import jax

    from ydb_tpu.ssa import plan_fuse

    if len(jax.devices()) < 4:
        pytest.skip("JAX started before this file asked for 4 devices")
    # SF 3 has 4.5M rows a device, far above the fusion cutoff
    monkeypatch.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    expected = run.load_cell(CELL)["traffic"]["executors"]
    cell = small_cell(CELL)
    cell["traffic"]["executors"] = expected
    res = run.run_cell(cell, seed=2147483999, seconds=0.5, trace=True)
    assert res["correct"], res["checks"]
    assert res["checks"]["unexpected_executor_statements"]["value"] == 0
    assert res["device"]["count"] >= 4
    got = res["metrics"]
    assert got["mesh_step_ms"]["value"] > 0
    assert got["mesh_scan_host_ms"]["value"] > 0
    # peaks exist for a TPU only, and the CPU's trace has no device
    # plane: nothing to divide
    assert "mesh_roofline_share" not in got
