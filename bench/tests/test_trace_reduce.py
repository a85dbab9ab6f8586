"""The reduction from a trace to busy time, launches and labelled gaps."""

import pytest
import trace_reduce as tr

MS = 1e6  # ns


def loaded():
    """One device, two statements; times in ms. Ops: 10-20 and 15-30
    (overlapping: busy 10-30), 50-60, one outside the window (200-210);
    three launches inside the window."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion.1", 10 * MS, 10 * MS),
                    ("fusion.2", 15 * MS, 15 * MS),
                    ("sort.3", 50 * MS, 10 * MS),
                    ("fusion.1", 200 * MS, 10 * MS)],
            "modules": [("jit_a", 10 * MS, 20 * MS),
                        ("jit_b", 50 * MS, 10 * MS),
                        ("jit_b", 90 * MS, 0.5 * MS),
                        ("jit_a", 200 * MS, 10 * MS)]}},
        "spans": [("bench.q1", 0.0, 40 * MS), ("bench.q6", 45 * MS, 55 * MS)],
    }


def test_union_merges_overlaps_and_keeps_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.union([]) == []


def test_clip_cuts_to_the_window():
    assert tr.clip([("a", 0, 10), ("b", 8, 10), ("c", 30, 5)], 5, 12) == [
        ("a", 5, 5), ("b", 8, 4)]


def test_reduce_busy_idle_launches():
    r = tr.reduce(loaded())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.030)       # 10-30 and 50-60
    assert r["launches"] == 3 and r["statements"] == 2
    assert r["devices"] == 1
    ops = dict(r["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.015)
    assert ops["fusion.1"] == pytest.approx(0.010)   # the one outside: cut
    gaps = dict(r["idle_gaps"])
    # q1 spans 0-40: idle 0-10 and 30-40; q6 spans 45-100: idle 45-50,
    # 60-100; between the two: 40-45
    assert gaps["q1 in flight"] == pytest.approx(0.020)
    assert gaps["q6 in flight"] == pytest.approx(0.045)
    assert gaps["between statements"] == pytest.approx(0.005)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_averages_over_devices():
    two = loaded()
    two["devices"]["/device:TPU:1"] = {"ops": [], "modules": []}
    r = tr.reduce(two)
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(0.015)


def test_a_trace_without_spans_has_no_window():
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "spans": []})


def test_load_reads_the_benchmarks_spans_from_a_recorded_trace(tmp_path):
    """A small trace recorded here on the CPU: the spans are found on the
    host plane, and no CPU thread passes for a device."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for name in ("q1", "q6"):
        with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    got = tr.load(tr.newest_trace(str(tmp_path)))
    assert sorted(n for n, _, _ in got["spans"]) == ["bench.q1", "bench.q6"]
    assert got["devices"] == {}
    r = tr.reduce(got)
    assert r["statements"] == 2 and r["busy_s"] == 0.0 and r["window_s"] > 0


SAMPLE = __import__("pathlib").Path(__file__).with_name("fixtures") / "v5e.xplane.pb"


@pytest.mark.skipif(not SAMPLE.exists(), reason="no recorded TPU trace")
def test_load_finds_the_device_plane_of_a_recorded_tpu_trace():
    got = tr.load(str(SAMPLE))
    assert list(got["devices"]) == ["/device:TPU:0"]
    plane = got["devices"]["/device:TPU:0"]
    assert plane["ops"] and plane["modules"]
    r = tr.reduce(got)
    # one Q6 over 18 resident blocks of 2^20 rows (my chip run, PR 27)
    assert r["statements"] == 1 and r["launches"] == 206
    assert r["window_s"] == pytest.approx(1.798841282)
    assert r["busy_s"] == pytest.approx(1.622932178)
    assert dict(r["idle_gaps"]) == {
        "q6 in flight": pytest.approx(r["window_s"] - r["busy_s"])}
    assert r["device_ops"][0][0].startswith("%fusion.5 = u32[1048576]")
    assert all(len(name) <= tr.NAME_CHARS for name, _ in r["device_ops"])
