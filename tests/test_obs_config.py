"""Observability + config tests: counters, tracing, sys views via SQL,
health check, YAML config, ICB knobs, feature flags (SURVEY.md §5.1,
§5.5, §5.6)."""

import pytest

from ydb_tpu.config import AppConfig, ConfigError, ControlBoard
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs.counters import CounterGroup
from ydb_tpu.obs.tracing import Tracer
from ydb_tpu.sql.planner import PlanError


# ---------- counters ----------

def test_counter_tree_and_prometheus_encoding():
    root = CounterGroup({"component": "test"})
    g = root.group(kind="select")
    g.counter("queries").inc()
    g.counter("queries").inc(2)
    g.histogram("latency_seconds").observe(0.003)
    text = root.encode_prometheus()
    assert 'queries{component="test",kind="select"} 3' in text
    assert "latency_seconds_count" in text
    assert g.histogram("latency_seconds").percentile(0.5) > 0


# ---------- tracing ----------

def test_span_nesting_and_export():
    tr = Tracer()
    with tr.trace("query") as root:
        with root.child("plan"):
            pass
        with root.child("execute") as ex:
            ex.set(rows=10)
    spans = tr.spans_for(root.trace_id)
    assert {s.name for s in spans} == {"query", "plan", "execute"}
    by_name = {s.name: s for s in spans}
    assert by_name["plan"].parent_id == by_name["query"].span_id
    assert all(s.thread == by_name["query"].thread for s in spans)


def test_session_emits_spans_and_counters():
    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE t (id int64, PRIMARY KEY (id))")
    s.execute("INSERT INTO t VALUES (1)")
    s.execute("SELECT id FROM t")
    kinds = [sp.attrs.get("kind") for sp in c.tracer.finished
             if sp.name == "query"]
    assert "createtable" in kinds and "select" in kinds
    snap = c.counters.snapshot()
    assert any("queries" in k and "kind=select" in k and v == 1
               for k, v in snap.items())
    assert len(c.query_log) == 3


# ---------- sys views ----------

def test_sys_views_via_sql():
    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE t (id int64, PRIMARY KEY (id)) "
              "WITH (shards = 2)")
    s.execute("INSERT INTO t VALUES (1), (2), (3)")
    out = s.execute("SELECT table_name, rows FROM sys_partition_stats "
                    "WHERE table_name = 't'")
    assert sum(out.column("rows")) == 3
    out = s.execute("SELECT kind, count(*) AS n FROM sys_query_stats "
                    "GROUP BY kind ORDER BY kind")
    kinds = [v.decode() for v in out.strings("kind")]
    assert "insert" in kinds
    out = s.execute("SELECT path FROM sys_scheme_paths ORDER BY path")
    paths = [v.decode() for v in out.strings("path")]
    assert "/t" in paths


def test_sys_views_can_be_disabled():
    from ydb_tpu.config import FeatureFlags

    c = Cluster(config=AppConfig(
        feature_flags=FeatureFlags(enable_sys_views=False)))
    s = c.session()
    with pytest.raises(PlanError):
        s.execute("SELECT path FROM sys_scheme_paths")


# ---------- health ----------

def test_health_check_good_and_degraded():
    from ydb_tpu.blobstorage import DSProxy, GroupBlobStore, GroupInfo

    group = GroupInfo(1, "block42")
    c = Cluster(store=GroupBlobStore(DSProxy(group)))
    assert c.health()["status"] == "GOOD"
    group.disks[0].down = True
    h = c.health()
    assert h["status"] == "DEGRADED"
    assert any("disk" in i["message"] for i in h["issues"])
    group.disks[1].down = True
    group.disks[2].down = True
    assert c.health()["status"] == "EMERGENCY"


# ---------- config ----------

def test_yaml_config_parse_and_validation():
    cfg = AppConfig.from_yaml("""
n_shards: 8
plan_cache_size: 16
auth_tokens: [a, b]
feature_flags:
  enable_changefeeds: false
""")
    assert cfg.n_shards == 8
    assert cfg.auth_tokens == ("a", "b")
    assert cfg.feature_flags.enable_changefeeds is False
    with pytest.raises(ConfigError):
        AppConfig.from_yaml("nope: 1")
    with pytest.raises(ConfigError):
        AppConfig.from_yaml("n_shards: many")
    with pytest.raises(ConfigError):
        AppConfig.from_yaml("feature_flags:\n  bogus_flag: true")
    with pytest.raises(ConfigError):
        AppConfig.from_yaml("n_shards: 0")


def test_config_drives_cluster_defaults_and_flags():
    from ydb_tpu.config import FeatureFlags

    cfg = AppConfig(n_shards=2, feature_flags=FeatureFlags(
        enable_changefeeds=False))
    c = Cluster(config=cfg)
    s = c.session()
    s.execute("CREATE TABLE t (id int64, PRIMARY KEY (id))")
    assert len(c.tables["t"].shards) == 2
    with pytest.raises(PlanError):
        s.execute("CREATE TABLE u (id int64, PRIMARY KEY (id)) "
                  "WITH (store = row, changefeed = on)")


def test_icb_knobs_clamp_and_apply():
    board = ControlBoard()
    board.register("k", default=5, lo=1, hi=10)
    assert board.set("k", 100) == 10      # clamped
    assert board.get("k") == 10
    board.reset("k")
    assert board.get("k") == 5

    # live compaction-threshold tuning takes effect in run_background
    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE t (id int64, PRIMARY KEY (id)) "
              "WITH (shards = 1)")
    for i in range(4):
        s.execute(f"INSERT INTO t VALUES ({i})")
    shard = c.tables["t"].shards[0]
    assert len(shard.visible_portions()) == 4
    c.icb.set("compact_portion_threshold", 2)
    c.run_background()
    assert len(shard.visible_portions()) == 1  # compacted under new knob


def test_histogram_export_has_inf_bucket():
    g = CounterGroup()
    h = g.histogram("lat", bounds=(1.0, 2.0))
    h.observe(5.0)  # beyond the top bound
    text = g.encode_prometheus()
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text


def test_trace_id_propagation_no_collision():
    tr = Tracer()
    with tr.trace("remote", trace_id=7):
        pass
    with tr.trace("local") as local:
        pass
    assert local.trace_id != 7
    assert len(tr.spans_for(7)) == 1
