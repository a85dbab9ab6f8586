"""The four-chip deployment of the benchmark (``tpch-sf3-4chip``: TPC-H
hash-sharded into 4 shards, one shard and its resident slice a mesh
device) at SF 0.01 on four of the virtual CPU devices: created and
loaded as the benchmark does it (``bench/deploy.py``), answered by the
mesh walk, held to the benchmark's plain numpy references and to the
same statements on one shard, with the spans, the ``mesh`` statement key
and the per-device report that say where a statement's time went."""

import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs.profile import MESH_KEY, STATEMENT_KEYS
from ydb_tpu.parallel.mesh import make_mesh
from ydb_tpu.ssa import plan_fuse

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
SCALE_FACTOR = 0.01
SEED = 2147483999       # the driver's seeds pass 2**31
DEVICES = 4
STATEMENTS = ("q1", "q6")


def bench_module(relative: str):
    """A file of ``bench/`` loaded by path: the benchmark is no package
    and the program imports nothing of it."""
    path = BENCH / relative
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deployed(data, config: dict, shards: int) -> Cluster:
    deploy = bench_module("deploy.py")
    config = dict(config, table_options=dict(config["table_options"],
                                             shards=shards))
    cluster = Cluster()
    readings = deploy.build(cluster, cluster.session(), data, config,
                            lambda line: None)
    assert readings == {"count_mismatch_tables": 0,
                        "upsert_extra_rows": 0, "upsert_stale_rows": 0}
    return cluster


@pytest.fixture(scope="module")
def deployment():
    """``(data, statements, sharded cluster on a 4-device mesh, the same
    data on one shard)``; the tables too large for the fused executors,
    resident in the device tier as on the chip."""
    mp = pytest.MonkeyPatch()
    mp.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    mp.setattr(resident_mod, "RESIDENT_FORCE", True)
    config = json.loads(
        (BENCH / "configs" / "tpch-sf3-4chip.json").read_text())
    assert config["mesh"] is True and config["chips"] == DEVICES
    assert config["table_options"]["shards"] == DEVICES
    data = bench_module(config["generator"] + ".py").make(
        SCALE_FACTOR, SEED, **config["generator_options"])
    statements = {
        sid: {"sql": (BENCH / "statements" / f"{sid}.sql").read_text()
              .strip(),
              "ref": bench_module(f"refs/{sid}.py")}
        for sid in STATEMENTS}
    sharded = deployed(data, config, DEVICES)
    sharded.enable_mesh(make_mesh(DEVICES, devices=jax.devices()))
    for t in sharded.tables.values():
        for sh in t.shards:
            sh.resident.drain()
    one = deployed(data, config, 1)
    try:
        yield data, statements, sharded, one
    finally:
        sharded.stop()
        one.stop()
        mp.undo()


def answer(res) -> dict:
    return {name: np.asarray(res.cols[name][0])
            for name in res.schema.names}


def mesh_walk_profile(cluster, sql: str):
    s = cluster.session()
    s.execute(sql)          # compiles
    s.execute(sql)
    return s.last_profile


def by_name(profile, name: str) -> list:
    return [sp for sp in profile.spans if sp["name"] == name]


@pytest.mark.parametrize("sid", STATEMENTS)
def test_answers_equal_the_plain_reference_and_one_shard(deployment, sid):
    data, statements, sharded, one = deployment
    sql = statements[sid]["sql"]
    got = answer(sharded.session().execute(sql))
    want = statements[sid]["ref"].reference(data)       # exact arithmetic
    assert list(got) == list(want)
    for name, kind in statements[sid]["ref"].COLUMNS.items():
        if kind[0] == "ratio":
            assert got[name] == pytest.approx(want[name], rel=1e-12)
        else:
            assert np.array_equal(got[name], want[name]), name
    # bit for bit what one shard answers, the averages too
    alone = answer(one.session().execute(sql))
    for name in want:
        assert got[name].dtype == alone[name].dtype
        assert got[name].tobytes() == alone[name].tobytes(), name


@pytest.mark.parametrize("sid", STATEMENTS)
def test_the_mesh_walk_answers_and_its_keys_sum_to_seconds(deployment,
                                                            sid):
    _, statements, sharded, _ = deployment
    p = mesh_walk_profile(sharded, statements[sid]["sql"])
    (mesh,) = by_name(p, "mesh")
    assert mesh["attrs"]["answered"] == 1
    assert mesh["attrs"]["devices"] == DEVICES
    assert not by_name(p, "plan.fuse")         # the walk, not mesh-fused
    seven = STATEMENT_KEYS + (MESH_KEY,)
    assert set(seven) <= set(p.stages)
    assert sum(p.stages[k] for k in seven) == pytest.approx(
        p.seconds, abs=max(0.01 * p.seconds, 2e-4))
    assert p.stages[MESH_KEY] > 0
    # what no named leaf covers is the bookkeeping of the mesh span and
    # its four scan spans, 1-2 ms whatever the size: a tenth of the
    # statement at most, once the statement is longer than this one.
    # The best of a few runs: a worker of a loaded machine is preempted
    # between spans as well as inside them
    s = sharded.session()
    least = p.stages["unattributed"]
    for _ in range(4):
        s.execute(statements[sid]["sql"])
        least = min(least, s.last_profile.stages["unattributed"])
    assert least < max(0.10 * p.seconds, 3e-3)


def test_the_mesh_key_holds_the_mesh_spans_apart_from_the_scans(
        deployment):
    _, statements, sharded, _ = deployment
    p = mesh_walk_profile(sharded, statements["q1"]["sql"])
    (mesh,) = by_name(p, "mesh")
    scans = {sp["span_id"] for sp in by_name(p, "scan")}
    assert all(sp["parent_id"] == mesh["span_id"]
               for sp in by_name(p, "scan"))
    programs = {sp["attrs"].get("program"): sp["parent_id"]
                for sp in by_name(p, "dispatch")}
    assert {"mesh_place", "mesh_step", "scan_partial"} <= set(programs)
    assert programs["scan_partial"] in scans
    assert programs["mesh_place"] not in scans
    assert programs["mesh_step"] not in scans
    # the wait for the shards' row counts, the wait for the collective
    # step and the answer's copy out are the mesh's own
    own = [sp for sp in p.spans
           if sp["name"] in ("device.wait", "device.get")
           and sp["parent_id"] == mesh["span_id"]]
    assert {sp["name"] for sp in own} == {"device.wait", "device.get"}
    assert len(own) >= 3
    by_id = {sp["span_id"]: sp for sp in p.spans}

    def under_a_scan(sp) -> bool:
        while sp is not None:
            if sp["span_id"] in scans:
                return True
            sp = by_id.get(sp["parent_id"])
        return False

    # spans nest on one thread here: a leaf's seconds are its self time
    leaves = [sp for sp in p.spans
              if sp["name"] in ("dispatch", "device.wait", "device.get")]
    own_s = sum(sp["seconds"] for sp in leaves if not under_a_scan(sp))
    scan_s = sum(sp["seconds"] for sp in leaves if under_a_scan(sp))
    # (each rounded to the microsecond in the profile)
    assert p.stages[MESH_KEY] == pytest.approx(own_s, abs=1e-4)
    assert p.stages["dispatch"] + p.stages["device_wait"] == \
        pytest.approx(scan_s, abs=1e-4)


@pytest.mark.parametrize("sid", STATEMENTS)
def test_the_shard_scans_charge_stages_and_portions(deployment, sid):
    _, statements, sharded, _ = deployment
    p = mesh_walk_profile(sharded, statements[sid]["sql"])
    scans = by_name(p, "scan")
    assert [sp["attrs"]["device"] for sp in scans] == list(range(DEVICES))
    assert {sp["attrs"]["table"] for sp in scans} == {"lineitem"}
    assert {sp["attrs"]["compile_cache"] for sp in scans} == {"hit"}
    assert any(p.stages[k] > 0
               for k in ("read", "merge", "stage", "compute"))
    assert p.stages["compute"] > 0
    portions = sum(len(sh.visible_portions(None))
                   for sh in sharded.tables["lineitem"].shards)
    assert portions >= DEVICES
    assert p.pruning["portions_total"] == portions
    assert p.pruning["resident_portions"] == portions
    assert p.pruning["resident_rows"] == \
        sum(sp["attrs"]["resident_rows"] for sp in scans)


def test_the_report_shows_a_quarter_of_lineitem_on_each_device(
        deployment):
    data, _, sharded, one = deployment
    report = sharded.mesh_report()
    assert [d["device"] for d in report] == list(range(DEVICES))
    rows = data.rows("lineitem")
    for d in report:
        held = d["tables"]["lineitem"]
        assert abs(100.0 * held["rows"] / rows - 25.0) <= 2.0
        assert held["bytes"] > 0
        assert d["stores"] == len(sharded.tables)
        assert d["bytes"] == sum(t["bytes"] for t in d["tables"].values())
    assert sum(d["tables"]["lineitem"]["rows"] for d in report) == rows
    assert sum(d["bytes"] for d in report) == sum(
        sh.resident.snapshot()["bytes"]
        for t in sharded.tables.values() for sh in t.shards)
    assert one.mesh_report() == []      # no mesh, nothing bound


def test_a_one_chip_statement_opens_no_mesh_key(deployment):
    _, statements, _, one = deployment
    s = one.session()
    s.execute(statements["q6"]["sql"])
    p = s.last_profile
    assert MESH_KEY not in p.stages
    assert not by_name(p, "mesh")
    assert set(STATEMENT_KEYS) <= set(p.stages)
    assert "mesh=" not in s.execute("EXPLAIN ANALYZE "
                                    + statements["q6"]["sql"])


def test_explain_analyze_prints_the_mesh_key_and_the_shard_scans(
        deployment):
    _, statements, sharded, _ = deployment
    txt = sharded.session().execute("EXPLAIN ANALYZE "
                                    + statements["q6"]["sql"])
    line = next(ln for ln in txt.splitlines()
                if ln.startswith("statement: "))
    got = dict(kv.split("=") for kv in line.split()[1:])
    assert list(got) == list(STATEMENT_KEYS) + [MESH_KEY]
    assert float(got[MESH_KEY]) > 0
    assert sum(ln.startswith("  scan: ") and "table=lineitem" in ln
               for ln in txt.splitlines()) == DEVICES
