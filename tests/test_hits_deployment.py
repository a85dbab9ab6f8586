"""The benchmark's ClickBench deployment (``clickbench-hits-1chip``: the
source's ``hits`` at its 105 columns, one column table, one shard, upsert
on, resident) at 60,000 rows in 4,096-row blocks on the CPU: generated,
created and loaded as the benchmark does it (``bench/clickbench_gen.py``,
``bench/deploy.py``), its three statements answered by the walk's path
for an aggregate that does not push down (the scan's block outputs
concatenated on the host, a Transform with a sort-derived group-by over
the one block) and held to the benchmark's plain numpy references, with
the spans and the two statement keys that say where the time went."""

import contextlib
import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from ydb_tpu.config import AppConfig
from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs import profile as profile_mod
from ydb_tpu.obs.profile import STATEMENT_KEYS, WALK_SPAN_KEYS
from ydb_tpu.plan import execute_plan
from ydb_tpu.plan import executor as plan_executor
from ydb_tpu.sql.parser import parse
from ydb_tpu.sql.planner import plan_select_full
from ydb_tpu.ssa import compiler, plan_fuse

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
SCALE_FACTOR = 0.06     # 60,000 rows
SEED = 2147483999       # the driver's seeds pass 2**31
OTHER_SEED = 3700000043
BLOCK_ROWS = 4096
STATEMENTS = ("hits_q12", "hits_q15", "hits_q16")
KEY_WORDS = {"hits_q12": 1, "hits_q15": 2, "hits_q16": 3}


def bench_module(relative: str):
    """A file of ``bench/`` loaded by path: the benchmark is no package
    and the program imports nothing of it."""
    path = BENCH / relative
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONFIG = json.loads(
    (BENCH / "configs" / "clickbench-hits-1chip.json").read_text())
GEN = bench_module(CONFIG["generator"] + ".py")


def statement(sid: str) -> dict:
    return {"sql": (BENCH / "statements" / f"{sid}.sql").read_text().strip(),
            "ref": bench_module(f"refs/{sid}.py")}


def deployed(data) -> Cluster:
    cluster = Cluster(config=AppConfig(scan_block_rows=BLOCK_ROWS))
    readings = bench_module("deploy.py").build(
        cluster, cluster.session(), data, CONFIG, lambda line: None)
    assert readings == {"count_mismatch_tables": 0,
                        "upsert_extra_rows": 0, "upsert_stale_rows": 0}
    return cluster


@pytest.fixture(scope="module")
def chip_like():
    """What the chip's size settles, brought down to 60,000 rows: the
    table too large for the fused executor, resident in the device tier,
    and every dictionary too large for the dense group layout (at 12.5M
    rows ``SearchPhrase`` has 752,408 values against the tier's 65,536)."""
    mp = pytest.MonkeyPatch()
    mp.setattr(plan_fuse, "FUSE_MAX_ROWS", 1000)
    mp.setattr(resident_mod, "RESIDENT_FORCE", True)
    mp.setattr(compiler, "_DENSE_GROUP_LIMIT", 512)
    yield
    mp.undo()


@pytest.fixture(scope="module")
def deployment(chip_like):
    data = GEN.make(SCALE_FACTOR, SEED, **CONFIG["generator_options"])
    cluster = deployed(data)
    try:
        yield data, cluster
    finally:
        cluster.stop()


def warm_profile(cluster, sql: str):
    s = cluster.session()
    s.execute(sql)          # compiles
    res = s.execute(sql)
    return res, s.last_profile


def by_name(profile, name: str) -> list:
    return [sp for sp in profile.spans if sp["name"] == name]


def is_walk(profile) -> bool:
    names = {sp["name"] for sp in profile.spans}
    return not names & {"mesh", "plan.fuse", "dq"}


@pytest.mark.parametrize("sid", STATEMENTS)
def test_the_walk_answers_exactly_and_says_where_the_time_went(
        deployment, sid):
    data, cluster = deployment
    st = statement(sid)
    res, p = warm_profile(cluster, st["sql"])
    want = st["ref"].reference(data)
    assert list(res.schema.names) == list(want)
    for name in want:
        assert np.array_equal(np.asarray(res.cols[name][0]), want[name]), name
    assert is_walk(p) and p.agg_pushdown == 0

    (scan,) = by_name(p, "scan")
    assert scan["attrs"]["agg_pushdown"] == 0
    assert scan["attrs"]["pushdown_declined"] == "layout=sorted"
    (concat,) = by_name(p, "host.concat")
    rows_in = concat["attrs"]["rows"]
    assert concat["attrs"]["blocks"] == 15
    assert rows_in == (data.rows("hits") if sid != "hits_q12" else int(
        (data.tables["hits"]["SearchPhrase"] != 0).sum()))
    assert concat["attrs"]["bytes"] == rows_in * (
        4 * (sid != "hits_q15") + 8 * (sid != "hits_q12")
        + len(st["ref"].TABLES["hits"]))        # one validity byte a column
    (transform,) = by_name(p, "transform")
    attrs = transform["attrs"]
    assert attrs["capacity"] == plan_fuse.shape_class(rows_in)
    assert attrs["rows_in"] == rows_in and attrs["rows"] == 10
    assert attrs["group_layout"] == "sorted"
    assert attrs["key_words"] == KEY_WORDS[sid]
    assert attrs["reduce_tier"] == "scatter"
    # the output's keys are the sorted keys at the segment heads (PR 40)
    assert attrs["key_tier"] == "segment"
    # the top-10 selects its rows: nothing orders the capacity (PR 38)
    assert attrs["sort_tier"] == "select" and attrs["sort_limit"] == 10
    assert attrs["compile_cache"] == "hit"

    keys = STATEMENT_KEYS + tuple(WALK_SPAN_KEYS.values())
    assert set(keys) == set(p.stages) - {"read", "merge", "stage", "compute"}
    assert sum(p.stages[k] for k in keys) == pytest.approx(
        p.seconds, abs=max(0.01 * p.seconds, 2e-4))
    assert p.stages["concat"] > 0 and p.stages["transform"] > 0
    # the program's wait is the transform's, not the fetch's, however
    # short the program: the wait for it is a span beneath `transform`,
    # after its dispatch, and the two keys split the waits by where
    # their spans stand, whatever their seconds (Q12's transform takes
    # 4 ms here since its top-10 selects, no longer than its scan's
    # wait for the filter's row count)
    beneath = [sp for sp in p.spans
               if sp["parent_id"] == transform["span_id"]]
    kinds = [(sp["name"], sp["attrs"].get("program")) for sp in beneath]
    # the counts of the rows in and out: the second waits for the program
    assert kinds[kinds.index(("dispatch", "transform")):] == [
        ("dispatch", "transform"), ("device.wait", None),
        ("device.wait", None)]
    assert all(sp["name"] in ("dispatch", "device.wait") for sp in beneath)
    assert p.stages["transform"] == pytest.approx(
        transform["seconds"], abs=1e-5)
    assert p.stages["transform"] > sum(
        sp["seconds"] for sp in beneath) - 1e-5       # each is rounded
    by_id = {sp["span_id"]: sp for sp in p.spans}

    def in_the_walk(sp):
        while sp is not None and sp["name"] not in WALK_SPAN_KEYS:
            sp = by_id.get(sp["parent_id"])
        return sp is not None

    waits = [sp for sp in p.spans
             if sp["name"] in ("device.wait", "device.get")]
    assert p.stages["device_wait"] == pytest.approx(
        sum(sp["seconds"] for sp in waits if not in_the_walk(sp)),
        abs=1e-6 * len(waits))


def test_an_order_without_a_limit_keeps_the_whole_sort(deployment):
    data, cluster = deployment
    res, p = warm_profile(
        cluster, "select UserID, count(*) as c from hits group by UserID "
                 "order by c desc, UserID")
    (transform,) = by_name(p, "transform")
    assert transform["attrs"]["sort_tier"] == "whole"
    assert "sort_limit" not in transform["attrs"]
    users, counts = np.unique(data.tables["hits"]["UserID"],
                              return_counts=True)
    order = np.lexsort((users, -counts))
    assert np.array_equal(np.asarray(res.cols["UserID"][0]), users[order])
    assert np.array_equal(np.asarray(res.cols["c"][0]), counts[order])


@pytest.mark.parametrize("sql", (
    "select count(*) as n, sum(ResolutionWidth) as w from hits "
    "where AdvEngineID <> 0",
    "select MobilePhoneModel, count(*) as c from hits "
    "group by MobilePhoneModel"), ids=("keyless", "dense"))
def test_a_statement_whose_aggregate_pushes_down_has_neither_key(
        deployment, sql):
    _, cluster = deployment
    _, p = warm_profile(cluster, sql)
    assert is_walk(p) and p.agg_pushdown == 1
    assert not set(WALK_SPAN_KEYS.values()) & set(p.stages)
    assert not by_name(p, "host.concat") and not by_name(p, "transform")
    assert "pushdown_declined" not in by_name(p, "scan")[0]["attrs"]
    assert sum(p.stages[k] for k in STATEMENT_KEYS) == pytest.approx(
        p.seconds, abs=max(0.01 * p.seconds, 2e-4))


@pytest.mark.parametrize("sql,key_tier", (
    ("select count(*) as n from hits where AdvEngineID <> 0", None),
    ("select MobilePhoneModel, count(*) as c from hits "
     "group by MobilePhoneModel", "dense")), ids=("keyless", "dense"))
def test_the_controls_transforms_say_their_own_key_tier(
        deployment, sql, key_tier):
    """The two controls push down and have no ``transform`` span; run
    the way the walk answered them before the pushdown (the scan's
    output, then the Transform over it), the span says where a dense
    layout's keys come from, and a keyless aggregate has none."""
    _, cluster = deployment
    plan = plan_select_full(parse(sql), cluster.catalog(), None).plan
    db = cluster.snapshot_db()
    with profile_mod.profiled() as held:
        plan_executor._transform_node(
            plan, execute_plan(plan.input, db), db)
    (transform,) = by_name(held.profile, "transform")
    assert transform["attrs"].get("key_tier") == key_tier
    assert transform["attrs"]["group_layout"] == (key_tier or "keyless")


@contextlib.contextmanager
def persistent_compile_cache(path):
    """JAX's persistent cache at ``path`` for the block: a program whose
    key it holds is fetched, not built (``obs/tracing.py`` tells them
    apart on the ``dispatch`` span)."""
    from jax._src import compilation_cache

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_a_second_seed_builds_no_transform_program(chip_like, tmp_path):
    """Q12 keeps the rows that carry a phrase, a count that follows the
    seed; its Transform is compiled at a shape class of it, so the second
    seed's cluster fetches the first's programs."""
    compiles, selected = {}, {}
    with persistent_compile_cache(tmp_path / "jax"):
        for seed in (SEED, OTHER_SEED):
            data = GEN.make(SCALE_FACTOR, seed)
            cluster = deployed(data)
            try:
                s = cluster.session()
                for sid in STATEMENTS:
                    s.execute(statement(sid)["sql"])
                    (transform,) = by_name(s.last_profile, "transform")
                    assert transform["attrs"]["compile_cache"] == "miss"
                    (dispatch,) = [
                        sp for sp in by_name(s.last_profile, "dispatch")
                        if sp["attrs"]["program"] == "transform"]
                    compiles[seed, sid] = {
                        k: dispatch["attrs"].get(k, 0)
                        for k in ("compile_built", "compile_fetched")}
                    selected[seed, sid] = transform["attrs"]["rows_in"]
            finally:
                cluster.stop()
    assert selected[SEED, "hits_q12"] != selected[OTHER_SEED, "hits_q12"]
    for sid in STATEMENTS:
        assert compiles[SEED, sid]["compile_built"] >= 1, sid
        assert compiles[OTHER_SEED, sid] == {
            "compile_built": 0,
            "compile_fetched": compiles[SEED, sid]["compile_built"]}, sid


def test_the_float32_control_is_not_correct(deployment):
    """The reference with the key and the count carried as float32 in
    the program's place: ``UserID``s fall together, so Q15 and Q16 come
    out wrong (Q12's keys are dictionary ids under 2^24: it may pass
    alone, and ``bench/refs/hits_q12.py`` says so)."""
    data, _ = deployment
    compare = bench_module("compare.py")
    wrong = {}
    for sid in STATEMENTS:
        ref = statement(sid)["ref"]
        wrong[sid] = compare.compare(ref.reference(data, "float32"),
                                     ref.reference(data),
                                     ref.COLUMNS)["wrong_cells"]
    assert wrong["hits_q15"] > 0 and wrong["hits_q16"] > 0
    assert wrong["hits_q12"] == 0


# ---------------- the generator --------------------------------------


def test_the_generator_gives_the_sources_105_columns(deployment):
    data, _ = deployment
    counts = {"int64": 6, "timestamp": 3, "date": 1, "string": 28,
              "int32": 19, "int16": 48}
    numpy_types = {"int64": np.int64, "timestamp": np.int64,
                   "date": np.int32, "string": np.int32,
                   "int32": np.int32, "int16": np.int16}
    for table in CONFIG["tables"]:
        schema = data.schema(table)
        assert len(schema) == 105 == len({name for name, _ in schema})
        assert [name for name, _ in schema][:3] == [
            "WatchID", "JavaEnable", "Title"]
        assert schema[-1] == ("CLID", "int32")
        for sql_type, n in counts.items():
            assert sum(t == sql_type for _, t in schema) == n, sql_type
        assert list(data.tables[table]) == [name for name, _ in schema]
        for name, sql_type in schema:
            assert data.tables[table][name].dtype == numpy_types[sql_type]
        # 360 B a row on the device
        assert sum(data.widths[t] for _, t in schema) == 360
        assert data.primary_key(table) == (
            "CounterID", "EventDate", "UserID", "EventTime", "WatchID")
    assert data.rows("hits") == 60_000 and data.rows("hits_probe") == 4096
    for col in data.dicts.columns():
        values = data.dicts[col].values
        assert len(set(values)) == len(values), col
        for table in CONFIG["tables"]:
            assert data.tables[table][col].max() < len(values), col


def test_the_whole_key_is_distinct_and_the_rows_arrive_in_its_order(
        deployment):
    data, _ = deployment
    for table in CONFIG["tables"]:
        t = data.tables[table]
        assert len(np.unique(t["WatchID"])) == data.rows(table)
        keys = [t[k] for k in data.primary_key(table)]
        order = np.lexsort(tuple(reversed(keys)))
        assert np.array_equal(order, np.arange(data.rows(table)))


def test_the_same_seed_gives_the_same_arrays(deployment):
    data, _ = deployment
    again = GEN.make(SCALE_FACTOR, SEED)
    other = GEN.make(SCALE_FACTOR, OTHER_SEED)
    for table in CONFIG["tables"]:
        for name, a in data.tables[table].items():
            assert np.array_equal(a, again.tables[table][name]), name
        assert other.rows(table) == data.rows(table)
    assert not np.array_equal(data.tables["hits"]["UserID"],
                              other.tables["hits"]["UserID"])
    for col in data.dicts.columns():
        assert data.dicts[col].values == again.dicts[col].values
        assert len(other.dicts[col]) == len(data.dicts[col])


@pytest.mark.parametrize("share", ("empty_phrases", "distinct_users",
                                    "distinct_phrases", "adv_engine"))
def test_the_stated_shares(deployment, share):
    data, _ = deployment
    hits, n = data.tables["hits"], data.rows("hits")
    phrased = hits["SearchPhrase"] != data.dicts["SearchPhrase"].get(b"")
    got, want, rel = {
        "empty_phrases": (1 - phrased.mean(), 0.868, 0.01),
        "distinct_users": (len(np.unique(hits["UserID"])) / n, 0.1763,
                           0.01),
        "distinct_phrases": (
            len(np.unique(hits["SearchPhrase"][phrased])) / phrased.sum(),
            0.457, 0.02),
        "adv_engine": ((hits["AdvEngineID"] != 0).mean(), 0.0063, 0.15),
    }[share]
    assert got == pytest.approx(want, rel=rel)
