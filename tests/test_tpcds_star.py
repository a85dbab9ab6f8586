"""TPC-DS's store channel as the benchmark deploys it
(``tpcds-store-1chip``: ``store_sales`` beside the seven dimensions its
star joins read, column tables, one shard, upsert on, resident), cut to a
CPU's size: generated and loaded as the benchmark does it
(``bench/tpcds_gen.py``, ``bench/deploy.py``), its three statements (the
specification's queries 3, 7 and 19 with their qualification values)
sent as SQL text over pgwire and held to the benchmark's plain numpy
references on two seeds, and answered on the path the chip takes: the
DQ executor, each join a stage whose span says its kind and its rows,
each group-by sort-derived."""

import importlib.util
import json
import pathlib

import jax
import numpy as np
import pytest

from ydb_tpu.api.pgwire import PgWireServer
from ydb_tpu.config import AppConfig
from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs.counters import root_counters
from ydb_tpu.obs.profile import DQ_JOIN_KEY
from ydb_tpu.plan import executor as plan_executor
from ydb_tpu.ssa import compiler, kernels

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
#: 180,000 store_sales rows (the configuration's eighth of SF 0.5), the
#: dimensions at their floors, 4,096-row blocks
SCALE_FACTOR = 0.5
BLOCK_ROWS = 4096
SEEDS = (2147483999, 4200000043)   # seeds past 2**31, as the cell's are
#: a pool of 40 zip codes: q19's ``ca_zip <> s_zip`` drops rows here,
#: where 10,000 codes would drop none at this size
ZIP_CODES = 40
STATEMENTS = ("tpcds_q3", "tpcds_q7", "tpcds_q19")
#: the joins of each statement's plan, in the order the planner takes
#: them (the fact table meets the smallest dimension that connects
#: first; q3 and q19 name date_dim first, so the fact table is the build
#: side of an expanding join there)
JOINS = {"tpcds_q3": ("expand", "lookup"),
         "tpcds_q7": ("lookup",) * 4,
         "tpcds_q19": ("expand",) + ("lookup",) * 4}


def bench_module(relative: str):
    """A file of ``bench/`` loaded by path: the benchmark is no package
    and the program imports nothing of it."""
    path = BENCH / relative
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONFIG = json.loads((BENCH / "configs" / "tpcds-store-1chip.json").read_text())
RUN = bench_module("run.py")       # puts bench/ on the path for the rest
GEN = bench_module(CONFIG["generator"] + ".py")
PGCLIENT = bench_module("pgclient.py")
COMPARE = bench_module("compare.py")


def statement(sid: str) -> dict:
    return {"sql": (BENCH / "statements" / f"{sid}.sql").read_text().strip(),
            "ref": bench_module(f"refs/{sid}.py")}


@pytest.fixture(scope="module")
def chip_like():
    """What the chip's size settles, brought down to this one: the
    tables resident in the device tier, and ``i_item_id``'s 1,000 ids
    past the dense group layout as its 102,000 are on the chip."""
    mp = pytest.MonkeyPatch()
    mp.setattr(resident_mod, "RESIDENT_FORCE", True)
    mp.setattr(compiler, "_DENSE_GROUP_LIMIT", 512)
    yield
    mp.undo()
    # every compiled program of both deployments goes: the next file's
    # queries share none of them
    jax.clear_caches()


@pytest.fixture(scope="module", params=SEEDS)
def deployment(request, chip_like):
    data = GEN.make(SCALE_FACTOR, request.param,
                    **dict(CONFIG["generator_options"], zip_codes=ZIP_CODES))
    cluster = Cluster(config=AppConfig(scan_block_rows=BLOCK_ROWS))
    pg = None
    try:
        readings = bench_module("deploy.py").build(
            cluster, cluster.session(), data, CONFIG, lambda line: None)
        assert readings == {"count_mismatch_tables": 0,
                            "upsert_extra_rows": 0, "upsert_stale_rows": 0}
        pg = PgWireServer(cluster, port=0).start()
        yield data, cluster, pg.port
    finally:
        if pg is not None:
            pg.stop()
        cluster.stop()


def over_the_wire(port: int, sql: str):
    client = PGCLIENT.PgClient(port)
    try:
        return client.query(sql)
    finally:
        client.close()


def join_counts() -> dict:
    g = root_counters().group(component="join")
    return {k: g.counter(k).value for k in ("joins", "probe_rows",
                                            "build_rows")}


@pytest.mark.parametrize("sid", STATEMENTS)
def test_the_answer_over_the_wire_is_the_reference(deployment, sid):
    data, cluster, port = deployment
    st = statement(sid)
    ref = st["ref"]
    before = join_counts()
    names, rows = over_the_wire(port, st["sql"])
    want = ref.reference(data)
    assert len(rows) > 0 and names == list(want)
    got = COMPARE.decode(names, rows, ref.COLUMNS, data.dicts)
    verdict = COMPARE.compare(got, want, ref.COLUMNS)
    assert verdict["wrong_cells"] == 0
    assert verdict["ratio_rel_gap"] <= getattr(ref, "RATIO_REL_GAP_LIMIT", 0)

    prof = cluster.profiles.recent()[-1]
    assert prof.sql.strip() == st["sql"]
    assert RUN.executor_of(prof) == "dq"
    joins = [sp["attrs"] for sp in prof.spans
             if sp["name"] == "dispatch" and "join" in sp["attrs"]]
    tasks = plan_executor._DQ_TASKS
    assert len(joins) == len(JOINS[sid]) * tasks
    # a join stage's tasks run one after the other, stage after stage
    assert tuple(j["join"] for j in joins[::tasks]) == JOINS[sid]
    for j in joins:
        assert j["program"] == "dq_stage" and j["kind"] == "inner"
        if j["join"] == "lookup":
            assert 0 <= j["out_rows"] <= j["probe_rows"]
    # the first join's sides: the fact table whole on one side of it
    first = joins[:tasks]
    side = "build_rows" if JOINS[sid][0] == "expand" else "probe_rows"
    assert sum(j[side] for j in first) == data.rows("store_sales")
    # the process counts what the spans say
    after = join_counts()
    assert after["joins"] - before["joins"] == len(joins)
    assert after["probe_rows"] - before["probe_rows"] == sum(
        j["probe_rows"] for j in joins)
    assert after["build_rows"] - before["build_rows"] == sum(
        j["build_rows"] for j in joins)

    # the join stages' own time, a view of what dispatch and device_wait
    # already count
    assert 0 < prof.stages[DQ_JOIN_KEY] <= (
        prof.stages["dispatch"] + prof.stages["device_wait"] + 1e-6)

    # every group-by sort-derived, its keys compacted at the segment
    # heads, as on the chip; the last stage's program orders the groups
    # for the LIMIT by the rule that picked `select` for q7's 65,536
    # slots there and `whole` for q3's 2,048 and q19's 4,096
    grouped = [sp["attrs"] for sp in prof.spans
               if sp["name"] == "dispatch" and "group_layout" in sp["attrs"]]
    assert len(grouped) >= 2
    for g in grouped:
        assert g["group_layout"] == "sorted" and g["key_tier"] == "segment"
        assert g["reduce_tier"] == "scatter"
    final = grouped[-1]
    assert final["sort_limit"] == 100
    assert final["sort_tier"] == (
        "select" if 100 * kernels.TOPK_ROOM <= final["groups"] else "whole")


def test_q19s_zip_codes_are_compared_as_texts(deployment):
    """``ca_zip`` and ``s_zip`` hold the same texts under different ids
    (each column's dictionary numbers its texts as they first come):
    comparing ids would keep rows the texts drop, and drop rows they
    keep."""
    data, _, _ = deployment
    ca, st = data.dicts["ca_zip"].values, data.dicts["s_zip"].values
    assert set(st) <= set(ca) and st != ca[:len(st)]
    ref = statement("tpcds_q19")["ref"]
    ss = data.tables["store_sales"]
    buyer = data.tables["customer_address"]["ca_zip"][
        data.tables["customer"]["c_current_addr_sk"][
            ss["ss_customer_sk"] - 1] - 1]
    seller = data.tables["store"]["s_zip"][ss["ss_store_sk"] - 1]
    texts_equal = (np.array(ca, dtype=object)[buyer]
                   == np.array(st, dtype=object)[seller])
    assert texts_equal.any() and (texts_equal != (buyer == seller)).any()
    assert len(ref.reference(data)["ext_price"]) > 0
