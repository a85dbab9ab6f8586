"""GROUP BY ROLLUP and NULL-correct ranking windows, and TPC-DS query 67
as published on the store channel's deployment.

The deployment is ``tpcds-q67-1chip`` (``tpcds-store-1chip``'s data and
tables, key for key) cut to a CPU's size as
``tests/test_tpcds_star.py`` cuts it: ``bench/tpcds_gen.py`` at SF 0.5
with the configuration's 1/8 fact share, 4,096-row blocks, two seeds
past 2^31. The statement (``bench/statements/tpcds_q67.sql``: the
specification's text, DMS 1200) goes over pgwire and is held to the
benchmark's plain numpy reference (``bench/refs/tpcds_q67.py``),
answered by the DQ executor, its levels and its window each a stage
span with their attrs and the process's counters; a rolled-up key
arrives as SQL NULL, never as an empty text. Then ROLLUP against a
numpy group-by at each prefix of its keys (the walk declines it and the
plan executor sends it to DQ), and
rank / dense_rank / row_number against a numpy oracle, over NULL keys.
"""

import collections
import importlib.util
import inspect
import json
import pathlib

import jax
import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.api.pgwire import PgWireServer
from ydb_tpu.blocks import TableBlock
from ydb_tpu.blocks.dictionary import DictionarySet
from ydb_tpu.config import AppConfig
from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.engine.oracle import OracleTable, run_oracle
from ydb_tpu.engine.scan import ColumnSource
from ydb_tpu.kqp.dq_lower import execute_plan_dq, partition_source
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs.counters import root_counters
from ydb_tpu.obs.profile import DQ_ROLLUP_KEY, DQ_WINDOW_KEY
from ydb_tpu.plan import Database, execute_plan, to_host
from ydb_tpu.runtime.test_runtime import SimRuntime
from ydb_tpu.sql.parser import parse
from ydb_tpu.sql.planner import Catalog, plan_select_full
from ydb_tpu.ssa import compiler
from ydb_tpu.ssa.program import Program, WindowStep

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
#: 180,000 store_sales rows (the configuration's eighth of SF 0.5), the
#: dimensions at their floors, 4,096-row blocks
SCALE_FACTOR = 0.5
BLOCK_ROWS = 4096
SEEDS = (2147483999, 4400000067)   # seeds past 2**31, as the cell's are


def bench_module(relative: str):
    """A file of ``bench/`` loaded by path: the benchmark is no package
    and the program imports nothing of it."""
    path = BENCH / relative
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CONFIG = json.loads((BENCH / "configs" / "tpcds-q67-1chip.json").read_text())
STORE = json.loads((BENCH / "configs" / "tpcds-store-1chip.json").read_text())
RUN = bench_module("run.py")       # puts bench/ on the path for the rest
GEN = bench_module(CONFIG["generator"] + ".py")
PGCLIENT = bench_module("pgclient.py")
COMPARE = bench_module("compare.py")
REF = bench_module("refs/tpcds_q67.py")
SQL = (BENCH / "statements" / "tpcds_q67.sql").read_text().strip()


def counts(component: str, names) -> dict:
    g = root_counters().group(component=component)
    return {k: g.counter(k).value for k in names}


ROLLUP_COUNTERS = ("rollups", "levels", "rows_in", "groups", "bytes_least")
WINDOW_COUNTERS = ("windows", "rows_in", "bytes_least")


@pytest.fixture(scope="module")
def chip_like():
    """What the chip's size settles, brought down to this one: the
    tables resident in the device tier, and the group-bys past the dense
    layout, as the 8-key grouping is on the chip."""
    mp = pytest.MonkeyPatch()
    mp.setattr(resident_mod, "RESIDENT_FORCE", True)
    mp.setattr(compiler, "_DENSE_GROUP_LIMIT", 512)
    yield
    mp.undo()
    # every compiled program of both deployments goes: the next file's
    # queries share none of them
    jax.clear_caches()


@pytest.fixture(scope="module", params=SEEDS)
def answer(request, chip_like):
    """q67 over the wire once on a deployment of the seed: the data, the
    answer's names and rows, its profile, and what the process counted
    while it ran."""
    data = GEN.make(SCALE_FACTOR, request.param,
                    **dict(CONFIG["generator_options"], zip_codes=40))
    cluster = Cluster(config=AppConfig(scan_block_rows=BLOCK_ROWS))
    pg = None
    try:
        readings = bench_module("deploy.py").build(
            cluster, cluster.session(), data, CONFIG, lambda line: None)
        assert readings == {"count_mismatch_tables": 0,
                            "upsert_extra_rows": 0, "upsert_stale_rows": 0}
        pg = PgWireServer(cluster, port=0).start()
        before = (counts("rollup", ROLLUP_COUNTERS),
                  counts("window", WINDOW_COUNTERS))
        client = PGCLIENT.PgClient(pg.port)
        try:
            names, rows = client.query(SQL)
        finally:
            client.close()
        after = (counts("rollup", ROLLUP_COUNTERS),
                 counts("window", WINDOW_COUNTERS))
        prof = cluster.profiles.recent()[-1]
        delta = [{k: a[k] - b[k] for k in a} for a, b in zip(after, before)]
        yield data, names, rows, prof, delta
    finally:
        if pg is not None:
            pg.stop()
        cluster.stop()


def stage_span(prof, attr: str) -> dict:
    spans = [sp["attrs"] for sp in prof.spans
             if sp["name"] == "dispatch" and attr in sp["attrs"]]
    assert len(spans) == 1, spans
    return spans[0]


def test_the_configuration_is_the_store_channels_deployment():
    """q67's configuration names its own statement and answers; its data,
    tables and their options are the star cell's, so the two cells
    measure one deployment."""
    differ = {k for k in set(CONFIG) | set(STORE)
              if CONFIG.get(k) != STORE.get(k)}
    assert differ == {"source", "deployment", "guarantees", "assumed"}
    assert "query 67" in CONFIG["source"] and "DMS = 1200" in CONFIG["source"]
    assert {k for k in CONFIG["guarantees"]
            if CONFIG["guarantees"][k] != STORE["guarantees"].get(k)} == {
        "answers"}
    mine, star = CONFIG["assumed"], STORE["assumed"]
    assert {k for k in set(mine) | set(star)
            if mine.get(k) != star.get(k)} == {
        "same_data_as", "substitution_parameters", "tiebreakers"}
    entry = {c["name"]: c for c in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["configs"]}
    assert entry["tpcds-q67-1chip"]["file"] == \
        "bench/configs/tpcds-q67-1chip.json"
    assert entry["tpcds-q67-1chip"]["reduced"] == \
        entry["tpcds-store-1chip"]["reduced"] == ["tables"]
    assert entry["tpcds-q67-1chip"]["source"] != \
        entry["tpcds-store-1chip"]["source"]


def test_q67_over_the_wire_is_the_reference(answer):
    data, names, rows, prof, (rollup, window) = answer
    want = REF.reference(data)
    assert names == list(want) and len(rows) == REF.LIMIT
    got = COMPARE.decode(names, rows, REF.COLUMNS, data.dicts)
    assert COMPARE.compare(got, want, REF.COLUMNS)["wrong_cells"] == 0
    assert prof.sql.strip() == SQL
    assert RUN.executor_of(prof) == "dq"

    # the levels' stage says each level's rows, the finest first: the
    # reference's groups at each prefix, grouped apart
    levels = [len(sums) for _, sums in REF.levels(data)]
    lv = stage_span(prof, "rollup_levels")
    assert lv["program"] == "dq_stage" and lv["rollup_levels"] == 9
    assert lv["groups"] == levels and lv["rows_in"] == levels[0]
    # the window's stage ranks every level's rows in the categories'
    # partitions and the grand total's NULL one
    win = stage_span(prof, "window")
    categories = len(np.unique(data.tables["item"]["i_category"]))
    assert win == dict(win, window="rank", rows_in=sum(levels),
                       partitions=categories + 1, key_words=3)
    # the process counts what the spans say
    assert rollup["rollups"] == 1 and rollup["levels"] == 9
    assert rollup["rows_in"] == levels[0]
    assert rollup["groups"] == sum(levels)
    assert window["windows"] == 1 and window["rows_in"] == sum(levels)
    assert rollup["bytes_least"] > 0 and window["bytes_least"] > 0
    # each stage's own time, a view of what dispatch and device_wait
    # already count
    for key in (DQ_ROLLUP_KEY, DQ_WINDOW_KEY):
        assert 0 < prof.stages[key] <= (
            prof.stages["dispatch"] + prof.stages["device_wait"] + 1e-6)


def test_rolled_up_keys_arrive_as_sql_null(answer):
    """A level's rolled-up keys are SQL NULL on the wire (``None`` from
    the client), never ``''``: ``compare.decode`` reads a text outside a
    column's dictionary as ``BAD`` just as it reads a NULL, so the
    comparison alone would pass an empty text."""
    data, names, rows, _, _ = answer
    keys = [col for _, col, _ in REF.KEYS]
    rolled = [sum(row[names.index(k)] is None for k in keys) for row in rows]
    assert max(rolled) > 0, "no subtotal among the first rows"
    for row, n in zip(rows, rolled):
        assert "" not in row
        # a level's NULLs are the last keys, whatever the level
        assert all(row[names.index(k)] is None for k in keys[8 - n:])
        assert all(row[names.index(k)] is not None for k in keys[:8 - n])
    got = COMPARE.decode(names, rows, REF.COLUMNS, data.dicts)
    assert (got["s_store_id"] == COMPARE.BAD).sum() == sum(
        n > 0 for n in rolled)


# ---------------- ROLLUP against a numpy group-by at each prefix ----------

T_SCHEMA = dtypes.schema(("a", dtypes.STRING, True), ("b", dtypes.INT64, True),
                         ("c", dtypes.STRING, True), ("v", dtypes.INT64, True))
ROLLUP_SQL = ("SELECT a, b, sum(v) AS s, count(*) AS n, count(v) AS nv,"
              " min(v) AS lo, max(v) AS hi, avg(v) AS m, min(c) AS cl,"
              " max(c) AS ch FROM t {where} GROUP BY ROLLUP(a, b)")


@pytest.fixture(scope="module")
def table_t():
    """80 rows: a key NULL in the data in both keys, a NULL value, and
    texts whose dictionary order is not their text order."""
    rng = np.random.default_rng(44)
    n = 80
    dicts = DictionarySet()
    a_texts = [b"pear", b"apple", b"fig"]
    c_texts = [b"zeta", b"alpha", b"mu", b"beta"]
    a = dicts.for_column("a").encode(
        [a_texts[i] for i in rng.integers(0, 3, n)])
    c = dicts.for_column("c").encode(
        [c_texts[i] for i in rng.integers(0, 4, n)])
    cols = {"a": a.astype(np.int32), "b": rng.integers(0, 4, n),
            "c": c.astype(np.int32), "v": rng.integers(-40, 90, n)}
    valid = {"a": rng.random(n) > 0.15, "b": rng.random(n) > 0.2,
             "c": rng.random(n) > 0.1, "v": rng.random(n) > 0.1}
    return cols, valid, dicts


def rollup_by_numpy(cols, valid, dicts, keep) -> collections.Counter:
    """The ROLLUP's rows by SQL: for each prefix of (a, b), the kept
    rows grouped by it (NULL a group of its own), the other key NULL."""
    rows = np.flatnonzero(keep)

    def val(name, i):
        return cols[name][i] if valid[name][i] else None

    def text(name, i):
        v = val(name, i)
        return None if v is None else dicts[name].values[v]

    out = collections.Counter()
    for kept in (2, 1, 0):
        groups = collections.defaultdict(list)
        for i in rows:
            groups[tuple(text("a", i) if k == "a" else val("b", i)
                         for k in ("a", "b")[:kept])].append(i)
        if kept == 0 and not groups:
            groups[()] = []     # the grand total is a row over no rows
        for key, members in groups.items():
            vs = [int(cols["v"][i]) for i in members if valid["v"][i]]
            cs = [text("c", i) for i in members if valid["c"][i]]
            out[tuple(key) + (None,) * (2 - kept) + (
                sum(vs) if vs else None, len(members), len(vs),
                min(vs) if vs else None, max(vs) if vs else None,
                round(sum(vs) / len(vs), 9) if vs else None,
                min(cs) if cs else None, max(cs) if cs else None)] += 1
    return out


def rollup_rows(table, dicts) -> collections.Counter:
    names = ("a", "b", "s", "n", "nv", "lo", "hi", "m", "cl", "ch")
    out = collections.Counter()
    for i in range(table.num_rows):
        row = []
        for name in names:
            v, ok = table.cols[name]
            if not ok[i]:
                row.append(None)
            elif name in ("a", "cl", "ch"):
                src = {"a": "a", "cl": "c", "ch": "c"}[name]
                row.append(dicts[src].values[int(v[i])])
            elif name == "m":
                row.append(round(float(v[i]), 9))
            else:
                row.append(int(v[i]))
        out[tuple(row)] += 1
    return out


@pytest.mark.parametrize("where", ["", "WHERE v > 1000000"],
                         ids=["rows", "empty"])
@pytest.mark.parametrize("executor", ["walk", "dq"])
def test_rollup_is_a_group_by_at_each_prefix(table_t, executor, where):
    cols, valid, dicts = table_t
    src = ColumnSource(cols, T_SCHEMA, dicts, valid)
    catalog = Catalog(schemas={"t": T_SCHEMA}, dicts=dicts)
    plan = plan_select_full(parse(ROLLUP_SQL.format(where=where)),
                            catalog).plan
    if executor == "walk":
        # the walk declines a ROLLUP (its levels are sized by their rows
        # on DQ alone), and the plan executor sends it there unasked
        db = Database(sources={"t": src}, dicts=dicts)
        with pytest.raises(NotImplementedError, match="ROLLUP"):
            execute_plan(plan, db, use_dq=False)
        got = to_host(execute_plan(plan, db))
    else:
        got = execute_plan_dq(plan, {"t": partition_source(src, 2)},
                              SimRuntime(n_nodes=1), dicts=dicts,
                              n_tasks=2, block_rows=32)
    keep = valid["v"] & (cols["v"] > 1000000) if where else np.ones(
        len(cols["v"]), dtype=bool)
    want = rollup_by_numpy(cols, valid, dicts, keep)
    assert rollup_rows(got, dicts) == want
    if not where:
        # a key NULL in the data keeps its own finest rows beside the
        # subtotal rows whose key is rolled up
        nulls = [r for r in want.elements() if r[0] is None]
        assert len(nulls) > len({r[1] for r in nulls if r[1] is not None})


# ---------------- ranking windows over NULL keys ----------------

W_SCHEMA = dtypes.schema(("p", dtypes.INT32, True), ("o", dtypes.INT64, True),
                         ("s", dtypes.STRING, True))


@pytest.fixture(scope="module")
def window_table():
    """60 rows: NULLs in the partition key, the numeric order key and a
    text order key, stale bits under every NULL, many ties."""
    rng = np.random.default_rng(67)
    n = 60
    dicts = DictionarySet()
    s = dicts.for_column("s").encode(
        [[b"kiwi", b"date", b"lime", b"apple"][i]
         for i in rng.integers(0, 4, n)]).astype(np.int32)
    valid = {"p": rng.random(n) > 0.25, "o": rng.random(n) > 0.25,
             "s": rng.random(n) > 0.2}
    cols = {"p": rng.integers(0, 3, n).astype(np.int32),
            "o": rng.integers(-3, 4, n), "s": s}
    # what lies under a NULL differs from row to row
    for name, c in cols.items():
        cols[name] = np.where(valid[name], c,
                              rng.integers(5, 1000, n)).astype(c.dtype)
    return cols, valid, dicts


def ranks_by_definition(func, part, order, desc) -> list:
    """rank: 1 + the rows of the partition strictly before in the order;
    dense_rank: 1 + the distinct order values strictly before; row_number:
    1 + the rows before in the order, ties by row number. NULL is one
    partition; NULL order values come last and are peers."""
    n = len(part)
    key = [(0, -o if desc else o) if o is not None else (1, 0)
           for o in order]
    out = []
    for i in range(n):
        mates = [j for j in range(n) if part[j] == part[i]]
        if func == "rank":
            out.append(1 + sum(key[j] < key[i] for j in mates))
        elif func == "dense_rank":
            out.append(1 + len({key[j] for j in mates if key[j] < key[i]}))
        else:
            out.append(1 + sum((key[j], j) < (key[i], i) for j in mates))
    return out


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
@pytest.mark.parametrize("by", ["o", "s"])
@pytest.mark.parametrize("func", ["rank", "dense_rank", "row_number"])
def test_window_over_null_keys_is_sql(window_table, func, by, desc):
    cols, valid, dicts = window_table
    prog = Program((WindowStep(func, ("p",), (by,), (desc,), "w"),))
    block = TableBlock.from_numpy(cols, W_SCHEMA, valid, 64)
    cp = compiler.compile_program(prog, W_SCHEMA, dicts)
    got = jax.jit(cp.run)(block, cp.aux).to_numpy()["w"].tolist()
    part = [int(p) if ok else None for p, ok in zip(cols["p"], valid["p"])]
    if by == "o":
        order = [int(o) if ok else None
                 for o, ok in zip(cols["o"], valid["o"])]
    else:
        order = [dicts["s"].values[s] if ok else None
                 for s, ok in zip(cols["s"], valid["s"])]
        # bytes have no minus: a descending text orders by its rank
        ranks = {t: r for r, t in enumerate(sorted(set(
            t for t in order if t is not None)))}
        order = [None if t is None else ranks[t] for t in order]
    want = ranks_by_definition(func, part, order, desc)
    assert got == want
    # the host oracle engine ranks the same way
    table = OracleTable({k: (cols[k], valid[k]) for k in cols}, W_SCHEMA)
    assert run_oracle(prog, table, dicts).cols["w"][0].tolist() == want


def test_no_lexsort_left_in_the_compiler():
    """The window step sorts by ``kernels.stable_lexsort``'s passes (a
    word a pass, NULLs alike), not by one comparator sort over raw data."""
    assert "lexsort(" not in inspect.getsource(compiler).replace(
        "stable_lexsort(", "")


def test_window_on_dq_counts_its_rows_unprofiled(window_table):
    """With no statement span (profiling off) a DQ window reads nothing
    back for its span: its rows are its input blocks' known counts, and
    the process still counts them."""
    cols, valid, dicts = window_table
    src = ColumnSource(cols, W_SCHEMA, dicts, valid)
    plan = plan_select_full(parse(
        "SELECT p, o, rank() OVER (PARTITION BY p ORDER BY o DESC) AS w"
        " FROM t WHERE o > -2"), Catalog(schemas={"t": W_SCHEMA},
                                         dicts=dicts)).plan
    before = counts("window", WINDOW_COUNTERS)
    got = execute_plan_dq(plan, {"t": partition_source(src, 2)},
                          SimRuntime(n_nodes=1), dicts=dicts, n_tasks=2,
                          block_rows=16)
    delta = {k: v - before[k]
             for k, v in counts("window", WINDOW_COUNTERS).items()}
    keep = valid["o"] & (cols["o"] > -2)
    assert got.num_rows == delta["rows_in"] == int(keep.sum())
    assert delta["windows"] == 1 and delta["bytes_least"] > 0
    part = [int(p) if ok else None
            for p, ok in zip(cols["p"][keep], valid["p"][keep])]
    want = ranks_by_definition("rank", part, cols["o"][keep].tolist(),
                               True)
    w, _ = got.cols["w"]
    p, p_ok = got.cols["p"]
    o, _ = got.cols["o"]
    rows = collections.Counter(zip(
        [int(x) if ok else None for x, ok in zip(p, p_ok)],
        o.tolist(), w.tolist()))
    assert rows == collections.Counter(zip(
        part, cols["o"][keep].tolist(), want))
