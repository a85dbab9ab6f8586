"""work.py's rows and bytes against hand-counted widths; the peaks."""

import json

import pytest
import run
import tpch_gen
import work

BENCH = run.HERE


class Rows:
    """Row counts only: the widths are the schema's."""

    def __init__(self, rows):
        self._rows = rows

    def rows(self, t):
        return self._rows[t]

    def schema(self, t):
        return tpch_gen.SCHEMAS[t]


ROWS = {"lineitem": 1000, "orders": 100, "customer": 10}
# hand-counted: date 4, dictionary id 4, decimal 8, int64 8, int32 4
HAND = {
    # shipdate 4 + returnflag 4 + linestatus 4 + qty, price, disc, tax 4x8
    "q1": 1000 * (4 + 4 + 4 + 32),
    # shipdate 4 + discount 8 + quantity 8 + extendedprice 8
    "q6": 1000 * (4 + 8 + 8 + 8),
    # customer: mktsegment 4 + custkey 8; orders: orderkey 8 + custkey 8
    # + orderdate 4 + shippriority 4; lineitem: orderkey 8 + price 8 +
    # discount 8 + shipdate 4
    "q3": 10 * 12 + 100 * 24 + 1000 * 28,
}


@pytest.mark.parametrize("sid", sorted(HAND))
def test_statement_bytes_match_hand_counted_widths(sid):
    ref = run.load_module(BENCH, "refs", sid)
    assert work.statement_bytes(ref.TABLES, Rows(ROWS),
                                tpch_gen.WIDTHS) == HAND[sid]


def test_statement_rows_are_the_from_tables():
    q3 = run.load_module(BENCH, "refs", "q3")
    q1 = run.load_module(BENCH, "refs", "q1")
    assert work.statement_rows(q3.TABLES, Rows(ROWS)) == 1110
    assert work.statement_rows(q1.TABLES, Rows(ROWS)) == 1000


def test_every_referenced_column_is_in_the_sql():
    for sid in HAND:
        ref = run.load_module(BENCH, "refs", sid)
        sql = (BENCH / "statements" / f"{sid}.sql").read_text()
        for cols in ref.TABLES.values():
            for c in cols:
                assert c in sql, (sid, c)


def test_peaks_of_the_v5e_and_an_unknown_kind(tmp_path):
    p = work.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flop_per_s"] == 197e12
    assert p["int8_op_per_s"] == 393e12 and p["source"]
    assert work.least_seconds(819_000_000, p) == pytest.approx(1e-3)
    with pytest.raises(work.UnknownDevice):
        work.peaks_for("TPU v9 imaginary")
    other = tmp_path / "peaks.json"
    other.write_text(json.dumps({"X": {"hbm_bytes_per_s": 1.0}}))
    assert work.peaks_for("X", other)["hbm_bytes_per_s"] == 1.0


OPTIONS = json.loads((BENCH / "configs" / "tpch-sf3-1chip.json").read_text()
                     )["generator_options"]


def test_the_configurations_options_give_every_seed_the_same_sizes():
    """With the configuration's ``generator_options`` the lineitem rows
    and the rows shipped by its date are pinned: the seed changes the
    values, never the amount of work. Without them the counts wander,
    as the program's own generator's do."""
    cutoff = tpch_gen.days(OPTIONS["shipped_by"])
    sizes, free = set(), set()
    for seed in (1, 2147483999, 4000000123):
        d = tpch_gen.make(0.02, seed, **OPTIONS)
        li = d.tables["lineitem"]
        sizes.add((d.rows("lineitem"),
                   int((li["l_shipdate"] <= cutoff).sum())))
        delay = li["l_shipdate"] - d.tables["orders"]["o_orderdate"][
            li["l_orderkey"] - 1]
        assert delay.min() >= 1 and delay.max() <= tpch_gen.MAX_SHIP_DELAY
        assert li["l_linenumber"].max() <= 7
        free.add(tpch_gen.make(0.02, seed).rows("lineitem"))
    assert sizes == {(120000, 118312)}
    assert len(free) == 3
    a, b = tpch_gen.make(0.02, 5, **OPTIONS), tpch_gen.make(0.02, 5, **OPTIONS)
    assert all((a.tables[t][c] == b.tables[t][c]).all()
               for t in a.tables for c in a.tables[t])


# TPC-H specification, section 1.4.1: every column of the eight tables
SPEC_COLUMNS = {
    "part": "partkey name mfgr brand type size container retailprice "
            "comment",
    "supplier": "suppkey name address nationkey phone acctbal comment",
    "partsupp": "partkey suppkey availqty supplycost comment",
    "customer": "custkey name address nationkey phone acctbal mktsegment "
                "comment",
    "orders": "orderkey custkey orderstatus totalprice orderdate "
              "orderpriority clerk shippriority comment",
    "lineitem": "orderkey partkey suppkey linenumber quantity "
                "extendedprice discount tax returnflag linestatus shipdate "
                "commitdate receiptdate shipinstruct shipmode comment",
    "nation": "nationkey name regionkey comment",
    "region": "regionkey name comment",
}
PREFIX = {"part": "p_", "supplier": "s_", "partsupp": "ps_",
          "customer": "c_", "orders": "o_", "lineitem": "l_",
          "nation": "n_", "region": "r_"}


def test_the_tables_have_every_column_of_the_specification():
    d = tpch_gen.make(0.01, 3)
    assert sum(len(v.split()) for v in SPEC_COLUMNS.values()) == 61
    for t, cols in SPEC_COLUMNS.items():
        want = [PREFIX[t] + c for c in cols.split()]
        assert [c for c, _ in d.schema(t)] == want
        assert list(d.tables[t]) == want
        n = d.rows(t)
        assert all(len(v) == n for v in d.tables[t].values())


def test_pooled_texts_have_the_stated_sizes_and_lengths():
    """The pools each configuration lists under ``assumed``."""
    d = tpch_gen.make(0.05, 11)
    assumed = json.loads((BENCH / "configs" / "tpch-sf1-1chip.json")
                         .read_text())["assumed"]["text_pools"]
    for col, (size, lo, hi) in tpch_gen.POOLS.items():
        texts = d.dicts[col].values
        assert len(texts) == len(set(texts)) == size
        assert lo <= min(map(len, texts)) and max(map(len, texts)) <= hi
        assert f"{col} {size} " in assumed and f"{lo}-{hi}" in assumed
        ids = next(d.tables[t][col] for t in d.tables if col in d.tables[t])
        assert ids.min() >= 0 and ids.max() < size
    for col, n in (("n_comment", 25), ("r_comment", 5)):
        texts = d.dicts[col].values
        assert len(set(texts)) == n
        assert all(31 <= len(x) <= 114 for x in texts)
    clerks = d.dicts["o_clerk"].values
    assert clerks[0] == b"Clerk#000000001" and len(clerks) == 50
    assert all(len(x.split()) == 5 for x in d.dicts["p_name"].values)
    # the chains the specification's LIKE predicates probe for are there
    assert any(b"special" in x and b"requests" in x
               for x in d.dicts["o_comment"].values)
    assert any(b"Customer" in x and b"Complaints" in x
               for x in d.dicts["s_comment"].values)
