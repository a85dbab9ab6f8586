"""The cell ``tpcds-store.star``: its files found by name (a subset check:
later cells may list more metrics), the three references against a
row-at-a-time join at a tiny size, the float32 control of q7's averages
against its stated limit, the generator's row counts and key domains on
two seeds, and the three readers over a hand-made run."""

import compare
import numpy as np
import pytest
import run
import tpcds_gen
import work

CELL = "tpcds-store.star"
STATEMENTS = ("tpcds_q3", "tpcds_q7", "tpcds_q19")
METRICS = {"tpcds_join_ms", "tpcds_device_idle_share", "tpcds_roofline_share"}
SEEDS = (2147483999, 4200000017)


def ref(sid: str):
    return run.load_module(run.HERE, "refs", sid)


def reader(name: str):
    return run.load_module(run.HERE, "layer_metrics", name).read


@pytest.fixture(scope="module")
def tiny():
    """Every table cut far down and a pool of 12 zip codes, so that q19's
    predicate drops rows; the whole fact table, so that q3 and q19 find
    their manufacturer's and manager's items."""
    return tpcds_gen.make(0.2, SEEDS[0], fact_share=1.0, zip_codes=12)


def test_the_cell_names_its_files_and_lists_its_metrics():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert METRICS <= {m["name"] for m in cell["per_layer"]}
    assert [m["name"] for m in cell["end_to_end"]] == [
        "rows_per_s", "query_geomean_ms", "setup_s"]
    traffic = cell["traffic"]
    assert traffic["statements"] == list(STATEMENTS)
    assert traffic["executors"] == dict.fromkeys(STATEMENTS, "dq")
    config = cell["config"]
    assert config["generator"] == "tpcds_gen"
    assert config["scale_factor"] == 100
    assert config["generator_options"] == {"fact_share": 0.125}
    assert config["tables"] == list(tpcds_gen.TABLES)
    assert config["guarantees"]["upsert_probe_table"] == "store"
    for sid in STATEMENTS:
        sql = (run.HERE / "statements" / f"{sid}.sql").read_text()
        for table, cols in ref(sid).TABLES.items():
            assert table in sql and all(c in sql for c in cols), (sid, table)


# ---------------- the references ---------------------------------------


def _rows(data, table: str) -> list[dict]:
    t = data.tables[table]
    return [dict(zip(t, values)) for values in zip(
        *(v.tolist() for v in t.values()))]


def _by(rows: list, key: str) -> dict:
    return {r[key]: r for r in rows}


def _text(data, col: str, i: int) -> bytes:
    return data.dicts[col].values[i]


def brute_q3(data) -> list:
    dates = _by(_rows(data, "date_dim"), "d_date_sk")
    items = _by(_rows(data, "item"), "i_item_sk")
    sums: dict = {}
    for s in _rows(data, "store_sales"):
        d, i = dates[s["ss_sold_date_sk"]], items[s["ss_item_sk"]]
        if d["d_moy"] == 11 and i["i_manufact_id"] == 128:
            k = (d["d_year"], i["i_brand_id"], i["i_brand"])
            sums[k] = sums.get(k, 0) + s["ss_ext_sales_price"]
    rows = sorted(sums.items(), key=lambda kv: (kv[0][0], -kv[1], kv[0][1]))
    return [k + (v,) for k, v in rows][:100]


def brute_q7(data) -> list:
    dates = _by(_rows(data, "date_dim"), "d_date_sk")
    items = _by(_rows(data, "item"), "i_item_sk")
    demos = _by(_rows(data, "customer_demographics"), "cd_demo_sk")
    promos = _by(_rows(data, "promotion"), "p_promo_sk")
    acc: dict = {}
    for s in _rows(data, "store_sales"):
        cd, p = demos[s["ss_cdemo_sk"]], promos[s["ss_promo_sk"]]
        if (_text(data, "cd_gender", cd["cd_gender"]) == b"M"
                and _text(data, "cd_marital_status",
                          cd["cd_marital_status"]) == b"S"
                and _text(data, "cd_education_status",
                          cd["cd_education_status"]) == b"College"
                and (_text(data, "p_channel_email",
                           p["p_channel_email"]) == b"N"
                     or _text(data, "p_channel_event",
                              p["p_channel_event"]) == b"N")
                and dates[s["ss_sold_date_sk"]]["d_year"] == 2000):
            a = acc.setdefault(items[s["ss_item_sk"]]["i_item_id"],
                               [0, 0, 0, 0, 0])
            for j, col in enumerate(("ss_quantity", "ss_list_price",
                                     "ss_coupon_amt", "ss_sales_price")):
                a[j] += s[col]
            a[4] += 1
    ids = sorted(acc, key=lambda i: _text(data, "i_item_id", i))[:100]
    return [(i, acc[i][0] / acc[i][4], acc[i][1] / (acc[i][4] * 100),
             acc[i][2] / (acc[i][4] * 100), acc[i][3] / (acc[i][4] * 100))
            for i in ids]


def brute_q19(data) -> list:
    dates = _by(_rows(data, "date_dim"), "d_date_sk")
    items = _by(_rows(data, "item"), "i_item_sk")
    customers = _by(_rows(data, "customer"), "c_customer_sk")
    addresses = _by(_rows(data, "customer_address"), "ca_address_sk")
    stores = _by(_rows(data, "store"), "s_store_sk")
    sums: dict = {}
    for s in _rows(data, "store_sales"):
        d, i = dates[s["ss_sold_date_sk"]], items[s["ss_item_sk"]]
        if not (d["d_moy"] == 11 and d["d_year"] == 1998
                and i["i_manager_id"] == 8):
            continue
        a = addresses[customers[s["ss_customer_sk"]]["c_current_addr_sk"]]
        st = stores[s["ss_store_sk"]]
        if (_text(data, "ca_zip", a["ca_zip"])[:5]
                == _text(data, "s_zip", st["s_zip"])[:5]):
            continue
        k = (i["i_brand_id"], i["i_brand"], i["i_manufact_id"],
             i["i_manufact"])
        sums[k] = sums.get(k, 0) + s["ss_ext_sales_price"]
    rows = sorted(sums.items(), key=lambda kv: (
        -kv[1], _text(data, "i_brand", kv[0][1]), kv[0][0], kv[0][2],
        _text(data, "i_manufact", kv[0][3])))
    return [k + (v,) for k, v in rows][:100]


BRUTE = {"tpcds_q3": brute_q3, "tpcds_q7": brute_q7, "tpcds_q19": brute_q19}


@pytest.mark.parametrize("sid", STATEMENTS)
def test_each_reference_is_a_row_at_a_time_join(tiny, sid):
    r = ref(sid)
    want = BRUTE[sid](tiny)
    got = r.reference(tiny)
    assert list(got) == list(r.COLUMNS)
    assert len(want) > 0 and all(len(v) == len(want) for v in got.values())
    for j, col in enumerate(r.COLUMNS):
        column = [row[j] for row in want]
        if r.COLUMNS[col][0] == "ratio":
            assert np.array_equal(got[col], np.array(column)), col
        else:
            assert got[col].tolist() == column, col


def test_q19s_zip_predicate_drops_rows_at_this_size(tiny):
    ss = tiny.tables["store_sales"]
    cu, ca, st = (tiny.tables[t] for t in ("customer", "customer_address",
                                           "store"))
    buyer = ca["ca_zip"][cu["c_current_addr_sk"][ss["ss_customer_sk"] - 1]
                         - 1]
    seller = st["s_zip"][ss["ss_store_sk"] - 1]
    same = (np.array(tiny.dicts["ca_zip"].values, dtype=object)[buyer]
            == np.array(tiny.dicts["s_zip"].values, dtype=object)[seller])
    assert 0 < same.mean() < 0.5
    # the two columns' dictionaries number the same texts differently
    assert tiny.dicts["ca_zip"].values != tiny.dicts["s_zip"].values


def test_the_float32_control_fails_q7s_ratio_limit(tiny):
    r = ref("tpcds_q7")
    got = compare.compare(r.reference(tiny, "float32"), r.reference(tiny),
                          r.COLUMNS)
    assert got["ratio_rel_gap"] > r.RATIO_REL_GAP_LIMIT
    exact = compare.compare(r.reference(tiny), r.reference(tiny), r.COLUMNS)
    assert exact == {"wrong_cells": 0, "ratio_rel_gap": 0.0}


# ---------------- the generator ---------------------------------------


@pytest.fixture(scope="module", params=SEEDS)
def sf100(request):
    """Every dimension at its SF 100 count, and a thousandth of the fact
    table (the chip's eighth is 6.5 GB of host arrays)."""
    return tpcds_gen.make(100, request.param, fact_share=0.001)


def test_the_stated_row_counts(sf100):
    for t, n in tpcds_gen.SF100_ROWS.items():
        want = n if t != "store_sales" else round(n * 0.001)
        assert sf100.rows(t) == want, t
    assert round(tpcds_gen.SF100_ROWS["store_sales"] * 0.125) == 35_999_628
    for t in tpcds_gen.TABLES:
        assert list(sf100.tables[t]) == [c for c, _ in sf100.schema(t)]
        assert len({len(v) for v in sf100.tables[t].values()}) == 1, t
    assert [len(sf100.schema(t)) for t in tpcds_gen.TABLES] == [
        29, 19, 28, 22, 9, 13, 18, 23]
    assert sum(tpcds_gen.WIDTHS[x] for _, x in
               sf100.schema("store_sales")) == 180


def test_the_stated_key_domains(sf100):
    it, cd, dd = (sf100.tables[t] for t in (
        "item", "customer_demographics", "date_dim"))
    assert set(np.unique(it["i_manufact_id"])) == set(range(1, 1001))
    assert set(np.unique(it["i_manager_id"])) == set(range(1, 101))
    assert len(np.unique(it["i_brand_id"])) == 1000
    assert len(sf100.dicts["i_brand"]) == 1000
    assert len(sf100.dicts["i_item_id"]) == 102_000
    combos = np.stack([cd[c] for c in list(cd)[1:]], axis=1)
    assert len(np.unique(combos, axis=0)) == 1_920_800
    assert [len(sf100.dicts[c]) for c in (
        "cd_gender", "cd_marital_status", "cd_education_status")] == [2, 5, 7]
    assert dd["d_year"].min() == 1900 and dd["d_year"].max() == 2100
    assert set(np.unique(dd["d_moy"])) == set(range(1, 13))
    ss = sf100.tables["store_sales"]
    first, last = (tpcds_gen.date_sk(d) for d in (
        tpcds_gen.SALES_FIRST, tpcds_gen.SALES_LAST))
    assert first <= ss["ss_sold_date_sk"].min() <= ss[
        "ss_sold_date_sk"].max() <= last
    # a million addresses cover the 10,000 zip codes; the 402 stores
    # draw from the same pool
    assert len(sf100.dicts["ca_zip"]) == 10_000
    assert set(sf100.dicts["s_zip"].values) <= set(
        sf100.dicts["ca_zip"].values)
    keys = ss["ss_item_sk"] * (1 << 32) + ss["ss_ticket_number"]
    assert np.all(np.diff(keys) > 0)      # distinct, in the key's order
    for col in sf100.dicts.columns():
        values = sf100.dicts[col].values
        assert len(set(values)) == len(values), col


def test_the_same_seed_gives_the_same_arrays():
    a, b = (tpcds_gen.make(0.2, SEEDS[0]) for _ in range(2))
    c = tpcds_gen.make(0.2, SEEDS[1])
    for t in tpcds_gen.TABLES:
        for col, v in a.tables[t].items():
            assert np.array_equal(v, b.tables[t][col]), col
        assert c.rows(t) == a.rows(t)
    assert not np.array_equal(a.tables["store_sales"]["ss_customer_sk"],
                              c.tables["store_sales"]["ss_customer_sk"])


# ---------------- the readers -----------------------------------------


def test_the_join_reader_means_its_key_over_the_statements_that_have_it():
    six = {"plan": 0.001, "dispatch": 0.02, "unattributed": 0.05}
    run_ = {"statements": [
        {"id": "tpcds_q3", "stages": dict(six, dq_join=0.5)},
        {"id": "tpcds_q7", "stages": dict(six, dq_join=1.5)},
        {"id": "untraced"}]}
    assert reader("tpcds_join_ms")(run_) == pytest.approx(1000.0)
    # a program without the key (the parent), or an untraced run
    assert reader("tpcds_join_ms")({"statements": [
        {"id": "q", "stages": six}, {"id": "q"}]}) is None


def test_the_trace_readers_use_the_accepted_formulas():
    run_ = {"least_seconds": 0.001,
            "trace": {"busy_s": 4.0, "window_s": 5.0, "devices": 1}}
    for name, accepted in (("tpcds_roofline_share", "device_roofline_share"),
                           ("tpcds_device_idle_share", "device_idle_share")):
        assert reader(name)(run_) == reader(accepted)(run_)
        assert reader(name)({"trace": None, "least_seconds": None}) is None
    assert reader("tpcds_roofline_share")(run_) == pytest.approx(0.025)


class Rows:
    """Row counts only: the widths are the schema's."""

    def rows(self, t):
        return tpcds_gen.SF100_ROWS[t] if t != "store_sales" else 35_999_628

    def schema(self, t):
        return tpcds_gen.SCHEMAS[t]


# hand-counted: an identifier 8 B, a decimal 8, an integer 4, a string id 4
@pytest.mark.parametrize("sid,fact_bytes", (
    ("tpcds_q3", 24), ("tpcds_q7", 60), ("tpcds_q19", 40)))
def test_rows_and_bytes_of_a_statement(sid, fact_bytes):
    r = ref(sid)
    assert work.statement_rows(r.TABLES, Rows()) == 35_999_628 + sum(
        tpcds_gen.SF100_ROWS[t] for t in r.TABLES if t != "store_sales")
    fact = work.statement_bytes({"store_sales": r.TABLES["store_sales"]},
                                Rows(), tpcds_gen.WIDTHS)
    assert fact == 35_999_628 * fact_bytes
