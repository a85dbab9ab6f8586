"""TPC-DS's store channel from a seed, at the specification's columns.

The source is the TPC-DS specification (v3/v4), section 2 (the logical
schema) and section 3 (row counts by scale factor); upstream ships the
benchmark as ``ydb workload tpcds``. The deployment is SF 100 over 8
chips with ``store_sales`` hash-sharded by its key: one chip holds its
share of ``store_sales`` (``fact_share``, 1/8 = 35,999,628 rows) and
every dimension its three statements read (q3, q7, q19) whole, as a
deployment replicates them. dsdgen is not in this repository, so a
seeded generator stands in for it. It imports nothing of the program
and gives ``deploy.py`` and ``work.py`` what ``tpch_gen.Data`` gives
them: ``tables``, ``rows``, ``schema``, ``primary_key``, ``dicts``,
``widths``, and ``make(scale_factor, seed, **options)``.

The schema is the specification's, column for column in its order
(``SCHEMAS``): identifiers are ``int64``, integers ``int32``, decimals
scaled ``int64`` at their scale (as ``tpch_gen.py`` holds TPC-H's),
dates ``date`` and every ``char`` / ``varchar`` a ``string``, a 4-byte
dictionary id on the device. No value is NULL (the harness declares
every column ``NOT NULL``): dsdgen's NULLs become values of the column's
domain (a record's open end date the calendar's last day, ``c_login``
the empty text). Every distribution is a stated choice that keeps the
domain of each key the statements read: 10 categories, their classes and
up to ``BRANDS_PER_CLASS`` brands a class (``i_brand`` a function of
``i_brand_id``), ``MANUFACTS`` manufacturers and ``MANAGERS`` managers
each covering the items evenly, ``zip_codes`` zip codes shared by
addresses and stores, the cross product of the demographics, the years
and months of the sales.

``scale_factor`` is TPC-DS's: 100 gives every dimension at its SF 100
count (``SF100_ROWS``); a smaller one scales every table but
``date_dim`` (a calendar) linearly, down to ``FLOORS``, so a test can
cut the deployment; ``customer_demographics`` then keeps the first rows
of its cross product, which hold every gender, marital status and
education. ``store_sales`` has ``SF100_ROWS`` x scale / 100 x
``fact_share`` rows, in tickets of 8 to 16 lines of distinct items; the
rows arrive in the order of the primary key (``ss_item_sk``,
``ss_ticket_number``), as a dump of a table stored by that key gives
them: each batch the loader writes covers a key range of its own.
The same sizes for every seed.
"""

from __future__ import annotations

import numpy as np

#: sql type -> bytes per value as the engine holds it on the device
WIDTHS = {"int64": 8, "int32": 4, "decimal(7, 2)": 8, "decimal(5, 2)": 8,
          "decimal(15, 2)": 8, "string": 4, "date": 4}

_ID, _N, _S, _DT = "int64", "int32", "string", "date"
_D7, _D5, _D15 = "decimal(7, 2)", "decimal(5, 2)", "decimal(15, 2)"

#: specification section 2: the tables the three statements read, every
#: column in its order
SCHEMAS = {
    "store_sales": (
        ("ss_sold_date_sk", _ID), ("ss_sold_time_sk", _ID),
        ("ss_item_sk", _ID), ("ss_customer_sk", _ID), ("ss_cdemo_sk", _ID),
        ("ss_hdemo_sk", _ID), ("ss_addr_sk", _ID), ("ss_store_sk", _ID),
        ("ss_promo_sk", _ID), ("ss_ticket_number", _ID),
        ("ss_quantity", _N), ("ss_wholesale_cost", _D7),
        ("ss_list_price", _D7), ("ss_sales_price", _D7),
        ("ss_ext_discount_amt", _D7), ("ss_ext_sales_price", _D7),
        ("ss_ext_wholesale_cost", _D7), ("ss_ext_list_price", _D7),
        ("ss_ext_tax", _D7), ("ss_coupon_amt", _D7), ("ss_net_paid", _D7),
        ("ss_net_paid_inc_tax", _D7), ("ss_net_profit", _D7)),
    "date_dim": (
        ("d_date_sk", _ID), ("d_date_id", _S), ("d_date", _DT),
        ("d_month_seq", _N), ("d_week_seq", _N), ("d_quarter_seq", _N),
        ("d_year", _N), ("d_dow", _N), ("d_moy", _N), ("d_dom", _N),
        ("d_qoy", _N), ("d_fy_year", _N), ("d_fy_quarter_seq", _N),
        ("d_fy_week_seq", _N), ("d_day_name", _S), ("d_quarter_name", _S),
        ("d_holiday", _S), ("d_weekend", _S), ("d_following_holiday", _S),
        ("d_first_dom", _N), ("d_last_dom", _N), ("d_same_day_ly", _N),
        ("d_same_day_lq", _N), ("d_current_day", _S),
        ("d_current_week", _S), ("d_current_month", _S),
        ("d_current_quarter", _S), ("d_current_year", _S)),
    "item": (
        ("i_item_sk", _ID), ("i_item_id", _S), ("i_rec_start_date", _DT),
        ("i_rec_end_date", _DT), ("i_item_desc", _S),
        ("i_current_price", _D7), ("i_wholesale_cost", _D7),
        ("i_brand_id", _N), ("i_brand", _S), ("i_class_id", _N),
        ("i_class", _S), ("i_category_id", _N), ("i_category", _S),
        ("i_manufact_id", _N), ("i_manufact", _S), ("i_size", _S),
        ("i_formulation", _S), ("i_color", _S), ("i_units", _S),
        ("i_container", _S), ("i_manager_id", _N), ("i_product_name", _S)),
    "customer": (
        ("c_customer_sk", _ID), ("c_customer_id", _S),
        ("c_current_cdemo_sk", _ID), ("c_current_hdemo_sk", _ID),
        ("c_current_addr_sk", _ID), ("c_first_shipto_date_sk", _ID),
        ("c_first_sales_date_sk", _ID), ("c_salutation", _S),
        ("c_first_name", _S), ("c_last_name", _S),
        ("c_preferred_cust_flag", _S), ("c_birth_day", _N),
        ("c_birth_month", _N), ("c_birth_year", _N),
        ("c_birth_country", _S), ("c_login", _S), ("c_email_address", _S),
        ("c_last_review_date_sk", _ID)),
    "customer_address": (
        ("ca_address_sk", _ID), ("ca_address_id", _S),
        ("ca_street_number", _S), ("ca_street_name", _S),
        ("ca_street_type", _S), ("ca_suite_number", _S), ("ca_city", _S),
        ("ca_county", _S), ("ca_state", _S), ("ca_zip", _S),
        ("ca_country", _S), ("ca_gmt_offset", _D5),
        ("ca_location_type", _S)),
    "customer_demographics": (
        ("cd_demo_sk", _ID), ("cd_gender", _S), ("cd_marital_status", _S),
        ("cd_education_status", _S), ("cd_purchase_estimate", _N),
        ("cd_credit_rating", _S), ("cd_dep_count", _N),
        ("cd_dep_employed_count", _N), ("cd_dep_college_count", _N)),
    "store": (
        ("s_store_sk", _ID), ("s_store_id", _S), ("s_rec_start_date", _DT),
        ("s_rec_end_date", _DT), ("s_closed_date_sk", _ID),
        ("s_store_name", _S), ("s_number_employees", _N),
        ("s_floor_space", _N), ("s_hours", _S), ("s_manager", _S),
        ("s_market_id", _N), ("s_geography_class", _S),
        ("s_market_desc", _S), ("s_market_manager", _S),
        ("s_division_id", _N), ("s_division_name", _S),
        ("s_company_id", _N), ("s_company_name", _S),
        ("s_street_number", _S), ("s_street_name", _S),
        ("s_street_type", _S), ("s_suite_number", _S), ("s_city", _S),
        ("s_county", _S), ("s_state", _S), ("s_zip", _S),
        ("s_country", _S), ("s_gmt_offset", _D5),
        ("s_tax_percentage", _D5)),
    "promotion": (
        ("p_promo_sk", _ID), ("p_promo_id", _S), ("p_start_date_sk", _ID),
        ("p_end_date_sk", _ID), ("p_item_sk", _ID), ("p_cost", _D15),
        ("p_response_target", _N), ("p_promo_name", _S),
        ("p_channel_dmail", _S), ("p_channel_email", _S),
        ("p_channel_catalog", _S), ("p_channel_tv", _S),
        ("p_channel_radio", _S), ("p_channel_press", _S),
        ("p_channel_event", _S), ("p_channel_demo", _S),
        ("p_channel_details", _S), ("p_purpose", _S),
        ("p_discount_active", _S)),
}
PRIMARY_KEYS = {"store_sales": ("ss_item_sk", "ss_ticket_number"),
                **{t: (s[0][0],) for t, s in SCHEMAS.items()
                   if t != "store_sales"}}
#: specification section 3: rows at SF 100
SF100_ROWS = {"store_sales": 287_997_024, "date_dim": 73_049,
              "item": 204_000, "customer": 2_000_000,
              "customer_address": 1_000_000,
              "customer_demographics": 1_920_800, "store": 402,
              "promotion": 1_000}
#: the fewest rows a cut deployment keeps of each dimension: every
#: manufacturer, manager and demographic combination the statements
#: select on stays
FLOORS = {"item": 2_000, "customer": 2_000, "customer_address": 1_000,
          "customer_demographics": 1_000, "store": 12, "promotion": 100}
#: load order: the small tables first, so a fault shows before the long
#: load
TABLES = ("store", "promotion", "date_dim", "item", "customer_demographics",
          "customer_address", "customer", "store_sales")

FIRST_DATE_SK, FIRST_DATE = 2_415_022, "1900-01-02"
#: the sales' days (dsdgen's: 1998-01-02 to 2003-01-02), uniform
SALES_FIRST, SALES_LAST = "1998-01-02", "2003-01-02"
LINES_PER_TICKET = (8, 16)
MANUFACTS, MANAGERS, BRANDS_PER_CLASS = 1000, 100, 10
HOUSEHOLD_DEMOGRAPHICS, TIME_DIM = 7_200, 86_400
ZIP_CODES = 10_000
#: e-mail addresses are first.last@domain from the name pools (4,000 first
#: and 5,000 last names: 20,000 pairs a domain)
EMAILS, MAIL_DOMAINS = 1 << 16, (b"example", b"mail", b"post", b"web")
CATEGORIES = (b"Women", b"Men", b"Children", b"Shoes", b"Music",
              b"Jewelry", b"Home", b"Sports", b"Books", b"Electronics")
#: classes a category (ids 1..n within each)
CLASSES = (4, 4, 4, 4, 4, 16, 16, 16, 16, 16)
#: dsdgen's syllables: a number's decimal digits spelled out name a
#: manufacturer (i_manufact), a product and a promotion
SYLLABLES = (b"ought", b"able", b"pri", b"ese", b"anti", b"cally",
             b"ation", b"eing", b"bar", b"n st")
BRAND_WORDS = (b"amalg", b"edu pack", b"exporti", b"importo", b"scholar",
               b"univ", b"brand", b"corp", b"maxi", b"nameless")
CLASS_WORDS = tuple(b"%s%s" % (a, b) for a in (b"k", b"z", b"q", b"x")
                    for b in (b"ula", b"ora", b"ine", b"ette"))
GENDERS = (b"M", b"F")
MARITAL = (b"M", b"S", b"D", b"W", b"U")
EDUCATION = (b"Primary", b"Secondary", b"College", b"2 yr Degree",
             b"4 yr Degree", b"Advanced Degree", b"Unknown")
CREDIT = (b"Low Risk", b"Good", b"High Risk", b"Unknown")
#: the cross product's radices, the fastest first (dsdgen's order of
#: the demographics' columns)
DEMOGRAPHICS = (2, 5, 7, 20, 4, 7, 7, 7)
#: the 50 states and DC, as dsdgen's addresses draw them
STATES = tuple(b"AK AL AR AZ CA CO CT DC DE FL GA HI IA ID IL IN KS KY LA MA MD ME "
               b"MI MN MO MS MT NC ND NE NH NJ NM NV NY OH OK OR PA RI SC SD TN TX "
               b"UT VA VT WA WI WV WY".split())
DAY_NAMES = (b"Sunday", b"Monday", b"Tuesday", b"Wednesday", b"Thursday",
             b"Friday", b"Saturday")


class Dict:
    """One string column's dictionary: distinct values, id = position."""

    def __init__(self, values):
        self.values: list[bytes] = list(values)
        self._ids: dict | None = None

    def get(self, v: bytes):
        if self._ids is None:
            self._ids = {v: i for i, v in enumerate(self.values)}
        return self._ids.get(v)

    def __len__(self) -> int:
        return len(self.values)


class Dicts:
    """The dictionaries of all string columns, by column name."""

    def __init__(self):
        self._by_column: dict[str, Dict] = {}

    def columns(self) -> list[str]:
        return list(self._by_column)

    def __getitem__(self, col: str) -> Dict:
        return self._by_column[col]

    def __setitem__(self, col: str, values) -> None:
        self._by_column[col] = Dict(values)


def days(s: str) -> int:
    """A date as the int32 the date columns hold."""
    return int(np.datetime64(s, "D").astype(np.int64))


def date_sk(s: str) -> int:
    """A date's surrogate key in ``date_dim``."""
    return FIRST_DATE_SK + days(s) - days(FIRST_DATE)


def bkey(numbers) -> list[bytes]:
    """dsdgen's 16-character business keys: eight 'A's, then the number's
    eight low hex digits as the letters A-P, the lowest first."""
    n = np.asarray(numbers, dtype=np.int64)
    digits = (n[:, None] >> (4 * np.arange(8))) & 15
    chars = np.empty((len(n), 16), dtype=np.uint8)
    chars[:, :8] = ord("A")
    chars[:, 8:] = digits + ord("A")
    return chars.view("S16").ravel().tolist()


def spelled(numbers) -> list[bytes]:
    """A number's decimal digits as dsdgen's syllables."""
    return [b"".join(SYLLABLES[int(c)] for c in str(int(v)))
            for v in np.asarray(numbers).tolist()]


def _words(rng, size: int, lo: int, hi: int) -> list[bytes]:
    """``size`` distinct texts of ``lo`` to ``hi`` words of two to four
    syllables (11,100 words in all)."""
    out: dict[bytes, None] = {}
    while len(out) < size:
        m = size - len(out) + 64
        picks = rng.integers(0, len(SYLLABLES), (m, hi, 4)).tolist()
        sizes = rng.integers(2, 5, (m, hi)).tolist()
        lengths = rng.integers(lo, hi + 1, m).tolist()
        for row, size_row, k in zip(picks, sizes, lengths):
            out[b" ".join(b"".join(SYLLABLES[s] for s in w[:j])
                          for w, j in zip(row[:k], size_row))] = None
    return list(out)[:size]


class Data:
    """The store channel's eight tables as host numpy column dicts, with
    the string dictionaries the id columns index into."""

    widths = WIDTHS

    def __init__(self, scale_factor: float, seed: int,
                 fact_share: float = 0.125, zip_codes: int = ZIP_CODES):
        self.scale_factor = scale_factor
        self.dicts = Dicts()
        self.tables: dict[str, dict[str, np.ndarray]] = {}
        rng = np.random.default_rng(seed)
        n = {t: max(int(round(SF100_ROWS[t] * scale_factor / 100)),
                    FLOORS.get(t, 1)) for t in SF100_ROWS}
        n["date_dim"] = SF100_ROWS["date_dim"]
        n["customer_demographics"] = min(n["customer_demographics"],
                                         SF100_ROWS["customer_demographics"])
        n["store_sales"] = int(round(SF100_ROWS["store_sales"]
                                     * scale_factor / 100 * fact_share))
        self._n = n
        zips = rng.choice(np.arange(600, 100_000), zip_codes, replace=False)
        self._zips = [b"%05d" % z for z in zips.tolist()]
        self._places = {
            "street_name": _words(rng, 1000, 1, 2),
            "city": _words(rng, 1000, 1, 2),
            "county": [w + b" County" for w in _words(rng, 1800, 1, 1)],
            "state": STATES,
        }
        self._names = {"first": _words(rng, 4000, 1, 1),
                       "last": _words(rng, 5000, 1, 1)}
        for t in TABLES:
            self.tables[t] = getattr(self, "_" + t)(rng, n[t])
            assert list(self.tables[t]) == [c for c, _ in SCHEMAS[t]], t

    # -- the harness's view --

    def rows(self, table: str) -> int:
        return len(self.tables[table][SCHEMAS[table][0][0]])

    def schema(self, table: str):
        """``(column, sql type)`` pairs in the table's column order."""
        return SCHEMAS[table]

    def primary_key(self, table: str):
        return PRIMARY_KEYS[table]

    # -- columns --

    def _strings(self, col: str, values) -> np.ndarray:
        """The column's dictionary from a list of texts, one a row: each
        distinct text once, in order of first appearance."""
        uniq, first, inv = np.unique(np.asarray(values, dtype=object),
                                     return_index=True, return_inverse=True)
        order = np.argsort(first, kind="stable")
        rank = np.empty(len(order), dtype=np.int32)
        rank[order] = np.arange(len(order), dtype=np.int32)
        self.dicts[col] = [uniq[i] for i in order.tolist()]
        return rank[inv.ravel()]

    def _pick(self, rng, col: str, pool, n: int) -> np.ndarray:
        """``n`` ids drawn uniformly from ``pool``, a list of distinct
        texts that becomes the column's dictionary."""
        self.dicts[col] = list(pool)
        return rng.integers(0, len(pool), n, dtype=np.int32)

    def _const(self, col: str, value: bytes, n: int) -> np.ndarray:
        self.dicts[col] = [value]
        return np.zeros(n, dtype=np.int32)

    def _unique(self, col: str, values: list) -> np.ndarray:
        self.dicts[col] = values
        return np.arange(len(values), dtype=np.int32)

    @staticmethod
    def _sks(n: int) -> np.ndarray:
        return np.arange(1, n + 1, dtype=np.int64)

    # -- tables --

    def _date_dim(self, rng, n: int) -> dict:
        i = np.arange(n, dtype=np.int64)
        d = (days(FIRST_DATE) + i).astype("datetime64[D]")
        year = d.astype("datetime64[Y]").astype(np.int64) + 1970
        month0 = d.astype("datetime64[M]").astype(np.int64)
        moy = month0 % 12 + 1
        dom = (d - d.astype("datetime64[M]")).astype(np.int64) + 1
        dow = (days(FIRST_DATE) + i + 4) % 7          # 1970-01-01: Thursday
        sk = FIRST_DATE_SK + i
        next_month = (month0 + 1).astype("datetime64[M]").astype(
            "datetime64[D]").astype(np.int64)
        qoy = (moy - 1) // 3 + 1
        week_seq = (i + dow[0]) // 7 + 1
        quarter_seq = (year - 1900) * 4 + qoy
        holiday = (((moy == 1) & (dom == 1)) | ((moy == 7) & (dom == 4))
                   | ((moy == 12) & (dom == 25)))
        yn = (b"N", b"Y")
        t = {
            "d_date_sk": sk,
            "d_date_id": self._unique("d_date_id", bkey(sk)),
            "d_date": d.astype(np.int64).astype(np.int32),
            "d_month_seq": ((year - 1900) * 12 + moy - 1).astype(np.int32),
            "d_week_seq": week_seq.astype(np.int32),
            "d_quarter_seq": quarter_seq.astype(np.int32),
            "d_year": year.astype(np.int32),
            "d_dow": dow.astype(np.int32),
            "d_moy": moy.astype(np.int32),
            "d_dom": dom.astype(np.int32),
            "d_qoy": qoy.astype(np.int32),
            "d_fy_year": year.astype(np.int32),
            "d_fy_quarter_seq": quarter_seq.astype(np.int32),
            "d_fy_week_seq": week_seq.astype(np.int32),
        }
        self.dicts["d_day_name"] = list(DAY_NAMES)
        t["d_day_name"] = dow.astype(np.int32)
        t["d_quarter_name"] = self._strings("d_quarter_name", [
            b"%dQ%d" % (y, q) for y, q in zip(year.tolist(), qoy.tolist())])
        for col, flag in (("d_holiday", holiday),
                          ("d_weekend", (dow == 0) | (dow == 6)),
                          ("d_following_holiday", np.roll(holiday, 1))):
            self.dicts[col] = list(yn)
            t[col] = flag.astype(np.int32)
        t["d_first_dom"] = (sk - dom + 1).astype(np.int32)
        t["d_last_dom"] = (sk + next_month - d.astype(np.int64) - 1
                           ).astype(np.int32)
        t["d_same_day_ly"] = (sk - 365).astype(np.int32)
        t["d_same_day_lq"] = (sk - 91).astype(np.int32)
        for col in ("d_current_day", "d_current_week", "d_current_month",
                    "d_current_quarter", "d_current_year"):
            t[col] = self._const(col, b"N", n)
        return t

    def _item(self, rng, n: int) -> dict:
        sk = self._sks(n)
        rev = (sk - 1) % 2           # two revisions of each business key
        cat = rng.integers(0, len(CATEGORIES), n)
        cls = rng.integers(0, 16, n) % np.asarray(CLASSES)[cat]
        brand_n = rng.integers(1, BRANDS_PER_CLASS + 1, n)
        brand_id = (cat + 1) * 1_000_000 + (cls + 1) * 1_000 + brand_n
        manufact = rng.permutation(n) % MANUFACTS + 1
        price = rng.integers(9, 10_000, n, dtype=np.int64)
        t = {
            "i_item_sk": sk,
            "i_item_id": self._strings("i_item_id", bkey((sk - 1) // 2 + 1)),
            "i_rec_start_date": np.where(
                rev == 0, days("1997-10-27"), days("2000-10-27")
            ).astype(np.int32),
            "i_rec_end_date": np.where(
                rev == 0, days("2000-10-26"), days("2100-01-01")
            ).astype(np.int32),
            "i_item_desc": self._pick(rng, "i_item_desc",
                                      _words(rng, 1 << 14, 3, 12), n),
            "i_current_price": price,
            "i_wholesale_cost": price * rng.integers(20, 81, n) // 100,
            "i_brand_id": brand_id.astype(np.int32),
            "i_brand": self._strings("i_brand", [
                b"%s%s #%d" % (BRAND_WORDS[c], CLASS_WORDS[k], b)
                for c, k, b in zip(cat.tolist(), cls.tolist(),
                                   brand_n.tolist())]),
            "i_class_id": (cls + 1).astype(np.int32),
            "i_class": self._strings("i_class", [
                CLASS_WORDS[k] for k in cls.tolist()]),
            "i_category_id": (cat + 1).astype(np.int32),
            "i_category": self._strings("i_category", [
                CATEGORIES[c] for c in cat.tolist()]),
            "i_manufact_id": manufact.astype(np.int32),
            "i_manufact": self._strings("i_manufact", spelled(manufact)),
            "i_size": self._pick(rng, "i_size", (
                b"petite", b"small", b"medium", b"large", b"extra large",
                b"economy", b"N/A"), n),
            "i_formulation": self._pick(rng, "i_formulation",
                                        _words(rng, 4096, 2, 3), n),
            "i_color": self._pick(rng, "i_color", _words(rng, 92, 1, 1), n),
            "i_units": self._pick(rng, "i_units", _words(rng, 21, 1, 1), n),
            "i_container": self._const("i_container", b"Unknown", n),
            "i_manager_id": (rng.permutation(n) % MANAGERS + 1
                             ).astype(np.int32),
            "i_product_name": self._strings("i_product_name", spelled(sk)),
        }
        return t

    def _customer_demographics(self, rng, n: int) -> dict:
        i = np.arange(n, dtype=np.int64)
        digit = []
        for radix in DEMOGRAPHICS:
            digit.append((i % radix).astype(np.int32))
            i = i // radix
        gender, marital, edu, estimate, credit, dep, emp, college = digit
        t = {"cd_demo_sk": self._sks(n)}
        for col, pool, ids in (("cd_gender", GENDERS, gender),
                               ("cd_marital_status", MARITAL, marital),
                               ("cd_education_status", EDUCATION, edu)):
            self.dicts[col] = list(pool)
            t[col] = ids
        t["cd_purchase_estimate"] = (estimate + 1) * 500
        self.dicts["cd_credit_rating"] = list(CREDIT)
        t["cd_credit_rating"] = credit
        t["cd_dep_count"] = dep
        t["cd_dep_employed_count"] = emp
        t["cd_dep_college_count"] = college
        return t

    def _address(self, rng, prefix: str, n: int) -> dict:
        """The address columns customer_address and store share."""
        p = self._places
        return {
            prefix + "street_number": self._pick(
                rng, prefix + "street_number",
                [b"%d" % k for k in range(1, 1001)], n),
            prefix + "street_name": self._pick(
                rng, prefix + "street_name", p["street_name"], n),
            prefix + "street_type": self._pick(
                rng, prefix + "street_type", _words(rng, 20, 1, 1), n),
            prefix + "suite_number": self._pick(
                rng, prefix + "suite_number",
                [b"Suite %d" % k for k in range(0, 100)], n),
            prefix + "city": self._pick(rng, prefix + "city", p["city"], n),
            prefix + "county": self._pick(rng, prefix + "county",
                                          p["county"], n),
            prefix + "state": self._pick(rng, prefix + "state",
                                         p["state"], n),
            # numbered in order of first appearance, as a loader numbers
            # them: ca_zip's and s_zip's ids of one text differ
            prefix + "zip": self._strings(prefix + "zip", [
                self._zips[k] for k in rng.integers(
                    0, len(self._zips), n).tolist()]),
            prefix + "country": self._const(prefix + "country",
                                            b"United States", n),
            prefix + "gmt_offset": rng.integers(-10, -4, n) * 100,
        }

    def _customer_address(self, rng, n: int) -> dict:
        sk = self._sks(n)
        t = {"ca_address_sk": sk,
             "ca_address_id": self._unique("ca_address_id", bkey(sk))}
        t.update(self._address(rng, "ca_", n))
        t["ca_location_type"] = self._pick(
            rng, "ca_location_type",
            (b"apartment", b"condo", b"single family"), n)
        return t

    def _customer(self, rng, n: int) -> dict:
        sk = self._sks(n)
        first_names, last_names = self._names["first"], self._names["last"]
        first = self._pick(rng, "c_first_name", first_names, n)
        last = self._pick(rng, "c_last_name", last_names, n)
        return {
            "c_customer_sk": sk,
            "c_customer_id": self._unique("c_customer_id", bkey(sk)),
            "c_current_cdemo_sk": rng.integers(
                1, self._n["customer_demographics"] + 1, n, dtype=np.int64),
            "c_current_hdemo_sk": rng.integers(
                1, HOUSEHOLD_DEMOGRAPHICS + 1, n, dtype=np.int64),
            "c_current_addr_sk": rng.integers(
                1, self._n["customer_address"] + 1, n, dtype=np.int64),
            "c_first_shipto_date_sk": rng.integers(
                2_449_028, 2_452_679, n, dtype=np.int64),
            "c_first_sales_date_sk": rng.integers(
                2_449_028, 2_452_679, n, dtype=np.int64),
            "c_salutation": self._pick(rng, "c_salutation", (
                b"Mr.", b"Mrs.", b"Ms.", b"Dr.", b"Miss", b"Sir"), n),
            "c_first_name": first,
            "c_last_name": last,
            "c_preferred_cust_flag": self._pick(
                rng, "c_preferred_cust_flag", (b"N", b"Y"), n),
            "c_birth_day": rng.integers(1, 29, n, dtype=np.int32),
            "c_birth_month": rng.integers(1, 13, n, dtype=np.int32),
            "c_birth_year": rng.integers(1924, 1993, n, dtype=np.int32),
            "c_birth_country": self._pick(
                rng, "c_birth_country",
                [w.upper() for w in _words(rng, 211, 1, 2)], n),
            "c_login": self._const("c_login", b"", n),
            "c_email_address": self._pick(rng, "c_email_address", [
                b"%s.%s@%s.com" % (first_names[i % len(first_names)],
                                   last_names[i % len(last_names)],
                                   MAIL_DOMAINS[i // 20_000])
                for i in range(EMAILS)], n),
            "c_last_review_date_sk": rng.integers(
                2_452_283, 2_452_649, n, dtype=np.int64),
        }

    def _store(self, rng, n: int) -> dict:
        sk = self._sks(n)
        rev = (sk - 1) % 2
        managers = [b"%s %s" % (a, b) for a, b in zip(
            self._names["first"][:64], self._names["last"][:64])]
        t = {
            "s_store_sk": sk,
            "s_store_id": self._strings("s_store_id", bkey((sk - 1) // 2 + 1)),
            "s_rec_start_date": np.where(
                rev == 0, days("1997-03-13"), days("2000-03-13")
            ).astype(np.int32),
            "s_rec_end_date": np.where(
                rev == 0, days("2000-03-12"), days("2100-01-01")
            ).astype(np.int32),
            "s_closed_date_sk": rng.integers(
                date_sk("1998-01-01"), date_sk("2003-01-01"), n,
                dtype=np.int64),
            "s_store_name": self._strings("s_store_name", [
                SYLLABLES[k % len(SYLLABLES)] for k in sk.tolist()]),
            "s_number_employees": rng.integers(200, 301, n, dtype=np.int32),
            "s_floor_space": rng.integers(5_000_000, 10_000_001, n,
                                          dtype=np.int32),
            "s_hours": self._pick(rng, "s_hours", (
                b"8AM-4PM", b"8AM-8PM", b"8AM-12AM"), n),
            "s_manager": self._pick(rng, "s_manager", managers, n),
            "s_market_id": rng.integers(1, 11, n, dtype=np.int32),
            "s_geography_class": self._const("s_geography_class",
                                             b"Unknown", n),
            "s_market_desc": self._pick(rng, "s_market_desc",
                                        _words(rng, 64, 4, 12), n),
            "s_market_manager": self._pick(rng, "s_market_manager",
                                           managers, n),
            "s_division_id": np.ones(n, dtype=np.int32),
            "s_division_name": self._const("s_division_name", b"Unknown", n),
            "s_company_id": np.ones(n, dtype=np.int32),
            "s_company_name": self._const("s_company_name", b"Unknown", n),
        }
        t.update(self._address(rng, "s_", n))
        t["s_tax_percentage"] = rng.integers(0, 12, n, dtype=np.int64)
        return t

    def _promotion(self, rng, n: int) -> dict:
        sk = self._sks(n)
        start = rng.integers(date_sk("1998-01-02"), date_sk("2003-01-02"),
                             n, dtype=np.int64)
        t = {
            "p_promo_sk": sk,
            "p_promo_id": self._unique("p_promo_id", bkey(sk)),
            "p_start_date_sk": start,
            "p_end_date_sk": start + rng.integers(1, 61, n),
            "p_item_sk": rng.integers(1, self._n["item"] + 1, n,
                                      dtype=np.int64),
            "p_cost": np.full(n, 100_000, dtype=np.int64),
            "p_response_target": np.ones(n, dtype=np.int32),
            "p_promo_name": self._strings("p_promo_name", [
                SYLLABLES[k % len(SYLLABLES)] for k in sk.tolist()]),
        }
        for ch in ("dmail", "email", "catalog", "tv", "radio", "press",
                   "event", "demo"):
            t["p_channel_" + ch] = self._pick(rng, "p_channel_" + ch,
                                              (b"N", b"Y"), n)
        t["p_channel_details"] = self._pick(rng, "p_channel_details",
                                            _words(rng, 64, 4, 12), n)
        t["p_purpose"] = self._const("p_purpose", b"Unknown", n)
        t["p_discount_active"] = self._const("p_discount_active", b"N", n)
        return t

    def _store_sales(self, rng, n: int) -> dict:
        n_item = self._n["item"]
        lo, hi = LINES_PER_TICKET
        counts = rng.integers(lo, hi + 1, n // lo + 1)
        ends = np.cumsum(counts)
        tickets = int(np.searchsorted(ends, n)) + 1
        counts = counts[:tickets]
        counts[-1] -= int(ends[tickets - 1]) - n     # exactly n lines
        ticket = np.repeat(np.arange(tickets, dtype=np.int64), counts)
        line = np.arange(n, dtype=np.int64) - np.repeat(
            np.cumsum(counts) - counts, counts)
        # a ticket's lines are distinct items: an arithmetic progression
        # modulo the item count, of a step prime to it
        steps = np.arange(1, n_item, dtype=np.int64)
        steps = steps[np.gcd(steps, n_item) == 1]
        base = rng.integers(0, n_item, tickets)
        step = steps[rng.integers(0, len(steps), tickets)]
        item = (base[ticket] + line * step[ticket]) % n_item + 1
        # the rows in the primary key's order (ss_item_sk, ticket)
        order = np.argsort(item * (tickets + 1) + ticket, kind="stable")
        item, ticket = item[order], ticket[order]
        del order, line

        def per_ticket(low, high):
            return rng.integers(low, high, tickets, dtype=np.int64)[ticket]

        t = {
            "ss_sold_date_sk": per_ticket(date_sk(SALES_FIRST),
                                          date_sk(SALES_LAST) + 1),
            "ss_sold_time_sk": per_ticket(0, TIME_DIM),
            "ss_item_sk": item,
            "ss_customer_sk": per_ticket(1, self._n["customer"] + 1),
            "ss_cdemo_sk": per_ticket(
                1, self._n["customer_demographics"] + 1),
            "ss_hdemo_sk": per_ticket(1, HOUSEHOLD_DEMOGRAPHICS + 1),
            "ss_addr_sk": per_ticket(1, self._n["customer_address"] + 1),
            "ss_store_sk": per_ticket(1, self._n["store"] + 1),
            "ss_promo_sk": per_ticket(1, self._n["promotion"] + 1),
            "ss_ticket_number": ticket + 1,
        }
        # dsdgen's pricing, in cents and whole percents: wholesale cost,
        # a markup of 0-200% to the list price, a discount of 0-99% to
        # the sales price; the extended amounts are the quantity's. No
        # price and no coupon is 0: every line's coupon is 0-100% of its
        # extended sales price and at least a cent, where dsdgen gives
        # most lines none, because the harness's comparison reads a zero
        # average against a zero reference as an infinite gap
        # (compare.py) and q7 averages the coupons of groups of a line
        qty = rng.integers(1, 101, n, dtype=np.int64)
        wholesale = rng.integers(100, 10_001, n, dtype=np.int64)
        price = wholesale * (100 + rng.integers(0, 201, n)) // 100
        sales = price * (100 - rng.integers(0, 100, n)) // 100
        ext_sales = qty * sales
        ext_wholesale = qty * wholesale
        coupon = np.maximum(ext_sales * rng.integers(0, 101, n) // 100, 1)
        tax = ext_sales * rng.integers(0, 10, n) // 100
        net_paid = ext_sales - coupon
        t.update({
            "ss_quantity": qty.astype(np.int32),
            "ss_wholesale_cost": wholesale,
            "ss_list_price": price,
            "ss_sales_price": sales,
            "ss_ext_discount_amt": qty * (price - sales),
            "ss_ext_sales_price": ext_sales,
            "ss_ext_wholesale_cost": ext_wholesale,
            "ss_ext_list_price": qty * price,
            "ss_ext_tax": tax,
            "ss_coupon_amt": coupon,
            "ss_net_paid": net_paid,
            "ss_net_paid_inc_tax": net_paid + tax,
            "ss_net_profit": net_paid - ext_wholesale,
        })
        return t


def make(scale_factor: float, seed: int, **options) -> Data:
    """The harness's entry: every generator module has this function;
    ``options`` are the configuration's ``generator_options``
    (``fact_share``, ``zip_codes``)."""
    return Data(scale_factor, seed, **options)
