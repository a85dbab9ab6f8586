"""TPC-H data from a seed: the benchmark's own copy of the generator.

Copied from ``ydb_tpu/workload/tpch.py`` (``TpchData``) so that a later
PR can change the program and never the yardstick, and brought up to the
tables of the specification's section 1.4: all 61 columns (the program's
generator leaves out ``l_comment``, ``o_clerk``, ``p_comment``,
``ps_comment``, ``n_comment`` and ``r_comment``), ``p_name`` of five
words, and every pooled text at the specification's length. It imports
nothing of the program: tables are dicts of numpy columns, string
columns are int32 ids into ``Dicts`` (insertion order, as the cluster's
own dictionaries number them when seeded value by value), and a schema
is a list of ``(name, sql type)``.

Row widths, key ranges and value domains follow section 1.4 and
dbgen's distributions (uniform approximations). Keys are dense. Dates
are int32 days since the epoch; money and quantity columns are
decimal(15, 2) held as scaled int64. One departure stays, and every
configuration lists it under ``assumed``: free text (comments,
addresses) is drawn from bounded pools (``POOLS``), where dbgen's is
nearly distinct from row to row; the program holds a string column as a
4-byte dictionary id on the device and its texts in a dictionary on the
host, so the pools bound the host dictionaries and change no width on
the device.

``make`` takes two options from a configuration's ``generator_options``
(the original has neither). They make every seed give the same sizes, as
dbgen's one database per scale factor does: ``lines_per_order`` moves
the number of lineitem rows to exactly that many an order, and
``shipped_by`` (a date) moves the number of lineitem rows shipped by
that date to its expectation. Without them the counts wander by
thousands of rows from seed to seed.
"""

from __future__ import annotations

import numpy as np

#: sql type -> bytes per value as the engine holds it on the device
WIDTHS = {"int64": 8, "int32": 4, "decimal(15, 2)": 8, "string": 4,
          "date": 4}

SCHEMAS = {
    "lineitem": (
        ("l_orderkey", "int64"), ("l_partkey", "int64"),
        ("l_suppkey", "int64"), ("l_linenumber", "int32"),
        ("l_quantity", "decimal(15, 2)"),
        ("l_extendedprice", "decimal(15, 2)"),
        ("l_discount", "decimal(15, 2)"), ("l_tax", "decimal(15, 2)"),
        ("l_returnflag", "string"), ("l_linestatus", "string"),
        ("l_shipdate", "date"), ("l_commitdate", "date"),
        ("l_receiptdate", "date"), ("l_shipinstruct", "string"),
        ("l_shipmode", "string"), ("l_comment", "string")),
    "orders": (
        ("o_orderkey", "int64"), ("o_custkey", "int64"),
        ("o_orderstatus", "string"), ("o_totalprice", "decimal(15, 2)"),
        ("o_orderdate", "date"), ("o_orderpriority", "string"),
        ("o_clerk", "string"), ("o_shippriority", "int32"),
        ("o_comment", "string")),
    "customer": (
        ("c_custkey", "int64"), ("c_name", "string"),
        ("c_address", "string"), ("c_nationkey", "int32"),
        ("c_phone", "string"), ("c_acctbal", "decimal(15, 2)"),
        ("c_mktsegment", "string"), ("c_comment", "string")),
    "supplier": (
        ("s_suppkey", "int64"), ("s_name", "string"),
        ("s_address", "string"), ("s_nationkey", "int32"),
        ("s_phone", "string"), ("s_acctbal", "decimal(15, 2)"),
        ("s_comment", "string")),
    "part": (
        ("p_partkey", "int64"), ("p_name", "string"),
        ("p_mfgr", "string"), ("p_brand", "string"),
        ("p_type", "string"), ("p_size", "int32"),
        ("p_container", "string"), ("p_retailprice", "decimal(15, 2)"),
        ("p_comment", "string")),
    "partsupp": (
        ("ps_partkey", "int64"), ("ps_suppkey", "int64"),
        ("ps_availqty", "int32"), ("ps_supplycost", "decimal(15, 2)"),
        ("ps_comment", "string")),
    "nation": (
        ("n_nationkey", "int32"), ("n_name", "string"),
        ("n_regionkey", "int32"), ("n_comment", "string")),
    "region": (("r_regionkey", "int32"), ("r_name", "string"),
               ("r_comment", "string")),
}

PRIMARY_KEYS = {
    "lineitem": ("l_orderkey", "l_linenumber"),
    "orders": ("o_orderkey",),
    "customer": ("c_custkey",),
    "supplier": ("s_suppkey",),
    "part": ("p_partkey",),
    "partsupp": ("ps_partkey", "ps_suppkey"),
    "nation": ("n_nationkey",),
    "region": ("r_regionkey",),
}

#: load order: small tables first, so a fault shows before the long load
TABLES = ("region", "nation", "supplier", "customer", "part", "partsupp",
          "orders", "lineitem")


def days(s: str) -> int:
    """A date as the int32 the date columns hold."""
    return int(np.datetime64(s, "D").astype(np.int64))


class Dict:
    """One string column's dictionary: value -> id in insertion order."""

    def __init__(self):
        self.values: list[bytes] = []
        self._ids: dict[bytes, int] = {}

    def add(self, v: bytes) -> int:
        i = self._ids.get(v)
        if i is None:
            i = self._ids[v] = len(self.values)
            self.values.append(v)
        return i

    def get(self, v: bytes) -> int | None:
        return self._ids.get(v)

    def __len__(self) -> int:
        return len(self.values)


class Dicts:
    """The dictionaries of all string columns, by column name."""

    def __init__(self):
        self._by_column: dict[str, Dict] = {}

    def for_column(self, col: str) -> Dict:
        return self._by_column.setdefault(col, Dict())

    def columns(self) -> list[str]:
        return list(self._by_column)

    def __getitem__(self, col: str) -> Dict:
        return self._by_column[col]


NATIONS = [
    b"ALGERIA", b"ARGENTINA", b"BRAZIL", b"CANADA", b"EGYPT", b"ETHIOPIA",
    b"FRANCE", b"GERMANY", b"INDIA", b"INDONESIA", b"IRAN", b"IRAQ",
    b"JAPAN", b"JORDAN", b"KENYA", b"MOROCCO", b"MOZAMBIQUE", b"PERU",
    b"CHINA", b"ROMANIA", b"SAUDI ARABIA", b"VIETNAM", b"RUSSIA",
    b"UNITED KINGDOM", b"UNITED STATES",
]
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                 3, 4, 2, 3, 3, 1]
REGIONS = [b"AFRICA", b"AMERICA", b"ASIA", b"EUROPE", b"MIDDLE EAST"]
SEGMENTS = [b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"MACHINERY",
            b"HOUSEHOLD"]
SHIPMODES = [b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"]
INSTRUCTS = [b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
             b"TAKE BACK RETURN"]
PRIORITIES = [b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW"]

# dbgen text grammar stand-ins: bounded pools keep dictionary sizes (and
# plan-time LIKE-mask evaluation) independent of SF while preserving the
# patterns the TPC-H predicates probe for (p_name '%green%', o_comment
# '%special%requests%', s_comment '%Customer%Complaints%', p_type
# '%BRASS' / 'PROMO%', ...). Reference grammar: dbgen dists.dss via
# ydb/library/workload/tpch_workload.cpp data generators.
COLORS = [
    b"almond", b"antique", b"aquamarine", b"azure", b"beige", b"bisque",
    b"black", b"blanched", b"blue", b"blush", b"brown", b"burlywood",
    b"burnished", b"chartreuse", b"chiffon", b"chocolate", b"coral",
    b"cornflower", b"cornsilk", b"cream", b"cyan", b"dark", b"deep",
    b"dim", b"dodger", b"drab", b"firebrick", b"floral", b"forest",
    b"frosted", b"gainsboro", b"ghost", b"goldenrod", b"green", b"grey",
    b"honeydew", b"hot", b"indian", b"ivory", b"khaki", b"lace",
    b"lavender", b"lawn", b"lemon", b"light", b"lime", b"linen",
    b"magenta", b"maroon", b"medium", b"metallic", b"midnight", b"mint",
    b"misty", b"moccasin", b"navajo", b"navy", b"olive", b"orange",
    b"orchid", b"pale", b"papaya", b"peach", b"peru", b"pink", b"plum",
    b"powder", b"puff", b"purple", b"red", b"rose", b"rosy", b"royal",
    b"saddle", b"salmon", b"sandy", b"seashell", b"sienna", b"sky",
    b"slate", b"smoke", b"snow", b"spring", b"steel", b"tan", b"thistle",
    b"tomato", b"turquoise", b"violet", b"wheat", b"white", b"yellow",
]
TYPE_SYL1 = [b"STANDARD", b"SMALL", b"MEDIUM", b"LARGE", b"ECONOMY",
             b"PROMO"]
TYPE_SYL2 = [b"ANODIZED", b"BURNISHED", b"PLATED", b"POLISHED", b"BRUSHED"]
TYPE_SYL3 = [b"TIN", b"NICKEL", b"BRASS", b"STEEL", b"COPPER"]
CONTAINER_SYL1 = [b"SM", b"LG", b"MED", b"JUMBO", b"WRAP"]
CONTAINER_SYL2 = [b"CASE", b"BOX", b"BAG", b"JAR", b"PKG", b"PACK", b"CAN",
                  b"DRUM"]
COMMENT_WORDS = [
    b"furiously", b"carefully", b"quickly", b"blithely", b"slyly",
    b"express", b"regular", b"final", b"ironic", b"pending", b"bold",
    b"unusual", b"even", b"special", b"silent", b"daring", b"requests",
    b"accounts", b"packages", b"deposits", b"instructions", b"theodolites",
    b"dependencies", b"excuses", b"platelets", b"asymptotes", b"somas",
    b"dugouts", b"sleep", b"nag", b"haggle", b"wake", b"cajole", b"detect",
    b"integrate", b"Customer", b"Complaints", b"above", b"against",
    b"along",
]


#: free text comes from bounded pools: column -> (pool size, shortest,
#: longest text in characters; the lengths are section 1.4's, 0.4 to 1.6
#: times the column's average). Every configuration lists these sizes
#: under ``assumed``.
POOLS = {
    "l_comment": (4096, 10, 43),
    "o_comment": (2048, 19, 78),
    "c_comment": (1024, 29, 116),
    "s_comment": (512, 25, 100),
    "p_comment": (1024, 5, 22),
    "ps_comment": (2048, 49, 198),
    "c_address": (512, 10, 40),
    "s_address": (256, 10, 40),
}
#: nation and region comments: a text of their own to each row
SMALL_COMMENT = (31, 114)
MAX_SHIP_DELAY = 121


def _pin_sum(a: np.ndarray, total: int, lo: int, hi: int, rng) -> None:
    """Moves ``a``'s sum to ``total`` by one step on as many randomly
    chosen entries as it is off by, keeping every entry in [lo, hi]."""
    d = total - int(a.sum())
    room = np.flatnonzero(a < hi if d > 0 else a > lo)
    pick = rng.choice(room, size=min(abs(d), len(room)), replace=False)
    a[pick] += 1 if d > 0 else -1


def _pin_shipped_by(delay: np.ndarray, orderdate: np.ndarray, cutoff: int,
                    first: int, last: int, rng) -> None:
    """The rows with orderdate + delay <= cutoff become exactly their
    expected number (order dates uniform on [first, last], delays
    uniform on [1, MAX_SHIP_DELAY]), by drawing anew the delay of as
    many randomly chosen rows as the count is off by, each on the other
    side of the cutoff."""
    room = np.clip(cutoff - np.arange(first, last + 1), 0, MAX_SHIP_DELAY)
    target = int(round(len(delay) * room.mean() / MAX_SHIP_DELAY))
    slack = cutoff - orderdate          # the largest delay that ships by
    by = delay <= slack
    d = target - int(by.sum())
    if d > 0:       # too few: pull late rows in, where a day fits
        room = np.flatnonzero(~by & (slack >= 1))
        pick = rng.choice(room, size=min(d, len(room)), replace=False)
        delay[pick] = rng.integers(1, slack[pick] + 1)
    elif d < 0:     # too many: push rows out, where the window reaches
        room = np.flatnonzero(by & (slack < MAX_SHIP_DELAY))
        pick = rng.choice(room, size=min(-d, len(room)), replace=False)
        delay[pick] = rng.integers(slack[pick] + 1, MAX_SHIP_DELAY + 1)


def _register(dicts: Dicts, col: str, values) -> np.ndarray:
    d = dicts.for_column(col)
    return np.fromiter((d.add(v) for v in values), dtype=np.int32,
                       count=len(values))


def _encode_pool(dicts: Dicts, col: str, pool: list[bytes],
                 picks: np.ndarray) -> np.ndarray:
    """Register the pool once, map pick indices."""
    ids = _register(dicts, col, pool)
    return ids[picks]


def _text(rng, lo: int, hi: int) -> bytes:
    """One pseudo-dbgen text (word-chain grammar) of lo..hi characters."""
    n = int(rng.integers(lo, hi + 1))
    words = [COMMENT_WORDS[i] for i in rng.integers(
        0, len(COMMENT_WORDS), n // 4 + 2)]
    return b" ".join(words)[:n].rstrip().ljust(lo, b".")


def _with_chain(chain: bytes, text: bytes, longest: int) -> bytes:
    """``text`` led by the word chain a LIKE pattern probes for, within
    the column's length."""
    return (chain + b" " + text)[:longest].rstrip()


def _distinct_texts(rng, size: int, lo: int, hi: int) -> list[bytes]:
    pool: dict[bytes, None] = {}
    while len(pool) < size:
        pool[_text(rng, lo, hi)] = None
    return list(pool)


def _make_text_pool(rng, col: str) -> list[bytes]:
    """The column's bounded pool, distinct texts of its lengths."""
    return _distinct_texts(rng, *POOLS[col])


def _pooled(dicts: Dicts, rng, col: str, n: int) -> np.ndarray:
    """``n`` picks from the column's pool, as dictionary ids."""
    pool = _make_text_pool(rng, col)
    return _encode_pool(dicts, col, pool, rng.integers(0, len(pool), n))


def _encode_values(dicts: Dicts, col: str, values) -> np.ndarray:
    """Encode a large, mostly distinct value list: register each
    distinct value once, then map by index."""
    arr = np.asarray(values, dtype=object)
    uniq, inv = np.unique(arr, return_inverse=True)
    ids = _register(dicts, col, list(uniq))
    return ids[inv].astype(np.int32)


class Data:
    """Generated tables as host numpy column dicts, with the string
    dictionaries the id columns index into."""

    def __init__(self, sf: float, seed: int, lines_per_order=None,
                 shipped_by=None):
        self.sf = sf
        self.lines_per_order = lines_per_order
        self.shipped_by = shipped_by
        self.dicts = Dicts()
        rng = np.random.default_rng(seed)
        self.tables: dict[str, dict[str, np.ndarray]] = {}
        self._gen_orders_lineitem(rng)
        self._gen_customer(rng)
        self._gen_supplier(rng)
        self._gen_part_partsupp(rng)
        self._gen_nation_region(rng)

    # dbgen cardinalities: orders = 1.5M * SF; lineitem ~ 4 lines/order
    def _gen_orders_lineitem(self, rng):
        n_orders = int(1_500_000 * self.sf)
        n_cust = max(int(150_000 * self.sf), 1)
        start = days("1992-01-01")
        end = days("1998-08-02")
        o_orderkey = np.arange(1, n_orders + 1, dtype=np.int64)
        o_orderdate = rng.integers(start, end + 1, n_orders, dtype=np.int32)
        o_custkey = rng.integers(1, n_cust + 1, n_orders, dtype=np.int64)
        lines_per_order = rng.integers(1, 8, n_orders, dtype=np.int32)
        if self.lines_per_order is not None:
            _pin_sum(lines_per_order,
                     int(round(self.lines_per_order * n_orders)), 1, 7, rng)
        n_li = int(lines_per_order.sum())

        li_order_idx = np.repeat(np.arange(n_orders), lines_per_order)
        l_orderkey = o_orderkey[li_order_idx]
        l_linenumber = (
            np.arange(n_li, dtype=np.int64)
            - np.repeat(
                np.cumsum(lines_per_order) - lines_per_order, lines_per_order
            )
            + 1
        ).astype(np.int32)
        n_part = max(int(200_000 * self.sf), 1)
        n_supp = max(int(10_000 * self.sf), 1)
        l_partkey = rng.integers(1, n_part + 1, n_li, dtype=np.int64)
        l_suppkey = rng.integers(1, n_supp + 1, n_li, dtype=np.int64)
        l_quantity = rng.integers(1, 51, n_li, dtype=np.int64) * 100
        # dbgen: extendedprice = qty * part retail price (~90k-110k cents)
        part_price = rng.integers(90_000, 110_001, n_li, dtype=np.int64)
        l_extendedprice = (l_quantity // 100) * part_price // 100 * 100
        l_discount = rng.integers(0, 11, n_li, dtype=np.int64)  # 0.00-0.10
        l_tax = rng.integers(0, 9, n_li, dtype=np.int64)        # 0.00-0.08
        ship_delay = rng.integers(1, 122, n_li, dtype=np.int32)
        if self.shipped_by is not None:
            _pin_shipped_by(ship_delay, o_orderdate[li_order_idx],
                            days(self.shipped_by), start, end, rng)
        l_shipdate = o_orderdate[li_order_idx] + ship_delay
        l_commitdate = o_orderdate[li_order_idx] + rng.integers(
            30, 91, n_li, dtype=np.int32)
        l_receiptdate = l_shipdate + rng.integers(1, 31, n_li, dtype=np.int32)

        today = days("1995-06-17")
        shipped = l_shipdate <= today
        # returnflag: R or A for shipped-long-ago (50/50), N otherwise
        ret = np.where(
            l_receiptdate > today,
            2,  # N
            rng.integers(0, 2, n_li),  # 0=R 1=A
        )
        rf_dict = self.dicts.for_column("l_returnflag")
        ids = np.array([rf_dict.add(b"R"), rf_dict.add(b"A"),
                        rf_dict.add(b"N")], dtype=np.int32)
        l_returnflag = ids[ret]
        ls_dict = self.dicts.for_column("l_linestatus")
        ls_ids = np.array([ls_dict.add(b"O"), ls_dict.add(b"F")],
                          dtype=np.int32)
        l_linestatus = ls_ids[shipped.astype(np.int32)]
        sm = rng.integers(0, len(SHIPMODES), n_li)
        si = rng.integers(0, len(INSTRUCTS), n_li)
        smd = self.dicts.for_column("l_shipmode")
        sm_ids = np.array([smd.add(v) for v in SHIPMODES], dtype=np.int32)
        sid = self.dicts.for_column("l_shipinstruct")
        si_ids = np.array([sid.add(v) for v in INSTRUCTS], dtype=np.int32)

        self.tables["lineitem"] = {
            "l_orderkey": l_orderkey,
            "l_partkey": l_partkey,
            "l_suppkey": l_suppkey,
            "l_linenumber": l_linenumber,
            "l_quantity": l_quantity,
            "l_extendedprice": l_extendedprice,
            "l_discount": l_discount,
            "l_tax": l_tax,
            "l_returnflag": l_returnflag,
            "l_linestatus": l_linestatus,
            "l_shipdate": l_shipdate.astype(np.int32),
            "l_commitdate": l_commitdate.astype(np.int32),
            "l_receiptdate": l_receiptdate.astype(np.int32),
            "l_shipinstruct": si_ids[si],
            "l_shipmode": sm_ids[sm],
            "l_comment": _pooled(self.dicts, rng, "l_comment", n_li),
        }
        pr = rng.integers(0, len(PRIORITIES), n_orders)
        prd = self.dicts.for_column("o_orderpriority")
        pr_ids = np.array([prd.add(v) for v in PRIORITIES], dtype=np.int32)
        osd = self.dicts.for_column("o_orderstatus")
        os_ids = np.array([osd.add(b"O"), osd.add(b"F"), osd.add(b"P")],
                          dtype=np.int32)
        status = rng.integers(0, 3, n_orders)
        # o_comment pool: ~2% of entries carry the q13 'special…requests'
        # chain, the rest are plain word chains
        pool = _make_text_pool(rng, "o_comment")
        for i in range(0, len(pool), 50):
            pool[i] = _with_chain(b"special handling requests", pool[i],
                                  POOLS["o_comment"][2])
        # dbgen: Clerk#<9 digits>, a key in [1, SF * 1000]
        n_clerk = max(int(1000 * self.sf), 1)
        self.tables["orders"] = {
            "o_orderkey": o_orderkey,
            "o_custkey": o_custkey,
            "o_orderstatus": os_ids[status],
            "o_totalprice": rng.integers(
                100_00, 500_000_00, n_orders, dtype=np.int64),
            "o_orderdate": o_orderdate,
            "o_orderpriority": pr_ids[pr],
            "o_clerk": _encode_pool(
                self.dicts, "o_clerk",
                [b"Clerk#%09d" % k for k in range(1, n_clerk + 1)],
                rng.integers(0, n_clerk, n_orders)),
            "o_shippriority": np.zeros(n_orders, dtype=np.int32),
            "o_comment": _encode_pool(
                self.dicts, "o_comment", pool,
                rng.integers(0, len(pool), n_orders)),
        }

    @staticmethod
    def _phones(rng, nationkey: np.ndarray) -> list[bytes]:
        """dbgen phone format: 'CC-xxx-xxx-xxxx', CC = 10 + nationkey
        (q22 reads substring(c_phone, 1, 2) as the country code)."""
        digits = rng.integers(0, 10, (len(nationkey), 10))
        return [
            b"%d-%d%d%d-%d%d%d-%d%d%d%d" % ((10 + int(nk),) + tuple(d))
            for nk, d in zip(nationkey, digits)
        ]

    def _gen_customer(self, rng):
        n = max(int(150_000 * self.sf), 1)
        seg = rng.integers(0, len(SEGMENTS), n)
        sd = self.dicts.for_column("c_mktsegment")
        seg_ids = np.array([sd.add(v) for v in SEGMENTS], dtype=np.int32)
        nationkey = rng.integers(0, 25, n, dtype=np.int32)
        self.tables["customer"] = {
            "c_custkey": np.arange(1, n + 1, dtype=np.int64),
            "c_name": _encode_values(
                self.dicts, "c_name",
                [b"Customer#%09d" % k for k in range(1, n + 1)]),
            "c_address": _pooled(self.dicts, rng, "c_address", n),
            "c_nationkey": nationkey,
            "c_phone": _encode_values(
                self.dicts, "c_phone", self._phones(rng, nationkey)),
            "c_acctbal": rng.integers(-999_99, 9999_99, n, dtype=np.int64),
            "c_mktsegment": seg_ids[seg],
            "c_comment": _pooled(self.dicts, rng, "c_comment", n),
        }

    def _gen_supplier(self, rng):
        n = max(int(10_000 * self.sf), 1)
        nationkey = rng.integers(0, 25, n, dtype=np.int32)
        # ~1.6% of suppliers carry the q16 'Customer Complaints' chain
        comment_pool = _make_text_pool(rng, "s_comment")
        for i in range(0, len(comment_pool), 64):
            comment_pool[i] = _with_chain(b"Customer loud Complaints",
                                          comment_pool[i],
                                          POOLS["s_comment"][2])
        self.tables["supplier"] = {
            "s_suppkey": np.arange(1, n + 1, dtype=np.int64),
            "s_name": _encode_values(
                self.dicts, "s_name",
                [b"Supplier#%09d" % k for k in range(1, n + 1)]),
            "s_address": _pooled(self.dicts, rng, "s_address", n),
            "s_nationkey": nationkey,
            "s_phone": _encode_values(
                self.dicts, "s_phone", self._phones(rng, nationkey)),
            "s_acctbal": rng.integers(-999_99, 9999_99, n, dtype=np.int64),
            "s_comment": _encode_pool(
                self.dicts, "s_comment", comment_pool,
                rng.integers(0, len(comment_pool), n)),
        }

    def _gen_part_partsupp(self, rng):
        n = max(int(200_000 * self.sf), 1)
        # p_name: five of dbgen's 92 colour words
        picks = rng.integers(0, len(COLORS), (n, 5))
        names = [b" ".join([COLORS[i] for i in row]) for row in picks]
        mfgr = rng.integers(1, 6, n)
        brand = mfgr * 10 + rng.integers(1, 6, n)
        t1 = rng.integers(0, len(TYPE_SYL1), n)
        t2 = rng.integers(0, len(TYPE_SYL2), n)
        t3 = rng.integers(0, len(TYPE_SYL3), n)
        types = [b" ".join((TYPE_SYL1[a], TYPE_SYL2[b], TYPE_SYL3[c]))
                 for a, b, c in zip(t1, t2, t3)]
        c1 = rng.integers(0, len(CONTAINER_SYL1), n)
        c2 = rng.integers(0, len(CONTAINER_SYL2), n)
        containers = [b" ".join((CONTAINER_SYL1[a], CONTAINER_SYL2[b]))
                      for a, b in zip(c1, c2)]
        self.tables["part"] = {
            "p_partkey": np.arange(1, n + 1, dtype=np.int64),
            "p_name": _encode_values(self.dicts, "p_name", names),
            "p_mfgr": _encode_values(
                self.dicts, "p_mfgr",
                [b"Manufacturer#%d" % m for m in mfgr]),
            "p_brand": _encode_values(
                self.dicts, "p_brand", [b"Brand#%d" % b for b in brand]),
            "p_type": _encode_values(self.dicts, "p_type", types),
            "p_size": rng.integers(1, 51, n, dtype=np.int32),
            "p_container": _encode_values(
                self.dicts, "p_container", containers),
            "p_retailprice": (90_000 + (np.arange(1, n + 1) % 20_001)
                              ).astype(np.int64),
            "p_comment": _pooled(self.dicts, rng, "p_comment", n),
        }
        # partsupp: each part has 4 suppliers (dbgen), pk (partkey, suppkey)
        n_supp = max(int(10_000 * self.sf), 1)
        ps_partkey = np.repeat(np.arange(1, n + 1, dtype=np.int64), 4)
        ps_suppkey = (
            (ps_partkey + np.tile(np.arange(4, dtype=np.int64), n)
             * max(n_supp // 4, 1)) % n_supp + 1
        )
        m = len(ps_partkey)
        self.tables["partsupp"] = {
            "ps_partkey": ps_partkey,
            "ps_suppkey": ps_suppkey,
            "ps_availqty": rng.integers(1, 10_000, m, dtype=np.int32),
            "ps_supplycost": rng.integers(100, 1000_00, m, dtype=np.int64),
            "ps_comment": _pooled(self.dicts, rng, "ps_comment", m),
        }

    def _gen_nation_region(self, rng):
        def comments(n):
            return _distinct_texts(rng, n, *SMALL_COMMENT)

        self.tables["nation"] = {
            "n_nationkey": np.arange(25, dtype=np.int32),
            "n_name": _register(self.dicts, "n_name", NATIONS),
            "n_regionkey": np.array(NATION_REGION, dtype=np.int32),
            "n_comment": _register(self.dicts, "n_comment", comments(25)),
        }
        self.tables["region"] = {
            "r_regionkey": np.arange(5, dtype=np.int32),
            "r_name": _register(self.dicts, "r_name", REGIONS),
            "r_comment": _register(self.dicts, "r_comment", comments(5)),
        }

    widths = WIDTHS

    def rows(self, table: str) -> int:
        return len(next(iter(self.tables[table].values())))

    def schema(self, table: str):
        """``(column, sql type)`` pairs in the table's column order."""
        return SCHEMAS[table]

    def primary_key(self, table: str):
        return PRIMARY_KEYS[table]


def make(scale_factor: float, seed: int, **options) -> Data:
    """The harness's entry: every generator module has this function;
    ``options`` are the configuration's ``generator_options``."""
    return Data(scale_factor, seed, **options)
