"""ClickBench's ``hits`` from a seed, at all 105 columns of the source.

The source is ClickBench (github.com/ClickHouse/ClickBench), table
``hits``: 105 columns, 99,997,497 rows; upstream ships it as ``ydb
workload clickbench`` (``ydb/library/workload/clickbench``). The data
itself is not in this repository, so a seeded generator stands in for
it: the schema is the source's, column for column in its order, at
YDB's types (``SMALLINT`` -> ``int16``, ``INTEGER`` -> ``int32``,
``BIGINT`` -> ``int64``, ``TIMESTAMP``, ``DATE``, text -> ``string``,
a 4-byte dictionary id on the device); the primary key is upstream's
``click_bench_schema.sql``'s; every distribution is a choice, recalled
from the published results of ClickBench's first queries on
``hits_100m`` and not read from the data, and the configuration lists
each under ``assumed``. It imports nothing of the program and gives
``deploy.py`` and ``work.py`` what ``tpch_gen.Data`` gives them:
``tables``, ``rows``, ``schema``, ``primary_key``, ``dicts``,
``widths``, and ``make(scale_factor, seed, **options)``.

The rows arrive in the order of the primary key, as a dump of a table
stored in that order gives them (and as ``tpch_gen.py``'s dense keys
do): each batch the loader writes then covers a key range of its own.
Rows that arrive in any order make every batch overlap every other, and
the program answers a scan over overlapping portions by merging them by
key on the host, outside the resident tier, until a compaction has run.

``scale_factor`` counts millions of rows: 12.5 is one chip's eighth of
the source's 99,997,497. The same sizes for every seed: exactly that
many rows, the same number of distinct users, and dictionaries of the
same lengths. What follows the seed is which rows carry a search
phrase (each with probability ``PHRASE_SHARE``), so Q12's selected
rows are a few thousand more or fewer from seed to seed.

What the three statements of the cell read:

* ``UserID``: ``USER_SHARE`` x rows distinct values (17,630,976 of
  99,997,497 in the source); every user has one hit and the rest are
  drawn by a Zipf law of exponent ``USER_SKEW`` over the users, so the
  heaviest has thousands of hits (29,097 of 10^8 in the source).
* ``SearchPhrase``: empty in all but ``PHRASE_SHARE`` of the rows
  (13,172,392 of 99,997,497 are not); ``PHRASE_DISTINCT`` distinct
  texts a non-empty row expected (6,019,103 over 13,172,392), texts of
  1-6 words; every text occurs once and the rest are drawn by a Zipf
  law of exponent ``PHRASE_SKEW`` (the source's heaviest phrase has
  0.5% of the non-empty rows).

The four long free-text columns that no statement of the cell groups
by (``URL``, ``Referer``, ``Title``, ``OriginalURL``) come from bounded
pools of ``TEXT_POOL`` texts at the source's typical lengths, as
``tpch_gen.py`` bounds its comments: that bounds the host dictionaries
and the time to seed them and changes no width on the device. Every
other column comes from a small stated domain (``INT_DOMAINS``,
``STRING_DOMAINS``).

A second table, ``hits_probe``, is the one thing here the source does
not have: ``PROBE_ROWS`` rows drawn as ``hits``' are, with the same 105
columns, key and dictionaries. ``deploy.upsert_probe`` rewrites its
whole table twice and reads it back through a Python dict, so it cannot
be ``hits``; the configuration names ``hits_probe`` as its
``upsert_probe_table``. It is created, loaded and counted with
``hits`` and no statement of the traffic reads it.
"""

from __future__ import annotations

import numpy as np

#: sql type -> bytes per value as the engine holds it on the device
WIDTHS = {"int64": 8, "int32": 4, "int16": 2, "timestamp": 8, "date": 4,
          "string": 4}

_S, _I, _B, _T = "int16", "int32", "int64", "string"
#: the source's 105 columns in its order
SCHEMA = (
    ("WatchID", _B), ("JavaEnable", _S), ("Title", _T), ("GoodEvent", _S),
    ("EventTime", "timestamp"), ("EventDate", "date"), ("CounterID", _I),
    ("ClientIP", _I), ("RegionID", _I), ("UserID", _B),
    ("CounterClass", _S), ("OS", _S), ("UserAgent", _S), ("URL", _T),
    ("Referer", _T), ("IsRefresh", _S), ("RefererCategoryID", _S),
    ("RefererRegionID", _I), ("URLCategoryID", _S), ("URLRegionID", _I),
    ("ResolutionWidth", _S), ("ResolutionHeight", _S),
    ("ResolutionDepth", _S), ("FlashMajor", _S), ("FlashMinor", _S),
    ("FlashMinor2", _T), ("NetMajor", _S), ("NetMinor", _S),
    ("UserAgentMajor", _S), ("UserAgentMinor", _T), ("CookieEnable", _S),
    ("JavascriptEnable", _S), ("IsMobile", _S), ("MobilePhone", _S),
    ("MobilePhoneModel", _T), ("Params", _T), ("IPNetworkID", _I),
    ("TraficSourceID", _S), ("SearchEngineID", _S), ("SearchPhrase", _T),
    ("AdvEngineID", _S), ("IsArtifical", _S), ("WindowClientWidth", _S),
    ("WindowClientHeight", _S), ("ClientTimeZone", _S),
    ("ClientEventTime", "timestamp"), ("SilverlightVersion1", _S),
    ("SilverlightVersion2", _S), ("SilverlightVersion3", _I),
    ("SilverlightVersion4", _S), ("PageCharset", _T), ("CodeVersion", _I),
    ("IsLink", _S), ("IsDownload", _S), ("IsNotBounce", _S),
    ("FUniqID", _B), ("OriginalURL", _T), ("HID", _I),
    ("IsOldCounter", _S), ("IsEvent", _S), ("IsParameter", _S),
    ("DontCountHits", _S), ("WithHash", _S), ("HitColor", _T),
    ("LocalEventTime", "timestamp"), ("Age", _S), ("Sex", _S),
    ("Income", _S), ("Interests", _S), ("Robotness", _S), ("RemoteIP", _I),
    ("WindowName", _I), ("OpenerName", _I), ("HistoryLength", _S),
    ("BrowserLanguage", _T), ("BrowserCountry", _T), ("SocialNetwork", _T),
    ("SocialAction", _T), ("HTTPError", _S), ("SendTiming", _I),
    ("DNSTiming", _I), ("ConnectTiming", _I), ("ResponseStartTiming", _I),
    ("ResponseEndTiming", _I), ("FetchTiming", _I),
    ("SocialSourceNetworkID", _S), ("SocialSourcePage", _T),
    ("ParamPrice", _B), ("ParamOrderID", _T), ("ParamCurrency", _T),
    ("ParamCurrencyID", _S), ("OpenstatServiceName", _T),
    ("OpenstatCampaignID", _T), ("OpenstatAdID", _T),
    ("OpenstatSourceID", _T), ("UTMSource", _T), ("UTMMedium", _T),
    ("UTMCampaign", _T), ("UTMContent", _T), ("UTMTerm", _T),
    ("FromTag", _T), ("HasGCLID", _S), ("RefererHash", _B),
    ("URLHash", _B), ("CLID", _I),
)
#: upstream's click_bench_schema.sql
PRIMARY_KEY = ("CounterID", "EventDate", "UserID", "EventTime", "WatchID")
#: load order: the probe first, so a fault shows before the long load
TABLES = ("hits_probe", "hits")
PROBE_ROWS = 4096

USER_SHARE = 17_630_976 / 99_997_497
USER_SKEW = 0.5
PHRASE_SHARE = 13_172_392 / 99_997_497
PHRASE_DISTINCT = 6_019_103 / 13_172_392
PHRASE_SKEW = 0.75
PHRASE_WORDS = (1, 6)
ADV_ENGINE_SHARE = 0.0063
REGIONS, REGION_SKEW = 9000, 1.0
COUNTERS, COUNTER_SKEW = 6500, 1.0
PHONE_MODELS, PHONE_MODEL_EMPTY = 170, 0.94
FIRST_DAY, DAYS = "2013-07-01", 31
#: texts in each bounded pool, and (shortest, longest) in characters
TEXT_POOL = 1 << 18
TEXT_LENGTHS = {"URL": (30, 120), "Referer": (0, 120), "Title": (10, 90),
                "OriginalURL": (0, 120)}
#: integer columns drawn uniformly from [0, domain); a column of
#: neither table is a flag, 0 or 1
INT_DOMAINS = {
    "OS": 100, "UserAgent": 80, "RefererCategoryID": 20,
    "RefererRegionID": 9000, "URLCategoryID": 20, "URLRegionID": 9000,
    "ResolutionWidth": 2600, "ResolutionHeight": 1600,
    "ResolutionDepth": 33, "FlashMajor": 12, "FlashMinor": 10,
    "NetMajor": 5, "NetMinor": 6, "UserAgentMajor": 60, "MobilePhone": 100,
    "IPNetworkID": 1 << 20, "TraficSourceID": 10, "SearchEngineID": 100,
    "WindowClientWidth": 2600, "WindowClientHeight": 1600,
    "ClientTimeZone": 24, "SilverlightVersion1": 6,
    "SilverlightVersion2": 4, "SilverlightVersion3": 1 << 16,
    "SilverlightVersion4": 4, "CodeVersion": 2000, "HID": 1 << 30,
    "Age": 60, "Sex": 3, "Income": 5, "Interests": 1 << 15,
    "Robotness": 4, "WindowName": 1 << 20, "OpenerName": 1 << 20,
    "HistoryLength": 50, "HTTPError": 3, "SendTiming": 10000,
    "DNSTiming": 10000, "ConnectTiming": 10000,
    "ResponseStartTiming": 10000, "ResponseEndTiming": 10000,
    "FetchTiming": 10000, "SocialSourceNetworkID": 10,
    "ParamCurrencyID": 4, "CLID": 1 << 16, "ClientIP": 1 << 31,
    "RemoteIP": 1 << 31, "ParamPrice": 1 << 40, "FUniqID": 1 << 62,
    "RefererHash": 1 << 62, "URLHash": 1 << 62,
}
#: string columns outside the pools above: (distinct values, the empty
#: text among them; share of rows that are empty)
STRING_DOMAINS = {
    "FlashMinor2": (12, 0.5), "UserAgentMinor": (40, 0.1),
    "Params": (256, 0.9), "PageCharset": (4, 0.0), "HitColor": (6, 0.0),
    "BrowserLanguage": (60, 0.0), "BrowserCountry": (60, 0.0),
    "SocialNetwork": (8, 0.98), "SocialAction": (8, 0.98),
    "SocialSourcePage": (256, 0.98), "ParamOrderID": (256, 0.98),
    "ParamCurrency": (4, 0.98), "OpenstatServiceName": (16, 0.98),
    "OpenstatCampaignID": (256, 0.98), "OpenstatAdID": (256, 0.98),
    "OpenstatSourceID": (64, 0.98), "UTMSource": (64, 0.97),
    "UTMMedium": (16, 0.97), "UTMCampaign": (256, 0.97),
    "UTMContent": (256, 0.97), "UTMTerm": (256, 0.97),
    "FromTag": (32, 0.98),
}

_SYLLABLES = [c + v for c in "bdfgklmnprstvz" for v in "aeiou"]
_HOSTS = [b"example.com", b"news.site", b"search.net", b"shop.io",
          b"mail.org", b"video.tv", b"maps.info", b"blog.net"]
_NP_INT = {"int16": np.int16, "int32": np.int32, "int64": np.int64}


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
    """The dictionaries of all string columns, by column name; both
    tables share them, as the cluster's are held by column name."""

    def __init__(self):
        self._by_column: dict[str, Dict] = {}

    def columns(self) -> list[str]:
        return list(self._by_column)

    def __getitem__(self, col: str) -> Dict:
        return self._by_column[col]

    def __setitem__(self, col: str, values) -> None:
        self._by_column[col] = Dict(values)


def _vocabulary(rng, size: int) -> np.ndarray:
    """``size`` distinct pseudo-words of two to four syllables."""
    words: dict[bytes, None] = {}
    while len(words) < size:
        picks = rng.integers(0, len(_SYLLABLES), (size, 4))
        lengths = rng.integers(2, 5, size)
        for row, k in zip(picks.tolist(), lengths.tolist()):
            words["".join(_SYLLABLES[i] for i in row[:k]).encode()] = None
    return np.array(list(words)[:size], dtype=object)


def _phrases(rng, vocabulary, size: int, lo: int, hi: int) -> list[bytes]:
    """``size`` distinct texts of ``lo`` to ``hi`` words."""
    out: dict[bytes, None] = {}
    while len(out) < size:
        m = size - len(out) + 1024
        picks = vocabulary[rng.integers(0, len(vocabulary), (m, hi))]
        lengths = rng.integers(lo, hi + 1, m)
        for row, k in zip(picks.tolist(), lengths.tolist()):
            out[b" ".join(row[:k])] = None
    return list(out)[:size]


def _texts(rng, vocabulary, col: str, size: int) -> list[bytes]:
    """A bounded pool of ``size`` distinct texts within the column's
    lengths: a URL shape for the three URL columns, words for titles.
    The first text is the shortest the column allows (the empty text
    for ``Referer`` and ``OriginalURL``)."""
    lo, hi = TEXT_LENGTHS[col]
    words = vocabulary[rng.integers(0, len(vocabulary), (size, 12))].tolist()
    hosts = rng.integers(0, len(_HOSTS), size).tolist()
    lengths = rng.integers(max(lo, 12), hi + 1, size).tolist()
    out: dict[bytes, None] = {b"x" * lo: None}
    for i in range(size):
        if col == "Title":
            text = b" ".join(words[i])
        else:
            text = b"http://" + _HOSTS[hosts[i]] + b"/" + b"/".join(words[i])
        # the row number keeps the texts distinct whatever was cut
        out[text[:lengths[i] - 7] + b"%07d" % i] = None
    return list(out)[:size]


def _zipf(rng, size: int, n: int, exponent: float) -> np.ndarray:
    """``n`` picks from [0, size) with P(k) ~ (k + 1) ** -exponent."""
    cdf = np.cumsum(np.arange(1, size + 1, dtype=np.float64) ** -exponent)
    picks = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return np.minimum(picks, size - 1)


def _covering(rng, size: int, n: int, exponent: float) -> np.ndarray:
    """``_zipf``, with every value of [0, size) picked at least once
    where ``n`` allows: ``size`` of the picks, at random places, are a
    permutation of the values."""
    picks = _zipf(rng, size, n, exponent)
    if n >= size:
        picks[rng.permutation(n)[:size]] = rng.permutation(size)
    return picks


def _distinct_ints(rng, size: int, bits: int) -> np.ndarray:
    """``size`` distinct non-negative int64 below 2**bits: random high
    bits over the value's own number, then shuffled."""
    low = max(size - 1, 1).bit_length()
    values = (rng.integers(0, 1 << (bits - low), size, dtype=np.int64)
              << low) | np.arange(size, dtype=np.int64)
    return rng.permutation(values)


class _Pools:
    """What the rows of both tables draw from: the users, regions and
    counters, and every string column's dictionary."""

    def __init__(self, rng, n: int, dicts: Dicts):
        self.users = _distinct_ints(rng, max(int(round(USER_SHARE * n)), 1),
                                    62)
        self.regions = rng.choice(np.arange(1, 1 << 17, dtype=np.int32),
                                  REGIONS, replace=False)
        self.counters = rng.choice(np.arange(1, 1 << 21, dtype=np.int32),
                                   COUNTERS, replace=False)
        vocabulary = _vocabulary(rng, 8192)
        n_phrases = max(int(round(PHRASE_DISTINCT * PHRASE_SHARE * n)), 1)
        dicts["SearchPhrase"] = [b""] + _phrases(rng, vocabulary, n_phrases,
                                                 *PHRASE_WORDS)
        dicts["MobilePhoneModel"] = [b""] + [
            b"model %d" % i for i in range(1, PHONE_MODELS)]
        for col in TEXT_LENGTHS:
            dicts[col] = _texts(rng, vocabulary, col, TEXT_POOL)
        for col, (size, _) in STRING_DOMAINS.items():
            dicts[col] = [b""] + [b"%s-%d" % (col.lower().encode(), i)
                                  for i in range(1, size)]
        self.sizes = {col: len(dicts[col]) for col in dicts.columns()}


def _mostly_empty(rng, n: int, size: int, empty: float) -> np.ndarray:
    """Dictionary ids: 0 (the empty text) with probability ``empty``,
    else uniform over the other values."""
    ids = rng.integers(1, max(size, 2), n, dtype=np.int32)
    ids[rng.random(n) < empty] = 0
    return ids


def _rows(rng, n: int, pools: _Pools, first_row: int,
          cover: bool) -> dict:
    """``n`` rows of the 105 columns. ``cover``: every user and every
    phrase occurs (``hits``); else they are drawn by their skew alone
    (the probe table). ``first_row`` numbers the rows, for WatchID."""
    draw = _covering if cover else _zipf
    day0 = int(np.datetime64(FIRST_DAY, "D").astype(np.int64))
    t = {}
    low = max(first_row + n - 1, 1).bit_length()
    t["WatchID"] = rng.permutation(
        (rng.integers(0, 1 << (62 - low), n, dtype=np.int64) << low)
        | np.arange(first_row, first_row + n, dtype=np.int64))
    seconds = rng.integers(0, DAYS * 86400, n, dtype=np.int64)
    t["EventTime"] = (day0 * 86400 + seconds) * 1_000_000
    t["EventDate"] = (day0 + seconds // 86400).astype(np.int32)
    t["CounterID"] = pools.counters[_zipf(rng, COUNTERS, n, COUNTER_SKEW)]
    t["UserID"] = pools.users[draw(rng, len(pools.users), n, USER_SKEW)]
    # the rows arrive in the order of the primary key; every other
    # column is drawn independently of the key, so only the key moves
    order = np.lexsort(tuple(t[k] for k in reversed(PRIMARY_KEY)))
    for k in PRIMARY_KEY:
        t[k] = t[k][order]
    t["ClientEventTime"] = t["EventTime"] + rng.integers(
        -3600, 3600, n, dtype=np.int64) * 1_000_000
    t["LocalEventTime"] = t["EventTime"] + rng.integers(
        0, 4, n, dtype=np.int64) * 3_600_000_000
    t["RegionID"] = pools.regions[_zipf(rng, REGIONS, n, REGION_SKEW)]
    phrased = np.flatnonzero(rng.random(n) < PHRASE_SHARE)
    t["SearchPhrase"] = np.zeros(n, dtype=np.int32)
    t["SearchPhrase"][phrased] = 1 + draw(
        rng, pools.sizes["SearchPhrase"] - 1, len(phrased), PHRASE_SKEW)
    t["AdvEngineID"] = np.where(
        rng.random(n) < ADV_ENGINE_SHARE,
        rng.integers(1, 60, n, dtype=np.int16), np.int16(0))
    t["MobilePhoneModel"] = _mostly_empty(rng, n, PHONE_MODELS,
                                          PHONE_MODEL_EMPTY)
    for col in TEXT_LENGTHS:
        t[col] = rng.integers(0, pools.sizes[col], n, dtype=np.int32)
    for col, (size, empty) in STRING_DOMAINS.items():
        t[col] = _mostly_empty(rng, n, size, empty)
    for col, sql_type in SCHEMA:
        if col not in t:
            t[col] = rng.integers(0, INT_DOMAINS.get(col, 2), n,
                                  dtype=_NP_INT[sql_type])
    return {col: t[col] for col, _ in SCHEMA}


class Data:
    """``hits`` and ``hits_probe`` as host numpy column dicts, with the
    string dictionaries the id columns index into."""

    widths = WIDTHS

    def __init__(self, scale_factor: float, seed: int):
        n = int(round(scale_factor * 1_000_000))
        rng = np.random.default_rng(seed)
        self.dicts = Dicts()
        pools = _Pools(rng, n, self.dicts)
        self.tables = {
            "hits": _rows(rng, n, pools, 0, cover=True),
            "hits_probe": _rows(rng, PROBE_ROWS, pools, n, cover=False),
        }

    def rows(self, table: str) -> int:
        return len(self.tables[table]["WatchID"])

    def schema(self, table: str):
        """``(column, sql type)`` pairs in the table's column order."""
        return SCHEMA

    def primary_key(self, table: str):
        return PRIMARY_KEY


def make(scale_factor: float, seed: int, **options) -> Data:
    """The harness's entry: every generator module has this function;
    ``options`` are the configuration's ``generator_options``."""
    return Data(scale_factor, seed, **options)
