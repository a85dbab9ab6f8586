"""TPC-DS query 67, the top-100 revenue cells of each category with every
subtotal: the plain numpy reference.

Qualification substitution value (specification's query 67: DMS 1200,
the twelve months 1200..1211, calendar 2000) in ``PARAMS``. The fact's
rows whose sale day falls in those months meet their store and item;
``sum(ss_sales_price * ss_quantity)`` in cents, exact in int64, for
each of the nine grouping sets of ``ROLLUP(i_category, i_class,
i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id)``: each level
grouped on its own by ``np.unique`` over its prefix of the keys (the
keys it rolls up NULL), none derived from another. ``rank() over
(partition by i_category order by sumsales desc)`` by its definition:
one more than the rows of the same partition whose sum is greater, so
ties share a rank; the grand total's NULL category is one partition.
The rows ranked 100 or better in the statement's ten-column order,
ascending, strings by their text, NULLs last (``kernels.sort_block``'s
rule), the first 100.

A NULL cell is ``compare.BAD``: ``compare.decode`` reads a wire NULL
so, and a text outside the column's dictionary too, so a program that
sent ``''`` for a NULL would pass here; the tier-1 test
``tests/test_tpcds_rollup.py::test_rolled_up_keys_arrive_as_sql_null``
holds the program to SQL NULL on the wire. ``arith="float32"`` is the
control (see ``q1.py``): the products and sums in float32.
"""

import compare
import numpy as np

TABLES = {
    "store_sales": ("ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                    "ss_sales_price", "ss_quantity"),
    "date_dim": ("d_date_sk", "d_month_seq", "d_year", "d_qoy", "d_moy"),
    "store": ("s_store_sk", "s_store_id"),
    "item": ("i_item_sk", "i_category", "i_class", "i_brand",
             "i_product_name"),
}
PARAMS = {"DMS": 1200}
#: the rollup's keys, in its order: (table, column, text dictionary?)
KEYS = (("item", "i_category", True), ("item", "i_class", True),
        ("item", "i_brand", True), ("item", "i_product_name", True),
        ("date_dim", "d_year", False), ("date_dim", "d_qoy", False),
        ("date_dim", "d_moy", False), ("store", "s_store_id", True))
COLUMNS = dict(
    {col: ("dict", col) if text else ("int",) for _, col, text in KEYS},
    sumsales=("decimal", 2), rk=("int",))
RANK_LIMIT = 100
LIMIT = 100


def _lookup(keys: np.ndarray, values: np.ndarray) -> np.ndarray:
    """``values`` indexed by a surrogate key (-1 where no row has it)."""
    out = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
    out[keys] = values
    return out


def levels(data, arith: str = "exact") -> list:
    """The nine grouping sets, the finest first: per level the key
    codes of its groups (-1 where a key is rolled up) and their sums."""
    ss, dd, st, it = (data.tables[t] for t in TABLES)
    month = _lookup(dd["d_date_sk"], dd["d_month_seq"])[ss["ss_sold_date_sk"]]
    m = (month >= PARAMS["DMS"]) & (month <= PARAMS["DMS"] + 11)
    by = {"date_dim": (dd["d_date_sk"], ss["ss_sold_date_sk"]),
          "store": (st["s_store_sk"], ss["ss_store_sk"]),
          "item": (it["i_item_sk"], ss["ss_item_sk"])}
    # each key's values as dense codes, so that a prefix of the keys is
    # one int64 (a mixed radix) that np.unique groups
    values, codes = zip(*(
        np.unique(_lookup(by[t][0], data.tables[t][col])[by[t][1][m]],
                  return_inverse=True) for t, col, _ in KEYS))
    radix = [len(v) for v in values]
    assert np.prod(np.array(radix, dtype=np.float64)) < 2.0 ** 62
    if arith == "exact":
        value = (ss["ss_sales_price"][m].astype(np.int64)
                 * ss["ss_quantity"][m].astype(np.int64))
    else:
        value = (ss["ss_sales_price"][m].astype(np.float32)
                 * ss["ss_quantity"][m].astype(np.float32))
    out = []
    for kept in range(len(KEYS), -1, -1):
        code = np.zeros(len(value), dtype=np.int64)
        for j in range(kept):
            code = code * radix[j] + codes[j].ravel()
        groups, inv = np.unique(code, return_inverse=True)
        sums = np.zeros(len(groups), dtype=value.dtype)
        np.add.at(sums, inv.ravel(), value)
        if kept == 0:       # the grand total is a row over no rows too
            groups, sums = np.zeros(1, np.int64), np.array(
                [sums.sum() if len(sums) else 0], dtype=value.dtype)
        cols = np.full((len(groups), len(KEYS)), -1, dtype=np.int64)
        rest = groups.copy()
        for j in range(kept - 1, -1, -1):
            cols[:, j] = values[j][rest % radix[j]]
            rest //= radix[j]
        out.append((cols, np.rint(sums).astype(np.int64)))
    return out


def reference(data, arith: str = "exact") -> dict:
    grouped = levels(data, arith)
    keys = np.concatenate([k for k, _ in grouped])
    sums = np.concatenate([s for _, s in grouped])
    part = keys[:, 0]
    rank = np.zeros(len(sums), dtype=np.int64)
    for p in np.unique(part):
        rows = np.flatnonzero(part == p)
        ordered = np.sort(sums[rows])
        greater = len(rows) - np.searchsorted(ordered, sums[rows], "right")
        rank[rows] = greater + 1
    kept = np.flatnonzero(rank <= RANK_LIMIT)

    def text(j: int, code: int):
        _, col, is_text = KEYS[j]
        return data.dicts[col].values[code] if is_text else code

    def order(i: int) -> tuple:
        row = [(keys[i, j] < 0, text(j, keys[i, j]) if keys[i, j] >= 0
                else 0) for j in range(len(KEYS))]
        return tuple(row) + ((False, sums[i]), (False, rank[i]))

    top = sorted(kept.tolist(), key=order)[:LIMIT]
    out = {col: np.array([keys[i, j] if keys[i, j] >= 0 else compare.BAD
                          for i in top], dtype=np.int64)
           for j, (_, col, _) in enumerate(KEYS)}
    out["sumsales"] = sums[top]
    out["rk"] = rank[top]
    return out
