"""TPC-DS query 3, a manufacturer's November revenue by brand and year:
the plain numpy reference.

Qualification substitution values (specification's query 3: MANUFACT
128, MONTH 11, AGGC ``ss_ext_sales_price``) in ``PARAMS``. The fact's
rows whose sale day is in the month and whose item is the
manufacturer's, summed exactly per (year, brand id, brand), the first
100 by year, revenue descending, brand id: the statement's own order,
whose keys decide every tie (``i_brand`` is a function of
``i_brand_id``). ``arith="float32"`` is the control (see ``q1.py``).
"""

import numpy as np

TABLES = {
    "date_dim": ("d_date_sk", "d_year", "d_moy"),
    "store_sales": ("ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"),
    "item": ("i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"),
}
PARAMS = {"MANUFACT": 128, "MONTH": 11}
COLUMNS = {"d_year": ("int",), "brand_id": ("int",),
           "brand": ("dict", "i_brand"), "sum_agg": ("decimal", 2)}


def by_key(keys: np.ndarray, *columns) -> list:
    """Each column indexed by a surrogate key: slot k holds the row whose
    key is k (-1 where no row has it)."""
    out = []
    for col in columns:
        a = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
        a[keys] = col
        out.append(a)
    return out


def reference(data, arith: str = "exact") -> dict:
    dd, ss, it = (data.tables[t] for t in ("date_dim", "store_sales",
                                           "item"))
    year, moy = by_key(dd["d_date_sk"], dd["d_year"], dd["d_moy"])
    brand_id, brand, manufact = by_key(
        it["i_item_sk"], it["i_brand_id"], it["i_brand"], it["i_manufact_id"])
    d, i = ss["ss_sold_date_sk"], ss["ss_item_sk"]
    m = (moy[d] == PARAMS["MONTH"]) & (manufact[i] == PARAMS["MANUFACT"])
    keys = np.stack([year[d[m]], brand_id[i[m]], brand[i[m]]], axis=1)
    groups, inv = np.unique(keys, axis=0, return_inverse=True)
    num = np.int64 if arith == "exact" else np.float32
    sums = np.zeros(len(groups), dtype=num)
    np.add.at(sums, inv.ravel(), ss["ss_ext_sales_price"][m].astype(num))
    top = np.lexsort((groups[:, 1], -sums, groups[:, 0]))[:100]
    return {"d_year": groups[top, 0], "brand_id": groups[top, 1],
            "brand": groups[top, 2], "sum_agg": sums[top].astype(np.int64)}
