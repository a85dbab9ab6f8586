"""TPC-DS query 19, a manager's brand revenue where the buyer's zip code
is not the store's: the plain numpy reference.

Qualification substitution values (specification's query 19: MANAGER 8,
MONTH 11, YEAR 1998) in ``PARAMS``. The fact's rows of the month whose
item is the manager's and whose customer's current address has a zip
code whose first five characters are not the store's, summed exactly
per (brand id, brand, manufacturer id, manufacturer), the first 100 by
revenue descending, then the four keys (strings by their text): the
statement's own order, all of the group's keys. ``arith="float32"`` is
the control (see ``q1.py``).
"""

import numpy as np

TABLES = {
    "date_dim": ("d_date_sk", "d_year", "d_moy"),
    "store_sales": ("ss_sold_date_sk", "ss_item_sk", "ss_customer_sk",
                    "ss_store_sk", "ss_ext_sales_price"),
    "item": ("i_item_sk", "i_brand_id", "i_brand", "i_manufact_id",
             "i_manufact", "i_manager_id"),
    "customer": ("c_customer_sk", "c_current_addr_sk"),
    "customer_address": ("ca_address_sk", "ca_zip"),
    "store": ("s_store_sk", "s_zip"),
}
PARAMS = {"MANAGER": 8, "MONTH": 11, "YEAR": 1998}
COLUMNS = {"brand_id": ("int",), "brand": ("dict", "i_brand"),
           "i_manufact_id": ("int",), "i_manufact": ("dict", "i_manufact"),
           "ext_price": ("decimal", 2)}


def by_key(keys: np.ndarray, *columns) -> list:
    """Each column indexed by a surrogate key (-1 where no row has it)."""
    out = []
    for col in columns:
        a = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
        a[keys] = col
        out.append(a)
    return out


def text_rank(data, col: str) -> np.ndarray:
    """Dictionary id -> the rank of its text among the column's texts."""
    values = data.dicts[col].values
    rank = np.empty(len(values), dtype=np.int64)
    rank[sorted(range(len(values)), key=values.__getitem__)] = np.arange(
        len(values))
    return rank


def prefix5(data, col: str) -> np.ndarray:
    """Dictionary id -> the first five characters of its text."""
    return np.array([v[:5] for v in data.dicts[col].values], dtype=object)


def reference(data, arith: str = "exact") -> dict:
    dd, ss, it, cu, ca, st = (data.tables[t] for t in TABLES)
    year, moy = by_key(dd["d_date_sk"], dd["d_year"], dd["d_moy"])
    brand_id, brand, manufact_id, manufact, manager = by_key(
        it["i_item_sk"], it["i_brand_id"], it["i_brand"],
        it["i_manufact_id"], it["i_manufact"], it["i_manager_id"])
    (addr,) = by_key(cu["c_customer_sk"], cu["c_current_addr_sk"])
    (ca_zip,) = by_key(ca["ca_address_sk"], ca["ca_zip"])
    (s_zip,) = by_key(st["s_store_sk"], st["s_zip"])
    d, i = ss["ss_sold_date_sk"], ss["ss_item_sk"]
    m = ((moy[d] == PARAMS["MONTH"]) & (year[d] == PARAMS["YEAR"])
         & (manager[i] == PARAMS["MANAGER"]))
    rows = np.flatnonzero(m)
    buyer_zip = prefix5(data, "ca_zip")[
        ca_zip[addr[ss["ss_customer_sk"][rows]]]]
    store_zip = prefix5(data, "s_zip")[s_zip[ss["ss_store_sk"][rows]]]
    rows = rows[buyer_zip != store_zip]
    i = i[rows]
    keys = np.stack([brand_id[i], brand[i], manufact_id[i], manufact[i]],
                    axis=1)
    groups, inv = np.unique(keys, axis=0, return_inverse=True)
    num = np.int64 if arith == "exact" else np.float32
    sums = np.zeros(len(groups), dtype=num)
    np.add.at(sums, inv.ravel(), ss["ss_ext_sales_price"][rows].astype(num))
    top = np.lexsort((text_rank(data, "i_manufact")[groups[:, 3]],
                      groups[:, 2], groups[:, 0],
                      text_rank(data, "i_brand")[groups[:, 1]],
                      -sums))[:100]
    return {"brand_id": groups[top, 0], "brand": groups[top, 1],
            "i_manufact_id": groups[top, 2], "i_manufact": groups[top, 3],
            "ext_price": sums[top].astype(np.int64)}
