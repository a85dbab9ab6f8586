"""TPC-DS query 7, item averages under a demographic and promotion slice:
the plain numpy reference.

Qualification substitution values (specification's query 7: GEN 'M',
MS 'S', ES 'College', YEAR 2000) in ``PARAMS``. The fact's rows whose
buyer's demographics, promotion (by e-mail or by event: either channel
'N') and sale year match, four averages per ``i_item_id`` (an item's two
revisions share one), the first 100 ids by text. Each average is the
correctly rounded quotient of an exact sum by the count (``ratio``
columns). ``arith="float32"`` is the control (see ``q1.py``): sums and
quotients in float32.
"""

import numpy as np

TABLES = {
    "store_sales": ("ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk",
                    "ss_promo_sk", "ss_quantity", "ss_list_price",
                    "ss_coupon_amt", "ss_sales_price"),
    "customer_demographics": ("cd_demo_sk", "cd_gender", "cd_marital_status",
                              "cd_education_status"),
    "date_dim": ("d_date_sk", "d_year"),
    "item": ("i_item_sk", "i_item_id"),
    "promotion": ("p_promo_sk", "p_channel_email", "p_channel_event"),
}
PARAMS = {"GEN": "M", "MS": "S", "ES": "College", "YEAR": 2000}
COLUMNS = {"i_item_id": ("dict", "i_item_id"), "agg1": ("ratio",),
           "agg2": ("ratio",), "agg3": ("ratio",), "agg4": ("ratio",)}
#: the widest relative gap an average may show against the correctly
#: rounded quotient of the exact sums. ``refs/q1.py``'s limit, for the
#: same reason: f64 division is emulated on the chip with about 48 bits,
#: so a sound run reads ~2^-48 at the widest, and the float32 control
#: reads a gap of the order of 2^-24 (PERF.md section 2 has the readings)
RATIO_REL_GAP_LIMIT = 1e-10

#: (column, scale) of each average's argument: ``ss_quantity`` is an
#: integer, the three prices decimals at scale 2
_ARGS = {"agg1": ("ss_quantity", 0), "agg2": ("ss_list_price", 2),
         "agg3": ("ss_coupon_amt", 2), "agg4": ("ss_sales_price", 2)}


def _code(data, col: str, text: str) -> int:
    got = data.dicts[col].get(text.encode())
    return -1 if got is None else got


def reference(data, arith: str = "exact") -> dict:
    ss, cd, dd, it, pr = (data.tables[t] for t in TABLES)
    cd_ok = np.zeros(int(cd["cd_demo_sk"].max()) + 1, dtype=bool)
    cd_ok[cd["cd_demo_sk"]] = (
        (cd["cd_gender"] == _code(data, "cd_gender", PARAMS["GEN"]))
        & (cd["cd_marital_status"]
           == _code(data, "cd_marital_status", PARAMS["MS"]))
        & (cd["cd_education_status"]
           == _code(data, "cd_education_status", PARAMS["ES"])))
    p_ok = np.zeros(int(pr["p_promo_sk"].max()) + 1, dtype=bool)
    p_ok[pr["p_promo_sk"]] = (
        (pr["p_channel_email"] == _code(data, "p_channel_email", "N"))
        | (pr["p_channel_event"] == _code(data, "p_channel_event", "N")))
    d_ok = np.zeros(int(dd["d_date_sk"].max()) + 1, dtype=bool)
    d_ok[dd["d_date_sk"]] = dd["d_year"] == PARAMS["YEAR"]
    item_id = np.full(int(it["i_item_sk"].max()) + 1, -1, dtype=np.int64)
    item_id[it["i_item_sk"]] = it["i_item_id"]
    m = (cd_ok[ss["ss_cdemo_sk"]] & p_ok[ss["ss_promo_sk"]]
         & d_ok[ss["ss_sold_date_sk"]])
    ids, inv = np.unique(item_id[ss["ss_item_sk"][m]], return_inverse=True)
    texts = data.dicts["i_item_id"].values
    order = sorted(range(len(ids)), key=lambda g: texts[ids[g]])[:100]
    counts = np.bincount(inv, minlength=len(ids))
    out = {"i_item_id": ids[order]}
    num = np.int64 if arith == "exact" else np.float32
    for name, (col, scale) in _ARGS.items():
        sums = np.zeros(len(ids), dtype=num)
        np.add.at(sums, inv, ss[col][m].astype(num))
        if arith == "exact":     # a Python int quotient is correctly rounded
            out[name] = np.array([int(sums[g]) / (int(counts[g]) * 10 ** scale)
                                  for g in order], dtype=np.float64)
        else:
            out[name] = np.array([sums[g] / num(counts[g]) / num(10 ** scale)
                                  for g in order], dtype=np.float64)
    return out
