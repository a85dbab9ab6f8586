"""TPC-H Q6, forecasting revenue change: the plain numpy reference.

Validation parameters (specification 2.4.6.3) in ``PARAMS``, the
discount in hundredths as the column holds it. ``arith="float32"`` is the
control (see ``q1.py``).
"""

import numpy as np

TABLES = {"lineitem": ("l_shipdate", "l_discount", "l_quantity",
                       "l_extendedprice")}
PARAMS = {"DATE": "1994-01-01", "DISCOUNT_HUNDREDTHS": 6, "QUANTITY": 24}
COLUMNS = {"revenue": ("decimal", 4)}


def reference(data, arith: str = "exact") -> dict:
    li = data.tables["lineitem"]
    year = np.datetime64(PARAMS["DATE"], "Y")
    d0, d1 = (int(y.astype("datetime64[D]").astype(np.int64))
              for y in (year, year + 1))
    disc = PARAMS["DISCOUNT_HUNDREDTHS"]
    m = ((li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1)
         & (li["l_discount"] >= disc - 1) & (li["l_discount"] <= disc + 1)
         & (li["l_quantity"] < PARAMS["QUANTITY"] * 100))
    num = np.int64 if arith == "exact" else np.float32
    revenue = (li["l_extendedprice"][m].astype(num)
               * li["l_discount"][m].astype(num)).sum(dtype=num)
    return {"revenue": np.array([int(revenue)], dtype=np.int64)}
