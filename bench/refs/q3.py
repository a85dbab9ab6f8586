"""TPC-H Q3, shipping priority: the plain numpy reference.

Validation parameters (specification 2.4.3.3) in ``PARAMS``. Top 10 of (orderkey, revenue at scale 4, orderdate,
shippriority) by revenue descending, then orderdate, then orderkey (the
statement's own tie-break). ``arith="float32"`` is the control (see
``q1.py``).
"""

import numpy as np

TABLES = {
    "customer": ("c_mktsegment", "c_custkey"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate",
               "o_shippriority"),
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                 "l_shipdate"),
}
PARAMS = {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}
COLUMNS = {"l_orderkey": ("int",), "revenue": ("decimal", 4),
           "o_orderdate": ("date",), "o_shippriority": ("int",)}


def reference(data, arith: str = "exact") -> dict:
    cu, od, li = (data.tables[t] for t in ("customer", "orders",
                                           "lineitem"))
    date = int(np.datetime64(PARAMS["DATE"], "D").astype(np.int64))
    seg = data.dicts["c_mktsegment"].get(PARAMS["SEGMENT"].encode())
    cust = np.zeros(int(cu["c_custkey"].max()) + 1, dtype=bool)
    cust[cu["c_custkey"][cu["c_mktsegment"] == seg]] = True
    om = (od["o_orderdate"] < date) & cust[od["o_custkey"]]
    n_ok = int(max(od["o_orderkey"].max(), li["l_orderkey"].max())) + 1
    odate = np.full(n_ok, -1, dtype=np.int64)
    odate[od["o_orderkey"][om]] = od["o_orderdate"][om]
    oprio = np.zeros(n_ok, dtype=np.int64)
    oprio[od["o_orderkey"][om]] = od["o_shippriority"][om]
    lm = (li["l_shipdate"] > date) & (odate[li["l_orderkey"]] >= 0)
    keys = li["l_orderkey"][lm]
    num = np.int64 if arith == "exact" else np.float32
    rev = np.zeros(n_ok, dtype=num)
    np.add.at(rev, keys, li["l_extendedprice"][lm].astype(num)
              * (num(100) - li["l_discount"][lm].astype(num)))
    uk = np.unique(keys)
    top = uk[np.lexsort((uk, odate[uk], -rev[uk]))[:10]]
    return {"l_orderkey": top, "revenue": rev[top].astype(np.int64),
            "o_orderdate": odate[top], "o_shippriority": oprio[top]}
