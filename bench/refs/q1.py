"""TPC-H Q1, pricing summary report: the plain numpy reference.

Validation parameters (specification 2.4.1.3) in ``PARAMS``. One pass
over ``lineitem``, exact int64 sums per (returnflag, linestatus), rows
in the strings' order. ``arith="float32"`` is the control: the same
query with the decimal arithmetic done in float32, the chip's native
type, which the configuration's "answers exact" forbids.
"""

import numpy as np

TABLES = {"lineitem": ("l_shipdate", "l_returnflag", "l_linestatus",
                       "l_quantity", "l_extendedprice", "l_discount",
                       "l_tax")}
PARAMS = {"DELTA": 90}
COLUMNS = {
    "l_returnflag": ("dict", "l_returnflag"),
    "l_linestatus": ("dict", "l_linestatus"),
    "sum_qty": ("decimal", 2), "sum_base_price": ("decimal", 2),
    "sum_disc_price": ("decimal", 4), "sum_charge": ("decimal", 6),
    "avg_qty": ("ratio",), "avg_price": ("ratio",), "avg_disc": ("ratio",),
    "count_order": ("int",),
}
#: the widest relative gap an average may show against the correctly
#: rounded quotient of the exact sums. Set from two readings (PERF.md
#: section 2): sound runs on the chip read 1.06e-14 at the widest (f64 is
#: emulated there with about 48 bits), the float32 control 4.9e-7 at the
#: least
RATIO_REL_GAP_LIMIT = 1e-10


def reference(data, arith: str = "exact") -> dict:
    li = data.tables["lineitem"]
    cutoff = (int(np.datetime64("1998-12-01", "D").astype(np.int64))
              - PARAMS["DELTA"])
    m = li["l_shipdate"] <= cutoff
    num = np.int64 if arith == "exact" else np.float32
    rf, ls = li["l_returnflag"][m], li["l_linestatus"][m]
    qty = li["l_quantity"][m].astype(num)
    price = li["l_extendedprice"][m].astype(num)
    disc = li["l_discount"][m].astype(num)
    tax = li["l_tax"][m].astype(num)
    disc_price = price * (num(100) - disc)              # scale 4
    charge = disc_price * (num(100) + tax)              # scale 6
    rf_text = data.dicts["l_returnflag"].values
    ls_text = data.dicts["l_linestatus"].values
    groups = sorted(
        (rf_text[a], ls_text[b], a, b)
        for a in range(len(rf_text)) for b in range(len(ls_text)))
    out = {c: [] for c in COLUMNS}
    for _, _, a, b in groups:
        g = (rf == a) & (ls == b)
        n = int(np.count_nonzero(g))
        if not n:
            continue
        sums = {name: col[g].sum(dtype=num) for name, col in (
            ("sum_qty", qty), ("sum_base_price", price),
            ("sum_disc_price", disc_price), ("sum_charge", charge),
            ("sum_disc", disc))}
        out["l_returnflag"].append(a)
        out["l_linestatus"].append(b)
        for name in ("sum_qty", "sum_base_price", "sum_disc_price",
                     "sum_charge"):
            out[name].append(int(sums[name]))
        out["count_order"].append(n)
        # a Python int quotient is correctly rounded
        for name, s in (("avg_qty", "sum_qty"),
                        ("avg_price", "sum_base_price"),
                        ("avg_disc", "sum_disc")):
            out[name].append(int(sums[s]) / (n * 100) if arith == "exact"
                             else float(sums[s] / num(n) / num(100)))
    return {c: np.array(v, dtype=np.float64 if COLUMNS[c][0] == "ratio"
                        else np.int64) for c, v in out.items()}
