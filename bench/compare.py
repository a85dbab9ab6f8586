"""The comparison that decides ``correct``: what the timed statements
returned over the wire against the plain numpy references.

An answer, the program's or a reference's, is a dict of column ->
numpy array, one entry per result row in result order: int64 for the
columns compared exactly (keys, counts, dates as days, decimals as
integers at the column's scale, strings as the generator's dictionary
ids), float64 for quotients (averages), which are compared by relative
gap. A reference module says which column is which (``COLUMNS``).
"""

from __future__ import annotations

import datetime
import decimal

import numpy as np

#: stands for a value that cannot be read as the column's kind; equals
#: no reference value
BAD = -(2 ** 62)


def _decimal(text: str, scale: int) -> int:
    d = decimal.Decimal(text).scaleb(scale)
    return int(d) if d == d.to_integral_value() else BAD


def _date(text: str) -> int:
    return (datetime.date.fromisoformat(text)
            - datetime.date(1970, 1, 1)).days


def decode(names, rows, columns: dict, dicts) -> dict:
    """Wire text rows -> an answer. ``columns`` maps each result column
    to its kind: ``("dict", dictionary column)``, ``("decimal",
    scale)``, ``("int",)``, ``("date",)`` or ``("ratio",)``."""
    readers = {
        "dict": lambda v, col: dicts[col].get(v.encode()),
        "decimal": _decimal,
        "int": lambda v: int(v),
        "date": _date,
    }
    out = {}
    for j, name in enumerate(names):
        kind, *args = columns.get(name, ("int",))
        values = []
        for row in rows:
            v = row[j]
            if kind == "ratio":
                values.append(float("nan") if v is None else float(v))
                continue
            try:
                got = None if v is None else readers[kind](v, *args)
            except (ValueError, ArithmeticError):
                got = None
            values.append(BAD if got is None else got)
        out[name] = np.array(
            values, dtype=np.float64 if kind == "ratio" else np.int64)
    return out


def compare(got: dict, want: dict, columns: dict) -> dict:
    """``wrong_cells``: values of the exactly compared columns that
    differ (every cell of the reference where the shapes differ);
    ``ratio_rel_gap``: the widest relative gap of a quotient."""
    n_want = sum(len(v) for v in want.values())
    if list(got) != list(want) or any(
            len(got[c]) != len(want[c]) for c in want):
        return {"wrong_cells": max(n_want, 1), "ratio_rel_gap": 0.0}
    wrong, gap = 0, 0.0
    for c, w in want.items():
        if columns[c][0] == "ratio":
            with np.errstate(invalid="ignore", divide="ignore"):
                g = np.abs(got[c] - w) / np.abs(w)
            g = np.where(np.isnan(g), np.inf, g)
            gap = max(gap, float(g.max(initial=0.0)))
        else:
            wrong += int(np.count_nonzero(got[c] != w))
    return {"wrong_cells": wrong, "ratio_rel_gap": gap}
