"""ClickBench Q15 (counted from 0), the ten most active visitors: the
plain numpy reference.

The source's text with a tiebreaker after the count (``UserID``).
``arith="float32"`` is the control (see ``hits_q12.py``): ``UserID``s
are 62-bit numbers, float32 holds 24 bits of them, so users fall
together and their counts add up.
"""

import numpy as np

TABLES = {"hits": ("UserID",)}
PARAMS = {}
COLUMNS = {"UserID": ("int",), "c": ("int",)}


def reference(data, arith: str = "exact") -> dict:
    num = np.int64 if arith == "exact" else np.float32
    users, counts = np.unique(data.tables["hits"]["UserID"].astype(num),
                              return_counts=True)
    counts = counts.astype(num)
    top = np.lexsort((users, -counts))[:10]
    return {"UserID": users[top].astype(np.int64),
            "c": counts[top].astype(np.int64)}
