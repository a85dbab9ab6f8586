"""ClickBench Q16 (counted from 0), the ten most frequent (visitor,
search phrase) pairs: the plain numpy reference.

The source's text with tiebreakers after the count (``UserID``, then
the phrase by its text). ``arith="float32"`` is the control (see
``hits_q12.py``; the ``UserID`` half of the key falls together as in
``hits_q15.py``).
"""

import numpy as np

TABLES = {"hits": ("UserID", "SearchPhrase")}
PARAMS = {}
COLUMNS = {"UserID": ("int",), "SearchPhrase": ("dict", "SearchPhrase"),
           "c": ("int",)}


def text_rank(dictionary) -> np.ndarray:
    """id -> the place of its text among the dictionary's, by bytes."""
    order = sorted(range(len(dictionary)), key=dictionary.values.__getitem__)
    rank = np.empty(len(order), dtype=np.int64)
    rank[order] = np.arange(len(order))
    return rank


def reference(data, arith: str = "exact") -> dict:
    hits = data.tables["hits"]
    num = np.int64 if arith == "exact" else np.float32
    users, user_of = np.unique(hits["UserID"].astype(num),
                               return_inverse=True)
    n_phrases = len(data.dicts["SearchPhrase"])
    # a pair as one number: the user's place among the users, then the
    # phrase's dictionary id
    pairs, counts = np.unique(
        user_of.astype(np.int64) * n_phrases + hits["SearchPhrase"],
        return_counts=True)
    counts = counts.astype(num)
    user, phrase = users[pairs // n_phrases], pairs % n_phrases
    top = np.lexsort((text_rank(data.dicts["SearchPhrase"])[phrase], user,
                      -counts))[:10]
    return {"UserID": user[top].astype(np.int64), "SearchPhrase": phrase[top],
            "c": counts[top].astype(np.int64)}
