"""ClickBench Q12 (counted from 0, as ``click_bench_queries.sql`` has
it), the ten most frequent search phrases: the plain numpy reference.

The source's text with a tiebreaker after the count (the phrase, by its
text), so that ten rows are one answer. ``arith="float32"`` is the
control: the guarantee "answers exact" broken in the chip's native
type, the group key and the count carried as float32. This statement's
keys are dictionary ids under 2^24 and its counts are small, so float32
holds them exactly and the control may pass here alone; ``hits_q15``
and ``hits_q16`` fail it on every seed (``UserID``s above 2^24 fall
together).
"""

import numpy as np

TABLES = {"hits": ("SearchPhrase",)}
PARAMS = {}
COLUMNS = {"SearchPhrase": ("dict", "SearchPhrase"), "c": ("int",)}


def text_rank(dictionary) -> np.ndarray:
    """id -> the place of its text among the dictionary's, by bytes."""
    order = sorted(range(len(dictionary)), key=dictionary.values.__getitem__)
    rank = np.empty(len(order), dtype=np.int64)
    rank[order] = np.arange(len(order))
    return rank


def reference(data, arith: str = "exact") -> dict:
    d = data.dicts["SearchPhrase"]
    phrase = data.tables["hits"]["SearchPhrase"]
    num = np.int64 if arith == "exact" else np.float32
    keys, counts = np.unique(phrase[phrase != d.get(b"")].astype(num),
                             return_counts=True)
    counts = counts.astype(num)
    keys = keys.astype(np.int64)
    top = np.lexsort((text_rank(d)[keys], -counts))[:10]
    return {"SearchPhrase": keys[top], "c": counts[top].astype(np.int64)}
