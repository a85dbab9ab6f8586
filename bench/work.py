"""The least work a statement needs, from shapes alone: the rows of its
FROM tables and the bytes of the columns it references over all those
rows, at the widths the copied schema gives. The same whatever kernel or
executor answers it. Nothing prunes in cells whose keys are dense and
whose predicates are uncorrelated with the key order; a cell in which
portions are pruned must count the bytes of the portions that are left.
"""

from __future__ import annotations

import json
import pathlib


class UnknownDevice(RuntimeError):
    """The device's kind is not in the table of peaks."""


def peaks_for(device_kind: str, path=None) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is
    an error, never a default."""
    path = path or pathlib.Path(__file__).with_name("peaks.json")
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {path}: "
            f"known kinds are {sorted(table)}")
    return table[device_kind]


def statement_rows(tables: dict, data) -> int:
    """Rows of the base tables one execution of the statement reads."""
    return sum(data.rows(t) for t in tables)


def statement_bytes(tables: dict, data, widths: dict) -> int:
    """Bytes of the referenced columns over all rows of their tables."""
    total = 0
    for t, cols in tables.items():
        types = dict(data.schema(t))
        total += data.rows(t) * sum(widths[types[c]] for c in cols)
    return total


def least_seconds(n_bytes: int, peaks: dict) -> float:
    """A scan is bound by bandwidth: bytes over the peak HBM rate."""
    return n_bytes / peaks["hbm_bytes_per_s"]
