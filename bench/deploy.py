"""Build a configuration's deployment through the program's normal path.

``Cluster()`` -> ``CREATE TABLE ... WITH (store = column, shards = N,
upsert = on)`` by SQL -> the generator's dictionaries seeded id for id
-> ``ShardedTable.insert`` in ``scan_block_rows`` batches -> every
table's ``COUNT(*)`` against the generator's -> a probe of the upsert
guarantee -> promotions drained. Copied from ``chip_smoke.py``; holds no
cell's, table's or statement's name: the tables, their options and the
probe come from the configuration file, the schemas from the generator.
"""

from __future__ import annotations

import time

import numpy as np


class DeployError(RuntimeError):
    """The deployment does not hold what its configuration states."""


def one_value(res, name: str) -> int:
    return int(np.asarray(res.cols[name][0])[0])


def create_tables(session, data, config: dict) -> None:
    opts = config["table_options"]
    for t in config["tables"]:
        cols = ", ".join(f"{name} {sql_type} NOT NULL"
                         for name, sql_type in data.schema(t))
        pk = ", ".join(data.primary_key(t))
        session.execute(
            f"CREATE TABLE {t} ({cols}, PRIMARY KEY ({pk})) "
            f"WITH (store = {opts['store']}, shards = {opts['shards']}, "
            f"upsert = {opts['upsert']})")


def seed_dicts(cluster, data) -> None:
    """The generator's dictionaries become the cluster's, id for id, so
    string columns load as the integer ids the generator produced."""
    for c in data.dicts.columns():
        d = cluster.dicts.for_column(c)
        for v in data.dicts[c].values:
            d.add(v)
        if len(d) != len(data.dicts[c]):
            raise DeployError(f"dictionary of {c}: {len(d)} values in "
                              f"the cluster, {len(data.dicts[c])} made")


def load(cluster, data, config: dict, say) -> None:
    batch = cluster.config.scan_block_rows * config["table_options"]["shards"]
    for t in config["tables"]:
        cols = data.tables[t]
        n = data.rows(t)
        t0 = time.perf_counter()
        for lo in range(0, n, batch):
            res = cluster.tables[t].insert(
                {k: v[lo:lo + batch] for k, v in cols.items()})
            if not res.committed:
                raise DeployError(f"insert into {t} at row {lo}: {res}")
        say(f"load {t}: rows={n} "
            f"bytes={sum(v.nbytes for v in cols.values())} "
            f"seconds={time.perf_counter() - t0:.2f}")
    cluster._invalidate_plans()     # dictionaries grew under the plans


def count_mismatches(session, data, config: dict) -> int:
    """Tables whose COUNT(*) is not the number of rows generated."""
    wrong = 0
    for t in config["tables"]:
        got = one_value(session.execute(f"SELECT COUNT(*) AS n FROM {t}"),
                        "n")
        wrong += got != data.rows(t)
    return wrong


def _probe_column(data, t: str) -> str:
    """The first non-key column whose values, moved on by one row, differ
    from the rows' own somewhere."""
    pk = data.primary_key(t)
    for name, _ in data.schema(t):
        v = data.tables[t][name]
        if name not in pk and np.any(np.roll(v, 1) != v):
            return name
    raise DeployError(f"upsert probe: {t} has no non-key column to change")


def _stale_rows(session, t: str, pk, col: str, want: dict) -> int:
    """Rows of ``want`` whose key, read back over the session, does not
    carry exactly the value written last."""
    res = session.execute(f"SELECT {', '.join(pk)}, {col} FROM {t}")
    got: dict = {}
    keys = zip(*(np.asarray(res.cols[k][0]).tolist() for k in pk))
    for key, v in zip(keys, np.asarray(res.cols[col][0]).tolist()):
        got.setdefault(key, []).append(v)
    return sum(got.get(key) != [v] for key, v in want.items())


def upsert_probe(cluster, session, data, config: dict) -> dict:
    """The stated guarantee, as far as a run can show it: the probe
    table's rows are written again under their own keys with one non-key
    column's values moved on by one row, and read back by key over the
    same session; then the rows as generated are written once more and
    read back, so the table is left as it was made. With ``upsert = on``
    every write replaces. Returns the rows the table then holds too many
    (or too few) and the rows read back, after either write, with
    another value than the one written last."""
    t = config["guarantees"]["upsert_probe_table"]
    rows, pk = data.tables[t], data.primary_key(t)
    col = _probe_column(data, t)
    changed = dict(rows, **{col: np.roll(rows[col], 1)})
    stale = 0
    for written in (changed, rows):
        res = cluster.tables[t].insert(dict(written))
        if not res.committed:
            raise DeployError(f"upsert probe on {t}: {res}")
        want = dict(zip(zip(*(written[k].tolist() for k in pk)),
                        written[col].tolist()))
        stale += _stale_rows(session, t, pk, col, want)
    got = one_value(session.execute(f"SELECT COUNT(*) AS n FROM {t}"), "n")
    return {"upsert_extra_rows": abs(got - data.rows(t)),
            "upsert_stale_rows": stale}


def resident_stores(cluster):
    return [sh.resident for tab in cluster.tables.values()
            for sh in getattr(tab, "shards", ())]


def drain_promotions(cluster) -> None:
    for st in resident_stores(cluster):
        st.drain(timeout=120.0)


RESIDENT_KEYS = ("bytes", "portions", "hits", "misses", "promotions",
                 "evictions", "spills", "errors")


def resident_totals(cluster) -> dict:
    """The resident tier's counters summed over every shard's store."""
    total = dict.fromkeys(RESIDENT_KEYS, 0)
    for st in resident_stores(cluster):
        snap = st.snapshot()
        for k in total:
            total[k] += snap[k]
    return total


def build(cluster, session, data, config: dict, say) -> dict:
    """The whole deployment; returns the guarantee readings a run's
    ``correct`` holds at 0: tables with a wrong count, rows the upsert
    probe left over, rows it read back stale."""
    times = {}
    t0 = time.perf_counter()
    create_tables(session, data, config)
    seed_dicts(cluster, data)
    times["seed_dicts"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    load(cluster, data, config, say)
    times["load"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    readings = {"count_mismatch_tables": count_mismatches(session, data,
                                                          config),
                **upsert_probe(cluster, session, data, config)}
    drain_promotions(cluster)
    times["count_and_drain"] = time.perf_counter() - t0
    bc = cluster.scan_block_cache
    say(f"resident total: {resident_totals(cluster)}")
    say(f"block cache: budget={bc.budget()} entries={len(bc)} "
        f"hits={bc.hits} misses={bc.misses}")
    say("deploy seconds: " + " ".join(f"{k}={v:.2f}"
                                      for k, v in times.items()))
    return readings
