#!/usr/bin/env python3
"""chip_smoke.py: the served SQL path, end to end, on one TPU chip.

The quickest proof that the system still starts on the chip. One
process, no children, no network:

  1. asserts the device (a TPU, or exit non-zero naming what JAX found);
  2. builds the TPC-H deployment through the normal path: ``Cluster``
     over a ``DirBlobStore``, ``CREATE TABLE ... WITH (store = column)``
     for all eight tables, data from ``tpch.TpchData(sf, seed)`` loaded
     in batches through ``ShardedTable.insert``;
  3. answers Q1, Q6, Q3 and a write-then-read through
     ``Session.execute``, each checked against a plain numpy reference
     computed from the generated arrays, outside any timing;
  4. answers Q1 again over a PostgreSQL-wire socket served by this
     process;
  5. says which executor answered each statement and what the HBM tiers
     hold, and fails on a swallowed promotion error, an unexpected
     executor or any wrong answer.

``--chips 4`` runs only the cross-chip path and what it is compared
with: lineitem/orders/customer on four shards, Q1 and Q3 on the
single-chip executors, then again over ``Cluster.enable_mesh()``,
bit-identical, with the bytes each device holds.

Last line of stdout: ``{"ok": true, "device": {"platform": "tpu",
"kind": "...", "count": N}}``. Any failure is a non-zero exit and no
such line.
"""

from __future__ import annotations

import argparse
import decimal
import json
import os
import shutil
import socket
import struct
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ALL_TABLES = ("region", "nation", "supplier", "customer", "part",
              "partsupp", "orders", "lineitem")
MESH_TABLES = ("customer", "orders", "lineitem")
Q1_SUMS = (("sum_qty", "sum_qty", 2), ("sum_base_price", "sum_base_price", 2),
           ("sum_disc_price", "sum_disc_price", 4),
           ("sum_charge", "sum_charge", 6))


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------- numpy references (independent of the engine) -------


def _days(s: str) -> int:
    return int(np.datetime64(s, "D").astype(np.int64))


def cpu_q1(li, cutoff):
    """Single-pass numpy Q1; exact int64 sums per (returnflag,
    linestatus) dictionary-id pair."""
    m = li["l_shipdate"] <= cutoff
    nls = int(li["l_linestatus"].max()) + 1
    gid = (li["l_returnflag"][m].astype(np.int64) * nls
           + li["l_linestatus"][m].astype(np.int64))
    ng = int(gid.max()) + 1
    qty = li["l_quantity"][m]
    price = li["l_extendedprice"][m]
    disc = li["l_discount"][m]
    disc_price = price * (100 - disc)                 # scale 4
    charge = disc_price * (100 + li["l_tax"][m])      # scale 6
    out = {"count": np.bincount(gid, minlength=ng)}
    of_group = [gid == g for g in range(ng)]
    for name, col in (("sum_qty", qty), ("sum_base_price", price),
                      ("sum_disc_price", disc_price),
                      ("sum_charge", charge), ("sum_disc", disc)):
        out[name] = np.array([int(col[m_g].sum()) for m_g in of_group],
                             dtype=np.int64)
    keep = out["count"] > 0
    out = {k: v[keep] for k, v in out.items()}
    out["gid"] = np.flatnonzero(keep)
    return out, nls


def cpu_q6(li, d0, d1) -> int:
    m = ((li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1)
         & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
         & (li["l_quantity"] < 2400))
    return int(np.sum(li["l_extendedprice"][m] * li["l_discount"][m]))


def cpu_q3(data, date):
    """Top-10 (orderkey, revenue scale 4, orderdate, shippriority)."""
    cu, od, li = (data.tables[t] for t in ("customer", "orders",
                                           "lineitem"))
    seg = data.dicts["c_mktsegment"].get(b"BUILDING")
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
    rev = np.zeros(n_ok, dtype=np.int64)
    np.add.at(rev, keys, li["l_extendedprice"][lm]
              * (100 - li["l_discount"][lm]))
    uk = np.unique(keys)
    order = np.lexsort((uk, odate[uk], -rev[uk]))[:10]
    top = uk[order]
    return {"l_orderkey": top, "revenue": rev[top],
            "o_orderdate": odate[top], "o_shippriority": oprio[top]}


# ---------------- result access + checks -----------------------------


def col(res, name):
    return np.asarray(res.cols[name][0])


def scaled_int(res, name, scale: int) -> np.ndarray:
    """A decimal result column as exact integers at ``scale``."""
    t = res.schema.field(name).type
    assert t.is_decimal, (name, t)
    v = col(res, name).astype(np.int64)
    assert t.scale >= scale or np.all(v % 10 ** (scale - t.scale) == 0)
    return (v * 10 ** (scale - t.scale) if scale >= t.scale
            else v // 10 ** (t.scale - scale))


def check_q1(res, ref, nls) -> None:
    gid = (col(res, "l_returnflag").astype(np.int64) * nls
           + col(res, "l_linestatus").astype(np.int64))
    order = np.argsort(gid)
    assert np.array_equal(gid[order], ref["gid"]), "Q1 group keys differ"
    assert np.array_equal(col(res, "count_order")[order], ref["count"])
    for name, rname, scale in Q1_SUMS:
        got = scaled_int(res, name, scale)[order]
        assert np.array_equal(got, ref[rname]), \
            f"Q1 {name} differs: got {got}, want {ref[rname]}"
    for name, rname, scale in (("avg_qty", "sum_qty", 2),
                               ("avg_price", "sum_base_price", 2),
                               ("avg_disc", "sum_disc", 2)):
        t = res.schema.field(name).type
        got = col(res, name)[order].astype(np.float64)
        if t.is_decimal:
            got = got / 10.0 ** t.scale
        want = ref[rname] / ref["count"] / 10.0 ** scale
        # an average is a rounded quotient: float tolerance, or the
        # last digit of its decimal scale
        atol = 10.0 ** -t.scale if t.is_decimal else 0.0
        assert np.allclose(got, want, rtol=1e-9, atol=atol), \
            f"Q1 {name} differs: {got} vs {want}"


def check_q6(res, ref: int) -> None:
    got = scaled_int(res, "revenue", 4)
    assert got.shape == (1,) and int(got[0]) == ref, \
        f"Q6 revenue {got} != {ref}"


def check_q3(res, ref) -> None:
    assert np.array_equal(col(res, "l_orderkey"), ref["l_orderkey"]), \
        (col(res, "l_orderkey"), ref["l_orderkey"])
    assert np.array_equal(scaled_int(res, "revenue", 4), ref["revenue"])
    assert np.array_equal(col(res, "o_orderdate"), ref["o_orderdate"])
    assert np.array_equal(col(res, "o_shippriority"),
                          ref["o_shippriority"])


def same_result(a, b) -> None:
    """Bit-identical result tables (values and validity)."""
    assert a.schema.names == b.schema.names, (a.schema.names,
                                              b.schema.names)
    for n in a.schema.names:
        for x, y in zip(a.cols[n], b.cols[n]):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and np.array_equal(x, y), \
                f"column {n} differs"


# ---------------- the executor that answered -------------------------


def executor_of(profile) -> str:
    spans = {s["name"]: s for s in profile.spans}
    if spans.get("mesh", {}).get("attrs", {}).get("answered"):
        return "mesh-fused" if "plan.fuse" in spans else "mesh-walk"
    if "plan.fuse" in spans:
        return "fused"
    if "dq" in spans:
        return "dq"
    return "walk"


#: programs XLA built so far, or fetched from the persistent cache (JAX
#: fires the event around both; never on a jit-cache hit)
COMPILES = [0]


def count_compiles() -> None:
    import jax

    def on_event(name, _seconds, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)


def hbm_in_use() -> int:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))


def run_statement(session, label: str, sql: str, expect, check):
    """Cold then warm through Session.execute; both answers checked
    (outside the timing); the executor and what the profile says go on
    one line. Nothing is asserted about compiles on the cold statement
    (a persistent cache may serve them); the warm repeat makes none."""
    c0 = COMPILES[0]
    t0 = time.perf_counter()
    cold = session.execute(sql)
    cold_s = time.perf_counter() - t0
    cold_prof = session.last_profile
    c1 = COMPILES[0]
    t0 = time.perf_counter()
    warm = session.execute(sql)
    warm_s = time.perf_counter() - t0
    prof = session.last_profile
    warm_compiles = COMPILES[0] - c1
    check(cold)
    check(warm)
    ex = executor_of(prof)
    say(f"{label}: executor={ex} fused_stages={prof.fused_stages} "
        f"compile_cache cold={cold_prof.compile_cache or '-'} "
        f"warm={prof.compile_cache or '-'} "
        f"cold_seconds={cold_s:.3f} warm_seconds={warm_s:.3f} "
        f"cold_xla_compiles={c1 - c0} warm_xla_compiles={warm_compiles} "
        f"rows={warm.num_rows} "
        f"warm_stages={ {k: round(v, 4) for k, v in prof.stages.items()} } "
        f"warm_pruning={dict(prof.pruning)} "
        f"hbm_in_use={hbm_in_use()} result=ok")
    assert executor_of(cold_prof) == ex, (executor_of(cold_prof), ex)
    assert ex in expect, f"{label}: answered by {ex}, expected {expect}"
    assert warm_compiles == 0, \
        f"{label}: the warm repeat made {warm_compiles} XLA compiles"
    return warm


# ---------------- build + load ---------------------------------------


def create_tables(session, data, tables, shards: int) -> None:
    from ydb_tpu.workload import tpch

    def ddl_type(t) -> str:
        # TPC-H's money and quantity columns are decimal(15, 2)
        return f"decimal(15, {t.scale})" if t.is_decimal else t.kind.value

    for t in tables:
        cols = ", ".join(
            f"{f.name} {ddl_type(f.type)}"
            + ("" if f.nullable else " NOT NULL")
            for f in data.schema(t).fields)
        pk = ", ".join(tpch.PRIMARY_KEYS[t])
        # upsert = on: a row written again under its primary key
        # replaces the old one, as YDB column tables do
        session.execute(
            f"CREATE TABLE {t} ({cols}, PRIMARY KEY ({pk})) "
            f"WITH (store = column, shards = {shards}, upsert = on)")


def seed_dicts(cluster, data) -> None:
    """The generator's dictionaries become the cluster's, id for id, so
    string columns load as the integer ids the generator produced."""
    for c in data.dicts.columns():
        d = cluster.dicts.for_column(c)
        for v in data.dicts[c].values:
            d.add(v)
        assert len(d) == len(data.dicts[c]), c


def load(cluster, session, data, tables, batch_rows: int) -> dict:
    rows = {}
    for t in tables:
        cols = data.tables[t]
        n = len(next(iter(cols.values())))
        t0 = time.perf_counter()
        for lo in range(0, n, batch_rows):
            res = cluster.tables[t].insert(
                {k: v[lo:lo + batch_rows] for k, v in cols.items()})
            assert res.committed, (t, lo, res)
        rows[t] = n
        say(f"load {t}: rows={n} bytes="
            f"{sum(v.nbytes for v in cols.values())} "
            f"seconds={time.perf_counter() - t0:.2f}")
    cluster._invalidate_plans()
    for t in tables:
        assert cluster.tables[t].schema == data.schema(t), t
        got = int(col(session.execute(
            f"SELECT COUNT(*) AS n FROM {t}"), "n")[0])
        assert got == rows[t], f"{t}: {got} rows loaded, {rows[t]} made"
    return rows


def stores_of(cluster):
    return [(t, sh) for t, tab in cluster.tables.items()
            for sh in getattr(tab, "shards", ())]


def drain_promotions(cluster) -> None:
    for _t, sh in stores_of(cluster):
        sh.resident.drain(timeout=120.0)


def tier_report(cluster) -> dict:
    """Resident-tier and block-cache state; fails on a swallowed
    promotion error (ResidentStore.errors counts a bare except, device
    OOM included)."""
    total = {"bytes": 0, "portions": 0, "hits": 0, "misses": 0,
             "promotions": 0, "evictions": 0, "spills": 0, "errors": 0}
    for t, sh in stores_of(cluster):
        snap = sh.resident.snapshot()
        for k in total:
            total[k] += snap[k]
        if snap["bytes"] or snap["errors"]:
            say(f"resident {sh.shard_id}: {snap}")
    bc = cluster.scan_block_cache
    say(f"resident total: {total}")
    say(f"block cache: budget={bc.budget()} entries={len(bc)} "
        f"hits={bc.hits} misses={bc.misses} "
        f"flight_waits={bc.flight_waits}")
    assert total["errors"] == 0, \
        f"ResidentStore.errors == {total['errors']}"
    return total


# ---------------- pgwire client (from the protocol spec) -------------


class PgClient:
    """Just enough of the frontend side of PostgreSQL protocol 3.0."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=600)
        params = b"user\x00smoke\x00database\x00postgres\x00\x00"
        self.sock.sendall(
            struct.pack("!II", len(params) + 8, 196608) + params)
        while True:
            t, body = self._message()
            if t == b"E":
                raise RuntimeError(body)
            if t == b"Z":
                return

    def _exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            c = self.sock.recv(n - len(buf))
            if not c:
                raise ConnectionError("server closed")
            buf += c
        return buf

    def _message(self):
        t = self._exact(1)
        (ln,) = struct.unpack("!I", self._exact(4))
        return t, self._exact(ln - 4)

    def query(self, sql: str):
        q = sql.encode() + b"\x00"
        self.sock.sendall(b"Q" + struct.pack("!I", len(q) + 4) + q)
        names, rows = [], []
        while True:
            t, body = self._message()
            if t == b"T":
                (n,) = struct.unpack("!H", body[:2])
                off = 2
                for _ in range(n):
                    end = body.index(b"\x00", off)
                    names.append(body[off:end].decode())
                    off = end + 19
            elif t == b"D":
                (n,) = struct.unpack("!H", body[:2])
                off, row = 2, []
                for _ in range(n):
                    (ln,) = struct.unpack("!i", body[off:off + 4])
                    off += 4
                    if ln == -1:
                        row.append(None)
                    else:
                        row.append(body[off:off + ln].decode())
                        off += ln
                rows.append(row)
            elif t == b"E":
                raise RuntimeError(body)
            elif t == b"Z":
                return names, rows

    def close(self) -> None:
        self.sock.sendall(b"X" + struct.pack("!I", 4))
        self.sock.close()


def check_wire(names, rows, res) -> None:
    """The wire's text rows equal the session's result, value by value
    (strings by text, decimals and integers exactly, floats to 1e-9)."""
    assert names == list(res.schema.names), (names, res.schema.names)
    assert len(rows) == res.num_rows, (len(rows), res.num_rows)
    for j, n in enumerate(names):
        t = res.schema.field(n).type
        v, ok = res.cols[n]
        texts = res.strings(n) if t.is_string else None
        for i, row in enumerate(rows):
            if not ok[i]:
                assert row[j] is None, (n, i, row[j])
            elif t.is_string:
                assert row[j].encode() == texts[i], (n, i, row[j])
            elif t.is_decimal:
                got = decimal.Decimal(row[j]).scaleb(t.scale)
                assert got == int(v[i]), (n, i, row[j], v[i])
            elif np.issubdtype(np.asarray(v).dtype, np.floating):
                assert np.isclose(float(row[j]), float(v[i]),
                                  rtol=1e-9, atol=0.0), (n, i, row[j])
            else:
                assert int(row[j]) == int(v[i]), (n, i, row[j], v[i])


# ---------------- write, then read -----------------------------------


def sql_literal(data, table: str, name: str, value) -> str:
    t = data.schema(table).field(name).type
    if t.is_string:
        return "'" + data.dicts[name].values[int(value)].decode() + "'"
    if t.is_decimal:
        return str(decimal.Decimal(int(value)).scaleb(-t.scale))
    if t.kind.value == "date":
        return f"date '{np.datetime64(int(value), 'D')}'"
    return str(int(value))


def write_then_read(session, data, q6_sql, q6_args, q6_before) -> int:
    """UPSERT a handful of lineitem rows under their existing keys with
    values Q6 selects; the write is acknowledged, the rows read back by
    key carry the new values, every other line of those orders is as it
    was, the table has as many rows as before, and Q6 includes them."""
    li = data.tables["lineitem"]
    n = len(li["l_orderkey"])
    idx = np.arange(n // 2, n // 2 + 5)   # adjacent keys: one portion
    for k in ("l_quantity", "l_extendedprice", "l_discount",
              "l_shipdate"):
        li[k] = li[k].copy()
    li["l_quantity"][idx] = 100                       # 1.00
    li["l_extendedprice"][idx] = 1234500 + 100 * np.arange(5)
    li["l_discount"][idx] = 6                         # 0.06
    li["l_shipdate"][idx] = _days("1994-06-01")
    names = list(data.schema("lineitem").names)
    values = ", ".join(
        "(" + ", ".join(sql_literal(data, "lineitem", c, li[c][i])
                        for c in names) + ")"
        for i in idx)
    res = session.execute(
        f"UPSERT INTO lineitem ({', '.join(names)}) VALUES {values}")
    assert res.committed, res
    lo, hi = int(li["l_orderkey"][idx[0]]), int(li["l_orderkey"][idx[-1]])
    read_cols = ("l_orderkey", "l_linenumber", "l_quantity",
                 "l_extendedprice", "l_discount", "l_shipdate")
    back = session.execute(
        f"SELECT {', '.join(read_cols)} FROM lineitem "
        f"WHERE l_orderkey >= {lo} AND l_orderkey <= {hi} "
        f"ORDER BY l_orderkey, l_linenumber")
    m = (li["l_orderkey"] >= lo) & (li["l_orderkey"] <= hi)
    for c in read_cols:
        assert np.array_equal(col(back, c), li[c][m]), \
            f"read back {c}: {col(back, c)} != {li[c][m]}"
    count = int(col(session.execute(
        "SELECT COUNT(*) AS n FROM lineitem"), "n")[0])
    assert count == n, f"lineitem has {count} rows after UPSERT, not {n}"
    q6_after = cpu_q6(li, *q6_args)
    assert q6_after != q6_before, "the written rows must move Q6"
    check_q6(session.execute(q6_sql), q6_after)
    say(f"write-then-read: upserted={len(idx)} keys=[{lo}..{hi}] "
        f"acknowledged step={res.step} read_back_rows={back.num_rows} "
        f"lineitem_rows={count} q6_before={q6_before} "
        f"q6_after={q6_after} result=ok")
    return q6_after


# ---------------- phases ----------------------------------------------


def device_line(cache_dir: str) -> dict:
    import jax

    from ydb_tpu import native

    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say(f"device: platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']} hbm_bytes={stats.get('bytes_limit')} "
        f"jax={jax.__version__} compile_cache={cache_dir} "
        f"native_host_library={'built' if native.available() else 'numpy twins'}")
    return info


def memory_line(cluster, table_bytes: int) -> None:
    """What has to fit one HBM together: the tables, the two tiers'
    budgets (shares of the HBM the device reports, engine/hbm.py), and
    the rest, which program temporaries, staging and results take."""
    from ydb_tpu.engine import hbm, resident

    say(f"memory: hbm_bytes={hbm.device_bytes()} "
        f"table_bytes={table_bytes} "
        f"resident_budget={resident.default_budget()} "
        f"block_cache_budget={cluster.scan_block_cache.budget()} "
        f"scan_block_rows={cluster.config.scan_block_rows}")


def peak_line(resident_bytes: int) -> None:
    """Measured: the most the device held at once, and how much of it
    was not resident table data (temporaries, staging, results)."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    say(f"memory peak: peak_bytes_in_use={peak} "
        f"resident_bytes={resident_bytes} "
        f"beside_the_tables={max(peak - resident_bytes, 0)} "
        f"bytes_limit={stats.get('bytes_limit')}")


def phase_one_chip(cluster, session, data) -> None:
    from ydb_tpu.api.pgwire import PgWireServer
    from ydb_tpu.ssa import plan_fuse
    from ydb_tpu.workload.queries import TPCH

    create_tables(session, data, ALL_TABLES, shards=1)
    seed_dicts(cluster, data)
    rows = load(cluster, session, data, ALL_TABLES,
                cluster.config.scan_block_rows)
    drain_promotions(cluster)
    memory_line(cluster, sum(
        v.nbytes for t in ALL_TABLES for v in data.tables[t].values()))
    tier_report(cluster)

    li = data.tables["lineitem"]
    small = rows["lineitem"] <= plan_fuse.FUSE_MAX_ROWS
    scan_executor = ("fused",) if small else ("walk",)
    q1_ref, nls = cpu_q1(li, _days("1998-12-01") - 90)
    run_statement(session, "Q1", TPCH["q1"], scan_executor,
                  lambda r: check_q1(r, q1_ref, nls))
    q6_args = (_days("1994-01-01"), _days("1995-01-01"))
    q6_ref = cpu_q6(li, *q6_args)
    run_statement(session, "Q6", TPCH["q6"], scan_executor,
                  lambda r: check_q6(r, q6_ref))
    q3_ref = cpu_q3(data, _days("1995-03-15"))
    run_statement(session, "Q3", TPCH["q3"], ("dq",),
                  lambda r: check_q3(r, q3_ref))
    write_then_read(session, data, TPCH["q6"], q6_args, q6_ref)

    # the same Q1 over the wire: the server is a thread of this process
    # (the chip belongs to one process)
    q1_ref, nls = cpu_q1(li, _days("1998-12-01") - 90)
    direct = session.execute(TPCH["q1"])
    check_q1(direct, q1_ref, nls)
    pg = PgWireServer(cluster, port=0).start()
    try:
        client = PgClient(pg.port)
        t0 = time.perf_counter()
        names, wire_rows = client.query(TPCH["q1"])
        wire_s = time.perf_counter() - t0
        client.close()
    finally:
        pg.stop()
    check_wire(names, wire_rows, direct)
    say(f"pgwire Q1: port={pg.port} rows={len(wire_rows)} "
        f"seconds={wire_s:.3f} result=ok (equal to Session.execute)")
    drain_promotions(cluster)
    peak_line(tier_report(cluster)["bytes"])


def device_bytes_report(cluster) -> list[int]:
    """Bytes each device holds, from the live arrays' shards."""
    import jax

    held = {d.id: 0 for d in jax.devices()}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            held[s.device.id] += int(s.data.nbytes)
    for _t, sh in stores_of(cluster):
        snap = sh.resident.snapshot()
        say(f"resident {sh.shard_id}: device_slot={snap['device_slot']} "
            f"bytes={snap['bytes']} portions={snap['portions']}")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(f"device {d.id}: live_array_bytes={held[d.id]} "
            f"bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return [held[d.id] for d in jax.devices()]


def phase_four_chips(cluster, session, data) -> None:
    import jax

    from ydb_tpu.workload.queries import TPCH

    n = len(jax.devices())
    create_tables(session, data, MESH_TABLES, shards=n)
    seed_dicts(cluster, data)
    load(cluster, session, data, MESH_TABLES,
         cluster.config.scan_block_rows * n)
    drain_promotions(cluster)
    li = data.tables["lineitem"]
    q1_ref, nls = cpu_q1(li, _days("1998-12-01") - 90)
    q3_ref = cpu_q3(data, _days("1995-03-15"))
    single = {
        "Q1": run_statement(session, "Q1 single-chip", TPCH["q1"],
                            ("walk", "fused"),
                            lambda r: check_q1(r, q1_ref, nls)),
        "Q3": run_statement(session, "Q3 single-chip", TPCH["q3"],
                            ("dq",), lambda r: check_q3(r, q3_ref)),
    }
    say("before enable_mesh:")
    device_bytes_report(cluster)
    cluster.enable_mesh()
    mesh = {
        "Q1": run_statement(session, "Q1 mesh", TPCH["q1"],
                            ("mesh-walk", "mesh-fused"),
                            lambda r: check_q1(r, q1_ref, nls)),
        "Q3": run_statement(session, "Q3 mesh", TPCH["q3"],
                            ("mesh-walk", "mesh-fused"),
                            lambda r: check_q3(r, q3_ref)),
    }
    for q in ("Q1", "Q3"):
        same_result(single[q], mesh[q])
        say(f"{q}: mesh result bit-identical to single-chip")
    drain_promotions(cluster)
    say(f"after enable_mesh over {n} devices:")
    held = device_bytes_report(cluster)
    tier_report(cluster)
    assert all(b > 0 for b in held), \
        f"a device holds no share of the data: {held}"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor")
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the mesh path and its comparison")
    args = ap.parse_args()
    if not __debug__:
        print("chip_smoke: its checks are assert statements; run it "
              "without -O", file=sys.stderr)
        return 1

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devs)}", file=sys.stderr)
        return 1

    from ydb_tpu.engine.blobs import DirBlobStore
    from ydb_tpu.kqp.session import Cluster
    from ydb_tpu.runtime import compile_cache
    from ydb_tpu.workload import tpch

    t_start = time.perf_counter()
    info = device_line(compile_cache.configure())
    count_compiles()
    t0 = time.perf_counter()
    data = tpch.TpchData(sf=args.sf, seed=args.seed)
    say(f"generate: sf={args.sf} seed={args.seed} "
        f"seconds={time.perf_counter() - t0:.2f}")
    root = tempfile.mkdtemp(prefix=".chip_smoke_store_", dir=HERE)
    cluster = None
    try:
        cluster = Cluster(store=DirBlobStore(root))
        session = cluster.session()
        if args.chips == 4:
            phase_four_chips(cluster, session, data)
        else:
            phase_one_chip(cluster, session, data)
    finally:
        if cluster is not None:
            cluster.stop()
        shutil.rmtree(root, ignore_errors=True)
    say(f"total_seconds={time.perf_counter() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
