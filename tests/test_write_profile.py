"""The write path's spans and counters (PR 39): one ``write`` span tree
an insert from ``ShardedTable.insert`` to the portion resident on the
device, the process's ``component=write | resident | compact`` counters
at the same boundaries, the ``write`` statement key.

CPU, a table of a few thousand rows; the resident tier is forced on
(``RESIDENT_FORCE``) so the promotion runs. The process counters are
read as deltas: every test file of a worker shares them."""

import threading
import time

import numpy as np
import pytest

from ydb_tpu.engine import resident
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs import profile, tracing
from ydb_tpu.obs.counters import root_counters

ROWS = 6000

#: the documented tree (ydb_tpu/obs/README.md, "The span tree of a
#: write"): span name -> the names directly beneath it
WRITE_TREE = {
    "write": {"write.encode", "write.route", "write.buffer",
              "write.commit"},
    "write.commit": {"write.portion"},
    "write.portion": {"write.concat", "write.sort", "write.blob",
                      "write.index", "write.log", "write.promote.enqueue"},
    "write.promote.enqueue": {"resident.promote"},
    "resident.promote": {"resident.promote.load", "resident.promote.put",
                         "resident.promote.admit"},
}
#: a compaction's or a TTL rewrite's portion has no batches to join
PORTION_TREE = WRITE_TREE["write.portion"] - {"write.concat"}


@pytest.fixture
def resident_on(monkeypatch):
    monkeypatch.setattr(resident, "RESIDENT_FORCE", True)
    monkeypatch.setattr(tracing, "PROFILE_FORCE", True)


def make_table(shards: int = 2, upsert: str = "on", name: str = "t"):
    c = Cluster()
    s = c.session()
    s.execute(
        f"CREATE TABLE {name} (id int64 NOT NULL, k int64 NOT NULL, "
        f"v int64 NOT NULL, tag string NOT NULL, PRIMARY KEY (id, k)) "
        f"WITH (store = column, shards = {shards}, upsert = {upsert})")
    return c, s, c.tables[name]


def batch(rows: int = ROWS, repeat: int = 1, base: int = 0) -> dict:
    """``rows`` rows whose keys each come ``repeat`` times."""
    ids = base + np.arange(rows, dtype=np.int64) // repeat
    return {"id": ids, "k": np.zeros(rows, dtype=np.int64),
            "v": np.arange(rows, dtype=np.int64),
            "tag": [b"tag%d" % (i % 5) for i in range(rows)]}


def drain(table) -> None:
    for sh in table.shards:
        sh.resident.drain(timeout=30.0)


def counters() -> dict:
    """The flat snapshot plus each histogram's sum."""
    out = root_counters().snapshot()
    res = root_counters().group(component="resident")
    out["lag_sum"] = res.histogram("resident_lag_seconds").total
    return out


def delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def tree_of(spans, root) -> dict:
    """name -> names beneath it, over ``root``'s subtree."""
    by_id = {s.span_id: s for s in spans}
    out: dict = {}
    for s in profile.subtree(spans, root.span_id):
        out.setdefault(by_id[s.parent_id].name, set()).add(s.name)
    return out


def last_root(c, name: str):
    return [s for s in c.tracer.finished
            if s.name == name and s.parent_id is None][-1]


@pytest.mark.parametrize("shards", [1, 2])
def test_direct_insert_opens_exactly_the_documented_tree(resident_on,
                                                         shards):
    c, _s, t = make_table(shards)
    try:
        # a batch large enough for the work to outweigh ~20 spans' own
        # bookkeeping (~0.3 ms): at 6,000 rows that is a tenth
        covered, rows = [], 20 * ROWS
        for i in range(3):
            assert t.insert(batch(rows, base=i * rows)).committed
            drain(t)
            root = last_root(c, "write")
            spans = c.tracer.spans_for(root.trace_id)
            assert tree_of(spans, root) == WRITE_TREE
            mine = [root] + [s for s in profile.subtree(spans, root.span_id)
                             if s.thread == root.thread]
            selfs = profile.self_seconds(mine)
            parents = {s.parent_id for s in mine}
            leaves = sum(selfs[s.span_id] for s in mine
                         if s.span_id not in parents)
            covered.append(leaves / root.seconds)
        # one a batch a shard, never one a column or a row
        per_shard = [s for s in mine if s.name == "write.portion"]
        assert len(per_shard) == shards
        assert len(mine) <= 4 + 10 * shards
        assert root.attrs["table"] == "t" and root.attrs["rows"] == rows
        assert root.attrs["shards_hit"] == shards
        commit = next(s for s in mine if s.name == "write.commit")
        assert commit.attrs["volatile"] == int(shards == 1)
        assert commit.attrs["participants"] == shards
        # the leaves explain the write (the best of three: a thread
        # switch inside a gap is not the tree's)
        assert max(covered) >= 0.95
        # and the promotion hangs under the enqueue, on another thread
        promo = [s for s in spans if s.name == "resident.promote"]
        assert len(promo) == shards
        assert all(p.thread != root.thread for p in promo)
        assert all(p.attrs["source"] == "memory" and p.attrs["bytes"] > 0
                   and p.attrs["lag_s"] >= 0 for p in promo)
    finally:
        c.stop()


@pytest.mark.parametrize("upsert,repeat", [("on", 1), ("on", 3),
                                           ("off", 3)])
def test_counters_equal_what_was_written(resident_on, upsert, repeat):
    c, _s, t = make_table(2, upsert)
    try:
        before = counters()
        assert t.insert(batch(repeat=repeat)).committed
        drain(t)
        d = delta(before, counters())
        w = "|component=write"
        assert d["inserts" + w] == 1 and d["rows" + w] == ROWS
        assert d["portions" + w] == 2
        deduped = ROWS - ROWS // repeat if upsert == "on" else 0
        assert d.get("rows_deduped" + w, 0) == deduped
        assert sum(sh.portions[1].num_rows for sh in t.shards) \
            == ROWS - deduped
        blobs = [b for b in c.store.list("t/") if "/portion/" in b]
        assert d["blob_bytes" + w] == sum(len(c.store.get(b))
                                          for b in blobs)
        assert d["bytes_in" + w] == (3 * 8 + 4) * ROWS   # tag: int32 ids
        assert d["visible_seconds_count" + w] == 1
        assert "failed" + w not in d
        stages = {k.split("stage=")[1]: v for k, v in d.items()
                  if k.startswith("stage_seconds|")}
        assert set(stages) == set(profile.WRITE_SPAN_STAGE.values())
        assert 0 < sum(stages.values()) <= d["seconds" + w]
        assert d["seconds" + w] == pytest.approx(
            last_root(c, "write").seconds)
        r = "|component=resident"
        assert d["promotions" + r] == 2 and d["promote_bytes" + r] > 0
        assert d["resident_lag_seconds_count" + r] == 2
        assert {k for k in d if k.startswith("promote_seconds|")} == {
            f"promote_seconds|component=resident,stage={st}"
            for st in ("load", "put", "admit")}
        assert not any(k.startswith("promote_declined") for k in d)
    finally:
        c.stop()


@pytest.mark.parametrize("verb", ["UPSERT", "INSERT"])
def test_statement_that_writes_nests_write_and_gets_the_key(resident_on,
                                                            verb):
    c, s, t = make_table(2)
    try:
        n_profiles = len(c.profiles)
        s.execute(f"{verb} INTO t (id, k, v, tag) VALUES "
                  "(1, 0, 10, 'a'), (2, 0, 20, 'b'), (3, 0, 30, 'c')")
        drain(t)
        p = s.last_profile
        assert len(c.profiles) == n_profiles + 1
        by_id = {sp["span_id"]: sp for sp in p.spans}
        write = next(sp for sp in p.spans if sp["name"] == "write")
        assert by_id[write["parent_id"]]["name"] == "execute"
        keys = profile.STATEMENT_KEYS + (profile.WRITE_KEY,)
        assert p.stages[profile.WRITE_KEY] > 0
        assert sum(p.stages[k] for k in keys) == pytest.approx(
            p.seconds, abs=2e-5)
        assert p.stages["unattributed"] < p.seconds
        # a SELECT has no such key
        s.execute("SELECT COUNT(*) AS n FROM t")
        assert profile.WRITE_KEY not in s.last_profile.stages
        # and a write called on the table adds nothing to the ring
        assert t.insert(batch(100, base=10)).committed
        assert len(c.profiles) == n_profiles + 2
    finally:
        c.stop()


def test_profile_off_keeps_the_write_span_alone(monkeypatch):
    monkeypatch.setattr(resident, "RESIDENT_FORCE", True)
    monkeypatch.setattr(tracing, "PROFILE_FORCE", False)
    opened = []
    annotation = tracing.TraceAnnotation
    monkeypatch.setattr(
        tracing, "TraceAnnotation",
        lambda name: opened.append(name) or annotation(name))
    c, _s, t = make_table(2)
    try:
        n = len(c.tracer.finished)
        before = counters()
        assert t.insert(batch()).committed
        drain(t)
        new = c.tracer.finished[n:]
        assert [s.name for s in new] == ["write"]
        assert new[0].attrs == {} and not new[0].annotated
        assert opened == []
        d = delta(before, counters())
        w = "|component=write"
        assert d["rows" + w] == ROWS and d["portions" + w] == 2
        assert d["seconds" + w] == pytest.approx(new[0].seconds)
        assert d["visible_seconds_count" + w] == 1
        # the split stands still; the promotions still count, untimed
        assert not any(k.startswith(("stage_seconds", "promote_seconds"))
                       for k in d)
        assert d["promotions|component=resident"] == 2
        assert d["resident_lag_seconds_count|component=resident"] == 2
    finally:
        c.stop()


def test_declined_promotion_is_counted_and_takes_no_lag_sample(
        resident_on):
    c, _s, t = make_table(1)
    store = t.shards[0].resident
    gate = threading.Event()
    held = {"c": np.arange(8, dtype=np.int64)}

    def blocked():
        gate.wait(20.0)
        return held, None

    try:
        before = counters()
        for i in range(resident.MAX_INFLIGHT):
            assert store.promote_async(1000 + i, 8, blocked,
                                       committed_at=time.perf_counter())
        assert not store.promote_async(1000, 8, blocked)
        assert t.insert(batch()).committed
        enq = [s for s in c.tracer.finished
               if s.name == "write.promote.enqueue"][-1]
        assert enq.attrs == {"queued": 0,
                             "promote_declined": "inflight_full"}
        d = delta(before, counters())
        r = "component=resident"
        assert d[f"promote_declined|{r},reason=inflight_full"] == 1
        assert d[f"promote_declined|{r},reason=in_flight"] == 1
        assert f"resident_lag_seconds_count|{r}" not in d
        assert f"promotions|{r}" not in d
        # what was written is written: the portion is there, on the host
        assert t.shards[0].portions[1].num_rows == ROWS
        assert store.lookup(1, ("id",)) is None
    finally:
        gate.set()
    try:
        store.drain(timeout=30.0)
        d = delta(before, counters())
        assert d[f"promotions|{r}"] == resident.MAX_INFLIGHT
        assert d[f"resident_lag_seconds_count|{r}"] == resident.MAX_INFLIGHT
        assert d["lag_sum"] > 0
        assert store.snapshot()["inflight"] == 0
    finally:
        c.stop()


def test_disabled_tier_declines_every_eager_promotion(monkeypatch):
    monkeypatch.setattr(resident, "RESIDENT_FORCE", False)
    monkeypatch.setattr(tracing, "PROFILE_FORCE", True)
    c, _s, t = make_table(2)
    try:
        before = counters()
        assert t.insert(batch()).committed
        d = delta(before, counters())
        assert d["promote_declined|component=resident,reason=disabled"] == 2
        root = last_root(c, "write")
        tree = tree_of(c.tracer.spans_for(root.trace_id), root)
        assert "write.promote.enqueue" not in tree
    finally:
        c.stop()


def test_heat_promotion_reads_the_blob_under_the_callers_trace(
        resident_on):
    c, _s, t = make_table(1)
    try:
        assert t.insert(batch()).committed
        drain(t)
        shard = t.shards[0]
        shard.resident.clear()
        before = counters()
        with profile.profiled(tracer=c.tracer) as holder:
            assert shard.resident.promote_async(
                1, ROWS, resident.portion_loader(shard, shard.portions[1]))
            drain(t)
        names = {s["name"]: s for s in holder.profile.spans}
        assert names["resident.promote"]["attrs"]["source"] == "blob"
        assert "lag_s" not in names["resident.promote"]["attrs"]
        assert names["resident.promote.load"]["seconds"] > 0
        d = delta(before, counters())
        assert d["promotions|component=resident"] == 1
        assert "resident_lag_seconds_count|component=resident" not in d
        assert shard.resident.lookup(1, ("id", "v")) is not None
    finally:
        c.stop()


@pytest.mark.parametrize("upsert", ["on", "off"])
def test_compaction_opens_compact_over_the_same_portion_subtree(
        resident_on, upsert):
    c, _s, t = make_table(1, upsert)
    try:
        for i in range(3):
            assert t.insert(batch(2000, base=i * 1000)).committed
        drain(t)
        before = counters()
        shard = t.shards[0]
        shard.compact()
        drain(t)
        root = last_root(c, "compact")
        tree = tree_of(c.tracer.spans_for(root.trace_id), root)
        assert tree["compact"] == {"write.portion"}
        assert tree["write.portion"] == PORTION_TREE
        assert tree["write.promote.enqueue"] == {"resident.promote"}
        rows_out = 4000 if upsert == "on" else 6000
        assert root.attrs == {"shard": "t/0", "portions_in": 3,
                              "rows_in": 6000, "rows_out": rows_out}
        d = delta(before, counters())
        k = "|component=compact"
        assert d["runs" + k] == 1 and d["rows_in" + k] == 6000
        assert d["rows_out" + k] == rows_out
        assert d["seconds" + k] == pytest.approx(root.seconds)
        # a compaction is no write: its portions count, its time does not
        assert d["portions|component=write"] == 1
        assert "seconds|component=write" not in d
        assert not any(k.startswith("stage_seconds") for k in d)
    finally:
        c.stop()


def test_compile_counts_split_seconds_by_kind():
    import jax
    import jax.numpy as jnp

    before = tracing.compile_counts()
    jax.jit(lambda x: x * 3 + len(str(time.perf_counter_ns())))(
        jnp.arange(7)).block_until_ready()
    after = tracing.compile_counts()
    assert after["built"] + after["fetched"] \
        > before["built"] + before["fetched"]
    assert after["built_seconds"] + after["fetched_seconds"] \
        == pytest.approx(after["seconds"])
    assert after["seconds"] > before["seconds"]


def test_prometheus_page_serves_the_process_root_after_the_clusters(
        resident_on):
    from ydb_tpu.obs.viewer import Viewer

    c, s, t = make_table(1)
    try:
        assert t.insert(batch(100)).committed
        s.execute("SELECT COUNT(*) AS n FROM t")
        text = Viewer(c).render("/counters/prometheus", {})[0].decode()
        own = text.index('queries{component="kqp"')
        assert own < text.index('rows{component="write"}')
        assert 'stage_seconds{component="write",stage="sort"}' in text
        assert 'visible_seconds_count{component="write"}' in text
    finally:
        c.stop()


def test_the_three_unread_probes_are_gone():
    import ydb_tpu.engine.shard  # noqa: F401 - registers its probes
    from ydb_tpu.obs.probes import list_probes

    names = set(list_probes())
    assert "columnshard.commit" in names
    assert not names & {"resident.promote", "resident.evict",
                        "columnshard.compact"}


@pytest.mark.parametrize("background", [False, True])
def test_trace_breakdown_names_the_other_threads_work_in_a_gap(background):
    """``scripts/trace_breakdown.py --background``: a device-idle gap the
    statement's thread spends under ``dispatch`` is put down to what a
    conveyor worker was doing then as well."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "scripts" \
        / "trace_breakdown.py"
    spec = importlib.util.spec_from_file_location("trace_breakdown", path)
    tb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tb)
    lines = [
        [(0, 100, "ydb.query"), (10, 50, "ydb.dispatch"),
         (60, 90, "ydb.scan.pull")],
        [(20, 40, "ydb.resident.promote"),
         (25, 35, "ydb.resident.promote.put"),
         (55, 95, "ydb.compact")],
    ]
    busy = [(0, 15), (45, 65), (80, 100)]      # idle: 15-45 and 65-80
    got = tb.idle_by_span(busy, lines, 0, 100, background)
    if not background:
        assert got == {"ydb.dispatch": 30, "ydb.scan.pull": 15}
    else:
        assert got == {
            "ydb.dispatch": 10,
            "ydb.dispatch + ydb.resident.promote": 10,
            "ydb.dispatch + ydb.resident.promote.put": 10,
            "ydb.scan.pull": 15}    # a pull is the producer's to explain
    assert sum(got.values()) == 45
