"""DQ stage-graph + actor runtime tests on the simulated multi-node
runtime (tier-2: deterministic dispatch, virtual time, interceptors)."""

import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.dq import (
    HashPartition,
    ResultOutput,
    SourceInput,
    StageSpec,
    UnionAllInput,
    run_stage_graph,
)
from ydb_tpu.dq.spilling import Spiller
from ydb_tpu.engine.oracle import OracleTable, run_oracle
from ydb_tpu.engine.scan import ColumnSource
from ydb_tpu.runtime.actors import Actor, ActorSystem
from ydb_tpu.runtime.test_runtime import SimRuntime
from ydb_tpu.ssa import Agg, AggSpec, Call, Col, FilterStep, GroupByStep, Op
from ydb_tpu.ssa import twophase
from ydb_tpu.ssa.program import Program, ProjectStep, SortStep, lit


class Echo(Actor):
    def __init__(self, reply=False):
        super().__init__()
        self.got = []
        self.reply = reply

    def receive(self, message, sender):
        self.got.append(message)
        if self.reply and isinstance(message, int) and sender is not None:
            self.send(sender, message + 1)


def test_actor_system_basics():
    sys = ActorSystem()
    a, b = Echo(), Echo(reply=True)
    ida, idb = sys.register(a), sys.register(b)
    sys.send(idb, 41, sender=ida)
    sys.run()
    assert b.got == [41]
    assert a.got == [42]


def test_sim_runtime_virtual_time_and_interception():
    rt = SimRuntime(n_nodes=2)
    a, b = Echo(), Echo(reply=True)
    ida = rt.system(1).register(a)
    idb = rt.system(2).register(b)

    # cross-node send
    rt.system(1).send(idb, 1, sender=ida)
    rt.dispatch()
    assert b.got == [1] and a.got == [2]

    # scheduled message fires only after virtual time advances
    rt.system(2).schedule(5.0, idb, "tick")
    rt.dispatch()
    assert "tick" not in b.got
    rt.advance_time(5.0)
    rt.dispatch()
    assert "tick" in b.got

    # interceptor can drop messages (race/failure interleaving hook)
    rt.observer = lambda env: "drop" if env.message == "lost" else "pass"
    rt.system(1).send(idb, "lost")
    rt.system(1).send(idb, "kept")
    rt.dispatch()
    assert "lost" not in b.got and "kept" in b.got


def _make_sources(n_parts=4, rows=3000, seed=5):
    rng = np.random.default_rng(seed)
    sch = dtypes.schema(("k", dtypes.INT64), ("v", dtypes.INT64))
    parts = []
    all_cols = {"k": [], "v": []}
    for p in range(n_parts):
        cols = {
            "k": rng.integers(0, 50, rows // n_parts),
            "v": rng.integers(0, 1000, rows // n_parts),
        }
        parts.append(ColumnSource(
            {k: np.asarray(v) for k, v in cols.items()}, sch))
        for k in all_cols:
            all_cols[k].append(cols[k])
    merged = {k: np.concatenate(v) for k, v in all_cols.items()}
    return sch, parts, merged


AGG = Program((
    FilterStep(Call(Op.GE, Col("v"), lit(100))),
    GroupByStep(keys=("k",), aggs=(
        AggSpec(Agg.SUM, "v", "total"),
        AggSpec(Agg.COUNT_ALL, None, "n"),
    )),
    SortStep(keys=("k",)),
))


def _run_two_stage(runtime, sch, parts, window=4, quota=64 << 20,
                   channel_budget=None):
    """scan(partial agg) -> HashPartition(k) -> final agg -> result."""
    partial, final = twophase.split(AGG)
    # stage 0: partial agg per partition, shuffle by key
    s0 = StageSpec(
        program=partial, inputs=(SourceInput("t"),),
        output=HashPartition(("k",)), tasks=len(parts),
    )
    # stage 1: merge partials per key bucket
    s1 = StageSpec(
        program=None, inputs=(UnionAllInput(0),),
        output=HashPartition(("k",)), tasks=2,
        final_program=final,
    )
    # stage 2: gather buckets into the ordered result
    s2 = StageSpec(
        program=None, inputs=(UnionAllInput(1),),
        output=ResultOutput(), tasks=1,
        final_program=Program((SortStep(keys=("k",)),)),
    )
    return run_stage_graph(
        [s0, s1, s2], {"t": parts}, runtime,
        window=window, spill_quota_bytes=quota,
        channel_budget=channel_budget,
    )


def _count_spills(monkeypatch) -> list:
    """Every payload the spillers write to a blob, as it is written."""
    from ydb_tpu.dq import spilling

    spilled = []
    encode = spilling._encode
    monkeypatch.setattr(spilling, "_encode",
                        lambda p: (spilled.append(p), encode(p))[1])
    return spilled


def test_stage_graph_distributed_agg_matches_oracle():
    sch, parts, merged = _make_sources()
    rt = SimRuntime(n_nodes=3)
    res = _run_two_stage(rt, sch, parts)
    ora = run_oracle(AGG, OracleTable(
        {k: (v, np.ones(len(v), dtype=bool)) for k, v in merged.items()},
        sch,
    ))
    np.testing.assert_array_equal(res.cols["k"][0], ora.cols["k"][0])
    np.testing.assert_array_equal(res.cols["total"][0],
                                  ora.cols["total"][0])
    np.testing.assert_array_equal(res.cols["n"][0], ora.cols["n"][0])


def test_stage_graph_with_tiny_window_and_spilling(monkeypatch):
    """Credit window of 1 + zero memory quota: every parked block spills,
    results stay exact. The host path is asked for (a budget of 0): a
    device block parks on the chip, not in the spiller."""
    from ydb_tpu.engine.hbm import ChannelBudget

    spilled = _count_spills(monkeypatch)
    sch, parts, merged = _make_sources(n_parts=3, rows=1500)
    rt = SimRuntime(n_nodes=2)
    res = _run_two_stage(rt, sch, parts, window=1, quota=0,
                         channel_budget=ChannelBudget(0))
    assert spilled
    ora = run_oracle(AGG, OracleTable(
        {k: (v, np.ones(len(v), dtype=bool)) for k, v in merged.items()},
        sch,
    ))
    np.testing.assert_array_equal(res.cols["total"][0],
                                  ora.cols["total"][0])


def test_spiller_quota_and_roundtrip():
    sp = Spiller(mem_quota_bytes=100, prefix="s")
    small = {"a": np.arange(4, dtype=np.int64)}       # 32 bytes
    big = {"a": np.arange(100, dtype=np.int64)}       # 800 bytes -> spill
    s1 = sp.put(small)
    s2 = sp.put(big)
    assert sp.spill_count == 1
    np.testing.assert_array_equal(sp.get(s2)["a"], big["a"])
    np.testing.assert_array_equal(sp.get(s1)["a"], small["a"])
    with pytest.raises(KeyError):
        sp.get(s2)


def test_spiller_peek_does_not_consume():
    sp = Spiller(mem_quota_bytes=0, prefix="s")  # everything spills
    sid = sp.put({"a": np.arange(8, dtype=np.int64)})
    np.testing.assert_array_equal(sp.peek(sid)["a"], np.arange(8))
    np.testing.assert_array_equal(sp.peek(sid)["a"], np.arange(8))
    np.testing.assert_array_equal(sp.get(sid)["a"], np.arange(8))
    with pytest.raises(KeyError):
        sp.peek(sid)


def test_aggregate_accumulation_spills_beyond_quota(monkeypatch):
    """Operator spilling (SURVEY §2.9 spilling-interface row): an agg
    stage's accumulated partial states live in the spiller, so a zero
    quota forces them to blobs while results stay exact. The host path
    is asked for (a budget of 0): on the chip the partials stay there."""
    from ydb_tpu.engine.hbm import ChannelBudget

    spilled = _count_spills(monkeypatch)
    sch, parts, merged = _make_sources(n_parts=3, rows=900)
    rt = SimRuntime(n_nodes=1)
    handle_res = _run_two_stage(rt, sch, parts, window=4, quota=0,
                                channel_budget=ChannelBudget(0))
    assert spilled
    ora = run_oracle(AGG, OracleTable(
        {k: (v, np.ones(len(v), dtype=bool)) for k, v in merged.items()},
        sch,
    ))
    np.testing.assert_array_equal(handle_res.cols["total"][0],
                                  ora.cols["total"][0])


def test_filter_map_stage_without_agg():
    sch, parts, merged = _make_sources(n_parts=2, rows=400)
    prog = Program((
        FilterStep(Call(Op.GE, Col("v"), lit(900))),
        ProjectStep(("k", "v")),
    ))
    rt = SimRuntime(n_nodes=2)
    s0 = StageSpec(program=prog, inputs=(SourceInput("t"),),
                   output=ResultOutput(), tasks=1)
    # single-task result stage reading the source directly
    res = run_stage_graph([s0], {"t": [parts[0]]}, rt)
    ora = run_oracle(prog, OracleTable(
        {k: (v[: 200], np.ones(200, dtype=bool))
         for k, v in merged.items()}, sch))
    assert res.num_rows == ora.num_rows


def test_source_partitions_differ_from_task_count():
    """Strided partition assignment: every partition is read exactly once
    whether tasks < partitions or tasks > partitions."""
    sch, parts, merged = _make_sources(n_parts=4, rows=2000)
    total = int(merged["v"].sum())
    prog = Program((GroupByStep(keys=(), aggs=(
        AggSpec(Agg.SUM, "v", "total"),)),))
    partial, final = twophase.split(prog)
    for tasks in (2, 3, 4, 6):
        rt = SimRuntime(n_nodes=2)
        s0 = StageSpec(program=partial, inputs=(SourceInput("t"),),
                       output=HashPartition(()), tasks=tasks)
        s1 = StageSpec(program=None, inputs=(UnionAllInput(0),),
                       output=ResultOutput(), tasks=1,
                       final_program=final)
        res = run_stage_graph([s0, s1], {"t": parts}, rt)
        assert int(res.cols["total"][0][0]) == total, tasks


def test_multi_consumer_stage_gets_full_stream():
    """A producer feeding two consumer stages must route the FULL stream
    to each (per-consumer channel groups), not split it across them."""
    sch, parts, merged = _make_sources(n_parts=2, rows=1000)
    total = int(merged["v"].sum())
    keyless = Program((GroupByStep(keys=(), aggs=(
        AggSpec(Agg.SUM, "v", "total"),)),))
    _, final = twophase.split(keyless)
    rt = SimRuntime(n_nodes=2)
    s0 = StageSpec(program=None, inputs=(SourceInput("t"),),
                   output=HashPartition(("k",)), tasks=2)
    # two independent consumers of stage 0, same output schema
    s1 = StageSpec(program=None, inputs=(UnionAllInput(0),),
                   output=HashPartition(()), tasks=2,
                   final_program=keyless)
    s2 = StageSpec(program=None, inputs=(UnionAllInput(0),),
                   output=HashPartition(()), tasks=1,
                   final_program=keyless)
    # result merges both totals: 2x the table sum iff each consumer saw
    # every row
    s3 = StageSpec(program=None, inputs=(UnionAllInput(1), UnionAllInput(2)),
                   output=ResultOutput(), tasks=1,
                   final_program=final)
    res = run_stage_graph([s0, s1, s2, s3], {"t": parts}, rt)
    assert int(res.cols["total"][0][0]) == 2 * total


def test_multi_input_schema_mismatch_raises():
    sch, parts, merged = _make_sources(n_parts=2, rows=200)
    rt = SimRuntime(n_nodes=1)
    s0 = StageSpec(program=Program((ProjectStep(("k",)),)),
                   inputs=(SourceInput("t"),),
                   output=HashPartition(("k",)), tasks=1)
    s1 = StageSpec(program=Program((ProjectStep(("v",)),)),
                   inputs=(SourceInput("t"),),
                   output=HashPartition(("v",)), tasks=1)
    s2 = StageSpec(program=None, inputs=(UnionAllInput(0), UnionAllInput(1)),
                   output=ResultOutput(), tasks=1)
    with pytest.raises(ValueError, match="share one schema"):
        run_stage_graph([s0, s1, s2], {"t": parts}, rt)


# ---- channels on the chip against channels through the host ----

from ydb_tpu.blocks.block import Column  # noqa: E402
from ydb_tpu.engine import hbm  # noqa: E402
from ydb_tpu.engine.hbm import ChannelBudget  # noqa: E402
from ydb_tpu.dq.graph import JoinSpec, UnionAll  # noqa: E402
from ydb_tpu.obs.counters import root_counters  # noqa: E402

PROBE = dtypes.schema(("a", dtypes.INT64), ("b", dtypes.INT64),
                      ("v", dtypes.INT64))
BUILD = dtypes.schema(("a", dtypes.INT64), ("b", dtypes.INT64),
                      ("w", dtypes.INT64))


def _join_tables(probe_rows=300, build_rows=40, seed=11, spread=1):
    """Probe keys with NULLs in both key columns; build ``a`` unique (a
    lookup join's side), ``b`` repeated with NULLs (an expanding one's);
    ``a`` on both sides ``spread`` apart."""
    rng = np.random.default_rng(seed)
    probe = {"a": rng.integers(0, 60, probe_rows) * spread,
             "b": rng.integers(0, 5, probe_rows),
             "v": rng.integers(0, 1000, probe_rows)}
    pval = {"a": rng.random(probe_rows) > 0.1,
            "b": rng.random(probe_rows) > 0.1,
            "v": np.ones(probe_rows, bool)}
    build = {"a": np.arange(build_rows, dtype=np.int64) * spread,
             "b": np.arange(build_rows, dtype=np.int64) % 5,
             "w": rng.integers(0, 1000, build_rows)}
    bval = {"a": np.ones(build_rows, bool),
            "b": rng.random(build_rows) > 0.2,
            "w": np.ones(build_rows, bool)}

    def parts(cols, valid, sch):
        return [ColumnSource({k: v[p::2] for k, v in cols.items()}, sch,
                             None, {k: v[p::2] for k, v in valid.items()})
                for p in range(2)]

    return parts(probe, pval, PROBE), parts(build, bval, BUILD)


JOINS = {
    "lookup_inner": JoinSpec(("a",), ("a",), payload=("w",)),
    "lookup_left": JoinSpec(("a",), ("a",), payload=("w",), kind="left"),
    "lookup_semi": JoinSpec(("a",), ("a",), kind="semi"),
    "lookup_anti": JoinSpec(("a",), ("a",), kind="anti"),
    "expand_inner": JoinSpec(("b",), ("b",), probe_payload=("a", "v"),
                             build_payload=("w",), expand=True),
    "expand_left": JoinSpec(("b",), ("b",), probe_payload=("a", "v"),
                            build_payload=("w",), kind="left", expand=True),
    "two_keys": JoinSpec(("a", "b"), ("a", "b"), payload=("w",)),
}

GROUPED = Program((
    GroupByStep(keys=("w",), aggs=(AggSpec(Agg.SUM, "v", "total"),
                                   AggSpec(Agg.COUNT_ALL, None, "n"))),
    SortStep(keys=("w",)),
))


def _join_stages(j: JoinSpec, final: Program | None = None):
    """Both sides scanned by two tasks and hash-partitioned on their
    keys, joined by two tasks, the output gathered by one task: in
    arrival order, or grouped by ``final`` in two phases."""
    partial = None
    if final is not None:
        partial, final = twophase.split(final)
    return [
        StageSpec(program=None, inputs=(SourceInput("p"),),
                  output=HashPartition(j.probe_keys), tasks=2),
        StageSpec(program=None, inputs=(SourceInput("b"),),
                  output=HashPartition(j.build_keys), tasks=2),
        StageSpec(program=None, inputs=(UnionAllInput(0), UnionAllInput(1)),
                  output=UnionAll(), tasks=2, join=j),
        StageSpec(program=partial, inputs=(UnionAllInput(2),),
                  output=ResultOutput(), tasks=1, final_program=final),
    ]


def _channel_counters() -> dict:
    g = root_counters().group(component="dq")
    out = {p: g.group(path=p).counter("channel_rows").value
           for p in ("device", "host")}
    out.update({r: g.group(reason=r).counter("channel_host_reason").value
                for r in ("remote", "checkpoint", "budget", "result")})
    return out


def _run_join(stages, tables, runtime=None, budget=None, **kw):
    """The graph's answer, the budget its channel blocks held HBM
    against (the process's unless given) and the channel counters'
    movement: rows by path, host reasons."""
    probe, build = tables
    budget = hbm.channels() if budget is None else budget
    before = _channel_counters()
    res = run_stage_graph(stages, {"p": probe, "b": build},
                          runtime or ActorSystem(), block_rows=32,
                          channel_budget=budget, **kw)
    after = _channel_counters()
    return res, budget, {k: after[k] - before[k] for k in after}


def _assert_same(got: OracleTable, want: OracleTable):
    assert got.schema.names == want.schema.names
    assert got.num_rows == want.num_rows
    for c in want.schema.names:
        np.testing.assert_array_equal(got.cols[c][1], want.cols[c][1],
                                      err_msg=c)
        np.testing.assert_array_equal(got.cols[c][0], want.cols[c][0],
                                      err_msg=c)


@pytest.mark.parametrize("case", sorted(JOINS) + ["empty_bucket",
                                                  "grouped"])
def test_device_channels_give_the_host_channels_answer(case):
    """Every join shape, NULL keys, a two-column key, a build side whose
    one row leaves a task's bucket empty, and a group-by over a join:
    the same rows in the same order whether the channels carry device
    blocks or (budget 0) host payloads."""
    tables = _join_tables(build_rows=1 if case == "empty_bucket" else 40)
    j = JOINS.get(case, JOINS["lookup_inner"])
    stages = _join_stages(j, GROUPED if case == "grouped" else None)
    dev, dev_budget, dev_moved = _run_join(stages, tables)
    host, host_budget, host_moved = _run_join(stages, tables,
                                              budget=ChannelBudget(0))
    _assert_same(dev, host)
    if case not in ("lookup_anti", "empty_bucket"):
        assert dev.num_rows > 0
    # every channel row but the result's rode the chip, and none did
    # with a budget of 0; both runs moved the same rows, and gave back
    # every byte their blocks held (the first the process's budget)
    assert dev_budget is hbm.channels()
    assert dev_moved["device"] > 0 and host_moved["device"] == 0
    assert dev_moved["host"] == dev.num_rows and dev_moved["result"] > 0
    assert (dev_moved["device"] + dev_moved["host"]
            == host_moved["device"] + host_moved["host"])
    assert host_moved["budget"] > 0 and dev_moved["budget"] == 0
    assert dev_budget.held == 0 and host_budget.held == 0


def test_a_checkpointed_graph_keeps_its_channels_on_the_host():
    from ydb_tpu.dq.checkpoint import CheckpointStorage
    from ydb_tpu.engine.blobs import MemBlobStore

    tables = _join_tables()
    stages = _join_stages(JOINS["lookup_left"])
    dev, _, _ = _run_join(stages, tables)
    ckpt, _, moved = _run_join(
        stages, tables,
        checkpoint_storage=CheckpointStorage(MemBlobStore(), "g"))
    _assert_same(ckpt, dev)
    assert moved["device"] == 0
    assert moved["checkpoint"] > 0


def test_a_budget_sends_what_passes_it_through_the_host():
    """A budget of a few blocks: the first channel blocks stay on the
    chip, those that would pass it go as host payloads, and the answer
    is the same."""
    tables = _join_tables()
    stages = _join_stages(JOINS["expand_inner"])
    whole, _, whole_moved = _run_join(stages, tables)
    small, budget, moved = _run_join(stages, tables,
                                     budget=ChannelBudget(6000))
    _assert_same(small, whole)
    assert 0 < budget.peak <= 6000 and budget.held == 0
    assert moved["device"] > 0 and moved["budget"] > 0
    assert (moved["device"] + moved["host"]
            == whole_moved["device"] + whole_moved["host"])


def test_local_and_remote_producers_feed_one_consumer():
    """Three nodes: a consumer hears from producers on its own node
    (device blocks) and on the others (host payloads); the routing hash
    is one function on both paths, so every row meets its match."""
    tables = _join_tables()
    stages = _join_stages(JOINS["lookup_inner"], GROUPED)
    want, _, _ = _run_join(stages, tables, budget=ChannelBudget(0))
    got, _, moved = _run_join(stages, tables, runtime=SimRuntime(3))
    _assert_same(got, want)
    assert moved["device"] > 0 and moved["remote"] > 0


def _lookups() -> dict:
    g = root_counters().group(component="join")
    return {p: g.group(path=p).counter("lookups").value
            for p in ("direct", "search")}


@pytest.mark.parametrize("spread, path", [(1, "direct"), (1000, "search")])
def test_a_lookup_join_stage_counts_and_says_its_path(spread, path):
    """Dense build keys (0..39) take the direct table, the same keys
    1,000 apart (a span past the table's 4,096 slots) the search: each
    join task counts ``component=join`` ``lookups{path}``, untraced as
    traced, and says ``lookup=`` on its ``dispatch`` span; the answers
    are the same rows."""
    from ydb_tpu.obs import tracing

    stages = _join_stages(JOINS["lookup_inner"])
    before = _lookups()
    untraced, _, _ = _run_join(stages, _join_tables(spread=spread))
    assert untraced.num_rows > 0
    tracer = tracing.Tracer()
    root = tracer.trace("query")
    with tracing.activate(root):
        traced, _, _ = _run_join(stages, _join_tables(spread=spread))
    root.finish()
    _assert_same(traced, untraced)
    after = _lookups()
    assert {p: after[p] - before[p] for p in after} == {
        "direct": 4 * (path == "direct"), "search": 4 * (path == "search")}
    joins = [sp.attrs for sp in tracer.spans_for(root.trace_id)
             if sp.name == "dispatch" and "join" in sp.attrs]
    assert [a["lookup"] for a in joins] == [path, path]
    assert {a["program"] for a in joins} == {"dq_stage"}


@pytest.mark.parametrize("native_lib", [True, False])
def test_the_device_hash_is_the_host_hash(monkeypatch, native_lib):
    """``parallel/shuffle.hash_rows`` and ``native.hash_rows`` give the
    same bits on int64 keys with NULLs: a row routes to one consumer
    whichever side hashed it."""
    import jax.numpy as jnp

    from ydb_tpu import native
    from ydb_tpu.parallel.shuffle import hash_rows

    if not native_lib:
        monkeypatch.setattr(native, "_lib", False)
    rng = np.random.default_rng(7)
    keys = [rng.integers(-2**62, 2**62, 4096), rng.integers(0, 9, 4096)]
    valid = [rng.random(4096) > 0.2, rng.random(4096) > 0.5]
    dev = hash_rows([Column(jnp.asarray(k), jnp.asarray(v))
                     for k, v in zip(keys, valid)])
    np.testing.assert_array_equal(np.asarray(dev),
                                  native.hash_rows(keys, valid))


def _device_part(rows: int, seed: int):
    """A channel block of ``rows`` probe rows cut to their shape class,
    as a split hands it on, and the same rows as a host payload."""
    from ydb_tpu.blocks.block import TableBlock
    from ydb_tpu.dq.compute import _hold
    from ydb_tpu.ssa.plan_fuse import shape_class

    rng = np.random.default_rng(seed)
    cols = {n: rng.integers(1, 1000, rows) for n in PROBE.names}
    valid = {n: rng.random(rows) > 0.1 for n in PROBE.names}
    block = TableBlock.from_numpy(cols, PROBE, valid, shape_class(rows))
    payload = dict(cols)
    payload.update({f"__v_{n}": v for n, v in valid.items()})
    return _hold(ChannelBudget(None), block, rows), payload


def test_a_bucket_packs_with_its_shape_classes_programs():
    """Buckets of one total in other row splits, each part at the same
    shape class, pack with the programs the first built: nothing
    compiles for a new split, not where a part's padding runs past the
    bucket (1,500 rows then 300 in 2,048 slots, the second part's 1,024
    from slot 1,500) nor for a host part among device parts. The bucket
    is the host's concatenation row for row, zeros behind it."""
    from ydb_tpu.dq import compute

    sizes = []
    for split in ((1500, 300, 0), (1600, 150, 50)):
        (d0, p0), (d1, p1), (d2, p2) = (
            _device_part(r, seed) for seed, r in enumerate(split, 1))
        items = [d0, p1, d2] if split[2] else [d0, p1]
        packed = compute._pack(items, PROBE)
        want = compute._assemble([p0, p1, p2][:len(items)], PROBE)
        assert packed.capacity == 2048 and int(packed.length) == 1800
        for n in PROBE.names:
            got, ok = (np.asarray(x) for x in (packed.columns[n].data,
                                                packed.columns[n].validity))
            np.testing.assert_array_equal(
                got[:1800], np.asarray(want.columns[n].data)[:1800])
            np.testing.assert_array_equal(
                ok[:1800], np.asarray(want.columns[n].validity)[:1800])
            assert not got[1800:].any() and not ok[1800:].any()
        sizes.append([f._cache_size() for f in
                      (compute._zeros, compute._place, compute._cut)])
    assert sizes[1] == sizes[0]


def test_a_dropped_channel_block_gives_its_bytes_back():
    """A channel block gives its bytes back once, where it is consumed
    or, in a graph torn down with blocks parked or in flight, when it is
    dropped."""
    budget = ChannelBudget(None)
    held, _ = _device_part(700, 3)
    from ydb_tpu.dq.compute import _hold

    kept = _hold(budget, held.block, 700)
    dropped = _hold(budget, held.block, 700)
    assert budget.held == 2 * kept.nbytes > 0
    kept.release()
    kept.release()
    assert budget.held == kept.nbytes
    del dropped
    assert budget.held == 0 and budget.peak == 2 * kept.nbytes
