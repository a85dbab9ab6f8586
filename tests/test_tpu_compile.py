"""The main path's kernels and scan programs, compiled for a described
v5e at the sizes the SQL path really uses.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (on-chip-measurement guide, section 2), so
what interpret mode cannot show — a Mosaic refusal, a program whose
temporaries do not fit beside a resident table — costs no chip time.
Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library, and every xdist
worker imports every test file.
"""

import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from ydb_tpu.engine.scan import DEFAULT_BLOCK_ROWS, ColumnSource, ScanExecutor
from ydb_tpu.engine.shard import ShardConfig
from ydb_tpu.ssa import pallas_kernels
from ydb_tpu.workload import tpch

#: one v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16e9
#: the share of it one program's temporaries may take: engine/hbm.py
#: leaves 3/8 of the device to temporaries, staging and results
TEMP_SHARE = 1 / 4
GROUPS = (513, 1024, 2048)
DTYPES = ("float32", "int32")
#: the slot count of a wide fused bank (Q1 stacks 5 + 4 + 6)
FUSED_SLOTS = 15


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it off round these."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile_grouped_sum_multi(dtype, groups, one_chip):
    n = DEFAULT_BLOCK_ROWS
    return pallas_kernels.grouped_sum_multi.lower(
        _shape((n, FUSED_SLOTS), dtype, one_chip),
        _shape((n,), "int32", one_chip), num_groups=groups).compile()


def test_block_size_is_the_one_the_path_uses():
    assert ShardConfig().scan_block_rows == DEFAULT_BLOCK_ROWS == 1 << 20


@pytest.mark.parametrize("groups", GROUPS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_grouped_sum_multi_admitted_iff_it_compiles(
        dtype, groups, one_chip, no_persistent_cache):
    """``supported_fused`` admits exactly what the chip's compiler
    takes: float32 compiles, int32 is refused by Mosaic (its
    ``tpu.matmul`` takes no int32 operands) and must not be admitted."""
    admitted = pallas_kernels.supported_fused(dtype, groups, FUSED_SLOTS)
    try:
        compiled = _compile_grouped_sum_multi(dtype, groups, one_chip)
    except Exception as e:  # noqa: BLE001 - MosaicError is not public
        assert "Mosaic" in f"{type(e).__name__}: {e}", e
        assert not admitted, \
            f"supported_fused admits {dtype} x {groups}, Mosaic refuses"
        return
    assert "tpu_custom_call" in compiled.as_text()
    assert admitted, \
        f"{dtype} x {groups} compiles but supported_fused rejects it"


def test_supported_fused_rejects_what_mosaic_refuses():
    for groups in GROUPS:
        assert not pallas_kernels.supported_fused(
            jnp.int32, groups, FUSED_SLOTS)
        assert pallas_kernels.supported_fused(
            jnp.float32, groups, FUSED_SLOTS)
    assert not pallas_kernels.supported_fused(jnp.float32, 2049, 4)
    assert not pallas_kernels.supported_fused(
        jnp.float32, 1024, pallas_kernels.MAX_FUSED_SLOTS + 1)


def _pushed_down(sql_file: str, data):
    """The program the SQL path hands the scan: the statement as the
    benchmark sends it, its aggregating Transform pushed into the scan
    by the walk (plan/executor.py), with the Transform's aliases."""
    from ydb_tpu.plan import executor as plan_executor
    from ydb_tpu.sql.parser import parse
    from ydb_tpu.sql.planner import Catalog, plan_select_full

    sql = (pathlib.Path(__file__).resolve().parents[1] / "bench"
           / "statements" / sql_file).read_text()
    catalog = Catalog(schemas={t: data.schema(t) for t in data.tables},
                      primary_keys=dict(tpch.PRIMARY_KEYS),
                      dicts=data.dicts)
    plan = plan_select_full(parse(sql), catalog, None).plan
    pushed = plan_executor._pushdown_scan(plan, set())
    return pushed.program, dict(plan.dict_aliases)


@pytest.mark.parametrize("query", ("q1", "q6", "q1.sql", "q6.sql"))
def test_scan_partial_fits_beside_a_resident_table(
        query, one_chip, no_persistent_cache):
    """The pushdown partial program of Q1/Q6 over one scan block (the
    engine tier's hand-made programs, and what the walk composes from
    the benchmark's SQL text): at ``scan_block_rows`` its temporaries
    stay under TEMP_SHARE of the chip."""
    cap = 1 << 12
    data = tpch.TpchData(sf=0.001, seed=5)
    src = ColumnSource(columns=data.tables["lineitem"],
                       schema=tpch.LINEITEM_SCHEMA, dicts=data.dicts)
    if query.endswith(".sql"):
        program, aliases = _pushed_down(query, data)
    else:
        program = {"q1": tpch.q1_program, "q6": tpch.q6_program}[query]()
        aliases = None
    ex = ScanExecutor(program, src, block_rows=cap, dict_aliases=aliases)
    assert ex.folds_partials
    block = next(iter(src.blocks(cap, ex.read_cols)))
    rows = ShardConfig().scan_block_rows

    def described(x):
        x = np.asarray(x) if not hasattr(x, "shape") else x
        shape = tuple(rows if d == cap else d for d in x.shape)
        return _shape(shape, x.dtype, one_chip)

    args = jax.tree_util.tree_map(
        described, (block, dict(ex.partial.aux)))
    compiled = jax.jit(ex.partial.run).lower(*args).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_SHARE * V5E_HBM_BYTES, (query, temp)
    # the block is aggregated under its filter mask: nothing sorts,
    # gathers or scatters over its rows (kernels.compact would)
    moved = [ln.strip()[:120] for ln in compiled.as_text().splitlines()
             if re.search(r"\b(sort|gather|scatter)\(", ln)
             and f"[{rows}]" in ln]
    assert not moved, (query, moved[:3])


@pytest.mark.parametrize("which", ("first_block", "later_block"))
@pytest.mark.parametrize("query", ("q1.sql", "q6.sql"))
def test_mesh_walk_block_program_moves_no_rows(
        query, which, one_chip, no_persistent_cache):
    """What the mesh walk's aggregate pushdown enqueues a resident
    block on the block's own chip (parallel/dist.py, MeshScan): the
    slot-aligned partial of the benchmark's statement, and for every
    block after a shard's first the fold into the shard's state in the
    same program. As on the one-chip walk the block is aggregated under
    its filter mask: nothing sorts, gathers or scatters over its rows,
    and the temporaries fit beside a resident slice."""
    from ydb_tpu.parallel.dist import MeshScan

    cap = 1 << 12
    data = tpch.TpchData(sf=0.001, seed=5)
    src = ColumnSource(columns=data.tables["lineitem"],
                       schema=tpch.LINEITEM_SCHEMA, dicts=data.dicts)
    program, aliases = _pushed_down(query, data)
    scan = MeshScan(program, tpch.LINEITEM_SCHEMA, data.dicts,
                    dict_aliases=aliases)
    assert scan.folds_partials
    block = next(iter(src.blocks(cap, scan.read_cols)))
    aux = dict(scan.partial.aux)
    state = jax.eval_shape(scan._first_jit, block, aux)
    rows = ShardConfig().scan_block_rows

    def described(x):
        x = np.asarray(x) if not hasattr(x, "shape") else x
        shape = tuple(rows if d == cap else d for d in x.shape)
        return _shape(shape, x.dtype, one_chip)

    if which == "first_block":
        lowered = scan._first_jit.lower(
            *jax.tree_util.tree_map(described, (block, aux)))
    else:
        lowered = scan._fold_jit.lower(
            *jax.tree_util.tree_map(described, (state, block, aux)))
    compiled = lowered.compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_SHARE * V5E_HBM_BYTES, (query, which, temp)
    moved = [ln.strip()[:120] for ln in compiled.as_text().splitlines()
             if re.search(r"\b(sort|gather|scatter)\(", ln)
             and f"[{rows}]" in ln]
    assert not moved, (query, which, moved[:3])
    # the state the next block's program takes is a handful of slots
    assert all(leaf.shape[0] == 1 and leaf.size <= 1024
               for leaf in jax.tree_util.tree_leaves(state))


@pytest.mark.parametrize("query", ("q1.sql", "q6.sql"))
def test_block_assembly_moves_no_rows(query, one_chip,
                                      no_persistent_cache):
    """What the resident tier enqueues for a block that is not one whole
    portion (engine/resident.py, ``_assemble``): at ``scan_block_rows``,
    over the columns the benchmark's statement reads (Q1 seven, Q6
    four), cut from two pieces as on the four-chip deployment (a
    portion just over a block held at the next granule, and a short
    tail). Each piece is read as one window: nothing sorts, gathers or
    scatters over the block's rows, and the temporaries fit beside a
    resident slice."""
    from ydb_tpu.engine import resident

    data = tpch.TpchData(sf=0.001, seed=5)
    program, aliases = _pushed_down(query, data)
    src = ColumnSource(columns=data.tables["lineitem"],
                       schema=tpch.LINEITEM_SCHEMA, dicts=data.dicts)
    names = ScanExecutor(program, src, block_rows=1 << 12,
                         dict_aliases=aliases).read_cols
    assert len(names) == {"q1.sql": 7, "q6.sql": 4}[query]
    dtypes = [tpch.LINEITEM_SCHEMA.field(n).type.physical for n in names]
    rows = ShardConfig().scan_block_rows
    held = (resident.resident_rows(rows + 1600),
            resident.resident_rows(305_500))
    assert held[0] == rows + resident.GRANULE
    assert rows % resident.GRANULE == 0
    datas = tuple(tuple(_shape((n,), dt, one_chip) for dt in dtypes)
                  for n in held)
    valids = tuple(tuple(_shape((n,), "bool", one_chip) for _ in dtypes)
                   for n in held)
    compiled = resident._assemble.lower(
        datas, valids, _shape((2, 3), "int32", one_chip),
        cap=rows).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_SHARE * V5E_HBM_BYTES, (query, temp)
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"\b(sort|gather|scatter)\(", ln)
             and f"[{rows}]" in ln]
    assert not moved, (query, moved[:3])
    assert "ydb.device_blocks" in text


#: the exchange's first bucket at SF 1's `lineitem` on four chips:
#: 1.5 x the mean a device (parallel/mesh_exec.py)
EXCHANGE_ROWS = 1_572_864


@pytest.mark.parametrize("rows", (DEFAULT_BLOCK_ROWS, EXCHANGE_ROWS))
def test_compact_moves_a_block_without_a_sort_or_a_gather(
        rows, one_chip, no_persistent_cache, capsys):
    """``kernels.compact`` over ``lineitem``'s Q3 columns (three int64,
    one int32, their validities) at the scan's block and at the
    exchange's: a prefix count and a round of shift-and-select a bit of
    the capacity (PR 36). Nothing sorts, gathers or scatters over the
    block's rows, and the temporaries fit beside a resident table."""
    import time

    from ydb_tpu.blocks.block import Column, TableBlock
    from ydb_tpu.ssa import kernels

    names = ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate")
    schema = tpch.LINEITEM_SCHEMA.select(names)
    block = TableBlock(
        {n: Column(_shape((rows,), schema.field(n).type.physical, one_chip),
                   _shape((rows,), "bool", one_chip)) for n in names},
        _shape((), "int32", one_chip), schema)
    assert [str(c.data.dtype) for c in block.columns.values()] == [
        "int64", "int64", "int64", "int32"]
    t0 = time.perf_counter()
    compiled = jax.jit(kernels.compact).lower(
        block, _shape((rows,), "bool", one_chip)).compile()
    with capsys.disabled():
        print(f"\ncompact at {rows} rows compiled for a described v5e in "
              f"{time.perf_counter() - t0:.1f} s")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_SHARE * V5E_HBM_BYTES, (rows, temp)
    text = compiled.as_text()
    moved = [ln.strip()[:120] for ln in text.splitlines()
             if re.search(r"\b(sort|gather|scatter)\(", ln)]
    assert not moved, (rows, moved[:3])
    assert "ydb.compact" in text


#: a task's probe side of TPC-DS q7's first lookup join (`promotion`,
#: 1,000 rows) in `tpcds-store.star`: the shape class of 18.0M rows
STAR_PROBE_ROWS = 20_971_520


def test_a_star_lookup_compiles_its_direct_table(one_chip,
                                                 no_persistent_cache,
                                                 capsys):
    """q7's first join as a task runs it: 20.97M fact slots of five
    int64 columns probe 1,024 promotion slots on one key. Both branches
    compile, the direct table (a scatter into 2^21 slots, one gather a
    probe row) beside the search, and the temporaries fit beside a
    resident table."""
    import time

    from ydb_tpu import dtypes
    from ydb_tpu.blocks.block import Column, TableBlock
    from ydb_tpu.ssa import join as jk

    def side(rows, names):
        return TableBlock(
            {n: Column(_shape((rows,), "int64", one_chip),
                       _shape((rows,), "bool", one_chip)) for n in names},
            _shape((), "int32", one_chip),
            dtypes.schema(*[(n, dtypes.INT64) for n in names]))

    probe = side(STAR_PROBE_ROWS, ("ss_promo_sk", "ss_item_sk",
                                   "ss_cdemo_sk", "ss_sold_date_sk",
                                   "ss_quantity"))
    build = side(1024, ("p_promo_sk", "p_channel_email"))
    t0 = time.perf_counter()
    compiled = jk._lookup_join_of_kind.lower(
        probe, build, ("ss_promo_sk",), ("p_promo_sk",),
        ("p_channel_email",), "", "inner").compile()
    with capsys.disabled():
        print(f"\na star lookup at {STAR_PROBE_ROWS} x 1024 slots compiled"
              f" for a described v5e in {time.perf_counter() - t0:.1f} s")
    assert jk._direct_slots([build.columns["p_promo_sk"]], STAR_PROBE_ROWS,
                            1024) == jk.DIRECT_SLOTS_CAP
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_SHARE * V5E_HBM_BYTES, temp
    text = compiled.as_text()
    assert "conditional(" in text and "scatter(" in text
    assert "ydb.direct_lookup" in text and "ydb.sorted_build" in text


#: the Transform's capacity over ClickBench's `hits` on one chip: the
#: shape class of 12.5M rows (PERF.md section 5)
HITS_CAPACITY = 12_582_912


def test_a_top_10_moves_ten_rows_of_a_hits_sized_block(
        one_chip, no_persistent_cache, capsys):
    """ClickBench Q15's ``order by c desc, UserID limit 10`` over the
    group-by's 12.58M slots (two int64 keys, their validities, the live
    flag): ``kernels.sort_block`` selects its ten rows (PR 38). No sort,
    gather or scatter is as long as the block, and the selection's words
    fit beside the resident table."""
    import time

    from ydb_tpu import dtypes
    from ydb_tpu.blocks.block import Column, TableBlock
    from ydb_tpu.ssa import kernels

    rows = HITS_CAPACITY
    schema = dtypes.schema(("UserID", dtypes.INT64), ("c", dtypes.INT64))
    block = TableBlock(
        {n: Column(_shape((rows,), "int64", one_chip),
                   _shape((rows,), "bool", one_chip))
         for n in schema.names},
        _shape((), "int32", one_chip), schema)
    assert kernels.sort_tier(10, rows, [jnp.int64, jnp.int64]) == "select"

    def top10(block, live):
        return kernels.sort_block(block, ["c", "UserID"], [True, False], 10,
                                  live=live)

    t0 = time.perf_counter()
    compiled = jax.jit(top10).lower(
        block, _shape((rows,), "bool", one_chip)).compile()
    with capsys.disabled():
        print(f"\na top-10 over {rows} slots compiled for a described v5e "
              f"in {time.perf_counter() - t0:.1f} s")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_SHARE * V5E_HBM_BYTES / 8, temp
    text = compiled.as_text()
    assert "ydb.sort_block" in text
    long = [ln.strip()[:140] for ln in text.splitlines()
            if re.search(r"\b(sort|gather|scatter)\(", ln)
            and str(rows) in ln.split(" gather(")[0].split(" scatter(")[0]]
    assert not long, long[:3]


def test_a_hits_sized_group_by_takes_its_keys_from_the_segment_heads(
        one_chip, no_persistent_cache, capsys):
    """ClickBench Q16's ``group by UserID, SearchPhrase`` with a count
    over 12.58M slots: the output's keys are the sorted keys compacted
    at the segment heads (PR 40). No ``ydb.scatter_first`` is left of
    the 12.58M-row scatters a key column; what scatters is
    ``group_ids_sorted``'s inverse permutation and the reduce's add, and
    the program's temporaries fit beside the resident table."""
    import time

    from ydb_tpu import dtypes
    from ydb_tpu.blocks.block import Column, TableBlock
    from ydb_tpu.ssa import AggSpec, GroupByStep, Program, compile_program
    from ydb_tpu.ssa.ops import Agg

    rows = HITS_CAPACITY
    schema = dtypes.schema(("UserID", dtypes.INT64),
                           ("SearchPhrase", dtypes.INT32))
    block = TableBlock(
        {f.name: Column(_shape((rows,), f.type.physical, one_chip),
                        _shape((rows,), "bool", one_chip))
         for f in schema.fields},
        _shape((), "int32", one_chip), schema)
    cp = compile_program(Program((GroupByStep(
        keys=schema.names, aggs=(AggSpec(Agg.COUNT_ALL, None, "c"),)),)),
        schema)
    t0 = time.perf_counter()
    compiled = jax.jit(cp.run).lower(block, {}).compile()
    with capsys.disabled():
        print(f"\na group-by of two keys over {rows} slots compiled for a "
              f"described v5e in {time.perf_counter() - t0:.1f} s")
    assert cp.notes["key_tier"] == "segment"
    assert cp.notes["groups"] == rows and cp.notes["key_words"] == 3
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_SHARE * V5E_HBM_BYTES / 8, temp
    text = compiled.as_text()
    assert "ydb.scatter_first" not in text and "ydb.compact" in text
    scatters = [ln for ln in text.splitlines()
                if re.search(r"\bscatter\(", ln)]
    scopes = sorted({m for ln in scatters
                     for m in re.findall(r"ydb\.\w+", ln)})
    assert scopes == ["ydb.fused_group_reduce", "ydb.group_ids_sorted"], (
        [ln.strip()[:160] for ln in scatters])
