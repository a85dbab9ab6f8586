"""SSA program verifier tests: one per diagnostic code, asserting the
structured payload (code, step index, path) — the plan-time analog of
the reference's TProgramContainer::Init rejection tests."""

import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.analysis import (
    VerificationError,
    analyze_program,
    check_program,
    verify_program,
)
from ydb_tpu.blocks import TableBlock
from ydb_tpu.ssa import (
    Agg,
    AggSpec,
    AssignStep,
    Call,
    Col,
    FilterStep,
    GroupByStep,
    Op,
    Program,
    ProjectStep,
    SortStep,
    compile_program,
)
from ydb_tpu.ssa.program import WindowStep, lit


SCH = dtypes.schema(
    ("a", dtypes.INT64, False),
    ("b", dtypes.INT64, True),
    ("s", dtypes.STRING, False),
)


def _only(diags, code):
    hits = [d for d in diags if d.code == code]
    assert hits, f"no {code} in {[d.code for d in diags]}"
    return hits[0]


def test_clean_program_has_no_diagnostics():
    prog = Program((
        AssignStep("c", Call(Op.ADD, Col("a"), lit(1))),
        FilterStep(Call(Op.GT, Col("c"), lit(3))),
        ProjectStep(("a", "c")),
    ))
    assert verify_program(prog, SCH) == []
    check_program(prog, SCH)  # does not raise


def test_unknown_column():
    prog = Program((
        AssignStep("c", Call(Op.ADD, Col("nope"), lit(1))),
    ))
    d = _only(verify_program(prog, SCH), "V001")
    assert d.name == "unknown-column"
    assert d.step == 0
    assert "nope" in d.message
    assert d.path == "steps[0].expr.args[0]"
    with pytest.raises(VerificationError) as ei:
        check_program(prog, SCH)
    assert ei.value.diagnostics[0].code == "V001"


def test_filter_not_boolean():
    prog = Program((
        AssignStep("c", Call(Op.ADD, Col("a"), lit(1))),
        FilterStep(Col("c")),
    ))
    d = _only(verify_program(prog, SCH), "V002")
    assert d.step == 1
    assert "BOOL" in d.message


def test_agg_dtype_mismatch():
    prog = Program((
        GroupByStep(("a",), (AggSpec(Agg.SUM, "s", "x"),)),
    ))
    d = _only(verify_program(prog, SCH), "V003")
    assert d.step == 0
    assert "string" in d.message
    assert "dictionary ids" in d.message


def test_dead_projection():
    prog = Program((
        FilterStep(Call(Op.GT, Col("a"), lit(0))),
        ProjectStep(("a", "ghost")),
    ))
    d = _only(verify_program(prog, SCH), "V004")
    assert d.step == 1
    assert "ghost" in d.message
    assert d.path == "steps[1].names[1]"


def test_nullable_window_key_rejected_as_plan_error():
    """A window over a key that may be NULL was refused (V005: the
    lowering sorted raw physical values, so a NULL ranked by the stale
    bits under it). It now verifies and runs: the NULL partition key is
    one partition whatever lies under its NULLs, NULL order keys come
    last and are peers, as the numpy oracle ranks them."""
    import jax

    prog = Program((
        WindowStep("rank", ("b",), ("a",), (False,), "rnk"),
        WindowStep("rank", ("a",), ("b",), (True,), "by_b"),
    ))
    assert verify_program(prog, SCH) == []
    check_program(prog, SCH)     # does not raise
    a = np.array([5, 3, 5, 1, 3, 2, 7, 5], dtype=np.int64)
    b_ok = np.array([1, 0, 1, 1, 0, 1, 0, 1], dtype=bool)
    # stale bits under the NULLs: distinct values a raw sort would split
    b = np.where(b_ok, np.array([9, 0, 9, 4, 0, 4, 0, 9]),
                 np.array([0, 77, 0, 0, -5, 0, 123, 0])).astype(np.int64)
    block = TableBlock.from_numpy(
        {"a": a, "b": b, "s": np.zeros(8, np.int32)}, SCH,
        {"a": np.ones(8, bool), "b": b_ok, "s": np.ones(8, bool)})
    out = jax.jit(compile_program(prog, SCH).run)(
        block, {}).to_numpy()

    def ranks(part, order, desc):
        """rank() by its definition: 1 + the rows of the partition
        that come strictly before in the order, NULLs last."""
        key = [(0, -o if desc else o) if o is not None else (1, 0)
               for o in order]
        return [1 + sum(1 for j in range(8)
                        if part[j] == part[i] and key[j] < key[i])
                for i in range(8)]

    b_vals = [int(v) if ok else None for v, ok in zip(b, b_ok)]
    assert out["rnk"].tolist() == ranks(b_vals, a.tolist(), False)
    assert out["by_b"].tolist() == ranks(a.tolist(), b_vals, True)
    # the NULL partition (rows 1, 4, 6) ranks 3 < 3 == 3 < 7 inside itself
    assert out["rnk"][[1, 4, 6]].tolist() == [1, 1, 3]


def test_non_nullable_window_key_accepted():
    prog = Program((
        WindowStep("rank", ("a",), ("a",), (False,), "rnk"),
    ))
    assert verify_program(prog, SCH) == []


def test_group_capacity_must_be_positive():
    prog = Program((
        GroupByStep(("a",), (AggSpec(Agg.COUNT_ALL, None, "n"),),
                    max_groups=0),
    ))
    d = _only(verify_program(prog, SCH), "V006")
    assert d.step == 0


def test_expr_type_error_timestamp():
    prog = Program((AssignStep("h", Call(Op.HOUR, Col("a"))),))
    d = _only(verify_program(prog, SCH), "V007")
    assert "timestamp" in d.message


def test_sort_desc_arity():
    prog = Program((SortStep(("a", "b"), (True,)),))
    d = _only(verify_program(prog, SCH), "V008")
    assert d.step == 0


def test_unknown_window_function():
    prog = Program((WindowStep("ntile", (), ("a",), (False,), "x"),))
    d = _only(verify_program(prog, SCH), "V009")
    assert "ntile" in d.message


def test_duplicate_projection_column():
    prog = Program((ProjectStep(("a", "b", "a")),))
    d = _only(verify_program(prog, SCH), "V010")
    assert d.name == "duplicate-output-column"
    assert d.step == 0
    assert d.path == "steps[0].names[2]"
    assert "'a'" in d.message
    with pytest.raises(VerificationError):
        check_program(prog, SCH)


def test_duplicate_group_by_key():
    prog = Program((
        GroupByStep(("a", "a"), (AggSpec(Agg.COUNT_ALL, None, "n"),)),
    ))
    d = _only(verify_program(prog, SCH), "V010")
    assert d.path == "steps[0].keys[1]"


def test_aggregate_output_shadows_key():
    prog = Program((
        GroupByStep(("a",), (
            AggSpec(Agg.COUNT_ALL, None, "a"),   # collides with key
            AggSpec(Agg.SUM, "b", "t"),
            AggSpec(Agg.COUNT_ALL, None, "t"),   # collides with agg
        )),
    ))
    hits = [d for d in verify_program(prog, SCH) if d.code == "V010"]
    assert [d.path for d in hits] == \
        ["steps[0].aggs[0]", "steps[0].aggs[2]"]
    assert all(d.hint for d in hits)


def test_distinct_outputs_stay_clean():
    prog = Program((
        GroupByStep(("a",), (AggSpec(Agg.SUM, "b", "t"),)),
        ProjectStep(("a", "t")),
    ))
    assert not [d for d in verify_program(prog, SCH)
                if d.code == "V010"]


def test_multiple_diagnostics_accumulate():
    prog = Program((
        FilterStep(Col("a")),            # V002
        ProjectStep(("a", "ghost")),     # V004
    ))
    codes = {d.code for d in verify_program(prog, SCH)}
    assert {"V002", "V004"} <= codes


def test_compiler_is_a_choke_point():
    """compile_program rejects malformed programs with the structured
    error instead of a trace-time KeyError."""
    prog = Program((ProjectStep(("ghost",)),))
    with pytest.raises(VerificationError):
        compile_program(prog, SCH)


def test_scan_executor_verifies_original_program():
    from ydb_tpu.engine.scan import ColumnSource, ScanExecutor

    src = ColumnSource(
        {"a": np.arange(5, dtype=np.int64)},
        dtypes.schema(("a", dtypes.INT64, False)), None)
    prog = Program((FilterStep(Col("a")),))  # non-bool filter
    with pytest.raises(VerificationError) as ei:
        ScanExecutor(prog, src)
    assert ei.value.diagnostics[0].code == "V002"


def test_nullability_threads_into_out_schema():
    """The verifier's nullability inference types the compiled output
    schema: keyed aggregates over non-null inputs stay non-null, and a
    downstream window keeps each column's flag and adds a non-null
    rank, over a nullable order key as over a non-null one."""
    prog = Program((
        GroupByStep(("a",), (
            AggSpec(Agg.SUM, "a", "total"),
            AggSpec(Agg.COUNT_ALL, None, "n"),
            AggSpec(Agg.SUM, "b", "maybe"),
            AggSpec(Agg.STDDEV_SAMP, "a", "sd"),
        )),
    ))
    cp = compile_program(prog, SCH)
    by_name = {f.name: f for f in cp.out_schema.fields}
    assert not by_name["a"].nullable       # key from non-null column
    assert not by_name["total"].nullable   # keyed SUM over non-null
    assert not by_name["n"].nullable       # COUNT is never NULL
    assert by_name["maybe"].nullable       # input column is nullable
    assert by_name["sd"].nullable          # NULL for singleton groups

    for key in ("total", "maybe"):
        downstream = compile_program(Program((
            WindowStep("rank", (), (key,), (True,), "rnk"),
        )), cp.out_schema)
        flags = {f.name: f.nullable for f in downstream.out_schema.fields}
        assert flags == {"a": False, "total": False, "n": False,
                         "maybe": True, "sd": True, "rnk": False}


def test_keyless_aggregate_is_nullable():
    prog = Program((GroupByStep((), (AggSpec(Agg.SUM, "a", "t"),)),))
    ana = analyze_program(prog, SCH)
    assert ana.out_nullable["t"]  # zero-row input -> NULL sum


def test_division_is_nullable_unless_nonzero_literal_divisor():
    """a / b NULLs rows where b == 0, whatever the operands declare: the
    division's column is typed nullable in the output schema, through a
    window over it too."""
    by_col = Program((AssignStep("r", Call(Op.DIV, Col("a"), Col("a"))),))
    assert analyze_program(by_col, SCH).out_nullable["r"]
    by_lit = Program((AssignStep("r", Call(Op.DIV, Col("a"), lit(2))),))
    assert not analyze_program(by_lit, SCH).out_nullable["r"]
    by_zero = Program((AssignStep("r", Call(Op.DIV, Col("a"), lit(0))),))
    assert analyze_program(by_zero, SCH).out_nullable["r"]
    windowed = compile_program(Program((
        AssignStep("r", Call(Op.DIV, Col("a"), Col("a"))),
        WindowStep("rank", (), ("r",), (False,), "rnk"),
    )), SCH)
    assert windowed.out_schema.field("r").nullable
    assert not windowed.out_schema.field("rnk").nullable


def test_scan_result_schema_keeps_original_agg_nullability():
    """AVG lowers through a two-phase division fixup; the scan's RESULT
    schema must carry the original program's knowledge (keyed AVG over
    a non-null input is never NULL), not the fixup's widening — that is
    what keeps a downstream window over the average plannable."""
    from ydb_tpu.engine.scan import ColumnSource, ScanExecutor

    sch = dtypes.schema(("g", dtypes.INT64, False),
                        ("a", dtypes.INT64, False))
    src = ColumnSource(
        {"g": np.array([1, 1, 2], dtype=np.int64),
         "a": np.array([10, 20, 30], dtype=np.int64)}, sch, None)
    prog = Program((
        GroupByStep(("g",), (AggSpec(Agg.AVG, "a", "m"),)),
    ))
    ex = ScanExecutor(prog, src, block_rows=2)  # forces a real merge
    blk = ex.run_stream(src.blocks(2, ex.read_cols))
    assert not blk.schema.field("m").nullable
    assert not blk.schema.field("g").nullable
    # the executor's static out_schema agrees with delivered blocks
    assert ex.out_schema == blk.schema
    vals = dict(zip(blk.to_numpy()["g"].tolist(),
                    blk.to_numpy()["m"].tolist()))
    assert vals == {1: 15.0, 2: 30.0}
    downstream = Program((
        WindowStep("rank", (), ("m",), (True,), "rnk"),
    ))
    assert verify_program(downstream, blk.schema) == []


def test_verified_program_still_executes():
    import jax

    prog = Program((
        AssignStep("c", Call(Op.MUL, Col("a"), lit(2))),
        FilterStep(Call(Op.GT, Col("c"), lit(2))),
        ProjectStep(("c",)),
    ))
    blk = TableBlock.from_numpy(
        {"a": np.array([1, 2, 3], dtype=np.int64)},
        dtypes.schema(("a", dtypes.INT64, False)))
    cp = compile_program(prog, blk.schema)
    out = jax.jit(cp.run)(
        blk, {k: np.asarray(v) for k, v in cp.aux.items()})
    np.testing.assert_array_equal(out.to_numpy()["c"], [4, 6])


def test_diagnostic_renders_step_and_path():
    prog = Program((AssignStep("c", Col("nope")),))
    d = verify_program(prog, SCH)[0]
    text = d.render()
    assert "V001" in text and "step 0" in text and "steps[0].expr" in text
    as_dict = d.to_dict()
    assert as_dict["code"] == "V001" and as_dict["step"] == 0
