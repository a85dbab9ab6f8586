"""SSA program → one traced JAX function over a TableBlock.

The analog of the reference's program parse + apply pipeline
(ydb/core/tx/program/program.cpp:553 TProgramContainer::Init;
TProgramStep::Apply formats/arrow/program.h:394) — except here "apply" is a
*trace*: the whole step list lowers into a single XLA computation (assigns,
filters, group-by, sort fused into one HBM pass wherever XLA can).

Compilation resolves string predicates against host dictionaries into small
device lookup tables ("aux inputs"), picks dense vs sort-based group-id
assignment from key cardinalities, and fixes the output schema. The result
is pure: ``run(block, aux) -> block`` — jit it, vmap it, shard_map it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu import dtypes
from ydb_tpu.blocks.block import Column, TableBlock, device_aux
from ydb_tpu.blocks.dictionary import DictionarySet
from ydb_tpu.ssa import kernels
from ydb_tpu.ssa.ops import Agg, Op
from ydb_tpu.ssa.program import (
    AggSpec,
    AssignStep,
    Call,
    Col,
    Const,
    DictMap,
    DictPredicate,
    Expr,
    FilterStep,
    GroupByStep,
    ProjectStep,
    Program,
    RollupStep,
    SortStep,
    UdfCall,
    WindowStep,
    agg_result_type,
    infer_type,
)

#: CompiledProgram.group_layout -> the ``group_layout`` a span says: a
#: sort-derived layout's groups come out compacted
LAYOUT_NAMES = {"keyless": "keyless", "dense": "dense",
                "dense_slots": "dense", "compact": "sorted"}


@dataclasses.dataclass
class CompiledProgram:
    """A lowered program plus its plan-time inputs.

    ``group_layout`` describes the group-by output layout for distributed
    merging (ydb_tpu.parallel):
      ("dense_slots", n)  — uncompacted fixed slots, psum-mergeable
      ("keyless", 1)      — single-row global aggregate, psum-mergeable
      ("dense", n)        — dense ids, compacted but shape-stable (n slots)
      ("compact", None)   — compacted rows; merge via all_gather + re-agg
      (None, None)        — no group-by in the program
    """

    run: Callable  # (TableBlock, dict[str, jax.Array]) -> TableBlock
    aux: dict[str, np.ndarray]  # plan-time tables (dict masks etc.)
    out_schema: dtypes.Schema
    in_schema: dtypes.Schema
    group_layout: tuple = (None, None)
    #: what the newest trace of ``run`` settled about its group-by and
    #: its sort, known only once the block's capacity is: ``groups``
    #: (the slots the states are sized to), ``key_words`` (32-bit words
    #: of the key a sort-derived layout sorts by), ``reduce_tier``
    #: (kernels.reduce_tier of each accumulator bank), ``key_tier``
    #: (where the output's key columns come from: ``dense`` = decoded
    #: from the slot number, ``onehot`` = gathered at each group's first
    #: row, ``segment`` = a sort-derived layout's sorted keys compacted
    #: at their segment heads; a keyless aggregate has none), ``sort_tier``
    #: (kernels.sort_tier of its SortStep) and ``sort_limit`` (that
    #: step's LIMIT, where it has one); the executor's ``transform``
    #: span carries them
    notes: dict = dataclasses.field(default_factory=dict, compare=False)
    #: the keys whose equal prefixes the output holds in contiguous runs
    #: (a group-by's output and what keeps its order): a RollupStep over
    #: it takes the rows as they come
    ordered: tuple = ()
    # aux staged to the device once, on first dispatch — restaging the
    # whole dict per call cost an H2D transfer per statement. Staleness
    # is impossible: the compile caches key on the dict contents and
    # drop the whole CompiledProgram when plan-time tables change. A
    # first-dispatch race double-stages idempotently (last write wins).
    _staged: "dict | None" = dataclasses.field(
        default=None, repr=False, compare=False)

    def __call__(self, block: TableBlock) -> TableBlock:
        if self._staged is None:
            self._staged = device_aux(self.aux)
        return self.run(block, self._staged)


class _Lowering:
    """Single-pass lowering context (types + aux tables + trace builder)."""

    def __init__(self, schema: dtypes.Schema, dicts: DictionarySet | None,
                 key_spaces: dict[str, int] | None,
                 partial_slots: bool = False,
                 dict_aliases: dict[str, str] | None = None):
        self.schema = schema
        self.dicts = dicts
        self.key_spaces = dict(key_spaces or {})
        # column -> source column whose dictionary it carries (aggregate
        # outputs like MIN(s) AS lo keep s's dictionary)
        self.dict_aliases = dict(dict_aliases or {})
        # partial_slots: keep dense group-by states in their slots
        # (uncompacted) so per-device states align elementwise for
        # psum/pmin/pmax merging over the mesh
        self.partial_slots = partial_slots
        self.group_layout: tuple = (None, None)
        self.notes: dict = {}
        # advisory NDV-based distinct-group estimate (compile_program)
        self.group_est: float | None = None
        self.types: dict[str, dtypes.LogicalType] = {
            f.name: f.type for f in schema.fields
        }
        self.aux: dict[str, np.ndarray] = {}
        self._aux_n = 0

    def add_aux(self, prefix: str, table: np.ndarray) -> str:
        key = f"{prefix}#{self._aux_n}"
        self._aux_n += 1
        self.aux[key] = table
        return key

    def dictionary(self, name: str):
        """Dictionary for a (possibly renamed) string column, or None."""
        if self.dicts is None:
            return None
        src = self.dict_aliases.get(name, name)
        return self.dicts[src] if src in self.dicts else None

    def key_bound(self, name: str, t: dtypes.LogicalType) -> int | None:
        """Static cardinality bound for a group-by key column, if known.

        ``t`` is the column's *current* type (assigned columns included)."""
        if t.kind == dtypes.Kind.BOOL:
            return 2
        if t.is_string:
            d = self.dictionary(name)
            if d is not None:
                return len(d)
        return self.key_spaces.get(name)


def compile_program(
    program: Program,
    schema: dtypes.Schema,
    dicts: DictionarySet | None = None,
    key_spaces: dict[str, int] | None = None,
    partial_slots: bool = False,
    dict_aliases: dict[str, str] | None = None,
    group_est: float | None = None,
) -> CompiledProgram:
    # program lowering is attributed to the active query trace (the
    # "ssa.compile" spans are one half of the compile-vs-execute split;
    # the other half — the first jitted dispatch's XLA compile — is
    # timed at the call sites). NULL span when no trace is active.
    from ydb_tpu.obs import tracing

    with tracing.span("ssa.compile") as _sp:
        _sp.set(steps=len(program.steps), cols=len(schema.names))
        return _compile_program(program, schema, dicts, key_spaces,
                                partial_slots, dict_aliases, group_est)


def _compile_program(
    program: Program,
    schema: dtypes.Schema,
    dicts: DictionarySet | None = None,
    key_spaces: dict[str, int] | None = None,
    partial_slots: bool = False,
    dict_aliases: dict[str, str] | None = None,
    group_est: float | None = None,
) -> CompiledProgram:
    # mandatory precondition: no program reaches the trace unverified.
    # Malformed programs raise VerificationError (a PlanError) with
    # step-indexed diagnostics instead of an opaque trace-time failure.
    # (Lazy import: ydb_tpu.ssa.__init__ imports this module, and the
    # verifier's own program imports would re-enter it mid-init.)
    from ydb_tpu.analysis import verify as _verify

    analysis = _verify.check_program(program, schema)
    out_nullable = analysis.out_nullable
    if partial_slots and program.group_by is not None:
        # slot layouts keep dead group slots in place (invalid values,
        # zero counts) so every output column is effectively nullable
        out_nullable = {n: True for n in out_nullable}

    ctx = _Lowering(schema, dicts, key_spaces, partial_slots, dict_aliases)
    # advisory distinct-group estimate (stats.cost NDV product): picks
    # between equally-exact group-by tiers; never a correctness bound
    ctx.group_est = group_est

    # ---- static pass: resolve plan, types, aux tables, output schema ----
    plan: list = []  # (kind, payload) closures prepared statically
    cur_types = dict(ctx.types)
    cur_names = list(schema.names)
    # static nullability at each step (the verifier's inference rules):
    # the fused group-by collapses per-column valid counts and input
    # masking for columns that provably carry no NULLs
    cur_nullable = {f.name: f.nullable for f in schema.fields}
    # the keys whose equal prefixes run contiguous in the rows, the live
    # rows a prefix (a group-by's output), where a RollupStep may take
    # the rows as they come
    ordered: tuple = ()

    def resolve_expr(expr: Expr):
        """Return (lower_fn(env, aux) -> Column, LogicalType)."""
        if isinstance(expr, Col):
            t = cur_types[expr.name]
            name = expr.name
            return (lambda env, aux: env[name]), t
        if isinstance(expr, Const):
            t = expr.type
            val = expr.value

            def lower_const(env, aux, _t=t, _v=val):
                any_col = next(iter(env.values()))
                n = any_col.data.shape[0]
                if _v is None:  # typed NULL (CASE without ELSE)
                    return Column(jnp.zeros((n,), dtype=_t.physical),
                                  jnp.zeros((n,), dtype=bool))
                data = jnp.full((n,), _v, dtype=_t.physical)
                return Column(data, jnp.ones((n,), dtype=bool))

            return lower_const, t
        if isinstance(expr, DictPredicate):
            return _resolve_dict_predicate(ctx, expr, cur_types)
        if isinstance(expr, DictMap):
            return _resolve_dict_map(ctx, expr, cur_types)
        if isinstance(expr, UdfCall):
            arg_fns = [resolve_expr(a)[0] for a in expr.args]
            out_dtype = expr.out_type.physical
            user_fn = expr.fn

            def call_host(*arrs, _fn=user_fn, _dt=out_dtype):
                return np.asarray(_fn(*arrs), dtype=_dt)

            def lower_udf(env, aux, _fns=tuple(arg_fns),
                          _dt=out_dtype, _call=call_host):
                cols = [f(env, aux) for f in _fns]
                valid = cols[0].validity
                for c in cols[1:]:
                    valid = valid & c.validity
                out = jax.pure_callback(
                    _call,
                    jax.ShapeDtypeStruct(cols[0].data.shape, _dt),
                    *[c.data for c in cols],
                )
                return Column(out, valid)

            return lower_udf, expr.out_type
        assert isinstance(expr, Call)
        return _resolve_call(ctx, expr, cur_types, resolve_expr)

    for step in program.steps:
        if isinstance(step, AssignStep):
            fn, t = resolve_expr(step.expr)
            cur_types[step.name] = t
            cur_nullable[step.name] = _verify.infer_nullable(
                step.expr, cur_nullable)
            if step.name not in cur_names:
                cur_names.append(step.name)
            if step.name in ordered:
                ordered = ()
            plan.append(("assign", (step.name, fn)))
        elif isinstance(step, FilterStep):
            fn, t = resolve_expr(step.expr)
            if t.kind != dtypes.Kind.BOOL:
                raise TypeError(f"filter predicate must be bool, got {t}")
            ordered = ()
            plan.append(("filter", fn))
        elif isinstance(step, GroupByStep):
            lowered = _resolve_group_by(ctx, step, cur_types,
                                        cur_nullable)
            plan.append(("group_by", lowered))
            cur_names = list(lowered.out_names)
            cur_types = dict(lowered.out_types)
            # aggregate outputs may be NULL for empty/dead groups;
            # conservative for any later step
            cur_nullable = {n: True for n in cur_names}
            ordered = group_order(step, ctx.group_layout)
        elif isinstance(step, RollupStep):
            # a program traced blind would give every level the finest
            # level's capacity: the DQ stage reads the levels' rows
            # first and sizes them (dq/compute.py, plan/executor.py)
            raise NotImplementedError(
                "GROUP BY ROLLUP runs on the DQ executor")
        elif isinstance(step, ProjectStep):
            missing = [n for n in step.names if n not in cur_types]
            if missing:
                raise KeyError(f"projection of unknown columns {missing}")
            cur_names = list(step.names)
            if not set(ordered) <= set(step.names):
                ordered = ()
            plan.append(("project", tuple(step.names)))
        elif isinstance(step, SortStep):
            ordered = ()
            desc = step.descending or (False,) * len(step.keys)
            # string keys order by dictionary *rank*, not id: ship a
            # plan-time rank table per string key (ydb_tpu.blocks.dictionary)
            ranks = []
            for k in step.keys:
                t = cur_types[k]
                if t.is_string:
                    d = ctx.dictionary(k)
                    if d is None:
                        raise ValueError(
                            f"ORDER BY on string column {k} needs its"
                            " dictionary")
                    ranks.append(ctx.add_aux(f"rank.{k}", d.sort_rank()))
                else:
                    ranks.append(None)
            plan.append(
                ("sort", (tuple(step.keys), tuple(desc), step.limit,
                          tuple(ranks))))
        elif isinstance(step, WindowStep):
            lowered = WindowLowering.resolve(ctx, step, cur_types)
            cur_types[step.out_name] = dtypes.INT64
            if step.out_name not in cur_names:
                cur_names.append(step.out_name)
            if step.out_name in ordered:
                ordered = ()
            plan.append(("window", lowered))
        else:
            raise NotImplementedError(f"step {step}")

    out_schema = dtypes.Schema(
        tuple(dtypes.Field(n, cur_types[n], out_nullable.get(n, True))
              for n in cur_names)
    )

    # ---- trace-time pass ----
    def run(block: TableBlock, aux: dict[str, jax.Array]) -> TableBlock:
        env: dict[str, Column] = dict(block.columns)
        mask = block.row_mask()
        length = block.length
        names = list(block.columns.keys())

        for kind, payload in plan:
            if kind == "assign":
                name, fn = payload
                env[name] = fn(env, aux)
                if name not in names:
                    names.append(name)
            elif kind == "filter":
                # mask-only (late materialization); `length` keeps the live
                # range until a compaction point (group_by/sort/output)
                pred = payload(env, aux)
                mask = mask & kernels.pred_mask(pred)
            elif kind == "project":
                names = list(payload)
                env = {n: env[n] for n in names}
            elif kind == "group_by":
                gb = payload
                env, length = gb.lower(env, aux, mask)
                names = list(gb.out_names)
                mask = (
                    jnp.arange(next(iter(env.values())).data.shape[0],
                               dtype=jnp.int32) < length
                )
            elif kind == "sort":
                keys, desc, limit, ranks = payload
                cols = {n: env[n] for n in names}
                sort_cols = []
                for k, rk in zip(keys, ranks):
                    c = cols[k] if k in cols else env[k]
                    if rk is not None:
                        c = kernels.dict_gather(aux[rk], c)
                    sort_cols.append(c)
                tmp_names = list(names)
                for i, c in enumerate(sort_cols):
                    cols[f"__sort{i}"] = c
                    tmp_names.append(f"__sort{i}")
                blk = TableBlock(
                    cols, length,
                    dtypes.Schema(tuple(
                        dtypes.Field(n, cur_types.get(n, dtypes.INT64))
                        for n in tmp_names)),
                )
                ctx.notes["sort_tier"] = kernels.sort_tier(
                    limit, blk.capacity, (c.data.dtype for c in sort_cols))
                if limit is not None:
                    ctx.notes["sort_limit"] = limit
                # single lexsort pass: the filter mask rides in as `live`
                # (non-selected rows sink past the length cut)
                blk = kernels.sort_block(
                    blk, [f"__sort{i}" for i in range(len(keys))],
                    list(desc), limit, live=mask)
                env = {n: blk.columns[n] for n in names}
                length = blk.length
                mask = blk.row_mask()
            elif kind == "window":
                win = payload
                cap = next(iter(env.values())).data.shape[0]
                live = mask & (jnp.arange(cap, dtype=jnp.int32)
                               < length)
                env[win.step.out_name], _ = win.apply(env, aux, live)
                ctx.notes["window"] = win.step.func
                if win.step.out_name not in names:
                    names.append(win.step.out_name)
        out_cols = {n: env[n] for n in out_schema.names}
        blk = TableBlock(out_cols, length, out_schema)
        return kernels.compact(blk, mask)

    return CompiledProgram(run=run, aux=ctx.aux, out_schema=out_schema,
                           in_schema=schema, group_layout=ctx.group_layout,
                           notes=ctx.notes, ordered=ordered)


# ---------------- whole-input steps: ROLLUP, ranking windows ----------------


def group_order(step: GroupByStep, layout: tuple) -> tuple:
    """The keys whose equal prefixes a group-by's output holds in
    contiguous runs, its live rows a prefix: a sort-derived layout's
    groups come in sorted key order, a dense one's in slot order (the
    mixed radix of the keys, the first most significant); a layout
    that keeps dead slots in place (mesh partials) holds none."""
    if step.keys and layout[0] in ("compact", "dense"):
        return tuple(step.keys)
    return ()


#: RollupStep aggregate -> what ``kernels.rollup`` does with it a level
_ROLL_KIND = {Agg.SUM: "sum", Agg.COUNT: "count", Agg.COUNT_ALL: "count",
              Agg.MIN: "min", Agg.MAX: "max"}


@dataclasses.dataclass(frozen=True)
class RollupLowering:
    """A RollupStep resolved against its input's types: its keys, each
    aggregate's roll kind, and for a MIN / MAX over a string the rank
    table it orders by (the value packed as ``rank << 32 | id``, as the
    group-by packs it). Only the DQ executor runs it: it reads each
    level's rows first (``counts``) and sizes the levels to them."""

    keys: tuple[str, ...]
    rolls: tuple[tuple[str, str], ...]
    packed: tuple[tuple[str, str], ...]   # (column, rank aux key)

    @property
    def names(self) -> tuple[str, ...]:
        return self.keys + tuple(n for n, _ in self.rolls)

    @property
    def counts_of(self) -> tuple[str, ...]:
        return tuple(n for n, kind in self.rolls if kind == "count")

    @classmethod
    def resolve(cls, ctx: "_Lowering", step: RollupStep, cur_types):
        for n in step.keys + tuple(a.out_name for a in step.aggs):
            if n not in cur_types:
                raise KeyError(f"rollup column {n} not in scope")
        rolls, packed = [], []
        for a in step.aggs:
            if a.func not in _ROLL_KIND:
                raise NotImplementedError(f"{a.func} does not roll up")
            rolls.append((a.out_name, _ROLL_KIND[a.func]))
            if a.func in (Agg.MIN, Agg.MAX) and \
                    cur_types[a.out_name].is_string:
                d = ctx.dictionary(a.out_name)
                if d is None:
                    raise ValueError(f"MIN/MAX over string column"
                                     f" {a.out_name} needs its dictionary")
                packed.append((a.out_name, ctx.add_aux(
                    f"rank.{a.out_name}", d.sort_rank())))
        return cls(tuple(step.keys), tuple(rolls), tuple(packed))

    def _pack(self, block: TableBlock, aux, unpack: bool) -> TableBlock:
        cols = dict(block.columns)
        for name, rank in self.packed:
            c = cols[name]
            if unpack:
                data = (c.data & 0xFFFFFFFF).astype(jnp.int32)
            else:
                data = (kernels.dict_gather(aux[rank], c).data.astype(
                    jnp.int64) << 32) | c.data.astype(jnp.int64)
            cols[name] = Column(data, c.validity)
        return TableBlock(cols, block.length, block.schema)

    def counts(self, block: TableBlock, ordered: bool) -> jax.Array:
        return kernels.rollup_counts(block, self.keys, ordered)

    def apply(self, block: TableBlock, aux, caps, out_cap: int,
              ordered: bool) -> tuple[TableBlock, jax.Array]:
        """Every level of the rollup of ``block`` (its columns
        ``names``), ``kernels.rollup`` in ``out_cap`` slots, and each
        level's rows."""
        out, counts = kernels.rollup(self._pack(block, aux, False),
                                     self.keys, self.rolls, caps,
                                     out_cap, ordered)
        return self._pack(out, aux, True), counts


@dataclasses.dataclass(frozen=True)
class WindowLowering:
    """A WindowStep resolved against its input's types: a string key
    compares by its dictionary's sort rank (a partition needs only
    equality, which ranks keep too)."""

    step: WindowStep
    ranks: tuple    # aux key per partition + order key, or None

    @classmethod
    def resolve(cls, ctx: "_Lowering", step: WindowStep, cur_types):
        if step.func not in ("rank", "dense_rank", "row_number"):
            raise NotImplementedError(f"window function {step.func}")
        ranks = []
        for k in step.partition + step.order_keys:
            if cur_types[k].is_string:
                d = ctx.dictionary(k)
                if d is None:
                    raise ValueError(f"window key on string column {k}"
                                     " needs its dictionary")
                ranks.append(ctx.add_aux(f"wrank.{k}", d.sort_rank()))
            else:
                ranks.append(None)
        return cls(step, tuple(ranks))

    def apply(self, env: dict, aux, live) -> tuple[Column, jax.Array]:
        """The window's column over the ``live`` rows of ``env``, and
        the number of partitions."""
        s = self.step
        cols = []
        for k, rk in zip(s.partition + s.order_keys, self.ranks):
            cols.append(env[k] if rk is None
                        else kernels.dict_gather(aux[rk], env[k]))
        p = len(s.partition)
        values, partitions = kernels.window_rank(
            s.func, cols[:p], cols[p:],
            s.descending or (False,) * len(s.order_keys), live)
        return Column(values, live), partitions


def resolve_whole_input_step(step, schema: dtypes.Schema, dicts=None,
                             key_spaces=None, dict_aliases=None):
    """A RollupStep or WindowStep resolved over ``schema`` for an
    executor that runs it apart from a program (the DQ stage that sizes
    a rollup's levels by their rows): (its lowering, the plan-time
    tables it reads)."""
    ctx = _Lowering(schema, dicts, key_spaces, dict_aliases=dict_aliases)
    types = {f.name: f.type for f in schema.fields}
    cls = RollupLowering if isinstance(step, RollupStep) else WindowLowering
    return cls.resolve(ctx, step, types), ctx.aux


# ---------------- expression lowering helpers ----------------


def _resolve_dict_predicate(ctx: _Lowering, p: DictPredicate, cur_types):
    t = cur_types[p.column]
    if not t.is_string:
        raise TypeError(f"dict predicate on non-string column {p.column}")
    d = ctx.dictionary(p.column)
    if d is None:
        raise ValueError(f"no dictionary for column {p.column}")
    if p.kind in ("eq", "ne"):
        want = d.eq_id(p.pattern)
        table = np.zeros(max(len(d), 1), dtype=np.bool_)
        if want >= 0:
            table[want] = True
        if p.kind == "ne":
            table = ~table
    elif p.kind == "like":
        table = d.like_mask(p.pattern)
    elif p.kind == "prefix":
        table = d.prefix_mask(p.pattern)
    elif p.kind in ("in_set", "not_in_set"):
        table = np.zeros(max(len(d), 1), dtype=np.bool_)
        for v in p.pattern:
            i = d.eq_id(v)
            if i >= 0:
                table[i] = True
        if p.kind == "not_in_set":
            table = ~table
    elif p.kind == "custom":
        table = _custom_dict_mask(d, p.pattern)
    else:
        raise NotImplementedError(f"dict predicate kind {p.kind}")
    if table.size == 0:
        table = np.zeros(1, dtype=np.bool_)
    key = ctx.add_aux(f"dict.{p.column}.{p.kind}", table)
    col = p.column

    def lower(env, aux, _key=key, _col=col):
        return kernels.dict_gather(aux[_key], env[_col])

    return lower, dtypes.BOOL


def dict_map_table(d, out_d, kind: str, args: tuple) -> np.ndarray:
    """id->id gather table for a string transform: apply the transform to
    every dictionary value, register results in the output dictionary.
    Shared by the JAX lowering and the CPU oracle (identical id
    assignment: first-seen order over the source dictionary)."""
    if kind == "substr":
        start, length = args  # SQL 1-based start
        lo = start - 1
        out = [out_d.add(v[lo:lo + length]) for v in d.values]
    elif kind == "upper":
        out = [out_d.add(v.upper()) for v in d.values]
    elif kind == "lower":
        out = [out_d.add(v.lower()) for v in d.values]
    elif kind == "trim":
        out = [out_d.add(v.strip()) for v in d.values]
    elif kind == "ltrim":
        out = [out_d.add(v.lstrip()) for v in d.values]
    elif kind == "rtrim":
        out = [out_d.add(v.rstrip()) for v in d.values]
    elif kind == "replace":
        old, new = args
        out = [out_d.add(v.replace(old, new)) for v in d.values]
    elif kind == "concat_suffix":
        (lit,) = args
        out = [out_d.add(v + lit) for v in d.values]
    elif kind == "concat_prefix":
        (lit,) = args
        out = [out_d.add(lit + v) for v in d.values]
    elif kind == "gethost":
        # URL -> host part (Url::GetHost): strip scheme, path, query
        def _host(v: bytes) -> bytes:
            s = v.split(b"://", 1)[-1]
            return s.split(b"/", 1)[0].split(b"?", 1)[0]

        out = [out_d.add(_host(v)) for v in d.values]
    elif kind == "cutwww":
        # Url::CutWWW: drop one leading "www." if present
        out = [out_d.add(v[4:] if v.startswith(b"www.") else v)
               for v in d.values]
    elif kind == "strlen":
        # int output: byte length per dictionary value (no out dict)
        out = [len(v) for v in d.values]
    elif kind == "xrank":
        # cross-dictionary compare: rank each value within the sorted
        # union of this column's and the peer column's dictionaries
        # (out_d here is the PEER dictionary, not an output dict); both
        # sides of the comparison derive identical ranks from the same
        # union, so ==/!=/</<= on the ranks match byte-string compare.
        ranks = {v: i for i, v in enumerate(
            sorted(set(d.values) | set(out_d.values)))}
        out = [ranks[v] for v in d.values]
    else:
        raise NotImplementedError(f"dict map kind {kind}")
    return np.asarray(out or [0], dtype=np.int32)


def _resolve_dict_map(ctx: _Lowering, m: DictMap, cur_types):
    t = cur_types[m.column]
    if not t.is_string:
        raise TypeError(f"dict map on non-string column {m.column}")
    d = ctx.dictionary(m.column)
    if d is None:
        raise ValueError(f"no dictionary for column {m.column}")
    if ctx.dicts is None:
        raise ValueError("dict map needs a shared DictionarySet")
    # for "xrank" out_column names the PEER dictionary (already
    # registered) and the result is an int rank, not a string
    out_d = ctx.dicts.for_column(m.out_column)
    table = dict_map_table(d, out_d, m.kind, m.args)
    key = ctx.add_aux(f"map.{m.column}.{m.kind}", table)
    col = m.column

    def lower(env, aux, _key=key, _col=col):
        return kernels.dict_gather(aux[_key], env[_col])

    return lower, (dtypes.INT32 if m.kind in ("xrank", "strlen")
                   else dtypes.STRING)


def _custom_dict_mask(d, pattern) -> np.ndarray:
    """Plan-time masks beyond the fixed kinds. ("ord", op, val) = ordered
    byte-string comparison evaluated over the dictionary values."""
    from ydb_tpu.blocks.dictionary import _as_bytes

    tag = pattern[0]
    if tag == "ord":
        _, op, val = pattern
        val = _as_bytes(val)
        cmp = {
            "lt": lambda v: v < val,
            "le": lambda v: v <= val,
            "gt": lambda v: v > val,
            "ge": lambda v: v >= val,
        }[op]
        return d.match_mask(cmp)
    if tag == "suffix":
        _, val = pattern
        val = _as_bytes(val)
        return d.match_mask(lambda v: v.endswith(val))
    raise NotImplementedError(f"custom dict predicate {tag}")


def _as_f64(f):
    """Float-domain math over any numeric input: cast to f64 first."""
    return lambda *xs: f(*(x.astype(jnp.float64) for x in xs))


_SIMPLE_BINOPS = {
    Op.EQ: lambda a, b: a == b,
    Op.NE: lambda a, b: a != b,
    Op.LT: lambda a, b: a < b,
    Op.LE: lambda a, b: a <= b,
    Op.GT: lambda a, b: a > b,
    Op.GE: lambda a, b: a >= b,
    Op.ADD: lambda a, b: a + b,
    Op.SUB: lambda a, b: a - b,
    Op.MUL: lambda a, b: a * b,
    Op.XOR: lambda a, b: a ^ b,
    Op.GREATEST: jnp.maximum,
    Op.LEAST: jnp.minimum,
    Op.ATAN2: _as_f64(jnp.arctan2),
    Op.HYPOT: _as_f64(jnp.hypot),
    Op.BIT_AND: lambda a, b: a & b,
    Op.BIT_OR: lambda a, b: a | b,
    Op.BIT_XOR: lambda a, b: a ^ b,
    Op.SHIFT_LEFT: lambda a, b: a << b,
    Op.SHIFT_RIGHT: lambda a, b: a >> b,
}

_SIMPLE_UNOPS = {
    Op.NOT: lambda a: ~a,
    Op.NEG: lambda a: -a,
    Op.ABS: jnp.abs,
    Op.SQRT: jnp.sqrt,
    Op.EXP: jnp.exp,
    Op.LN: jnp.log,
    Op.LOG10: lambda a: jnp.log(a) / jnp.log(10.0),
    Op.FLOOR: jnp.floor,
    Op.CEIL: jnp.ceil,
    Op.ROUND: jnp.round,
    Op.SIGN: jnp.sign,
    Op.SIN: _as_f64(jnp.sin),
    Op.COS: _as_f64(jnp.cos),
    Op.TAN: _as_f64(jnp.tan),
    Op.ASIN: _as_f64(jnp.arcsin),
    Op.ACOS: _as_f64(jnp.arccos),
    Op.ATAN: _as_f64(jnp.arctan),
    Op.SINH: _as_f64(jnp.sinh),
    Op.COSH: _as_f64(jnp.cosh),
    Op.TANH: _as_f64(jnp.tanh),
    Op.ASINH: _as_f64(jnp.arcsinh),
    Op.ACOSH: _as_f64(jnp.arccosh),
    Op.ATANH: _as_f64(jnp.arctanh),
    Op.CBRT: _as_f64(jnp.cbrt),
    Op.ERF: _as_f64(lambda x: jax.scipy.special.erf(x)),
    Op.LOG2: _as_f64(jnp.log2),
    Op.EXP2: _as_f64(jnp.exp2),
    Op.TRUNC: _as_f64(jnp.trunc),
    Op.RINT: _as_f64(jnp.round),
    Op.RADIANS: _as_f64(jnp.deg2rad),
    Op.DEGREES: _as_f64(jnp.rad2deg),
    Op.BIT_NOT: lambda a: ~a,
}


def _resolve_call(ctx: _Lowering, call: Call, cur_types, resolve_expr):
    op = call.op
    resolved = [resolve_expr(a) for a in call.args]
    fns = [r[0] for r in resolved]
    ts = [r[1] for r in resolved]
    out_t = infer_type(call, ctx.schema, cur_types)

    # mixed decimal x float: descale the decimal side to float (the
    # comparison/arithmetic then runs in double — exactness is already
    # lost the moment a float entered)
    if op in (Op.ADD, Op.SUB, Op.MUL, Op.EQ, Op.NE, Op.LT, Op.LE, Op.GT,
              Op.GE, Op.DIV, Op.GREATEST, Op.LEAST):
        fns, ts = _descale_mixed(fns, ts)
    # rescale decimal operands to a common scale for add/sub/compare
    if op in (Op.ADD, Op.SUB, Op.EQ, Op.NE, Op.LT, Op.LE, Op.GT, Op.GE,
              Op.MOD, Op.GREATEST, Op.LEAST):
        fns, ts = _align_decimals(op, call, fns, ts)

    if op in _SIMPLE_BINOPS and len(fns) == 2:
        f = _SIMPLE_BINOPS[op]
        fa, fb = fns

        def lower(env, aux, _f=f, _fa=fa, _fb=fb):
            return kernels.binop(_f, _fa(env, aux), _fb(env, aux))

        return lower, out_t
    if op in _SIMPLE_UNOPS and len(fns) == 1:
        f = _SIMPLE_UNOPS[op]
        fa = fns[0]

        def lower(env, aux, _f=f, _fa=fa):
            return kernels.unop(_f, _fa(env, aux))

        return lower, out_t
    if op is Op.AND:
        fa, fb = fns

        def lower(env, aux, _fa=fa, _fb=fb):
            return kernels.kleene_and(_fa(env, aux), _fb(env, aux))

        return lower, out_t
    if op is Op.OR:
        fa, fb = fns

        def lower(env, aux, _fa=fa, _fb=fb):
            return kernels.kleene_or(_fa(env, aux), _fb(env, aux))

        return lower, out_t
    if op is Op.DIV:
        fa, fb = fns
        ta, tb = ts[0], ts[1]
        as_float = out_t.is_floating
        sa = 10.0 ** ta.scale if ta.is_decimal else 1.0
        sb = 10.0 ** tb.scale if tb.is_decimal else 1.0

        def lower(env, aux, _fa=fa, _fb=fb, _sa=sa, _sb=sb, _ff=as_float):
            a, b = _fa(env, aux), _fb(env, aux)
            if _ff and (_sa != 1.0 or _sb != 1.0):
                a = Column(a.data.astype(jnp.float64) / _sa, a.validity)
                b = Column(b.data.astype(jnp.float64) / _sb, b.validity)
            elif _ff:
                a = Column(a.data.astype(jnp.float64), a.validity)
            return kernels.safe_div(a, b, _ff)

        return lower, out_t
    if op is Op.MOD:
        fa, fb = fns

        def lower(env, aux, _fa=fa, _fb=fb):
            a, b = _fa(env, aux), _fb(env, aux)
            zero = b.data == 0
            denom = jnp.where(zero, jnp.ones_like(b.data), b.data)
            return Column(
                kernels.trunc_mod(a.data, denom),
                a.validity & b.validity & ~zero,
            )

        return lower, out_t
    if op is Op.POW:
        fa, fb = fns

        def lower(env, aux, _fa=fa, _fb=fb):
            a, b = _fa(env, aux), _fb(env, aux)
            return Column(
                jnp.power(a.data.astype(jnp.float64),
                          b.data.astype(jnp.float64)),
                a.validity & b.validity,
            )

        return lower, out_t
    if op is Op.IS_NULL:
        fa = fns[0]

        def lower(env, aux, _fa=fa):
            a = _fa(env, aux)
            return Column(~a.validity, jnp.ones_like(a.validity))

        return lower, out_t
    if op is Op.IS_NOT_NULL:
        fa = fns[0]

        def lower(env, aux, _fa=fa):
            a = _fa(env, aux)
            return Column(a.validity, jnp.ones_like(a.validity))

        return lower, out_t
    if op is Op.COALESCE:
        def lower(env, aux, _fns=tuple(fns)):
            cols = [f(env, aux) for f in _fns]
            data = cols[-1].data
            valid = cols[-1].validity
            for c in reversed(cols[:-1]):
                data = jnp.where(c.validity, c.data, data)
                valid = c.validity | valid
            return Column(data, valid)

        return lower, out_t
    if op is Op.IF:
        fc, fa, fb = fns

        def lower(env, aux, _fc=fc, _fa=fa, _fb=fb):
            c, a, b = _fc(env, aux), _fa(env, aux), _fb(env, aux)
            take_a = kernels.pred_mask(c)
            return Column(
                jnp.where(take_a, a.data, b.data),
                c.validity & jnp.where(take_a, a.validity, b.validity),
            )

        return lower, out_t
    if op in (Op.CAST_INT32, Op.CAST_INT64, Op.CAST_FLOAT,
              Op.CAST_DOUBLE, Op.CAST_INT8, Op.CAST_INT16,
              Op.CAST_UINT64, Op.CAST_BOOL):
        fa = fns[0]
        ta = ts[0]
        scale = 10.0 ** ta.scale if ta.is_decimal else None
        target = out_t.physical

        def lower(env, aux, _fa=fa, _sc=scale, _tp=target):
            a = _fa(env, aux)
            d = a.data
            if _sc is not None:
                if np.issubdtype(_tp, np.floating):
                    d = d.astype(jnp.float64) / _sc
                else:
                    d = d // int(_sc)
            return Column(d.astype(_tp), a.validity)

        return lower, out_t
    if op in (Op.YEAR, Op.MONTH, Op.DAY):
        fa = fns[0]
        ta = ts[0]
        is_ts = ta.kind == dtypes.Kind.TIMESTAMP
        part = {Op.YEAR: 0, Op.MONTH: 1, Op.DAY: 2}[op]

        def lower(env, aux, _fa=fa, _ts=is_ts, _p=part):
            a = _fa(env, aux)
            days = a.data // 86_400_000_000 if _ts else a.data
            parts = kernels.civil_from_days(days)
            return Column(parts[_p], a.validity)

        return lower, out_t
    if op in (Op.HOUR, Op.MINUTE, Op.SECOND):
        fa = fns[0]
        if ts[0].kind != dtypes.Kind.TIMESTAMP:
            raise TypeError(f"{op} needs a timestamp operand")
        div = {Op.HOUR: 3_600_000_000, Op.MINUTE: 60_000_000,
               Op.SECOND: 1_000_000}[op]
        mod = 24 if op is Op.HOUR else 60

        def lower(env, aux, _fa=fa, _d=div, _m=mod):
            a = _fa(env, aux)
            return Column(
                ((a.data // _d) % _m).astype(jnp.int32), a.validity)

        return lower, out_t
    if op in (Op.DAY_OF_WEEK, Op.DAY_OF_YEAR, Op.WEEK, Op.QUARTER):
        fa = fns[0]
        is_ts = ts[0].kind == dtypes.Kind.TIMESTAMP

        def lower(env, aux, _fa=fa, _ts=is_ts, _op=op):
            a = _fa(env, aux)
            days = a.data // 86_400_000_000 if _ts else a.data
            days = days.astype(jnp.int64)
            if _op is Op.DAY_OF_WEEK:
                out = (days + 4) % 7  # 1970-01-01 = Thursday; 0=Sunday
            elif _op is Op.QUARTER:
                _y, m, _d = kernels.civil_from_days(days)
                out = (m - 1) // 3 + 1
            else:
                y, _m, _d = kernels.civil_from_days(days)
                doy = days - kernels.days_from_civil(
                    y, jnp.ones_like(y), jnp.ones_like(y)) + 1
                out = doy if _op is Op.DAY_OF_YEAR else (doy - 1) // 7 + 1
            return Column(out.astype(jnp.int32), a.validity)

        return lower, out_t
    if op is Op.DIV_INT:
        fa, fb = fns
        ta, tb = ts[0], ts[1]
        sa = 10.0 ** ta.scale if ta.is_decimal else 1.0
        sb = 10.0 ** tb.scale if tb.is_decimal else 1.0
        descale = (ta.is_decimal or tb.is_decimal or ta.is_floating
                   or tb.is_floating)

        def lower(env, aux, _fa=fa, _fb=fb, _sa=sa, _sb=sb,
                  _ds=descale):
            a, b = _fa(env, aux), _fb(env, aux)
            if _ds:
                # integer division of the VALUES: descale, divide,
                # truncate toward zero -> int64
                zero = b.data == 0
                av = a.data.astype(jnp.float64) / _sa
                bv = jnp.where(zero, 1.0,
                               b.data.astype(jnp.float64) / _sb)
                q = jnp.trunc(av / bv).astype(jnp.int64)
                return Column(q, a.validity & b.validity & ~zero)
            return kernels.safe_div(a, b, False)

        return lower, out_t
    if op is Op.NULLIF:
        fa, fb = fns
        ta, tb = ts[0], ts[1]
        # compare in VALUE space (scale-aligned decimals / descaled
        # floats) but return a's ORIGINAL data + type
        sa = ta.scale if ta.is_decimal else 0
        sb = tb.scale if tb.is_decimal else 0
        use_float = ta.is_floating or tb.is_floating
        m = max(sa, sb)

        def lower(env, aux, _fa=fa, _fb=fb, _sa=sa, _sb=sb, _m=m,
                  _ff=use_float):
            a, b = _fa(env, aux), _fb(env, aux)
            if _ff:
                av = a.data.astype(jnp.float64) / (10.0 ** _sa)
                bv = b.data.astype(jnp.float64) / (10.0 ** _sb)
            else:
                av = a.data * (10 ** (_m - _sa))
                bv = b.data * (10 ** (_m - _sb))
            equal = (av == bv) & b.validity
            return Column(a.data, a.validity & ~equal)

        return lower, out_t
    if op is Op.IN_SET:
        # IN over numeric literals: OR of equalities
        fa = fns[0]
        consts = call.args[1:]

        def lower(env, aux, _fa=fa, _cs=tuple(c.value for c in consts)):
            a = _fa(env, aux)
            hit = jnp.zeros_like(a.validity)
            for v in _cs:
                hit = hit | (a.data == v)
            return Column(hit, a.validity)

        return lower, out_t
    raise NotImplementedError(f"lowering for op {op}")


def _descale_mixed(fns, ts):
    """decimal op float -> both float (scaled-int decimals descale)."""
    if len(ts) != 2:
        return fns, ts
    a, b = ts
    if not ((a.is_decimal and b.is_floating)
            or (b.is_decimal and a.is_floating)):
        return fns, ts

    def descaled(fn, scale):
        div = 10.0 ** scale

        def lower(env, aux, _fn=fn, _d=div):
            c = _fn(env, aux)
            return Column(c.data.astype(jnp.float64) / _d, c.validity)

        return lower

    out = list(fns)
    t_out = list(ts)
    for i, t in enumerate(ts):
        if t.is_decimal:
            out[i] = descaled(fns[i], t.scale)
            t_out[i] = dtypes.DOUBLE
    return out, t_out


def _align_decimals(op, call, fns, ts):
    """Rescale decimal operands to a common scale (exact, compile-time)."""
    if len(ts) != 2:
        return fns, ts
    a, b = ts
    if not (a.is_decimal or b.is_decimal):
        return fns, ts
    sa = a.scale if a.is_decimal else 0
    sb = b.scale if b.is_decimal else 0
    if sa == sb:
        return fns, ts
    target = max(sa, sb)

    def rescaled(fn, frm, to):
        mult = 10 ** (to - frm)

        def lower(env, aux, _fn=fn, _m=mult):
            c = _fn(env, aux)
            if jnp.issubdtype(c.data.dtype, jnp.floating):
                # float operand meeting a decimal: scale FIRST, then round
                # to the integer grid (casting first would truncate to 0)
                d = jnp.round(c.data * _m).astype(jnp.int64)
            else:
                d = c.data.astype(jnp.int64) * _m
            return Column(d, c.validity)

        return lower

    out = list(fns)
    t_out = [dtypes.decimal(target), dtypes.decimal(target)]
    if sa < target:
        out[0] = rescaled(fns[0], sa, target)
    if sb < target:
        out[1] = rescaled(fns[1], sb, target)
    return out, t_out


# ---------------- group-by lowering ----------------


@dataclasses.dataclass
class _GroupByLowered:
    lower: Callable  # (env, aux, live_mask) -> (env, length)
    out_names: tuple[str, ...]
    out_types: dict[str, dtypes.LogicalType]


#: Dense group-id path cap: above this many key combinations the sorted
#: path wins (scatter target arrays stay small).
_DENSE_GROUP_LIMIT = 65536


def _resolve_group_by(ctx: _Lowering, step: GroupByStep, cur_types,
                      cur_nullable: dict | None = None):
    keys = step.keys
    bounds = []
    for k in keys:
        if k not in cur_types:
            raise KeyError(f"group-by key {k} not in scope")
        bounds.append(ctx.key_bound(k, cur_types[k]))
    # exact distinct-combination bound: the product of per-key
    # cardinality bounds (+1 for the NULL slot each), when every key
    # has one (dictionary sizes, stats zone maps, caller key_spaces)
    bound_product: int | None = None
    if keys and all(b is not None for b in bounds):
        bound_product = 1
        for b in bounds:
            bound_product *= b + 1
    num_groups = bound_product or 0
    dense = bound_product is not None and \
        bound_product <= _DENSE_GROUP_LIMIT
    if dense and ctx.group_est is not None and not ctx.partial_slots \
            and num_groups > 64 and num_groups > 8 * ctx.group_est:
        # NDV says the mixed-radix slot space is mostly dead (e.g. two
        # 100-ary keys with 50 real combinations): the sorted tier at
        # bound_product capacity beats scattering into dead slots. Both
        # tiers are exact — this is purely a cost choice. partial_slots
        # callers need the slot layout for mesh psum merging, so they
        # keep dense.
        dense = False

    out_types: dict[str, dtypes.LogicalType] = {}
    for k in keys:
        out_types[k] = cur_types[k]
    specs: list[tuple[AggSpec, dtypes.LogicalType]] = []
    # MIN/MAX over a string column must order by dictionary *rank*; ship
    # the rank table and reduce over (rank << 32 | id) packed keys.
    str_rank_aux: dict[str, str] = {}
    for spec in step.aggs:
        t = agg_result_type(spec, ctx.schema, cur_types)
        out_types[spec.out_name] = t
        specs.append((spec, t))
        if (
            spec.func in (Agg.MIN, Agg.MAX)
            and cur_types[spec.column].is_string
        ):
            d = ctx.dictionary(spec.column)
            if d is None:
                raise ValueError(
                    f"MIN/MAX over string column {spec.column} needs its"
                    " dictionary"
                )
            if spec.column not in str_rank_aux:
                str_rank_aux[spec.column] = ctx.add_aux(
                    f"rank.{spec.column}", d.sort_rank()
                )
    out_names = tuple(keys) + tuple(s.out_name for s, _ in specs)

    key_names = tuple(keys)
    use_dense = dense
    b_tuple = tuple(bounds) if dense else ()
    explicit_cap = step.max_groups
    group_bound = bound_product  # exact cap for the sorted tier
    keep_slots = ctx.partial_slots and (dense or not keys)
    if not keys:
        ctx.group_layout = ("keyless", 1)
    elif keep_slots:
        ctx.group_layout = ("dense_slots", num_groups)
    elif dense:
        # dense group-ids, compacted output: array shape is num_groups
        # regardless of input capacity, so partial states are shape-stable
        # and can fold incrementally (ScanExecutor combine path)
        ctx.group_layout = ("dense", num_groups)
    else:
        ctx.group_layout = ("compact", None)

    src_types = {
        s.column: cur_types[s.column] for s, _ in specs
        if s.column is not None
    }
    # statically NULL-free aggregate inputs: their valid-count is the
    # live count and their values need no validity masking — for the
    # common all-NOT-NULL schema this collapses every per-column count
    # slot and every input mask out of the fused pipeline
    nonnull_cols = {
        s.column for s, _ in specs
        if s.column is not None
        and not (cur_nullable or {}).get(s.column, True)
    }
    # integer SUM states double as AVG numerators (the fused reduction
    # keeps integer sums exact, so the f64 cast afterwards is at least
    # as precise as accumulating f64 per row)
    int_sum_cols = {
        s.column: jnp.dtype(t.physical) for s, t in specs
        if s.func is Agg.SUM
        and jnp.issubdtype(jnp.dtype(t.physical), jnp.integer)
    }

    def trace_fused(env, aux, live, gid, ng, kcols, capacity,
                    sorted_keys):
        """ONE shared hit expansion per GroupByStep.

        All linear aggregates (COUNT/SUM/AVG/VAR/STDDEV states) stack
        into per-accumulator-dtype banks, each reduced by one
        kernels.fused_group_reduce; MIN/MAX and the key columns reuse
        the same bool hit matrix. ``sorted_keys`` is what a sort-derived
        layout's ``group_ids_sorted`` left of the keys (else None).
        """
        onehot = ng <= kernels.ONEHOT_GROUP_LIMIT
        # counts share the f64 bank of the AVG/VAR sums in the one-hot
        # tier (exact below 2^53); the large-group tier keeps them int32
        count_dt = jnp.float64 if onehot else jnp.int32

        bank_vecs: dict = {}   # accumulator dtype -> list of row vectors
        slot_ix: dict = {}     # state key -> (dtype, slot index)

        def slot(key, dtype, make_vec):
            dtype = jnp.dtype(dtype)
            if key not in slot_ix:
                vecs = bank_vecs.setdefault(dtype, [])
                slot_ix[key] = (dtype, len(vecs))
                vecs.append(make_vec().astype(dtype))

        def cnt_key(col):
            # NULL-free column: its valid count IS the live count
            return ("live",) if col in nonnull_cols else ("cnt", col)

        def masked(c, col):
            return (c.data if col in nonnull_cols
                    else jnp.where(c.validity, c.data,
                                   jnp.zeros_like(c.data)))

        slot(("live",), count_dt,
             lambda: jnp.ones((capacity,), dtype=jnp.int32))
        for spec, t in specs:
            if spec.func is Agg.COUNT_ALL:
                continue
            c = env[spec.column]
            # per-column valid count: COUNT's value, everyone's validity
            slot(cnt_key(spec.column), count_dt,
                 lambda _c=c: _c.validity.astype(jnp.int32))
            if spec.func is Agg.SUM:
                acc = jnp.dtype(t.physical)
                slot(("sum", spec.column, acc.name), acc,
                     lambda _c=c, _col=spec.column: masked(_c, _col))
            elif spec.func is Agg.AVG:
                if spec.column in int_sum_cols:
                    # share the exact integer SUM state
                    slot(("sum", spec.column,
                          int_sum_cols[spec.column].name),
                         int_sum_cols[spec.column],
                         lambda _c=c, _col=spec.column: masked(_c, _col))
                else:
                    slot(("sum", spec.column, "float64"), jnp.float64,
                         lambda _c=c, _col=spec.column:
                         masked(_c, _col).astype(jnp.float64))
            elif spec.func in (Agg.VAR_SAMP, Agg.STDDEV_SAMP):
                scale = (10.0 ** src_types[spec.column].scale
                         if src_types[spec.column].is_decimal else 1.0)

                def mk_vals(_c=c, _col=spec.column, _s=scale):
                    v = masked(_c, _col).astype(jnp.float64)
                    if _s != 1.0:
                        v = v / _s
                    return v

                slot(("vsum", spec.column), jnp.float64, mk_vals)
                slot(("vsq", spec.column), jnp.float64,
                     lambda _mk=mk_vals: _mk() ** 2)

        banks = {dtype: (vecs[0][:, None] if len(vecs) == 1
                         else jnp.stack(vecs, axis=1))
                 for dtype, vecs in bank_vecs.items()}
        results = {dtype: kernels.fused_group_reduce(
                       stacked, gid, ng, dtype=dtype)
                   for dtype, stacked in banks.items()}
        ctx.notes.update(
            groups=ng,
            key_words=sum(-(-k.data.dtype.itemsize // 4) for k in kcols),
            reduce_tier="+".join(sorted({
                kernels.reduce_tier(ng, dtype, stacked.shape[1])
                for dtype, stacked in banks.items()})))

        def state(key):
            dtype, i = slot_ix[key]
            return results[dtype][:, i]

        def count_of(key):
            return state(key).astype(jnp.int64)

        live_count = count_of(("live",))
        group_live = live_count > 0

        hits = kernels.group_hits(gid, ng) if onehot else None
        new_env: dict[str, Column] = {}
        if key_names and use_dense:
            ctx.notes["key_tier"] = "dense"
            # dense slot ids ARE the keys: decode each key value from
            # the slot index arithmetically (enc = value + 1, 0 = NULL,
            # group_ids_dense's mixed-radix encoding) — zero row passes
            strides = []
            acc = 1
            for b in reversed(b_tuple):
                strides.append(acc)
                acc *= b + 1
            strides.reverse()
            slot_ids = jnp.arange(ng, dtype=jnp.int32)
            for k, c, b, stride in zip(key_names, kcols, b_tuple,
                                       strides):
                enc = (slot_ids // stride) % (b + 1)
                kd = jnp.maximum(enc - 1, 0).astype(c.data.dtype)
                kv = (enc > 0) & group_live
                new_env[k] = Column(kd, kv)
        elif key_names and onehot:
            ctx.notes["key_tier"] = "onehot"
            # one first-row expansion shared by EVERY key column
            first, found = kernels.first_live_index(hits)
            for k, c in zip(key_names, kcols):
                kd = jnp.where(found, c.data[first],
                               jnp.zeros_like(c.data[first]))
                kv = c.validity[first] & found
                new_env[k] = Column(kd, kv & group_live)
        elif key_names:
            # a sort-derived layout above the one-hot tier: group g's
            # key IS the sorted key columns at the g-th segment head, so
            # one compact by the boundary flags puts it in slot g (ids
            # are given in sorted key order); groups past an explicit
            # cap fall off the slice as they fall off the reduce
            ctx.notes["key_tier"] = "segment"
            heads = kernels.compact(
                TableBlock(dict(zip(key_names, sorted_keys.columns)),
                           jnp.int32(capacity),
                           dtypes.schema(*((k, out_types[k])
                                           for k in key_names))),
                sorted_keys.boundary)
            for k in key_names:
                c = heads.columns[k]
                new_env[k] = Column(c.data[:ng],
                                    c.validity[:ng] & group_live)

        for spec, t in specs:
            if spec.func is Agg.COUNT_ALL:
                data = live_count
                valid = (jnp.ones_like(group_live) if not key_names
                         else group_live)
                new_env[spec.out_name] = Column(data, valid)
                continue
            c = env[spec.column]
            nn = count_of(cnt_key(spec.column))
            if spec.func is Agg.COUNT:
                data = nn
                valid = (jnp.ones_like(group_live) if not key_names
                         else group_live)
            elif spec.func is Agg.SUM:
                data = state(("sum", spec.column,
                              jnp.dtype(t.physical).name))
                valid = nn > 0
            elif spec.func in (Agg.MIN, Agg.MAX):
                vals = c.data
                packed = spec.column in str_rank_aux
                if packed:
                    rank = kernels.dict_gather(
                        aux[str_rank_aux[spec.column]], c
                    ).data
                    vals = (
                        rank.astype(jnp.int64) << 32
                    ) | c.data.astype(jnp.int64)
                if onehot:
                    fill = kernels._extreme(
                        vals.dtype, maximum=spec.func is Agg.MIN)
                    hv = (hits if spec.column in nonnull_cols
                          else hits & c.validity[:, None])
                    expanded = jnp.where(
                        hv, vals[:, None],
                        jnp.asarray(fill, dtype=vals.dtype))
                    reduce_fn = (jnp.min if spec.func is Agg.MIN
                                 else jnp.max)
                    data = reduce_fn(expanded, axis=0)
                elif spec.func is Agg.MIN:
                    data = kernels.scatter_min(
                        vals, live & c.validity, gid, ng)
                else:
                    data = kernels.scatter_max(
                        vals, live & c.validity, gid, ng)
                if packed:
                    data = (data & 0xFFFFFFFF).astype(jnp.int32)
                valid = nn > 0
            elif spec.func is Agg.AVG:
                src_t = src_types[spec.column]
                if spec.column in int_sum_cols:
                    s = state(("sum", spec.column,
                               int_sum_cols[spec.column].name)
                              ).astype(jnp.float64)
                else:
                    s = state(("sum", spec.column, "float64"))
                if src_t.is_decimal:
                    s = s / (10.0 ** src_t.scale)
                data = s / jnp.maximum(nn, 1)
                valid = nn > 0
            elif spec.func is Agg.SOME:
                data = kernels.scatter_first(
                    c.data, live & c.validity, gid, ng)
                valid = nn > 0
            elif spec.func in (Agg.VAR_SAMP, Agg.STDDEV_SAMP):
                s = state(("vsum", spec.column))
                q = state(("vsq", spec.column))
                nf = nn.astype(jnp.float64)
                var = (q - s * s / jnp.maximum(nf, 1.0)) \
                    / jnp.maximum(nf - 1.0, 1.0)
                var = jnp.maximum(var, 0.0)  # fp cancellation
                data = (jnp.sqrt(var)
                        if spec.func is Agg.STDDEV_SAMP else var)
                valid = nn > 1
            else:
                raise NotImplementedError(spec.func)
            new_env[spec.out_name] = Column(data, valid)
        return new_env, group_live

    def lower(env, aux, live):
        kcols = [env[k] for k in key_names]
        capacity = next(iter(env.values())).data.shape[0]
        ng_scalar = sorted_keys = None
        if key_names:
            if use_dense:
                gid, ng = kernels.group_ids_dense(kcols, list(b_tuple), live)
            else:
                # a block of N rows has at most N groups: default the group
                # capacity to the block capacity so nothing is ever
                # silently dropped; an explicit max_groups caps it, and
                # a statistics-derived bound product (exact — distinct
                # combinations cannot exceed it) sizes the capacity
                # instead of the block-capacity worst case.
                caps = [capacity]
                if explicit_cap is not None:
                    caps.append(explicit_cap)
                if group_bound is not None:
                    caps.append(group_bound)
                ng = max(1, min(caps))
                gid, ng_scalar, sorted_keys = kernels.group_ids_sorted(
                    kcols, live, ng)
                ng_scalar = jnp.minimum(ng_scalar, jnp.int32(ng))
        else:
            # global aggregate: one group
            gid = jnp.where(live, 0, 1).astype(jnp.int32)
            ng = 1

        new_env, group_live = trace_fused(
            env, aux, live, gid, ng, kcols, capacity, sorted_keys)

        if key_names and keep_slots:
            # mesh-mergeable layout: every slot stays in place; dead slots
            # carry invalid values and zero counts
            length = jnp.int32(ng)
        elif key_names and not use_dense:
            # sorted path: groups already dense [0, n); length = ng_scalar
            length = ng_scalar
        elif not key_names:
            # keyless aggregate always yields exactly one row (SQL:
            # SELECT COUNT(*) ... WHERE false => one row with 0)
            length = jnp.int32(1)
        else:
            # dense path: compact scattered group slots to the front
            blk = TableBlock(
                new_env, jnp.int32(ng),
                dtypes.Schema(tuple(
                    dtypes.Field(n, out_types[n]) for n in out_names)),
            )
            blk = kernels.compact(blk, group_live)
            new_env = dict(blk.columns)
            length = blk.length
        return new_env, length

    return _GroupByLowered(lower=lower, out_names=out_names,
                           out_types=out_types)
