"""Distributed plan execution over the device mesh: grace joins + SPMD
aggregation driving the SAME logical plan trees the single-chip executor
runs (ydb_tpu.plan.nodes).

The reference distributes a query as stage tasks exchanging rows through
hash-partition channels (kqp_tasks_graph.cpp:448; vectorized partition
consumer dq_output_consumer.cpp:338) and joins with GraceJoin buckets
(mkql_grace_join_imp.cpp). The TPU-native design maps those pieces onto
mesh collectives:

  * table scans run per shard (each mesh device owns a table partition;
    filters/projections execute in the per-shard compiled scan),
  * every equi-join hash-REPARTITIONS both sides over the ``shard`` axis
    with ``jax.lax.all_to_all`` (parallel/shuffle.py) so matching keys
    land on the same device, then joins device-locally with the
    sort/searchsorted kernels (ssa/join.py) — the grace-join shape with
    ICI as the spill fabric; a bucket that overflows is exchanged again
    at the observed size (the respill protocol),
  * the final Transform (aggregate/HAVING/ORDER BY) reuses the MeshScan
    two-phase machinery: per-device partial states, psum/pmin/pmax or
    all_gather merge, replicated finalization.

Each stage is one jitted shard_map step; data stays device-resident and
mesh-sharded between stages.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ydb_tpu import chaos
from ydb_tpu.analysis import host_ok, memsan
from ydb_tpu.blocks.block import Column, TableBlock
from ydb_tpu.blocks.dictionary import DictionarySet
from ydb_tpu.chaos import deadline as statement_deadline
from ydb_tpu.engine.oracle import OracleTable
from ydb_tpu.engine.scan import (
    DEFAULT_BLOCK_ROWS,
    ColumnSource,
    ScanExecutor,
)
from ydb_tpu.parallel.dist import (
    MeshScan,
    _local,
    _relocal,
    place_shards,
    shard_scan_span,
)
from ydb_tpu.obs import timeline, tracing
from ydb_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_map
from ydb_tpu.parallel.shuffle import (
    exchange_bytes_per_device,
    heavy_bound,
    repartition,
    size_buckets,
)
from ydb_tpu.plan.executor import _Memo, _pushdown_scan
from ydb_tpu.plan.nodes import ExpandJoin, LookupJoin, TableScan, Transform
from ydb_tpu.ssa import join as join_kernels
from ydb_tpu.ssa import kernels
from ydb_tpu.ssa.plan_fuse import shape_class
from ydb_tpu.ssa.program import RollupStep, SortStep, WindowStep


#: the device-local joins' operations outside ssa/join.py's own scopes
#: (``ydb.sorted_build``, ``ydb.expand_match``, ...) in a device trace
JOIN_SCOPE = "ydb.mesh_join"


def _round_up(n: int) -> int:
    """Intermediate staging capacity: plan_fuse's shape classes (1024
    quantum, quarter-of-power-of-two steps), replacing the walk's old
    ad-hoc 64-row quantum so the per-node mesh walk and the fused mesh
    path land on the SAME block capacities — one compile-cache entry per
    class serves both executors instead of two near-identical traces."""
    return shape_class(n)


class MeshDatabase:
    """Per-shard table partitions + shared dictionaries for mesh runs.

    ``sources[table]`` is a list of per-shard ColumnSource /
    PortionStreamSource objects, EXACTLY one per mesh device
    (row-partitioned tables; partition a small table with empty-slice
    sources for the extra devices).
    """

    def __init__(self, sources: dict[str, list], dicts=None,
                 key_spaces=None, table_stats=None):
        self.sources = sources
        self.dicts = dicts if dicts is not None else DictionarySet()
        self.key_spaces = key_spaces
        # aggregator TableStats by name: sizes shuffle buckets (the
        # count-min heavy-hitter bound); advisory — missing stats only
        # cost a grow-retrace under skew, never correctness
        self.table_stats = table_stats


class _ChainSource:
    """Several per-shard sources presented as ONE device's scan input
    (shard count need not equal mesh size: shards group round-robin
    onto devices). Duck-types the ColumnSource surface ScanExecutor
    streams from; sub-streams rechunk to ONE fixed capacity so the
    compiled per-block program never retraces, and start_block seeks
    work (the stream_blocks contract every other source honors)."""

    def __init__(self, subs: list):
        self.subs = list(subs)
        self.schema = subs[0].schema
        self.dicts = subs[0].dicts

    @property
    def num_rows(self) -> int:
        return sum(s.num_rows for s in self.subs)

    def blocks(self, block_rows: int, columns=None, start_block: int = 0):
        from ydb_tpu.engine.reader import stream_blocks

        names = tuple(columns) if columns is not None else self.schema.names
        sch = self.schema.select(names)
        cap = min(block_rows, max(self.num_rows, 1))

        def payloads():
            for s in self.subs:
                for b in s.blocks(block_rows, names):
                    yield b.to_numpy(), b.validity_numpy()

        yield from stream_blocks(payloads(), names, sch, cap,
                                 start_block=start_block)


@host_ok("mesh partition grouping: bounded by device count; only"
         " EMPTY mesh slots allocate (0-row placeholder sources)")
def device_partitions(sources: list, n: int, schema, dicts) -> list:
    """Group a table's per-shard sources onto exactly ``n`` mesh devices
    (round-robin; empty devices get an empty source) — the seam that
    lets any shard count ride any mesh size."""
    out = []
    for d in range(n):
        g = sources[d::n]
        if not g:
            out.append(ColumnSource(
                {f.name: np.empty(0, dtype=f.type.physical)
                 for f in schema.fields}, schema, dicts))
        elif len(g) == 1:
            out.append(g[0])
        else:
            out.append(_ChainSource(g))
    return out


def _chaos_dispatch(n_devices: int) -> None:
    """``mesh.dispatch`` injection site: 'device_lost' raises
    :class:`chaos.DeviceLostError`, which the plan executor's fallback
    chain turns into single-chip execution (fused, then the walk)."""
    fault = chaos.hit("mesh.dispatch", devices=n_devices)
    if fault is not None:
        fault.sleep()
        if fault.kind == "device_lost":
            raise chaos.DeviceLostError(
                f"injected device loss on the {n_devices}-device mesh")


class MeshPlanExecutor:
    """Executes a logical plan tree SPMD over the mesh."""

    def __init__(self, db: MeshDatabase, mesh=None):
        self.db = db
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n = self.mesh.shape[SHARD_AXIS]
        self._jit_cache: dict = {}

    # ---- node execution (stacked, device-sharded results) ----

    def execute(self, plan) -> OracleTable:
        _chaos_dispatch(self.n)
        out = self._exec(plan, _Memo(plan), root=True)
        return OracleTable.from_block(out)

    # ---- whole-plan sharded fusion (parallel/mesh_fuse) ----

    def execute_fused(self, plan) -> OracleTable | None:
        """One sharded jitted dispatch for the whole plan, or None when
        the plan does not mesh-fuse (the caller falls through to the
        per-node walk above). Compiled MeshFusedPlans — and the negative
        doesn't-fuse verdicts — cache per (plan fingerprint, shape-class
        vector, mesh size) in the executor's jit cache."""
        from ydb_tpu.obs import tracing
        from ydb_tpu.parallel import mesh_fuse

        if not mesh_fuse.mesh_fusion_enabled():
            return None
        sig = mesh_fuse.mesh_signature(plan, self.db, self.n)
        if sig is None or not sig.sites:
            return None
        key = ("mesh_fuse", self.n, sig.cache_key(self.db))
        fused = self._jit_cache.get(key)
        if fused == "unfusible":
            return None
        fresh = fused is None
        with tracing.span("plan.fuse") as fsp:
            if fresh:
                try:
                    fused = mesh_fuse.build(sig, self.db, self.mesh,
                                            stats=self.db.table_stats)
                except (mesh_fuse.Unfusible, NotImplementedError):
                    # negative verdicts cache too: plan_signature is
                    # cheap but build walks every program
                    self._jit_cache[key] = "unfusible"
                    return None
                self._jit_cache[key] = fused
            ft0 = fused.first_trace_seconds or 0.0
            grows0 = fused.shuffle_grows
            inputs = self._stage_fused(fused)
            while True:
                # cancellation + device-loss points between dispatches:
                # a statement past its deadline stops HERE (the fused
                # computation itself is uninterruptible), and an
                # injected device loss degrades to the single-chip path
                statement_deadline.check_current("mesh dispatch")
                _chaos_dispatch(self.n)
                out, totals = fused.run(inputs)
                over = fused.overflowed(totals)
                if not over:
                    break
                # a shuffle bucket or expand join outgrew its static
                # capacity: widen to the observed size (the cached plan
                # keeps it for later statements) and re-stage — donation
                # consumed the inputs
                for j in over:
                    fused.grow(j, totals[j])
                inputs = self._stage_fused(fused)
            if fsp.recording:
                fsp.set(fused_stages=fused.fused_stages,
                        fragments_elided=fused.fused_stages - 1,
                        compile_cache=("miss" if fresh else "hit"),
                        mesh_devices=self.n,
                        shuffle_capacity=fused.shuffle_capacity(),
                        shuffle_grows=fused.shuffle_grows - grows0)
                ft = (fused.first_trace_seconds or 0.0) - ft0
                if ft:
                    fsp.set(first_trace_seconds=round(ft, 6))
        return OracleTable.from_block(out)

    def _stage_fused(self, fused) -> dict:
        """Stage every scan site as a mesh-sharded stacked block: each
        device's partition streams, fits to the per-device shape-class
        capacity (plan_fuse.fit_blocks — fresh buffers, safe to donate),
        and the per-device blocks stack under NamedSharding(P(shard))."""
        from ydb_tpu.ssa.plan_fuse import fit_blocks

        inputs: dict = {}
        for site in fused.sites:
            subs = self.db.sources[site.table]
            if len(subs) != self.n:
                raise ValueError(
                    f"table {site.table} has {len(subs)} shards for a"
                    f" {self.n}-device mesh (need exactly one per device)")
            devs = []
            for sub in subs:
                blocks = tuple(sub.blocks(DEFAULT_BLOCK_ROWS, site.read_cols))
                if not blocks:
                    # portion streams yield nothing for an empty shard
                    blocks = (TableBlock.from_numpy(
                        {f.name: np.empty(0, dtype=f.type.physical)
                         for f in site.in_schema.fields},
                        site.in_schema),)
                devs.append(fit_blocks(blocks, site.capacity))
            inputs[site.key] = place_shards(devs, self.mesh,
                                            owner="stage_fused")
        return inputs

    def _exec(self, plan, memo: dict, root: bool = False):
        hit = memo.get(id(plan))
        if hit is not None:
            return hit
        if isinstance(plan, TableScan):
            out = self._scan(plan)
        elif isinstance(plan, LookupJoin):
            out = self._join(plan, memo, expand=False)
        elif isinstance(plan, ExpandJoin):
            out = self._join(plan, memo, expand=True)
        elif isinstance(plan, Transform):
            out = self._transform(plan, memo, root)
        else:
            raise NotImplementedError(plan)
        memo[id(plan)] = out
        return out

    def _shards_of(self, table: str) -> list:
        subs = self.db.sources[table]
        if len(subs) != self.n:
            # more sources than devices would silently drop every block
            # past the first per device (sharded leading axis)
            raise ValueError(
                f"table {table} has {len(subs)} shards for a"
                f" {self.n}-device mesh (need exactly one per device)")
        return subs

    def _scan(self, plan: TableScan) -> TableBlock:
        """Per-shard scan: pushdown program runs in each shard's scan
        executor; per-shard results pad-stack onto the mesh."""
        subs = self._shards_of(plan.table)
        ex, fresh = None, False
        if plan.program is not None:
            # one compiled executor per (table, program), shared by the
            # shards and kept across statements like every other step
            # here: a fresh ScanExecutor is a fresh jit, i.e. an XLA
            # compile per shard per statement
            key = ("scan", plan.table, plan.program)
            ex = self._jit_cache.get(key)
            fresh = ex is None
            if fresh:
                ex = ScanExecutor(
                    plan.program, subs[0], block_rows=DEFAULT_BLOCK_ROWS,
                    key_spaces=self.db.key_spaces).detach()
                self._jit_cache[key] = ex
        locals_: list[TableBlock] = []
        for d, sub in enumerate(subs):
            # the shards are scanned one after another on this thread,
            # each waited for: its rows are fetched and concatenated
            with shard_scan_span(sub, plan.table, d, fresh) as timer:
                if ex is None:
                    names = plan.columns or sub.schema.names
                    blks = list(sub.blocks(DEFAULT_BLOCK_ROWS, names))
                    blk = blks[0] if len(blks) == 1 else _concat(blks)
                else:
                    blk = ex.run_stream(
                        sub.blocks(DEFAULT_BLOCK_ROWS, ex.read_cols),
                        timer=timer)
                locals_.append(blk)
        with tracing.span("device.wait"):
            cap = _round_up(max(int(b.length) for b in locals_))
        return place_shards(locals_, self.mesh, capacity=cap)

    def _join(self, plan, memo, expand: bool) -> TableBlock:
        probe = self._exec(plan.probe, memo)
        build = self._exec(plan.build, memo)
        pkeys = list(plan.probe_keys)
        bkeys = list(plan.build_keys)
        probe = self._repartition(probe, pkeys)
        build = self._repartition(build, bkeys)
        if not expand:
            return self._local_lookup(plan, probe, build)
        return self._local_expand(plan, probe, build)

    # -- repartition with overflow retry --

    def _repartition(self, stacked: TableBlock, keys: list[str]):
        """One side of a join exchanged over the mesh, under a
        ``mesh.shuffle`` span: every attempt's dispatch, the wait for
        its worst bucket count and the wait that tightens the output
        lie beneath it (the ``mesh_shuffle`` statement key)."""
        # the rows a device holds: a stacked block's ``capacity`` is its
        # leading axis, the devices
        cap = _device_rows(stacked)
        # stats-sized first attempt (mean load × margin + the count-min
        # heavy-hitter bound) instead of the old blind 2/n-of-capacity;
        # overflow grows to the shape class of the OBSERVED worst count
        # — one exact retry, not a doubling ladder
        B = first = size_buckets(
            cap, self.n, heavy=heavy_bound(self.db.table_stats, keys))
        attempts = sent = 0
        with tracing.span("mesh.shuffle") as sp:
            while True:
                key = ("repart", stacked.schema, tuple(keys), cap, B)
                step = self._jit_cache.get(key)
                if step is None:
                    n = self.n

                    def go(st, _B=B):
                        blk, worst = repartition(
                            _local(st), keys, n, bucket_rows=_B,
                            with_counts=True)
                        return _relocal(blk), worst

                    step = jax.jit(shard_map(
                        go, mesh=self.mesh, in_specs=P(SHARD_AXIS),
                        out_specs=(P(SHARD_AXIS), P()),
                        check_vma=False,
                    ))
                    self._jit_cache[key] = step
                with tracing.span("dispatch", program="mesh_repartition"):
                    out, worst = step(stacked)
                # every attempt (including an overflow retry) was a real
                # mesh exchange — account its per-device bytes, and charge
                # the send/recv bucket capacity to the shuffle budget (an
                # overflow retry re-allocates GROWN buckets: each attempt
                # charges its own footprint)
                per_dev = exchange_bytes_per_device(stacked.schema, self.n,
                                                    B)
                attempts += 1
                sent += per_dev
                for d in range(self.n):
                    timeline.add_bytes(f"shuffle_bytes_dev{d}", per_dev)
                if memsan.armed():
                    memsan.charge(per_dev * self.n, "shuffle",
                                  owner="repartition")
                with tracing.span("device.wait"):
                    w = int(np.asarray(worst))
                if w <= B:
                    out = self._tighten(out)
                    sp.set(keys=",".join(keys), capacity=cap,
                           bucket_rows=first, bucket_rows_final=B,
                           worst=w, attempts=attempts,
                           bytes_per_device=sent)
                    return out
                # grace respill, sized by the observation
                B = shape_class(w)
                timeline.add_count("shuffle_grows")

    def _tighten(self, stacked: TableBlock) -> TableBlock:
        """Slice a front-packed stacked block down to a tight capacity so
        join/shuffle output capacities do not compound across stages."""
        with tracing.span("device.wait"):
            max_len = int(np.asarray(stacked.length).max())
        cap = _round_up(max_len)
        if cap >= _device_rows(stacked):
            return stacked
        key = ("tighten", stacked.schema, _device_rows(stacked), cap)
        step = self._jit_cache.get(key)
        if step is None:
            # one program for all the columns; each device slices its own
            step = jax.jit(lambda st: TableBlock(
                {n: Column(c.data[:, :cap], c.validity[:, :cap])
                 for n, c in st.columns.items()}, st.length, st.schema))
            self._jit_cache[key] = step
        with tracing.span("dispatch", program="mesh_tighten"):
            return step(stacked)

    # -- local joins --

    def _local_lookup(self, plan: LookupJoin, probe, build):
        key = ("lookup", plan.probe_keys, plan.build_keys, plan.payload,
               plan.kind, plan.suffix, probe.schema, build.schema,
               probe.capacity, build.capacity)
        step = self._jit_cache.get(key)
        if step is None:
            @jax.named_scope(JOIN_SCOPE)
            def go(pst, bst):
                # shared dispatch with the single-chip executor/DQ path
                # (lookup joins are jit-safe; no host retry involved)
                out = join_kernels.run_equi_join(
                    _local(pst), _local(bst), plan.probe_keys,
                    plan.build_keys, kind=plan.kind, suffix=plan.suffix,
                    payload=plan.payload)
                return _relocal(out)

            step = jax.jit(shard_map(
                go, mesh=self.mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                out_specs=P(SHARD_AXIS), check_vma=False,
            ))
            self._jit_cache[key] = step
        with tracing.span("mesh.join", kind=plan.kind, expand=0,
                          probe_capacity=_device_rows(probe),
                          build_capacity=_device_rows(build)) as sp:
            with tracing.span("dispatch", program="mesh_lookup"):
                out = step(probe, build)
            out = self._tighten(out)
            sp.set(out_capacity=_device_rows(out), attempts=1)
        return out

    def _local_expand(self, plan: ExpandJoin, probe, build):
        """An expanding join in two programs, as ``run_equi_join`` makes
        it on one chip: the sort-bearing match, which does not depend on
        the output's size, then, with the devices' exact totals read
        back, the emit at the largest total's shape class. No guessed
        capacity, so no overflow that would compile and run the sort
        again."""
        shapes = (plan.probe_keys, plan.build_keys, plan.kind,
                  probe.schema, build.schema, _device_rows(probe),
                  _device_rows(build))
        sides = (P(SHARD_AXIS), P(SHARD_AXIS))
        with tracing.span("mesh.join", kind=plan.kind, expand=1,
                          probe_capacity=_device_rows(probe),
                          build_capacity=_device_rows(build)) as sp:
            key = ("match",) + shapes
            match = self._jit_cache.get(key)
            if match is None:
                @jax.named_scope(JOIN_SCOPE)
                def find(pst, bst):
                    found = join_kernels._expand_match(
                        _local(pst), _local(bst), list(plan.probe_keys),
                        list(plan.build_keys), plan.kind)
                    return tuple(x[None] for x in found)

                match = jax.jit(shard_map(
                    find, mesh=self.mesh, in_specs=sides,
                    out_specs=P(SHARD_AXIS), check_vma=False,
                ))
                self._jit_cache[key] = match
            with tracing.span("dispatch", program="mesh_match"):
                found = match(probe, build)
            with tracing.span("device.wait"):
                cap = _round_up(int(np.asarray(found[-1]).max()))
            key = ("emit", plan.probe_payload, plan.build_payload,
                   plan.build_suffix, cap) + shapes
            emit = self._jit_cache.get(key)
            if emit is None:
                @jax.named_scope(JOIN_SCOPE)
                def go(pst, bst, mst, _cap=cap):
                    out, _ = join_kernels._expand_emit(
                        _local(pst), _local(bst),
                        tuple(x[0] for x in mst),
                        list(plan.probe_payload),
                        list(plan.build_payload), _cap,
                        plan.build_suffix, plan.kind)
                    return _relocal(out)

                emit = jax.jit(shard_map(
                    go, mesh=self.mesh, in_specs=sides + (P(SHARD_AXIS),),
                    out_specs=P(SHARD_AXIS), check_vma=False,
                ))
                self._jit_cache[key] = emit
            with tracing.span("dispatch", program="mesh_expand"):
                out = emit(probe, build, found)
            sp.set(out_capacity=cap, attempts=1)
        return out

    # -- final transform (two-phase over the mesh) --

    def _scan_aggregated(self, plan: Transform,
                         shared: set) -> TableBlock | None:
        """The walk's aggregate pushdown (plan/executor.py
        ``_scan_aggregated``) over the mesh: ``Transform(TableScan)``
        as ONE streaming scan a shard, each on its own device, that
        aggregates every block under its filter mask and folds the
        partial states into one; one collective step merges the shards'
        states and finalizes (``MeshScan.run_sources``). No block is
        compacted, nothing is fetched before the answer, no program is
        shaped by a selected row count, and the shards run side by
        side. None where the shape does not allow it (the caller scans
        the shards' rows out and aggregates them in the collective
        step): ``_pushdown_scan``'s conditions, or a group layout whose
        states are not slot-aligned (sort-derived). The MeshScan that
        says so stays cached, so the next run of the statement asks a
        dict."""
        pushed = _pushdown_scan(plan, shared)
        if pushed is None:
            return None
        subs = self._shards_of(pushed.table)
        key = ("pushdown", pushed.table, pushed.program, plan.dict_aliases)
        scan = self._jit_cache.get(key)
        fresh = scan is None
        if fresh:
            scan = MeshScan(
                pushed.program, subs[0].schema, self.db.dicts,
                self.db.key_spaces, mesh=self.mesh,
                dict_aliases=dict(plan.dict_aliases),
            )
            self._jit_cache[key] = scan
        if not scan.folds_partials:
            return None
        # on the ``mesh`` span: the shards whose scans took the pushdown
        tracing.annotate(agg_pushdown=self.n)
        return scan.run_sources(subs, DEFAULT_BLOCK_ROWS,
                                table=pushed.table, fresh=fresh)

    def _transform(self, plan: Transform, memo, root: bool):
        if any(isinstance(s, (RollupStep, WindowStep))
               for s in plan.program.steps):
            # ranking windows and rollups need every row at once; a
            # per-shard elementwise run would rank within shards. Fall
            # back to the single-chip/DQ path.
            raise NotImplementedError("window function on the mesh")
        if root:
            out = self._scan_aggregated(plan, memo.shared)
            if out is not None:
                return out
        stacked = self._exec(plan.input, memo)
        has_gb = plan.program.group_by is not None
        has_sort = any(isinstance(s, SortStep) for s in plan.program.steps)
        if not (has_gb or has_sort):
            # distributed elementwise transform: stays sharded
            key = ("xform", plan.program, plan.dict_aliases,
                   stacked.schema, stacked.capacity)
            step = self._jit_cache.get(key)
            if step is None:
                from ydb_tpu.ssa.compiler import compile_program

                cp = compile_program(
                    plan.program, stacked.schema, self.db.dicts,
                    self.db.key_spaces,
                    dict_aliases=dict(plan.dict_aliases))
                with memsan.seam("staging"):
                    aux = {k: jnp.asarray(v)
                           for k, v in cp.aux.items()}
                if memsan.armed():
                    memsan.charge(memsan.nbytes_of(aux), "staging",
                                  owner="xform_aux")

                def go(st):
                    return _relocal(cp.run(_local(st), aux))

                step = jax.jit(shard_map(
                    go, mesh=self.mesh, in_specs=P(SHARD_AXIS),
                    out_specs=P(SHARD_AXIS), check_vma=False,
                ))
                self._jit_cache[key] = step
            with tracing.span("dispatch", program="mesh_xform"):
                out = step(stacked)
            return self._tighten(out)
        if not root:
            raise NotImplementedError(
                "non-root aggregating Transform on the mesh")
        key = ("final", plan.program, plan.dict_aliases, stacked.schema,
               stacked.capacity)
        scan = self._jit_cache.get(key)
        if scan is None:
            scan = MeshScan(
                plan.program, stacked.schema, self.db.dicts,
                self.db.key_spaces, mesh=self.mesh,
                dict_aliases=dict(plan.dict_aliases),
            )
            self._jit_cache[key] = scan
        # MeshScan's step expects the partial program's read columns only
        return scan.run_stacked(stacked)


def _device_rows(stacked: TableBlock) -> int:
    """Rows a device holds of a stacked block (its ``capacity`` is the
    leading axis, the devices)."""
    return next(iter(stacked.columns.values())).data.shape[1]


def _concat(blocks: list[TableBlock]) -> TableBlock:
    from ydb_tpu.blocks.block import concat_blocks

    return concat_blocks(blocks)
