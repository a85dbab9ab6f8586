"""Sharded whole-plan fusion: ONE jitted SPMD computation per plan.

The tentpole of ROADMAP item 3: instead of a third parallel executor,
the PR 9 whole-plan lowering (ssa.plan_fuse) gets sharding annotations.
A fusible plan lowers ONCE into a ``jax.shard_map`` over the ``shard``
mesh axis — per-device scan fragments, ``all_to_all`` hash repartition
in front of every equi-join (parallel/shuffle), psum/gather
partial→final merges for the root aggregate (parallel/dist) — and jits
with donated staged inputs, exactly like "Query Processing on Tensor
Computation Runtimes" compiles whole queries to single sharded tensor
programs. One compiled executable per (plan fingerprint, shape-class
vector, mesh shape); a 1-device mesh degenerates to the single-chip
lowering verbatim (MeshLowering inherits PlanLowering's node hooks);
plans that do not lower fall back to the per-node mesh walk
(mesh_exec.MeshPlanExecutor) and from there to DQ/single-chip.

Shuffle buckets are STATS-SIZED (ISSUE 10 tentpole part 2): the send
bucket per destination is mean load × safety margin plus the
aggregator's count-min heaviest-hitter bound (shuffle.size_buckets),
shape-class rounded so same-class re-runs stay zero-retrace. The traced
worst per-destination count returns to the host with the expand-join
totals; overflow reuses the FusedPlan.grow protocol — the capacity is a
trace-time constant, so growing re-jits with the exact observed size
and the cached plan keeps it for later statements. Correct under 100%
skew, ~n_dev× fewer rows moved on uniform keys.

Results are bit-identical to the single-chip executor: row
partitioning only changes the ORDER partial states fold in, and every
merge is exact (int/decimal sums are int64 limb adds; MIN/MAX/COUNT are
order-free; AVG divides identical sums by identical counts in the
replicated final program).

Env gates: ``YDB_TPU_MESH_FUSE=0`` keeps the per-node mesh walk (A/B
escape hatch); ``YDB_TPU_SHUFFLE_STATS=0`` restores full-capacity
buckets; ``YDB_TPU_MESH=1`` (kqp.session) enables the mesh itself.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ydb_tpu.blocks.block import TableBlock, device_aux
from ydb_tpu.parallel import shuffle as shuffle_mod
from ydb_tpu.parallel.dist import (
    _gather_rows,
    _local,
    _merge_slots,
    merge_spec,
)
from ydb_tpu.parallel.mesh import SHARD_AXIS, shard_map
from ydb_tpu.plan.nodes import (
    Concat,
    ExpandJoin,
    LookupJoin,
    PlanNode,
    TableScan,
    Transform,
)
from ydb_tpu.ssa import join as join_kernels
from ydb_tpu.ssa import plan_fuse, twophase
from ydb_tpu.ssa.plan_fuse import (
    FusedPlan,
    PlanLowering,
    PlanSignature,
    Unfusible,
    expand_schema,
    lookup_schema,
    shape_class,
)
from ydb_tpu.ssa.program import RollupStep, SortStep, WindowStep

#: in-process override (tests pick the executor with it); None defers
#: to the env
MESH_FUSE_FORCE: "bool | None" = None


def mesh_fusion_enabled() -> bool:
    if MESH_FUSE_FORCE is not None:
        return MESH_FUSE_FORCE
    return os.environ.get("YDB_TPU_MESH_FUSE", "1") not in (
        "0", "", "off")


def _walk(plan: PlanNode):
    stack = [plan]
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (LookupJoin, ExpandJoin)):
            stack += [n.probe, n.build]
        elif isinstance(n, Transform):
            stack.append(n.input)
        elif isinstance(n, Concat):
            stack += list(n.inputs)


def _aggregating(program) -> bool:
    return (program is not None
            and (program.group_by is not None
                 or any(isinstance(s, (SortStep, WindowStep, RollupStep))
                        for s in program.steps)))


class _DeviceBound:
    """Facade scan source for plan_signature: per-DEVICE staging bound
    (max rows any one mesh device holds for the table), so shape
    classes — and the FUSE_MAX_ROWS cutoff — size per device, not per
    table. A mesh effectively raises the fusible-table ceiling to
    ndev × FUSE_MAX_ROWS."""

    def __init__(self, num_rows: int, schema):
        self.num_rows = num_rows
        self.schema = schema


class _FacadeDB:
    def __init__(self, sources, dicts, key_spaces):
        self.sources = sources
        self.dicts = dicts
        self.key_spaces = key_spaces


def mesh_signature(plan: PlanNode, db, ndev: int) -> PlanSignature | None:
    """Classify a plan for sharded fusion, None when it doesn't map.

    On top of plan_signature's fusibility rules, the mesh needs the
    ROOT to be a group-by Transform (its two-phase split is the only
    cross-device merge point) and every other program to be elementwise
    — a non-root aggregate or sort would need its own global merge.
    Windows need every row on one device; not mesh-fusible."""
    if not isinstance(plan, Transform):
        return None
    if plan.program.group_by is None:
        return None
    if any(isinstance(s, (RollupStep, WindowStep))
           for s in plan.program.steps):
        return None
    fsources: dict = {}
    for node in _walk(plan):
        if isinstance(node, Transform) and node is not plan:
            if _aggregating(node.program):
                return None
        elif isinstance(node, TableScan):
            if _aggregating(node.program):
                return None  # per-device pushdown aggregate won't merge
            if node.table in fsources:
                continue
            if node.table not in db.sources:
                return None
            subs = db.sources[node.table]
            if not isinstance(subs, (list, tuple)) or not subs:
                return None
            per_dev = max(int(s.num_rows) for s in subs)
            fsources[node.table] = _DeviceBound(per_dev, subs[0].schema)
    return plan_fuse.plan_signature(
        plan, _FacadeDB(fsources, db.dicts, db.key_spaces))


class MeshLowering(PlanLowering):
    """PlanLowering with sharding: every emit runs device-local inside
    shard_map; joins repartition both sides over the shard axis first;
    the root transform merges two-phase partial states across the mesh.
    A 1-device mesh skips every collective and inherits the single-chip
    hooks unchanged — the degenerate case IS the base lowering."""

    def __init__(self, sig: PlanSignature, db, mesh, stats=None):
        super().__init__(sig, db)
        self.mesh = mesh
        self.ndev = int(mesh.shape[SHARD_AXIS])
        self.stats = stats or {}
        self.root = sig.plan
        # (cap slot, physical row bytes) per shuffle — the dispatch-time
        # byte accounting reads the CURRENT cap, so bucket grows show
        self.shuffle_rows: list[tuple[int, int]] = []

    # -- stats-sized shuffle slots (grow protocol, kind="shuffle") --

    def shuffle_slot(self, subtree_cap: int, keys, sch=None) -> int:
        heavy = shuffle_mod.heavy_bound(self.stats, keys)
        self.caps.append(shuffle_mod.size_buckets(
            subtree_cap, self.ndev, heavy=heavy))
        self.cap_kinds.append("shuffle")
        slot = len(self.caps) - 1
        if sch is not None:
            self.shuffle_rows.append((slot, shuffle_mod.row_bytes(sch)))
        return slot

    def _repart(self, block: TableBlock, keys, slot: int, totals):
        out, worst = shuffle_mod.repartition(
            block, list(keys), self.ndev,
            bucket_rows=self.caps[slot], with_counts=True)
        totals[slot] = worst
        return out

    def expand_total(self, total):
        # per-device match counts differ; the host must see the global
        # worst to grow once for everyone
        if self.ndev > 1:
            return jax.lax.pmax(total, SHARD_AXIS)
        return total

    # -- node hooks --

    def lower_lookup(self, node: LookupJoin):
        if self.ndev == 1:
            return super().lower_lookup(node)
        p_emit, p_sch, p_cap = self.lower(node.probe)
        b_emit, b_sch, b_cap = self.lower(node.build)
        sch = lookup_schema(node, p_sch, b_sch)
        pi = self.shuffle_slot(p_cap, node.probe_keys, p_sch)
        bi = self.shuffle_slot(b_cap, node.build_keys, b_sch)
        # after the exchange a device holds at most its receive buffer:
        # one stats-sized bucket from every peer
        out_cap = self.ndev * self.caps[pi]

        def emit(inputs, aux, memo, totals, _n=node, _pe=p_emit,
                 _be=b_emit, _pi=pi, _bi=bi):
            p = self._repart(_pe(inputs, aux, memo, totals),
                             _n.probe_keys, _pi, totals)
            b = self._repart(_be(inputs, aux, memo, totals),
                             _n.build_keys, _bi, totals)
            return join_kernels.run_equi_join(
                p, b, _n.probe_keys, _n.build_keys, kind=_n.kind,
                suffix=_n.suffix, payload=_n.payload)

        return emit, sch, out_cap

    def lower_expand(self, node: ExpandJoin):
        if self.ndev == 1:
            return super().lower_expand(node)
        p_emit, p_sch, p_cap = self.lower(node.probe)
        b_emit, b_sch, b_cap = self.lower(node.build)
        sch = expand_schema(node, p_sch, b_sch)
        pi = self.shuffle_slot(p_cap, node.probe_keys, p_sch)
        bi = self.shuffle_slot(b_cap, node.build_keys, b_sch)
        ei = self.expand_slot(self.ndev * self.caps[pi],
                              node.fanout_hint)
        caps = self.caps

        def emit(inputs, aux, memo, totals, _n=node, _pe=p_emit,
                 _be=b_emit, _pi=pi, _bi=bi, _ei=ei):
            p = self._repart(_pe(inputs, aux, memo, totals),
                             _n.probe_keys, _pi, totals)
            b = self._repart(_be(inputs, aux, memo, totals),
                             _n.build_keys, _bi, totals)
            out, total = join_kernels.expand_join(
                p, b, list(_n.probe_keys), list(_n.build_keys),
                list(_n.probe_payload), list(_n.build_payload),
                out_capacity=caps[_ei],
                build_suffix=_n.build_suffix, kind=_n.kind)
            totals[_ei] = self.expand_total(total)
            return out

        return emit, sch, self.caps[ei]

    def lower_transform(self, node: Transform):
        prog = node.program
        if any(isinstance(s, (RollupStep, WindowStep)) for s in prog.steps):
            raise Unfusible("window function on the mesh")
        if self.ndev == 1 or not _aggregating(prog):
            # 1-device mesh: the base (single-chip) lowering IS the
            # degenerate case; elementwise transforms stay device-local
            return super().lower_transform(node)
        if node is not self.root or prog.group_by is None:
            raise Unfusible("non-root aggregating Transform on the mesh")
        i_emit, i_sch, i_cap = self.lower(node.input)
        partial_prog, final_prog = twophase.split(
            prog, with_row_counts=True)
        aliases = dict(node.dict_aliases)
        p_run, p_cp = self.compiled(partial_prog, i_sch, self.db.dicts,
                                    dict_aliases=aliases,
                                    partial_slots=True)
        f_run = f_cp = None
        if final_prog is not None:
            f_run, f_cp = self.compiled(
                final_prog, p_cp.out_schema, self.db.dicts,
                dict_aliases={**aliases,
                              **twophase.dict_aliases(partial_prog)})
        layout = p_cp.group_layout[0]
        use_slots = layout in ("dense_slots", "keyless")
        merge_kinds, rank_tables = merge_spec(
            partial_prog, p_cp.out_schema, self.db.dicts)
        out_sch = f_cp.out_schema if f_cp is not None else p_cp.out_schema

        def emit(inputs, aux, memo, totals, _ie=i_emit, _pr=p_run,
                 _fr=f_run):
            part = _pr(_ie(inputs, aux, memo, totals), aux)
            # mirror MeshScan.merge_final exactly (bit-identity with the
            # per-node mesh walk and, through it, the single-chip path)
            if _fr is None:
                return _gather_rows(part)
            if use_slots:
                # slot-aligned states: elementwise psum/pmin/pmax — the
                # gradient-allreduce shape (dist._merge_slots)
                merged = _merge_slots(part, merge_kinds, rank_tables)
                if layout == "dense_slots" and "__rows" in merged.columns:
                    from ydb_tpu.ssa import kernels

                    live = merged.columns["__rows"].data > 0
                    merged = kernels.compact(
                        merged, live & merged.row_mask())
            else:
                # generic layouts: all_gather compacted partial rows,
                # re-aggregate replicated (the UnionAll-final shape)
                merged = _gather_rows(part)
            return _fr(merged, aux)

        return emit, out_sch, i_cap


class MeshFusedPlan(FusedPlan):
    """FusedPlan whose run_all is a shard_map over the mesh: staged
    inputs arrive sharded P(shard), the result and totals come back
    replicated. The grow protocol covers BOTH capacity kinds: expand
    joins grow quantum-rounded (exact retry), shuffle buckets grow to
    the shape class of the observed worst destination count."""

    def __init__(self, sites, out_schema, aux, run_all, caps, cap_kinds,
                 fused_stages, donate, mesh, ndev, shuffle_rows=()):
        self.cap_kinds = list(cap_kinds)
        self.mesh = mesh
        self.ndev = ndev
        self.shuffle_grows = 0  # lifetime counter (obs reports deltas)
        self.shuffle_rows = list(shuffle_rows)
        super().__init__(sites, out_schema, aux, run_all, caps,
                         fused_stages, donate)

    def run(self, inputs):
        out = super().run(inputs)
        # host-side movement accounting per dispatch: each shuffle
        # exchanged ndev buckets of the slot's CURRENT capacity from
        # every device (static shapes — grown buckets report grown
        # bytes on later dispatches)
        from ydb_tpu.analysis import memsan
        from ydb_tpu.obs import timeline

        for slot, rb in self.shuffle_rows:
            per_dev = self.ndev * self.expand_caps[slot] * rb
            for d in range(self.ndev):
                timeline.add_bytes(f"shuffle_bytes_dev{d}", per_dev)
            if memsan.armed():
                memsan.charge(per_dev * self.ndev, "shuffle",
                              owner="mesh_fused_dispatch")
        return out

    def shuffle_capacity(self) -> int:
        caps = [c for c, k in zip(self.expand_caps, self.cap_kinds)
                if k == "shuffle"]
        return max(caps) if caps else 0

    def grow(self, idx: int, total: int) -> None:
        if self.cap_kinds[idx] == "shuffle":
            self.expand_caps[idx] = shape_class(int(total))
            self.shuffle_grows += 1
            self._traced = False
            self._jit = self._make_jit()
        else:
            super().grow(idx, total)


def build(sig: PlanSignature, db, mesh, stats=None) -> MeshFusedPlan:
    """Compile a mesh-fusible plan into one sharded MeshFusedPlan (one
    ``ssa.compile`` span covers the whole build, like plan_fuse.build)."""
    from ydb_tpu.obs import tracing

    with tracing.span("ssa.compile") as sp:
        fused = _build(sig, db, mesh, stats)
        sp.set(fused_stages=fused.fused_stages,
               cols=sum(len(s.read_cols) for s in sig.sites),
               mesh_devices=fused.ndev)
    return fused


def _build(sig: PlanSignature, db, mesh, stats=None) -> MeshFusedPlan:
    lo = MeshLowering(sig, db, mesh, stats=stats)
    root, out_schema, _ = lo.lower(sig.plan)
    caps = lo.caps

    def device_fn(inputs, aux):
        totals: list = [jnp.int64(0)] * len(caps)
        local = {k: _local(b) for k, b in inputs.items()}
        out = root(local, aux, {}, totals)
        return out, tuple(totals)

    # the whole plan is ONE shard_map: scans and joins run device-local
    # on the P(shard)-sharded stage, collectives (all_to_all repartition,
    # psum/gather merges) are the only cross-device edges, and the root
    # result is replicated (out_specs=P()) — one XLA executable, fused
    # collectives, no host hops between fragments
    run_all = shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return MeshFusedPlan(
        sig.sites, out_schema, device_aux(lo.aux_np), run_all, caps,
        lo.cap_kinds, sig.fused_stages, plan_fuse._DONATE, mesh, lo.ndev,
        shuffle_rows=lo.shuffle_rows)
