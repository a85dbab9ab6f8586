"""HBM-resident column tier: decoded portion columns pinned on device.

The third level of the storage hierarchy (blob store -> host blocks ->
device-resident columns) and the engine-side answer to ROADMAP item 1:
the kernel tier runs Q1 at billions of rows/s because its blocks ALREADY
live in device memory, while the engine path re-ingests from host bytes
on every scan. Theseus's thesis (PAPERS.md) is that accelerator query
efficiency comes from *not moving data*, and TQP shows a device-resident
table representation is what makes whole-query tensor execution pay off
— this module is that representation for the ColumnShard.

Unlike ``DeviceBlockCache`` (whole block STREAMS keyed by portion set +
read columns + geometry + predicate fingerprint — any new column subset
or predicate rebuilds from host bytes), the resident store pins
per-(portion, column) decoded device arrays. Portions are immutable, so
one promoted portion serves EVERY scan shape: scans assemble
fixed-capacity ``TableBlock``s directly from resident arrays (zero host
decode or transfer), and portions not yet resident fall through to the
staged host path mid-stream — a partially resident table still wins on
its resident fraction.

Block assembly costs the host one enqueue or none. A portion that
fills a block exactly hands over its own arrays. Every other block is
cut by ONE compiled program (``_assemble``) over all the columns read,
whose bounds are runtime arguments; its identity is the block capacity,
the column dtypes and the pieces' array lengths. So that those lengths
do not follow the data, a resident array is held at its portion length
rounded up (``resident_rows``: to a multiple of ``GRANULE``, below it
to a power of two), the padding data 0 / validity False, and the budget
counts the padded bytes.

Promotion is asynchronous on the shared conveyor ("resident_promote"
queue): eager at portion write/compaction output (the columns are
already in memory) and heat-driven from scan access counters (a portion
read twice from the host path is worth pinning). Eviction is
budget-bounded (``YDB_TPU_RESIDENT_BYTES`` valve, same semantics as the
scan-cache valve) with zone-map-informed victim choice: portions the
zone maps keep pruning away deliver no resident value and go first,
then cold-by-access portions (LRU heat). Invalidation is by immutable
portion id: compaction/TTL rewrites mint NEW ids, old ids keep serving
readers at old snapshots until GC drops them from the portion map.

Gates: ``YDB_TPU_RESIDENT=0`` disables the tier everywhere (every
portion stages through the host path, with bit-identical answers);
``=1`` forces it on even on CPU backends (where the default budget is 0
because "device" memory is host RSS). ``RESIDENT_FORCE`` is the
in-process override for tests, without environment mutation.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ydb_tpu import chaos
from ydb_tpu.analysis import leaksan, memsan, sanitizer
from ydb_tpu.blocks.block import Column, TableBlock
from ydb_tpu.chaos import deadline as statement_deadline
from ydb_tpu.obs import timeline, tracing
from ydb_tpu.obs.counters import root_counters

#: test override: True/False forces the gate, None = environment
RESIDENT_FORCE: "bool | None" = None

#: budget when the gate is FORCED on where the device reports no HBM
#: (CPU tests: the bytes are host memory)
FORCED_BYTES = 4 << 30

#: every live store: ResidentStore.budget() reads the others' bytes
_STORES: "weakref.WeakSet[ResidentStore]" = weakref.WeakSet()

#: host-path reads of one portion before heat promotion triggers
PROMOTE_HEAT = 2

#: concurrent promotion tasks per store: promotions ride the SHARED
#: conveyor next to scan-prefetch producers, so a flood of queued
#: promotions must never starve staging admission (submit_if_free turns
#: producers away whenever the heap is non-empty)
MAX_INFLIGHT = 4

#: a resident array's length is its portion's row count rounded up to a
#: multiple of this (``resident_rows``), so the assembly program, keyed
#: by its arguments' shapes, is the same program for every portion
#: length inside one granule. A power of two that divides
#: ``scan_block_rows``, so a block-filling portion pads nothing. 2^13:
#: the benchmark's hash-sharded portions (2^20 +- 2K rows, a 305-307K
#: tail) fall into the same three lengths at every granule from 2^13 to
#: 2^16, and 2^13 adds 0.5% to the resident bytes where 2^16 adds 3.4%
#: (PERF.md section 4)
GRANULE = 1 << 13


def _gate() -> "bool | None":
    """Tri-state tier gate: False = off, True = forced on, None = auto
    (budget decides — on for accelerator backends, off on CPU)."""
    if RESIDENT_FORCE is not None:
        return RESIDENT_FORCE
    env = os.environ.get("YDB_TPU_RESIDENT")
    if env is None:
        return None
    return env not in ("0", "", "off")


def default_budget() -> int:
    """Auto budget of ONE device's resident tier: a share of the HBM
    it reports (engine/hbm.py), 0 on CPU (there "device" memory is
    host RSS and the out-of-core tests own that bound)."""
    from ydb_tpu.engine import hbm

    return hbm.resident_budget()


def resident_rows(rows: int) -> int:
    """The length a portion of ``rows`` rows is held at: the next
    multiple of GRANULE, or below GRANULE the next power of two (a
    trickle of small portions must not cost a granule each)."""
    if rows >= GRANULE:
        return -(-rows // GRANULE) * GRANULE
    return 1 << max(rows - 1, 0).bit_length()


def _padded(a: np.ndarray, rows: int) -> np.ndarray:
    """``a`` at ``rows`` rows, zeros (False) past its own."""
    if len(a) == rows:
        return a
    out = np.zeros(rows, dtype=a.dtype)
    out[:len(a)] = a
    return out


def _counters():
    """The process's ``component=resident`` counters: every store's
    promotions summed, and still there once the stores are freed."""
    return root_counters().group(component="resident")


@contextlib.contextmanager
def _promote_stage(stage: str):
    """One stage of a promotion as a leaf ``resident.promote.<stage>``;
    its duration, once it has one, into ``promote_seconds{stage=}``."""
    with tracing.leaf("resident.promote." + stage) as sp:
        yield sp
    if sp.recording:
        _counters().group(stage=stage).counter(
            "promote_seconds").inc(sp.seconds)


class _Entry:
    """One resident column of one portion: decoded device arrays at
    ``resident_rows`` of the portion's length, data 0 / validity False
    past its rows (the portion's true count rides the scan's run)."""

    __slots__ = ("data", "validity", "nbytes")

    def __init__(self, data, validity):
        self.data = data
        self.validity = validity
        self.nbytes = int(data.nbytes) + int(validity.nbytes)


class ResidentStore:
    """Per-shard device-resident portion store.

    Structured per-shard deliberately: ROADMAP item 3 (multi-device
    scan parallelism) slices tables shard-per-device, so a per-shard
    store maps 1:1 onto a per-device resident set later.

    Thread model: one lock guards ALL mutable state (entry map, portion
    info, heat counters, in-flight set, byte ledger, stat counters).
    Device work (jnp array construction) always happens OUTSIDE the
    lock; promotion single-flights per portion id via ``_inflight``.
    """

    def __init__(self, name: str, budget: "int | None" = None):
        self.name = name
        self._budget = budget
        # sanitizer-tracked under YDB_TPU_TSAN=1; per-instance names so
        # distinct stores never share lockset state
        self._lock = sanitizer.make_lock(f"resident.{name}.lock")
        # (portion_id, column) -> _Entry
        self._cols = sanitizer.share({}, f"resident.{name}.cols")
        # portion_id -> {rows, nbytes, cols, heat, tick, zskips}
        self._info: dict = {}
        # portion_id -> host-path access count (heat toward promotion)
        self._miss_heat: dict = {}
        self._inflight: set = set()
        self._pending: list = []  # conveyor TaskHandles (drain support)
        # mesh device slice (set_device_slice): when the cluster mesh is
        # on, each shard's store binds to ONE mesh device — promotions
        # place arrays there and the budget narrows to the device share,
        # so mesh scans read columns already resident on the device that
        # computes them (no cross-device pull at dispatch)
        self._slice_slot: "int | None" = None
        self._slice_device = None
        self._slice_budget: "int | None" = None
        self._nbytes = 0
        self._tick = 0
        # counters (the sys_resident_store / viewer surface)
        self.hits = 0
        self.misses = 0
        self.promotions = 0
        self.evictions = 0
        self.spills = 0
        self.invalidations = 0
        self.errors = 0
        _STORES.add(self)

    # ---- gates ----

    def budget(self) -> int:
        """YDB_TPU_RESIDENT_BYTES overrides EVERYTHING (the operator's
        emergency valve for HBM pressure; malformed values disable
        rather than poison the read path). Otherwise the mesh device
        slice, else the constructor budget, else auto — and an auto
        store never exceeds what the other auto stores on its device
        leave of the device's share: a table has a store per shard and
        a cluster many tables, all in one HBM."""
        env = os.environ.get("YDB_TPU_RESIDENT_BYTES")
        if env is not None:
            try:
                return int(env)
            except ValueError:
                return 0
        own = (self._slice_budget if self._slice_budget is not None
               else self._base_budget())
        if self._budget is not None:
            return own
        held = sum(st._nbytes for st in list(_STORES)
                   if st is not self and st._budget is None
                   and st._slice_device is self._slice_device)
        return max(min(own, self._base_budget() - held), 0)

    def _base_budget(self) -> int:
        """Budget ignoring any mesh device slice and any other store:
        what this store may hold when it owns its device alone
        (assign_device_slices divides this across the shards sharing
        one mesh device). Auto = the device's share, or FORCED_BYTES
        when the gate is forced on where no HBM is reported."""
        if self._budget is not None:
            return self._budget
        total = default_budget()
        if total <= 0 and _gate() is True:
            total = FORCED_BYTES
        return total

    # ---- mesh device slices ----

    def set_device_slice(self, slot: int, device, budget: int) -> None:
        """Bind this store to one mesh device: promotions land on
        ``device`` and the budget narrows to the per-device share (the
        ledger evicts down immediately — a store that grew under the
        full budget must not keep over-occupying its device)."""
        with self._lock:
            rebound = device is not None and \
                device is not self._slice_device
            self._slice_slot = slot
            self._slice_device = device
            self._slice_budget = int(budget)
            self._evict_to_budget_locked(self._slice_budget)
            entries = list(self._cols.values()) if rebound else []
        if entries:
            # columns promoted before the binding sit on the default
            # device: move them (outside the lock; a concurrent scan
            # reads either copy), or every mesh scan computes there.
            # The same bytes, already charged at promotion
            with memsan.seam("resident"):
                for e in entries:
                    e.data = jax.device_put(e.data, device)
                    e.validity = jax.device_put(e.validity, device)

    def clear_device_slice(self) -> None:
        with self._lock:
            self._slice_slot = None
            self._slice_device = None
            self._slice_budget = None

    def enabled(self) -> bool:
        g = _gate()
        if g is False:
            return False
        return self.budget() > 0

    # ---- read path ----

    def lookup(self, portion_id: int, names) -> "dict | None":
        """All-or-nothing: every requested column resident -> the entry
        dict (and a heat/LRU touch); any gap -> None (the scan falls
        through to the host path and ``record_miss`` counts the heat)."""
        if not names:
            return None
        if chaos.hit("resident.lookup", portion=portion_id) is not None:
            # injected device-memory fault: served as a miss, so the
            # scan degrades mid-stream to the staged host path
            chaos.note_fallback("resident.lookup")
            with self._lock:
                self.misses += 1
            return None
        with self._lock:
            self._tick += 1
            out = {}
            for n in names:
                e = self._cols.get((portion_id, n))
                if e is None:
                    self.misses += 1
                    return None
                out[n] = e
            info = self._info.get(portion_id)
            if info is not None:
                info["heat"] += 1
                info["tick"] = self._tick
            self.hits += 1
            return out

    def record_miss(self, portion_id: int) -> bool:
        """Host-path access bookkeeping. True when the portion just
        crossed the heat threshold and is worth promoting now."""
        with self._lock:
            self._tick += 1
            if len(self._miss_heat) > 4096 and \
                    portion_id not in self._miss_heat:
                # bound the heat map for ad-hoc workloads that scan a
                # long tail of portions exactly once
                self._miss_heat.clear()
            n = self._miss_heat.get(portion_id, 0) + 1
            self._miss_heat[portion_id] = n
            return n == PROMOTE_HEAT and portion_id not in self._inflight

    def note_pruned(self, portion_id: int) -> None:
        """A scan's zone maps pruned this portion entirely: its resident
        bytes served nothing. Eviction sends such portions first."""
        with self._lock:
            info = self._info.get(portion_id)
            if info is not None:
                info["zskips"] += 1

    # ---- promotion ----

    def promote(self, portion_id: int, rows: int, cols: dict,
                valid: "dict | None") -> bool:
        """Synchronous promote: decode-free device put of host arrays."""
        return self._promote(portion_id, rows, lambda: (cols, valid),
                             "memory")

    def _promote(self, portion_id: int, rows: int, loader, source: str,
                 committed_at: "float | None" = None) -> bool:
        """``loader()``'s host arrays onto the device, under one
        ``resident.promote`` span with a leaf a stage: ``load`` (the
        loader: nothing for a fresh write, the blob read and decode on
        the heat path), ``put`` (the pads and the device put of every
        column, OUTSIDE the lock), ``admit`` (insertion, accounting and
        the eviction to the budget inside it). ``committed_at`` is the
        ``time.perf_counter`` reading at which the portion's commit was
        logged: admitted, the promotion samples ``resident_lag_seconds``,
        a write's time to be scannable from HBM."""
        if not self.enabled():
            return False
        with tracing.span("resident.promote", store=self.name,
                          portion=portion_id, rows=rows,
                          source=source) as sp:
            with _promote_stage("load"):
                cols, valid = loader()
            added = self._put_and_admit(sp, portion_id, rows, cols, valid)
            if added:
                g = _counters()
                g.counter("promotions").inc()
                g.counter("promote_bytes").inc(added)
                if committed_at is not None:
                    lag = time.perf_counter() - committed_at
                    g.histogram("resident_lag_seconds").observe(lag)
                    sp.set(lag_s=round(lag, 6))
        return added > 0

    def _put_and_admit(self, sp, portion_id: int, rows: int, cols: dict,
                       valid: "dict | None") -> int:
        """The bytes admitted (0: spilled, or a concurrent promotion
        landed every column first), under the promotion's span ``sp``."""
        budget = self.budget()
        dev = self._slice_device
        entries = {}
        total = 0
        valid = valid or {}
        with _promote_stage("put") as put_sp, memsan.seam("resident"):
            for n, a in cols.items():
                v = valid.get(n)
                if v is None:
                    v = np.ones(len(a), dtype=np.bool_)
                held = resident_rows(len(a))
                a = _padded(np.asarray(a), held)
                v = _padded(np.asarray(v, dtype=np.bool_), held)
                if dev is not None:
                    e = _Entry(jax.device_put(a, dev),
                               jax.device_put(v, dev))
                else:
                    e = _Entry(jnp.asarray(a), jnp.asarray(v))
                entries[n] = e
                total += e.nbytes
            put_sp.set(bytes=total, device=self._slice_slot)
        if total > budget:
            # a single portion larger than the whole valve can never be
            # resident: spill — the host path keeps serving it
            with self._lock:
                self.spills += 1
            _counters().counter("spills").inc()
            sp.set(spilled=1)
            return 0
        with _promote_stage("admit") as admit_sp, self._lock:
            info = self._info.get(portion_id)
            if info is None:
                info = {"rows": rows, "nbytes": 0, "cols": set(),
                        "heat": self._miss_heat.pop(portion_id, 0),
                        "tick": self._tick, "zskips": 0}
                self._info[portion_id] = info
            added = 0
            for n, e in entries.items():
                if (portion_id, n) in self._cols:
                    continue  # concurrent promotion landed first
                self._cols[(portion_id, n)] = e
                info["cols"].add(n)
                info["nbytes"] += e.nbytes
                added += e.nbytes
            self._nbytes += added
            if added:
                self.promotions += 1
                if memsan.armed():
                    info.setdefault("tickets", []).append(
                        memsan.charge(added, "resident",
                                      owner=portion_id))
            held = self._nbytes
            evicted = self._evict_to_budget_locked(budget,
                                                   keep=portion_id)
            admit_sp.set(evicted_bytes=held - self._nbytes)
        sp.set(bytes=added, evicted=evicted)
        return added

    def _evict_to_budget_locked(self, budget: int, keep=None) -> int:
        """Drop whole portions until the ledger fits the budget. Victim
        order: zone-pruned-away portions first (their zone maps keep
        proving scans don't need them — zero resident value), then
        coldest by (access heat, LRU tick). Caller holds the lock."""
        evicted = 0
        while self._nbytes > budget and self._info:
            candidates = [p for p in self._info if p != keep]
            if not candidates:
                break
            victim = min(
                candidates,
                key=lambda p: (-self._info[p]["zskips"],
                               self._info[p]["heat"],
                               self._info[p]["tick"]))
            self._drop_locked(victim)
            self.evictions += 1
            evicted += 1
        return evicted

    def _drop_locked(self, portion_id: int) -> None:
        info = self._info.pop(portion_id, None)
        if info is None:
            return
        for n in info["cols"]:
            e = self._cols.pop((portion_id, n), None)
            if e is not None:
                self._nbytes -= e.nbytes
        for t in info.get("tickets", ()):
            memsan.release(t, evicted=True)

    def promote_async(self, portion_id: int, rows: int, loader,
                      committed_at: "float | None" = None) -> bool:
        """Queue a promotion on the shared conveyor. ``loader()`` runs
        on a worker and returns (cols, valid) host dicts — either the
        in-memory arrays of a fresh portion write (eager path, which
        hands ``committed_at``: see ``_promote``) or a blob-store read
        (heat path). Single-flight per portion id; bounded in-flight so
        queued promotions never crowd out scan prefetch admission. A
        promotion not queued is counted by its reason
        (``promote_declined{reason=disabled|in_flight|inflight_full}``)
        and named on the caller's active span: declined here, a portion
        reaches HBM only once PROMOTE_HEAT scans have missed it."""
        if not self.enabled():
            return self._declined("disabled")
        with self._lock:
            if portion_id in self._inflight:
                return self._declined("in_flight")
            if len(self._inflight) >= MAX_INFLIGHT:
                return self._declined("inflight_full")
            self._inflight.add(portion_id)
            fh = leaksan.track("resident.flight",
                               f"{self.name}:{portion_id}")
            # compact finished handles while here (drain bookkeeping)
            self._pending = [h for h in self._pending
                             if not h.done.is_set()]
        source = "blob" if committed_at is None else "memory"

        def task():
            try:
                self._promote(portion_id, rows, loader, source,
                              committed_at)
            except Exception:
                # best-effort: a GC'd blob or a shrunk budget mid-task
                # is not a scan error — the host path still serves
                with self._lock:
                    self.errors += 1
                _counters().counter("errors").inc()
            finally:
                with self._lock:
                    self._inflight.discard(portion_id)
                leaksan.close(fh)

        from ydb_tpu.runtime.conveyor import shared_conveyor

        try:
            # promotions are background work owned by the STORE, not the
            # statement that triggered them: submit outside the
            # statement's deadline so a cancelled query can never strand
            # the _inflight entry (its discard lives in task()'s finally)
            with statement_deadline.activate(None):
                h = shared_conveyor().submit("resident_promote", task,
                                             priority=20)
        except RuntimeError:  # conveyor shut down (tests teardown)
            with self._lock:
                self._inflight.discard(portion_id)
            leaksan.close(fh)
            return False
        with self._lock:
            self._pending.append(h)
        return True

    @staticmethod
    def _declined(reason: str) -> bool:
        _counters().group(reason=reason).counter("promote_declined").inc()
        tracing.annotate(promote_declined=reason)
        return False

    def drain(self, timeout: float = 30.0) -> None:
        """Wait for every queued promotion (tests/bench determinism).
        Bounded: a wedged conveyor stops the wait at ``timeout``, it
        never wedges the caller."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                pending = [h for h in self._pending
                           if not h.done.is_set()]
                self._pending = pending
            left = deadline - time.monotonic()
            if not pending or left <= 0:
                return
            pending[0].done.wait(left)

    # ---- invalidation ----

    def invalidate(self, portion_ids) -> None:
        """Drop by immutable portion id (GC'd portions that no snapshot
        can ever name again — compaction/TTL tombstones keep serving
        old-snapshot readers until then)."""
        with self._lock:
            for pid in portion_ids:
                if pid in self._info:
                    self._drop_locked(pid)
                    self.invalidations += 1
                self._miss_heat.pop(pid, None)

    def prune(self, live) -> None:
        """Keep only portions in ``live`` (the shard's portion map)."""
        with self._lock:
            for pid in [p for p in self._info if p not in live]:
                self._drop_locked(pid)
                self.invalidations += 1
            for pid in [p for p in self._miss_heat if p not in live]:
                del self._miss_heat[pid]

    def clear(self) -> None:
        with self._lock:
            self._cols.clear()
            self._info.clear()
            self._miss_heat.clear()
            self._nbytes = 0

    # ---- observability ----

    @property
    def nbytes(self) -> int:
        return self._nbytes

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "portions": len(self._info),
                "rows": sum(i["rows"] for i in self._info.values()),
                "columns": len(self._cols),
                "bytes": self._nbytes,
                "budget": self.budget(),
                "hits": self.hits,
                "misses": self.misses,
                "promotions": self.promotions,
                "evictions": self.evictions,
                "spills": self.spills,
                "invalidations": self.invalidations,
                "errors": self.errors,
                "inflight": len(self._inflight),
                "device_slot": self._slice_slot,
            }


def assign_device_slices(stores, n_devices: int, devices=None,
                         per_device_budget: "int | None" = None) -> None:
    """Bind a table's per-shard ResidentStores onto mesh devices.

    Stores group round-robin (``stores[d::n_devices]``) — the SAME
    grouping mesh_exec.device_partitions uses for scan sources, so a
    shard's resident columns live exactly where its rows are scanned.
    Shards sharing one device split the device budget evenly; the base
    is ``per_device_budget`` when given, else each store's own un-sliced
    budget standing in for the device's HBM share."""
    for d in range(n_devices):
        group = stores[d::n_devices]
        if not group:
            continue
        dev = devices[d] if devices is not None else None
        for st in group:
            base = (per_device_budget if per_device_budget is not None
                    else st._base_budget())
            st.set_device_slice(d, dev, max(base // len(group), 0))


def clear_device_slices(stores) -> None:
    for st in stores:
        st.clear_device_slice()


# ---------------- scan-side block assembly ----------------


def portion_loader(shard, meta):
    """Blob-store loader for heat promotions: full current schema, with
    schema-evolution NULLs projected exactly as the host path would."""
    names = tuple(shard.schema.names)

    def load():
        from ydb_tpu.engine.portion import project_chunk, read_portion_blob

        c, v = read_portion_blob(shard.store, meta.blob_id)
        return project_chunk(shard.schema, shard.column_added, meta,
                             names, c, v)

    return load


@functools.partial(jax.jit, static_argnames=("cap",))
def _assemble(datas, valids, bounds, *, cap):
    """One ``cap``-row block of every column read, cut from K resident
    pieces: ``datas[k][c]`` / ``valids[k][c]`` are piece k's arrays of
    column c, ``bounds[k]`` its (first row taken, offset in the block,
    rows taken) as int32. Row ``i`` of the block is row
    ``i - offset + first`` of the piece whose ``[offset, offset + rows)``
    holds ``i``; rows no piece holds are data 0 / validity False.
    Returns (datas, valids, length). The bounds are runtime values, so
    the program is one for every cut of the same array lengths, and it
    moves no row by index: each piece is read as ONE window of ``cap``
    rows (a dynamic slice of the piece padded by ``cap`` either side, so
    the window never clamps) and the pieces are selected on an iota."""
    with jax.named_scope("ydb.device_blocks"):
        rows = lax.iota(jnp.int32, cap)
        out_d = out_v = None
        for k, (ds, vs) in enumerate(zip(datas, valids)):
            first, offset, n = bounds[k, 0], bounds[k, 1], bounds[k, 2]
            inside = (rows >= offset) & (rows < offset + n)
            start = first - offset + cap

            def window(a):
                return lax.dynamic_slice(jnp.pad(a, (cap, cap)),
                                         (start,), (cap,))

            d = [jnp.where(inside, window(a), jnp.zeros((), a.dtype))
                 for a in ds]
            v = [inside & window(a) for a in vs]
            if out_d is None:
                out_d, out_v = d, v
            else:
                out_d = [jnp.where(inside, x, y)
                         for x, y in zip(d, out_d)]
                out_v = [x | y for x, y in zip(v, out_v)]
        return (tuple(out_d), tuple(out_v),
                jnp.sum(bounds[:, 2], dtype=jnp.int32))


@functools.lru_cache(maxsize=256)
def _whole_length(device, rows: int):
    """The int32 ``length`` of a block one portion fills, on the
    portion's device (None: the default one, uncommitted as the arrays
    of an unbound store are): made once, not once a block."""
    with memsan.seam("resident"):
        if device is None:
            return jnp.asarray(rows, dtype=jnp.int32)
        return jax.device_put(np.int32(rows), device)


def _device_blocks(run, names, sch, cap, timer):
    """Cut a RUN of consecutive resident portions, ``(entries, rows,
    source)`` each, into capacity-``cap`` TableBlocks.

    Coalescing across portion boundaries matters as much as skipping
    the host stage: emitting one padded block per small portion would
    hand the executor mostly-padding blocks and multiply compute by
    the portion count. A portion that fills a block exactly hands over
    its resident arrays, no device work and no enqueue; every other
    block is one ``_assemble`` call over all the columns. Either way
    the block counts on the source its first row came from
    (``resident_blocks_whole`` / ``resident_blocks_assembled``)."""
    stage = (timer.stage if timer is not None else None)
    starts = []
    total = 0
    for _, rows, _ in run:
        starts.append(total)
        total += rows
    for off in range(0, total, cap):
        take = min(cap, total - off)
        # resident pieces overlapping [off, off+take): (entries, first
        # row taken, offset in the block, rows taken)
        parts = []
        for (entries, rows, source), s in zip(run, starts):
            lo = max(off, s) - s
            hi = min(off + take, s + rows) - s
            if lo < hi:
                if not parts:
                    owner = source
                parts.append((entries, lo, s + lo - off, hi - lo))
        ctx = stage("stage") if stage is not None \
            else contextlib.nullcontext()
        with ctx:
            first = parts[0][0][names[0]].data
            whole = (len(parts) == 1 and parts[0][1:] == (0, 0, cap)
                     and first.shape[0] == cap)
            if whole:
                ents = parts[0][0]
                datas = [ents[n].data for n in names]
                valids = [ents[n].validity for n in names]
                length = _whole_length(
                    next(iter(first.devices())) if first.committed
                    else None, cap)
                owner.resident_blocks_whole += 1
            else:
                # the pieces carry their own bounds, so their order is
                # free: by array length, one program a SET of lengths
                parts.sort(key=lambda p: p[0][names[0]].data.shape[0])
                datas, valids, length = _assemble(
                    tuple(tuple(p[0][n].data for n in names)
                          for p in parts),
                    tuple(tuple(p[0][n].validity for n in names)
                          for p in parts),
                    np.array([p[1:4] for p in parts], dtype=np.int32),
                    cap=cap)
                owner.resident_blocks_assembled += 1
            blk = TableBlock(
                {n: Column(d, v)
                 for n, d, v in zip(names, datas, valids)},
                length, sch)
        yield blk


def mixed_blocks(items, names, sch, cap, timer=None):
    """('dev', entries, rows, source) / ('host', cols, valid) item
    stream -> fixed-capacity TableBlocks.

    Host runs pack through ``reader.rechunk`` (the same low-copy
    re-cutting as the pure host path); a device item flushes the
    pending host run as a partial block first, so row ORDER is exactly
    the host path's. Block BOUNDARIES may differ from the pure host
    stream (partial flushes at tier transitions) — programs are
    boundary-agnostic (fixed capacity + masked padding), only row order
    matters. Always emits at least one (possibly empty) block:
    consumers size their compiled programs off the stream."""
    from ydb_tpu.engine.reader import rechunk

    def build(cols, valid):
        ctx = (timer.stage("stage") if timer is not None
               else contextlib.nullcontext())
        with ctx:
            blk = TableBlock.from_numpy(cols, sch, valid, capacity=cap)
        timeline.add_bytes("staged_bytes", sum(
            c.data.nbytes + c.validity.nbytes
            for c in blk.columns.values()))
        return blk

    it = iter(items)
    emitted = 0
    pending = None
    while True:
        item = pending if pending is not None else next(it, None)
        pending = None
        if item is None:
            break
        if item[0] == "dev":
            # absorb the whole consecutive resident run so blocks
            # coalesce across portion boundaries
            dev_run = [item[1:]]
            for nxt in it:
                if nxt[0] != "dev":
                    pending = nxt
                    break
                dev_run.append(nxt[1:])
            for blk in _device_blocks(dev_run, names, sch, cap, timer):
                emitted += 1
                yield blk
            continue

        def host_run(first=item):
            nonlocal pending
            yield first[1], first[2]
            for nxt in it:
                if nxt[0] != "host":
                    pending = nxt
                    return
                yield nxt[1], nxt[2]

        for cols, valid in rechunk(host_run(), names, cap):
            emitted += 1
            yield build(cols, valid)
    if emitted == 0:
        yield build(
            {m: np.empty(0, dtype=sch.field(m).type.physical)
             for m in names},
            {m: np.empty(0, dtype=bool) for m in names})
