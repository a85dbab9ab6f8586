"""Sync sanitizer: per-statement host-boundary counters (YDB_TPU_SYNCSAN=1).

The runtime half of the dispatch-purity pillar. ``hotpath.py`` proves
statically that no host work is *written* on the warm path; this
sanitizer counts what actually *crosses* the host boundary per
statement — H2D transfers, D2H transfers, blocking syncs and XLA
compilations — and enforces a warm-statement budget: after warmup,
**zero compilations** and a bounded sync count, or the statement
raises ``SyncBudgetError``.

Seams patched while armed (restored on disarm):

  ``jax.block_until_ready``   blocking sync
  ``jax.device_get``          one D2H transfer + one blocking sync
                              (the repo batches whole blocks through a
                              single call — one RTT, one count)
  ``jnp.asarray``             H2D transfer when staging host data
  ``np.asarray``              D2H sync when materializing a jax.Array

Compilations come from the process's one ``jax.monitoring`` listener
(``obs.tracing``), which this module subscribes to: the
``/jax/core/compile/backend_compile_duration`` event fires exactly
once per XLA backend compile, built or fetched from the persistent
cache (never on a hit of the in-process cache), so it is the ground
truth the compile caches are judged against.
``.item()`` lives on the C++ ArrayImpl and cannot be patched — the
static analyzer (H001) owns that seam.

Counters attribute to the active statement: the thread that called
``begin_statement`` resolves via a thread-local; conveyor workers
resolve via the obs span they inherited (``tracing.wrap_current``
propagates spans across the pool) and the trace-id registry; anything
else lands in the orphan totals. ``end_statement`` annotates the obs
span (``syncsan_*`` attributes, surfaced by EXPLAIN ANALYZE) and
enforces the budget.

Gates mirror ``leaksan.py``: ``YDB_TPU_SYNCSAN=1`` env,
``set_force()`` pin, ``activate()`` context manager for tests and
bench. All functions are None-safe no-ops while disabled.
"""

from __future__ import annotations

import os
import threading

from ydb_tpu.obs import tracing

#: tri-state pin: None -> follow the env var; True/False -> forced
_FORCE: "bool | None" = None

_meta_lock = threading.Lock()
_tls = threading.local()


def enabled() -> bool:
    if _FORCE is not None:
        return _FORCE
    return os.environ.get("YDB_TPU_SYNCSAN", "") not in ("", "0")


_ON = enabled()


class SyncBudgetError(AssertionError):
    """A warm statement exceeded its host-boundary budget."""


class Budget:
    __slots__ = ("compiles", "syncs", "warmup")

    def __init__(self, compiles: int = 0, syncs: "int | None" = None,
                 warmup: int = 1):
        self.compiles = compiles
        self.syncs = syncs
        self.warmup = warmup


_budget: "Budget | None" = None
_warm_seen: dict = {}  # label -> statements ended (warmup tracking)


class Statement:
    """Counters for one statement (one ``begin``/``end`` pair)."""

    __slots__ = ("label", "trace_id", "span", "h2d", "d2h", "syncs",
                 "compiles", "_lock")

    def __init__(self, label: str, trace_id: "str | None"):
        self.label = label
        self.trace_id = trace_id
        self.span = tracing.current_span()
        self.h2d = 0
        self.d2h = 0
        self.syncs = 0
        self.compiles = 0
        self._lock = threading.Lock()

    def note(self, *, h2d: int = 0, d2h: int = 0, syncs: int = 0,
             compiles: int = 0) -> None:
        with self._lock:
            self.h2d += h2d
            self.d2h += d2h
            self.syncs += syncs
            self.compiles += compiles

    def snapshot(self) -> dict:
        with self._lock:
            return {"h2d": self.h2d, "d2h": self.d2h,
                    "syncs": self.syncs, "compiles": self.compiles}


_by_trace: dict = {}       # trace_id -> Statement
_orphans = Statement("<orphan>", None)


def _resolve() -> "Statement | None":
    st = getattr(_tls, "stat", None)
    if st is not None:
        return st
    span = tracing.current_span()
    if span is not None:
        st = _by_trace.get(span.trace_id)
        if st is not None:
            return st
    return _orphans


def _note(**counts) -> None:
    if not _ON:
        return
    st = _resolve()
    if st is not None:
        st.note(**counts)


# every backend compile of the process, on the compiling thread (the
# body gates on _ON: subscribed once, for good)
tracing.on_compile(lambda _fetched, _seconds: _note(compiles=1))


# ---------------- seam patches ----------------

_patched = False
_orig: dict = {}


def _is_device_value(x) -> bool:
    try:
        import jax

        return isinstance(x, jax.Array)
    except Exception:
        return False


def _install() -> None:
    global _patched
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
    except Exception:
        return

    def block_until_ready(x):
        _note(syncs=1)
        return _orig["block_until_ready"](x)

    def device_get(x):
        _note(d2h=1, syncs=1)
        return _orig["device_get"](x)

    def jnp_asarray(a, *args, **kwargs):
        if isinstance(a, np.ndarray):
            _note(h2d=1)
        return _orig["jnp_asarray"](a, *args, **kwargs)

    def np_asarray(a, *args, **kwargs):
        if _is_device_value(a):
            _note(d2h=1, syncs=1)
        return _orig["np_asarray"](a, *args, **kwargs)

    with _meta_lock:
        if _patched:
            return
        _orig["block_until_ready"] = jax.block_until_ready
        _orig["device_get"] = jax.device_get
        _orig["jnp_asarray"] = jnp.asarray
        _orig["np_asarray"] = np.asarray
        jax.block_until_ready = block_until_ready
        jax.device_get = device_get
        jnp.asarray = jnp_asarray
        np.asarray = np_asarray
        _patched = True


def _uninstall() -> None:
    global _patched
    import jax
    import jax.numpy as jnp
    import numpy as np

    with _meta_lock:
        if not _patched:
            return
        jax.block_until_ready = _orig["block_until_ready"]
        jax.device_get = _orig["device_get"]
        jnp.asarray = _orig["jnp_asarray"]
        np.asarray = _orig["np_asarray"]
        _patched = False


# ---------------- gates (leaksan idiom) ----------------


def refresh() -> None:
    """Re-read the gate; arm or disarm the seams to match."""
    global _ON
    with _meta_lock:
        _ON = enabled()
        on = _ON
    # the seam patchers take the lock themselves (their idempotence
    # checks run under it); racing refreshes converge on the last gate
    if on:
        _install()
    else:
        _uninstall()


def set_force(value: "bool | None") -> None:
    """Pin the sanitizer on/off regardless of the env (tests, bench);
    ``None`` returns control to ``YDB_TPU_SYNCSAN``."""
    global _FORCE
    with _meta_lock:
        _FORCE = value
    refresh()


# honor an env set before import
if _ON:
    refresh()


# ---------------- statement lifecycle ----------------


def begin_statement(label: str,
                    trace_id: "str | None" = None,
                    span=None) -> "Statement | None":
    """Open a counting window for one statement. Returns None (and
    counts nothing) while the sanitizer is off. ``span`` pins the obs
    span the counters annotate at close — callers opening the window
    BEFORE activating their root span (the session statement path)
    must pass it, else ``current_span()`` is still the caller's
    parent (or None) and the ``syncsan_*`` attrs land elsewhere."""
    if not _ON:
        return None
    st = Statement(label, trace_id)
    if span is not None:
        st.span = span
    _tls.stat = st
    if trace_id is not None:
        with _meta_lock:
            _by_trace[trace_id] = st
    return st


def _close(st: "Statement | None") -> None:
    if getattr(_tls, "stat", None) is st:
        _tls.stat = None
    if st is not None and st.trace_id is not None:
        with _meta_lock:
            _by_trace.pop(st.trace_id, None)


def discard(st: "Statement | None") -> None:
    """Drop a window without budget enforcement (error paths)."""
    _close(st)


def end_statement(st: "Statement | None", *,
                  enforce: bool = True) -> "dict | None":
    """Close the window: annotate the obs span with ``syncsan_*``
    attributes and enforce the warm budget. Returns the counter
    snapshot (None while disabled)."""
    if st is None:
        return None
    _close(st)
    snap = st.snapshot()
    if st.span is not None:
        st.span.set(syncsan_h2d=snap["h2d"], syncsan_d2h=snap["d2h"],
                    syncsan_syncs=snap["syncs"],
                    syncsan_compiles=snap["compiles"])
    if enforce and _budget is not None:
        with _meta_lock:
            seen = _warm_seen.get(st.label, 0)
            _warm_seen[st.label] = seen + 1
        if seen >= _budget.warmup:
            if snap["compiles"] > _budget.compiles:
                raise SyncBudgetError(
                    f"statement {st.label!r} compiled"
                    f" {snap['compiles']}x on the warm path"
                    f" (budget {_budget.compiles}); a compile cache"
                    " is missing or its key is unstable")
            if _budget.syncs is not None and \
                    snap["syncs"] > _budget.syncs:
                raise SyncBudgetError(
                    f"statement {st.label!r} blocked on the device"
                    f" {snap['syncs']}x (budget {_budget.syncs});"
                    " host work leaked into the dispatch loop")
    return snap


def set_budget(compiles: int = 0, syncs: "int | None" = None,
               warmup: int = 1) -> None:
    """Arm the warm-statement budget: statements past ``warmup`` (per
    label) must stay within ``compiles``/``syncs``."""
    global _budget
    with _meta_lock:
        _budget = Budget(compiles=compiles, syncs=syncs, warmup=warmup)
        _warm_seen.clear()


def clear_budget() -> None:
    global _budget
    with _meta_lock:
        _budget = None
        _warm_seen.clear()


def totals() -> dict:
    """Aggregate counters across live windows + orphans (bench)."""
    agg = _orphans.snapshot()
    with _meta_lock:
        stats = list(_by_trace.values())
    for st in stats:
        for k, v in st.snapshot().items():
            agg[k] += v
    return agg


def reset() -> None:
    """Drop all windows, budgets and orphan counts (tests)."""
    global _orphans
    with _meta_lock:
        _by_trace.clear()
        _warm_seen.clear()
        _orphans = Statement("<orphan>", None)
    _tls.stat = None


class activate:
    """``with syncsan.activate():`` — force the sanitizer on for a
    scope regardless of the env var, starting from clean counters."""

    def __init__(self, budget: "Budget | None" = None):
        self._budget = budget

    def __enter__(self):
        reset()
        set_force(True)
        if self._budget is not None:
            set_budget(compiles=self._budget.compiles,
                       syncs=self._budget.syncs,
                       warmup=self._budget.warmup)
        return self

    def __exit__(self, *exc):
        clear_budget()
        set_force(None)
        reset()
        return False
