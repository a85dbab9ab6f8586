"""Distributed tracing: spans with trace-id propagation.

Mirror of the reference's Wilson tracing (NWilson::TSpan
wilson/wilson_span.h:50, TTraceId wilson/wilson_trace.h, uploader ->
OTLP wilson/wilson_uploader.cpp; SURVEY.md §5.1): spans open under a
trace id, nest by parent span id, and finished spans collect in a
Tracer which exports OTLP-shaped JSON. The session opens a root span
per query; inner phases (parse/plan/compile/execute/scan/fetch) nest
under it; actor envelopes can carry the id across nodes.

Span threading: the ACTIVE span rides thread-local context
(``activate`` / ``current_span`` / ``span``), so deep layers — the
scan executor, DQ compute actors, the conveyor prefetch pool — attach
children without plumbing a span argument through every signature.
``runtime.conveyor`` captures the submitter's active span and
re-activates it on the worker, so one query's trace id follows its
work across threads; the Tracer is therefore thread-safe (spans
finish from prefetch producers while the session thread records its
own) with a per-trace-id index replacing the old linear scan.

One clock, and the profiler's: spans run on ``time.perf_counter`` (the
clock ``StageTimer``, the timeline ring and the benchmark use), and
every annotated span also opens a ``jax.profiler.TraceAnnotation``
named ``ydb.<span name>``. Outside a profiler session that is one inert
TraceMe; inside one the span lands on its thread's host line of the same
``.xplane.pb`` as the device's "XLA Ops", on that trace's clock. A span
opened in one call and finished in another (``dq.task``) is not
lexical: it is opened ``annotated=False``, stays out of the profiler
trace and out of the self-time arithmetic of ``profile.build_profile``.

Compiles: one process-wide ``jax.monitoring`` listener splits every
``backend_compile_duration`` event into *built* and *fetched* (from the
persistent cache), counts both for the process (``compile_counts``)
and charges them to the thread's active span (``compile_built`` /
``compile_fetched`` / ``compile_seconds`` attrs). ``on_compile``
subscribes further readers (the sync sanitizer).

Gating: profiling is ON by default; ``YDB_TPU_PROFILE=0`` keeps the
per-query root span but skips activation and annotation, so no child
spans (and none of their attribute computation) and no TraceAnnotation
happen anywhere below the session. ``PROFILE_FORCE`` is the in-process
test override (same contract as stats.STATS_FORCE).
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time

import jax
from jax.profiler import TraceAnnotation

from ydb_tpu.analysis import sanitizer
from ydb_tpu.obs import timeline

_ids = itertools.count(1)

#: test override: True/False forces profiling regardless of the
#: environment (same contract as plan_fuse.FUSE_FORCE).
PROFILE_FORCE: bool | None = None


def profiling_enabled() -> bool:
    """Whether the session threads its span through the query path
    (activation + child spans + profile assembly). Default on;
    ``YDB_TPU_PROFILE=0`` restores the root-span-only behavior."""
    if PROFILE_FORCE is not None:
        return PROFILE_FORCE
    return os.environ.get("YDB_TPU_PROFILE", "1") not in ("0", "", "off")


#: prefix of every host event the program writes into a profiler trace
#: (never ``bench.``: the benchmark's reduction takes those for its own)
ANNOTATION_PREFIX = "ydb."


class Span:
    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 parent_id: int | None = None, annotated: bool = True):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = next(_ids)
        self.parent_id = parent_id
        self.attrs: dict = {}
        #: the thread the span opened on: self time is reckoned among
        #: the spans of one thread
        self.thread = threading.get_ident()
        self.annotated = annotated
        self._annotation = None
        if annotated:
            self._annotation = TraceAnnotation(ANNOTATION_PREFIX + name)
            self._annotation.__enter__()
        self.start = time.perf_counter()
        self.end: float | None = None

    #: real spans record; the shared null span (disabled path) does not
    recording = True

    def child(self, name: str, annotated: bool | None = None) -> "Span":
        """A child span; ``annotated`` defaults to the parent's, so a
        root opened unannotated (profiling off) keeps its whole tree
        out of the profiler trace."""
        return Span(self.tracer, name, self.trace_id, self.span_id,
                    self.annotated if annotated is None else annotated)

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    @property
    def seconds(self) -> float:
        """Wall duration (to now while unfinished)."""
        return (self.end if self.end is not None
                else time.perf_counter()) - self.start

    def finish(self) -> None:
        if self.end is None:
            self.end = time.perf_counter()
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
                self._annotation = None
            self.tracer._record(self)
            if timeline.timeline_enabled():
                timeline.RING.record(self.name, "span", self.start,
                                     self.end, self.trace_id)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None:
            self.attrs["error"] = repr(exc)
        self.finish()


class _NullSpan:
    """No-op span: returned by ``span()`` when no trace is active, so
    instrumentation sites need no ``if`` around their annotations."""

    recording = False
    annotated = False
    trace_id = 0
    span_id = 0
    parent_id = None
    attrs: dict = {}
    seconds = 0.0

    def child(self, name: str, annotated=None) -> "_NullSpan":
        return self

    def set(self, **attrs) -> "_NullSpan":
        return self

    def finish(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


NULL_SPAN = _NullSpan()

# thread-local active span; workers inherit it via ``wrap_current``
_tls = threading.local()


def current_span() -> Span | None:
    """The thread's active span (None outside any activated trace)."""
    return getattr(_tls, "span", None)


@contextlib.contextmanager
def activate(sp: Span):
    """Make ``sp`` the thread's active span for the block."""
    prev = current_span()
    _tls.span = sp
    try:
        yield sp
    finally:
        _tls.span = prev


@contextlib.contextmanager
def span(name: str, **attrs):
    """Open (and activate) a child of the active span; a shared no-op
    span when no trace is active — the disabled path costs one
    thread-local read."""
    parent = current_span()
    if parent is None:
        yield NULL_SPAN
        return
    s = parent.child(name)
    if attrs:
        s.set(**attrs)
    prev = parent
    _tls.span = s
    try:
        yield s
    except BaseException as e:
        s.attrs["error"] = repr(e)
        raise
    finally:
        _tls.span = prev
        s.finish()


@contextlib.contextmanager
def leaf(name: str, **attrs):
    """``span`` whose block opens no span beneath it: for a loop that
    would otherwise open one per block per column (the span budget is
    per block, per dispatch, per message batch)."""
    with span(name, **attrs) as s:
        _tls.span = None
        try:
            yield s
        finally:
            _tls.span = s if s.recording else None


@contextlib.contextmanager
def entry(tracer: "Tracer | None", name: str):
    """The span of a piece of work that may start inside a trace or
    outside any (``ShardedTable.insert``: under a session's statement,
    or called on the table; a compaction on a background thread): a
    child of the active span where there is one, else a root on
    ``tracer``, and the shared no-op span where there is neither.
    Activated only while profiling is on or a trace is already active,
    so ``YDB_TPU_PROFILE=0`` leaves the one span and nothing beneath
    it, unannotated."""
    parent = current_span()
    if parent is not None:
        sp = parent.child(name)
    elif tracer is not None:
        sp = tracer.trace(name, annotated=profiling_enabled())
    else:
        yield NULL_SPAN
        return
    with sp, (activate(sp) if sp.annotated else contextlib.nullcontext()):
        yield sp


def annotate(**attrs) -> None:
    """Attach attributes to the active span, if any."""
    sp = current_span()
    if sp is not None:
        sp.set(**attrs)


def wrap_current(fn):
    """Bind the submitter's active span to ``fn`` so a worker thread
    runs it under the same trace (the conveyor submit hook)."""
    sp = current_span()
    if sp is None:
        return fn

    def bound(*args, **kwargs):
        with activate(sp):
            return fn(*args, **kwargs)

    return bound


class Tracer:
    """Thread-safe span collector with a per-trace-id index.

    DQ stages and conveyor prefetch producers finish spans from worker
    threads while the session thread records its own — ``finished``
    appends and ``spans_for`` lookups run under a sanitizer-tracked
    lock, and the index makes per-query lookups O(spans in trace)
    instead of a scan over the whole ring."""

    def __init__(self, max_spans: int = 10000):
        self.max_spans = max_spans
        self.finished: list[Span] = []
        self._by_trace: dict[int, list[Span]] = {}
        self._lock = sanitizer.make_lock(f"tracer.{id(self):x}.lock")
        self._next_tid = 1

    def trace(self, name: str, trace_id: int | None = None,
              annotated: bool = True) -> Span:
        """Open a root span (new trace id unless one is propagated).
        The local allocator always skips past propagated ids so two
        unrelated traces never share an id."""
        with self._lock:
            if trace_id is not None:
                tid = trace_id
                self._next_tid = max(self._next_tid, trace_id + 1)
            else:
                tid = self._next_tid
                self._next_tid += 1
        return Span(self, name, tid, None, annotated)

    def _record(self, span: Span) -> None:
        with self._lock:
            self.finished.append(span)
            self._by_trace.setdefault(span.trace_id, []).append(span)
            excess = len(self.finished) - self.max_spans
            if excess > 0:
                evicted = self.finished[:excess]
                del self.finished[:excess]
                for s in evicted:
                    spans = self._by_trace.get(s.trace_id)
                    if spans is not None:
                        spans.remove(s)
                        if not spans:
                            del self._by_trace[s.trace_id]

    def spans_for(self, trace_id: int) -> list[Span]:
        with self._lock:
            return list(self._by_trace.get(trace_id, ()))


# ---------------- which step compiled ----------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: fired inside the compile event's interval, on its thread, when the
#: executable came out of the persistent cache instead of the compiler
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_compile_lock = threading.Lock()
_compile_counts = {"built": 0, "fetched": 0, "seconds": 0.0,
                   "built_seconds": 0.0, "fetched_seconds": 0.0}
_compile_subscribers: list = []


def compile_counts() -> dict:
    """Programs this process built with XLA / fetched from the
    persistent cache, and the seconds both took (``seconds``, and each
    kind's own: ``built_seconds``, ``fetched_seconds``)."""
    with _compile_lock:
        return dict(_compile_counts)


def on_compile(fn) -> None:
    """Subscribe ``fn(fetched: bool, seconds: float)`` to every backend
    compile of the process (called on the compiling thread)."""
    with _compile_lock:
        _compile_subscribers.append(fn)


def _on_cache_hit(event, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _tls.fetched = True


def _on_compile(event, seconds, **_kw) -> None:
    if event != _COMPILE_EVENT:
        return
    fetched = getattr(_tls, "fetched", False)
    _tls.fetched = False
    kind = "fetched" if fetched else "built"
    with _compile_lock:
        _compile_counts[kind] += 1
        _compile_counts["seconds"] += seconds
        _compile_counts[kind + "_seconds"] += seconds
        subscribers = list(_compile_subscribers)
    sp = current_span()
    if sp is not None:
        a = sp.attrs
        a["compile_" + kind] = a.get("compile_" + kind, 0) + 1
        a["compile_seconds"] = round(
            a.get("compile_seconds", 0.0) + seconds, 6)
    for fn in subscribers:
        fn(fetched, seconds)


# jax.monitoring offers no per-listener removal worth relying on: one
# pair for the process, registered with the module
jax.monitoring.register_event_listener(_on_cache_hit)
jax.monitoring.register_event_duration_secs_listener(_on_compile)
