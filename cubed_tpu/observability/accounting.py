"""Byte accounting for storage IO, with per-task attribution.

The storage layer calls ``record_bytes_read`` / ``record_bytes_written`` on
every chunk transfer. Attribution rules:

- Inside an active **task scope** (``task_scope()`` — entered by
  ``execute_with_stats`` around every task body), bytes accumulate on the
  scope object and ride back to the client in the task's stats dict. This is
  what makes the numbers survive process boundaries: multiprocess and
  distributed workers measure their own IO and the client aggregates it from
  ``TaskEndEvent``s.
- Outside any task scope (plan-level metadata ops, a read-back after the
  executor has returned), bytes go straight to the process registry. The
  JAX executor opens a scope around each segment, eager op and flush, so
  its whole-array preloads and flushes ride its ``TaskEndEvent``s too.

The two paths are exclusive by construction, so summing task-event bytes
into the registry (``callback._ComputeAggregator``) never double-counts.

A bounded per-store breakdown (``store_totals()``) is kept in-process either
way, for debugging which store dominates IO; overflow beyond
``MAX_TRACKED_STORES`` aggregates under ``"<other>"``.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

from . import clock
from .metrics import get_registry

#: cap on per-store breakdown entries (plans create one temp store per
#: intermediate array; an unbounded dict would grow with every plan)
MAX_TRACKED_STORES = 128

#: cap on spans buffered per task: a pathological task (thousands of chunk
#: reads) must not ship a megabyte of span payload with its result — excess
#: spans drop with a count, surfaced as the ``spans_dropped`` counter
MAX_TASK_SPANS = 128

#: operator override for span recording ("1" forces it on everywhere; also
#: how a client's arming reaches spawned pool workers)
SPANS_ENV_VAR = "CUBED_TPU_TASK_SPANS"

#: process-global arming state (None = defer to env/default-off). Span
#: recording is opt-in per compute: ``Plan.execute`` arms it only while a
#: ``TraceCollector``/``FlightRecorder`` is attached, so an unobserved
#: compute records no span dicts and ships no span payload in its result
#: frames — the same arming pattern fault injection and the integrity mode
#: use (env export for pool spawns, task-message mirroring for fleets)
_spans_armed: Optional[bool] = None

_tls = threading.local()

_store_lock = threading.Lock()
_store_totals: Dict[str, list] = {}

#: a human-readable label for THIS process ("local-0" for a fleet worker,
#: None for the client / pool workers) — stamped on task stats so merged
#: traces can give each worker its own lane and look up its clock offset
_process_label: Optional[str] = None


def set_process_label(label: Optional[str]) -> None:
    global _process_label
    _process_label = label


def get_process_label() -> Optional[str]:
    return _process_label


def spans_enabled() -> bool:
    """Whether ``scope_span`` records anything (env > armed > off)."""
    env = os.environ.get(SPANS_ENV_VAR)
    if env:
        return env == "1"
    if _spans_armed is not None:
        return _spans_armed
    return False


def spans_wire() -> bool:
    """The client's resolved arming, attached to every fleet task message
    so pre-started workers record spans exactly when the client collects
    them (and stop when it doesn't)."""
    return spans_enabled()


def arm_spans_from_wire(armed) -> None:
    """Fleet-worker side: mirror the arming a task message carried."""
    global _spans_armed
    _spans_armed = None if armed is None else bool(armed)


class spans_scoped:
    """Arm span recording for a ``with`` block (``Plan.execute`` uses this
    while a trace collector is attached); ``None`` is a no-op. With
    ``export_env`` the env var is set so pool workers spawned inside the
    block inherit the arming — unless the operator already set it, in
    which case their override passes through untouched (the same env-wins
    rule the integrity/memory-guard scopes follow)."""

    def __init__(self, armed: Optional[bool] = None, export_env: bool = False):
        self._armed = armed
        self._export_env = export_env

    def __enter__(self):
        if self._armed is None:
            return None
        global _spans_armed
        self._prev = _spans_armed
        self._prev_env = os.environ.get(SPANS_ENV_VAR)
        _spans_armed = bool(self._armed)
        if self._export_env and self._armed and self._prev_env is None:
            os.environ[SPANS_ENV_VAR] = "1"
        return self._armed

    def __exit__(self, *exc) -> None:
        if self._armed is None:
            return
        global _spans_armed
        _spans_armed = self._prev
        if self._export_env:
            if self._prev_env is None:
                os.environ.pop(SPANS_ENV_VAR, None)
            else:
                os.environ[SPANS_ENV_VAR] = self._prev_env


class TaskScope:
    """Accumulates IO (and named event counts) attributed to one task body."""

    __slots__ = (
        "bytes_read",
        "bytes_written",
        "chunks_read",
        "chunks_written",
        "virtual_bytes_read",
        "counters",
        "spans",
        "spans_dropped",
        "max_spans",
        "thread",
        "_open",
        "_next_id",
    )

    def __init__(self, max_spans: int = MAX_TASK_SPANS):
        #: bound of the span buffer (``MAX_TASK_SPANS`` for a task whose
        #: stats are shipped; the device executor's in-process scopes, each
        #: a whole array's worth of chunk IO, take more room)
        self.max_spans = max_spans
        #: name of the thread the scope was made on, which is the one that
        #: records into it (``fold`` marks a helper thread's spans with it)
        self.thread = threading.current_thread().name
        self.bytes_read = 0
        self.bytes_written = 0
        self.chunks_read = 0
        self.chunks_written = 0
        self.virtual_bytes_read = 0
        #: named counts (integrity verifications/corruption/quarantines)
        #: recorded inside this scope — riding the stats dict across process
        #: boundaries exactly like the byte counters
        self.counters: Dict[str, int] = {}
        #: bounded buffer of spans recorded inside this task body (storage
        #: reads/writes, kernel apply, integrity verify, retry sleeps) —
        #: measured on THIS process's clock, shipped back in the stats dict
        #: like the byte counters so remote work becomes visible in the
        #: merged trace (observability/collect.py)
        self.spans: list = []
        self.spans_dropped = 0
        #: ids of the ``scope_span``s open right now, outermost first: a
        #: span's parent is the one open when it was entered
        self._open: list = []
        self._next_id = 0

    def add_span(
        self, name: str, start: float, end: float, cat: str = "span",
        span_id: Optional[int] = None, parent: Optional[int] = None, **attrs
    ) -> None:
        """Buffer one finished span. ``span_id`` is unique within this
        scope and ``parent`` is the id of the span that enclosed it, so a
        reader gets self time as duration minus children."""
        if len(self.spans) >= self.max_spans:
            self.spans_dropped += 1
            return
        if span_id is None:
            span_id = self._next_id
            self._next_id += 1
        span = {"name": name, "ts": start, "dur": max(0.0, end - start),
                "cat": cat, "id": span_id}
        if parent is not None:
            span["parent"] = parent
        if attrs:
            span["attrs"] = attrs
        self.spans.append(span)

    def fold(self, other: "TaskScope") -> None:
        """Add what ``other`` recorded to this scope: the scope of a helper
        thread that did a part of this task's work (the device executor's
        flush writes chunks on one), which found no scope of its own
        through ``current_scope()``, that being per thread. Byte and chunk
        counts and named counters add up. ``other``'s spans get fresh ids
        above this scope's, so that parents stay parents, and those that
        had no parent become children of the span open here now. Spans of
        another thread than this scope's say so (attr ``thread``): they ran
        beside their new parent and not inside its time, and a reader of
        self time leaves them out of it (``_ComputeAggregator._fold_spans``).
        Call it when ``other`` is closed, from the thread that owns this
        scope."""
        self.bytes_read += other.bytes_read
        self.bytes_written += other.bytes_written
        self.chunks_read += other.chunks_read
        self.chunks_written += other.chunks_written
        self.virtual_bytes_read += other.virtual_bytes_read
        for name, n in other.counters.items():
            self.counters[name] = self.counters.get(name, 0) + n
        base, parent = self._next_id, self._open[-1] if self._open else None
        self._next_id += other._next_id
        room = max(0, self.max_spans - len(self.spans))
        for span in other.spans[:room]:
            span = dict(span, id=span["id"] + base)
            if other.thread != self.thread:
                span["attrs"] = {"thread": other.thread, **span.get("attrs", {})}
            if "parent" in span:
                span["parent"] += base
            elif parent is not None:
                span["parent"] = parent
            self.spans.append(span)
        self.spans_dropped += other.spans_dropped + max(0, len(other.spans) - room)

    def stats(self) -> dict:
        return {
            "bytes_read": self.bytes_read,
            "bytes_written": self.bytes_written,
            "chunks_read": self.chunks_read,
            "chunks_written": self.chunks_written,
            "virtual_bytes_read": self.virtual_bytes_read,
            "counters": dict(self.counters),
            "spans": list(self.spans),
            "spans_dropped": self.spans_dropped,
        }


class task_scope:
    """Context manager establishing a per-task accounting scope.

    Scopes nest (a task body running a nested compute): each byte is
    attributed to the INNERMOST scope only, never folded outward — the
    inner task's event already carries those bytes into client-side
    aggregation, so folding them into the outer task's stats as well would
    count them twice.
    """

    def __init__(self, max_spans: int = MAX_TASK_SPANS):
        self._max_spans = max_spans

    def __enter__(self) -> TaskScope:
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._scope = TaskScope(self._max_spans)
        stack.append(self._scope)
        return self._scope

    def __exit__(self, *exc) -> None:
        _tls.stack.pop()


def current_scope() -> Optional[TaskScope]:
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class scope_span:
    """Time a block of code as a span on the current task scope.

    A no-op (no timestamps taken, nothing allocated beyond this object)
    when no task scope is active — metadata/plan-level IO stays unspanned —
    or when span recording is disarmed (``spans_enabled``): a compute with
    no trace collector attached pays nothing for span bookkeeping.
    The ``attrs`` dict is mutable until exit, so callers can attach
    results measured inside the block (byte counts, retry counts). A block
    that raises still records its span, closed at the raise instant with
    ``error=True`` — failures are when the trace matters most.

    A recording span knows the span of the same scope that encloses it
    (``parent``), and is also entered as a ``jax.profiler.TraceAnnotation``
    named ``cubed:<name>``: a profiler session running at the time (the
    ``JaxProfilerCallback``, a benchmark's traced run) then holds the
    host's phases on the same clock as the device's operations. Without a
    session the annotation is a sub-microsecond no-op.
    """

    __slots__ = (
        "name", "cat", "attrs", "_scope", "_start", "_id", "_parent",
        "_annotation",
    )

    def __init__(self, name: str, cat: str = "span", **attrs):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self._scope: Optional[TaskScope] = None

    @property
    def recording(self) -> bool:
        """Whether this (entered) span records: work that exists only to
        be measured (a device sync ahead of a fetch) is guarded by it."""
        return self._scope is not None

    def __enter__(self) -> "scope_span":
        scope = self._scope = current_scope() if spans_enabled() else None
        if scope is not None:
            self._id = scope._next_id
            scope._next_id += 1
            self._parent = scope._open[-1] if scope._open else None
            scope._open.append(self._id)
            self._annotation = _trace_annotation(ANNOTATION_PREFIX + self.name)
            self._annotation.__enter__()
            self._start = clock.now()
        return self

    def __exit__(self, exc_type, *exc) -> None:
        scope = self._scope
        if scope is None:
            return
        end = clock.now()
        self._annotation.__exit__(None, None, None)
        scope._open.pop()
        if exc_type is not None:
            self.attrs["error"] = True
            self.attrs["error_type"] = exc_type.__name__
        scope.add_span(
            self.name, self._start, end, cat=self.cat,
            span_id=self._id, parent=self._parent, **self.attrs
        )


#: what every annotation ``scope_span`` writes into a profiler trace starts
#: with, so that a reader of the trace finds the program's spans again
ANNOTATION_PREFIX = "cubed:"

_TraceAnnotation = None


def _trace_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``; jax is imported on first use,
    which only a process with spans armed ever reaches."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)



def _track_store(store: str, read: int, written: int) -> None:
    key = str(store)
    with _store_lock:
        entry = _store_totals.get(key)
        if entry is None:
            if len(_store_totals) >= MAX_TRACKED_STORES:
                key = "<other>"
                entry = _store_totals.get(key)
            if entry is None:
                entry = _store_totals[key] = [0, 0]
        entry[0] += read
        entry[1] += written


def record_bytes_read(store: str, n: int) -> None:
    scope = current_scope()
    if scope is not None:
        scope.bytes_read += n
        scope.chunks_read += 1
    else:
        reg = get_registry()
        reg.counter("bytes_read").inc(n)
        reg.counter("chunks_read").inc()
    _track_store(store, n, 0)


def record_bytes_written(store: str, n: int) -> None:
    scope = current_scope()
    if scope is not None:
        scope.bytes_written += n
        scope.chunks_written += 1
    else:
        reg = get_registry()
        reg.counter("bytes_written").inc(n)
        reg.counter("chunks_written").inc()
    _track_store(store, 0, n)


def record_scoped_counter(name: str, n: int = 1) -> None:
    """Count a named event with per-task attribution.

    Inside a task scope the count rides the task's stats dict back to the
    client (surviving process/fleet boundaries) and the compute aggregator
    folds it into the client registry; outside any scope it goes straight
    to the process registry. Used by the integrity layer so worker-side
    verification/corruption/quarantine counts reach compute stats."""
    scope = current_scope()
    if scope is not None:
        scope.counters[name] = scope.counters.get(name, 0) + n
    else:
        get_registry().counter(name).inc(n)


def record_virtual_read(n: int) -> None:
    """A read served by a virtual (never-materialized) array: logical bytes,
    no IO — tracked separately from ``bytes_read`` so that stays an IO
    number, but still scope-attributed so worker-side virtual reads reach
    the client like real IO does."""
    scope = current_scope()
    if scope is not None:
        scope.virtual_bytes_read += n
    else:
        get_registry().counter("virtual_bytes_read").inc(n)


def store_totals() -> Dict[str, dict]:
    """Per-store {bytes_read, bytes_written} seen by THIS process."""
    with _store_lock:
        return {
            k: {"bytes_read": r, "bytes_written": w}
            for k, (r, w) in _store_totals.items()
        }


def reset_store_totals() -> None:
    with _store_lock:
        _store_totals.clear()
