"""Executor protocol and observability event types.

The full callback lifecycle, fired consistently by every executor:

    on_compute_start(ComputeStartEvent)
      on_operation_start(OperationStartEvent)      # per op
        on_task_start(TaskStartEvent)              # per task (attempt)
        on_task_end(TaskEndEvent)                  # per completed task
      on_operation_end(OperationEndEvent)          # per op
    on_compute_end(ComputeEndEvent)                # carries executor_stats

Reference parity: cubed/runtime/types.py:9-88, extended with task-start and
operation-end events plus task attribution fields (chunk key, attempt,
executor, storage bytes) for the observability subsystem.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Optional, Sequence

logger = logging.getLogger(__name__)


class DagExecutor:
    """Protocol for plan executors: map each op's task function over its tasks."""

    @property
    def name(self) -> str:
        raise NotImplementedError

    def execute_dag(self, dag, callbacks=None, array_names=None, resume=None, spec=None, **kwargs) -> None:
        raise NotImplementedError


Executor = DagExecutor


@dataclass
class TaskStartEvent:
    """A task (or a retry/backup attempt of one) has been submitted."""

    array_name: str
    num_tasks: int = 1
    #: the task's chunk key (stringified mappable item), when known
    chunk_key: Optional[str] = None
    #: 0 for the first attempt, incremented per retry
    attempt: int = 0
    #: True when this is a speculative straggler backup of a running task
    backup: bool = False


@dataclass
class TaskEndEvent:
    """Metrics for a completed task (or ``num_tasks`` tasks of one op that
    ran as one). An event of zero tasks completes none: it carries the IO
    and spans of work done in an op's name outside its tasks (the device
    executor's flush of the op's array)."""

    array_name: str
    num_tasks: int = 1
    task_create_tstamp: Optional[float] = None
    function_start_tstamp: Optional[float] = None
    function_end_tstamp: Optional[float] = None
    task_result_tstamp: Optional[float] = None
    peak_measured_mem_start: Optional[int] = None
    peak_measured_mem_end: Optional[int] = None
    #: the task's chunk key (stringified mappable item), when known
    chunk_key: Optional[str] = None
    #: which attempt produced this result (0 = first try)
    attempt: int = 0
    #: name of the executor that ran the task
    executor: Optional[str] = None
    #: storage bytes moved by THIS task, measured where it ran (worker-side
    #: for remote executors) — see observability/accounting.py
    bytes_read: Optional[int] = None
    bytes_written: Optional[int] = None
    chunks_read: Optional[int] = None
    chunks_written: Optional[int] = None
    #: logical bytes served by virtual (never-materialized) arrays — not IO
    virtual_bytes_read: Optional[int] = None
    #: named event counts recorded inside this task's scope (integrity
    #: verifications, detected corruption, quarantines — see
    #: observability/accounting.py ``record_scoped_counter``), measured
    #: where the task ran and folded into the client registry like bytes
    counters: Optional[dict] = None
    #: peak RSS growth the memory guard attributed to this task (bytes),
    #: measured where it ran (runtime/memory.py); None when the guard was
    #: off or couldn't measure — per-op maxima feed the projected-vs-
    #: measured summary in ``ComputeEndEvent.executor_stats``
    guard_mem_peak: Optional[int] = None
    #: spans recorded inside this task's body (storage IO, kernel apply,
    #: integrity verify, retry sleeps), measured on the executing process's
    #: clock — see ``observability/accounting.py`` (bounded buffer) and
    #: ``observability/collect.py`` (clock-aligned merge)
    spans: Optional[list] = None
    #: spans beyond the per-task buffer bound, dropped where the task ran
    spans_dropped: Optional[int] = None
    #: pid of the process that executed the task (lane + clock identity)
    pid: Optional[int] = None
    #: fleet worker name when the task ran on a named worker, else None
    worker: Optional[str] = None
    #: the task's control-plane dispatch ledger: client-clock stamps and
    #: coordinator-side costs for its lifecycle transitions (deps-ready ->
    #: dequeued -> serialized -> sent -> result-received), merged from the
    #: dispatch loop's per-submit timing and, on the distributed executor,
    #: the coordinator's per-frame measurements — keys like
    #: ``ready_tstamp``/``submitted_tstamp``/``submit_cost_s``/
    #: ``serialize_s``/``send_s``/``lock_wait_s``/``sent_tstamp``/
    #: ``result_recv_tstamp``/``unpickle_s``; None when no ledger rode the
    #: stats channel (see docs/observability.md "Control-plane
    #: observability")
    dispatch: Optional[dict] = None


class Callback:
    """Observer protocol for compute lifecycle events.

    Callback exceptions are swallowed and logged by ``callbacks_on`` — a
    broken observer can never fail a compute.
    """

    def on_compute_start(self, event) -> None:
        """Called when the computation is about to start; event has .dag, .resume."""

    def on_compute_end(self, event) -> None:
        """Called when the computation has finished; event has .dag, .executor_stats."""

    def on_operation_start(self, event) -> None:
        """Called when an op begins; event has .name and .num_tasks."""

    def on_operation_end(self, event) -> None:
        """Called when all of an op's tasks have finished."""

    def on_task_start(self, event: TaskStartEvent) -> None:
        """Called when a task attempt is submitted for execution."""

    def on_task_end(self, event: TaskEndEvent) -> None:
        """Called when one or more tasks of an op finish."""


@dataclass
class ComputeStartEvent:
    dag: object
    resume: Optional[bool] = None
    #: unique id for this compute (``Plan.execute`` mints one); correlates
    #: traces, structured logs and flight-recorder bundles
    compute_id: Optional[str] = None


@dataclass
class ComputeEndEvent:
    dag: object
    #: merged stats for this compute: the executor's own execution-path
    #: counters (e.g. segments traced, batched dispatches) plus the
    #: observability metrics snapshot (task counters, bytes_read/written,
    #: retries/timeouts/backups, per_op summary) — None if nothing reported
    executor_stats: Optional[dict] = None
    #: the compute's id (matches the start event's)
    compute_id: Optional[str] = None
    #: the exception that failed the compute, or None on success — how the
    #: flight recorder knows to assemble a bundle (the event still fires on
    #: failure; the exception propagates to the caller regardless)
    error: Optional[BaseException] = None


@dataclass
class OperationStartEvent:
    name: str
    num_tasks: int = 0


@dataclass
class OperationEndEvent:
    name: str
    num_tasks: int = 0


def callbacks_on(callbacks: Optional[Sequence[Callback]], method: str, event) -> None:
    """Dispatch ``event`` to every callback's ``method``, swallowing (and
    logging) observer exceptions so a broken callback can't fail a compute."""
    if not callbacks:
        return
    for cb in callbacks:
        fn = getattr(cb, method, None)
        if fn is None:
            continue
        try:
            fn(event)
        except Exception:
            logger.exception(
                "callback %r raised in %s; continuing", cb, method
            )
