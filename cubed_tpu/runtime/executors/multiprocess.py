"""Multiprocess executor: tasks run in separate OS processes.

This is the process-boundary analogue of the reference's serverless
executors (cubed/runtime/executors/lithops.py, modal.py): the serialized
payload crossing the boundary is exactly the reference's
``(function, input, config=BlockwiseSpec)`` triple (cloudpickle, since chunk
kernels and block functions are closures — same reason lithops/modal use
cloudpickle), and all inter-task data movement goes through the shared Zarr
store — workers share no memory. Retries, speculative straggler backups and
batched submission reuse the same completion-ordered core as the threaded
executor (cubed/runtime/executors/asyncio.py:11-102 in the reference).

Semantics exercised here that in-process executors can't:

- payload serializability (what a cloud executor would ship to a worker)
- idempotent whole-chunk Zarr writes surviving duplicate/backup tasks
- crash-level fault isolation: a worker process dying breaks the whole
  ProcessPoolExecutor (stdlib semantics), so the executor rebuilds the pool
  and re-runs the op — tasks are idempotent whole-chunk writes, so
  re-running completed tasks is safe (the same property that makes the
  reference's speculative backups safe)
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import logging
import os
from typing import Optional

from ...observability.metrics import get_registry
from ..dataflow import (
    DataflowScheduler,
    record_scheduler_mode,
    effective_scheduler,
)
from ..memory import AdmissionController
from ..pipeline import (
    RecomputeResolver,
    ResumeState,
    pending_mappable,
    visit_node_generations,
    visit_nodes,
)
from ..resilience import (
    DEFAULT_RETRIES,
    RetryPolicy,
    budget_exhausted_error,
    resolve_policy,
)
from ..types import (
    DagExecutor,
    OperationEndEvent,
    OperationStartEvent,
    callbacks_on,
)
from ..utils import end_generation, merge_generation
from .python_async import compute_retry_budget, map_unordered

logger = logging.getLogger(__name__)


@contextlib.contextmanager
def _worker_safe_env():
    """Pin spawned workers to the CPU jax platform while the pool spawns.

    Workers do chunk IO + CPU compute only: device execution lives in the
    parent's JaxExecutor, and an accelerator belongs to one process at a
    time, so a worker that initialized the default backend would fail or
    hang behind the parent. Restored on exit so the parent process's own
    device access is unaffected.

    NOTE: the pin is made in the PARENT's own ``os.environ`` (spawned
    workers copy it) and ``execute_dag`` holds it for the whole compute,
    because the pool spawns workers lazily. A parent whose jax backend
    first initializes inside that window lands on the CPU without a word;
    a process that also drives a device must touch jax before it computes
    with this executor. Not repaired here: passing the variable to the
    workers alone needs a pool initializer that runs before jax imports.
    """
    prev_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev_platform is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev_platform


#: worker exit codes that read as the kernel OOM killer's work: -9 is a
#: SIGKILL-terminated multiprocessing child (negative signal convention),
#: 137 is the 128+SIGKILL form a worker that re-execs (or an injected
#: ``os._exit(137)`` chaos crash) reports
_OOM_EXITCODES = (-9, 137)


def _dead_worker_exitcodes(pool) -> list:
    """Nonzero exit codes of a broken pool's worker processes.

    Today a pool crash is reported cause-less ("worker process died"); the
    exit code distinguishes an OOM-kill (SIGKILL, -9) from a segfault or a
    plain exit, which decides whether the rebuild should also step
    concurrency down. Reaches into ``pool._processes`` (stdlib-private but
    stable since 3.7); best-effort — an empty list just means no
    diagnosis, never an error. Polls briefly: BrokenProcessPool can escape
    to the caller before the dead child is reaped (exitcode still None),
    and a definite code is worth a short wait."""
    import time

    try:
        procs = list((pool._processes or {}).values())
    except Exception:
        return []
    for _ in range(10):
        codes = []
        unreaped = False
        for p in procs:
            try:
                code = p.exitcode
            except Exception:
                continue
            if code is None:
                unreaped = True
            elif code not in (0, -15):
                # -15 (SIGTERM) is the pool's own terminate_broken cleanup
                # tearing down SURVIVORS — reporting it would misattribute
                # the crash to a worker that died of the cleanup
                codes.append(code)
        if codes or not unreaped:
            return codes
        time.sleep(0.05)
    return codes


def exitcode_hint(codes) -> str:
    """Human-readable rendering of dead-worker exit codes, with the
    "likely OOM-killed" hint for SIGKILL shapes."""
    if not codes:
        return "unknown exit code"
    parts = []
    for c in codes:
        if c in _OOM_EXITCODES:
            parts.append(f"{c} — likely OOM-killed (SIGKILL)")
        else:
            parts.append(str(c))
    return "exitcode " + ", ".join(parts)


class _ProcessTaskRunner:
    """Picklable callable handed to the process pool: carries the op's
    serialized (function, config) and deserializes per call in the worker."""

    def __init__(self, function, config):
        import cloudpickle

        self.blob = cloudpickle.dumps((function, config))

    def __call__(self, m):
        import cloudpickle

        function, config = cloudpickle.loads(self.blob)
        if config is not None:
            return function(m, config=config)
        return function(m)


class MultiprocessDagExecutor(DagExecutor):
    """ProcessPool executor: true process isolation with retries/backups.

    Parameters mirror the threaded executor; ``max_workers`` defaults to the
    CPU count. Use ``compute_arrays_in_parallel=True`` to interleave tasks of
    ops in the same topological generation (reference
    cubed/runtime/executors/python_async.py:93-114).
    """

    def __init__(
        self,
        max_workers: Optional[int] = None,
        retries: int = DEFAULT_RETRIES,
        use_backups: bool = False,
        batch_size: Optional[int] = None,
        compute_arrays_in_parallel: bool = False,
        retry_policy: Optional[RetryPolicy] = None,
        **kwargs,
    ):
        self.max_workers = max_workers or os.cpu_count() or 1
        self.retries = retries
        self.use_backups = use_backups
        self.batch_size = batch_size
        self.compute_arrays_in_parallel = compute_arrays_in_parallel
        self.retry_policy = retry_policy
        self.kwargs = kwargs

    @property
    def name(self) -> str:
        return "processes"

    def execute_dag(
        self,
        dag,
        callbacks=None,
        array_names=None,
        resume=None,
        spec=None,
        retries: Optional[int] = None,
        use_backups: Optional[bool] = None,
        batch_size: Optional[int] = None,
        compute_arrays_in_parallel: Optional[bool] = None,
        retry_policy: Optional[RetryPolicy] = None,
        journal=None,
        cancellation=None,
        **kwargs,
    ) -> None:
        retries = self.retries if retries is None else retries
        use_backups = self.use_backups if use_backups is None else use_backups
        batch_size = self.batch_size if batch_size is None else batch_size
        if compute_arrays_in_parallel is None:
            compute_arrays_in_parallel = self.compute_arrays_in_parallel
        policy = resolve_policy(retry_policy or self.retry_policy, retries)
        budget = compute_retry_budget(policy, dag)
        # shared per compute: an OOM-killed worker steps task admission
        # down for every later op, not just the one that crashed
        admission = AdmissionController()
        state = (
            ResumeState(quarantine=True, journal=journal) if resume else None
        )
        # integrity failures detected worker-side arrive pickled; the repair
        # (re-running the producing task) runs client-side against the
        # shared store, which is valid for any executor
        resolver = RecomputeResolver(dag)

        # spawn (not fork): workers must not inherit live device handles or
        # jax state — same as a cloud worker booting from a clean image
        import multiprocessing

        ctx = multiprocessing.get_context("spawn")
        stack = contextlib.ExitStack()
        stack.enter_context(_worker_safe_env())
        pool = concurrent.futures.ProcessPoolExecutor(
            max_workers=self.max_workers, mp_context=ctx
        )
        # a defaulted dataflow yields to an explicit batch_size (the rule
        # lives in dataflow.effective_scheduler); explicit requests win
        # and warn below
        scheduler = effective_scheduler(spec, batch_size)
        record_scheduler_mode(scheduler, executor=self.name)
        try:
            if scheduler == "dataflow":
                # one dependency-gated map over the whole DAG: workers
                # receive the same per-op (function, config) blobs as the
                # interleaved path; a pool-crash re-run resumes from the
                # scheduler's done-set instead of re-running the world
                if batch_size:
                    logger.warning(
                        "batch_size=%s is ignored under scheduler="
                        "\"dataflow\" (the whole DAG is one dependency-"
                        "gated map)", batch_size,
                    )
                sched = DataflowScheduler(
                    dag, resume=resume, state=state, callbacks=callbacks
                )
                sched.start()
                try:
                    runners = {
                        name: _ProcessTaskRunner(p.function, p.config)
                        for name, p in sched.pipelines.items()
                    }
                    pool = self._map_surviving_pool_crash(
                        pool,
                        ctx,
                        _GenerationTask(runners),
                        sched.items,
                        policy=policy,
                        budget=budget,
                        use_backups=use_backups,
                        batch_size=None,
                        callbacks=callbacks,
                        array_names=sched.array_names,
                        executor_name=self.name,
                        recompute_resolver=resolver,
                        admission=admission,
                        dependencies=sched.dependencies,
                        on_input_submit=sched.on_submit,
                        on_input_done=sched.on_done,
                        completed_inputs=sched.completed,
                        cancellation=cancellation,
                    )
                finally:
                    sched.finish()
            elif compute_arrays_in_parallel:
                for generation in visit_node_generations(
                    dag, resume=resume, state=state
                ):
                    merged, pipelines = merge_generation(
                        generation, callbacks, resume=resume, resume_state=state
                    )
                    runners = {
                        name: _ProcessTaskRunner(p.function, p.config)
                        for name, p in pipelines.items()
                    }

                    # interleaved tasks still go through one unordered map
                    pool = self._map_surviving_pool_crash(
                        pool,
                        ctx,
                        _GenerationTask(runners),
                        merged,
                        policy=policy,
                        budget=budget,
                        use_backups=use_backups,
                        batch_size=batch_size,
                        callbacks=callbacks,
                        array_names=[m[0] for m in merged],
                        executor_name=self.name,
                        recompute_resolver=resolver,
                        admission=admission,
                        cancellation=cancellation,
                    )
                    end_generation(generation, callbacks)
            else:
                for name, node in visit_nodes(dag, resume=resume, state=state):
                    primitive_op = node["primitive_op"]
                    pipeline = primitive_op.pipeline
                    callbacks_on(
                        callbacks, "on_operation_start",
                        OperationStartEvent(name, primitive_op.num_tasks),
                    )
                    mappable, _ = pending_mappable(name, node, resume, state)
                    pool = self._map_surviving_pool_crash(
                        pool,
                        ctx,
                        _ProcessTaskRunner(pipeline.function, pipeline.config),
                        list(mappable),
                        policy=policy,
                        budget=budget,
                        use_backups=use_backups,
                        batch_size=batch_size,
                        callbacks=callbacks,
                        array_name=name,
                        executor_name=self.name,
                        recompute_resolver=resolver,
                        admission=admission,
                        cancellation=cancellation,
                    )
                    callbacks_on(
                        callbacks, "on_operation_end",
                        OperationEndEvent(name, primitive_op.num_tasks),
                    )
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
            stack.close()

    def _map_surviving_pool_crash(
        self, pool, ctx, fn, inputs, *, policy=None, budget=None,
        retries=None, **map_kwargs,
    ):
        """map_unordered, rebuilding the pool when a worker death breaks it.

        A dead worker (OOM-kill, segfault) permanently breaks a stdlib
        ProcessPoolExecutor; every op task is an idempotent whole-chunk
        write, so the whole op is safely re-run on a fresh pool. Returns the
        (possibly new) pool for subsequent ops. Pool rebuilds follow the
        retry policy: they are infrastructure failures, so each rebuild
        waits out a backoff delay (a crashing-on-load input would otherwise
        respawn the pool in a tight loop) and draws on the compute's retry
        budget so systemic crash loops abort promptly.

        The dead workers' exit codes are captured before the broken pool is
        discarded: a SIGKILL shape (-9/137) reads as the kernel OOM killer
        (``worker_oom_kills``), so the rebuilt pool comes back with HALF
        the workers — re-running the same op at full process parallelism
        would feed the same pressure that killed it — and the compute's
        admission controller steps down with it. Other codes rebuild at
        full size with the code in the log line instead of today's
        cause-less generic rebuild.

        Note: a re-run fires ``on_task_end`` again for tasks that completed
        before the crash, so progress/history counters can exceed num_tasks
        across pool-crash retries — the same at-least-once event semantics a
        cloud executor's speculative backups have.
        """
        import time

        from concurrent.futures.process import BrokenProcessPool

        policy = resolve_policy(policy, retries)
        if budget is None:
            budget = policy.new_budget(len(inputs))
        retries = policy.retries
        admission = map_kwargs.get("admission")
        workers = getattr(pool, "_max_workers", self.max_workers)
        for attempt in range(retries + 1):
            try:
                map_unordered(
                    pool, fn, inputs, retry_policy=policy,
                    retry_budget=budget, **map_kwargs,
                )
                return pool
            except BrokenProcessPool as exc:
                codes = _dead_worker_exitcodes(pool)
                pool.shutdown(wait=False, cancel_futures=True)
                if attempt == retries:
                    raise  # caller's finally shuts down this (dead) pool
                if not budget.consume():
                    raise budget_exhausted_error(exc, budget) from exc
                oom = any(c in _OOM_EXITCODES for c in codes)
                if oom:
                    get_registry().counter("worker_oom_kills").inc()
                    workers = max(1, workers // 2)
                    if admission is not None:
                        admission.step_down(workers * 2)
                delay = policy.backoff_delay(attempt + 1)
                get_registry().counter("pool_rebuilds").inc()
                get_registry().histogram("retry_backoff_s").observe(delay)
                from ...observability.collect import record_decision

                record_decision(
                    "pool_rebuild", exitcodes=codes, workers=workers,
                    oom=oom, delay_s=round(delay, 4),
                )
                logger.warning(
                    "worker process died (%s); rebuilding pool with %d "
                    "worker(s) in %.3fs, re-running op (attempt %d/%d)",
                    exitcode_hint(codes), workers, delay,
                    attempt + 2, retries + 1,
                )
                if delay > 0:
                    time.sleep(delay)
                pool = concurrent.futures.ProcessPoolExecutor(
                    max_workers=workers, mp_context=ctx
                )
        return pool


class _GenerationTask:
    """Picklable dispatcher for interleaved-generation items (name, m)."""

    def __init__(self, runners):
        self.runners = runners

    def __call__(self, item):
        name, m = item
        return self.runners[name](m)
