"""Shared test helpers: executor lists and a task-counting callback.

Reference parity: cubed/tests/utils.py:14-103.
"""

from __future__ import annotations

import contextlib
import platform

from cubed_tpu.runtime.types import Callback


class SlowAdd:
    """Picklable deterministic task body with a wall-clock footprint: slow
    enough for a drain to catch it in flight, and fleet-capacity changes
    show up in elapsed time."""

    def __init__(self, delay_s: float):
        self.delay_s = delay_s

    def __call__(self, x):
        import time

        time.sleep(self.delay_s)
        return x + 1.0


_ALL_EXECUTORS = None


def all_executors():
    # cached: fixture definitions in several test modules call this at
    # collection; caching keeps ONE distributed fleet for the whole session
    global _ALL_EXECUTORS
    if _ALL_EXECUTORS is not None:
        return _ALL_EXECUTORS
    from cubed_tpu.runtime.executors.python import PythonDagExecutor

    executors = [PythonDagExecutor()]
    try:
        from cubed_tpu.runtime.executors.python_async import AsyncPythonDagExecutor

        if platform.system() != "Windows":
            executors.append(AsyncPythonDagExecutor())
    except ImportError:
        pass
    try:
        from cubed_tpu.runtime.executors.jax import JaxExecutor

        executors.append(JaxExecutor())
    except ImportError:
        pass
    try:
        from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor

        # one instance shared by every parametrized test: the worker fleet
        # spawns lazily on first compute and is reused (workers exit on
        # coordinator EOF at interpreter shutdown)
        executors.append(DistributedDagExecutor(n_local_workers=2, worker_threads=2))
    except ImportError:
        pass
    _ALL_EXECUTORS = executors
    return executors


def main_executors():
    return all_executors()


class TaskCounter(Callback):
    """Counts completed tasks and validates event timestamp ordering.

    Callback exceptions are swallowed by ``callbacks_on`` (a broken observer
    must never fail a compute), so ordering violations are recorded and
    re-raised when ``value`` is read instead of asserted inline.
    """

    def __init__(self):
        self._value = 0
        self.events = []
        self.violations = []

    def on_compute_start(self, event):
        self._value = 0

    def on_task_end(self, event):
        self.events.append(event)
        if event.task_create_tstamp is not None:
            ok = (
                event.task_result_tstamp
                >= event.function_end_tstamp
                >= event.function_start_tstamp
                >= event.task_create_tstamp
                > 0
            )
            if not ok:
                self.violations.append(event)
        self._value += event.num_tasks

    @property
    def value(self):
        assert not self.violations, (
            f"task events with out-of-order timestamps: {self.violations}"
        )
        return self._value


def execute_pipeline(primitive_op, executor=None):
    """Run a single primitive op outside a plan (unit-test harness)."""
    from cubed_tpu.storage.zarr import LazyZarrArray

    if isinstance(primitive_op.target_array, LazyZarrArray):
        primitive_op.target_array.create(mode="a")
    for m in primitive_op.pipeline.mappable:
        primitive_op.pipeline.function(m, config=primitive_op.pipeline.config)


@contextlib.contextmanager
def leased_staging(executor):
    """A ``JaxExecutor`` with a staging pair in its hands, as inside a
    compute, for tests that drive ``_device_put`` or ``_flush`` themselves:
    the lease it has, or one for the length of the block."""
    if executor._staging is not None:
        yield executor._staging
    else:
        with executor._lease() as staging:
            yield staging
