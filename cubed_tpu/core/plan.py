"""The lazy whole-operation DAG.

Nodes alternate between *op* nodes (carrying a PrimitiveOperation) and *array*
nodes (carrying a Zarr target). Data never flows through the graph — each op
reads chunks of input arrays from shared storage (or, under the TPU executor,
from HBM-resident buffers) and writes chunks of one output array.

Reference parity: cubed/core/plan.py (behavioral; clean-room).
"""

from __future__ import annotations

import inspect
import logging
import os
import shutil
import tempfile
import time
import uuid
from functools import lru_cache
from typing import Any, Callable, Optional, Sequence

import networkx as nx

from ..primitive.types import CubedPipeline, PrimitiveOperation
from ..runtime.pipeline import (
    ResumeState,
    already_computed,
    iter_op_nodes,
    pending_mappable,
)
from ..runtime.types import (
    ComputeEndEvent,
    ComputeStartEvent,
    callbacks_on,
)
from ..storage.zarr import LazyZarrArray
from ..utils import (  # noqa: F401  (gensym re-exported for plan builders)
    StackSummary,
    extract_stack_summaries,
    gensym,
    join_path,
    memory_repr,
)

logger = logging.getLogger(__name__)

#: unique run id for this client process; work_dir data lives under it.
#: Overridable via CUBED_TPU_CONTEXT_ID: a resumable deployment (resume=True
#: across client restarts, or resume_from_journal after a coordinator
#: crash) must pin it so the restarted client resolves intermediate-array
#: paths to the SAME store locations the crashed run wrote
CONTEXT_ID = (
    os.environ.get("CUBED_TPU_CONTEXT_ID") or f"cubed-{uuid.uuid4().hex[:10]}"
)


def new_temp_path(name: str, spec=None) -> str:
    """A unique storage path for an intermediate array in the work_dir."""
    work_dir = spec.work_dir if spec is not None and spec.work_dir else tempfile.gettempdir()
    context_dir = join_path(work_dir, CONTEXT_ID)
    return join_path(context_dir, f"{name}.zarr")


class Plan:
    """A deferred computation constructed as a DAG of whole-array operations."""

    def __init__(self, dag: nx.MultiDiGraph):
        self.dag = dag

    # -- construction ------------------------------------------------------

    @classmethod
    def _new(
        cls,
        name: str,
        op_name: str,
        target,
        primitive_op: Optional[PrimitiveOperation] = None,
        hidden: bool = False,
        *source_arrays,
    ) -> "Plan":
        """Create a new plan adding an op (and its output array — or arrays,
        when ``name``/``target`` are lists for a multi-output op) to the
        union of the source arrays' plans."""
        dag = arrays_to_dag(*source_arrays)

        frame = inspect.currentframe()
        # skip this frame and internal callers
        stack_summaries = extract_stack_summaries(frame.f_back if frame else None)

        if isinstance(name, (list, tuple)):
            # multi-output op: one op node feeding N array nodes
            op_node = gensym(f"op-{op_name}")
            dag.add_node(
                op_node,
                name=op_node,
                type="op",
                op_display_name=f"{op_name}\n" + "\n".join(name),
                op_name=op_name,
                primitive_op=primitive_op,
                pipeline=primitive_op.pipeline if primitive_op else None,
                hidden=hidden,
                stack_summaries=stack_summaries,
            )
            for n, t in zip(name, target):
                dag.add_node(n, name=n, type="array", target=t, hidden=hidden)
                dag.add_edge(op_node, n)
            for x in source_arrays:
                dag.add_edge(x.name, op_node)
            return Plan(dag)

        if primitive_op is None:
            # op with no computation (e.g. wrapping an existing zarr array)
            op_node = gensym(f"op-{op_name}")
            dag.add_node(
                op_node,
                name=op_node,
                type="op",
                op_display_name=f"{op_name}\n{name}",
                op_name=op_name,
                primitive_op=None,
                hidden=hidden,
                stack_summaries=stack_summaries,
            )
            dag.add_node(name, name=name, type="array", target=target, hidden=hidden)
            dag.add_edge(op_node, name)
            for x in source_arrays:
                dag.add_edge(x.name, op_node)
        else:
            op_node = gensym(f"op-{op_name}")
            dag.add_node(
                op_node,
                name=op_node,
                type="op",
                op_display_name=f"{op_name}\n{name}",
                op_name=op_name,
                primitive_op=primitive_op,
                pipeline=primitive_op.pipeline,
                hidden=hidden,
                stack_summaries=stack_summaries,
            )
            dag.add_node(name, name=name, type="array", target=target, hidden=hidden)
            dag.add_edge(op_node, name)
            for x in source_arrays:
                dag.add_edge(x.name, op_node)
        return Plan(dag)

    @classmethod
    def arrays_to_plan(cls, *arrays) -> "Plan":
        return Plan(arrays_to_dag(*arrays))

    # -- finalization ------------------------------------------------------

    def _finalize(
        self,
        optimize_graph: bool = True,
        optimize_function: Optional[Callable] = None,
        array_names: Optional[tuple] = None,
    ) -> "FinalizedPlan":
        dag = self.optimize(optimize_function, array_names).dag if optimize_graph else self.dag
        dag = dag.copy()
        dag = self.create_lazy_zarr_arrays(dag)
        return FinalizedPlan(nx.freeze(dag))

    def optimize(
        self,
        optimize_function: Optional[Callable] = None,
        array_names: Optional[tuple] = None,
    ) -> "Plan":
        from .optimization import multiple_inputs_optimize_dag

        if optimize_function is None:
            optimize_function = multiple_inputs_optimize_dag
        dag = optimize_function(self.dag.copy(), array_names=array_names)
        return Plan(dag)

    def create_lazy_zarr_arrays(self, dag: nx.MultiDiGraph) -> nx.MultiDiGraph:
        """Inject a single first op that writes metadata for every lazy target."""
        lazy = [
            (name, data["target"])
            for name, data in dag.nodes(data=True)
            if data.get("type") == "array" and isinstance(data.get("target"), LazyZarrArray)
        ]
        if not lazy:
            return dag
        op_node = "create-arrays"
        targets = [t for _, t in lazy]
        pipeline = CubedPipeline(
            create_zarr_array, op_node, targets, None
        )
        primitive_op = PrimitiveOperation(
            pipeline=pipeline,
            source_array_names=[],
            target_array=None,
            projected_mem=0,
            allowed_mem=0,
            reserved_mem=0,
            num_tasks=len(targets),
            fusable=False,
        )
        dag.add_node(
            op_node,
            name=op_node,
            type="op",
            op_display_name=f"{op_node}\n{len(targets)} arrays",
            op_name=op_node,
            primitive_op=primitive_op,
            pipeline=pipeline,
            hidden=False,
            stack_summaries=[],
        )
        # run before every other op (reference: edges to all pipeline nodes,
        # cubed/core/plan.py:136-176)
        for name, _ in list(iter_op_nodes(dag)):
            if name != op_node:
                dag.add_edge(op_node, name)
        return dag

    # -- execution ---------------------------------------------------------

    def execute(
        self,
        executor=None,
        callbacks: Optional[Sequence] = None,
        optimize_graph: bool = True,
        optimize_function: Optional[Callable] = None,
        resume: Optional[bool] = None,
        resume_from_journal: Optional[str] = None,
        array_names: Optional[tuple] = None,
        spec=None,
        finalized: Optional["FinalizedPlan"] = None,
        deadline_s: Optional[float] = None,
        cancellation=None,
        **kwargs,
    ) -> None:
        if executor is None:
            from ..runtime.executors.python import PythonDagExecutor

            executor = PythonDagExecutor()

        # end-to-end time bound (runtime/cancellation.py): deadline_s
        # mints a per-compute CancellationToken (or tightens one the
        # caller passed — the service threads its own through here so
        # RequestHandle.cancel() reaches RUNNING computes); the token is
        # checked by every dispatch loop, carried on distributed task
        # messages, and enforced cooperatively inside task bodies
        from ..runtime import cancellation as cancel_mod

        cancel_token = cancellation
        if deadline_s is not None:
            if cancel_token is None:
                cancel_token = cancel_mod.CancellationToken()
            cancel_token.set_deadline(deadline_s)
        if cancel_token is not None:
            kwargs["cancellation"] = cancel_token

        if resume_from_journal is not None:
            # coordinator-crash recovery: the journal's completed-task set
            # intersects the chunk-integrity resume scan (the executors
            # build the ResumeState from this), so only tasks that BOTH
            # verify on disk AND were journaled complete are skipped
            from ..runtime.journal import load_journal

            resume = True
            kwargs["journal"] = load_journal(resume_from_journal)

        # optimise and finalise run before on_compute_start, where no task
        # scope reaches: timed here, reported as executor_stats'
        # plan_finalize_s (0.0 for a plan handed in finalized)
        finalize_started = time.perf_counter()
        if finalized is None:
            finalized = self._finalize(
                optimize_graph, optimize_function, array_names
            )
        plan_finalize_s = time.perf_counter() - finalize_started
        # else: a pre-finalized plan (the service's structural plan cache)
        # skips optimization + lazy-array creation entirely; the caller is
        # responsible for the fingerprint match that makes this sound
        dag = finalized.dag

        # every compute carries an aggregator: it folds per-task stats
        # (completion counts, storage bytes measured where each task ran)
        # into the process metrics registry and builds the per-op summary
        from ..observability import logs
        from ..observability.callback import _ComputeAggregator
        from ..observability.collect import TraceCollector
        from ..observability.flightrecorder import (
            FLIGHT_RECORDER_ENV_VAR,
            FlightRecorder,
        )
        from ..observability.metrics import get_registry

        #: correlates this compute's trace, structured logs, flight bundle
        compute_id = f"c-{uuid.uuid4().hex[:10]}"
        aggregator = _ComputeAggregator()
        all_callbacks = list(callbacks) if callbacks else []
        all_callbacks.append(aggregator)
        journal_path = getattr(spec, "journal", None)
        if journal_path:
            # durable compute journal (runtime/journal.py): compute
            # metadata, per-task dispatch/completion, and the decision ring
            # land in an append-only fsync'd JSONL beside the store — what
            # resume_from_journal rebuilds coordinator state from after a
            # client crash
            from ..runtime.journal import JournalCallback

            all_callbacks.append(JournalCallback(journal_path))
        # live telemetry (observability/export.py): env > Spec > off. When
        # armed, the process-global sampler/HTTP endpoint starts (or keeps
        # running — it outlives computes, like any scrape target) and this
        # compute reports live progress (tasks done/total -> task rate/ETA
        # on the /snapshot.json feed and `python -m cubed_tpu.top`)
        from ..observability import export as telemetry_export
        from ..observability.timeseries import ComputeProgressCallback

        if telemetry_export.maybe_start(spec) is not None:
            all_callbacks.append(ComputeProgressCallback())
        recorder_dir = os.environ.get(FLIGHT_RECORDER_ENV_VAR)
        if recorder_dir and not any(
            isinstance(cb, TraceCollector) for cb in all_callbacks
        ):
            # operator-armed post-mortems: every compute records, bundles
            # are only written on failure. Suppressed when the caller
            # already attached ANY collector (FlightRecorder included) —
            # two collectors would double-count the spans_dropped/
            # stragglers_detected counters and duplicate scheduler-lane
            # straggler instants; a caller who wants both a loose trace
            # AND bundles should attach one FlightRecorder and export from
            # it (observability/flightrecorder.py)
            all_callbacks.append(FlightRecorder(bundle_dir=recorder_dir))
        # durable run-history archive (observability/runhistory.py): a
        # compact record per compute — fingerprint, wall clock, analyze()
        # buckets, outcome — appended at completion. The bucket
        # decomposition needs the merged task spans, so arming run_history
        # attaches a TraceCollector when the caller (or the flight
        # recorder above) didn't already bring one; an existing collector
        # is reused, never doubled (same single-collector rule as the
        # operator flight recorder)
        run_history_dir = getattr(spec, "run_history", None)
        run_collector = None
        if run_history_dir:
            run_collector = next(
                (
                    cb for cb in all_callbacks
                    if isinstance(cb, TraceCollector)
                ),
                None,
            )
            if run_collector is None:
                run_collector = TraceCollector()
                all_callbacks.append(run_collector)
        run_started_at = time.monotonic()
        metrics_before = get_registry().snapshot()

        callbacks_on(
            all_callbacks, "on_compute_start",
            ComputeStartEvent(dag, resume, compute_id=compute_id),
        )
        if cancel_token is not None:
            # registered under the compute id for the compute's duration:
            # the coordinator reads it per task message, in-process chunk
            # IO checks it between reads/writes (unregistered in finally)
            cancel_mod.register_compute(compute_id, cancel_token)
        compute_error: Optional[BaseException] = None
        try:
            # Spec-level chaos config arms fault injection for this
            # compute's duration (exported to the env so spawned workers
            # inherit it); a None config makes this a no-op. Arming is
            # process-global while active — same caveat as the metrics
            # registry below: concurrent computes in one process share it
            from ..observability import accounting, dispatchprofile
            from ..runtime import faults, memory
            from ..storage import integrity

            with logs.compute_scope(
                # log-correlation context: every client/pool/fleet log line
                # emitted under this compute carries its id (the env export
                # is how spawned pool workers inherit it; fleet workers get
                # it from each task message)
                compute_id, export_env=True
            ), accounting.spans_scoped(
                # span recording is pay-for-what-you-watch: armed only while
                # a collector is attached to merge the spans (exported to
                # the env for pool spawns; fleet task messages mirror it).
                # None leaves an operator's CUBED_TPU_TASK_SPANS untouched
                True if any(
                    isinstance(cb, TraceCollector) for cb in all_callbacks
                ) else None,
                export_env=True,
            ), faults.scoped(
                getattr(spec, "fault_injection", None), export_env=True
            ), integrity.scoped(
                # Spec-level integrity mode, armed (and exported to the env,
                # so spawned pool/fleet workers inherit it) for this
                # compute's duration; None defers to env/default
                getattr(spec, "integrity", None), export_env=True
            ), memory.scoped(
                # runtime memory guard: the Spec's mode (default observe)
                # plus its allowed_mem, armed for the compute and exported
                # so pool workers measure against the same budget; an
                # operator CUBED_TPU_MEMORY_GUARD env var wins untouched.
                # No spec at all -> no budget to judge against -> no guard
                getattr(spec, "memory_guard", None),
                allowed_mem=getattr(spec, "allowed_mem", None),
                export_env=True,
            ), dispatchprofile.profile_scoped(
                # coordinator self-profiling (env > Spec > off): a true
                # no-op unless armed; the finished profile registers under
                # the compute id for bundles/diagnose/the trace lane
                spec, compute_id,
            ):
                executor.execute_dag(
                    dag,
                    callbacks=all_callbacks,
                    array_names=array_names,
                    resume=resume,
                    spec=spec,
                    **kwargs,
                )
        except BaseException as e:
            # captured for the end event (the flight recorder keys its
            # bundle assembly off it), then re-raised untouched
            compute_error = e
            raise
        finally:
            # on_compute_end fires even when the compute FAILS: that is when
            # a trace of the partial run (TracingCallback's trace.json) and
            # the stats gathered so far matter most. Stats assembly is
            # guarded so it can never mask the executor's own exception.
            #
            # executor_stats: the executor's own counters, overlaid with
            # this compute's metrics delta (task/retry/byte counters) and
            # the per-op wall-clock + projected-vs-measured summary.
            # Overlay order is deliberate: where an executor's lifetime
            # counter shares a name with a registry metric (a persistent
            # distributed fleet's task_timeouts/workers_lost), the
            # PER-COMPUTE windowed value wins — lifetime totals remain
            # available on executor.stats itself.
            #
            # Known limitation: the registry is process-global, so computes
            # running CONCURRENTLY in one process see each other's counter
            # increments in their windows (docs/observability.md). The
            # event-derived numbers (per_op, tasks/bytes via the
            # aggregator's own fold) are exact per compute either way.
            if cancel_token is not None:
                cancel_mod.unregister_compute(compute_id)
            stats: dict = {"plan_finalize_s": plan_finalize_s}
            try:
                executor_own = getattr(executor, "stats", None)
                if executor_own:
                    stats.update(dict(executor_own))
                stats.update(get_registry().snapshot_delta(metrics_before))
                stats.update(aggregator.summary())
            except Exception:
                logger.exception(
                    "failed to assemble executor_stats; reporting partial "
                    "stats (%d keys)", len(stats)
                )
            callbacks_on(
                all_callbacks,
                "on_compute_end",
                ComputeEndEvent(
                    dag,
                    executor_stats=stats or None,
                    compute_id=compute_id,
                    error=compute_error,
                ),
            )
            if run_history_dir:
                # after on_compute_end so the collector's trace is sealed;
                # the append itself never raises (archive discipline)
                from ..observability import runhistory

                # fingerprint the PRE-finalize dag: finalized lazy targets
                # carry per-build store paths that defeat the structural
                # masking, and the service fingerprints pre-finalize too —
                # archive records and plan-cache keys must agree
                runhistory.record_compute(
                    run_history_dir,
                    compute_id=compute_id,
                    dag=self.dag,
                    error=compute_error,
                    stats=stats,
                    collector=run_collector,
                    wall_clock_s=time.monotonic() - run_started_at,
                )

    # -- introspection -----------------------------------------------------

    def num_tasks(self, optimize_graph=True, optimize_function=None, resume=None) -> int:
        finalized = self._finalize(optimize_graph, optimize_function)
        return finalized.num_tasks(resume=resume)

    def num_arrays(self, optimize_graph=True, optimize_function=None) -> int:
        finalized = self._finalize(optimize_graph, optimize_function)
        return finalized.num_arrays()

    def max_projected_mem(self, optimize_graph=True, optimize_function=None, resume=None) -> int:
        finalized = self._finalize(optimize_graph, optimize_function)
        return finalized.max_projected_mem(resume=resume)

    def total_nbytes_written(self, optimize_graph=True, optimize_function=None) -> int:
        finalized = self._finalize(optimize_graph, optimize_function)
        return finalized.total_nbytes_written()

    def explain(
        self,
        spec=None,
        optimize_graph=True,
        optimize_function=None,
        array_names=None,
    ):
        """EXPLAIN this plan pre-execution: finalize it exactly like
        ``execute`` would and report per-op task counts, projected memory
        vs ``allowed_mem``, predicted bytes read/written (+ peer-eligible),
        the fusion outcome, and the scheduler/barrier decisions — an
        :class:`~cubed_tpu.observability.analytics.ExplainReport`
        (``print()`` it, ``.to_dict()`` it, or ``.save(path)`` for
        ``python -m cubed_tpu.explain``)."""
        from ..observability.analytics import explain as _explain

        return _explain(
            self,
            spec=spec,
            optimize_graph=optimize_graph,
            optimize_function=optimize_function,
            array_names=array_names,
        )

    def visualize(
        self,
        filename="cubed",
        format=None,
        rankdir="TB",
        optimize_graph=True,
        optimize_function=None,
        show_hidden=False,
    ):
        from .visualization import visualize_dag

        finalized = self._finalize(optimize_graph, optimize_function)
        return visualize_dag(
            finalized.dag,
            filename=filename,
            format=format,
            rankdir=rankdir,
            show_hidden=show_hidden,
        )


class FinalizedPlan:
    """A frozen, optimized DAG ready for execution."""

    def __init__(self, dag: nx.MultiDiGraph):
        self.dag = dag

    def num_tasks(self, resume=None) -> int:
        """Task count, chunk-granular under ``resume``: a partially-complete
        blockwise op contributes only its still-pending tasks — the same
        per-task skip the executors apply, so this number matches what a
        resumed compute actually runs. The scan is read-only (no
        quarantining, no metrics)."""
        nodes = dict(self.dag.nodes(data=True))
        state = ResumeState(count=False) if resume else None
        total = 0
        for name in nx.topological_sort(self.dag):
            if already_computed(name, self.dag, nodes, resume, state):
                continue
            node = nodes[name]
            if resume:
                _, skipped = pending_mappable(
                    name, node, resume, state, record=False
                )
                total += node["primitive_op"].num_tasks - skipped
            else:
                total += node["primitive_op"].num_tasks
        return total

    def num_arrays(self) -> int:
        return sum(1 for _, d in self.dag.nodes(data=True) if d.get("type") == "array")

    def num_ops(self) -> int:
        return sum(1 for _ in iter_op_nodes(self.dag))

    def max_projected_mem(self, resume=None) -> int:
        """Peak projected memory over the ops a compute would actually run;
        under ``resume`` an op skipped (all outputs checksum-valid) drops
        out, exactly mirroring the executors' skip decision."""
        nodes = dict(self.dag.nodes(data=True))
        state = ResumeState(count=False) if resume else None
        mems = [
            nodes[name]["primitive_op"].projected_mem
            for name in nx.topological_sort(self.dag)
            if not already_computed(name, self.dag, nodes, resume, state)
        ]
        return max(mems) if mems else 0

    def total_nbytes_written(self) -> int:
        return sum(
            d["target"].nbytes
            for _, d in self.dag.nodes(data=True)
            if d.get("type") == "array" and isinstance(d.get("target"), LazyZarrArray)
        )

    def explain(self, spec=None):
        """EXPLAIN this already-finalized plan (see ``Plan.explain``)."""
        from ..observability.analytics import explain_finalized

        return explain_finalized(self, spec=spec)


def arrays_to_dag(*arrays) -> nx.MultiDiGraph:
    """Union of the plans of the given arrays (sharing nodes by name)."""
    from .array import check_array_specs

    check_array_specs(arrays)
    dags = [a.plan.dag for a in arrays if hasattr(a, "plan")]
    if not dags:
        return nx.MultiDiGraph()
    return nx.compose_all(dags)


def arrays_to_plan(*arrays) -> Plan:
    return Plan(arrays_to_dag(*arrays))


def create_zarr_array(lazy_array: LazyZarrArray, config=None) -> None:
    """Task body of the create-arrays op."""
    lazy_array.create(mode="a")
