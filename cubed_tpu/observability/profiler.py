"""**Device** profiler: a JAX profiler trace of one compute.

``JaxProfilerCallback`` brackets a compute in ``jax.profiler.start_trace``
/ ``stop_trace`` (an ``.xplane.pb`` for TensorBoard/XProf). Together with
the span pipeline it is the operator's joint timeline: every recording
``scope_span`` is also a ``cubed:<name>`` trace annotation
(``observability/accounting.py``), so with spans armed (a ``TraceCollector``
attached, or ``CUBED_TPU_TASK_SPANS=1``) the trace holds the host's phases
(the device executor's preload, dispatch, device wait and fetch, the store's
reads, writes and fsyncs) on the same clock as the device's operations,
and the operations of a fused segment carry the plan's op in their metadata
(``op00.blockwise...``). Start and stop are recorded as
:func:`collect.record_decision` entries, so they appear on the
``scheduler`` lane of the merged trace and inside flight-recorder bundles.

Device memory is not sampled here: ``peak_bytes_in_use`` does not see a
program's temporaries on this runtime; the executor's
``segment_hbm_footprint`` (XLA's own accounting of the compiled segment) in
``executor_stats`` is the number to read.

Not to be confused with ``observability/dispatchprofile.py`` — the
**dispatch** profiler, which samples the host-side control-plane threads
(coordinator/dispatch loop) with ``sys._current_frames()``. This module
profiles what the *devices* do; that one profiles what the *coordinator*
does. See docs/observability.md "Device profiler" vs "Control-plane
observability".
"""

from __future__ import annotations

from ..runtime.types import Callback
from .collect import record_decision


class JaxProfilerCallback(Callback):
    """Write a jax profiler trace for the span of one compute call."""

    def __init__(self, log_dir: str = "profile"):
        self.log_dir = log_dir
        self._active = False

    def on_compute_start(self, event) -> None:
        import jax

        try:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
            record_decision("jax_profiler_start", log_dir=self.log_dir)
        except Exception:
            self._active = False

    def on_compute_end(self, event) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            record_decision("jax_profiler_stop", log_dir=self.log_dir)
