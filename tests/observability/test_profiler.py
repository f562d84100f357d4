"""The device profiler callback: it brackets a compute in a jax profiler
trace, feeds the span pipeline's decision ring, and cannot fail a compute
when jax's profiler is unavailable."""

from __future__ import annotations

import time
import types

from cubed_tpu.observability.collect import decisions_since
from cubed_tpu.observability.profiler import JaxProfilerCallback


def test_jax_profiler_callback_brackets_the_compute(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop", None))
    )
    t0 = time.time()
    cb = JaxProfilerCallback(log_dir="prof-dir")
    cb.on_compute_start(types.SimpleNamespace(dag=None))
    assert cb._active
    cb.on_compute_end(types.SimpleNamespace(dag=None))
    assert not cb._active
    assert [c[0] for c in calls] == ["start", "stop"]
    kinds = [d["kind"] for d in decisions_since(t0)]
    assert "jax_profiler_start" in kinds and "jax_profiler_stop" in kinds


def test_jax_profiler_start_failure_is_swallowed(monkeypatch):
    import jax

    def boom(_):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    cb = JaxProfilerCallback()
    cb.on_compute_start(types.SimpleNamespace(dag=None))
    assert not cb._active
    cb.on_compute_end(types.SimpleNamespace(dag=None))  # no stop, no raise
