"""Host seconds of the last compute spent waiting for the device ahead of its
fetches: the program's ``jax.device_wait`` spans. While the harness still
wraps ``JaxExecutor._to_host`` with ``ready_first`` (for ``fetch_s`` and
``device_wait_s``), that wrapper waits first and the program's span finds the
value ready; the wait is the same wait whoever takes it, so the wrapper's
``JaxExecutor._to_host.ready`` spans of the same compute are added. The metric
then means the same before and after a ``benchmark`` PR retires the wrapper."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "flush_wait_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    waited = program_spans.span_seconds(traced, "jax.device_wait")
    if waited is None or not traced.window:
        return waited
    ahead = traced.recorder.of("JaxExecutor._to_host.ready", traced.window[-1])
    return waited + sum(s.seconds for s in ahead)
