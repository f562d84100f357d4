"""Host seconds per compute spent waiting for the device before a fetch: the
wrapper of ``JaxExecutor._to_host`` first waits for the value to be ready
and times that apart, so that the fetch does not absorb the execution."""

METRICS = [
    {"name": "device_wait_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "device", "moves": "compute_s"},
]
SPANS = {"cubed_tpu.runtime.executors.jax:JaxExecutor._to_host": {"ready_first": True}}


def read(traced):
    return traced.span_seconds("JaxExecutor._to_host.ready")
