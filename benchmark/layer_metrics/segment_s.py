"""Self time of ``JaxExecutor._run_segment``: classifying, the structural
key, the lookup of the compiled program and its dispatch; what ``_preload``
takes inside it is taken off. The device's execution is not in it: the call
returns when the program is enqueued."""

METRICS = [
    {"name": "segment_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "segment dispatch", "moves": "compute_s"},
]
SPANS = {"cubed_tpu.runtime.executors.jax:JaxExecutor._run_segment": {}, "cubed_tpu.runtime.executors.jax:JaxExecutor._preload": {}}


def read(traced):
    return traced.span_seconds("JaxExecutor._run_segment", self_time=True)
