"""Self time of the program's ``jax.h2d`` spans in the last compute: the
``device_put`` (or ``make_array_from_callback``) of the sources, without the
store's reads where they happen inside it. A part of ``preload_s``."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "h2d_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    return program_spans.span_seconds(traced, "jax.h2d", self_time=True)
