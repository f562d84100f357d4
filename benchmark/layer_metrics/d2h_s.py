"""Seconds of the last compute in the program's ``jax.d2h`` spans: the copy
of a ready device value to the host and the view back to float64. What
``fetch_s`` times from outside, less the call itself."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "d2h_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    return program_spans.span_seconds(traced, "jax.d2h")
