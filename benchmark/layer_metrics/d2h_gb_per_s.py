"""Bytes the last compute fetched from the device (``d2h_bytes``) over its
seconds in ``jax.d2h``: the rate of the 64-bit fetch."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "d2h_gb_per_s", "unit": "GB/s", "better": "higher", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    seconds = program_spans.span_seconds(traced, "jax.d2h")
    fetched = traced.stats.get("d2h_bytes")
    if not seconds or not fetched:
        return None
    return fetched / seconds / 1e9
