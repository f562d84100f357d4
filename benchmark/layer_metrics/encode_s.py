"""Seconds of the last compute in the store's ``chunk_encode`` spans: a
chunk made contiguous, turned into bytes and put through the codec. A part
of ``store_write_s``."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "encode_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    return program_spans.span_seconds(traced, "chunk_encode")
