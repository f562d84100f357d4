"""Seconds of the last compute in the store's ``storage_read`` and
``integrity_verify`` spans: reading the sources' chunk files on the host (and
checking them where the integrity mode verifies reads). A part of
``preload_s``; the read-back after the executor has returned is not in it."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "host_read_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    return program_spans.span_seconds(traced, "storage_read", "integrity_verify")
