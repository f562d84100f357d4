"""Seconds of the last compute that its chunk writes spent in ``fsync``: what
the spans directly inside the store's ``storage_write`` spans cover, and those
are the file's ``os.fsync`` before the rename and the directory's after it. A
part of ``store_write_s``. The fsyncs of a target's metadata file, which the
store writes outside any chunk write, are in the program's ``fsync`` total
(``span_s["fsync"]``) and not here."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "fsync_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    return program_spans.child_seconds(traced, "storage_write")
