"""Seconds of the last compute in the program's ``jax.flush`` spans: one
resident array on its way to its store until every chunk is durable and in
the manifest, as the executor's thread sees it (the first fetch, then each
chunk's write or fetch, whichever is longer). Since the flush works on two
threads, ``fetch_s`` and ``store_write_s`` overlap and their sum exceeds this;
``write_wait_s`` is the part of it this thread spent blocked on the writer."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "flush_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    return program_spans.span_seconds(traced, "jax.flush")
