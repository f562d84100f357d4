"""Seconds of the last compute that the executor's thread was blocked on the
flush's writer thread (``write_wait_us``, counted armed or not, around
``future.result()`` in ``_flush_chunks``): most of ``flush_s`` where the
writer paces the flush, next to nothing where the fetch does. A program
without the counter (the parent of the PR that brought it) gives nothing."""

METRICS = [
    {"name": "write_wait_s", "unit": "s", "better": "lower", "source": "program_counter",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    waited = traced.stats.get("write_wait_us")
    return None if waited is None else waited / 1e6
