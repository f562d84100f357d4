"""Pages that the last compute's preloads made resident
(``preload_page_faults``: the growth of the process's resident set,
``/proc/self/statm``, over each ``jax.preload`` span, in pages of the
system's size): what their first touches faulted in. A fresh executor's first
two chunk reads fill two staging buffers nobody has written; buffers that
outlive the executor, or pages the allocator hands back touched, read near 0.
Not the kernel's own count of faults (``ru_minflt``), which the host of the
chip does not keep. A program without the counter (the parent of the PR that
brought it) gives nothing."""

METRICS = [
    {"name": "preload_page_faults", "unit": "count", "better": "lower",
     "source": "program_counter", "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    return traced.stats.get("preload_page_faults")
