"""Share of the bytes the last compute fetched from the device (``d2h_bytes``)
that reached the store from one of the executor's reused staging buffers with
no copy on the host after the join (``flush_stream_bytes``): 100 where every
chunk left as planes, was joined into a buffer and written from there, 0 where
each was fetched as it is and handed over as the runtime's own array. A
program without the counter (the parent of the PR that brought the streamed
flush) gives nothing."""

METRICS = [
    {"name": "flush_stream_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    streamed = traced.stats.get("flush_stream_bytes")
    fetched = traced.stats.get("d2h_bytes")
    if streamed is None or not fetched:
        return None
    return 100.0 * streamed / fetched
