"""Share of the bytes the last compute put on the device (``h2d_bytes``) that
went there chunk by chunk through the executor's reused staging buffers
(``h2d_stream_bytes``): 100 where every source was a stored array of several
chunks with room in HBM, 0 where each was assembled whole on the host first.
A program without the counter (the parent of the PR that brought the stream)
gives nothing."""

METRICS = [
    {"name": "h2d_stream_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    streamed = traced.stats.get("h2d_stream_bytes")
    put = traced.stats.get("h2d_bytes")
    if streamed is None or not put:
        return None
    return 100.0 * streamed / put
