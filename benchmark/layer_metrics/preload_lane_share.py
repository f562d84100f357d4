"""Share of the bytes the last compute streamed to the device chunk by chunk
(``h2d_stream_bytes``) that reached their chip on a lane thread of that
chip's own (``h2d_lane_bytes``): 100 where every source had several owners
and a streamed preload ran one lane a chip, each on its thread through its
pair (a mesh); 0 where every source had one owner and its one lane ran on the
calling thread (one chip). A program without the counter (one thread takes
the chips in turn) gives nothing, as does a compute that streamed nothing."""

METRICS = [
    {"name": "preload_lane_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    on_lanes = traced.stats.get("h2d_lane_bytes")
    streamed = traced.stats.get("h2d_stream_bytes")
    if on_lanes is None or not streamed:
        return None
    return 100.0 * on_lanes / streamed
