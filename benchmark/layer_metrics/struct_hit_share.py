"""The share of the last compute's traced segments that found their compiled
program by structural key, without tracing or lowering. It should read 100:
the first compute has warmed every shape."""

_COMMON = {"unit": "%", "better": "higher", "source": "program_counter",
           "layer": "compile and persistent cache"}
METRICS = [
    {"name": "struct_hit_share.gen", "moves": "compute_s", **_COMMON},
    {"name": "struct_hit_share.zarr", "moves": "zarr_compute_s", **_COMMON},
]


def read(traced):
    traced_segments = traced.stats.get("segments_traced")
    if not traced_segments:
        return None
    return 100.0 * traced.stats.get("segment_struct_hits", 0) / traced_segments
