"""Fetches of the last compute (calls of ``JaxExecutor._to_host`` on one
device value), each of which blocks the host on the device: one for a
reduced result, one a chunk for a stored array."""

_COMMON = {"unit": "count", "better": "lower", "source": "program_counter"}
METRICS = [
    {"name": "host_syncs.gen", "layer": "device", "moves": "compute_s", **_COMMON},
    {"name": "host_syncs.zarr", "layer": "HBM to Zarr flush", "moves": "zarr_compute_s",
     **_COMMON},
]


def read(traced):
    return traced.stats.get("host_syncs")
