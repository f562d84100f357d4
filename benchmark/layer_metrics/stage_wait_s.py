"""Seconds of the last compute that the executor's thread waited for a device
update to let go of a staging buffer (``stage_wait_us``, counted armed or not
in ``_stream_to_device`` and nowhere else): at the end of a chunk's
``jax.h2d`` span and, for the last update of the source before, ahead of a
chunk's read. Where it is most of ``h2d_s`` the device's update paces the
streamed preload, else the file read does. A program without the counter (the
parent of the PR that brought it) gives nothing."""

METRICS = [
    {"name": "stage_wait_s", "unit": "s", "better": "lower", "source": "program_counter",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    waited = traced.stats.get("stage_wait_us")
    return None if waited is None else waited / 1e6
