"""Share of the bytes the last compute put on the device (``h2d_bytes``) that
entered as uint64 bit patterns (``h2d_bits_bytes``): 100 where every source
was float64 in a compute that only moves values, on a device whose float64
is not one; 0 where the compute does arithmetic, or the device's float64
round-trips. A program without the counter (the parent of the PR that
brought it) gives nothing."""

METRICS = [
    {"name": "bits_carried_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    bits = traced.stats.get("h2d_bits_bytes")
    put = traced.stats.get("h2d_bytes")
    if bits is None or not put:
        return None
    return 100.0 * bits / put
