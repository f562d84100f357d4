"""Share of the bytes the last compute fetched from the device
(``d2h_bytes``) that left it as 32-bit planes (``d2h_plane_bytes``): 100
where every fetch was a large 64-bit value on a device without native
float64, 0 where every fetch was under the crossover. A program without the
counter (the parent of the PR that brought the planes) gives nothing."""

METRICS = [
    {"name": "d2h_plane_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    planes = traced.stats.get("d2h_plane_bytes")
    fetched = traced.stats.get("d2h_bytes")
    if planes is None or not fetched:
        return None
    return 100.0 * planes / fetched
