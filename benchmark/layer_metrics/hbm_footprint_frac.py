"""XLA's own accounting of the largest segment program (arguments, outputs
and temporaries on one device: the executor's ``segment_hbm_footprint``)
over one device's ``bytes_limit``."""

_COMMON = {"unit": "%", "better": "lower", "source": "program_counter", "layer": "memory"}
METRICS = [
    {"name": "hbm_footprint_frac.gen", "moves": "compute_s", **_COMMON},
    {"name": "hbm_footprint_frac.zarr", "moves": "zarr_compute_s", **_COMMON},
]


def read(traced):
    footprint = traced.stats.get("segment_hbm_footprint")
    if not footprint or not traced.bytes_limit:
        return None
    return 100.0 * footprint / traced.bytes_limit
