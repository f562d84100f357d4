"""Seconds per compute in which a collective operation ran on the busiest
chip: the union of their intervals in the device trace."""

METRICS = [
    {"name": "collective_s", "unit": "s", "better": "lower", "source": "device_trace",
     "layer": "mesh placement", "moves": "compute_s"},
]


def read(traced):
    d = traced.device
    if not d or len(d["busy_s"]) < 2:
        return None
    return traced.busiest_per_compute("collective_s")
