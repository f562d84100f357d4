"""Seconds per compute in which an operation ran on the device: the union of
the operations' intervals in the profiler's trace, inside the traced computes,
over their number; on several chips, the busiest chip."""

METRICS = [
    {"name": "device_busy_s", "unit": "s", "better": "lower", "source": "device_trace",
     "layer": "device", "moves": "compute_s"},
]


def read(traced):
    return traced.busiest_per_compute("busy_s")
