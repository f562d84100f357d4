"""The least busy chip's busy time over the busiest chip's, from the device
trace: near 100 every chip works as long as the busiest, near 0 one works and
the others wait."""

METRICS = [
    {"name": "chip_busy_min_share", "unit": "%", "better": "higher", "source": "device_trace",
     "layer": "mesh placement", "moves": "compute_s"},
]


def read(traced):
    d = traced.device
    if not d or len(d["busy_s"]) < 2:
        return None
    return 100.0 * min(d["busy_s"].values()) / max(d["busy_s"].values())
