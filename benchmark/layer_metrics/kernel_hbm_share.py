"""The query's nominal bytes over the device's busy time, as a share of the
HBM peak of the chips used. Named for what it is: emulated float64 and
threefry are bound by neither published peak, so this is no roofline share
(that needs operation counts, which the next tracing issue brings)."""

from benchmark.harness.peaks import peaks_for

METRICS = [
    {"name": "kernel_hbm_share", "unit": "%", "better": "higher", "source": "device_trace",
     "layer": "device", "moves": "compute_s"},
]


def read(traced):
    busy = traced.busiest_per_compute("busy_s")
    if not busy:
        return None
    peak = peaks_for(traced.device_kind)["hbm_bytes_per_s"] * traced.chips
    return 100.0 * traced.nominal_bytes / busy / peak
