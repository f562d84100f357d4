"""The run's first compute on the host's clock: trace, lower, and compile or
load from the persistent cache, then one execution. Most of what set-up costs
that only the program can shorten."""

METRICS = [
    {"name": "first_compute_s", "unit": "s", "better": "lower", "source": "host_clock",
     "layer": "compile and persistent cache", "moves": "setup_s"},
]


def read(traced):
    return traced.first_compute_s
