"""Programs compiled (or loaded from the persistent cache) inside the measured
window. It should read 0: every shape is warmed up by the first compute."""

_COMMON = {"unit": "count", "better": "lower", "source": "program_counter",
           "layer": "compile and persistent cache"}
METRICS = [
    {"name": "compiles_in_window.gen", "moves": "compute_s", **_COMMON},
    {"name": "compiles_in_window.zarr", "moves": "zarr_compute_s", **_COMMON},
]


def read(traced):
    return traced.compile_window["programs"]
