"""Seconds in ``JaxExecutor._preload`` per compute: the host reads the
sources' chunk files and puts them on the device. The store's reads are
wrapped too, for the breakdown of idle gaps only."""

METRICS = [
    {"name": "preload_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]
SPANS = {"cubed_tpu.runtime.executors.jax:JaxExecutor._preload": {}, "cubed_tpu.storage.store:ZarrV2Array.__getitem__": {}}


def read(traced):
    return traced.span_seconds("JaxExecutor._preload")
