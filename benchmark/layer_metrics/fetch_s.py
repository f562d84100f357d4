"""Seconds per compute in ``JaxExecutor._to_host`` after the value is ready
on the device: the transfer to the host and the conversion there.
``_flush`` is wrapped too, for the breakdown of idle gaps only."""

METRICS = [
    {"name": "fetch_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]
SPANS = {"cubed_tpu.runtime.executors.jax:JaxExecutor._to_host": {"ready_first": True}, "cubed_tpu.runtime.executors.jax:JaxExecutor._flush": {}}


def read(traced):
    return traced.span_seconds("JaxExecutor._to_host")
