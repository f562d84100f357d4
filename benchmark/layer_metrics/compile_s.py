"""Seconds of backend compilation or of loading an executable from the
persistent cache during set-up, as ``jax.monitoring`` reports them."""

METRICS = [
    {"name": "compile_s", "unit": "s", "better": "lower", "source": "program_counter",
     "layer": "compile and persistent cache", "moves": "setup_s"},
]


def read(traced):
    return traced.compile_setup["compile_seconds"]
