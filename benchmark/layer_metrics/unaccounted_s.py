"""The part of a compute that neither the plan nor a span that some metric
reads covers: it says how far the table of layers can be trusted. All the
wrapped callables are named here, so that every cell records all of them."""

from benchmark.harness.trace_reduce import total, union

_COMMON = {"unit": "s", "better": "lower", "source": "program_span", "layer": "whole compute"}
METRICS = [
    {"name": "unaccounted_s.gen", "moves": "compute_s", **_COMMON},
    {"name": "unaccounted_s.zarr", "moves": "zarr_compute_s", **_COMMON},
]
SPANS = {
    "cubed_tpu.runtime.executors.jax:JaxExecutor.execute_dag": {},
    "cubed_tpu.runtime.executors.jax:JaxExecutor._preload": {},
    "cubed_tpu.runtime.executors.jax:JaxExecutor._run_segment": {},
    "cubed_tpu.runtime.executors.jax:JaxExecutor._to_host": {"ready_first": True},
    "cubed_tpu.runtime.executors.jax:JaxExecutor._flush": {},
    "cubed_tpu.storage.store:ZarrV2Array.__getitem__": {},
    "cubed_tpu.storage.store:ZarrV2Array.__setitem__": {},
}
#: the spans that a metric reads; the others only structure the breakdown
COVERING = (
    "JaxExecutor._preload", "JaxExecutor._run_segment",
    "JaxExecutor._to_host.ready", "JaxExecutor._to_host",
    "ZarrV2Array.__setitem__",
)


def read(traced):
    rec = traced.recorder

    def rest(compute):
        root, entered = rec.of("compute", compute), rec.of("JaxExecutor.execute_dag", compute)
        if not root or not entered:
            return None
        covered = [(root[0].start, entered[0].start)] + [
            (s.start, s.end) for name in COVERING for s in rec.of(name, compute)
        ]
        return root[0].seconds - total(union(covered))

    return traced.median_per_compute(rest)
