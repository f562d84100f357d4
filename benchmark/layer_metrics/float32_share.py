"""Share of the bytes of the floating-point values that the last compute's
segment programs produce, op by op, that are float32: ``device_f32_bytes``
over it, ``device_f64_bytes`` and ``device_f16_bytes`` together. The
counters follow the dtype the traced value has, not the one the plan
declares: 0 where a plan declares and computes float64, nearly 100 where it
declares float32 and only ``mean``'s partial sums are float64, and 100 too
for a float64 plan under ``compute_dtype="float32"``, which the plan alone
would not show. A program without the counters (the parent of the PR that
brought them) gives nothing, as does a compute that produced no float."""

METRICS = [
    {"name": "float32_share.gen", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "device", "moves": "compute_s"},
]

_COUNTERS = ("device_f32_bytes", "device_f64_bytes", "device_f16_bytes")


def read(traced):
    counted = [traced.stats.get(name) for name in _COUNTERS]
    if None in counted or not sum(counted):
        return None
    return 100.0 * counted[0] / sum(counted)
