"""Seconds of the last compute in the program's ``jax.dispatch`` span: the
call of the compiled segment program until it returns (the program is then
enqueued, not run) and the admission of its outputs. A part of ``segment_s``."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "dispatch_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "segment dispatch", "moves": "compute_s"},
]


def read(traced):
    return program_spans.span_seconds(traced, "jax.dispatch")
