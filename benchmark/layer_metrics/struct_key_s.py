"""Seconds of the last compute in the program's ``jax.struct_key`` span: the
fingerprint of a segment (``JaxExecutor._structural_key``) that finds its
compiled program without tracing. A part of ``segment_s``."""

from benchmark.harness import program_spans

program_spans.arm()

METRICS = [
    {"name": "struct_key_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "segment dispatch", "moves": "compute_s"},
]


def read(traced):
    return program_spans.span_seconds(traced, "jax.struct_key")
