"""Collective instructions (all-to-all, all-reduce, all-gather,
collective-permute) in the compiled segment programs of the last compute:
the executor's ``segment_collectives``, counted once from the compiled
module and kept with the cached executable. A query whose only dependence
between chips is one sum should read a handful. A program without the
counter (the parent of the PR that brought it) gives nothing."""

METRICS = [
    {"name": "segment_collectives.gen", "unit": "count", "better": "lower",
     "source": "program_counter", "layer": "mesh placement", "moves": "compute_s"},
]


def read(traced):
    return traced.stats.get("segment_collectives")
