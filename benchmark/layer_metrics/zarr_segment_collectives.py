"""Collective instructions (all-to-all, all-reduce, all-gather,
collective-permute) in the compiled segment programs of the last compute of
a cell that reads a stored array over a mesh: the executor's
``segment_collectives``, as ``segment_collectives.gen`` reads it for the
cells that report ``compute_s``. A reduction along the axis the mesh divides
should read a handful: each chip's partial sums cross once. A program
without the counter gives nothing."""

METRICS = [
    {"name": "zarr_segment_collectives", "unit": "count", "better": "lower",
     "source": "program_counter", "layer": "mesh placement", "moves": "zarr_compute_s"},
]


def read(traced):
    return traced.stats.get("segment_collectives")
