"""Seconds of the profiled compute in which a collective operation ran on the
busiest chip, in a cell that moves a stored array over a mesh: the union of
their intervals in the device trace, as ``collective_s`` reads it for the
cells that report ``compute_s``. A chunk that reaches and leaves the chip
that owns it asks for none, and the add of two arrays placed alike has none;
a reduction along the axis the mesh divides crosses the chips once with its
partial sums. Anything beyond that is an operand gathered, or a slice that
crossed chips. Nothing without a trace of more than one chip."""

METRICS = [
    {"name": "zarr_collective_s", "unit": "s", "better": "lower", "source": "device_trace",
     "layer": "mesh placement", "moves": "zarr_compute_s"},
]


def read(traced):
    d = traced.device
    if not d or len(d["busy_s"]) < 2:
        return None
    return traced.busiest_per_compute("collective_s")
