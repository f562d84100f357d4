"""Seconds per compute in ``ZarrV2Array.__setitem__``: encoding, the atomic
fsynced chunk writes and the checksum manifest."""

METRICS = [
    {"name": "store_write_s", "unit": "s", "better": "lower", "source": "program_span",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]
SPANS = {"cubed_tpu.storage.store:ZarrV2Array.__setitem__": {}}


def read(traced):
    return traced.span_seconds("ZarrV2Array.__setitem__")
