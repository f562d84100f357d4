"""Seconds of the last compute that its chunk writes spent on the CRC-32 of
the stored bytes and the manifest line (``checksum_us``, the store's scoped
counter around ``integrity.record_checksum``): a part of ``store_write_s``
that is neither the file write nor ``fsync_s``. A program without the counter
(the parent of the PR that brought it) gives nothing."""

METRICS = [
    {"name": "checksum_s", "unit": "s", "better": "lower", "source": "program_counter",
     "layer": "HBM to Zarr flush", "moves": "zarr_compute_s"},
]


def read(traced):
    spent = traced.stats.get("checksum_us")
    return None if spent is None else spent / 1e6
