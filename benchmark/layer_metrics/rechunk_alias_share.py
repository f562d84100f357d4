"""Share of the last compute's rechunk ops that ran as an alias of a resident
array (``rechunk_alias``) among the four routes of ``_exec_rechunk``: the
alias, a virtual source made on the device (``rechunk_virtual``), a stored
source read whole on the host and put (``rechunk_host_whole``), a copy chunk
by chunk on the host that never touches the chip (``rechunk_host_copy``).
100 where every rechunk was metadata. A window's last compute finds its
segment program compiled, so the counters are there only where the program
reports with a structural hit what its trace did: a program that does not
(the parent of the PR that brought this reader), and a compute without a
rechunk, give nothing."""

METRICS = [
    {"name": "rechunk_alias_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "segment dispatch", "moves": "zarr_compute_s"},
]

ROUTES = ("rechunk_alias", "rechunk_virtual", "rechunk_host_whole", "rechunk_host_copy")


def read(traced):
    total = sum(traced.stats.get(route) or 0 for route in ROUTES)
    if not total:
        return None
    return 100.0 * (traced.stats.get("rechunk_alias") or 0) / total
