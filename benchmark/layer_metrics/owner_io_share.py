"""Share of the bytes the last compute moved between the host and a mesh that
went between the host and exactly the chip that owns the chunk
(``mesh_owner_bytes``), of those and the bytes of values that touched more
than one chip on the way (``mesh_gathered_bytes``: a shard assembled on the
host by the callback, a chunk sliced out of the sharded value by a program
of the whole mesh). 100 where every chunk of every source streams to its
owner and every chunk of the result is sliced and fetched there. A program
without the counters (the parent of the PR that brought them) gives nothing,
as does a compute without a mesh, whose two counters read 0."""

METRICS = [
    {"name": "owner_io_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "mesh placement", "moves": "zarr_compute_s"},
]


def read(traced):
    owner = traced.stats.get("mesh_owner_bytes", 0)
    moved = owner + traced.stats.get("mesh_gathered_bytes", 0)
    if not moved:
        return None
    return 100.0 * owner / moved
