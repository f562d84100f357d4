"""Share of the bytes of the arrays the last compute pinned under the mesh
whose ``PartitionSpec`` came out empty (``replicated_bytes``, every chip
holding the whole array) over all the bytes it pinned (``sharded_bytes`` +
``replicated_bytes``): 0 where the chunk grid divided every array. A program
without the counters, or a compute that pinned nothing, gives nothing."""

METRICS = [
    {"name": "replicated_share.gen", "unit": "%", "better": "lower",
     "source": "program_counter", "layer": "mesh placement", "moves": "compute_s"},
]


def read(traced):
    replicated = traced.stats.get("replicated_bytes")
    sharded = traced.stats.get("sharded_bytes")
    if replicated is None or sharded is None or not replicated + sharded:
        return None
    return 100.0 * replicated / (replicated + sharded)
