"""Share of the bytes the last compute sent through a staging buffer, on the
way in (``h2d_stream_bytes``) or out (``flush_stream_bytes``), that passed
through a buffer the compute found already allocated when it leased the
process's pair (``stage_reused_bytes``): 100 from a process's second compute
on, where the pair outlives the executor, 0 where every compute makes its own
two buffers and fills their fresh pages under its first two chunk reads. A
program without the counter (the parent of the PR that brought the pool) gives
nothing, as does a compute that staged nothing."""

METRICS = [
    {"name": "stage_reuse_share", "unit": "%", "better": "higher", "source": "program_counter",
     "layer": "Zarr to HBM preload", "moves": "zarr_compute_s"},
]


def read(traced):
    reused = traced.stats.get("stage_reused_bytes")
    staged = traced.stats.get("h2d_stream_bytes", 0) + traced.stats.get("flush_stream_bytes", 0)
    if reused is None or not staged:
        return None
    return 100.0 * reused / staged
