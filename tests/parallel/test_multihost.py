"""Multi-host IO-sharding seams on the virtual CPU mesh with simulated
hosts (docs/multihost.md; real DCN needs >1 process — the partitioning
logic is host-count agnostic and fully testable here)."""

import itertools
import math

import numpy as np
import pytest

from cubed_tpu.parallel.mesh import make_mesh, sharding_for_chunks
from cubed_tpu.parallel.multihost import (
    dcn_mesh,
    host_chunk_assignment,
)


def _cpu_devices():
    import jax

    try:
        return jax.devices("cpu")
    except RuntimeError:
        return []


needs_8 = pytest.mark.skipif(
    len(_cpu_devices()) < 8, reason="needs 8 virtual CPU devices"
)


def virtual_host(device):
    """Simulate 2 hosts of 4 devices on the virtual CPU mesh."""
    return device.id // 4


@needs_8
def test_host_assignment_partitions_chunk_grid():
    devs = _cpu_devices()[:8]
    mesh = make_mesh(shape=(8,), axis_names=("data",), devices=devs)
    shape, chunks = (16, 24), (2, 6)
    chunkset = ((2,) * 8, (6,) * 4)
    sharding = sharding_for_chunks(mesh, chunkset, shape)
    assignment = host_chunk_assignment(
        sharding, shape, chunks, host_of_device=virtual_host
    )
    # exactly two hosts, all 32 chunks covered exactly once
    all_chunks = sorted(itertools.chain.from_iterable(assignment.values()))
    assert all_chunks == sorted(
        itertools.product(range(8), range(4))
    )
    assert set(assignment) == {0, 1}
    # the sharded dim is dim 0 (8 blocks over 8 devices): host 0 gets the
    # first half of the grid rows, host 1 the second
    assert all(c[0] < 4 for c in assignment[0])
    assert all(c[0] >= 4 for c in assignment[1])


@needs_8
def test_host_assignment_balanced_on_2d_mesh():
    devs = _cpu_devices()[:8]
    mesh = make_mesh(shape=(2, 4), axis_names=("dcn", "ici"), devices=devs)
    shape, chunks = (8, 16), (2, 2)
    chunkset = ((2,) * 4, (2,) * 8)
    sharding = sharding_for_chunks(mesh, chunkset, shape)
    assignment = host_chunk_assignment(
        sharding, shape, chunks, host_of_device=virtual_host
    )
    total = sum(len(v) for v in assignment.values())
    assert total == 4 * 8
    # both virtual hosts own work
    assert len(assignment) == 2
    sizes = sorted(len(v) for v in assignment.values())
    assert sizes == [16, 16]


@needs_8
def test_chunk_within_owner_shard():
    from cubed_tpu.parallel.multihost import chunk_within_owner_shard

    devs = _cpu_devices()[:8]
    mesh = make_mesh(shape=(8,), axis_names=("data",), devices=devs)
    # aligned: 16 rows / 8 shards of 2 rows; chunks of 2 rows sit in shards
    shape = (16, 4)
    aligned = sharding_for_chunks(mesh, ((2,) * 8, (4,)), shape)
    chunkset = ((2,) * 8, (4,))
    assert all(
        chunk_within_owner_shard(aligned, shape, chunkset, (i, 0))
        for i in range(8)
    )
    # misaligned: chunks of 4 rows straddle 2-row shards? no — larger chunks
    # over smaller shards DO straddle: chunk rows [0:4) spans shards 0 and 1
    big_chunkset = ((4,) * 4, (4,))
    assert not chunk_within_owner_shard(aligned, shape, big_chunkset, (0, 0))


@needs_8
def test_host_assignment_replicated_goes_to_one_host():
    devs = _cpu_devices()[:8]
    mesh = make_mesh(shape=(8,), axis_names=("data",), devices=devs)
    # prime dims: nothing shards -> fully replicated -> host of first device
    shape, chunks = (7, 11), (7, 11)
    sharding = sharding_for_chunks(mesh, ((7,), (11,)), shape)
    assignment = host_chunk_assignment(
        sharding, shape, chunks, host_of_device=virtual_host
    )
    assert sum(len(v) for v in assignment.values()) == 1


@needs_8
def test_dcn_mesh_shape_and_order():
    devs = _cpu_devices()[:8]
    # single real process: all devices report process_index 0 -> 1 host
    mesh = dcn_mesh(ici_shape=(8,), devices=devs)
    assert mesh.devices.shape == (1, 8)
    assert mesh.axis_names == ("dcn", "ici0")
    with pytest.raises(ValueError):
        dcn_mesh(ici_shape=(3,), devices=devs)


@needs_8
def test_dcn_mesh_simulated_two_hosts():
    devs = _cpu_devices()[:8]
    mesh = dcn_mesh(ici_shape=(2, 2), devices=devs, host_of_device=virtual_host)
    assert mesh.devices.shape == (2, 2, 2)
    assert mesh.axis_names == ("dcn", "ici0", "ici1")
    # leading axis is exactly the (virtual) host axis, host-major order
    for h in range(2):
        assert all(virtual_host(d) == h for d in mesh.devices[h].flat)


@needs_8
def test_sharded_zarr_roundtrip_uses_per_host_io_seams(tmp_path_factory):
    """End-to-end through the REAL seams: zarr source ingested via
    make_array_from_callback (per-shard reads), computed under the mesh,
    flushed via the per-host chunk assignment, read back exactly."""
    import tempfile

    import cubed_tpu as ct
    import cubed_tpu.array_api as xp
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    devs = _cpu_devices()[:8]
    mesh = make_mesh(shape=(8,), axis_names=("data",), devices=devs)
    tmp = tempfile.mkdtemp()
    spec = ct.Spec(work_dir=tmp, allowed_mem="1GB")

    an = np.arange(16.0 * 24).reshape(16, 24)
    src = f"{tmp}/src.zarr"
    a0 = ct.from_array(an, chunks=(2, 6), spec=spec)
    ct.to_zarr(a0, src)  # default executor writes the source

    a = ct.from_zarr(src, spec=spec)  # concrete zarr input -> preload path
    out = f"{tmp}/out.zarr"
    ex = JaxExecutor(mesh=mesh)
    ct.to_zarr(xp.add(xp.multiply(a, 2.0), 1.0), out, executor=ex)

    back = np.asarray(ct.from_zarr(out, spec=spec).compute())
    np.testing.assert_allclose(back, an * 2.0 + 1.0)


@needs_8
def test_sharded_compute_matches_io_assignment():
    """End-to-end: a sharded compute's result is correct AND the assignment
    the flush seam would use covers the output grid exactly once."""
    import cubed_tpu as ct
    import cubed_tpu.array_api as xp
    import tempfile
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    devs = _cpu_devices()[:8]
    mesh = make_mesh(shape=(8,), axis_names=("data",), devices=devs)
    spec = ct.Spec(work_dir=tempfile.mkdtemp(), allowed_mem="1GB")
    an = np.arange(16.0 * 24).reshape(16, 24)
    a = ct.from_array(an, chunks=(2, 6), spec=spec)
    ex = JaxExecutor(mesh=mesh)
    out = xp.add(a, 1.0).compute(executor=ex)
    np.testing.assert_allclose(np.asarray(out), an + 1.0)

    sharding = ex._sharding_for((16, 24), ((2,) * 8, (6,) * 4))
    assignment = host_chunk_assignment(
        sharding, (16, 24), (2, 6), host_of_device=virtual_host
    )
    covered = sorted(itertools.chain.from_iterable(assignment.values()))
    assert covered == sorted(itertools.product(range(8), range(4)))


def test_two_process_jax_distributed_smoke(tmp_path):
    """REAL multi-controller SPMD over a process boundary: 2 processes x 4
    virtual CPU devices call jax.distributed.initialize on localhost, run
    the SAME framework plan under the mesh-sharded executor, and the
    instrumented Zarr store proves the per-host IO seams: each element of
    the source read exactly once and each element of the output written
    exactly once, split across the two processes (docs/multihost.md)."""
    import os
    import socket
    import subprocess
    import sys

    import cubed_tpu as ct

    work = str(tmp_path)
    shape = (16, 24)
    an = np.arange(float(np.prod(shape))).reshape(shape)
    spec = ct.Spec(work_dir=work, allowed_mem="1GB")
    a0 = ct.from_array(an, chunks=(2, 6), spec=spec)
    ct.to_zarr(a0, f"{work}/src.zarr")

    # each worker sets its own virtual device count
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")

    def spawn_and_wait():
        # ephemeral-port pick races the coordinator's rebind; retry with a
        # fresh port if a worker loses the race
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs = [
            subprocess.Popen(
                [sys.executable, worker, str(pid), f"localhost:{port}", work],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
            for pid in range(2)
        ]
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=240)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            outs.append(out)
        return [(p.returncode, out) for p, out in zip(procs, outs)]

    for attempt in range(3):
        results = spawn_and_wait()
        if all(rc == 0 for rc, _ in results):
            break
        if not any("bind" in out.lower() for _, out in results):
            break
    for rc, out in results:
        assert rc == 0, out[-4000:]

    # exactly-once IO, partitioned across the two processes
    reads = [np.load(f"{work}/read_mask_{pid}.npy") for pid in range(2)]
    writes = [np.load(f"{work}/write_mask_{pid}.npy") for pid in range(2)]
    np.testing.assert_array_equal(reads[0] + reads[1], np.ones(shape, np.int32))
    np.testing.assert_array_equal(writes[0] + writes[1], np.ones(shape, np.int32))
    # both processes did a real share of the IO (no one-host degeneracy)
    for m in (*reads, *writes):
        assert 0 < m.sum() < np.prod(shape), m.sum()

    # and the output is the correct computation
    back = np.asarray(ct.from_zarr(f"{work}/out.zarr", spec=spec).compute())
    np.testing.assert_allclose(back, an * 2.0 + 1.0)
