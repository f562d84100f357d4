"""Upstream's add, Zarr to Zarr, under a mesh: storage under a sharding.

The deployment ``zarr-add-mesh4`` at a small size on four of the suite's
virtual CPU devices: ``to_zarr(add(from_zarr, from_zarr))`` over a 4 x 4 grid
of chunks under ``JaxExecutor(mesh=...)``, where ``sharding_for_chunks`` gives
every chip one chunk-row. Every chunk of a source is read into a leased
staging buffer and put on the chip that owns it (``_stream_to_device``), a
lane a chip: that chip's chunks in grid order, on a thread of the lane's own
through a staging pair of its own (without a mesh the one lane runs on the
calling thread). Every chunk of the target is sliced, split and fetched on its owner
(``_flush_chunks``, ``_chunk_of``); ``mesh_owner_bytes`` counts those bytes
and ``mesh_gathered_bytes`` the ones that touched more than one chip. The
target is read back with numpy alone. ``mean(a + b, axis=0)`` over the same
sources (the cell ``zarr-add-mesh4.colmean``) reduces along the axis the mesh
divides. A layout with no owner for a chunk keeps the route it had and is
counted."""

from __future__ import annotations

import json
import os
import sys
import threading
import zlib

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.runtime.executors.jax as jx
from cubed_tpu.parallel.mesh import chunk_owners, make_mesh, sharding_for_chunks
from cubed_tpu.runtime import faults
from cubed_tpu.runtime.cancellation import CancellationToken, ComputeCancelledError
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.runtime.executors.python import PythonDagExecutor
from cubed_tpu.storage.store import _LocalIO, open_zarr_array
from tests.test_preload_stream import EDGE_BITS
from tests.utils import leased_staging

RNG = np.random.default_rng(39)
SHAPE, CHUNKS = (32, 24), (8, 6)  # a 4 x 4 grid


class _Capture:
    stats = None

    def on_compute_end(self, event):
        self.stats = event.executor_stats


@pytest.fixture(autouse=True)
def _empty_pool():
    jx.release_staging_buffers()
    yield
    jx.release_staging_buffers()


def _mesh():
    import jax

    return make_mesh(devices=jax.devices()[:4])


def _stored(path, host, chunks=CHUNKS):
    z = open_zarr_array(str(path), "w", shape=host.shape, dtype=host.dtype, chunks=chunks)
    z[...] = host
    return z


def _read_with_numpy(path: str) -> np.ndarray:
    """An uncompressed C-order Zarr v2 store, every chunk's CRC-32 checked
    against the store's manifest on the way (numpy, json and zlib alone)."""
    with open(os.path.join(path, ".zarray")) as f:
        meta = json.load(f)
    shape, chunks, dtype = tuple(meta["shape"]), tuple(meta["chunks"]), np.dtype(meta["dtype"])
    manifest = {}
    for name in sorted(os.listdir(path)):
        if name.startswith(".manifest-") and name.endswith(".json"):
            with open(os.path.join(path, name)) as f:
                for doc in map(json.loads, filter(str.strip, f)):
                    if doc["k"] not in manifest or doc.get("t", 0) >= manifest[doc["k"]].get("t", 0):
                        manifest[doc["k"]] = doc
    out = np.empty(shape, dtype)
    grid = [range(-(-s // c)) for s, c in zip(shape, chunks)]
    for idx in np.ndindex(*map(len, grid)):
        key = meta.get("dimension_separator", ".").join(map(str, idx))
        with open(os.path.join(path, key), "rb") as f:
            raw = f.read()
        assert len(raw) == int(np.prod(chunks)) * dtype.itemsize
        entry = manifest[key]
        assert (entry["c"], entry["n"]) == (zlib.crc32(raw) & 0xFFFFFFFF, len(raw)), key
        sel = tuple(slice(i * c, min((i + 1) * c, s)) for i, c, s in zip(idx, chunks, shape))
        block = np.frombuffer(raw, dtype).reshape(chunks)
        out[sel] = block[tuple(slice(0, s.stop - s.start) for s in sel)]
    return out


def _deployment(tmp_path, dtype, plan, monkeypatch):
    """(build, what numpy makes of it) of one of the three plans."""
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    a, b = (RNG.standard_normal(SHAPE).astype(dtype) for _ in "ab")
    pa, pb = (_stored(tmp_path / f"{k}.zarr", h).store for k, h in (("a", a), ("b", b)))
    if plan == "add":
        return (lambda: xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec))), a + b
    # a plan that only moves values carries float64 as uint64 bits where the
    # device's float64 is not one; the CPU's is, so the test says it is not
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    a.view(np.uint64)[0, :3] = [0x7FF8000000000123, 0x8000000000000000, 1]
    _stored(tmp_path / "a.zarr", a)
    return (lambda: ct.from_zarr(pa, spec=spec)), a


@pytest.mark.parametrize(
    "dtype, plan", [(np.float64, "add"), (np.float32, "add"), (np.float64, "move")],
    ids=["float64", "float32", "float64-as-bits"],
)
def test_the_deployment_moves_every_chunk_between_the_host_and_its_owner(
    tmp_path, monkeypatch, dtype, plan
):
    build, expected = _deployment(tmp_path, dtype, plan, monkeypatch)
    targets = {}
    for name, mesh in (("mesh", _mesh()), ("plain", None)):
        cap, target = _Capture(), str(tmp_path / f"{name}.zarr")
        executor = JaxExecutor(mesh=mesh)
        ct.to_zarr(build(), target, executor=executor, callbacks=[cap])
        targets[name] = (_read_with_numpy(target), cap.stats)
        assert executor._staging is None
    got, stats = targets["mesh"]
    # bit for bit numpy's on the CPU, and what one device gives
    assert got.dtype == expected.dtype and got.tobytes() == expected.tobytes()
    assert targets["plain"][0].tobytes() == got.tobytes()
    sources = 2 if plan == "add" else 1
    assert stats["h2d_bytes"] == sources * expected.nbytes and stats["d2h_bytes"] == expected.nbytes
    assert stats["mesh_gathered_bytes"] == 0
    assert stats["mesh_owner_bytes"] == stats["h2d_bytes"] + stats["d2h_bytes"]
    assert stats["h2d_stream_bytes"] == stats["h2d_bytes"]
    assert not stats.get("h2d_stream_declined")
    assert stats["mesh_devices"] == 4 and stats["host_syncs"] == 16
    assert stats.get("f64_as_bits", 0) == (1 if plan == "move" else 0)
    assert stats["h2d_bits_bytes"] == (expected.nbytes if plan == "move" else 0)
    # without a mesh the counters are there, and 0
    plain = targets["plain"][1]
    assert (plain["mesh_owner_bytes"], plain["mesh_gathered_bytes"]) == (0, 0)
    assert type(plain["mesh_owner_bytes"]) is int and plain["mesh_devices"] == 0
    assert plain["h2d_stream_bytes"] == plain["h2d_bytes"] == stats["h2d_bytes"]


@pytest.mark.parametrize("dtype", [np.float64, np.int32], ids=["float64", "int32"])
def test_each_chips_shard_is_its_chunk_row(tmp_path, dtype):
    host = (RNG.standard_normal(SHAPE) * 1e3).astype(dtype)
    z = _stored(tmp_path / "a.zarr", host)
    executor = JaxExecutor(mesh=_mesh())
    with leased_staging(executor) as staging:
        value = executor._device_put(z, tuple(z.shape), z.chunkset())
        assert [stage.buffer.nbytes for stage in staging] == [z._chunk_nbytes()] * 2
    assert value.sharding == executor._sharding_for(tuple(z.shape), z.chunkset())
    shards = sorted(value.addressable_shards, key=lambda s: s.index[0].start)
    assert len({shard.device for shard in shards}) == 4
    for row, shard in enumerate(shards):
        assert shard.data.shape == (8, 24)
        assert np.asarray(shard.data).tobytes() == host[8 * row : 8 * (row + 1)].tobytes()
    assert np.asarray(value).tobytes() == host.tobytes()
    assert executor.stats["mesh_owner_bytes"] == executor.stats["h2d_stream_bytes"] == host.nbytes
    assert executor.stats["mesh_gathered_bytes"] == 0


@pytest.mark.parametrize("carry_bits", [False, True], ids=["as_numbers", "as_bits"])
def test_the_streamed_value_is_the_callbacks_and_numpys_bit_for_bit(
    tmp_path, monkeypatch, carry_bits
):
    host = RNG.standard_normal(SHAPE) * 1e3
    # edge values in the first and in the last chunk-row, so on two chips
    host.reshape(-1)[: EDGE_BITS.size] = EDGE_BITS.view(np.float64)
    host.reshape(-1)[-EDGE_BITS.size :] = EDGE_BITS.view(np.float64)[::-1]
    z = _stored(tmp_path / "a.zarr", host)
    if carry_bits:
        # the CPU's float64 round trips; the test says it does not, so that
        # the room is asked for twice as on the chip
        monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    values = {}
    for route in ("stream", "callback"):
        executor = JaxExecutor(mesh=_mesh())
        executor._carry_bits = carry_bits
        if route == "callback":
            monkeypatch.setattr(JaxExecutor, "_streams", lambda self, stored, sharding: None)
        with leased_staging(executor):
            values[route] = executor._device_put(z, tuple(z.shape), z.chunkset())
        streamed = executor.stats["h2d_stream_bytes"]
        assert streamed == (host.nbytes if route == "stream" else 0)
        owner, gathered = executor.stats["mesh_owner_bytes"], executor.stats["mesh_gathered_bytes"]
        assert (owner, gathered) == ((host.nbytes, 0) if route == "stream" else (0, host.nbytes))
        assert executor.stats["h2d_bits_bytes"] == (host.nbytes if carry_bits else 0)
    stream, callback = values["stream"], values["callback"]
    assert stream.dtype == callback.dtype == (np.uint64 if carry_bits else np.float64)
    assert stream.sharding == callback.sharding and stream.shape == callback.shape
    for ours, theirs in zip(stream.addressable_shards, callback.addressable_shards):
        assert (ours.device, ours.index) == (theirs.device, theirs.index)
        assert np.asarray(ours.data).tobytes() == np.asarray(theirs.data).tobytes()
    got = np.asarray(stream)
    assert got.tobytes() == host.tobytes()
    if not carry_bits:
        # as numbers too: NaN where numpy has NaN, the same sign on each zero
        np.testing.assert_array_equal(got, host)
        assert np.array_equal(np.signbit(got), np.signbit(host))


def test_both_staging_buffers_are_taken_and_the_next_source_finds_them(tmp_path):
    """Of every lane's pair: a compute under a mesh of four leases four, the
    process keeps four, and the next compute's lanes find each their own."""
    z = _stored(tmp_path / "a.zarr", RNG.standard_normal(SHAPE))
    first, second, plain = JaxExecutor(mesh=_mesh()), JaxExecutor(mesh=_mesh()), JaxExecutor()
    with leased_staging(first) as staging:
        first._device_put(z, tuple(z.shape), z.chunkset())
        pairs = list(first._leased)
        # the second source of a compute streams through the pairs of the first
        first._device_put(z, tuple(z.shape), z.chunkset())
        assert first._leased == pairs
    assert len(pairs) == 4 and pairs[0] is staging
    buffers = [stage.buffer for pair in pairs for stage in pair]
    assert [buffer.nbytes for buffer in buffers] == [z._chunk_nbytes()] * 8
    assert len({buffer.ctypes.data for buffer in buffers}) == 8
    # the first source had to make them, the second found them
    assert first.stats["stage_reused_bytes"] == 0
    assert first.stats["h2d_stream_bytes"] == first.stats["h2d_lane_bytes"] == 2 * z.nbytes
    assert sorted(map(id, jx._STAGING_POOL)) == sorted(map(id, pairs))
    with leased_staging(second) as staging:
        second._device_put(z, tuple(z.shape), z.chunkset())
        # lane for lane the pair it had, and no fresh buffer
        assert staging is pairs[0] and jx._STAGING_POOL == []
        assert all(ours is theirs for ours, theirs in zip(second._leased, pairs))
        assert [stage.buffer for pair in second._leased for stage in pair] == buffers
    assert second.stats["stage_reused_bytes"] == second.stats["h2d_stream_bytes"] == z.nbytes
    assert second.stats["h2d_lane_bytes"] == z.nbytes
    assert type(second.stats["stage_wait_us"]) is int and "preload_page_faults" not in second.stats
    # a compute of one lane takes exactly one pair, the first, and returns it
    with leased_staging(plain) as staging:
        assert staging is pairs[0] and len(jx._STAGING_POOL) == 3
        plain._device_put(z, tuple(z.shape), z.chunkset())
        assert plain._leased == [pairs[0]] and len(jx._STAGING_POOL) == 3
    assert plain.stats["stage_reused_bytes"] == plain.stats["h2d_stream_bytes"] == z.nbytes
    assert plain.stats["h2d_lane_bytes"] == 0
    assert sorted(map(id, jx._STAGING_POOL)) == sorted(map(id, pairs))
    # the process lets go of all of them at once, and the width starts over
    jx.release_staging_buffers()
    assert jx._STAGING_POOL == []
    with leased_staging(JaxExecutor()) as mine, leased_staging(JaxExecutor()) as yours:
        assert mine is not yours
    assert jx._STAGING_POOL == [yours]  # the first to end; the pool is one wide again


def test_a_pair_whose_update_raised_is_not_given_back(tmp_path):
    class _Lost:
        def block_until_ready(self):
            raise RuntimeError("the device is gone, says the test")

    z = _stored(tmp_path / "a.zarr", RNG.standard_normal(SHAPE))
    executor = JaxExecutor(mesh=_mesh())
    with pytest.raises(RuntimeError, match="the device is gone"):
        with leased_staging(executor):
            executor._device_put(z, tuple(z.shape), z.chunkset())
            pairs = list(executor._leased)
            pairs[2][1].release()
            pairs[2][1].busy = _Lost()
    assert executor._staging is None and executor._leased is None
    # the three sound ones came back with nothing of the device left on them
    assert sorted(map(id, jx._STAGING_POOL)) == sorted(id(p) for p in pairs if p is not pairs[2])
    assert all(stage.busy is None for pair in jx._STAGING_POOL for stage in pair)


def test_without_a_mesh_the_loop_issues_the_device_operations_it_issued_before(
    tmp_path, monkeypatch
):
    import jax

    host = RNG.standard_normal(SHAPE)
    z = _stored(tmp_path / "a.zarr", host)
    puts, zeros, writes = [], [], []
    real_put, real_zeros, real_writer = jax.device_put, jax.numpy.zeros, jx._chunk_writer

    def writer():
        write = real_writer()

        def spy(whole, piece, start, extent):
            writes.append((whole.shape, piece.shape, tuple(int(s) for s in start), extent))
            return write(whole, piece, start, extent)

        return spy

    monkeypatch.setattr(
        jax, "device_put", lambda x, device=None, **kw: (puts.append(device), real_put(x, device, **kw))[1])
    monkeypatch.setattr(
        jax.numpy, "zeros",
        lambda shape, dtype=None, **kw: (zeros.append((shape, kw)), real_zeros(shape, dtype, **kw))[1])
    monkeypatch.setattr(jx, "_chunk_writer", writer)
    executor = JaxExecutor()
    with leased_staging(executor):
        value = executor._device_put(z, tuple(z.shape), z.chunkset())
    # one put a chunk to the default device, one array of the whole shape
    # made once, one update a chunk at its place in it, in grid order
    assert puts == [None] * 16
    assert zeros == [(SHAPE, {"device": None})]
    assert writes == [(SHAPE, CHUNKS, (8 * i, 6 * j), CHUNKS) for i in range(4) for j in range(4)]
    assert len(value.sharding.device_set) == 1 and not value.committed
    assert np.asarray(value).tobytes() == host.tobytes()
    assert executor.stats["mesh_owner_bytes"] == executor.stats["mesh_gathered_bytes"] == 0
    assert executor.stats["h2d_stream_bytes"] == host.nbytes


@pytest.mark.parametrize("dtype", [np.float64, np.float32], ids=["float64", "float32"])
def test_the_reduction_along_the_sharded_axis_agrees_with_the_python_executor(tmp_path, dtype):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    a, b = (RNG.random(SHAPE).astype(dtype) for _ in "ab")
    pa, pb = (_stored(tmp_path / f"{k}.zarr", h).store for k, h in (("a", a), ("b", b)))

    def build():
        return xp.mean(xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)), axis=0)

    cap = _Capture()
    got = np.asarray(build().compute(executor=JaxExecutor(mesh=_mesh()), callbacks=[cap]))
    plain = np.asarray(build().compute(executor=PythonDagExecutor()))
    assert got.shape == (24,) and got.dtype == plain.dtype == dtype
    eps = np.finfo(dtype).eps
    np.testing.assert_allclose(got, plain, rtol=SHAPE[0] * eps, atol=0)
    np.testing.assert_allclose(got, (a + b).mean(axis=0), rtol=SHAPE[0] * eps, atol=0)
    stats = cap.stats
    # both sources streamed to their owners; the row that comes back leaves
    # chunk by chunk, each from one chip
    assert stats["h2d_stream_bytes"] == stats["h2d_bytes"] == 2 * a.nbytes
    # (the mean of float32 leaves as the float64 it was summed in)
    assert stats["d2h_bytes"] == 24 * 8 and stats["host_syncs"] == 4
    assert stats["mesh_gathered_bytes"] == 0
    assert stats["mesh_owner_bytes"] == stats["h2d_bytes"] + stats["d2h_bytes"]
    assert stats["segments_traced"] + stats.get("segment_struct_hits", 0) >= 1
    assert not stats.get("eager_fallbacks") and not stats.get("trace_failures")
    # the partitioned program crosses the chips with partial sums only
    assert stats["segment_collectives"] >= 1
    assert stats["segment_collectives"] == sum(
        stats[k] for k in ("segment_all_to_all", "segment_all_reduce",
                           "segment_all_gather", "segment_collective_permute"))


def test_the_stored_add_agrees_with_the_python_executor(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    a, b = RNG.standard_normal(SHAPE), RNG.standard_normal(SHAPE)
    pa, pb = (_stored(tmp_path / f"{k}.zarr", h).store for k, h in (("a", a), ("b", b)))
    read = {}
    for name, executor in (("mesh", JaxExecutor(mesh=_mesh())), ("python", PythonDagExecutor())):
        target = str(tmp_path / f"{name}.zarr")
        ct.to_zarr(xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)), target,
                   executor=executor)
        read[name] = _read_with_numpy(target)
    assert read["mesh"].tobytes() == read["python"].tobytes() == (a + b).tobytes()


def test_consecutive_chunks_go_to_different_chips(tmp_path, monkeypatch):
    """They did while one thread took the chips in turn. Now a chip's chunks
    are a lane: in grid order, on a thread of that chip's own, through a
    pair no other lane touches."""
    import jax

    z = _stored(tmp_path / "a.zarr", RNG.standard_normal(SHAPE))
    reads, puts, zeros = [], [], []
    real, real_put, real_zeros = _LocalIO.readinto, jax.device_put, jax.numpy.zeros

    def readinto(self, name, buffer):
        address = np.asarray(buffer).__array_interface__["data"][0]
        reads.append((threading.current_thread().name, os.path.basename(name), address))
        return real(self, name, buffer)

    def device_put(x, device=None, **kw):
        puts.append((threading.current_thread().name, device))
        return real_put(x, device, **kw)

    def make_zeros(shape, dtype=None, **kw):
        zeros.append((threading.current_thread().name, kw["device"], jax.config.jax_default_device))
        return real_zeros(shape, dtype, **kw)

    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    monkeypatch.setattr(jax, "device_put", device_put)
    monkeypatch.setattr(jax.numpy, "zeros", make_zeros)
    executor = JaxExecutor(mesh=_mesh())
    before = {t.ident for t in threading.enumerate()}
    with leased_staging(executor):
        value = executor._device_put(z, tuple(z.shape), z.chunkset())
        pairs = list(executor._leased)
    owners = chunk_owners(value.sharding, SHAPE, z.chunkset())
    lanes = [f"cubed-tpu-preload-{n}" for n in range(4)]
    assert {thread for thread, _, _ in reads} == set(lanes) == {thread for thread, _ in puts}
    chips, addresses = [], []
    for n, lane in enumerate(lanes):
        keys = [key for thread, key, _ in reads if thread == lane]
        (row,) = {int(key.split(".")[0]) for key in keys}
        assert keys == [f"{row}.{j}" for j in range(4)]  # grid order
        # put on the chip that owns the row, and on no other
        (chip,) = {device for thread, device in puts if thread == lane}
        assert chip is owners[(row, 0)][0]
        chips.append(chip)
        # its shard made once, there, with that chip the lane's default device:
        # ``zeros`` fills on the default device and copies to the one named
        assert [made[1:] for made in zeros if made[0] == lane] == [(chip, chip)]
        # the lane's pair, buffer for buffer, taking turns
        mine = [address for thread, _, address in reads if thread == lane]
        assert mine == [stage.buffer.ctypes.data for stage in pairs[n]] * 2
        addresses += mine[:2]
    assert len(set(chips)) == 4 and len(set(addresses)) == 8
    assert {shard.device for shard in value.addressable_shards} == set(chips)
    # joined before the shards were: no thread outlives the call
    assert not [t for t in threading.enumerate()
                if t.ident not in before and t.name.startswith("cubed-tpu")]
    # without a mesh: one lane, on the calling thread, in grid order, as before
    reads.clear(), puts.clear()
    plain = JaxExecutor()
    with leased_staging(plain):
        plain._device_put(z, tuple(z.shape), z.chunkset())
        assert len(plain._leased) == 1
    me = threading.current_thread().name
    assert [(thread, key) for thread, key, _ in reads] == [
        (me, f"{i}.{j}") for i in range(4) for j in range(4)]
    assert puts == [(me, None)] * 16 and zeros[4:] == [(me, None, None)]
    assert not [t for t in threading.enumerate()
                if t.ident not in before and t.name.startswith("cubed-tpu")]


def _grid(devices):
    """(shape, chunks, mesh): a chunk-row a chip on 4 and on 8 devices."""
    import jax

    shape, chunks = (SHAPE, CHUNKS) if devices == 4 else ((64, 12), (8, 6))
    return shape, chunks, make_mesh(devices=jax.devices()[:devices])


@pytest.mark.parametrize("carry_bits", [False, True], ids=["as_numbers", "as_bits"])
@pytest.mark.parametrize("devices", [4, 8])
def test_the_lanes_counters_add_up_to_the_one_thread_loops(
    tmp_path, monkeypatch, devices, carry_bits
):
    """Every lane counts into a counter of its own, added at the join: with
    the interpreter switching threads as often as it can, nothing is lost."""
    shape, chunks, mesh = _grid(devices)
    host = RNG.standard_normal(shape)
    z = _stored(tmp_path / "a.zarr", host, chunks)
    if carry_bits:
        monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    counted = {}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for name, executor in (("loop", JaxExecutor()), ("lanes", JaxExecutor(mesh=mesh))):
            jx.release_staging_buffers()  # each as a process's first computes
            executor._carry_bits = carry_bits
            for _ in range(3):  # the first makes the buffers, the others find them
                with leased_staging(executor):
                    value = executor._device_put(z, tuple(z.shape), z.chunkset())
                assert np.asarray(value).tobytes() == host.tobytes()
            counted[name] = executor.stats
    finally:
        sys.setswitchinterval(interval)
    lanes, loop = counted["lanes"], counted["loop"]
    for key in ("h2d_bytes", "h2d_stream_bytes", "h2d_bits_bytes", "stage_reused_bytes", "f64_as_bits"):
        assert lanes[key] == loop[key], key
    assert lanes["h2d_stream_bytes"] == 3 * host.nbytes and lanes["stage_reused_bytes"] == 2 * host.nbytes
    assert lanes["h2d_bits_bytes"] == (3 * host.nbytes if carry_bits else 0)
    # all of it on a lane thread of its owner's under a mesh, none without one
    assert lanes["h2d_lane_bytes"] == lanes["mesh_owner_bytes"] == lanes["h2d_stream_bytes"]
    assert loop["h2d_lane_bytes"] == loop["mesh_owner_bytes"] == 0
    assert lanes["mesh_gathered_bytes"] == 0
    assert all(type(lanes[key]) is int for key in ("h2d_lane_bytes", "stage_wait_us", "h2d_bytes"))
    assert lanes["stage_wait_us"] >= 0
    assert len(jx._STAGING_POOL) == devices


def test_with_float32_compute_a_lane_puts_what_the_calling_thread_would(tmp_path, monkeypatch):
    """``execute_dag`` turns x64 off for its own thread alone; a lane's
    thread takes the setting over, and with it the program the writer
    compiled for the calling thread's."""
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    a, b = RNG.standard_normal(SHAPE), RNG.standard_normal(SHAPE)
    pa, pb = (_stored(tmp_path / f"{k}.zarr", h).store for k, h in (("a", a), ("b", b)))
    written, real_writer = [], jx._chunk_writer

    def writer():
        write = real_writer()

        def spy(whole, piece, start, extent):
            written.append((threading.current_thread().name, str(whole.dtype), str(piece.dtype)))
            return write(whole, piece, start, extent)

        return spy

    monkeypatch.setattr(jx, "_chunk_writer", writer)
    read = {}
    for name, mesh in (("mesh", _mesh()), ("plain", None)):
        cap, target = _Capture(), str(tmp_path / f"{name}.zarr")
        ct.to_zarr(xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)), target,
                   executor=JaxExecutor(mesh=mesh, compute_dtype="float32"), callbacks=[cap])
        read[name] = (_read_with_numpy(target), cap.stats, list(written))
        written.clear()
    (on_lanes, lanes_stats, lanes), (inline, plain_stats, loop) = read["mesh"], read["plain"]
    assert {thread for thread, _, _ in lanes} == {f"cubed-tpu-preload-{n}" for n in range(4)}
    assert {thread for thread, _, _ in loop} == {threading.current_thread().name}
    assert {row[1:] for row in lanes} == {("float32", "float32")}
    assert {row[1:] for row in loop} == {("float32", "float32")}
    assert on_lanes.tobytes() == inline.tobytes()
    expected = (a.astype(np.float32) + b.astype(np.float32)).astype(np.float64)
    assert on_lanes.tobytes() == expected.tobytes()
    assert lanes_stats["h2d_lane_bytes"] == lanes_stats["h2d_stream_bytes"] == plain_stats["h2d_stream_bytes"]
    assert plain_stats["h2d_lane_bytes"] == 0 and type(plain_stats["h2d_lane_bytes"]) is int


def test_a_chunk_leaves_from_its_owner_and_no_other_chip(tmp_path):
    import jax

    host = RNG.standard_normal(SHAPE)
    z = _stored(tmp_path / "a.zarr", host)
    executor = JaxExecutor(mesh=_mesh())
    with leased_staging(executor):
        value = executor._device_put(z, tuple(z.shape), z.chunkset())
    owners = chunk_owners(value.sharding, SHAPE, z.chunkset())
    for (i, j), (device, bounds) in owners.items():
        sel = (slice(8 * i, 8 * i + 8), slice(6 * j, 6 * j + 6))
        piece = jx._chunk_of(value, sel)
        assert piece.sharding.device_set == {device} and bounds == ((8 * i, 8 * i + 8), (0, 24))
        assert np.asarray(piece).tobytes() == np.asarray(value[sel]).tobytes() == host[sel].tobytes()
    # a selection across two chunk-rows has no owner: the value's own slice
    across = jx._chunk_of(value, (slice(4, 12), slice(0, 6)))
    assert np.asarray(across).tobytes() == host[4:12, :6].tobytes()
    # and a value on one device is sliced as it is
    single = jax.device_put(host)
    assert np.asarray(jx._chunk_of(single, (slice(0, 8), slice(0, 6)))).tobytes() == host[:8, :6].tobytes()


def _through_a_chunk(tmp_path):
    # a 3 x 3 grid over four chips: 24 rows divide by 4, no side of the grid does
    return _stored(tmp_path / "cut.zarr", RNG.standard_normal((24, 18)), (8, 6))


def _one_chunk(tmp_path):
    return _stored(tmp_path / "one.zarr", RNG.standard_normal((8, 8)), (8, 8))


def _record(tmp_path):
    host = np.zeros(SHAPE, dtype=[("x", np.float64), ("n", np.int32)])
    host["x"], host["n"] = RNG.standard_normal(SHAPE), RNG.integers(0, 99, SHAPE)
    return _stored(tmp_path / "rec.zarr", host)


@pytest.mark.parametrize("make, declined", [(_through_a_chunk, 1), (_one_chunk, 0), (_record, 0)],
                         ids=["through_a_chunk", "one_chunk", "record"])
def test_a_layout_with_no_owner_keeps_its_route_and_is_counted(tmp_path, make, declined):
    z = make(tmp_path)
    host = z[...]
    meshed, plain = JaxExecutor(mesh=_mesh()), JaxExecutor()
    values = []
    for executor in (meshed, plain):
        with leased_staging(executor):
            value = executor._device_put(z, tuple(z.shape), z.chunkset())
        if isinstance(value, dict):
            values.append(b"".join(np.asarray(value[k]).tobytes() for k in host.dtype.names))
        else:
            values.append(np.asarray(value).tobytes())
    assert values[0] == values[1]
    if not isinstance(value, dict):
        assert values[0] == host.tobytes()
    assert meshed.stats["h2d_stream_bytes"] == 0
    assert meshed.stats.get("h2d_stream_declined", 0) == declined
    assert meshed.stats["mesh_gathered_bytes"] == meshed.stats["h2d_bytes"] == host.nbytes
    assert meshed.stats["mesh_owner_bytes"] == 0
    assert plain.stats["mesh_gathered_bytes"] == plain.stats["mesh_owner_bytes"] == 0


def test_a_target_whose_chunks_cross_shards_is_gathered_and_has_the_same_bytes(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    a, b = RNG.standard_normal((24, 18)), RNG.standard_normal((24, 18))
    pa, pb = (_stored(tmp_path / f"{k}.zarr", h, (8, 6)).store for k, h in (("a", a), ("b", b)))
    out = {}
    for name, mesh in (("mesh", _mesh()), ("plain", None)):
        cap, target = _Capture(), str(tmp_path / f"{name}.zarr")
        ct.to_zarr(xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)), target,
                   executor=JaxExecutor(mesh=mesh), callbacks=[cap])
        out[name] = (_read_with_numpy(target), cap.stats)
    assert out["mesh"][0].tobytes() == out["plain"][0].tobytes() == (a + b).tobytes()
    stats = out["mesh"][1]
    assert stats["h2d_stream_declined"] == 2 and stats["h2d_stream_bytes"] == 0
    assert stats["mesh_gathered_bytes"] + stats["mesh_owner_bytes"] == stats["h2d_bytes"] + stats["d2h_bytes"]
    assert stats["mesh_gathered_bytes"] >= stats["h2d_bytes"] > 0


def test_the_room_asked_for_is_a_chips(tmp_path):
    z = _stored(tmp_path / "a.zarr", RNG.standard_normal(SHAPE))
    # a chip's shard and two chunks, on each of the four chips
    needed = 4 * (z.nbytes // 4 + 2 * z._chunk_nbytes())
    roomy = JaxExecutor(mesh=_mesh(), device_mem=needed)
    tight = JaxExecutor(mesh=_mesh(), device_mem=needed - 1)
    values = []
    for executor in (roomy, tight):
        with leased_staging(executor):
            values.append(np.asarray(executor._device_put(z, tuple(z.shape), z.chunkset())).tobytes())
    assert values[0] == values[1]
    assert roomy.stats["h2d_stream_bytes"] == z.nbytes and not roomy.stats.get("h2d_stream_declined")
    assert tight.stats["h2d_stream_bytes"] == 0 and tight.stats["h2d_stream_declined"] == 1
    assert tight.stats["mesh_gathered_bytes"] == z.nbytes


def test_chunk_owners_names_one_chip_a_chunk_or_none():
    mesh = JaxExecutor(mesh=_mesh())._placement_mesh()
    grid = ((8,) * 4, (6,) * 4)
    aligned = sharding_for_chunks(mesh, grid, SHAPE)
    owners = chunk_owners(aligned, SHAPE, grid)
    assert len(owners) == 16 and len({device for device, _ in owners.values()}) == 4
    assert all(bounds == ((8 * i, 8 * i + 8), (0, 24)) for (i, _), (_, bounds) in owners.items())
    # a shard boundary inside a chunk; a layout that holds every byte twice
    cut = ((8,) * 3, (6,) * 3)
    assert chunk_owners(sharding_for_chunks(mesh, cut, (24, 18)), (24, 18), cut) is None
    from jax.sharding import NamedSharding, PartitionSpec

    half = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    assert chunk_owners(half, SHAPE, grid) is None


@pytest.mark.parametrize("ending", ["read_fault", "cancelled"])
def test_a_stream_that_ends_early_leaves_no_buffer_leased_and_no_thread(
    tmp_path, monkeypatch, ending
):
    """A read that keeps failing, or a cancellation, in one lane of the
    second source ends all four: no chunk is started after it, the error is
    raised once every lane's thread has ended, and all four pairs are the
    pool's again with nothing of the device left on them."""
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    a, b = RNG.standard_normal(SHAPE), RNG.standard_normal(SHAPE)
    pa, pb = (_stored(tmp_path / f"{k}.zarr", h).store for k, h in (("a", a), ("b", b)))
    token, executor, reads = CancellationToken(), JaxExecutor(mesh=_mesh()), []
    real, lock = _LocalIO.readinto, threading.Lock()
    at = 16 + 7  # in the second source: every lane has made both its buffers

    def readinto(self, name, buffer):
        with lock:
            reads.append(name)
            nth = len(reads)
        if nth == at and ending == "cancelled":
            token.cancel("the test asked")
        if nth >= at and ending == "read_fault":
            raise faults.FaultInjectedIOError("the disk is gone, says the test")
        return real(self, name, buffer)

    before = {t.ident for t in threading.enumerate()}
    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    error = faults.FaultInjectedIOError if ending == "read_fault" else ComputeCancelledError
    with pytest.raises(error):
        ct.to_zarr(xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)),
                   str(tmp_path / "c.zarr"), executor=executor, cancellation=token)
    assert executor._staging is None and executor._leased is None
    if ending == "cancelled":
        # the reads in flight finished, one a lane at most; no other began
        assert at <= len(reads) <= at + 3
    pairs = list(jx._STAGING_POOL)
    assert len(pairs) == 4
    assert all(stage.busy is None and stage.buffer is not None for pair in pairs for stage in pair)
    assert not [t for t in threading.enumerate()
                if t.ident not in before and t.name.startswith("cubed-tpu")]
    # and the next compute, under the same mesh, streams through the pairs
    monkeypatch.setattr(_LocalIO, "readinto", real)
    cap, target = _Capture(), str(tmp_path / "d.zarr")
    ct.to_zarr(xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)), target,
               executor=JaxExecutor(mesh=_mesh()), callbacks=[cap])
    assert _read_with_numpy(target).tobytes() == (a + b).tobytes()
    assert cap.stats["stage_reused_bytes"] == cap.stats["h2d_stream_bytes"] == 2 * a.nbytes
    assert cap.stats["h2d_lane_bytes"] == cap.stats["h2d_stream_bytes"]
    assert jx._STAGING_POOL == pairs
