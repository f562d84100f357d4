"""A stored source goes to the device chunk by chunk through reused staging
buffers.

``JaxExecutor._device_put`` walks a stored array's chunk grid
(``_stream_to_device``): each chunk file is read into one of two host
buffers that the process keeps and a compute leases, put on the device from
there and written into its place in one resident array, updated in place: a
lane, on the calling thread where the source has one owner. The device value is
bit for bit what the whole-array route gives (the array assembled on the
host, put in one piece), which a stored array still takes where HBM lacks the
room, and which one chunk, a 0-d array and a record array always take.
Under a mesh the same walk sends each chunk to the chip that owns it, where
every shard is a block of whole chunks, a lane a chip, each on a thread and
through a pair of its own (``tests/test_zarr_add_mesh.py``);
any other layout is read shard by shard through the callback. The reads stay the store's: cancellation, injected faults, retries and
breaker pacing, byte accounting, verification with quarantine."""

from __future__ import annotations

import gc
import json
import mmap
import os
import time
import weakref

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.runtime.executors.jax as jx
from cubed_tpu.observability.accounting import SPANS_ENV_VAR, task_scope
from cubed_tpu.observability.metrics import get_registry
from cubed_tpu.runtime import faults
from cubed_tpu.runtime.cancellation import CancellationToken, ComputeCancelledError
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.storage import health, integrity
from cubed_tpu.storage.integrity import ChunkIntegrityError
from cubed_tpu.storage.store import _LocalIO, open_zarr_array
from tests.utils import leased_staging

RNG = np.random.default_rng(29)

#: float64 bit patterns that a careless route changes: NaNs with a payload
#: and a sign, both zeros, both infinities, the least and the largest
#: subnormal, the extremes of the normal range
EDGE_BITS = np.array(
    [0x7FF8000000000123, 0xFFF0000000000ABC, 0x8000000000000000, 0,
     0x7FF0000000000000, 0xFFF0000000000000, 1, 0x000FFFFFFFFFFFFF,
     0x7FEFFFFFFFFFFFFF, 0x0010000000000000],
    np.uint64,
)
EDGE_BITS_32 = np.array(
    [0x7FC00123, 0xFF800ABC, 0x80000000, 0, 0x7F800000, 0xFF800000, 1,
     0x007FFFFF, 0x7F7FFFFF, 0x00800000],
    np.uint32,
)


def _values(dtype, shape) -> np.ndarray:
    """Values of ``dtype`` that fill its range, edge values among them."""
    dtype = np.dtype(dtype)
    n = int(np.prod(shape))
    if dtype == np.bool_:
        flat = RNG.integers(0, 2, n).astype(np.bool_)
    elif dtype.kind in "iu":
        info = np.iinfo(dtype)
        flat = RNG.integers(info.min, info.max, n, dtype=dtype, endpoint=True)
    elif dtype.kind == "c":
        flat = (RNG.standard_normal(n) + 1j * RNG.standard_normal(n)).astype(dtype)
        flat.view(np.float64)[: EDGE_BITS.size] = EDGE_BITS.view(np.float64)
    else:
        flat = (RNG.standard_normal(n) * 1e3).astype(dtype)
        edges = EDGE_BITS.view(np.float64) if dtype == np.float64 else EDGE_BITS_32.view(np.float32)
        # edge values at both ends, so that the first and the last chunk
        # (a ragged one) both hold some
        flat[: edges.size] = edges
        flat[-edges.size :] = edges[::-1]
    return flat.reshape(shape)


def _stored(tmp_path, host, chunks, name="a", **kwargs):
    z = open_zarr_array(
        str(tmp_path / f"{name}.zarr"), "w", shape=host.shape, dtype=host.dtype,
        chunks=chunks, **kwargs,
    )
    z[...] = host
    return z


@pytest.fixture(autouse=True)
def _empty_pool():
    """Every test starts as a process's first compute does, with no staging
    pair kept, and leaves none behind."""
    jx.release_staging_buffers()
    yield
    jx.release_staging_buffers()


def _put(z, executor=None, carry_bits=False):
    """``z`` through ``_device_put`` as ``_preload`` calls it: (fetched device
    value, the executor)."""
    executor = executor or JaxExecutor()
    executor._carry_bits = carry_bits
    with leased_staging(executor):
        value = executor._device_put(z, tuple(z.shape), z.chunkset() if z.shape else None)
        return np.asarray(value), executor


def _whole(z, carry_bits=False):
    """``z`` by the whole-array route: an executor whose budget holds the
    array and nothing beside it declines the stream."""
    executor = JaxExecutor(device_mem=max(z.nbytes, 1))
    got, executor = _put(z, executor, carry_bits)
    assert executor.stats["h2d_stream_bytes"] == 0
    return got, executor


def _same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


# -- the same device value, bit for bit ---------------------------------------


@pytest.mark.parametrize(
    "dtype, carry_bits",
    [(np.float64, False), (np.float64, True), (np.float32, False),
     (np.int64, False), (np.uint8, False), (np.bool_, False),
     (np.complex128, False)],
    ids=["float64", "float64_as_bits", "float32", "int64", "uint8", "bool",
         "complex128"],
)
def test_streamed_value_is_the_whole_array_routes_bit_for_bit(tmp_path, dtype, carry_bits):
    host = _values(dtype, (23, 17))
    z = _stored(tmp_path, host, (8, 5))
    streamed, ex = _put(z, carry_bits=carry_bits)
    whole, declined = _whole(z, carry_bits=carry_bits)
    assert _same_bits(streamed, whole)
    on_device = np.uint64 if carry_bits else dtype
    assert streamed.dtype == on_device
    assert streamed.view(host.dtype).tobytes() == host.tobytes()
    # every chunk went through the staging buffers, padded as it is stored
    padded = z.nchunks * z._chunk_nbytes()
    assert ex.stats["h2d_stream_bytes"] == ex.stats["h2d_bytes"] == padded
    assert ex.stats["f64_as_bits"] == declined.stats["f64_as_bits"] == int(carry_bits)
    assert declined.stats["h2d_bytes"] == host.nbytes
    assert declined.stats["h2d_stream_declined"] == 1
    assert "h2d_stream_declined" not in ex.stats


@pytest.mark.parametrize(
    "shape, chunks",
    [((29,), (8,)), ((13, 22), (5, 8)), ((7, 9, 11), (3, 4, 5)), ((12, 9), (4, 3))],
    ids=["1d", "2d", "3d", "2d_no_edge"],
)
@pytest.mark.parametrize("carry_bits", [False, True], ids=["numbers", "bits"])
def test_ragged_edge_chunks_land_in_their_place(tmp_path, shape, chunks, carry_bits):
    host = _values(np.float64, shape)
    z = _stored(tmp_path, host, chunks)
    streamed, ex = _put(z, carry_bits=carry_bits)
    assert streamed.shape == shape
    assert streamed.view(np.float64).tobytes() == host.tobytes()
    assert _same_bits(streamed, _whole(z, carry_bits=carry_bits)[0])
    assert ex.stats["h2d_stream_bytes"] == z.nchunks * z._chunk_nbytes()


@pytest.mark.parametrize("case", range(EDGE_BITS.size))
def test_an_edge_value_in_every_position_of_a_chunk_survives(tmp_path, case):
    """One edge value fills the array: no zero written around it by the
    allocation, no default beside it, can hide a changed bit."""
    host = np.full((6, 10), EDGE_BITS[case], np.uint64).view(np.float64)
    z = _stored(tmp_path, host, (4, 4))
    for carry_bits in (False, True):
        streamed, _ = _put(z, carry_bits=carry_bits)
        assert streamed.view(np.uint64).tobytes() == host.tobytes()


def test_a_chunk_never_written_reads_as_the_fill_value(tmp_path):
    host = _values(np.float64, (8, 8))
    z = _stored(tmp_path, host, (4, 4), fill_value=-7.5)
    os.remove(os.path.join(z.store, "1.0"))
    expected = host.copy()
    expected[4:, :4] = -7.5
    streamed, ex = _put(z)
    assert streamed.tobytes() == expected.tobytes()
    assert _same_bits(streamed, _whole(z)[0])
    assert ex.stats["h2d_stream_bytes"] == ex.stats["h2d_bytes"] == host.nbytes


def test_a_missing_chunk_that_the_manifest_lists_is_an_integrity_error(tmp_path):
    with integrity.scoped("write"):
        z = _stored(tmp_path, _values(np.float64, (8, 8)), (4, 4))
    assert "1.0" in z._manifest()[0]
    os.remove(os.path.join(z.store, "1.0"))
    with integrity.scoped("verify"), task_scope():
        with pytest.raises(ChunkIntegrityError) as info:
            _put(z)
    assert info.value.kind == "missing" and info.value.chunk_key == "1.0"


@pytest.mark.parametrize("compressor", [{"id": "zlib", "level": 1}, {"id": "lzma"}],
                         ids=["zlib", "lzma"])
def test_a_compressed_store_streams_what_the_codec_hands_over(tmp_path, compressor):
    host = _values(np.float64, (11, 10))
    z = _stored(tmp_path, host, (4, 4), compressor=compressor)
    streamed, ex = _put(z)
    assert streamed.tobytes() == host.tobytes()
    assert _same_bits(streamed, _whole(z)[0])
    assert ex.stats["h2d_stream_bytes"] == ex.stats["h2d_bytes"] == z.nchunks * z._chunk_nbytes()


def test_an_io_class_without_readinto_streams_the_bytes_it_reads(tmp_path, monkeypatch):
    host = _values(np.int64, (9, 9))
    z = _stored(tmp_path, host, (4, 4))
    monkeypatch.delattr(_LocalIO, "readinto")
    streamed, ex = _put(z)
    assert streamed.tobytes() == host.tobytes()
    assert ex.stats["h2d_stream_bytes"] == ex.stats["h2d_bytes"] > 0


# -- what takes the whole-array route ---------------------------------------------


def _one_chunk(tmp_path):
    return _stored(tmp_path, _values(np.float64, (6, 5)), (6, 5))


def _zero_d(tmp_path):
    return _stored(tmp_path, np.array(2.5), ())


def _record(tmp_path):
    host = np.zeros((6, 4), dtype=[("x", np.float64), ("n", np.int32)])
    host["x"], host["n"] = _values(np.float64, (6, 4)), np.arange(24).reshape(6, 4)
    return _stored(tmp_path, host, (3, 2))


def _empty(tmp_path):
    return _stored(tmp_path, np.zeros((0, 6)), (1, 3))


@pytest.mark.parametrize("make", [_one_chunk, _zero_d, _record, _empty],
                         ids=["one_chunk", "zero_d", "record", "empty"])
def test_what_is_not_several_plain_chunks_takes_the_whole_array_route(tmp_path, make):
    z = make(tmp_path)
    executor = JaxExecutor()
    with executor._lease() as staging:
        value = executor._device_put(z, tuple(z.shape), None)
    host = z[...] if z.shape else z[()]
    if isinstance(value, dict):
        assert all(np.asarray(value[k]).tobytes() == np.ascontiguousarray(host[k]).tobytes()
                   for k in host.dtype.names)
    else:
        assert np.asarray(value).tobytes() == np.asarray(host).tobytes()
    assert executor.stats["h2d_stream_bytes"] == 0
    assert executor.stats["h2d_bytes"] == host.nbytes
    # not for want of room: nothing here qualified by kind
    assert "h2d_stream_declined" not in executor.stats
    # no buffer was made, for this compute or for the pool
    assert all(stage.buffer is None for stage in staging)
    assert jx._STAGING_POOL == [staging] and executor._staging is None


def test_a_host_array_is_put_in_one_piece(tmp_path):
    host = _values(np.float64, (8, 8))
    executor = JaxExecutor()
    assert np.asarray(executor._device_put(host, host.shape)).tobytes() == host.tobytes()
    assert executor.stats["h2d_stream_bytes"] == 0 and executor.stats["h2d_bytes"] == host.nbytes


@pytest.mark.parametrize(
    "shape, streams",
    # a 4 x 2 grid: a chunk-row a chip; a 3 x 3 grid: 12 rows divide by four
    # chips and no side of the grid does, so a shard ends inside a chunk
    [((16, 8), True), ((12, 12), False)],
    ids=["chunk_aligned_streams_to_the_owners", "through_a_chunk_keeps_the_callback"],
)
def test_under_a_mesh_a_source_streams_where_every_chunk_has_one_owner(tmp_path, shape, streams):
    import jax

    from cubed_tpu.parallel.mesh import make_mesh

    host = _values(np.float64, shape)
    z = _stored(tmp_path, host, (4, 4))
    executor = JaxExecutor(mesh=make_mesh(devices=jax.devices()[:4]))
    with executor._lease() as staging:
        value = executor._device_put(z, tuple(z.shape), z.chunkset())
        buffers = [stage.buffer for stage in staging]
    assert len(value.sharding.device_set) == 4
    assert np.asarray(value).tobytes() == host.tobytes()
    if streams:
        # every chunk to the chip that owns it, on a lane thread of that chip's
        # (the first lane's pair is the one the lease began with)
        assert executor.stats["h2d_stream_bytes"] == executor.stats["mesh_owner_bytes"] == host.nbytes
        assert executor.stats["h2d_lane_bytes"] == host.nbytes
        assert "h2d_stream_declined" not in executor.stats
        assert executor.stats["mesh_gathered_bytes"] == 0
        assert [buffer.nbytes for buffer in buffers] == [z._chunk_nbytes()] * 2
    else:
        # each shard assembled on the host by the callback, and counted
        assert executor.stats["h2d_stream_bytes"] == 0 and executor.stats["mesh_owner_bytes"] == 0
        assert executor.stats["h2d_stream_declined"] == 1
        assert executor.stats["mesh_gathered_bytes"] == host.nbytes
        assert buffers == [None, None]


@pytest.mark.parametrize("short_by", [1, 4 * 4 * 8], ids=["a_byte", "a_chunk"])
def test_no_room_for_the_array_and_two_chunks_takes_it_and_is_counted(tmp_path, short_by):
    host = _values(np.float64, (8, 8))
    z = _stored(tmp_path, host, (4, 4))
    needed = z.nbytes + 2 * z._chunk_nbytes()
    roomy, tight = JaxExecutor(device_mem=needed), JaxExecutor(device_mem=needed - short_by)
    streamed, _ = _put(z, roomy)
    whole, _ = _put(z, tight)
    assert _same_bits(streamed, whole)
    assert roomy.stats["h2d_stream_bytes"] == host.nbytes
    assert "h2d_stream_declined" not in roomy.stats
    assert tight.stats["h2d_stream_bytes"] == 0 and tight.stats["h2d_stream_declined"] == 1


def test_what_is_resident_counts_against_the_room(tmp_path):
    z = _stored(tmp_path, _values(np.float64, (8, 8)), (4, 4))
    executor = JaxExecutor(device_mem=z.nbytes + 2 * z._chunk_nbytes() + 100)
    executor._resident = {"held": jx._Resident(None, 101, None)}
    _put(z, executor)
    assert executor.stats["h2d_stream_declined"] == 1
    executor._resident = {"held": jx._Resident(None, 100, None)}
    _put(z, executor)
    assert executor.stats["h2d_stream_declined"] == 1
    assert executor.stats["h2d_stream_bytes"] == z.nbytes


@pytest.mark.parametrize("dtype, twice", [(np.float64, True), (np.int64, True),
                                          (np.complex128, True), (np.float32, False),
                                          (np.complex64, False)])
def test_a_pair_device_needs_the_room_twice_for_64_bit_elements(
    tmp_path, monkeypatch, dtype, twice
):
    """A device that holds 64-bit elements as pairs updates through a split
    copy of the array and of the chunk."""
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    z = _stored(tmp_path, _values(dtype, (8, 8)), (4, 4))
    needed = (z.nbytes + 2 * z._chunk_nbytes()) * (2 if twice else 1)
    for budget, streams in ((needed, True), (needed - 1, False)):
        executor = JaxExecutor(device_mem=budget)
        assert (executor._streams(z, None) is not None) is streams
        assert executor.stats["h2d_stream_declined"] == int(not streams)


def test_whether_float64_round_trips_is_the_devices_and_not_the_threads_x64(monkeypatch):
    """Asked for the first time inside a compute under
    ``compute_dtype="float32"`` (x64 off for that thread), the probe still
    goes as float64: the answer is kept for the process."""
    import jax

    monkeypatch.setattr(jx, "_FLOAT64_ROUND_TRIPS", {})
    with jax.enable_x64(False):
        assert jx._float64_round_trips(jax.devices()[0]) is True
    assert list(jx._FLOAT64_ROUND_TRIPS.values()) == [True]


def test_a_pair_device_is_not_sent_a_64_bit_array_in_many_chunks(tmp_path, monkeypatch):
    """Every update of a 64-bit array is a pass over all of it there."""
    fine = _stored(tmp_path, _values(np.float64, (65, 4)), (1, 4), name="fine")
    coarse = _stored(tmp_path, _values(np.float64, (64, 4)), (1, 4), name="coarse")
    narrow = _stored(tmp_path, _values(np.float32, (65, 4)), (1, 4), name="narrow")
    assert (fine.nchunks, coarse.nchunks) == (65, jx._PAIR_STREAM_MAX_CHUNKS)
    assert all(JaxExecutor()._streams(z, None) for z in (fine, coarse, narrow))
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    executor = JaxExecutor()
    assert not executor._streams(fine, None)
    assert executor._streams(coarse, None) and executor._streams(narrow, None)
    got, _ = _put(fine, executor)
    assert got.tobytes() == fine[...].tobytes()
    assert executor.stats["h2d_stream_bytes"] == 0
    assert "h2d_stream_declined" not in executor.stats  # not for want of room


# -- through a compute -------------------------------------------------------------


class _Capture:
    stats = None

    def on_compute_end(self, event):
        self.stats = event.executor_stats


@pytest.fixture
def sources(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    # plain values: what an add makes of edge values is not the stream's doing
    hosts = [RNG.standard_normal((30, 20)) for _ in "ab"]
    paths = []
    for name, host in zip("ab", hosts):
        paths.append(_stored(tmp_path, host, (8, 8), name=name).store)
    return spec, paths, hosts


def test_a_compute_streams_every_source_and_says_so(sources, tmp_path):
    spec, (pa, pb), (a, b) = sources
    cap = _Capture()
    executor = JaxExecutor()
    ct.to_zarr(
        xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)),
        str(tmp_path / "c.zarr"), executor=executor, callbacks=[cap],
    )
    got = ct.from_zarr(str(tmp_path / "c.zarr"), spec=spec).compute()
    np.testing.assert_array_equal(got, a + b)
    padded = 2 * 4 * 3 * 8 * 8 * 8
    assert cap.stats["h2d_stream_bytes"] == cap.stats["h2d_bytes"] == padded
    # one owner: the lane ran on the calling thread
    assert "h2d_lane_bytes" in cap.stats and cap.stats["h2d_lane_bytes"] == 0
    assert not cap.stats.get("h2d_stream_declined")
    assert cap.stats["bytes_read"] == padded and cap.stats["chunks_read"] == 24
    # both sources went through the same two buffers, each one chunk large,
    # which this compute had to make and the process now keeps
    assert cap.stats["stage_reused_bytes"] == 0
    assert executor._staging is None
    (pair,) = jx._STAGING_POOL
    assert [stage.buffer.nbytes for stage in pair] == [8 * 8 * 8] * 2
    assert all(stage.busy is None for stage in pair)
    # the next compute, of another executor, streams through them
    kept = [stage.buffer for stage in pair]
    ct.to_zarr(
        xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)),
        str(tmp_path / "d.zarr"), executor=JaxExecutor(), callbacks=[cap],
    )
    assert cap.stats["stage_reused_bytes"] == cap.stats["h2d_stream_bytes"] == padded
    assert jx._STAGING_POOL == [pair] and [stage.buffer for stage in pair] == kept


def test_a_copy_of_a_stored_array_carries_its_bits_chunk_by_chunk(tmp_path, monkeypatch):
    """A compute that only moves values streams float64 as uint64."""
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    host = _values(np.float64, (20, 12))
    z = _stored(tmp_path, host, (8, 8))
    cap = _Capture()
    # the CPU holds a real float64 and so carries nothing as bits by itself
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    ct.to_zarr(
        ct.from_zarr(z.store, spec=spec).rechunk((10, 6)), str(tmp_path / "out.zarr"),
        executor=JaxExecutor(), callbacks=[cap],
    )
    out = open_zarr_array(str(tmp_path / "out.zarr"), "r")
    assert out[...].tobytes() == host.tobytes()
    assert cap.stats["f64_as_bits"] >= 1
    assert cap.stats["h2d_stream_bytes"] == cap.stats["h2d_bytes"] == z.nchunks * z._chunk_nbytes()


def test_the_counter_is_present_and_zero_where_nothing_streamed(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB")
    a = ct.from_array(np.arange(36.0).reshape(6, 6), chunks=(3, 3), spec=spec)
    cap = _Capture()
    assert float(xp.sum(a).compute(executor=JaxExecutor(), callbacks=[cap])) == 630.0
    assert "h2d_stream_bytes" in cap.stats and cap.stats["h2d_stream_bytes"] == 0
    assert "stage_reused_bytes" in cap.stats and cap.stats["stage_reused_bytes"] == 0
    assert type(cap.stats["stage_reused_bytes"]) is int
    assert "h2d_lane_bytes" in cap.stats and cap.stats["h2d_lane_bytes"] == 0
    assert type(cap.stats["h2d_lane_bytes"]) is int


# -- the reads are still the store's ----------------------------------------------


@pytest.fixture
def _fresh_breakers():
    health.reset_breakers()
    yield
    health.reset_breakers()


def test_injected_read_faults_are_retried_in_place(tmp_path, _fresh_breakers):
    host = _values(np.float64, (16, 16))
    z = _stored(tmp_path, host, (4, 4))
    retries = get_registry().counter("storage_read_retries")
    before = retries.value
    with faults.scoped(faults.FaultConfig(seed=3, storage_read_failure_rate=0.3)):
        with task_scope() as scope:
            streamed, ex = _put(z)
    assert streamed.tobytes() == host.tobytes()
    assert retries.value > before
    assert scope.chunks_read == 16 and scope.bytes_read == host.nbytes
    assert ex.stats["h2d_stream_bytes"] == host.nbytes


def test_a_read_that_keeps_failing_raises_out_of_the_stream(tmp_path, _fresh_breakers):
    z = _stored(tmp_path, _values(np.float64, (8, 8)), (4, 4))
    with faults.scoped(faults.FaultConfig(seed=3, storage_read_failure_rate=1.0)):
        with task_scope(), pytest.raises(faults.FaultInjectedIOError):
            _put(z)


def test_injected_throttles_are_paced_by_the_breaker(tmp_path, _fresh_breakers):
    host = _values(np.float64, (16, 16))
    z = _stored(tmp_path, host, (4, 4))
    with faults.scoped(faults.FaultConfig(seed=23, storage_throttle_rate=0.25)):
        with task_scope() as scope:
            streamed, _ = _put(z)
    assert streamed.tobytes() == host.tobytes()
    assert scope.counters.get("store_throttled", 0) > 0
    assert health.store_breaker(z.store).state != "closed"


def test_mode_verify_checks_the_staged_bytes_and_quarantines_a_corrupted_chunk(tmp_path):
    host = _values(np.float64, (8, 8))
    with integrity.scoped("write"):
        z = _stored(tmp_path, host, (4, 4))
    with integrity.scoped("verify"), task_scope() as scope:
        streamed, _ = _put(z)
    assert streamed.tobytes() == host.tobytes()
    assert scope.counters["chunks_verified"] == 4
    # one flipped bit in the third chunk
    path = os.path.join(z.store, "1.0")
    raw = bytearray(open(path, "rb").read())
    raw[17] ^= 0x10
    open(path, "wb").write(bytes(raw))
    with integrity.scoped("verify"), task_scope() as scope:
        with pytest.raises(ChunkIntegrityError) as info:
            _put(z)
    assert info.value.kind == "checksum" and info.value.chunk_key == "1.0"
    assert scope.counters["chunks_quarantined"] == 1
    assert not os.path.exists(path)
    assert any(n.startswith("1.0.quarantine.") for n in os.listdir(z.store))
    # unverified, the flipped bit would have gone through: the check is the
    # staged bytes' and not a formality
    open(path, "wb").write(bytes(raw))
    with integrity.scoped("write"), task_scope():
        assert _put(z)[0].tobytes() != host.tobytes()


def test_a_file_longer_than_a_chunk_is_not_cut_to_fit_the_buffer(tmp_path):
    z = _stored(tmp_path, _values(np.float64, (8, 8)), (4, 4))
    with open(os.path.join(z.store, "0.1"), "ab") as f:
        f.write(b"\0" * 8)
    with pytest.raises(ValueError):
        _put(z)


def test_a_cancel_lands_between_chunks(sources, tmp_path, monkeypatch):
    spec, (pa, pb), _ = sources
    token = CancellationToken()
    reads = []
    real = _LocalIO.readinto

    def readinto(self, name, buffer):
        reads.append(name)
        if len(reads) == 3:
            token.cancel("the test asked")
        return real(self, name, buffer)

    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    with pytest.raises(ComputeCancelledError):
        ct.to_zarr(
            xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)),
            str(tmp_path / "c.zarr"), executor=JaxExecutor(), cancellation=token,
        )
    # the read in flight finished; the next chunk's was never started
    assert len(reads) == 3


# -- the staging buffers -----------------------------------------------------------


def _spy_on_reads(monkeypatch):
    """Records (address of the buffer, chunk key) of every read-into."""
    seen = []
    real = _LocalIO.readinto

    def readinto(self, name, buffer):
        seen.append((np.asarray(buffer).__array_interface__["data"][0], name))
        return real(self, name, buffer)

    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    return seen


def test_two_buffers_take_turns_across_chunks_and_sources(tmp_path, monkeypatch):
    a = _stored(tmp_path, _values(np.float64, (12, 8)), (4, 4), name="a")
    b = _stored(tmp_path, _values(np.float64, (8, 8)), (4, 4), name="b")
    seen = _spy_on_reads(monkeypatch)
    executor = JaxExecutor()
    with executor._lease() as staging:
        got_a, _ = _put(a, executor)
        first = [stage.buffer for stage in staging]
        got_b, _ = _put(b, executor)
    assert got_a.tobytes() == a[...].tobytes() and got_b.tobytes() == b[...].tobytes()
    assert all(stage.buffer is kept for stage, kept in zip(staging, first))
    addresses = [address for address, _ in seen]
    assert len(addresses) == 6 + 4 and len(set(addresses)) == 2
    # they alternate within a source; each source starts with the first
    assert addresses[:6] == addresses[:2] * 3 and addresses[6:] == addresses[:2] * 2
    assert all(stage.buffer.nbytes == 4 * 4 * 8 for stage in staging)
    # each starts on a page boundary, as the page cache's pages do
    assert all(address % mmap.PAGESIZE == 0 for address in addresses)


def test_the_buffers_grow_to_the_largest_chunk_seen_and_stay_with_the_process(tmp_path):
    """The pool holds one pair where no compute ran more than one lane,
    whatever executor streams: it grows to the largest chunk seen (a larger
    buffer replaces the smaller, never adds), outlives the executors, and
    goes when the process says so."""
    small = _stored(tmp_path, _values(np.float64, (8, 8)), (4, 4), name="small")
    large = _stored(tmp_path, _values(np.float64, (16, 16)), (8, 8), name="large")
    assert jx._STAGING_POOL == []
    _, first = _put(small)
    (pair,) = jx._STAGING_POOL
    assert first._staging is None  # the executor keeps no buffer
    assert [s.buffer.nbytes for s in pair] == [128, 128]
    assert first.stats["stage_reused_bytes"] == 0  # it made them
    outgrown = [weakref.ref(s.buffer) for s in pair]
    _, second = _put(large)  # another executor: the same pair, made larger
    assert jx._STAGING_POOL == [pair]
    assert [s.buffer.nbytes for s in pair] == [512, 512]
    assert second.stats["stage_reused_bytes"] == 0  # made larger by this one
    gc.collect()
    assert all(ref() is None for ref in outgrown)  # replaced, not added
    kept = [s.buffer for s in pair]
    _, third = _put(small)  # a smaller chunk reuses the larger buffers
    assert jx._STAGING_POOL == [pair] and [s.buffer for s in pair] == kept
    assert third.stats["stage_reused_bytes"] == third.stats["h2d_stream_bytes"] == small.nbytes
    # the executors go, the pair stays; nothing of a compute is left on it
    del first, second, third
    gc.collect()
    assert jx._STAGING_POOL == [pair] and [s.buffer for s in pair] == kept
    assert all(s.busy is None for s in pair)
    refs = [weakref.ref(s.buffer) for s in pair]
    jx.release_staging_buffers()
    assert jx._STAGING_POOL == []
    del pair, kept
    gc.collect()
    assert all(ref() is None for ref in refs)
    # and the next compute starts over
    _, fourth = _put(small)
    assert fourth.stats["stage_reused_bytes"] == 0
    assert [s.buffer.nbytes for s in jx._STAGING_POOL[0]] == [128, 128]


def test_a_pair_leased_is_left_alone_by_the_release_and_comes_back(tmp_path):
    small = _stored(tmp_path, _values(np.float64, (8, 8)), (4, 4), name="small")
    executor = JaxExecutor()
    with executor._lease() as staging:
        _put(small, executor)
        assert jx._STAGING_POOL == []  # it is out
        jx.release_staging_buffers()
        assert [s.buffer.nbytes for s in staging] == [128, 128]
        _put(small, executor)
    assert jx._STAGING_POOL == [staging]


def test_a_forked_child_starts_with_an_empty_pool(tmp_path):
    """``multiprocess.py`` gives the accelerator to one process at a time: a
    child keeps no copy of the parent's pages."""
    _put(_stored(tmp_path, _values(np.float64, (8, 8)), (4, 4)))
    assert len(jx._STAGING_POOL) == 1
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's word on fork and threads
        pid = os.fork()
    if pid == 0:  # the child looks and leaves, touching nothing of jax
        os._exit(0 if jx._STAGING_POOL == [] else 1)
    _, status = os.waitpid(pid, 0)
    assert os.WIFEXITED(status) and os.WEXITSTATUS(status) == 0
    assert len(jx._STAGING_POOL) == 1  # the parent's is where it was


def test_a_buffer_is_not_rewritten_before_the_update_that_read_it_is_ready(
    tmp_path, monkeypatch
):
    """The reads made instant (a memcpy from memory, no file), so that the
    host is always ahead of the device: were a buffer rewritten while its
    transfer or update still read it, some chunk would hold another's
    values. The waits are counted too: every update but a source's last is
    waited for inside the stream, and that one before its buffer's next use."""
    host = _values(np.float64, (64, 96))
    z = _stored(tmp_path, host, (8, 8))
    files = {name: open(os.path.join(z.store, name), "rb").read()
             for name in os.listdir(z.store) if not name.startswith(".")}

    def readinto(self, name, buffer):
        view = memoryview(buffer).cast("B")[: len(files[name])]
        view[:] = files[name]
        return view

    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    waits = []
    real_release = jx._Staging.release

    def release(self):
        waits.append(self.busy is not None)
        waited = real_release(self)
        assert self.busy is None
        return waited

    monkeypatch.setattr(jx._Staging, "release", release)
    executor = JaxExecutor()
    with executor._lease() as staging:
        for _ in range(3):
            got, _ = _put(z, executor)
            assert got.tobytes() == host.tobytes()
        assert len({id(s.buffer) for s in staging}) == 2
        # ahead of every read the stream's own release, which is counted, and
        # ``sized``'s, which finds nothing left; one at the end of every chunk's span
        assert len(waits) == 3 * 3 * z.nchunks
        # and a real wait for every update: none is left unwaited but the last
        assert sum(waits) == 3 * z.nchunks - 1
        assert sum(s.busy is not None for s in staging) == 1
    # which the end of the lease waits out: the pool keeps no device value
    assert len(waits) == 3 * 3 * z.nchunks + 2 and sum(waits) == 3 * z.nchunks
    assert all(s.busy is None for s in staging) and jx._STAGING_POOL == [staging]
    # each timed, armed or not, and the time is a whole number of microseconds
    assert type(executor.stats["stage_wait_us"]) is int
    assert executor.stats["stage_wait_us"] >= 0


# -- who waited for whom, and whether the pages were fresh -------------------------


class _Held:
    """A device update that keeps its staging buffer for ``seconds``."""

    def __init__(self, seconds):
        self.seconds = seconds

    def block_until_ready(self):
        time.sleep(self.seconds)


@pytest.mark.parametrize(
    "where", ["release", "ahead_of_a_read", "inside_h2d", "a_flushs_join"]
)
def test_stage_wait_us_rises_when_the_device_update_is_held(tmp_path, monkeypatch, where):
    """``release`` says how long a device update kept its staging buffer,
    and the stream counts the waits it makes: ahead of a chunk's read, and
    at the end of a chunk's ``jax.h2d`` span, which then says so
    (``wait_us``) and has no child for it. The wait of a flush's join for
    the preload's last update is the flush's (``jax.d2h``) and not counted
    here, so that the count stays a part of the preload's time."""
    held_us = 20_000
    if where == "release":
        stage = jx._Staging()
        assert stage.release() == 0
        stage.busy = _Held(held_us / 1e6)
        waited = stage.release()
        assert type(waited) is int and waited >= held_us and stage.busy is None
        assert stage.release() == 0
        return
    host = _values(np.float64, (8, 8))
    z = _stored(tmp_path, host, (4, 8))  # two chunks
    executor = JaxExecutor()
    with executor._lease() as staging:
        _put(z, executor)  # compiled, the buffers made
        quick = executor.stats["stage_wait_us"]
        assert quick < held_us
        if where == "a_flushs_join":
            stage = staging[1]
            stage.busy = _Held(held_us / 1e6)
            started = time.perf_counter()
            out = stage.array((4, 8), np.dtype(np.float64))
            assert time.perf_counter() - started >= held_us / 1e6
            assert stage.holds(out) and stage.busy is None
            assert executor.stats["stage_wait_us"] == quick
            return
        # chunk 0 is read into the first buffer and waits, inside its span, for
        # the second
        staging[0 if where == "ahead_of_a_read" else 1].busy = _Held(held_us / 1e6)
        monkeypatch.setenv(SPANS_ENV_VAR, "1")
        with task_scope(jx._SCOPE_SPANS) as scope:
            got, _ = _put(z, executor)
        assert got.tobytes() == host.tobytes()
        assert executor.stats["stage_wait_us"] >= quick + held_us
        puts = [s for s in scope.spans if s["name"] == "jax.h2d"]
        assert len(puts) == 2 and all(type(s["attrs"]["wait_us"]) is int for s in puts)
        assert (puts[0]["attrs"]["wait_us"] >= held_us) == (where == "inside_h2d")
        assert (puts[0]["dur"] >= held_us / 1e6) == (where == "inside_h2d")
        # the wait has no span of its own: ``h2d_s`` is the put's self time
        assert not [s for s in scope.spans if s.get("parent") in {p["id"] for p in puts}]
        assert {s["name"] for s in scope.spans} == {"jax.h2d", "storage_read"}


@pytest.mark.parametrize("where", ["ahead_of_a_read", "inside_h2d"])
def test_under_a_mesh_a_held_update_is_waited_for_on_the_lane_whose_pair_it_holds(
    tmp_path, monkeypatch, where
):
    """A lane waits for its own pair and for no other's: the held update
    shows in ``stage_wait_us``, the sum over the threads that waited, and
    where it falls inside a chunk's ``jax.h2d`` in that lane's span alone,
    which the fold marks with the lane's thread."""
    import jax

    from cubed_tpu.parallel.mesh import make_mesh

    held_us, lane = 20_000, 2
    host = _values(np.float64, (16, 8))
    z = _stored(tmp_path, host, (4, 4))  # a 4 x 2 grid: two chunks a chip
    executor = JaxExecutor(mesh=make_mesh(devices=jax.devices()[:4]))
    with executor._lease():
        _put(z, executor)  # compiled, every lane's buffers made
        quick = executor.stats["stage_wait_us"]
        assert quick < held_us and len(executor._leased) == 4
        # a lane's first chunk is read into its first buffer and waits, inside
        # its span, for its second
        executor._leased[lane][0 if where == "ahead_of_a_read" else 1].busy = _Held(held_us / 1e6)
        monkeypatch.setenv(SPANS_ENV_VAR, "1")
        with task_scope(jx._SCOPE_SPANS) as scope:
            got, _ = _put(z, executor)
        assert got.tobytes() == host.tobytes()
        assert executor.stats["stage_wait_us"] >= quick + held_us
        puts = [s for s in scope.spans if s["name"] == "jax.h2d"]
        assert len(puts) == 8 and all(type(s["attrs"]["wait_us"]) is int for s in puts)
        assert {s["attrs"]["thread"] for s in puts} == {f"cubed-tpu-preload-{n}" for n in range(4)}
        waited = {s["attrs"]["thread"] for s in puts if s["attrs"]["wait_us"] >= held_us}
        assert waited == ({f"cubed-tpu-preload-{lane}"} if where == "inside_h2d" else set())
        # each lane's spans name one chip, and the four lanes four
        chips = {}
        for s in puts:
            chips.setdefault(s["attrs"]["thread"], set()).add(s["attrs"]["device"])
        assert all(len(c) == 1 for c in chips.values())
        assert len(set().union(*chips.values())) == 4
        assert sorted(s["name"] for s in scope.spans) == ["jax.h2d"] * 8 + ["storage_read"] * 8
        assert scope.spans_dropped == 0 and scope.chunks_read == 8 and scope.bytes_read == host.nbytes


def test_resident_pages_sees_fresh_pages_touched():
    """What ``preload_page_faults`` is read from: the resident set grows by
    the pages a first touch brings in, where the kernel's own count of
    faults may stand still (gVisor's ``ru_minflt``)."""
    before = jx._resident_pages()
    if not before:
        pytest.skip("no /proc/self/statm on this system")
    assert type(before) is int
    pages = 4096
    with mmap.mmap(-1, pages * mmap.PAGESIZE) as fresh:
        for page in range(pages):
            fresh[page * mmap.PAGESIZE] = 1
        assert jx._resident_pages() - before >= pages // 2


def _padded_33_mib_chunks(tmp_path):
    """(a stored array of two chunks of 33 MiB, its values): above the size
    at which glibc ever recycles freed memory, so that fresh buffers are
    fresh pages whatever the process did before; and an array one column
    wide under them (the store pads a chunk to its full size), so that the
    resident array, made anew by every preload, is a few pages and does not
    count beside them."""
    rows, columns = 2112, 2048  # 33 MiB a stored chunk
    host = _values(np.float64, (rows + 1, 1))
    # chunks wider than the array, as Zarr allows and another writer may
    # leave them: the store's own ``create`` cuts them to the shape
    path = _stored(tmp_path, np.zeros((1, columns)), (rows, columns)).store
    os.remove(os.path.join(path, "0.0"))
    with open(os.path.join(path, ".zarray")) as f:
        meta = json.load(f)
    meta.update(shape=list(host.shape), chunks=[rows, columns])
    with open(os.path.join(path, ".zarray"), "w") as f:
        json.dump(meta, f)
    z = open_zarr_array(path, "a")
    z[...] = host
    assert z.nchunks == 2 and z._chunk_nbytes() == rows * columns * 8 > 32 * 2**20
    return z, host


def test_a_processs_first_preload_faults_in_the_staging_buffers_and_a_second_executors_does_not(
    tmp_path,
):
    """``preload_page_faults`` is the growth of the resident set over
    ``_preload``: the first compute of a process streams into two staging
    buffers nobody has touched, and the next one, of **another executor**,
    into the same two: its resident set grows by under half as much, and
    all it staged counts as ``stage_reused_bytes``."""
    if not jx._resident_pages():
        pytest.skip("no /proc/self/statm on this system")
    z, host = _padded_33_mib_chunks(tmp_path)
    staged = 2 * z._chunk_nbytes()
    faults_of, reused_of = [], []
    for name in ("first", "second"):
        executor = JaxExecutor()
        resident = executor._resident = {}
        with executor._lease():
            assert executor._preload(z, resident, executor._budget())
        (res,) = resident.values()
        assert np.asarray(res.value).tobytes() == host.tobytes()
        assert type(executor.stats["preload_page_faults"]) is int
        assert type(executor.stats["stage_reused_bytes"]) is int
        assert executor.stats["h2d_stream_bytes"] == staged
        faults_of.append(executor.stats["preload_page_faults"])
        reused_of.append(executor.stats["stage_reused_bytes"])
        del executor, resident, res
    first, second = faults_of
    assert reused_of == [0, staged]
    assert first > 0 and second < first / 2, faults_of
    # two buffers of 33 MiB, in pages of the system's size
    assert first - second >= 2 * 32 * 2**20 // mmap.PAGESIZE, faults_of
    # the pages are the process's until it says otherwise
    before = jx._resident_pages()
    jx.release_staging_buffers()
    gc.collect()  # the CPU backend's put aliased a buffer; the collector lets go of it
    assert before - jx._resident_pages() >= 2 * 32 * 2**20 // mmap.PAGESIZE


@pytest.mark.parametrize("ending", ["segment_error", "read_fault", "cancelled"])
def test_the_lease_comes_back_however_the_compute_ends(
    sources, tmp_path, monkeypatch, _fresh_breakers, ending
):
    """An error in a segment, a read that keeps failing and a cancellation
    between chunks each end the compute with a device update still holding a
    staging buffer: the end of the lease waits it out and lets go of it, the
    pair is the pool's again, and the executor holds none."""
    spec, (pa, pb), (a, b) = sources
    token, executor, held = CancellationToken(), JaxExecutor(), []
    real_read, real_dispatch = _LocalIO.readinto, JaxExecutor._run_segment

    def readinto(self, name, buffer):
        held.append((executor._staging, [s.busy is not None for s in executor._staging]))
        if len(held) == 11 and ending == "cancelled":
            token.cancel("the test asked")
        if len(held) >= 11 and ending == "read_fault":
            raise faults.FaultInjectedIOError("the disk is gone, says the test")
        return real_read(self, name, buffer)

    def run_segment(self, *args, **kwargs):
        real_dispatch(self, *args, **kwargs)
        # the second source's last update is still on its buffer
        assert any(s.busy is not None for s in self._staging)
        raise RuntimeError("the segment failed, says the test")

    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    if ending == "segment_error":
        monkeypatch.setattr(JaxExecutor, "_run_segment", run_segment)
    error = {"segment_error": RuntimeError, "read_fault": faults.FaultInjectedIOError,
             "cancelled": ComputeCancelledError}[ending]
    with pytest.raises(error):
        ct.to_zarr(
            xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)),
            str(tmp_path / "c.zarr"), executor=executor, cancellation=token,
        )
    pair = held[0][0]
    assert all(lease is pair for lease, _ in held)
    # when the eleventh read began the chunk before it was still being taken in
    assert any(held[10][1])
    assert executor._staging is None and jx._STAGING_POOL == [pair]
    assert all(stage.busy is None and stage.buffer is not None for stage in pair)
    # and the next compute works with it
    monkeypatch.setattr(_LocalIO, "readinto", real_read)
    monkeypatch.setattr(JaxExecutor, "_run_segment", real_dispatch)
    cap = _Capture()
    ct.to_zarr(
        xp.add(ct.from_zarr(pa, spec=spec), ct.from_zarr(pb, spec=spec)),
        str(tmp_path / "d.zarr"), executor=JaxExecutor(), callbacks=[cap],
    )
    np.testing.assert_array_equal(ct.from_zarr(str(tmp_path / "d.zarr"), spec=spec).compute(), a + b)
    assert cap.stats["stage_reused_bytes"] == cap.stats["h2d_stream_bytes"] > 0
    assert jx._STAGING_POOL == [pair]
