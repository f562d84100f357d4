"""A resident array goes to its store chunk by chunk through the two reused
staging buffers its compute has leased from the process, the mirror image of
``test_preload_stream.py``.

``JaxExecutor._flush_chunks`` is a pipeline of depth two over the target's
chunk grid: the calling thread slices a chunk on the device, fetches it and,
where it leaves as 32-bit planes, joins them into one of the two page-aligned
buffers (``_join_planes(out=)``); a second thread writes the chunk before it
from the other buffer (``ZarrV2Array.__setitem__``, whose ``_write_chunk``
hands the file, the checksum and the codec a view of the array and makes no
``tobytes()`` copy). The stored bytes, the manifest, the order of the writes,
cancellation, injected faults and the spans are what the serial loop gave.
The CPU of these tests holds a real float64, so the device probe is forced
false where the planes are wanted (as ``test_d2h_planes.py`` does), and
float64 values are float32 pairs by construction."""

from __future__ import annotations

import gc
import json
import mmap
import os
import threading
import time
import tracemalloc
import zlib

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.runtime.executors.jax as jx
from cubed_tpu.observability.accounting import SPANS_ENV_VAR, TaskScope, task_scope
from cubed_tpu.observability.collect import TraceCollector
from cubed_tpu.runtime import faults, memory
from cubed_tpu.runtime.cancellation import CancellationToken, ComputeCancelledError
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.storage import integrity
from cubed_tpu.storage.store import ZarrV2Array, _LocalIO, open_zarr_array
from tests.utils import leased_staging

RNG = np.random.default_rng(35)
WRITER = "cubed-tpu-flush"


def _pairs(shape) -> np.ndarray:
    """float64 values that are float32 pairs, all a pair device can hold."""
    head = (RNG.standard_normal(shape) * 1e3).astype(np.float32)
    tail = (RNG.standard_normal(shape) * 1e-6).astype(np.float32)
    return head.astype(np.float64) + tail.astype(np.float64)


def _values(dtype, shape) -> np.ndarray:
    dtype = np.dtype(dtype)
    if dtype == np.float64:
        return _pairs(shape)
    if dtype.kind == "f":
        return RNG.standard_normal(shape).astype(dtype)
    info = np.iinfo(dtype)
    return RNG.integers(info.min, info.max, shape, dtype=dtype, endpoint=True)


@pytest.fixture(autouse=True)
def _empty_pool():
    """Every test starts as a process's first compute does, with no staging
    pair kept, and leaves none behind."""
    jx.release_staging_buffers()
    yield
    jx.release_staging_buffers()


@pytest.fixture
def pair_device(monkeypatch):
    """The device probe says float64 does not round-trip, and the crossover
    is below the tiny chunks of these tests: 64-bit chunks leave as planes."""
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 64)


def _on_device(host: np.ndarray, carry_bits: bool = False):
    import jax

    if host.dtype.fields is not None:
        return {k: _on_device(np.ascontiguousarray(host[k]), carry_bits) for k in host.dtype.names}
    if carry_bits and host.dtype == np.float64:
        host = host.view(np.uint64)
    return jax.device_put(host)


def _flush(tmp_path, host, chunks, name="t", executor=None, carry_bits=False, **store):
    """``host`` put on the device and flushed to a fresh target as the end
    of a compute does it, in a task scope: (target, executor, scope)."""
    z = open_zarr_array(
        str(tmp_path / f"{name}.zarr"), "w", shape=host.shape, dtype=host.dtype,
        chunks=chunks, **store,
    )
    executor = executor or JaxExecutor()
    executor._carry_bits = carry_bits
    res = jx._Resident(_on_device(host, carry_bits), host.nbytes, z)
    with task_scope(jx._SCOPE_SPANS) as scope, leased_staging(executor):
        executor._flush(res)
    return z, executor, scope


def _read_back(z) -> np.ndarray:
    again = open_zarr_array(z.store, "r")
    return again[...] if again.shape else again[()]


def _no_writer_left() -> bool:
    return not any(t.name.startswith(WRITER) for t in threading.enumerate())


# -- the same stored bytes, whichever way the chunk left ---------------------------


@pytest.mark.parametrize("dtype, carry_bits", [
    (np.float64, False), (np.float64, True), (np.int64, False), (np.uint64, False),
    (np.float32, False), (np.int32, False),
], ids=["float64", "float64_as_bits", "int64", "uint64", "float32", "int32"])
def test_streamed_target_is_the_direct_routes_bit_for_bit(tmp_path, monkeypatch, dtype, carry_bits):
    host = _values(dtype, (16, 12))
    if carry_bits:
        host = RNG.integers(0, 2**64, host.shape, dtype=np.uint64).view(np.float64)
    direct, ex_direct, _ = _flush(tmp_path, host, (8, 6), "direct", carry_bits=carry_bits)
    assert ex_direct.stats["flush_stream_bytes"] == 0 == ex_direct.stats["d2h_plane_bytes"]
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 64)
    streamed, ex, scope = _flush(tmp_path, host, (8, 6), "streamed", carry_bits=carry_bits)
    assert _read_back(streamed).tobytes() == _read_back(direct).tobytes() == host.tobytes()
    for key in ("0.0", "0.1", "1.0", "1.1"):
        with open(os.path.join(streamed.store, key), "rb") as a, \
                open(os.path.join(direct.store, key), "rb") as b:
            assert a.read() == b.read()
    wide = np.dtype(dtype).itemsize == 8
    assert ex.stats["d2h_bytes"] == host.nbytes
    assert ex.stats["d2h_plane_bytes"] == (host.nbytes if wide else 0)
    # what left as planes reached the store from a buffer, nothing was copied
    assert ex.stats["flush_stream_bytes"] == (host.nbytes if wide else 0)
    assert ex.stats["encode_copy_bytes"] == 0
    assert scope.bytes_written == host.nbytes and scope.chunks_written == 4
    assert _no_writer_left()


@pytest.mark.parametrize("shape, chunks", [
    ((29,), (8,)), ((13, 22), (5, 8)), ((7, 9, 11), (3, 4, 5)),
], ids=["1d", "2d", "3d"])
@pytest.mark.parametrize("dtype", [np.float64, np.int64])
def test_ragged_edge_chunks_are_padded_by_the_store_and_counted_as_copied(
    tmp_path, pair_device, shape, chunks, dtype
):
    host = _values(dtype, shape)
    z, ex, scope = _flush(tmp_path, host, chunks)
    assert _read_back(z).tobytes() == host.tobytes()
    full = edge = 0
    for idx in np.ndindex(*z.cdata_shape):
        extent = [min(c, s - i * c) for i, c, s in zip(idx, chunks, shape)]
        if extent == list(chunks):
            full += z._chunk_nbytes()
        else:
            edge += z._chunk_nbytes()
        # every file is a whole padded chunk
        assert os.path.getsize(os.path.join(z.store, z._chunk_key(idx))) == z._chunk_nbytes()
    assert ex.stats["flush_stream_bytes"] > 0 and edge > 0
    # a full chunk streams; an edge chunk is copied into its padding, once
    assert ex.stats["encode_copy_bytes"] == edge == scope.counters["encode_copy_bytes"]
    assert scope.bytes_written == full + edge
    assert ex.stats["d2h_bytes"] == host.nbytes


def _one_chunk():
    return _pairs((6, 5)), (6, 5)


def _zero_d():
    return np.array(2.5), ()


def _record():
    host = np.zeros((12, 8), dtype=[("x", np.float64), ("n", np.int64), ("flag", np.int32)])
    host["x"], host["n"] = _pairs((12, 8)), _values(np.int64, (12, 8))
    host["flag"] = np.arange(96).reshape(12, 8)
    return host, (6, 4)


def _empty():
    return np.zeros((0, 6)), (1, 3)


@pytest.mark.parametrize("make", [_one_chunk, _zero_d, _record, _empty],
                         ids=["one_chunk", "zero_d", "record", "empty"])
def test_one_chunk_zero_d_record_and_empty_targets(tmp_path, pair_device, make):
    host, chunks = make()
    z, ex, scope = _flush(tmp_path, host, chunks, carry_bits=host.dtype.fields is not None)
    assert _read_back(z).tobytes() == host.tobytes()
    (pair,) = jx._STAGING_POOL  # the lease came back
    if host.dtype.fields is not None:
        # the 8-byte fields leave as planes into fresh arrays and are copied
        # into the record: nothing of a record lies in a buffer
        assert ex.stats["d2h_plane_bytes"] == host.size * 16
        assert ex.stats["flush_stream_bytes"] == 0
        assert all(stage.buffer is None for stage in pair)
    elif host.shape == (6, 5):
        assert ex.stats["flush_stream_bytes"] == host.nbytes
    else:
        assert ex.stats["flush_stream_bytes"] == 0
    files = [n for n in os.listdir(z.store) if not n.startswith(".")]
    assert scope.chunks_written == len(files)
    assert scope.bytes_written == sum(os.path.getsize(os.path.join(z.store, n)) for n in files)
    assert _no_writer_left()


@pytest.mark.parametrize("compressor", [{"id": "zlib", "level": 1}, {"id": "lzma"}],
                         ids=["zlib", "lzma"])
def test_a_compressed_store_encodes_from_the_buffer(tmp_path, pair_device, compressor):
    host = _values(np.int64, (8, 8)) % 7
    z, ex, scope = _flush(tmp_path, host, (4, 4), compressor=compressor)
    assert _read_back(z).tobytes() == host.tobytes()
    assert ex.stats["flush_stream_bytes"] == host.nbytes
    assert ex.stats["encode_copy_bytes"] == 0
    assert 0 < scope.bytes_written < host.nbytes
    manifest = open_zarr_array(z.store, "r")._manifest()[0]
    for key, entry in manifest.items():
        with open(os.path.join(z.store, key), "rb") as f:
            stored = f.read()
        assert entry["n"] == len(stored) and entry["c"] == zlib.crc32(stored)


def test_manifest_lines_are_in_grid_order_with_the_crc_of_the_stored_bytes(tmp_path, pair_device):
    host = _pairs((12, 9))
    with integrity.scoped("write"):
        z, _, _ = _flush(tmp_path, host, (4, 3))
    with open(os.path.join(z.store, integrity.shard_name())) as f:
        lines = [json.loads(line) for line in f]
    grid = [z._chunk_key(idx) for idx in np.ndindex(*z.cdata_shape)]
    assert [line["k"] for line in lines] == grid and len(grid) == 9
    for line, idx in zip(lines, np.ndindex(*z.cdata_shape)):
        with open(os.path.join(z.store, line["k"]), "rb") as f:
            stored = f.read()
        i, j = idx
        assert stored == host[4 * i : 4 * i + 4, 3 * j : 3 * j + 3].tobytes()
        assert line["n"] == len(stored) and line["c"] == zlib.crc32(stored)


# -- the join writes where it is told to ------------------------------------------


def _planes_of(host):
    import jax

    on_device = jax.device_put(host)
    first, second, inexact = jax.device_get(jx._plane_program_of(on_device)[0](on_device))
    assert not inexact
    return first, second


@pytest.mark.parametrize("order", ["row_major", "column_major", "strided_view"])
@pytest.mark.parametrize("shape", [(40, 30), (1200, 900)], ids=["single", "threaded"])
@pytest.mark.parametrize("dtype", [np.float64, np.int64, np.uint64])
def test_join_planes_into_out_equals_the_fresh_result(dtype, shape, order):
    first, second = _planes_of(_values(dtype, shape))
    if order == "column_major":
        first, second = np.asfortranarray(first), np.asfortranarray(second)
    elif order == "strided_view":
        wide = [np.zeros((2 * shape[0], 3 * shape[1]), np.uint32) for _ in range(2)]
        for base, plane in zip(wide, (first, second)):
            base[::2, ::3] = plane
        first, second = wide[0][::2, ::3], wide[1][::2, ::3]
    assert jx._planes_strided(first, second) == (order != "row_major")
    fresh = jx._join_planes(first, second, np.dtype(dtype))
    stage = jx._Staging()
    out = stage.array(shape, np.dtype(dtype))
    out.view(np.uint8)[...] = 0xAB  # what a chunk before it left there
    got = jx._join_planes(first, second, np.dtype(dtype), out)
    assert got is out and stage.holds(got) and not stage.holds(fresh)
    assert got.flags.c_contiguous and got.tobytes() == fresh.tobytes()
    assert out.ctypes.data % mmap.PAGESIZE == 0


def test_a_staging_array_is_the_head_of_a_buffer_that_only_grows():
    stage = jx._Staging()
    small = stage.array((4, 4), np.dtype(np.float64))
    kept = stage.buffer
    assert small.nbytes == 128 == kept.nbytes and small.ctypes.data == kept.ctypes.data
    large = stage.array((8, 8), np.dtype(np.int64))
    assert stage.buffer is not kept and stage.buffer.nbytes == 512
    kept = stage.buffer
    again = stage.array((3, 5), np.dtype(np.uint64))
    assert stage.buffer is kept and again.shape == (3, 5) and again.flags.c_contiguous
    assert stage.holds(again) and stage.holds(large) and not stage.holds(np.zeros(4))
    assert not jx._Staging().holds(again)


# -- the two buffers ----------------------------------------------------------------


def _spy_on_the_pipeline(monkeypatch, write_s=0.0):
    """Records of every join (``("join", k, address of out, thread)`` at its
    start) and of every write (``("write", ...)`` at its start, ``("wrote",
    ...)`` at its return), in the order they happened. A write sleeps
    ``write_s`` first and then says whether its value still held its chunk."""
    seen, lock = [], threading.Lock()
    real_join, real_set = jx._join_planes, ZarrV2Array.__setitem__

    def note(kind, *record):
        with lock:
            k = sum(r[0] == kind for r in seen)  # which chunk of the flush
            seen.append((kind, *record, k, threading.current_thread().name))

    def join(first, second, dtype, out=None):
        note("join", None if out is None else out.ctypes.data)
        return real_join(first, second, dtype, out)

    def setitem(self, key, value):
        before = value.tobytes()
        note("write", value.ctypes.data)
        time.sleep(write_s)
        real_set(self, key, value)
        note("wrote", value.ctypes.data, value.tobytes() == before)

    monkeypatch.setattr(jx, "_join_planes", join)
    monkeypatch.setattr(ZarrV2Array, "__setitem__", setitem)
    return seen


def test_two_buffers_take_turns_and_none_is_rewritten_before_its_write_returned(
    tmp_path, pair_device, monkeypatch
):
    host = _pairs((24, 8))  # six chunks
    seen = _spy_on_the_pipeline(monkeypatch, write_s=0.03)
    z, ex, _ = _flush(tmp_path, host, (4, 8))
    assert _read_back(z).tobytes() == host.tobytes()
    assert ex._staging is None
    (staging,) = jx._STAGING_POOL
    joins = [r for r in seen if r[0] == "join"]
    writes = [r for r in seen if r[0] == "write"]
    wrote = [r for r in seen if r[0] == "wrote"]
    assert len(joins) == len(writes) == len(wrote) == 6
    addresses = [r[1] for r in joins]
    # the leased pair, page-aligned, alternating, written from in place
    assert addresses == [s.buffer.ctypes.data for s in staging] * 3
    assert len(set(addresses)) == 2 and all(a % mmap.PAGESIZE == 0 for a in addresses)
    assert [r[1] for r in writes] == addresses
    assert all(s.buffer.nbytes == 4 * 8 * 8 for s in staging)
    # fetches on the calling thread, every write on the one writer, in order
    assert {r[-1] for r in joins} == {threading.current_thread().name}
    assert all(r[-1].startswith(WRITER) for r in writes)
    # no write saw its buffer change under it
    assert all(r[2] for r in wrote)
    assert [r[-2] for r in joins] == [r[-2] for r in wrote] == list(range(6))
    for k in range(6):
        # chunk k + 1 is joined while chunk k is written ...
        if k + 1 < 6:
            assert seen.index(joins[k + 1]) < seen.index(wrote[k])
        # ... and chunk k + 2, into chunk k's buffer, only after that write
        if k + 2 < 6:
            assert seen.index(wrote[k]) < seen.index(joins[k + 2])
        # one writer: a write starts after the one before it has returned
        if k:
            assert seen.index(wrote[k - 1]) < seen.index(writes[k])
    assert _no_writer_left()


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "disarmed"])
def test_a_slow_writer_is_what_the_flush_waits_for_and_it_says_so(
    tmp_path, pair_device, monkeypatch, armed
):
    """Every chunk write slowed by 50 ms: the fetch of chunk k + 1 hides
    behind the write of chunk k and the executor's thread then waits for the
    writer. ``write_wait_us`` says for how long, armed or not; armed, every
    write waited for is one ``jax.write_wait`` span inside ``jax.flush``."""
    real = ZarrV2Array._write_chunk

    def write_chunk(self, *args, **kwargs):
        time.sleep(0.05)
        return real(self, *args, **kwargs)

    monkeypatch.setattr(ZarrV2Array, "_write_chunk", write_chunk)
    if armed:
        monkeypatch.setenv(SPANS_ENV_VAR, "1")
    else:
        monkeypatch.delenv(SPANS_ENV_VAR, raising=False)
    host, chunks = _pairs((24, 8)), 6
    z, ex, scope = _flush(tmp_path, host, (4, 8))
    assert _read_back(z).tobytes() == host.tobytes()
    assert type(ex.stats["write_wait_us"]) is int
    assert ex.stats["write_wait_us"] >= (chunks - 1) * 30_000
    waits = [s for s in scope.spans if s["name"] == "jax.write_wait"]
    if not armed:
        assert scope.spans == [] and scope.spans_dropped == 0
        return
    (flush,) = [s for s in scope.spans if s["name"] == "jax.flush"]
    assert [s["attrs"] for s in waits] == [{"chunk": k} for k in range(chunks)]
    assert all(s["cat"] == "wait" and s["parent"] == flush["id"] for s in waits)
    assert scope.spans_dropped == 0
    # the span and the counter time the same wait, on two clocks
    assert sum(s["dur"] for s in waits) == pytest.approx(
        ex.stats["write_wait_us"] / 1e6, abs=0.02
    )
    # on this thread's line every stretch of the flush has a name: what the
    # fetches and the waits leave of it is the slicing between them
    own = [s for s in scope.spans if s.get("parent") == flush["id"]
           and "thread" not in s.get("attrs", {})]
    assert {s["name"] for s in own} == {"jax.device_wait", "jax.d2h", "jax.write_wait"}
    assert flush["dur"] - sum(s["dur"] for s in own) < 0.05
    # and the writer's spans say whose they are
    theirs = [s for s in scope.spans if "thread" in s.get("attrs", {})]
    assert {s["name"] for s in theirs} == {"chunk_encode", "storage_write", "fsync"}
    assert all(s["attrs"]["thread"].startswith(WRITER) for s in theirs)


def test_the_buffers_are_the_preloads_and_a_larger_chunk_grows_them(tmp_path, pair_device):
    src = open_zarr_array(str(tmp_path / "src.zarr"), "w", shape=(8, 8), dtype="f8", chunks=(4, 4))
    src[...] = _pairs((8, 8))
    ex = JaxExecutor()
    with ex._lease() as staging:
        ex._device_put(src, (8, 8), src.chunkset())
        kept = [s.buffer for s in staging]
        assert [b.nbytes for b in kept] == [128, 128]
        _flush(tmp_path, _pairs((8, 8)), (4, 4), "same", ex)
        assert all(s.buffer is k for s, k in zip(staging, kept))
        # this compute made the buffers: nothing of it counts as reused
        assert ex.stats["stage_reused_bytes"] == 0
        _flush(tmp_path, _pairs((16, 8)), (8, 8), "larger", ex)
        assert [s.buffer.nbytes for s in staging] == [512, 512]
        assert ex.stats["flush_stream_bytes"] == 8 * 8 * 8 + 16 * 8 * 8
        assert ex.stats["stage_reused_bytes"] == 0
    # the next executor's flush finds them, and says so
    _, other, _ = _flush(tmp_path, _pairs((16, 8)), (8, 8), "next")
    (pair,) = jx._STAGING_POOL
    assert pair is staging and [s.buffer.nbytes for s in pair] == [512, 512]
    assert other.stats["stage_reused_bytes"] == other.stats["flush_stream_bytes"] == 16 * 8 * 8
    # a chunk through a buffer that had to be made larger does not count
    _, third, _ = _flush(tmp_path, _pairs((32, 8)), (16, 8), "grown")
    assert third.stats["flush_stream_bytes"] == 32 * 8 * 8
    assert third.stats["stage_reused_bytes"] == 0
    assert [s.buffer.nbytes for s in pair] == [1024, 1024] and jx._STAGING_POOL == [pair]


def test_a_flush_holds_two_chunks_and_one_pair_of_planes_on_the_host(tmp_path, pair_device):
    """tracemalloc over a four-chunk flush of 2 MB chunks: the two staging
    buffers and the planes of the chunk in flight, and no joined array nor
    ``tobytes`` copy beside them."""
    chunk = 512 * 512 * 8
    host = _pairs((1024, 1024))
    _flush(tmp_path, host, (512, 512), "warm")  # compile outside the count
    gc.collect()
    tracemalloc.start()
    try:
        z, ex, _ = _flush(tmp_path, host, (512, 512), "counted")
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert _read_back(z).tobytes() == host.tobytes()
    assert ex.stats["flush_stream_bytes"] == host.nbytes
    assert peak <= 2 * chunk + chunk + chunk // 4, peak / chunk


# -- errors and cancellation ----------------------------------------------------------


def _failing_writes(monkeypatch, fail_at: int, make_error):
    """``write_bytes_atomic`` of chunk files counted; the ``fail_at``-th raises."""
    calls = []
    real = _LocalIO.write_bytes_atomic

    def write(self, name, data, inject=True):
        if name.startswith("."):
            return real(self, name, data, inject)
        calls.append((name, threading.current_thread().name))
        if len(calls) == fail_at:
            raise make_error()
        return real(self, name, data, inject)

    monkeypatch.setattr(_LocalIO, "write_bytes_atomic", write)
    return calls


@pytest.mark.parametrize("planes", [True, False], ids=["planes", "direct"])
def test_a_write_error_on_the_second_thread_is_raised_from_flush(tmp_path, monkeypatch, planes):
    if planes:
        monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
        monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 64)
    calls = _failing_writes(monkeypatch, 2, lambda: OSError("disk full, says the test"))
    fetched = []
    real = JaxExecutor._to_host
    monkeypatch.setattr(
        JaxExecutor, "_to_host",
        lambda self, *a, **k: fetched.append(1) or real(self, *a, **k),
    )
    with pytest.raises(OSError, match="disk full"):
        _flush(tmp_path, _pairs((24, 8)), (4, 8))
    # the failed write was the last one started; one more chunk had been
    # fetched beside it, none after
    assert [name for name, _ in calls] == ["0.0", "1.0"]
    assert all(thread.startswith(WRITER) for _, thread in calls)
    assert len(fetched) == 3
    assert sorted(n for n in os.listdir(tmp_path / "t.zarr") if not n.startswith(".")) == ["0.0"]
    assert _no_writer_left()


def test_a_fetch_error_waits_for_the_write_in_flight_and_keeps_its_records(
    tmp_path, pair_device, monkeypatch
):
    real = JaxExecutor._to_host
    fetched = []

    def to_host(self, *args, **kwargs):
        fetched.append(1)
        if len(fetched) == 3:
            raise RuntimeError("the device is gone, says the test")
        return real(self, *args, **kwargs)

    monkeypatch.setattr(JaxExecutor, "_to_host", to_host)
    seen = _spy_on_the_pipeline(monkeypatch, write_s=0.05)
    z = open_zarr_array(str(tmp_path / "t.zarr"), "w", shape=(24, 8), dtype="f8", chunks=(4, 8))
    ex = JaxExecutor()
    res = jx._Resident(_on_device(_pairs((24, 8))), 24 * 8 * 8, z)
    with task_scope(jx._SCOPE_SPANS) as scope, pytest.raises(RuntimeError, match="device is gone"):
        with leased_staging(ex):
            ex._flush(res)
    # chunk 1's write was in flight: it finished, and is accounted for
    assert [r[0] for r in seen].count("wrote") == 2
    assert scope.chunks_written == 2 and scope.bytes_written == 2 * 4 * 8 * 8
    assert ex.stats["flush_stream_bytes"] == 2 * 4 * 8 * 8
    assert _no_writer_left()


def test_injected_storage_faults_engage_on_the_writer_thread(tmp_path, pair_device):
    """The injector's storage faults fire only where the calling thread has a
    task scope: the writer has one of its own."""
    host = _pairs((8, 8))
    with faults.scoped(faults.FaultConfig(seed=5, storage_write_failure_rate=1.0)):
        with pytest.raises(faults.FaultInjectedIOError):
            _flush(tmp_path, host, (4, 4), "failing")
    # (a killed writer's partial temp file may stay: ``storage_write_leaves_tmp``)
    assert all(n.startswith(".") or n.endswith(".tmp") for n in os.listdir(tmp_path / "failing.zarr"))
    # a corrupted write is handed a view and returns its own bytes: the file
    # is wrong, the manifest holds the checksum of what was meant
    with integrity.scoped("write"), faults.scoped(
        faults.FaultConfig(seed=5, storage_corrupt_rate=1.0)
    ):
        z, ex, scope = _flush(tmp_path, host, (4, 4), "rotten")
    assert ex.stats["flush_stream_bytes"] == host.nbytes
    manifest = open_zarr_array(z.store, "r")._manifest()[0]
    for (i, j), key in zip(np.ndindex(2, 2), ("0.0", "0.1", "1.0", "1.1")):
        meant = host[4 * i : 4 * i + 4, 4 * j : 4 * j + 4].tobytes()
        with open(os.path.join(z.store, key), "rb") as f:
            assert f.read() != meant
        assert manifest[key]["c"] == zlib.crc32(meant) and manifest[key]["n"] == len(meant)
    assert _no_writer_left()


def test_without_a_scope_on_the_caller_the_writer_has_none_either(tmp_path, pair_device):
    """Outside a task scope the injector leaves storage alone and bytes go to
    the registry, on either thread, as in the serial loop."""
    host = _pairs((8, 8))
    z = open_zarr_array(str(tmp_path / "t.zarr"), "w", shape=(8, 8), dtype="f8", chunks=(4, 4))
    ex = JaxExecutor()
    with faults.scoped(faults.FaultConfig(seed=5, storage_write_failure_rate=1.0)):
        with leased_staging(ex):
            ex._flush(jx._Resident(_on_device(host), host.nbytes, z))
    assert _read_back(z).tobytes() == host.tobytes()
    # nothing observed, nothing claimed
    assert ex.stats["flush_stream_bytes"] == 0 == ex.stats["encode_copy_bytes"]


class _Capture:
    def __init__(self):
        self.stats, self.events = None, []

    def on_task_end(self, event):
        self.events.append(event)

    def on_compute_end(self, event):
        self.stats = event.executor_stats


@pytest.fixture
def spec(tmp_path):
    return ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB", reserved_mem=0)


def _sum_of_two(tmp_path, spec, shape=(16, 6), chunks=(4, 3)):
    grids = [RNG.integers(0, 2**30, size=shape).astype(np.float64) / 2**10 for _ in "ab"]
    arrays = []
    for name, grid in zip("ab", grids):
        path = str(tmp_path / f"{name}.zarr")
        stored = open_zarr_array(path, "w", shape=shape, dtype="f8", chunks=chunks)
        stored[...] = grid
        arrays.append(ct.from_zarr(path, spec=spec))
    return xp.add(*arrays), grids[0] + grids[1]


def test_a_cancellation_seen_on_the_second_thread_is_raised_from_the_compute(
    tmp_path, spec, pair_device, monkeypatch
):
    """The writer finds the compute's token through the caller's context."""
    token = CancellationToken()
    calls = []
    real = _LocalIO.write_bytes_atomic

    def write(self, name, data, inject=True):
        if self.root.endswith("out.zarr") and not name.startswith("."):
            calls.append((name, threading.current_thread().name))
            if len(calls) == 2:
                token.cancel("the test asked")
        return real(self, name, data, inject)

    monkeypatch.setattr(_LocalIO, "write_bytes_atomic", write)
    expr, _ = _sum_of_two(tmp_path, spec)
    with pytest.raises(ComputeCancelledError):
        ct.to_zarr(expr, str(tmp_path / "out.zarr"), executor=JaxExecutor(), cancellation=token)
    # the write in flight finished; the next chunk's was never started
    assert [name for name, _ in calls] == ["0.0", "0.1"]
    assert all(thread.startswith(WRITER) for _, thread in calls)
    assert _no_writer_left()


@pytest.mark.parametrize("ending", ["write_fault", "write_error", "cancelled"])
def test_the_lease_comes_back_however_the_flush_ends(
    tmp_path, spec, pair_device, monkeypatch, ending
):
    """An injected storage fault in a chunk write, an error of the disk and
    a cancellation seen by the writer each end the compute from inside its
    flush: the pair it had leased is the pool's again, with no device update
    left on either buffer, and the executor holds none."""
    token, ex, held = CancellationToken(), JaxExecutor(), []
    real = _LocalIO.write_bytes_atomic

    def write(self, name, data, inject=True):
        if self.root.endswith("out.zarr") and not name.startswith("."):
            held.append(ex._staging)
            if len(held) == 2 and ending == "write_error":
                raise OSError("disk full, says the test")
            if len(held) == 2 and ending == "cancelled":
                token.cancel("the test asked")
        return real(self, name, data, inject)

    real_flush = JaxExecutor._flush

    def flush(self, res):
        # the injector armed for this flush alone: the target's metadata is written
        rate = 0.0 if held else 1.0
        with faults.scoped(faults.FaultConfig(seed=5, storage_write_failure_rate=rate)):
            real_flush(self, res)

    monkeypatch.setattr(_LocalIO, "write_bytes_atomic", write)
    if ending == "write_fault":
        monkeypatch.setattr(JaxExecutor, "_flush", flush)
    expr, _ = _sum_of_two(tmp_path, spec)
    error = {"write_fault": faults.FaultInjectedIOError, "write_error": OSError,
             "cancelled": ComputeCancelledError}[ending]
    with pytest.raises(error):
        ct.to_zarr(expr, str(tmp_path / "out.zarr"), executor=ex, cancellation=token)
    assert held and all(pair is held[0] for pair in held)
    assert ex._staging is None and jx._STAGING_POOL == [held[0]]
    assert all(stage.busy is None and stage.buffer is not None for stage in held[0])
    assert _no_writer_left()
    # and the next compute works with it
    cap = _Capture()
    expr, want = _sum_of_two(tmp_path, spec)
    ct.to_zarr(expr, str(tmp_path / "again.zarr"), executor=JaxExecutor(), callbacks=[cap])
    assert open_zarr_array(str(tmp_path / "again.zarr"), "r")[...].tobytes() == want.tobytes()
    assert cap.stats["stage_reused_bytes"] == (
        cap.stats["h2d_stream_bytes"] + cap.stats["flush_stream_bytes"]
    ) == 3 * want.nbytes
    assert jx._STAGING_POOL == [held[0]]


# -- whose the buffers are --------------------------------------------------------------


def test_two_computes_at_once_never_write_the_same_buffer(tmp_path, spec, pair_device, monkeypatch):
    """Two threads compute at once in a process whose pool holds a pair: one
    leases it, the other finds it gone and works with a fresh pair of its
    own, waiting for nobody. Every read-into and every join of the one lands
    at another address than any of the other's, both targets are right to
    the bit, and the pool holds one pair afterwards."""
    # ``Plan.execute`` arms the memory guard for the process by save and
    # restore (``memory.scoped``, with its environment variable), which two
    # computes at once interleave: whatever they leave, put back what was
    monkeypatch.setattr(memory, "_active", memory._active)
    if memory.MEMORY_GUARD_ENV_VAR in os.environ:
        monkeypatch.setenv(memory.MEMORY_GUARD_ENV_VAR, os.environ[memory.MEMORY_GUARD_ENV_VAR])
    else:
        monkeypatch.delenv(memory.MEMORY_GUARD_ENV_VAR, raising=False)
    for name in ("warm", "one", "two"):
        (tmp_path / name).mkdir()
    expr, _ = _sum_of_two(tmp_path / "warm", spec)
    ct.to_zarr(expr, str(tmp_path / "warm" / "out.zarr"), executor=JaxExecutor())
    (pooled,) = jx._STAGING_POOL
    jobs = {name: (*_sum_of_two(tmp_path / name, spec), JaxExecutor(), _Capture())
            for name in ("one", "two")}
    both_inside = threading.Barrier(2, timeout=60)
    leases, addresses, lock = {}, {"one": set(), "two": set()}, threading.Lock()
    real_read, real_join = _LocalIO.readinto, jx._join_planes

    def note(address):
        me = threading.current_thread().name
        if me not in jobs:
            return
        with lock:
            first = me not in leases
            # kept, so that no buffer is freed and its address used again
            leases.setdefault(me, jobs[me][2]._staging)
            addresses[me].add(address)
        if first:
            both_inside.wait()  # each holds its lease before either goes on

    def readinto(self, name, buffer):
        note(np.asarray(buffer).__array_interface__["data"][0])
        return real_read(self, name, buffer)

    def join(first, second, dtype, out=None):
        assert out is not None
        note(out.ctypes.data)
        return real_join(first, second, dtype, out)

    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    monkeypatch.setattr(jx, "_join_planes", join)
    errors = []

    def run(name):
        expr, _, ex, cap = jobs[name]
        try:
            ct.to_zarr(expr, str(tmp_path / name / "out.zarr"), executor=ex, callbacks=[cap])
        except BaseException as error:  # shown by the test's thread
            errors.append(error)

    threads = [threading.Thread(target=run, args=(name,), name=name) for name in jobs]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(120)
    assert not errors and not any(thread.is_alive() for thread in threads)
    for name, (_, want, _, _) in jobs.items():
        assert open_zarr_array(str(tmp_path / name / "out.zarr"), "r")[...].tobytes() == want.tobytes()
    assert leases["one"] is not leases["two"]
    assert all(len(seen) == 2 for seen in addresses.values())
    assert not addresses["one"] & addresses["two"]
    # one of them had the pool's pair and found its buffers made, the other made its own
    mine = [name for name in jobs if leases[name] is pooled]
    assert len(mine) == 1
    for name, (_, want, _, cap) in jobs.items():
        staged = cap.stats["h2d_stream_bytes"] + cap.stats["flush_stream_bytes"]
        assert staged == 3 * want.nbytes
        assert cap.stats["stage_reused_bytes"] == (staged if name in mine else 0)
    # the pool never holds more than one pair: the first to end gave its own back
    assert len(jx._STAGING_POOL) == 1 and jx._STAGING_POOL[0] in leases.values()
    assert all(stage.busy is None for pair in leases.values() for stage in pair)
    assert _no_writer_left()


def test_nothing_a_compute_returns_lies_in_a_pooled_buffer(tmp_path, spec, pair_device):
    """What goes through a staging buffer goes to the device or to the store's
    file: the array ``compute`` returns is read back from the store and shares
    no memory with the pair the pool keeps, so the next compute, which writes
    other values through the same two buffers, leaves it as it was."""
    expr, want = _sum_of_two(tmp_path, spec)
    cap = _Capture()
    got = expr.compute(executor=JaxExecutor(), callbacks=[cap])
    assert got.tobytes() == want.tobytes()
    assert cap.stats["flush_stream_bytes"] == want.nbytes  # it did go through them
    (pair,) = jx._STAGING_POOL
    assert all(stage.buffer is not None and stage.busy is None for stage in pair)
    assert not any(np.may_share_memory(got, stage.buffer) for stage in pair)
    (tmp_path / "next").mkdir()
    other, other_want = _sum_of_two(tmp_path / "next", spec)
    again = other.compute(executor=JaxExecutor(), callbacks=[cap])
    assert cap.stats["stage_reused_bytes"] == 3 * want.nbytes
    assert again.tobytes() == other_want.tobytes() != want.tobytes()
    assert got.tobytes() == want.tobytes()
    assert not any(np.may_share_memory(again, stage.buffer) for stage in pair)


# -- spans and counters --------------------------------------------------------------


def test_the_flushs_event_holds_both_threads_spans_bytes_and_chunks(tmp_path, spec, pair_device):
    expr, want = _sum_of_two(tmp_path, spec)
    tc, cap = TraceCollector(trace_dir=None), _Capture()
    ct.to_zarr(expr, str(tmp_path / "out.zarr"), executor=JaxExecutor(), callbacks=[tc, cap])
    out = open_zarr_array(str(tmp_path / "out.zarr"), "r")
    assert out[...].tobytes() == want.tobytes()
    (event,) = [e for e in cap.events if e.chunk_key == jx._FLUSH_KEY]
    assert event.spans_dropped == 0 and cap.stats["spans_dropped"] == 0
    assert event.bytes_written == want.nbytes and event.chunks_written == out.nchunks == 8
    by_id = {s["id"]: s for s in event.spans}
    assert len(by_id) == len(event.spans), "span ids repeat after the fold"
    names = [s["name"] for s in event.spans]
    for name, n in (("jax.flush", 1), ("jax.device_wait", 8), ("jax.d2h", 8),
                    ("jax.write_wait", 8),
                    ("chunk_encode", 8), ("storage_write", 8), ("fsync", 16)):
        assert names.count(name) == n, name
    # what the writer thread recorded says so; this thread's spans do not
    for s in event.spans:
        on_writer = s["name"] in ("chunk_encode", "storage_write", "fsync")
        assert s.get("attrs", {}).get("thread", "").startswith(WRITER) == on_writer, s
    (flush,) = [s for s in event.spans if s["name"] == "jax.flush"]
    for s in event.spans:
        if s["name"] == "fsync":
            parent = by_id[s["parent"]]
            assert parent["name"] == "storage_write"
            assert parent["ts"] <= s["ts"] + 1e-6
            assert s["ts"] + s["dur"] <= parent["ts"] + parent["dur"] + 1e-6
        elif s is not flush:
            # what the writer recorded at its top level hangs under the flush
            assert s["parent"] == flush["id"], s["name"]
            assert flush["ts"] <= s["ts"] + 1e-6
            assert s["ts"] + s["dur"] <= flush["ts"] + flush["dur"] + 1e-6
    encodes = [s for s in event.spans if s["name"] == "chunk_encode"]
    assert all(s["attrs"]["copied"] is False for s in encodes)
    assert sorted(s["attrs"]["key"] for s in encodes) == sorted(
        out._chunk_key(idx) for idx in np.ndindex(*out.cdata_shape)
    )
    # the totals by name, which the benchmark's readers read
    assert cap.stats["span_n"]["fsync"] == 16 + 2  # and the target's .zarray
    assert cap.stats["span_s"]["storage_write"] >= (
        cap.stats["span_s"]["storage_write"] - cap.stats["span_self_s"]["storage_write"]
    ) > 0
    assert cap.stats["flush_stream_bytes"] == cap.stats["d2h_bytes"] == want.nbytes
    assert cap.stats["encode_copy_bytes"] == 0
    # the flush's self time is this thread's: the writer's spans ran beside
    # it, and only the fetches and the waits are taken from it
    own = sum(s["dur"] for s in event.spans if s["name"] in
              ("jax.device_wait", "jax.d2h", "jax.write_wait"))
    assert cap.stats["span_self_s"]["jax.flush"] == pytest.approx(flush["dur"] - own)
    assert 0 < cap.stats["span_self_s"]["jax.flush"] < flush["dur"]
    assert 0 < cap.stats["checksum_us"] <= cap.stats["span_self_s"]["storage_write"] * 1e6
    assert _no_writer_left()


def test_the_counters_are_present_and_zero_where_nothing_streamed(tmp_path, spec):
    """A device with a real float64 hands every chunk over as its own array."""
    expr, want = _sum_of_two(tmp_path, spec)
    cap = _Capture()
    ct.to_zarr(expr, str(tmp_path / "out.zarr"), executor=JaxExecutor(), callbacks=[cap])
    assert open_zarr_array(str(tmp_path / "out.zarr"), "r")[...].tobytes() == want.tobytes()
    assert cap.stats["flush_stream_bytes"] == 0 and cap.stats["encode_copy_bytes"] == 0
    assert cap.stats["d2h_bytes"] == want.nbytes
    # nor where nothing was flushed
    a = ct.from_array(np.arange(36.0).reshape(6, 6), chunks=(3, 3), spec=spec)
    assert float(xp.sum(a).compute(executor=JaxExecutor(), callbacks=[cap])) == 630.0
    assert cap.stats["flush_stream_bytes"] == 0 and cap.stats["encode_copy_bytes"] == 0


def test_a_ragged_compute_says_how_much_the_store_copied(tmp_path, spec, pair_device):
    expr, want = _sum_of_two(tmp_path, spec, shape=(10, 6), chunks=(4, 3))
    cap = _Capture()
    ct.to_zarr(expr, str(tmp_path / "out.zarr"), executor=JaxExecutor(), callbacks=[cap])
    assert open_zarr_array(str(tmp_path / "out.zarr"), "r")[...].tobytes() == want.tobytes()
    chunk = 4 * 3 * 8
    assert cap.stats["flush_stream_bytes"] == 4 * chunk
    assert cap.stats["encode_copy_bytes"] == 2 * chunk
    assert cap.stats["d2h_bytes"] == want.nbytes


def test_a_spill_flush_takes_the_pipeline_and_no_buffer(tmp_path, spec, pair_device):
    """A flush that makes room fetches directly (``d2h_plane_no_room``): the
    same two threads, nothing in a buffer, nothing claimed."""
    a = RNG.integers(0, 2**30, size=(8, 6)).astype(np.float64) / 2**10
    path = str(tmp_path / "a.zarr")
    stored = open_zarr_array(path, "w", shape=a.shape, dtype="f8", chunks=(4, 3))
    stored[...] = a
    src = ct.from_zarr(path, spec=spec)
    ex = JaxExecutor(device_mem=2 * a.nbytes + 8, fuse_plan=False)
    out, kept = str(tmp_path / "sum.zarr"), str(tmp_path / "kept.zarr")
    ct.store([xp.add(src, src), src.rechunk((2, 6))], [out, kept], executor=ex)
    assert open_zarr_array(out, "r")[...].tobytes() == (a + a).tobytes()
    assert open_zarr_array(kept, "r")[...].tobytes() == a.tobytes()
    assert ex.stats["d2h_plane_no_room"] >= 1
    assert ex.stats["flush_stream_bytes"] < ex.stats["d2h_bytes"]
    assert _no_writer_left()


# -- a scope folded into another -------------------------------------------------------


@pytest.mark.parametrize("thread", [None, WRITER + "_0"], ids=["same_thread", "helper_thread"])
def test_fold_renumbers_spans_and_keeps_parents(thread):
    outer, inner = TaskScope(max_spans=8), TaskScope()
    assert outer.thread == inner.thread == threading.current_thread().name
    # a scope is its thread's: what another thread's scope hands over says so
    mark = {}
    if thread is not None:
        inner.thread, mark = thread, {"thread": thread}
    outer.add_span("a", 0.0, 5.0, span_id=0)
    outer._next_id, outer._open = 3, [2]  # span 2 is open: the flush
    inner.add_span("w", 1.0, 2.0, span_id=1, key="0.0")
    inner.add_span("f", 1.5, 1.75, span_id=2, parent=1)
    inner._next_id = 3
    inner.bytes_written, inner.chunks_written = 128, 1
    inner.counters["encode_copy_bytes"] = 64
    outer.counters["encode_copy_bytes"] = 1
    outer.fold(inner)
    assert outer.bytes_written == 128 and outer.chunks_written == 1
    assert outer.counters == {"encode_copy_bytes": 65}
    w, f = outer.spans[1:]
    assert (w["id"], w["parent"], w["attrs"]) == (4, 2, {"key": "0.0", **mark})
    assert (f["id"], f["parent"], f["dur"]) == (5, 4, 0.25)
    assert f.get("attrs", {}) == mark
    assert outer._next_id == 6 and inner.spans[0]["id"] == 1  # the source is left alone
    assert inner.spans[0]["attrs"] == {"key": "0.0"} and "attrs" not in inner.spans[1]
    # a second scope lands above the first; what finds no room is counted
    outer.max_spans = 4
    outer.fold(inner)
    assert [s["id"] for s in outer.spans] == [0, 4, 5, 7] and outer.spans_dropped == 1
