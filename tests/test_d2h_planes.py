"""64-bit values leave a device without native float64 as 32-bit planes.

``JaxExecutor._to_host`` splits a large 64-bit value on the device
(``_split_planes``), fetches the two planes together and puts the host array
together with numpy (``_join_planes``); the result is bit for bit what the
direct fetch gives. The CPU of these tests holds a real float64, so the
device probe is forced false where the route is wanted, and float64 values
are float32 pairs by construction (``f64(head) + f64(tail)``), which is all
that such a device can hold."""

from __future__ import annotations

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.runtime.executors.jax as jx
from cubed_tpu.observability.collect import TraceCollector
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.storage.store import open_zarr_array

F32 = np.finfo(np.float32)
RNG = np.random.default_rng(27)


def _pairs(head, tail) -> np.ndarray:
    """float64 values that are float32 pairs: ``f64(head) + f64(tail)``."""
    return np.asarray(head, np.float32).astype(np.float64) + np.asarray(
        tail, np.float32
    ).astype(np.float64)


def _as_pair(values) -> np.ndarray:
    """What a pair device holds of ``values``: rounded to head + tail."""
    values = np.asarray(values, np.float64)
    head = values.astype(np.float32)
    return _pairs(head, (values - head.astype(np.float64)).astype(np.float32))


def _through_planes(host: np.ndarray):
    """(joined host array, the split's flag) of ``host`` put on the device."""
    import jax

    on_device = jax.device_put(host)
    first, second, inexact = jax.device_get(jx._plane_program_of(on_device)[0](on_device))
    assert first.dtype == second.dtype == np.uint32
    return jx._join_planes(first, second, host.dtype), bool(inexact)


INTEGERS = {
    "random_bits": RNG.integers(0, 2**64, size=(7, 33), dtype=np.uint64),
    "zero": np.zeros(5, np.uint64),
    "all_ones": np.full((3, 2, 2), 2**64 - 1, np.uint64),
    "sign_bit": np.array([2**63, 2**63 - 1, 2**32, 2**32 - 1, 2**31, 1], np.uint64),
}


@pytest.mark.parametrize("dtype", [np.int64, np.uint64])
@pytest.mark.parametrize("case", sorted(INTEGERS))
def test_integers_split_and_join_bit_for_bit(case, dtype):
    host = INTEGERS[case].view(dtype)
    joined, inexact = _through_planes(host)
    assert not inexact
    assert joined.dtype == host.dtype and joined.shape == host.shape
    assert joined.tobytes() == host.tobytes()


PAIRS = {
    "random_pairs": _pairs(
        RNG.standard_normal((9, 31)) * 1e3, RNG.standard_normal((9, 31)) * 1e-6
    ),
    "tail_is_zero": _pairs([1.5, -2.0**100, 3.0, 2.0**-60], [0.0] * 4),
    # a NaN's sign and payload live in its head
    "nans": np.array(
        [0x7FF8000000000000, 0xFFF8000000000000, 0x7FFFFFFFE0000000, 0xFFF8000020000000],
        np.uint64,
    ).view(np.float64),
    # of ``_float64_round_trips``; its 1e300 is an infinity on such a device
    "probe_values": _as_pair([np.pi, 1.0 + 2.0**-52, 1e-300]),
    "top_of_float32": _pairs([F32.max, -F32.max, F32.max], [0.0, 0.0, -(2.0**78)]),
    "plus_zero": np.zeros(4),
    "just_above_the_small_heads": _pairs([2.0**-73, -(2.0**-73)], [2.0**-100, 2.0**-126]),
}


@pytest.mark.parametrize("case", sorted(PAIRS))
def test_float32_pairs_split_and_join_bit_for_bit(case):
    host = PAIRS[case]
    joined, inexact = _through_planes(host)
    assert not inexact
    assert joined.dtype == np.float64 and joined.shape == host.shape
    assert joined.tobytes() == host.tobytes()


@pytest.mark.parametrize(
    "value",
    [-0.0, 2.0**-74, -(2.0**-100), float(F32.tiny), np.inf, -np.inf],
    ids=["minus_zero", "below_2_pow_-73", "tail_may_be_subnormal", "smallest_normal",
         "inf", "minus_inf"],
)
def test_heads_whose_tail_the_split_cannot_vouch_for_raise_the_flag(value):
    host = np.ones(6)
    host[4] = value
    _, inexact = _through_planes(host)
    assert inexact


def _finite_planes(shape, dtype):
    """Two uint32 planes of ``shape``; for float64, bits of finite float32s."""
    first = RNG.integers(0, 2**32, size=shape, dtype=np.uint32)
    second = RNG.integers(0, 2**32, size=shape, dtype=np.uint32)
    if dtype == np.float64:
        first &= np.uint32(0xBF7FFFFF)
        second &= np.uint32(0xBF7FFFFF)
    return first, second


def _joined_by_numpy(first, second, dtype) -> np.ndarray:
    """What the two planes stand for, by whole-array numpy."""
    if dtype == np.float64:
        return first.view(np.float32).astype(np.float64) + second.view(np.float32)
    return ((second.astype(np.uint64) << np.uint64(32)) | first).view(dtype)


@pytest.mark.parametrize("dtype", [np.float64, np.int64, np.uint64])
def test_join_in_threads_equals_join_in_one(dtype, monkeypatch):
    n = 3 * jx._JOIN_MIN_BYTES_PER_THREAD // 8 + 5  # three slabs, ragged
    first, second = _finite_planes(n, dtype)
    threaded = jx._join_planes(first, second, np.dtype(dtype))
    monkeypatch.setattr(jx, "_JOIN_THREADS", 1)
    assert jx._join_planes(first, second, np.dtype(dtype)).tobytes() == threaded.tobytes()
    assert threaded.tobytes() == _joined_by_numpy(first, second, dtype).tobytes()


def _strided_view(plane: np.ndarray) -> np.ndarray:
    """``plane``'s values as a view of a larger array, contiguous in no order."""
    base = np.zeros((2 * plane.shape[0], 3 * plane.shape[1]), plane.dtype)
    base[::2, ::3] = plane
    return base[::2, ::3]


#: the orders in which a pair of planes can reach the host
PLANE_ORDERS = {
    "c_contiguous": (np.ascontiguousarray, np.ascontiguousarray),
    "f_contiguous": (np.asfortranarray, np.asfortranarray),
    "strided_view": (_strided_view, _strided_view),
    "one_of_each": (np.asfortranarray, np.ascontiguousarray),
}
#: a result of 8.6 MB, which two threads fill, and one that a single call fills
JOIN_SHAPES = {"threaded": (1200, 900), "single": (40, 30)}


@pytest.mark.parametrize("size", sorted(JOIN_SHAPES))
@pytest.mark.parametrize("order", sorted(PLANE_ORDERS))
@pytest.mark.parametrize("dtype", [np.float64, np.int64, np.uint64])
def test_join_reads_planes_of_any_order_into_a_row_major_result(dtype, order, size):
    shape = JOIN_SHAPES[size]
    threads = min(jx._JOIN_THREADS, int(np.prod(shape)) * 8 // jx._JOIN_MIN_BYTES_PER_THREAD)
    assert (threads >= 2) == (size == "threaded")
    planes = _finite_planes(shape, dtype)
    first, second = (lay(plane) for lay, plane in zip(PLANE_ORDERS[order], planes))
    assert (first.tobytes(), second.tobytes()) == (planes[0].tobytes(), planes[1].tobytes())
    assert jx._planes_strided(first, second) == (order != "c_contiguous")
    joined = jx._join_planes(first, second, np.dtype(dtype))
    assert joined.flags.c_contiguous and joined.shape == shape and joined.dtype == dtype
    assert joined.tobytes() == jx._join_planes(*planes, np.dtype(dtype)).tobytes()
    assert joined.tobytes() == _joined_by_numpy(*planes, dtype).tobytes()


# ---------------------------------------------------------------------------
# through the executor
# ---------------------------------------------------------------------------

SHAPE, CHUNKS = (8, 6), (4, 3)
N_CHUNKS = 4
#: sums of two of these have 31 significant bits: float32 pairs
GRID_A = RNG.integers(0, 2**30, size=SHAPE).astype(np.float64) / 2**10
GRID_B = RNG.integers(0, 2**30, size=SHAPE).astype(np.float64) / 2**10
#: anything a float64 can be: carried as bits, so never interpreted
ANY_F64 = RNG.integers(0, 2**64, size=SHAPE, dtype=np.uint64).view(np.float64)
ANY_F64[0, :3] = [-0.0, np.nan, 1e-310]
ANY_I64 = RNG.integers(-(2**63), 2**63, size=SHAPE, dtype=np.int64)


@pytest.fixture
def spec(tmp_path):
    return ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB", reserved_mem=0)


@pytest.fixture
def pair_device(monkeypatch):
    """The device probe says float64 does not round-trip, and the crossover
    is below the tiny chunks of these tests."""
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 64)


def _source(tmp_path, name, values, spec):
    path = str(tmp_path / f"{name}.zarr")
    stored = open_zarr_array(
        path, mode="w", shape=values.shape, dtype=values.dtype, chunks=CHUNKS
    )
    stored[...] = values
    return ct.from_zarr(path, spec=spec)


def _add(tmp_path, spec):
    return xp.add(
        _source(tmp_path, "a", GRID_A, spec), _source(tmp_path, "b", GRID_B, spec)
    )


def _rechunk(tmp_path, spec):
    return _source(tmp_path, "f", ANY_F64, spec).rechunk((2, 6))


def _int64(tmp_path, spec):
    return xp.add(
        _source(tmp_path, "i", ANY_I64, spec), _source(tmp_path, "j", ANY_I64 * 0, spec)
    )


def _record(tmp_path, spec):
    rec = np.empty(SHAPE, dtype=[("n", "<i8"), ("total", "<f8"), ("flag", "<i4")])
    rec["n"] = np.arange(rec.size).reshape(SHAPE) - 2**40
    rec["total"] = ANY_F64
    rec["flag"] = 7
    return _source(tmp_path, "r", rec, spec).rechunk((2, 6))


PIPELINES = {"add": _add, "rechunk_as_bits": _rechunk, "int64": _int64, "record": _record}


def _stored(build, tmp_path, spec, name, **executor_options):
    ex = JaxExecutor(**executor_options)
    out = str(tmp_path / f"{name}.zarr")
    ct.to_zarr(build(tmp_path, spec), out, executor=ex)
    return open_zarr_array(out, mode="r")[...], ex.stats


@pytest.mark.parametrize("pipeline", sorted(PIPELINES))
def test_stored_result_is_the_direct_fetch_bit_for_bit(pipeline, tmp_path, spec, monkeypatch):
    build = PIPELINES[pipeline]
    direct, direct_stats = _stored(build, tmp_path, spec, "direct")
    assert direct_stats["d2h_plane_bytes"] == 0
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 64)
    planes, stats = _stored(build, tmp_path, spec, "planes")
    assert planes.dtype == direct.dtype
    assert planes.tobytes() == direct.tobytes()
    assert not stats.get("d2h_plane_inexact") and not stats.get("d2h_plane_no_room")
    if pipeline == "record":
        # the 8-byte fields leave as planes, the int32 field as it is
        assert stats["d2h_plane_bytes"] == direct.size * 16
        assert stats["d2h_bytes"] == direct.size * 20
        assert stats["host_syncs"] == 3 * N_CHUNKS
    else:
        assert stats["d2h_plane_bytes"] == stats["d2h_bytes"] == direct.nbytes
        assert stats["host_syncs"] == N_CHUNKS
    if pipeline in ("rechunk_as_bits", "record"):
        assert stats["f64_as_bits"] >= 1


def test_below_the_crossover_the_fetch_is_direct_and_the_counter_reads_zero(
    tmp_path, spec, monkeypatch
):
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    assert CHUNKS[0] * CHUNKS[1] * 8 < jx._PLANES_MIN_BYTES
    got, stats = _stored(_add, tmp_path, spec, "small")
    assert got.tobytes() == (GRID_A + GRID_B).tobytes()
    assert "d2h_plane_bytes" in stats and stats["d2h_plane_bytes"] == 0
    assert stats["d2h_bytes"] == got.nbytes and stats["host_syncs"] == N_CHUNKS


def test_at_the_crossover_a_chunk_leaves_as_planes(tmp_path, monkeypatch):
    """The constant as it stands: a chunk of exactly that many bytes."""
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    rows = jx._PLANES_MIN_BYTES // (512 * 8)
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB", reserved_mem=0)
    values = RNG.integers(0, 2**30, size=(rows, 1024)).astype(np.float64)
    a = ct.from_array(values, chunks=(rows, 512), spec=spec)
    ex = JaxExecutor()
    out = str(tmp_path / "out.zarr")
    ct.to_zarr(xp.add(a, a), out, executor=ex)
    assert open_zarr_array(out, mode="r")[...].tobytes() == (values + values).tobytes()
    assert ex.stats["d2h_plane_bytes"] == ex.stats["d2h_bytes"] == values.nbytes
    assert ex.stats["host_syncs"] == 2


def test_complex128_and_narrow_dtypes_are_fetched_directly(tmp_path, spec, pair_device):
    z = (GRID_A + 1j * GRID_B).astype(np.complex128)
    for name, values in (("z", z), ("f4", GRID_A.astype(np.float32)),
                         ("i4", np.arange(48, dtype=np.int32).reshape(SHAPE))):
        got, stats = _stored(
            lambda tmp, sp, v=values, n=name: xp.add(_source(tmp, n, v, sp), _source(tmp, n + "2", v, sp)),
            tmp_path, spec, "out-" + name,
        )
        assert got.tobytes() == (values + values).tobytes()
        assert stats["d2h_plane_bytes"] == 0 and stats["d2h_bytes"] == got.nbytes


def test_values_the_split_cannot_vouch_for_are_fetched_directly_and_counted(
    tmp_path, spec, pair_device
):
    a = GRID_A.copy()
    a[0, 0] = -0.0  # lands in one chunk of four
    got, stats = _stored(
        lambda tmp, sp: xp.add(_source(tmp, "a", a, sp), _source(tmp, "z", a * 0 - 0.0, sp)),
        tmp_path, spec, "minus-zero",
    )
    want = a + (a * 0 - 0.0)
    assert np.signbit(want[0, 0]) and got.tobytes() == want.tobytes()
    assert stats["d2h_plane_inexact"] == 1
    assert stats["host_syncs"] == N_CHUNKS + 1
    assert stats["d2h_bytes"] == got.nbytes
    assert stats["d2h_plane_bytes"] == got.nbytes * (N_CHUNKS - 1) // N_CHUNKS


def test_a_flush_that_makes_room_takes_the_direct_fetch(tmp_path, spec, pair_device):
    """The spill path: with room for two of the three arrays, admitting the
    sum evicts (and so flushes) an older one. The planes would be a further
    temporary on a device that is over budget."""
    ex = JaxExecutor(device_mem=2 * GRID_A.nbytes + 8, fuse_plan=False)
    out, kept = str(tmp_path / "sum.zarr"), str(tmp_path / "kept.zarr")
    a = _source(tmp_path, "a", GRID_A, spec)
    ct.store([xp.add(a, _source(tmp_path, "b", GRID_B, spec)), a.rechunk((2, 6))],
             [out, kept], executor=ex)
    assert open_zarr_array(out, mode="r")[...].tobytes() == (GRID_A + GRID_B).tobytes()
    assert open_zarr_array(kept, mode="r")[...].tobytes() == GRID_A.tobytes()
    assert ex.stats["d2h_plane_no_room"] >= 1
    assert ex._spilling is False


def test_without_room_for_the_planes_the_last_flush_is_direct(tmp_path, spec, pair_device):
    """The accounting at the end of a compute: three arrays resident fill the
    budget to within less than what the split's program holds, which is the
    chunk and its two planes at least."""
    import jax

    chunk = CHUNKS[0] * CHUNKS[1] * 8
    _, needed = jx._plane_program_of(jax.device_put(GRID_A[: CHUNKS[0], : CHUNKS[1]]))
    assert needed >= 2 * chunk
    got, stats = _stored(_add, tmp_path, spec, "tight",
                         device_mem=3 * GRID_A.nbytes + needed - 1)
    assert got.tobytes() == (GRID_A + GRID_B).tobytes()
    assert stats["d2h_plane_no_room"] == N_CHUNKS and stats["d2h_plane_bytes"] == 0
    got, stats = _stored(_add, tmp_path, spec, "fits",
                         device_mem=3 * GRID_A.nbytes + needed)
    assert not stats.get("d2h_plane_no_room") and stats["d2h_plane_bytes"] == got.nbytes


@pytest.mark.parametrize("planes", [True, False])
def test_the_d2h_span_says_which_way_the_value_left(tmp_path, spec, monkeypatch, planes):
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: not planes)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 64)
    tc = TraceCollector(trace_dir=None)
    ct.to_zarr(_add(tmp_path, spec), str(tmp_path / "out.zarr"),
               executor=JaxExecutor(), callbacks=[tc])
    fetches = [s for rec in tc._records for s in rec["spans"] if s["name"] == "jax.d2h"]
    assert len(fetches) == N_CHUNKS
    assert all(s["attrs"]["planes"] is planes for s in fetches)
    assert all(s["attrs"]["strided"] is False for s in fetches)
    assert all(s["attrs"]["bytes"] == CHUNKS[0] * CHUNKS[1] * 8 for s in fetches)


@pytest.mark.parametrize("mesh", [False, True])
def test_under_a_mesh_the_planes_come_back_whole(tmp_path, spec, pair_device, mesh):
    from cubed_tpu.parallel.mesh import make_mesh

    got, stats = _stored(_add, tmp_path, spec, "meshed", mesh=make_mesh() if mesh else None)
    assert got.tobytes() == (GRID_A + GRID_B).tobytes()
    assert stats["d2h_plane_bytes"] == got.nbytes


TALL = {
    # what a rechunk to column slabs cuts: many rows, few columns
    "float64": _pairs(RNG.standard_normal((600, 24)) * 1e3, RNG.standard_normal((600, 24)) * 1e-6),
    "uint64": RNG.integers(0, 2**64, size=(600, 24), dtype=np.uint64),
    "int64_3d": RNG.integers(-(2**63), 2**63, size=(50, 6, 8), dtype=np.int64),
}


@pytest.mark.parametrize("case", sorted(TALL))
def test_a_tall_slab_is_fetched_row_major_and_nothing_is_read_strided(case, pair_device):
    import jax

    host = TALL[case]
    ex = JaxExecutor()
    fetched, strided = ex._fetch_as_planes(jax.device_put(host))
    assert strided is False
    assert fetched.flags.c_contiguous and fetched.shape == host.shape
    assert fetched.dtype == host.dtype and fetched.tobytes() == host.tobytes()
    assert ex._to_host(jax.device_put(host), host.dtype).tobytes() == host.tobytes()
    assert ex.stats["d2h_plane_bytes"] == host.nbytes
    assert "d2h_plane_strided_bytes" in ex.stats and ex.stats["d2h_plane_strided_bytes"] == 0


@pytest.fixture
def planes_arrive_column_major(monkeypatch):
    """``device_get`` hands every plane back in Fortran order, as the v5e's
    runtime did for a (10000, 2500) slab before the layout was pinned."""
    import jax

    fetch = jax.device_get

    def column_major(tree):
        return jax.tree.map(
            lambda leaf: np.asfortranarray(leaf) if leaf.dtype == np.uint32 else leaf,
            fetch(tree),
        )

    monkeypatch.setattr(jax, "device_get", column_major)


@pytest.mark.parametrize("pipeline", sorted(PIPELINES))
def test_planes_that_arrive_column_major_are_joined_bit_for_bit_and_counted(
    pipeline, tmp_path, spec, monkeypatch, planes_arrive_column_major
):
    build = PIPELINES[pipeline]
    direct, direct_stats = _stored(build, tmp_path, spec, "direct")
    assert direct_stats["d2h_plane_strided_bytes"] == 0
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 64)
    tc = TraceCollector(trace_dir=None)
    ex = JaxExecutor()
    out = str(tmp_path / "strided.zarr")
    ct.to_zarr(build(tmp_path, spec), out, executor=ex, callbacks=[tc])
    got = open_zarr_array(out, mode="r")[...]
    assert got.dtype == direct.dtype and got.tobytes() == direct.tobytes()
    assert ex.stats["d2h_plane_bytes"] > 0
    assert ex.stats["d2h_plane_strided_bytes"] == ex.stats["d2h_plane_bytes"]
    fetches = [s for rec in tc._records for s in rec["spans"] if s["name"] == "jax.d2h"]
    assert fetches and all(s["attrs"]["strided"] is s["attrs"]["planes"] for s in fetches)
    assert sum(s["attrs"]["bytes"] for s in fetches if s["attrs"]["strided"]) == (
        ex.stats["d2h_plane_strided_bytes"]
    )
