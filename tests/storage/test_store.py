"""Zarr v2 store tests: layout conformance, indexing, atomicity, resume
counters. Reference parity: cubed/tests/storage/test_zarr.py."""

import json
import os

import numpy as np
import pytest

from cubed_tpu.storage.store import open_zarr_array
from cubed_tpu.storage.zarr import LazyZarrArray, lazy_empty, open_if_lazy_zarr_array


def test_create_and_roundtrip(tmp_path):
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(5, 7), dtype=np.float64, chunks=(2, 3))
    an = np.arange(35.0).reshape(5, 7)
    z[...] = an
    np.testing.assert_array_equal(z[...], an)
    # reopen
    z2 = open_zarr_array(store, "r")
    np.testing.assert_array_equal(z2[...], an)
    assert z2.chunks == (2, 3)
    assert z2.dtype == np.float64


def test_zarr_v2_layout(tmp_path):
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(4, 4), dtype=np.int32, chunks=(2, 2))
    z[...] = np.arange(16, dtype=np.int32).reshape(4, 4)
    meta = json.loads(open(os.path.join(store, ".zarray")).read())
    assert meta["zarr_format"] == 2
    assert meta["shape"] == [4, 4]
    assert meta["chunks"] == [2, 2]
    assert meta["compressor"] is None
    assert meta["dimension_separator"] == "."
    # chunk 1.1 holds the bottom-right block, raw C-order
    raw = np.frombuffer(open(os.path.join(store, "1.1"), "rb").read(), dtype="<i4")
    np.testing.assert_array_equal(raw.reshape(2, 2), [[10, 11], [14, 15]])


def test_partial_reads_writes(tmp_path):
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(6, 6), dtype=np.float64, chunks=(4, 4))
    an = np.zeros((6, 6))
    z[...] = an
    z[1:3, 2:5] = 7.0
    an[1:3, 2:5] = 7.0
    np.testing.assert_array_equal(z[...], an)
    np.testing.assert_array_equal(z[0:4, 3:6], an[0:4, 3:6])
    np.testing.assert_array_equal(z[5], an[5])
    np.testing.assert_array_equal(z[::2, 1::2], an[::2, 1::2])


def test_edge_chunks_padded(tmp_path):
    # 5x5 with 2x2 chunks: edge chunks stored padded, reads clip to shape
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(5, 5), dtype=np.float64, chunks=(2, 2))
    an = np.arange(25.0).reshape(5, 5)
    z[...] = an
    np.testing.assert_array_equal(z[...], an)
    np.testing.assert_array_equal(z[4:5, 3:5], an[4:5, 3:5])


def test_oindex(tmp_path):
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(6, 8), dtype=np.float64, chunks=(2, 3))
    an = np.arange(48.0).reshape(6, 8)
    z[...] = an
    np.testing.assert_array_equal(z.oindex[[0, 3, 5], :], an[[0, 3, 5], :])
    np.testing.assert_array_equal(
        z.oindex[[1, 4], [0, 2, 7]], an[np.ix_([1, 4], [0, 2, 7])]
    )
    np.testing.assert_array_equal(z.oindex[slice(1, 5), [2, 2, 3]],
                                  an[1:5][:, [2, 2, 3]])


def test_nchunks_initialized(tmp_path):
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(4, 4), dtype=np.float64, chunks=(2, 2))
    assert z.nchunks == 4
    assert z.nchunks_initialized == 0
    z[0:2, 0:2] = 1.0
    assert z.nchunks_initialized == 1
    z[...] = 1.0
    assert z.nchunks_initialized == 4


def test_structured_dtype(tmp_path):
    dtype = np.dtype([("n", np.int64), ("total", np.float64)])
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(2, 2), dtype=dtype, chunks=(1, 2))
    rec = np.zeros((2, 2), dtype=dtype)
    rec["n"] = [[1, 2], [3, 4]]
    rec["total"] = [[0.5, 1.5], [2.5, 3.5]]
    z[...] = rec
    out = z[...]
    np.testing.assert_array_equal(out["n"], rec["n"])
    np.testing.assert_array_equal(out["total"], rec["total"])


def test_0d_array(tmp_path):
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(), dtype=np.float64)
    z[()] = 42.0
    assert float(z[()]) == 42.0


def test_lazy_zarr_array(tmp_path):
    store = str(tmp_path / "a.zarr")
    lazy = lazy_empty((4, 4), dtype=np.float64, chunks=(2, 2), store=store)
    # no metadata until create()
    with pytest.raises(FileNotFoundError):
        lazy.open()
    lazy.create()
    z = open_if_lazy_zarr_array(lazy)
    assert z.shape == (4, 4)


def test_mode_a_preserves_chunks(tmp_path):
    # reopening with mode=a must not clobber existing chunk data (resume)
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(4, 4), dtype=np.float64, chunks=(2, 2))
    z[0:2, 0:2] = 5.0
    z2 = open_zarr_array(store, "a", shape=(4, 4), dtype=np.float64, chunks=(2, 2))
    np.testing.assert_array_equal(z2[0:2, 0:2], np.full((2, 2), 5.0))
    assert z2.nchunks_initialized == 1


def test_fill_value(tmp_path):
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(
        store, "w", shape=(4,), dtype=np.float64, chunks=(2,), fill_value=np.nan
    )
    out = z[...]
    assert np.isnan(out).all()


# ---------------------------------------------------------------------------
# Zarr v2 spec golden files: the on-disk format is the interchange contract
# (other implementations must be able to read our stores); these pin the
# exact metadata JSON so any drift fails loudly. Spec reference:
# https://zarr-specs.readthedocs.io/en/latest/v2/v2.0.html
# ---------------------------------------------------------------------------


def test_zarray_metadata_golden(tmp_path):
    import json
    import os

    a = open_zarr_array(
        str(tmp_path / "g.zarr"), mode="w",
        shape=(10, 7), dtype=np.dtype("float64"), chunks=(4, 3),
    )
    a[...] = np.arange(70.0).reshape(10, 7)
    meta = json.loads((tmp_path / "g.zarr" / ".zarray").read_text())
    assert meta == {
        "zarr_format": 2,
        "shape": [10, 7],
        "chunks": [4, 3],
        "dtype": "<f8",
        "compressor": None,
        "fill_value": 0.0,
        "order": "C",
        "filters": None,
        "dimension_separator": ".",
    }
    # v2 mandatory keys, exactly (no extras that could confuse readers)
    assert set(meta) == {
        "zarr_format", "shape", "chunks", "dtype", "compressor",
        "fill_value", "order", "filters", "dimension_separator",
    }


@pytest.mark.parametrize(
    "np_dtype,v2_dtype",
    [("float32", "<f4"), ("int64", "<i8"), ("uint8", "|u1"), ("bool", "|b1"),
     ("int16", "<i2"), ("complex128", "<c16")],
)
def test_zarray_dtype_encoding(tmp_path, np_dtype, v2_dtype):
    import json

    a = open_zarr_array(
        str(tmp_path / f"d-{np_dtype}.zarr"), mode="w",
        shape=(4,), dtype=np.dtype(np_dtype), chunks=(2,),
    )
    meta = json.loads((tmp_path / f"d-{np_dtype}.zarr" / ".zarray").read_text())
    assert meta["dtype"] == v2_dtype


def test_zarray_structured_dtype_encoding(tmp_path):
    import json

    dt = np.dtype([("n", np.int64), ("total", np.float64)])
    a = open_zarr_array(
        str(tmp_path / "s.zarr"), mode="w", shape=(4,), dtype=dt, chunks=(2,),
    )
    meta = json.loads((tmp_path / "s.zarr" / ".zarray").read_text())
    # v2 structured dtypes are lists of [name, dtype] pairs
    assert meta["dtype"] == [["n", "<i8"], ["total", "<f8"]]


def test_raw_chunk_layout_c_order_readback(tmp_path):
    """Chunk files are raw C-order buffers a third-party v2 reader decodes
    with nothing but the .zarray JSON."""
    import json
    import os

    an = np.arange(70.0).reshape(10, 7)
    a = open_zarr_array(
        str(tmp_path / "r.zarr"), mode="w",
        shape=(10, 7), dtype=np.dtype("float64"), chunks=(4, 3),
    )
    a[...] = an
    meta = json.loads((tmp_path / "r.zarr" / ".zarray").read_text())
    chunks = meta["chunks"]
    sep = meta["dimension_separator"]
    # reconstruct the full array exactly the way an independent reader would
    out = np.empty(meta["shape"], dtype=meta["dtype"])
    for ci in range((meta["shape"][0] + chunks[0] - 1) // chunks[0]):
        for cj in range((meta["shape"][1] + chunks[1] - 1) // chunks[1]):
            raw = (tmp_path / "r.zarr" / f"{ci}{sep}{cj}").read_bytes()
            block = np.frombuffer(raw, dtype=meta["dtype"]).reshape(chunks)
            i0, j0 = ci * chunks[0], cj * chunks[1]
            i1 = min(i0 + chunks[0], meta["shape"][0])
            j1 = min(j0 + chunks[1], meta["shape"][1])
            out[i0:i1, j0:j1] = block[: i1 - i0, : j1 - j0]
    np.testing.assert_array_equal(out, an)


@pytest.mark.parametrize(
    "compressor",
    [
        {"id": "zlib", "level": 5},
        {"id": "gzip", "level": 1},
        {"id": "bz2", "level": 1},
        {"id": "lzma", "preset": 0},
    ],
)
def test_compressed_roundtrip(tmp_path, compressor):
    store = str(tmp_path / "c.zarr")
    z = open_zarr_array(
        store, "w", shape=(5, 7), dtype=np.float64, chunks=(2, 3),
        compressor=compressor,
    )
    an = np.arange(35.0).reshape(5, 7)
    z[...] = an
    np.testing.assert_array_equal(z[...], an)
    # reopened array picks the codec up from the on-disk metadata
    z2 = open_zarr_array(store, "r")
    assert z2.compressor["id"] == compressor["id"]
    np.testing.assert_array_equal(z2[...], an)
    # chunk objects on disk really are compressed (not raw C-order bytes)
    meta = json.loads(open(os.path.join(store, ".zarray")).read())
    assert meta["compressor"]["id"] == compressor["id"]
    raw = open(os.path.join(store, "0.0"), "rb").read()
    assert raw != an[:2, :3].tobytes()


def test_compressed_interop_zlib(tmp_path):
    """Read a zlib-compressed chunk written byte-for-byte the way any other
    Zarr v2 implementation would write it (spec fixture, no zarr-python)."""
    import zlib

    store = tmp_path / "other.zarr"
    store.mkdir()
    an = np.arange(6.0).reshape(2, 3)
    meta = {
        "zarr_format": 2,
        "shape": [2, 3],
        "chunks": [2, 3],
        "dtype": "<f8",
        "compressor": {"id": "zlib", "level": 1},
        "fill_value": 0.0,
        "order": "C",
        "filters": None,
    }
    (store / ".zarray").write_text(json.dumps(meta))
    (store / "0.0").write_bytes(zlib.compress(an.tobytes(), 1))
    z = open_zarr_array(str(store), "r")
    np.testing.assert_array_equal(z[...], an)


def test_unsupported_compressor_raises(tmp_path):
    with pytest.raises(ValueError, match="blosc"):
        open_zarr_array(
            str(tmp_path / "b.zarr"), "w", shape=(2,), dtype=np.float64,
            chunks=(2,), compressor={"id": "blosc", "cname": "lz4"},
        )


def test_to_zarr_compressed_end_to_end(tmp_path):
    import cubed_tpu as ct
    import cubed_tpu.array_api as xp

    spec_ = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="500MB")
    an = np.arange(100.0).reshape(10, 10)
    a = ct.from_array(an, chunks=(4, 4), spec=spec_)
    target = str(tmp_path / "out.zarr")
    ct.to_zarr(xp.add(a, 1.0), target, compressor={"id": "zlib", "level": 1})
    z = open_zarr_array(target, "r")
    assert z.compressor == {"id": "zlib", "level": 1}
    np.testing.assert_array_equal(z[...], an + 1.0)
    # and from_zarr reads it back through the framework
    b = ct.from_zarr(target)
    np.testing.assert_array_equal(b.compute(), an + 1.0)


def test_lzma_raw_format_roundtrip(tmp_path):
    """FORMAT_RAW lzma requires the filter chain on decompression too."""
    import lzma

    comp = {
        "id": "lzma",
        "format": lzma.FORMAT_RAW,
        "filters": [{"id": lzma.FILTER_LZMA2, "preset": 1}],
    }
    store = str(tmp_path / "raw.zarr")
    z = open_zarr_array(
        store, "w", shape=(4, 4), dtype=np.float64, chunks=(2, 2),
        compressor=comp,
    )
    an = np.arange(16.0).reshape(4, 4)
    z[...] = an
    np.testing.assert_array_equal(z[...], an)
    np.testing.assert_array_equal(open_zarr_array(store, "r")[...], an)


def test_lzma_xz_with_filters_roundtrip(tmp_path):
    """Container formats embed the filter chain; decompress must NOT be
    handed filters (CPython rejects them except with FORMAT_RAW)."""
    import lzma

    comp = {
        "id": "lzma",
        "format": lzma.FORMAT_XZ,
        "filters": [{"id": lzma.FILTER_LZMA2, "preset": 1}],
    }
    store = str(tmp_path / "xzf.zarr")
    z = open_zarr_array(
        store, "w", shape=(4, 4), dtype=np.float64, chunks=(2, 2),
        compressor=comp,
    )
    an = np.arange(16.0).reshape(4, 4)
    z[...] = an
    np.testing.assert_array_equal(open_zarr_array(store, "r")[...], an)


def test_fsspec_memory_store_roundtrip():
    """The _FsspecIO path (s3://, gs://, ... in production) via memory://."""
    import uuid

    pytest.importorskip("fsspec")

    store = f"memory://zarr-{uuid.uuid4().hex}"
    z = open_zarr_array(
        store, "w", shape=(5, 6), dtype=np.float64, chunks=(2, 3),
        compressor={"id": "zlib", "level": 1},
    )
    an = np.arange(30.0).reshape(5, 6)
    z[...] = an
    np.testing.assert_array_equal(z[...], an)
    z2 = open_zarr_array(store, "r")
    np.testing.assert_array_equal(z2[...], an)
    assert z2.nchunks_initialized == z2.nchunks


def test_fsspec_memory_workdir_end_to_end():
    """A whole plan with its work_dir on an fsspec store (single-process
    executors only: memory:// is per-process)."""
    import uuid

    pytest.importorskip("fsspec")
    import cubed_tpu as ct
    import cubed_tpu.array_api as xp

    spec_ = ct.Spec(
        work_dir=f"memory://work-{uuid.uuid4().hex}", allowed_mem="500MB"
    )
    an = np.arange(64.0).reshape(8, 8)
    a = ct.from_array(an, chunks=(3, 3), spec=spec_)
    got = float(xp.sum(xp.multiply(a, 3.0)).compute())
    assert got == 3 * an.sum()


# -- orphaned .tmp hygiene (crashed mid-write writers) --------------------


def _litter_tmp(store: str, name: str, age_s: float = 120.0) -> str:
    """Plant a stale partial temp file as a crashed writer would leave it."""
    import time

    path = os.path.join(store, name)
    with open(path, "wb") as f:
        f.write(b"\x00" * 7)  # partial payload: not a valid chunk
    old = time.time() - age_s
    os.utime(path, (old, old))
    return path


def test_orphaned_tmp_ignored_by_resume_counters(tmp_path):
    """Regression: a crashed write's leftover .tmp next to chunks must not
    count as an initialized chunk (it would fool resume into skipping an
    op whose output is incomplete)."""
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(4, 4), dtype=np.float64, chunks=(2, 2))
    z[:2, :2] = np.ones((2, 2))  # 1 real chunk of 4
    _litter_tmp(store, "1.1.deadbeef.tmp")
    z2 = open_zarr_array(store, "r")
    assert z2.nchunks_initialized == 1
    # and reading the chunk the orphan shadows returns fill, not garbage
    np.testing.assert_array_equal(z2[2:, 2:], np.zeros((2, 2)))


def test_orphaned_tmp_swept_on_writer_open(tmp_path):
    """Opening in a writer mode (what the create-arrays op and resume do)
    sweeps stale orphans; fresh temp files — possibly a live writer mid
    os.replace — are left alone."""
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(4, 4), dtype=np.float64, chunks=(2, 2))
    z[...] = np.arange(16.0).reshape(4, 4)
    stale = _litter_tmp(store, "0.0.cafe0000.tmp", age_s=120.0)
    fresh = _litter_tmp(store, "0.1.cafe0001.tmp", age_s=0.0)
    os.utime(fresh)  # make it genuinely fresh
    z2 = open_zarr_array(store, "a")  # resume-style reopen
    assert not os.path.exists(stale), "stale orphan should be swept"
    assert os.path.exists(fresh), "a live writer's temp must survive"
    np.testing.assert_array_equal(z2[...], np.arange(16.0).reshape(4, 4))


def test_orphaned_tmp_not_swept_on_read_open(tmp_path):
    """Read opens (every task opening an input) skip the sweep — hygiene
    belongs to the op-start writer open, not the hot read path."""
    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(2,), dtype=np.float64, chunks=(2,))
    z[...] = np.arange(2.0)
    stale = _litter_tmp(store, "0.feed0000.tmp", age_s=120.0)
    open_zarr_array(store, "r")
    assert os.path.exists(stale)


def test_sweep_counts_metric(tmp_path):
    from cubed_tpu.observability.metrics import get_registry
    from cubed_tpu.storage.store import _LocalIO

    store = str(tmp_path / "a.zarr")
    os.makedirs(store)
    _litter_tmp(store, "0.0.aa.tmp")
    _litter_tmp(store, "0.1.bb.tmp")
    before = get_registry().snapshot()
    removed = _LocalIO(store).sweep_tmp()
    assert removed == 2
    delta = get_registry().snapshot_delta(before)
    assert delta.get("orphan_tmps_swept", 0) == 2


def test_vanished_chunk_read_fails_loudly_not_fill(tmp_path, monkeypatch):
    """A FileNotFoundError AFTER a successful exists() is an anomaly
    (chunks are write-once); it must raise — not silently read as an
    absent chunk and substitute fill values for real data."""
    from cubed_tpu.storage.store import _LocalIO

    store = str(tmp_path / "a.zarr")
    z = open_zarr_array(store, "w", shape=(2,), dtype=np.float64, chunks=(2,))
    z[...] = np.arange(2.0)

    monkeypatch.setenv("CUBED_TPU_STORAGE_READ_RETRIES", "1")

    def gone(self, name):
        raise FileNotFoundError(name)

    monkeypatch.setattr(_LocalIO, "read_bytes", gone)
    with pytest.raises(FileNotFoundError):
        z[...]


# -- a chunk's bytes are written from where they lie ------------------------
#
# ``_write_chunk`` hands the file, the checksum and the codec a view of a
# C-contiguous array of the store's dtype: no ``tobytes()`` copy. What keeps
# bytes past the call (the peer cache, an injected corruption) takes its own.


def _spy_on_chunk_writes(monkeypatch):
    """What ``write_bytes_atomic`` was handed for every chunk file."""
    from cubed_tpu.storage.store import _LocalIO

    handed = []
    real = _LocalIO.write_bytes_atomic

    def write(self, name, data, inject=True):
        if not name.startswith("."):
            handed.append(data)
        return real(self, name, data, inject)

    monkeypatch.setattr(_LocalIO, "write_bytes_atomic", write)
    return handed


_WRITTEN = {
    # name: (what is assigned, shape, chunks, dtype of the store, copied?)
    "contiguous": (lambda: np.arange(24.0).reshape(4, 6), (4, 6), (4, 6), "f8", False),
    "a_view_of_rows": (lambda: np.arange(48.0).reshape(8, 6)[4:], (4, 6), (4, 6), "f8", False),
    "transposed": (lambda: np.arange(24.0).reshape(6, 4).T, (4, 6), (4, 6), "f8", True),
    "other_dtype": (lambda: np.arange(24, dtype=np.int32).reshape(4, 6), (4, 6), (4, 6), "f8", True),
    "a_list": (lambda: [[1.0, 2.0], [3.0, 4.0]], (2, 2), (2, 2), "f8", True),
    "zero_d": (lambda: np.array(2.5), (), (), "f8", False),
    # the store writes the one chunk that the empty region touches, all padding
    "zero_size": (lambda: np.zeros((0, 6)), (0, 6), (1, 6), "f8", True),
    "a_record": (
        lambda: np.array([(1, 2.5), (3, 4.5)], dtype=[("n", "<i4"), ("x", "<f8")]),
        (2,), (2,), [("n", "<i4"), ("x", "<f8")], False,
    ),
}


@pytest.mark.parametrize("compressor", [None, {"id": "zlib", "level": 1}], ids=["raw", "zlib"])
@pytest.mark.parametrize("case", sorted(_WRITTEN))
def test_a_chunk_is_written_from_where_it_lies(tmp_path, monkeypatch, case, compressor):
    import zlib

    from cubed_tpu.observability.accounting import task_scope
    from cubed_tpu.storage import integrity

    make, shape, chunks, dtype, copies = _WRITTEN[case]
    value = make()
    handed = _spy_on_chunk_writes(monkeypatch)
    z = open_zarr_array(str(tmp_path / "a.zarr"), "w", shape=shape, dtype=dtype,
                        chunks=chunks, compressor=compressor)
    with integrity.scoped("write"), task_scope() as scope:
        if shape:
            z[...] = value
        else:
            z[()] = value
    # the same file, checksum and manifest entry as the copying write gave
    raw = np.ascontiguousarray(value, dtype=np.dtype(dtype)).tobytes() or bytes(48)
    want = zlib.compress(raw, 1) if compressor else raw
    (name,) = [n for n in os.listdir(z.store) if not n.startswith(".")]
    with open(os.path.join(z.store, name), "rb") as f:
        assert f.read() == want
    entry = open_zarr_array(z.store, "r")._manifest()[0][name]
    assert (entry["c"], entry["n"]) == (zlib.crc32(want), len(want))
    assert scope.bytes_written == len(want) and scope.chunks_written == 1
    (data,) = handed
    assert bytes(data) == want
    assert scope.counters.get("encode_copy_bytes", 0) == (len(raw) if copies else 0)
    if compressor is None and not copies:
        # not bytes made for the call: the caller's own memory
        assert isinstance(data, memoryview) and data.format == "B" and data.ndim == 1
        assert np.shares_memory(np.frombuffer(data, np.uint8), np.asarray(value))
    readback = open_zarr_array(z.store, "r")
    got = readback[...] if shape else readback[()]
    assert got.tobytes() == (raw if got.size else b"")


@pytest.mark.parametrize("shape, chunks, edge", [((5, 6), (4, 6), True), ((8, 6), (4, 6), False)],
                         ids=["ragged", "aligned"])
def test_a_padded_or_merged_chunk_counts_as_copied(tmp_path, shape, chunks, edge):
    from cubed_tpu.observability.accounting import task_scope

    z = open_zarr_array(str(tmp_path / "a.zarr"), "w", shape=shape, dtype="f8", chunks=chunks)
    value = np.arange(float(np.prod(shape))).reshape(shape)
    with task_scope() as scope:
        z[...] = value
    chunk = 4 * 6 * 8
    # a full chunk cut from rows is contiguous and goes as it is; the edge
    # chunk is copied into its padding
    assert scope.counters.get("encode_copy_bytes", 0) == (chunk if edge else 0)
    assert scope.bytes_written == 2 * chunk
    with task_scope() as scope:
        z[1:3, 2:4] = -1.0  # read, merged, written
    assert scope.counters["encode_copy_bytes"] == chunk
    value[1:3, 2:4] = -1.0
    np.testing.assert_array_equal(z[...], value)


def test_the_encode_span_says_whether_the_chunk_was_copied(tmp_path, monkeypatch):
    from cubed_tpu.observability import accounting
    from cubed_tpu.observability.accounting import task_scope

    monkeypatch.setenv(accounting.SPANS_ENV_VAR, "1")
    z = open_zarr_array(str(tmp_path / "a.zarr"), "w", shape=(4, 4), dtype="f8", chunks=(4, 4))
    with task_scope() as scope:
        z[...] = np.ones((4, 4))
        z[...] = np.ones((4, 4)).T
        z[...] = np.ones((4, 4), np.float32)
    encodes = [s for s in scope.spans if s["name"] == "chunk_encode"]
    assert [s["attrs"]["copied"] for s in encodes] == [False, True, True]
    assert all(s["attrs"]["bytes"] == 128 for s in encodes)


@pytest.fixture
def peer_cache():
    """This process armed as a fleet worker with a peer cache."""
    from cubed_tpu.runtime import transfer

    runtime = transfer.PeerRuntime("w-test", max_cache_bytes=1 << 20)
    transfer.set_worker_runtime(runtime)
    transfer.arm_from_wire(transfer.PeerConfig(enabled=True).to_wire())
    try:
        yield runtime.cache
    finally:
        transfer.arm_from_wire(None)
        transfer.set_worker_runtime(None)


@pytest.mark.parametrize("armed", [False, True], ids=["no_peer_cache", "peer_cache_armed"])
def test_the_caller_may_overwrite_its_array_once_the_write_returned(
    tmp_path, request, armed
):
    import zlib

    from cubed_tpu.observability.accounting import task_scope
    from cubed_tpu.storage import integrity

    cache = request.getfixturevalue("peer_cache") if armed else None
    z = open_zarr_array(str(tmp_path / "a.zarr"), "w", shape=(8, 4), dtype="f8", chunks=(4, 4))
    buffer = np.empty((4, 4))
    written = []
    with integrity.scoped("write"), task_scope() as scope:
        for k in range(2):
            buffer[...] = np.arange(16.0).reshape(4, 4) + 100 * k
            written.append(buffer.tobytes())
            z[4 * k : 4 * k + 4, :] = buffer
            buffer[...] = np.nan  # the caller's again
    for k, name in enumerate(("0.0", "1.0")):
        with open(os.path.join(z.store, name), "rb") as f:
            assert f.read() == written[k]
        entry = open_zarr_array(z.store, "r")._manifest()[0][name]
        assert (entry["c"], entry["n"]) == (zlib.crc32(written[k]), 128)
        if armed:
            # the cache kept bytes of its own, with the checksum of the file
            kept, crc = cache.get_with_crc(z.store, name)
            assert type(kept) is bytes and kept == written[k] and crc == entry["c"]
    # the cache's copy is the one copy, and it is counted
    assert scope.counters.get("encode_copy_bytes", 0) == (256 if armed else 0)


def test_a_chunk_too_large_for_the_peer_cache_is_not_copied(tmp_path, peer_cache):
    from cubed_tpu.observability.accounting import task_scope

    peer_cache.max_bytes = 100
    z = open_zarr_array(str(tmp_path / "a.zarr"), "w", shape=(4, 4), dtype="f8", chunks=(4, 4))
    with task_scope() as scope:
        z[...] = np.ones((4, 4))
    assert peer_cache.get(z.store, "0.0") is None
    assert "encode_copy_bytes" not in scope.counters


@pytest.mark.parametrize("leaves_tmp", [False, True])
def test_injected_write_faults_behave_as_before_when_handed_a_view(tmp_path, leaves_tmp):
    from cubed_tpu.observability.accounting import task_scope
    from cubed_tpu.runtime import faults

    z = open_zarr_array(str(tmp_path / "a.zarr"), "w", shape=(4, 4), dtype="f8", chunks=(4, 4))
    value = np.arange(16.0).reshape(4, 4)
    config = faults.FaultConfig(
        seed=3, storage_write_failure_rate=1.0, storage_write_leaves_tmp=leaves_tmp
    )
    with faults.scoped(config), task_scope():
        with pytest.raises(faults.FaultInjectedIOError):
            z[...] = value
    names = [n for n in os.listdir(z.store) if not n.startswith(".")]
    if leaves_tmp:
        # a writer killed mid-write: half the chunk in a temp file, no chunk
        (tmp,) = names
        assert tmp.startswith("0.0.") and tmp.endswith(".tmp")
        with open(os.path.join(z.store, tmp), "rb") as f:
            assert f.read() == value.tobytes()[:64]
    else:
        assert names == []
    assert z.nchunks_initialized == 0


def test_an_injected_corruption_is_a_copy_and_the_callers_array_is_untouched(tmp_path):
    import zlib

    from cubed_tpu.observability.accounting import task_scope
    from cubed_tpu.runtime import faults
    from cubed_tpu.storage import integrity

    value = np.arange(16.0).reshape(4, 4)
    meant = value.tobytes()
    seen = set()
    for seed in range(8):  # both kinds: a flipped bit, a file cut in half
        z = open_zarr_array(str(tmp_path / f"a{seed}.zarr"), "w", shape=(4, 4), dtype="f8",
                            chunks=(4, 4))
        with integrity.scoped("write"), task_scope(), faults.scoped(
            faults.FaultConfig(seed=seed, storage_corrupt_rate=1.0)
        ):
            z[...] = value
        with open(os.path.join(z.store, "0.0"), "rb") as f:
            stored = f.read()
        assert stored != meant and len(stored) in (128, 64)
        seen.add(len(stored))
        assert value.tobytes() == meant
        # the manifest holds the checksum of what was meant
        entry = open_zarr_array(z.store, "r")._manifest()[0]["0.0"]
        assert (entry["c"], entry["n"]) == (zlib.crc32(meant), 128)
        view = memoryview(value.reshape(-1).view(np.uint8))
        with task_scope():
            again = faults.FaultInjector(
                faults.FaultConfig(seed=seed, storage_corrupt_rate=1.0)
            ).storage_corrupt_fault(f"a{seed}.zarr/0.0", view)
        assert type(again) is bytes and again == stored
    assert seen == {128, 64}
