"""Zarr v2 by hand: numpy, json and zlib only, independent of the code under
test (after ``chip_smoke.write_zarr_v2`` and ``read_zarr_v2``, writing chunk by
chunk, with the checks of the chunk files and of the store's checksum manifest
beside them).

Only raw (uncompressed), C-order directory stores are handled: that is what
the benchmark's configurations deploy."""

from __future__ import annotations

import itertools
import json
import math
import os
import zlib
from typing import Sequence

import numpy as np


def chunk_slices(shape: Sequence[int], chunks: Sequence[int]):
    """(chunk index, slices of the array it covers) for every chunk."""
    grid = [range(math.ceil(s / c)) for s, c in zip(shape, chunks)]
    for idx in itertools.product(*grid):
        yield idx, tuple(
            slice(i * c, min((i + 1) * c, s))
            for i, c, s in zip(idx, chunks, shape)
        )


def create(path: str, shape: Sequence[int], chunks: Sequence[int], dtype) -> None:
    """An empty uncompressed C-order Zarr v2 directory store."""
    os.makedirs(path)
    meta = {
        "zarr_format": 2,
        "shape": list(shape),
        "chunks": list(chunks),
        "dtype": np.dtype(dtype).str,
        "compressor": None,
        "fill_value": 0.0,
        "order": "C",
        "filters": None,
        "dimension_separator": ".",
    }
    with open(os.path.join(path, ".zarray"), "w") as f:
        json.dump(meta, f)


def write_chunk(path: str, idx: Sequence[int], block: np.ndarray) -> None:
    """One chunk of a store made by ``create``; ``block`` has the chunk's full
    shape (an edge chunk is stored padded) and is C-contiguous."""
    if not block.flags.c_contiguous:
        raise ValueError("a chunk is written from a C-contiguous block")
    block.tofile(os.path.join(path, ".".join(map(str, idx))))


def read_meta(path: str) -> dict:
    with open(os.path.join(path, ".zarray")) as f:
        meta = json.load(f)
    if meta["compressor"] is not None or meta["filters"] or meta["order"] != "C":
        raise ValueError(f"{path}: not a raw C-order store: {meta}")
    return meta


def chunk_files(path: str) -> dict:
    """{chunk key: (file path, bytes a full chunk holds)} from ``.zarray``."""
    meta = read_meta(path)
    sep = meta.get("dimension_separator", ".")
    full = math.prod(meta["chunks"]) * np.dtype(meta["dtype"]).itemsize
    return {
        sep.join(map(str, idx)): (os.path.join(path, sep.join(map(str, idx))), full)
        for idx, _ in chunk_slices(meta["shape"], meta["chunks"])
    }


def read_zarr_v2(path: str) -> np.ndarray:
    """Read an uncompressed C-order Zarr v2 directory store with numpy."""
    meta = read_meta(path)
    shape, chunks = tuple(meta["shape"]), tuple(meta["chunks"])
    dtype = np.dtype(meta["dtype"])
    sep = meta.get("dimension_separator", ".")
    out = np.empty(shape, dtype=dtype)
    for idx, sel in chunk_slices(shape, chunks):
        block = np.fromfile(
            os.path.join(path, sep.join(map(str, idx))), dtype=dtype
        ).reshape(chunks)
        out[sel] = block[tuple(slice(0, s.stop - s.start) for s in sel)]
    return out


def read_checksum_manifest(path: str) -> dict:
    """{chunk key: {"c": crc32, "n": length}} merged over the store's
    ``.manifest-*.json`` shards (one JSON object to a line, the newest line
    of a key wins), as ``cubed_tpu/storage/integrity.py`` documents them."""
    entries: dict = {}
    for name in sorted(os.listdir(path)):
        if not (name.startswith(".manifest-") and name.endswith(".json")):
            continue
        with open(os.path.join(path, name)) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                doc = json.loads(line)
                prev = entries.get(doc["k"])
                if prev is None or doc.get("t", 0) >= prev.get("t", 0):
                    entries[doc["k"]] = doc
    return entries


def check_chunks_present(path: str) -> None:
    """Every chunk file is there at full length and has a manifest entry of
    that length. Reads no chunk."""
    manifest = read_checksum_manifest(path)
    for key, (file, full) in chunk_files(path).items():
        size = os.path.getsize(file)  # raises if the chunk is missing
        if size != full:
            raise AssertionError(f"{file}: {size} bytes, a full chunk has {full}")
        entry = manifest.get(key)
        if entry is None or entry["n"] != full:
            raise AssertionError(f"{path}: chunk {key} has manifest entry {entry}")


def excess(got: np.ndarray, want: np.ndarray, rtol: float, room: np.ndarray) -> float:
    """The most by which ``|got - want|`` passes ``rtol * max(|want|, tiny)``
    (not above 0 where ``got`` is within ``rtol`` relative of ``want``
    everywhere). Works in ``got`` and ``room``, which it overwrites, and makes
    no new array; raises on a value that is not finite."""
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    np.subtract(got, want, out=room)
    np.abs(room, out=room)
    np.abs(want, out=got)
    np.maximum(got, np.finfo(want.dtype).tiny, out=got)
    np.multiply(got, rtol, out=got)
    np.subtract(room, got, out=room)
    return float(room.max())


def check_store(path: str, ref: np.ndarray, rtol: float) -> None:
    """The stored array against ``ref`` at ``rtol`` relative, and every stored
    chunk's CRC-32 against its manifest entry, in one pass over the chunk
    files. Each file is read once into one buffer and compared there: fresh
    arrays of a chunk's size cost more in page faults than the arithmetic."""
    meta = read_meta(path)
    shape, chunks = tuple(meta["shape"]), tuple(meta["chunks"])
    dtype = np.dtype(meta["dtype"])
    if shape != ref.shape or dtype != ref.dtype:
        raise AssertionError(
            f"{path}: stored {dtype}{shape}, expected {ref.dtype}{ref.shape}"
        )
    manifest = read_checksum_manifest(path)
    files = chunk_files(path)
    sep = meta.get("dimension_separator", ".")
    block = np.empty(chunks, dtype=dtype)
    room = np.empty(chunks, dtype=dtype)
    for idx, sel in chunk_slices(shape, chunks):
        key = sep.join(map(str, idx))
        file, full = files[key]
        with open(file, "rb") as f:
            if f.readinto(memoryview(block).cast("B")) != full or f.read(1):
                raise AssertionError(f"{file}: not the {full} bytes of a full chunk")
        crc = zlib.crc32(memoryview(block).cast("B")) & 0xFFFFFFFF
        entry = manifest.get(key)
        if entry is None or entry["c"] != crc or entry["n"] != full:
            raise AssertionError(
                f"{path}: chunk {key} has CRC-32 {crc}, manifest says {entry}"
            )
        inside = tuple(slice(0, s.stop - s.start) for s in sel)
        worst = excess(block[inside], ref[sel], rtol, room[inside])
        if worst > 0.0:
            raise AssertionError(
                f"{path}: chunk {key} is off by more than {rtol} relative "
                f"(by {worst} absolute beyond it)"
            )
