"""``to_zarr(from_zarr(a).rechunk((n, chunk // 2)))`` over one Zarr source:
upstream's rechunk primitive (``cubed/primitive/rechunk.py``,
``cubed/core/ops.py`` ``rechunk``) at the size of upstream's memory test
(``cubed/tests/test_mem_utilization.py``: 10000 x 10000 float64 in (5000,
5000) chunks of 200 MB under ``allowed_mem=2GB``), Zarr to HBM to Zarr with
no arithmetic. The timed region ends when ``to_zarr`` has returned: every
chunk of the target written by the store's atomic, fsynced write and entered
in the checksum manifest.

The guarantee of its configuration ``zarr-rechunk-10k`` (whose data side is
``zarr-add-10k``'s to the letter), which ``check`` enforces: **a copy is a
copy**. The target holds the source's bytes, bit for bit, NaN payloads,
signed zeros, infinities and subnormals included.

Assumed: the target chunking (n, chunk // 2), that is (10000, 2500), row
blocks to column slabs. ``SURVEY.md`` and ``BASELINE.md`` bear out the shape,
the source chunks and the memory limit of upstream's test, not the target
chunking to the digit; it is the one ``chip_smoke.py`` and ``ROADMAP.md``
have named for this deployment since PR 21."""

from __future__ import annotations

import math
import os
import zlib

import numpy as np

import cubed_tpu as ct
from benchmark.harness import zarrv2

WRITES_TARGET = True

#: float64 bit patterns that a 64-bit transfer is most likely to change (a
#: copy of ``chip_smoke.EDGE_VALUES``): NaNs with a payload and a sign, both
#: zeros, both infinities, the least and the largest subnormal, the extremes
#: of float64's normal range and of float32's
EDGE_BITS = np.array(
    [0x7FF8000000000123, 0xFFF0000000000ABC, 0x8000000000000000, 0,
     0x7FF0000000000000, 0xFFF0000000000000, 1, 0x000FFFFFFFFFFFFF,
     0x7FEFFFFFFFFFFFFF, 0x0010000000000000, 0x47EFFFFFE0000000,
     0x3690000000000000],
    dtype=np.uint64,
)

#: counters of the device executor that this query's own rule holds at 0
#: where the result carries them: a copy through a device float64 that is not
#: one, and a rechunk that went through the host instead of the chip
ZERO_COUNTERS = ("f64_lossy_moves", "rechunk_host_whole", "rechunk_host_copy")


def target_chunks(deploy: dict) -> tuple:
    """Column slabs: every row, half a source chunk's columns."""
    return (deploy["shape"][0], deploy["chunks"][1] // 2)


def make_sources(deploy: dict, seed: int, workdir: str) -> dict:
    """Write the source from the seed, chunk by chunk from one reused buffer:
    uniform values in [0, 1) and, in every source chunk, the twelve
    ``EDGE_BITS`` at positions drawn from the seed, some of them in each of
    the target's column slabs that the chunk crosses. The reference is the
    source's bytes as numpy alone reads them back, viewed as ``uint64``."""
    shape, chunks = tuple(deploy["shape"]), tuple(deploy["chunks"])
    width = target_chunks(deploy)[1]
    if any(s % c for s, c in zip(shape, chunks)) or chunks[1] % width:
        raise ValueError("the source is made of whole chunks, each of whole slabs")
    slabs = chunks[1] // width
    rng = np.random.Generator(np.random.PCG64(seed))
    path = os.path.join(workdir, "a.zarr")
    zarrv2.create(path, shape, chunks, np.float64)
    block = np.empty(chunks)
    bits = block.view(np.uint64)
    for idx, _ in zarrv2.chunk_slices(shape, chunks):
        rng.random(out=block)
        for slab in range(slabs):
            mine = EDGE_BITS[slab::slabs]
            where = rng.choice(chunks[0] * width, size=mine.size, replace=False)
            bits[where // width, slab * width + where % width] = mine
        zarrv2.write_chunk(path, idx, block)
    return {"a": path, "ref": zarrv2.read_zarr_v2(path).view(np.uint64)}


def build(deploy: dict, sources: dict, spec, target):
    return ct.from_zarr(sources["a"], spec=spec).rechunk(target_chunks(deploy))


def run(expr, executor, callbacks, target):
    """Returns the executor's counters where it has them, for ``check``."""
    ct.to_zarr(expr, target, executor=executor, callbacks=callbacks)
    return dict(getattr(executor, "stats", None) or {})


def _check_files(deploy: dict, target: str) -> dict:
    """``.zarray`` says the deployment's shape, ``<f8``, the column slabs and
    no compressor, and the store holds exactly their chunk files (their
    lengths are the caller's to check); returns ``zarrv2.chunk_files``."""
    try:
        meta = zarrv2.read_meta(target)
    except ValueError as e:
        raise AssertionError(str(e)) from None
    want = {"shape": list(deploy["shape"]), "chunks": list(target_chunks(deploy)),
            "dtype": "<f8"}
    got = {k: meta[k] for k in want}
    if got != want:
        raise AssertionError(f"{target}: .zarray says {got}, expected {want}")
    files = zarrv2.chunk_files(target)
    present = sorted(n for n in os.listdir(target) if not n.startswith("."))
    if present != sorted(files):
        raise AssertionError(f"{target}: holds {present}, expected {sorted(files)}")
    return files


def check(deploy: dict, sources: dict, result, first, target, full: bool) -> None:
    """Full: the target read back with numpy alone equals the source as
    ``uint64``, every element; every chunk's CRC-32 equals its manifest
    entry; the device executor's counters, where the result holds them, say
    that no copy went through a lossy float64 nor through the host. Otherwise:
    the chunk files at full length with their manifest entries, no chunk read.

    The comparison is of bit patterns and not of numbers: at tolerance 0
    ``zarrv2.excess`` calls ``-0.0`` equal to ``0.0`` and a NaN unequal to
    itself. Being exact, it fails any compute in a lower precision than the
    configuration's float64: the v5e's own float64 (a pair of float32, about
    49 bits) changes 86% of uniform values (``_zarr_add_sources.py``, PR 21)
    and every NaN payload, and float32 changes nearly all."""
    files = _check_files(deploy, target)
    if not full:
        zarrv2.check_chunks_present(target)
        return
    ref = sources["ref"]
    chunks = target_chunks(deploy)
    manifest = zarrv2.read_checksum_manifest(target)
    block = np.empty(chunks, np.uint64)
    unequal = np.empty(chunks, np.bool_)
    differ, first_at, first_got, bad_crc = 0, None, None, []
    for idx, sel in zarrv2.chunk_slices(ref.shape, chunks):
        key = ".".join(map(str, idx))
        file, size = files[key]
        with open(file, "rb") as f:
            if f.readinto(memoryview(block).cast("B")) != size or f.read(1):
                raise AssertionError(f"{file}: not the {size} bytes of a full chunk")
        crc = zlib.crc32(memoryview(block).cast("B")) & 0xFFFFFFFF
        entry = manifest.get(key)
        if entry is None or entry["c"] != crc or entry["n"] != size:
            bad_crc.append(f"chunk {key} has CRC-32 {crc}, manifest says {entry}")
        np.not_equal(block, ref[sel], out=unequal)
        n = int(np.count_nonzero(unequal))
        if n and first_at is None:
            inside = np.unravel_index(int(np.flatnonzero(unequal)[0]), chunks)
            first_at = tuple(int(s.start + i) for s, i in zip(sel, inside))
            first_got = int(block[inside])
        differ += n
    if differ:
        raise AssertionError(
            f"{target}: {differ} of {ref.size} elements differ from the source "
            f"bitwise, the first at {first_at}: {first_got:#018x}, the source has "
            f"{int(ref[first_at]):#018x}"
        )
    if bad_crc:
        raise AssertionError(f"{target}: " + "; ".join(bad_crc))
    if "segments_traced" in (result or {}):
        nonzero = {k: result[k] for k in ZERO_COUNTERS if result.get(k)}
        if nonzero:
            raise AssertionError(f"the copy left the chip's exact path: {nonzero}")


def nominal_bytes(deploy: dict) -> int:
    """One array read and one written."""
    return 2 * math.prod(deploy["shape"]) * 8
