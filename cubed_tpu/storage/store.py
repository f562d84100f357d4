"""A minimal, dependency-free Zarr-v2-compatible chunked array store.

The image has no zarr-python, so the persistent-storage layer is implemented
from scratch: directory stores holding a ``.zarray`` JSON metadata document and
one raw (uncompressed, C-order) file per chunk, named with ``.``-separated
chunk indices — the standard Zarr v2 on-disk layout, readable by any Zarr
implementation. Chunk writes are atomic and durable (temp file + fsync +
rename), which is what makes duplicate/backup tasks and retries safe,
matching the reference's object-storage semantics (docs/reliability.md).
Every chunk write also records a checksum in a per-array sidecar manifest,
task-scope reads can verify it, and resume scans trust only verified
chunks — see ``storage/integrity.py`` for the full contract.

Local paths use direct file IO; other URLs go through fsspec.

Reference parity: the role of the zarr-python dependency in cubed
(cubed/storage/zarr.py uses ``zarr.open_array``).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import tempfile
import time
import uuid
from math import prod
from typing import Any, Optional, Sequence

import numpy as np

from ..chunks import blockdims_from_blockshape
from ..observability.accounting import (
    record_bytes_read,
    record_bytes_written,
    record_scoped_counter,
    scope_span,
)
from ..observability.metrics import get_registry
from ..runtime import cancellation
from ..runtime import transfer as p2p
from ..runtime.faults import (
    FaultInjectedIOError,
    FaultInjectedThrottleError,
    get_injector,
)
from ..runtime.shuffle import byte_ranges, chunk_key_str
from ..runtime.resilience import RetryPolicy
from ..utils import join_path
from . import health, integrity
from .integrity import ChunkIntegrityError

logger = logging.getLogger(__name__)

_LOCAL_SCHEMES = ("", "file")

#: a crashed writer's orphaned ``.tmp`` is only swept once it is at least
#: this old — a LIVE writer's temp file (written then atomically renamed
#: within milliseconds) must never be yanked out from under it
ORPHAN_TMP_MAX_AGE_S = 60.0

#: (raw env value, policy) — chunk-read retries for transient IO errors,
#: tunable via CUBED_TPU_STORAGE_READ_RETRIES (0 disables)
_read_policy_cache: tuple = (None, None)


def _read_retry_policy() -> RetryPolicy:
    global _read_policy_cache
    raw = os.environ.get("CUBED_TPU_STORAGE_READ_RETRIES", "2")
    cached_raw, cached = _read_policy_cache
    if raw == cached_raw:
        return cached
    try:
        retries = max(0, int(raw))
    except ValueError:
        retries = 2
    policy = RetryPolicy(retries=retries, backoff_base=0.02, backoff_max=0.5)
    _read_policy_cache = (raw, policy)
    return policy


def _is_local(path: str) -> bool:
    from urllib.parse import urlsplit

    return urlsplit(str(path)).scheme in _LOCAL_SCHEMES


def _strip_file_scheme(path: str) -> str:
    return str(path)[7:] if str(path).startswith("file://") else str(path)


class _LocalIO:
    """Direct filesystem IO for local stores (the fast path)."""

    def __init__(self, root: str):
        self.root = _strip_file_scheme(root)

    def makedirs(self) -> None:
        os.makedirs(self.root, exist_ok=True)

    def exists(self, name: str) -> bool:
        return os.path.exists(os.path.join(self.root, name))

    def _open_for_read(self, name: str, **kwargs):
        """The object's file, open for reading, once the fault injector has
        had its say (a throttle, a failed read)."""
        injector = get_injector()
        if injector is not None:
            if injector.storage_throttle_fault(_fault_key(self.root, name)):
                raise FaultInjectedThrottleError(
                    f"injected store throttle (503 SlowDown): {name}"
                )
            if injector.storage_read_fault(_fault_key(self.root, name)):
                raise FaultInjectedIOError(f"injected read failure: {name}")
        return open(os.path.join(self.root, name), "rb", **kwargs)

    def read_bytes(self, name: str) -> bytes:
        with self._open_for_read(name) as f:
            return f.read()

    def readinto(self, name: str, buffer):
        """``read_bytes`` into the caller's writable ``buffer``: the bytes
        read, as a view of its head. A caller that hands over the same
        buffer again pays for no fresh pages (200 MB: 160 to 225 ms into
        fresh pages, 22 to 24 ms into touched ones on the v5e's host;
        PERF.md section 6, PR 29). An object that does not fit is read as
        ``read_bytes`` reads it, and nothing of ``buffer`` then holds it."""
        with self._open_for_read(name, buffering=0) as f:
            size = os.fstat(f.fileno()).st_size
            view = memoryview(buffer).cast("B")
            if size > len(view):
                return f.read()
            n = 0
            while n < size:
                got = f.readinto(view[n:size])
                if not got:
                    break
                n += got
            return view[:n]

    def write_bytes_atomic(self, name: str, data: bytes, inject: bool = True) -> None:
        path = os.path.join(self.root, name)
        tmp = path + f".{uuid.uuid4().hex[:8]}.tmp"
        injector = get_injector() if inject else None
        if injector is not None and injector.storage_throttle_fault(
            _fault_key(self.root, name)
        ):
            # a throttled PUT touches nothing: the request was refused
            raise FaultInjectedThrottleError(
                f"injected store throttle (503 SlowDown): {name}"
            )
        if injector is not None and injector.storage_write_fault(
            _fault_key(self.root, name)
        ):
            if injector.config.storage_write_leaves_tmp:
                # model a writer killed mid-write: a partial temp file is
                # left behind, the chunk itself stays untouched (exactly
                # what the orphan sweep + resume must tolerate)
                with open(tmp, "wb") as f:
                    f.write(data[: max(1, len(data) // 2)])
            raise FaultInjectedIOError(f"injected write failure: {name}")
        if injector is not None:
            # seeded bit-flip/truncation corruption: the write "succeeds"
            # but the bytes on disk are wrong — exactly what checksums exist
            # to catch (the caller records the checksum of the bytes it
            # intended to write, not what landed on disk)
            corrupted = injector.storage_corrupt_fault(
                _fault_key(self.root, name), data
            )
            if corrupted is not None:
                data = corrupted
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            # fsync before rename: without it a host crash can leave a
            # renamed-but-empty chunk that existence-based accounting (and
            # any pre-checksum reader) counts as done
            with scope_span("fsync", cat="storage"):
                os.fsync(f.fileno())
        os.replace(tmp, path)  # atomic on POSIX: concurrent duplicate tasks are safe
        with scope_span("fsync", cat="storage", dir=True):
            _fsync_dir(os.path.dirname(path))

    def rename(self, old: str, new: str) -> None:
        os.replace(os.path.join(self.root, old), os.path.join(self.root, new))

    def append_bytes(self, name: str, data: bytes) -> None:
        """O_APPEND write for the manifest's JSONL shards. One writer per
        shard file by construction (per-process naming), so appends never
        interleave; no fsync — a lost tail costs recomputation on resume,
        never correctness (the loader skips torn lines)."""
        with open(os.path.join(self.root, name), "ab") as f:
            f.write(data)

    def list_names(self) -> list[str]:
        try:
            return os.listdir(self.root)
        except FileNotFoundError:
            return []

    def sweep_tmp(self, max_age_s: float = ORPHAN_TMP_MAX_AGE_S) -> int:
        """Remove orphaned ``*.tmp`` files left by crashed writers.

        Only files older than *max_age_s* go: a temp file that young may
        belong to a live writer about to ``os.replace`` it. Returns the
        number removed. Missing files (a concurrent sweeper or the writer's
        rename) are skipped silently — the sweep is best-effort hygiene,
        never load-bearing (readers and ``nchunks_initialized`` already
        ignore ``.tmp`` names)."""
        removed = 0
        now = time.time()
        for name in self.list_names():
            if not name.endswith(".tmp"):
                continue
            path = os.path.join(self.root, name)
            try:
                if now - os.path.getmtime(path) < max_age_s:
                    continue
                os.unlink(path)
                removed += 1
            except OSError:
                continue
        if removed:
            get_registry().counter("orphan_tmps_swept").inc(removed)
            logger.info(
                "swept %d orphaned tmp file(s) from %s", removed, self.root
            )
        return removed


def _fsync_dir(path: str) -> None:
    """Best-effort directory fsync after a rename: makes the new directory
    entry itself durable, so a host crash can't forget a chunk whose bytes
    were already fsynced. Filesystems without directory fsync (or platforms
    without O_DIRECTORY) just skip it — the chunk data is still synced."""
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


class _FsspecIO:
    """fsspec-backed IO for remote stores (s3://, gs://, memory://, ...)."""

    def __init__(self, root: str, storage_options: Optional[dict] = None):
        import fsspec

        self.fs, self.root = fsspec.core.url_to_fs(root, **(storage_options or {}))

    def makedirs(self) -> None:
        self.fs.makedirs(self.root, exist_ok=True)

    def exists(self, name: str) -> bool:
        return self.fs.exists(f"{self.root}/{name}")

    def read_bytes(self, name: str) -> bytes:
        injector = get_injector()
        if injector is not None:
            if injector.storage_throttle_fault(_fault_key(self.root, name)):
                raise FaultInjectedThrottleError(
                    f"injected store throttle (503 SlowDown): {name}"
                )
            if injector.storage_read_fault(_fault_key(self.root, name)):
                raise FaultInjectedIOError(f"injected read failure: {name}")
        with self.fs.open(f"{self.root}/{name}", "rb") as f:
            return f.read()

    def write_bytes_atomic(self, name: str, data: bytes, inject: bool = True) -> None:
        injector = get_injector() if inject else None
        if injector is not None and injector.storage_throttle_fault(
            _fault_key(self.root, name)
        ):
            raise FaultInjectedThrottleError(
                f"injected store throttle (503 SlowDown): {name}"
            )
        if injector is not None and injector.storage_write_fault(
            _fault_key(self.root, name)
        ):
            # whole-object PUTs can't leave partial objects; just fail
            raise FaultInjectedIOError(f"injected write failure: {name}")
        if injector is not None:
            corrupted = injector.storage_corrupt_fault(
                _fault_key(self.root, name), data
            )
            if corrupted is not None:
                data = corrupted
        # object stores have atomic whole-object PUTs
        with self.fs.open(f"{self.root}/{name}", "wb") as f:
            f.write(data)

    def rename(self, old: str, new: str) -> None:
        self.fs.mv(f"{self.root}/{old}", f"{self.root}/{new}")

    def list_names(self) -> list[str]:
        try:
            return [p.rsplit("/", 1)[-1] for p in self.fs.ls(self.root, detail=False)]
        except FileNotFoundError:
            return []

    def sweep_tmp(self, max_age_s: float = ORPHAN_TMP_MAX_AGE_S) -> int:
        """Object-store writes are whole-object PUTs — no temp files to
        sweep (a crashed PUT leaves nothing)."""
        return 0


def _active_breaker(store: str):
    """The store's health breaker, or None when the breaker is disabled
    (``CUBED_TPU_STORE_BREAKER=off``)."""
    return health.store_breaker(store) if health.breaker_enabled() else None


@contextlib.contextmanager
def _breaker_slot(breaker, key: str):
    """Take (and release) the breaker's IO slot around ONE IO attempt —
    callers keep retry sleeps OUTSIDE the slot so a paced holder never
    idles the store's whole concurrency allowance. While the breaker is
    degraded, the wait for a slot — the whole point of AIMD pacing — is
    recorded as a ``throttle_wait`` span so ``analyze()`` attributes
    brownout time honestly. ``breaker=None`` (disabled) is a no-op."""
    if breaker is None:
        yield
        return
    if breaker.state == "closed":
        breaker.acquire()  # counter bump, no wait possible
    else:
        with scope_span(
            "throttle_wait", cat="throttle", site="breaker_slot", key=key
        ):
            # poll the cancellation token between wait quanta: a
            # cancelled/deadlined compute escapes a degraded store's
            # slot queue immediately instead of serving out the wait
            breaker.acquire(poll=cancellation.check_current)
    try:
        yield
    finally:
        breaker.release()


def _note_throttle(store: str, breaker) -> float:
    """Shared throttle accounting: counts ``store_throttled`` (a scoped
    counter, so fleet-worker throttles ride task stats back to the client
    registry) and steps the breaker down, returning its paced retry
    delay (a deterministic floor when the breaker is off)."""
    record_scoped_counter("store_throttled")
    if breaker is not None:
        return breaker.on_throttle()
    return 0.0


def _fault_key(root: str, name: str) -> str:
    """Injection-decision key: array dirname + chunk name, NOT the full
    path. Work dirs are per-run temp paths; hashing them would make a
    seeded chaos run non-reproducible, while the array's own name (the
    plan's stable op naming) plus the chunk index replays identically."""
    return f"{os.path.basename(str(root).rstrip('/'))}/{name}"


def _make_io(store: str, storage_options: Optional[dict] = None):
    if _is_local(store):
        return _LocalIO(store)
    return _FsspecIO(store, storage_options)


def _encode_dtype(dtype: np.dtype) -> Any:
    if dtype.fields is not None:
        return [[name, dtype.fields[name][0].str] for name in dtype.names]
    return dtype.str


def _decode_dtype(d: Any) -> np.dtype:
    if isinstance(d, list):
        return np.dtype([(name, dt) for name, dt in d])
    return np.dtype(d)


def _codec_from_meta(comp: Optional[dict]):
    """(compress, decompress) callables for a Zarr v2 ``compressor`` config.

    Covers the numcodecs ids expressible with the stdlib — ``zlib``,
    ``gzip``, ``bz2``, ``lzma`` — which is what this image can run (no
    numcodecs/blosc wheel; the reference's default blosc-compressed stores
    need that C library and fail here with a clear message instead of
    garbage)."""
    if comp is None:
        return None
    cid = comp.get("id")
    if cid == "zlib":
        import zlib

        level = int(comp.get("level", 1))
        return (lambda b: zlib.compress(b, level)), zlib.decompress
    if cid == "gzip":
        import gzip

        level = int(comp.get("level", 1))
        return (lambda b: gzip.compress(b, compresslevel=level)), gzip.decompress
    if cid == "bz2":
        import bz2

        level = int(comp.get("level", 1))
        return (lambda b: bz2.compress(b, level)), bz2.decompress
    if cid == "lzma":
        import lzma

        preset = comp.get("preset")
        fmt = comp.get("format", lzma.FORMAT_XZ)
        filters = comp.get("filters")
        # FORMAT_RAW streams are undecodable without the filter chain, but
        # container formats (XZ/ALONE) embed it and lzma.decompress REJECTS
        # an explicit filters argument for them
        if fmt == lzma.FORMAT_RAW:
            decompress = lambda b: lzma.decompress(  # noqa: E731
                b, format=lzma.FORMAT_RAW, filters=filters
            )
        else:
            decompress = lzma.decompress
        return (
            lambda b: lzma.compress(b, format=fmt, preset=preset, filters=filters),
            decompress,
        )
    raise ValueError(
        f"Unsupported Zarr compressor {cid!r}: this store supports the "
        "stdlib codecs zlib/gzip/bz2/lzma (blosc and friends need the "
        "numcodecs C library, absent from this environment)"
    )


def _copied(made: np.ndarray, given) -> bool:
    """Whether ``made``, numpy's conversion of ``given``, is a copy of it
    and not a view of the same memory."""
    return made.size > 0 and not (
        isinstance(given, np.ndarray) and np.may_share_memory(made, given)
    )


def _encode_fill(fill_value: Any, dtype: np.dtype) -> Any:
    if fill_value is None:
        return None
    if dtype.kind == "f":
        f = float(fill_value)
        if np.isnan(f):
            return "NaN"
        if np.isinf(f):
            return "Infinity" if f > 0 else "-Infinity"
        return f
    if dtype.kind in "iu":
        return int(fill_value)
    if dtype.kind == "b":
        return bool(fill_value)
    return None


def _decode_fill(v: Any, dtype: np.dtype) -> Any:
    if v is None:
        return None
    if v == "NaN":
        return np.nan
    if v == "Infinity":
        return np.inf
    if v == "-Infinity":
        return -np.inf
    return v


class ZarrV2Array:
    """A chunked N-dimensional array persisted in Zarr v2 directory format."""

    def __init__(
        self,
        store: str,
        meta: dict,
        storage_options: Optional[dict] = None,
    ):
        self.store = str(store)
        self._io = _make_io(store, storage_options)
        self._meta = meta
        self.shape: tuple[int, ...] = tuple(meta["shape"])
        self.chunks: tuple[int, ...] = tuple(meta["chunks"])
        self.dtype: np.dtype = _decode_dtype(meta["dtype"])
        self.fill_value = _decode_fill(meta.get("fill_value"), self.dtype)
        self.compressor: Optional[dict] = meta.get("compressor")
        self._codec = _codec_from_meta(self.compressor)
        #: merged manifest, loaded lazily per instance (instances are opened
        #: per task, so the cache lives at most one task — fresh enough,
        #: since an array's chunks are fully written before a consuming op
        #: reads them)
        self._manifest_cache: Optional[tuple[dict, bool]] = None

    # -- metadata ----------------------------------------------------------

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def size(self) -> int:
        return prod(self.shape) if self.shape else 1

    @property
    def nbytes(self) -> int:
        return self.size * self.dtype.itemsize

    @property
    def cdata_shape(self) -> tuple[int, ...]:
        """Number of chunks along each dimension."""
        return tuple(
            max(1, -(-s // c)) for s, c in zip(self.shape, self.chunks)
        ) if self.shape else ()

    @property
    def nchunks(self) -> int:
        return prod(self.cdata_shape) if self.shape else 1

    def _chunk_names(self) -> list[str]:
        """Names of chunk objects present in the store: digit-dotted keys
        only — metadata, manifests, ``.tmp`` litter and ``*.quarantine.*``
        files are all excluded."""
        out = []
        for name in self._io.list_names():
            if name.startswith("."):  # .zarray/.zattrs/.manifest-*
                continue
            if name.endswith(".tmp"):
                continue
            parts = name.split(".")
            if all(p.lstrip("-").isdigit() for p in parts):
                out.append(name)
        return out

    @property
    def nchunks_initialized(self) -> int:
        """Number of chunk objects present in the store (drives the
        existence-only resume fallback; checksum-verified resume uses
        :meth:`verify_chunks`)."""
        return len(self._chunk_names())

    def chunkset(self) -> tuple[tuple[int, ...], ...]:
        """Chunks in tuple-of-block-sizes form."""
        return blockdims_from_blockshape(self.shape, self.chunks)

    # -- chunk IO ----------------------------------------------------------

    def _chunk_key(self, idx: tuple[int, ...]) -> str:
        # the ONE dotted chunk-file-key formatter, shared with the
        # dataflow/shuffle edge math (a drift would silently degrade every
        # rechunk edge to a barrier and break resume-key matching)
        return chunk_key_str(idx)

    def _chunk_nbytes(self) -> int:
        return prod(self.chunks) * self.dtype.itemsize if self.chunks else self.dtype.itemsize

    def _read_chunk(
        self, idx: tuple[int, ...], allow_peer: bool = True, into=None
    ) -> Optional[np.ndarray]:
        """Read the full (padded) chunk at block index *idx*, or None if
        absent. ``allow_peer=False`` skips the peer fast path — used after
        a sub-chunk range fetch already attempted (and missed/failed) the
        peer for this chunk, so one logical read never draws the fault
        injector or counts a miss twice. ``into``: see
        ``_read_chunk_into``."""
        key = self._chunk_key(idx)
        # cooperative cancellation: between chunk reads is a safe abort
        # boundary — nothing half-written, resume is bitwise-correct
        cancellation.check_current()
        verify = integrity.verify_reads_active()
        if allow_peer and p2p.task_fetch_active():
            # peer-fetch fast path (fleet workers, Spec/executor-armed):
            # bytes come from the producing worker's chunk cache, verified
            # (CRC32 + length) against the authoritative manifest entry
            # inside fetch_chunk — a chunk without an entry, or any miss/
            # timeout/peer-death/mismatch, returns None and the normal
            # store read below proceeds as if the peer path didn't exist
            entry = self._manifest()[0].get(key)
            if entry is not None:
                data = p2p.fetch_chunk(self.store, key, entry)
                if data is not None:
                    if self._codec is not None:
                        data = self._codec[1](data)
                    arr = np.frombuffer(data, dtype=self.dtype)
                    return arr.reshape(self.chunks if self.shape else ())
        if not self._io.exists(key):
            if verify and key in self._manifest()[0]:
                # the manifest says this chunk WAS written: absence is an
                # integrity failure (quarantined earlier, or the store lost
                # it), NOT a never-written chunk that may serve fill values
                # — silently substituting fill for real data would complete
                # the compute with wrong results
                record_scoped_counter("chunks_corrupt_detected")
                raise ChunkIntegrityError(
                    f"chunk {key} of {self.store} is recorded in the "
                    "manifest but missing from the store",
                    store=self.store, chunk_key=key, kind="missing",
                )
            return None
        with scope_span("storage_read", cat="storage", key=key) as sp:
            data = self._read_bytes_with_retries(key, into)
            sp.attrs["bytes"] = len(data)
        # IO bytes as stored (pre-decompression), attributed to the reading
        # task's scope when one is active (observability/accounting.py)
        record_bytes_read(self.store, len(data))
        if verify:
            with scope_span("integrity_verify", cat="integrity", key=key):
                self._verify_chunk_bytes(key, data)
        if self._codec is not None:
            data = self._codec[1](data)
        arr = np.frombuffer(data, dtype=self.dtype)
        return arr.reshape(self.chunks if self.shape else ())

    def _read_chunk_into(
        self, idx: tuple[int, ...], staging: np.ndarray
    ) -> Optional[np.ndarray]:
        """``_read_chunk`` with the chunk's file read into ``staging``, a
        writable buffer of at least ``_chunk_nbytes()`` bytes that the
        caller owns and hands over again: the chunk returned is then a view
        of it, valid until the caller's next read into it. Everything else
        is ``_read_chunk``'s: the cancellation point, the injected faults,
        retries and breaker pacing, the byte accounting, the
        ``storage_read`` span, verification of the staged bytes with
        quarantine, None for a chunk never written. A store with a codec, an
        IO class without a ``readinto`` and the peer path hand over the
        bytes they produce, and ``staging`` is left alone."""
        return self._read_chunk(idx, into=staging)

    def _read_chunk_region(
        self, idx: tuple[int, ...], chunk_sel: tuple[slice, ...]
    ) -> tuple[Optional[np.ndarray], bool]:
        """Peer-fetch exactly the sub-region of one chunk that a bulk read
        needs (the shuffle fast path: a rechunk target task overlapping a
        sliver of a source chunk pulls that sliver, not the whole chunk).

        Returns ``(region, peer_attempted)``: the selected sub-array, or
        None with ``peer_attempted`` saying whether the peer path already
        tried (and missed/failed) for this chunk — the caller then reads
        the store directly instead of re-trying the whole-chunk peer
        path, so one logical read records exactly one peer outcome. Only
        for uncompressed stores (a codec makes byte ranges of the stored
        object meaningless), unit-step selections, manifest-recorded
        chunks, and regions small enough that ranged fetching beats a
        whole-chunk fetch (``shuffle.byte_ranges`` decides)."""
        if self._codec is not None or not p2p.task_fetch_active():
            return None, False
        if any((s.step or 1) != 1 for s in chunk_sel):
            return None, False
        key = self._chunk_key(idx)
        entry = self._manifest()[0].get(key)
        if entry is None:
            return None, False  # unverifiable: never take the peer path
        ranges = byte_ranges(
            self.chunks if self.shape else (), self.dtype.itemsize, chunk_sel
        )
        if ranges is None:
            return None, False
        payload, attempted = p2p.fetch_chunk_ranges(
            self.store, key, entry, ranges
        )
        if payload is None:
            return None, attempted
        region_shape = tuple(s.stop - s.start for s in chunk_sel)
        arr = np.frombuffer(payload, dtype=self.dtype)
        return arr.reshape(region_shape), True

    def _manifest(self) -> tuple[dict, bool]:
        """Merged checksum manifest ``(entries, had_shards)``, cached per
        instance (see ``__init__``)."""
        if self._manifest_cache is None:
            self._manifest_cache = integrity.load_manifest(self._io)
        return self._manifest_cache

    def _verify_chunk_bytes(self, key: str, data: bytes) -> None:
        """Verify stored chunk bytes against the manifest; on mismatch
        quarantine the file and raise :class:`ChunkIntegrityError`. Chunks
        with no manifest entry pass unverified (written with integrity off,
        or by a pre-integrity version — there is nothing to check against)."""
        entry = self._manifest()[0].get(key)
        if entry is None:
            return
        record_scoped_counter("chunks_verified")
        actual = (integrity.checksum(data), len(data))
        expected = (entry.get("c"), entry.get("n"))
        if actual != expected:
            record_scoped_counter("chunks_corrupt_detected")
            integrity.quarantine_chunk(self._io, key, store=self.store)
            raise ChunkIntegrityError(
                f"chunk {key} of {self.store} failed checksum verification "
                f"(expected crc32={expected[0]} len={expected[1]}, got "
                f"crc32={actual[0]} len={actual[1]}); file quarantined",
                store=self.store, chunk_key=key, kind="checksum",
                expected=expected, actual=actual,
            )

    def verify_chunks(
        self,
        quarantine: bool = True,
        verify: bool = True,
        count: bool = True,
    ) -> tuple[set, list, bool]:
        """Verify every stored chunk against the manifest.

        Returns ``(valid, corrupt, verified)``: the set of chunk keys whose
        bytes match their recorded checksum, the list that failed (moved to
        ``*.quarantine.*`` when *quarantine* is set), and whether
        verification actually ran. With no manifest at all (integrity off /
        legacy store) — or with ``verify=False`` (how a resume scan honors
        ``integrity="off"``) — every present chunk is reported valid and
        ``verified`` is False: existence-only accounting, the pre-integrity
        behavior. ``count=False`` keeps the scan off the metrics registry
        (plan introspection must not skew execution counters). A chunk
        present on disk but absent from the manifest is reported corrupt
        (it cannot be trusted), but is never quarantined — it may be a
        legitimate write that raced manifest recording, and re-running its
        producing task overwrites it in place.
        """
        names = self._chunk_names()
        if not verify:
            return set(names), [], False
        entries, had_shards = integrity.load_manifest(self._io)
        if not had_shards:
            return set(names), [], False
        valid: set = set()
        corrupt: list = []
        for name in names:
            entry = entries.get(name)
            ok = False
            if entry is not None:
                try:
                    data = self._io.read_bytes(name)
                except OSError:
                    data = None
                ok = (
                    data is not None
                    and len(data) == entry.get("n")
                    and integrity.checksum(data) == entry.get("c")
                )
                if count:
                    record_scoped_counter("chunks_verified")
            if ok:
                valid.add(name)
            else:
                corrupt.append(name)
                if count:
                    record_scoped_counter("chunks_corrupt_detected")
                if quarantine and entry is not None:
                    integrity.quarantine_chunk(self._io, name, store=self.store)
        return valid, corrupt, True

    def _read_bytes_with_retries(self, key: str, into=None) -> bytes:
        """Chunk reads retry transient IO errors at the storage layer.

        A flaky read inside a task would otherwise burn a whole task retry
        (re-running every read and the compute the task already did); two
        cheap in-place retries with short backoff absorb the common blip.
        ``FileNotFoundError`` after a successful exists() is an anomaly
        (chunks are write-once; the sweep only touches ``.tmp`` names), so
        it retries like any OSError — an eventually-consistent store heals,
        anything else fails the task loudly. It must NOT read as "absent":
        silently substituting fill values for real data would complete the
        compute with wrong results.

        With ``into`` (a writable buffer) the stored bytes land there and
        come back as a view of it, where they are the chunk's own bytes (no
        codec) and the IO class can read into a buffer.
        """
        readinto = None
        if into is not None and self._codec is None:
            readinto = getattr(self._io, "readinto", None)
        policy = _read_retry_policy()
        breaker = _active_breaker(self.store)
        failures = 0
        throttles = 0
        while True:
            try:
                # the breaker slot covers only the IO attempt itself —
                # retry sleeps below run with the slot RELEASED, so a
                # paced holder never idles the store's whole allowance
                with _breaker_slot(breaker, key):
                    if readinto is None:
                        data = self._io.read_bytes(key)
                    else:
                        data = readinto(key, into)
                if breaker is not None:
                    breaker.on_success()
                return data
            except OSError as exc:
                if health.is_throttle_error(exc):
                    # the store is browning out (429/503/SlowDown):
                    # retry IN PLACE with breaker pacing — slowing
                    # down is the cure, and an absorbed throttle
                    # draws nothing from the task-retry budget. With
                    # the breaker off (or pacing exhausted) the
                    # throttle surfaces to the task level, classified
                    # THROTTLE
                    throttles += 1
                    delay = _note_throttle(self.store, breaker)
                    if (
                        breaker is None
                        or throttles > health.THROTTLE_IO_RETRIES
                    ):
                        raise
                    logger.info(
                        "store %s throttled read %s (throttle %d); "
                        "paced in-place retry in %.3fs",
                        self.store, key, throttles, delay,
                    )
                    if delay > 0:
                        with scope_span(
                            "throttle_wait", cat="throttle",
                            site="storage_read", key=key,
                        ):
                            time.sleep(delay)
                    # a cancel/deadline that landed during the paced
                    # sleep aborts here instead of retrying the store
                    cancellation.check_current()
                    continue
                failures += 1
                if failures > policy.retries:
                    raise
                delay = policy.backoff_delay(failures)
                logger.info(
                    "retrying chunk read %s/%s (attempt %d) in %.3fs: %s",
                    self.store, key, failures + 1, delay, exc,
                )
                get_registry().counter("storage_read_retries").inc()
                if delay > 0:
                    with scope_span(
                        "retry_sleep", cat="retry", site="storage_read",
                        key=key,
                    ):
                        time.sleep(delay)

    def _write_chunk(
        self, idx: tuple[int, ...], arr: np.ndarray, copied: bool = False
    ) -> None:
        """One whole chunk, durable and in the manifest when this returns.

        The bytes are written from where they lie: a C-contiguous ``arr``
        of the store's dtype is handed to the file, the checksum and the
        codec as a view, and is the caller's again once this returns (what
        keeps bytes beyond that, the peer cache and an injected corruption,
        takes a copy of its own). Any other input is made contiguous
        first, which is the one copy. ``copied``: the caller made ``arr``
        by copying what it was given (``__setitem__``: a padded or merged
        chunk, a converted dtype); either way the chunk's bytes count as
        ``encode_copy_bytes``, and the ``chunk_encode`` span says so. The
        CRC-32 and the manifest line are counted as ``checksum_us``."""
        # cooperative cancellation: checked BEFORE the write starts — an
        # abort never interrupts an atomic chunk write mid-flight, so the
        # store/manifest/journal stay consistent for resume
        cancellation.check_current()
        key = self._chunk_key(idx)
        with scope_span("chunk_encode", cat="storage", key=key) as sp:
            given, arr = arr, np.ascontiguousarray(arr, dtype=self.dtype)
            copied = sp.attrs["copied"] = copied or _copied(arr, given)
            data = memoryview(arr.reshape(-1).view(np.uint8))
            if self._codec is not None:
                data = self._codec[0](data)
            sp.attrs["bytes"] = len(data)
        kept = 0
        with scope_span(
            "storage_write", cat="storage", key=key, bytes=len(data)
        ):
            self._write_bytes_throttle_paced(key, data)
            if integrity.current_mode() != "off":
                # recorded AFTER the chunk write succeeds: a crash between
                # the two leaves a chunk without an entry, which resume
                # treats as not-computed (safe re-run) — never an entry
                # without its chunk
                # counted and no span: ``storage_write``'s children are its
                # fsyncs, and what remains of it is this and the file write
                started = time.perf_counter_ns()
                entry = integrity.record_checksum(
                    self._io, self.store, key, data
                )
                record_scoped_counter(
                    "checksum_us", (time.perf_counter_ns() - started) // 1000
                )
                if self._manifest_cache is not None:
                    self._manifest_cache[0][key] = entry
                    self._manifest_cache = (self._manifest_cache[0], True)
                # peer-transfer hook, strictly AFTER the durable write and
                # its checksum record: cache the stored bytes on this
                # worker and queue the (store, key, nbytes) advertisement
                # for the result frame. Zarr stays write-through — losing
                # the cached copy costs a store read, never data. Only
                # checksummed writes are cached: readers refuse peer bytes
                # they cannot verify against the manifest
                kept = p2p.note_chunk_written(self.store, key, data)
        record_bytes_written(self.store, len(data))
        if copied or kept:
            record_scoped_counter(
                "encode_copy_bytes", (arr.nbytes if copied else 0) + kept
            )

    def _write_bytes_throttle_paced(self, key: str, data: bytes) -> None:
        """Atomic chunk write with breaker-paced in-place retries for
        THROTTLE-shaped failures only (whole-chunk writes are idempotent,
        so an in-place retry after a refused PUT is always safe). Plain
        transient write failures keep their historical behavior: raise to
        the task level, where the retry machinery re-runs the task."""
        breaker = _active_breaker(self.store)
        throttles = 0
        while True:
            try:
                with _breaker_slot(breaker, key):
                    self._io.write_bytes_atomic(key, data)
                if breaker is not None:
                    breaker.on_success()
                return
            except OSError as exc:
                if not health.is_throttle_error(exc):
                    raise
                throttles += 1
                delay = _note_throttle(self.store, breaker)
                if (
                    breaker is None
                    or throttles > health.THROTTLE_IO_RETRIES
                ):
                    raise
                logger.info(
                    "store %s throttled write %s (throttle %d); "
                    "paced in-place retry in %.3fs",
                    self.store, key, throttles, delay,
                )
                if delay > 0:
                    with scope_span(
                        "throttle_wait", cat="throttle",
                        site="storage_write", key=key,
                    ):
                        time.sleep(delay)
                # a cancel/deadline that landed during the paced sleep
                # aborts here (the chunk write never started: atomic
                # writes are all-or-nothing, so state stays consistent)
                cancellation.check_current()

    def _empty_chunk(self) -> np.ndarray:
        fill = self.fill_value if self.fill_value is not None else 0
        return np.full(self.chunks if self.shape else (), fill, dtype=self.dtype)

    # -- indexing ----------------------------------------------------------

    def _normalize_key(self, key) -> tuple[slice, ...]:
        if not isinstance(key, tuple):
            key = (key,)
        if Ellipsis in key:
            i = key.index(Ellipsis)
            fill = self.ndim - (len(key) - 1)
            key = key[:i] + (slice(None),) * fill + key[i + 1 :]
        key = key + (slice(None),) * (self.ndim - len(key))
        out = []
        for k, s in zip(key, self.shape):
            if isinstance(k, (int, np.integer)):
                k = int(k)
                if k < 0:
                    k += s
                out.append(slice(k, k + 1))
            elif isinstance(k, slice):
                out.append(slice(*k.indices(s)))
            else:
                raise IndexError(f"Unsupported index {k!r} (use .oindex for fancy)")
        return tuple(out)

    def __getitem__(self, key) -> np.ndarray:
        if self.ndim == 0:
            chunk = self._read_chunk(())
            return chunk if chunk is not None else self._empty_chunk()
        sel = self._normalize_key(key)
        int_axes = []
        if isinstance(key, tuple):
            int_axes = [i for i, k in enumerate(key) if isinstance(k, (int, np.integer))]
        elif isinstance(key, (int, np.integer)):
            int_axes = [0]
        out_shape = tuple(
            max(0, (s.stop - s.start + (s.step or 1) - 1) // (s.step or 1)) for s in sel
        )
        out = np.empty(out_shape, dtype=self.dtype)
        if out.size == 0:
            return out.squeeze(axis=tuple(int_axes)) if int_axes else out

        # iterate over chunks intersecting the selection
        for cidx in self._chunks_overlapping(sel):
            c_starts = tuple(i * c for i, c in zip(cidx, self.chunks))
            chunk_sel = []
            out_sel = []
            skip = False
            for ax, (s, cs, clen, extent) in enumerate(
                zip(sel, c_starts, self.chunks, self.shape)
            ):
                step = s.step or 1
                lo = max(s.start, cs)
                hi = min(s.stop, cs + clen, extent)
                if step != 1:
                    # first selected index >= lo on the step grid anchored at s.start
                    offset = (lo - s.start) % step
                    if offset:
                        lo += step - offset
                if lo >= hi:
                    skip = True
                    break
                chunk_sel.append(slice(lo - cs, hi - cs, step))
                out_sel.append(
                    slice((lo - s.start) // step, (hi - s.start + step - 1) // step)
                )
            if skip:
                continue
            # sub-chunk peer fetch first (shuffle reads touching a sliver
            # of the chunk move only that sliver); an ineligible read
            # falls through to the whole-chunk peer-then-store path, an
            # attempted-and-failed one goes straight to the store (the
            # range path's fallback record is the one peer outcome)
            region, peer_tried = self._read_chunk_region(
                cidx, tuple(chunk_sel)
            )
            if region is not None:
                out[tuple(out_sel)] = region
                continue
            chunk = self._read_chunk(cidx, allow_peer=not peer_tried)
            if chunk is None:
                chunk = self._empty_chunk()
            out[tuple(out_sel)] = chunk[tuple(chunk_sel)]
        if int_axes:
            out = out.squeeze(axis=tuple(int_axes))
        return out

    def __setitem__(self, key, value) -> None:
        given, value = value, np.asarray(value, dtype=self.dtype)
        converted = _copied(value, given)
        if self.ndim == 0:
            self._write_chunk((), value, converted)
            return
        sel = self._normalize_key(key)
        if any((s.step or 1) != 1 for s in sel):
            raise IndexError("strided writes not supported")
        region_shape = tuple(s.stop - s.start for s in sel)
        value = np.broadcast_to(value, region_shape)

        for cidx in self._chunks_overlapping(sel):
            c_starts = tuple(i * c for i, c in zip(cidx, self.chunks))
            chunk_sel = []
            val_sel = []
            full_cover = True
            for s, cs, clen, extent in zip(sel, c_starts, self.chunks, self.shape):
                lo = max(s.start, cs)
                hi = min(s.stop, cs + clen)
                chunk_sel.append(slice(lo - cs, hi - cs))
                val_sel.append(slice(lo - s.start, hi - s.start))
                # chunk fully covered if the write spans [cs, min(cs+clen, extent))
                if lo > cs or hi < min(cs + clen, extent):
                    full_cover = False
            piece = value[tuple(val_sel)]
            covered_extent = tuple(
                min(cs + clen, ext) - cs
                for cs, clen, ext in zip(c_starts, self.chunks, self.shape)
            )
            if full_cover and covered_extent == self.chunks:
                self._write_chunk(cidx, piece, converted)
            elif full_cover:
                # edge chunk fully covered within array bounds: pad to chunk shape
                chunk = self._empty_chunk()
                chunk[tuple(slice(0, e) for e in covered_extent)] = piece
                self._write_chunk(cidx, chunk, True)
            else:
                chunk = self._read_chunk(cidx)
                if chunk is None:
                    chunk = self._empty_chunk()
                else:
                    chunk = chunk.copy()
                chunk[tuple(chunk_sel)] = piece
                self._write_chunk(cidx, chunk, True)

    def _chunks_overlapping(self, sel: tuple[slice, ...]):
        ranges = []
        for s, c in zip(sel, self.chunks):
            first = s.start // c
            last = max(first, (max(s.stop - 1, s.start)) // c)
            ranges.append(range(first, last + 1))
        import itertools

        return itertools.product(*ranges)

    # -- orthogonal (outer) indexing --------------------------------------

    @property
    def oindex(self) -> "_OIndex":
        return _OIndex(self)

    def __repr__(self) -> str:
        return f"ZarrV2Array<{self.store}, shape={self.shape}, dtype={self.dtype}, chunks={self.chunks}>"


class _OIndex:
    """Orthogonal indexing view: per-axis slices or integer arrays."""

    def __init__(self, array: ZarrV2Array):
        self.array = array

    def __getitem__(self, key) -> np.ndarray:
        a = self.array
        if not isinstance(key, tuple):
            key = (key,)
        key = key + (slice(None),) * (a.ndim - len(key))
        index_lists = []
        squeeze_axes = []
        for ax, k in enumerate(key):
            if isinstance(k, slice):
                index_lists.append(np.arange(*k.indices(a.shape[ax])))
            elif isinstance(k, (int, np.integer)):
                kk = int(k) + (a.shape[ax] if k < 0 else 0)
                index_lists.append(np.array([kk]))
                squeeze_axes.append(ax)
            else:
                arr = np.asarray(k)
                if arr.dtype == bool:
                    arr = np.flatnonzero(arr)
                arr = np.where(arr < 0, arr + a.shape[ax], arr)
                index_lists.append(arr.astype(np.int64))
        out_shape = tuple(len(ix) for ix in index_lists)
        out = np.empty(out_shape, dtype=a.dtype)
        if out.size:
            # group selected indices by chunk along each axis, then gather per chunk
            import itertools

            axis_groups = []
            for ax, ix in enumerate(index_lists):
                groups: dict[int, tuple[np.ndarray, np.ndarray]] = {}
                cidx = ix // a.chunks[ax]
                for c in np.unique(cidx):
                    mask = cidx == c
                    groups[int(c)] = (ix[mask] - c * a.chunks[ax], np.flatnonzero(mask))
                axis_groups.append(groups)
            for combo in itertools.product(*(g.items() for g in axis_groups)):
                cids = tuple(c for c, _ in combo)
                chunk = a._read_chunk(cids)
                if chunk is None:
                    chunk = a._empty_chunk()
                in_sel = np.ix_(*[within for _, (within, _) in combo])
                out_sel = np.ix_(*[pos for _, (_, pos) in combo])
                out[out_sel] = chunk[in_sel]
        if squeeze_axes:
            out = out.squeeze(axis=tuple(squeeze_axes))
        return out


def open_zarr_array(
    store: str,
    mode: str,
    shape: Optional[Sequence[int]] = None,
    dtype: Any = None,
    chunks: Optional[Sequence[int]] = None,
    fill_value: Any = None,
    storage_options: Optional[dict] = None,
    compressor: Optional[dict] = None,
) -> ZarrV2Array:
    """Open (or create) a Zarr v2 array at *store*.

    Modes: ``r`` read-only (must exist), ``a`` open-or-create, ``w`` recreate
    metadata (chunk data from a previous run is reused — create-arrays uses
    ``a`` so resumed runs don't clobber; reference cubed/core/plan.py:430-432).
    """
    io = _make_io(store, storage_options)
    if mode != "r":
        # writer-mode opens (the create-arrays op at compute start, resume
        # re-opens) sweep orphaned .tmp litter from previously crashed
        # writers; read opens skip the listdir (readers ignore .tmp anyway)
        io.sweep_tmp()
    meta_exists = io.exists(".zarray")
    if mode == "r" or (mode == "a" and meta_exists):
        if not meta_exists:
            raise FileNotFoundError(f"No zarr array at {store}")
        try:
            meta = json.loads(io.read_bytes(".zarray"))
            return ZarrV2Array(store, meta, storage_options)
        except (ValueError, KeyError, TypeError, UnicodeDecodeError) as exc:
            # corrupt/truncated .zarray JSON (a writer killed mid-crash era,
            # bit rot). Readers fail loudly with a diagnosable error; a
            # writer-mode open WITH full creation parameters quarantines the
            # bad document and recreates it — chunk data is untouched, and
            # checksum-verified resume decides per chunk what to trust
            if mode != "r" and shape is not None and dtype is not None:
                logger.warning(
                    "quarantining corrupt .zarray at %s and recreating "
                    "metadata (%s)", store, exc,
                )
                try:
                    io.rename(".zarray", f".zarray.quarantine.{int(time.time() * 1000)}")
                except OSError:
                    pass
                get_registry().counter("zarray_meta_recreated").inc()
            else:
                raise ValueError(
                    f"corrupt .zarray metadata at {store}: {exc!r} (reopen "
                    "in a writer mode with shape/dtype to recreate it)"
                ) from exc
    if shape is None or dtype is None:
        raise ValueError("shape and dtype required to create a new array")
    dtype = np.dtype(dtype)
    shape = tuple(int(s) for s in shape)
    if chunks is None:
        chunks = shape
    chunks = tuple(int(c) for c in chunks) if shape else ()
    chunks = tuple(min(c, s) if s > 0 else max(1, c) for c, s in zip(chunks, shape))
    if compressor is not None:
        _codec_from_meta(compressor)  # unsupported ids fail at create time
    meta = {
        "zarr_format": 2,
        "shape": list(shape),
        "chunks": [max(1, c) for c in chunks] if shape else [],
        "dtype": _encode_dtype(dtype),
        "compressor": dict(compressor) if compressor is not None else None,
        "fill_value": _encode_fill(fill_value if fill_value is not None else 0, dtype),
        "order": "C",
        "filters": None,
        "dimension_separator": ".",
    }
    io.makedirs()
    io.write_bytes_atomic(".zarray", json.dumps(meta).encode())
    return ZarrV2Array(store, meta, storage_options)
