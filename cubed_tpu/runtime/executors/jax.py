"""The JAX/TPU executor: HBM-resident execution of the plan.

Design (SURVEY.md section 7; north star in BASELINE.json):

- **Residency.** Arrays live as ``jax.Array``s in HBM, keyed by their target
  store path. Zarr is only touched at plan boundaries: sources are loaded once,
  and requested outputs are flushed at the end. Intermediates never hit
  storage (the reference pays a full storage round-trip per op).
- **Streamed preload.** A stored source of several chunks is never
  assembled on the host: each chunk file is read into one of a pair of
  staging buffers, put from there on the chip that owns the chunk and
  written into its place in that chip's shard, which is updated in place
  (``_stream_to_device``, ``_stream_lane``, ``_chunk_writer``), bit for bit
  what the put of the whole array gives. That loop is a lane, and there is
  one for each chip that owns chunks. Without a mesh the one owner is the
  default device and its shard the array, and the lane runs on the calling
  thread; under one, every shard has to be a block of whole chunks on a
  chip of its own, every lane runs on a thread and through a pair of its
  own (``_stream_lanes``), and the shards are joined at the end. The route
  and the number of lanes are chosen from what ``_device_put`` observes
  (``_streams``); ``stats["h2d_stream_bytes"]`` counts what went that way,
  ``stats["h2d_lane_bytes"]`` the part of it that went on a lane thread,
  and ``stats["h2d_stream_declined"]`` what found no room, or no owner.
- **Under a mesh a chunk touches one chip.** On the way out a chunk is
  sliced from the shard that holds it (``_chunk_of``), so its slice, split
  and fetch run on that chip alone. ``stats["mesh_owner_bytes"]`` counts
  the bytes that moved between the host and exactly one chip, either way,
  ``stats["mesh_gathered_bytes"]`` those that touched several (a shard
  assembled by the callback, a chunk that crosses shards).
- **The staging pairs are the process's.** The buffers outlive the
  executor: ``execute_dag`` leases a pair from those the process keeps
  (``_leased_staging``), a source with several owners takes one more for
  each further lane into the same lease, and all go back when the compute
  ends, however it ends, with no device update left on any buffer. A
  compute that finds the pool empty (the service runs computes on many
  threads) works with a fresh pair of its own and waits for nobody. So
  from a process's second compute on no chunk is read into, or joined
  into, a page nobody has touched: ``stats["stage_reused_bytes"]`` counts
  the streamed bytes that passed through a buffer the compute found
  allocated. Between computes the process holds a pair a lane of its
  widest compute so far, two buffers each of the largest chunk streamed
  through it; ``release_staging_buffers()`` hands the pages back, and a
  forked child starts with none.
- **Streamed flush.** The way out is the mirror image
  (``_flush_chunks``): a chunk that leaves as planes is joined into one of
  the two buffers of the compute's first pair and written from there (the
  store hands the file a view, ``ZarrV2Array._write_chunk``), by a second
  thread, while this one fetches the next chunk into the other buffer; one
  writer, grid order, and ``_flush`` returns when every chunk is durable.
  ``stats["flush_stream_bytes"]`` counts what reached the store that way
  and ``stats["encode_copy_bytes"]`` what the store had to copy.
- **Whole-array fast path.** Ops whose kernel is shape-invariant (elementwise /
  broadcasting chains, including everything the optimizer fused) and whose
  block mapping is 1:1-with-broadcast run as ONE jitted call on whole resident
  arrays — XLA fuses the entire chain; intermediates stay in registers/HBM.
- **Chunked route.** Any other op (tree-reduce combines, map_direct,
  index, reshape, block_id kernels) runs per output chunk: inputs are sliced
  from resident arrays on device (XLA slice, no host transfer), the chunk
  kernel is jitted once per shape, and results assemble by concatenation.
- **An exception is an error.** A route is chosen from what the plan says
  and declines by returning ``None``; what it raises reaches the caller from
  where it was raised, but for two designed aborts, each a type handled at
  one site. ``_TraceAbort`` (a source that is not resident, or a flush,
  inside a trace) sends the segment to the eager route (``_run_segment``).
  What JAX raises when a kernel asks a tracer for a concrete value
  (``_needs_concrete_value``) does the same inside a trace, and at an eager
  op runs the kernel un-jitted on concrete chunks, once
  (``_exec_blockwise``, ``stats["host_kernel_ops"]``).
- **Rechunk is free.** Resident arrays are whole arrays, so a rechunk op is
  pure metadata (an alias). Under a device mesh the corresponding physical
  movement is a resharding (``device_put`` with a new NamedSharding), which
  XLA lowers to all-to-all over ICI — not a storage round-trip.
  ``_exec_rechunk`` has four routes, each counted: the alias
  (``stats["rechunk_alias"]``, the route of a traced segment), a virtual
  source made on the device (``rechunk_virtual``), a stored source under
  half the budget read whole on the host and put (``rechunk_host_whole``),
  and any other stored source copied chunk by chunk on the host by the
  primitive's own function, never touching the device
  (``rechunk_host_copy``; it creates its destination, which residency left
  uncreated). What the routes count while a segment is traced is kept with
  the compiled program, so a structural hit reports what the miss did.
- **Mesh / SPMD.** With ``mesh`` set, resident arrays are placed with a
  ``NamedSharding`` over the chunk grid's largest dim and whole-array kernels
  run under that sharding; XLA's partitioner inserts the collectives
  (psum trees for reductions riding ICI).
- **Spill path.** If HBM residency would exceed ``device_mem``, least-recently
  used arrays are flushed to their Zarr targets and dropped; reads fall back
  to storage. This keeps the bounded-memory story for arrays larger than HBM.
- **64-bit floats that only move.** A device without native f64 (TPU v5e
  holds a float64 as a pair of float32: ~49 significand bits, float32's
  exponent range) changes values merely by holding them. A compute whose
  every op only moves values (rechunk, the ``to_zarr`` store op) therefore
  carries float64 arrays (and float64 fields of record arrays) through HBM
  as their uint64 bit patterns, so Zarr -> HBM -> Zarr stays bit for bit;
  arithmetic plans compute in the device's float64 as before. The choice
  is made once per compute (``_execute_dag_inner``) and acted on in one
  pair of functions, ``_device_put`` and ``_to_host``, which every
  transfer in either direction goes through
  (``stats["h2d_bits_bytes"]`` counts the bytes that entered as bit
  patterns). It is all or nothing: a
  movement op that shares its compute with arithmetic, or with a
  complex128 array, moves in the device's float64, and
  ``stats["f64_lossy_moves"]`` counts those. Such a device also hands
  64-bit elements to the host ten times slower than 32-bit ones, so
  ``_to_host`` splits a large 64-bit value into two 32-bit planes on the
  device (the words of an integer, the float32 pair of a float64), fetches
  both at once and joins them on the host, bit for bit the direct fetch.
  The planes leave in a row-major device layout, so that the host receives
  them in the order it writes the result in (``_plane_program``).
  ``stats["d2h_plane_bytes"]`` counts the bytes that left that way, and
  ``stats["d2h_plane_strided_bytes"]`` those that arrived in another order.
- **Scheduling.** This executor always keeps op ordering and ignores
  ``Spec(scheduler="dataflow")``: whole (fused) segments compile to single
  XLA programs over HBM-resident arrays, so there is no per-chunk task
  frontier for the chunk-granular scheduler to overlap — XLA's own
  scheduler already overlaps at the instruction level inside each program
  (``runtime/dataflow.py`` is the multi-host fleet's analogue).
- **Spans.** Each segment (with its preloads), eager op and flush runs in a
  task scope of the span pipeline (``observability/accounting.py``) and
  times its phases with ``scope_span`` (``jax.preload``, ``jax.h2d`` (one
  a chunk of a streamed preload), ``jax.struct_key``, ``jax.trace_lower``,
  ``jax.compile``, ``jax.dispatch``, ``jax.flush``, ``jax.device_wait``,
  ``jax.d2h``, ``jax.write_wait`` (this thread blocked on the flush's
  writer), and ``jax.rechunk`` around a rechunk through storage): the
  phases' only clock (the task events keep their timestamps), a no-op unless a ``TraceCollector`` is attached or
  ``CUBED_TPU_TASK_SPANS=1`` (docs/observability.md, "Device executor
  spans"). What the two pipelines wait for, and how many pages a preload
  makes resident, is counted armed or not, on ``perf_counter_ns`` and from
  ``/proc/self/statm``: ``stats["write_wait_us"]``, ``stage_wait_us``,
  ``preload_page_faults`` (the staging buffers of a process's first
  compute, 0 from its second on: ``stage_reused_bytes`` says why).

Reference parity: replaces cubed's serverless executors
(cubed/runtime/executors/*) with a device-mesh substrate.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import logging
import math
import mmap
import os
import re
import sys
import threading
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterator, NamedTuple, Optional, Tuple

import numpy as np

from ...chunks import blockdims_from_blockshape
from ...primitive.blockwise import BlockwiseSpec, apply_blockwise
from ...primitive.rechunk import copy_read_to_write
from ...core.plan import create_zarr_array
from ...observability.accounting import (
    current_scope,
    scope_span,
    spans_enabled,
    task_scope,
)
from ...storage.store import ZarrV2Array
from ...storage.virtual import (
    VirtualEmptyArray,
    VirtualFullArray,
    VirtualInMemoryArray,
    VirtualOffsetsArray,
)
from ...storage.zarr import LazyZarrArray
from ...utils import get_item
from ..pipeline import ResumeState, visit_nodes
from ..types import (
    Callback,
    DagExecutor,
    OperationEndEvent,
    OperationStartEvent,
    TaskEndEvent,
    callbacks_on,
)
from ..utils import fire_task_start

logger = logging.getLogger(__name__)


class _TraceAbort(Exception):
    """Raised when an op cannot be traced into a fused segment program
    (a host-side storage read or flush inside the trace); the segment falls
    back to eager per-op execution."""


def _jax():
    import jax

    return jax


def _needs_concrete_value() -> tuple:
    """What JAX raises when a kernel asks a tracer for a concrete value, as
    upstream's ``map_blocks`` functions on numpy blocks do: with
    ``_TraceAbort`` all that route code handles. Named one by one: their
    bases ``JAXTypeError`` and ``JAXIndexError`` also hold
    ``UnexpectedTracerError`` and ``KeyReuseError``, a kernel's bugs."""
    errors = _jax().errors
    return (
        errors.TracerArrayConversionError,  # np.asarray(x), np.sort(x)
        # float(x), x.item(); its subclass TracerBoolConversionError: if x > 0
        errors.ConcretizationTypeError,
        errors.TracerIntegerConversionError,  # range(x), x as a Python index
        errors.NonConcreteBooleanIndexError,  # x[mask]: the shape is a value
    )


class _Resident:
    """An HBM-resident array (or dict-of-arrays pytree) plus bookkeeping."""

    __slots__ = ("value", "nbytes", "last_used", "target")

    def __init__(self, value, nbytes: int, target):
        self.value = value
        self.nbytes = nbytes
        self.last_used = time.monotonic()
        self.target = target

    def touch(self):
        self.last_used = time.monotonic()


class _Staging:
    """One of a pair of host buffers that a chunk passes through between
    the store and the device: a lane of a streamed preload reads its chip's
    chunk files into its pair (``JaxExecutor._stream_lane``), and a flush
    joins the planes of a chunk into the compute's first pair and writes
    the chunk file from there (``JaxExecutor._flush_chunks``). ``busy`` is
    a result of the device update that consumed the bytes now in
    ``buffer``: until it is ready the buffer may still be read by the
    transfer (the put is asynchronous on the TPU, and on the CPU backend a
    put value may alias the numpy memory), so it is not written. One
    thread at a time works with a pair: the lane's, or the calling one.

    A pair belongs to the process, not to an executor: a compute leases
    one, and one more for each further lane of a source with several
    owners, for the length of one ``execute_dag`` (``_leased_staging``), so
    that the pages a chunk is read into have been touched by the compute
    before. ``kept`` says whether ``buffer`` is still the one the lease
    found: False once this compute had to make it, or make it larger."""

    __slots__ = ("buffer", "busy", "kept")

    def __init__(self):
        self.buffer: Optional[np.ndarray] = None
        self.busy = None
        self.kept = False

    def release(self) -> int:
        """Wait until the buffer may be written again: the microseconds the
        device update took to let go of it, 0 where none held it."""
        if self.busy is None:
            return 0
        started = time.perf_counter_ns()
        self.busy.block_until_ready()
        self.busy = None
        return (time.perf_counter_ns() - started) // 1000

    def sized(self, nbytes: int) -> np.ndarray:
        """The buffer, free to be written, with room for ``nbytes``: made on
        first use and again only for a larger chunk (which replaces the
        smaller one), so that after its first chunk no read writes a fresh
        page."""
        self.release()
        if self.buffer is None or self.buffer.nbytes < nbytes:
            # on a page boundary, as the page cache's pages are that the
            # read copies from. numpy's own large arrays start 16 bytes
            # into a page, and on the v5e's host that one offset costs a
            # read of 200 MB 81 ms against 21 to 25 ms at any other: every
            # load then follows a store to the same place of a page
            # (PERF.md section 6, PR 29)
            raw = np.empty(nbytes + mmap.PAGESIZE, np.uint8)
            shift = -raw.ctypes.data % mmap.PAGESIZE
            self.buffer = raw[shift : shift + nbytes]
            self.kept = False
        return self.buffer

    def array(self, shape, dtype: np.dtype) -> np.ndarray:
        """The head of the buffer, free to be written, as a C-contiguous
        array of ``shape`` and ``dtype``."""
        nbytes = math.prod(shape) * dtype.itemsize
        return self.sized(nbytes)[:nbytes].view(dtype).reshape(shape)

    def holds(self, host: np.ndarray) -> bool:
        """Whether ``host`` lies in the buffer."""
        return self.buffer is not None and np.may_share_memory(host, self.buffer)


def _moves_values(op) -> bool:
    """Whether a primitive op passes values through unchanged: rechunk,
    array creation, a blockwise kernel declared ``bit_preserving``."""
    function = op.pipeline.function
    return (
        function is copy_read_to_write
        or function is create_zarr_array
        or (
            function is apply_blockwise
            and getattr(op.pipeline.config.function, "bit_preserving", False)
        )
    )


def _holds(dtype, *kinds) -> bool:
    """Whether ``dtype`` is one of ``kinds`` or a record with such a field."""
    if dtype.fields is not None:
        return any(_holds(field[0], *kinds) for field in dtype.fields.values())
    return any(dtype == kind for kind in kinds)


def _resident_pages() -> int:
    """The pages of this process that are resident now
    (``/proc/self/statm``), 0 where the system has no such file. Its growth
    over a phase that frees nothing is the pages the phase's first touches
    faulted in (``preload_page_faults``); the kernel's own count of faults
    (``ru_minflt``) is not kept by every host: gVisor's reads 0."""
    try:
        with open("/proc/self/statm", "rb") as statm:
            return int(statm.read().split()[1])
    except (OSError, ValueError, IndexError):
        return 0


def _value_nbytes(value) -> int:
    if isinstance(value, dict):
        return sum(_value_nbytes(v) for v in value.values())
    return int(np.prod(value.shape)) * value.dtype.itemsize if value.shape else value.dtype.itemsize


class JaxExecutor(DagExecutor):
    """Executes the plan with HBM residency on the default jax backend.

    Parameters
    ----------
    mesh : jax.sharding.Mesh | None
        Shard resident arrays and whole-array kernels over this mesh.
    device_mem : int | None
        HBM residency budget in bytes (default: 75% of one device's memory,
        times the number of mesh devices when sharded).
    compute_dtype : str | None
        ``"float32"`` opts f64 plans into single-precision on-device
        compute ("f32 ingestion"): the executor runs every trace with jax
        x64 canonicalization disabled, so float64 kernels — including
        threefry random GENERATION, the dominant device cost of f64
        pipelines on v5e, which has no native f64 — produce float32, and
        results cast back to the declared f64 dtype at the Zarr store
        boundary (storage/store.py:380). Error bounds: each elementwise
        op contributes relative error <= f32 eps (1.19e-7); a k-element
        tree-sum accumulates <= (log2(k)+chunks) * eps * sum|a| absolute
        error — ~1e-4 relative for the 1e8-element bench reductions —
        versus ~1e-13 in f64. Accuracy-sensitive pipelines should stay on
        the default. Conformance runs exclude this mode (it intentionally
        diverges from the f64 oracle past f32 eps;
        tests/conformance/SKIPS.txt). Side effect: the first f32 DAG
        installs a process-global warnings filter ignoring jax's
        "requested dtype float64 is not available" message (see
        _install_f32_truncation_filter for why and what it costs).

    Notes
    -----
    An executor keeps no host buffer between computes. The chunk-sized
    staging buffers of a streamed preload and flush are the process's: a
    compute leases a pair of them (under a mesh a pair a chip, one for each
    lane of a sharded preload) for the length of ``execute_dag`` and the
    process keeps them afterwards (two buffers a pair of the largest chunk
    streamed so far), so that the next compute, of this executor or
    another, reads into pages already touched.
    ``release_staging_buffers()`` of this module hands those pages back;
    nothing calls it automatically.
    """

    def __init__(
        self,
        mesh=None,
        device_mem: Optional[int] = None,
        fuse_plan: bool = True,
        compute_dtype: Optional[str] = None,
        matmul_precision: Optional[str] = None,
        **kwargs,
    ):
        self.mesh = mesh
        self.device_mem = device_mem
        if compute_dtype not in (None, "float32", "float64"):
            raise ValueError(
                "compute_dtype must be None, 'float32' or 'float64'; "
                f"got {compute_dtype!r}"
            )
        self.compute_dtype = compute_dtype
        if matmul_precision not in (
            None, "bfloat16", "bfloat16_3x", "tensorfloat32", "float32",
            "highest", "default",
        ):
            raise ValueError(
                "matmul_precision must be one of None, 'bfloat16', "
                "'bfloat16_3x', 'tensorfloat32', 'float32', 'highest', "
                f"'default'; got {matmul_precision!r}"
            )
        #: contraction precision for every dot/conv in the DAG, applied as
        #: the thread-local ``jax.default_matmul_precision`` scope. On TPU
        #: the MXU is a native bf16xbf16->f32 systolic array: 'bfloat16'
        #: is one MXU pass per contraction (fastest, ~3 decimal digits of
        #: input precision), 'bfloat16_3x' error-compensates with 3 passes,
        #: 'highest' emulates full f32 (6 passes). Combine with
        #: ``compute_dtype='float32'`` for the canonical f64-source opt-in:
        #: f32 storage/elementwise, bf16 MXU contractions.
        self.matmul_precision = matmul_precision
        #: trace consecutive traceable ops into ONE jitted XLA program
        self.fuse_plan = fuse_plan
        self.kwargs = kwargs
        self._tracing = False
        #: this compute moves float64 arrays as uint64 bit patterns
        #: (decided per compute in _execute_dag_inner)
        self._carry_bits = False
        #: the arrays this compute holds in HBM (``_execute_dag_inner``'s
        #: table), and whether a flush is running to make room (``_evict``):
        #: what ``_leaves_as_planes`` reads the room for its planes from
        self._resident: Dict[str, _Resident] = {}
        self._spilling = False
        #: the host memory of a streamed preload and of a flush: a pair of
        #: chunk-sized buffers that take turns, for every source a compute
        #: loads and every array it stores. The pair is the process's and
        #: is here only while a compute runs: ``execute_dag`` leases it at
        #: entry and gives it back at the end, on success, error and
        #: cancellation alike (``_lease``); a compute that finds it leased
        #: by another thread's gets a fresh pair of its own, dropped at the
        #: end. ``release_staging_buffers()`` hands the pages of the idle
        #: pairs back. One pair serves both ways: the executor's units run
        #: one after another, so no flush runs while a stream holds a buffer.
        #: ``_leased`` is all this compute holds: that pair first, then one
        #: more for each further lane of a source with several owners
        #: (``_lane_pairs``), given back together
        self._staging: Optional[Tuple[_Staging, _Staging]] = None
        self._leased: Optional[list] = None
        self._prepared_bases: Dict[int, Any] = {}
        #: keys of the task events of this compute in the order they were
        #: fired, kept only while spans are recorded (see ``_task_end``)
        self._task_order: Optional[list] = None
        self._placement = None  # factorized placement mesh, built lazily
        #: bytes of the arrays ``_admit`` pinned while the segment being
        #: lowered was traced (see ``_lower_and_compile``)
        self._pinned: Counter = Counter()
        #: execution-path counters for the last ``execute_dag`` call, reported
        #: via ``ComputeEndEvent.executor_stats``. Keys: ``segments_traced``,
        #: ``segments_compiled``, ``segment_cache_hits``, ``segment_struct_hits``,
        #: ``segment_mem_aborts``, ``segment_hbm_footprint``,
        #: ``whole_array_hits``, ``whole_concat_hits``, ``batched_ops``,
        #: ``chunked_ops``, ``rechunk_alias`` (zero-copy), ``rechunk_virtual``
        #: (materialized), ``rechunk_host_whole`` / ``rechunk_host_copy`` (a
        #: stored source read whole on the host and put / copied on the host
        #: chunk by chunk; 0, not absent, where none was), ``eager_ops``,
        #: ``f64_as_bits`` (float64 arrays moved to the device as bit
        #: patterns), ``h2d_bits_bytes`` (the part of ``h2d_bytes`` that
        #: entered that way; 0, not absent), ``f64_lossy_moves`` (copies
        #: of 64-bit floats through a device float64 that is not one),
        #: ``host_syncs`` (fetches in ``_to_host``, each of which blocks on the
        #: device), ``h2d_bytes`` / ``d2h_bytes`` (bytes moved by ``_device_put``
        #: / ``_to_host``), ``h2d_stream_bytes`` (the part of ``h2d_bytes`` that
        #: went chunk by chunk through the staging buffers; 0, not absent,
        #: where nothing did), ``h2d_lane_bytes`` (the part of that which
        #: reached its chip on a lane thread of that chip's own: all of it
        #: where a source had several owners, 0, not absent, where the one
        #: lane ran on the calling thread), ``flush_stream_bytes`` / ``encode_copy_bytes``
        #: (the part of ``d2h_bytes`` that reached the store from a staging
        #: buffer with no copy on the host after the join, and the bytes the
        #: store had to copy in a flush's writes; each 0, not absent),
        #: ``write_wait_us`` / ``stage_wait_us`` (microseconds this thread
        #: was blocked on the flush's writer thread, and microseconds a
        #: lane of ``_stream_to_device`` was blocked on a device update
        #: that still read a staging buffer, summed over the lanes'
        #: threads where there are several),
        #: ``preload_page_faults`` (the pages the preloads made resident:
        #: the growth of the process's resident set over each: the staging
        #: buffers in a process's first compute, 0 from its second on; the
        #: three each 0, not absent), ``stage_reused_bytes`` (the part of
        #: ``h2d_stream_bytes + flush_stream_bytes`` that passed through a
        #: staging buffer the compute found allocated when it leased the
        #: pair, not one it had to make or to make larger; 0, not absent),
        #: ``h2d_stream_declined`` (stored arrays that
        #: qualified for the stream and did not take it: put whole for want
        #: of room in HBM, or read through the callback under a sharding
        #: that gives some chunk no one owner), ``mesh_owner_bytes`` /
        #: ``mesh_gathered_bytes`` (under a mesh, the bytes of values that
        #: moved between the host and exactly one chip, either way, and of
        #: those that touched several; each 0, not absent, without one),
        #: ``d2h_plane_bytes`` (the part of ``d2h_bytes`` that
        #: left as 32-bit planes), ``d2h_plane_strided_bytes`` (the part of
        #: that whose planes reached the host in another order than
        #: row-major; 0, not absent), ``d2h_plane_no_room`` / ``d2h_plane_inexact``
        #: (fetches that qualified for planes and were made directly: no room
        #: in HBM, values the split cannot vouch for), ``mesh_devices`` (0
        #: without a mesh) and the ``_MESH_COUNTERS`` of the segment programs
        #: (``segment_collectives`` and its kinds, ``sharded_bytes`` /
        #: ``replicated_bytes``; ``segment_hbm_footprint`` is per chip), and the
        #: counters of the designed aborts: ``trace_failures`` (segments sent
        #: to the eager route), ``host_kernel_ops`` (eager ops whose kernel
        #: ran un-jitted), ``eager_fallbacks`` (both; must stay 0 on
        #: fused-path plans, tests pin it). The counters that routes move
        #: while a segment is traced (``whole_array_hits``, ``rechunk_alias``,
        #: ...) are of this compute's segments, traced in this compute or
        #: found compiled (``_SegmentProgram.routes``). So are
        #: ``device_f32_bytes``, ``device_f64_bytes`` and ``device_f16_bytes``
        #: (bfloat16 and float16 together; each 0, not absent): the bytes of
        #: the floating-point values the segments' ops produce, op by op, by
        #: the dtype the traced value has, which under ``compute_dtype`` is
        #: not the one the plan declares (``_FLOAT_BYTES``)
        self.stats: Counter = Counter()

    @property
    def name(self) -> str:
        return "jax"

    # ------------------------------------------------------------------

    def _budget(self) -> int:
        if self.device_mem is not None:
            return self.device_mem
        device = self._first_device()
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            per_device = int(stats["bytes_limit"] * 0.75)
        elif device.platform == "cpu":
            # host (and virtual) CPU devices report no limit
            per_device = 8 * 2**30
        else:
            raise RuntimeError(
                f"{device} reports no memory_stats()['bytes_limit']; pass "
                "device_mem= to set the HBM residency budget explicitly"
            )
        n = len(self.mesh.devices.flat) if self.mesh is not None else 1
        return per_device * n

    def _first_device(self):
        """The first device this process can address (under multi-controller
        SPMD the mesh also holds other hosts' devices)."""
        jax = _jax()
        if self.mesh is None:
            return jax.local_devices()[0]
        return next(
            d for d in self.mesh.devices.flat
            if d.process_index == jax.process_index()
        )

    def _only_moves_values(self, dag) -> bool:
        """True when every op of the plan passes values through unchanged
        (see ``_moves_values``) between Zarr arrays, so nothing on the
        device ever needs them as numbers. complex128 has no bit-pattern
        form here (a uint64 view changes the shape), so a plan that holds
        one is not carried."""
        for _, d in dag.nodes(data=True):
            op = d.get("primitive_op")
            if op is not None:
                if not _moves_values(op):
                    return False
            elif d.get("type") == "array":
                target = d.get("target")
                if not isinstance(
                    target, (LazyZarrArray, ZarrV2Array)
                ) or _holds(target.dtype, np.complex128):
                    return False
        return True

    def _lossy_moves(self, dag) -> int:
        """Ops that only move values and write an array holding 64-bit
        floats: on a device without native float64, outside a compute that
        carries bits, each such copy is not exact."""
        count = 0
        for name, d in dag.nodes(data=True):
            op = d.get("primitive_op")
            if op is None or not _moves_values(op):
                continue
            written = (
                dag.nodes[out].get("target") for out in dag.successors(name)
            )
            count += any(
                _holds(t.dtype, np.float64, np.complex128)
                for t in written
                if hasattr(t, "dtype")
            )
        return count

    def _placement_mesh(self):
        """Prime-factorized view of the mesh used for all array placement
        (parallel/mesh.py:factorized_mesh) — cached per executor."""
        if self._placement is None:
            from ...parallel.mesh import factorized_mesh

            self._placement = factorized_mesh(self.mesh)
        return self._placement

    def _keep_sharding_constraint(self, value, target):
        """Pin an array of a traced segment to the executor's mesh sharding
        (every array the segment produces, see ``_admit``, and its outputs).

        Batched kernels gather/stack/reassemble chunks inside the trace,
        after which XLA may propagate a REPLICATED layout to the output.
        Replication is only a memory detail single-process, but under
        multi-controller SPMD the per-host flush derives chunk ownership
        from the output's sharding — a replicated output degrades to
        "host 0 writes everything". Constraining the kept outputs keeps
        ownership (and the write path of docs/multihost.md) split across
        hosts."""
        sharding = self._target_sharding(value, target)
        if sharding is None:
            return value
        return _jax().lax.with_sharding_constraint(value, sharding)

    def _target_sharding(self, value, target):
        """The mesh sharding of ``target``'s chunk grid if ``value`` is that
        whole array; None without a mesh, for a record array (a dict) and
        for a value of another shape."""
        if self.mesh is None or isinstance(value, dict) or target is None:
            return None
        shape = tuple(getattr(target, "shape", ()) or ())
        if not shape or tuple(value.shape) != shape:
            return None
        cs = (
            blockdims_from_blockshape(shape, target.chunks)
            if getattr(target, "chunks", None)
            else None
        )
        return self._sharding_for(shape, cs)

    def _sharding_for(self, shape: tuple[int, ...], chunkset=None):
        """The chunk-grid-aligned sharding policy (parallel/mesh.py).

        One policy for the whole executor: dims ranked by block count then
        extent, mesh prime factors stacked per-dim, so ragged grids (e.g. the
        vorticity slice (499, 450, 400)) shard instead of replicating.
        """
        if self.mesh is None or not shape:
            return None
        from ...parallel.mesh import sharding_for_chunks

        return sharding_for_chunks(self._placement_mesh(), chunkset, shape)

    def _virtual_to_device(self, arr):
        """Materialize a whole virtual array on device, mesh-aware (sharded
        placement under a mesh); None when ``arr`` isn't a virtual type."""
        if isinstance(arr, VirtualInMemoryArray):
            return self._device_put(np.asarray(arr.array), tuple(arr.shape))
        if isinstance(arr, (VirtualEmptyArray, VirtualFullArray)):
            fill = getattr(arr, "fill_value", 0)
            return self._full(tuple(arr.shape), fill, arr.dtype)
        return None

    def _full(self, shape, fill_value, dtype):
        """Materialize a constant array, sharded over the mesh if present."""
        jax = _jax()
        sharding = self._sharding_for(tuple(shape))
        if sharding is not None:
            fn = jax.jit(
                lambda: jax.numpy.full(shape, fill_value, dtype=dtype),
                out_shardings=sharding,
            )
            return fn()
        return jax.numpy.full(shape, fill_value, dtype=dtype)

    def _device_put(self, value, shape, chunkset=None):
        """Host -> device. With ``_to_host`` the only pair of functions that
        knows how a value is represented on the device, and every transfer
        goes through them.

        ``value`` is a host array or an opened storage array; ``shape`` is
        the shape of the array it is (a part of) for sharding, None for a
        piece that is placed whole. A record array becomes a dict of its
        fields. float64 goes as its uint64 bit pattern when this compute
        carries bits (see the module docstring). A stored array of several
        chunks goes chunk by chunk, each to the chip that owns it
        (``_stream_to_device``), where every chunk has one owner and HBM
        has the room (``_streams``). Any other storage array under a mesh
        is read through ``make_array_from_callback``: each process
        materializes only the regions its addressable shards cover — the
        per-host Zarr IO sharding seam of docs/multihost.md (on one host
        this degenerates to reading everything, shard by shard). Anything
        else is read whole on the host and put in one piece. All routes
        share the representation rules here and give the same device
        value, bit for bit; under a mesh every one counts its bytes as
        ``mesh_owner_bytes`` or ``mesh_gathered_bytes``
        (``_count_mesh_io``)."""
        jax = _jax()
        stored = not isinstance(value, (np.ndarray, np.generic))
        if value.dtype.fields is not None:
            data = (value[...] if value.shape else value[()]) if stored else value
            return {
                k: self._device_put(np.ascontiguousarray(data[k]), shape, chunkset)
                for k in data.dtype.names
            }
        as_bits = self._carry_bits and value.dtype == np.float64
        if as_bits:
            self.stats["f64_as_bits"] += 1

        def transferred(data, stats=self.stats):
            """``data`` as it goes to the device, counted into ``stats`` (a
            lane of ``_stream_to_device`` on a thread of its own counts into
            its own)."""
            data = np.asarray(data)
            stats["h2d_bytes"] += data.nbytes
            if not as_bits:
                return data
            stats["h2d_bits_bytes"] += data.nbytes
            return data.view(np.uint64)

        sharding = self._sharding_for(shape, chunkset)
        if stored:
            owners = self._streams(value, sharding)
            if owners is not None:
                return self._stream_to_device(value, transferred, sharding, owners)
            if sharding is None:
                value = value[...] if value.shape else value[()]
                stored = False
        with scope_span("jax.h2d", cat="transfer") as sp:
            if stored:
                # the store's reads happen inside, shard by shard
                out = jax.make_array_from_callback(
                    tuple(shape), sharding, lambda idx: transferred(value[idx])
                )
            else:
                out = jax.device_put(transferred(value), sharding)
            sp.attrs["bytes"] = _value_nbytes(out)
            self._count_mesh_io(out, sp)
        return out

    def _count_mesh_io(self, value, sp, stats=None) -> None:
        """One transfer's bytes on the mesh's two counters, and the chip on
        the transfer's span. ``value`` is what lies on the device: on one
        chip, it moved between the host and exactly that chip
        (``mesh_owner_bytes``, and the span's ``device``); on several, it
        was assembled for them or gathered from them
        (``mesh_gathered_bytes``). Without a mesh neither counts. ``stats``
        is the counter of a preload lane that runs on a thread of its own,
        else ``self.stats``."""
        sharding = getattr(value, "sharding", None)
        if sharding is None:  # a host value that an eager op left resident
            return
        devices = sharding.device_set
        one_chip = len(devices) == 1
        if one_chip:
            sp.attrs["device"] = next(iter(devices)).id
        if self.mesh is not None:
            (self.stats if stats is None else stats)[
                "mesh_owner_bytes" if one_chip else "mesh_gathered_bytes"
            ] += _value_nbytes(value)

    def _streams(self, stored, sharding) -> Optional[Dict[tuple, tuple]]:
        """Where ``_device_put`` sends ``stored`` to the device chunk by
        chunk, each chunk's owner: chunk coords -> (device, the bounds of
        its shard); None where it does not. It does for one of the
        package's own Zarr arrays (the chunk-level read is theirs), of more
        than one chunk, whose chunks each have one chip to go to, with room
        there for its shard and two chunks in flight beside what is
        resident. Without a sharding the one owner is the default device
        (None) and its shard the whole array; under one, every shard has to
        be a block of whole chunks on a chip of its own
        (``parallel.mesh.chunk_owners``), and the room is a chip's share of
        the budget.
        A device that holds 64-bit elements as 32-bit pairs updates such an
        array through a split copy of it and of the chunk (1.21 GB of
        temporaries for an 800 MB float64 array and a 200 MB chunk, by the
        v5e's compiler), so there the room is asked for twice, and every
        update is a pass over the whole shard, so there the chunks are few
        (``_PAIR_STREAM_MAX_CHUNKS``). All of it observed in the call;
        nothing selects the route from outside. A source that qualifies and
        is declined, for want of room or of a chunk-aligned layout, is
        counted (``h2d_stream_declined``)."""
        if (
            not isinstance(stored, ZarrV2Array)
            or stored.nchunks < 2
            or stored.size == 0
        ):
            return None
        chunkset = stored.chunkset()
        if sharding is None:
            whole = tuple((0, dim) for dim in stored.shape)
            grid = itertools.product(*(range(len(c)) for c in chunkset))
            owners, chips = {idx: (None, whole) for idx in grid}, 1
        else:
            from ...parallel.mesh import chunk_owners

            owners = chunk_owners(sharding, stored.shape, chunkset)
            if owners is None:
                self.stats["h2d_stream_declined"] += 1
                return None
            chips = len(sharding.device_set)
        held = sum(r.nbytes for r in self._resident.values())
        needed = stored.nbytes // chips + 2 * stored._chunk_nbytes()
        if stored.dtype in _PAIR_DTYPES and not _float64_round_trips(
            self._first_device()
        ):
            if stored.nchunks > _PAIR_STREAM_MAX_CHUNKS * chips:
                return None
            needed *= 2
        if held + needed * chips > self._budget():
            self.stats["h2d_stream_declined"] += 1
            return None
        return owners

    def _stream_to_device(self, stored, transferred, sharding, owners):
        """``stored`` on the device without a copy of it on the host: one
        lane (``_stream_lane``) for each chip that owns chunks of it, as
        ``owners`` says (from ``_streams``), observed in the call. One
        owner, which is every source without a sharding: the lane runs here,
        on the calling thread, through the pair this compute leased first
        (``_lease``); its shard is the array. Several: every lane runs on a
        thread of its own through a pair of its own (``_stream_lanes``), so
        that no chip's reads, puts and waits stand in line behind
        another's, and the shards are joined at the end, in the owners'
        order, into one array of ``sharding``. ``transferred`` is
        ``_device_put``'s: the byte count and the bit-pattern view."""
        # a chip's chunks in grid order, the chips in the owners' order
        queues: Dict[Any, list] = {}
        for idx, (device, _) in owners.items():
            queues.setdefault(device, []).append(idx)
        if len(queues) == 1:
            (chunks,) = queues.values()
            shards = [
                self._stream_lane(
                    stored, transferred, owners, chunks, self._staging, self.stats
                )
            ]
        else:
            shards = self._stream_lanes(
                stored, transferred, owners, list(queues.values())
            )
        if sharding is None:
            (whole,) = shards
            return whole
        return _jax().make_array_from_single_device_arrays(
            tuple(stored.shape), sharding, shards
        )

    def _stream_lane(self, stored, transferred, owners, chunks, pair, stats):
        """One chip's ``chunks`` of ``stored`` (all of one owner in
        ``owners``, in grid order) onto that chip; its shard. Each chunk
        file is read into one of the two staging buffers of ``pair``, put on
        the chip from there and written into its place in the shard, which
        is allocated once and updated in place (``_chunk_writer``).

        The buffers take turns, so the read of chunk k + 1 overlaps the
        transfer and update of chunk k; chunk k's span ends with the wait
        for chunk k - 1's update, which frees the buffer that chunk k + 1 is
        read into (the span's ``wait_us``). A lane's last update is waited
        for by whichever read needs its buffer next: the next source's
        lane through this pair, here, or a flush's join, in its
        ``jax.d2h``. The waits made here, and no other, are
        ``stats["stage_wait_us"]``: where they are most of the ``jax.h2d``
        spans' time the device's update paces the lane, else the read does.
        ``stats`` is what the lane counts into: the compute's, or a lane
        thread's own."""
        jax = _jax()
        chunk_nbytes = stored._chunk_nbytes()
        write = _chunk_writer()
        chunkset = stored.chunkset()
        shard = None
        for k, idx in enumerate(chunks):
            device, bounds = owners[idx]
            stage, other = pair[k % 2], pair[1 - k % 2]
            # the source before may have left its last update on this one
            stats["stage_wait_us"] += stage.release()
            chunk = stored._read_chunk_into(idx, stage.sized(chunk_nbytes))
            if chunk is None:
                chunk = stored._empty_chunk()
            # where the chunk goes in its shard, and what of it (stored
            # padded) lies inside the array
            sel = get_item(chunkset, idx)
            start = tuple(s.start - b[0] for s, b in zip(sel, bounds))
            extent = tuple(s.stop - s.start for s in sel)
            with scope_span("jax.h2d", cat="transfer") as sp:
                piece = jax.device_put(transferred(chunk, stats), device)
                if shard is None:
                    shard = jax.numpy.zeros(
                        tuple(b[1] - b[0] for b in bounds), piece.dtype, device=device
                    )
                shard, stage.busy = write(
                    shard, piece, np.asarray(start, np.int32), extent
                )
                sp.attrs["bytes"] = piece.nbytes
                self._count_mesh_io(piece, sp, stats)
                waited = sp.attrs["wait_us"] = other.release()
            stats["stage_wait_us"] += waited
            stats["h2d_stream_bytes"] += piece.nbytes
            stats["stage_reused_bytes"] += piece.nbytes if stage.kept else 0
        return shard

    def _stream_lanes(self, stored, transferred, owners, queues) -> list:
        """The lanes of a source with several owners, lane n (``queues[n]``,
        one chip's chunks) on a thread of its own
        (``cubed-tpu-preload-<n>``) through the nth pair this compute has
        leased (``_lane_pairs``); the shards, in the lanes' order, once
        every thread has ended.

        A lane's thread finds the compute's cancellation token and the
        injected faults through a copy of this thread's context, traces and
        dispatches under this thread's jax configuration (``execute_dag``
        sets x64 and the matmul precision thread-locally: a lane that did
        not take them over would put a float64 where the compute carries
        float32) with its chip as the default device (``zeros`` fills an
        array on the default device and copies it to the one asked for:
        800 MB a shard through the first chip, for every lane at once), and
        works in a task scope and a counter of its own, since
        spans and byte counts find theirs through the calling thread and
        ``+=`` on a shared ``Counter`` loses counts. Both are folded into
        this thread's here, lane by lane, so ``stage_wait_us`` and the
        ``jax.h2d`` and ``storage_read`` spans are sums over the threads
        that waited and read. ``stats["h2d_lane_bytes"]`` counts the bytes
        that reached their chip on such a thread (0 where the one lane ran
        on the calling thread). The first error of any lane, a cancellation
        among them, is raised from here after every lane has ended: no
        chunk is started after it and no thread is left."""
        jax = _jax()
        pairs = self._lane_pairs(len(queues))
        outer = current_scope()
        x64 = jax.config.jax_enable_x64
        precision = jax.config.jax_default_matmul_precision
        failed: list = []  # what the lanes raised, in the order they did
        ended: list = [None] * len(queues)

        def lane(n):
            """On lane n's thread: (its shard, its counters, its scope)."""
            scoped = (
                task_scope(_SCOPE_SPANS) if outer is not None
                else contextlib.nullcontext()
            )
            counted: Counter = Counter()
            shard = inner = None
            chip = owners[queues[n][0]][0]
            try:
                with scoped as inner, jax.enable_x64(x64), \
                        jax.default_matmul_precision(precision), \
                        jax.default_device(chip):
                    shard = self._stream_lane(
                        stored, transferred, owners,
                        itertools.takewhile(lambda _: not failed, queues[n]),
                        pairs[n], counted,
                    )
            except BaseException as error:  # raised again below
                failed.append(error)
            ended[n] = (shard, counted, inner)

        threads = [
            threading.Thread(
                target=contextvars.copy_context().run, args=(lane, n),
                name=f"cubed-tpu-preload-{n}",
            )
            for n in range(len(queues))
        ]
        # every thread that started is waited for, however this block ends
        with contextlib.ExitStack() as joined:
            try:
                for thread in threads:
                    thread.start()
                    joined.callback(thread.join)
            except BaseException as error:
                failed.append(error)  # the lanes that run start no further chunk
                raise
        for _, counted, inner in ended:
            self.stats.update(counted)
            self.stats["h2d_lane_bytes"] += counted["h2d_stream_bytes"]
            if inner is not None:
                outer.fold(inner)
        if failed:
            raise failed[0]
        return [shard for shard, _, _ in ended]

    def _lane_pairs(self, lanes: int) -> list:
        """The first ``lanes`` staging pairs of this compute's lease, taking
        from the process's pool (or making) those it does not hold yet: they
        stay leased until the lease ends, so a compute's second source finds
        the pairs its first took. The first is ``self._staging``."""
        while len(self._leased) < lanes:
            self._leased.append(_take_staging())
        return self._leased[:lanes]

    def _to_host(self, value, dtype, stage: Optional[_Staging] = None) -> np.ndarray:
        """Device -> host: a device value (or dict of record fields) as a
        host array of the target's ``dtype``; undoes ``_device_put``.

        A large 64-bit value on a device without native float64 leaves as
        two 32-bit planes (``_fetch_as_planes``); everything else is fetched
        as it is. Either way the host array is the same, bit for bit. The
        planes are joined into ``stage``'s buffer where the caller gives
        one, and the host array is then a view of it (``stage.holds``),
        valid until the caller next hands that buffer over; a value fetched
        as it is, and a record, is the runtime's own array or a fresh one
        and leaves the buffer alone."""
        if isinstance(value, dict):
            fields = {k: self._to_host(value[k], dtype[k]) for k in dtype.names}
            rec = np.empty(next(iter(fields.values())).shape, dtype=dtype)
            for k, field in fields.items():
                rec[k] = field
            return rec
        self.stats["host_syncs"] += 1
        with scope_span("jax.device_wait", cat="kernel") as sp:
            # only a recording span waits here: it keeps the execution that
            # produces the value out of the fetch's time. Unobserved, the
            # fetch below is the one synchronisation
            if sp.recording:
                _jax().block_until_ready(value)
        with scope_span("jax.d2h", cat="transfer") as sp:
            host, strided = self._fetch_as_planes(value, stage)
            planes = sp.attrs["planes"] = host is not None
            sp.attrs["strided"] = strided
            if not planes:
                host = np.asarray(value)
            if self._carry_bits and host.dtype == np.uint64 and dtype == np.float64:
                host = host.view(np.float64)
            sp.attrs["bytes"] = host.nbytes
            self._count_mesh_io(value, sp)
        self.stats["d2h_bytes"] += host.nbytes
        # present, and 0, where nothing left as planes, or none strided
        self.stats["d2h_plane_bytes"] += host.nbytes if planes else 0
        self.stats["d2h_plane_strided_bytes"] += host.nbytes if strided else 0
        return host

    def _leaves_as_planes(self, value) -> bool:
        """Whether ``_to_host`` fetches ``value`` as 32-bit planes: a 64-bit
        real value, large enough to pay for the split's dispatch, on a
        device that holds 64-bit elements as 32-bit pairs, with room in HBM
        for the planes. All of it observed in the call; nothing selects the
        route from outside."""
        if (
            np.dtype(value.dtype) not in _PLANE_DTYPES
            or value.nbytes < _PLANES_MIN_BYTES
            # under multi-controller SPMD every process would have to run
            # the split of every chunk; each fetches only its own
            or _jax().process_count() > 1
            or _float64_round_trips(self._first_device())
        ):
            return False
        # what the split holds on the device (the slice being fetched, the
        # planes, a relayout's temporary) comes on top of what is resident.
        # A flush that runs because HBM is over budget must not be what
        # tips it over, and one at the end of a compute takes the planes
        # only where the residency accounting leaves room for the program
        held = sum(r.nbytes for r in self._resident.values())
        if self._spilling or held + _plane_program_of(value)[1] > self._budget():
            self.stats["d2h_plane_no_room"] += 1
            return False
        return True

    def _fetch_as_planes(
        self, value, stage: Optional[_Staging] = None
    ) -> Tuple[Optional[np.ndarray], bool]:
        """``value`` through ``_split_planes`` on the device, one fetch of
        both planes, ``_join_planes`` on the host (into ``stage``'s buffer
        where there is one, else into a fresh array); and whether a plane
        arrived in another order than row-major, so that the join read it
        strided. (None, False) where it does not leave as planes
        (``_leaves_as_planes``) or the device reports values that the split
        cannot reproduce (counted): the caller then fetches the value
        itself."""
        if not self._leaves_as_planes(value):
            return None, False
        split, _ = _plane_program_of(value)
        first, second, inexact = _jax().device_get(split(value))
        if inexact:
            self.stats["d2h_plane_inexact"] += 1
            self.stats["host_syncs"] += 1
            return None, False
        dtype = np.dtype(value.dtype)
        out = None if stage is None else stage.array(first.shape, dtype)
        return _join_planes(first, second, dtype, out), _planes_strided(first, second)

    # ------------------------------------------------------------------

    def execute_dag(
        self,
        dag,
        callbacks: Optional[list[Callback]] = None,
        array_names=None,
        resume=None,
        spec=None,
        **kwargs,
    ) -> None:
        jax = _jax()
        with contextlib.ExitStack() as stack:
            if self.compute_dtype == "float32" and jax.config.jax_enable_x64:
                # f32 ingestion: run the whole DAG with x64 canonicalization
                # off. ``jax.enable_x64(False)`` is THREAD-LOCAL, so a
                # concurrent thread computing with a default executor keeps
                # f64, and an exception anywhere in the DAG restores the
                # flag on context exit. The structural segment cache keys on
                # jax_enable_x64 (thread-local-aware), so f32 and f64
                # executions of one plan shape never share a compiled
                # program. jax warns per f64 request it truncates; that's
                # this mode working as designed, so silence it — with a
                # once-per-process permanent filter rather than
                # warnings.catch_warnings, whose save/restore of GLOBAL
                # filter state races concurrent executor threads (a
                # restore landing mid-flight would re-enable or swallow
                # another thread's filters). See the helper's docstring
                # for the cost: the filter stays installed process-wide,
                # so other x64-off code in this process loses the same
                # truncation warning.
                _install_f32_truncation_filter()
                stack.enter_context(jax.enable_x64(False))
            if self.matmul_precision is not None:
                # thread-local contraction-precision scope (MXU pass count)
                stack.enter_context(
                    jax.default_matmul_precision(self.matmul_precision)
                )
            if self.mesh is not None:
                # RNG kernels must stay fused threefry under a mesh: the
                # CPU Philox pure_callback path (random.generation_mode)
                # doesn't partition across an SPMD program
                from ...random import _mode_scope

                stack.enter_context(_mode_scope("threefry"))
            stack.enter_context(self._lease())
            return self._execute_dag_inner(
                dag, callbacks, array_names, resume, spec, **kwargs
            )

    @contextlib.contextmanager
    def _lease(self) -> Iterator[Tuple[_Staging, _Staging]]:
        """A staging pair in this executor's hands for the length of the
        block: one ``execute_dag``, or what drives ``_device_put`` or
        ``_flush`` without one (``chip_smoke.py``, the tests). A source with
        several owners takes a pair a lane into the same lease
        (``_lane_pairs``). Outside it the executor holds no host buffer."""
        with _leased_staging() as self._leased:
            self._staging = self._leased[0]
            try:
                yield self._staging
            finally:
                self._staging = self._leased = None

    def _execute_dag_inner(
        self,
        dag,
        callbacks: Optional[list[Callback]] = None,
        array_names=None,
        resume=None,
        spec=None,
        journal=None,
        **kwargs,
    ) -> None:
        jax = _jax()
        self.stats = Counter(
            dict.fromkeys((*_MESH_COUNTERS, *_FLOAT_BYTES.values()), 0),
            mesh_devices=0 if self.mesh is None else self.mesh.devices.size,
            h2d_stream_bytes=0,
            h2d_lane_bytes=0,
            flush_stream_bytes=0,
            stage_reused_bytes=0,
            encode_copy_bytes=0,
            write_wait_us=0,
            stage_wait_us=0,
            preload_page_faults=0,
            h2d_bits_bytes=0,
            mesh_owner_bytes=0,
            mesh_gathered_bytes=0,
            rechunk_host_whole=0,
            rechunk_host_copy=0,
        )
        self._task_order = [] if spans_enabled() else None
        resident: Dict[str, _Resident] = {}
        self._resident = resident
        budget = self._budget()
        self._carry_bits = False
        # with x64 off (compute_dtype="float32") float64 is given up by
        # choice, and there is no uint64 to carry it in
        if jax.config.jax_enable_x64 and not _float64_round_trips(
            self._first_device()
        ):
            self._carry_bits = self._only_moves_values(dag)
            if not self._carry_bits:
                self.stats["f64_lossy_moves"] = self._lossy_moves(dag)

        # map array-node name -> target, to know what must be flushed
        requested_stores = set()
        node_targets = {}
        for name, d in dag.nodes(data=True):
            if d.get("type") == "array" and d.get("target") is not None:
                node_targets[name] = d["target"]
                if array_names is None or name in array_names:
                    t = d["target"]
                    if isinstance(t, (LazyZarrArray, ZarrV2Array)):
                        requested_stores.add(str(t.store))

        segment: list = []

        def run_segment():
            if segment:
                ops, segment[:] = list(segment), []
                self._run_segment(
                    ops, dag, resident, budget, requested_stores, callbacks
                )

        def run_eager(name, node):
            primitive_op = node["primitive_op"]
            pipeline = primitive_op.pipeline
            callbacks_on(
                callbacks, "on_operation_start",
                OperationStartEvent(name, primitive_op.num_tasks),
            )
            fire_task_start(callbacks, name, num_tasks=primitive_op.num_tasks)
            t0 = time.time()
            self.stats["eager_ops"] += 1
            # observe-only guard (see _run_segment): measure, never enforce
            from ..memory import task_guard

            with task_scope(_SCOPE_SPANS) as scope, task_guard(
                f"eager:{name}", observe_only=True
            ) as guard:
                if pipeline.function is apply_blockwise:
                    self._exec_blockwise(primitive_op, resident, budget)
                elif pipeline.function is copy_read_to_write:
                    self._exec_rechunk(primitive_op, resident, budget)
                elif pipeline.function is create_zarr_array:
                    # create metadata only for arrays that will actually be
                    # persisted; residency replaces the rest
                    for lazy in pipeline.mappable:
                        if str(lazy.store) in requested_stores:
                            lazy.create(mode="a")
                else:  # pragma: no cover - unknown pipeline type: run as-is
                    for m in pipeline.mappable:
                        pipeline.function(m, config=pipeline.config)
            t1 = time.time()
            self._task_end(
                callbacks,
                array_name=name,
                num_tasks=primitive_op.num_tasks,
                task_create_tstamp=t0,
                function_start_tstamp=t0,
                function_end_tstamp=t1,
                task_result_tstamp=t1,
                guard_mem_peak=guard.measured,
                **scope.stats(),
            )
            callbacks_on(
                callbacks, "on_operation_end",
                OperationEndEvent(name, primitive_op.num_tasks),
            )

        # resume is op-granular here (segments run as whole-array device
        # programs, so per-task skip doesn't apply), but the skip decision
        # is still checksum-verified: a corrupt persisted output re-runs
        # (and is quarantined by the scan) instead of being trusted; a
        # loaded compute journal (resume_from_journal) further requires an
        # op to be journaled fully complete before it may skip
        resume_state = (
            ResumeState(quarantine=True, journal=journal) if resume else None
        )
        cancellation = kwargs.get("cancellation")
        for name, node in visit_nodes(dag, resume=resume, state=resume_state):
            if cancellation is not None and cancellation.cancelled:
                # cooperative abort at the op/segment boundary (a fused
                # device segment is not an interruptible unit): flushes
                # nothing partial — materialized arrays are whole
                from ..cancellation import abort as _cancel_abort

                raise _cancel_abort(cancellation)
            primitive_op = node["primitive_op"]
            kind = self._classify(primitive_op) if self.fuse_plan else "eager"
            if kind == "trace":
                segment.append((name, node))
            else:
                run_segment()
                run_eager(name, node)
        run_segment()

        # flush requested outputs that are still resident. Each flush is a
        # unit of work with IO and spans of its own but no task of the plan:
        # they ride an event of zero tasks in the name of the op that
        # produced the array
        producers = {
            str(node_targets[arr].store): op
            for op, arr in dag.edges()
            if arr in node_targets and hasattr(node_targets[arr], "store")
            and dag.nodes[op].get("primitive_op") is not None
        }
        for store, res in list(resident.items()):
            if store in requested_stores:
                t0 = time.time()
                with task_scope(_SCOPE_SPANS) as scope:
                    self._flush(res)
                t1 = time.time()
                self._task_end(
                    callbacks,
                    array_name=producers.get(store, store),
                    num_tasks=0,
                    chunk_key=_FLUSH_KEY,
                    task_create_tstamp=t0,
                    function_start_tstamp=t0,
                    function_end_tstamp=t1,
                    task_result_tstamp=t1,
                    **scope.stats(),
                )
        if self._task_order:
            # the dependency edges of this compute's tasks, for the critical
            # path of ``analytics.analyze`` (as the dataflow scheduler hands
            # over its chunk graph)
            from ...observability.collect import record_chunk_graph

            order = self._task_order
            record_chunk_graph(
                {key: [gate] for gate, key in zip(order, order[1:])}
                | {order[0]: []}
            )

    def _task_end(self, callbacks, **fields) -> None:
        """Fire one ``TaskEndEvent``. While spans are recorded its key is
        kept too: this executor runs its units one after another on one
        host thread, so each task is gated by the one before it, and
        ``_execute_dag_inner`` hands that chain to the trace analysis."""
        event = TaskEndEvent(executor=self.name, **fields)
        if self._task_order is not None:
            self._task_order.append(f"{event.array_name}\t{event.chunk_key}")
        callbacks_on(callbacks, "on_task_end", event)

    # ------------------------------------------------------------------
    # plan fusion: trace runs of ops into ONE jitted XLA program
    # ------------------------------------------------------------------

    def _classify(self, primitive_op) -> str:
        """'trace' if this op's execution is a pure device computation given
        resident inputs (so it can join a fused segment program); 'eager'
        otherwise. Decisions use plan metadata only, never values."""
        pipeline = primitive_op.pipeline
        if pipeline.function is copy_read_to_write:
            return "trace"  # rechunk: resident alias (or preloaded source)
        if pipeline.function is not apply_blockwise:
            return "eager"  # create-arrays (host metadata) / unknown
        f = pipeline.config.function
        if getattr(f, "host_data_nbytes", 0) > 2**18:
            # kernel closes over non-trivial host data (from_array): tracing
            # would bake it into the program as CONSTANTS — bloating the
            # program, defeating the structural cache (the fingerprint and
            # compiled executable become data-dependent), and inviting
            # XLA's compile-time constant folding to evaluate whole op
            # chains (a sort network over a 4 MB baked source measured
            # MINUTES of folding). Run the source op eagerly: it
            # materializes once as a resident device array and downstream
            # segments take it as a program INPUT.
            return "eager"
        side_inputs = getattr(f, "side_inputs", None)
        if side_inputs and not (
            (
                len(side_inputs) == 1
                and (
                    getattr(f, "resident_identity", False)
                    or getattr(f, "whole_select", None) is not None
                )
            )
            or getattr(f, "whole_concat", None) is not None
        ):
            # generic map_direct: the task body reads storage directly
            return "eager"
        return "trace"

    def _segment_sources(self, ops) -> tuple[list, list]:
        """(concrete source arrays to preload, offsets arrays to hoist)."""
        preload, offsets = [], []
        seen = set()
        for _, node in ops:
            pipeline = node["primitive_op"].pipeline
            if pipeline.function is copy_read_to_write:
                proxies = [pipeline.config.read]
            else:
                spec = pipeline.config
                proxies = list(spec.reads_map.values())
                proxies += [
                    type("P", (), {"array": a})
                    for a in (getattr(spec.function, "side_inputs", None) or [])
                ]
            for proxy in proxies:
                arr = proxy.array
                key = str(getattr(arr, "store", id(arr)))
                if key in seen:
                    continue
                seen.add(key)
                if isinstance(arr, VirtualOffsetsArray):
                    offsets.append(arr)
                elif isinstance(arr, (ZarrV2Array, LazyZarrArray)):
                    preload.append(arr)
        return preload, offsets

    def _preload(self, arr, resident, budget) -> bool:
        """Load a concrete storage array onto the device (outside any trace)
        so segment programs take it as an input, not a baked constant."""
        key = str(arr.store)
        if key in resident:
            return True
        try:
            concrete = arr.open() if isinstance(arr, LazyZarrArray) else arr
        except FileNotFoundError:
            return False
        nbytes = int(np.prod(concrete.shape or (1,))) * concrete.dtype.itemsize
        if nbytes > budget:
            return False
        cs = (
            blockdims_from_blockshape(concrete.shape, concrete.chunks)
            if concrete.shape and getattr(concrete, "chunks", None)
            else None
        )
        before = _resident_pages()
        with scope_span(
            "jax.preload", bytes=nbytes, chunks=concrete.nchunks
        ) as sp:
            streamed = self.stats["h2d_stream_bytes"]
            value = self._device_put(concrete, tuple(concrete.shape), cs)
            sp.attrs["streamed"] = self.stats["h2d_stream_bytes"] > streamed
            self._admit(resident, key, value, arr, budget)
            faults = sp.attrs["faults"] = max(0, _resident_pages() - before)
        self.stats["preload_page_faults"] += faults
        return True

    def _segment_keep(self, ops, dag, requested_stores) -> Dict[str, Any]:
        """store -> target for segment outputs that must materialize: arrays
        consumed by ops outside the segment or requested as plan outputs."""
        seg_names = {name for name, _ in ops}
        keep: Dict[str, Any] = {}
        for name, _ in ops:
            for arr_name in dag.successors(name):
                target = dag.nodes[arr_name].get("target")
                if target is None or not hasattr(target, "store"):
                    continue
                store = str(target.store)
                consumers = set(dag.successors(arr_name))
                if store in requested_stores or not consumers <= seg_names:
                    keep[store] = target
        return keep

    def _run_segment(
        self, ops, dag, resident, budget, requested_stores, callbacks
    ) -> None:
        t0 = time.time()
        for name, node in ops:
            callbacks_on(
                callbacks, "on_operation_start",
                OperationStartEvent(name, node["primitive_op"].num_tasks),
            )
            fire_task_start(
                callbacks, name, num_tasks=node["primitive_op"].num_tasks
            )

        # observe-only memory guard: the fused segment is one program, not
        # a retryable task, so enforcement (which degrades via retry) makes
        # no sense here — but the host-RSS measurement still feeds the
        # projected-vs-measured summary and observe-mode warnings
        from ...observability.collect import record_decision
        from ..memory import task_guard

        seg_key = ",".join(name for name, _ in ops)
        with task_scope(_SCOPE_SPANS) as scope, task_guard(
            f"segment:{seg_key}", observe_only=True
        ) as guard:
            traced = False
            try:
                traced = self._trace_segment(
                    ops, dag, resident, budget, requested_stores
                )
            except (_TraceAbort, *_needs_concrete_value()) as abort:
                # the two designed aborts and nothing else: a cancel, a
                # kernel's own exception, a storage or integrity error of a
                # preload, a bug in a route reach the caller as they are
                self.stats["trace_failures"] += 1
                self.stats["eager_fallbacks"] += 1
                record_decision(
                    "jax_eager_fallback", segment=seg_key,
                    reason=type(abort).__name__,
                )
            else:
                if traced:
                    self.stats["segments_traced"] += 1
                else:
                    self.stats["segment_mem_aborts"] += 1
                    record_decision("jax_segment_mem_abort", segment=seg_key)
            if not traced:
                for name, node in ops:
                    primitive_op = node["primitive_op"]
                    if primitive_op.pipeline.function is apply_blockwise:
                        self._exec_blockwise(primitive_op, resident, budget)
                    else:
                        self._exec_rechunk(primitive_op, resident, budget)

        t1 = time.time()
        # the segment ran as ONE fused program; apportion its wall time across
        # the member ops by task count so history/timeline totals sum to the
        # real segment duration instead of len(ops) x duration
        total_tasks = sum(node["primitive_op"].num_tasks for _, node in ops) or 1
        elapsed = t1 - t0
        start = t0
        # what the segment's scope measured (preload and spill IO, spans)
        # rides the first member op's event alone: once, not per op
        carried = scope.stats()
        for name, node in ops:
            num_tasks = node["primitive_op"].num_tasks
            end = start + elapsed * (num_tasks / total_tasks)
            self._task_end(
                callbacks,
                array_name=name,
                num_tasks=num_tasks,
                task_create_tstamp=start,
                function_start_tstamp=start,
                function_end_tstamp=end,
                task_result_tstamp=end,
                # the guard measured the WHOLE segment: attributing
                # that aggregate to each member op would flag
                # correctly-modelled ops as over-projected, so per-op
                # attribution only exists for single-op segments
                guard_mem_peak=guard.measured if len(ops) == 1 else None,
                **carried,
            )
            callbacks_on(
                callbacks, "on_operation_end",
                OperationEndEvent(name, num_tasks),
            )
            start = end
            carried = {}

    def _structural_key(
        self, ops, dag, in_keys, resident, keep_list, seeded
    ) -> Optional[str]:
        """A pre-trace fingerprint of the segment program.

        Tracing + lowering a large fused segment costs ~0.6 s of pure Python
        per compute — ~80% of the warm vorticity benchmark — even when the
        compiled executable is cached by HLO hash. This key lets a repeat
        compute of a structurally identical plan skip tracing entirely.

        It must capture EVERYTHING that shapes the traced program. Op
        kernels and block functions are fingerprinted by cloudpickle (code
        objects + closure values); quantities that provably do NOT enter the
        program are masked so they don't defeat the cache:

        - array store paths (asserted out of the jitted signature by design;
          masked to order-of-first-use tokens),
        - RNG seeds (``VirtualOffsetsArray.base``) — ONLY for arrays whose
          every consuming kernel honors seed hoisting (``traced_offsets``);
          otherwise the base may be baked as a constant and stays in the key,
        - Spec resources (work_dir / mem budgets: plan-time-only).

        Returns None when fingerprinting fails (caller traces as usual).
        """
        import hashlib
        import io

        try:
            import cloudpickle
        except Exception:
            return None
        jax = _jax()

        from ...random import generation_mode as _generation_mode
        from ...core.plan import Plan
        from ...spec import Spec
        from ...utils import StackSummary

        # seed-hoist eligibility: every consumer must declare traced_offsets
        honored: Dict[int, bool] = {}
        for _, node in ops:
            pipeline = node["primitive_op"].pipeline
            if pipeline.function is not apply_blockwise:
                continue
            spec_ = pipeline.config
            f_traced = getattr(spec_.function, "traced_offsets", False)
            for proxy in spec_.reads_map.values():
                arr = proxy.array
                if isinstance(arr, VirtualOffsetsArray):
                    honored[id(arr)] = honored.get(id(arr), True) and f_traced
        maskable = {
            id(a) for a in seeded if honored.get(id(a), False)
        }

        tokens: Dict[str, str] = {}

        def tok(path: str) -> str:
            return tokens.setdefault(path, f"@{len(tokens)}")

        # gensym identifiers to canonicalize: the dag's node names plus every
        # reads_map key encountered while pickling (fused kernels nest the
        # specs of fused-away ops whose names no longer exist as dag nodes)
        plan_names = {str(n) for n in dag.nodes}

        class _MaskingPickler(cloudpickle.CloudPickler):
            def reducer_override(self, obj):  # noqa: D401
                if isinstance(obj, BlockwiseSpec):
                    plan_names.update(obj.reads_map.keys())
                if isinstance(obj, (LazyZarrArray, ZarrV2Array)):
                    return (
                        str,
                        (
                            f"zarr:{tok(str(obj.store))}:{tuple(obj.shape)}:"
                            f"{obj.dtype}:{tuple(getattr(obj, 'chunks', ()) or ())}",
                        ),
                    )
                if isinstance(obj, VirtualOffsetsArray):
                    base = "H" if id(obj) in maskable else obj.base
                    return (str, (f"offsets:{tuple(obj.shape)}:{base}",))
                if isinstance(obj, (VirtualEmptyArray, VirtualFullArray)):
                    return (
                        str,
                        (
                            f"vconst:{tuple(obj.shape)}:{obj.dtype}:"
                            f"{getattr(obj, 'fill_value', 0)}",
                        ),
                    )
                if isinstance(obj, VirtualInMemoryArray):
                    h = hashlib.sha256(
                        np.ascontiguousarray(obj.array).tobytes()
                    ).hexdigest()
                    return (
                        str,
                        (f"vmem:{obj.array.shape}:{obj.array.dtype}:{h}",),
                    )
                if isinstance(obj, Spec):
                    return (str, ("spec",))
                if isinstance(obj, (Plan, StackSummary)):
                    # plan/provenance metadata reachable through kernel
                    # closures: never part of the traced program, and carries
                    # per-build noise (caller linenos, op display names)
                    return (str, ("meta",))
                # cloudpickle implements its function-by-value support in
                # reducer_override itself — delegate, don't swallow it
                return super().reducer_override(obj)

        def aval(v):
            if isinstance(v, dict):
                return tuple(
                    sorted((k, tuple(x.shape), str(x.dtype)) for k, x in v.items())
                )
            return (tuple(v.shape), str(v.dtype))

        payload: list = [("inputs", tuple((tok(k), aval(resident[k].value)) for k in in_keys))]
        for _, node in ops:
            pop = node["primitive_op"]
            pipeline = pop.pipeline
            if pipeline.function is copy_read_to_write:
                cfg = pipeline.config
                payload.append(("copy", cfg.read, cfg.write, pop.num_tasks))
            else:
                spec_ = pipeline.config
                payload.append(
                    (
                        "blockwise",
                        spec_.function,
                        spec_.block_function,
                        getattr(spec_, "shape_invariant", False),
                        tuple(spec_.writes),
                        tuple(
                            (n, spec_.reads_map[n])
                            for n in sorted(spec_.reads_map)
                        ),
                        pop.num_tasks,
                    )
                )
        payload.append(("keep", tuple(tok(k) for k in keep_list)))
        payload.append(("bases", len(seeded)))
        devices = (
            tuple(d.id for d in self.mesh.devices.flat)
            if self.mesh is not None
            else (jax.devices()[0].id,)
        )
        payload.append(
            (
                "env",
                bool(jax.config.jax_enable_x64),
                devices,
                jax.devices()[0].platform,
                # executor config that changes the traced program: the mesh
                # SHAPE (not just the flat device order) determines
                # shardings; the contraction precision changes MXU pass
                # counts inside the same HLO shape
                str(self.matmul_precision),
                tuple(self.mesh.devices.shape) if self.mesh is not None else None,
                tuple(self.mesh.axis_names) if self.mesh is not None else None,
                # RNG kernels branch on the resolved generation mode at
                # trace time (random.generation_mode), so threefry- and
                # philox-traced programs of one plan shape must not share
                # a cache entry
                _generation_mode(),
            )
        )
        buf = io.BytesIO()
        try:
            _MaskingPickler(buf).dump(payload)
        except Exception:
            return None
        # gensym'd plan identifiers ("array-012", "op-047", ...) differ
        # between structurally identical plans and leak into pickled closures
        # (block functions carry argument names, fused kernels nest inner
        # specs); canonicalize them by order of first appearance in the byte
        # stream. Only the EXACT identifiers present in this plan's dag are
        # rewritten — a user string can collide only by literally equaling
        # one of this plan's own gensym names.
        if not plan_names:
            return hashlib.sha256(buf.getvalue()).hexdigest()
        pattern = re.compile(
            b"|".join(
                re.escape(n.encode())
                for n in sorted(plan_names, key=len, reverse=True)
            )
        )
        seen: Dict[bytes, bytes] = {}

        def repl(m):
            s = m.group(0)
            if s not in seen:
                seen[s] = b"N%06d" % len(seen)
            return seen[s]

        norm = pattern.sub(repl, buf.getvalue())
        if _STRUCT_DEBUG is not None:
            _STRUCT_DEBUG.append(norm)
        return hashlib.sha256(norm).hexdigest()

    def _trace_segment(
        self, ops, dag, resident, budget, requested_stores
    ) -> bool:
        """Trace every op in the segment into one jitted program and run it.

        Returns False when the segment should run eagerly instead (memory
        pre-check failed); raises on trace failure (the caller falls back
        on a designed abort alone)."""
        preload, offsets_arrays = self._segment_sources(ops)
        for arr in preload:
            self._preload(arr, resident, budget)

        # memory pre-check: resident inputs + every segment output must fit
        # (tracing cannot evict; the eager path can spill instead)
        out_bytes = 0
        for _, node in ops:
            pipeline = node["primitive_op"].pipeline
            cfg = pipeline.config
            for w in getattr(cfg, "writes", None) or (cfg.write,):
                target = w.array
                shape = tuple(getattr(target, "shape", ()) or ())
                dt = np.dtype(target.dtype)
                out_bytes += int(np.prod(shape or (1,))) * dt.itemsize
        in_bytes = sum(r.nbytes for r in resident.values())
        if in_bytes + out_bytes > budget:
            return False

        # hoist per-plan RNG seeds (VirtualOffsetsArray.base) to inputs so the
        # traced program's HLO is seed-independent (stable compile cache).
        # base_vals is positional in topo order of first appearance, so the
        # jitted arg order is identical for structurally equal plans; id(arr)
        # is used only as an in-trace lookup key and never enters the program
        seeded = [a for a in offsets_arrays if getattr(a, "base", 0)]

        # positional inputs/outputs: store paths must not appear in the jitted
        # signature (they leak into arg/result debug info, which enters the
        # persistent-cache key — tempdir paths would bust the cache every run)
        in_keys = sorted(resident.keys())
        in_vals = [resident[k].value for k in in_keys]
        base_vals = [np.int64(arr.base) for arr in seeded]
        keep = self._segment_keep(ops, dag, requested_stores)
        produced = set()
        for _, node in ops:
            cfg = node["primitive_op"].pipeline.config
            for w in getattr(cfg, "writes", None) or (cfg.write,):
                produced.add(str(w.array.store))
        keep_list = [k for k in keep if k in produced or k in in_keys]

        # structural fast path: a repeat compute of an identical plan shape
        # reuses the compiled program WITHOUT re-tracing (the dominant warm
        # cost); store paths/seeds are re-bound positionally
        with scope_span("jax.struct_key", cat="dispatch"):
            skey = self._structural_key(
                ops, dag, in_keys, resident, keep_list, seeded
            )
        with _CACHE_LOCK:
            cached_struct = (
                _STRUCT_CACHE.get(skey) if skey is not None else None
            )
        if cached_struct is not None:
            program = cached_struct
            self.stats["segment_struct_hits"] += 1
        else:
            program = self._lower_and_compile(
                ops, resident, in_keys, in_vals, seeded, base_vals, keep,
                keep_list,
            )
            if skey is not None:
                with _CACHE_LOCK:
                    if len(_STRUCT_CACHE) >= 64:
                        _STRUCT_CACHE.pop(next(iter(_STRUCT_CACHE)))
                    _STRUCT_CACHE[skey] = program
        if program.footprint:
            self.stats["segment_hbm_footprint"] = max(
                self.stats.get("segment_hbm_footprint", 0), program.footprint
            )
        # what the program is, not what this call did: a structural hit
        # reports the same collectives, placement and routes as the miss
        # before it
        self.stats.update(program.placement)
        self.stats.update(program.routes)
        with scope_span(
            "jax.dispatch", cat="dispatch",
            struct_hit=cached_struct is not None,
            widest_float=_widest_float(program.routes),
        ):
            # returns when the program is enqueued, not when it has run
            outs = program.compiled(in_vals, base_vals)
            for store, value in zip(keep_list, outs):
                self._admit(resident, store, value, keep[store], budget)
        return True

    def _lower_and_compile(
        self, ops, resident, in_keys, in_vals, seeded, base_vals, keep,
        keep_list,
    ):
        """Trace and lower the segment's ops as one function, and compile
        it unless a program of the same HLO is cached: a
        ``_SegmentProgram``. The structural miss of ``_trace_segment``."""
        jax = _jax()
        targets = {k: resident[k].target for k in in_keys}
        self._pinned = Counter(sharded_bytes=0, replicated_bytes=0)

        def seg_fn(vals, bases):
            local = {
                k: _Resident(v, 0, targets[k]) for k, v in zip(in_keys, vals)
            }
            self._tracing = True
            self._prepared_bases = {
                id(arr): b for arr, b in zip(seeded, bases)
            }
            try:
                for position, (_, node) in enumerate(ops):
                    primitive_op = node["primitive_op"]
                    # the plan's op in the metadata of its device operations
                    with jax.named_scope(_op_scope_name(position, primitive_op)):
                        if primitive_op.pipeline.function is apply_blockwise:
                            self._exec_blockwise(
                                primitive_op, local, budget=float("inf")
                            )
                        else:
                            self._exec_rechunk(
                                primitive_op, local, budget=float("inf")
                            )
            finally:
                self._tracing = False
                self._prepared_bases = {}
            return [
                self._keep_sharding_constraint(local[k].value, keep.get(k))
                for k in keep_list
            ]

        before = Counter(self.stats)
        with scope_span("jax.trace_lower", cat="dispatch"):
            lowered = jax.jit(seg_fn).lower(in_vals, base_vals)
        # what the routes counted while the ops were traced (counters only
        # rise) is the program's, not this call's: taken back here, kept
        # with the program, and added by ``_trace_segment`` in this compute
        # and in every later one that finds the program compiled
        routes = dict(self.stats - before)
        self.stats.subtract(routes)
        try:
            import hashlib

            # key on HLO text PLUS the device set: the same program lowered
            # for a different mesh/device assignment must not reuse an
            # executable compiled for another topology
            devices = (
                tuple(d.id for d in self.mesh.devices.flat)
                if self.mesh is not None
                else (jax.devices()[0].id,)
            )
            fingerprint = lowered.as_text() + repr(devices)
            key = hashlib.sha256(fingerprint.encode()).hexdigest()
        except Exception:
            key = None
        with _CACHE_LOCK:
            cached = _SEGMENT_CACHE.get(key) if key is not None else None
        if cached is None:
            with scope_span("jax.compile", cat="dispatch"):
                compiled = lowered.compile()
            self.stats["segments_compiled"] += 1
            placement = dict(self._pinned)
            if self.mesh is not None:
                placement.update(_count_collectives(compiled))
            program = _SegmentProgram(
                compiled, _hbm_footprint(compiled), placement, routes
            )
            if key is not None:
                with _CACHE_LOCK:
                    if len(_SEGMENT_CACHE) >= 64:
                        _SEGMENT_CACHE.pop(next(iter(_SEGMENT_CACHE)))
                    _SEGMENT_CACHE[key] = program
        else:
            # the same HLO from another plan shape (an alias lowers to
            # nothing): the program is shared, the routes are this trace's
            program = cached._replace(routes=routes)
            self.stats["segment_cache_hits"] += 1
        return program

    # ------------------------------------------------------------------
    # blockwise
    # ------------------------------------------------------------------

    def _exec_blockwise(self, op, resident: Dict[str, _Resident], budget: int) -> None:
        jax = _jax()
        spec: BlockwiseSpec = op.pipeline.config
        target = spec.write.array  # LazyZarrArray (or concrete for store ops)
        out_shape = tuple(target.shape)
        out_store = str(target.store)

        side_inputs = getattr(spec.function, "side_inputs", None)

        # whole-op concat: every source resident -> ONE device concatenate
        # along the declared axis (traceable; no storage round-trip)
        wc_axis = getattr(spec.function, "whole_concat", None)
        if side_inputs and wc_axis is not None:
            jnp = jax.numpy
            vals = []
            for arr in side_inputs:
                skey = str(getattr(arr, "store", id(arr)))
                res = resident.get(skey)
                if res is not None and not isinstance(res.value, dict):
                    res.touch()
                    vals.append(res.value)
                    continue
                virt = self._virtual_to_device(arr)
                if virt is None:
                    vals = None
                    break
                vals.append(virt)
            if vals is not None:
                value = (
                    vals[0] if len(vals) == 1 else jnp.concatenate(vals, axis=wc_axis)
                )
                if tuple(value.shape) == out_shape:
                    self.stats["whole_concat_hits"] += 1
                    self._admit(resident, out_store, value, target, budget)
                    return

        # residency-native fast paths for map_direct-family ops whose task
        # bodies declared their access pattern
        if side_inputs and len(side_inputs) == 1:
            skey = str(getattr(side_inputs[0], "store", id(side_inputs[0])))
            if skey in resident:
                res = resident[skey]
                if getattr(spec.function, "resident_identity", False):
                    # merge_chunks: values pass through; chunking is metadata
                    res.touch()
                    self._admit(resident, out_store, res.value, target, budget)
                    return
                ws = getattr(spec.function, "whole_select", None)
                if ws is not None:
                    value = self._apply_whole_select(res.value, ws)
                    if isinstance(value, dict) or tuple(value.shape) == out_shape:
                        res.touch()
                        self._admit(resident, out_store, value, target, budget)
                        return

        # other map_direct ops read arbitrary regions from storage inside the
        # task: materialize any resident side inputs first (they stay resident
        # for later consumers too)
        if side_inputs:
            for arr in side_inputs:
                skey = str(getattr(arr, "store", id(arr)))
                if skey in resident:
                    self._flush(resident[skey])

        try:
            value = self._exec_routes(op, spec, resident, out_shape)
        except _needs_concrete_value() as abort:
            if self._tracing:
                raise  # the segment level's: the whole segment runs eagerly
            # the kernel wants concrete values: it gets concrete chunks, once
            logger.info(
                "%s: kernel of %s runs un-jitted", type(abort).__name__, out_store
            )
            self.stats["eager_fallbacks"] += 1
            self.stats["host_kernel_ops"] += 1
            value = self._exec_chunked(op, spec, resident, jit=False)

        if spec.writes_rest:
            # multi-output: value is one device array per output proxy
            for proxy, v in zip(spec.writes, value):
                t = proxy.array
                if tuple(v.shape) != tuple(t.shape):
                    raise ValueError(
                        f"multi-output op produced shape {tuple(v.shape)}, "
                        f"target expects {tuple(t.shape)} (kernel/block-"
                        "function contract violation)"
                    )
                self._admit(resident, str(t.store), v, t, budget)
            return

        if not isinstance(value, dict) and tuple(value.shape) != out_shape:
            # chunked is the last resort: a shape mismatch here is a kernel
            # contract violation that must fail loudly, not assemble garbage
            raise ValueError(
                f"op produced shape {tuple(value.shape)}, target expects "
                f"{out_shape} (kernel/block-function contract violation)"
            )
        self._admit(resident, out_store, value, target, budget)

    def _exec_routes(self, op, spec: BlockwiseSpec, resident, out_shape):
        """The op's value by the first route that takes it: one jitted call
        on whole arrays, one vmapped call a bucket of chunks, one jitted
        call a chunk. A route declines (``None``) from what it reads in the
        plan or in the shape it produced; what a route raises is not caught
        here."""
        inputs = self._whole_inputs(spec, resident)
        host_bound = getattr(spec.function, "needs_block_id", False)
        value = None
        if spec.shape_invariant and not spec.writes_rest and not host_bound:
            mapping = self._probe_one_to_one(spec, op)
            if mapping and inputs is not None:
                value = _jax().jit(spec.function)(*(inputs[n] for n in mapping))
                if isinstance(value, dict) or tuple(value.shape) == out_shape:
                    self.stats["whole_array_hits"] += 1
                else:
                    value = None  # kernel wasn't truly shape-invariant
        if (
            value is None
            and not host_bound
            and not getattr(spec.function, "host_block_id", False)
        ):
            value = self._exec_batched(op, spec, resident)
            if value is not None:
                self.stats["batched_ops"] += 1
        if value is None:
            value = self._exec_chunked(op, spec, resident)
        return value

    def _apply_whole_select(self, value, selections):
        """Apply a per-axis orthogonal selection to a resident array on device."""
        jnp = _jax().numpy
        v = value
        for ax, s in enumerate(selections):
            if isinstance(s, tuple):  # resolved slice (start, stop, step)
                s0, s1, st = s
                if st < 0 and s1 < 0:
                    # .indices() reports "walked past index 0" as stop=-1,
                    # which a literal slice bound would wrap to the end
                    s1 = None
                sel = (slice(None),) * ax + (slice(s0, s1, st),)
                v = (
                    {k: vv[sel] for k, vv in v.items()}
                    if isinstance(v, dict)
                    else v[sel]
                )
            else:
                idx = jnp.asarray(np.asarray(s))
                v = (
                    {k: jnp.take(vv, idx, axis=ax) for k, vv in v.items()}
                    if isinstance(v, dict)
                    else jnp.take(v, idx, axis=ax)
                )
        return v

    def _whole_inputs(self, spec: BlockwiseSpec, resident) -> Optional[Dict[str, Any]]:
        """Whole arrays for every input, from residency or storage."""
        out = {}
        for name, proxy in spec.reads_map.items():
            arr = proxy.array
            key = str(getattr(arr, "store", id(arr)))
            if key in resident:
                resident[key].touch()
                out[name] = resident[key].value
            elif isinstance(
                arr, (VirtualFullArray, VirtualEmptyArray, VirtualInMemoryArray)
            ):
                out[name] = self._virtual_to_device(arr)
            elif isinstance(arr, VirtualOffsetsArray):
                return None  # block-id arrays have no whole-array meaning
            elif isinstance(arr, ZarrV2Array):
                if self._tracing:
                    raise _TraceAbort("storage read inside traced segment")
                data = arr[...] if arr.shape else arr[()]
                out[name] = self._device_put(data, data.shape)
            elif isinstance(arr, LazyZarrArray):
                if self._tracing:
                    raise _TraceAbort("storage read inside traced segment")
                try:
                    concrete = arr.open()
                except FileNotFoundError:
                    return None
                data = concrete[...] if concrete.shape else concrete[()]
                out[name] = self._device_put(data, data.shape)
            else:
                return None
        return out

    def _probe_one_to_one(self, spec: BlockwiseSpec, op) -> Optional[list[str]]:
        """Check the block mapping is 1:1 (with broadcast-clamp) and return the
        per-argument input names in order."""
        mappable = op.pipeline.mappable
        try:
            keys = list(itertools.islice(iter(mappable), 0, 3))
        except TypeError:
            return None
        if not keys:
            return None
        names: Optional[list[str]] = None
        for out_key in keys:
            structure = spec.block_function(out_key)
            out_coords = out_key[1:]
            cur = []
            for entry in structure:
                if not (isinstance(entry, tuple) and entry and isinstance(entry[0], str)):
                    return None  # contraction/iterator: not 1:1
                name, coords = entry[0], entry[1:]
                proxy = spec.reads_map.get(name)
                if proxy is None:
                    return None
                arr = proxy.array
                nb = (
                    tuple(
                        len(c)
                        for c in blockdims_from_blockshape(arr.shape, proxy.chunks)
                    )
                    if arr.shape
                    else ()
                )
                # coords must equal out coords (rightmost-aligned) or clamp to
                # 0 on broadcast dims
                oc = out_coords[len(out_coords) - len(coords):]
                for c, o, n in zip(coords, oc, nb):
                    if c != o and not (c == 0 and n == 1):
                        return None
                cur.append(name)
            if names is None:
                names = cur
            elif names != cur:
                return None
        return names

    # ------------------------------------------------------------------
    # batched: ALL tasks of a uniform-grid op in ONE vmapped XLA dispatch
    # ------------------------------------------------------------------

    def _exec_batched(self, op, spec: BlockwiseSpec, resident):
        """Stack every task's input chunks on device and run vmap(kernel) once.

        Collapses the reference's task fan-out (one dispatch per chunk through
        storage) into a single XLA program: per-task host overhead
        vanishes, and XLA tiles the batched kernel onto the MXU/VPU.
        Returns None when the op isn't batchable (ragged grid, streamed reads,
        non-uniform structure)."""
        jax = _jax()
        jnp = jax.numpy
        _register_pred_pytrees()

        target = spec.write.array
        out_shape = tuple(target.shape)
        if not out_shape:
            return None
        out_chunkset = blockdims_from_blockshape(out_shape, spec.write.chunks)
        out_nb = tuple(len(c) for c in out_chunkset)

        keys = list(op.pipeline.mappable)
        if len(keys) <= 1:
            return None
        # mappable is the C-order product over the out grid by construction
        structures = [spec.block_function(k) for k in keys]

        # flatten each task's key structure to leaves; all tasks must agree
        treedef0, leaves0 = _flatten_keys(structures[0])
        if treedef0 is None:
            return None
        task_leaves = [leaves0]
        for s in structures[1:]:
            td, leaves = _flatten_keys(s)
            if td != treedef0 or len(leaves) != len(leaves0):
                return None
            task_leaves.append(leaves)

        # per-leaf metadata (source array + chunk grid), shared by all buckets
        leaf_meta = []
        for k in leaves0:
            name = k[0]
            proxy = spec.reads_map.get(name)
            if proxy is None:
                return None
            arr = proxy.array
            chunkset = (
                blockdims_from_blockshape(arr.shape, proxy.chunks)
                if arr.shape
                else ()
            )
            leaf_meta.append((name, proxy, arr, chunkset))
        for leaves in task_leaves:
            for k, (name, _, _, _) in zip(leaves, leaf_meta):
                if k[0] != name:
                    return None  # leaf source varies across tasks

        def chunk_shape_at(chunkset, coords):
            return tuple(chunkset[d][c] for d, c in enumerate(coords))

        # bucket tasks by their full chunk-shape signature: each bucket is one
        # vmapped dispatch, so ragged grids cost one extra program per distinct
        # edge-chunk shape instead of one program per chunk
        buckets: Dict[tuple, list[int]] = {}
        for t, key in enumerate(keys):
            out_coords = tuple(key[1:])
            sig = (chunk_shape_at(out_chunkset, out_coords),) + tuple(
                chunk_shape_at(cs, tuple(k[1:])) if arr.shape else ()
                for k, (_, _, arr, cs) in zip(task_leaves[t], leaf_meta)
            )
            buckets.setdefault(sig, []).append(t)

        if len(buckets) > max(8, len(keys) // 4):
            return None  # too ragged: batching would hardly help

        fn = spec.function
        td = treedef0

        def task_fn(*flat):
            args = _unflatten_keys(td, list(flat))
            return fn(*args)

        # under a mesh, buckets that tile the out grid as dense subgrids keep
        # their grid dims (see ``_dense_subgrid``); ``grids`` is then the
        # (lows, extents) of each bucket, else None and every bucket stacks.
        # Without a mesh nothing is gained, and the stacked form is the one
        # XLA generates in full tiles (PERF.md section 5, PR 28)
        grids = (
            self._bucket_grids(keys, buckets, task_leaves, leaf_meta, resident)
            if self.mesh is not None
            else None
        )
        ndim = len(out_nb)
        regions: Dict[tuple, Any] = {}

        chunk_grid: Dict[tuple, Any] = {}
        for sig, tasks in buckets.items():
            T = len(tasks)
            # the leading dims of this bucket's stacked leaves and results
            lead = grids[sig][1] if grids else (T,)
            stacked_leaves = []
            in_axes_leaves = []
            for i, (name, proxy, arr, chunkset) in enumerate(leaf_meta):
                leaf_keys = [task_leaves[t][i] for t in tasks]
                coords = [tuple(k[1:]) for k in leaf_keys]
                if all(c == coords[0] for c in coords):
                    # same chunk for every task: broadcast (no stacking)
                    stacked_leaves.append(
                        self._resolve(
                            leaf_keys[0],
                            spec,
                            resident,
                            getattr(spec.function, "traced_offsets", False),
                        )
                    )
                    in_axes_leaves.append(None)
                    continue

                if isinstance(arr, VirtualOffsetsArray):
                    base = getattr(arr, "base", 0)
                    rel = np.asarray(
                        [np.ravel_multi_index(c, arr.shape) for c in coords],
                        dtype=arr.dtype,
                    ).reshape(lead + (1,) * len(arr.shape))
                    if self._tracing and id(arr) in self._prepared_bases:
                        # seed rides a hoisted input; relative offsets are a
                        # seed-independent constant -> stable HLO across plans
                        offs = (
                            jnp.asarray(rel)
                            + self._prepared_bases[id(arr)].astype(arr.dtype)
                        )
                    else:
                        offs = self._device_put(rel + base, None)
                    stacked_leaves.append(offs)
                    in_axes_leaves.append(0)
                    continue
                if isinstance(arr, (VirtualEmptyArray, VirtualFullArray)):
                    fill = getattr(arr, "fill_value", 0)
                    cshape = chunk_shape_at(chunkset, coords[0])
                    stacked_leaves.append(
                        jnp.full(cshape, fill, dtype=arr.dtype)
                    )
                    in_axes_leaves.append(None)  # constant: broadcast
                    continue

                store_key = str(getattr(arr, "store", id(arr)))
                if store_key in resident:
                    res = resident[store_key]
                    res.touch()
                    value = res.value
                    nb = tuple(len(c) for c in chunkset)
                    if grids:
                        stacked = _gather_subgrid(
                            value, chunkset, coords, keep_grid=True
                        )
                    elif all(len(set(c)) == 1 for c in chunkset):
                        idx = np.asarray(
                            [np.ravel_multi_index(c, nb) for c in coords],
                            dtype=np.int32,
                        )
                        chunk_shape = tuple(c[0] for c in chunkset)
                        stacked = _gather_blocks(value, nb, chunk_shape, idx)
                    else:
                        stacked = _gather_subgrid(value, chunkset, coords)
                        if stacked is None:
                            # irregular coord set: stack device slices
                            sels = [get_item(chunkset, c) for c in coords]
                            if isinstance(value, dict):
                                stacked = {
                                    k: jnp.stack([v[s] for s in sels])
                                    for k, v in value.items()
                                }
                            else:
                                stacked = jnp.stack([value[s] for s in sels])
                    stacked_leaves.append(stacked)
                    in_axes_leaves.append(0)
                    continue

                # host source (in-memory / zarr): stack once, transfer once
                if self._tracing and isinstance(arr, (ZarrV2Array, LazyZarrArray)):
                    raise _TraceAbort("storage read inside traced segment")
                opened = proxy.open()
                host = np.stack(
                    [np.asarray(opened[get_item(chunkset, c)]) for c in coords]
                )
                stacked_leaves.append(self._device_put(host, None))
                in_axes_leaves.append(0)

            if all(ax is None for ax in in_axes_leaves):
                return None

            batched = task_fn
            for _ in lead:
                batched = jax.vmap(batched, in_axes=tuple(in_axes_leaves))
            out_stacked = jax.jit(batched)(*stacked_leaves)

            if grids:
                # one region a bucket: the results' grid dims merge with
                # their chunk dims, no chunk is sliced out and put back
                first = tuple(keys[tasks[0]][1:])
                outs = out_stacked if spec.writes_rest else (out_stacked,)
                if not isinstance(outs, (tuple, list)) or len(outs) != len(
                    spec.writes
                ):
                    return None
                for w, stacked in zip(spec.writes, outs):
                    cs_w = blockdims_from_blockshape(tuple(w.array.shape), w.chunks)
                    expect = lead + chunk_shape_at(cs_w, first)
                    # (the fields of a record array have its chunk's shape)
                    if any(
                        tuple(v.shape) != expect
                        for v in jax.tree_util.tree_leaves(stacked)
                    ):
                        return None
                regions[grids[sig][0]] = jax.tree_util.tree_map(
                    lambda v: _merge_grid(v, ndim), out_stacked
                )
                continue

            for ti, t in enumerate(tasks):
                out_coords = tuple(keys[t][1:])
                if spec.writes_rest:
                    if not isinstance(out_stacked, (tuple, list)) or len(
                        out_stacked
                    ) != len(spec.writes):
                        return None
                    for j, (w, stacked) in enumerate(
                        zip(spec.writes, out_stacked)
                    ):
                        cs_j = blockdims_from_blockshape(
                            tuple(w.array.shape), w.chunks
                        )
                        if tuple(stacked.shape[1:]) != chunk_shape_at(
                            cs_j, out_coords
                        ):
                            return None
                    chunk_grid[out_coords] = tuple(v[ti] for v in out_stacked)
                elif isinstance(out_stacked, dict):
                    chunk_grid[out_coords] = {
                        k: v[ti] for k, v in out_stacked.items()
                    }
                else:
                    expect = chunk_shape_at(out_chunkset, out_coords)
                    if tuple(out_stacked.shape[1:]) != expect:
                        return None
                    chunk_grid[out_coords] = out_stacked[ti]

        if grids:
            # the buckets' regions tile the array as a coarse grid of their own
            cuts = [sorted({lows[d] for lows in regions}) for d in range(ndim)]
            chunk_grid = {
                tuple(cuts[d].index(lo) for d, lo in enumerate(lows)): region
                for lows, region in regions.items()
            }
            out_nb = tuple(len(c) for c in cuts)
        if spec.writes_rest:
            return tuple(
                _assemble(
                    {c: v[j] for c, v in chunk_grid.items()}, out_nb
                )
                for j in range(len(spec.writes))
            )
        value = _assemble(chunk_grid, out_nb)
        if not isinstance(value, dict) and tuple(value.shape) != out_shape:
            return None
        return value

    def _bucket_grids(self, keys, buckets, task_leaves, leaf_meta, resident):
        """``{bucket: (lows, extents)}`` when every bucket of a batched op is
        a dense subgrid of the out grid (``_dense_subgrid``), the buckets
        tile that grid as a product of cuts per dim, and each leaf that
        varies over a bucket's tasks walks a subgrid of the same extents of
        an offsets array or a resident one; None otherwise (the op stacks
        its tasks along one dim, as it does without a mesh)."""
        grids = {}
        for sig, tasks in buckets.items():
            grid = _dense_subgrid([tuple(keys[t][1:]) for t in tasks])
            if grid is None:
                return None
            for i, (_, _, arr, _) in enumerate(leaf_meta):
                coords = [tuple(task_leaves[t][i][1:]) for t in tasks]
                if all(c == coords[0] for c in coords) or isinstance(
                    arr, (VirtualEmptyArray, VirtualFullArray)
                ):
                    continue  # broadcast over the bucket
                if not isinstance(arr, VirtualOffsetsArray) and (
                    str(getattr(arr, "store", id(arr))) not in resident
                ):
                    return None
                walked = _dense_subgrid(coords)
                if walked is None or walked[1] != grid[1]:
                    return None
            grids[sig] = grid
        # per dim, the distinct (low, extent) runs of the buckets
        cuts = [set(runs) for runs in zip(*(zip(*grid) for grid in grids.values()))]
        if len(grids) != math.prod(len(c) for c in cuts):
            return None
        return grids

    # ------------------------------------------------------------------

    def _exec_chunked(self, op, spec: BlockwiseSpec, resident, jit=True):
        """Per-output-chunk execution with on-device slicing; with ``jit``
        false the kernel is called as it is, on concrete chunks."""
        self.stats["chunked_ops"] += 1
        target = spec.write.array
        out_shape = tuple(target.shape)
        chunkset = (
            blockdims_from_blockshape(out_shape, spec.write.chunks)
            if out_shape
            else ()
        )
        nb = tuple(len(c) for c in chunkset)
        needs_block_id = getattr(spec.function, "needs_block_id", False)

        jitted = _JitCache(spec.function, jit)
        region_fn = getattr(spec.function, "combine_region", None)
        jitted_region = (
            _JitCache(region_fn, jit) if region_fn is not None else None
        )

        traced_offsets = self._tracing and getattr(
            spec.function, "traced_offsets", False
        )

        chunk_grid: Dict[tuple, Any] = {}
        for out_key in op.pipeline.mappable:
            out_coords = tuple(out_key[1:])
            structure = spec.block_function(out_key)
            result = None
            if (
                jitted_region is not None
                and structure
                and all(isinstance(e, Iterator) for e in structure)
            ):
                # one contiguous region per argument (N=1 for plain
                # reductions; one per field for pytree intermediates held
                # as N arrays), combined in a single jitted call
                keyss = [list(e) for e in structure]
                regions = [
                    self._resolve_region(keys, spec, resident)
                    for keys in keyss
                ]
                if all(r is not None for r in regions):
                    result = jitted_region(*regions)
                else:
                    structure = tuple(iter(keys) for keys in keyss)
            if result is None:
                args = [
                    self._resolve(entry, spec, resident, traced_offsets)
                    for entry in structure
                ]
                if needs_block_id:
                    result = spec.function(*args, block_id=out_coords)
                else:
                    result = jitted(*args)
            chunk_grid[out_coords] = result

        if spec.writes_rest:
            # multi-output: per-chunk tuples -> one assembled array per output
            return tuple(
                _assemble({c: v[j] for c, v in chunk_grid.items()}, nb)
                if out_shape
                else chunk_grid[()][j]
                for j in range(len(spec.writes))
            )
        if not out_shape:
            return chunk_grid[()]
        return _assemble(chunk_grid, nb)

    def _resolve_region(self, keys, spec: BlockwiseSpec, resident):
        """Slice the contiguous region covering a group of blocks of one
        resident array — one device slice replaces a streamed combine."""
        if not keys:
            return None
        names = {k[0] for k in keys}
        if len(names) != 1:
            return None
        name = keys[0][0]
        proxy = spec.reads_map.get(name)
        if proxy is None:
            return None
        arr = proxy.array
        key = str(getattr(arr, "store", id(arr)))
        if key not in resident or not arr.shape:
            return None
        res = resident[key]
        res.touch()
        chunkset = blockdims_from_blockshape(arr.shape, proxy.chunks)
        coords = [tuple(k[1:]) for k in keys]
        ndim = len(arr.shape)
        los = [min(c[d] for c in coords) for d in range(ndim)]
        his = [max(c[d] for c in coords) for d in range(ndim)]
        # must be the full dense block range
        if len(coords) != math.prod(h - l + 1 for l, h in zip(los, his)):
            return None
        sel = tuple(
            slice(
                sum(chunkset[d][: los[d]]),
                sum(chunkset[d][: his[d] + 1]),
            )
            for d in range(ndim)
        )
        value = res.value
        if isinstance(value, dict):
            return {k: v[sel] for k, v in value.items()}
        return value[sel]

    def _resolve(self, entry, spec: BlockwiseSpec, resident, traced_offsets=False):
        """Resolve a key structure to device chunks (sliced from residents)."""
        from ...primitive.blockwise import PredArgs, PredKeys, _is_key

        if isinstance(entry, PredKeys):
            return PredArgs(
                [self._resolve(e, spec, resident, traced_offsets) for e in entry]
            )
        if isinstance(entry, (list, tuple)) and not _is_key(entry):
            return [self._resolve(e, spec, resident, traced_offsets) for e in entry]
        if isinstance(entry, Iterator):
            return (self._resolve(e, spec, resident, traced_offsets) for e in entry)
        name, coords = entry[0], tuple(entry[1:])
        proxy = spec.reads_map[name]
        arr = proxy.array
        key = str(getattr(arr, "store", id(arr)))
        if (
            traced_offsets
            and isinstance(arr, VirtualOffsetsArray)
            and id(arr) in self._prepared_bases
        ):
            # kernel accepts a traced seed: relative offset is a stable
            # constant, the per-plan seed rides the hoisted segment input
            jnp = _jax().numpy
            rel = np.ravel_multi_index(coords, arr.shape) if arr.shape else 0
            off = self._prepared_bases[id(arr)].astype(arr.dtype) + rel
            return jnp.reshape(off, (1,) * len(arr.shape))
        if key in resident:
            res = resident[key]
            res.touch()
            chunkset = (
                blockdims_from_blockshape(arr.shape, proxy.chunks) if arr.shape else ()
            )
            sel = get_item(chunkset, coords) if arr.shape else ()
            value = res.value
            if isinstance(value, dict):
                return {k: v[sel] for k, v in value.items()}
            return value[sel]
        # constant-valued chunks are created on device — no host transfer
        if isinstance(arr, (VirtualEmptyArray, VirtualFullArray)):
            jax = _jax()
            chunkset = (
                blockdims_from_blockshape(arr.shape, proxy.chunks) if arr.shape else ()
            )
            sel = get_item(chunkset, coords) if arr.shape else ()
            shape = tuple(s.stop - s.start for s in sel)
            fill = getattr(arr, "fill_value", 0)
            return jax.numpy.full(shape, fill, dtype=arr.dtype)
        if isinstance(arr, VirtualOffsetsArray):
            # raw numpy, NOT backend-converted: inside a traced segment the
            # backend conversion turns the block into a (constant-valued)
            # tracer, which a host_block_id kernel's int(offset) cannot
            # consume — the whole segment then trace-fails to eager. The
            # hoisted-seed path above serves traced_offsets kernels; every
            # other consumer wants a concrete value (it IS concrete: pure
            # plan metadata).
            sel = get_item(
                blockdims_from_blockshape(arr.shape, proxy.chunks), coords
            ) if arr.shape else ()
            return np.asarray(arr[sel])
        # storage / small-virtual fallback (host read + device transfer)
        if self._tracing and isinstance(arr, (ZarrV2Array, LazyZarrArray)):
            raise _TraceAbort("storage read inside traced segment")
        opened = proxy.open()
        chunkset = (
            blockdims_from_blockshape(opened.shape, proxy.chunks)
            if opened.shape
            else ()
        )
        return self._device_put(
            np.asarray(opened[get_item(chunkset, coords)]), None
        )

    # ------------------------------------------------------------------
    # rechunk: resident alias / storage fallback
    # ------------------------------------------------------------------

    def _exec_rechunk(self, op, resident: Dict[str, _Resident], budget: int) -> None:
        config = op.pipeline.config  # CubedCopySpec
        src = config.read.array
        dst = config.write.array
        src_key = str(getattr(src, "store", id(src)))
        dst_key = str(dst.store)

        if src_key in resident:
            # chunking is metadata; the resident value is the whole array
            res = resident[src_key]
            res.touch()
            self.stats["rechunk_alias"] += 1
            self._admit(resident, dst_key, res.value, dst, budget)
            return

        # virtual sources materialize on device directly (trace-safe) — a
        # real materialization, counted apart from zero-copy aliases
        virt = self._virtual_to_device(src)
        if virt is not None:
            self.stats["rechunk_virtual"] += 1
            self._admit(resident, dst_key, virt, dst, budget)
            return

        # source lives in storage: load whole if it fits, else host-side copy
        if self._tracing:
            raise _TraceAbort("rechunk storage source inside traced segment")
        try:
            opened = src.open() if hasattr(src, "open") else src
        except FileNotFoundError:
            opened = None
        whole = opened is not None and opened.nbytes < budget // 2
        route = "host_whole" if whole else "host_copy"
        self.stats["rechunk_" + route] += 1
        with scope_span("jax.rechunk", route=route, bytes=dst.nbytes):
            if whole:
                data = opened[...] if opened.shape else opened[()]
                value = self._device_put(data, data.shape)
                self._admit(resident, dst_key, value, dst, budget)
            else:
                # bounded host-side copy (the spill path), never touching
                # the device. Residency leaves an array that nobody asked
                # for uncreated (``run_eager``), so the route that does
                # write to storage makes sure its destination is there, as
                # ``_flush`` does
                if isinstance(dst, LazyZarrArray):
                    dst.create(mode="a")
                for m in op.pipeline.mappable:
                    op.pipeline.function(m, config=config)

    # ------------------------------------------------------------------
    # residency bookkeeping
    # ------------------------------------------------------------------

    def _admit(self, resident, store: str, value, target, budget: int) -> None:
        nbytes = _value_nbytes(value)
        if self._tracing:
            # what precision the program computes in, whatever the plan says
            jnp = _jax().numpy
            for leaf in value.values() if isinstance(value, dict) else (value,):
                counter = _FLOAT_BYTES.get(leaf.dtype.itemsize)
                if counter and jnp.issubdtype(leaf.dtype, jnp.floating):
                    self.stats[counter] += _value_nbytes(leaf)
            # inside a traced segment this is where an array is "placed":
            # without the constraint a segment whose inputs are all virtual
            # (random arrays) carries no sharding at all and XLA compiles
            # it for ONE device of the mesh
            sharding = self._target_sharding(value, target)
            if sharding is not None:
                value = _jax().lax.with_sharding_constraint(value, sharding)
                # a grid nothing divides gets an empty spec: every chip
                # then holds the whole array, and only this counter says so
                divided = any(p is not None for p in sharding.spec)
                self._pinned[
                    "sharded_bytes" if divided else "replicated_bytes"
                ] += nbytes
        self._evict(resident, budget - nbytes, exclude=store)
        resident[store] = _Resident(value, nbytes, target)

    def _evict(self, resident, budget: int, exclude: Optional[str] = None) -> None:
        total = sum(r.nbytes for r in resident.values())
        if total <= budget:
            return
        self._spilling = True
        try:
            for store, res in sorted(
                resident.items(), key=lambda kv: kv[1].last_used
            ):
                if store == exclude:
                    continue
                self._flush(res)
                del resident[store]
                total -= res.nbytes
                if total <= budget:
                    return
        finally:
            self._spilling = False

    def _flush(self, res: _Resident) -> None:
        """Write a resident array to its Zarr target, chunk by chunk."""
        if self._tracing:
            raise _TraceAbort("flush inside traced segment")
        target = res.target
        if isinstance(target, LazyZarrArray):
            concrete = target.create(mode="a")
        elif isinstance(target, ZarrV2Array):
            concrete = target
        else:
            return
        with scope_span("jax.flush", bytes=res.nbytes) as sp:
            sp.attrs["chunks"] = self._flush_chunks(res.value, concrete)

    def _flush_chunks(self, value, concrete) -> int:
        """``_flush``'s fetches and writes; the number of chunks written.

        A pipeline of depth two over the chunk grid, in grid order, the
        mirror image of ``_stream_to_device``: this thread slices chunk
        k + 1 on the device (on the chip that holds it, where one does:
        ``_chunk_of``), fetches it and joins its planes into staging
        buffer (k + 1) mod 2 while a second thread writes chunk k from
        buffer k mod 2 (``ZarrV2Array.__setitem__``: file write, fsync,
        rename, directory fsync, CRC-32, manifest line, all of which let
        the other thread run). Chunk k + 1's write starts once chunk k's
        has returned, so one writer enters the chunks in grid order, a
        buffer is rewritten only after the write that read it has
        returned, and when this returns every chunk is durable and in the
        manifest. The first error of either side, a cancellation among
        them, is raised from here: no chunk is started after it and no
        thread is left. On the device one chunk's slice and planes exist
        at a time, as before.

        The writer finds the compute's cancellation token through a copy of
        this thread's context and works in a task scope of its own, since
        spans, byte counts and injected storage faults find theirs through
        the calling thread; each write's scope is folded into this
        thread's. ``stats["flush_stream_bytes"]`` counts the bytes that
        reached the store from a staging buffer with no copy on the host
        after the join, ``stats["encode_copy_bytes"]`` those the store had
        to copy (``encode_copy_bytes`` of the write's scope). The time this
        thread is blocked on the writer is the span ``jax.write_wait`` (one
        a write, a child of ``jax.flush``) and, armed or not,
        ``stats["write_wait_us"]``: most of the flush where the writer
        paces it, next to nothing where the fetch does. A value that does
        not leave as planes takes the same pipeline and no buffer."""
        shape = tuple(concrete.shape)
        if not shape:
            concrete[()] = self._to_host(value, concrete.dtype)
            return 1
        chunkset = blockdims_from_blockshape(shape, concrete.chunks)
        coords_iter = itertools.product(*(range(len(c)) for c in chunkset))
        sharding = getattr(value, "sharding", None)
        jax = _jax()
        if (
            self.mesh is not None
            and not isinstance(value, dict)
            and sharding is not None
            and jax.process_count() > 1
        ):
            # per-host write sharding (docs/multihost.md): under
            # multi-controller SPMD every process runs this flush, but each
            # writes only the chunks its own devices own — together exactly
            # the full grid, each byte written once. Single-process runs
            # skip the assignment scan (every chunk is addressable anyway).
            from ...parallel.multihost import (
                chunk_within_owner_shard,
                local_chunks,
            )

            mine = local_chunks(sharding, shape, tuple(concrete.chunks))
            for coords in mine:
                if not chunk_within_owner_shard(
                    sharding, shape, chunkset, coords
                ):
                    raise NotImplementedError(
                        "multi-host flush requires a chunk-aligned sharding "
                        f"(chunk {coords} straddles shard boundaries); "
                        "rechunk or choose a chunk-aligned mesh layout "
                        "(parallel.mesh.sharding_for_chunks prefers one)"
                    )
            coords_iter = iter(mine)
        outer = current_scope()

        def write(sel, host):
            """On the writer's thread: (the write's scope, what it raised)."""
            scoped = (
                task_scope(_SCOPE_SPANS) if outer is not None
                else contextlib.nullcontext()
            )
            with scoped as inner:
                try:
                    concrete[sel] = host
                except BaseException as error:  # raised again by ``settle``
                    return inner, error
            return inner, None

        def settle(pending: list) -> None:
            """Wait for the write in flight, if there is one: its records
            into this thread's scope, its error raised."""
            if not pending:
                return
            future, staged_nbytes, reused, k = pending.pop()
            with scope_span("jax.write_wait", cat="wait", chunk=k):
                started = time.perf_counter_ns()
                inner, error = future.result()
                self.stats["write_wait_us"] += (
                    time.perf_counter_ns() - started
                ) // 1000
            copied = None  # not observed without a scope
            if inner is not None:
                copied = inner.counters.get("encode_copy_bytes", 0)
                outer.fold(inner)
                self.stats["encode_copy_bytes"] += copied
            if error is not None:
                raise error
            if copied == 0:
                self.stats["flush_stream_bytes"] += staged_nbytes
                self.stats["stage_reused_bytes"] += staged_nbytes if reused else 0

        chunks = 0
        pending: list = []  # at most the one write in flight
        with ThreadPoolExecutor(1, thread_name_prefix="cubed-tpu-flush") as pool:
            try:
                for k, idx in enumerate(coords_iter):
                    sel = get_item(chunkset, idx)
                    stage = self._staging[k % 2]
                    # the device slice is not bound to a name here: it would
                    # stay alive on the device while the next chunk is sliced
                    host = self._to_host(
                        {f: _chunk_of(v, sel) for f, v in value.items()}
                        if isinstance(value, dict)
                        else _chunk_of(value, sel),
                        concrete.dtype,
                        stage,
                    )
                    settle(pending)
                    pending.append((
                        pool.submit(contextvars.copy_context().run, write, sel, host),
                        host.nbytes if stage.holds(host) else 0,
                        stage.kept,
                        k,
                    ))
                    chunks += 1
            finally:
                # also on an error of this side: the write in flight is
                # waited for and its records kept
                settle(pending)
        return chunks


def _chunk_of(value, sel):
    """``value[sel]``, cut on the chip that holds it where one does: a
    chunk that lies inside one shard of a value laid over several chips is
    sliced out of that shard, so the slice, and the split and fetch that
    follow it, are programs of that one chip and no other is touched. A
    chunk that crosses shards, and any value on one device, is sliced as it
    is (of a sharded value that is a program of every chip it lies on)."""
    shards = getattr(value, "addressable_shards", ())
    if len(shards) > 1:
        from ...parallel.mesh import shard_bounds, within

        for shard in shards:
            bounds = shard_bounds(shard.index, value.shape)
            if within(sel, bounds):
                return shard.data[
                    tuple(slice(c.start - lo, c.stop - lo) for c, (lo, _) in zip(sel, bounds))
                ]
    return value[sel]


#: the ``chunk_key`` of the event that carries a flush's IO and spans
_FLUSH_KEY = "flush"

#: span buffer of one of this executor's task scopes. A scope here is a whole
#: segment with its preloads, or the flush of a whole array: a few spans a
#: chunk, in-process, shipped nowhere. Beyond it spans drop, with a count
_SCOPE_SPANS = 4096


def _op_scope_name(position: int, primitive_op) -> str:
    """The ``jax.named_scope`` of one op of a traced segment. Structural
    (its place in the segment, its kind, its kernel's name), never a store
    path nor a gensym'd array name: the compiled program is shared by every
    compute of the same plan shape."""
    pipeline = primitive_op.pipeline
    if pipeline.function is not apply_blockwise:
        return f"op{position:02d}.rechunk"
    kernel = getattr(pipeline.config.function, "__name__", None)
    if not kernel or not kernel.isidentifier():  # a lambda, a partial
        return f"op{position:02d}.blockwise"
    return f"op{position:02d}.blockwise.{kernel}"


class _SegmentProgram(NamedTuple):
    """A compiled segment program with what was learned of it once, when it
    was compiled: every later compute that finds it cached reports the same."""

    compiled: Any
    #: ``_hbm_footprint``: bytes on ONE device, also under a mesh
    footprint: int
    #: the ``_MESH_COUNTERS`` of this program
    placement: Dict[str, int]
    #: what the routes counted while the program's ops were traced
    #: (``rechunk_alias``, ``whole_array_hits``, ``batched_ops``, ..., and
    #: the ``_FLOAT_BYTES`` of the values its ops produce): of the plan
    #: shape that found or compiled the program, not of its HLO
    routes: Dict[str, int]


#: the kinds of collective instruction ``_count_collectives`` counts, as XLA
#: names them and as ``stats`` does (``segment_all_to_all``, ...)
_COLLECTIVE_KINDS = ("all-to-all", "all-reduce", "all-gather", "collective-permute")
_COLLECTIVE_INSTRUCTION = re.compile(
    r"\s(" + "|".join(_COLLECTIVE_KINDS) + r")(?:-start)?\("
)

#: counters of a compute's segment programs under a mesh, each 0 (not
#: absent) without one: the collective instructions by kind and in all, and
#: the bytes of the arrays ``_admit`` pinned to a sharding that divides them
#: and to one whose ``PartitionSpec`` came out empty
_MESH_COUNTERS = (
    "segment_collectives",
    *("segment_" + kind.replace("-", "_") for kind in _COLLECTIVE_KINDS),
    "sharded_bytes",
    "replicated_bytes",
)


#: counters of the floating-point values a compute's segment programs
#: produce, by the bytes of one value as traced (``_admit``): float32,
#: float64, and bfloat16 and float16 together. Each is 0, not absent, where
#: nothing counts; they are kept with ``_SegmentProgram.routes``
_FLOAT_BYTES = {
    4: "device_f32_bytes", 8: "device_f64_bytes", 2: "device_f16_bytes",
}


def _widest_float(routes: Dict[str, int]) -> Optional[str]:
    """The widest floating-point dtype a program's ops produce (a
    ``jax.dispatch`` span's ``widest_float``), None where they produce none."""
    widths = [w for w, name in _FLOAT_BYTES.items() if routes.get(name)]
    return f"float{8 * max(widths)}" if widths else None


def _count_collectives(compiled) -> Dict[str, int]:
    """Collective instructions of a compiled program by kind
    (``segment_all_to_all``, ...) and in all (``segment_collectives``), read
    once from the compiled module's text: the partitioner puts them in, so
    no earlier form of the program has them. An asynchronous pair counts
    once, at its start."""
    counts: Counter = Counter()
    for match in _COLLECTIVE_INSTRUCTION.finditer(compiled.as_text()):
        counts["segment_" + match.group(1).replace("-", "_")] += 1
        counts["segment_collectives"] += 1
    return counts


#: in-process cache of ``_SegmentProgram`` keyed by the
#: sha256 hex digest of (lowered HLO text, device-id tuple): repeat computes
#: of structurally equal plans on the same device set skip compilation (and
#: re-analysis) entirely, while a different mesh/topology gets its own entry
_SEGMENT_CACHE: Dict[str, Any] = {}


#: structural-fingerprint cache: ``_SegmentProgram`` keyed by
#: the pre-trace segment fingerprint (see JaxExecutor._structural_key) —
#: repeat computes of structurally identical plans skip tracing entirely
_STRUCT_CACHE: Dict[str, Any] = {}

#: debugging hook: set to a list to collect normalized fingerprint payloads
_STRUCT_DEBUG: Optional[list] = None

#: guards the two module-level program caches: concurrent computes (the
#: multi-tenant service drives Plan.execute from many threads) would
#: otherwise interleave the size-check/evict/insert sequences and could
#: evict an entry a sibling just read or resurrect one past the bound
_CACHE_LOCK = threading.Lock()

#: the process's staging pairs while no compute has them: none, or as many
#: as the widest compute so far has leased at once (one, but under a mesh,
#: where a streamed preload runs a lane a chip, each through a pair of its
#: own: ``_POOL_WIDTH``). They outlive executors as the program caches do,
#: so that a compute reads its chunks into pages the compute before has
#: touched (a fresh 200 MB buffer costs a read 0.15 s more on the v5e's
#: host, PERF.md section 6, PRs 36 and 37). What they keep from the process
#: between computes is two buffers a pair of the largest chunk streamed
#: through it so far
_STAGING_POOL: list = []

#: the most pairs the pool keeps: the most one compute has leased at once
#: since the process started or last called ``release_staging_buffers``
_POOL_WIDTH = 1


def _take_staging() -> Tuple[_Staging, _Staging]:
    """A staging pair out of the process's pool, or a fresh one where the
    pool has none left (another thread's compute has them, or no compute was
    as wide yet): concurrent computes never share a buffer and never wait
    for one another. Whoever takes one gives it back through the lease it
    belongs to (``_leased_staging``)."""
    with _CACHE_LOCK:
        pair = _STAGING_POOL.pop() if _STAGING_POOL else None
    if pair is None:
        pair = (_Staging(), _Staging())
    for stage in pair:
        stage.kept = stage.buffer is not None
    return pair


@contextlib.contextmanager
def _leased_staging() -> Iterator[list]:
    """The staging pairs of one compute for the length of the block: a list
    that starts with one pair (``_take_staging``) and to which the lessee
    appends what more it takes (a pair a lane, ``JaxExecutor._lane_pairs``).
    At the end, however the block ends, every buffer's device update is
    waited out and let go of, so that no device value outlives its compute
    here and the next lessee's first ``release`` costs nothing; then the
    pairs go back, as many as bring the pool to the width of the widest
    compute so far, and the rest are dropped (a compute that found the pool
    taken by another thread's). A pair whose update raises is not given
    back; the first such error is raised once the others are."""
    global _POOL_WIDTH
    pairs = [_take_staging()]
    try:
        yield pairs
    finally:
        sound, errors = [], []
        for pair in pairs:
            try:
                for stage in pair:
                    stage.release()
                sound.append(pair)
            except BaseException as error:
                errors.append(error)
        with _CACHE_LOCK:
            _POOL_WIDTH = max(_POOL_WIDTH, len(pairs))
            room = _POOL_WIDTH - len(_STAGING_POOL)  # never under 0
            # the first leased goes in last, and comes out first
            _STAGING_POOL.extend(reversed(sound[:room]))
        if errors:
            raise errors[0]


def _drop_staging_pool() -> None:
    global _POOL_WIDTH
    _STAGING_POOL.clear()
    _POOL_WIDTH = 1


def release_staging_buffers() -> None:
    """Hand the pages of the idle staging pairs back to the system (two
    buffers a pair of the largest chunk streamed through it; 400 MB after a
    compute over 200 MB chunks on one chip, 1.6 GB after one under a mesh of
    four), and start the pool's width over. Nothing calls it: a process
    that computes again wants them kept. Pairs that a running compute has
    leased are not touched, and come back to the pool when that compute
    ends."""
    with _CACHE_LOCK:
        _drop_staging_pool()


# a forked child starts with no pair: the pages would be copied on its first
# write, and ``multiprocess.py`` gives the accelerator to one process at a
# time. No lock: the thread that held it may not exist in the child
os.register_at_fork(after_in_child=_drop_staging_pool)


#: the dtypes that ``_to_host`` can fetch as two 32-bit planes. complex128
#: (a pair of float64) and record fields narrower than 8 bytes are fetched
#: as they are
_PLANE_DTYPES = frozenset(map(np.dtype, (np.float64, np.int64, np.uint64)))

#: the dtypes of 64-bit elements, which a device without native float64
#: holds as pairs of 32-bit ones and computes on through split copies
_PAIR_DTYPES = _PLANE_DTYPES | {np.dtype(np.complex128)}

#: the most chunks of a 64-bit array that a device of 32-bit pairs is sent
#: one by one. There each update splits and recombines the whole array: 12.1
#: ms for an 800 MB float64 or uint64 array whatever the chunk (10.5 ms with
#: an 8 MB chunk), 15 ms a GB, against 0.9 to 1.8 ms for a 32-bit one
#: updated in place; the host assembles and puts the whole array in 2.2 s a
#: GB and reads it chunk by chunk in 0.1 to 0.4 s a GB (TPU v5e, PERF.md
#: section 6, PR 29). The two routes meet near 145 chunks; the constant
#: stands where the stream takes under half the whole route's time
_PAIR_STREAM_MAX_CHUNKS = 64

#: bytes at or above which a 64-bit value leaves the device as planes.
#: TPU v5e hands over a 64-bit array at 0.21 GB/s whatever its size, a
#: 32-bit one at 2.6 to 5.6 GB/s; the split costs one more dispatch and
#: one more buffer to fetch. Milliseconds a fetch on the chip, ``np.asarray``
#: of a float64 value against split, fetch and join (medians of 11, of 3 at
#: 200 MB, on two machines; uint64 reads the same; PERF.md section 6, PR 27):
#:
#:   bytes    direct  planes          bytes    direct  planes
#:   8         0.42   1.2 to 1.5      1 MB       3.8   1.6 to 3.3
#:   40 KB     0.60   1.3             2 MB       6.8   1.8 to 5.0
#:   256 KB    1.4    1.3 to 1.5      16 MB     56.7   6.6 to 19.5
#:   512 KB    2.3    1.4 to 1.5      200 MB   960     171
#:
#: The two meet near 256 KB; the constant stands where planes won on both
#: machines
_PLANES_MIN_BYTES = 2**20

#: float32 bit pattern of 2**-73. A float64 below it in magnitude can have
#: a tail (its second float32) that is subnormal, which the device's
#: arithmetic flushes to zero (see ``_split_planes``)
_HEAD_BITS_2_POW_MINUS_73 = (127 - 73) << 23
#: float32 bit pattern of infinity, less its sign
_HEAD_BITS_INF = 0x7F800000

#: the most a split with row-major planes may hold on the device, in bytes
#: of the value: the value, its planes and a relayout's temporary are three,
#: the tile's padding of a well-shaped value a few hundredths more (3.06 for
#: a (10000, 2500) slab, 2.03 for a (5000, 5000) block; 33 for (3125000, 8))
_PLANES_ROW_MAJOR_MAX = 4

#: threads of ``_join_planes`` and the least bytes of result each one
#: takes. The host pays for the result's fresh pages by the page (200 ms
#: for 200 MB on the v5e's host, 33 ms into pages already touched), and
#: that cost divides among threads
_JOIN_THREADS = 4
_JOIN_MIN_BYTES_PER_THREAD = 2**22


def _split_planes(x):
    """A 64-bit device array as two uint32 planes and a flag, on the device.

    int64 / uint64: the low and the high word of each element; the flag is
    False. float64, which such a device holds as a pair of float32 (head,
    tail; value = head + tail): the bit patterns of the pair. The head is
    what the conversion to float32 hands back untouched. The tail has to be
    computed, ``x - head``, which is exact in the pair arithmetic but does
    not give back the tail's bits in four cases, each decided from the
    head's bits (all measured on the v5e: PERF.md section 6, PR 27):

    - a NaN head: the subtraction gives a NaN of its own. The tail is set
      to zero: ``head + tail`` is the head's NaN, sign and payload,
      whatever the tail;
    - an infinite head: a direct fetch reads ``inf + tail``, inf or NaN by
      a tail that the subtraction cannot show (the chip's division leaves
      (inf, NaN) pairs);
    - a head of -0.0: ``head + tail`` is -0.0 or 0.0 by the tail's sign,
      which the subtraction loses;
    - a head below 2**-73 in magnitude: the tail may be subnormal, and the
      subtraction flushes it to zero.

    The flag says that some element is of the last three kinds. They cannot
    be repaired from here (the device offers no bitcast of a float64), so
    the caller fetches such a value directly.

    The planes are integers so that no operation on the way out (a
    concatenate lowers to a ``maximum`` on this chip, which flushes
    subnormals and canonicalises NaNs) can touch their bits."""
    jax = _jax()
    jnp, lax = jax.numpy, jax.lax
    if x.dtype == jnp.float64:
        head = x.astype(jnp.float32)
        tail = (x - head.astype(jnp.float64)).astype(jnp.float32)
        tail = jnp.where(jnp.isfinite(head), tail, jnp.float32(0))
        bits = lax.bitcast_convert_type(head, jnp.uint32)
        magnitude = bits & jnp.uint32(0x7FFFFFFF)
        inexact = jnp.any(
            ((bits != 0) & (magnitude < jnp.uint32(_HEAD_BITS_2_POW_MINUS_73)))
            | (magnitude == jnp.uint32(_HEAD_BITS_INF))
        )
        return bits, lax.bitcast_convert_type(tail, jnp.uint32), inexact
    if x.dtype == jnp.int64:
        x = lax.bitcast_convert_type(x, jnp.uint64)
    low = (x & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    high = (x >> jnp.uint64(32)).astype(jnp.uint32)
    return low, high, jnp.bool_(False)


@functools.lru_cache(maxsize=256)
def _plane_program(shape: tuple, dtype: np.dtype, sharding):
    """``_split_planes`` compiled for values of one (shape, dtype,
    sharding), and the bytes it holds on the device while it runs; made by
    the first fetch that needs it and kept.

    The host receives a value in the order of its device layout, and the
    device's own choice goes by which dimension pads less to its tile: the
    planes of a (10000, 2500) slab come column-major (10000 pads 1.1% to a
    multiple of 128, 2500 pads 2.4%), those of a (5000, 5000) block
    row-major. So both planes are pinned to a row-major device layout: the
    device lays 2 x 100 MB out again in under a millisecond, and the join
    that takes 97 ms for row-major planes takes 138 ms reading them strided
    (and took 530 ms through a transposing copy; TPU v5e, PERF.md section 6,
    PR 33). The layout keeps shape and sharding, so under a mesh the planes
    stay where the value is. It is the integer planes that are laid out
    again, after the split: a relayout of the float64 could change its bits
    (``_split_planes``).

    Row-major planes whose rows are short pad each row to 128 elements, in
    HBM and on the way out: those of a (3125000, 8) value hold 6.6 GB for
    200 MB and fetch in 238 ms against 72. Where the pinned program holds
    more than ``_PLANES_ROW_MAJOR_MAX`` times the value's bytes the device
    keeps its own layout, and ``_join_planes`` reads what comes strided.

    The bytes are XLA's own accounting (``_hbm_footprint``: argument,
    planes, temporaries) of one device, times the devices the value lies
    on, and never under the value and its two planes. A relayout adds a
    temporary the size of the planes (612 MB against 405 MB for a 200 MB
    slab): ``_leaves_as_planes`` reads the room from here."""
    jax = _jax()
    from jax.experimental.layout import Format, Layout

    value = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    nbytes = math.prod(shape) * dtype.itemsize
    row_major = Format(Layout(major_to_minor=tuple(range(len(shape)))), sharding)
    for planes in (row_major, None):
        split = jax.jit(_split_planes, out_shardings=(planes, planes, None))
        compiled = split.lower(value).compile()
        held = _hbm_footprint(compiled) * len(sharding.device_set)
        if held <= _PLANES_ROW_MAJOR_MAX * nbytes:
            break
    return compiled, max(held, 2 * nbytes)


def _plane_program_of(value):
    return _plane_program(tuple(value.shape), np.dtype(value.dtype), value.sharding)


@functools.cache
def _chunk_writer():
    """The device half of a streamed preload, jitted: ``piece`` (a padded
    chunk, cut to its static ``extent`` inside the array) written into
    ``whole`` at ``start``, in place, since ``whole`` is donated. One
    program a (shape, chunk shape, extent, dtype), whatever the position.

    A ``dynamic_update_slice`` and not a concatenate, which lowers to a
    ``maximum`` on the v5e and then flushes subnormals and canonicalises
    NaNs (``_split_planes``). The second result is one element of the
    updated array: ``whole`` itself is gone with the next update, so the
    host waits on this one to know that ``piece`` has been consumed."""
    jax = _jax()
    lax = jax.lax

    def write(whole, piece, start, extent):
        at = tuple(start[d] for d in range(whole.ndim))
        piece = lax.slice(piece, (0,) * whole.ndim, extent)
        updated = lax.dynamic_update_slice(whole, piece, at)
        return updated, lax.dynamic_slice(updated, at, (1,) * whole.ndim)

    return jax.jit(write, static_argnums=3, donate_argnums=0)


def _join_planes(
    first: np.ndarray, second: np.ndarray, dtype: np.dtype,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """The host array of ``dtype`` that ``_split_planes``'s two planes stand
    for, in one pass into ``out``, a C-contiguous array of the planes' shape
    and of ``dtype`` that the caller keeps (a fresh one where it gives
    none: 200 MB of pages never touched cost 200 ms to fill, touched ones
    33).

    float64: ``float64(head) + float64(tail)``, one correctly rounded add of
    two exactly represented numbers, which is what the runtime computes
    when it fetches the pair itself. Integers: the words written side by
    side. Large results are filled by a few threads, a slab each.

    The planes are read where they lie. Row-major ones (what
    ``_plane_program`` asks the device for) are cut as flat views; any
    other order (``_planes_strided``) is cut along the longest axis and
    read strided by every thread: a ``reshape(-1)`` of such a plane would
    be a transposing copy on one thread, 230 ms for 100 MB (PERF.md
    section 6, PR 32)."""
    if out is None:
        out = np.empty(first.shape, dtype)
    if _planes_strided(first, second):
        flat, a, b, axis = out, first, second, int(np.argmax(first.shape))
    else:
        flat, a, b, axis = out.reshape(-1), first.reshape(-1), second.reshape(-1), 0
    span = (slice(None),) * axis
    if dtype == np.float64:
        a, b = a.view(np.float32), b.view(np.float32)

        def fill(lo: int, hi: int) -> None:
            at = span + (slice(lo, hi),)
            # a signalling NaN head is quieted, as it is by a direct fetch
            with np.errstate(invalid="ignore"):
                np.add(a[at], b[at], out=flat[at], dtype=np.float64)
    else:
        words = flat.view(np.uint32).reshape(flat.shape + (2,))
        low, high = (0, 1) if sys.byteorder == "little" else (1, 0)

        def fill(lo: int, hi: int) -> None:
            at = span + (slice(lo, hi),)
            words[at + (..., low)] = a[at]
            words[at + (..., high)] = b[at]

    n = flat.shape[axis]
    threads = min(_JOIN_THREADS, out.nbytes // _JOIN_MIN_BYTES_PER_THREAD)
    if threads < 2:
        fill(0, n)
        return out
    cuts = [n * i // threads for i in range(threads + 1)]
    with ThreadPoolExecutor(threads) as pool:
        # list(): a slab that raised raises here
        list(pool.map(fill, cuts[:-1], cuts[1:]))
    return out


def _planes_strided(first: np.ndarray, second: np.ndarray) -> bool:
    """Whether a plane reached the host in another order than row-major, so
    that ``_join_planes`` reads it strided."""
    return not (first.flags.c_contiguous and second.flags.c_contiguous)


#: (platform, device_kind) -> whether float64 survives a round trip
_FLOAT64_ROUND_TRIPS: Dict[tuple, bool] = {}


def _float64_round_trips(device) -> bool:
    """Whether a float64 comes back from ``device`` bit for bit.

    Observed, not assumed from the platform's name: a few values that need
    all 53 significand bits or float64's exponent range go to the device
    and back once per kind of device, as float64 whatever the calling
    thread's x64 setting (a compute under ``compute_dtype="float32"`` would
    else put them as float32 and leave that answer for every later one)."""
    key = (device.platform, device.device_kind)
    known = _FLOAT64_ROUND_TRIPS.get(key)
    if known is None:
        jax = _jax()
        probe = np.array([np.pi, 1.0 + 2.0**-52, 1e300, 1e-300])
        with jax.enable_x64(True):
            back = np.asarray(jax.device_put(probe, device))
        known = back.tobytes() == probe.tobytes()
        _FLOAT64_ROUND_TRIPS[key] = known
    return known


def _hbm_footprint(compiled) -> int:
    """XLA's own accounting of a program's device footprint (args + outputs
    + temps); 0 when the backend offers no analysis. Computed once per
    compile — it never changes for a given executable. Of a program
    partitioned over a mesh this is what ONE device holds (its shards and
    its temporaries), so ``stats["segment_hbm_footprint"]`` is per chip and
    compares with one device's ``bytes_limit`` with or without a mesh."""
    try:
        ma = compiled.memory_analysis()
        return (
            int(getattr(ma, "argument_size_in_bytes", 0))
            + int(getattr(ma, "output_size_in_bytes", 0))
            + int(getattr(ma, "temp_size_in_bytes", 0))
        )
    except Exception:
        return 0

_F32_FILTER_ENTRY = None


def _install_f32_truncation_filter() -> None:
    """Silence jax's per-request "requested dtype float64 is not
    available" warning with a process-global filter.

    Prepending a filter is effectively atomic under the GIL and is never
    restored by us, so concurrent executor threads can't observe
    half-saved filter state (unlike ``warnings.catch_warnings``, which
    save/restores the GLOBAL filter list and races other threads).
    Presence is re-checked against ``warnings.filters`` on every DAG —
    not a trust-me flag — because an enclosing ``catch_warnings`` scope
    (e.g. pytest's warnings plugin around each test) discards the entry
    on exit.

    Caveat, stated rather than hidden: while installed, the filter also
    suppresses this warning for any OTHER code in the process that runs
    with x64 canonicalization off (its own ``jax.enable_x64(False)``
    scope). That is the documented cost of ``compute_dtype="float32"``:
    it mutates global warnings state instead of save/restoring it
    thread-unsafely."""
    global _F32_FILTER_ENTRY
    import warnings

    if _F32_FILTER_ENTRY is not None and _F32_FILTER_ENTRY in warnings.filters:
        return
    warnings.filterwarnings(
        "ignore", message=".*requested dtype.*is not available.*"
    )
    _F32_FILTER_ENTRY = warnings.filters[0]


_PYTREES_REGISTERED = False


def _register_pred_pytrees() -> None:
    """Register fusion marker types as jax pytrees so vmap maps through them."""
    global _PYTREES_REGISTERED
    if _PYTREES_REGISTERED:
        return
    import jax

    from ...primitive.blockwise import PredArgs

    try:
        jax.tree_util.register_pytree_node(
            PredArgs,
            lambda x: (list(x), None),
            lambda _, children: PredArgs(children),
        )
    except ValueError:
        pass  # already registered
    _PYTREES_REGISTERED = True


def _flatten_keys(structure):
    """Flatten a block-function result into (treedef, leaf keys).

    Treedef is a comparable nested template: 'leaf' for a chunk key,
    ('pred', ...) for fused-predecessor groups, ('list', ...) for contraction
    lists, ('args', ...) at the top. Returns (None, None) on iterators
    (streamed reads are not batchable)."""
    from ...primitive.blockwise import PredKeys, _is_key

    leaves: list = []

    def walk(node):
        if isinstance(node, PredKeys):
            return ("pred", tuple(walk(c) for c in node))
        if _is_key(node):
            leaves.append(node)
            return "leaf"
        if isinstance(node, (list, tuple)):
            return ("list", tuple(walk(c) for c in node))
        return None  # Iterator / unknown

    out = []
    for entry in structure:
        t = walk(entry)
        if t is None or _contains_none(t):
            return None, None
        out.append(t)
    return ("args", tuple(out)), leaves


def _contains_none(t) -> bool:
    if t is None:
        return True
    if isinstance(t, tuple) and len(t) == 2 and t[0] in ("pred", "list"):
        return any(_contains_none(c) for c in t[1])
    return False


def _unflatten_keys(treedef, flat: list):
    """Rebuild the argument structure with chunks in place of keys.

    PredKeys groups become PredArgs (the resolved-chunk marker the fused
    kernel expects); contraction groups become plain lists."""
    from ...primitive.blockwise import PredArgs

    it = iter(flat)

    def build(t):
        if t == "leaf":
            return next(it)
        kind, children = t
        if kind == "pred":
            return PredArgs([build(c) for c in children])
        return [build(c) for c in children]

    kind, entries = treedef
    assert kind == "args"
    return tuple(build(e) for e in entries)


def _gather_subgrid(value, chunkset, coords, keep_grid=False):
    """Gather a bucket's blocks as ONE region slice + reshape.

    A shape-bucket over a ragged grid is a rectangular subgrid whose per-dim
    chunk size is uniform; when its per-dim indices are consecutive the whole
    bucket is a contiguous region — one slice, then an interleave reshape to
    (T, *chunk). Returns None when the coords don't form such a product
    (caller falls back to per-task slices). This keeps the traced program's
    memory traffic at one read of the region instead of one windowed read per
    task, which XLA otherwise fails to fuse (~50x bytes-accessed blowup).
    ``keep_grid`` leaves the blocks' grid dims apart, (*grid, *chunk): the
    form a sharded region keeps its sharding in (see ``_dense_subgrid``)."""
    import jax.numpy as jnp

    ndim = len(chunkset)
    per_dim = []
    for d in range(ndim):
        idxs = sorted({c[d] for c in coords})
        if idxs != list(range(idxs[0], idxs[-1] + 1)):
            return None
        sizes = {chunkset[d][i] for i in idxs}
        if len(sizes) != 1:
            return None
        per_dim.append(idxs)
    if len(coords) != math.prod(len(p) for p in per_dim):
        return None
    if sorted(coords) != coords:
        return None  # caller must supply C-ordered tasks
    sel = tuple(
        slice(
            sum(chunkset[d][: per_dim[d][0]]),
            sum(chunkset[d][: per_dim[d][-1] + 1]),
        )
        for d in range(ndim)
    )
    nb = tuple(len(p) for p in per_dim)
    chunk_shape = tuple(chunkset[d][per_dim[d][0]] for d in range(ndim))

    def one(v):
        region = v[sel]
        inter = []
        for n, c in zip(nb, chunk_shape):
            inter.extend([n, c])
        r = region.reshape(tuple(inter))
        perm = list(range(0, 2 * ndim, 2)) + list(range(1, 2 * ndim, 2))
        blocks = r.transpose(perm)
        return blocks if keep_grid else blocks.reshape((-1,) + chunk_shape)

    if isinstance(value, dict):
        return {k: one(v) for k, v in value.items()}
    return one(value)


def _dense_subgrid(coords):
    """``(lows, extents)`` when ``coords``, in the order given, are the
    C-order product of one run of consecutive block indices per dim; None
    otherwise.

    Under a mesh the batched route keeps such a bucket's tasks as the dims of
    their grid, (*extents, *chunk), instead of one stacked dim: merging the
    grid dims into one puts the sharded dim anywhere but first, a layout no
    sharding of the stacked dim describes, and the partitioner then moves
    every chunk between chips (3,240 all-to-all in the vorticity program on
    four chips, PERF.md section 6, PR 28). With the dims apart the chip that
    holds a slab of the array holds the same slab of the grid."""
    ndim = len(coords[0])
    lows = tuple(min(c[d] for c in coords) for d in range(ndim))
    extents = tuple(max(c[d] for c in coords) - lows[d] + 1 for d in range(ndim))
    if len(coords) != math.prod(extents):
        return None
    dense = itertools.product(*(range(lo, lo + n) for lo, n in zip(lows, extents)))
    if any(tuple(c) != d for c, d in zip(coords, dense)):
        return None
    return lows, extents


def _merge_grid(value, ndim: int):
    """(*grid, *chunk) -> the region the blocks tile, (grid[d] * chunk[d])."""
    perm = [axis for d in range(ndim) for axis in (d, ndim + d)]
    shape = tuple(value.shape[d] * value.shape[ndim + d] for d in range(ndim))
    return value.transpose(perm).reshape(shape)


def _gather_blocks(value, nb, chunk_shape, idx):
    """(full array, grid, chunk shape, task->block index) -> (T, *chunk)."""
    import jax.numpy as jnp

    def one(v):
        inter = []
        for n, c in zip(nb, chunk_shape):
            inter.extend([n, c])
        r = v.reshape(tuple(inter))
        perm = list(range(0, 2 * len(nb), 2)) + list(range(1, 2 * len(nb), 2))
        blocks = r.transpose(perm).reshape((-1,) + tuple(chunk_shape))
        return blocks[idx]

    if isinstance(value, dict):
        return {k: one(v) for k, v in value.items()}
    return one(value)


class _JitCache:
    """A chunk kernel, jitted on first use unless it is host-bound."""

    def __init__(self, function, jit: bool = True):
        self.function = function
        self._jitted = None
        # host-bound kernels (block_id sync, closed-over host data) can't jit
        self._use_eager = (
            not jit
            or getattr(function, "host_block_id", False)
            or bool(getattr(function, "host_data_nbytes", 0))
        )

    def __call__(self, *args):
        # iterators / nested lists can't be jitted as-is; run eagerly
        if self._use_eager or any(
            isinstance(a, Iterator) or isinstance(a, list) for a in args
        ):
            return self.function(*args)
        if self._jitted is None:
            self._jitted = _jax().jit(self.function)
        return self._jitted(*args)


def _assemble(chunk_grid: Dict[tuple, Any], nb: tuple[int, ...]):
    """Assemble a grid of device chunks into one array by axis-wise concat."""
    jax = _jax()
    jnp = jax.numpy

    def concat(vals, axis):
        if isinstance(vals[0], dict):
            return {k: concat([v[k] for v in vals], axis) for k in vals[0]}
        if len(vals) == 1:
            return vals[0]
        return jnp.concatenate(vals, axis=axis)

    def build(prefix: tuple, axis: int):
        if axis == len(nb):
            return chunk_grid[prefix]
        vals = [build(prefix + (i,), axis + 1) for i in range(nb[axis])]
        return concat(vals, axis)

    return build((), 0)
