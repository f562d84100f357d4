#!/usr/bin/env python3
"""The quickest proof that cubed_tpu still starts on the chip.

    python3 chip_smoke.py [--seed N]

Drives the system's main path — Zarr -> HBM -> Zarr — once, through the
entry points a user calls (``ct.Spec``, ``cubed_tpu.random.random``,
``ct.from_zarr``, ``cubed_tpu.array_api``, ``.compute(executor=
JaxExecutor())``, ``ct.to_zarr``, ``.rechunk``), in f64 as upstream runs
it, at sizes the upstream project itself calls real:

- ``vorticity`` — the repo's headline, BASELINE.json config 5: four random
  (500, 450, 400) f64 arrays, chunks 100, ``allowed_mem="4GB"``,
  ``mean(a[1:]*x[1:] + b[1:]*y[1:])``; 2.9 GB generated on the device.
- ``zarr_add`` — upstream's canonical add pipeline at the size of its own
  memory test: two 10000x10000 f64 Zarr sources in (5000, 5000) chunks
  (200 MB), ``allowed_mem="2GB"``, and three computes over them:
  ``to_zarr(add(a, b))``, ``mean(add(a, b), axis=0)`` and
  ``to_zarr(a.rechunk((10000, 2500)))``.

It is one process that owns the chip from start to finish and starts no
other. It refuses to run unless ``jax.devices()[0].platform == "tpu"``
(there is no CPU mode: tests rehearse the legs by calling the functions
below with small sizes), fails if any compute left the device path
(``FAILURE_COUNTERS``), and prints as its last line
``{"ok": true, "device": {...}}``. With more than one device visible it
also runs the mesh legs (``JaxExecutor(mesh=make_mesh())``: vorticity, and
``zarr_add``'s two ``to_zarr`` computes) and checks that every chip held its
share. It ends with the raw device facts the timings are read against
(``device_facts``: float64 round trip, transfer rates, the executor's
plane route out, dispatch, the executor's streamed preload against the
whole-array put, a column slab's way out as planes against a block's).

The reference for ``zarr_add`` is numpy on the host arrays the sources
were written from, and outputs are read back from the store's files with
numpy (``.zarray`` + raw chunk files), not through cubed_tpu: with the
default jax backend even ``PythonDagExecutor`` runs its chunk kernels on
jax's default device — the chip — so it is no independent reference here.

Timings printed here are smoke timings: one cold run (with compile) and one
warm repeat per compute, on a shared host. They are not benchmark numbers.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import random
import shutil
import statistics
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass
from importlib import metadata
from typing import Callable, Optional, Sequence

import numpy as np

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.random
from cubed_tpu.parallel.mesh import make_mesh
from cubed_tpu.runtime.executors.jax import JaxExecutor

#: a non-zero count means some op left the fused device path by one of the
#: executor's designed aborts (a segment ran eagerly, a kernel that needs
#: concrete values ran un-jitted, a segment was refused for memory): the run
#: fails. Anything else that goes wrong on that path raises
FAILURE_COUNTERS = (
    "eager_fallbacks",
    "trace_failures",
    "segment_mem_aborts",
)

#: which execution path each op took (JaxExecutor.stats)
PATH_COUNTERS = (
    "segments_traced",
    "segments_compiled",
    "segment_hbm_footprint",
    "whole_array_hits",
    "batched_ops",
    "chunked_ops",
    "eager_ops",
    "rechunk_alias",
    "f64_as_bits",
    "f64_lossy_moves",
    "d2h_bytes",
    "d2h_plane_bytes",
    "d2h_plane_strided_bytes",
    "h2d_bytes",
    "h2d_stream_bytes",
)

#: how the chunks of a stored array moved under a mesh (JaxExecutor.stats)
MESH_IO_COUNTERS = (
    "mesh_owner_bytes",
    "mesh_gathered_bytes",
    "h2d_bytes",
    "h2d_stream_bytes",
    "h2d_stream_declined",
    "d2h_bytes",
)

#: the sizes the upstream project itself calls real (see the module docstring)
VORTICITY = dict(shape=(500, 450, 400), chunks=100, allowed_mem="4GB")
ZARR_ADD = dict(n=10000, chunk=5000, allowed_mem="2GB")
#: the transfer probe moves what ``_flush`` moves: one chunk of ``zarr_add``
PROBE = dict(n=ZARR_ADD["chunk"], readings=5, calls=50)

#: vorticity passes within this many standard errors of 0.5. The mean of
#: u1*u2 + u3*u4 over n uniform samples has variance (7/72)/n; at the full
#: size (n = 499*450*400) fifteen standard errors are 4.9e-4, and the numpy
#: record for this configuration is 0.5000219.
VORTICITY_STDERRS = 15.0

#: f64 add has one correctly rounded answer, so the tolerance only leaves
#: room for a device whose float64 is not IEEE: v5e holds one as a pair of
#: float32 (~49 significand bits), so each input loses up to 2**-49 on the
#: way in and the add rounds again (measured there: 86% of results differ
#: bitwise, worst 2**-46.8). 2**-44 keeps 44 of the 53 bits — far past f32
#: (2**-24), so a silent downcast fails. The count of results that differ
#: bitwise is printed as a finding.
ADD_RTOL = 2.0**-44
#: the mean over n rows of positive terms inherits the add's bound, and
#: sums in another order than numpy's pairwise sum: either order is within
#: n * 2**-53 relative
MEAN_RTOL_PER_ROW = 2.0**-53


# ---------------------------------------------------------------------------
# Zarr v2 by hand: numpy only, independent of the code under test
# ---------------------------------------------------------------------------


def _chunk_slices(shape: Sequence[int], chunks: Sequence[int]):
    """(chunk index, slices of the array it covers) for every chunk."""
    grid = [range(math.ceil(s / c)) for s, c in zip(shape, chunks)]
    for idx in itertools.product(*grid):
        yield idx, tuple(
            slice(i * c, min((i + 1) * c, s))
            for i, c, s in zip(idx, chunks, shape)
        )


def write_zarr_v2(path: str, arr: np.ndarray, chunks: Sequence[int]) -> None:
    """Write ``arr`` as an uncompressed C-order Zarr v2 directory store."""
    os.makedirs(path)
    meta = {
        "zarr_format": 2,
        "shape": list(arr.shape),
        "chunks": list(chunks),
        "dtype": arr.dtype.str,
        "compressor": None,
        "fill_value": 0.0,
        "order": "C",
        "filters": None,
        "dimension_separator": ".",
    }
    with open(os.path.join(path, ".zarray"), "w") as f:
        json.dump(meta, f)
    for idx, sel in _chunk_slices(arr.shape, chunks):
        part = arr[sel]
        if part.shape != tuple(chunks):  # edge chunks are stored padded
            block = np.zeros(chunks, dtype=arr.dtype)
            block[tuple(slice(0, n) for n in part.shape)] = part
            part = block
        np.ascontiguousarray(part).tofile(
            os.path.join(path, ".".join(map(str, idx)))
        )


def read_zarr_v2(path: str) -> np.ndarray:
    """Read an uncompressed C-order Zarr v2 directory store with numpy."""
    with open(os.path.join(path, ".zarray")) as f:
        meta = json.load(f)
    if meta["compressor"] is not None or meta["filters"] or meta["order"] != "C":
        raise ValueError(f"{path}: not a raw C-order store: {meta}")
    shape, chunks = tuple(meta["shape"]), tuple(meta["chunks"])
    dtype = np.dtype(meta["dtype"])
    sep = meta.get("dimension_separator", ".")
    out = np.empty(shape, dtype=dtype)
    for idx, sel in _chunk_slices(shape, chunks):
        block = np.fromfile(
            os.path.join(path, sep.join(map(str, idx))), dtype=dtype
        ).reshape(chunks)
        out[sel] = block[tuple(slice(0, s.stop - s.start) for s in sel)]
    return out


# ---------------------------------------------------------------------------
# measuring one compute
# ---------------------------------------------------------------------------


class CompileLog:
    """Counts XLA compilations through ``jax.monitoring`` listeners.

    Every backend compile request fires one duration event carrying the
    jitted function's name — also when the persistent cache serves it, in
    which case a ``cache_hits`` event fires too."""

    def __init__(self):
        import jax.monitoring

        self.programs: Counter = Counter()
        self.events: Counter = Counter()
        self.compile_seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs[kwargs.get("fun_name", "?")] += 1
            self.compile_seconds += seconds

    def _event(self, event: str, **kwargs) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def mark(self):
        return (Counter(self.programs), Counter(self.events), self.compile_seconds)

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


@dataclass
class Run:
    """One compute, measured: wall time to a host-readable result, the
    executor's own counters, and what compiled while it ran."""

    seconds: float
    stats: dict
    programs: Counter
    compile_seconds: float
    cache_hits: int
    cache_misses: int

    def line(self) -> str:
        return (
            f"{self.seconds:.3f} s, {sum(self.programs.values())} programs "
            f"compiled in {self.compile_seconds:.3f} s (persistent cache: "
            f"{self.cache_hits} hits, {self.cache_misses} misses)"
        )


class _StatsCapture:
    stats: Optional[dict] = None

    def on_compute_end(self, event) -> None:
        self.stats = event.executor_stats


def measured(compute: Callable[[list], object], log: CompileLog):
    """Run ``compute(callbacks)``, which must end with its result readable
    on the host, and fail if any op left the device path."""
    cap = _StatsCapture()
    programs, events, compile_seconds = log.mark()
    t0 = time.perf_counter()
    result = compute([cap])
    seconds = time.perf_counter() - t0
    if cap.stats is None:
        raise RuntimeError("compute ended without executor_stats")
    failed = {k: cap.stats[k] for k in FAILURE_COUNTERS if cap.stats.get(k)}
    if failed:
        raise RuntimeError(f"ops left the device path: {failed}")
    return result, Run(
        seconds=seconds,
        stats=cap.stats,
        programs=log.programs - programs,
        compile_seconds=log.compile_seconds - compile_seconds,
        cache_hits=log.events["cache_hits"] - events["cache_hits"],
        cache_misses=log.events["cache_misses"] - events["cache_misses"],
    )


def _say(text: str) -> None:
    print(f"  {text}", flush=True)


def _say_runs(what: str, cold: Run, warm: Run) -> None:
    _say(f"{what}: cold {cold.line()} [smoke timing, with compile]")
    repeated = {n: c for n, c in cold.programs.items() if c > 1}
    if repeated:
        _say(f"{what}: compiled more than once: {repeated}")
    _say(f"{what}: warm {warm.line()} [smoke timing, ends in a host fetch]")
    _say(
        f"{what}: path "
        + " ".join(f"{k}={cold.stats.get(k, 0)}" for k in PATH_COUNTERS)
    )
    _say(
        f"{what}: failures "
        + " ".join(f"{k}={cold.stats.get(k, 0)}" for k in FAILURE_COUNTERS)
    )


def compare(what: str, got: np.ndarray, ref: np.ndarray, rtol: float) -> None:
    """Compare with the numpy reference; print how far from bitwise."""
    if got.shape != ref.shape or got.dtype != ref.dtype:
        raise AssertionError(
            f"{what}: got {got.dtype}{got.shape}, expected {ref.dtype}{ref.shape}"
        )
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite values")
    mismatched = int(np.count_nonzero(got != ref))
    rel = 0.0
    if mismatched:
        scale = np.maximum(np.abs(ref), np.finfo(ref.dtype).tiny)
        rel = float(np.max(np.abs(got - ref) / scale))
    _say(
        f"{what}: {mismatched} of {got.size} values differ bitwise from "
        f"numpy, max relative error {rel:.3e} (tolerance {rtol:.3e})"
    )
    if rel > rtol:
        raise AssertionError(f"{what}: max relative error {rel} > {rtol}")


# ---------------------------------------------------------------------------
# the legs
# ---------------------------------------------------------------------------


def vorticity_leg(
    shape: Sequence[int],
    chunks: int,
    allowed_mem: str,
    *,
    seed: int,
    work_dir: str,
    make_executor: Callable[[], JaxExecutor],
    log: CompileLog,
    name: str = "vorticity",
) -> dict:
    """Run the leg cold then warm; returns {compute: (cold Run, warm Run)}."""
    print(f"== {name}: 4 x random{tuple(shape)} f64, chunks={chunks}, "
          f"allowed_mem={allowed_mem}", flush=True)
    spec = ct.Spec(work_dir=work_dir, allowed_mem=allowed_mem)

    def build():
        # cubed_tpu.random draws each array's root seed from Python's random
        random.seed(seed)
        a, b, x, y = (
            cubed_tpu.random.random(tuple(shape), chunks=chunks, spec=spec)
            for _ in range(4)
        )
        return xp.mean(
            xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:]))
        )

    def compute(callbacks):
        return float(build().compute(executor=make_executor(), callbacks=callbacks))

    value, cold = measured(compute, log)
    warm_value, warm = measured(compute, log)
    _say_runs("mean", cold, warm)

    n = (shape[0] - 1) * math.prod(shape[1:])
    tol = VORTICITY_STDERRS * math.sqrt(7.0 / 72.0 / n)
    _say(
        f"value {value!r}, |value - 0.5| = {abs(value - 0.5):.3e} "
        f"(tolerance {tol:.3e} = {VORTICITY_STDERRS:g} standard errors at "
        f"{n} samples); warm repeat identical: {warm_value == value}"
    )
    if not abs(value - 0.5) < tol:
        raise AssertionError(f"{name}: {value} is not within {tol} of 0.5")
    if warm_value != value:
        raise AssertionError(f"{name}: same seed gave {value} then {warm_value}")
    return {"mean": (cold, warm)}


def check_owner_io(runs: dict) -> None:
    """Of a ``zarr_add_leg`` under a mesh whose shards are blocks of whole
    chunks: every compute streamed every source to the chips that own its
    chunks (``h2d_stream_bytes`` all of ``h2d_bytes``, ``mesh_owner_bytes``
    above 0), and what touched more than one chip
    (``mesh_gathered_bytes``) is at most what came back: nothing for the
    add, whose target's chunks each lie on one chip. (The mean's row of two
    chunks lies over four chips, so each of its chunks crosses two shards
    and is sliced by a program of them all.)"""
    for what, pair in runs.items():
        for temp, run in zip(("cold", "warm"), pair):
            moved = {k: run.stats.get(k, 0) for k in MESH_IO_COUNTERS}
            _say(f"{what} ({temp}): mesh " + " ".join(f"{k}={v}" for k, v in moved.items()))
            crossed = 0 if what == "add" else moved["d2h_bytes"]
            if (
                moved["mesh_gathered_bytes"] > crossed
                or not moved["mesh_owner_bytes"]
                or moved["h2d_stream_bytes"] != moved["h2d_bytes"]
            ):
                raise RuntimeError(
                    f"{what} ({temp}): a chunk did not move between the host "
                    f"and its owner: {moved}"
                )


def zarr_add_leg(
    n: int,
    chunk: int,
    allowed_mem: str,
    *,
    seed: int,
    work_dir: str,
    make_executor: Callable[[], JaxExecutor],
    log: CompileLog,
    computes: Sequence[str] = ("add", "mean", "rechunk"),
    name: str = "zarr_add",
) -> dict:
    """Run the leg's computes cold then warm, each checked against numpy;
    returns {compute: (cold Run, warm Run)}."""
    print(f"== {name}: 2 x Zarr({n}, {n}) f64, chunks=({chunk}, {chunk}), "
          f"allowed_mem={allowed_mem}, computes={list(computes)}", flush=True)
    os.makedirs(work_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    a_np, b_np = rng.random((n, n)), rng.random((n, n))
    a_path, b_path = (os.path.join(work_dir, f"{k}.zarr") for k in "ab")
    write_zarr_v2(a_path, a_np, (chunk, chunk))
    write_zarr_v2(b_path, b_np, (chunk, chunk))

    spec = ct.Spec(work_dir=work_dir, allowed_mem=allowed_mem)
    runs: dict = {}

    def sources():
        return ct.from_zarr(a_path, spec=spec), ct.from_zarr(b_path, spec=spec)

    def stored(what: str, build, ref: np.ndarray, rtol: float) -> None:
        """``to_zarr(build())`` cold then warm, each read back with numpy."""
        pair = []
        for temp in ("cold", "warm"):
            out = os.path.join(work_dir, f"{what}-{temp}.zarr")
            _, run = measured(
                lambda cbs: ct.to_zarr(
                    build(), out, executor=make_executor(), callbacks=cbs
                ),
                log,
            )
            pair.append(run)
            compare(f"{what} ({temp})", read_zarr_v2(out), ref, rtol)
            shutil.rmtree(out)
        _say_runs(what, *pair)
        runs[what] = tuple(pair)

    if "add" in computes:
        stored("add", lambda: xp.add(*sources()), a_np + b_np, ADD_RTOL)

    if "mean" in computes:
        def mean(callbacks):
            return xp.mean(xp.add(*sources()), axis=0).compute(
                executor=make_executor(), callbacks=callbacks
            )

        got, cold = measured(mean, log)
        _, warm = measured(mean, log)
        compare(
            "mean", np.asarray(got), (a_np + b_np).mean(axis=0),
            ADD_RTOL + n * MEAN_RTOL_PER_ROW,
        )
        _say_runs("mean", cold, warm)
        runs["mean"] = (cold, warm)

    if "rechunk" in computes:
        # pure movement Zarr -> HBM -> Zarr: must come back bit for bit
        # (JaxExecutor carries the float64 as bit patterns where the
        # device's own float64 would change them)
        stored(
            "rechunk", lambda: sources()[0].rechunk((n, chunk // 2)), a_np, 0.0
        )
    return runs


# ---------------------------------------------------------------------------
# raw device facts
# ---------------------------------------------------------------------------


#: float64 values that a transfer is most likely to change: NaNs with a
#: payload and a sign, zeros of both signs, infinities, the least and a larger
#: subnormal, the extremes of float64's range and of float32's
EDGE_VALUES = np.array(
    [0x7FF8000000000123, 0xFFF0000000000ABC, 0x8000000000000000, 0,
     0x7FF0000000000000, 0xFFF0000000000000, 1, 0x000FFFFFFFFFFFFF,
     0x7FEFFFFFFFFFFFFF, 0x0010000000000000, 0x47EFFFFFE0000000,
     0x3690000000000000],
    dtype=np.uint64,
).view(np.float64)


def preload_stream_reading(n: int, *, seed: int) -> dict:
    """A float64 Zarr array of (n, n) chunks, two whole and one ragged, with
    ``EDGE_VALUES`` in every chunk, through ``JaxExecutor._preload`` (which
    streams it chunk by chunk through the staging buffers) and through the
    whole-array put of the same values, as numbers and as bit patterns.
    Raises if the two device values, fetched, differ in any bit. The
    seconds are smoke timings of one preload each."""
    import jax

    from cubed_tpu.storage.store import open_zarr_array

    host = np.random.default_rng(seed).random((2 * n + n // 4, n))
    for row in range(0, host.shape[0], max(1, n // 4)):
        host[row, : EDGE_VALUES.size] = EDGE_VALUES
        host[row, -EDGE_VALUES.size :] = EDGE_VALUES[::-1]
    out = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke-stream-") as root:
        stored = open_zarr_array(
            os.path.join(root, "source.zarr"), "w", shape=host.shape,
            dtype=host.dtype, chunks=(n, n),
        )
        stored[...] = host
        for form, carry_bits in (("float64", False), ("uint64", True)):
            executor = JaxExecutor()
            executor._carry_bits = carry_bits
            resident: dict = {}
            t0 = time.perf_counter()
            # the staging pair as a compute holds it; the second form finds
            # the buffers the first one made
            with executor._lease() as staging:
                if not executor._preload(stored, resident, executor._budget()):
                    raise AssertionError("the stored array was not preloaded")
            (res,) = resident.values()
            jax.block_until_ready(res.value)
            t1 = time.perf_counter()
            whole = executor._device_put(host, host.shape)
            jax.block_until_ready(whole)
            t2 = time.perf_counter()
            stats = executor.stats
            if stats["h2d_stream_bytes"] != 3 * n * n * 8:
                raise AssertionError(f"the preload did not stream: {dict(stats)}")
            streamed, put = np.asarray(res.value), np.asarray(whole)
            if streamed.dtype != put.dtype or streamed.dtype.name != form:
                raise AssertionError(f"{form}: {streamed.dtype} and {put.dtype} on the device")
            differ = int(np.count_nonzero(
                streamed.view(np.uint64) != put.view(np.uint64)
            ))
            if differ:
                raise AssertionError(
                    f"{form}: the streamed preload differs from the whole-array "
                    f"put in {differ} of {host.size} values"
                )
            if carry_bits and streamed.tobytes() != host.tobytes():
                raise AssertionError("bit patterns changed on the way to the device")
            out[form] = (t1 - t0, t2 - t1)
            _say(
                f"{form} {host.nbytes / 1e9:.2f} GB in {stored.nchunks} chunks of "
                f"{n * n * 8 / 1e6:.0f} MB, edge values in each: streamed preload "
                f"{t1 - t0:.3f} s (h2d_stream_bytes={stats['h2d_stream_bytes']} of "
                f"h2d_bytes={stats['h2d_bytes']}, stage_reused_bytes="
                f"{stats['stage_reused_bytes']}, staging buffers "
                f"{[b.buffer.nbytes for b in staging]}), whole-array put of "
                f"the host array {t2 - t1:.3f} s; 0 of {host.size} values differ"
            )
            del res, resident, whole, streamed, put
    return out


def fetch_layout_reading(device, n: int, readings: int, *, seed: int) -> dict:
    """What a chunk's shape does to its way out as planes: from a resident
    (n, n) array, as uint64 and as float64, a column slab (n, n/4) and a
    block (n/2, n/2) of the same bytes, each cut on the device, split by the
    executor's own program (``_plane_program_of``), fetched and joined. It
    prints the order in which the planes reached the host (strides,
    contiguity: a plane that is not C-contiguous is read strided by the
    join, and ``stats["d2h_plane_strided_bytes"]`` counts it in a compute),
    the device layout the compiled program gives them (major to minor),
    what the program holds on the device, and the four steps in
    milliseconds, medians of ``readings``: smoke timings on a shared host,
    not benchmark numbers. On the v5e the device's own layout for the slab's
    planes is column-major, which cost the join 530 ms against the block's
    97 (PERF.md section 6, PRs 32 and 33). Raises if a joined value differs
    from the direct fetch in any bit (float64 only on a device that holds
    one as a float32 pair)."""
    import jax

    from cubed_tpu.runtime.executors.jax import (
        _float64_round_trips,
        _join_planes,
        _plane_program_of,
        _planes_strided,
    )

    host = np.random.default_rng(seed).random((n, n))
    cuts = {
        "slab": (slice(0, n), slice(0, n // 4)),
        "block": (slice(0, n // 2), slice(0, n // 2)),
    }
    out = {}
    for form in ("uint64", "float64"):
        value = jax.device_put(host.view(form), device)
        jax.block_until_ready(value)
        for name, sel in cuts.items():
            split, held = _plane_program_of(value[sel])  # compiled here
            steps = []
            for _ in range(readings):
                t0 = time.perf_counter()
                piece = value[sel]
                jax.block_until_ready(piece)
                t1 = time.perf_counter()
                planes = split(piece)
                jax.block_until_ready(planes)
                t2 = time.perf_counter()
                first, second, inexact = jax.device_get(planes)
                t3 = time.perf_counter()
                joined = _join_planes(first, second, np.dtype(form))
                t4 = time.perf_counter()
                steps.append((t1 - t0, t2 - t1, t3 - t2, t4 - t3))
            if inexact:
                raise AssertionError(f"{form} {name}: uniform values raised the split's flag")
            # a device with a real float64 loses bits to the split into pairs
            exact = form == "uint64" or not _float64_round_trips(device)
            if exact and joined.tobytes() != np.asarray(piece).tobytes():
                raise AssertionError(f"{form} {name}: the planes' join differs from the direct fetch")
            ms = [1e3 * statistics.median(step) for step in zip(*steps)]
            out[form, name] = {
                "strided": _planes_strided(first, second), "strides": first.strides, "ms": ms,
            }
            _say(
                f"{form} {name} {first.shape}, {joined.nbytes / 1e6:.0f} MB: planes reach the "
                f"host with strides {first.strides} (C-contiguous {first.flags.c_contiguous}, "
                f"F-contiguous {first.flags.f_contiguous}), the program lays them out "
                f"{split.output_formats[0].layout.major_to_minor}, the split holds {held / 1e6:.0f} MB "
                f"on the device; cut {ms[0]:.1f} split {ms[1]:.1f} fetch {ms[2]:.1f} "
                f"join {ms[3]:.1f} ms"
            )
            del piece, planes, first, second, joined
        del value
    return out


def device_facts(device, n: int, readings: int, calls: int, *, seed: int) -> dict:
    """What the legs' timings are read against (PERF.md section 5), through
    jax alone: whether a float64 survives being held by the device, how fast
    an (n, n) array moves each way as float64, as its uint64 bit pattern and
    as float32, how fast the float64 leaves through the executor's own split
    into 32-bit planes, what one dispatch costs, and (through the executor)
    that the streamed preload puts on the device what the whole-array put
    does, and in which order a slab's and a block's planes reach the host
    (``fetch_layout_reading``). Medians of ``readings`` transfers and of
    ``calls`` dispatches, on the host's clock."""
    import jax

    print(f"== device facts: ({n}, {n}) arrays, medians of {readings} "
          f"transfers and {calls} dispatches", flush=True)
    host = np.random.default_rng(seed).random((n, n))
    back = np.asarray(jax.device_put(host, device))
    changed = int(np.count_nonzero(back != host))
    rel = float(np.max(np.abs(back - host) / np.maximum(host, 2.0**-1022)))
    wide = np.array([1e300, 1e-300, 1e40, 1.0 + 2.0**-52])
    _say(
        f"float64 put on the device and fetched: {changed} of {host.size} "
        f"values changed, max relative error {rel:.3e}; {wide.tolist()} -> "
        f"{np.asarray(jax.device_put(wide, device)).tolist()}"
    )
    facts = {"changed": changed}
    forms = (host, host.view(np.uint64), host.astype(np.float32))
    for data in forms:
        put, fetch = [], []
        for _ in range(readings):
            # a fresh device array each time: once fetched, a jax.Array
            # keeps its host copy
            t0 = time.perf_counter()
            on_device = jax.device_put(data, device)
            on_device.block_until_ready()
            t1 = time.perf_counter()
            fetched = np.asarray(on_device)
            fetch.append(time.perf_counter() - t1)
            put.append(t1 - t0)
        if data.dtype != np.float64 and fetched.tobytes() != data.tobytes():
            raise AssertionError(f"{data.dtype} came back changed")
        gb = data.nbytes / 1e9
        rates = (gb / statistics.median(put), gb / statistics.median(fetch))
        facts[data.dtype.name] = rates
        _say(
            f"{data.dtype.name} {gb:.1f} GB: host->device {rates[0]:.2f} "
            f"GB/s, device->host {rates[1]:.2f} GB/s"
        )
    # the float64 array again, the way the executor fetches it: split into
    # 32-bit planes on the device, one fetch, joined on the host
    from cubed_tpu.runtime.executors.jax import _join_planes, _plane_program_of

    # compiled here
    (split, _), fetch = _plane_program_of(jax.device_put(host, device)), []
    for _ in range(readings):
        on_device = jax.device_put(host, device)
        on_device.block_until_ready()
        t0 = time.perf_counter()
        head, tail, _ = jax.device_get(split(on_device))
        joined = _join_planes(head, tail, host.dtype)
        fetch.append(time.perf_counter() - t0)
    differ = int(np.count_nonzero(joined.view(np.uint64) != back.view(np.uint64)))
    # the two fetches agree bit for bit only on a device that holds a
    # float64 as two float32; one with a real float64 loses bits to the split
    if changed and differ:
        raise AssertionError(
            f"float64 fetched as planes differs from the direct fetch in {differ} values"
        )
    facts["float64_planes"] = host.nbytes / 1e9 / statistics.median(fetch)
    _say(
        f"float64 {host.nbytes / 1e9:.1f} GB as two 32-bit planes (split on the "
        f"device, joined on the host): device->host {facts['float64_planes']:.2f} "
        f"GB/s; {differ} of {host.size} values differ from the direct fetch"
    )
    step = jax.jit(lambda v: v + 1.0)
    small = jax.device_put(np.ones(8, np.float32), device)
    step(small).block_until_ready()
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        step(small).block_until_ready()
        times.append(time.perf_counter() - t0)
    facts["dispatch_us"] = statistics.median(times) * 1e6
    _say(f"trivial jitted call to block_until_ready: {facts['dispatch_us']:.0f} us")
    # the way in, as the executor's preload takes it: chunk by chunk through
    # reused staging buffers, against the put of the whole array
    facts["preload_stream"] = preload_stream_reading(n, seed=seed)
    # the way out by the chunk's shape: a column slab against a block
    facts["fetch_layout"] = fetch_layout_reading(device, 2 * n, readings, seed=seed)
    return facts


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def _memory_line(devices) -> str:
    parts = []
    for d in devices:
        ms = d.memory_stats()
        parts.append(
            f"dev{d.id} peak_bytes_in_use={ms['peak_bytes_in_use']} "
            f"({ms['peak_bytes_in_use'] / ms['bytes_limit']:.1%} of "
            f"bytes_limit) bytes_in_use={ms['bytes_in_use']}"
        )
    return "hbm (peak is cumulative over the process): " + "; ".join(parts)


def _cache_files(cache_dir: Optional[str]) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def check_mesh_shares(peaks: Sequence[int]) -> None:
    """Every chip held its share: no peak zero, none above twice the mean."""
    mean = sum(peaks) / len(peaks)
    if any(p == 0 or p > 2 * mean for p in peaks):
        raise AssertionError(
            f"per-device peak_bytes_in_use {list(peaks)}: a chip held "
            f"nothing, or more than twice the mean ({mean:.0f})"
        )


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; jax found platform={dev0.platform!r} "
            f"({dev0.device_kind}). Nothing was run.",
            file=sys.stderr,
        )
        return 1

    cache_dir = jax.config.jax_compilation_cache_dir
    cache_before = _cache_files(cache_dir)
    rng_policy = cubed_tpu.random.generation_mode()
    print(
        f"chip_smoke: platform={dev0.platform} device_kind={dev0.device_kind!r} "
        f"count={len(devices)} seed={args.seed}\n"
        f"chip_smoke: jax={jax.__version__} jaxlib={metadata.version('jaxlib')} "
        f"libtpu={metadata.version('libtpu')} numpy={np.__version__} "
        f"x64={jax.config.jax_enable_x64}\n"
        f"chip_smoke: bytes_limit={dev0.memory_stats()['bytes_limit']} "
        f"rng_policy={rng_policy}\n"
        f"chip_smoke: compile_cache_dir={cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}) "
        f"files_before={cache_before}",
        flush=True,
    )
    if rng_policy != "threefry":
        raise RuntimeError(
            f"RNG policy is {rng_policy!r}: generation would not be the "
            "fused threefry path"
        )

    log = CompileLog()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as root:
        if len(devices) > 1:
            # mesh legs FIRST: peak_bytes_in_use never resets, and the
            # single-device legs below raise device 0's peak, so the
            # per-device shares are read here, before they run — after
            # each mesh leg, vorticity first while every chip is untouched
            def mesh_executor():
                return JaxExecutor(mesh=make_mesh())

            def check_shares():
                _say(_memory_line(devices))
                check_mesh_shares(
                    [d.memory_stats()["peak_bytes_in_use"] for d in devices]
                )

            vorticity_leg(
                **VORTICITY, seed=args.seed,
                work_dir=os.path.join(root, "mesh-vorticity"),
                make_executor=mesh_executor, log=log, name="mesh vorticity",
            )
            check_shares()
            # a 2 x 2 grid on four chips: a chunk a chip, in and out; the
            # mean reduces stored data along an axis the mesh divides
            check_owner_io(zarr_add_leg(
                **ZARR_ADD, seed=args.seed,
                work_dir=os.path.join(root, "mesh-zarr_add"),
                make_executor=mesh_executor, log=log, name="mesh zarr_add",
            ))
            check_shares()
        else:
            print("== mesh legs skipped: one device visible", flush=True)

        vorticity_leg(
            **VORTICITY, seed=args.seed,
            work_dir=os.path.join(root, "vorticity"),
            make_executor=JaxExecutor, log=log,
        )
        _say(_memory_line(devices[:1]))
        zarr_add_leg(
            **ZARR_ADD, seed=args.seed,
            work_dir=os.path.join(root, "zarr_add"),
            make_executor=JaxExecutor, log=log,
        )
        _say(_memory_line(devices[:1]))
    # last: its transfers would raise device 0's peak before the shares are read
    device_facts(dev0, **PROBE, seed=args.seed)
    log.close()

    cache_after = _cache_files(cache_dir)
    print(
        f"chip_smoke: {sum(log.programs.values())} programs compiled in "
        f"{log.compile_seconds:.3f} s; persistent cache "
        f"{log.events['cache_hits']} hits, {log.events['cache_misses']} "
        f"misses; cache files before={cache_before} after={cache_after}",
        flush=True,
    )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev0.platform,
                    "kind": dev0.device_kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
