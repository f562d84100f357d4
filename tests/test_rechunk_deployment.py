"""Upstream's rechunk as a pipeline, Zarr to Zarr: ``to_zarr(from_zarr(a)
.rechunk(...))`` is a copy, bit for bit, on every route ``JaxExecutor`` has
for it.

The reference is numpy's alone: the source's chunk files read and cut at the
target's grid, compared with the target's chunk files **as uint64** (at
tolerance 0 a comparison of numbers calls ``-0.0`` equal to ``0.0`` and a
NaN unequal to itself). Edge values are planted in every source chunk.

``_exec_rechunk`` has four routes, each with a counter: an alias of a
resident array (``rechunk_alias``), a virtual source made on the device
(``rechunk_virtual``), a stored source read whole on the host and put
(``rechunk_host_whole``), a copy chunk by chunk on the host that never
touches the device (``rechunk_host_copy``). What the routes count while a
segment is traced comes back with the compiled program, so a compute that
finds the program compiled reports what the one that traced it did."""

from __future__ import annotations

import itertools
import json
import math
import os

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.runtime.executors.jax as jx
from chip_smoke import EDGE_VALUES
from cubed_tpu.primitive.rechunk import copy_read_to_write
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.runtime.executors.python import PythonDagExecutor

#: float64 bit patterns that a 64-bit transfer is most likely to change:
#: NaNs with a payload and a sign, both zeros, both infinities, subnormals,
#: the extremes of float64's normal range and of float32's
EDGE_BITS = EDGE_VALUES.view(np.uint64)

#: failure counters of the device path: 0 wherever a segment was traced
FAILURES = ("eager_fallbacks", "trace_failures", "segment_mem_aborts", "host_kernel_ops")
STORAGE_ROUTES = ("rechunk_host_whole", "rechunk_host_copy")


# -- Zarr v2 with numpy alone ---------------------------------------------------


def _grid(shape, chunks):
    for idx in itertools.product(*(range(math.ceil(s / c)) for s, c in zip(shape, chunks))):
        yield idx, tuple(
            slice(i * c, min((i + 1) * c, s)) for i, c, s in zip(idx, chunks, shape)
        )


def _write_source(path, shape, chunks, seed) -> np.ndarray:
    """A raw C-order float64 Zarr v2 store written by hand, uniform values
    with ``EDGE_BITS`` in every chunk; returns the array as uint64."""
    rng = np.random.default_rng(seed)
    whole = rng.random(shape)
    bits = whole.view(np.uint64)
    os.makedirs(path)
    with open(os.path.join(path, ".zarray"), "w") as f:
        json.dump({"zarr_format": 2, "shape": list(shape), "chunks": list(chunks),
                   "dtype": "<f8", "compressor": None, "fill_value": 0.0,
                   "order": "C", "filters": None, "dimension_separator": "."}, f)
    for idx, sel in _grid(shape, chunks):
        inside = bits[sel]
        where = rng.choice(inside.size, size=EDGE_BITS.size, replace=False)
        inside[np.unravel_index(where, inside.shape)] = EDGE_BITS
        block = np.zeros(chunks, np.uint64)  # an edge chunk is stored padded
        block[tuple(slice(0, s.stop - s.start) for s in sel)] = inside
        block.tofile(os.path.join(path, ".".join(map(str, idx))))
    return bits


def _read_chunk_files(path) -> tuple:
    """(.zarray, {chunk key: uint64 block as stored}) with numpy alone."""
    with open(os.path.join(path, ".zarray")) as f:
        meta = json.load(f)
    files = {
        name: np.fromfile(os.path.join(path, name), np.uint64).reshape(meta["chunks"])
        for name in os.listdir(path) if not name.startswith(".")
    }
    return meta, files


def _assert_is_the_copy(target, source_path, chunks) -> None:
    """The target's chunk files are the source's chunk files, read with
    numpy and cut at the target's grid, as uint64."""
    smeta, sfiles = _read_chunk_files(source_path)
    shape = tuple(smeta["shape"])
    source = np.empty(shape, np.uint64)
    for idx, sel in _grid(shape, smeta["chunks"]):
        block = sfiles[".".join(map(str, idx))]
        source[sel] = block[tuple(slice(0, s.stop - s.start) for s in sel)]
    meta, files = _read_chunk_files(target)
    assert (meta["shape"], meta["chunks"], meta["dtype"], meta["compressor"]) == (
        list(shape), list(chunks), "<f8", None
    )
    wanted = {".".join(map(str, idx)): sel for idx, sel in _grid(shape, chunks)}
    assert sorted(files) == sorted(wanted)
    for key, sel in wanted.items():
        inside = tuple(slice(0, s.stop - s.start) for s in sel)
        differ = int(np.count_nonzero(files[key][inside] != source[sel]))
        assert differ == 0, f"chunk {key}: {differ} elements differ bitwise"


# -- the pipeline -------------------------------------------------------------


def _copy_ops(expr) -> int:
    return sum(
        1 for _, d in expr.plan.dag.nodes(data=True)
        if d.get("primitive_op") is not None
        and d["primitive_op"].pipeline.function is copy_read_to_write
    )


def _pipeline(tmp_path, shape, chunks, target_chunks, allowed_mem, executor, name="t"):
    """``to_zarr(from_zarr(a).rechunk(target_chunks))``; returns (the
    executor's counters, the plan's copy ops, the target, the source)."""
    source = str(tmp_path / "a.zarr")
    if not os.path.exists(source):
        _write_source(source, shape, chunks, seed=32)
    spec = ct.Spec(work_dir=str(tmp_path / f"work-{name}"), allowed_mem=allowed_mem,
                   reserved_mem=0)
    expr = ct.from_zarr(source, spec=spec).rechunk(target_chunks)
    target = str(tmp_path / f"{name}.zarr")
    ct.to_zarr(expr, target, executor=executor)
    return dict(getattr(executor, "stats", None) or {}), _copy_ops(expr), target, source


#: (shape, source chunks, target chunks, allowed_mem): target chunks of 1 MiB
#: or more, so that they leave as planes where the device's float64 is a pair
ONE_STAGE = ((800, 800), (400, 400), (800, 200), "100MB")
TWO_STAGE = ((800, 800), (400, 400), (800, 200), "16MB")
RAGGED = ((1000, 900), (400, 400), (1000, 250), "100MB")
PLANS = pytest.mark.parametrize(
    "shape, chunks, target_chunks, allowed_mem, copies",
    [(*ONE_STAGE, 1), (*TWO_STAGE, 2), (*RAGGED, 1)],
    ids=["one_stage", "two_stage", "ragged"],
)


@PLANS
def test_carried_as_bits_streamed_in_and_out_as_planes(
    tmp_path, monkeypatch, shape, chunks, target_chunks, allowed_mem, copies
):
    """A device whose float64 is a pair of float32 (the probe forced false):
    the streamed preload, the carry and the planes run together."""
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    stats, copy_ops, target, source = _pipeline(
        tmp_path, shape, chunks, target_chunks, allowed_mem, JaxExecutor()
    )
    _assert_is_the_copy(target, source, target_chunks)
    assert copy_ops == copies
    assert stats["segments_traced"] >= 1
    assert not any(stats.get(k) for k in FAILURES)
    assert stats["rechunk_alias"] == copies
    assert [stats[k] for k in STORAGE_ROUTES] == [0, 0]
    assert stats["f64_as_bits"] >= 1 and not stats.get("f64_lossy_moves")
    assert stats["h2d_bits_bytes"] == stats["h2d_bytes"] == stats["h2d_stream_bytes"] > 0
    assert stats["d2h_plane_bytes"] == stats["d2h_bytes"] == math.prod(shape) * 8
    assert not stats.get("d2h_plane_inexact") and not stats.get("d2h_plane_no_room")


@PLANS
def test_the_same_on_a_device_whose_float64_round_trips(
    tmp_path, shape, chunks, target_chunks, allowed_mem, copies
):
    stats, copy_ops, target, source = _pipeline(
        tmp_path, shape, chunks, target_chunks, allowed_mem, JaxExecutor()
    )
    _assert_is_the_copy(target, source, target_chunks)
    assert copy_ops == copies and stats["rechunk_alias"] == copies
    assert stats["segments_traced"] >= 1
    assert not any(stats.get(k) for k in FAILURES)
    # nothing is carried as bits, and the counter says so with a 0
    assert stats["h2d_bits_bytes"] == 0 and not stats.get("f64_as_bits")
    assert stats["h2d_bytes"] == stats["h2d_stream_bytes"] > 0
    assert [stats[k] for k in STORAGE_ROUTES] == [0, 0]


# -- the routes through storage ---------------------------------------------------

#: the source of the issue's record: 1.28 MB
SMALL = ((400, 400), (200, 200), (400, 100))


@pytest.mark.parametrize("allowed_mem, copies", [("16MB", 1), ("4MB", 2)],
                         ids=["one_stage", "two_stage"])
def test_a_source_over_the_hbm_budget_is_copied_on_the_host(tmp_path, allowed_mem, copies):
    """The rechunk's own output stands between the source and the requested
    target, and nobody created it: the route that copies through storage
    makes sure its destination exists."""
    stats, copy_ops, target, source = _pipeline(
        tmp_path, *SMALL, allowed_mem, JaxExecutor(device_mem=600_000)
    )
    _assert_is_the_copy(target, source, SMALL[2])
    assert copy_ops == copies
    assert stats["rechunk_host_copy"] == copies
    assert stats.get("rechunk_alias", 0) == stats["rechunk_host_whole"] == 0
    assert stats["segment_mem_aborts"] >= 1 and not stats.get("segments_traced")


@pytest.mark.parametrize("device_mem", [2_000_000, 3_000_000])
def test_a_budget_a_little_above_the_source_spills_the_intermediates(tmp_path, device_mem):
    stats, _, target, source = _pipeline(
        tmp_path, *SMALL, "4MB", JaxExecutor(device_mem=device_mem)
    )
    _assert_is_the_copy(target, source, SMALL[2])
    assert stats["segment_mem_aborts"] == 1
    assert [stats[k] for k in STORAGE_ROUTES] == [0, 0]


def test_an_unfused_plan_reads_a_small_stored_source_whole_on_the_host(tmp_path):
    stats, copy_ops, target, source = _pipeline(
        tmp_path, *SMALL, "16MB", JaxExecutor(fuse_plan=False)
    )
    _assert_is_the_copy(target, source, SMALL[2])
    assert copy_ops == 1
    assert stats["rechunk_host_whole"] == 1
    assert stats["rechunk_host_copy"] == stats.get("rechunk_alias", 0) == 0


@pytest.mark.parametrize(
    "executor_kwargs, route",
    [({"device_mem": 600_000}, "host_copy"), ({"fuse_plan": False}, "host_whole")],
    ids=["host_copy", "host_whole"],
)
def test_a_rechunk_through_storage_runs_inside_a_span(tmp_path, executor_kwargs, route):
    """Armed (a ``TraceCollector`` attached), the two storage routes are
    timed as ``jax.rechunk`` with their route and the array's bytes; the
    alias inside a traced segment has no clock and no span."""
    from cubed_tpu.observability.collect import TraceCollector

    def spans_of(executor, name) -> list:
        tc = TraceCollector(trace_dir=None)
        source = str(tmp_path / "a.zarr")
        if not os.path.exists(source):
            _write_source(source, *SMALL[:2], seed=32)
        spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="16MB", reserved_mem=0)
        target = str(tmp_path / f"{name}.zarr")
        ct.to_zarr(ct.from_zarr(source, spec=spec).rechunk(SMALL[2]), target,
                   executor=executor, callbacks=[tc])
        _assert_is_the_copy(target, source, SMALL[2])
        return [s for rec in tc._records for s in rec["spans"] if s["name"] == "jax.rechunk"]

    (span,) = spans_of(JaxExecutor(**executor_kwargs), "through-storage")
    assert span["attrs"]["route"] == route
    assert span["attrs"]["bytes"] == math.prod(SMALL[0]) * 8
    assert spans_of(JaxExecutor(), "resident") == []


def test_arrays_that_stay_resident_are_not_created_in_storage(tmp_path):
    """Only the requested target exists after a compute through residency:
    the rechunk's own output and its intermediate never reach storage."""
    _, copy_ops, target, _ = _pipeline(tmp_path, *TWO_STAGE, JaxExecutor())
    assert copy_ops == 2
    work = tmp_path / "work-t"
    stored = [p for p in work.rglob(".zarray")] if work.exists() else []
    assert stored == []
    assert os.path.exists(os.path.join(target, ".zarray"))


@pytest.mark.parametrize("allowed_mem", ["16MB", "4MB"], ids=["one_stage", "two_stage"])
def test_the_python_executor_on_numpy_gives_the_same_bytes(tmp_path, allowed_mem):
    stats, _, target, source = _pipeline(tmp_path, *SMALL, allowed_mem, PythonDagExecutor())
    assert stats == {}
    _assert_is_the_copy(target, source, SMALL[2])


# -- what a structural hit reports ----------------------------------------------


@pytest.fixture
def empty_program_caches(monkeypatch):
    """The first compute of a test is a miss, whatever ran before it."""
    monkeypatch.setattr(jx, "_STRUCT_CACHE", {})
    monkeypatch.setattr(jx, "_SEGMENT_CACHE", {})


#: what the routes count while a segment is traced
ROUTES = ("rechunk_alias", "rechunk_virtual", "whole_array_hits", "batched_ops",
          "chunked_ops", "whole_concat_hits")


def test_a_structural_hit_reports_the_routes_of_the_miss(tmp_path, empty_program_caches):
    """Three computes of one plan shape, each with a fresh executor, target
    and intermediate: the second and third compile nothing and report what
    the first did."""
    seen = []
    for k in range(3):
        stats, copy_ops, target, source = _pipeline(
            tmp_path, *TWO_STAGE, JaxExecutor(), name=f"t{k}"
        )
        _assert_is_the_copy(target, source, TWO_STAGE[2])
        assert copy_ops == 2
        seen.append(stats)
    first, *hits = seen
    assert first["segments_compiled"] == 1 and not first.get("segment_struct_hits")
    assert first["rechunk_alias"] == 2 and first["whole_array_hits"] == 1
    for hit in hits:
        assert hit["segment_struct_hits"] == 1 and not hit.get("segments_compiled")
        assert {k: hit.get(k, 0) for k in ROUTES} == {k: first.get(k, 0) for k in ROUTES}
        # what is counted outside the trace is counted once, as it was
        for k in ("segments_traced", "h2d_bytes", "d2h_bytes", "host_syncs"):
            assert hit[k] == first[k]


def test_a_hit_of_an_arithmetic_plan_reports_its_routes_too(tmp_path, empty_program_caches):
    """An elementwise add (one call on whole arrays), a mean (batched and
    chunked ops): the route counters of the second compute are the first's."""
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem="100MB", reserved_mem=0)
    grid = np.arange(48.0 * 36).reshape(48, 36)
    seen = []
    for _ in range(2):
        a = ct.from_array(grid, chunks=(12, 12), spec=spec)
        executor = JaxExecutor()
        got = xp.mean(xp.add(a, a), axis=0).compute(executor=executor)
        np.testing.assert_allclose(got, (2 * grid).mean(axis=0), rtol=1e-12)
        seen.append(dict(executor.stats))
    first, hit = seen
    assert first["segments_compiled"] >= 1
    assert hit["segment_struct_hits"] >= 1 and not hit.get("segments_compiled")
    assert sum(first.get(k, 0) for k in ("whole_array_hits", "batched_ops", "chunked_ops")) >= 2
    assert {k: hit.get(k, 0) for k in ROUTES} == {k: first.get(k, 0) for k in ROUTES}
    # the new counters are there, and 0, where no rechunk ran and nothing
    # was carried as bits
    for stats in seen:
        assert [stats[k] for k in (*STORAGE_ROUTES, "h2d_bits_bytes")] == [0, 0, 0]


def test_two_plans_of_one_program_keep_their_own_route_counts(tmp_path, empty_program_caches):
    """An alias compiles to nothing, so one rechunk and two in a row lower to
    the same program: each plan shape still reports its own count."""
    source = str(tmp_path / "a.zarr")
    _write_source(source, (240, 240), (120, 120), seed=5)
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="100MB", reserved_mem=0)
    counts = []
    for k, hops in enumerate([((240, 60),), ((240, 60), (60, 240)), ((240, 60),)]):
        expr = ct.from_zarr(source, spec=spec)
        for chunks in hops:
            expr = expr.rechunk(chunks)
        executor = JaxExecutor()
        target = str(tmp_path / f"t{k}.zarr")
        ct.to_zarr(expr, target, executor=executor)
        _assert_is_the_copy(target, source, hops[-1])
        counts.append((_copy_ops(expr), executor.stats["rechunk_alias"]))
    assert [alias for _, alias in counts] == [ops for ops, _ in counts]
