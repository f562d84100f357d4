"""On the device path an exception is an error.

``JaxExecutor`` handles two designed aborts and nothing else: its own
``_TraceAbort`` (a source that is not resident, or a flush, inside a trace)
and what JAX raises when a kernel asks a tracer for a concrete value
(``_needs_concrete_value``). The first sends a segment to the eager route;
the second does that too and, at an eager op, runs the kernel un-jitted on
concrete chunks, once. Everything else reaches the caller from where it
was raised: a kernel's own exception, a cancel, a bug in a route."""

from __future__ import annotations

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
from cubed_tpu.core.ops import elemwise
from cubed_tpu.runtime.cancellation import CancellationToken, ComputeCancelledError
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.storage.store import _LocalIO, open_zarr_array

HOST = np.random.default_rng(30).standard_normal((8, 8))

#: the counters that went with the handlers they counted
GONE = ("whole_array_errors", "batched_errors", "whole_select_errors", "jit_kernel_errors")


@pytest.fixture
def spec(tmp_path):
    return ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="200MB", reserved_mem=0)


def _stored(tmp_path, host, chunks) -> str:
    z = open_zarr_array(
        str(tmp_path / "a.zarr"), "w", shape=host.shape, dtype=host.dtype, chunks=chunks
    )
    z[...] = host
    return z.store


class KernelBoom(Exception):
    pass


@pytest.mark.parametrize("fuse_plan", [True, False], ids=["fused", "eager_op"])
def test_a_kernels_own_exception_reaches_the_caller_at_its_first_raise(spec, fuse_plan):
    calls = []

    def boom(x):
        calls.append(x.shape)
        raise KernelBoom("kernel boom")

    a = ct.from_array(HOST, chunks=(4, 4), spec=spec)
    executor = JaxExecutor(fuse_plan=fuse_plan)
    with pytest.raises(KernelBoom, match="kernel boom"):
        ct.map_blocks(boom, a, dtype=a.dtype).compute(executor=executor)
    assert len(calls) == 1
    assert not executor.stats["trace_failures"] and not executor.stats["eager_fallbacks"]


def test_a_cancel_in_a_preload_is_not_a_trace_failure(spec, tmp_path, monkeypatch):
    path = _stored(tmp_path, np.tile(HOST, (4, 2)), (8, 8))
    token = CancellationToken()
    reads = []
    real = _LocalIO.readinto

    def readinto(self, name, buffer):
        reads.append(name)
        if len(reads) == 3:
            token.cancel("the test asked")
        return real(self, name, buffer)

    monkeypatch.setattr(_LocalIO, "readinto", readinto)
    executor = JaxExecutor()
    with pytest.raises(ComputeCancelledError):
        ct.to_zarr(
            xp.add(ct.from_zarr(path, spec=spec), 1.0), str(tmp_path / "c.zarr"),
            executor=executor, cancellation=token,
        )
    # the read in flight finished, no other was started, and the segment was
    # not begun again on the eager route
    assert len(reads) == 3
    assert executor.stats["trace_failures"] == 0 and executor.stats["eager_fallbacks"] == 0
    assert executor.stats["chunked_ops"] == 0 and executor.stats["batched_ops"] == 0


def _planted_in_batched(monkeypatch, a):
    def batched(self, op, spec, resident):
        raise RuntimeError("planted")

    monkeypatch.setattr(JaxExecutor, "_exec_batched", batched)
    return ct.map_blocks(lambda x: x + 1, a, dtype=a.dtype)


def _planted_in_whole_array(monkeypatch, a):
    def kernel(x):
        # the whole-array route alone hands the kernel the whole array
        if x.shape == a.shape:
            raise RuntimeError("planted")
        return x + 1

    return elemwise(kernel, a, dtype=a.dtype)


@pytest.mark.parametrize(
    "planted", [_planted_in_batched, _planted_in_whole_array],
    ids=["batched", "whole_array"],
)
def test_an_error_in_a_route_is_not_the_next_routes_turn(spec, monkeypatch, planted):
    a = ct.from_array(HOST, chunks=(4, 4), spec=spec)
    executor = JaxExecutor()
    with pytest.raises(RuntimeError, match="planted"):
        planted(monkeypatch, a).compute(executor=executor)
    assert executor.stats["chunked_ops"] == 0 and executor.stats["eager_fallbacks"] == 0


def _numpy_sort(x):
    return np.sort(np.asarray(x)) + 1  # TracerArrayConversionError


def _python_branch(x):
    return x * 2 if x.sum() > 0 else x - 1  # TracerBoolConversionError


def _python_float(x):
    return x + float(x[0, 0])  # ConcretizationTypeError


def _python_range(x):
    for _ in range((x[0, 0] > -1e9).astype(int)):  # TracerIntegerConversionError
        x = x + 1
    return x


def _boolean_mask(x):
    return x[x > -1e9].reshape(x.shape) + 1  # NonConcreteBooleanIndexError


@pytest.mark.parametrize(
    "kernel, fuse_plan",
    [(_numpy_sort, True), (_numpy_sort, False), (_python_branch, True),
     (_python_float, True), (_python_range, False), (_boolean_mask, True)],
    ids=["numpy_sort-fused", "numpy_sort-unfused", "python_branch", "python_float",
         "python_range", "boolean_mask"],
)
def test_a_kernel_that_needs_concrete_values_runs_unjitted_once(spec, kernel, fuse_plan):
    a = ct.from_array(HOST, chunks=(4, 4), spec=spec)
    executor = JaxExecutor(fuse_plan=fuse_plan)
    got = ct.map_blocks(kernel, a, dtype=a.dtype).compute(executor=executor)
    want = np.block(
        [[kernel(HOST[i : i + 4, j : j + 4]) for j in (0, 4)] for i in (0, 4)]
    )
    np.testing.assert_array_equal(got, want)
    stats = executor.stats
    assert stats["host_kernel_ops"] == 1 and stats["chunked_ops"] == 1
    # fused, the segment went to the eager route first: one abort at each level
    assert stats["trace_failures"] == int(fuse_plan)
    assert stats["eager_fallbacks"] == 1 + int(fuse_plan)
    assert not any(name in stats for name in GONE)


def test_trace_abort_sends_a_segment_whose_source_is_not_resident_to_the_eager_route(
    spec, tmp_path
):
    host = np.random.default_rng(31).standard_normal((64, 64))
    path = _stored(tmp_path, host, (16, 16))
    # the source does not fit the budget: _preload declines, the trace meets
    # a storage read and aborts, and the ops run one by one from storage
    executor = JaxExecutor(device_mem=host.nbytes // 2)
    got = xp.sum(xp.add(ct.from_zarr(path, spec=spec), 1.0), axis=0).compute(
        executor=executor
    )
    np.testing.assert_allclose(got, (host + 1.0).sum(axis=0), rtol=1e-12)
    assert executor.stats["trace_failures"] == 1
    assert executor.stats["segments_traced"] == 0
    assert not executor.stats["host_kernel_ops"]
