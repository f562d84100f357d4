"""Core integration tests, executor-parametrized.

Reference parity: cubed/tests/test_core.py (behavioral).
"""

import numpy as np
import pytest

import chip_smoke
import cubed_tpu as ct
import cubed_tpu.array_api as xp
from cubed_tpu.core.optimization import fuse_all_optimize_dag, simple_optimize_dag

from .utils import TaskCounter, all_executors


@pytest.fixture(params=all_executors(), ids=lambda e: e.name)
def executor(request):
    return request.param


def test_regular_chunks(spec):
    a = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    assert a.chunks == ((2, 2, 2), (2, 2, 2))
    assert a.numblocks == (3, 3)
    assert a.npartitions == 9


def test_ragged_chunks(spec):
    a = xp.ones((7, 5), chunks=(3, 2), spec=spec)
    assert a.chunks == ((3, 3, 1), (2, 2, 1))


def test_add(spec, executor):
    a = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    b = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    c = xp.add(a, b)
    assert np.array_equal(c.compute(executor=executor), np.full((6, 6), 2.0))


def test_add_ragged(spec, executor):
    an = np.arange(35.0).reshape(7, 5)
    a = ct.from_array(an, chunks=(3, 2), spec=spec)
    b = ct.from_array(an, chunks=(3, 2), spec=spec)
    c = xp.add(a, b)
    assert np.allclose(c.compute(executor=executor), an + an)


def test_add_different_chunks(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    b = ct.from_array(an, chunks=(3, 3), spec=spec)
    c = xp.add(a, b)
    assert np.allclose(c.compute(executor=executor), an + an)


def test_add_scalar(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    c = a + 5.0
    assert np.allclose(c.compute(executor=executor), an + 5.0)


def test_broadcast(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    bn = np.arange(6.0)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    b = ct.from_array(bn, chunks=(2,), spec=spec)
    c = xp.add(a, b)
    assert np.allclose(c.compute(executor=executor), an + bn)


def test_sum(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    assert np.allclose(xp.sum(a).compute(executor=executor), an.sum())


def test_sum_axis(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    assert np.allclose(xp.sum(a, axis=0).compute(executor=executor), an.sum(axis=0))
    assert np.allclose(xp.sum(a, axis=1).compute(executor=executor), an.sum(axis=1))


def test_mean_keepdims(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    assert np.allclose(
        xp.mean(a, axis=1, keepdims=True).compute(executor=executor),
        an.mean(axis=1, keepdims=True),
    )


def test_fused_add_sum(spec, executor):
    a = xp.ones((10, 10), chunks=(3, 3), spec=spec)
    b = xp.ones((10, 10), chunks=(3, 3), spec=spec)
    s = xp.sum(xp.add(a, b))
    assert float(s.compute(executor=executor)) == 200.0


def test_multiple_outputs(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    b = xp.add(a, a)
    c = xp.multiply(a, a)
    rb, rc = ct.compute(b, c, executor=executor)
    assert np.allclose(rb, an + an)
    assert np.allclose(rc, an * an)


def test_from_zarr_to_zarr(spec, executor, tmp_path):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    store = str(tmp_path / "out.zarr")
    ct.to_zarr(xp.add(a, 1.0), store, executor=executor)
    b = ct.from_zarr(store, spec=spec)
    assert np.allclose(b.compute(executor=executor), an + 1.0)


def test_rechunk(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    b = a.rechunk((3, 3))
    assert b.chunksize == (3, 3)
    assert np.allclose(b.compute(executor=executor), an)


def test_rechunk_staged(executor, tmp_path):
    # tight memory budget forces the two-pass (intermediate) rechunk
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem=20000, reserved_mem=0)
    an = np.arange(900.0).reshape(30, 30)
    a = ct.from_array(an, chunks=(30, 2), spec=spec)
    b = a.rechunk((2, 30))
    assert np.allclose(b.compute(executor=executor), an)


def test_compute_is_idempotent(spec, executor):
    a = xp.ones((4, 4), chunks=(2, 2), spec=spec)
    b = xp.add(a, 1)
    assert np.array_equal(b.compute(executor=executor), np.full((4, 4), 2.0))
    assert np.array_equal(b.compute(executor=executor), np.full((4, 4), 2.0))


def test_plan_scaling(spec):
    # plan size is O(ops); a long chain must build fast and count tasks
    a = xp.ones((4, 4), chunks=(2, 2), spec=spec)
    for _ in range(50):
        a = xp.add(a, 1)
    assert a.plan.num_tasks(optimize_graph=False) > 0


def test_callbacks(spec, executor):
    counter = TaskCounter()
    a = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    b = xp.add(a, 1)
    b.compute(executor=executor, callbacks=[counter], optimize_graph=False)
    assert counter.value > 0


def test_resume(spec):
    a = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    b = xp.add(a, 1)
    c = xp.add(b, 1)
    counter = TaskCounter()
    c.compute(callbacks=[counter], optimize_graph=False)
    n_first = counter.value
    counter2 = TaskCounter()
    c.compute(callbacks=[counter2], optimize_graph=False, resume=True)
    # everything already computed -> no (or far fewer) tasks
    assert counter2.value < n_first


def test_visualize(spec, tmp_path):
    a = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    b = xp.add(a, 1)
    out = b.visualize(filename=str(tmp_path / "plan"))
    import os

    assert os.path.exists(out)


def test_projected_mem_exceeded(tmp_path):
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem=1000, reserved_mem=0)
    a = xp.ones((100, 100), chunks=(100, 100), spec=spec)
    with pytest.raises(ValueError, match="exceeds allowed_mem"):
        xp.add(a, a)


def test_spec_mismatch(tmp_path):
    s1 = ct.Spec(work_dir=str(tmp_path), allowed_mem=100_000_000)
    s2 = ct.Spec(work_dir=str(tmp_path), allowed_mem=200_000_000)
    a = xp.ones((4, 4), chunks=(2, 2), spec=s1)
    b = xp.ones((4, 4), chunks=(2, 2), spec=s2)
    with pytest.raises(ValueError, match="same spec"):
        xp.add(a, b)


def test_optimization_fuses_map_chain(spec):
    a = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    b = xp.add(a, 1)
    c = xp.add(b, 1)
    unopt = c.plan.num_tasks(optimize_graph=False)
    opt = c.plan.num_tasks(optimize_graph=True)
    assert opt < unopt
    assert np.array_equal(c.compute(), np.full((6, 6), 3.0))


def test_reduction_multiple_rounds(spec, executor):
    an = np.ones((64, 4))
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    s = xp.sum(a, axis=0, split_every=2)
    assert np.allclose(s.compute(executor=executor), an.sum(axis=0))


def test_merge_chunks(spec, executor):
    from cubed_tpu.core.ops import merge_chunks

    an = np.arange(100.0).reshape(10, 10)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    b = merge_chunks(a, (4, 4))
    assert b.chunksize == (4, 4)
    assert np.allclose(b.compute(executor=executor), an)


def test_unify_chunks_applies(spec, executor):
    an = np.arange(36.0).reshape(6, 6)
    a = ct.from_array(an, chunks=(2, 3), spec=spec)
    b = ct.from_array(an, chunks=(3, 2), spec=spec)
    c = xp.add(a, b)
    assert np.allclose(c.compute(executor=executor), an + an)


@pytest.mark.parametrize("n", [5, 6, 7])
def test_unify_chunks_misaligned_1d(spec, executor, n):
    # reference semantics: add of (3,)-chunked and (2,)-chunked computes
    # (cubed/core/ops.py:1172-1219); here via smallest-chunksize rechunk
    an = np.arange(float(n))
    a = ct.from_array(an, chunks=(3,), spec=spec)
    b = ct.from_array(an, chunks=(2,), spec=spec)
    c = xp.add(a, b)
    assert np.allclose(c.compute(executor=executor), an + an)


def test_unify_chunks_misaligned_2d_with_broadcast(spec, executor):
    an = np.arange(30.0).reshape(6, 5)
    bn = np.arange(5.0)
    a = ct.from_array(an, chunks=(4, 3), spec=spec)
    b = ct.from_array(bn, chunks=(2,), spec=spec)
    c = xp.multiply(a, b)
    assert np.allclose(c.compute(executor=executor), an * bn)


def test_unify_chunks_extent_mismatch_raises(spec):
    a = ct.from_array(np.arange(6.0), chunks=(3,), spec=spec)
    b = ct.from_array(np.arange(7.0), chunks=(2,), spec=spec)
    with pytest.raises(ValueError):
        xp.add(a, b)


# -- to_zarr / store end when the target is durable; compute() reads back --


_STORED_EXECUTORS = [e for e in all_executors() if e.name in ("single-threaded", "jax")]


def _assert_durable(target, expected):
    """The values as a client with numpy alone reads them, and every chunk
    file present with a manifest entry whose CRC32 is the file's. Returns
    the bytes the chunk files hold (edge chunks are stored whole)."""
    from math import prod

    from cubed_tpu.storage.store import open_zarr_array

    assert np.array_equal(chip_smoke.read_zarr_v2(target), expected)
    arr = open_zarr_array(target, mode="r")
    valid, corrupt, verified = arr.verify_chunks(quarantine=False, count=False)
    assert verified and not corrupt and len(valid) == arr.nchunks
    return arr.nchunks * prod(arr.chunks) * arr.dtype.itemsize


@pytest.mark.parametrize("call", ["to_zarr", "store"])
@pytest.mark.parametrize("executor", _STORED_EXECUTORS, ids=lambda e: e.name)
def test_stored_without_read_back(spec, executor, call, tmp_path, monkeypatch):
    from cubed_tpu.core.array import CoreArray
    from cubed_tpu.observability import reset_store_totals, store_totals

    an = np.arange(35.0).reshape(7, 5)
    bn = an[::-1].copy()
    sources = {str(tmp_path / "a.zarr"): an, str(tmp_path / "b.zarr"): bn}
    for path, values in sources.items():
        ct.to_zarr(ct.from_array(values, chunks=(3, 2), spec=spec), path)
    a, b = (ct.from_zarr(path, spec=spec) for path in sources)

    def no_read_back(self):
        raise AssertionError("to_zarr / store read the target back")

    monkeypatch.setattr(CoreArray, "_read_stored", no_read_back)
    reset_store_totals()
    if call == "to_zarr":
        sum_path = str(tmp_path / "sum.zarr")
        targets = {sum_path: an + bn}
        returned = ct.to_zarr(xp.add(a, b), sum_path, executor=executor)
    else:
        targets = {
            str(tmp_path / "inc.zarr"): an + 1.0,
            str(tmp_path / "neg.zarr"): -bn,
        }
        returned = ct.store(
            [xp.add(a, 1.0), xp.negative(b)], list(targets), executor=executor
        )
    totals = store_totals()

    assert returned is None
    for target, expected in targets.items():
        stored = _assert_durable(target, expected)
        assert totals[target] == {"bytes_read": 0, "bytes_written": stored}
    # each source feeds one fused op: read once, whoever executes
    for path, values in sources.items():
        stored = _assert_durable(path, values)
        assert totals[path] == {"bytes_read": stored, "bytes_written": 0}


@pytest.mark.parametrize("executor", _STORED_EXECUTORS, ids=lambda e: e.name)
def test_compute_reads_back_and_keywords_pass_both_ways(spec, executor, tmp_path):
    an = np.arange(36.0).reshape(6, 6)

    def expr():
        a = ct.from_array(an, chunks=(2, 2), spec=spec)
        return xp.add(xp.add(a, 1.0), 1.0)

    assert np.array_equal(expr().compute(executor=executor), an + 2.0)
    (both,) = ct.compute(expr(), executor=executor)
    assert np.array_equal(both, an + 2.0)

    # callbacks, optimize_graph and resume reach the plan from either call
    def tasks(run, **kwargs):
        counter = TaskCounter()
        run(callbacks=[counter], executor=executor, **kwargs)
        return counter.value

    c = expr()
    target = str(tmp_path / "out.zarr")
    stored = expr()
    for run in (c.compute, lambda **kw: ct.to_zarr(stored, target, **kw)):
        fused = tasks(run)
        unfused = tasks(run, optimize_graph=False)
        assert 0 < fused < unfused
        assert tasks(run, optimize_graph=False, resume=True) < unfused
    _assert_durable(target, an + 2.0)


def test_to_zarr_never_holds_the_whole_target(tmp_path):
    # what the bound on memory per task is for: 64 chunks of 128 KB under an
    # allowed_mem of a fifth of the 8 MB target, and the client never holds
    # anything near the target's size
    import tracemalloc

    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem=1_600_000, reserved_mem=0)

    def stored(n, name):
        a = xp.ones((n, n), chunks=(128, 128), spec=spec)
        target = str(tmp_path / name)
        ct.to_zarr(xp.add(a, 1.0), target)
        return target, a.nbytes

    stored(256, "warm.zarr")  # imports and first-use caches are not the array
    tracemalloc.start()
    try:
        target, nbytes = stored(1024, "out.zarr")
        _, peak_stored = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        value = ct.from_zarr(target, spec=spec).compute()
        _, peak_computed = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()

    assert value.nbytes == nbytes == 8 * 1024 * 1024
    assert peak_computed >= nbytes  # the yardstick sees a whole-array read
    assert peak_stored < nbytes / 4, peak_stored
