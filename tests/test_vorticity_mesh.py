"""The sharded path of ``JaxExecutor`` on the pangeo-vorticity query, on the
virtual CPU devices of ``conftest.py``: the benchmark cell
``vorticity-mesh4.mean`` (BENCHMARK.json) runs this at (500, 900, 800) on four
chips, and these are its small-size guards. The query and its blockwise
reference are the benchmark's own (``benchmark/queries/vorticity_mean_exact.py``,
loaded by path): the reference uses nothing of ``cubed_tpu``."""

import importlib.util
import random
from pathlib import Path

import jax
import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.random
import cubed_tpu.runtime.executors.jax as jx
from cubed_tpu.chunks import blockdims_from_blockshape
from cubed_tpu.parallel.mesh import factorized_mesh, make_mesh, sharding_for_chunks
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.runtime.executors.python import PythonDagExecutor

#: a[1:] of 50 rows in chunks of 10 leaves chunks of (10, 10, 10, 10, 9),
#: each straddling two blocks of the generated arrays, as (500, 900, 800) does
DEPLOY = {"shape": [50, 30, 40], "chunks": 10, "allowed_mem": "500MB"}
SEED = 2**31 + 7
MESHES = [1, 2, 4, 8]


def _load_query():
    path = Path(__file__).resolve().parents[1] / "benchmark/queries/vorticity_mean_exact.py"
    spec = importlib.util.spec_from_file_location("bench_vorticity_mean_exact", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


query = _load_query()


@pytest.fixture
def fresh_caches(monkeypatch):
    monkeypatch.setattr(jx, "_SEGMENT_CACHE", {})
    monkeypatch.setattr(jx, "_STRUCT_CACHE", {})


def _spec(tmp_path):
    return ct.Spec(work_dir=str(tmp_path), allowed_mem=DEPLOY["allowed_mem"], reserved_mem=0)


def _mesh(n):
    return make_mesh(devices=jax.devices()[:n])


def _compute(tmp_path, executor, deploy=DEPLOY):
    expr = query.build(deploy, {"seed": SEED}, _spec(tmp_path), None)
    return query.run(expr, executor, None, None), executor


@pytest.fixture(scope="module")
def reference():
    return query.reference_mean(DEPLOY, SEED)


@pytest.mark.parametrize("n", MESHES)
def test_mesh_result_agrees_with_the_blockwise_reference(tmp_path, reference, n):
    result, ex = _compute(tmp_path, JaxExecutor(mesh=_mesh(n)))
    query.check(DEPLOY, {"seed": SEED}, result, None, None, True)
    assert query.compare(result, reference) <= query.RELATIVE_TOLERANCE
    assert ex.stats["segments_traced"] == 1 and ex.stats["mesh_devices"] == n
    assert not ex.stats.get("eager_fallbacks") and not ex.stats.get("host_kernel_ops")


@pytest.mark.parametrize("n", MESHES)
def test_mesh_result_agrees_with_the_python_executor_on_the_threefry_stream(tmp_path, n):
    # under a mesh the executor forces threefry; the Python executor would
    # otherwise draw Philox for blocks this small
    with cubed_tpu.random._mode_scope("threefry"):
        expected, _ = _compute(tmp_path / "py", PythonDagExecutor())
    result, _ = _compute(tmp_path / "jax", JaxExecutor(mesh=_mesh(n)))
    np.testing.assert_allclose(result, float(expected), rtol=1e-13, atol=0)


def test_a_float32_run_fails_the_check(tmp_path):
    result, _ = _compute(tmp_path, JaxExecutor(mesh=_mesh(4), compute_dtype="float32"))
    assert abs(result - 0.5) < 0.05  # a mean all the same, and not the reference's
    with pytest.raises(AssertionError, match="blockwise reference"):
        query.check(DEPLOY, {"seed": SEED}, float(result), None, None, True)


def test_a_run_that_leaves_one_slab_out_fails_the_check(tmp_path):
    # the same four arrays, reduced without the slab of axis 2 that the last
    # of four chips holds, and divided by the smaller count
    random.seed(SEED)
    a, b, x, y = (
        cubed_tpu.random.random(tuple(DEPLOY["shape"]), chunks=DEPLOY["chunks"],
                                spec=_spec(tmp_path))
        for _ in range(4)
    )
    whole = xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:]))
    result = float(xp.mean(whole[:, :, :30]).compute(executor=JaxExecutor(mesh=_mesh(4))))
    with pytest.raises(AssertionError, match="blockwise reference"):
        query.check(DEPLOY, {"seed": SEED}, result, None, None, True)


@pytest.mark.parametrize("shape", [(500, 900, 800), (499, 900, 800)])
def test_the_cell_s_grid_puts_both_mesh_factors_on_axis_2(shape):
    # the grid of 5 x 9 x 8 blocks: only axis 2 divides by 2 and by 4 in whole
    # chunks, so each chip holds a slab of 200 of it, two chunks wide
    mesh = factorized_mesh(_mesh(4))
    assert mesh.devices.shape == (2, 2)
    chunkset = blockdims_from_blockshape(shape, (100, 100, 100))
    assert tuple(len(c) for c in chunkset) == (5, 9, 8)
    sharding = sharding_for_chunks(mesh, chunkset, shape)
    assert tuple(sharding.spec) == (None, None, ("f0", "f1"))
    assert sharding.shard_shape(shape) == (shape[0], 900, 200)


def test_counters_are_equal_on_a_structural_miss_and_the_hit_after_it(tmp_path, fresh_caches):
    _, miss = _compute(tmp_path / "a", JaxExecutor(mesh=_mesh(4)))
    _, hit = _compute(tmp_path / "b", JaxExecutor(mesh=_mesh(4)))
    assert miss.stats["segments_compiled"] == 1 and not miss.stats.get("segment_struct_hits")
    assert hit.stats["segment_struct_hits"] == 1 and not hit.stats.get("segments_compiled")
    for name in ("mesh_devices", *jx._MESH_COUNTERS, "segment_hbm_footprint"):
        assert name in miss.stats and miss.stats[name] == hit.stats[name], name
    kinds = [n for n in jx._MESH_COUNTERS if n.startswith("segment_") and n != "segment_collectives"]
    assert len(kinds) == 4
    assert miss.stats["segment_collectives"] == sum(miss.stats[k] for k in kinds) > 0
    # the generated arrays and their slices divide; nothing of size is replicated
    assert miss.stats["sharded_bytes"] >= 4 * 8 * 50 * 30 * 40
    assert miss.stats["replicated_bytes"] < 1024


def test_without_a_mesh_the_counters_are_there_and_read_nothing(tmp_path, fresh_caches):
    _, ex = _compute(tmp_path, JaxExecutor())
    assert ex.stats["segments_traced"] == 1
    for name in ("mesh_devices", *jx._MESH_COUNTERS):
        assert name in ex.stats and ex.stats[name] == 0, name


def test_a_grid_nothing_divides_is_counted_as_replicated(tmp_path, fresh_caches):
    # 5 x 3 x 7 values in blocks of one: no axis divides by 2, so every chip
    # holds every generated array whole, and nothing but the counter says so
    # (their slices a[1:], of 4 x 3 x 7, divide along axis 0)
    deploy = {"shape": [5, 3, 7], "chunks": 1, "allowed_mem": "500MB"}
    result, ex = _compute(tmp_path, JaxExecutor(mesh=_mesh(4)), deploy)
    assert query.compare(result, query.reference_mean(deploy, SEED)) <= query.RELATIVE_TOLERANCE
    assert ex.stats["replicated_bytes"] >= 4 * 8 * 5 * 3 * 7


def test_each_chip_keeps_its_slab_no_chunk_changes_chips(tmp_path, fresh_caches):
    # the cell's own grid of 5 x 9 x 8 blocks at a tenth of its extents: the
    # generated chunks are not gathered, stacked and reassembled across chips
    deploy = {"shape": [50, 90, 80], "chunks": 10, "allowed_mem": "500MB"}
    result, ex = _compute(tmp_path, JaxExecutor(mesh=_mesh(4)), deploy)
    assert query.compare(result, query.reference_mean(deploy, SEED)) <= query.RELATIVE_TOLERANCE
    assert ex.stats["segment_all_to_all"] == 0 and ex.stats["segment_all_gather"] == 0
    assert 0 < ex.stats["segment_collectives"] < 200
    assert ex.stats["batched_ops"] == 5 and ex.stats["replicated_bytes"] < 1024


@pytest.mark.parametrize(
    "coords,expected",
    [
        ([(1, 0), (1, 1), (2, 0), (2, 1)], ((1, 0), (2, 2))),
        ([(0, 3)], ((0, 3), (1, 1))),
        ([(1, 1), (1, 0), (2, 0), (2, 1)], None),  # not in C order
        ([(0, 0), (0, 2)], None),  # a gap
        ([(0, 0), (0, 1), (1, 0)], None),  # not a product
    ],
)
def test_dense_subgrid(coords, expected):
    assert jx._dense_subgrid(coords) == expected


def test_merge_grid_undoes_gather_subgrid():
    value = np.arange(6 * 8 * 4, dtype=np.float64).reshape(6, 8, 4)
    chunkset = ((2, 2, 2), (4, 4), (1, 1, 1, 1))
    coords = [(i, j, k) for i in (1, 2) for j in (0, 1) for k in (0, 1, 2, 3)]
    blocks = jx._gather_subgrid(jax.numpy.asarray(value), chunkset, coords, keep_grid=True)
    assert blocks.shape == (2, 2, 4, 2, 4, 1)
    flat = jx._gather_subgrid(jax.numpy.asarray(value), chunkset, coords)
    np.testing.assert_array_equal(np.asarray(blocks).reshape(flat.shape), np.asarray(flat))
    np.testing.assert_array_equal(np.asarray(jx._merge_grid(blocks, 3)), value[2:6])
