"""Mesh-sharded execution tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp


def _cpu_devices():
    import jax

    try:
        return jax.devices("cpu")
    except RuntimeError:
        return []


needs_8 = pytest.mark.skipif(
    len(_cpu_devices()) < 8, reason="needs 8 virtual CPU devices"
)


@pytest.fixture
def mesh():
    from cubed_tpu.parallel.mesh import make_mesh

    return make_mesh(shape=(8,), axis_names=("data",), devices=_cpu_devices()[:8])


@pytest.fixture
def mesh_executor(mesh):
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    return JaxExecutor(mesh=mesh)


@needs_8
def test_sharded_elementwise(spec, mesh_executor):
    an = np.arange(16.0 * 24).reshape(16, 24)
    a = ct.from_array(an, chunks=(2, 6), spec=spec)
    b = ct.from_array(an, chunks=(2, 6), spec=spec)
    c = xp.add(xp.multiply(a, 2.0), b)
    np.testing.assert_allclose(c.compute(executor=mesh_executor), an * 3.0)


@needs_8
def test_sharded_reduction(spec, mesh_executor):
    an = np.arange(16.0 * 24).reshape(16, 24)
    a = ct.from_array(an, chunks=(2, 6), spec=spec)
    s = xp.sum(a, axis=0)
    np.testing.assert_allclose(s.compute(executor=mesh_executor), an.sum(axis=0))
    m = xp.mean(a)
    np.testing.assert_allclose(m.compute(executor=mesh_executor), an.mean())


@needs_8
def test_sharded_rechunk_is_reshard(spec, mesh_executor):
    an = np.arange(16.0 * 24).reshape(16, 24)
    a = ct.from_array(an, chunks=(2, 24), spec=spec)
    b = a.rechunk((16, 3))
    np.testing.assert_allclose(b.compute(executor=mesh_executor), an)


@needs_8
def test_sharded_matmul(spec, mesh_executor):
    rng = np.random.default_rng(0)
    an = rng.random((16, 24))
    bn = rng.random((24, 8))
    a = ct.from_array(an, chunks=(8, 12), spec=spec)
    b = ct.from_array(bn, chunks=(12, 8), spec=spec)
    np.testing.assert_allclose(
        xp.matmul(a, b).compute(executor=mesh_executor), an @ bn, rtol=1e-12
    )


@needs_8
def test_sharded_vorticity_pipeline(spec, mesh_executor):
    import cubed_tpu.random

    shape = (16, 16, 16)
    a = cubed_tpu.random.random(shape, chunks=8, spec=spec)
    b = cubed_tpu.random.random(shape, chunks=8, spec=spec)
    r = xp.mean(xp.add(xp.multiply(a[1:], 2.0), xp.multiply(b[1:], 3.0)))
    val = float(r.compute(executor=mesh_executor))
    assert 2.0 < val < 3.0  # 2*U + 3*U has mean 2.5


def test_spill_to_storage(spec):
    """With a tiny device budget, residents spill to zarr and results stay right."""
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    an = np.arange(64.0 * 64).reshape(64, 64)
    a = ct.from_array(an, chunks=(16, 16), spec=spec)
    b = xp.add(a, 1.0)
    c = xp.multiply(b, 2.0)
    d = b.rechunk((32, 32))
    e = xp.add(c, d)
    # budget smaller than one array: everything evicts constantly
    ex = JaxExecutor(device_mem=20_000)
    np.testing.assert_allclose(
        e.compute(executor=ex), (an + 1) * 2 + (an + 1)
    )


def test_sharding_for_chunks():
    from cubed_tpu.parallel.mesh import make_mesh, sharding_for_chunks

    devs = _cpu_devices()
    if len(devs) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(shape=(8,), devices=devs[:8])
    sharding = sharding_for_chunks(mesh, ((2,) * 8, (6,) * 4), (16, 24))
    spec_dims = sharding.spec
    assert spec_dims[0] == "data"  # most blocks and divisible


def test_prime_factors():
    from cubed_tpu.parallel.mesh import prime_factors

    assert prime_factors(8) == [2, 2, 2]
    assert prime_factors(12) == [2, 2, 3]
    assert prime_factors(7) == [7]
    assert prime_factors(1) == []


@needs_8
def test_factorized_mesh_shards_odd_shapes():
    # the vorticity slice: (499, 450, 400) replicated under a 1-d 8-mesh
    # because no dim divides by 8; the factorized (2,2,2) placement shards it
    # 8-way across two dims
    from cubed_tpu.parallel.mesh import (
        factorized_mesh,
        make_mesh,
        sharding_for_chunks,
    )

    mesh = make_mesh(shape=(8,), devices=_cpu_devices()[:8])
    fmesh = factorized_mesh(mesh)
    assert fmesh.devices.shape == (2, 2, 2)

    shape = (499, 450, 400)
    chunkset = tuple(
        tuple(min(100, s - i) for i in range(0, s, 100)) for s in shape
    )
    sharding = sharding_for_chunks(fmesh, chunkset, shape)
    shard_shape = sharding.shard_shape(shape)
    # fully 8-way sharded: each shard holds 1/8 of the elements
    import math

    assert math.prod(shard_shape) * 8 == math.prod(shape)


@needs_8
def test_sharding_for_chunks_2d_mesh_uneven_grid():
    from cubed_tpu.parallel.mesh import make_mesh, sharding_for_chunks

    mesh = make_mesh(shape=(4, 2), axis_names=("a", "b"), devices=_cpu_devices()[:8])
    # ragged chunk grid: 19 = 5+5+5+4 blocks of chunk 5; both dims uneven
    sharding = sharding_for_chunks(mesh, ((5, 5, 5, 4), (6, 6, 2)), (19, 14))
    # 19 is prime (no axis divides); 14 % 2 == 0 -> 'b' lands on dim 1
    assert sharding.spec[1] == "b" or sharding.spec[1] == ("b",)
    assert sharding.spec[0] is None


@needs_8
def test_sharded_execution_nondivisible_shape(spec, mesh_executor):
    # shape with no dim divisible by 8: the factorized placement mesh must
    # still shard it AND produce correct results
    an = np.arange(34.0 * 12).reshape(34, 12)
    a = ct.from_array(an, chunks=(8, 6), spec=spec)
    b = ct.from_array(an, chunks=(8, 6), spec=spec)
    out = xp.sum(xp.add(xp.multiply(a, 2.0), b))
    np.testing.assert_allclose(
        float(out.compute(executor=mesh_executor)), (an * 3.0).sum()
    )


@needs_8
def test_executor_uses_mesh_policy(mesh_executor):
    # the executor must delegate to parallel.mesh.sharding_for_chunks (one
    # policy); (34, 12) has no dim divisible by 8 but shards 8-way factorized
    s = mesh_executor._sharding_for((34, 12))
    assert s is not None
    import math

    assert math.prod(s.shard_shape((34, 12))) * 8 == 34 * 12
