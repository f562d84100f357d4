"""Distributed RNG tests. Reference parity: cubed/tests/test_random.py."""

import numpy as np
import pytest

import cubed_tpu
import cubed_tpu.random


def test_random_basic(spec):
    a = cubed_tpu.random.random((10, 8), chunks=(4, 4), spec=spec)
    x = a.compute()
    assert x.shape == (10, 8)
    assert x.dtype == np.float64
    assert (x >= 0).all() and (x < 1).all()
    # not constant
    assert len(np.unique(x)) > 50


def test_random_deterministic_per_block(spec):
    # the same array computed twice gives identical results (per-block keys)
    a = cubed_tpu.random.random((8, 8), chunks=(4, 4), spec=spec)
    x1 = a.compute()
    x2 = a.compute()
    np.testing.assert_array_equal(x1, x2)


def test_random_different_arrays_differ(spec):
    a = cubed_tpu.random.random((8, 8), chunks=(4, 4), spec=spec)
    b = cubed_tpu.random.random((8, 8), chunks=(4, 4), spec=spec)
    assert not np.array_equal(a.compute(), b.compute())


def test_random_blocks_differ(spec):
    a = cubed_tpu.random.random((8, 8), chunks=(4, 4), spec=spec)
    x = a.compute()
    assert not np.array_equal(x[:4, :4], x[4:, 4:])


def test_partitionable_threefry_pinned():
    """cubed_tpu.random pins jax_threefry_partitionable=True (a different —
    still deterministic — stream than jax's default lowering, chosen for
    TPU generation speed). The flag must be set before any generation and
    never flipped: it is not part of jax's jit cache key, so a mid-process
    flip would silently serve programs with the old lowering."""
    import os

    import pytest

    from cubed_tpu.backend_array_api import BACKEND

    if BACKEND != "jax" or os.environ.get(
        "CUBED_TPU_THREEFRY_PARTITIONABLE", "1"
    ) == "0":
        pytest.skip("flag only pinned on the jax backend without the opt-out")
    import jax

    assert jax.config.jax_threefry_partitionable  # set at import


def test_random_deterministic_across_processes(spec):
    """The stream definition is process-invariant: a fresh interpreter
    generating the same block with the same seed matches this process."""
    import subprocess
    import sys

    code = (
        "import numpy as np, jax, jax.numpy as jnp\n"
        "import cubed_tpu.random  # pins the flag\n"
        "k = jax.random.fold_in(jax.random.key(0), 42)\n"
        "print(repr(np.asarray(jax.random.uniform(k, (4,), jnp.float32)).tolist()))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, check=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    import jax
    import jax.numpy as jnp

    k = jax.random.fold_in(jax.random.key(0), 42)
    here = np.asarray(jax.random.uniform(k, (4,), jnp.float32)).tolist()
    assert eval(out.stdout.strip()) == here


def test_normal(spec):
    a = cubed_tpu.random.normal((40, 30), chunks=(10, 10), spec=spec)
    x = a.compute()
    assert x.shape == (40, 30) and x.dtype == np.float64
    assert abs(x.mean()) < 0.2 and abs(x.std() - 1.0) < 0.2
    np.testing.assert_array_equal(x, a.compute())  # per-block determinism


def test_normal_mean_stddev(spec):
    a = cubed_tpu.random.normal((50, 50), mean=10.0, stddev=3.0,
                                chunks=(20, 20), spec=spec)
    x = a.compute()
    assert abs(x.mean() - 10.0) < 0.5 and abs(x.std() - 3.0) < 0.5


def test_randint(spec):
    a = cubed_tpu.random.randint(5, 15, (30, 30), chunks=(8, 8), spec=spec)
    x = a.compute()
    assert x.dtype == np.int64
    assert x.min() >= 5 and x.max() < 15
    assert len(np.unique(x)) == 10  # all values hit at this size
    np.testing.assert_array_equal(x, a.compute())


def test_randint_validation(spec):
    with pytest.raises(ValueError):
        cubed_tpu.random.randint(5, 5, (4,), chunks=(2,), spec=spec)


def test_normal_negative_stddev_rejected(spec):
    with pytest.raises(ValueError, match="non-negative"):
        cubed_tpu.random.normal((4,), stddev=-1.0, chunks=(2,), spec=spec)


# ---------------------------------------------------------------------------
# backend-appropriate generation routing (CUBED_TPU_RNG / generation_mode)


def _philox_expected(shape, chunks, root):
    """The numpy-backend oracle stream: Philox(root + linear block offset)."""
    nb = [-(-s // c) for s, c in zip(shape, chunks)]
    exp = np.empty(shape)
    for bi in range(nb[0]):
        for bj in range(nb[1]):
            off = root + bi * nb[1] + bj
            rng = np.random.Generator(np.random.Philox(seed=off))
            block = rng.random(
                (min(chunks[0], shape[0] - bi * chunks[0]),
                 min(chunks[1], shape[1] - bj * chunks[1])),
                dtype=np.float64,
            )
            exp[bi * chunks[0]:bi * chunks[0] + block.shape[0],
                bj * chunks[1]:bj * chunks[1] + block.shape[1]] = block
    return exp


def _jax_backend_or_skip():
    from cubed_tpu.backend_array_api import BACKEND

    if BACKEND != "jax":
        pytest.skip("generation routing is a jax-backend feature")


def test_auto_cpu_matches_numpy_philox_oracle(spec):
    """On CPU (the test platform) auto mode generates small blocks with
    the numpy Philox stream keyed by root + linear block offset — exactly
    the numpy-backend oracle's (and the reference's, cubed/random.py:
    13-36) stream, so cross-backend differential comparisons see
    identical values, and the CPU path gets numpy's generation rate
    instead of XLA-CPU threefry."""
    _jax_backend_or_skip()
    import random as pyrandom

    from cubed_tpu.runtime.executors.jax import JaxExecutor

    pyrandom.seed(1234)
    a = cubed_tpu.random.random((8, 6), chunks=(4, 3), spec=spec)
    x = a.compute(executor=JaxExecutor())
    pyrandom.seed(1234)
    root = pyrandom.getrandbits(30)
    np.testing.assert_array_equal(x, _philox_expected((8, 6), (4, 3), root))
    # the per-op oracle executor resolves the same mode: identical values
    np.testing.assert_array_equal(x, a.compute())


def test_generation_mode_resolution(monkeypatch):
    """Executor scope (mesh correctness) > env pin > platform auto with
    block-size threshold."""
    _jax_backend_or_skip()
    import cubed_tpu.random as ctr

    monkeypatch.delenv("CUBED_TPU_RNG", raising=False)
    assert ctr.generation_mode(8) == "philox"  # tiny block, cpu platform
    assert ctr.generation_mode(1 << 40) == "threefry"  # above threshold
    assert ctr.generation_mode().startswith("auto-cpu")  # policy string
    with ctr._mode_scope("threefry"):
        assert ctr.generation_mode(8) == "threefry"  # mesh-style override
    assert ctr.generation_mode(8) == "philox"  # scope restored
    monkeypatch.setenv("CUBED_TPU_RNG", "philox")
    assert ctr.generation_mode(1 << 40) == "philox"  # env pin beats size
    with ctr._mode_scope("threefry"):
        # the mesh-correctness scope outranks even an explicit philox pin
        # (callbacks don't partition across an SPMD program)
        assert ctr.generation_mode(8) == "threefry"
    monkeypatch.setenv("CUBED_TPU_RNG", "Philox")  # case-normalized
    assert ctr.generation_mode(1 << 40) == "philox"
    monkeypatch.setenv("CUBED_TPU_RNG", "phlox")
    with pytest.raises(ValueError, match="CUBED_TPU_RNG"):
        ctr.generation_mode(8)


def test_threshold_routes_large_blocks_to_threefry(spec, monkeypatch):
    """Blocks above _PHILOX_MAX_BLOCK_BYTES generate with fused threefry
    even in auto mode on CPU (the callback's materialization cost crosses
    over at large blocks) — pinned by shrinking the threshold so every
    block is 'large' and comparing against the env-pinned threefry
    stream."""
    _jax_backend_or_skip()
    import random as pyrandom

    import cubed_tpu.random as ctr
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    monkeypatch.setattr(ctr, "_PHILOX_MAX_BLOCK_BYTES", 1)
    pyrandom.seed(99)
    a = ctr.random((8, 6), chunks=(4, 3), spec=spec)
    x_routed = a.compute(executor=JaxExecutor())

    monkeypatch.setenv("CUBED_TPU_RNG", "threefry")
    pyrandom.seed(99)
    b = ctr.random((8, 6), chunks=(4, 3), spec=spec)
    np.testing.assert_array_equal(x_routed, b.compute(executor=JaxExecutor()))


def test_mesh_executor_forces_threefry(spec, monkeypatch):
    """Under a device mesh the executor pins threefry (the Philox
    pure_callback path doesn't partition across an SPMD program): values
    match the env-pinned threefry stream, not the CPU auto stream."""
    _jax_backend_or_skip()
    import random as pyrandom

    import jax
    from jax.sharding import Mesh

    from cubed_tpu.runtime.executors.jax import JaxExecutor

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("d",))
    pyrandom.seed(7)
    a = cubed_tpu.random.random((8, 6), chunks=(4, 3), spec=spec)
    x_mesh = a.compute(executor=JaxExecutor(mesh=mesh))

    monkeypatch.setenv("CUBED_TPU_RNG", "threefry")
    pyrandom.seed(7)
    b = cubed_tpu.random.random((8, 6), chunks=(4, 3), spec=spec)
    x_pinned = b.compute(executor=JaxExecutor())
    np.testing.assert_array_equal(x_mesh, x_pinned)
