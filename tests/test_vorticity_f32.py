"""``random(dtype=float32)`` through the normal path, and the vorticity
reduction over it: the documented per-block stream on every generation
route, the float64 stream left where it was, the plan's dtype and memory,
the value against a reference that uses nothing of ``cubed_tpu``, and the
counters that say what precision a compute ran in."""

import hashlib
import itertools
import math
import os
import random as pyrandom
import subprocess
import sys

import numpy as np
import pytest

import cubed_tpu.array_api as xp
import cubed_tpu.random
from cubed_tpu.observability.collect import TraceCollector
from cubed_tpu.runtime.executors.jax import JaxExecutor
from cubed_tpu.runtime.executors.python import PythonDagExecutor

SEED = 2**31 + 7
#: ragged on every axis: edge blocks of 3, 1 and 1
SHAPE, CHUNKS = (11, 7, 5), (4, 3, 2)
#: sha256 of the float64 array of ``SHAPE`` in ``CHUNKS`` whose root seed is
#: the first 30 bits of ``random.seed(SEED)``, as the parent commit (PR 33)
#: generates it, by route; first and last value beside it
PARENTS_FLOAT64 = {
    "philox": ("5ce2e8fa7525aeec43fe9c3fe13a8d9f7407702c43b5c4b15ab2e8bb76685c7d",
               "0x1.2d6fec2806f12p-2", "0x1.139f765531531p-1"),
    "threefry": ("1972629245c2c38050115a15aeefabb050ebdcf0f3b1ca2804747cc3c3d08c61",
                 "0x1.3dfd4ec63db80p-2", "0x1.315b60b1b7b64p-2"),
}


@pytest.fixture
def route(request, monkeypatch):
    """Pin the generation route. A kernel's route is chosen while it is
    traced and jax keeps that trace by function and shapes, so the traces of
    another route are dropped before and after."""
    import jax

    jax.clear_caches()
    monkeypatch.setenv("CUBED_TPU_RNG", request.param)
    yield request.param
    jax.clear_caches()


def _blocks(shape, chunks):
    """``(k, slices)`` of every block, k counted in C order."""
    edges = [[(lo, min(lo + c, n)) for lo in range(0, n, c)] for n, c in zip(shape, chunks)]
    for k, bounds in enumerate(itertools.product(*edges)):
        yield k, tuple(slice(lo, hi) for lo, hi in bounds)


def _documented(route, root, shape, chunks, dtype):
    """The documented stream whole, with numpy or ``jax.random`` alone."""
    import jax

    out = np.empty(shape, dtype)
    with jax.threefry_partitionable(True):
        for k, sel in _blocks(shape, chunks):
            block = out[sel].shape
            if route == "philox":
                rng = np.random.Generator(np.random.Philox(seed=root + k))
                out[sel] = rng.random(block, dtype=dtype)
            else:
                key = jax.random.fold_in(jax.random.key(0), root + k)
                out[sel] = np.asarray(jax.random.uniform(key, block, dtype=dtype))
    return out


def _root():
    pyrandom.seed(SEED)
    root = pyrandom.getrandbits(30)
    pyrandom.seed(SEED)
    return root


# -- the stream ---------------------------------------------------------------


@pytest.mark.parametrize("route", ["philox", "threefry"], indirect=True)
@pytest.mark.parametrize("executor", [JaxExecutor, PythonDagExecutor], ids=["jax", "python"])
def test_float32_blocks_are_the_documented_stream_bit_for_bit(spec, route, executor):
    root = _root()
    a = cubed_tpu.random.random(SHAPE, chunks=CHUNKS, spec=spec, dtype=np.float32)
    x = a.compute(executor=executor())
    assert x.dtype == np.float32 and x.shape == SHAPE
    expected = _documented(route, root, SHAPE, CHUNKS, np.float32)
    assert x.tobytes() == expected.tobytes()
    assert (x >= 0).all() and (x < 1).all() and len(np.unique(x)) > 300
    # not the float64 stream rounded: each width draws its own
    wide = _documented(route, root, SHAPE, CHUNKS, np.float64)
    assert not np.array_equal(x, wide.astype(np.float32))


@pytest.mark.parametrize("route", ["philox", "threefry"], indirect=True)
@pytest.mark.parametrize("executor", [JaxExecutor, PythonDagExecutor], ids=["jax", "python"])
def test_float64_stream_is_the_parents_to_the_bit(spec, route, executor):
    root = _root()
    a = cubed_tpu.random.random(SHAPE, chunks=CHUNKS, spec=spec)
    x = a.compute(executor=executor())
    assert x.dtype == np.float64
    digest, first, last = PARENTS_FLOAT64[route]
    assert (float(x[0, 0, 0]).hex(), float(x[-1, -1, -1]).hex()) == (first, last)
    assert hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest() == digest
    assert x.tobytes() == _documented(route, root, SHAPE, CHUNKS, np.float64).tobytes()


def test_numpy_backend_draws_the_philox_stream_in_both_widths(tmp_path):
    code = (
        "import random, sys, numpy as np\n"
        "import cubed_tpu as ct, cubed_tpu.random\n"
        f"spec = ct.Spec(work_dir={str(tmp_path)!r}, allowed_mem='500MB')\n"
        "for dtype in (np.float32, np.float64):\n"
        f"    random.seed({SEED})\n"
        f"    a = cubed_tpu.random.random({SHAPE}, chunks={CHUNKS}, spec=spec, dtype=dtype)\n"
        "    x = a.compute()\n"
        "    print(x.dtype, np.ascontiguousarray(x).tobytes().hex())\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
        cwd=repo, env=dict(os.environ, CUBED_TPU_BACKEND="numpy", JAX_PLATFORMS="cpu"),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [line.split() for line in done.stdout.splitlines() if line.startswith("float")]
    root = _root()
    assert [name for name, _ in lines] == ["float32", "float64"]
    for name, payload in lines:
        expected = _documented("philox", root, SHAPE, CHUNKS, np.dtype(name))
        assert bytes.fromhex(payload) == expected.tobytes()


@pytest.mark.parametrize("dtype", [np.int32, np.float16, "bfloat16", np.complex64, "float33"],
                         ids=str)
def test_a_dtype_random_does_not_draw_raises(spec, dtype):
    with pytest.raises(TypeError, match="float64 or float32"):
        cubed_tpu.random.random((4, 4), chunks=(2, 2), spec=spec, dtype=dtype)


# -- the plan -----------------------------------------------------------------


def test_the_plan_is_float32_and_its_memory_follows(spec):
    single, double = (
        cubed_tpu.random.random((40, 30), chunks=(10, 10), spec=spec, dtype=dtype)
        for dtype in (np.float32, np.float64)
    )
    assert (single.dtype, double.dtype) == (np.float32, np.float64)
    assert single.chunkmem * 2 == double.chunkmem == 10 * 10 * 8
    assert single[1:].dtype == np.float32
    product = xp.multiply(single[1:], single[1:])
    assert product.dtype == np.float32 and xp.mean(product).dtype == np.float32
    # what an op projects follows the chunk's bytes (reserved_mem is 0 here)
    narrow, wide = single.plan.max_projected_mem(), double.plan.max_projected_mem()
    assert 0 < narrow < wide
    # four chunk-sized buffers an op, and the seeds' 16 bytes on both sides
    assert (narrow - 16) * 2 == wide - 16 == 4 * double.chunkmem


# -- the vorticity reduction ----------------------------------------------------


def _vorticity(spec, dtype, shape=(50, 40, 30), chunks=10):
    pyrandom.seed(SEED)
    a, b, x, y = (
        cubed_tpu.random.random(shape, chunks=chunks, spec=spec, dtype=dtype)
        for _ in range(4)
    )
    return xp.mean(xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:])))


def _exact(shape=(50, 40, 30), chunks=(10, 10, 10)):
    """The mean with numpy alone, on the CPU's documented stream: float32
    products and sums of two, every product converted to float64 and summed
    with ``math.fsum``, the quotient rounded once to float32."""
    rng = pyrandom.Random(SEED)
    a, b, x, y = (
        _documented("philox", rng.getrandbits(30), shape, chunks, np.float32)
        for _ in range(4)
    )
    v = a[1:] * x[1:] + b[1:] * y[1:]
    assert v.dtype == np.float32
    return np.float32(math.fsum(v.astype(np.float64).ravel()) / v.size)


@pytest.mark.parametrize("executor", [
    lambda: JaxExecutor(), lambda: JaxExecutor(fuse_plan=False), lambda: PythonDagExecutor(),
], ids=["jax-fused", "jax-unfused", "python"])
def test_vorticity_in_float32_agrees_with_the_exact_reference(spec, executor):
    result = _vorticity(spec, np.float32).compute(executor=executor())
    assert result.dtype == np.float32 and result.shape == ()
    exact = _exact()
    # the sum is float64 on both sides and differs by its order alone, so
    # the one rounding gives the same float32 or, on a boundary, its neighbour
    distance = abs(int(np.float32(result).view(np.int32)) - int(exact.view(np.int32)))
    assert distance <= 1, (result, exact)
    # and a mean all the same
    assert abs(float(result) - 0.5) < 15 * math.sqrt(7.0 / 72.0 / (49 * 40 * 30))


# -- what precision a compute ran in --------------------------------------------

FLOAT_COUNTERS = ("device_f32_bytes", "device_f64_bytes", "device_f16_bytes")
#: (50, 40, 30) in chunks of 10: four arrays generated and four indexed
GENERATED = 4 * 50 * 40 * 30 + 4 * 49 * 40 * 30


@pytest.mark.parametrize("dtype,options,wide,narrow,widest", [
    (np.float32, {}, "device_f32_bytes", "device_f64_bytes", "float64"),
    (np.float64, {}, "device_f64_bytes", "device_f32_bytes", "float64"),
    (np.float64, {"compute_dtype": "float32"}, "device_f32_bytes", "device_f64_bytes", "float32"),
], ids=["float32-plan", "float64-plan", "float64-plan-under-compute_dtype"])
def test_float_counters_follow_the_traced_dtype_on_a_miss_and_on_a_hit(
    spec, dtype, options, wide, narrow, widest
):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    jxm._SEGMENT_CACHE.clear()
    seen = []
    for _ in range(2):
        executor, collector = JaxExecutor(**options), TraceCollector(trace_dir=None)
        _vorticity(spec, dtype).compute(executor=executor, callbacks=[collector])
        spans = [s for rec in collector._records for s in rec["spans"]]
        (dispatch,) = [s for s in spans if s["name"] == "jax.dispatch"]
        seen.append((dict(executor.stats), dispatch["attrs"]))
    (miss, miss_attrs), (hit, hit_attrs) = seen
    assert miss.get("segment_struct_hits", 0) == 0 and hit["segment_struct_hits"] == 1
    assert (miss_attrs["struct_hit"], hit_attrs["struct_hit"]) == (False, True)
    for stats, attrs in seen:
        assert set(FLOAT_COUNTERS) <= set(stats)  # each present, 0 where nothing counts
        itemsize = 4 if wide == "device_f32_bytes" else 8
        # the elementwise op's and the reduction's outputs come on top
        assert stats[wide] >= GENERATED * itemsize
        assert stats["device_f16_bytes"] == 0
        assert attrs["widest_float"] == widest
    # a hit reports what the miss did
    assert {k: miss[k] for k in FLOAT_COUNTERS} == {k: hit[k] for k in FLOAT_COUNTERS}
    if options:
        assert miss[narrow] == 0  # the plan says float64; nothing ran in it
    elif dtype == np.float32:
        # mean sums in float64 whatever the input: partial sums and the quotient
        assert 0 < miss[narrow] < 0.01 * miss[wide]
    else:
        assert miss[narrow] == 0


def test_float_counters_are_there_and_0_without_a_segment(spec):
    executor = JaxExecutor(fuse_plan=False)
    _vorticity(spec, np.float32).compute(executor=executor)
    assert [executor.stats[k] for k in FLOAT_COUNTERS] == [0, 0, 0]
    assert all(k in executor.stats for k in FLOAT_COUNTERS)


def test_a_float32_and_a_float64_plan_of_one_shape_share_no_program(spec):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    jxm._SEGMENT_CACHE.clear()
    stats = []
    for dtype in (np.float32, np.float64, np.float32):
        executor = JaxExecutor()
        _vorticity(spec, dtype, shape=(30, 20, 20)).compute(executor=executor)
        stats.append(executor.stats)
    first, other_width, again = stats
    assert first["segments_compiled"] >= 1 and other_width["segments_compiled"] >= 1
    assert not other_width.get("segment_struct_hits") and not other_width.get("segment_cache_hits")
    assert again["segment_struct_hits"] == 1 and not again.get("segments_compiled")
