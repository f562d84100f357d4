"""Plan-fusion (traced segment) behavior of the JAX executor.

The fused path must be an invisible optimization: results identical to
``fuse_plan=False`` (per-op eager execution) across representative plan
shapes, including the ones that exercise segment boundaries (storage-reading
map_direct bodies, large host sources) and in-segment fast paths (rechunk
alias, whole-array elementwise, bucketed ragged grids, RNG seed hoisting).
"""

from __future__ import annotations

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
import cubed_tpu.random
from cubed_tpu.runtime.executors.jax import JaxExecutor


@pytest.fixture
def spec(tmp_path):
    return ct.Spec(work_dir=str(tmp_path), allowed_mem="500MB", reserved_mem=0)


def _both(arr):
    fused = arr.compute(executor=JaxExecutor(fuse_plan=True))
    eager = arr.compute(executor=JaxExecutor(fuse_plan=False))
    return np.asarray(fused), np.asarray(eager)


def test_fused_elementwise_chain(spec):
    an = np.arange(64, dtype=np.float64).reshape(8, 8)
    a = ct.from_array(an, chunks=(4, 4), spec=spec)
    b = ct.from_array(an, chunks=(4, 4), spec=spec)
    fused, eager = _both(xp.add(xp.multiply(a, 2.0), b))
    np.testing.assert_allclose(fused, an * 2 + an)
    np.testing.assert_allclose(eager, an * 2 + an)


def test_fused_reduction_tree(spec):
    an = np.arange(400, dtype=np.float64).reshape(20, 20)
    a = ct.from_array(an, chunks=(4, 4), spec=spec)
    fused, eager = _both(xp.mean(a, axis=0))
    np.testing.assert_allclose(fused, an.mean(axis=0))
    np.testing.assert_allclose(eager, an.mean(axis=0))


def test_fused_ragged_grid_and_index(spec):
    an = np.arange(19 * 13, dtype=np.float64).reshape(19, 13)
    a = ct.from_array(an, chunks=(5, 4), spec=spec)  # ragged both dims
    fused, eager = _both(xp.sum(a[1:, ::2]))
    np.testing.assert_allclose(fused, an[1:, ::2].sum())
    np.testing.assert_allclose(eager, an[1:, ::2].sum())


def test_fused_rechunk_alias(spec):
    an = np.arange(64, dtype=np.float64).reshape(8, 8)
    a = ct.from_array(an, chunks=(2, 8), spec=spec)
    fused, eager = _both(xp.sum(a.rechunk((8, 2))))
    np.testing.assert_allclose(fused, an.sum())
    np.testing.assert_allclose(eager, an.sum())


def test_fused_random_seed_hoisting(spec):
    # two plans with different seeds must produce different data through the
    # SAME traced program structure (the seed is an input, not a constant)
    r1 = float(
        xp.mean(cubed_tpu.random.random((32, 32), chunks=8, spec=spec)).compute(
            executor=JaxExecutor()
        )
    )
    r2 = float(
        xp.mean(cubed_tpu.random.random((32, 32), chunks=8, spec=spec)).compute(
            executor=JaxExecutor()
        )
    )
    assert 0.3 < r1 < 0.7 and 0.3 < r2 < 0.7
    assert r1 != r2  # different seeds -> different arrays


def test_fused_segment_boundary_concat(spec):
    # concat declares whole_concat: with resident sources it becomes one
    # device concatenate INSIDE the traced segment (no eager boundary)
    an = np.arange(24, dtype=np.float64).reshape(4, 6)
    a = ct.from_array(an, chunks=(2, 3), spec=spec)
    b = ct.from_array(an + 1, chunks=(2, 3), spec=spec)
    fused, eager = _both(xp.sum(xp.concat([xp.multiply(a, 2.0), b], axis=0)))
    expect = np.concatenate([an * 2, an + 1], axis=0).sum()
    np.testing.assert_allclose(fused, expect)
    np.testing.assert_allclose(eager, expect)


def test_var_multiaxis_region_combine(spec):
    """var/std with axis=None over a multi-chunk 2-d grid: the executor's
    region combine hands _var_combine a MULTI-AXIS block region in one call
    (regression: it reduced only axis 0, silently corrupting the result —
    found by the differential fuzzer)."""
    an = np.asarray([[0.0, 1.0], [1.0, 1.0]])
    a = ct.from_array(an, chunks=(1, 1), spec=spec)  # 4 single-element blocks
    got = float(xp.var(a).compute(executor=JaxExecutor()))
    np.testing.assert_allclose(got, an.var())
    an2 = np.random.default_rng(0).random((6, 9))
    b = ct.from_array(an2, chunks=(2, 3), spec=spec)
    np.testing.assert_allclose(
        float(xp.std(b).compute(executor=JaxExecutor())), an2.std(), rtol=1e-12
    )


def test_segment_task_events_partition_wall_time(spec):
    """Per-op TaskEndEvents of a fused segment must PARTITION the segment's
    wall time (contiguous, non-overlapping, summing to the total) — not each
    span the whole segment (which over-reports history totals len(ops)x)."""
    from cubed_tpu.runtime.types import Callback

    events = []

    class Capture(Callback):
        def on_task_end(self, event):
            events.append(event)

    an = np.arange(400, dtype=np.float64).reshape(20, 20)
    a = ct.from_array(an, chunks=(4, 4), spec=spec)
    xp.mean(xp.multiply(a, 2.0)).compute(
        executor=JaxExecutor(), callbacks=[Capture()]
    )
    assert len(events) >= 2
    spans = sorted(
        (e.function_start_tstamp, e.function_end_tstamp) for e in events
    )
    for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
        assert e1 <= s2 + 1e-9  # non-overlapping
    total = sum(e - s for s, e in spans)
    wall = max(e for _, e in spans) - min(s for s, _ in spans)
    assert total <= wall + 1e-6  # durations sum to (at most) the wall time


@pytest.mark.parametrize(
    "name",
    ["stack", "reshape", "broadcast_to", "eye", "flip", "repeat", "concat"],
)
def test_op_families_trace_without_fallback(name, spec):
    """These plan shapes must all run as traced segments — a regression here
    silently costs the eager path's per-op overhead."""
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()  # a struct hit would skip tracing legitimately
    an = np.arange(24, dtype=np.float64).reshape(4, 6)
    a = ct.from_array(an, chunks=(2, 3), spec=spec)
    b = ct.from_array(an + 1, chunks=(2, 3), spec=spec)
    exprs = {
        "stack": (xp.sum(xp.stack([a, b], axis=0)), an.sum() + (an + 1).sum()),
        "reshape": (xp.sum(xp.reshape(a, (24,))), an.sum()),
        "broadcast_to": (xp.sum(xp.broadcast_to(a, (3, 4, 6))), 3 * an.sum()),
        "eye": (xp.sum(xp.eye(7, chunks=3, spec=spec)), 7.0),
        "flip": (xp.sum(xp.flip(a, axis=0)), an.sum()),
        "repeat": (xp.sum(xp.repeat(a, 2, axis=1)), 2 * an.sum()),
        "concat": (xp.sum(xp.concat([a, b], axis=0)), an.sum() + (an + 1).sum()),
    }
    expr, expect = exprs[name]
    ex = JaxExecutor()
    val = float(expr.compute(executor=ex))
    np.testing.assert_allclose(val, expect)
    assert ex.stats["segments_traced"] >= 1
    assert ex.stats["trace_failures"] == 0
    assert ex.stats["eager_fallbacks"] == 0


def test_concat_traces_into_one_segment(spec):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    an = np.arange(24, dtype=np.float64).reshape(4, 6)
    a = ct.from_array(an, chunks=(2, 3), spec=spec)
    b = ct.from_array(an + 1, chunks=(2, 3), spec=spec)
    s = xp.sum(xp.concat([xp.multiply(a, 2.0), b], axis=1))
    ex = JaxExecutor()
    val = float(s.compute(executor=ex))
    np.testing.assert_allclose(val, np.concatenate([an * 2, an + 1], axis=1).sum())
    assert ex.stats["segments_traced"] == 1  # one fused program, no break
    assert ex.stats["whole_concat_hits"] >= 1
    assert ex.stats["eager_fallbacks"] == 0
    assert ex.stats["trace_failures"] == 0


def test_fused_structured_mean_intermediates(spec):
    # mean uses dict-of-arrays ({n, total}) intermediates through the tree
    an = np.arange(100, dtype=np.float64).reshape(10, 10)
    a = ct.from_array(an, chunks=(3, 3), spec=spec)
    fused, eager = _both(xp.mean(a))
    np.testing.assert_allclose(fused, an.mean())
    np.testing.assert_allclose(eager, an.mean())


# ---------------------------------------------------------------------------
# executor stats: the fast paths must be *observably* taken. A silently broken
# fast path costs 10x quietly; these pins make it fail a test instead.
# ---------------------------------------------------------------------------


def test_stats_fused_elementwise_counts(spec):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()  # force a real trace so path counters fire
    an = np.arange(64, dtype=np.float64).reshape(8, 8)
    a = ct.from_array(an, chunks=(4, 4), spec=spec)
    b = ct.from_array(an, chunks=(4, 4), spec=spec)
    ex = JaxExecutor()
    result = xp.add(xp.multiply(a, 2.0), b).compute(executor=ex)
    np.testing.assert_allclose(np.asarray(result), an * 2 + an)
    assert ex.stats["segments_traced"] == 1
    assert ex.stats["trace_failures"] == 0
    assert ex.stats["eager_fallbacks"] == 0
    # the fused op must take a vectorized path, never per-chunk dispatch
    assert ex.stats["batched_ops"] + ex.stats["whole_array_hits"] >= 1
    assert ex.stats["chunked_ops"] == 0


def test_stats_vorticity_plan_fully_fused(spec):
    # the benchmark plan shape (bench.py WORKLOAD) at test size: the whole
    # pipeline must run as ONE traced segment with zero eager fallbacks
    def rnd():
        return cubed_tpu.random.random((12, 10, 8), chunks=4, spec=spec)

    a, b, x, y = rnd(), rnd(), rnd(), rnd()
    s = xp.mean(xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:])))
    ex = JaxExecutor()
    val = float(s.compute(executor=ex))
    assert 0.0 < val < 1.0
    assert ex.stats["segments_traced"] == 1
    assert ex.stats["trace_failures"] == 0
    assert ex.stats["eager_fallbacks"] == 0
    assert ex.stats["host_kernel_ops"] == 0


def test_stats_segment_cache_hit_on_recompute(spec):
    # same plan structure twice: the second compute reuses the compiled
    # executable — via the structural fingerprint (no re-trace) or, with the
    # structural layer disabled, via the HLO hash (re-trace, no re-compile)
    an = np.arange(36, dtype=np.float64).reshape(6, 6)

    def build():
        a = ct.from_array(an, chunks=(3, 3), spec=spec)
        return xp.sum(xp.multiply(a, 3.7193))

    ex1 = JaxExecutor()
    ex2 = JaxExecutor()
    v1 = float(build().compute(executor=ex1))
    v2 = float(build().compute(executor=ex2))
    assert v1 == v2
    assert ex1.stats["segments_traced"] == 1
    assert ex2.stats["segments_traced"] == 1
    assert (
        ex2.stats["segment_cache_hits"] + ex2.stats["segment_struct_hits"] == 1
    )
    assert ex2.stats["segments_compiled"] == 0


def test_stats_eager_mode_traces_nothing(spec):
    an = np.arange(64, dtype=np.float64).reshape(8, 8)
    a = ct.from_array(an, chunks=(4, 4), spec=spec)
    ex = JaxExecutor(fuse_plan=False)
    xp.add(a, 1.0).compute(executor=ex)
    assert ex.stats["segments_traced"] == 0
    assert ex.stats["eager_ops"] >= 1


def test_stats_reported_via_compute_end_event(spec):
    from cubed_tpu.runtime.types import Callback

    seen = {}

    class Capture(Callback):
        def on_compute_end(self, event):
            seen["stats"] = event.executor_stats

    an = np.arange(16, dtype=np.float64).reshape(4, 4)
    a = ct.from_array(an, chunks=(2, 2), spec=spec)
    ex = JaxExecutor()
    xp.sum(a).compute(executor=ex, callbacks=[Capture()])
    # executor_stats carries the executor's own counters merged with the
    # per-compute observability metrics (task counters, per_op summary)
    assert seen["stats"]["segments_traced"] == 1
    for key, val in ex.stats.items():
        assert seen["stats"][key] == val
    assert seen["stats"]["tasks_completed"] > 0
    assert "per_op" in seen["stats"]


# ---------------------------------------------------------------------------
# structural segment cache: repeat computes of identical plan shapes must
# skip tracing, rebind seeds, and never alias across different programs
# ---------------------------------------------------------------------------


def test_struct_cache_hit_skips_trace_and_rebinds_seed(spec):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()

    def build():
        r = cubed_tpu.random.random((24, 24), chunks=6, spec=spec)
        return xp.mean(xp.multiply(r, 1.618))

    ex1, ex2 = JaxExecutor(), JaxExecutor()
    v1 = float(build().compute(executor=ex1))
    v2 = float(build().compute(executor=ex2))
    assert ex1.stats["segment_struct_hits"] == 0
    assert ex1.stats["segments_traced"] == 1
    assert ex2.stats["segment_struct_hits"] == 1  # tracing skipped entirely
    assert ex2.stats["segments_compiled"] == 0
    # both runs valid, and the DIFFERENT per-plan seed was rebound (the
    # cached program did not bake the first plan's randomness)
    assert 0.4 < v1 / 1.618 < 0.6 and 0.4 < v2 / 1.618 < 0.6


def test_struct_cache_stable_across_gensym_counter_positions(spec):
    """Identical plans built at arbitrary points of the process-global
    gensym counter must produce the SAME structural key. Regression: with
    variable-width gensym names (%03d), crossing a digit boundary (999 →
    1000) changed pickle string length-prefix bytes that the post-pickle
    name canonicalization cannot rewrite, silently missing the cache."""
    import itertools

    import cubed_tpu.utils as utilsmod
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()

    def build():
        r = cubed_tpu.random.random((12, 12), chunks=6, spec=spec)
        return xp.mean(xp.multiply(r, 1.618))

    # jump the shared gensym counter forward across what used to be the
    # %03d boundary between the two builds (monotonically — never
    # backwards, so node names stay unique within the process)
    utilsmod.sym_counter = itertools.count(
        max(995, next(utilsmod.sym_counter))
    )
    ex1, ex2 = JaxExecutor(), JaxExecutor()
    v1 = float(build().compute(executor=ex1))
    v2 = float(build().compute(executor=ex2))
    assert ex1.stats["segments_traced"] == 1
    assert ex2.stats["segment_struct_hits"] == 1, (
        "structurally identical plan missed the struct cache across a "
        "gensym counter digit boundary"
    )
    assert 0.4 < v1 / 1.618 < 0.6 and 0.4 < v2 / 1.618 < 0.6
    assert v1 != v2


def test_struct_cache_distinguishes_kernel_constants(spec):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    an = np.arange(16.0).reshape(4, 4)

    def build(c):
        a = ct.from_array(an, chunks=(2, 2), spec=spec)
        return xp.sum(xp.multiply(a, c))

    ex1, ex2 = JaxExecutor(), JaxExecutor()
    v1 = float(build(2.0).compute(executor=ex1))
    v2 = float(build(3.0).compute(executor=ex2))
    assert ex2.stats["segment_struct_hits"] == 0  # different program
    assert v1 == an.sum() * 2 and v2 == an.sum() * 3


def test_struct_cache_distinguishes_chunking(spec):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    an = np.arange(64.0).reshape(8, 8)

    def build(chunks):
        a = ct.from_array(an, chunks=chunks, spec=spec)
        return xp.sum(xp.negative(a))

    v1 = float(build((2, 2)).compute(executor=JaxExecutor()))
    ex2 = JaxExecutor()
    v2 = float(build((4, 4)).compute(executor=ex2))
    assert ex2.stats["segment_struct_hits"] == 0
    assert v1 == v2 == -an.sum()


def test_struct_cache_distinguishes_executor_config(spec):
    # matmul_precision changes the MXU pass count inside the same HLO
    # shape: a program cached for one precision must not be reused by an
    # executor configured for another
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    an = np.arange(16 * 16, dtype=np.float32).reshape(16, 16) / 256.0

    def build():
        a = ct.from_array(an, chunks=(8, 8), spec=spec)
        b = ct.from_array(an, chunks=(8, 8), spec=spec)
        return xp.sum(xp.matmul(a, b))

    ex1 = JaxExecutor()
    ex2 = JaxExecutor(matmul_precision="bfloat16")
    v1 = float(build().compute(executor=ex1))
    v2 = float(build().compute(executor=ex2))
    assert ex2.stats["segment_struct_hits"] == 0  # different config, no reuse
    expect = float(np.sum(an @ an))
    np.testing.assert_allclose(v1, expect, rtol=1e-5)
    np.testing.assert_allclose(v2, expect, rtol=2e-2)  # bf16 passes


def test_struct_cache_no_collision_on_gensym_like_user_strings(spec):
    # user closure strings that merely LOOK like gensym identifiers must not
    # normalize away: only this plan's own names are canonicalized
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    an = np.full((4, 4), 2.0)

    def build(tag):
        def kernel(block):
            return block * len(tag.split("-")[1])

        a = ct.from_array(an, chunks=(2, 2), spec=spec)
        return xp.sum(ct.map_blocks(kernel, a, dtype=a.dtype))

    ex1, ex2 = JaxExecutor(), JaxExecutor()
    v1 = float(build("exp-0010").compute(executor=ex1))
    v2 = float(build("exp-009876").compute(executor=ex2))
    assert v1 == an.sum() * 4
    assert v2 == an.sum() * 6  # a struct-cache collision would return *4


def test_struct_cache_hit_matches_fresh_result(spec):
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()
    an = np.arange(36.0).reshape(6, 6)

    def build():
        a = ct.from_array(an, chunks=(2, 3), spec=spec)
        b = ct.from_array(an + 1, chunks=(2, 3), spec=spec)
        return xp.mean(xp.add(xp.multiply(a, 0.5), b))

    v1 = np.asarray(build().compute(executor=JaxExecutor()))
    ex2 = JaxExecutor()
    v2 = np.asarray(build().compute(executor=ex2))
    assert ex2.stats["segment_struct_hits"] == 1
    np.testing.assert_allclose(v1, (an * 0.5 + an + 1).mean())
    np.testing.assert_allclose(v2, v1)


def test_fused_output_also_persisted(spec, tmp_path):
    # a kept store must flush correctly after a traced segment
    an = np.arange(64, dtype=np.float64).reshape(8, 8)
    a = ct.from_array(an, chunks=(4, 4), spec=spec)
    out = str(tmp_path / "out.zarr")
    ct.to_zarr(xp.add(a, 1.0), out, executor=JaxExecutor())
    readback = ct.from_zarr(out, spec=spec).compute()
    np.testing.assert_allclose(np.asarray(readback), an + 1.0)


def test_compute_dtype_f32_ingestion(spec):
    """f32 ingestion: an f64 plan executed with
    ``compute_dtype="float32"`` computes on-device in single precision —
    including random generation — and casts back to the declared f64 at
    the store boundary, within f32 error bounds of the f64 result."""
    import cubed_tpu.random

    def build():
        a = cubed_tpu.random.random((40, 40), chunks=(13, 13), spec=spec)
        b = cubed_tpu.random.random((40, 40), chunks=(13, 13), spec=spec)
        return xp.mean(xp.add(xp.multiply(a, b), xp.sin(a)))

    f64 = np.asarray(build().compute(executor=JaxExecutor()))
    f32 = np.asarray(build().compute(executor=JaxExecutor(compute_dtype="float32")))
    assert f64.dtype == np.float64
    assert f32.dtype == np.float64  # declared dtype preserved at the boundary
    # different seeds each build, so compare statistically: both are means of
    # ~0.25+sin-ish uniform products over 1600 elements
    assert abs(float(f64) - float(f32)) < 0.1
    # a seed-held comparison: same plan, both precisions, one from_array source
    an = np.linspace(0.0, 1.0, 64, dtype=np.float64).reshape(8, 8)
    src = ct.from_array(an, chunks=(3, 3), spec=spec)
    expr = xp.sum(xp.sqrt(xp.abs(xp.sin(src) * 2.0 + 1.0)))
    r64 = float(expr.compute(executor=JaxExecutor()))
    an2 = np.linspace(0.0, 1.0, 64, dtype=np.float64).reshape(8, 8)
    src2 = ct.from_array(an2, chunks=(3, 3), spec=spec)
    expr2 = xp.sum(xp.sqrt(xp.abs(xp.sin(src2) * 2.0 + 1.0)))
    r32 = float(expr2.compute(executor=JaxExecutor(compute_dtype="float32")))
    np.testing.assert_allclose(r32, r64, rtol=1e-5)  # f32 eps * tree depth


def test_compute_dtype_restores_x64(spec):
    """The x64 flag is restored even when the plan fails mid-execution."""
    import jax

    assert jax.config.jax_enable_x64
    a = xp.ones((6, 6), chunks=(2, 2), spec=spec)
    xp.add(a, 1).compute(executor=JaxExecutor(compute_dtype="float32"))
    assert jax.config.jax_enable_x64

    def boom(x):
        raise ValueError("kernel boom")

    b = ct.map_blocks(boom, xp.ones((6, 6), chunks=(2, 2), spec=spec),
                      dtype=np.float64)
    with pytest.raises(Exception, match="kernel boom"):
        b.compute(executor=JaxExecutor(compute_dtype="float32"))
    assert jax.config.jax_enable_x64  # restored on the failure path too


def test_compute_dtype_invalid():
    with pytest.raises(ValueError, match="compute_dtype"):
        JaxExecutor(compute_dtype="bfloat16")


def test_matmul_precision_bf16(spec):
    """The MXU contraction opt-in: matmul under
    ``matmul_precision='bfloat16'`` runs the same plan with one-pass MXU
    contractions — f32-accumulated, inputs rounded to bf16 (~3 decimal
    digits), so the result tracks full precision to ~1e-2 relative."""
    an = np.linspace(0.0, 1.0, 64 * 48, dtype=np.float64).reshape(64, 48)
    bn = np.linspace(1.0, 2.0, 48 * 32, dtype=np.float64).reshape(48, 32)

    def build():
        a = ct.from_array(an, chunks=(16, 16), spec=spec)
        b = ct.from_array(bn, chunks=(16, 16), spec=spec)
        return xp.sum(xp.matmul(a, b))

    exact = float(build().compute(executor=JaxExecutor()))
    fast = float(build().compute(executor=JaxExecutor(
        compute_dtype="float32", matmul_precision="bfloat16")))
    np.testing.assert_allclose(fast, exact, rtol=2e-2)
    np.testing.assert_allclose(exact, float((an @ bn).sum()), rtol=1e-12)


def test_matmul_precision_invalid():
    with pytest.raises(ValueError, match="matmul_precision"):
        JaxExecutor(matmul_precision="int8")


def test_host_sliced_from_array_splits_cleanly(spec):
    """A >256KB from_array source runs as an EAGER op (its host data must
    not bake into a traced program as constants — XLA constant-folds op
    chains over baked data at compile time, measured at minutes for a
    sort network over a 4MB source) while downstream ops still trace.
    Regression x2: previously (a) 1-8MB sources were classified traceable
    and then trace-FAILED the whole segment to eager (their offsets block
    was backend-converted into a tracer the host block-id kernel can't
    consume), (b) the classifier threshold allowed the constant-bake."""
    n = 262_144  # 2MB f64: above the in-memory-virtual cap
    an = np.arange(n, dtype=np.float64)
    a = ct.from_array(an, chunks=(n // 8,), spec=spec)
    ex = JaxExecutor()
    v = float(xp.sum(xp.multiply(a, 2.0)).compute(executor=ex))
    assert v == 2.0 * an.sum()
    assert ex.stats["segments_traced"] == 1  # downstream traced
    assert ex.stats["trace_failures"] == 0   # no failed trace attempt
    assert ex.stats["eager_fallbacks"] == 0
    assert ex.stats["eager_ops"] >= 2        # create-arrays + the source op


def test_small_host_from_array_traces(spec):
    """A small in-memory source (VirtualInMemoryArray, <=1MB cap) is cheap
    to bake: the whole plan stays one traced segment."""
    n = 32_768  # 256KB f64
    an = np.arange(n, dtype=np.float64)
    a = ct.from_array(an, chunks=(n // 4,), spec=spec)
    ex = JaxExecutor()
    v = float(xp.sum(a).compute(executor=ex))
    assert v == an.sum()
    assert ex.stats["segments_traced"] == 1
    assert ex.stats["trace_failures"] == 0
    assert ex.stats["eager_fallbacks"] == 0


# values a float-float device representation would change: all 53
# significand bits, and exponents beyond float32's range
_FULL_F64 = np.array(
    [[np.pi, 1.0 + 2.0**-52, 1e300, 1e-300], [-np.e, 2.0**-1000, 1e40, 2.0**-1022]]
    * 4
)


@pytest.mark.parametrize("mesh", [False, True])
@pytest.mark.parametrize("fuse_plan", [True, False])
def test_movement_only_compute_carries_float64_as_bits(
    spec, tmp_path, monkeypatch, mesh, fuse_plan
):
    """On a device whose float64 does not survive a round trip (TPU v5e
    holds it as a float32 pair), a compute that only moves values —
    ``to_zarr(from_zarr(...).rechunk(...))`` — carries them through the
    device as uint64 bit patterns, so the copy is bit for bit; a compute
    with arithmetic is left to the device's float64."""
    import cubed_tpu.runtime.executors.jax as jx
    from cubed_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    src, out = str(tmp_path / "src.zarr"), str(tmp_path / "out.zarr")
    ct.to_zarr(ct.from_array(_FULL_F64, chunks=(4, 2), spec=spec), src)
    a = ct.from_zarr(src, spec=spec)

    def executor():
        return JaxExecutor(
            mesh=make_mesh() if mesh else None, fuse_plan=fuse_plan
        )

    ex = executor()
    ct.to_zarr(a.rechunk((2, 4)), out, executor=ex)
    assert ex.stats["f64_as_bits"] >= 1
    assert not ex.stats.get("eager_fallbacks")
    got = ct.from_zarr(out, spec=spec)
    assert got.chunks == ((2, 2, 2, 2), (4,))
    assert got.compute().tobytes() == _FULL_F64.tobytes()

    ex = executor()
    doubled = xp.add(a, a).compute(executor=ex)
    assert not ex.stats.get("f64_as_bits")
    assert not ex.stats.get("f64_lossy_moves")  # nothing here only moves
    assert np.array_equal(doubled, _FULL_F64 + _FULL_F64)

    # a copy that shares its compute with arithmetic is not carried as
    # bits, and the counter says so
    ex = executor()
    out2 = str(tmp_path / "out2.zarr")
    ct.store([a.rechunk((2, 4)), xp.add(a, a)], [out2, str(tmp_path / "sum.zarr")],
             executor=ex)
    assert not ex.stats.get("f64_as_bits")
    assert ex.stats["f64_lossy_moves"] >= 1


def _records(dtype):
    rec = np.empty(_FULL_F64.shape, dtype=dtype)
    rec["n"] = np.arange(_FULL_F64.size).reshape(_FULL_F64.shape)
    rec["total"] = _FULL_F64
    return rec


@pytest.mark.parametrize("mesh", [False, True])
def test_movement_only_compute_carries_float64_fields_of_records_as_bits(
    spec, tmp_path, monkeypatch, mesh
):
    """A float64 field of a record array takes the same way in and out as a
    plain float64 array: the dict-of-fields form goes through the same pair
    of conversions, in ``_preload``/``_exec_rechunk`` and in ``_flush``."""
    import cubed_tpu.runtime.executors.jax as jx
    from cubed_tpu.parallel.mesh import make_mesh
    from cubed_tpu.storage.store import open_zarr_array

    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    rec = _records([("n", "<i8"), ("total", "<f8")])
    src, out = str(tmp_path / "src.zarr"), str(tmp_path / "out.zarr")
    stored = open_zarr_array(
        src, mode="w", shape=rec.shape, dtype=rec.dtype, chunks=(4, 2)
    )
    stored[...] = rec
    a = ct.from_zarr(src, spec=spec)

    ex = JaxExecutor(mesh=make_mesh() if mesh else None)
    ct.to_zarr(a.rechunk((2, 4)), out, executor=ex)
    assert ex.stats["f64_as_bits"] >= 1
    assert not ex.stats.get("eager_fallbacks")
    got = open_zarr_array(out, mode="r")[...]
    assert got.dtype == rec.dtype
    assert got.tobytes() == rec.tobytes()


def test_complex128_is_not_carried_as_bits(spec, tmp_path, monkeypatch):
    """complex128 has no uint64 form of the same shape: a compute that holds
    one stays in the device's representation and is counted as lossy."""
    import cubed_tpu.runtime.executors.jax as jx

    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    z = _FULL_F64[:, :2] + 1j * _FULL_F64[:, 2:]
    src, out = str(tmp_path / "src.zarr"), str(tmp_path / "out.zarr")
    ct.to_zarr(ct.from_array(z, chunks=(4, 2), spec=spec), src)

    ex = JaxExecutor()
    ct.to_zarr(ct.from_zarr(src, spec=spec).rechunk((2, 2)), out, executor=ex)
    assert not ex.stats.get("f64_as_bits")
    assert ex.stats["f64_lossy_moves"] >= 1
    assert np.array_equal(ct.from_zarr(out, spec=spec).compute(), z)


def test_float64_round_trip_is_observed_once_per_device_kind(monkeypatch):
    import jax

    import cubed_tpu.runtime.executors.jax as jx

    monkeypatch.setattr(jx, "_FLOAT64_ROUND_TRIPS", {})
    device = jax.devices()[0]
    assert jx._float64_round_trips(device) is True  # the CPU holds real f64
    assert jx._FLOAT64_ROUND_TRIPS == {(device.platform, device.device_kind): True}


def test_budget_refuses_to_assume_a_limit_off_the_cpu():
    class _Device:
        platform = "tpu"

        def memory_stats(self):
            return None

    ex = JaxExecutor()
    ex._first_device = lambda: _Device()
    with pytest.raises(RuntimeError, match="bytes_limit"):
        ex._budget()
    assert JaxExecutor(device_mem=123)._budget() == 123
    assert JaxExecutor()._budget() == 8 * 2**30  # CPU devices report no limit


def test_mesh_segment_with_only_virtual_inputs_is_partitioned(spec, monkeypatch):
    """Under a mesh, a fused segment whose arrays are all generated inside it
    (the vorticity pipeline: four random arrays, nothing preloaded with a
    sharding) must still be compiled for the whole mesh — every array the
    segment produces is pinned to the chunk-grid sharding — not for one
    device of it."""
    import jax

    import cubed_tpu.runtime.executors.jax as jx
    from cubed_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(jx, "_SEGMENT_CACHE", {})
    monkeypatch.setattr(jx, "_STRUCT_CACHE", {})
    a, b, x, y = (
        cubed_tpu.random.random((20, 18, 16), chunks=4, spec=spec)
        for _ in range(4)
    )
    expr = xp.mean(xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:])))
    ex = JaxExecutor(mesh=make_mesh())
    value = float(expr.compute(executor=ex))
    assert 0.3 < value < 0.7
    assert ex.stats["segments_traced"] == 1 and not ex.stats.get("eager_fallbacks")
    (program,) = jx._SEGMENT_CACHE.values()
    (out_sharding,) = jax.tree_util.tree_leaves(program.compiled.output_shardings)
    assert len(out_sharding.device_set) == len(jax.devices())
