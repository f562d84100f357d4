"""Scale-out (multi-chunk bitonic) sort — beyond the reference, which skips
sort entirely (.github/workflows/array-api-tests.yml skip list).

The headline property: an axis LARGER than ``allowed_mem`` sorts, because
every network task touches exactly two chunks (no single-chunk-axis
wall). The conformance suite additionally fuzzes the
multi-chunk path against numpy across dtypes/shapes (chunks_for always
splits axes, so sorting there goes through the network).
"""

from __future__ import annotations

import numpy as np
import pytest

import cubed_tpu as ct
import cubed_tpu.array_api as xp
from cubed_tpu.runtime.executors.jax import JaxExecutor


@pytest.fixture
def spec(tmp_path, monkeypatch):
    # small arrays would pass the memory heuristic and take the one-kernel
    # path; force the network so these tests actually cover it
    monkeypatch.setenv("CUBED_TPU_SORT_NETWORK", "force")
    return ct.Spec(work_dir=str(tmp_path), allowed_mem="100MB", reserved_mem=0)


@pytest.mark.parametrize("executor", [None, "jax"])
def test_sort_axis_larger_than_allowed_mem(tmp_path, executor):
    """The scale criterion: 4MB axis slab, 2MB allowed_mem, 0.125MB chunks.
    The old single-chunk path raised at plan time here; the network sorts."""
    small = ct.Spec(work_dir=str(tmp_path), allowed_mem="2MB", reserved_mem=0)
    n = 500_000  # 4MB f64
    an = np.random.default_rng(0).permutation(n).astype(np.float64)
    a = ct.from_array(an, chunks=(15_625,), spec=small)  # 32 chunks
    kw = {"executor": JaxExecutor()} if executor == "jax" else {}
    got = np.asarray(xp.sort(a).compute(**kw))
    np.testing.assert_array_equal(got, np.arange(n, dtype=np.float64))


def test_argsort_axis_larger_than_allowed_mem(tmp_path):
    # 3MB axis slab, 2MB allowed_mem. Chunks sized for the pair round's
    # projection (7 value + 9 index blocks, both int64 here): 100KB blocks
    # -> 1.6MB per op
    small = ct.Spec(work_dir=str(tmp_path), allowed_mem="2MB", reserved_mem=0)
    n = 375_000
    an = np.random.default_rng(1).integers(0, 50, n).astype(np.int64)
    a = ct.from_array(an, chunks=(12_500,), spec=small)  # 30 chunks, heavy ties
    got = np.asarray(xp.argsort(a).compute(executor=JaxExecutor()))
    np.testing.assert_array_equal(got, np.argsort(an, kind="stable"))


def test_argsort_one_op_per_round(spec):
    """Each argsort network round is ONE multi-output op (merge runs once),
    not a values op plus an indices op over the same merge."""
    an = np.random.default_rng(7).random(64)
    a = ct.from_array(an, chunks=(8,), spec=spec)  # 8 chunks -> 1+6 rounds
    arg = xp.argsort(a)
    dag = arg.plan.dag
    pair_ops = [
        n for n, d in dag.nodes(data=True)
        if d.get("type") == "op" and "pair" in d.get("op_name", "")
    ]
    # local pair sort + log2(8)*(log2(8)+1)/2 = 6 merge rounds
    assert len(pair_ops) == 7
    # every pair op feeds exactly two array nodes (values + indices)
    for op_node in pair_ops:
        outs = list(dag.successors(op_node))
        assert len(outs) == 2
        pop = dag.nodes[op_node]["primitive_op"]
        assert pop.target_arrays is not None and len(pop.target_arrays) == 2
    np.testing.assert_array_equal(
        np.asarray(arg.compute()), np.argsort(an, kind="stable")
    )


def test_multioutput_op_on_distributed_executor(spec):
    """Multi-output ops write all targets on the per-task executor fabric."""
    from cubed_tpu.runtime.executors.distributed import DistributedDagExecutor

    an = np.random.default_rng(8).integers(0, 9, 48)
    a = ct.from_array(an, chunks=(6,), spec=spec)
    got = xp.argsort(a).compute(executor=DistributedDagExecutor(n_workers=2))
    np.testing.assert_array_equal(np.asarray(got), np.argsort(an, kind="stable"))


def test_multioutput_resume_checks_all_outputs(spec):
    """Resume skips a multi-output op only when EVERY output is complete."""
    import shutil

    from cubed_tpu.core.ops import general_blockwise
    from cubed_tpu.runtime.executors.python import PythonDagExecutor

    an = np.arange(12, dtype=np.float64)
    a = ct.from_array(an, chunks=(4,), spec=spec)

    def two(chunk):
        return chunk + 1.0, (chunk * 2.0).astype(np.float64)

    def block_function(out_key):
        return ((a.name, *out_key[1:]),)

    p, d = general_blockwise(
        two, block_function, a,
        shape=a.shape, dtype=[a.dtype, np.dtype(np.float64)],
        chunks=a.chunks, op_name="two_out",
    )
    ex = PythonDagExecutor()
    np.testing.assert_array_equal(np.asarray(p.compute(executor=ex)), an + 1.0)
    np.testing.assert_array_equal(np.asarray(d.compute(executor=ex)), an * 2.0)
    # wipe only the SECONDARY output's store: the op must re-run under
    # resume=True (primary alone being complete is not enough)
    shutil.rmtree(str(d.zarray_maybe_lazy.store))
    np.testing.assert_array_equal(
        np.asarray(d.compute(executor=ex, resume=True)), an * 2.0
    )


def test_auto_network_coarsens_large_m(tmp_path):
    """auto routing rechunks the sort axis to the largest fitting merge
    before building the network: rounds scale as log2(m)*(log2(m)+1)/2 and
    every round is a full pass (O(n log^2 m) IO on non-fused executors), so
    64 tiny chunks must NOT produce a 22-round network when allowed_mem
    admits far larger merges."""
    # 512KB axis in 64 x 8KB chunks; 2MB allowed_mem fits a c=~37k merge
    # (7 blocks x 8B), so the axis coarsens to few chunks
    small = ct.Spec(work_dir=str(tmp_path), allowed_mem="2MB", reserved_mem=0)
    n = 65_536
    an = np.random.default_rng(11).random(n)
    a = ct.from_array(an, chunks=(1_024,), spec=small)
    # single-chunk slab (4x 512KB = 2MB + int64 out) exceeds allowed: network
    srt = xp.sort(a)
    rounds = [
        d["op_name"]
        for _, d in srt.plan.dag.nodes(data=True)
        if d.get("type") == "op" and "bitonic" in d.get("op_name", "")
    ]
    # uncoarsened m2=64 would give 1+21 bitonic ops; coarsened m2=2 gives 2
    assert len(rounds) <= 4, rounds
    np.testing.assert_array_equal(np.asarray(srt.compute()), np.sort(an))
    # argsort coarsens too (int64 outputs priced into the merge bound)
    arg = xp.argsort(a)
    arounds = [
        d["op_name"]
        for _, d in arg.plan.dag.nodes(data=True)
        if d.get("type") == "op" and "bitonic" in d.get("op_name", "")
    ]
    assert len(arounds) <= 7, arounds
    np.testing.assert_array_equal(
        np.asarray(arg.compute()), np.argsort(an, kind="stable")
    )


def test_auto_network_shrinks_oversized_chunks(tmp_path):
    """Chunks larger than the feasible pair-merge rechunk DOWN to it —
    auto routing must not build a network the planner then rejects."""
    small = ct.Spec(work_dir=str(tmp_path), allowed_mem="2MB", reserved_mem=0)
    n = 200_000
    an = np.random.default_rng(13).random(n)
    a = ct.from_array(an, chunks=(50_000,), spec=small)  # merge 2x50k f64 > 2MB
    np.testing.assert_array_equal(np.asarray(xp.sort(a).compute()), np.sort(an))
    np.testing.assert_array_equal(
        np.asarray(xp.argsort(a).compute()), np.argsort(an, kind="stable")
    )


def test_multioutput_plan_hits_struct_cache(spec):
    """Repeat computes of a structurally identical multi-output plan skip
    tracing entirely — the fingerprint covers ALL writes, so a key bug
    would show up here as a recompile instead of a struct hit."""
    from cubed_tpu.runtime.executors import jax as jxm

    jxm._STRUCT_CACHE.clear()  # a struct hit would skip tracing legitimately
    an = np.random.default_rng(21).random(4096)

    def build():
        a = ct.from_array(an, chunks=(512,), spec=spec)
        return xp.argsort(a)

    ex1 = JaxExecutor()
    r1 = np.asarray(build().compute(executor=ex1))
    assert ex1.stats["segments_traced"] == 1
    ex2 = JaxExecutor()
    r2 = np.asarray(build().compute(executor=ex2))
    assert ex2.stats.get("segment_struct_hits", 0) == 1
    assert ex2.stats.get("segments_compiled", 0) == 0
    np.testing.assert_array_equal(r1, np.argsort(an, kind="stable"))
    np.testing.assert_array_equal(r2, r1)


def test_predecessor_fuses_into_multioutput_consumer(spec):
    """A single-output elemwise producer fuses INTO a multi-output
    consumer (writes_rest carried through fuse_multiple); the multi-output
    op itself never fuses away as a predecessor."""
    from cubed_tpu.core.ops import elemwise, general_blockwise
    from cubed_tpu.core.optimization import multiple_inputs_optimize_dag

    an = np.arange(12, dtype=np.float64)
    a = ct.from_array(an, chunks=(4,), spec=spec)
    doubled = elemwise(
        lambda x: x * 2.0, a, dtype=np.dtype(np.float64)
    )

    def two(chunk):
        return chunk + 1.0, chunk - 1.0

    def block_function(out_key):
        return ((doubled.name, *out_key[1:]),)

    p, q = general_blockwise(
        two, block_function, doubled,
        shape=a.shape, dtype=[a.dtype, a.dtype], chunks=a.chunks,
        op_name="two_out",
    )
    dag = multiple_inputs_optimize_dag(p.plan.dag.copy())
    multi_ops = [
        d["primitive_op"]
        for _, d in dag.nodes(data=True)
        if d.get("type") == "op"
        and d.get("primitive_op") is not None
        and d["primitive_op"].target_arrays is not None
    ]
    assert len(multi_ops) == 1
    # the elemwise producer fused in: the multi-output op reads `a` directly
    reads = {
        proxy.array for proxy in multi_ops[0].pipeline.config.reads_map.values()
    }
    assert a.zarray_maybe_lazy in reads
    np.testing.assert_array_equal(np.asarray(p.compute()), an * 2.0 + 1.0)
    np.testing.assert_array_equal(np.asarray(q.compute()), an * 2.0 - 1.0)


def test_multichunk_sort_matches_numpy(spec):
    rng = np.random.default_rng(2)
    an = rng.random((13, 17))
    a = ct.from_array(an, chunks=(3, 4), spec=spec)
    np.testing.assert_array_equal(
        np.asarray(xp.sort(a, axis=0).compute()), np.sort(an, axis=0)
    )
    np.testing.assert_array_equal(
        np.asarray(xp.sort(a, axis=1, descending=True).compute()),
        np.sort(an, axis=1)[:, ::-1],
    )


def test_multichunk_argsort_stable_with_ties(spec):
    an = np.random.default_rng(3).integers(0, 5, 37)
    a = ct.from_array(an, chunks=(5,), spec=spec)
    np.testing.assert_array_equal(
        np.asarray(xp.argsort(a).compute()), np.argsort(an, kind="stable")
    )
    got = np.asarray(xp.argsort(a, descending=True).compute())
    m = len(an)
    expect = (m - 1 - np.argsort(an[::-1], kind="stable"))[::-1]
    np.testing.assert_array_equal(got, expect)


def test_multichunk_sort_nan_last(spec):
    an = np.random.default_rng(4).random(19)
    an[[2, 7, 11]] = np.nan
    a = ct.from_array(an, chunks=(4,), spec=spec)
    np.testing.assert_array_equal(np.asarray(xp.sort(a).compute()), np.sort(an))
    np.testing.assert_array_equal(
        np.asarray(xp.argsort(a).compute()), np.argsort(an, kind="stable")
    )


def test_multichunk_sort_sentinel_collision(spec):
    """Real int64 max values must survive padding-sentinel dedup."""
    imax = np.iinfo(np.int64).max
    an = np.array([3, imax, 1, imax, 2] * 3, dtype=np.int64)
    a = ct.from_array(an, chunks=(4,), spec=spec)
    np.testing.assert_array_equal(np.asarray(xp.sort(a).compute()), np.sort(an))
    np.testing.assert_array_equal(
        np.asarray(xp.argsort(a).compute()), np.argsort(an, kind="stable")
    )


def test_multichunk_sort_traces_on_jax_executor(spec):
    """The network must stay on the traced/batched path (uniform kernels,
    offsets as data) — no eager fallbacks."""
    an = np.random.default_rng(5).random(100)
    a = ct.from_array(an, chunks=(16,), spec=spec)
    ex = JaxExecutor()
    got = np.asarray(xp.sort(a).compute(executor=ex))
    np.testing.assert_array_equal(got, np.sort(an))
    assert ex.stats["trace_failures"] == 0
    assert ex.stats["eager_fallbacks"] == 0


# -- 'auto' routing heuristic (no force; the default production path) -------


def test_auto_prefers_single_chunk_when_slab_fits(tmp_path, monkeypatch):
    """Plenty of memory: a multi-chunk axis must take the one-kernel path,
    not the network (network entry would hit the raising sentinel)."""
    import cubed_tpu.array_api._block_sort as bs

    def boom(*a, **k):
        raise AssertionError("network used despite fitting slab")

    monkeypatch.setattr(bs, "block_sort", boom)
    monkeypatch.setattr(bs, "block_argsort", boom)
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem="100MB", reserved_mem=0)
    an = np.random.default_rng(6).random(1000)
    a = ct.from_array(an, chunks=(100,), spec=spec)
    np.testing.assert_array_equal(np.asarray(xp.sort(a).compute()), np.sort(an))
    a = ct.from_array(an, chunks=(100,), spec=spec)
    np.testing.assert_array_equal(
        np.asarray(xp.argsort(a).compute()), np.argsort(an, kind="stable")
    )


def test_auto_network_when_reserved_mem_eats_budget(tmp_path):
    """reserved_mem counts against the slab fit (review regression): a slab
    whose 4x estimate fits allowed_mem alone must still go to the network
    when reserved_mem leaves no room — and the plan must succeed."""
    # slab 0.8MB f64: 4x = 3.2MB fits 8MB, but reserved 6MB leaves 2MB
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem="8MB", reserved_mem="6MB")
    n = 100_000
    an = np.random.default_rng(7).permutation(n).astype(np.float64)
    a = ct.from_array(an, chunks=(12_500,), spec=spec)
    got = np.asarray(xp.sort(a).compute())
    np.testing.assert_array_equal(got, np.arange(n, dtype=np.float64))


def test_auto_argsort_accounts_int64_output(tmp_path):
    """f32 argsort: the int64 output doubles the kernel's output bytes; the
    heuristic must charge it (review regression) so the chosen path plans."""
    # slab 0.4MB f32 -> a naive 4x-input estimate (1.6MB) fits 2.3MB and
    # would pick the single-chunk path, whose kernel the planner prices at
    # 2*0.4 + 2*0.8 = 2.4MB > 2.3MB (ValueError); charging the int64
    # output routes to the network, which plans and sorts
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem="2300KB", reserved_mem=0)
    n = 100_000
    an = np.random.default_rng(8).permutation(n).astype(np.float32)
    a = ct.from_array(an, chunks=(12_500,), spec=spec)
    got = np.asarray(xp.argsort(a).compute())
    np.testing.assert_array_equal(got, np.argsort(an, kind="stable"))


# -- searchsorted partial-counts (memory-bounded x1) ------------------------


def test_searchsorted_partial_counts_matches_numpy(spec):
    """Forced network: per-chunk counts summed over the tree must equal the
    single-chunk binary search for both sides, with duplicates straddling
    chunk boundaries."""
    rng = np.random.default_rng(9)
    x1n = np.sort(rng.integers(0, 8, 29)).astype(np.float64)
    x2n = np.array([[0.0, 3.0, 7.0], [8.0, -1.0, 3.5]])
    x1 = ct.from_array(x1n, chunks=(4,), spec=spec)
    x2 = ct.from_array(x2n, chunks=(1, 2), spec=spec)
    for side in ("left", "right"):
        got = np.asarray(xp.searchsorted(x1, x2, side=side).compute())
        np.testing.assert_array_equal(got, np.searchsorted(x1n, x2n, side=side))
        got = np.asarray(
            xp.searchsorted(x1, x2, side=side).compute(executor=JaxExecutor())
        )
        np.testing.assert_array_equal(got, np.searchsorted(x1n, x2n, side=side))


def test_searchsorted_x1_larger_than_allowed_mem(tmp_path):
    """The scale criterion for searchsorted: a sorted x1 bigger than
    allowed_mem searches via partial counts (the old path rechunked x1 to
    one chunk and raised at plan time)."""
    small = ct.Spec(work_dir=str(tmp_path), allowed_mem="2MB", reserved_mem=0)
    n = 500_000  # 4MB f64 > 2MB allowed
    x1n = np.arange(n, dtype=np.float64)
    x2n = np.random.default_rng(10).random(500) * n
    x1 = ct.from_array(x1n, chunks=(31_250,), spec=small)
    x2 = ct.from_array(x2n, chunks=(125,), spec=small)
    got = np.asarray(xp.searchsorted(x1, x2).compute(executor=JaxExecutor()))
    np.testing.assert_array_equal(got, np.searchsorted(x1n, x2n))
