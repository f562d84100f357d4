"""The cell ``vorticity-f32.mean``: its entries in the manifest, its reader,
what its check refuses, and the cell rehearsed on the CPU at a tiny size,
untraced and traced. Shares read here say nothing about the device.

Importing this file enters the configuration's tiny shape into the tables
that ``test_rehearsal.py`` shrinks configurations from (that file and
``benchmark/conftest.py`` are not a later PR's to edit), so an invocation
that collects this file with it rehearses the cell tiny. One that runs
``test_rehearsal.py`` alone does not, and would run the cell at its real
size on the CPU."""

import math
import time
from types import SimpleNamespace

import numpy as np
import pytest

import benchmark.conftest as bench_conftest
import benchmark.tests.test_rehearsal as rehearsal
from benchmark.harness import loop, manifest

CONFIG, CELL = "vorticity-f32", "vorticity-f32.mean"
TINY = {"shape": [50, 40, 30], "chunks": 10}
# before the session fixture of benchmark/conftest.py reads its table (it
# runs when the first test starts), and for the copy of test_rehearsal.py
# that this file imports
bench_conftest.TINY.setdefault(CONFIG, TINY)
rehearsal.TINY.setdefault(CONFIG, TINY)

SHARE = manifest.load_module(manifest.ROOT, manifest.reader_file("float32_share.gen"))
#: the lists of the one-chip float64 cell that the new cell joins
JOINED = [
    "plan_s", "segment_s", "compiles_in_window.gen", "device_busy_s", "device_wait_s",
    "kernel_hbm_share", "hbm_footprint_frac.gen", "unaccounted_s.gen", "plan_finalize_s",
    "struct_key_s", "dispatch_s", "struct_hit_share.gen", "host_syncs.gen",
]
SEED = 2**31 + 34


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return rehearsal._tiny_root(tmp_path_factory.mktemp("tiny-f32"))


@pytest.fixture(scope="module")
def query():
    return manifest.load_module(manifest.ROOT, manifest.query_file("vorticity_f32_mean"))


def _measure(root, trace):
    import jax

    bench = manifest.load(root)
    return loop.measure(
        root=root, bench=bench, cell=manifest.cell(bench, CELL), seed=SEED, seconds=0.5,
        trace=trace, devices=jax.devices(), t_start=time.perf_counter(),
    )


# -- the manifest -------------------------------------------------------------


def test_the_cell_is_in_the_manifest_with_its_configuration_mix_and_readers():
    bench = manifest.load()
    assert manifest.check() == []
    cell = manifest.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "vorticity_f32_mean.closed-1", 1)
    entry = manifest.config_entry(bench, CONFIG)
    config = manifest.load_json(manifest.ROOT, entry["file"])
    assert entry["file"] == "benchmark/configs/vorticity-f32.json"
    assert entry["reduced"] == ["shape"] == list(config["reduced"])
    assert {"source", "reduced", "assumed", "deployment", "device_path",
            "guarantees"} <= set(config)
    assert config["deployment"] == {
        "shape": [500, 900, 800], "dtype": "float32", "chunks": 100,
        "allowed_mem": "4GB", "executor": {"mesh": False},
    }
    assert {"dtype", "rng"} <= set(config["assumed"])
    assert {"host_kernel_ops", "device_f16_bytes", "segment_mem_aborts"} <= set(
        config["device_path"]["zero"])
    assert {"segments_traced", "device_f32_bytes", "device_f64_bytes"} == set(
        config["device_path"]["positive"])
    mix = manifest.load_json(manifest.ROOT, manifest.traffic_file(cell["traffic"]))
    assert (mix["query"], mix["loop"], mix["clients"], mix["metric"], mix["profiled_computes"]) == (
        "vorticity_f32_mean", "closed", 1, "compute_s", 5)
    assert [x["name"] for x in manifest.metrics_for(bench, "end_to_end", CELL)] == [
        "compute_s", "setup_s"]
    per_layer = {x["name"]: x for x in manifest.metrics_for(bench, "per_layer", CELL)}
    assert set(per_layer) == {*JOINED, "float32_share.gen", "first_compute_s", "compile_s"}
    assert per_layer["float32_share.gen"]["workloads"] == [
        "vorticity.mean", "vorticity-mesh4.mean", CELL]
    assert bench["per_layer"][-1]["name"] == "float32_share.gen"
    assert (bench["configs"][-1]["name"], bench["workloads"][-1]["name"]) == (CONFIG, CELL)


def test_nothing_of_the_cell_reaches_for_compute_dtype():
    # float32 is declared in the plan; the executor option that computes a
    # float64 plan in float32 is another thing, and is not used
    root = manifest.ROOT / "benchmark"
    for file in ("configs/vorticity-f32.json", "traffic/vorticity_f32_mean.closed-1.json",
                 "queries/vorticity_f32_mean.py", "harness/loop.py"):
        text = (root / file).read_text()
        assert "compute_dtype=" not in text and '"compute_dtype"' not in text, file


def test_the_tiny_shape_is_in_both_tables_of_the_rehearsal():
    import sys

    tables = [
        module.TINY for module in list(sys.modules.values())
        if (getattr(module, "__file__", None) or "").endswith(bench_conftest._REHEARSAL)
    ]
    assert tables and all(table[CONFIG] == TINY for table in tables)


# -- the reader ---------------------------------------------------------------


@pytest.mark.parametrize("stats,expected", [
    ({"device_f32_bytes": 0, "device_f64_bytes": 800, "device_f16_bytes": 0}, 0.0),
    ({"device_f32_bytes": 300, "device_f64_bytes": 100, "device_f16_bytes": 0}, 75.0),
    ({"device_f32_bytes": 300, "device_f64_bytes": 0, "device_f16_bytes": 100}, 75.0),
    ({"device_f32_bytes": 4096, "device_f64_bytes": 0, "device_f16_bytes": 0}, 100.0),
    # the parent of the PR that brought the counters: nothing, no error
    ({"segments_traced": 1}, None),
    # a compute that produced no float
    ({"device_f32_bytes": 0, "device_f64_bytes": 0, "device_f16_bytes": 0}, None),
], ids=["float64", "mixed", "with-16-bit", "float32", "no-counters", "no-floats"])
def test_float32_share_reads_the_three_counters(stats, expected):
    assert SHARE.read(SimpleNamespace(stats=stats)) == expected
    (declared,) = SHARE.METRICS
    assert (declared["name"], declared["layer"], declared["moves"], declared["source"]) == (
        "float32_share.gen", "device", "compute_s", "program_counter")


def test_the_share_is_0_for_the_float64_query_and_follows_the_traced_dtype(tmp_path):
    import cubed_tpu as ct
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    plain = manifest.load_module(manifest.ROOT, manifest.query_file("vorticity_mean"))
    deploy = {**TINY, "dtype": "float64"}
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem="4GB")
    shares = []
    for executor in (JaxExecutor(), JaxExecutor(compute_dtype="float32")):
        plain.run(plain.build(deploy, {"seed": SEED}, spec, None), executor, None, None)
        shares.append(SHARE.read(SimpleNamespace(stats=executor.stats)))
    # under compute_dtype the plan says float64 and the counters say what ran
    assert shares == [0.0, 100.0]


# -- the check ----------------------------------------------------------------


def test_the_reference_alone_agrees_with_numpy_on_both_streams(query):
    deploy = {"shape": [23, 17, 12], "chunks": 5, "dtype": "float32"}
    for stream in ("philox", "threefry"):
        partial = query.reference_partials(deploy, SEED, stream=stream)
        assert len(partial) == 5 * 4 * 3 and partial[0][0] == (0, 0, 0)
        assert [c for c, _ in partial] == sorted(c for c, _ in partial)  # C order
        value = query.reference_mean(deploy, SEED, stream=stream)
        assert value == float(np.float32(value)) and abs(value - 0.5) < 0.05
    # the Philox stream whole, with numpy alone: float32 products, a float64 sum
    arrays = [np.empty(deploy["shape"], np.float32) for _ in range(4)]
    grid = query.block_grid(deploy["shape"], deploy["chunks"])
    k = 0
    for i in grid[0]:
        for j in grid[1]:
            for l in grid[2]:
                sel = tuple(slice(lo, hi) for lo, hi in (i, j, l))
                for array, root in zip(arrays, query.root_seeds(SEED)):
                    rng = np.random.Generator(np.random.Philox(seed=root + k))
                    array[sel] = rng.random(array[sel].shape, dtype=np.float32)
                k += 1
    a, b, x, y = arrays
    v = a[1:] * x[1:] + b[1:] * y[1:]
    assert v.dtype == np.float32
    expected = float(np.float32(math.fsum(v.astype(np.float64).ravel()) / v.size))
    assert query.reference_mean(deploy, SEED, stream="philox") == expected


def test_check_refuses_each_wrong_program(query):
    deploy = {"shape": [30, 20, 40], "chunks": 10, "dtype": "float32"}
    sources = {"seed": SEED}
    exact = query.reference_mean(deploy, SEED)
    query.check(deploy, sources, exact, None, None, True)
    query.check(deploy, sources, exact, exact, None, False)
    neighbour = float(np.nextafter(np.float32(exact), np.float32(1)))
    query.check(deploy, sources, neighbour, None, None, True)  # one unit: the limit
    assert query.ulps(neighbour, exact) == 1 == query.ULPS_TOLERANCE

    partial = query.reference_partials(deploy, SEED)
    slab = [s for coords, s in partial if coords[2] == 3]  # the last blocks of axis 2
    rest = [s for coords, s in partial if coords[2] != 3]
    n = query.count(deploy)
    wrong = {
        "generated as float64 and cast": query.reference_mean(deploy, SEED, generate="float64"),
        "generated in bfloat16": query.reference_mean(
            deploy, SEED, generate="bfloat16", stream="threefry"),
        "products in bfloat16": query.reference_mean(deploy, SEED, multiply="bfloat16"),
        "summed in bfloat16": query.reference_mean(deploy, SEED, accumulate="bfloat16"),
        "a slab dropped": query.rounded(math.fsum(rest) / (n - n // 4), "float32"),
        "a slab twice": query.rounded((math.fsum(rest) + 2 * math.fsum(slab)) / n, "float32"),
        "two units away": float(np.nextafter(np.float32(neighbour), np.float32(1))),
    }
    for what, value in wrong.items():
        assert query.ulps(value, exact) > query.ULPS_TOLERANCE, what
        with pytest.raises(AssertionError, match="blockwise reference"):
            query.check(deploy, sources, value, None, None, True)
    with pytest.raises(AssertionError, match="the same seed"):
        query.check(deploy, sources, exact, neighbour, None, False)
    with pytest.raises(AssertionError, match="no float32"):
        query.ulps(exact + 1e-12, exact)


def test_a_float32_accumulator_is_refused_by_the_counters_where_the_value_cannot_tell(query):
    # 24,000 values summed in float32 land within a unit of the float64 sum
    # rounded once, so at this size the value passes; the configuration's
    # rule does not: a program that sums in float32 produces no float64
    deploy = {"shape": [30, 20, 40], "chunks": 10, "dtype": "float32"}
    single = query.reference_mean(deploy, SEED, accumulate="float32")
    assert query.ulps(single, query.reference_mean(deploy, SEED)) <= 8
    rule = manifest.load_json(manifest.ROOT, "benchmark/configs/vorticity-f32.json")["device_path"]
    right = {"segments_traced": 1, "device_f32_bytes": 96000, "device_f64_bytes": 512,
             "device_f16_bytes": 0}
    loop.check_device_path(right, rule)
    for counter, value in (("device_f64_bytes", 0), ("device_f32_bytes", 0),
                           ("device_f16_bytes", 2), ("host_kernel_ops", 1)):
        with pytest.raises(AssertionError):
            loop.check_device_path({**right, counter: value}, rule)


# -- the cell, tiny -----------------------------------------------------------


def test_cell_untraced(tiny_root):
    out = _measure(tiny_root, trace=False)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"compute_s", "setup_s"}


def test_cell_traced_reads_a_share_near_100(tiny_root):
    out = _measure(tiny_root, trace=True)
    assert out["correct"] is True and out["failed"] == 0
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    # only mean's partial sums and the quotient are float64
    assert 99.0 < metrics["float32_share.gen"] < 100.0
    assert metrics["struct_hit_share.gen"] == 100.0
    assert metrics["compiles_in_window.gen"] == 0.0
    assert metrics["host_syncs.gen"] == 1.0
    assert {"plan_s", "segment_s", "struct_key_s", "dispatch_s", "plan_finalize_s",
            "unaccounted_s.gen", "first_compute_s", "compile_s"} <= set(metrics)
    assert not {"device_busy_s", "kernel_hbm_share"} & set(metrics)  # no device plane here


def test_the_parent_fails_the_cell_at_once(tiny_root, monkeypatch):
    # a program whose random() takes no dtype (the parent's): the first
    # compute raises, nothing hangs, no result is printed
    import cubed_tpu.random

    parents = cubed_tpu.random.random

    def random_without_dtype(size, *, diagnostics=None, chunks=None, spec=None):
        return parents(size, chunks=chunks, spec=spec)

    monkeypatch.setattr(cubed_tpu.random, "random", random_without_dtype)
    with pytest.raises(TypeError, match="dtype"):
        _measure(tiny_root, trace=False)
