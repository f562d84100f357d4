"""The cell ``vorticity-mesh4.mean`` rehearsed on the CPU: its query and mix at
a tiny shape through ``loop.measure`` on four virtual devices, the blockwise
reference against numpy, what the comparison refuses, and the two readers of
the mesh placement counters. No time read here means anything."""

import itertools
import json
import shutil
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.harness import loop, manifest

CELL = "vorticity-mesh4.mean"
TINY = {"shape": [50, 40, 40], "chunks": 10}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark whose mesh configuration deploys a tiny shape."""
    path = tmp_path_factory.mktemp("tiny-exact")
    shutil.copytree(manifest.ROOT / "benchmark", path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(manifest.ROOT / "BENCHMARK.json", path)
    file = path / "benchmark" / "configs" / "vorticity-mesh4.json"
    config = json.loads(file.read_text())
    config["deployment"].update(TINY)
    file.write_text(json.dumps(config))
    return path


@pytest.fixture(scope="module")
def query():
    return manifest.load_module(manifest.ROOT, manifest.query_file("vorticity_mean_exact"))


def test_the_cell_is_in_the_manifest_with_its_mix_and_readers():
    bench = manifest.load()
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "vorticity_mean_exact.closed-1"
    mix = manifest.load_json(manifest.ROOT, manifest.traffic_file(cell["traffic"]))
    assert mix["query"] == "vorticity_mean_exact" and mix["profiled_computes"] == 2
    assert [x["name"] for x in manifest.metrics_for(bench, "end_to_end", CELL)] == [
        "compute_s", "setup_s"]
    reported = {x["name"] for x in manifest.metrics_for(bench, "per_layer", CELL)}
    assert {"chip_busy_min_share", "collective_s", "segment_collectives.gen",
            "replicated_share.gen", "hbm_footprint_frac.gen", "struct_hit_share.gen",
            "compiles_in_window.gen"} <= reported
    assert manifest.check() == []


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_cell_runs_correct_on_four_virtual_devices(tiny_root, trace):
    import jax

    bench = manifest.load(tiny_root)
    cell = manifest.cell(bench, CELL)
    assert len(jax.devices()) >= cell["chips"]
    out = loop.measure(
        root=tiny_root, bench=bench, cell=cell, seed=2**31 + 17, seconds=0.5,
        trace=trace, devices=jax.devices(), t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    if not trace:
        assert set(out["metrics"]) == {"compute_s", "setup_s"}
        return
    # what is read from the program's counters is there without a device trace
    # (the CPU states no ``bytes_limit``, so no ``hbm_footprint_frac.gen`` here)
    assert {"segment_collectives.gen", "replicated_share.gen", "struct_hit_share.gen",
            "compiles_in_window.gen"} <= set(out["metrics"])
    assert out["metrics"]["struct_hit_share.gen"]["value"] == 100.0
    assert out["metrics"]["compiles_in_window.gen"]["value"] == 0.0
    assert out["metrics"]["replicated_share.gen"]["value"] < 1.0
    assert not {"chip_busy_min_share", "collective_s"} & set(out["metrics"])


def _values_with_numpy(query, deploy, seed):
    """The four arrays whole, from the documented stream, and numpy's
    ``a[1:]*x[1:] + b[1:]*y[1:]`` of them."""
    import jax

    shape, chunk = tuple(deploy["shape"]), deploy["chunks"]
    grid = query.block_grid(shape, chunk)
    numblocks = tuple(len(axis) for axis in grid)
    arrays = [np.empty(shape) for _ in range(4)]
    with jax.threefry_partitionable(True):
        for k, coords in enumerate(itertools.product(*map(range, numblocks))):
            bounds = [grid[d][c] for d, c in enumerate(coords)]
            sel = tuple(slice(lo, hi) for lo, hi in bounds)
            for array, root in zip(arrays, query.root_seeds(seed)):
                key = jax.random.fold_in(jax.random.key(0), root + k)
                array[sel] = jax.random.uniform(
                    key, tuple(hi - lo for lo, hi in bounds), dtype="float64")
    a, b, x, y = arrays
    return a[1:] * x[1:] + b[1:] * y[1:]


@pytest.mark.parametrize("shape,chunk", [((30, 20, 20), 10), ((23, 17, 12), 5)])
def test_reference_alone_agrees_with_numpy(query, shape, chunk):
    deploy = {"shape": list(shape), "chunks": chunk}
    seed = 2**31 + 3
    expected = float(np.mean(_values_with_numpy(query, deploy, seed)))
    assert query.compare(query.reference_mean(deploy, seed), expected) < 1e-14
    import jax

    rotated = query.reference_mean(deploy, seed, devices=jax.local_devices())
    assert rotated == query.reference_mean(deploy, seed)


def test_check_refuses_float32_a_dropped_slab_and_a_slab_counted_twice(query):
    deploy = {"shape": [30, 20, 40], "chunks": 10}
    seed = 2**31 + 29
    sources = {"seed": seed}
    exact = query.reference_mean(deploy, seed)
    query.check(deploy, sources, exact, None, None, True)
    query.check(deploy, sources, exact, exact, None, False)

    # float32 ``uniform`` draws other values, so this lies a standard error away
    single = query.reference_mean(deploy, seed, dtype="float32")
    assert query.compare(single, exact) > 1e-9
    values = _values_with_numpy(query, deploy, seed)
    slab = values[:, :, 30:]  # what the last of four chips holds of axis 2
    dropped = float(np.mean(values[:, :, :30]))  # left out, divided by the smaller count
    twice = float((values.sum() + slab.sum()) / values.size)
    for wrong in (single, dropped, twice, float(np.float32(exact))):
        with pytest.raises(AssertionError):
            query.check(deploy, sources, wrong, None, None, True)
    with pytest.raises(AssertionError):
        query.check(deploy, sources, exact, np.nextafter(exact, 1.0), None, False)
    assert query.RELATIVE_TOLERANCE <= 1e-9


def test_readers_of_the_mesh_placement_counters():
    collectives = manifest.load_module(manifest.ROOT, manifest.reader_file("segment_collectives.gen"))
    share = manifest.load_module(manifest.ROOT, manifest.reader_file("replicated_share.gen"))
    recorded = SimpleNamespace(stats={
        "mesh_devices": 4, "segment_collectives": 7, "segment_all_reduce": 3,
        "segment_all_gather": 4, "segment_all_to_all": 0, "segment_collective_permute": 0,
        "sharded_bytes": 3 * 2**20, "replicated_bytes": 2**20,
    })
    assert collectives.read(recorded) == 7
    assert share.read(recorded) == 25.0
    # the parent of the PR that brought the counters has none: nothing, no error
    parent = SimpleNamespace(stats={"segments_traced": 1})
    assert collectives.read(parent) is None and share.read(parent) is None
    # one device: the counters are there and nothing was pinned
    single = SimpleNamespace(stats={"segment_collectives": 0, "sharded_bytes": 0,
                                    "replicated_bytes": 0})
    assert collectives.read(single) == 0 and share.read(single) is None
