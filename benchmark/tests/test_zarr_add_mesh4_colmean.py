"""The cell ``zarr-add-mesh4.colmean``: its entries in the manifest, its three
readers, what its ``device_path`` rule refuses, the query against
``PythonDagExecutor`` on numpy under the configuration's placement, and the
cell rehearsed on four virtual CPU devices at a tiny size, untraced and
traced. Shares read here say nothing about the device.

Importing this file enters the configuration's tiny shape into the tables
that ``test_rehearsal.py`` shrinks configurations from (that file and
``benchmark/conftest.py`` are not a later PR's to edit), so an invocation
that collects this file with it rehearses the cell tiny. One that runs
``test_rehearsal.py`` alone does not, and would run the cell at its real
size on the CPU."""

import time
from types import SimpleNamespace

import numpy as np
import pytest

import benchmark.conftest as bench_conftest
import benchmark.tests.test_rehearsal as rehearsal
from benchmark.harness import loop, manifest

CONFIG, CELL = "zarr-add-mesh4", "zarr-add-mesh4.colmean"
TINY = {"shape": [400, 400], "chunks": [100, 100]}  # a 4 x 4 grid, a chunk-row a chip
# before the session fixture of benchmark/conftest.py reads its table (it
# runs when the first test starts), and for the copy of test_rehearsal.py
# that this file imports
bench_conftest.TINY.setdefault(CONFIG, TINY)
rehearsal.TINY.setdefault(CONFIG, TINY)

OWNER = manifest.load_module(manifest.ROOT, manifest.reader_file("owner_io_share"))
COLLECTIVE = manifest.load_module(manifest.ROOT, manifest.reader_file("zarr_collective_s"))
COUNT = manifest.load_module(manifest.ROOT, manifest.reader_file("zarr_segment_collectives"))
#: what the cell adds, in the manifest's order: (reader, name, source, unit)
NEW = [
    (OWNER, "owner_io_share", "program_counter", "%"),
    (COLLECTIVE, "zarr_collective_s", "device_trace", "s"),
    (COUNT, "zarr_segment_collectives", "program_counter", "count"),
]
CONTROL = "zarr-add.colmean"
SEED = 2**31 + 39


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return rehearsal._tiny_root(tmp_path_factory.mktemp("tiny-mesh4-colmean"))


def _measure(root, trace):
    import jax

    bench = manifest.load(root)
    cell = manifest.cell(bench, CELL)
    assert len(jax.devices()) >= cell["chips"]
    return loop.measure(
        root=root, bench=bench, cell=cell, seed=SEED, seconds=0.5,
        trace=trace, devices=jax.devices(), t_start=time.perf_counter(),
    )


# -- the manifest -------------------------------------------------------------


def test_the_cell_is_in_the_manifest_with_its_configuration_mix_and_readers():
    bench = manifest.load()
    assert manifest.check() == []
    # a later PR appends after these: nothing here pins the end of a list
    assert len(bench["configs"]) >= 6 and len(bench["workloads"]) >= 7
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4][:2] == [
        "vorticity-mesh4.mean", CELL]
    cell = manifest.cell(bench, CELL)
    control = manifest.cell(bench, CONTROL)
    # the mix and the query are the one-chip control's, unchanged
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, control["traffic"], 4)
    mix = manifest.load_json(manifest.ROOT, manifest.traffic_file(cell["traffic"]))
    assert (mix["query"], mix["loop"], mix["clients"], mix["metric"], mix["profiled_computes"]) == (
        "zarr_add_colmean", "closed", 1, "zarr_compute_s", 1)
    entry = manifest.config_entry(bench, CONFIG)
    config = manifest.load_json(manifest.ROOT, entry["file"])
    assert entry["file"] == "benchmark/configs/zarr-add-mesh4.json"
    assert entry["source"] == config["source"] and "20000x20000" in entry["source"]
    assert entry["reduced"] == ["shape"] == list(config["reduced"])
    assert config["chips"] == 4
    assert "zarr-add-mesh4.store is owed" in config["stands_for"]
    # the data side, the memory limit, the integrity mode and every guarantee
    # are zarr-add-10k's, word for word: only the side and the placement differ
    store = manifest.load_json(manifest.ROOT, "benchmark/configs/zarr-add-10k.json")
    assert config["deployment"] == {
        **store["deployment"], "shape": [20000, 20000], "executor": {"mesh": True}}
    assert {k: config["guarantees"][k] for k in store["guarantees"]} == store["guarantees"]
    assert set(config["guarantees"]) == {*store["guarantees"], "path"}
    assert "owns it" in config["guarantees"]["path"]
    assert "twice the mean" in config["guarantees"]["path"]
    assert {k: config["assumed"][k] for k in store["assumed"]} == store["assumed"]
    assert {"executor", "placement"} <= set(config["assumed"])
    assert config["device_path"]["zero"] == store["device_path"]["zero"] + ["mesh_gathered_bytes"]
    assert config["device_path"]["positive"] == ["segments_traced", "mesh_owner_bytes"]
    assert [x["name"] for x in manifest.metrics_for(bench, "end_to_end", CELL)] == [
        "zarr_compute_s", "setup_s"]


def test_the_cell_joins_every_list_of_its_control_and_brings_three_metrics():
    bench = manifest.load()
    per_layer = {x["name"]: x for x in manifest.metrics_for(bench, "per_layer", CELL)}
    of_control = {x["name"] for x in manifest.metrics_for(bench, "per_layer", CONTROL)}
    assert set(per_layer) == of_control | {name for _, name, _, _ in NEW}
    for name in of_control:
        listed = per_layer[name].get("workloads")
        assert listed is None or listed.index(CELL) > listed.index(CONTROL), name
    # what the vorticity cells and the rechunk read is not this cell's
    for name in ("rechunk_alias_share", "bits_carried_share", "collective_s",
                 "chip_busy_min_share", "segment_collectives.gen", "replicated_share.gen"):
        assert name not in per_layer
    names = [x["name"] for x in bench["per_layer"]]
    at = [names.index(name) for _, name, _, _ in NEW]
    assert at == list(range(at[0], at[0] + 3)) and at[0] > names.index("stage_reuse_share")
    for reader, name, source, unit in NEW:
        declared = {**per_layer[name]}
        assert declared.pop("workloads")[0] == CELL
        assert reader.METRICS == [declared]
        assert (declared["layer"], declared["moves"], declared["source"], declared["unit"]) == (
            "mesh placement", "zarr_compute_s", source, unit)


def test_the_tiny_shape_is_in_both_tables_of_the_rehearsal():
    import sys

    tables = [
        module.TINY for module in list(sys.modules.values())
        if (getattr(module, "__file__", None) or "").endswith(bench_conftest._REHEARSAL)
    ]
    assert tables and all(table[CONFIG] == TINY for table in tables)


# -- the readers --------------------------------------------------------------


@pytest.mark.parametrize("stats, expected", [
    ({"mesh_owner_bytes": 6_400_160_000, "mesh_gathered_bytes": 0}, 100.0),
    ({"mesh_owner_bytes": 300, "mesh_gathered_bytes": 100}, 75.0),
    ({"mesh_owner_bytes": 0, "mesh_gathered_bytes": 6_400_000_000}, 0.0),
    # the parent of the PR that brought the counters: nothing, no error
    ({"segments_traced": 1, "h2d_bytes": 6_400_000_000}, None),
    # a compute without a mesh: both present, both 0
    ({"mesh_owner_bytes": 0, "mesh_gathered_bytes": 0}, None),
    # a compute that failed its check leaves the harness no counters
    ({}, None),
], ids=["every-chunk-on-its-owner", "mixed", "all-gathered", "no-counters", "no-mesh", "empty"])
def test_owner_io_share_reads_the_two_counters(stats, expected):
    assert OWNER.read(SimpleNamespace(stats=stats)) == expected


@pytest.mark.parametrize("device, expected", [
    # two profiled computes, chip 2 the busiest: its union of collectives, a compute
    ({"busy_s": {0: 0.2, 1: 0.2, 2: 0.3, 3: 0.2}, "collective_s": {0: 0.5, 1: 0.5, 2: 0.04, 3: 0.5},
      "busiest": 2, "computes": 2}, 0.02),
    ({"busy_s": {0: 0.2, 1: 0.2, 2: 0.3, 3: 0.2}, "collective_s": {0: 0.0, 1: 0.0, 2: 0.0, 3: 0.0},
      "busiest": 2, "computes": 1}, 0.0),
    # one chip in the trace, and no trace at all: nothing
    ({"busy_s": {0: 0.2}, "collective_s": {0: 0.0}, "busiest": 0, "computes": 1}, None),
    (None, None),
], ids=["present", "zero", "one-chip", "absent"])
def test_zarr_collective_s_reads_the_busiest_chips_collectives(device, expected):
    traced = SimpleNamespace(device=device)
    traced.busiest_per_compute = lambda key: loop.Traced.busiest_per_compute(traced, key)
    assert COLLECTIVE.read(traced) == expected


@pytest.mark.parametrize("stats, expected", [
    ({"segment_collectives": 6, "segment_all_reduce": 6, "segment_all_gather": 0}, 6),
    ({"segment_collectives": 0}, 0),
    # a program without the counter, and a compute that left no counters
    ({"segments_traced": 1}, None),
    ({}, None),
], ids=["a-handful", "none", "no-counter", "empty"])
def test_zarr_segment_collectives_reads_the_counter(stats, expected):
    assert COUNT.read(SimpleNamespace(stats=stats)) == expected


# -- the rule -----------------------------------------------------------------


def test_the_rule_refuses_a_chunk_that_touched_more_than_one_chip():
    rule = manifest.load_json(manifest.ROOT, "benchmark/configs/zarr-add-mesh4.json")["device_path"]
    right = {"segments_traced": 1, "mesh_owner_bytes": 6_400_160_000, "mesh_gathered_bytes": 0}
    loop.check_device_path(right, rule)
    for wrong in ({"mesh_gathered_bytes": 40_000}, {"mesh_owner_bytes": 0},
                  {"segments_traced": 0}, {"eager_fallbacks": 1}, {"segment_mem_aborts": 1}):
        with pytest.raises(AssertionError):
            loop.check_device_path({**right, **wrong}, rule)
    # the parent of this PR has neither counter: the rule reads it as off the path
    with pytest.raises(AssertionError, match="mesh_owner_bytes"):
        loop.check_device_path({"segments_traced": 1}, rule)


# -- the query under the configuration's placement ----------------------------


def test_the_query_agrees_with_the_python_executor_and_with_numpy(tmp_path):
    import jax

    import cubed_tpu as ct
    from cubed_tpu.parallel.mesh import make_mesh
    from cubed_tpu.runtime.executors.jax import JaxExecutor
    from cubed_tpu.runtime.executors.python import PythonDagExecutor

    query = manifest.load_module(manifest.ROOT, manifest.query_file("zarr_add_colmean"))
    deploy = {**TINY, "dtype": "float64"}
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem="2GB")
    sources = query.make_sources(deploy, SEED, str(tmp_path))
    meshed = JaxExecutor(mesh=make_mesh(devices=jax.devices()[:4]))
    got = query.run(query.build(deploy, sources, spec, None), meshed, None, None)
    plain = query.run(query.build(deploy, sources, spec, None), PythonDagExecutor(), None, None)
    assert got.shape == (400,) and got.dtype == np.float64 == plain.dtype
    query.check(deploy, sources, got, None, None, True)
    query.check(deploy, sources, plain, None, None, True)
    np.testing.assert_allclose(got, plain, rtol=400 * query.MEAN_RTOL_PER_ROW, atol=0)
    # every chunk of both sources went to the chip that owns its chunk-row,
    # and every chunk of the row that came back left from one chip
    stats = meshed.stats
    assert stats["mesh_gathered_bytes"] == 0 and stats["h2d_stream_bytes"] == stats["h2d_bytes"]
    assert stats["mesh_owner_bytes"] == stats["h2d_bytes"] + stats["d2h_bytes"]
    assert stats["h2d_bytes"] == 2 * 400 * 400 * 8 and stats["d2h_bytes"] == 400 * 8
    # the check refuses a mean taken over the wrong axis, or of one source
    a = sources["ref"]
    for wrong in (a[::-1].copy(), a / 2, a * (1 + 2.0**-40)):
        with pytest.raises(AssertionError):
            query.check(deploy, sources, wrong, None, None, True)


# -- the cell, tiny, on four virtual devices ----------------------------------


def test_cell_untraced(tiny_root):
    out = _measure(tiny_root, trace=False)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"zarr_compute_s", "setup_s"}
    assert out["device"]["count"] >= 4


def test_cell_traced_moves_every_chunk_between_the_host_and_its_owner(tiny_root):
    out = _measure(tiny_root, trace=True)
    assert out["correct"] is True and out["failed"] == 0
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert metrics["owner_io_share"] == 100.0
    assert metrics["h2d_stream_share"] == 100.0
    assert metrics["stage_reuse_share"] == 100.0
    assert metrics["preload_page_faults"] == 0.0
    assert metrics["host_syncs.zarr"] == 4.0  # one a chunk of the row
    assert metrics["struct_hit_share.zarr"] == 100.0
    assert metrics["compiles_in_window.zarr"] == 0.0
    assert metrics["flush_stream_share"] == 0.0 and metrics["d2h_plane_share"] == 0.0
    assert metrics["zarr_segment_collectives"] >= 1.0
    assert {"preload_s", "fetch_s", "store_write_s", "flush_s", "unaccounted_s.zarr",
            "first_compute_s", "compile_s", "host_read_s", "h2d_s", "stage_wait_s"} <= set(metrics)
    # no device plane in a CPU trace, so nothing read from one
    assert "zarr_collective_s" not in metrics
