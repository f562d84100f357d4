"""``preload_lane_share``: the share of the bytes a compute streamed to the
device that reached their chip on a lane thread of that chip's own. The
reader on hand-made counters; its entry in the manifest; and the four Zarr
cells rehearsed on the CPU at the tiny size, traced: 0 in the three one-chip
cells, whose one lane runs on the calling thread, 100 under the mesh of four
virtual devices. Shares read here say nothing about the device."""

import os
import time
from types import SimpleNamespace

import pytest

import benchmark.tests.test_zarr_add_mesh4_colmean as mesh4  # enters the mesh cell's tiny shape
from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

CELLS = {"zarr-add.store": 0.0, "zarr-add.colmean": 0.0, "zarr-add.rechunk": 0.0, mesh4.CELL: 100.0}
READER = manifest.load_module(manifest.ROOT, manifest.reader_file("preload_lane_share"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-lanes"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


def test_the_reader_on_hand_made_counters():
    def read(**stats):
        return READER.read(SimpleNamespace(stats=stats))

    assert read(h2d_lane_bytes=800, h2d_stream_bytes=800) == 100.0
    assert read(h2d_lane_bytes=0, h2d_stream_bytes=800) == 0.0
    assert read(h2d_lane_bytes=200, h2d_stream_bytes=800) == 25.0
    # a program without the counter: the parent of the PR that brought the lanes
    assert read(h2d_stream_bytes=800, stage_wait_us=5) is None
    assert read() is None
    # nothing streamed: nothing to take a share of
    assert read(h2d_lane_bytes=0, h2d_stream_bytes=0) is None
    assert read(h2d_lane_bytes=0) is None


def test_the_entry_is_in_the_manifest_for_the_zarr_cells():
    bench = manifest.load()
    assert manifest.check() == []
    (entry,) = [x for x in bench["per_layer"] if x["name"] == "preload_lane_share"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "preload_lane_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Zarr to HBM preload",
        "moves": "zarr_compute_s",
    } == READER.METRICS[0]
    # a later cell that streams may be appended: the end of the list is not pinned
    assert entry["workloads"][:4] == list(CELLS)
    for cell in bench["workloads"]:
        names = {x["name"] for x in manifest.metrics_for(bench, "per_layer", cell["name"])}
        assert ("preload_lane_share" in names) == (cell["name"] in entry["workloads"])


@pytest.mark.parametrize("cell_name", list(CELLS))
def test_the_share_is_the_meshs_and_zero_on_one_chip(tiny_root, cell_name):
    import jax

    import cubed_tpu.runtime.executors.jax as jx

    jx.release_staging_buffers()  # as a process starts
    bench = manifest.load(tiny_root)
    cell = manifest.cell(bench, cell_name)
    assert len(jax.devices()) >= cell["chips"]
    out = loop.measure(
        root=tiny_root, bench=bench, cell=cell, seed=2**31 + 41, seconds=1e-3,
        trace=True, devices=jax.devices(), t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0
    got = {name: x["value"] for name, x in out["metrics"].items()}
    assert got["preload_lane_share"] == CELLS[cell_name]
    assert got["h2d_stream_share"] == got["stage_reuse_share"] == 100.0
    assert got["compiles_in_window.zarr"] == 0
    # a pair a lane stays with the process
    assert len(jx._STAGING_POOL) == cell["chips"]
    jx.release_staging_buffers()
