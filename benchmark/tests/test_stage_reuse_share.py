"""``stage_reuse_share``: the share of the bytes a compute sent through a
staging buffer that passed through one it found already allocated when it
leased the process's pair. Rehearsed on the CPU at the tiny size: the set-up's
first compute makes the two buffers, every compute of the window finds them;
a program without the counter gives nothing. Shares read here say nothing
about the device."""

import os
import time
from types import SimpleNamespace

import pytest

from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

CELLS = ["zarr-add.colmean", "zarr-add.store", "zarr-add.rechunk"]
READER = manifest.load_module(manifest.ROOT, manifest.reader_file("stage_reuse_share"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-stage"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_compute_of_the_window_finds_the_buffers_made(tiny_root, cell_name, monkeypatch):
    """On a pair device, so that ``store`` and ``rechunk`` stage on the way
    out too: the last compute's whole traffic through the pair counts."""
    import jax

    import cubed_tpu.runtime.executors.jax as jx

    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 2048)
    jx.release_staging_buffers()  # as a process starts
    bench = manifest.load(tiny_root)
    out = loop.measure(
        root=tiny_root, bench=bench, cell=manifest.cell(bench, cell_name),
        seed=2**31 + 37, seconds=1e-3, trace=True, devices=jax.devices(),
        t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0
    got = {name: x["value"] for name, x in out["metrics"].items()}
    assert got["stage_reuse_share"] == 100.0
    assert got["h2d_stream_share"] == 100.0
    assert got["flush_stream_share"] == (0.0 if cell_name == "zarr-add.colmean" else 100.0)
    assert got["compiles_in_window.zarr"] == 0
    assert len(jx._STAGING_POOL) == 1


def test_the_reader_gives_none_for_a_program_without_the_counter():
    parent = SimpleNamespace(stats={"h2d_stream_bytes": 800, "flush_stream_bytes": 800})
    assert READER.read(parent) is None
    assert READER.read(SimpleNamespace(stats={})) is None
    # nothing staged: nothing to take a share of
    nothing = {"stage_reused_bytes": 0, "h2d_stream_bytes": 0, "flush_stream_bytes": 0}
    assert READER.read(SimpleNamespace(stats=nothing)) is None
    assert READER.read(SimpleNamespace(stats={"stage_reused_bytes": 0})) is None
    stats = {"stage_reused_bytes": 0, "h2d_stream_bytes": 60, "flush_stream_bytes": 20}
    assert READER.read(SimpleNamespace(stats=stats)) == 0.0
    assert READER.read(SimpleNamespace(stats={**stats, "stage_reused_bytes": 60})) == 75.0
    assert READER.read(SimpleNamespace(stats={**stats, "stage_reused_bytes": 80})) == 100.0
    assert READER.read(SimpleNamespace(stats={"stage_reused_bytes": 8, "h2d_stream_bytes": 8})) == 100.0


def test_the_entry_is_in_the_manifest_for_the_zarr_cells():
    """Appended for the three Zarr cells there were; a later cell that streams
    may be appended to its list, so the end of the list is not pinned."""
    bench = manifest.load()
    assert manifest.check() == []
    (entry,) = [x for x in bench["per_layer"] if x["name"] == "stage_reuse_share"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "stage_reuse_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Zarr to HBM preload",
        "moves": "zarr_compute_s",
    } == READER.METRICS[0]
    assert entry["workloads"][:3] == ["zarr-add.store", "zarr-add.colmean", "zarr-add.rechunk"]
    for cell in bench["workloads"]:
        names = {x["name"] for x in manifest.metrics_for(bench, "per_layer", cell["name"])}
        assert ("stage_reuse_share" in names) == (cell["name"] in entry["workloads"])
