"""``h2d_stream_share``: the share of the bytes put on the device that went
chunk by chunk through the executor's staging buffers. Rehearsed on the CPU
on a tiny store and a tiny column mean, whose sources are four chunks each
and so stream whole; a source that finds no room is put whole and the share
says so; a program without the counter gives nothing. Shares read here
say nothing about the device."""

import os
import time
from types import SimpleNamespace

import pytest

from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

CELLS = ["zarr-add.colmean", "zarr-add.store"]
READER = manifest.load_module(manifest.ROOT, manifest.reader_file("h2d_stream_share"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-stream"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


def _traced(root, cell_name):
    import jax

    bench = manifest.load(root)
    out = loop.measure(
        root=root, bench=bench, cell=manifest.cell(bench, cell_name),
        seed=2**31 + 29, seconds=1e-3, trace=True, devices=jax.devices(),
        t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0
    return {name: x["value"] for name, x in out["metrics"].items()}


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_source_of_a_zarr_cell_streams(tiny_root, cell_name):
    got = _traced(tiny_root, cell_name)
    assert got["h2d_stream_share"] == 100.0
    # the stream's reads and puts are what the preload's other readers read
    assert 0 < got["host_read_s"] <= got["preload_s"] + 1e-3
    assert 0 < got["h2d_s"] <= got["preload_s"] + 1e-3
    # and nothing else of the compute changed its count
    assert got["host_syncs.zarr"] == (4 if cell_name == "zarr-add.store" else 2)
    assert got["compiles_in_window.zarr"] == 0
    assert got["struct_hit_share.zarr"] == 100.0


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_source_without_room_is_put_whole_and_the_share_says_so(
    tiny_root, cell_name, monkeypatch
):
    """A device of 32-bit pairs (the probe forced false) whose budget holds
    the two sources and the result: the first source finds the room that its
    update asks for, twice the array and two chunks; the second, with the
    first resident, does not, and is put whole."""
    import cubed_tpu.runtime.executors.jax as jx

    nbytes = 200 * 200 * 8
    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx.JaxExecutor, "_budget", lambda self: 3 * nbytes)
    got = _traced(tiny_root, cell_name)
    assert got["h2d_stream_share"] == 50.0


def test_the_reader_gives_none_for_a_program_without_the_counter():
    parent = SimpleNamespace(stats={"h2d_bytes": 800, "host_syncs": 4})
    assert READER.read(parent) is None
    assert READER.read(SimpleNamespace(stats={})) is None
    assert READER.read(SimpleNamespace(stats={"h2d_stream_bytes": 0, "h2d_bytes": 0})) is None
    assert READER.read(SimpleNamespace(stats={"h2d_stream_bytes": 0, "h2d_bytes": 80})) == 0.0
    assert READER.read(SimpleNamespace(stats={"h2d_stream_bytes": 60, "h2d_bytes": 80})) == 75.0
    assert READER.read(SimpleNamespace(stats={"h2d_stream_bytes": 80, "h2d_bytes": 80})) == 100.0


def test_the_entry_is_appended_for_the_two_zarr_cells_only():
    bench = manifest.load()
    assert manifest.check() == []
    (entry,) = [x for x in bench["per_layer"] if x["name"] == "h2d_stream_share"]
    assert entry == {
        "name": "h2d_stream_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Zarr to HBM preload",
        "moves": "zarr_compute_s", "workloads": ["zarr-add.store", "zarr-add.colmean"],
    }
    assert READER.METRICS[0] == {k: v for k, v in entry.items() if k != "workloads"}
    for cell in bench["workloads"]:
        names = {x["name"] for x in manifest.metrics_for(bench, "per_layer", cell["name"])}
        assert ("h2d_stream_share" in names) == (cell["name"] in CELLS)
