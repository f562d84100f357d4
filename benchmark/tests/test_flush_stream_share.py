"""``flush_stream_share``: the share of the bytes fetched from the device that
reached the store from one of the executor's reused staging buffers with no
copy on the host after the join. Rehearsed on the CPU, where no value leaves
as planes (the CPU holds a real float64) and so nothing lies in a buffer until
the program's device probe is forced false; a program without the counter
gives nothing. Shares read here say nothing about the device."""

import os
import threading
import time
from types import SimpleNamespace

import pytest

from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

CELLS = ["zarr-add.colmean", "zarr-add.store", "zarr-add.rechunk"]
FLUSHING = ("zarr-add.store", "zarr-add.rechunk")
READER = manifest.load_module(manifest.ROOT, manifest.reader_file("flush_stream_share"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-flush"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


def _traced(root, cell_name):
    import jax

    bench = manifest.load(root)
    out = loop.measure(
        root=root, bench=bench, cell=manifest.cell(bench, cell_name),
        seed=2**31 + 35, seconds=1e-3, trace=True, devices=jax.devices(),
        t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0
    return {name: x["value"] for name, x in out["metrics"].items()}


@pytest.mark.parametrize("cell_name", CELLS)
def test_on_a_device_with_float64_nothing_lies_in_a_buffer(tiny_root, cell_name):
    got = _traced(tiny_root, cell_name)
    assert got["flush_stream_share"] == 0.0
    assert got["d2h_plane_share"] == 0.0


@pytest.mark.parametrize("cell_name", CELLS)
def test_on_a_pair_device_every_chunk_of_a_flushing_cell_streams(
    tiny_root, cell_name, monkeypatch
):
    """The probe forced false and the crossover lowered between the tiny
    store's chunks and the tiny column mean's: two cells exercise the route,
    the third bypasses it, as at full size. The flush's other readers still
    read, from two threads' spans folded into one event."""
    import cubed_tpu.runtime.executors.jax as jx

    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 2048)
    got = _traced(tiny_root, cell_name)
    streams = cell_name in FLUSHING
    assert got["flush_stream_share"] == (100.0 if streams else 0.0)
    assert got["d2h_plane_share"] == got["flush_stream_share"]
    for name in ("encode_s", "fsync_s", "store_write_s", "fetch_s", "d2h_s", "flush_wait_s"):
        assert got[name] >= 0.0, name
    assert got["fsync_s"] <= got["store_write_s"] + 1e-3
    assert got["encode_s"] <= got["store_write_s"] + 1e-3
    assert got["host_syncs.zarr"] == (4 if streams else 2)
    assert got["compiles_in_window.zarr"] == 0
    assert not any(t.name.startswith("cubed-tpu-flush") for t in threading.enumerate())


def test_the_reader_gives_none_for_a_program_without_the_counter():
    parent = SimpleNamespace(stats={"d2h_bytes": 800, "d2h_plane_bytes": 800, "host_syncs": 4})
    assert READER.read(parent) is None
    assert READER.read(SimpleNamespace(stats={})) is None
    assert READER.read(SimpleNamespace(stats={"flush_stream_bytes": 0, "d2h_bytes": 0})) is None
    assert READER.read(SimpleNamespace(stats={"flush_stream_bytes": 0, "d2h_bytes": 80})) == 0.0
    assert READER.read(SimpleNamespace(stats={"flush_stream_bytes": 60, "d2h_bytes": 80})) == 75.0
    assert READER.read(SimpleNamespace(stats={"flush_stream_bytes": 80, "d2h_bytes": 80})) == 100.0


def test_the_entry_is_in_the_manifest_for_the_zarr_cells():
    """Appended for the three Zarr cells there were; a later cell that flushes
    may be appended to its list, so the end of the list is not pinned."""
    bench = manifest.load()
    assert manifest.check() == []
    (entry,) = [x for x in bench["per_layer"] if x["name"] == "flush_stream_share"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "flush_stream_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "HBM to Zarr flush",
        "moves": "zarr_compute_s",
    } == READER.METRICS[0]
    assert entry["workloads"][:3] == ["zarr-add.store", "zarr-add.colmean", "zarr-add.rechunk"]
    for cell in bench["workloads"]:
        names = {x["name"] for x in manifest.metrics_for(bench, "per_layer", cell["name"])}
        assert ("flush_stream_share" in names) == (cell["name"] in entry["workloads"])
        if cell["name"].startswith("vorticity"):
            assert "flush_stream_share" not in names
