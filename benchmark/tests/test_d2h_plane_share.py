"""``d2h_plane_share``: the share of the fetched bytes that left the device as
32-bit planes. Rehearsed on the CPU, where no value takes that route (the
CPU holds a real float64) until the program's device probe is forced false;
a program without the counter gives nothing. Shares read here say nothing
about the device."""

import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

CELLS = ["zarr-add.colmean", "zarr-add.store"]
READER = manifest.load_module(manifest.ROOT, manifest.reader_file("d2h_plane_share"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-planes"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


def _traced(root, cell_name):
    import jax

    bench = manifest.load(root)
    out = loop.measure(
        root=root, bench=bench, cell=manifest.cell(bench, cell_name),
        seed=2**31 + 31, seconds=1e-3, trace=True, devices=jax.devices(),
        t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0
    return {name: x["value"] for name, x in out["metrics"].items()}


@pytest.mark.parametrize("cell_name", CELLS)
def test_on_a_device_with_float64_nothing_leaves_as_planes(tiny_root, cell_name):
    got = _traced(tiny_root, cell_name)
    assert got["d2h_plane_share"] == 0.0
    assert got["d2h_gb_per_s"] > 0 and got["d2h_s"] <= got["fetch_s"] + 1e-3


@pytest.mark.parametrize("cell_name", CELLS)
def test_on_a_pair_device_the_share_follows_the_size_of_the_fetches(
    tiny_root, cell_name, monkeypatch
):
    """The probe forced false and the crossover lowered between the tiny
    store's chunks and the tiny column mean's: one cell exercises the route,
    the other bypasses it, as at full size."""
    import cubed_tpu.runtime.executors.jax as jx

    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    monkeypatch.setattr(jx, "_PLANES_MIN_BYTES", 2048)
    got = _traced(tiny_root, cell_name)
    assert 0.0 <= got["d2h_plane_share"] <= 100.0
    assert got["d2h_plane_share"] == (100.0 if cell_name == "zarr-add.store" else 0.0)
    # the split, the fetch and the join all lie inside jax.d2h, inside _to_host
    assert got["d2h_s"] <= got["fetch_s"] + 1e-3
    assert got["host_syncs.zarr"] == (4 if cell_name == "zarr-add.store" else 2)
    assert got["compiles_in_window.zarr"] == 0


def test_the_reader_gives_none_for_a_program_without_the_counter():
    parent = SimpleNamespace(stats={"d2h_bytes": 800, "host_syncs": 4})
    assert READER.read(parent) is None
    assert READER.read(SimpleNamespace(stats={})) is None
    assert READER.read(SimpleNamespace(stats={"d2h_plane_bytes": 0, "d2h_bytes": 0})) is None
    assert READER.read(SimpleNamespace(stats={"d2h_plane_bytes": 0, "d2h_bytes": 80})) == 0.0
    assert READER.read(SimpleNamespace(stats={"d2h_plane_bytes": 60, "d2h_bytes": 80})) == 75.0


def test_the_manifest_passes_with_the_appended_entry():
    done = subprocess.run(
        [sys.executable, "benchmark/check_manifest.py"], cwd=manifest.ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stdout + done.stderr
    bench = manifest.load()
    assert bench["per_layer"][-1]["name"] == "d2h_plane_share"
    assert bench["per_layer"][-1]["workloads"] == ["zarr-add.store", "zarr-add.colmean"]
    for cell_name in CELLS:
        assert "d2h_plane_share" in {
            x["name"] for x in manifest.metrics_for(bench, "per_layer", cell_name)
        }
    assert "d2h_plane_share" not in {
        x["name"] for x in manifest.metrics_for(bench, "per_layer", "vorticity.mean")
    }
