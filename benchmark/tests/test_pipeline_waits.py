"""``flush_s``, ``write_wait_s``, ``checksum_s``, ``stage_wait_s``,
``preload_page_faults``: who waited for whom in the two streaming pipelines,
and whether the preload's pages were fresh. One reads a span of the program,
four its always-on counters. Rehearsed on the CPU at a tiny size:
every reader gives a number in the three Zarr cells, and nothing from a
program without the span or the counter. Times and counts read here say
nothing about the device or its host."""

import os
import threading
import time
from types import SimpleNamespace

import pytest

from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

CELLS = ["zarr-add.colmean", "zarr-add.store", "zarr-add.rechunk"]
#: metric -> (the counter it reads, what one unit of the counter is in the metric's)
COUNTERS = {
    "write_wait_s": ("write_wait_us", 1e-6),
    "checksum_s": ("checksum_us", 1e-6),
    "stage_wait_s": ("stage_wait_us", 1e-6),
    "preload_page_faults": ("preload_page_faults", 1),
}
NAMES = ["flush_s", *COUNTERS]
READERS = {
    name: manifest.load_module(manifest.ROOT, manifest.reader_file(name)) for name in NAMES
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-waits"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


def _traced(root, cell_name):
    import jax

    bench = manifest.load(root)
    out = loop.measure(
        root=root, bench=bench, cell=manifest.cell(bench, cell_name),
        seed=2**31 + 36, seconds=1e-3, trace=True, devices=jax.devices(),
        t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0
    return {name: x["value"] for name, x in out["metrics"].items()}


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_reader_gives_a_number_in_a_zarr_cell(tiny_root, cell_name):
    got = _traced(tiny_root, cell_name)
    for name in NAMES:
        assert name in got and got[name] >= 0.0, name
    # the executor's thread cannot wait for the writer longer than the flush
    # lasts, and the flush is shorter than its two overlapping sides together
    assert got["write_wait_s"] <= got["flush_s"] + 1e-3
    assert got["flush_s"] <= got["fetch_s"] + got["store_write_s"] + got["flush_wait_s"] + 5e-3
    # integrity mode write: every target chunk has a checksum, inside its write
    assert 0.0 < got["checksum_s"] <= got["store_write_s"] + 1e-3
    # the preload's waits alone: the flush's wait for the last update is not in it
    assert got["stage_wait_s"] <= got["preload_s"] + 1e-3
    # the readers the flush and the preload already had still read
    for name in ("h2d_s", "fsync_s", "fetch_s", "store_write_s", "d2h_s", "flush_wait_s",
                 "host_read_s", "preload_s"):
        assert got[name] >= 0.0, name
    assert got["fsync_s"] <= got["store_write_s"] + 1e-3
    assert got["h2d_s"] <= got["preload_s"] + 1e-3
    assert got["compiles_in_window.zarr"] == 0
    assert not any(t.name.startswith("cubed-tpu-flush") for t in threading.enumerate())


@pytest.mark.parametrize("name", sorted(COUNTERS))
def test_a_counters_reader_gives_none_for_a_program_without_it(name):
    reader, (counter, unit) = READERS[name], COUNTERS[name]
    parent = SimpleNamespace(stats={
        "d2h_bytes": 800, "flush_stream_bytes": 800, "encode_copy_bytes": 0, "host_syncs": 4,
        "span_s": {"jax.flush": 0.5}, "span_self_s": {"jax.flush": 0.0}, "spans_dropped": 0,
    })
    assert reader.read(parent) is None
    assert reader.read(SimpleNamespace(stats={})) is None
    assert reader.read(SimpleNamespace(stats={counter: 0})) == 0
    assert reader.read(SimpleNamespace(stats={counter: 250_000})) == pytest.approx(250_000 * unit)


def test_flush_s_reads_the_span_and_gives_none_without_it():
    reader = READERS["flush_s"]
    assert reader.read(SimpleNamespace(stats={})) is None
    assert reader.read(SimpleNamespace(stats={"write_wait_us": 5})) is None
    spans = {"span_s": {"jax.flush": 0.65, "jax.d2h": 0.2}, "span_self_s": {"jax.flush": 0.01}}
    assert reader.read(SimpleNamespace(stats={**spans, "spans_dropped": 0})) == 0.65
    # totals that lack a dropped span are short: nothing, rather than less
    assert reader.read(SimpleNamespace(stats={**spans, "spans_dropped": 1})) is None
    assert reader.read(SimpleNamespace(stats={"span_s": {"jax.d2h": 0.2}})) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_is_in_the_manifest_for_the_zarr_cells(name):
    """Appended for the three Zarr cells there were; a later cell may be
    appended to a metric's list and a later metric to ``per_layer``, so the
    end of neither list is pinned."""
    bench = manifest.load()
    assert manifest.check() == []
    (entry,) = [x for x in bench["per_layer"] if x["name"] == name]
    (declared,) = READERS[name].METRICS
    assert {k: v for k, v in entry.items() if k != "workloads"} == declared
    assert declared["moves"] == "zarr_compute_s"
    assert declared["source"] == ("program_span" if name == "flush_s" else "program_counter")
    assert entry["workloads"][:3] == ["zarr-add.store", "zarr-add.colmean", "zarr-add.rechunk"]
    for cell in bench["workloads"]:
        names = {x["name"] for x in manifest.metrics_for(bench, "per_layer", cell["name"])}
        assert (name in names) == (cell["name"] in entry["workloads"])
        if cell["name"].startswith("vorticity"):
            assert name not in names
