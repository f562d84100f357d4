"""The cell ``zarr-add.rechunk``: its entries in the manifest, a check that
compares bit patterns and refuses what a comparison of numbers lets through,
and its two readers. Rehearsed on the CPU at a tiny size; shares read here
say nothing about the device."""

import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

CELL = "zarr-add.rechunk"
ALIAS = manifest.load_module(manifest.ROOT, manifest.reader_file("rechunk_alias_share"))
BITS = manifest.load_module(manifest.ROOT, manifest.reader_file("bits_carried_share"))

NEG_ZERO = 0x8000000000000000
NAN_WITH_PAYLOAD = 0x7FF8000000000123


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-rechunk"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


@pytest.fixture(scope="module")
def deployed(tiny_root, tmp_path_factory):
    """(query, deployment, sources) of the tiny cell."""
    query = manifest.load_module(tiny_root, manifest.query_file("zarr_add_rechunk"))
    deploy = manifest.load_json(tiny_root, "benchmark/configs/zarr-rechunk-10k.json")["deployment"]
    sources = query.make_sources(deploy, 2**31 + 32, str(tmp_path_factory.mktemp("src")))
    return query, deploy, sources


def _store(path, bits: np.ndarray, chunks) -> str:
    """``bits`` as a float64 Zarr store written by the program's own store,
    so that every chunk's CRC-32 is in the manifest: what is left for the
    check to find is the bytes."""
    from cubed_tpu.storage.store import open_zarr_array

    z = open_zarr_array(str(path), "w", shape=bits.shape, dtype=np.float64, chunks=chunks)
    z[...] = bits.view(np.float64)
    return str(path)


# -- the manifest -------------------------------------------------------------


def test_the_cell_is_in_the_manifest_with_its_mix_and_readers():
    bench = manifest.load()
    assert manifest.check() == []
    cell = manifest.cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zarr-rechunk-10k", "zarr_add_rechunk.closed-1", 1
    )
    # its configuration: nothing of the source cut, the data side that of
    # zarr-add-10k, the copy's guarantee and the routes it may not take
    entry = manifest.config_entry(bench, cell["config"])
    config = manifest.load_json(manifest.ROOT, entry["file"])
    assert entry["reduced"] == [] and config["reduced"] == {}
    store = manifest.load_json(manifest.ROOT, "benchmark/configs/zarr-add-10k.json")
    assert config["deployment"] == store["deployment"]
    assert config["guarantees"]["durability"] == store["guarantees"]["durability"]
    assert "bit for bit" in config["guarantees"]["copy"]
    assert set(ALIAS.ROUTES[2:]) | {"f64_lossy_moves"} <= set(config["device_path"]["zero"])
    mix = manifest.load_json(manifest.ROOT, manifest.traffic_file(cell["traffic"]))
    assert (mix["query"], mix["loop"], mix["clients"], mix["metric"], mix["profiled_computes"]) == (
        "zarr_add_rechunk", "closed", 1, "zarr_compute_s", 1
    )
    assert [x["name"] for x in manifest.metrics_for(bench, "end_to_end", CELL)] == [
        "zarr_compute_s", "setup_s"
    ]
    per_layer = {x["name"]: x for x in manifest.metrics_for(bench, "per_layer", CELL)}
    assert set(per_layer) == {
        "first_compute_s", "compile_s", "compiles_in_window.zarr", "preload_s", "fetch_s",
        "store_write_s", "hbm_footprint_frac.zarr", "unaccounted_s.zarr",
        "struct_hit_share.zarr", "host_syncs.zarr", "host_read_s", "h2d_s", "flush_wait_s",
        "d2h_s", "d2h_gb_per_s", "encode_s", "fsync_s", "d2h_plane_share", "h2d_stream_share",
        "rechunk_alias_share", "bits_carried_share",
    }
    # the two it brings are its own, and the last of the list
    assert [x["name"] for x in bench["per_layer"][-2:]] == ["rechunk_alias_share", "bits_carried_share"]
    for reader, layer in ((ALIAS, "segment dispatch"), (BITS, "Zarr to HBM preload")):
        (declared,) = reader.METRICS
        entry = per_layer[declared["name"]]
        assert entry == {**declared, "workloads": [CELL]}
        assert (entry["layer"], entry["moves"], entry["source"], entry["unit"]) == (
            layer, "zarr_compute_s", "program_counter", "%"
        )


# -- the source and the check ---------------------------------------------------


def test_every_source_chunk_holds_the_edge_values_in_each_slab(deployed):
    query, deploy, sources = deployed
    ref = sources["ref"]
    assert ref.dtype == np.uint64 and ref.shape == tuple(deploy["shape"])
    rows, cols = deploy["chunks"]
    width = query.target_chunks(deploy)[1]
    for r in range(0, ref.shape[0], rows):
        for c in range(0, ref.shape[1], cols):
            chunk = ref[r:r + rows, c:c + cols]
            assert np.isin(query.EDGE_BITS, chunk).all()
            for s in range(0, cols, width):
                assert np.isin(chunk[:, s:s + width], query.EDGE_BITS).any()
    assert query.nominal_bytes(deploy) == 2 * ref.nbytes


def test_check_passes_the_copy_and_refuses_what_a_tolerance_lets_through(deployed, tmp_path):
    query, deploy, sources = deployed
    ref, chunks = sources["ref"], query.target_chunks(deploy)
    exact = _store(tmp_path / "exact.zarr", ref, chunks)
    query.check(deploy, sources, {}, None, exact, True)
    query.check(deploy, sources, {}, None, exact, False)

    flipped = ref.copy()
    flipped[5, 7] ^= 1  # the lowest bit of one mantissa
    lost_sign = np.where(ref == NEG_ZERO, np.uint64(0), ref)
    assert np.count_nonzero(lost_sign != ref) == 4  # one a source chunk
    quiet = np.where(ref == NAN_WITH_PAYLOAD, np.uint64(0x7FF8000000000000), ref)
    with np.errstate(over="ignore", invalid="ignore"):
        single = ref.view(np.float64).astype(np.float32).astype(np.float64).view(np.uint64)
    for name, bad, differ in (
        ("flipped", flipped, 1), ("lost_sign", lost_sign, 4), ("quiet", quiet, 4),
        ("single", single, None),
    ):
        target = _store(tmp_path / f"{name}.zarr", bad, chunks)
        query.check(deploy, sources, {}, None, target, False)  # the light check reads nothing
        with pytest.raises(AssertionError, match="differ from the source bitwise") as failure:
            query.check(deploy, sources, {}, None, target, True)
        if differ is not None:
            assert f"{differ} of {ref.size} elements" in str(failure.value)
    # a comparison of numbers at tolerance 0 passes the lost sign: the reason
    # for comparing bits
    assert np.array_equal(lost_sign.view(np.float64)[ref == NEG_ZERO], ref.view(np.float64)[ref == NEG_ZERO])


def test_check_refuses_a_short_chunk_a_stale_checksum_and_the_sources_chunking(deployed, tmp_path):
    query, deploy, sources = deployed
    ref, chunks = sources["ref"], query.target_chunks(deploy)
    target = _store(tmp_path / "t.zarr", ref, chunks)
    chunk = os.path.join(target, "0.1")
    data = bytearray(open(chunk, "rb").read())
    data[8] ^= 0x01  # on disk, after the manifest was written
    open(chunk, "wb").write(bytes(data))
    with pytest.raises(AssertionError, match="differ from the source bitwise"):
        query.check(deploy, sources, {}, None, target, True)
    open(chunk, "wb").write(bytes(data[:-8]))
    for full in (False, True):
        with pytest.raises(AssertionError, match="full chunk"):
            query.check(deploy, sources, {}, None, target, full)
    unchanged = _store(tmp_path / "unchanged.zarr", ref, tuple(deploy["chunks"]))
    for full in (False, True):
        with pytest.raises(AssertionError, match=".zarray says"):
            query.check(deploy, sources, {}, None, unchanged, full)
    stray = _store(tmp_path / "stray.zarr", ref, chunks)
    open(os.path.join(stray, "0.9"), "wb").write(b"x")
    with pytest.raises(AssertionError, match="holds"):
        query.check(deploy, sources, {}, None, stray, False)


def test_check_holds_the_device_path_to_the_cells_own_rule(deployed, tmp_path):
    query, deploy, sources = deployed
    target = _store(tmp_path / "t.zarr", sources["ref"], query.target_chunks(deploy))
    on_device = {"segments_traced": 1, "rechunk_alias": 2}
    query.check(deploy, sources, on_device, None, target, True)
    query.check(deploy, sources, {**on_device, "f64_lossy_moves": 0}, None, target, True)
    for counter in query.ZERO_COUNTERS:
        with pytest.raises(AssertionError, match="left the chip's exact path"):
            query.check(deploy, sources, {**on_device, counter: 1}, None, target, True)
        # an executor without the device's counters is held to the bytes alone
        query.check(deploy, sources, {counter: 1}, None, target, True)


# -- the cell, rehearsed ----------------------------------------------------------


def _traced(root):
    import jax

    bench = manifest.load(root)
    out = loop.measure(
        root=root, bench=bench, cell=manifest.cell(bench, CELL),
        seed=2**31 + 33, seconds=1e-3, trace=True, devices=jax.devices(),
        t_start=time.perf_counter(),
    )
    assert out["correct"] is True and out["failed"] == 0
    return {name: x["value"] for name, x in out["metrics"].items()}


def test_the_window_compute_reports_its_routes(tiny_root):
    """The last compute of a window finds its program compiled: the alias
    share is there only because a structural hit reports its routes."""
    got = _traced(tiny_root)
    assert got["rechunk_alias_share"] == 100.0
    assert got["bits_carried_share"] == 0.0  # a CPU's float64 round-trips
    assert got["struct_hit_share.zarr"] == 100.0 and got["compiles_in_window.zarr"] == 0
    assert got["h2d_stream_share"] == 100.0 and got["host_syncs.zarr"] == 4


def test_a_device_of_float32_pairs_carries_every_byte_as_bits(tiny_root, monkeypatch):
    import cubed_tpu.runtime.executors.jax as jx

    monkeypatch.setattr(jx, "_float64_round_trips", lambda device: False)
    got = _traced(tiny_root)
    assert got["bits_carried_share"] == 100.0 and got["rechunk_alias_share"] == 100.0


# -- the readers ----------------------------------------------------------------


def test_the_alias_reader_gives_a_share_or_nothing():
    def read(**stats):
        return ALIAS.read(SimpleNamespace(stats=stats))

    assert read(rechunk_alias=2, rechunk_host_whole=0, rechunk_host_copy=0) == 100.0
    assert read(rechunk_alias=1, rechunk_virtual=1, rechunk_host_whole=1, rechunk_host_copy=1) == 25.0
    assert read(rechunk_alias=0, rechunk_host_copy=2) == 0.0
    # the parent's hit: no route counter moved, the new ones are not there
    assert read(segment_struct_hits=1, host_syncs=4) is None
    assert read(rechunk_host_whole=0, rechunk_host_copy=0) is None
    assert read() is None


def test_the_bits_reader_gives_a_share_or_nothing():
    def read(**stats):
        return BITS.read(SimpleNamespace(stats=stats))

    assert read(h2d_bits_bytes=800, h2d_bytes=800) == 100.0
    assert read(h2d_bits_bytes=200, h2d_bytes=800) == 25.0
    assert read(h2d_bits_bytes=0, h2d_bytes=800) == 0.0
    assert read(h2d_bytes=800, f64_as_bits=1) is None  # the parent has no such counter
    assert read(h2d_bits_bytes=0, h2d_bytes=0) is None
    assert read() is None
