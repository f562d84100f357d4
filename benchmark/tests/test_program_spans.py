"""The per-layer metrics that read the program's own spans and counters
(``harness/program_spans.py``): every cell rehearsed traced at the tiny size
on the CPU reports each new metric of its family, each refinement is no more
than the outside metric it refines, and an untraced run arms nothing. Times
read here say nothing about the device."""

import os
import subprocess
import sys
import time

import pytest

from benchmark.harness import loop, manifest, program_spans
from benchmark.tests.test_rehearsal import _tiny_root

GEN = {"plan_finalize_s", "struct_key_s", "dispatch_s", "struct_hit_share.gen",
       "host_syncs.gen"}
ZARR = {"struct_hit_share.zarr", "host_syncs.zarr", "host_read_s", "h2d_s",
        "flush_wait_s", "d2h_s", "d2h_gb_per_s", "encode_s", "fsync_s"}
NEW = {"vorticity.mean": GEN, "zarr-add.store": ZARR, "zarr-add.colmean": ZARR}
#: chunks of the tiny result: a fetch each
SYNCS = {"vorticity.mean": 1, "zarr-add.store": 4, "zarr-add.colmean": 2}
SLACK = 1e-3


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny-spans"))


@pytest.fixture(autouse=True)
def _spans_env_restored(monkeypatch):
    """A traced rehearsal arms the program's spans for the rest of its
    process, which here is the test session: put the variable back."""
    monkeypatch.delenv(program_spans.SPANS_ENV_VAR, raising=False)
    monkeypatch.setitem(os.environ, "BENCH_RUN", "ignored")


def _measure(root, cell_name, trace):
    """One compute in the window, so that a median over the window's
    computes and the last compute's spans are the same compute's."""
    import jax

    bench = manifest.load(root)
    return loop.measure(
        root=root, bench=bench, cell=manifest.cell(bench, cell_name),
        seed=2**31 + 29, seconds=1e-3, trace=trace, devices=jax.devices(),
        t_start=time.perf_counter(),
    )


@pytest.mark.parametrize("cell_name", sorted(NEW))
def test_traced_cell_reports_every_new_metric_of_its_family(tiny_root, cell_name):
    out = _measure(tiny_root, cell_name, trace=True)
    assert out["correct"] is True and out["attempted"] == 1
    got = {name: x["value"] for name, x in out["metrics"].items()}
    assert NEW[cell_name] <= set(got)
    for name in NEW[cell_name]:
        assert got[name] > 0, name
    family = "gen" if cell_name == "vorticity.mean" else "zarr"
    assert got[f"struct_hit_share.{family}"] == 100.0
    assert got[f"compiles_in_window.{family}"] == 0
    assert got[f"host_syncs.{family}"] == SYNCS[cell_name]
    if family == "gen":
        assert got["struct_key_s"] + got["dispatch_s"] <= got["segment_s"] + SLACK
        assert got["plan_finalize_s"] <= got["plan_s"] + SLACK
        assert not ZARR & set(got)
    else:
        assert got["host_read_s"] <= got["preload_s"] + SLACK
        assert got["h2d_s"] <= got["preload_s"] + SLACK
        assert got["d2h_s"] <= got["fetch_s"] + SLACK
        assert got["encode_s"] + got["fsync_s"] <= got["store_write_s"] + SLACK
        assert not (GEN - {"struct_hit_share.gen", "host_syncs.gen"}) & set(got)


@pytest.mark.parametrize("cell_name", sorted(NEW))
def test_untraced_cell_leaves_the_program_unarmed(tiny_root, cell_name):
    out = _measure(tiny_root, cell_name, trace=False)
    assert out["correct"] is True
    assert program_spans.SPANS_ENV_VAR not in os.environ
    assert not set(NEW[cell_name]) & set(out["metrics"])


def test_readers_give_none_for_a_program_without_the_spans():
    """What the parent of the PR that brought the spans gives: counters of
    old, no ``span_s``. Nothing raises, every new metric is left out but the
    share that old counters already carry."""
    from types import SimpleNamespace

    old = SimpleNamespace(
        stats={"segments_traced": 1, "segment_struct_hits": 1, "eager_ops": 1},
        window=[0], recorder=None,
    )
    dropped = SimpleNamespace(
        stats={"span_s": {"jax.d2h": 1.0}, "span_self_s": {"jax.d2h": 1.0},
               "spans_dropped": 2, "d2h_bytes": 8},
        window=[0], recorder=None,
    )
    for names in NEW.values():
        for name in names:
            reader = manifest.load_module(manifest.ROOT, manifest.reader_file(name))
            if name.startswith("struct_hit_share"):
                assert reader.read(old) == 100.0
            else:
                assert reader.read(old) is None, name
            if reader.METRICS[0]["source"] == "program_span":
                assert reader.read(dropped) is None, name


def test_the_manifest_passes_with_the_appended_entries():
    done = subprocess.run(
        [sys.executable, "benchmark/check_manifest.py"], cwd=manifest.ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stdout + done.stderr
    bench = manifest.load()
    for cell_name, names in NEW.items():
        mine = {x["name"] for x in manifest.metrics_for(bench, "per_layer", cell_name)}
        assert names <= mine
    # appended, each with its cells: what stood before still stands first
    order = [x["name"] for x in bench["per_layer"]]
    added = set().union(*NEW.values())
    assert min(order.index(n) for n in added) > order.index("unaccounted_s.zarr")
    assert all("workloads" in x for x in bench["per_layer"] if x["name"] in added)
