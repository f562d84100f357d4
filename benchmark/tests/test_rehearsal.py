"""Every cell rehearsed on the CPU at a tiny size: the queries against
``PythonDagExecutor`` and numpy, the mesh query on four virtual devices, the
span wrappers, the last line's keys, and the command refusing to measure off
a TPU. These are rehearsals of control flow and results; no time read here
means anything about the device, and none is printed."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmark.harness import loop, manifest, zarrv2
from benchmark.harness.spans import Recorder

TINY = {
    "vorticity-1chip": {"shape": [50, 40, 30], "chunks": 10},
    "vorticity-mesh4": {"shape": [80, 40, 40], "chunks": 10},
    "zarr-add-10k": {"shape": [200, 200], "chunks": [100, 100]},
}
#: the four-chip cell is prepared (configuration, readers, entries) but not in
#: the manifest yet: its entries wait in ``data/mesh4_entries.json`` for the PR
#: that proves it on the chip, and the rehearsals add them to their copy
PREPARED = json.loads(
    (manifest.ROOT / "benchmark" / "tests" / "data" / "mesh4_entries.json").read_text()
)
CELLS = [w["name"] for w in manifest.load()["workloads"] + PREPARED["workloads"]]
QUERIES = sorted(
    {manifest.load_json(manifest.ROOT, manifest.traffic_file(w["traffic"]))["query"]
     for w in manifest.load()["workloads"]}
)
WRAPPED = "cubed_tpu.runtime.executors.jax:JaxExecutor._run_segment"


def _tiny_root(path):
    """A copy of the benchmark whose configurations deploy tiny shapes."""
    shutil.copytree(manifest.ROOT / "benchmark", path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = manifest.load()
    for group in ("configs", "workloads", "per_layer"):
        bench[group] += PREPARED[group]
    for x in bench["end_to_end"] + bench["per_layer"]:
        if x["name"] in PREPARED["append_to_workloads_of"]:
            x["workloads"] = x["workloads"] + [w["name"] for w in PREPARED["workloads"]]
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    for name, tiny in TINY.items():
        file = path / "benchmark" / "configs" / f"{name}.json"
        config = json.loads(file.read_text())
        config["deployment"].update(tiny)
        file.write_text(json.dumps(config))
    return path


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("tiny"))


def test_the_prepared_cell_needs_entries_only(tiny_root):
    assert PREPARED["workloads"][0]["name"] not in [w["name"] for w in manifest.load()["workloads"]]
    assert manifest.check(tiny_root) == []


def _measure(root, cell_name, trace, seconds=0.5):
    import jax

    bench = manifest.load(root)
    cell = manifest.cell(bench, cell_name)
    assert len(jax.devices()) >= cell["chips"]
    return loop.measure(
        root=root, bench=bench, cell=cell, seed=2**31 + 11, seconds=seconds,
        trace=trace, devices=jax.devices(), t_start=time.perf_counter(),
    )


def _is_wrapped(target=WRAPPED):
    from benchmark.harness.spans import resolve

    return hasattr(resolve(target)[2], "__wrapped__")


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_untraced_prints_its_end_to_end_metrics(tiny_root, cell_name, monkeypatch):
    # --trace 0 wraps nothing in the program and starts no profiler
    import jax.profiler

    def refuse(*args, **kwargs):
        raise AssertionError("an untraced run must not touch this")

    monkeypatch.setattr(Recorder, "__init__", refuse)
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    out = _measure(tiny_root, cell_name, trace=False)
    assert not _is_wrapped()
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1
    bench = manifest.load(tiny_root)
    wanted = {x["name"] for x in manifest.metrics_for(bench, "end_to_end", cell_name)}
    assert set(out["metrics"]) == wanted and "setup_s" in wanted
    for value in out["metrics"].values():
        assert set(value) == {"value", "unit"} and value["value"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(out)  # the last line must serialise


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_traced_prints_per_layer_metrics_and_unwraps(tiny_root, cell_name):
    out = _measure(tiny_root, cell_name, trace=True)
    assert not _is_wrapped()
    assert out["correct"] is True
    bench = manifest.load(tiny_root)
    allowed = {x["name"] for x in manifest.metrics_for(bench, "per_layer", cell_name)}
    assert set(out["metrics"]) <= allowed
    # what needs no device trace is there on the CPU too
    assert {"first_compute_s", "compile_s"} <= set(out["metrics"])
    assert any(name.startswith("unaccounted_s.") for name in out["metrics"])
    # there is no device plane in a CPU trace, so nothing read from one
    assert not {"device_busy_s", "kernel_hbm_share", "chip_busy_min_share"} & set(out["metrics"])
    assert "busy_s" not in out["device"]


@pytest.mark.parametrize("query_name", QUERIES)
def test_query_agrees_with_the_python_executor_on_numpy(tiny_root, tmp_path, query_name):
    import cubed_tpu as ct
    from cubed_tpu.runtime.executors.jax import JaxExecutor
    from cubed_tpu.runtime.executors.python import PythonDagExecutor

    query = manifest.load_module(tiny_root, manifest.query_file(query_name))
    config = "vorticity-1chip" if query_name.startswith("vorticity") else "zarr-add-10k"
    deploy = manifest.load_json(tiny_root, f"benchmark/configs/{config}.json")["deployment"]
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem=deploy["allowed_mem"])
    sources = query.make_sources(deploy, 2**31 + 5, str(tmp_path))
    results = {}
    for name, executor in (("jax", JaxExecutor()), ("python", PythonDagExecutor())):
        target = str(tmp_path / f"{name}.zarr") if query.WRITES_TARGET else None
        result = query.run(query.build(deploy, sources, spec, target), executor, None, target)
        # each against the plain reference (numpy, or the expectation of the mean)
        query.check(deploy, sources, result, None, target, True)
        results[name] = zarrv2.read_zarr_v2(target) if target else result
    import numpy as np

    np.testing.assert_allclose(results["jax"], results["python"], rtol=1e-12, atol=0)
    assert query.nominal_bytes(deploy) > 0


def test_check_fails_a_float32_result(tiny_root, tmp_path):
    import numpy as np

    query = manifest.load_module(tiny_root, manifest.query_file("zarr_add_colmean"))
    deploy = manifest.load_json(tiny_root, "benchmark/configs/zarr-add-10k.json")["deployment"]
    sources = query.make_sources(deploy, 3, str(tmp_path))
    exact = sources["ref"]
    query.check(deploy, sources, exact.copy(), None, None, True)
    with pytest.raises(AssertionError):
        query.check(deploy, sources, exact.astype(np.float32).astype(np.float64), None, None, True)


def test_store_check_finds_a_corrupt_and_a_short_chunk(tiny_root, tmp_path):
    import cubed_tpu as ct
    from cubed_tpu.runtime.executors.jax import JaxExecutor

    query = manifest.load_module(tiny_root, manifest.query_file("zarr_add_store"))
    deploy = manifest.load_json(tiny_root, "benchmark/configs/zarr-add-10k.json")["deployment"]
    spec = ct.Spec(work_dir=str(tmp_path / "work"), allowed_mem=deploy["allowed_mem"])
    sources = query.make_sources(deploy, 4, str(tmp_path))
    target = str(tmp_path / "t.zarr")
    query.run(query.build(deploy, sources, spec, target), JaxExecutor(), None, target)
    query.check(deploy, sources, None, None, target, True)
    chunk = os.path.join(target, "0.1")
    data = bytearray(open(chunk, "rb").read())
    data[8] ^= 0x01  # one bit of one value's low mantissa byte
    open(chunk, "wb").write(bytes(data))
    query.check(deploy, sources, None, None, target, False)  # the light check reads nothing
    with pytest.raises(AssertionError):
        query.check(deploy, sources, None, None, target, True)
    open(chunk, "wb").write(bytes(data[:-8]))
    with pytest.raises(AssertionError):
        query.check(deploy, sources, None, None, target, False)


def test_a_missing_callable_makes_its_metric_absent_and_fails_nothing(tmp_path):
    root = _tiny_root(tmp_path)
    reader = root / "benchmark" / "layer_metrics" / "store_write_s.py"
    reader.write_text(reader.read_text().replace("ZarrV2Array.__setitem__", "ZarrV2Array.no_such_method"))
    other = root / "benchmark" / "layer_metrics" / "unaccounted_s.py"
    other.write_text(other.read_text().replace('    "cubed_tpu.storage.store:ZarrV2Array.__setitem__": {},\n', ""))
    out = _measure(root, "zarr-add.store", trace=True)
    assert out["correct"] is True and out["failed"] == 0
    assert "store_write_s" not in out["metrics"]
    assert "fetch_s" in out["metrics"]

    recorder = Recorder()
    assert recorder.wrap("cubed_tpu.runtime.executors.jax:JaxExecutor._no_such") is False
    assert recorder.wrap("cubed_tpu.no_such_module:thing") is False
    assert len(recorder.missing) == 2
    assert recorder.wrap(WRAPPED) is True and _is_wrapped()
    recorder.unwrap()
    assert not _is_wrapped()


def test_device_path_rule_and_mesh_shares():
    rule = {"zero": ["eager_fallbacks"], "positive": ["segments_traced"]}
    loop.check_device_path({"segments_traced": 1}, rule)
    with pytest.raises(AssertionError):
        loop.check_device_path({"segments_traced": 1, "eager_fallbacks": 1}, rule)
    with pytest.raises(AssertionError):
        loop.check_device_path({"segments_traced": 0}, rule)
    with pytest.raises(AssertionError):
        loop.check_device_path(None, rule)
    loop.check_mesh_shares([10, 11, 12, 10])
    with pytest.raises(AssertionError):
        loop.check_mesh_shares([10, 0, 12, 10])
    with pytest.raises(AssertionError):
        loop.check_mesh_shares([100, 1, 1, 1])


def test_the_command_refuses_to_measure_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert "Nothing was measured" in done.stderr
    assert '"metrics"' not in done.stdout and '"correct"' not in done.stdout


def test_the_command_fails_where_the_program_is_absent(tmp_path):
    shutil.copytree(manifest.ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0 and '"metrics"' not in done.stdout
