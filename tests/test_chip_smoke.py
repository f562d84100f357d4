"""CPU rehearsal of ``chip_smoke.py``: the legs' control flow and checks are
exercised here by calling its functions at small sizes, so chip time is not
spent finding a typo — and the script itself must refuse to run without a
TPU, before any leg starts."""

import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from cubed_tpu.parallel.mesh import make_mesh
from cubed_tpu.runtime.executors.jax import JaxExecutor

REPO = os.path.dirname(os.path.abspath(chip_smoke.__file__))


@pytest.fixture
def compile_log():
    log = chip_smoke.CompileLog()
    yield log
    log.close()


def _mesh_executor():
    return JaxExecutor(mesh=make_mesh())


def _assert_clean(runs, expected):
    assert set(runs) == set(expected)
    for cold, warm in runs.values():
        for run in (cold, warm):
            assert not any(run.stats.get(k) for k in chip_smoke.FAILURE_COUNTERS)
            assert run.stats.get("segments_traced", 0) >= 1
        # the warm repeat reuses the cold run's programs
        assert sum(warm.programs.values()) == 0, warm.programs


@pytest.mark.parametrize("make_executor", [JaxExecutor, _mesh_executor])
def test_vorticity_leg_small(tmp_path, compile_log, make_executor):
    runs = chip_smoke.vorticity_leg(
        (50, 45, 40), 10, "400MB", seed=0, work_dir=str(tmp_path),
        make_executor=make_executor, log=compile_log,
    )
    _assert_clean(runs, ["mean"])


@pytest.mark.parametrize("make_executor", [JaxExecutor, _mesh_executor])
def test_zarr_add_leg_small(tmp_path, compile_log, make_executor):
    # 90 is not a multiple of 40: edge chunks are stored padded
    runs = chip_smoke.zarr_add_leg(
        90, 40, "200MB", seed=0, work_dir=str(tmp_path / "z"),
        make_executor=make_executor, log=compile_log,
    )
    _assert_clean(runs, ["add", "mean", "rechunk"])


def _four_chip_executor():
    import jax

    return JaxExecutor(mesh=make_mesh(devices=jax.devices()[:4]))


def test_mesh_zarr_add_leg_moves_every_chunk_to_and_from_its_owner(tmp_path, compile_log, capsys):
    # the chip's leg in small: a 2 x 2 grid on four chips, one chunk a chip,
    # a mean along an axis the mesh divides, and a rechunk whose target the
    # segment lays out by column slab
    runs = chip_smoke.zarr_add_leg(
        80, 40, "200MB", seed=0, work_dir=str(tmp_path / "z"),
        make_executor=_four_chip_executor, log=compile_log, name="mesh zarr_add",
    )
    _assert_clean(runs, ["add", "mean", "rechunk"])
    chip_smoke.check_owner_io(runs)
    for what, (cold, warm) in runs.items():
        for run in (cold, warm):
            assert run.stats["h2d_stream_bytes"] == run.stats["h2d_bytes"] > 0
            # the mean's two chunks of 40 lie over four shards of 20
            assert run.stats["mesh_gathered_bytes"] == (640 if what == "mean" else 0)
            assert (run.stats["mesh_owner_bytes"] + run.stats["mesh_gathered_bytes"]
                    == run.stats["h2d_bytes"] + run.stats["d2h_bytes"])
    # more than the row that came back is a source that missed its owners
    runs["mean"][1].stats["mesh_gathered_bytes"] = 641
    with pytest.raises(RuntimeError, match=r"mean \(warm\).*'mesh_gathered_bytes': 641"):
        chip_smoke.check_owner_io(runs)
    assert "add (cold): mesh mesh_owner_bytes=153600 mesh_gathered_bytes=0" in capsys.readouterr().out


def test_mesh_zarr_add_leg_fails_where_a_chunk_crossed_chips(tmp_path, compile_log):
    # 90 rows in chunks of 40 on eight chips: a shard ends inside a chunk
    runs = chip_smoke.zarr_add_leg(
        90, 40, "200MB", seed=0, work_dir=str(tmp_path / "z"),
        make_executor=_mesh_executor, log=compile_log,
        computes=("add",), name="mesh zarr_add",
    )
    with pytest.raises(RuntimeError, match="did not move between the host and its owner"):
        chip_smoke.check_owner_io(runs)
    cold, warm = runs["add"]
    streamed = dict(mesh_gathered_bytes=0, h2d_stream_bytes=cold.stats["h2d_bytes"])
    cold.stats.update(streamed, mesh_owner_bytes=8)
    warm.stats.update(streamed, mesh_owner_bytes=0)
    with pytest.raises(RuntimeError, match=r"add \(warm\).*'mesh_owner_bytes': 0"):
        chip_smoke.check_owner_io(runs)
    warm.stats.update(mesh_owner_bytes=8, h2d_stream_bytes=0)
    with pytest.raises(RuntimeError, match=r"add \(warm\).*'h2d_stream_bytes': 0"):
        chip_smoke.check_owner_io(runs)
    warm.stats.update(streamed)
    chip_smoke.check_owner_io(runs)


def test_measured_fails_when_an_op_left_the_device_path(compile_log):
    def compute(callbacks):
        class _Event:
            executor_stats = {"segments_traced": 1, "trace_failures": 1}

        callbacks[0].on_compute_end(_Event())

    with pytest.raises(RuntimeError, match="trace_failures"):
        chip_smoke.measured(compute, compile_log)


def test_compare_states_distance_and_enforces_tolerance(capsys):
    ref = np.linspace(1.0, 2.0, 16)
    chip_smoke.compare("same", ref.copy(), ref, 0.0)
    assert "0 of 16 values differ bitwise" in capsys.readouterr().out
    off = ref.copy()
    off[3] = np.nextafter(off[3], 4.0)
    chip_smoke.compare("one ulp", off, ref, chip_smoke.ADD_RTOL)
    assert "1 of 16 values differ bitwise" in capsys.readouterr().out
    with pytest.raises(AssertionError):
        chip_smoke.compare("bitwise", off, ref, 0.0)
    with pytest.raises(AssertionError):  # a silent f32 round trip fails
        chip_smoke.compare(
            "f32", ref.astype(np.float32).astype(np.float64), ref,
            chip_smoke.ADD_RTOL,
        )


def test_zarr_v2_by_hand_roundtrip_and_matches_the_store(tmp_path):
    import cubed_tpu as ct

    arr = np.random.default_rng(3).random((7, 10))
    path = str(tmp_path / "a.zarr")
    chip_smoke.write_zarr_v2(path, arr, (4, 4))
    assert np.array_equal(chip_smoke.read_zarr_v2(path), arr)
    # the code under test reads the hand-written store the same way
    spec = ct.Spec(work_dir=str(tmp_path), allowed_mem="100MB")
    assert np.array_equal(ct.from_zarr(path, spec=spec).compute(), arr)


def test_device_facts_small(capsys):
    import jax

    facts = chip_smoke.device_facts(jax.devices()[0], 64, 2, 3, seed=0)
    assert facts["changed"] == 0  # the CPU holds a float64 as one
    for form in ("float64", "uint64", "float32"):
        assert all(rate > 0 for rate in facts[form])
    assert facts["float64_planes"] > 0 and facts["dispatch_us"] > 0
    out = capsys.readouterr().out
    assert "0 of 4096 values changed" in out and "device->host" in out
    assert "as two 32-bit planes" in out
    # the streamed preload against the whole-array put, as numbers and as bits
    assert set(facts["preload_stream"]) == {"float64", "uint64"}
    assert out.count("streamed preload") == 2 and "0 of 9216 values differ" in out
    assert f"h2d_stream_bytes={3 * 64 * 64 * 8}" in out
    # a column slab's way out as planes against a block's, as words and as pairs
    assert set(facts["fetch_layout"]) == {
        (form, cut) for form in ("uint64", "float64") for cut in ("slab", "block")
    }
    for reading in facts["fetch_layout"].values():
        assert reading["strided"] is False and len(reading["ms"]) == 4
    assert out.count("planes reach the host with strides") == 4
    assert "uint64 slab (128, 32)" in out and "float64 block (64, 64)" in out


def test_check_mesh_shares():
    chip_smoke.check_mesh_shares([100, 120, 90, 110])
    with pytest.raises(AssertionError):
        chip_smoke.check_mesh_shares([100, 0, 100, 100])
    with pytest.raises(AssertionError):
        chip_smoke.check_mesh_shares([900, 100, 100, 100])


def test_script_refuses_to_run_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    # no leg started and no result line was printed
    assert "==" not in out.stdout and '"ok"' not in out.stdout
