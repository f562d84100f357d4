"""Test configuration: run JAX on a virtual 8-device CPU mesh so sharding
paths are exercised without TPU hardware; the chip is driven by
``chip_smoke.py``, not the test suite.

The environment is pinned before jax is imported, so subprocesses a test
spawns inherit the CPU platform too."""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import tempfile

import pytest

# the conformance suite is hypothesis-based property testing; on minimal
# environments without hypothesis, skip collecting the whole directory
# (including its conftest, which imports hypothesis at module scope) so
# tier-1 collection stays clean
try:
    import hypothesis  # noqa: F401
except ImportError:
    collect_ignore = ["conformance"]


@pytest.fixture
def spec(tmp_path):
    import cubed_tpu as ct

    return ct.Spec(work_dir=str(tmp_path), allowed_mem="500MB", reserved_mem=0)


@pytest.fixture(autouse=True)
def _memory_guard_as_found():
    """Every test ends with the memory guard it found. ``Plan.execute`` arms
    the guard by save and restore of a process global and its environment
    variable (``memory.scoped``), which two computes at once interleave
    (PERF.md section 7): a test that computes on several threads can leave
    an ``observe`` configuration behind, and that then overrides
    ``memory_guard="enforce"`` in every later test of the same worker
    (``runtime/test_memory_guard.py`` failed so, by the files a worker
    happened to be given)."""
    from cubed_tpu.runtime import memory

    active, env = memory._active, os.environ.get(memory.MEMORY_GUARD_ENV_VAR)
    yield
    memory._active = active
    if env is None:
        os.environ.pop(memory.MEMORY_GUARD_ENV_VAR, None)
    else:
        os.environ[memory.MEMORY_GUARD_ENV_VAR] = env


@pytest.fixture
def invariant_audit():
    """Post-hoc exactly-once audit over whatever durable artifacts a test's
    compute left behind (journal / control log / store / metrics delta) —
    asserts the report is clean and returns it. Chaos suites call this at
    the end so 'survived the fault' also means 'never did anything
    illegal along the way'."""
    from cubed_tpu.runtime.audit import InvariantAuditor

    def _audit(journal=None, control_dir=None, work_dir=None, metrics=None,
               expect_success=True):
        report = InvariantAuditor(
            journal=journal, control_dir=control_dir, work_dir=work_dir,
            metrics=metrics, expect_success=expect_success,
        ).audit()
        assert report.ok, report.render()
        assert report.checked, "auditor was given nothing to audit"
        return report

    return _audit


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False, help="run slow tests"
    )


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: mark test as slow to run")
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection chaos tests (seeded, tier-1)",
    )
    config.addinivalue_line(
        "markers",
        "mem: memory-guard sampler tests (need a readable /proc; "
        "auto-skipped on platforms without one)",
    )


def _proc_mem_readable() -> bool:
    """True when the memory guard can measure here (Linux /proc)."""
    try:
        from cubed_tpu.utils import current_measured_mem

        return current_measured_mem() is not None
    except Exception:
        return False


def pytest_collection_modifyitems(config, items):
    if not _proc_mem_readable():
        skip_mem = pytest.mark.skip(
            reason="no readable /proc: the memory-guard sampler cannot "
            "measure RSS on this platform"
        )
        for item in items:
            if "mem" in item.keywords:
                item.add_marker(skip_mem)
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="need --runslow option to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
