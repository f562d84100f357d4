"""Where the persistent XLA compilation cache goes (backend_array_api.py).

Checked in subprocesses: the decision is taken once, at import, from the
environment. A non-CPU ``JAX_PLATFORMS`` string is enough — importing
cubed_tpu configures jax without initializing a backend."""

import os
import subprocess
import sys

import cubed_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(cubed_tpu.__file__)))

_PRINT_CACHE_DIR = (
    "import sys; sys.path.insert(0, {repo!r}); import cubed_tpu, jax; "
    "print(jax.config.jax_compilation_cache_dir)"
).format(repo=REPO)


def _cache_dir_in_subprocess(cwd, **env_overrides):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="tpu", **env_overrides)
    out = subprocess.run(
        [sys.executable, "-c", _PRINT_CACHE_DIR],
        env=env, capture_output=True, text=True, timeout=300, cwd=str(cwd),
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_env_var_stands_and_no_directory_is_set(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    got = _cache_dir_in_subprocess(tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    # jax's own reading of the variable, untouched
    assert got == placed


def test_default_is_one_fixed_path_inside_the_checkout(tmp_path):
    other = tmp_path / "elsewhere"
    other.mkdir()
    from_tmp = _cache_dir_in_subprocess(tmp_path)
    from_other = _cache_dir_in_subprocess(other)
    assert from_tmp == from_other == os.path.join(REPO, ".jax_cache")
