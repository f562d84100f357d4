"""The single seam selecting the per-chunk compute namespace.

TPU-first: the default backend namespace is ``jax.numpy``, so every per-chunk
kernel in the framework is a pure jittable function and fused op chains compile
to one XLA program. A numpy backend is selectable (``CUBED_TPU_BACKEND=numpy``)
as the float64-exact CPU oracle for differential testing.

Reference parity: cubed/backend_array_api.py:1-23 (there the namespace is
array_api_compat.numpy; here the seam itself is the TPU design point).
"""

from __future__ import annotations

import os

import numpy as np

BACKEND = os.environ.get("CUBED_TPU_BACKEND", "jax").lower()

if BACKEND == "jax":
    import jax

    # Array-API dtype parity (int64 indices, float64 defaults) requires x64.
    # TPU kernels run in f32/bf16; the TPU executor downcasts f64 tiles on
    # device ingestion when the hardware lacks double support.
    if os.environ.get("CUBED_TPU_ENABLE_X64", "1") == "1":
        jax.config.update("jax_enable_x64", True)

    # Every plan builds fresh kernel closures, which defeats jax's in-process
    # jit cache; the persistent (HLO-keyed) compilation cache makes repeat
    # compiles of structurally identical kernels ~100x cheaper.
    # CPU-only runs (tests) skip it: XLA:CPU AOT entries bake host machine
    # features, so a cache written on one machine can SIGILL on another.
    # Where JAX_COMPILATION_CACHE_DIR is set, jax's own reading of it stands
    # and no directory is set here, so whoever launches the process decides
    # where compiled programs persist; otherwise the cache lives at one
    # fixed path beside the package, the same from any working directory.
    if os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update(
                "jax_compilation_cache_dir",
                os.path.join(
                    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    ".jax_cache",
                ),
            )
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import jax.numpy as namespace  # noqa: F401

    def backend_array_to_numpy_array(arr) -> np.ndarray:
        """Device array -> host numpy (blocks on transfer)."""
        return np.asarray(arr)

    def numpy_array_to_backend_array(arr, *, dtype=None):
        """Host numpy -> backend array (device placement is executor policy).

        Structured numpy arrays become dict-of-arrays pytrees (jax has no
        structured dtypes); the dict presents the same ``arr["field"]`` access
        the reference's kernels use on zarr structured intermediates.
        """
        if isinstance(arr, dict):  # pytree chunk (e.g. mean's {n, total})
            return {k: numpy_array_to_backend_array(v, dtype=None) for k, v in arr.items()}
        a = np.asarray(arr)
        if a.dtype.fields is not None:
            return {k: namespace.asarray(np.ascontiguousarray(a[k])) for k in a.dtype.names}
        return namespace.asarray(a, dtype=dtype)

else:
    import numpy as namespace  # noqa: F401

    def backend_array_to_numpy_array(arr) -> np.ndarray:
        return np.asarray(arr)

    def numpy_array_to_backend_array(arr, *, dtype=None):
        if isinstance(arr, dict):
            return {k: numpy_array_to_backend_array(v, dtype=None) for k, v in arr.items()}
        return np.asarray(arr, dtype=dtype)


#: alias used throughout the codebase, mirroring the reference's ``nxp``
nxp = namespace


def default_dtypes() -> dict:
    """Array-API default dtypes (float64/int64/complex128, bool)."""
    return {
        "real floating": np.dtype(np.float64),
        "integral": np.dtype(np.int64),
        "complex floating": np.dtype(np.complex128),
        "boolean": np.dtype(np.bool_),
    }
