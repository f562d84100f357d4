"""Coordination-free distributed random arrays.

The reference keys a Philox generator by ``root_seed + linear block offset``
(cubed/random.py:13-36); the TPU-native equivalent is the jax threefry PRNG
with ``jax.random.fold_in(key, root_seed + block_offset)`` — the same
per-block determinism contract (reproducible regardless of which worker/chip
computes which block), expressed with the native counter-based PRNG.

The seed rides the offsets *data* (VirtualOffsetsArray base) so the kernel's
HLO is identical for every plan — one persistent-cache compile serves all
random arrays of a given chunk shape.

Backend-appropriate generation (``CUBED_TPU_RNG`` = ``auto`` | ``threefry``
| ``philox``, default ``auto``): threefry is the accelerator path (counter-
based, fuses into the surrounding XLA program), but XLA-CPU executes the
same threefry far slower than numpy's Philox. ``auto`` therefore routes by
the actual execution platform at kernel-trace time: TPU/GPU generate with fused
threefry; single-device CPU generates with the numpy Philox stream via
``jax.pure_callback`` — block-sized host generation feeding the fused XLA
consumer, giving the CPU path the numpy backend's generation rate AND
making its streams exactly match the numpy-backend oracle (``Philox(seed=
root + block_offset)``, the reference's own contract). Blocks larger than
``_PHILOX_MAX_BLOCK_BYTES`` stay fused threefry even on CPU: the
callback's copy/materialization cost scales with block bytes and crosses
over around there (see the constant's comment). Under a device mesh
the executor forces threefry (callbacks don't partition across a
multi-controller SPMD program); a heterogeneous CPU+TPU fleet must pin one
stream via ``CUBED_TPU_RNG`` if cross-platform per-block reproducibility
matters.

``random`` draws float64 or float32, as declared (``dtype=``): the array has
that dtype in the plan and every route generates in it, one named kernel a
width. The per-block contract for both widths is in ``random``'s docstring.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import random as pyrandom

import numpy as np

from .backend_array_api import BACKEND, nxp

#: executor-scoped resolution override (e.g. "threefry" under a mesh);
#: a ContextVar so concurrently executing executors in other threads keep
#: their own scope
_MODE_OVERRIDE: contextvars.ContextVar = contextvars.ContextVar(
    "cubed_tpu_rng_mode", default=None
)


#: auto-mode block-size crossover on the CPU: the callback's copy and
#: materialization cost scales with block bytes, while fused threefry never
#: materializes the generation at all, so small blocks go to the Philox
#: callback and large ones stay fused. Set between the block sizes at which
#: each route won on an earlier installation's CPU; no ratio from there is
#: quoted here, and none of it says anything about the chip (PERF.md).
_PHILOX_MAX_BLOCK_BYTES = 16 * 2**20


def generation_mode(block_nbytes=None) -> str:
    """Resolve the RNG implementation for kernels traced/executed NOW.

    Order: executor scope (the mesh-correctness constraint, always
    threefry) > ``CUBED_TPU_RNG`` env pin > platform auto (cpu -> philox
    for blocks up to ``_PHILOX_MAX_BLOCK_BYTES``, else threefry).
    Resolved at kernel-trace time, so one plan computed on different
    executors uses each executor's appropriate path.

    ``block_nbytes=None`` asks for the POLICY rather than a per-block
    decision — the JaxExecutor's structural segment cache folds that
    policy string into its key (block shapes are already in the key, so
    policy + shape fully determines every kernel's branch).

    The executor scope outranks an env ``philox`` pin: the scope is only
    ever set to threefry as the mesh-correctness constraint (callbacks
    don't partition across an SPMD program), and a preference must not
    override a correctness requirement — a mesh execution under
    ``CUBED_TPU_RNG=philox`` generates with threefry.
    """
    mode = os.environ.get("CUBED_TPU_RNG", "auto").lower()
    if mode not in ("auto", "threefry", "philox"):
        raise ValueError(
            f"CUBED_TPU_RNG must be 'auto', 'threefry' or 'philox'; "
            f"got {os.environ['CUBED_TPU_RNG']!r}"
        )
    override = _MODE_OVERRIDE.get()
    if override is not None:
        return override
    if mode in ("threefry", "philox"):
        return mode
    if BACKEND != "jax":
        return "philox"
    import jax

    if jax.default_backend() != "cpu":
        return "threefry"
    if block_nbytes is None:
        # policy string for cache keys: the threshold is part of the
        # policy (tests patch it; two thresholds trace different programs
        # for the same plan shape)
        return f"auto-cpu:{_PHILOX_MAX_BLOCK_BYTES}"
    return (
        "philox" if block_nbytes <= _PHILOX_MAX_BLOCK_BYTES else "threefry"
    )


def _maybe_philox(shape, seeded_offset, np_dtype, draw):
    """Route one block's generation: the philox-callback array if the
    resolved mode for this block size is philox, else None (caller
    generates with fused threefry). ``draw(rng, shape)`` produces the
    block from a numpy Generator."""
    import jax

    dt = np.dtype(jax.dtypes.canonicalize_dtype(np_dtype))
    nbytes = int(np.prod(shape, dtype=np.int64)) * dt.itemsize if shape else dt.itemsize
    if generation_mode(nbytes) != "philox":
        return None
    return _philox_block(shape, seeded_offset, lambda rng: draw(rng, shape), dt)


@contextlib.contextmanager
def _mode_scope(mode: str):
    """Pin :func:`generation_mode`'s executor-scope resolution (this thread
    / async context only) for the duration — the JaxExecutor wraps mesh
    executions with ``_mode_scope("threefry")``."""
    token = _MODE_OVERRIDE.set(mode)
    try:
        yield
    finally:
        _MODE_OVERRIDE.reset(token)


def _philox_block(shape, seeded_offset, draw, out_dtype):
    """One block generated host-side with the numpy Philox stream, fed to
    the traced program as a ``pure_callback`` — the offsets stay DATA, so
    the HLO is still plan-invariant.

    Batching: under the executor's batched (vmapped) dispatch path the
    callback must NOT lower through ``vmap_method="sequential"`` — that
    becomes an XLA loop whose per-iteration result updates copy the full
    stacked buffer (measured: 62 s vs 15 s on the 4 GB addsum_scaled
    config). ``"expand_dims"`` instead delivers the whole batch of offsets
    to ONE host call, which loops the per-block Philox draws in numpy and
    returns the stacked batch — per-block stream semantics preserved, one
    host round-trip per op."""
    import jax

    base_ndim = len(shape)

    def host(off):
        off = np.asarray(off)
        batch_shape = off.shape[: max(off.ndim - base_ndim, 0)]
        offs = off.ravel()

        def gen(o):
            rng = np.random.Generator(np.random.Philox(seed=int(o)))
            return np.asarray(draw(rng)).astype(out_dtype, copy=False)

        if offs.size == 1 and not batch_shape:
            return gen(offs[0])
        out = np.stack([gen(o) for o in offs])
        return out.reshape(*batch_shape, *shape)

    return jax.pure_callback(
        host,
        jax.ShapeDtypeStruct(shape, out_dtype),
        seeded_offset,
        vmap_method="expand_dims",
    )

def _ensure_partitionable_threefry():
    """Counter-parallel threefry lowering: generates each element
    independently instead of odd/even halves + strided interleave — the
    interleave (a 2-tuple "select_select" fusion) was the dominant kernel
    of the vorticity pipeline in a device profile taken on an earlier
    installation. This selects a DIFFERENT (still deterministic,
    platform-invariant) stream than the default lowering, which is fine
    for the per-block contract: the flag is set lazily at the FIRST
    cubed_tpu RNG use in a process — array construction client-side, and
    kernel trace/execution worker-side — so every executor and worker
    sees the same stream, while merely importing cubed_tpu leaves the
    host application's own ``jax.random`` streams untouched (the numpy
    backend already has its own Philox stream, as the reference's
    backends do). Set ``CUBED_TPU_THREEFRY_PARTITIONABLE=0`` to never
    touch jax's default if that matters more than generation speed
    (tests/test_random.py::test_partitionable_threefry_pinned)."""
    if BACKEND != "jax":
        return
    import os

    if os.environ.get("CUBED_TPU_THREEFRY_PARTITIONABLE", "1") == "0":
        return
    import jax

    if not jax.config.jax_threefry_partitionable:
        jax.config.update("jax_threefry_partitionable", True)
from .chunks import normalize_chunks
from .core.ops import general_blockwise, new_array
from .core.plan import Plan, gensym
from .spec import spec_from_config
from .storage.virtual import virtual_empty, VirtualOffsetsArray
from .utils import to_chunksize


def random(size, *, diagnostics=None, chunks=None, spec=None, dtype=np.float64):
    """Uniform [0, 1) array of ``dtype`` (float64, the default, or float32)
    with per-block reproducible randomness.

    The array has ``dtype`` in the plan (chunk memory and ``projected_mem``
    follow from it) and every generation route draws in it; nothing is
    drawn wider and cast. Block ``k`` of the chunk grid, counted in C
    order, of an array whose root seed is ``root`` (30 bits from Python's
    ``random``, drawn when the array is declared) is, for either width,

    - fused threefry (the accelerator route):
      ``jax.random.uniform(fold_in(key(0), root + k), block_shape, dtype)``
      with ``jax_threefry_partitionable`` on;
    - the numpy backend and the Philox callback of the jax backend on a CPU:
      ``Generator(Philox(root + k)).random(block_shape, dtype=dtype)``.

    A float32 block is not the float64 block rounded: each width draws its
    own stream (32 random bits a value against 64). Beyond upstream, whose
    ``cubed.random.random`` is float64 only; numpy's ``Generator.random``
    takes the same two dtypes."""
    try:
        kernel = _RANDOM_KERNELS[np.dtype(dtype)]
    except (KeyError, TypeError):
        raise TypeError(
            f"random() draws float64 or float32, not {dtype!r}"
        ) from None
    return _distribution(
        size, chunks, spec, kernel=kernel, op_name="random",
        params=None, dtype=dtype,
    )


def _uniform_block(chunk, seeded_offset, dtype):
    """One random block of the declared ``dtype``; ``seeded_offset`` is
    data, so the HLO has no per-plan constants."""
    if BACKEND == "jax":
        import jax

        routed = _maybe_philox(
            chunk.shape, seeded_offset, dtype,
            lambda rng, shape: rng.random(shape, dtype=dtype),
        )
        if routed is not None:
            return routed
        _ensure_partitionable_threefry()
        off = seeded_offset.ravel()[0]
        key = jax.random.fold_in(jax.random.key(0), off)
        return jax.random.uniform(key, chunk.shape, dtype=dtype)
    off = int(np.asarray(seeded_offset).ravel()[0])
    rng = np.random.Generator(np.random.Philox(seed=off))
    return rng.random(chunk.shape, dtype=dtype)


# one named kernel a width (the name is in the traced op's scope, and the
# two never share a compiled program); both accept a traced offset, letting
# the fused-plan tracer hoist the seed to a program input
def _random_block(chunk, seeded_offset):
    return _uniform_block(chunk, seeded_offset, np.float64)


def _random_block_f32(chunk, seeded_offset):
    return _uniform_block(chunk, seeded_offset, np.float32)


_random_block.traced_offsets = True
_random_block_f32.traced_offsets = True

_RANDOM_KERNELS = {
    np.dtype(np.float64): _random_block,
    np.dtype(np.float32): _random_block_f32,
}


def normal(size, *, mean=0.0, stddev=1.0, chunks=None, spec=None):
    """Normal array with the same per-block determinism contract as
    :func:`random` (beyond the reference, which only has uniform).

    The kernel generates the STANDARD normal (parameter-free, so one
    compile serves every (mean, stddev)); scaling applies as ordinary
    elemwise ops, which fuse into the same program."""
    mean, stddev = float(mean), float(stddev)
    if stddev < 0:
        raise ValueError(f"stddev must be non-negative, got {stddev}")
    out = _distribution(
        size, chunks, spec, kernel=_normal_block, op_name="normal",
        params=None, dtype=np.float64,
    )
    from .array_api.elementwise_functions import add, multiply

    if stddev != 1.0:
        out = multiply(out, stddev)
    if mean != 0.0:
        out = add(out, mean)
    return out


def randint(low, high, size, *, chunks=None, spec=None):
    """Uniform integers in [low, high) with per-block determinism.

    The kernel draws from [0, high-low) — its compiled program is keyed by
    the span only — and the low offset applies as a fused elemwise add."""
    low, high = int(low), int(high)
    if high <= low:
        raise ValueError(f"high ({high}) must be greater than low ({low})")
    out = _distribution(
        size, chunks, spec, kernel=_randint_block, op_name="randint",
        params=(high - low,), dtype=np.int64,
    )
    if low != 0:
        from .array_api.elementwise_functions import add

        out = add(out, low)
    return out


def _distribution(size, chunks, spec, *, kernel, op_name, params, dtype):
    import functools

    _ensure_partitionable_threefry()
    shape = (size,) if isinstance(size, int) else tuple(size)
    dtype = np.dtype(dtype)
    spec = spec_from_config(spec)
    chunks = normalize_chunks(chunks, shape, dtype=dtype)
    numblocks = tuple(len(c) for c in chunks)
    root_seed = pyrandom.getrandbits(30)

    template_t = virtual_empty(
        shape, dtype=dtype, chunks=to_chunksize(chunks) if shape else ()
    )
    t_name = gensym("template")
    t_plan = Plan._new(t_name, "template", template_t, None, True)
    template = new_array(t_name, template_t, spec, t_plan)

    offsets_t = VirtualOffsetsArray(numblocks, base=root_seed)
    o_name = gensym("seeds")
    o_plan = Plan._new(o_name, "seeds", offsets_t, None, True)
    offsets = new_array(o_name, offsets_t, spec, o_plan)

    def block_function(out_key):
        coords = out_key[1:]
        return ((t_name, *coords), (o_name, *coords))

    fn = kernel if params is None else functools.partial(kernel, params=params)
    fn.traced_offsets = True
    return general_blockwise(
        fn,
        block_function,
        template,
        offsets,
        shape=shape,
        dtype=dtype,
        chunks=chunks,
        op_name=op_name,
    )


def _normal_block(chunk, seeded_offset):
    if BACKEND == "jax":
        import jax

        routed = _maybe_philox(
            chunk.shape, seeded_offset, np.float64,
            lambda rng, shape: rng.normal(size=shape),
        )
        if routed is not None:
            return routed
        _ensure_partitionable_threefry()
        off = seeded_offset.ravel()[0]
        key = jax.random.fold_in(jax.random.key(0), off)
        return jax.random.normal(key, chunk.shape, np.float64)
    off = int(np.asarray(seeded_offset).ravel()[0])
    rng = np.random.Generator(np.random.Philox(seed=off))
    return rng.normal(size=chunk.shape)


def _randint_block(chunk, seeded_offset, *, params):
    (span,) = params
    if BACKEND == "jax":
        import jax

        routed = _maybe_philox(
            chunk.shape, seeded_offset, np.int64,
            lambda rng, shape: rng.integers(0, span, size=shape, dtype=np.int64),
        )
        if routed is not None:
            return routed
        _ensure_partitionable_threefry()
        off = seeded_offset.ravel()[0]
        key = jax.random.fold_in(jax.random.key(0), off)
        return jax.random.randint(key, chunk.shape, 0, span, np.int64)
    off = int(np.asarray(seeded_offset).ravel()[0])
    rng = np.random.Generator(np.random.Philox(seed=off))
    return rng.integers(0, span, size=chunk.shape, dtype=np.int64)
