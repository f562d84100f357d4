"""``mean(a[1:]*x[1:] + b[1:]*y[1:])`` over four random arrays of the dtype
the deployment declares, float32 in ``vorticity-f32``: upstream's
examples/pangeo-vorticity.ipynb (BASELINE.json config 5) over
single-precision fields, declared through ``random(dtype=)``. Touches no
storage but the scalar it returns.

The check compares the value with a plain blockwise reference that uses
nothing of ``cubed_tpu``. It follows what ``cubed_tpu.random.random``
documents for both widths: each array draws a root seed of 30 bits from
Python's ``random`` (seeded with ``--seed``), and block ``k`` of the chunk
grid, counted in C order, is ``uniform(fold_in(key(0), root + k), block
shape, dtype)`` of the threefry stream with ``jax_threefry_partitionable``
on (on one CPU device, for a block of at most 16 MiB, the documented
``Generator(Philox(root + k)).random(block shape, dtype=dtype)``: the
stream of the rehearsals). The reference generates one block of each of the
four arrays at a time on the device, computes ``a*x + b*y`` in the declared
dtype, drops row 0 of the blocks at the head of axis 0, converts to float64
and sums the block there (``mean`` sums in float64 whatever the input, as
upstream's does), adds the partial sums on the host with ``math.fsum``, and
rounds the quotient once to the declared dtype: a sum of another order than
the program's reduction tree, over values drawn apart from it.

Where the program divides and rounds: the reduction's last op divides the
float64 total by the count on the device (the device's own float64, a pair
of float32), the executor fetches that float64 scalar (``d2h_bytes`` 8), and
the host rounds it to float32 as it is written into the result's float32
array: once, as here."""

from __future__ import annotations

import itertools
import math
import os
import random

import cubed_tpu.array_api as xp
import cubed_tpu.random

WRITES_TARGET = False

#: ``check(full=True)`` passes a float32 result within this many units of
#: float32's last place of the reference (``ulps``). Why it is so small: the
#: sum is float64 on both sides, so the two float64 quotients differ by the
#: order of summation and the device's 49-bit float64 alone, 1e-14 or so,
#: and the one rounding to float32 then gives the same float32, or its
#: neighbour where the quotient lies that close to a rounding boundary. One
#: unit is 6.0e-8 relative just under 0.5 and 1.2e-7 just above it, and the
#: mean lies on either side. Two sets of readings set it, at the timed
#: shape, (500, 900, 800) in chunks of 100 on one v5e chip (my chip runs, PR
#: 34; PERF.md section 6 lists the seeds). The program's first compute read
#: 0 units from the reference on each of ten seeds. The wrong programs
#: (``reference_partials``'s other arguments) read, in units, on three
#: seeds: generated as float64 and cast to float32 (another stream) 76,
#: 1123, 338; generated in bfloat16 261200, 261922, 260581; summed in
#: bfloat16 4608, 4462, 4376; the last slab of axis 2 counted twice
#: 1048593, 1048669, 1048739; that slab dropped with its count 13, 17, 3.
#: The limit of one unit refuses each of those: it stands between 0 and
#: the nearest wrong reading, 3, and a whole-number distance leaves no
#: closer place. **What this value cannot tell apart**, read on the same
#: seeds: a float32 accumulator (the blocks' sums in float32: 1, 0, 0) and
#: products rounded to bfloat16 (0, 0, 0): over 3.6e8 values their errors
#: average out under one unit of float32. The configuration's
#: ``device_path`` refuses them instead: ``device_f64_bytes`` above 0 (a
#: program that sums in float32 produces no float64) and
#: ``device_f16_bytes`` 0. A dropped slab whose count is dropped with it
#: moves the mean by a standard error of the slab, about 1e-6, so on some
#: seeds it would land within a unit too; ``vorticity-mesh4.mean``'s
#: float64 check, at 1e-11, is the one that always refuses it
ULPS_TOLERANCE = 1
#: the same for a deployment that declares float64 (the rehearsals run this
#: query on ``vorticity-1chip``'s), relative: ``vorticity_mean_exact.py``'s
FLOAT64_RELATIVE_TOLERANCE = 1e-11

#: blocks above this are generated by fused threefry on a CPU too (the
#: program's documented crossover, ``cubed_tpu/random.py``)
_PHILOX_MAX_BLOCK_BYTES = 16 * 2**20


def make_sources(deploy: dict, seed: int, workdir: str) -> dict:
    return {"seed": seed}


def build(deploy: dict, sources: dict, spec, target):
    # cubed_tpu.random draws each array's root seed from Python's random, so
    # every compute of a run generates the same four arrays
    random.seed(sources["seed"])
    dtype = deploy.get("dtype", "float64")
    a, b, x, y = (
        cubed_tpu.random.random(
            tuple(deploy["shape"]), chunks=deploy["chunks"], spec=spec, dtype=dtype
        )
        for _ in range(4)
    )
    return xp.mean(xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:])))


def run(expr, executor, callbacks, target):
    # a float32 is a float64 exactly: nothing is rounded here
    return float(expr.compute(executor=executor, callbacks=callbacks))


def nominal_bytes(deploy: dict) -> int:
    """The four arrays generated, in the dtype declared."""
    itemsize = {"float32": 4, "float64": 8}[deploy.get("dtype", "float64")]
    return 4 * math.prod(deploy["shape"]) * itemsize


def root_seeds(seed: int) -> list:
    """The root seeds of the four arrays, in the order ``build`` makes them
    (a, b, x, y)."""
    rng = random.Random(seed)
    return [rng.getrandbits(30) for _ in range(4)]


def block_grid(shape, chunk: int) -> list:
    """Per axis, the ``(start, stop)`` of every block."""
    return [
        [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)] for n in shape
    ]


def documented_stream(block_nbytes: int) -> str:
    """Which of the two documented streams a block of this size is drawn
    from here: ``threefry`` on a chip, and wherever ``CUBED_TPU_RNG`` pins
    it; ``philox`` on a CPU for a block up to the crossover."""
    import jax

    pinned = os.environ.get("CUBED_TPU_RNG", "auto").lower()
    if pinned in ("threefry", "philox"):
        return pinned
    if jax.default_backend() != "cpu" or block_nbytes > _PHILOX_MAX_BLOCK_BYTES:
        return "threefry"
    return "philox"


def reference_partials(deploy: dict, seed: int, *, generate=None, multiply=None,
                       accumulate="float64", stream=None) -> list:
    """The sum of ``a*x + b*y`` over each block, row 0 of the head blocks
    dropped: ``(coords, float)`` a block in C order, with plain ``jax.random``
    (or numpy's Philox). ``generate``, ``multiply`` and ``accumulate`` are the
    dtypes the blocks are drawn in, multiplied and added in, and summed in on
    the device; left alone they are the deployment's dtype, the same, and
    float64. Anything else is a reading of what a wrong program gives, never
    the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    declared = deploy.get("dtype", "float64")
    generate, multiply = generate or declared, multiply or declared
    shape, chunk = tuple(deploy["shape"]), int(deploy["chunks"])
    grid = block_grid(shape, chunk)
    numblocks = tuple(len(axis) for axis in grid)
    roots = np.asarray(root_seeds(seed), dtype=np.int64)
    itemsize = jnp.dtype(generate).itemsize

    def block_sum(block_shape, head, drawn):
        def f(values):
            if not drawn:
                values = [
                    jax.random.uniform(
                        jax.random.fold_in(jax.random.key(0), s), block_shape,
                        dtype=generate,
                    )
                    for s in values
                ]
            # a wider or narrower draw reaches the plan's dtype by a cast
            a, b, x, y = (v.astype(declared).astype(multiply) for v in values)
            v = (a * x + b * y).astype(accumulate)
            return jnp.sum(v[1:] if head else v).astype("float64")

        return jax.jit(f)

    compiled = {}
    partial = []
    with jax.threefry_partitionable(True), jax.default_matmul_precision("highest"):
        # block k in C order, as np.ravel_multi_index counts the grid
        for k, coords in enumerate(itertools.product(*map(range, numblocks))):
            bounds = [grid[d][c] for d, c in enumerate(coords)]
            block_shape = tuple(hi - lo for lo, hi in bounds)
            nbytes = math.prod(block_shape) * itemsize
            drawn = (stream or documented_stream(nbytes)) == "philox"
            signature = (block_shape, coords[0] == 0, drawn)
            if signature not in compiled:
                compiled[signature] = block_sum(*signature)
            if drawn:
                values = [
                    np.random.Generator(np.random.Philox(seed=int(root) + k))
                    .random(block_shape, dtype=generate)
                    for root in roots
                ]
            else:
                values = roots + k
            partial.append((coords, compiled[signature](values)))
        sums = [float(v) for v in jax.device_get([v for _, v in partial])]
    return [(coords, s) for (coords, _), s in zip(partial, sums)]


def count(deploy: dict) -> int:
    shape = deploy["shape"]
    return (shape[0] - 1) * math.prod(shape[1:])


def rounded(quotient: float, dtype: str) -> float:
    """The one rounding to the declared dtype, as a Python float."""
    import numpy as np

    return float(np.dtype(dtype).type(quotient))


def reference_mean(deploy: dict, seed: int, **variant) -> float:
    """``mean(a[1:]*x[1:] + b[1:]*y[1:])`` block by block: the partial sums
    added on the host with ``math.fsum``, the quotient rounded once to the
    deployment's dtype. ``variant`` as in ``reference_partials``."""
    partial = reference_partials(deploy, seed, **variant)
    total = math.fsum(s for _, s in partial)
    return rounded(total / count(deploy), deploy.get("dtype", "float64"))


def ulps(result: float, reference: float) -> int:
    """How many float32 values lie between two float32 values, the far one
    counted: 0 is equal to the last bit."""
    import numpy as np

    pair = np.asarray([result, reference], dtype=np.float32)
    if not (pair == np.asarray([result, reference])).all():
        raise AssertionError(f"{result!r} or {reference!r} is no float32")
    a, b = (int(v) for v in pair.view(np.int32))  # both positive: the order of the bits
    return abs(a - b)


def check(deploy: dict, sources: dict, result, first, target, full: bool) -> None:
    """Every compute: equal to the run's first value to the last bit (the
    same seed generates the same arrays). The full check, once a run: within
    ``ULPS_TOLERANCE`` units of float32's last place of the blockwise
    reference at the timed shape (a float64 deployment: within
    ``FLOAT64_RELATIVE_TOLERANCE``, relative)."""
    if first is not None and result != first:
        raise AssertionError(f"the same seed gave {first!r} and then {result!r}")
    if not full:
        return
    reference = reference_mean(deploy, sources["seed"])
    if deploy.get("dtype", "float64") == "float32":
        distance, limit = ulps(result, reference), ULPS_TOLERANCE
        unit = "units of float32's last place"
    else:
        distance = abs(result - reference) / abs(reference)
        limit, unit = FLOAT64_RELATIVE_TOLERANCE, "relative"
    print(
        f"vorticity_f32_mean: result {result!r} reference {reference!r} "
        f"distance {distance:g} {unit} (limit {limit:g})",
        flush=True,
    )
    if not distance <= limit:
        raise AssertionError(
            f"{result!r} is {distance:g} {unit} from the blockwise reference "
            f"{reference!r}; the limit is {limit:g}"
        )
