"""The vorticity query of ``vorticity_mean.py`` (the same ``make_sources``,
``build``, ``run`` and ``nominal_bytes``, loaded from that file), checked
against a plain blockwise reference instead of a statistical bound.

The reference uses nothing of ``cubed_tpu``. It follows what
``cubed_tpu.random`` documents: each array draws a root seed of 30 bits from
Python's ``random`` (seeded with ``--seed``), and block ``k`` of the chunk
grid, counted in C order, is ``uniform(fold_in(key(0), root + k), block
shape, float64)`` of the threefry stream with
``jax_threefry_partitionable`` on. It generates one block of each of the four
arrays at a time on one device, drops row 0 of the blocks at the head of axis
0, sums ``a*x + b*y`` over the block on that device, and adds the partial
sums on the host with ``math.fsum``: a sum of another order than the program's
reduction tree, over values drawn apart from it. A run whose program drops a
slab, counts one twice, or generates or accumulates in float32 is not
``correct``."""

from __future__ import annotations

import importlib.util
import itertools
import math
import random
from pathlib import Path

WRITES_TARGET = False

#: ``check(full=True)`` passes a result within this of the reference,
#: relative. Two readings set it, both at the timed shape, (500, 900, 800) on
#: four v5e chips (PERF.md section 6, PR 28, which lists every seed). The
#: program's mean lay 1.1e-15 and 1.7e-15 from the reference on the first two
#: seeds, not 0: the device's float64 is a pair of float32 of about 49 bits,
#: and the two sums differ in order. The same reference generated, multiplied
#: and summed in float32 lay 3.8e-6 to 6.0e-5 from it on three seeds; a result
#: merely rounded to float32 lies 1e-8 away, a dropped or doubled slab 1e-5
#: or more. The limit stands three decades or more from both sides
RELATIVE_TOLERANCE = 1e-11


def _load_plain_query():
    path = Path(__file__).with_name("vorticity_mean.py")
    spec = importlib.util.spec_from_file_location("bench_vorticity_mean_plain", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_plain = _load_plain_query()
make_sources = _plain.make_sources
build = _plain.build
run = _plain.run
nominal_bytes = _plain.nominal_bytes


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


def reference_mean(deploy: dict, seed: int, dtype: str = "float64", devices=None) -> float:
    """``mean(a[1:]*x[1:] + b[1:]*y[1:])`` block by block, with plain
    ``jax.random``. ``dtype`` is what the blocks are generated, multiplied
    and summed in on the device: anything but float64 is a reading of what a
    lower precision gives, never the reference. Block ``k`` is computed whole
    on ``devices[k % len(devices)]`` (default: the first local device), so
    the reference holds four blocks at a time wherever it runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    shape, chunk = tuple(deploy["shape"]), int(deploy["chunks"])
    devices = list(devices) if devices else jax.local_devices()[:1]
    grid = block_grid(shape, chunk)
    numblocks = tuple(len(axis) for axis in grid)
    roots = np.asarray(root_seeds(seed), dtype=np.int64)

    def block_sum(block_shape, head):
        def f(seeds):
            a, b, x, y = (
                jax.random.uniform(
                    jax.random.fold_in(jax.random.key(0), s), block_shape, dtype=dtype
                )
                for s in seeds
            )
            v = a * x + b * y
            return jnp.sum(v[1:] if head else v)

        return jax.jit(f)

    compiled = {}
    partial = []
    with jax.threefry_partitionable(True), jax.default_matmul_precision("highest"):
        # block k in C order, as np.ravel_multi_index counts the grid
        for k, coords in enumerate(itertools.product(*map(range, numblocks))):
            bounds = [grid[d][c] for d, c in enumerate(coords)]
            signature = (tuple(hi - lo for lo, hi in bounds), coords[0] == 0)
            if signature not in compiled:
                compiled[signature] = block_sum(*signature)
            seeds = jax.device_put(roots + k, devices[k % len(devices)])
            partial.append(compiled[signature](seeds))
        partial = [float(v) for v in jax.device_get(partial)]
    return math.fsum(partial) / ((shape[0] - 1) * math.prod(shape[1:]))


def compare(result: float, reference: float) -> float:
    """The relative distance that ``RELATIVE_TOLERANCE`` bounds."""
    return abs(result - reference) / abs(reference)


def check(deploy: dict, sources: dict, result, first, target, full: bool) -> None:
    """Every compute: equal to the run's first value to the last bit (the
    same seed generates the same arrays). The full check, once a run: within
    ``RELATIVE_TOLERANCE`` of the blockwise reference at the timed shape."""
    if first is not None and result != first:
        raise AssertionError(f"the same seed gave {first!r} and then {result!r}")
    if not full:
        return
    import jax

    # the blocks take turns over the local devices: the harness reads every
    # chip's memory peak after this check to see that each held its share of
    # the program, and a reference on one chip alone would raise that one's
    reference = reference_mean(deploy, sources["seed"], devices=jax.local_devices())
    distance = compare(result, reference)
    print(
        f"vorticity_mean_exact: result {result!r} reference {reference!r} "
        f"relative distance {distance:.3e} (limit {RELATIVE_TOLERANCE:g})",
        flush=True,
    )
    if not distance <= RELATIVE_TOLERANCE:
        raise AssertionError(
            f"{result!r} is {distance:.3e} from the blockwise reference "
            f"{reference!r}, relative; the limit is {RELATIVE_TOLERANCE:g}"
        )
