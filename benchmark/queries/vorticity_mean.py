"""``mean(a[1:]*x[1:] + b[1:]*y[1:])`` over four random float64 arrays
generated inside the plan: upstream's examples/pangeo-vorticity.ipynb
(BASELINE.json config 5). Touches no storage but the scalar it returns."""

from __future__ import annotations

import math
import random

import cubed_tpu.array_api as xp
import cubed_tpu.random

WRITES_TARGET = False

#: the mean of u1*u2 + u3*u4 over n uniform samples has variance (7/72)/n; a
#: value passes within this many standard errors of 0.5 (PR 21's check). An
#: exact reference for the threefry stream at full size would cost more set-up
#: than the window; ``benchmark/tests/test_rehearsal.py`` compares the same
#: query with PythonDagExecutor at a small size on the CPU.
STDERRS = 15.0


def make_sources(deploy: dict, seed: int, workdir: str) -> dict:
    return {"seed": seed}


def build(deploy: dict, sources: dict, spec, target):
    # cubed_tpu.random draws each array's root seed from Python's random, so
    # every compute of a run generates the same four arrays
    random.seed(sources["seed"])
    a, b, x, y = (
        cubed_tpu.random.random(tuple(deploy["shape"]), chunks=deploy["chunks"], spec=spec)
        for _ in range(4)
    )
    return xp.mean(xp.add(xp.multiply(a[1:], x[1:]), xp.multiply(b[1:], y[1:])))


def run(expr, executor, callbacks, target):
    return float(expr.compute(executor=executor, callbacks=callbacks))


def check(deploy: dict, sources: dict, result, first, target, full: bool) -> None:
    """Within ``STDERRS`` standard errors of 0.5, and equal to the run's first
    value to the last bit (the same seed generates the same arrays)."""
    shape = deploy["shape"]
    n = (shape[0] - 1) * math.prod(shape[1:])
    tol = STDERRS * math.sqrt(7.0 / 72.0 / n)
    if not abs(result - 0.5) < tol:
        raise AssertionError(f"{result!r} is not within {tol:.3e} of 0.5")
    if first is not None and result != first:
        raise AssertionError(f"the same seed gave {first!r} and then {result!r}")


def nominal_bytes(deploy: dict) -> int:
    """The four arrays generated, as float64."""
    return 4 * math.prod(deploy["shape"]) * 8
