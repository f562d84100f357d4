"""``mean(a + b, axis=0)`` over the same two Zarr sources as
``zarr_add_store``: the same preload and the same add, reduced to one row, so
nothing is flushed but a few kilobytes. The timed region ends with the numpy
values in the caller's hands."""

from __future__ import annotations

import math

import numpy as np

import cubed_tpu as ct
import cubed_tpu.array_api as xp
from benchmark.queries import _zarr_add_sources as sources_of

WRITES_TARGET = False

#: the mean over n rows of positive terms inherits the add's bound, and sums
#: in another order than numpy's pairwise sum: either order is within
#: n * 2**-53 relative (PR 21)
MEAN_RTOL_PER_ROW = 2.0**-53


def make_sources(deploy: dict, seed: int, workdir: str) -> dict:
    a, b, total = sources_of.make(deploy, seed, workdir)
    return {"a": a, "b": b, "ref": total.mean(axis=0)}


def build(deploy: dict, sources: dict, spec, target):
    return xp.mean(
        xp.add(ct.from_zarr(sources["a"], spec=spec),
               ct.from_zarr(sources["b"], spec=spec)),
        axis=0,
    )


def run(expr, executor, callbacks, target):
    return np.asarray(expr.compute(executor=executor, callbacks=callbacks))


def check(deploy: dict, sources: dict, result, first, target, full: bool) -> None:
    """Every compute's values against numpy's ``(a + b).mean(axis=0)``."""
    rows = deploy["shape"][0]
    sources_of.compare("mean(a + b, axis=0)", result, sources["ref"],
                       sources_of.ADD_RTOL + rows * MEAN_RTOL_PER_ROW)


def nominal_bytes(deploy: dict) -> int:
    """Two arrays read; the row that comes back is nothing beside them."""
    return 2 * math.prod(deploy["shape"]) * 8
