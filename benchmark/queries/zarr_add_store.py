"""``to_zarr(a + b)`` over two Zarr sources: upstream's canonical add
pipeline (examples/lithops/aws-lambda/lithops-add-random.py), Zarr to HBM to
Zarr. The timed region ends when ``to_zarr`` has returned: every chunk of the
target written by the store's atomic, fsynced write and entered in the
checksum manifest."""

from __future__ import annotations

import math

import cubed_tpu as ct
import cubed_tpu.array_api as xp
from benchmark.harness import zarrv2
from benchmark.queries import _zarr_add_sources as sources_of

WRITES_TARGET = True


def make_sources(deploy: dict, seed: int, workdir: str) -> dict:
    a, b, total = sources_of.make(deploy, seed, workdir)
    return {"a": a, "b": b, "ref": total}


def build(deploy: dict, sources: dict, spec, target):
    return xp.add(ct.from_zarr(sources["a"], spec=spec),
                  ct.from_zarr(sources["b"], spec=spec))


def run(expr, executor, callbacks, target):
    ct.to_zarr(expr, target, executor=executor, callbacks=callbacks)
    return None


def check(deploy: dict, sources: dict, result, first, target, full: bool) -> None:
    """Full: the target read back with numpy alone against numpy's ``a + b``,
    and every stored chunk's CRC-32 against the manifest. Otherwise: every
    chunk file at full length with a manifest entry, no chunk read."""
    if full:
        zarrv2.check_store(target, sources["ref"], sources_of.ADD_RTOL)
    else:
        zarrv2.check_chunks_present(target)


def nominal_bytes(deploy: dict) -> int:
    """Two arrays read and one written."""
    return 3 * math.prod(deploy["shape"]) * 8
