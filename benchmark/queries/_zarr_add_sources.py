"""The sources that the queries of the Zarr add pipeline share (no query
itself: the leading underscore keeps it out of a traffic mix's reach)."""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness import zarrv2

#: f64 add has one correctly rounded answer, so the tolerance only leaves room
#: for a device whose float64 is not IEEE: v5e holds one as a pair of float32
#: (about 49 significand bits), so each input loses up to 2**-49 on the way in
#: and the add rounds again (PR 21 measured 86% of results differing bitwise,
#: worst 2**-46.8). 2**-44 keeps 44 of the 53 bits: far past float32 (2**-24),
#: so a silent downcast fails by six orders of magnitude.
ADD_RTOL = 2.0**-44


def make(deploy: dict, seed: int, workdir: str):
    """Write the two sources from the seed, chunk by chunk; returns their
    paths and numpy's ``a + b``, the plain reference of the add. The values of
    a chunk are drawn into one buffer that every chunk uses again, and written
    from there: fresh arrays of this size cost seconds of page faults, which
    every run of every later check would pay as set-up."""
    shape, chunks = tuple(deploy["shape"]), tuple(deploy["chunks"])
    if any(s % c for s, c in zip(shape, chunks)):
        raise ValueError("the sources are made of whole chunks")
    rng = np.random.Generator(np.random.PCG64(seed))
    a_path, b_path = (os.path.join(workdir, f"{k}.zarr") for k in "ab")
    for path in (a_path, b_path):
        zarrv2.create(path, shape, chunks, np.float64)
    a, b = np.empty(chunks), np.empty(chunks)
    total = np.empty(shape)
    for idx, sel in zarrv2.chunk_slices(shape, chunks):
        rng.random(out=a)
        rng.random(out=b)
        zarrv2.write_chunk(a_path, idx, a)
        zarrv2.write_chunk(b_path, idx, b)
        np.add(a, b, out=total[sel])
    return a_path, b_path, total


def compare(what: str, got: np.ndarray, ref: np.ndarray, rtol: float) -> None:
    """A result in the caller's hands against the numpy reference, at ``rtol``
    relative (the rule of ``zarrv2.excess``, which the store's check shares)."""
    if got.shape != ref.shape or got.dtype != ref.dtype:
        raise AssertionError(
            f"{what}: got {got.dtype}{got.shape}, expected {ref.dtype}{ref.shape}"
        )
    worst = zarrv2.excess(got.copy(), ref, rtol, np.empty_like(ref))
    if worst > 0.0:
        raise AssertionError(f"{what}: off by more than {rtol} relative")
