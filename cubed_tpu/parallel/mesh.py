"""Device-mesh utilities: the substrate that replaces the reference's
serverless worker pools (cubed/runtime/executors/*) with TPU chips.

The chunk grid of each whole-array op is the unit of parallelism in the
reference (one task per output chunk, communicating through object storage).
Here the same grid is laid over a ``jax.sharding.Mesh``: each chip owns a tile
of the grid resident in HBM, XLA inserts the collectives (reduction trees over
ICI, all-to-all for resharding) that the reference realizes as storage
round-trips. Multi-host meshes extend the same mapping over DCN.
"""

from __future__ import annotations

import itertools
import math
from typing import Optional, Sequence

import numpy as np

from ..utils import get_item


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Optional[Sequence[str]] = None,
    devices=None,
):
    """Create a Mesh over the available devices.

    Default: a 1-d ``("data",)`` mesh over all devices — chunk-grid
    parallelism is data parallelism over the grid. Pass an n-d shape (e.g.
    ``(4, 2)`` with ``("data", "model")``) for hybrid layouts.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if shape is None:
        shape = (n,)
    if axis_names is None:
        axis_names = ("data", "model", "seq", "expert")[: len(shape)]
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {shape} does not match {n} devices")
    dev_array = np.asarray(devices).reshape(tuple(shape))
    return Mesh(dev_array, tuple(axis_names))


def prime_factors(n: int) -> list[int]:
    """Prime factorization (ascending); [] for n <= 1."""
    out = []
    f = 2
    while f * f <= n:
        while n % f == 0:
            out.append(f)
            n //= f
        f += 1
    if n > 1:
        out.append(n)
    return out


def factorized_mesh(mesh):
    """A view of ``mesh``'s devices with one axis per prime factor.

    Splitting the device count into prime-sized axes lets
    ``sharding_for_chunks`` place factors on *different* array dims, so odd
    shapes still shard fully: (499, 450, 400) on 8 devices replicates under a
    1-d mesh (no dim divides by 8) but shards 8-way under (2, 2, 2)
    (450 % 2 == 0 on one dim, 400 % 4 == 0 on another). Device order is
    preserved, so collectives still ride the same ICI neighbours.
    """
    from jax.sharding import Mesh

    devs = mesh.devices.flatten()
    factors = prime_factors(len(devs)) or [1]
    return Mesh(
        devs.reshape(tuple(factors)),
        tuple(f"f{i}" for i in range(len(factors))),
    )


def sharding_for_chunks(
    mesh,
    chunkset: Optional[Sequence[Sequence[int]]],
    shape: Sequence[int],
):
    """A NamedSharding laying the chunk grid over the mesh.

    Mesh axes are assigned greedily to array dims — dims with the most chunk
    blocks first, then by extent. Several mesh axes may stack on one dim
    (their product must divide it), and no dim is required to be divisible by
    the whole mesh — combined with :func:`factorized_mesh` this shards ragged
    grids that a single-axis policy would replicate.

    Chunk-aligned assignments (the chunk count divisible by the axis product,
    so shard boundaries coincide with chunk boundaries and per-chunk task
    slices never straddle chips) are preferred in a first pass; remaining
    axes are then placed wherever the extent divides — a straddling shard
    beats replication. ``chunkset=None`` ranks dims by extent alone.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    if not shape:
        return NamedSharding(mesh, PartitionSpec())
    nb = [len(c) for c in chunkset] if chunkset else [1] * len(shape)
    assigned: list[list] = [[] for _ in shape]
    prods = [1] * len(shape)
    pool = [(n, s) for n, s in zip(mesh.axis_names, mesh.devices.shape) if s > 1]
    order = sorted(range(len(shape)), key=lambda d: (-nb[d], -shape[d]))
    for aligned_only in (True, False):
        for dim in order:
            if not pool:
                break
            for name, size in list(pool):
                total = prods[dim] * size
                if shape[dim] % total != 0:
                    continue
                if aligned_only and nb[dim] % total != 0:
                    continue
                assigned[dim].append(name)
                prods[dim] = total
                pool.remove((name, size))
    spec = [
        (tuple(a) if len(a) > 1 else a[0]) if a else None for a in assigned
    ]
    return NamedSharding(mesh, PartitionSpec(*spec))


def shard_bounds(index, shape: Sequence[int]) -> tuple:
    """A shard's index (slices, as a sharding or a shard gives it) as one
    (start, stop) a dim."""
    return tuple(
        (sl.start or 0, dim if sl.stop is None else sl.stop)
        for sl, dim in zip(index, shape)
    )


def within(sel, bounds) -> bool:
    """Whether the region ``sel`` (slices) lies inside ``bounds``."""
    return all(lo <= cut.start and cut.stop <= hi for cut, (lo, hi) in zip(sel, bounds))


def chunk_owners(sharding, shape: Sequence[int], chunkset):
    """chunk coords -> (device, the bounds of that device's shard) where
    ``sharding`` lays the array out as blocks of whole chunks, each held by
    one device this process can address: every chunk then has one owner and
    lies inside its shard. None for any other layout (a shard boundary
    inside a chunk, a region held by several devices, a device of another
    process), which has no owner to stream a chunk to."""
    shape = tuple(shape)
    if not sharding.is_fully_addressable:
        return None
    shards = {}
    for device, index in sharding.devices_indices_map(shape).items():
        bounds = shard_bounds(index, shape)
        if bounds in shards:
            return None
        shards[bounds] = device
    owners = {}
    for coords in itertools.product(*(range(len(c)) for c in chunkset)):
        sel = get_item(chunkset, coords)
        held = next((b for b in shards if within(sel, b)), None)
        if held is None:
            return None
        owners[coords] = (shards[held], held)
    return owners


def reshard(x, mesh, chunkset, shape):
    """Move an array to the sharding implied by a (new) chunk grid.

    Under jit this is the in-HBM rechunk: XLA lowers the layout change to
    collective permutes / all-to-all over ICI instead of the reference's
    storage round-trip (SURVEY.md section 3.3).
    """
    import jax

    return jax.device_put(x, sharding_for_chunks(mesh, chunkset, shape))
