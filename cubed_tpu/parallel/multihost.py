"""Multi-host (DCN) execution seams.

Single-host meshes scale the chunk grid over one host's chips via ICI; a
multi-host mesh extends the same mapping over DCN (docs/multihost.md holds
the full design). The reference has no equivalent — its scale-out is
serverless workers communicating through object storage
(cubed/runtime/executors/lithops.py etc.); here the control plane is JAX's
multi-controller SPMD (`jax.distributed.initialize` + one process per host)
and the data plane is XLA collectives, with Zarr IO sharded per host by the
functions in this module so every byte is read/written exactly once,
by the host whose chips own it.

These seams are testable without hardware: every function takes an
explicit ``host_of_device`` so a virtual 8-device CPU mesh can simulate N
hosts (tests/parallel/test_multihost.py), and the driver's dryrun exercises
the same path.
"""

from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..chunks import blockdims_from_blockshape
from ..utils import get_item
from .mesh import shard_bounds, within


def default_host_of_device(device) -> int:
    """Real multi-host: the controlling process index of the device."""
    return getattr(device, "process_index", 0)


def dcn_mesh(
    ici_shape: Sequence[int],
    axis_names: Optional[Sequence[str]] = None,
    devices=None,
    host_of_device: Optional[Callable] = None,
):
    """A mesh with the DCN (cross-host) axis leading.

    XLA maps the *leading* mesh axes onto the slower interconnect, so the
    canonical multi-host layout is ``("dcn", *ici_axes)``: data parallelism
    (or any axis whose collectives are infrequent, e.g. gradient all-reduce)
    rides DCN, while every per-step collective rides ICI within a host's
    slice. ``ici_shape`` is the per-host device grid.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    host_of_device = host_of_device or default_host_of_device
    n_hosts = max(host_of_device(d) for d in devices) + 1
    per_host = len(devices) // max(1, n_hosts)
    import math

    if math.prod(ici_shape) != per_host:
        raise ValueError(
            f"ici_shape {tuple(ici_shape)} does not match {per_host} devices/host"
        )
    names = tuple(axis_names) if axis_names else ("dcn",) + tuple(
        f"ici{i}" for i in range(len(ici_shape))
    )
    # devices sorted host-major so the leading axis is exactly the host axis
    devs = sorted(devices, key=lambda d: (host_of_device(d), d.id))
    arr = np.asarray(devs).reshape((n_hosts,) + tuple(ici_shape))
    return Mesh(arr, names)


def chunk_owner_devices(
    sharding, shape: Tuple[int, ...], chunkset
) -> Dict[Tuple[int, ...], object]:
    """chunk coord -> the device whose shard contains the chunk's start corner.

    With a chunk-aligned sharding (parallel.mesh.sharding_for_chunks prefers
    one) a chunk lies entirely in its owner's shard; for straddling chunks
    the start-corner rule still yields a total, deterministic partition —
    which is all per-host IO needs (each byte read once, by one host).
    """
    held = _device_bounds(sharding, shape)
    owners: Dict[Tuple[int, ...], object] = {}
    for coords in itertools.product(*(range(len(c)) for c in chunkset)):
        corner = _start_corner(get_item(chunkset, coords))
        owners[coords] = next(
            (device for device, bounds in held if within(corner, bounds)), None
        )
    return owners


def _device_bounds(sharding, shape) -> list:
    """[(device, the bounds of its shard)] in the sharding's device order."""
    shape = tuple(shape)
    return [
        (device, shard_bounds(index, shape))
        for device, index in sharding.devices_indices_map(shape).items()
    ]


def _start_corner(sel) -> tuple:
    """The one-element region at the start of ``sel``."""
    return tuple(slice(s.start, s.start + 1) for s in sel)


def chunk_within_owner_shard(
    sharding, shape, chunkset, coords: Tuple[int, ...]
) -> bool:
    """True when the chunk's whole region lies inside its owner's shard —
    the alignment a multi-process flush needs (a straddling chunk's data
    spans devices other processes own and cannot be fetched locally)."""
    sel = get_item(chunkset, coords)
    corner = _start_corner(sel)
    for _, bounds in _device_bounds(sharding, shape):
        if within(corner, bounds):
            return within(sel, bounds)
    return False


def host_chunk_assignment(
    sharding,
    shape: Tuple[int, ...],
    chunks: Tuple[int, ...],
    host_of_device: Optional[Callable] = None,
) -> Dict[int, List[Tuple[int, ...]]]:
    """host id -> chunk coords that host reads/writes for this array.

    The per-host Zarr IO sharding seam: under multi-controller SPMD every
    host runs the same plan, but only touches storage for the chunks its
    local devices own. Union over hosts is exactly the full chunk grid.
    """
    host_of_device = host_of_device or default_host_of_device
    chunkset = blockdims_from_blockshape(tuple(shape), tuple(chunks))
    owners = chunk_owner_devices(sharding, tuple(shape), chunkset)
    out: Dict[int, List[Tuple[int, ...]]] = {}
    for coords, device in owners.items():
        host = host_of_device(device) if device is not None else 0
        out.setdefault(host, []).append(coords)
    for v in out.values():
        v.sort()
    return out


def local_chunks(
    sharding,
    shape: Tuple[int, ...],
    chunks: Tuple[int, ...],
    host: Optional[int] = None,
    host_of_device: Optional[Callable] = None,
) -> List[Tuple[int, ...]]:
    """The chunk coords THIS host is responsible for (its IO shard)."""
    import jax

    if host is None:
        host = jax.process_index()
    return host_chunk_assignment(
        sharding, shape, chunks, host_of_device=host_of_device
    ).get(host, [])
