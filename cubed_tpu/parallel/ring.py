"""Ring-pipelined sharded contractions over the device mesh.

The reference scales contractions by fanning chunk tasks over serverless
workers with storage round-trips between tree levels; on a TPU mesh the same
scaling dimension (a chunk-grid axis too large for one device's memory) is
handled by keeping both operands sharded and rotating one of them around the
ICI ring with ``lax.ppermute`` — Cannon's algorithm — so no chip ever
materializes more than its own tile and the full contraction needs no
all-gather. This is the same communication pattern as ring attention: a ring
of peers each holding one shard of the "sequence", overlapping compute with
neighbor transfers.

``ring_matmul`` computes ``A @ B`` with A sharded by rows and B by the
contraction dim; step k multiplies the local A-column-slab against the
currently-held B shard, then rotates B to the next ring neighbor.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np


def ring_matmul(a, b, mesh=None, axis_name: str = "data"):
    """Sharded ``a @ b`` via a ppermute ring over *mesh*.

    a: (M, K) sharded on M; b: (K, N) sharded on K. Per-chip memory is
    O(M/p * K + K/p * N): the K axis never materializes whole anywhere.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if mesh is None:
        from .mesh import make_mesh

        mesh = make_mesh()
    p = math.prod(mesh.devices.shape)
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if K % p != 0 or M % p != 0:
        raise ValueError(f"M={M} and K={K} must be divisible by mesh size {p}")

    def step(a_local, b_local):
        # a_local: (M/p, K); b_local: (K/p, N) — the ring rotates b shards.
        idx = jax.lax.axis_index(axis_name)
        kp = K // p

        def body(i, carry):
            b_cur, acc = carry
            # which K-shard do we currently hold? it started at our own index
            # and has been rotated i times
            shard = ((idx + i) % p).astype(jnp.int32)
            a_slab = jax.lax.dynamic_slice(
                a_local,
                (jnp.int32(0), shard * jnp.int32(kp)),
                (a_local.shape[0], kp),
            )
            acc = acc + a_slab @ b_cur
            # rotate b to the next neighbor on the ring (ICI hop)
            b_nxt = jax.lax.ppermute(
                b_cur, axis_name, [(j, (j - 1) % p) for j in range(p)]
            )
            return (b_nxt, acc)

        acc0 = jnp.zeros((a_local.shape[0], N), dtype=jnp.result_type(a_local, b_local))
        try:
            # constants start axis-invariant; the carry must be marked varying
            # over the mesh axis to match the per-iteration accumulator type
            acc0 = jax.lax.pcast(acc0, (axis_name,), to="varying")
        except (AttributeError, TypeError):
            pass
        _, acc = jax.lax.fori_loop(0, p, body, (b_local, acc0))
        return acc

    fn = jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(P(axis_name, None), P(axis_name, None)),
            out_specs=P(axis_name, None),
        )
    )
    return fn(a, b)


def ring_reduction(x, combine, mesh=None, axis_name: str = "data"):
    """Tree-free ring all-reduce of per-shard partials (psum generalization).

    ``combine`` reduces the local shard to a partial; partials ride the ring
    accumulating, so every chip ends with the global result without a
    dedicated root — the communication shape of ring attention's softmax
    statistics exchange.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    if mesh is None:
        from .mesh import make_mesh

        mesh = make_mesh()
    p = math.prod(mesh.devices.shape)

    def step(x_local):
        partial = combine(x_local)

        def body(i, acc_incoming):
            acc, incoming = acc_incoming
            nxt = jax.lax.ppermute(
                incoming, axis_name, [(j, (j + 1) % p) for j in range(p)]
            )
            return (acc + nxt, nxt)

        acc, _ = jax.lax.fori_loop(0, p - 1, body, (partial, partial))
        return acc[None] if acc.ndim == 0 else acc

    fn = jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(P(axis_name),),
            out_specs=P(axis_name),
        )
    )
    return fn(x)
