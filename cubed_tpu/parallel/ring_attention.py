"""Ring attention: sequence-parallel attention over a device mesh axis.

Long-context scaling for the TPU build: the sequence dimension is sharded
over a mesh axis, each device holds one block of Q/K/V, and K/V blocks
rotate around the ring via ``lax.ppermute`` (one ICI hop per step) while a
flash-style online softmax accumulates exact attention — no device ever
materializes the full (S, S) score matrix or the full K/V.

The reference has no attention ops (SURVEY.md §5.7) — its structural
analogue of "a dimension larger than one worker's memory" is the chunk
grid; this module is the corresponding first-class long-context capability
for the mesh substrate (blockwise-parallel transformers / ring attention,
computed with jax collectives riding ICI).

Memory per device: O(S_local * d) activations + one in-flight K/V block —
the same bounded-memory contract the chunked array layer gives, applied to
attention.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np


def _jax():
    import jax

    return jax


def dense_attention(q, k, v, *, causal: bool = False, scale: Optional[float] = None):
    """Reference single-device attention (B, S, H, D) — the test oracle."""
    jax = _jax()
    jnp = jax.numpy
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        S_q, S_k = scores.shape[-2], scores.shape[-1]
        qi = jnp.arange(S_q)[:, None]
        ki = jnp.arange(S_k)[None, :]
        scores = jnp.where(qi >= ki, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _ring_attention_local(
    q, k, v, *, axis_name: str, causal: bool, scale: float, ring_size: int
):
    """Per-device body (runs inside shard_map): rotate K/V, accumulate online.

    q, k, v: (B, S_local, H, D) — this device's sequence block.
    Accumulators follow the flash-attention recurrence: running max ``m``,
    running denominator ``l``, and unnormalized output ``o``; each ring step
    rescales by ``exp(m_old - m_new)`` so the final ``o / l`` is exact
    softmax attention regardless of block order.
    """
    jax = _jax()
    jnp = jax.numpy
    lax = jax.lax

    n = ring_size  # static: the ppermute permutation needs a Python int
    idx = lax.axis_index(axis_name)
    B, S, H, D = q.shape

    q_bhsd = q.transpose(0, 2, 1, 3)  # (B, H, S, D)

    m0 = jnp.full((B, H, S), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, S), dtype=jnp.float32)
    o0 = jnp.zeros((B, H, S, D), dtype=jnp.float32)
    # the loop body's outputs are device-varying (they mix in axis_index and
    # ppermute'd blocks); the initial carry must carry the same vma type
    o0, l0, m0 = (lax.pcast(x, (axis_name,), to="varying") for x in (o0, l0, m0))

    q_pos = idx * S + jnp.arange(S)  # global positions of this device's queries

    def body(step, carry):
        o, l, m, k_blk, v_blk = carry
        src = (idx - step) % n  # which device's block we currently hold
        scores = (
            jnp.einsum(
                "bhqd,bkhd->bhqk",
                q_bhsd.astype(jnp.float32),
                k_blk.astype(jnp.float32),
            )
            * scale
        )
        if causal:
            k_pos = src * S + jnp.arange(S)
            mask = q_pos[:, None] >= k_pos[None, :]  # (S_q, S_k)
            scores = jnp.where(mask[None, None], scores, -jnp.inf)

        m_new = jnp.maximum(m, scores.max(axis=-1))
        # fully-masked rows keep m == -inf; guard the exp against inf - inf
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - safe_m[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )

        # pass our current K/V block to the next device in the ring (ICI hop)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, l, m_new, k_blk, v_blk)

    o, l, m, _, _ = lax.fori_loop(0, n, body, (o0, l0, m0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked queries output 0
    out = (o / l[..., None]).transpose(0, 2, 1, 3)  # back to (B, S, H, D)
    return out.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    *,
    mesh=None,
    axis_name: str = "seq",
    causal: bool = False,
    scale: Optional[float] = None,
):
    """Exact attention with the sequence dimension sharded over ``axis_name``.

    q, k, v: (batch, seq, heads, head_dim), with seq divisible by the mesh
    axis size. With ``mesh=None`` falls back to dense single-device
    attention (the ring of size 1).

    The returned array is sharded like the inputs (seq over ``axis_name``).
    Differentiable: gradients flow through ``ppermute`` (reverse ring).
    """
    jax = _jax()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if mesh is None:
        return dense_attention(q, k, v, causal=causal, scale=scale)

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    spec = P(None, axis_name, None, None)
    fn = functools.partial(
        _ring_attention_local,
        axis_name=axis_name,
        causal=causal,
        scale=scale,
        ring_size=int(mesh.shape[axis_name]),
    )
    mapped = shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    return mapped(q, k, v)


def sequence_sharded(x, mesh, axis_name: str = "seq", dim: int = 1):
    """Place an array with dimension ``dim`` sharded over a mesh axis."""
    jax = _jax()
    from jax.sharding import NamedSharding, PartitionSpec

    spec = [None] * x.ndim
    spec[dim] = axis_name
    return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))
