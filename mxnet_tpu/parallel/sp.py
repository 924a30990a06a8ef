"""Sequence/context parallelism: ring attention over an ICI mesh axis.

The reference has NO sequence parallelism (SURVEY.md §5 "Long-context /
sequence parallelism: Absent" — its sequence-scale story is BucketingModule
+ FusedRNNCell).  This module is the beyond-reference long-context path the
TPU build treats as first-class: the sequence axis is sharded over a mesh
axis and attention runs as a *ring*: each step every device computes
blockwise (flash-style, online-softmax) attention of its local queries
against the K/V block currently resident, then rotates K/V one hop around
the ring with ``lax.ppermute`` (an ICI neighbor exchange), overlapping
compute with the collective.  After ``sp`` steps every query has seen every
key without any device ever materializing the full sequence.

Gradients flow through ``jax.grad`` of the scan — ``ppermute``'s transpose
is the reverse-ring ``ppermute``, so the backward pass is itself a ring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


__all__ = ["ring_attention", "ring_self_attention", "blockwise_attention",
           "local_attention"]

_NEG = -1e30


def _block_step(q, k, v, mask, m_prev, l_prev, o_prev, scale):
    """One online-softmax accumulation step (flash-attention recurrence).

    q: [B,H,Lq,D]  k,v: [B,H,Lk,D]  mask: [B,H,Lq,Lk] bool (True = attend)
    m/l/o: running max [B,H,Lq], denominator [B,H,Lq], numerator [B,H,Lq,D].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, _NEG)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # masked-out columns contribute exactly 0 (avoids exp(0)=1 poisoning
    # fully-masked blocks)
    p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    o_new = o_prev * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(p.dtype),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def blockwise_attention(q, k, v, causal=False, scale=None, block_size=None):
    """Single-device flash-style attention via lax.scan over K/V blocks.

    Shapes [B, H, L, D].  Reference memory behavior: O(L·block) not O(L²).
    """
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if block_size is None or block_size >= Lk:
        block_size = Lk
    assert Lk % block_size == 0, \
        "block_size %d must divide key length %d" % (block_size, Lk)
    nblocks = Lk // block_size

    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, H, Lq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Lq), jnp.float32)
    o0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    qpos = jnp.arange(Lq)

    def step(carry, i):
        m, l, o = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * block_size, block_size, 2)
        vb = jax.lax.dynamic_slice_in_dim(v, i * block_size, block_size, 2)
        kpos = i * block_size + jnp.arange(block_size)
        if causal:
            mask = (qpos[:, None] >= kpos[None, :])[None, None]
        else:
            mask = jnp.ones((1, 1, Lq, block_size), bool)
        mask = jnp.broadcast_to(mask, (B, H, Lq, block_size))
        m, l, o = _block_step(qf, kb, vb, mask, m, l, o, scale)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(step, (m0, l0, o0), jnp.arange(nblocks))
    out = o / jnp.where(l > 0, l, 1.0)[..., None]
    return out.astype(q.dtype)


def _pick_block(length):
    """Largest Mosaic-tileable block (multiple of the 16-sublane bf16 min)
    dividing ``length``; None if the length can't be tiled."""
    for b in (128, 64, 32, 16):
        if length % b == 0:
            return b
    return None


def local_attention(q, k, v, causal=False, scale=None):
    """Single-device attention: the hand-blocked Pallas flash kernel on
    TPU (pallas_ops/flash_attention.py), the scan recurrence elsewhere
    (and for shapes the kernel's tiling can't cover)."""
    from ..pallas_ops.flash_attention import _on_tpu
    Lq, Lk = q.shape[2], k.shape[2]
    bq, bk = _pick_block(Lq), _pick_block(Lk)
    if _on_tpu() and bq and bk and q.shape[3] % 8 == 0:
        from ..pallas_ops import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=bq, block_k=bk, interpret=False)
    return blockwise_attention(q, k, v, causal=causal, scale=scale)


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Ring attention body — call INSIDE shard_map/pjit with the sequence
    axis of q/k/v sharded over ``axis_name``.

    q, k, v: [B, H, L_local, D] (the local sequence shard).
    Returns [B, H, L_local, D].
    """
    B, H, Lc, D = q.shape
    sp = jax.lax.axis_size(axis_name)
    if sp == 1:
        # degenerate ring: pure local attention (flash kernel on TPU)
        return local_attention(q, k, v, causal=causal, scale=scale)
    idx = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, H, Lc), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Lc), jnp.float32)
    o0 = jnp.zeros((B, H, Lc, D), jnp.float32)
    qpos = idx * Lc + jnp.arange(Lc)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(carry, s):
        m, l, o, kb, vb = carry
        # K/V block currently resident started life on device (idx - s) mod sp
        src = (idx - s) % sp
        kpos = src * Lc + jnp.arange(Lc)
        if causal:
            mask = (qpos[:, None] >= kpos[None, :])[None, None]
            mask = jnp.broadcast_to(mask, (B, H, Lc, Lc))
        else:
            mask = jnp.broadcast_to(
                jnp.ones((1, 1, Lc, Lc), bool), (B, H, Lc, Lc))
        m, l, o = _block_step(qf, kb, vb, mask, m, l, o, scale)
        # rotate K/V one hop around the ring (overlaps with next compute)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (m, l, o, kb, vb), None

    (m, l, o, _, _), _ = jax.lax.scan(
        step, (m0, l0, o0, k, v), jnp.arange(sp))
    out = o / jnp.where(l > 0, l, 1.0)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh, axis_name="sp", batch_axis=None,
                        causal=False, scale=None):
    """Convenience wrapper: shard q/k/v [B,H,L,D] over the mesh (L over
    ``axis_name``, optionally B over ``batch_axis``) and run ring attention.
    """
    spec = P(batch_axis, None, axis_name, None)
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, scale=scale)
    sharded = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return sharded(q, k, v)
