"""Paged multi-head LATENT attention (MLA, absorbed form): every head
of a sequence attends over ONE shared latent row per token.

The latent pool holds ``[c_kv | k_r]`` a token a layer — ``rank``
values of compressed key/value and ``D - rank`` rotary key values, 576
for DeepSeek-V3 (``models/deepseek_v3.py``).  In the absorbed form the
key up-projection is folded into the query and the value up-projection
applied to the result, so that attention itself needs nothing but the
latent row:

    score = (q_abs . c_kv + q_rope . k_r) * scale
    o_lat = softmax(score) . c_kv            (the first ``rank`` values)

The row is key AND value, and it is the same for all heads: the kernel
puts every head of a sequence (and, in a prompt chunk, every query row)
into the rows of one Q tile, so a latent tile is fetched once a
sequence, not once a head.  At one query a head that is 128 x (576 +
512) x 2 operations against 1,152 bytes, 242 FLOP/B, the v5e's ridge.

The rest is ``flash_attention_paged``'s machinery: block tables and
frontiers ride as scalar-prefetch operands, the index map reads tile
``(layer, 0, tables[b, j], 0)`` of the WHOLE stacked pool (no layer is
cut out of the stack), fp32 online softmax with the shared ``-1e30``
constant, dynamic skip past the frontier and of every sequence outside
the dispatch (a step program runs all slots, and most rows of a chunk
program are such).  One pool block (64 rows of 1,152 bytes) is too
little work for a grid step, so a step takes ``group`` consecutive
table entries: the pool is handed to the call ``group`` times, each
operand with its own index map.

``mla_attention_reference`` is the dense XLA twin: gather the rows
through the same table arithmetic, then plain masked softmax attention
— the ``MXNET_PALLAS=0`` lowering and the parity oracle
(tests/test_deepseek_v3.py).  Forward-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import (_resolve_interpret, _softmax_scratch,
                              _vmem_spec as _spec, divisor_block, pltpu)

__all__ = ["mla_paged_attention", "mla_attention_reference"]

_NEG = -1e30  # flash_attention._NEG: shared mask constant for parity


def _mla_kernel(tbl_ref, pos_ref, q_ref, *refs, scale, rank, lq, block_q,
                block_size, group, nk, first):
    """One (sequence, Q tile, group of logical blocks) grid cell.  Row
    ``i`` of the flattened ``(H * Lq)`` query axis is head ``i // lq``,
    query ``i % lq``, at global position ``pos[b] + i % lq``; keys
    before position ``first`` (static) are seen by no query."""
    kv_refs = refs[:group]
    o_ref, m_ref, l_ref, acc_ref = refs[group:]
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    ofs = pos_ref[b]
    span = group * block_size

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # dynamic skip: the group contributes iff the chunk's last query
    # (global position ofs + lq - 1) can see its first key, and nothing
    # does for a sequence outside the dispatch (a table that owns no
    # first block: its output is zeros, and the caller discards it)
    @pl.when((tbl_ref[b, 0] != 0) & (ofs + lq - 1 >= ki * span))
    def _step():
        q = q_ref[0]                                     # (BQ, D)
        kv = jnp.concatenate([r[0, 0] for r in kv_refs], axis=0) \
            if group > 1 else kv_refs[0][0, 0]           # (span, D)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, span)
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, span), 0)
        qpos = ofs + jax.lax.rem(row, lq)
        kpos = ki * span + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, span), 1)
        seen = qpos >= kpos
        if first:
            seen = seen & (kpos >= first)
        s = jnp.where(seen, s, _NEG)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p.astype(kv.dtype), kv[:, :rank],
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] /
                    jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def mla_paged_attention(q, pool, layer, tables, positions, block_size,
                        rank, scale, block_q=512, group=8,
                        interpret=None, first=0):
    """Absorbed-form latent attention against the PAGED latent pool.

    q: ``(B, H, Lq, D)`` — ``[q_abs | q_rope]``, query row r of
    sequence b at global position ``positions[b] + r``; pool: the whole
    stacked ``(L, 1, num_blocks * block_size, D)`` latent pool, read in
    place at the static index ``layer``; tables ``(B, T)`` int32,
    positions ``(B,)`` int32 as for ``flash_attention_paged``.  Returns
    ``o_lat (B, H, Lq, rank)``: the softmax-weighted sum of the rows'
    first ``rank`` values, to be up-projected by the caller.  ``first``
    (static): the lowest position any query sees — 1 for a sequence
    whose row 0 is never written (a prediction module's cache,
    ``models/pangu_ultra_moe.py``); 0 traces what it always traced."""
    B, H, Lq, D = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    layer, rank = int(layer), int(rank)
    assert pool.ndim == 4 and pool.shape[1] == 1 and pool.shape[3] == D \
        and 0 <= layer < pool.shape[0] and 0 < rank <= D
    assert pool.shape[2] % bs == 0, \
        "pool length must be a multiple of block_size"
    rows = H * Lq
    block_q = divisor_block(rows, block_q)
    group = divisor_block(T, group)
    nk = T // group
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(B)

    kernel = functools.partial(
        _mla_kernel, scale=float(scale), rank=rank, lq=Lq,
        block_q=block_q, block_size=bs, group=group, nk=nk,
        first=int(first))
    q_map = lambda b, i, j, *_: (b, i, 0)

    def kv_map(g):
        return lambda b, i, j, tbl, *_: (layer, 0, tbl[b, j * group + g],
                                         0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, rows // block_q, nk),
        in_specs=[_spec((1, block_q, D), q_map)] + [
            # the PHYSICAL block tbl[b, j * group + g] of this layer,
            # in units of whole (bs, D) blocks of the stacked pool
            _spec((1, 1, bs, D), kv_map(g)) for g in range(group)],
        out_specs=_spec((1, block_q, rank), q_map),
        scratch_shapes=_softmax_scratch(block_q, rank))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, rank), q.dtype),
        interpret=_resolve_interpret(interpret),
        name="mla_paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))(
                tbl, pos, q.reshape(B, rows, D), *([pool] * group))
    return out.reshape(B, H, Lq, rank)


def mla_attention_reference(q, pool, layer, tables, positions,
                            block_size, rank, scale, first=0):
    """Dense XLA twin of :func:`mla_paged_attention`: gather layer
    ``layer``'s latent rows through the same block-table arithmetic,
    then masked softmax attention with the same ``-1e30`` constant and
    fp32 accumulation."""
    B, H, Lq, D = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(B)
    idx = (tbl[:, :, None] * bs +
           jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(
               B, T * bs)
    kv = jnp.take(pool[int(layer), 0], idx, axis=0)     # (B, T*bs, D)
    s = jnp.einsum("bhqd,bkd->bhqk", q, kv,
                   preferred_element_type=jnp.float32) * scale
    qpos = pos[:, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (Lq, T * bs), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, T * bs), 1)
    seen = qpos >= kpos[None]
    if first:
        seen = seen & (kpos[None] >= int(first))
    s = jnp.where(seen[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkd->bhqd", p.astype(kv.dtype),
                      kv[..., :int(rank)],
                      preferred_element_type=jnp.float32).astype(q.dtype)
