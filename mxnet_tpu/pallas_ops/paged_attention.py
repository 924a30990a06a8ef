"""Paged flash attention: block-table indirection over a global KV pool.

The decode plane's contiguous cache reserves ``(slots, cache_len)``
per-sequence rectangles; the paged plane replaces them with a single
pool of ``MXNET_SERVE_KV_BLOCK``-token blocks shared by every sequence,
addressed through per-sequence block tables.  Logical token position p
of sequence b lives at pool row ``tables[b, p // bs] * bs + p % bs`` —
so sequences share physical blocks (prefix reuse), grow one block at a
time, and free their blocks at retire.

The kernel rides the ``flash_attention_offset`` machinery: same online
softmax, same ``-1e30`` masking constant, same fp32 accumulation, same
dynamic block skip on the per-sequence frontier.  What changes is WHERE
a K/V tile comes from: the k-grid dimension walks LOGICAL blocks and the
BlockSpec index map dereferences the block table — Pallas fetches the
physical tile ``tables[b, j]`` from the pool.  The tables and frontiers
ride as scalar-prefetch operands (``PrefetchScalarGridSpec``): they land
in SMEM before the grid runs, so index maps can read them.

The kernel takes the WHOLE stacked pool ``(L, H, num_blocks * bs, D)``
and a static ``layer``: the index map reads tile ``(layer, h,
tables[b, j], 0)`` of the array the program was handed, so no layer is
ever cut out of the stack (a slice handed to a Pallas call is a copy of
that layer, 135 MB at the served width;
docs/architecture/pallas_kernels.md).

``paged_attention_reference`` is the dense XLA twin — gather the pool
rows through the same table arithmetic, then the exact dense
offset-causal attention of ``ops/attention._dense_attention`` — the
``MXNET_PALLAS=0`` lowering and the parity oracle
(tests/test_paged_decode.py).  Forward-only, like every decode kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import (_resolve_interpret, _softmax_scratch,
                              _vmem_spec as _spec, divisor_block, pltpu)

__all__ = ["flash_attention_paged", "paged_attention_reference"]

_NEG = -1e30  # flash_attention._NEG: shared mask constant for parity


def _paged_kernel(*refs, scale, block_q, block_size, nt, int8):
    """One (batch, head, q-block, logical-block) grid cell.

    ``tbl_ref``/``pos_ref`` are the scalar-prefetch operands (SMEM);
    the k dimension walks logical blocks j — the index maps already
    dereferenced ``tbl_ref[b, j]``, so ``k_ref``/``v_ref`` hold the
    PHYSICAL tile.  Masking happens in logical position space.

    ``int8`` adds two more scalar-prefetch operands — per-(head,
    physical block) fp32 absmax scales for the K and V pools — and the
    tile loads dequantize on-tile (``codes * sk_ref[h, tbl_ref[b, ki]]``)
    before the unchanged fp32 online softmax."""
    if int8:
        (tbl_ref, pos_ref, sk_ref, sv_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (tbl_ref, pos_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
        sk_ref = sv_ref = None
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    ofs = pos_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # dynamic skip: logical block ki contributes iff the last query row
    # (global position ofs + qi*block_q + block_q - 1) can see its first
    # key position (ki * block_size)
    run = ofs + qi * block_q + block_q - 1 >= ki * block_size

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)     # (BQ, D)
        kb = k_ref[0, 0].astype(jnp.float32)    # (BS, D)
        vb = v_ref[0, 0].astype(jnp.float32)
        if int8:
            phys = tbl_ref[b, ki]               # SMEM scalar read
            kb = kb * sk_ref[h, phys]
            vb = vb * sv_ref[h, phys]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, BS)
        qpos = ofs + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 0)
        kpos = ki * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_size), 1)
        s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(qpos >= kpos, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32)

    @pl.when(ki == nt - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def flash_attention_paged(q, k_pool, v_pool, layer, tables, positions,
                          block_size, scale=None, block_q=128,
                          interpret=None, kv_scales=None):
    """Offset-causal flash attention against a PAGED KV pool.

    q: (B, H, Lq, D) — query row r of sequence b sits at global
    position ``positions[b] + r``; k_pool/v_pool: the whole stacked
    ``(L, H, num_blocks * block_size, D)`` global pools, read in place
    at the static index ``layer``; tables: (B, T) int32 per-sequence
    block tables mapping logical block j to a physical pool block
    (entries past a sequence's frontier must point at a valid block —
    conventionally the reserved trash block 0 — their keys are masked
    either way); positions: (B,) int32 frontiers.

    ``kv_scales`` — a ``(scale_k, scale_v)`` pair of ``(L, H,
    num_blocks)`` fp32 per-(layer, head, physical block) absmax scales
    — selects the int8 pool layout: the pools hold int8 codes and every
    K/V tile is dequantized ON-TILE (``codes * scale[layer, h,
    tbl[b, j]]``; the layer's scales are what rides in SMEM) before
    the unchanged fp32 online softmax, so accumulation numerics match
    the dense twin exactly on identically-dequantized values.

    The tables/positions ride as scalar-prefetch operands so BlockSpec
    index maps can gather physical tiles; blocks a sequence cannot see
    are skipped dynamically like ``flash_attention_offset``.
    Forward-only."""
    B, H, Lq, D = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    layer = int(layer)
    assert k_pool.shape == v_pool.shape and k_pool.ndim == 4 \
        and k_pool.shape[1] == H and 0 <= layer < k_pool.shape[0]
    assert k_pool.shape[2] % bs == 0, \
        "pool length must be a multiple of block_size"
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_q = divisor_block(Lq, block_q)
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(B)
    int8 = kv_scales is not None

    kernel = functools.partial(_paged_kernel, scale=float(scale),
                               block_q=block_q, block_size=bs, nt=T,
                               int8=int8)

    if int8:
        sk = jnp.asarray(kv_scales[0][layer], jnp.float32)
        sv = jnp.asarray(kv_scales[1][layer], jnp.float32)
        scalars = (tbl, pos, sk, sv)
    else:
        scalars = (tbl, pos)
    # the scalar operands stay first, tables then positions: a trace's
    # readers know the kernel by them (benchmark/layer_metrics/)
    q_map = lambda b, h, i, j, *_: (b, h, i, 0)
    kv_map = lambda b, h, i, j, tbl, *_: (layer, h, tbl[b, j], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, H, Lq // block_q, T),
        in_specs=[
            _spec((1, 1, block_q, D), q_map),  # Q tile
            # k/v: fetch PHYSICAL block tbl[b, j] of this layer from
            # the stacked pool — the index is in units of whole
            # (bs, D) blocks
            _spec((1, 1, bs, D), kv_map),
            _spec((1, 1, bs, D), kv_map),
        ],
        out_specs=_spec((1, 1, block_q, D), q_map),
        scratch_shapes=_softmax_scratch(block_q, D))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, D), q.dtype),
        interpret=_resolve_interpret(interpret),
        name="paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")))(*scalars, q, k_pool,
                                                v_pool)
    return out


def paged_attention_reference(q, k_pool, v_pool, layer, tables,
                              positions, block_size, scale=None,
                              kv_scales=None):
    """Dense XLA twin of :func:`flash_attention_paged`: gather layer
    ``layer``'s pool rows through the same block-table arithmetic, then
    the exact dense offset-causal attention (same ``-1e30`` constant,
    fp32 accumulation) — the ``MXNET_PALLAS=0`` lowering and the parity
    oracle.  ``kv_scales`` dequantizes int8 pools through the SAME
    per-(layer, head, physical block) scale arithmetic as the kernel."""
    B, H, Lq, D = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(B)
    # logical row p of sequence b = pool row tbl[b, p // bs]*bs + p % bs
    idx = (tbl[:, :, None] * bs +
           jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(
               B, T * bs)
    k = jnp.transpose(jnp.take(k_pool[layer], idx, axis=1), (1, 0, 2, 3))
    v = jnp.transpose(jnp.take(v_pool[layer], idx, axis=1), (1, 0, 2, 3))
    if kv_scales is not None:
        # per-(head, physical block) dequant, identical to the kernel's
        # on-tile multiply: scale[h, tbl[b, j]] covers pool rows
        # j*bs..j*bs+bs-1 of that gathered block
        sck = jnp.transpose(jnp.repeat(
            jnp.asarray(kv_scales[0][layer], jnp.float32)[:, tbl], bs,
            axis=2), (1, 0, 2))                          # (B, H, T*bs)
        scv = jnp.transpose(jnp.repeat(
            jnp.asarray(kv_scales[1][layer], jnp.float32)[:, tbl], bs,
            axis=2), (1, 0, 2))
        k = k.astype(jnp.float32) * sck[..., None]
        v = v.astype(jnp.float32) * scv[..., None]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, T * bs), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, T * bs), 1)
    qglob = pos[:, None, None] + qpos
    s = jnp.where((qglob >= kpos[None])[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
