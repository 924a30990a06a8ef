"""Flash attention as a Pallas TPU kernel.

Hand-blocked online-softmax: the grid is (batch·head, q-blocks,
k-blocks); Pallas pipelines one (block_q, D) Q tile and one (block_k, D)
K/V tile through VMEM per cell — never the full sequence — while the
running (m, l, acc) recurrence lives in VMEM scratch across the k steps
(grid's innermost dimension is sequential on TPU).  Both matmuls hit the
MXU with fp32 accumulation; memory stays O(block) per core at any L.
Backward recomputes through the scan-based ``blockwise_attention`` (same
recurrence, XLA-scheduled) — no O(L²) residuals are ever materialized.

The reference has no counterpart (its attention era was RNNs); this is
the TPU-first hot-op path promised by the framework design.  Off-TPU the
same kernel runs in Pallas interpret mode, so CPU tests exercise the real
kernel code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_offset", "divisor_block",
           "mosaic_block_ok", "_on_tpu"]

_NEG = -1e30
_SUBLANES = 8  # Mosaic tiles the second-to-last block dim in 8-row units


def divisor_block(length, bound):
    """Largest block size <= ``bound`` that divides ``length`` exactly,
    preferring a multiple of 8 when one exists.

    The decode-path kernels tile over KV caches whose lengths are
    multiples of ``MXNET_SERVE_KV_BLOCK``, not of the configured
    sequence block — degrading the block to a divisor (instead of
    failing the divisibility assert) keeps every cache bucket eligible.
    Mosaic only accepts a second-to-last block dim that is a multiple of
    8 or the whole axis (:func:`mosaic_block_ok`), so a 1088-token cache
    tiles by 64, not by its largest divisor 68.
    """
    length = int(length)
    divisors = [b for b in range(min(length, max(1, int(bound))), 0, -1)
                if length % b == 0]
    return next((b for b in divisors if mosaic_block_ok(b, length)),
                divisors[0])


def mosaic_block_ok(block, length):
    """Does the chip's compiler accept ``block`` as the second-to-last
    block dim over an axis of ``length``?  (A multiple of 8, or the
    whole axis.)  Interpret mode takes any block; the eligibility rules
    in :mod:`.dispatch` ask this only when kernels compile."""
    return block == length or block % _SUBLANES == 0


def _vmem_spec(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _resolve_interpret(interpret):
    """``None`` selects by platform: compiled Mosaic on a TPU, Pallas
    interpret mode elsewhere — a direct caller on a chip never gets the
    interpreter by omission."""
    return (not _on_tpu()) if interpret is None else bool(interpret)


_SEQ_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _on_tpu():
    """True when the default jax backend is a TPU (shared probe — rtc.py
    and parallel/sp.py import this rather than re-implementing it)."""
    return jax.default_backend() == "tpu"


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, block_q, block_k, causal, nk):
    """One (batch·head, q-block, k-block) grid cell.

    m/l/acc are VMEM scratch carrying the online-softmax state across the
    sequential k dimension; the normalized output is written on the last
    k step."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: skip blocks entirely above the diagonal
    run = True
    if causal:
        run = qi * block_q + block_q - 1 >= ki * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)        # (BQ, D)
        kb = k_ref[0].astype(jnp.float32)       # (BK, D)
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, BK)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(qpos >= kpos, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] /
                    jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _softmax_scratch(block_q, d):
    """VMEM scratch of the online-softmax state: running max, running
    sum and the fp32 accumulator."""
    return [pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32)]


def _fa_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    assert Lq % block_q == 0 and Lk % block_k == 0, \
        "sequence lengths must divide the block sizes"
    nk = Lk // block_k
    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H, Lk, D)
    vr = v.reshape(B * H, Lk, D)

    kernel = functools.partial(_fa_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal, nk=nk)

    in_specs = [
        _vmem_spec((1, block_q, D), lambda b, i, j: (b, i, 0)),   # Q tile
        _vmem_spec((1, block_k, D), lambda b, i, j: (b, j, 0)),   # K tile
        _vmem_spec((1, block_k, D), lambda b, i, j: (b, j, 0)),   # V tile
    ]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
        grid=(B * H, Lq // block_q, nk),
        in_specs=in_specs,
        out_specs=_vmem_spec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        scratch_shapes=_softmax_scratch(block_q, D),
        interpret=interpret,
        name="flash_attention_fwd",
        compiler_params=_SEQ_GRID)(qr, kr, vr)
    return out.reshape(B, H, Lq, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _fa_forward(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out = _fa_forward(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    from ..parallel.sp import blockwise_attention
    # memory-efficient backward: re-run the scan recurrence under vjp
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, causal=causal, scale=scale, block_size=block_k),
        q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=None):
    """Flash attention over [B, H, L, D] tensors.

    ``interpret=None`` auto-selects: compiled Mosaic kernel on TPU,
    Pallas interpret mode elsewhere (slow but exact — for tests)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _flash(q, k, v, causal, float(scale), int(block_q),
                  int(block_k), _resolve_interpret(interpret))


# ---------------------------------------------------------------------------
# Causal flash attention WITH QUERY OFFSET — the decode-path kernel.
#
# Query row r of sequence b sits at global position offsets[b] + r and
# attends causally to key positions 0..offsets[b]+r of a kv_len cache.
# offsets=0 everywhere recovers plain causal attention; a decode step is
# Lq=1 with offsets = the per-sequence cache lengths, so the freshly
# written cache slot (position offsets[b]) is attended and every slot
# past it — prefill pad junk, zero-initialized blocks, retired tenants'
# leftovers — is masked with the shared -1e30 constant.  The offset is
# data (a traced per-sequence vector), so block skipping is dynamic
# (pl.when on a traced predicate) rather than a static grid prune.
# Inference-only: no custom_vjp — the serving decode loop never
# differentiates through the cache.
# ---------------------------------------------------------------------------
def _fa_offset_kernel(ofs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                      acc_ref, *, scale, block_q, block_k, nk):
    """One (batch·head, q-block, k-block) grid cell, offset-causal."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    ofs = ofs_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # skip blocks entirely above the (offset) diagonal — dynamic, the
    # offset is data; block (qi, ki) contributes iff its last query row
    # can see its first key column
    run = ofs + qi * block_q + block_q - 1 >= ki * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)        # (BQ, D)
        kb = k_ref[0].astype(jnp.float32)       # (BK, D)
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, BK)
        qpos = ofs + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
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

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] /
                    jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def flash_attention_offset(q, k, v, offsets, scale=None, block_q=128,
                           block_k=128, interpret=None):
    """Offset-causal flash attention: [B, H, Lq, D] queries whose row r
    of sequence b sits at position ``offsets[b] + r``, attending to a
    [B, H, Lk, D] KV cache.  Block sizes degrade to divisors of the
    sequence lengths (``divisor_block``) so any cache-bucket length is
    legal.  Forward-only (serving decode never differentiates)."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_q = divisor_block(Lq, block_q)
    block_k = divisor_block(Lk, block_k)
    nk = Lk // block_k
    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H, Lk, D)
    vr = v.reshape(B * H, Lk, D)
    # one offset scalar per grid row (repeat per head), prefetched to
    # SMEM whole — the way flash_attention_paged carries its tables;
    # Mosaic refuses a (1,)-blocked rank-1 SMEM operand
    ofs = jnp.repeat(jnp.asarray(offsets, jnp.int32).reshape(B), H)

    kernel = functools.partial(_fa_offset_kernel, scale=float(scale),
                               block_q=block_q, block_k=block_k, nk=nk)
    q_map = lambda b, i, j, ofs: (b, i, 0)
    kv_map = lambda b, i, j, ofs: (b, j, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, Lq // block_q, nk),
        in_specs=[_vmem_spec((1, block_q, D), q_map),        # Q tile
                  _vmem_spec((1, block_k, D), kv_map),       # K tile
                  _vmem_spec((1, block_k, D), kv_map)],      # V tile
        out_specs=_vmem_spec((1, block_q, D), q_map),
        scratch_shapes=_softmax_scratch(block_q, D))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
        interpret=_resolve_interpret(interpret),
        name="flash_attention_offset",
        compiler_params=_SEQ_GRID)(ofs, qr, kr, vr)
    return out.reshape(B, H, Lq, D)
