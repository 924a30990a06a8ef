"""Attention operator: the symbol-level door to the flash kernel.

No reference counterpart (its attention era was RNNs): this is the
TPU-first hot-op surface the framework design promises.  The op lowers
scaled-dot-product attention over ``[batch, heads, length, head_dim]``
tensors; eligible shapes route through the Pallas dispatch seam to
``pallas_ops/flash_attention.py`` (online-softmax, O(block) memory, the
L×L score matrix never materializes), everything else — and
``MXNET_PALLAS=0`` — lowers to the dense XLA computation with the SAME
masking constant, so the two paths are numerically twins.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import MXNetError
from .registry import Bool, Float, register

_NEG = -1e30  # flash_attention._NEG: shared mask constant for parity


def _dense_attention(q, k, v, causal, scale, q_offsets=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    lq, lk = q.shape[2], k.shape[2]
    if q_offsets is not None:
        # offset-causal: query row r of sequence b sits at global
        # position q_offsets[b] + r (the decode path's per-sequence
        # cache frontier); the SAME -1e30 constant as the offset flash
        # kernel, so the two lowerings stay numerical twins
        qpos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        qglob = jnp.asarray(q_offsets, jnp.int32)[:, None, None] + qpos
        s = jnp.where((qglob >= kpos[None])[:, None], s, _NEG)
    elif causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def sdp_attention(query, key, value, causal=False, scale=0.0,
                  q_offsets=None):
    """Functional scaled-dot-product attention over [B, H, L, D] —
    the same route decision the ``DotProductAttention`` symbol op
    makes, callable from pure-JAX graphs (the serving decode engine).

    ``q_offsets`` (a per-sequence int32 vector) selects the
    offset-causal variant: query row r of sequence b sits at position
    ``q_offsets[b] + r`` and attends to key positions ``<= q_offsets[b]
    + r`` of the KV cache — eligible shapes route to
    ``flash_attention_offset`` (forward-only), everything else (and
    ``MXNET_PALLAS=0``) to the dense XLA twin with the same masking
    constant."""
    b, h, lq, d = query.shape
    lk = key.shape[2]
    if scale <= 0.0:
        scale = 1.0 / (d ** 0.5)
    from ..pallas_ops import dispatch as _pd
    if q_offsets is not None:
        if _pd.use_attention("DotProductAttentionOffset", b, h, lq, lk,
                             d, query.dtype, offset=True):
            from ..pallas_ops.flash_attention import flash_attention_offset
            bs = _pd.block_seq()
            return flash_attention_offset(
                query, key, value, q_offsets, scale=scale, block_q=bs,
                block_k=bs, interpret=_pd.interpret_mode())
        return _dense_attention(query, key, value, True, scale,
                                q_offsets=q_offsets)
    if _pd.use_attention("DotProductAttention", b, h, lq, lk, d,
                         query.dtype):
        from ..pallas_ops import flash_attention
        bs = _pd.block_seq()
        return flash_attention(query, key, value, causal=causal,
                               scale=scale, block_q=bs, block_k=bs,
                               interpret=_pd.interpret_mode())
    return _dense_attention(query, key, value, causal, scale)


def sdp_attention_paged(query, k_pool, v_pool, layer, tables, positions,
                        block_size, scale=0.0, kv_scales=None, group=1,
                        window=None, name="paged_attention", block_q=None):
    """Paged scaled-dot-product attention: [B, H, Lq, D] queries whose
    row r of sequence b sits at global position ``positions[b] + r``,
    attending over layer ``layer`` (a static int) of the whole stacked
    block pool (``(L, H, num_blocks * block_size, D)``, never a slice
    of it: the kernel addresses the layer in place) through
    per-sequence block tables (``(B, T)`` int32) — the decode engine's
    paged-KV door (docs/architecture/decode_engine.md).

    ``kv_scales`` — a ``(scale_k, scale_v)`` pair of ``(L, H,
    num_blocks)`` fp32 arrays — marks the pools as int8 codes with
    per-(layer, head, block) absmax scales; both lowerings dequantize
    through the identical scale arithmetic (on-tile in the kernel, on
    the gathered rows in the reference), so they remain numerical twins.

    GROUPED-QUERY heads: a pool of fewer heads than the query's (a
    divisor) serves ``H // pool heads`` query heads a pool head, all of
    them in one Q tile; ``v_pool=None`` reads a pool row as key AND
    value (``[K | V]`` rows under a query padded with zeros, the value
    half of the result taken by the caller: ``models/lfm2_moe.py``);
    ``group`` table entries make one grid step of the kernel.

    ``window`` (static; None: the causal frontier alone): the query at
    ``p`` sees keys ``p - window + 1 .. p``, in both lowerings; the
    kernel walks only the blocks such a query can reach, under
    ``name`` in a trace (``models/cohere2_moe.py``'s window layers).
    ``block_q`` bounds the kernel's Q tile (default: the configured
    sequence block): a model whose chunk puts many query heads on a KV
    head asks for a taller one, since every Q tile of a KV head fetches
    the sequence's keys and values again.

    Eligible shapes route to ``flash_attention_paged`` (scalar-prefetch
    block tables, dynamic block skip, forward-only); everything else —
    and ``MXNET_PALLAS=0`` — lowers to ``paged_attention_reference``,
    the gather + dense twin with the same masking constant."""
    b, h, lq, d = query.shape
    t = tables.shape[1]
    bs = int(block_size)
    if scale <= 0.0:
        scale = 1.0 / (d ** 0.5)
    from ..pallas_ops import dispatch as _pd
    # the Q tile's rows: the query heads of a pool head, flattened
    rows = lq * (h // k_pool.shape[1])
    if _pd.use_attention_paged("DotProductAttentionPaged", b, h, rows,
                               t * bs, d, query.dtype, bs):
        from ..pallas_ops.paged_attention import (flash_attention_paged,
                                                  heads_per_copy)
        # the route within the kernel: pool heads a copy brings in
        _pd._note("DotProductAttentionPaged.heads_per_copy=%d"
                  % heads_per_copy(h // k_pool.shape[1], k_pool.shape[1]))
        return flash_attention_paged(
            query, k_pool, v_pool, layer, tables, positions, bs,
            scale=scale, block_q=block_q or _pd.block_seq(),
            interpret=_pd.interpret_mode(), kv_scales=kv_scales,
            group=group, window=window, name=name)
    from ..pallas_ops.paged_attention import paged_attention_reference
    return paged_attention_reference(query, k_pool, v_pool, layer, tables,
                                     positions, bs, scale=scale,
                                     kv_scales=kv_scales, window=window)


def mla_attention_paged(query, pool, layer, tables, positions,
                        block_size, rank, scale, first=0):
    """Paged multi-head LATENT attention in the absorbed form: ``[B, H,
    Lq, D]`` queries ``[q_abs | q_rope]`` against layer ``layer`` (a
    static int) of the whole stacked latent pool ``(L, 1, num_blocks *
    block_size, D)``, whose row is key and — its first ``rank`` values
    — value for every head alike.  Returns ``o_lat [B, H, Lq, rank]``.
    ``first`` (static): the lowest position a query sees (0: all).

    Eligible shapes route to ``mla_paged_attention`` (all heads of a
    sequence in one Q tile, a latent tile fetched once a sequence);
    everything else — and ``MXNET_PALLAS=0`` — lowers to
    ``mla_attention_reference``, the gather + dense twin."""
    b, h, lq, d = query.shape
    bs = int(block_size)
    from ..pallas_ops import dispatch as _pd
    from ..pallas_ops import mla_attention as _mla
    if _pd.use_mla_paged("LatentAttentionPaged", b, h, lq,
                         tables.shape[1] * bs, d, rank, query.dtype, bs):
        return _mla.mla_paged_attention(
            query, pool, layer, tables, positions, bs, rank, scale,
            interpret=_pd.interpret_mode(), first=first)
    return _mla.mla_attention_reference(query, pool, layer, tables,
                                        positions, bs, rank, scale, first)


def lightning_index_scores(q, w, pool, layer, tables, positions,
                           block_size):
    """DeepSeek-V3.2's lightning indexer against the paged INDEX-KEY
    leaf: ``[B, Lq, Hi, d]`` index queries and ``[B, Lq, Hi]`` head
    weights against layer ``layer`` (a static int) of the whole stacked
    ``(L, 1, num_blocks * block_size, d)`` leaf through the block
    tables.  Returns ``[B, Lq, T * block_size]`` fp32 scores ``sum_j
    w_j relu(q_j . k_s)`` by LOGICAL position, ``-inf`` past each
    query's frontier and for a sequence outside the dispatch.

    Eligible shapes route to ``dsa_index_scores`` (ReLU and the heads'
    sum on the tile); everything else — and ``MXNET_PALLAS=0`` — lowers
    to ``dsa_index_scores_reference``, the gather + dense twin."""
    b, lq, hi, d = q.shape
    bs = int(block_size)
    from ..pallas_ops import dispatch as _pd
    from ..pallas_ops import dsa as _dsa
    if _pd.use_dsa_index("LightningIndexer", b, lq, hi,
                         tables.shape[1] * bs, d, q.dtype, bs):
        return _dsa.dsa_index_scores(q, w, pool, layer, tables, positions,
                                     bs, interpret=_pd.interpret_mode())
    return _dsa.dsa_index_scores_reference(q, w, pool, layer, tables,
                                           positions, bs)


def sparse_select(scores, k):
    """The ``k`` best positions of each query, EXACT, as two
    thresholds: ``[..., S]`` fp32 scores -> ``(thr, tie)``, ``[..., 1]``
    int32 each; ``pallas_ops.dsa.selected_mask(scores, thr, tie)`` is
    the set: the scores above the ``k``-th largest and, of those that
    tie with it, the ones at the lowest positions, ``k`` in all (where
    a query sees fewer than ``k`` positions, all it sees, the rest made
    up of positions it cannot see, which every reader masks).  No
    ``approx_max_k`` and no threshold that admits more or fewer than
    the model says.

    Eligible shapes route to ``dsa_select_threshold`` (the counting
    passes over a tile that stays in VMEM); everything else — and
    ``MXNET_PALLAS=0`` — to its XLA twin, the same passes."""
    from ..pallas_ops import dispatch as _pd
    from ..pallas_ops import dsa as _dsa
    lead, width = scores.shape[:-1], scores.shape[-1]
    flat = scores.reshape(-1, width)
    if _pd.use_dsa_select("SparseSelect", flat.shape[0], width, k,
                          scores.dtype):
        thr, tie = _dsa.dsa_select_threshold(
            flat, k, interpret=_pd.interpret_mode())
    else:
        thr, tie = _dsa.dsa_select_threshold_reference(flat, k)
    return thr.reshape(lead + (1,)), tie.reshape(lead + (1,))


def mla_attention_sparse(query, pool, layer, tables, positions, scores,
                         thr, tie, counts, k, block_size, rank, scale):
    """Absorbed-form latent attention over the SELECTED positions only:
    ``[B, H, Lq, D]`` queries, each with its own selection (``scores``
    ``[B, Lq, T * block_size]`` and :func:`sparse_select`'s ``thr``,
    ``tie``), against layer ``layer`` of the whole stacked latent
    pool.  Returns ``o_lat [B, H, Lq, rank]``.  Two forms of the same
    mathematics, chosen by what the program can observe:

    * ONE query a sequence (a decode step): the mask becomes ``k``
      ascending positions (``compact_positions``), they go through the
      block tables to pool rows, the rows are gathered (one gather over
      the pool, whose layer is an index and not a slice) and
      ``dsa_mla_attention`` reads those ``k`` rows a sequence whatever
      the context; ``counts`` ``[B, Lq]`` says how many of them are live
      (``min(seen, k)``; 0: a sequence outside the dispatch).
    * a chunk of queries: ``dsa_mla_attention_masked`` walks the
      sequence's rows once for all of them under the mask (their sets
      mostly coincide; a gathered copy a query would move ``Lq`` times
      what the walk reads).

    Everything else — and ``MXNET_PALLAS=0`` — takes the dense twin of
    the same form."""
    b, h, lq, d = query.shape
    bs = int(block_size)
    from ..pallas_ops import dispatch as _pd
    from ..pallas_ops import dsa as _dsa
    if lq > 1:
        if (_pd.interpret_mode() or lq % 8 == 0) and _pd.use_mla_paged(
                "LatentAttentionSparse", b, h, lq, tables.shape[1] * bs, d,
                rank, query.dtype, bs):
            _pd._note("LatentAttentionSparse.masked")
            return _dsa.dsa_mla_attention_masked(
                query, pool, layer, tables, positions, scores, thr, tie,
                bs, rank, scale, interpret=_pd.interpret_mode())
        return _dsa.dsa_mla_attention_masked_reference(
            query, pool, layer, tables, positions, scores, thr, tie, bs,
            rank, scale)
    picked = _dsa.compact_positions(
        _dsa.selected_mask(scores, thr, tie).reshape(b, -1), k)  # (B, k)
    tbl = jnp.asarray(tables, jnp.int32)
    # the table's entry of each position by comparison, not by a gather
    # of B x k single entries (a TPU gather costs by its slices, ~28 ns
    # apiece whatever their size: my chip run, PR 41)
    entry = jnp.arange(tbl.shape[1], dtype=jnp.int32)
    block = jnp.sum(jnp.where((picked // bs)[..., None] == entry,
                              tbl[:, None, :], 0), axis=-1)
    at = block * bs + picked % bs
    where = jnp.stack([jnp.full_like(at, int(layer)), at], axis=-1)
    rows = jax.lax.gather(
        pool, where,
        jax.lax.GatherDimensionNumbers(
            offset_dims=(2,), collapsed_slice_dims=(0, 1, 2),
            start_index_map=(0, 2)),
        slice_sizes=(1, 1, 1, d),
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)    # (B, k, D)
    q = query.reshape(b, h, d)
    if _pd.use_dsa_attention("LatentAttentionSparse", b, h, int(k), d,
                             rank, query.dtype):
        _pd._note("LatentAttentionSparse.gathered")
        out = _dsa.dsa_mla_attention(q, rows, counts, rank, scale,
                                     interpret=_pd.interpret_mode())
    else:
        out = _dsa.dsa_mla_attention_reference(q, rows, counts, rank,
                                               scale)
    return out.reshape(b, h, 1, int(rank))


def _attn_fc(attrs, query, key, value):
    if query.ndim != 4:
        raise MXNetError("DotProductAttention expects [batch, heads, "
                         "length, head_dim] inputs, got ndim=%d"
                         % query.ndim)
    return sdp_attention(query, key, value, causal=attrs["causal"],
                         scale=attrs["scale"])


def _attn_infer(attrs, in_shapes):
    qs, ks, vs = in_shapes
    known = qs or ks or vs
    if known is not None:
        for i in range(3):
            if in_shapes[i] is None:
                in_shapes[i] = known
    return in_shapes, [in_shapes[0]], []


register("DotProductAttention", fcompute=_attn_fc,
         arguments=("query", "key", "value"),
         attrs={"causal": Bool(False, doc="apply a lower-triangular "
                                          "mask: position q attends "
                                          "only to keys k <= q"),
                "scale": Float(0.0, doc="score scale; <= 0 selects "
                                        "1/sqrt(head_dim)")},
         infer_shape=_attn_infer,
         doc="Scaled dot-product attention over [batch, heads, length, "
             "head_dim]; scale<=0 means 1/sqrt(head_dim).  Eligible "
             "shapes run the Pallas flash-attention kernel (online "
             "softmax, no L×L score tensor); others lower to dense "
             "XLA attention (docs/architecture/pallas_kernels.md).")
