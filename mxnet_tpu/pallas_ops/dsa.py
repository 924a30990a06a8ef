"""DeepSeek sparse attention (DSA): the lightning indexer's scores over
a PAGED index-key leaf, and latent attention over the rows a query
selected.

DeepSeek-V3.2 (``models/deepseek_v32.py``) keeps, beside the latent row
``[c_kv | k_r]`` of MLA, one INDEX KEY of ``d`` values a token a layer
(128; one head for all the indexer's query heads).  A query scores
every cached position with it,

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])      (fp32)

keeps the ``index_topk`` best (2,048) and runs MLA's softmax over those
alone.  Four kernels:

* ``dsa_index_scores`` walks a sequence's index keys through its block
  table as ``mla_paged_attention`` walks its latent rows (scalar-
  prefetch tables and frontiers, the layer addressed in place in the
  whole stacked leaf, ``group`` table entries a grid step, dynamic skip
  past the frontier and of sequences outside the dispatch).  A Q tile
  holds every head of a few queries; ReLU and the weighted sum over
  the heads happen on the tile, so what leaves is one fp32 score a
  (query, key): 16,384 FLOP and, a sequence, 256 B a cached key.
* ``dsa_select_threshold`` is the SELECTION, exact, as a threshold: a
  query's ``k``-th largest score, found bit by bit over the scores'
  order-preserving integer keys (32 counting passes over a tile of 8
  queries that stays in VMEM; a stable sort of the table's width, which
  is what ``jax.lax.top_k`` compiles to, is 120 passes over HBM), and
  where scores tie at that place the position up to which the tied
  ones are kept (15 passes more), so that exactly ``k`` stay:
  ``selected_mask`` says which.  ``compact_positions`` turns a mask of
  ``k`` into ``k`` ascending positions with two small products and two
  counting comparisons (no sort, no scatter, no gather).
* ``dsa_mla_attention_masked`` is ``mla_paged_attention``'s walk of a
  sequence's latent rows under the selection's mask, rebuilt on the
  tile from the scores and the two thresholds: the form a prompt CHUNK
  takes, whose queries of one sequence share most of their rows (a
  gathered copy a query moves 32 times what the walk reads).
* ``dsa_mla_attention`` is the absorbed-form latent attention of
  ``mla_attention.py`` over rows that are ALREADY GATHERED: ``(N, K,
  D)``, the ``K`` selected rows of each of ``N`` queries side by side
  (the caller gathers them from the pool by their rows: ``ops/
  attention.mla_attention_sparse``), of which the first ``count[n]``
  are live.  Every head of a query is one Q tile; tiles of rows past
  ``count[n]`` are neither computed nor fetched.  It reads ``K`` rows a
  query whatever the context: the form a DECODE step takes.

Each has its dense XLA twin (``*_reference``): the ``MXNET_PALLAS=0``
lowering and the parity oracle (tests/test_deepseek_v32.py).
Forward-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import (_resolve_interpret, _softmax_scratch,
                              _vmem_spec as _spec, divisor_block, pltpu)

__all__ = ["dsa_index_scores", "dsa_index_scores_reference",
           "dsa_select_threshold", "dsa_select_threshold_reference",
           "selected_mask", "compact_positions",
           "dsa_mla_attention", "dsa_mla_attention_reference",
           "dsa_mla_attention_masked",
           "dsa_mla_attention_masked_reference"]

_NEG = -1e30  # flash_attention._NEG: shared mask constant for parity


def _index_kernel(tbl_ref, pos_ref, q_ref, w_ref, *refs, lq, heads, qt,
                  block_size, group):
    """One (sequence, tile of ``qt`` queries, group of logical blocks)
    grid cell.  Row ``i`` of the Q tile is query ``i // heads`` of the
    tile, head ``i % heads``."""
    k_refs, o_ref = refs[:group], refs[group]
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    ofs = pos_ref[b]
    span = group * block_size
    # the group holds a key some query of the chunk can see, and the
    # sequence is in the dispatch (a table that owns a first block)
    live = (tbl_ref[b, 0] != 0) & (ofs + lq - 1 >= ki * span)

    @pl.when(live)
    def _score():
        q = q_ref[0]                                     # (qt*heads, d)
        k = jnp.concatenate([r[0, 0] for r in k_refs], axis=0) \
            if group > 1 else k_refs[0][0, 0]            # (span, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (qt*heads, span)
        s = jnp.maximum(s, 0.0) * w_ref[0]               # w: (qt*heads, 1)
        if qt == 1:
            s = jnp.sum(s, axis=0, keepdims=True)
        else:
            s = jnp.sum(s.reshape(qt, heads, span), axis=1)
        qpos = ofs + qi * qt + jax.lax.broadcasted_iota(
            jnp.int32, (qt, span), 0)
        kpos = ki * span + jax.lax.broadcasted_iota(
            jnp.int32, (qt, span), 1)
        o_ref[0] = jnp.where(qpos >= kpos, s, -jnp.inf)

    @pl.when(jnp.logical_not(live))
    def _nothing():
        o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)


def dsa_index_scores(q, w, pool, layer, tables, positions, block_size,
                     block_q=512, group=8, interpret=None):
    """The lightning indexer's scores against the PAGED index-key leaf.

    q: ``(B, Lq, Hi, d)`` index queries (query row r of sequence b at
    global position ``positions[b] + r``); w: ``(B, Lq, Hi)`` fp32 head
    weights; pool: the whole stacked ``(L, 1, num_blocks * block_size,
    d)`` leaf, read in place at the static index ``layer``; tables
    ``(B, T)`` int32, positions ``(B,)`` int32.  Returns ``(B, Lq, T *
    block_size)`` fp32: ``sum_j w[j] relu(q[j] . k[s])`` at every
    logical position ``s`` the query can see, ``-inf`` past its
    frontier and for a sequence outside the dispatch."""
    B, Lq, Hi, d = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    layer = int(layer)
    assert pool.ndim == 4 and pool.shape[1] == 1 and pool.shape[3] == d \
        and 0 <= layer < pool.shape[0] and pool.shape[2] % bs == 0
    # whole queries a Q tile (8 of them keep the fp32 score tile at a
    # megabyte: 512 rows x 512 keys)
    qt = divisor_block(Lq, max(1, int(block_q) // Hi))
    group = divisor_block(T, group)
    span = group * bs
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(B)

    kernel = functools.partial(_index_kernel, lq=Lq, heads=Hi, qt=qt,
                               block_size=bs, group=group)
    q_map = lambda b, i, j, *_: (b, i, 0)

    def k_map(g):
        return lambda b, i, j, tbl, *_: (layer, 0, tbl[b, j * group + g],
                                         0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Lq // qt, T // group),
        in_specs=[_spec((1, qt * Hi, d), q_map),
                  _spec((1, qt * Hi, 1), q_map)] + [
            _spec((1, 1, bs, d), k_map(g)) for g in range(group)],
        out_specs=_spec((1, qt, span), lambda b, i, j, *_: (b, i, j)))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Lq, T * bs), jnp.float32),
        interpret=_resolve_interpret(interpret),
        name="dsa_index_scores",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))(
                tbl, pos, q.reshape(B, Lq * Hi, d),
                w.astype(jnp.float32).reshape(B, Lq * Hi, 1),
                *([pool] * group))


def _sequence_rows(pool, layer, tables, positions, bs):
    """The twins' gather: ``(tables, positions)`` as int32 and layer
    ``layer``'s rows of every sequence by LOGICAL position, ``(B, T *
    bs, width)``, through the block-table arithmetic of the kernels."""
    tbl = jnp.asarray(tables, jnp.int32)
    B, T = tbl.shape
    pos = jnp.asarray(positions, jnp.int32).reshape(B)
    idx = (tbl[:, :, None] * bs +
           jnp.arange(bs, dtype=jnp.int32)[None, None, :]).reshape(
               B, T * bs)
    return tbl, pos, jnp.take(pool[int(layer), 0], idx, axis=0)


def dsa_index_scores_reference(q, w, pool, layer, tables, positions,
                               block_size):
    """Dense XLA twin of :func:`dsa_index_scores`: gather layer
    ``layer``'s index keys through the same block-table arithmetic,
    then the plain weighted sum of ReLUs, masked alike."""
    B, Lq, Hi, d = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    tbl, pos, k = _sequence_rows(pool, layer, tables, positions, bs)
    s = jnp.einsum("blhd,bkd->blhk", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jnp.maximum(s, 0.0)
                * w.astype(jnp.float32)[..., None], axis=2)
    qpos = pos[:, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (Lq, T * bs), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, T * bs), 1)
    seen = (qpos >= kpos[None]) & (tbl[:, :1] != 0)[:, :, None]
    return jnp.where(seen, s, -jnp.inf)


# ---------------------------------------------------------------------------
# the selection: an exact threshold, a mask, ascending positions
# ---------------------------------------------------------------------------
_INT_MIN = -2 ** 31


def _keys(scores):
    """fp32 scores as int32 keys of the same order (``-inf`` lowest;
    the scores are sums of products, never NaN)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _threshold(key, k):
    """``(thr, tie)`` ``(R, 1)`` int32 of keys ``(R, S)``: ``thr`` the
    ``k``-th largest key of a row, built from its top bit down (the
    largest value that ``k`` keys reach), and ``tie`` the position such
    that the keys above ``thr`` and the keys AT ``thr`` before ``tie``
    are exactly ``k``.  Counts in fp32 (exact to 2^24 keys a row).  The
    kernel's body and the XLA twin alike."""
    S = key.shape[-1]
    f32 = jnp.float32

    def count(hit):
        return jnp.sum(hit.astype(f32), axis=-1, keepdims=True)

    thr = jnp.where(count(key >= 0) >= k, 0, _INT_MIN).astype(jnp.int32)

    def value_bit(i, thr):
        cand = thr | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(key >= cand) >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 31, value_bit, thr)
    need = k - count(key > thr)
    level = key == thr
    pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    bits = max(1, int(S).bit_length())

    def place_bit(i, tie):
        cand = tie | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count(level & (pos < cand)) <= need, cand, tie)

    tie = jax.lax.fori_loop(0, bits, place_bit, jnp.zeros_like(thr))
    return thr, tie


def _threshold_kernel(s_ref, thr_ref, tie_ref, *, k):
    thr, tie = _threshold(_keys(s_ref[:]), k)
    thr_ref[:] = thr
    tie_ref[:] = tie


def dsa_select_threshold(scores, k, block_rows=8, interpret=None):
    """The exact top-``k`` of every row of ``scores`` ``(R, S)`` fp32
    as two thresholds, ``(thr, tie)`` ``(R, 1)`` int32 each
    (:func:`selected_mask` reads them): a tile of ``block_rows`` rows
    stays in VMEM through all the counting passes."""
    R, S = scores.shape
    br = divisor_block(R, block_rows)
    out = jax.ShapeDtypeStruct((R, 1), jnp.int32)
    row = lambda i: (i, 0)
    return pl.pallas_call(
        functools.partial(_threshold_kernel, k=int(k)),
        grid=(R // br,),
        in_specs=[_spec((br, S), row)],
        out_specs=[_spec((br, 1), row), _spec((br, 1), row)],
        out_shape=[out, out],
        interpret=_resolve_interpret(interpret),
        name="dsa_select_threshold",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)))(scores)


def dsa_select_threshold_reference(scores, k):
    """XLA twin of :func:`dsa_select_threshold`: the same passes over
    the whole array."""
    return _threshold(_keys(scores), int(k))


def selected_mask(scores, thr, tie):
    """Which positions the thresholds keep: ``(..., S)`` bool for
    scores ``(..., S)`` and ``thr``, ``tie`` ``(..., 1)``."""
    key = _keys(scores)
    pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    return (key > thr) | ((key == thr) & (pos < tie))


def compact_positions(mask, k, group=128):
    """The positions of a mask's ones, ascending: ``(R, S)`` bool with
    ``k`` ones a row -> ``(R, k)`` int32.  By counting, in groups of
    ``group`` positions: a group's running count is a product with a
    triangle of ones, the group an output slot falls in a comparison
    with the groups' running totals, the group's row of running counts
    a product with a one-hot, the place in the group a comparison again;
    every count is an integer under 2^8 or summed in fp32, so exact."""
    R, S = mask.shape
    gs = divisor_block(S, group)
    G = S // gs
    f32 = jnp.float32
    m = mask.reshape(R, G, gs).astype(jnp.bfloat16)
    tri = (jnp.arange(gs)[:, None] <= jnp.arange(gs)[None, :]) \
        .astype(jnp.bfloat16)
    run = jnp.einsum("rgi,ij->rgj", m, tri,
                     preferred_element_type=f32)         # in-group count
    per = run[..., -1]                                   # (R, G)
    total = jnp.cumsum(per, axis=1)
    slot = jnp.arange(int(k), dtype=f32)
    before = total[:, None, :] <= slot[None, :, None]    # (R, k, G)
    grp = jnp.sum(before, axis=-1, dtype=jnp.int32)      # the slot's group
    rank = slot[None] - jnp.sum(jnp.where(before, per[:, None, :], 0.0),
                                axis=-1)                 # its place in it
    onehot = (grp[..., None] == jnp.arange(G)[None, None, :]) \
        .astype(jnp.bfloat16)
    mine = jnp.einsum("rkg,rgj->rkj", onehot, run.astype(jnp.bfloat16),
                      preferred_element_type=f32)        # (R, k, gs)
    inside = jnp.sum(mine <= rank[..., None], axis=-1, dtype=jnp.int32)
    return jnp.minimum(grp * gs + inside, S - 1)


def _sparse_kernel(n_ref, q_ref, kv_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, rank, block_k, nk):
    """One (query, tile of its gathered rows) grid cell; the Q tile is
    every head of the query."""
    n = pl.program_id(0)
    j = pl.program_id(1)
    count = n_ref[n]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_k < count)
    def _step():
        q = q_ref[0]                                     # (H, D)
        kv = kv_ref[0]                                   # (block_k, D)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, block_k)
        col = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        seen = col < count
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

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] /
                    jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def dsa_mla_attention(q, rows, counts, rank, scale, block_k=1024,
                      interpret=None):
    """Absorbed-form latent attention of each query over ITS OWN
    gathered rows.

    q: ``(N, H, D)`` — ``[q_abs | q_rope]``, every head of query n;
    rows: ``(N, K, D)`` the latent rows query n selected, of which the
    first ``counts[n]`` (``(N,)`` int32; 0: a query outside the
    dispatch, whose output is zeros) are live.  Returns ``o_lat (N, H,
    rank)``: the softmax-weighted sum of the live rows' first ``rank``
    values."""
    N, H, D = q.shape
    K = rows.shape[1]
    rank = int(rank)
    assert rows.shape == (N, K, D) and 0 < rank <= D
    block_k = divisor_block(K, block_k)
    nk = K // block_k
    counts = jnp.asarray(counts, jnp.int32).reshape(N)

    kernel = functools.partial(_sparse_kernel, scale=float(scale),
                               rank=rank, block_k=block_k, nk=nk)
    q_map = lambda n, j, *_: (n, 0, 0)
    # a tile past the live rows is the last live tile again: Pallas
    # does not fetch the block it already holds
    kv_map = lambda n, j, cnt: (
        n, jnp.minimum(j, jnp.maximum(cnt[n] - 1, 0) // block_k), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, nk),
        in_specs=[_spec((1, H, D), q_map),
                  _spec((1, block_k, D), kv_map)],
        out_specs=_spec((1, H, rank), q_map),
        scratch_shapes=_softmax_scratch(H, rank))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, H, rank), q.dtype),
        interpret=_resolve_interpret(interpret),
        name="dsa_mla_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")))(
                counts, q, rows)


def dsa_mla_attention_reference(q, rows, counts, rank, scale):
    """Dense XLA twin of :func:`dsa_mla_attention`: plain masked
    softmax attention over the gathered rows, the same ``-1e30``
    constant and fp32 accumulation."""
    K = rows.shape[1]
    counts = jnp.asarray(counts, jnp.int32).reshape(-1)
    s = jnp.einsum("nhd,nkd->nhk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(K, dtype=jnp.int32)[None] < counts[:, None])
    s = jnp.where(seen[:, None], s, _NEG)
    p = jnp.where(seen[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("nhk,nkd->nhd", p.astype(rows.dtype),
                      rows[..., :int(rank)],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _masked_kernel(tbl_ref, pos_ref, q_ref, s_ref, thr_ref, tie_ref, *refs,
                   scale, rank, lq, heads_q, block_size, group, nk):
    """``mla_attention._mla_kernel``'s grid cell under the selection's
    mask.  The Q tile holds ``heads_q`` heads of all ``lq`` queries of
    the chunk (row ``i`` is head ``i // lq``, query ``i % lq``); the
    mask of a (query, key) is the same for every head."""
    kv_refs = refs[:group]
    o_ref, m_ref, l_ref, acc_ref = refs[group:]
    b = pl.program_id(0)
    ki = pl.program_id(2)
    ofs = pos_ref[b]
    span = group * block_size

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when((tbl_ref[b, 0] != 0) & (ofs + lq - 1 >= ki * span))
    def _step():
        q = q_ref[0]                                     # (BQ, D)
        kv = jnp.concatenate([r[0, 0] for r in kv_refs], axis=0) \
            if group > 1 else kv_refs[0][0, 0]           # (span, D)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, span)
        key = _keys(s_ref[0])                            # (lq, span)
        kpos = ki * span + jax.lax.broadcasted_iota(
            jnp.int32, (lq, span), 1)
        qpos = ofs + jax.lax.broadcasted_iota(jnp.int32, (lq, span), 0)
        thr, tie = thr_ref[0], tie_ref[0]                # (lq, 1)
        seen = (qpos >= kpos) & (
            (key > thr) | ((key == thr) & (kpos < tie)))
        if heads_q > 1:
            seen = jnp.concatenate([seen] * heads_q, axis=0)
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


def dsa_mla_attention_masked(q, pool, layer, tables, positions, scores,
                             thr, tie, block_size, rank, scale,
                             block_q=512, group=8, interpret=None):
    """Absorbed-form latent attention against the PAGED latent pool
    (``mla_paged_attention``'s operands and walk) over the positions
    the selection kept: ``scores`` ``(B, Lq, T * block_size)`` fp32 by
    logical position and the thresholds ``thr``, ``tie`` ``(B, Lq, 1)``
    int32 of :func:`dsa_select_threshold`.  Returns ``o_lat (B, H, Lq,
    rank)``."""
    B, H, Lq, D = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    layer, rank = int(layer), int(rank)
    assert pool.ndim == 4 and pool.shape[1] == 1 and pool.shape[3] == D \
        and 0 <= layer < pool.shape[0] and pool.shape[2] % bs == 0
    # whole heads a Q tile, so that the mask of the chunk's queries
    # repeats down its rows
    heads_q = divisor_block(H, max(1, int(block_q) // Lq))
    bq = heads_q * Lq
    group = divisor_block(T, group)
    nk = T // group
    span = group * bs
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(B)

    kernel = functools.partial(
        _masked_kernel, scale=float(scale), rank=rank, lq=Lq,
        heads_q=heads_q, block_size=bs, group=group, nk=nk)
    q_map = lambda b, i, j, *_: (b, i, 0)
    one = lambda b, i, j, *_: (b, 0, 0)

    def kv_map(g):
        return lambda b, i, j, tbl, *_: (layer, 0, tbl[b, j * group + g],
                                         0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // heads_q, nk),
        in_specs=[_spec((1, bq, D), q_map),
                  _spec((1, Lq, span), lambda b, i, j, *_: (b, 0, j)),
                  _spec((1, Lq, 1), one), _spec((1, Lq, 1), one)] + [
            _spec((1, 1, bs, D), kv_map(g)) for g in range(group)],
        out_specs=_spec((1, bq, rank), q_map),
        scratch_shapes=_softmax_scratch(bq, rank))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H * Lq, rank), q.dtype),
        interpret=_resolve_interpret(interpret),
        name="dsa_mla_attention_masked",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))(
                tbl, pos, q.reshape(B, H * Lq, D), scores,
                thr.reshape(B, Lq, 1), tie.reshape(B, Lq, 1),
                *([pool] * group))
    return out.reshape(B, H, Lq, rank)


def dsa_mla_attention_masked_reference(q, pool, layer, tables, positions,
                                       scores, thr, tie, block_size, rank,
                                       scale):
    """Dense XLA twin of :func:`dsa_mla_attention_masked`:
    ``mla_attention_reference`` with the selection's mask beside the
    causal one."""
    B, H, Lq, D = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    _, pos, kv = _sequence_rows(pool, layer, tables, positions, bs)
    s = jnp.einsum("bhqd,bkd->bhqk", q, kv,
                   preferred_element_type=jnp.float32) * scale
    qpos = pos[:, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (Lq, T * bs), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, T * bs), 1)
    seen = (qpos >= kpos[None]) & selected_mask(
        scores, thr.reshape(B, Lq, 1), tie.reshape(B, Lq, 1))
    s = jnp.where(seen[:, None], s, _NEG)
    p = jnp.where(seen[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("bhqk,bkd->bhqd", p.astype(kv.dtype),
                      kv[..., :int(rank)],
                      preferred_element_type=jnp.float32).astype(q.dtype)
