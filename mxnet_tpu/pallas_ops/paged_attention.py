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


def _paged_kernel(*refs, scale, block_q, block_size, nk, int8, lq,
                  heads, group, own_v, window, nt):
    """One (batch, pool head, q-block, group of logical blocks) grid
    cell.

    ``tbl_ref``/``pos_ref`` are the scalar-prefetch operands (SMEM);
    the k dimension walks logical blocks, ``group`` of them a step —
    the index maps already dereferenced ``tbl_ref[b, j]``, so the
    ``group`` key refs (and, with ``own_v``, as many value refs) hold
    PHYSICAL tiles.  Masking happens in logical position space.  The Q
    tile holds the ``heads`` query heads that share this pool head,
    flattened: row ``i`` is query ``i % lq`` of head ``i // lq``.

    ``int8`` adds two more scalar-prefetch operands — per-(head,
    physical block) fp32 absmax scales for the K and V pools — and the
    tile loads dequantize on-tile (``codes * sk_ref[h, tbl_ref[b, j]]``)
    before the unchanged fp32 online softmax.

    ``window`` (static; None: every key at or before the query): a
    query at ``p`` sees keys ``p - window + 1 .. p``.  The k dimension
    then walks only the groups a dispatch row's queries can see, from
    group :func:`_first_group` of its frontier on (the index maps took
    the same offset), and masks the keys behind the window inside
    them; ``nt`` is the table's width, which the walk may overshoot by
    a group whose keys are all past the frontier."""
    if int8:
        tbl_ref, pos_ref, sk_ref, sv_ref, q_ref = refs[:5]
    else:
        tbl_ref, pos_ref, q_ref = refs[:3]
        sk_ref = sv_ref = None
    tiles = refs[5 if int8 else 3:-4]
    k_refs = tiles[:group]
    v_refs = tiles[group:] if own_v else k_refs   # a row is key AND value
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    ofs = pos_ref[b]
    span = group * block_size
    # the logical group this grid step holds
    kg = ki if window is None else _first_group(ofs, window, span) + ki
    # multiply in the tiles' own dtype where query and pool share it
    # (bfloat16 both: one MXU pass), else in fp32 as the dense twin does
    cdt = q_ref.dtype if not int8 and q_ref.dtype == k_refs[0].dtype \
        else jnp.float32

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # dynamic skip: the group contributes iff the tile's last query row
    # can see its first key position (kg * span).  A tile inside one
    # head ends at query qi*block_q % lq + block_q - 1; one that spans
    # heads holds the chunk's last query, lq - 1
    if heads == 1:
        last = qi * block_q + block_q - 1
    elif lq % block_q == 0:
        last = jax.lax.rem(qi * block_q, lq) + block_q - 1
    else:
        last = lq - 1
    run = ofs + last >= kg * span

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(cdt)             # (BQ, D)

        def tile(refs_, s_ref):
            out = []
            for g, ref in enumerate(refs_):
                t = ref[0, 0].astype(cdt)       # (BS, D)
                if int8:                        # SMEM scalar reads
                    t = t * s_ref[h, tbl_ref[b, _entry(kg, group, g,
                                                       window, nt)]]
                out.append(t)
            return out[0] if group == 1 else jnp.concatenate(out, axis=0)

        kb = tile(k_refs, sk_ref)               # (span, D)
        vb = tile(v_refs, sv_ref) if own_v else kb
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, span)
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, span), 0)
        qpos = ofs + (row if heads == 1 else jax.lax.rem(row, lq))
        kpos = kg * span + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, span), 1)
        seen = qpos >= kpos
        if window is not None:
            seen &= kpos > qpos - window
        s = jnp.where(seen, s, _NEG)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(seen, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p.astype(cdt), vb, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:]
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def _first_group(ofs, window, span):
    """The first group of ``span`` keys that a dispatch row whose first
    query sits at ``ofs`` can see through ``window``."""
    return jnp.maximum(ofs - (window - 1), 0) // span


def _entry(kg, group, g, window, nt):
    """The table entry of block ``g`` of logical group ``kg``; a walk
    that starts at the window may run past the table's last entry, to
    keys no query sees."""
    j = kg * group + g
    return j if window is None else jnp.minimum(j, nt - 1)


def flash_attention_paged(q, k_pool, v_pool, layer, tables, positions,
                          block_size, scale=None, block_q=128,
                          interpret=None, kv_scales=None, group=1,
                          window=None, name="paged_attention"):
    """Offset-causal flash attention against a PAGED KV pool.

    q: (B, H, Lq, D) — query row r of sequence b sits at global
    position ``positions[b] + r``; k_pool/v_pool: the whole stacked
    ``(L, Hp, num_blocks * block_size, D)`` global pools, read in place
    at the static index ``layer``; tables: (B, T) int32 per-sequence
    block tables mapping logical block j to a physical pool block
    (entries past a sequence's frontier must point at a valid block —
    conventionally the reserved trash block 0 — their keys are masked
    either way); positions: (B,) int32 frontiers.

    GROUPED-QUERY heads: the pool may hold fewer heads than the query,
    ``Hp`` dividing ``H``; query head i attends pool head ``i // (H //
    Hp)``, and the ``H // Hp`` query heads of a pool head sit in ONE Q
    tile, so a K/V tile is fetched once for all of them.  ``v_pool=
    None``: a pool row is key AND value (``score = q . row``, ``out =
    softmax . row``) — a model whose head is narrower than a 128-lane
    tile keeps ``[K | V]`` side by side in one row, hands a query that
    is zero over the value half and takes the value half of the result
    (``models/lfm2_moe.py``); each tile is then fetched once, not
    twice.  ``group`` consecutive table entries make one grid step (a
    divisor of T is taken): the pool is handed to the call that many
    times, each operand with its own index map.

    ``kv_scales`` — a ``(scale_k, scale_v)`` pair of ``(L, H,
    num_blocks)`` fp32 per-(layer, head, physical block) absmax scales
    — selects the int8 pool layout: the pools hold int8 codes and every
    K/V tile is dequantized ON-TILE (``codes * scale[layer, h,
    tbl[b, j]]``; the layer's scales are what rides in SMEM) before
    the unchanged fp32 online softmax, so accumulation numerics match
    the dense twin exactly on identically-dequantized values.

    ``window`` (a static int; None is the causal frontier alone): the
    query at ``p`` sees keys ``p - window + 1 .. p``, masked in logical
    position space.  Blocks wholly behind the window are skipped as
    blocks past the frontier are, and more cheaply: the grid's k
    dimension has only the ``(window + Lq - 2) // (group * block_size)
    + 2`` groups a row's queries can reach, and starts at the group
    that holds the first query's oldest visible key (read from the
    row's frontier in the index maps), so table entries behind it are
    never dereferenced and may point anywhere (the engine leaves them
    at the trash block 0 once it has released them).  ``name`` is the
    call's name in a trace: a model's window layers give their own.

    The tables/positions ride as scalar-prefetch operands so BlockSpec
    index maps can gather physical tiles; blocks a sequence cannot see
    are skipped dynamically like ``flash_attention_offset``.
    Forward-only."""
    B, H, Lq, D = q.shape
    T = tables.shape[1]
    bs = int(block_size)
    layer = int(layer)
    own_v = v_pool is not None
    Hp = k_pool.shape[1]
    assert k_pool.ndim == 4 and H % Hp == 0 and k_pool.shape[3] == D \
        and 0 <= layer < k_pool.shape[0] \
        and (not own_v or k_pool.shape == v_pool.shape)
    assert k_pool.shape[2] % bs == 0, \
        "pool length must be a multiple of block_size"
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    heads = H // Hp
    rows = heads * Lq
    block_q = divisor_block(rows, block_q)
    group = divisor_block(T, group)
    nk = T // group
    if window is not None:
        window = int(window)
        assert window >= 1
        nk = min(nk, (window + Lq - 2) // (group * bs) + 2)
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32).reshape(B)
    int8 = kv_scales is not None

    kernel = functools.partial(_paged_kernel, scale=float(scale),
                               block_q=block_q, block_size=bs, nk=nk,
                               int8=int8, lq=Lq, heads=heads,
                               group=group, own_v=own_v, window=window,
                               nt=T)

    if int8:
        sk = jnp.asarray(kv_scales[0][layer], jnp.float32)
        sv = jnp.asarray(kv_scales[1][layer], jnp.float32)
        scalars = (tbl, pos, sk, sv)
    else:
        scalars = (tbl, pos)
    # the scalar operands stay first, tables then positions: a trace's
    # readers know the kernel by them (benchmark/layer_metrics/)
    q_map = lambda b, h, i, j, *_: (b, h, i, 0)

    def kv_map(g):
        if window is None:
            return lambda b, h, i, j, tbl, *_: (
                layer, h, tbl[b, j * group + g], 0)
        return lambda b, h, i, j, tbl, pos, *_: (
            layer, h, tbl[b, _entry(
                _first_group(pos[b], window, group * bs) + j, group, g,
                window, T)], 0)

    # k/v: fetch PHYSICAL block tbl[b, j * group + g] of this layer
    # from the stacked pool — the index is in units of whole (bs, D)
    # blocks
    pools = [k_pool] * group + ([v_pool] * group if own_v else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, Hp, rows // block_q, nk),
        in_specs=[_spec((1, 1, block_q, D), q_map)] + [
            _spec((1, 1, bs, D), kv_map(g))
            for g in list(range(group)) * (2 if own_v else 1)],
        out_specs=_spec((1, 1, block_q, D), q_map),
        scratch_shapes=_softmax_scratch(block_q, D))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hp, rows, D), q.dtype),
        interpret=_resolve_interpret(interpret),
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")))(
                                     *scalars, q.reshape(B, Hp, rows, D),
                                     *pools)
    return out.reshape(B, H, Lq, D)


def paged_attention_reference(q, k_pool, v_pool, layer, tables,
                              positions, block_size, scale=None,
                              kv_scales=None, window=None):
    """Dense XLA twin of :func:`flash_attention_paged`: gather layer
    ``layer``'s pool rows through the same block-table arithmetic, then
    the exact dense offset-causal attention (same ``-1e30`` constant,
    fp32 accumulation) — the ``MXNET_PALLAS=0`` lowering and the parity
    oracle.  ``kv_scales`` dequantizes int8 pools through the SAME
    per-(layer, head, physical block) scale arithmetic as the kernel;
    fewer pool heads than query heads are repeated for their group,
    ``v_pool=None`` reads a row as key and value alike, and ``window``
    masks the keys at or before ``query position - window``."""
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
    v = k if v_pool is None else \
        jnp.transpose(jnp.take(v_pool[layer], idx, axis=1), (1, 0, 2, 3))
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
    heads = H // k.shape[1]
    if heads > 1:
        k, v = jnp.repeat(k, heads, axis=1), jnp.repeat(v, heads, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, T * bs), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (Lq, T * bs), 1)
    qglob = pos[:, None, None] + qpos
    seen = qglob >= kpos[None]
    if window is not None:
        seen &= kpos[None] > qglob - int(window)
    s = jnp.where(seen[:, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
