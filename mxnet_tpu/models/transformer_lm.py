"""Decoder-only transformer LM as a SYMBOL graph — the train-tier
headline for the Pallas kernel plane.

The reference model zoo stops at LSTMs (its attention era hadn't
happened); this is the workload that exercises every hot-op kernel
end-to-end through the classic ``Module``/``DataParallelTrainer``
machinery: causal ``DotProductAttention`` (the flash kernel), ``RMSNorm``
on both block norms, ``LayerNorm`` on the final norm, and a
``SoftmaxOutput`` loss head — each routed through the Pallas dispatch
seam when eligible (``MXNET_PALLAS``), each falling back to the plain
XLA lowering bit-for-bit when not (docs/architecture/pallas_kernels.md).

Pre-norm blocks, learned projections without biases on q/k/v/proj (the
standard decoder recipe), ReLU FFN at 4x width.  ``data`` is a
``(batch, seq_len)`` integer token grid, ``softmax_label`` its
next-token targets of the same shape.
"""
from .. import symbol as sym
from .paged import pool_write, write_plan as _write_plan

__all__ = ["get_symbol", "lm_spec", "random_params", "init_cache",
           "init_pool", "init_scale_pool", "prefill_apply",
           "decode_apply", "paged_step_apply", "quantize_lm_params",
           "lm_matmul_weights", "serving_spec", "required_params",
           "pack_params", "quantize_params", "paged_step", "OFFERS",
           "AUX_COUNTERS"]


def _attention_block(x, seq_len, num_hidden, num_heads, name):
    """Pre-norm causal self-attention with residual. x: (B, L, D)."""
    head_dim = num_hidden // num_heads
    a = sym.RMSNorm(x, name=name + "_ln1")
    a2 = sym.Reshape(a, shape=(-1, num_hidden))

    def heads(t, tag):
        proj = sym.FullyConnected(t, num_hidden=num_hidden, no_bias=True,
                                  name="%s_%s" % (name, tag))
        h = sym.Reshape(proj, shape=(-1, seq_len, num_heads, head_dim))
        return sym.transpose(h, axes=(0, 2, 1, 3))   # (B, H, L, dh)

    att = sym.DotProductAttention(heads(a2, "q"), heads(a2, "k"),
                                  heads(a2, "v"), causal=True,
                                  name=name + "_attn")
    att = sym.Reshape(sym.transpose(att, axes=(0, 2, 1, 3)),
                      shape=(-1, num_hidden))
    proj = sym.FullyConnected(att, num_hidden=num_hidden, no_bias=True,
                              name=name + "_proj")
    return x + sym.Reshape(proj, shape=(-1, seq_len, num_hidden))


def _ffn_block(x, seq_len, num_hidden, name):
    """Pre-norm ReLU FFN (4x) with residual."""
    f = sym.RMSNorm(x, name=name + "_ln2")
    f = sym.Reshape(f, shape=(-1, num_hidden))
    f = sym.FullyConnected(f, num_hidden=4 * num_hidden,
                           name=name + "_ffn1")
    f = sym.Activation(f, act_type="relu")
    f = sym.FullyConnected(f, num_hidden=num_hidden, name=name + "_ffn2")
    return x + sym.Reshape(f, shape=(-1, seq_len, num_hidden))


def get_symbol(seq_len, num_layers=2, num_hidden=64, num_heads=4,
               vocab_size=256, **kwargs):
    """Causal transformer LM symbol for one sequence length.

    data: (batch, seq_len) token ids; softmax_label: (batch, seq_len)
    next-token ids.  Loss head: SoftmaxOutput over the flattened
    (batch*seq_len, vocab) logits."""
    if num_hidden % num_heads:
        raise ValueError("num_hidden %d must divide into num_heads %d"
                         % (num_hidden, num_heads))
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=num_hidden,
                      name="embed")
    for i in range(num_layers):
        name = "blk%d" % i
        x = _attention_block(x, seq_len, num_hidden, num_heads, name)
        x = _ffn_block(x, seq_len, num_hidden, name)
    h = sym.LayerNorm(x, name="final_ln")
    logits = sym.FullyConnected(sym.Reshape(h, shape=(-1, num_hidden)),
                                num_hidden=vocab_size, name="pred")
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,)),
                             name="softmax")


# ---------------------------------------------------------------------------
# Decode-mode graphs: the SAME trained weights (the symbol graph's
# argument names), applied incrementally against a KV cache.
#
# The symbol graph above is one-shot: a (B, seq_len) grid in, all
# positions out, every token re-paying attention over the whole prefix.
# Autoregressive serving needs the split form — ``prefill_apply`` runs
# the prompt once and fills the cache, ``decode_apply`` consumes ONE
# token per sequence against it — as pure jax functions the serving
# program store can AOT-compile with the cache donated.  Numerics reuse
# the op registry's own lowerings (``_rms_fc``/``_ln_fc`` and the
# ``sdp_attention`` door), so the decode path routes through the same
# Pallas dispatch seam as the symbol graph and a T-step decode loop
# reproduces the one-shot forward's per-position logits (pinned by
# tests/test_decode_engine.py).
# ---------------------------------------------------------------------------
def lm_spec(num_layers=2, num_hidden=64, num_heads=4, vocab_size=256):
    """Validated architecture spec consumed by the decode-mode graphs
    (``seq_len`` is a property of the *call*, not the weights)."""
    if num_hidden % num_heads:
        raise ValueError("num_hidden %d must divide into num_heads %d"
                         % (num_hidden, num_heads))
    return {"num_layers": int(num_layers), "num_hidden": int(num_hidden),
            "num_heads": int(num_heads), "vocab_size": int(vocab_size)}


def random_params(spec, seed=0, scale=0.1):
    """Seeded random weights with the symbol graph's exact argument
    names/shapes (via ``get_symbol`` + ``infer_shape``) — the shared
    protocol model of the decode tests and bench rows."""
    import numpy as np
    net = get_symbol(seq_len=8, **spec)
    shapes, _, _ = net.infer_shape(data=(1, 8), softmax_label=(1, 8))
    rs = np.random.RandomState(seed)
    return {name: np.asarray(rs.uniform(-scale, scale, shape),
                             np.float32)
            for name, shape in zip(net.list_arguments(), shapes)
            if name not in ("data", "softmax_label")}


def init_cache(spec, batch, cache_len, dtype="float32"):
    """Zeroed stacked KV cache pair, each of shape
    ``(num_layers, batch, num_heads, cache_len, head_dim)``."""
    import jax.numpy as jnp
    dh = spec["num_hidden"] // spec["num_heads"]
    shape = (spec["num_layers"], batch, spec["num_heads"],
             int(cache_len), dh)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_pool(spec, num_blocks, block_size, dtype="float32"):
    """Zeroed paged KV pool pair, each of shape ``(num_layers,
    num_heads, num_blocks * block_size, head_dim)`` — one GLOBAL pool
    shared by every sequence, addressed through per-sequence block
    tables (:func:`paged_step_apply`).  Block 0 is conventionally the
    reserved trash block: unused table entries point at it (its keys
    are masked as future positions), no real table entry does."""
    import jax.numpy as jnp
    dh = spec["num_hidden"] // spec["num_heads"]
    shape = (spec["num_layers"], spec["num_heads"],
             int(num_blocks) * int(block_size), dh)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_scale_pool(spec, num_blocks):
    """Per-(layer, head, physical block) fp32 absmax scale pools for
    the int8 paged KV plane — a ``(num_layers, num_heads, num_blocks)``
    pair of ones, carried as donated state beside the int8 code pools
    of :func:`init_pool`.  Ones match the ``quantize_int8`` empty-block
    convention (absmax 0 → scale 1.0), and zero codes dequantize to
    zero under any scale."""
    import jax.numpy as jnp
    shape = (spec["num_layers"], spec["num_heads"], int(num_blocks))
    return (jnp.ones(shape, jnp.float32), jnp.ones(shape, jnp.float32))


def lm_matmul_weights(spec):
    """The 2D matmul weights of the LM argument set — the params int8
    weight-only serving quantizes (norm scales and biases stay fp32:
    they are a rounding error of the footprint and the numerics care)."""
    names = ["embed_weight", "pred_weight"]
    for i in range(spec["num_layers"]):
        names += ["blk%d_%s" % (i, k) for k in
                  ("q_weight", "k_weight", "v_weight", "proj_weight",
                   "ffn1_weight", "ffn2_weight")]
    return names


def quantize_lm_params(params, spec, granularity=None):
    """int8 weight-only transform of an LM param dict: every matmul
    weight (:func:`lm_matmul_weights`) becomes a
    :class:`~..pallas_ops.dequant_matmul.QuantizedWeight`; everything
    else passes through untouched.  Pure — the input dict is not
    mutated."""
    from ..pallas_ops.dequant_matmul import QuantizedWeight, quantize_int8
    quant = set(lm_matmul_weights(spec))
    out = {}
    for k, v in params.items():
        if k in quant:
            codes, scales = quantize_int8(v, granularity)
            out[k] = QuantizedWeight(codes, scales)
        else:
            out[k] = v
    return out


def _block_params(params, i):
    p = {k: params["blk%d_%s" % (i, k)] for k in
         ("ln1_gamma", "q_weight", "k_weight", "v_weight", "proj_weight",
          "ln2_gamma", "ffn1_weight", "ffn1_bias", "ffn2_weight",
          "ffn2_bias")}
    return p


def _mm(x2d, w):
    """``x @ w^T`` with int8 weight-only routing: a QuantizedWeight
    dequantizes inside the matmul (fused kernel or its dense XLA twin,
    per the dispatch seam); a plain array is one MXU matmul."""
    import jax.numpy as jnp
    from ..pallas_ops.dequant_matmul import QuantizedWeight, dequant_matmul
    if isinstance(w, QuantizedWeight):
        return dequant_matmul(x2d, w.codes, w.scales)
    return jnp.matmul(x2d, w.T)


def _embed(w, tokens):
    """Embedding gather with int8 routing: quantized rows are gathered
    as codes and dequantized per row (exact — the per-row scale rides
    the same gather)."""
    import jax.numpy as jnp
    from ..pallas_ops.dequant_matmul import QuantizedWeight
    ids = tokens.astype(jnp.int32)
    if isinstance(w, QuantizedWeight):
        rows = jnp.take(w.codes, ids, axis=0).astype(jnp.float32)
        scales = jnp.broadcast_to(
            jnp.asarray(w.scales, jnp.float32).reshape(-1),
            (w.codes.shape[0],))
        return rows * jnp.take(scales, ids, axis=0)[..., None]
    return jnp.take(w, ids, axis=0)


def _ffn(x2d, bp):
    import jax.numpy as jnp
    f = _mm(x2d, bp["ffn1_weight"]) + bp["ffn1_bias"]
    f = jnp.maximum(f, 0)
    return _mm(f, bp["ffn2_weight"]) + bp["ffn2_bias"]


def prefill_apply(params, tokens, lengths, cache_len, spec,
                  cache_dtype="float32"):
    """Run a padded prompt batch once and fill the KV cache.

    tokens: (B, P) int32, zero-padded past each sequence's ``lengths``;
    lengths: (B,) int32 true prompt lengths (1 <= lengths <= P).
    Returns ``(logits, k_cache, v_cache)`` — logits (B, P, vocab) fp32
    for every position (callers gather position ``lengths-1`` for the
    first generated token), caches ``(L, B, H, cache_len, head_dim)``
    of ``cache_dtype`` (``'bfloat16'`` halves the resident cache;
    attention inside the prefill itself still reads the full-precision
    K/V) holding K/V for positions 0..P-1 and zeros past P.  Pad
    positions DO write junk K/V inside 0..P-1 for rows shorter than P,
    but no real query ever attends past its own position (causal), and
    decode steps overwrite slots from ``lengths`` on — the
    offset-causal mask keeps them invisible throughout (pinned).

    Params may be bf16 (compute follows them; logits return fp32) or
    int8 :class:`QuantizedWeight` pairs (matmuls dequantize in-program).
    """
    import jax.numpy as jnp
    from ..ops.attention import sdp_attention
    from ..ops.nn import _ln_fc, _rms_fc

    L, D = spec["num_layers"], spec["num_hidden"]
    H = spec["num_heads"]
    dh = D // H
    cdt = jnp.dtype(cache_dtype)
    B, P = tokens.shape
    x = _embed(params["embed_weight"], tokens)              # (B, P, D)
    ks, vs = [], []
    for i in range(L):
        bp = _block_params(params, i)
        a = _rms_fc({"eps": 1e-6}, x, bp["ln1_gamma"])
        a2 = a.reshape(-1, D)

        def heads(w):
            h = _mm(a2, w).reshape(B, P, H, dh)
            return jnp.transpose(h, (0, 2, 1, 3))           # (B, H, P, dh)

        q, k, v = (heads(bp[t]) for t in
                   ("q_weight", "k_weight", "v_weight"))
        pad = ((0, 0), (0, 0), (0, int(cache_len) - P), (0, 0))
        ks.append(jnp.pad(k.astype(cdt), pad))
        vs.append(jnp.pad(v.astype(cdt), pad))
        att = sdp_attention(q, k, v, causal=True)
        att = jnp.transpose(att, (0, 2, 1, 3)).reshape(-1, D)
        x = x + _mm(att, bp["proj_weight"]).reshape(B, P, D)
        f = _rms_fc({"eps": 1e-6}, x, bp["ln2_gamma"]).reshape(-1, D)
        x = x + _ffn(f, bp).reshape(B, P, D)
    h = _ln_fc({"axis": -1, "eps": 1e-5}, x, params["final_ln_gamma"],
               params["final_ln_beta"])
    logits = (_mm(h.reshape(-1, D), params["pred_weight"]) +
              params["pred_bias"]).reshape(B, P, spec["vocab_size"])
    return (logits.astype(jnp.float32), jnp.stack(ks), jnp.stack(vs))


def decode_apply(params, cache_k, cache_v, tokens, lengths, spec):
    """One decode step: embed one token per sequence, write its K/V at
    each sequence's cache frontier, attend offset-causally over the
    cache, and emit next-token logits.

    tokens: (B,) int32 (the previously sampled token per sequence);
    lengths: (B,) int32 cache frontiers (the new token's position —
    must be < cache_len); caches as from :func:`prefill_apply` /
    :func:`init_cache` (their dtype is the cache dtype — the fresh
    K/V write casts to it, attention reads it back; the flash kernel
    and its dense twin both accumulate fp32 regardless).  Returns
    ``(logits (B, vocab) fp32, new_k, new_v)``.  Params may be bf16 or
    int8 ``QuantizedWeight`` pairs like :func:`prefill_apply`.  Callers
    AOT-compile this with both caches DONATED, so the update is an
    in-place ``dynamic_update_slice`` on the one device-resident
    copy."""
    import jax
    import jax.numpy as jnp
    from ..ops.attention import sdp_attention
    from ..ops.nn import _ln_fc, _rms_fc

    L, D = spec["num_layers"], spec["num_hidden"]
    H = spec["num_heads"]
    dh = D // H
    B = tokens.shape[0]
    cdt = cache_k.dtype
    lengths = jnp.asarray(lengths, jnp.int32)
    x = _embed(params["embed_weight"], tokens)              # (B, D)
    for i in range(L):
        bp = _block_params(params, i)
        a = _rms_fc({"eps": 1e-6}, x, bp["ln1_gamma"])

        def heads(w):
            return _mm(a, w).reshape(B, H, 1, dh)

        q, k, v = (heads(bp[t]) for t in
                   ("q_weight", "k_weight", "v_weight"))

        def write(cache_b, kv_b, l_b):
            # cache_b (H, C, dh), kv_b (H, 1, dh): in-place when donated
            return jax.lax.dynamic_update_slice(cache_b, kv_b,
                                                (0, l_b, 0))

        cache_k = cache_k.at[i].set(jax.vmap(write)(cache_k[i],
                                                    k.astype(cdt),
                                                    lengths))
        cache_v = cache_v.at[i].set(jax.vmap(write)(cache_v[i],
                                                    v.astype(cdt),
                                                    lengths))
        att = sdp_attention(q.astype(cdt), cache_k[i], cache_v[i],
                            q_offsets=lengths)              # (B, H, 1, dh)
        att = jnp.transpose(att, (0, 2, 1, 3)).reshape(B, D)
        x = x + _mm(att.astype(x.dtype), bp["proj_weight"])
        f = _rms_fc({"eps": 1e-6}, x, bp["ln2_gamma"])
        x = x + _ffn(f, bp)
    h = _ln_fc({"axis": -1, "eps": 1e-5}, x, params["final_ln_gamma"],
               params["final_ln_beta"])
    logits = _mm(h, params["pred_weight"]) + params["pred_bias"]
    return logits.astype(jnp.float32), cache_k, cache_v


def _pool_write(pool_k, pool_v, layer, k, v, plan, block_size):
    """``paged.pool_write`` over the ``(k, v)`` pool pair."""
    return pool_write((pool_k, pool_v), layer, (k, v), plan, block_size)


def paged_step_apply(params, pool_k, pool_v, tables, tokens, positions,
                     valid, spec, block_size, scales=None,
                     all_logits=False):
    """One PAGED step — the unified prefill-chunk/decode graph of the
    paged KV plane (docs/architecture/decode_engine.md).

    tokens: (B, Lq) int32 — ``Lq`` tokens per sequence (a prefill chunk;
    ``Lq=1`` is a decode step); positions: (B,) int32 — global position
    of ``tokens[:, 0]`` (row r sits at ``positions[b] + r``); valid:
    (B,) int32 — rows ``r < valid[b]`` are real (``1 <= valid <= Lq``;
    rows past it are pad); tables: (B, T) int32 per-sequence block
    tables over the global pools (``(L, H, num_blocks * block_size,
    dh)``, :func:`init_pool`); table entries past a sequence's frontier
    must point at a VALID pool block — conventionally the reserved
    trash block 0.

    Each layer writes the chunk's K/V to pool rows ``tables[b, p //
    bs] * bs + p % bs`` (:func:`_pool_write`; pad rows are written
    nowhere) and attends through the ``sdp_attention_paged`` door,
    which takes the whole pool and the layer — so intra-chunk causality
    and pad invisibility both come from the one offset-causal mask, and
    a DONATED pool is addressed in place from the program's entry to
    its exit: no instruction copies, relays or slices it.  Returns
    ``(logits (B, vocab) fp32 at each row's LAST VALID position, pool_k,
    pool_v)``.  Rows whose table is all zeros (non-participating slots
    in a fused dispatch) reach only the trash block and yield garbage
    logits — callers discard them.  Params may be bf16 or int8
    ``QuantizedWeight`` pairs like :func:`prefill_apply`.

    ``scales`` — a ``(scale_k, scale_v)`` pair from
    :func:`init_scale_pool` — selects the INT8 pool layout: the pools
    hold int8 codes with per-(layer, head, physical block) fp32 absmax
    scales, the cache update becomes a block requantization (dequantize
    each affected block, overlay the fresh fp32 rows, re-pick its
    absmax scale, re-encode — pure JAX, shared verbatim by the kernel
    and dense-twin routes), attention dequantizes through the
    ``kv_scales`` door, and the return gains the updated scale pools:
    ``(logits, pool_k, pool_v, scale_k, scale_v)``.  Affected blocks
    must be uniquely owned by their row (the engine's copy-on-write
    write-ready pass guarantees it); trash-block collisions between pad
    rows are harmless garbage.

    ``all_logits=True`` returns logits for EVERY chunk row —
    ``(B, Lq, vocab)`` fp32 — instead of only the last valid position
    (the speculative-verify program reads all K+1 positions)."""
    import jax.numpy as jnp
    from ..ops.attention import sdp_attention_paged
    from ..ops.nn import _ln_fc, _rms_fc

    L, D = spec["num_layers"], spec["num_hidden"]
    H = spec["num_heads"]
    dh = D // H
    bs = int(block_size)
    B, Lq = tokens.shape
    cdt = pool_k.dtype
    tables = jnp.asarray(tables, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    int8_kv = scales is not None
    if not int8_kv:
        plan = _write_plan(tables, positions, valid, Lq, bs)
    else:
        scale_k, scale_v = scales
        T = tables.shape[1]
        r = jnp.arange(Lq, dtype=jnp.int32)
        p = positions[:, None] + r[None, :]                 # (B, Lq)
        # static bound on blocks a row's write can touch: worst case
        # the chunk starts on a block's last row
        A = (Lq + bs - 2) // bs + 1
        first_log = positions // bs                         # (B,)
        aff_log = first_log[:, None] + \
            jnp.arange(A, dtype=jnp.int32)[None, :]
        last_log = (positions + valid - 1) // bs
        aff_ok = (aff_log <= last_log[:, None]) & (aff_log < T)
        phys = jnp.where(
            aff_ok,
            tables[jnp.arange(B)[:, None], jnp.clip(aff_log, 0, T - 1)],
            0)                                              # (B, A)
        phys_flat = phys.reshape(-1)                        # (B*A,)
        ws_rows = (phys_flat[:, None] * bs +
                   jnp.arange(bs, dtype=jnp.int32)[None, :]).reshape(-1)
        # overlay index of fresh token (b, r) inside the gathered
        # working set; pad rows target the appended dummy row
        loc = (jnp.arange(B, dtype=jnp.int32)[:, None] * (A * bs)
               + (p // bs - first_log[:, None]) * bs + p % bs)
        loc = jnp.where(r[None, :] < valid[:, None], loc,
                        B * A * bs).reshape(-1)             # (B*Lq,)

        def requant_write(pool_i, scale_i, fresh):
            """Requantize the affected blocks of one layer's pool.
            pool_i (H, R, dh) int8 codes, scale_i (H, NB) fp32, fresh
            (B*Lq, H, dh) fp32 rows → updated (pool_i, scale_i)."""
            old = jnp.transpose(pool_i[:, ws_rows, :],
                                (1, 0, 2)).astype(jnp.float32)
            sc = jnp.repeat(scale_i[:, phys_flat], bs, axis=1)
            ws = old * jnp.transpose(sc)[:, :, None]    # (B*A*bs, H, dh)
            ws = jnp.concatenate(
                [ws, jnp.zeros((1, H, dh), jnp.float32)], axis=0)
            ws = ws.at[loc].set(fresh)[:-1]
            blk = ws.reshape(B * A, bs, H, dh)
            absmax = jnp.max(jnp.abs(blk), axis=(1, 3))     # (B*A, H)
            # quantize_int8's convention: scale=absmax/127, empty → 1.0
            new_sc = jnp.where(absmax > 0, absmax / 127.0,
                               jnp.float32(1.0))
            codes = jnp.clip(jnp.rint(blk / new_sc[:, None, :, None]),
                             -127, 127).astype(jnp.int8)
            pool_i = pool_i.at[:, ws_rows, :].set(
                jnp.transpose(codes.reshape(B * A * bs, H, dh),
                              (1, 0, 2)))
            scale_i = scale_i.at[:, phys_flat].set(jnp.transpose(new_sc))
            return pool_i, scale_i

    x = _embed(params["embed_weight"], tokens)              # (B, Lq, D)
    for i in range(L):
        bp = _block_params(params, i)
        a = _rms_fc({"eps": 1e-6}, x, bp["ln1_gamma"])
        a2 = a.reshape(-1, D)

        def heads(w):
            h = _mm(a2, w).reshape(B, Lq, H, dh)
            return jnp.transpose(h, (0, 2, 1, 3))           # (B, H, Lq, dh)

        q, k, v = (heads(bp[t]) for t in
                   ("q_weight", "k_weight", "v_weight"))
        if int8_kv:
            kT = jnp.transpose(k, (0, 2, 1, 3)).reshape(
                B * Lq, H, dh).astype(jnp.float32)
            vT = jnp.transpose(v, (0, 2, 1, 3)).reshape(
                B * Lq, H, dh).astype(jnp.float32)
            pk_i, sk_i = requant_write(pool_k[i], scale_k[i], kT)
            pv_i, sv_i = requant_write(pool_v[i], scale_v[i], vT)
            pool_k = pool_k.at[i].set(pk_i)
            pool_v = pool_v.at[i].set(pv_i)
            scale_k = scale_k.at[i].set(sk_i)
            scale_v = scale_v.at[i].set(sv_i)
            att = sdp_attention_paged(q, pool_k, pool_v, i, tables,
                                      positions, bs,
                                      kv_scales=(scale_k, scale_v))
        else:
            pool_k, pool_v = _pool_write(pool_k, pool_v, i, k, v, plan,
                                         bs)
            att = sdp_attention_paged(q.astype(cdt), pool_k, pool_v, i,
                                      tables, positions, bs)
        att = jnp.transpose(att, (0, 2, 1, 3)).reshape(-1, D)
        x = x + _mm(att.astype(x.dtype), bp["proj_weight"]).reshape(
            B, Lq, D)
        f = _rms_fc({"eps": 1e-6}, x, bp["ln2_gamma"]).reshape(-1, D)
        x = x + _ffn(f, bp).reshape(B, Lq, D)
    h = _ln_fc({"axis": -1, "eps": 1e-5}, x, params["final_ln_gamma"],
               params["final_ln_beta"])
    if all_logits:
        logits = (_mm(h.reshape(-1, D), params["pred_weight"]) +
                  params["pred_bias"]).reshape(B, Lq,
                                               spec["vocab_size"])
    else:
        last = h[jnp.arange(B), valid - 1]                  # (B, D)
        logits = _mm(last, params["pred_weight"]) + params["pred_bias"]
    if int8_kv:
        return (logits.astype(jnp.float32), pool_k, pool_v,
                scale_k, scale_v)
    return logits.astype(jnp.float32), pool_k, pool_v


# ---------------------------------------------------------------------------
# The program store's model seam (serving/program_store.py): what a
# decode-mode model module offers under these names is all the store
# knows of an architecture.  ``models/deepseek_v3.py`` is the other one.
# ---------------------------------------------------------------------------
# planes beside the paged one with in-graph or host sampling
OFFERS = frozenset(("contiguous", "int8_kv", "draft"))
# counters a step returns beside its logits (none here)
AUX_COUNTERS = ()


def serving_spec(spec):
    return lm_spec(**dict(spec))


def required_params(spec):
    names = ["embed_weight", "final_ln_gamma", "final_ln_beta",
             "pred_weight", "pred_bias"]
    for i in range(spec["num_layers"]):
        names += ["blk%d_%s" % (i, k) for k in
                  ("ln1_gamma", "q_weight", "k_weight", "v_weight",
                   "proj_weight", "ln2_gamma", "ffn1_weight",
                   "ffn1_bias", "ffn2_weight", "ffn2_bias")]
    return names


def pack_params(params, spec):
    """Nothing to restack: the symbol graph's arguments are what the
    decode-mode graphs read."""
    return params


def quantize_params(params, spec):
    """:func:`quantize_lm_params` of the floating leaves, on the host."""
    import jax.numpy as jnp
    import numpy as np
    host = {k: np.asarray(v, np.float32)
            if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating) else v
            for k, v in params.items()}
    return quantize_lm_params(host, spec)


def paged_step(params, pools, tables, tokens, positions, valid, spec,
               block_size, scales=None, all_logits=False):
    """:func:`paged_step_apply` as ``(logits, donated leaves, None)``:
    the ``(k, v)`` pools, then the int8 plane's scale pools."""
    out = paged_step_apply(params, pools[0], pools[1], tables, tokens,
                           positions, valid, spec, block_size,
                           scales=scales, all_logits=all_logits)
    return out[0], tuple(out[1:]), None
