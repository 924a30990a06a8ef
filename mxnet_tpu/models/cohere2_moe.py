"""Command A+'s decoder (Cohere's ``cohere2_moe``) as a decode-mode
graph for the paged serving plane: WINDOW and FULL attention layers
over two classes of cache block, a parallel block, averaged shared
experts beside this chip's share of the routed ones.

Source: https://huggingface.co/CohereLabs/command-a-plus-05-2026
(``config.json``; the norm, the attention, its masks and the rotary are
the dense family's, ``transformers.models.cohere2.modeling_cohere2``).
``x`` a token's hidden row, no bias anywhere:

* **Norm.** ``LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * g``
  (``Cohere2LayerNorm``): one a layer, one before the head.
* **Layer** (``use_parallel_block``). ``h = LN(x)``; ``x <- x + attn(h)
  + moe(h)``: attention and the expert layer read the SAME ``h``.
* **Attention.** ``q``/``k``/``v`` projections to ``num_attention_
  heads``/``num_key_value_heads`` heads of ``head_dim``, no QK-norm;
  query head i attends KV head ``i // (heads / kv heads)``; softmax at
  ``head_dim^-0.5``.  ``sliding_attention`` layers: rotary over the
  whole head, INTERLEAVED pairs (``rope_gptj``), and the query at ``p``
  sees keys ``p - sliding_window + 1 .. p``.  ``full_attention``
  layers: NO rotary, every key ``<= p``.
* **Expert layer.** ``s = sigmoid(h W_r^T)`` in fp32 over ALL
  ``router_width`` experts; the ``num_experts_per_tok`` largest pick;
  weights the picked ``s`` over their sum (``norm_topk_prob``; no bias,
  no scaling factor); ``routed`` sums over the picked experts among
  the ``num_experts`` HELD here (``ops/moe.py``, as ``deepseek_v3``).
  ``shared = (1 / num_shared_experts) sum_i S_i(h)``, the ``"average"``
  of ``shared_expert_combination_strategy``: the shared experts'
  matrices lie side by side, so their sum is ONE gated unit of width
  ``num_shared_experts * intermediate_size`` and the average a scalar
  on it.  ``moe(h) = routed + shared``.
* **Head.** ``LN(x) E^T * logit_scale``, ``E`` the TIED embedding.

The vision tower in front of the published model is not here.

The pool has four leaves in TWO CLASSES of block
(:func:`cache_classes`): ``K`` and ``V`` of the full layers, and ``K``
and ``V`` of the window layers, whose blocks a sequence gives back as
they fall behind its window (docs/architecture/decode_engine.md,
"Classes of block").  A step takes a block table a class, side by side
in one array.  Norms, router scores, rotary and the softmax run in
fp32; products in the weights' dtype, accumulated fp32.
"""
from __future__ import annotations

import re

import numpy as np

from ..base import MXNetError
from .deepseek_v3 import (_mm, _rope, _swiglu_ffn, expert_layer,
                          layer_leaves, pack_params, quantize_leaves,
                          random_leaves)
from .paged import cat, last_logits, pool_write, row_groups
from .transformer_lm import _embed

__all__ = ["serving_spec", "param_shapes", "random_params",
           "required_params", "matmul_weights", "pack_params",
           "quantize_params", "init_pool", "cache_classes",
           "paged_step_apply", "paged_step", "paged_step_groups",
           "OFFERS", "AUX_COUNTERS", "WINDOW_KERNEL"]

# what of the serving plane this model can be put on besides the paged
# plane with in-graph or host sampling (program_store asks)
OFFERS = frozenset()
# the counters a step returns beside its logits, in order
# (deepseek_v3.expert_layer makes them)
AUX_COUNTERS = ("moe_tokens", "moe_local_assignments",
                "moe_expert_load_max", "moe_expert_steps",
                "moe_experts_touched", "moe_expert_streams")
# the window layers' attention call in a trace, apart from the full
# layers' ``paged_attention`` (the same kernel body)
WINDOW_KERNEL = "window_paged_attention"
# table entries a grid step of the attention kernel takes: a block is
# 64 rows of 256 bytes, far too little for a step
KV_GROUP = 16
# rows of the kernel's Q tile: a chunk of 64 tokens puts 16 x 64 query
# rows on a KV head, and every tile of them fetches the sequence's keys
# and values again (8 times at the configured 128; at 1,024 once, and
# the tile with its scores still fits the kernel's stack at KV_GROUP 16:
# my chip runs, PR 33: 28.7, 15.4, 9.2 and 6.1 ms a full layer's call at
# 128, 256, 512 and 1,024)
Q_TILE = 1024

_INT_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "intermediate_size",
             "num_experts", "router_width", "num_experts_per_tok",
             "num_shared_experts", "sliding_window", "vocab_size")
_TYPES = ("full_attention", "sliding_attention")


def serving_spec(spec):
    """Validated architecture spec (the published ``config.json`` keys;
    ``num_experts`` counts the experts HELD here, ``router_width`` all
    the experts the router scores).  Beside the model's own names it
    carries the ones the shared expert code reads (``models/
    deepseek_v3``, ``ops/moe``): ``n_routed_experts = num_experts``,
    ``first_k_dense_replace = 0`` (the published config has no leading
    dense layer), ``n_group = topk_group = 1``, ``routed_scaling_factor
    = 1``."""
    spec = dict(spec)
    missing = [k for k in _INT_KEYS + ("layer_types",) if k not in spec]
    if missing:
        raise MXNetError("cohere2_moe spec is missing %s" % missing)
    out = {"arch": "cohere2_moe"}
    for k in _INT_KEYS:
        out[k] = int(spec[k])
    out["layer_types"] = tuple(str(t) for t in spec["layer_types"])
    out["layer_norm_eps"] = float(spec.get("layer_norm_eps", 1e-5))
    out["rope_theta"] = float(spec.get("rope_theta", 50000.0))
    out["logit_scale"] = float(spec.get("logit_scale", 1.0))
    if len(out["layer_types"]) != out["num_hidden_layers"] or \
            set(out["layer_types"]) - set(_TYPES):
        raise MXNetError("cohere2_moe layer_types must name %d layers "
                         "of %s" % (out["num_hidden_layers"], (_TYPES,)))
    if out["num_attention_heads"] % out["num_key_value_heads"] or \
            out["head_dim"] % 2 or out["sliding_window"] < 1 or \
            not 0 < out["num_experts"] <= out["router_width"] or \
            out["num_experts_per_tok"] > out["router_width"] or \
            out["num_shared_experts"] < 1:
        raise MXNetError(
            "cohere2_moe spec: query heads must divide into KV heads, "
            "the head be even, the window hold a key, and the held "
            "experts and the picks fit the router")
    for theirs, value in (("n_routed_experts", out["num_experts"]),
                          ("first_k_dense_replace", 0)):
        if int(spec.get(theirs, value)) != value:
            raise MXNetError("cohere2_moe spec: %s must be %d"
                             % (theirs, value))
        out[theirs] = value
    out["n_group"] = out["topk_group"] = 1
    out["routed_scaling_factor"] = 1.0
    return out


def _layers(spec, kind):
    return [i for i, t in enumerate(spec["layer_types"]) if t == kind]


def _kinds(spec):
    """The layer types the spec has, full first: a pool class each."""
    return [t for t in _TYPES if t in spec["layer_types"]]


def cache_classes(spec):
    """The pool's classes of block, in the order a step takes their
    tables: ``(window, leaves)`` each — ``window`` the keys a query of
    the class's layers sees (None: all of them, so a sequence keeps
    every block), ``leaves`` the indices of the class's leaves in
    :func:`init_pool`'s tuple."""
    return tuple((spec["sliding_window"] if kind == "sliding_attention"
                  else None, (2 * c, 2 * c + 1))
                 for c, kind in enumerate(_kinds(spec)))


def param_shapes(spec):
    """name -> shape of the checkpoint's leaves: every matrix ``(out,
    in)``, the shared experts' matrices side by side (gate and up by
    rows, down by columns), each routed expert's three matrices leaves
    of their own (``pack_params`` stacks them)."""
    D, dh = spec["hidden_size"], spec["head_dim"]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    F = spec["intermediate_size"]
    S = F * spec["num_shared_experts"]
    out = {"embed_tokens_weight": (spec["vocab_size"], D),
           "final_norm_gamma": (D,)}
    for i in range(spec["num_hidden_layers"]):
        p = "l%d_" % i
        out.update({p + "norm_gamma": (D,),
                    p + "q_weight": (H * dh, D),
                    p + "k_weight": (Hkv * dh, D),
                    p + "v_weight": (Hkv * dh, D),
                    p + "o_weight": (D, H * dh),
                    p + "router_weight": (spec["router_width"], D),
                    p + "shared_gate_weight": (S, D),
                    p + "shared_up_weight": (S, D),
                    p + "shared_down_weight": (D, S)})
        for e in range(spec["num_experts"]):
            q = "%se%d_" % (p, e)
            out.update({q + "gate_weight": (F, D), q + "up_weight": (F, D),
                        q + "down_weight": (D, F)})
    return out


def required_params(spec):
    """The leaves a step reads: the checkpoint's, with each layer's
    routed experts as the two stacks of ``pack_params``."""
    names = [n for n in param_shapes(spec)
             if not re.match(r"l\d+_e\d+_", n)]
    for i in range(spec["num_hidden_layers"]):
        names += ["l%d_experts_gate_up" % i, "l%d_experts_down" % i]
    return names


def matmul_weights(spec):
    """The leaves int8 weight-only serving quantizes: every matmul
    weight, the tied embedding and the experts' stacks among them (the
    norm scales stay)."""
    return [n for n in required_params(spec)
            if n.endswith("_weight") or "_experts_" in n]


def quantize_params(params, spec):
    """int8 weight-only transform of a PACKED param dict
    (``deepseek_v3.quantize_leaves`` of :func:`matmul_weights`)."""
    return quantize_leaves(params, matmul_weights(spec))


def random_params(spec, seed=0):
    """Seeded random weights with :func:`param_shapes`' names: matrices
    and the tied embedding N(0, 1 / fan_in), norm scales near one."""
    return random_leaves(param_shapes(spec), seed)


def init_pool(spec, num_blocks, block_size, dtype="float32"):
    """The zeroed pool: ``K`` and ``V`` leaves ``(layers of the class,
    KV heads, num_blocks * block_size, head_dim)`` for each class of
    :func:`cache_classes` — a row is one head of one token (at
    ``head_dim`` 128 a whole lane tile each).  ``num_blocks`` blocks in
    EACH class, block 0 of each the reserved trash block."""
    import jax.numpy as jnp
    rows = int(num_blocks) * int(block_size)
    return tuple(
        jnp.zeros((len(_layers(spec, kind)), spec["num_key_value_heads"],
                   rows, spec["head_dim"]), dtype)
        for kind in _kinds(spec) for _ in "kv")


def _ln(x, gamma, eps):
    """``(x - mean) / sqrt(var + eps) * g`` in fp32, no bias."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma.astype(jnp.float32)


def paged_step_groups(params, pools, groups, spec, block_size,
                      all_logits=False):
    """One PAGED step over the leaves of :func:`init_pool` for a tuple
    of ROW GROUPS, each ``(tables (B, classes * T), tokens (B, Lq),
    positions (B,), valid (B,))`` with a ``B`` and an ``Lq`` of its own
    (a tick's decode rows, ``Lq = 1``, and its prompt chunk's); a
    group's tables hold the ``T`` entries of each class of
    :func:`cache_classes` side by side.  What works on a TOKEN (the
    norm, the projections, the shared and the routed experts, the
    head) runs ONCE over all the groups' rows laid end to end, so a
    weight is read once a step; what works on a SEQUENCE runs a group,
    in the order given, as that many one-group steps would: a layer
    writes the group's ``K`` and ``V`` rows into its class's leaves
    through its class's table (``paged.pool_write``) and attends through
    the ``sdp_attention_paged`` door, the query heads of a KV head in one
    tile; a window layer's call masks and skips what lies behind the
    window and is named :data:`WINDOW_KERNEL`.  ``params`` is a PACKED
    dict (``pack_params``), plain or int8.  The program store takes a
    model that has this name to offer a step over more than one group
    (``program_store.paged_program``).

    Returns ``(logits a group, pools, counts)``: a group's logits ``(B,
    vocab)`` fp32 at each row's last valid position (``all_logits``:
    ``(B, Lq, vocab)``), and :data:`AUX_COUNTERS` summed over the
    expert layers, all groups together (``deepseek_v3.
    paged_step_groups`` tells them)."""
    import jax.numpy as jnp
    from ..ops.attention import sdp_attention_paged

    dh = spec["head_dim"]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    eps = spec["layer_norm_eps"]
    bs = int(block_size)
    f32 = jnp.float32
    cdt = params["final_norm_gamma"].dtype      # the weights' dtype
    gs, tokens = row_groups(groups, bs)
    # a released block leaves a zero in a window table's first entry:
    # the first class's table, first in the array, says which rows are
    # in the dispatch (``RowGroup.live``)
    live = cat([g.live for g in gs])
    kinds = _kinds(spec)
    freqs = 1.0 / spec["rope_theta"] ** (
        np.arange(0, dh, 2, dtype=np.float64) / dh)
    for g in gs:
        # by layer type: the class's table, and where its fresh rows go
        T = g.tables.shape[1] // len(kinds)
        g.cls = {}
        for c, kind in enumerate(kinds):
            tbl = g.tables[:, c * T:(c + 1) * T]
            g.cls[kind] = (tbl, g.write_plan(tbl))
        angle = g.angles(freqs)[:, :, None]             # (B, Lq, 1, dh/2)
        g.cos, g.sin = jnp.cos(angle), jnp.sin(angle)
    # by layer type: the class's window, its leaves' place in the pool
    # and the next layer of them
    cls = {kind: [window, leaves, 0] for kind, (window, leaves)
           in zip(kinds, cache_classes(spec))}
    pools = list(pools)
    counts = jnp.zeros((len(AUX_COUNTERS),), jnp.int32)
    no_bias = jnp.zeros((spec["router_width"],), f32)
    share = 1.0 / spec["num_shared_experts"]

    embed = params["embed_tokens_weight"]       # the head too: tied
    x = _embed(embed, tokens).astype(f32)                    # (N, D)
    for i, kind in enumerate(spec["layer_types"]):
        p = layer_leaves(params, "l%d_" % i)
        window, (ik, iv), n = cls[kind]
        cls[kind][2] += 1
        h = _ln(x, p["norm_gamma"], eps).astype(cdt)
        q_all = _mm(h, p["q_weight"])
        k_all = _mm(h, p["k_weight"])
        v_all = _mm(h, p["v_weight"])
        outs = []
        for g in gs:
            B, Lq = g.B, g.Lq
            tbl, plan = g.cls[kind]
            q = q_all[g.span].reshape(B, Lq, H, dh)
            k = k_all[g.span].reshape(B, Lq, Hkv, dh)
            v = v_all[g.span].reshape(B, Lq, Hkv, dh)
            if window is not None:
                q = _rope(q.astype(f32), g.cos, g.sin)
                k = _rope(k.astype(f32), g.cos, g.sin)
            pools[ik], pools[iv] = pool_write(
                (pools[ik], pools[iv]), n,
                (jnp.transpose(k, (0, 2, 1, 3)),
                 jnp.transpose(v, (0, 2, 1, 3))), plan, bs)
            att = sdp_attention_paged(
                jnp.transpose(q, (0, 2, 1, 3)).astype(pools[ik].dtype),
                pools[ik], pools[iv], n, tbl, g.positions, bs,
                scale=dh ** -0.5, group=KV_GROUP, window=window,
                name="paged_attention" if window is None
                else WINDOW_KERNEL, block_q=Q_TILE)
            outs.append(jnp.transpose(att, (0, 2, 1, 3)).astype(cdt)
                        .reshape(B * Lq, H * dh))
        out = _mm(cat(outs), p["o_weight"]).astype(f32)
        routed, step = expert_layer(h, dict(p, router_bias=no_bias), spec,
                                    live)
        counts = counts + step
        shared = _swiglu_ffn(h, p["shared_gate_weight"],
                             p["shared_up_weight"],
                             p["shared_down_weight"])
        x = x + (out + routed + share * shared)
    hN = _ln(x, params["final_norm_gamma"], eps).astype(cdt)

    def head(rows):
        logits = _mm(rows, embed, f32)
        if spec["logit_scale"] != 1.0:
            logits = logits * spec["logit_scale"]
        return logits.astype(f32)

    if all_logits:
        every = head(hN)
        logits = tuple(every[g.span].reshape(g.B, g.Lq, -1) for g in gs)
    else:
        logits = last_logits(hN, gs, head)
    return logits, tuple(pools), counts


def paged_step_apply(params, pools, tables, tokens, positions, valid,
                     spec, block_size, all_logits=False):
    """:func:`paged_step_groups` of ONE group — ``transformer_lm.
    paged_step_apply``'s contract over the leaves of :func:`init_pool`:
    tokens ``(B, Lq)`` (``Lq = 1`` a decode step), positions/valid
    ``(B,)``, tables ``(B, classes * T)``.  Returns ``(logits, pools,
    counts)``."""
    (logits,), pools, counts = paged_step_groups(
        params, pools, ((tables, tokens, positions, valid),), spec,
        block_size, all_logits=all_logits)
    return logits, pools, counts


def paged_step(params, pools, tables, tokens, positions, valid, spec,
               block_size, scales=None, all_logits=False):
    """The program store's seam: ``(logits, pool leaves, counters)``."""
    if scales is not None:
        raise MXNetError("cohere2_moe has no int8 pool")
    return paged_step_apply(params, pools, tables, tokens, positions,
                            valid, spec, block_size,
                            all_logits=all_logits)
