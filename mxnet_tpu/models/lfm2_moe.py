"""LFM2-MoE's decoder (LiquidAI's ``lfm2_moe``) as a decode-mode graph
for the paged serving plane: gated SHORT-CONVOLUTION layers whose state
is kept per sequence beside the per-token KV pool, grouped-query
attention with QK-norm, and expert layers that hold every expert.

Source: https://huggingface.co/LiquidAI/LFM2-24B-A2B (``config.json``;
the operator, attention and decoder-layer classes are the dense
family's, ``transformers.models.lfm2.modeling_lfm2``).  ``x`` a token's
hidden row, RMSNorm ``x / sqrt(mean(x^2) + eps) * g`` everywhere, no
bias anywhere but the router's:

* **Layer.** ``x <- x + op(RMSNorm(x))``; ``x <- x + ffn(RMSNorm(x))``;
  ``op`` by ``layer_types[i]``.
* **conv.** ``[B | C | u] = h W_in``; ``z_t = B_t * u_t``; ``c_t = sum_j
  w[:, j] * z_{t-2+j}`` (depthwise, causal, ``conv_L_cache`` taps a
  channel, ``z`` zero before the sequence); ``out = (C_t * c_t) W_out``.
  A sequence's state is its last ``conv_L_cache - 1`` values of ``z``.
* **full_attention.** ``q``/``k``/``v`` projections to ``num_attention_
  heads``/``num_key_value_heads`` heads of ``head_dim``; RMSNorm over
  each head of ``q`` and ``k`` (one scale vector each); rotary over the
  whole head by HALVES (``x cos + rotate_half(x) sin``); query head i
  attends KV head ``i // (heads / kv heads)``; causal softmax at
  ``head_dim^-0.5``.
* **Dense layers** (the first ``num_dense_layers``): SwiGLU.
* **Expert layers.** ``sigma = sigmoid(h W_g^T)`` in fp32; the
  ``num_experts_per_tok`` largest of ``sigma + expert_bias`` pick;
  weights the picked ``sigma`` over ``(their sum + 1e-6)``, times
  ``routed_scaling_factor``; no shared expert (``ops/moe.py``, every
  expert held here).
* **Head.** RMSNorm (``embedding_norm``), then the TIED embedding.

The pool has two leaves (:func:`init_pool`): the attention layers'
``[K | V]`` rows by token, and the convolution layers' state by BLOCK
(``models/paged.py``: one row a block, riding the block tables).
Norms, router scores, rotary and the softmax run in fp32; products in
the weights' dtype, accumulated fp32.
"""
from __future__ import annotations

import re

import numpy as np

from ..base import MXNetError
from .deepseek_v3 import (_mm, _rms, _swiglu_ffn, expert_layer,
                          layer_leaves, pack_params, quantize_leaves,
                          random_leaves)
from .paged import (cat, last_logits, pool_write, row_groups, state_read,
                    state_write)
from .transformer_lm import _embed

__all__ = ["serving_spec", "param_shapes", "random_params",
           "required_params", "matmul_weights", "pack_params",
           "quantize_params", "init_pool", "paged_step_apply",
           "paged_step", "paged_step_groups", "OFFERS", "AUX_COUNTERS",
           "ROUTE_EPS"]

# what of the serving plane this model can be put on besides the paged
# plane with in-graph or host sampling (program_store asks)
OFFERS = frozenset()
# the counters a step returns beside its logits, in order
# (deepseek_v3.expert_layer makes them)
AUX_COUNTERS = ("moe_tokens", "moe_local_assignments",
                "moe_expert_load_max", "moe_expert_steps",
                "moe_experts_touched", "moe_expert_streams")
# what the router adds to the picked scores' sum before dividing
ROUTE_EPS = 1e-6
# table entries a grid step of the attention kernel takes: a block is
# 64 rows of 256 bytes, far too little for a step
KV_GROUP = 16

_INT_KEYS = ("num_hidden_layers", "num_dense_layers", "hidden_size",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "intermediate_size", "moe_intermediate_size", "num_experts",
             "num_experts_per_tok", "conv_L_cache", "vocab_size")
_TYPES = ("conv", "full_attention")


def serving_spec(spec):
    """Validated architecture spec (the published ``config.json`` keys
    and ``head_dim``).  Beside the model's own names it carries the ones
    the shared expert code reads (``models/deepseek_v3``, ``ops/moe``):
    ``n_routed_experts = router_width = num_experts`` (every expert is
    held), ``first_k_dense_replace = num_dense_layers``, ``n_group =
    topk_group = 1``."""
    spec = dict(spec)
    missing = [k for k in _INT_KEYS + ("layer_types",) if k not in spec]
    if missing:
        raise MXNetError("lfm2_moe spec is missing %s" % missing)
    out = {"arch": "lfm2_moe"}
    for k in _INT_KEYS:
        out[k] = int(spec[k])
    out["layer_types"] = tuple(str(t) for t in spec["layer_types"])
    out["norm_eps"] = float(spec.get("norm_eps", 1e-5))
    out["rope_theta"] = float(spec.get("rope_theta", 1e6))
    out["routed_scaling_factor"] = float(spec.get(
        "routed_scaling_factor", 1.0))
    if len(out["layer_types"]) != out["num_hidden_layers"] or \
            set(out["layer_types"]) - set(_TYPES):
        raise MXNetError("lfm2_moe layer_types must name %d layers of %s"
                         % (out["num_hidden_layers"], (_TYPES,)))
    if out["num_attention_heads"] % out["num_key_value_heads"] or \
            out["head_dim"] % 2 or out["conv_L_cache"] < 2 or \
            not 0 <= out["num_dense_layers"] <= out["num_hidden_layers"] \
            or out["num_experts_per_tok"] > out["num_experts"]:
        raise MXNetError(
            "lfm2_moe spec: query heads must divide into KV heads, the "
            "head be even, the filter have a past, and the dense layers "
            "and picks fit the layers and experts")
    for mine, theirs in (("num_experts", "n_routed_experts"),
                         ("num_experts", "router_width"),
                         ("num_dense_layers", "first_k_dense_replace")):
        if int(spec.get(theirs, out[mine])) != out[mine]:
            raise MXNetError("lfm2_moe spec: %s must equal %s"
                             % (theirs, mine))
        out[theirs] = out[mine]
    out["n_group"] = out["topk_group"] = 1
    return out


def _layers(spec, kind):
    return [i for i, t in enumerate(spec["layer_types"]) if t == kind]


def _is_dense(spec, i):
    return i < spec["num_dense_layers"]


def param_shapes(spec):
    """name -> shape of the checkpoint's leaves: every matrix ``(out,
    in)``, the filter ``(channels, taps)``, each expert's three matrices
    leaves of their own (``pack_params`` stacks them)."""
    D, dh = spec["hidden_size"], spec["head_dim"]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    F = spec["moe_intermediate_size"]
    out = {"embed_tokens_weight": (spec["vocab_size"], D),
           "final_norm_gamma": (D,)}
    for i, kind in enumerate(spec["layer_types"]):
        p = "l%d_" % i
        out.update({p + "op_norm_gamma": (D,), p + "ffn_norm_gamma": (D,)})
        if kind == "conv":
            out.update({p + "in_weight": (3 * D, D),
                        p + "conv_weight": (D, spec["conv_L_cache"]),
                        p + "out_weight": (D, D)})
        else:
            out.update({p + "q_weight": (H * dh, D),
                        p + "k_weight": (Hkv * dh, D),
                        p + "v_weight": (Hkv * dh, D),
                        p + "o_weight": (D, H * dh),
                        p + "q_norm_gamma": (dh,),
                        p + "k_norm_gamma": (dh,)})
        if _is_dense(spec, i):
            I = spec["intermediate_size"]
            out.update({p + "gate_weight": (I, D), p + "up_weight": (I, D),
                        p + "down_weight": (D, I)})
            continue
        out.update({p + "router_weight": (spec["num_experts"], D),
                    p + "router_bias": (spec["num_experts"],)})
        for e in range(spec["num_experts"]):
            q = "%se%d_" % (p, e)
            out.update({q + "gate_weight": (F, D), q + "up_weight": (F, D),
                        q + "down_weight": (D, F)})
    return out


def required_params(spec):
    """The leaves a step reads: the checkpoint's, with each expert
    layer's experts as the two stacks of ``pack_params``."""
    names = [n for n in param_shapes(spec)
             if not re.match(r"l\d+_e\d+_", n)]
    for i in range(spec["num_hidden_layers"]):
        if not _is_dense(spec, i):
            names += ["l%d_experts_gate_up" % i, "l%d_experts_down" % i]
    return names


def matmul_weights(spec):
    """The leaves int8 weight-only serving quantizes: every matmul
    weight, the tied embedding and the experts' stacks among them (norm
    scales, the filter's taps and the router's bias stay)."""
    return [n for n in required_params(spec)
            if (n.endswith("_weight") and not n.endswith("conv_weight"))
            or "_experts_" in n]


def quantize_params(params, spec):
    """int8 weight-only transform of a PACKED param dict
    (``deepseek_v3.quantize_leaves`` of :func:`matmul_weights`)."""
    return quantize_leaves(params, matmul_weights(spec))


def random_params(spec, seed=0):
    """Seeded random weights with :func:`param_shapes`' names: matrices,
    taps and the tied embedding N(0, 1 / fan_in), norm scales near one,
    the router's bias small."""
    return random_leaves(param_shapes(spec), seed)


def init_pool(spec, num_blocks, block_size, dtype="float32"):
    """The zeroed pool, two leaves.  ``kv`` ``(attention layers, KV
    heads, num_blocks * block_size, 2 * head_dim)``: a row is ``[K |
    V]`` of one head of one token (at ``head_dim`` 64 one whole
    128-lane tile; K and V apart would each fill half a tile and be
    fetched apart).  ``state`` ``(conv layers, 1, num_blocks,
    (conv_L_cache - 1) * hidden)``: ONE ROW A BLOCK, a sequence's last
    inputs of the filter as they stood after the block's last written
    token, oldest first (``models/paged.py``).  Block 0 is the reserved
    trash block, as in ``transformer_lm``."""
    import jax.numpy as jnp
    nb = int(num_blocks)
    return (jnp.zeros((len(_layers(spec, "full_attention")),
                       spec["num_key_value_heads"], nb * int(block_size),
                       2 * spec["head_dim"]), dtype),
            jnp.zeros((len(_layers(spec, "conv")), 1, nb,
                       (spec["conv_L_cache"] - 1) * spec["hidden_size"]),
                      dtype))


def _rope(x, cos, sin):
    """Rotary by HALVES: ``x cos + rotate_half(x) sin``, x ``(...,
    head_dim)`` fp32, cos/sin ``(..., head_dim / 2)``."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(z, taps):
    """``c_t = sum_j taps[:, j] * z_{t - (n-1) + j}``: ``z`` ``(B, n - 1
    + Lq, D)`` with the ``n - 1`` entries before the step first, taps
    ``(D, n)`` -> ``(B, Lq, D)`` fp32."""
    import jax.numpy as jnp
    n = taps.shape[1]
    Lq = z.shape[1] - (n - 1)
    z = z.astype(jnp.float32)
    taps = taps.astype(jnp.float32)
    return sum(taps[:, j] * z[:, j:j + Lq] for j in range(n))


def paged_step_groups(params, pools, groups, spec, block_size,
                      all_logits=False):
    """One PAGED step over the two leaves of :func:`init_pool` for a
    tuple of ROW GROUPS, each ``(tables (B, T), tokens (B, Lq),
    positions (B,), valid (B,))`` with a ``B`` and an ``Lq`` of its own
    (a tick's decode rows, ``Lq = 1``, and its prompt chunk's).  What
    works on a TOKEN (norms, projections, the feed-forward, the experts,
    the head) runs ONCE over all the groups' rows laid end to end, so a
    weight is read once a step; what works on a SEQUENCE runs a group,
    in the order given, as that many one-group steps would: an
    attention layer writes the group's ``[K | V]`` rows in place
    (``paged.pool_write``) and attends through the ``sdp_attention_
    paged`` door, the query heads of a KV head in one tile; a
    convolution layer takes the state its sequences bring from the row
    of the block before (``paged.state_read``), runs the filter over
    the chunk and leaves the state after each written block's last
    token in that block's row (``paged.state_write``).  ``params`` is a
    PACKED dict (``pack_params``), plain or int8.  The program store
    takes a model that has this name to offer a step over more than one
    group (``program_store.paged_program``).

    Returns ``(logits a group, (kv, state), counts)``: a group's logits
    ``(B, vocab)`` fp32 at each row's last valid position
    (``all_logits``: ``(B, Lq, vocab)``), and :data:`AUX_COUNTERS`
    summed over the expert layers, all groups together
    (``deepseek_v3.paged_step_groups`` tells them)."""
    import jax.numpy as jnp
    from ..ops.attention import sdp_attention_paged

    D, dh = spec["hidden_size"], spec["head_dim"]
    H, Hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    eps = spec["norm_eps"]
    past = spec["conv_L_cache"] - 1
    bs = int(block_size)
    f32 = jnp.float32
    cdt = params["final_norm_gamma"].dtype      # the weights' dtype
    kv, state = pools
    gs, tokens = row_groups(groups, bs)
    live = cat([g.live for g in gs])
    freqs = 1.0 / spec["rope_theta"] ** (
        np.arange(0, dh, 2, dtype=np.float64) / dh)
    for g in gs:
        angle = g.angles(freqs)[:, :, None]             # (B, Lq, 1, dh/2)
        g.plan, g.cos, g.sin = g.write_plan(), jnp.cos(angle), jnp.sin(angle)
    counts = jnp.zeros((len(AUX_COUNTERS),), jnp.int32)
    n_att = n_conv = 0

    embed = params["embed_tokens_weight"]       # the head too: tied
    x = _embed(embed, tokens).astype(f32)                    # (N, D)
    for i, kind in enumerate(spec["layer_types"]):
        p = layer_leaves(params, "l%d_" % i)
        h = _rms(x, p["op_norm_gamma"], eps).astype(cdt)
        outs = []
        if kind == "conv":
            bcu_all = _mm(h, p["in_weight"]).astype(f32)
            for g in gs:
                B, Lq = g.B, g.Lq
                bcu = bcu_all[g.span].reshape(B, Lq, 3, D)
                # rounded as the state leaf holds it, whatever row of a
                # chunk it is read back in
                z = (bcu[:, :, 0] * bcu[:, :, 2]).astype(state.dtype)
                trail = jnp.concatenate(
                    [state_read(state, n_conv, g.tables, g.positions, bs)
                     .reshape(B, past, D), z], axis=1)
                y = bcu[:, :, 1] * short_conv(trail, p["conv_weight"])
                outs.append(y.astype(cdt).reshape(B * Lq, D))
                state = state_write(state, n_conv, trail, g.plan, Lq, bs)
            out = _mm(cat(outs), p["out_weight"])
            n_conv += 1
        else:
            q_all = _mm(h, p["q_weight"])
            k_all = _mm(h, p["k_weight"])
            v_all = _mm(h, p["v_weight"])
            for g in gs:
                B, Lq = g.B, g.Lq
                q = q_all[g.span].reshape(B, Lq, H, dh)
                k = k_all[g.span].reshape(B, Lq, Hkv, dh)
                v = v_all[g.span].reshape(B, Lq, Hkv, dh)
                q = _rope(_rms(q, p["q_norm_gamma"], eps), g.cos, g.sin)
                k = _rope(_rms(k, p["k_norm_gamma"], eps), g.cos, g.sin)
                fresh = jnp.concatenate([k, v.astype(f32)], axis=-1)
                kv, = pool_write((kv,), n_att,
                                 (jnp.transpose(fresh, (0, 2, 1, 3)),),
                                 g.plan, bs)
                # the row is key and value: a query that is zero over
                # the value half scores the key half alone, and the
                # value half of the result is the attention's output
                query = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
                att = sdp_attention_paged(
                    jnp.transpose(query, (0, 2, 1, 3)).astype(kv.dtype),
                    kv, None, n_att, g.tables, g.positions, bs,
                    scale=dh ** -0.5, group=KV_GROUP)[..., dh:]
                outs.append(jnp.transpose(att, (0, 2, 1, 3)).astype(cdt)
                            .reshape(B * Lq, H * dh))
            out = _mm(cat(outs), p["o_weight"])
            n_att += 1
        x = x + out.astype(f32)

        f = _rms(x, p["ffn_norm_gamma"], eps).astype(cdt)
        if _is_dense(spec, i):
            y = _swiglu_ffn(f, p["gate_weight"], p["up_weight"],
                            p["down_weight"])
        else:
            y, step = expert_layer(f, p, spec, live, ROUTE_EPS)
            counts = counts + step
        x = x + y
    hN = _rms(x, params["final_norm_gamma"], eps).astype(cdt)
    if all_logits:
        every = _mm(hN, embed, f32).astype(f32)
        logits = tuple(every[g.span].reshape(g.B, g.Lq, -1) for g in gs)
    else:
        logits = last_logits(hN, gs, lambda last: _mm(
            last, embed, f32).astype(f32))
    return logits, (kv, state), counts


def paged_step_apply(params, kv, state, tables, tokens, positions, valid,
                     spec, block_size, all_logits=False):
    """:func:`paged_step_groups` of ONE group — ``transformer_lm.
    paged_step_apply``'s contract over the two leaves of
    :func:`init_pool`: tokens ``(B, Lq)`` (``Lq = 1`` a decode step),
    positions/valid ``(B,)``, tables ``(B, T)``.  Returns ``(logits,
    kv, state, counts)``."""
    (logits,), (kv, state), counts = paged_step_groups(
        params, (kv, state), ((tables, tokens, positions, valid),), spec,
        block_size, all_logits=all_logits)
    return logits, kv, state, counts


def paged_step(params, pools, tables, tokens, positions, valid, spec,
               block_size, scales=None, all_logits=False):
    """The program store's seam: ``(logits, pool leaves, counters)``."""
    if scales is not None:
        raise MXNetError("lfm2_moe has no int8 pool")
    logits, kv, state, counts = paged_step_apply(
        params, pools[0], pools[1], tables, tokens, positions, valid,
        spec, block_size, all_logits=all_logits)
    return logits, (kv, state), counts
