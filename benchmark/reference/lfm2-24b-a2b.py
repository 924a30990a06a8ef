"""Plain reference of the ``lfm2-24b-a2b`` configuration: LFM2-24B-A2B's
decoder (config.json of https://huggingface.co/LiquidAI/LFM2-24B-A2B,
``model_type`` ``lfm2_moe``; the operator, attention and decoder-layer
classes are those of ``transformers.models.lfm2.modeling_lfm2``), given
the same layers as the program.  One whole sequence in one forward
pass, ``jax.numpy`` float32: no cache, no state, no sorting, no kernel,
nothing of the program.

Per layer, ``x`` (T, hidden), RMSNorm ``x / sqrt(mean(x^2) + norm_eps) *
g``, no bias but the router's:

* ``x <- x + op(RMSNorm_operator(x))``, ``x <- x + ffn(RMSNorm_ffn(x))``,
  ``op`` by ``layer_types[i]``.
* ``conv``: ``[B | C | u] = h W_in`` (split in that order); ``z = B *
  u``; ``c_t = sum_j w[:, j] z_{t - (n-1) + j}`` over ``n =
  conv_L_cache`` taps a channel, ``z`` zero before the sequence (a
  depthwise causal convolution); ``out = (C * c) W_out``.
* ``full_attention``: ``q = h W_q`` -> heads x head_dim, ``k``, ``v`` ->
  KV heads x head_dim; RMSNorm over each head of ``q`` and of ``k`` (one
  scale vector of head_dim each); rotary over the whole head by HALVES
  (``x cos + rotate_half(x) sin``, ``theta = rope_theta``, no scaling);
  query head i attends KV head ``i // (heads / KV heads)``; causal
  softmax at ``head_dim^-0.5``; ``out = o W_o``.
* Dense feed-forward (the first ``num_dense_layers``): ``W_down(silu(
  W_gate h) * W_up h)``.
* Expert layers: ``s = sigmoid(h W_g^T)``; the ``num_experts_per_tok``
  largest of ``s + expert_bias`` pick (ties to the lower index); weights
  the picked ``s`` over ``(their sum + 1e-6)``, times
  ``routed_scaling_factor``; ``y = sum w_e expert_e(h)``, a dense loop
  over the experts under a mask; no shared expert.
* Head: RMSNorm (``embedding_norm``), then the TIED embedding matrix.

Attention runs in blocks of queries and the weights may come in
bfloat16 (each is cast to float32 where it is used).  The caller sets
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
ROUTE_EPS = 1e-6


def _spec(cfg):
    return cfg["spec"]


def param_shapes(cfg):
    """name -> shape of every learned leaf, matrices ``(out, in)``, the
    filter ``(channels, taps)``, each expert's three matrices leaves of
    their own."""
    s = _spec(cfg)
    d, dh = int(s["hidden_size"]), int(s["head_dim"])
    h, hkv = int(s["num_attention_heads"]), int(s["num_key_value_heads"])
    f, e = int(s["moe_intermediate_size"]), int(s["num_experts"])
    shapes = {"embed_tokens_weight": (int(s["vocab_size"]), d),
              "final_norm_gamma": (d,)}
    for i, kind in enumerate(s["layer_types"]):
        b = "l%d_" % i
        shapes.update({b + "op_norm_gamma": (d,),
                       b + "ffn_norm_gamma": (d,)})
        if kind == "conv":
            shapes.update({b + "in_weight": (3 * d, d),
                           b + "conv_weight": (d, int(s["conv_L_cache"])),
                           b + "out_weight": (d, d)})
        else:
            shapes.update({b + "q_weight": (h * dh, d),
                           b + "k_weight": (hkv * dh, d),
                           b + "v_weight": (hkv * dh, d),
                           b + "o_weight": (d, h * dh),
                           b + "q_norm_gamma": (dh,),
                           b + "k_norm_gamma": (dh,)})
        if i < int(s["num_dense_layers"]):
            w = int(s["intermediate_size"])
            shapes.update({b + "gate_weight": (w, d),
                           b + "up_weight": (w, d),
                           b + "down_weight": (d, w)})
            continue
        shapes.update({b + "router_weight": (e, d),
                       b + "router_bias": (e,)})
        for j in range(e):
            shapes.update({"%se%d_gate_weight" % (b, j): (f, d),
                           "%se%d_up_weight" % (b, j): (f, d),
                           "%se%d_down_weight" % (b, j): (d, f)})
    return shapes


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gamma


def short_conv(h, w_in, taps, w_out, low=lambda a: a):
    """The gated short convolution over one sequence: h (T, d), ``w_in``
    (3d, d), ``taps`` (d, n), ``w_out`` (d, d) -> (T, d)."""
    t, d = h.shape
    n = taps.shape[1]
    b, c, u = jnp.split(h @ w_in.T, 3, axis=-1)
    z = jnp.pad(low(low(b) * low(u)), ((n - 1, 0), (0, 0)))
    conv = sum(taps[:, j] * z[j:j + t] for j in range(n))
    return low(low(c) * conv) @ w_out.T


def _rope(x, angle):
    """x (T, heads, dh) turned by HALVES by ``angle`` (T, dh / 2)."""
    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, p, b, s, low=lambda a: a):
    """Grouped-query causal attention with QK-norm over one sequence:
    h (T, d), ``p[b + ...]`` the layer's leaves -> (T, d)."""
    f32 = jnp.float32
    t = h.shape[0]
    nh, nkv, dh = (int(s["num_attention_heads"]),
                   int(s["num_key_value_heads"]), int(s["head_dim"]))
    eps = float(s["norm_eps"])
    w = lambda name: p[b + name].astype(f32)
    q = low(h @ w("q_weight").T).reshape(t, nh, dh)
    k = low(h @ w("k_weight").T).reshape(t, nkv, dh)
    v = low(h @ w("v_weight").T).reshape(t, nkv, dh)
    freqs = 1.0 / float(s["rope_theta"]) ** (
        np.arange(0, dh, 2, dtype=np.float64) / dh)
    angle = jnp.arange(t, dtype=f32)[:, None] * jnp.asarray(freqs, f32)
    q = low(_rope(_rms(q, w("q_norm_gamma"), eps), angle))
    k = low(_rope(_rms(k, w("k_norm_gamma"), eps), angle))
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    keys = jnp.arange(t)

    def query_block(j):
        a = jax.lax.dynamic_slice_in_dim(q, j * qb, qb, 0)
        sc = jnp.einsum("qhd,khd->hqk", a, k) * dh ** -0.5
        rows = j * qb + jnp.arange(qb)
        sc = jnp.where(rows[None, :, None] >= keys[None, None, :], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(query_block, jnp.arange((t + pad) // qb))
    return low(out.reshape(t + pad, nh * dh)[:t]) @ w("o_weight").T


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.T) * (h @ up.T)) @ down.T


def route(scores, bias, s):
    """(picked (T, k) int32, weights (T, k)): the k largest of ``scores
    + bias`` (a stable descending sort: ties to the lower index), the
    picked scores over their sum plus 1e-6, times the scale."""
    k = int(s["num_experts_per_tok"])
    picked = jnp.argsort(-(scores + bias[None, :]), axis=-1,
                         stable=True)[:, :k]
    w = jnp.take_along_axis(scores, picked, axis=1)
    w = w / (w.sum(-1, keepdims=True) + ROUTE_EPS) \
        * float(s["routed_scaling_factor"])
    return picked.astype(jnp.int32), w


def expert_layer(h, p, b, s):
    """``sum w_e expert_e(h)`` over the picked experts, a dense loop
    under a mask.  Returns ``(y, picked, weights)``."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(h @ p[b + "router_weight"].astype(f32).T)
    picked, w = route(scores, p[b + "router_bias"].astype(f32), s)
    y = jnp.zeros_like(h)
    for e in range(int(s["num_experts"])):
        mine = jnp.sum(jnp.where(picked == e, w, 0.0), axis=-1)
        y = y + mine[:, None] * _swiglu(
            h, p["%se%d_gate_weight" % (b, e)].astype(f32),
            p["%se%d_up_weight" % (b, e)].astype(f32),
            p["%se%d_down_weight" % (b, e)].astype(f32))
    return y, picked, w


def decoder_layer(x, p, i, s, low=lambda a: a):
    """Layer ``i`` over one sequence x (T, d)."""
    f32 = jnp.float32
    b = "l%d_" % i
    eps = float(s["norm_eps"])
    w = lambda name: p[b + name].astype(f32)
    h = low(_rms(x, w("op_norm_gamma"), eps))
    if s["layer_types"][i] == "conv":
        x = x + short_conv(h, w("in_weight"), w("conv_weight"),
                           w("out_weight"), low)
    else:
        x = x + attention(h, p, b, s, low)
    f = low(_rms(x, w("ffn_norm_gamma"), eps))
    if i < int(s["num_dense_layers"]):
        return x + _swiglu(f, w("gate_weight"), w("up_weight"),
                           w("down_weight"))
    return x + expert_layer(f, p, b, s)[0]


def logits(p, tokens, cfg, dtype=jnp.float32):
    """Next-token logits (T, vocab) at every position of ``tokens``
    (T,).  ``dtype`` other than float32 rounds every activation that a
    matrix multiplies to that type (the lower-precision control of the
    tests)."""
    s = _spec(cfg)
    f32 = jnp.float32
    low = lambda a: a.astype(dtype).astype(f32)
    embed = p["embed_tokens_weight"].astype(f32)
    x = embed[tokens]
    for i in range(int(s["num_hidden_layers"])):
        x = decoder_layer(x, p, i, s, low)
    x = low(_rms(x, p["final_norm_gamma"].astype(f32),
                 float(s["norm_eps"])))
    return (x @ embed.T).astype(f32)


def served_gaps(p, tokens, first, served, cfg):
    """How far each served token lies below the reference's best.

    ``tokens`` (T,) is prompt + served tokens, padded; the served token
    ``served[j]`` was produced from position ``first + j`` (``served``
    may be padded: rows past the sequence repeat its last position).
    Returns ``(gap (n,), best (n,))``: the reference's top logit minus
    the served token's logit, and the reference's own first choice."""
    z = logits(p, tokens, cfg)
    rows = z[jnp.clip(first + jnp.arange(served.shape[0]), 0,
                      tokens.shape[0] - 1)]
    top = jnp.max(rows, axis=-1)
    mine = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
    return top - mine, jnp.argmax(rows, axis=-1)
