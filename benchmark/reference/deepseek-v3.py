"""Plain reference of the ``deepseek-v3`` configuration: DeepSeek-V3's
decoder (config.json of https://huggingface.co/deepseek-ai/DeepSeek-V3,
arXiv:2412.19437, the released ``inference/model.py``), given the same
share of the model as the program: ``n_routed_experts`` experts HELD of
the ``router_width`` the router scores, the leading slice of the
vocabulary, the first layers.  One whole sequence in one forward pass,
``jax.numpy`` float32: no cache, no absorbed form, no sorting, no
kernel, nothing of the program.

Per layer, ``x`` (T, hidden), RMSNorm eps ``rms_norm_eps``, no bias but
the router's correction bias:

* MLA, PLAIN form.  ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads
  x (nope | rope).  ``[c_kv | k_r] = x W_kva``; ``c_kv <- RMSNorm(c_kv)``;
  ``k_r <- RoPE(k_r)``, one row for all heads; ``q_rope <- RoPE(q_rope)``.
  ``[k_nope | v] = c_kv W_kvb`` -> heads x (nope | v).  ``score =
  (q_nope . k_nope + q_rope . k_r) s``, causal softmax, ``out = (softmax
  . v) W_o``; ``s = (nope + rope)^-0.5 m^2``, ``m = 0.1 ln(factor) + 1``.
  RoPE turns ADJACENT pairs; frequencies YaRN-corrected (``beta_fast``,
  ``beta_slow``, ``factor``, ``original_max_position_embeddings``).
* Dense layers (the first ``first_k_dense_replace``): ``x + W_down(silu(
  W_gate h) * W_up h)``.
* Expert layers: ``sigma = sigmoid(h W_g^T)``; choice ``sigma + b``; a
  group's score the sum of its two largest choices; the ``topk_group``
  best of ``n_group`` groups stay; the ``num_experts_per_tok`` best
  choices among them pick; weights the unbiased ``sigma`` of the picked
  over their sum, times ``routed_scaling_factor``; ties to the lower
  index.  ``y = shared(h) + sum w_e expert_e(h)``, the sum over the
  picked experts AMONG THE HELD (ids ``0 .. n_routed_experts - 1``): a
  dense loop over the held experts under a mask.  What the absent
  experts would add is left out, as in the program (departure from the
  source, the chip's share of 16-way expert parallelism).
* Head: RMSNorm, then the untied head's slice.
* Left out: the multi-token-prediction module (not part of inference).

Attention runs in blocks of heads and queries and the weights may come
in bfloat16 (each is cast to float32 where it is used), so that a
6,656-token request fits beside 9 GB of weights.  The caller sets
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 16
QUERY_BLOCK = 512


def _spec(cfg):
    return cfg["spec"]


def param_shapes(cfg):
    """name -> shape of every learned leaf, matrices ``(out, in)``, each
    held expert's three matrices leaves of their own."""
    s = _spec(cfg)
    d, h = int(s["hidden_size"]), int(s["num_attention_heads"])
    rq, r = int(s["q_lora_rank"]), int(s["kv_lora_rank"])
    dn, dr, dv = (int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
                  int(s["v_head_dim"]))
    f, v = int(s["moe_intermediate_size"]), int(s["vocab_size"])
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "head_weight": (v, d)}
    for i in range(int(s["num_hidden_layers"])):
        b = "l%d_" % i
        shapes.update({
            b + "attn_norm_gamma": (d,), b + "q_a_weight": (rq, d),
            b + "q_norm_gamma": (rq,),
            b + "q_b_weight": (h * (dn + dr), rq),
            b + "kv_a_weight": (r + dr, d), b + "kv_norm_gamma": (r,),
            b + "kv_b_weight": (h * (dn + dv), r),
            b + "o_weight": (d, h * dv), b + "ffn_norm_gamma": (d,)})
        if i < int(s["first_k_dense_replace"]):
            w = int(s["intermediate_size"])
            shapes.update({b + "gate_weight": (w, d),
                           b + "up_weight": (w, d),
                           b + "down_weight": (d, w)})
            continue
        sh = f * int(s["n_shared_experts"])
        shapes.update({
            b + "router_weight": (int(s["router_width"]), d),
            b + "router_bias": (int(s["router_width"]),),
            b + "shared_gate_weight": (sh, d),
            b + "shared_up_weight": (sh, d),
            b + "shared_down_weight": (d, sh)})
        for e in range(int(s["n_routed_experts"])):
            shapes.update({
                "%se%d_gate_weight" % (b, e): (f, d),
                "%se%d_up_weight" % (b, e): (f, d),
                "%se%d_down_weight" % (b, e): (d, f)})
    return shapes


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gamma


def frequencies(s):
    """Rotary frequencies with the YaRN correction of the released
    ``precompute_freqs_cis``."""
    dim, base = int(s["qk_rope_head_dim"]), float(s["rope_theta"])
    sc = s["rope_scaling"]
    orig = float(sc["original_max_position_embeddings"])
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(sc["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    smooth = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    factor = float(sc["factor"])
    return np.asarray(freqs / factor * (1 - smooth) + freqs * smooth,
                      np.float32)


def _rope(x, angle):
    """x (..., T, rope) with adjacent pairs turned by ``angle`` (T,
    rope / 2)."""
    pair = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pair[..., 0], pair[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def route(scores, bias, s):
    """(picked (T, k) int32, weights (T, k)) of the group-limited top-k
    router; a stable descending sort, so ties go to the lower index."""
    t, e = scores.shape
    groups, keep = int(s["n_group"]), int(s["topk_group"])
    k = int(s["num_experts_per_tok"])
    choice = scores + bias[None, :]
    per = e // groups
    grouped = -jnp.sort(-choice.reshape(t, groups, per), axis=-1)
    group_score = grouped[..., :2].sum(-1)
    best = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
    kept = (best[:, :, None] == jnp.arange(groups)[None, None, :]).any(1)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)
    picked = jnp.argsort(-masked, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, picked, axis=1)
    w = w / w.sum(-1, keepdims=True) * float(s["routed_scaling_factor"])
    return picked.astype(jnp.int32), w


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.T) * (h @ up.T)) @ down.T


def _attention(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal softmax attention, plain form, in blocks of heads and
    queries.  q_nope (T, H, dn), q_rope (T, H, dr), k_nope (T, H, dn),
    k_rope (T, dr), v (T, H, dv) -> (T, H, dv)."""
    t, h, _ = q_nope.shape
    hb = min(HEAD_BLOCK, h)
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    keys = jnp.arange(t)

    def head_block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * hb, hb, 1)
        qn, qr, kn, vv = sl(q_nope), sl(q_rope), sl(k_nope), sl(v)
        qn = jnp.pad(qn, ((0, pad), (0, 0), (0, 0)))
        qr = jnp.pad(qr, ((0, pad), (0, 0), (0, 0)))

        def query_block(j):
            a = jax.lax.dynamic_slice_in_dim(qn, j * qb, qb, 0)
            b = jax.lax.dynamic_slice_in_dim(qr, j * qb, qb, 0)
            sc = (jnp.einsum("qhd,khd->hqk", a, kn)
                  + jnp.einsum("qhd,kd->hqk", b, k_rope)) * scale
            rows = j * qb + jnp.arange(qb)
            sc = jnp.where(rows[None, :, None] >= keys[None, None, :],
                           sc, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vv)

        out = jax.lax.map(query_block, jnp.arange((t + pad) // qb))
        return out.reshape(t + pad, hb, -1)[:t]

    out = jax.lax.map(head_block, jnp.arange(h // hb))  # (h/hb, T, hb, dv)
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(t, h, -1)


def expert_layer(h, p, b, s, held=None):
    """``shared(h) + sum w_e expert_e(h)`` over the picked experts among
    ``held`` (default: ids ``0 .. n_routed_experts - 1``), a dense loop
    under a mask.  Returns ``(y, picked, weights)``."""
    f32 = jnp.float32
    scores = jax.nn.sigmoid(h @ p[b + "router_weight"].astype(f32).T)
    picked, w = route(scores, p[b + "router_bias"].astype(f32), s)
    y = _swiglu(h, p[b + "shared_gate_weight"].astype(f32),
                p[b + "shared_up_weight"].astype(f32),
                p[b + "shared_down_weight"].astype(f32))
    for e in (range(int(s["n_routed_experts"])) if held is None else held):
        mine = jnp.sum(jnp.where(picked == e, w, 0.0), axis=-1)
        y = y + mine[:, None] * _swiglu(
            h, p["%se%d_gate_weight" % (b, e)].astype(f32),
            p["%se%d_up_weight" % (b, e)].astype(f32),
            p["%se%d_down_weight" % (b, e)].astype(f32))
    return y, picked, w


def logits(p, tokens, cfg, dtype=jnp.float32):
    """Next-token logits (T, vocab) at every position of ``tokens``
    (T,).  ``dtype`` other than float32 rounds every activation that a
    matrix multiplies to that type (the lower-precision control of the
    tests)."""
    s = _spec(cfg)
    h = int(s["num_attention_heads"])
    r = int(s["kv_lora_rank"])
    dn, dr, dv = (int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
                  int(s["v_head_dim"]))
    eps = float(s["rms_norm_eps"])
    f32 = jnp.float32
    t = tokens.shape[0]
    m = 0.1 * math.log(float(s["rope_scaling"]["factor"])) + 1.0
    scale = (dn + dr) ** -0.5 * m * m
    angle = jnp.arange(t, dtype=f32)[:, None] * jnp.asarray(frequencies(s))
    low = lambda a: a.astype(dtype).astype(f32)

    x = p["embed_weight"][tokens].astype(f32)
    for i in range(int(s["num_hidden_layers"])):
        b = "l%d_" % i
        w = lambda name: p[b + name].astype(f32)
        a = low(_rms(x, w("attn_norm_gamma"), eps))
        cq = low(_rms(a @ w("q_a_weight").T, w("q_norm_gamma"), eps))
        q = (cq @ w("q_b_weight").T).reshape(t, h, dn + dr)
        kv = a @ w("kv_a_weight").T
        c_kv = low(_rms(kv[:, :r], w("kv_norm_gamma"), eps))
        k_rope = _rope(kv[:, r:], angle)
        q_rope = _rope(q[..., dn:], angle[:, None, :])
        kvb = (c_kv @ w("kv_b_weight").T).reshape(t, h, dn + dv)
        att = _attention(low(q[..., :dn]), low(q_rope), low(kvb[..., :dn]),
                         low(k_rope), low(kvb[..., dn:]), scale)
        x = x + low(att.reshape(t, h * dv)) @ w("o_weight").T
        f = low(_rms(x, w("ffn_norm_gamma"), eps))
        if i < int(s["first_k_dense_replace"]):
            x = x + _swiglu(f, w("gate_weight"), w("up_weight"),
                            w("down_weight"))
        else:
            x = x + expert_layer(f, p, b, s)[0]
    x = low(_rms(x, p["final_norm_gamma"].astype(f32), eps))
    return (x @ p["head_weight"].astype(f32).T).astype(f32)


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
