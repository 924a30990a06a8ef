"""Plain reference of the ``openpangu-ultra-moe`` configuration:
openPangu-Ultra-MoE-718B's decoder (config.json of
https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B,
``model_type: pangu_ultra_moe``; Pangu Ultra MoE, arXiv:2505.04519; the
sandwich norm is Pangu Ultra's, arXiv:2504.07866) AND its
multi-token-prediction module (after DeepSeek-V3, arXiv:2412.19437
section 2.2, which the config's ``num_nextn_predict_layers`` names),
given the same share of the model as the program: ``n_routed_experts``
experts HELD of the ``router_width`` the router scores, the leading
slice of the vocabulary, the first layers.  One whole sequence in one
forward pass, ``jax.numpy`` float32: no cache, no absorbed form, no
sorting, no kernel, nothing of the program.

Per layer ``l``, input ``x`` (T, hidden), RMSNorm everywhere (eps
``rms_norm_eps``, float32):

    a   = MLA(norm_in(x))        # q = W_qb norm_q(W_qa h); [c_kv | k_r] =
                                 # W_kva h, c_kv normed, rotary on the 64
                                 # rope dims (ADJACENT pairs, plain
                                 # frequencies theta^(-2i/64): the config
                                 # has no rope_scaling), [k_nope | v] =
                                 # c_kv W_kvb, causal softmax of (q_nope .
                                 # k_nope + q_rope . k_r)(128 + 64)^-0.5,
                                 # out = (softmax . v) W_o
    y   = x + norm_post_attn(a)  # SANDWICH: the sublayer's OUTPUT is
                                 # normed before it joins the residual
    f   = FFN(norm_pre_mlp(y))   # layers 0 .. first_k_dense_replace - 1:
                                 # SwiGLU of intermediate_size; after:
                                 # shared SwiGLU + the routed experts
    out = y + norm_post_mlp(f)
    router: s = sigmoid(W_r h) over router_width; the num_experts_per_tok
            largest s (no groups, no bias; a stable descending sort, ties
            to the lower index); w = s_i / sum(s_chosen) * scale.  The
            sum runs over the picked experts AMONG THE HELD (ids 0 ..
            n_routed_experts - 1); what the absent experts would add is
            left out, as in the program, and ``norm_post_mlp`` is applied
            to that PARTIAL sum (in a deployment it follows the
            exchange's combine).
    head: RMSNorm, then the untied head's slice.

The module (ONE), for position j >= 1 of a sequence x_0 x_1 ...:

    u_j = W_eh [ norm_h(h_{j-1}) ; norm_e(Emb(x_j)) ]
    v   = Layer_mtp(u)           # one whole expert layer as above; its
                                 # attention runs over rows 1 .. j (row 0
                                 # does not exist: there is no h_{-1})
    draft logits for position j + 1 = Head(norm_final_mtp(v_j))

``h`` is the target's last layer output BEFORE the final norm; ``Emb``
and ``Head`` are the target's (shared).  The order inside ``[ . ; . ]``
is the hidden state first (immaterial under seeded weights; stated in
``configs/openpangu-ultra-moe.json`` under ``assumed``).

Attention runs in blocks of heads and queries, a routed expert over the
rows that picked it (gathered, ``EXPERT_ROWS`` at most, every row under
a mask where more did: the same sum), and the weights may come in
bfloat16 (each is cast to float32 where it is used).  The caller sets
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 16
QUERY_BLOCK = 512
# a held expert is picked by one row in router_width / num_experts_per_tok
# (32 at the published widths): four times the mean of a 4,096-row pass
EXPERT_ROWS = 512


def _spec(cfg):
    return cfg["spec"]


def _drafts(cfg):
    return int((cfg.get("deploy") or {}).get("self_draft") or 0)


def param_shapes(cfg):
    """name -> shape of every learned leaf, matrices ``(out, in)``, each
    held expert's three matrices leaves of their own; the module's
    (``mtp_…``) for a configuration that deploys it (``self_draft``)."""
    s = _spec(cfg)
    d, h = int(s["hidden_size"]), int(s["num_attention_heads"])
    rq, r = int(s["q_lora_rank"]), int(s["kv_lora_rank"])
    dn, dr, dv = (int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
                  int(s["v_head_dim"]))
    f, v = int(s["moe_intermediate_size"]), int(s["vocab_size"])
    shapes = {"embed_weight": (v, d), "final_norm_gamma": (d,),
              "head_weight": (v, d)}
    layers = [("l%d_" % i, i < int(s["first_k_dense_replace"]))
              for i in range(int(s["num_hidden_layers"]))]
    if _drafts(cfg):
        layers.append(("mtp_", False))
        shapes.update({"mtp_h_norm_gamma": (d,), "mtp_e_norm_gamma": (d,),
                       "mtp_eh_weight": (d, 2 * d),
                       "mtp_final_norm_gamma": (d,)})
    for b, dense in layers:
        shapes.update({
            b + "attn_norm_gamma": (d,), b + "q_a_weight": (rq, d),
            b + "q_norm_gamma": (rq,),
            b + "q_b_weight": (h * (dn + dr), rq),
            b + "kv_a_weight": (r + dr, d), b + "kv_norm_gamma": (r,),
            b + "kv_b_weight": (h * (dn + dv), r),
            b + "o_weight": (d, h * dv), b + "ffn_norm_gamma": (d,),
            b + "post_attn_norm_gamma": (d,),
            b + "post_ffn_norm_gamma": (d,)})
        if dense:
            w = int(s["intermediate_size"])
            shapes.update({b + "gate_weight": (w, d),
                           b + "up_weight": (w, d),
                           b + "down_weight": (d, w)})
            continue
        sh = f * int(s["n_shared_experts"])
        shapes.update({
            b + "router_weight": (int(s["router_width"]), d),
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
    """Plain rotary frequencies ``theta^(-2i / dim)``: no YaRN."""
    dim, base = int(s["qk_rope_head_dim"]), float(s["rope_theta"])
    return np.asarray(
        1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim),
        np.float32)


def _rope(x, angle):
    """x (..., T, rope) with adjacent pairs turned by ``angle`` (T,
    rope / 2)."""
    pair = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pair[..., 0], pair[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def route(scores, s):
    """(picked (T, k) int32, weights (T, k)) of the plain top-k router:
    a stable descending sort, so ties go to the lower index."""
    k = int(s["num_experts_per_tok"])
    picked = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, picked, axis=1)
    w = w / w.sum(-1, keepdims=True) * float(s["routed_scaling_factor"])
    return picked.astype(jnp.int32), w


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.T) * (h @ up.T)) @ down.T


def _attention(q_nope, q_rope, k_nope, k_rope, v, scale, first=0):
    """Causal softmax attention, plain form, in blocks of heads and
    queries; keys before row ``first`` are seen by no query.  q_nope (T,
    H, dn), q_rope (T, H, dr), k_nope (T, H, dn), k_rope (T, dr), v (T,
    H, dv) -> (T, H, dv)."""
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
            seen = (rows[:, None] >= keys[None, :]) & (keys >= first)
            # a row that sees no key (row 0 of the module) reads zeros
            w = jnp.where(seen[None], jax.nn.softmax(
                jnp.where(seen[None], sc, -1e30), -1), 0.0)
            return jnp.einsum("hqk,khd->qhd", w, vv)

        out = jax.lax.map(query_block, jnp.arange((t + pad) // qb))
        return out.reshape(t + pad, hb, -1)[:t]

    out = jax.lax.map(head_block, jnp.arange(h // hb))  # (h/hb, T, hb, dv)
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(t, h, -1)


def expert_layer(h, p, b, s, held=None):
    """``shared(h) + sum w_e expert_e(h)`` over the picked experts among
    ``held`` (default: ids ``0 .. n_routed_experts - 1``), BEFORE the
    post-feed-forward norm.  Returns ``(y, picked, weights)``."""
    f32 = jnp.float32
    rows = h.shape[0]
    scores = jax.nn.sigmoid(h @ p[b + "router_weight"].astype(f32).T)
    picked, w = route(scores, s)
    y = _swiglu(h, p[b + "shared_gate_weight"].astype(f32),
                p[b + "shared_up_weight"].astype(f32),
                p[b + "shared_down_weight"].astype(f32))

    def every(mine, gate, up, down):
        return mine[:, None] * _swiglu(
            h, gate.astype(f32), up.astype(f32), down.astype(f32))

    def gathered(mine, gate, up, down):
        # the rows with a weight, in order; the places left over point
        # past the last row: they read zeros and add nothing
        at = jnp.nonzero(mine != 0, size=EXPERT_ROWS, fill_value=rows)[0]
        got = mine.at[at].get(mode="fill", fill_value=0.0)[:, None] \
            * _swiglu(h.at[at].get(mode="fill", fill_value=0.0),
                      gate.astype(f32), up.astype(f32), down.astype(f32))
        return jnp.zeros_like(h).at[at].add(got, mode="drop")

    for e in (range(int(s["n_routed_experts"])) if held is None else held):
        mine = jnp.sum(jnp.where(picked == e, w, 0.0), axis=-1)
        mats = [p["%se%d_%s_weight" % (b, e, m)]
                for m in ("gate", "up", "down")]
        if rows <= EXPERT_ROWS:
            y = y + every(mine, *mats)
        else:
            y = y + jax.lax.cond(jnp.sum(mine != 0) <= EXPERT_ROWS,
                                 gathered, every, mine, *mats)
    return y, picked, w


def layer(x, p, b, s, dense, angle, low, first=0):
    """One sandwich-normed decoder layer over ``x`` (T, hidden) with the
    leaves under prefix ``b``."""
    h = int(s["num_attention_heads"])
    r = int(s["kv_lora_rank"])
    dn, dr, dv = (int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
                  int(s["v_head_dim"]))
    eps = float(s["rms_norm_eps"])
    f32 = jnp.float32
    t = x.shape[0]
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
                     low(k_rope), low(kvb[..., dn:]), (dn + dr) ** -0.5,
                     first)
    att = low(att.reshape(t, h * dv)) @ w("o_weight").T
    x = x + _rms(att, w("post_attn_norm_gamma"), eps)
    f = low(_rms(x, w("ffn_norm_gamma"), eps))
    if dense:
        y = _swiglu(f, w("gate_weight"), w("up_weight"), w("down_weight"))
    else:
        y = expert_layer(f, p, b, s)[0]
    return x + _rms(y, w("post_ffn_norm_gamma"), eps)


def hidden(p, tokens, cfg, dtype=jnp.float32):
    """The target's last layer output BEFORE the final norm, (T,
    hidden).  ``dtype`` other than float32 rounds every activation that
    a matrix multiplies to that type (the tests' lower-precision
    control)."""
    s = _spec(cfg)
    f32 = jnp.float32
    t = tokens.shape[0]
    angle = jnp.arange(t, dtype=f32)[:, None] * jnp.asarray(frequencies(s))
    low = lambda a: a.astype(dtype).astype(f32)
    x = p["embed_weight"][tokens].astype(f32)
    for i in range(int(s["num_hidden_layers"])):
        x = layer(x, p, "l%d_" % i, s,
                  i < int(s["first_k_dense_replace"]), angle, low)
    return x


def _head(x, gamma, p, cfg, dtype):
    f32 = jnp.float32
    x = _rms(x, p[gamma].astype(f32), float(_spec(cfg)["rms_norm_eps"]))
    return (x.astype(dtype).astype(f32)
            @ p["head_weight"].astype(f32).T).astype(f32)


def logits(p, tokens, cfg, dtype=jnp.float32):
    """Next-token logits (T, vocab) at every position of ``tokens``."""
    return _head(hidden(p, tokens, cfg, dtype), "final_norm_gamma", p,
                 cfg, dtype)


def module_hidden(p, tokens, h, cfg, dtype=jnp.float32):
    """The prediction module over the whole sequence, teacher-forced:
    row ``j`` from ``h[j - 1]`` (the target's hidden state of the
    position before) and ``tokens[j]``; row 0 is not the module's (it
    is computed from a zero hidden state and no row sees it)."""
    s = _spec(cfg)
    f32 = jnp.float32
    eps = float(s["rms_norm_eps"])
    t = tokens.shape[0]
    angle = jnp.arange(t, dtype=f32)[:, None] * jnp.asarray(frequencies(s))
    low = lambda a: a.astype(dtype).astype(f32)
    before = jnp.concatenate([jnp.zeros_like(h[:1]), h[:-1]], axis=0)
    e = p["embed_weight"][tokens].astype(f32)
    u = jnp.concatenate(
        [_rms(before, p["mtp_h_norm_gamma"].astype(f32), eps),
         _rms(e, p["mtp_e_norm_gamma"].astype(f32), eps)], axis=-1)
    u = low(u) @ p["mtp_eh_weight"].astype(f32).T
    return layer(u, p, "mtp_", s, False, angle, low, first=1)


def draft_logits(p, tokens, cfg, dtype=jnp.float32):
    """The module's draft logits (T, vocab): row ``j`` (>= 1) drafts
    the token at position ``j + 1`` from the sequence up to ``j``."""
    v = module_hidden(p, tokens, hidden(p, tokens, cfg, dtype), cfg, dtype)
    return _head(v, "mtp_final_norm_gamma", p, cfg, dtype)


def _gaps(rows, mine):
    top = jnp.max(rows, axis=-1)
    got = jnp.take_along_axis(rows, mine[:, None], axis=-1)[:, 0]
    return top - got, jnp.argmax(rows, axis=-1)


def served_gaps(p, tokens, first, served, cfg):
    """How far each served token lies below the reference's best.

    ``tokens`` (T,) is prompt + served tokens, padded; the served token
    ``served[j]`` was produced from position ``first + j`` (``served``
    may be padded: rows past the sequence repeat its last position).
    Returns ``(gap (n,), best (n,))``: the reference's top logit minus
    the served token's logit, and the reference's own first choice."""
    return served_both(p, tokens, first, served, None, cfg)[:2]


def served_both(p, tokens, first, served, proposed, cfg):
    """:func:`served_gaps` and its twin for the PROPOSALS in one pass.

    ``proposed[j]`` is the token the program's module proposed for the
    position after ``served[j]``'s, i.e. what it drafted at row ``first
    + j + 1`` of the sequence from the target's hidden state at ``first
    + j`` and the token ``served[j]`` (negative: none was made, or it
    lies past the compared tokens).  Returns ``(gap, best, draft_gap
    (n,), draft_best (n,))``: the reference module's top draft logit
    minus the proposed token's, and the reference module's own choice
    there (``proposed`` None: the first two alone)."""
    h = hidden(p, tokens, cfg)
    at = jnp.clip(first + jnp.arange(served.shape[0]), 0,
                  tokens.shape[0] - 1)
    out = _gaps(_head(h[at], "final_norm_gamma", p, cfg, jnp.float32),
                served)
    if proposed is None:
        return out
    v = module_hidden(p, tokens, h, cfg)
    rows = _head(v[jnp.clip(at + 1, 0, tokens.shape[0] - 1)],
                 "mtp_final_norm_gamma", p, cfg, jnp.float32)
    return out + _gaps(rows, jnp.maximum(proposed, 0))
