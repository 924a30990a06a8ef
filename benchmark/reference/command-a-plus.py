"""Plain reference of the ``command-a-plus`` configuration: Command A+'s
decoder (config.json of
https://huggingface.co/CohereLabs/command-a-plus-05-2026, ``model_type``
``cohere2_moe``; the norm, the attention with its two masks, the rotary
and the parallel block are those of ``transformers.models.cohere2.
modeling_cohere2``, which the tests hold this file to), given the same
layers, the same share of the experts and the same slice of the
vocabulary as the program.  One whole sequence in one forward pass,
``jax.numpy`` float32: no cache, no block, no window bookkeeping, no
kernel, nothing of the program.

``x`` (T, hidden), no bias anywhere:

* ``LN(x) = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * g``.
* Layer (``use_parallel_block``): ``h = LN(x)``; ``x <- x + attn(h) +
  moe(h)``, both read the SAME ``h``.
* Attention: ``q = h W_q`` -> heads x head_dim, ``k``, ``v`` -> KV
  heads x head_dim, no QK-norm; query head i attends KV head ``i //
  (heads / KV heads)``; softmax at ``head_dim^-0.5``; ``out = o W_o``.
  ``sliding_attention``: rotary over the whole head in INTERLEAVED
  pairs (``x cos + rotate_half(x) sin``, ``rotate_half`` over ``x[...,
  ::2]``, ``x[..., 1::2]``; ``theta = rope_theta``, no scaling) and the
  query at ``p`` sees the keys ``p - sliding_window + 1 .. p``.
  ``full_attention``: NO rotary, every key ``<= p``.
* Expert layer: ``s = sigmoid(h W_r^T)`` over ALL ``router_width``
  experts; the ``num_experts_per_tok`` largest pick (ties to the lower
  index); weights the picked ``s`` over their sum (``norm_topk_prob``;
  the config has no router bias and no scaling factor); ``routed = sum
  w_e E_e(h)`` over the picked experts among the ``num_experts`` HELD
  here (ids ``0 .. held - 1``), ``E_e(h) = (silu(h G_e) * h U_e) D_e``.
  ``shared = (1 / num_shared_experts) sum_i S_i(h)``, each ``S_i`` the
  same gated unit, its matrices the i-th slice of the ``shared_*``
  leaves.  ``moe(h) = routed + shared``.
* Head: ``LN(x) E^T * logit_scale``, ``E`` the TIED embedding.

Departures, each also under ``assumed`` in the configuration's file:
``shared_expert_combination_strategy: "average"`` is read as the mean
of the shared experts' outputs (the other reading, the mean of routed
and shared, differs by a scalar on the layer's output); the width of an
expert is ``intermediate_size``; the vision tower in front of the
published model is left out; what the absent experts would add is left
out, here as in the program.

Cost, not mathematics: attention runs a KV head's query heads at a
time in blocks of queries (a window layer over the keys a block can
see); an expert multiplies the rows that picked it, gathered into
``CAPACITY`` times an expert's mean share of the rows, or every row
under a mask where more picked it: the same sum either way, never a
dropped token; :func:`served_gaps` runs over the shortest of ``WIDTHS``
that holds the sequence, keeps its padding out of the routing, and
asks the LAST layer for the served positions alone (their queries,
their experts, the head; every layer before it still computes every
position, whose keys and values the later ones read).  The weights may
come in bfloat16 (each is cast to float32 where it is used).  The
caller sets ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
# queries a block where a layer is asked for some positions alone
ROW_BLOCK = 256
# rows an expert's gather has room for, over the rows an expert gets on
# average (num_experts_per_tok / router_width of a sequence's: 1 / 16
# as published, so a quarter of the sequence)
CAPACITY = 4.0
# the lengths served_gaps pads a sequence to, as shares of the width it
# is handed
WIDTHS = (0.125, 0.25, 0.5, 0.75, 1.0)


def _spec(cfg):
    return cfg["spec"]


def param_shapes(cfg):
    """name -> shape of every learned leaf, matrices ``(out, in)``; the
    shared experts' matrices side by side (gate and up by rows, down by
    columns), each routed expert's three matrices leaves of their own."""
    s = _spec(cfg)
    d, dh = int(s["hidden_size"]), int(s["head_dim"])
    h, hkv = int(s["num_attention_heads"]), int(s["num_key_value_heads"])
    f = int(s["intermediate_size"])
    sh = f * int(s["num_shared_experts"])
    shapes = {"embed_tokens_weight": (int(s["vocab_size"]), d),
              "final_norm_gamma": (d,)}
    for i in range(int(s["num_hidden_layers"])):
        b = "l%d_" % i
        shapes.update({b + "norm_gamma": (d,),
                       b + "q_weight": (h * dh, d),
                       b + "k_weight": (hkv * dh, d),
                       b + "v_weight": (hkv * dh, d),
                       b + "o_weight": (d, h * dh),
                       b + "router_weight": (int(s["router_width"]), d),
                       b + "shared_gate_weight": (sh, d),
                       b + "shared_up_weight": (sh, d),
                       b + "shared_down_weight": (d, sh)})
        for j in range(int(s["num_experts"])):
            shapes.update({"%se%d_gate_weight" % (b, j): (f, d),
                           "%se%d_up_weight" % (b, j): (f, d),
                           "%se%d_down_weight" % (b, j): (d, f)})
    return shapes


def layer_norm(x, gamma, eps):
    """``Cohere2LayerNorm``: the mean taken off, no bias."""
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gamma


def rotate(x, angle):
    """x (T, heads, dh) turned in INTERLEAVED pairs by ``angle`` (T,
    dh / 2): values ``2j`` and ``2j + 1`` are one pair."""
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(h, p, b, s, kind, low=lambda a: a, rows=None):
    """Grouped-query attention of one layer over one sequence: h (T,
    d), ``p[b + ...]`` the layer's leaves, ``kind`` its layer type ->
    (T, d); with ``rows`` (n,), the queries at those positions alone
    over the keys of all T -> (n, d)."""
    f32 = jnp.float32
    t = h.shape[0]
    if rows is not None:
        return _attention_at(h, p, b, s, kind, low, rows)
    nh, nkv, dh = (int(s["num_attention_heads"]),
                   int(s["num_key_value_heads"]), int(s["head_dim"]))
    per = nh // nkv
    window = int(s["sliding_window"]) if kind == "sliding_attention" \
        else None
    w = lambda name: p[b + name].astype(f32)
    k = low(h @ w("k_weight").T).reshape(t, nkv, dh)
    v = low(h @ w("v_weight").T).reshape(t, nkv, dh)
    wq = w("q_weight").reshape(nkv, per * dh, -1)
    wo = w("o_weight").reshape(-1, nkv, per * dh)
    if window is not None:
        freqs = 1.0 / float(s["rope_theta"]) ** (
            np.arange(0, dh, 2, dtype=np.float64) / dh)
        angle = jnp.arange(t, dtype=f32)[:, None] * jnp.asarray(freqs, f32)
        k = low(rotate(k, angle))
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    # the keys a block of queries can see: all of them, or the block's
    # own and the window's before it
    span = t if window is None else min(t, qb + window - 1)

    def kv_head(out, g):            # a KV head's query heads at a time
        q = low(h @ wq[g].T).reshape(t, per, dh)
        if window is not None:
            q = low(rotate(q, angle))
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        kg, vg = k[:, g], v[:, g]

        def query_block(j):
            a = jax.lax.dynamic_slice_in_dim(q, j * qb, qb, 0)
            at = jnp.clip(j * qb + qb - span, 0, t - span)
            ks = jax.lax.dynamic_slice_in_dim(kg, at, span, 0)
            vs = jax.lax.dynamic_slice_in_dim(vg, at, span, 0)
            sc = jnp.einsum("qhd,kd->hqk", a, ks) * dh ** -0.5
            rows = (j * qb + jnp.arange(qb))[None, :, None]
            keys = (at + jnp.arange(span))[None, None, :]
            seen = rows >= keys
            if window is not None:
                seen &= keys > rows - window
            sc = jnp.where(seen, sc, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(sc, -1), vs)

        o = jax.lax.map(query_block, jnp.arange((t + pad) // qb))
        return out + low(o.reshape(t + pad, per * dh)[:t]) @ wo[:, g].T, None

    # one head after the other (a scan, so that no two heads' (T, d)
    # results are kept side by side)
    return jax.lax.scan(kv_head, jnp.zeros((t, wo.shape[0]), f32),
                        jnp.arange(nkv))[0]


def _attention_at(h, p, b, s, kind, low, rows):
    """:func:`attention` for the queries at positions ``rows`` (n,)
    alone, over every key: a KV head's query heads at a time, in blocks
    of ``ROW_BLOCK`` of them."""
    f32 = jnp.float32
    t, n = h.shape[0], rows.shape[0]
    nh, nkv, dh = (int(s["num_attention_heads"]),
                   int(s["num_key_value_heads"]), int(s["head_dim"]))
    per = nh // nkv
    window = int(s["sliding_window"]) if kind == "sliding_attention" \
        else None
    w = lambda name: p[b + name].astype(f32)
    k = low(h @ w("k_weight").T).reshape(t, nkv, dh)
    v = low(h @ w("v_weight").T).reshape(t, nkv, dh)
    wq = w("q_weight").reshape(nkv, per * dh, -1)
    wo = w("o_weight").reshape(-1, nkv, per * dh)
    if window is not None:
        freqs = jnp.asarray(1.0 / float(s["rope_theta"]) ** (
            np.arange(0, dh, 2, dtype=np.float64) / dh), f32)
        k = low(rotate(k, jnp.arange(t, dtype=f32)[:, None] * freqs))
    rb = min(ROW_BLOCK, n)
    pad = -n % rb
    at = jnp.pad(rows, (0, pad))
    keys = jnp.arange(t)[None, None, :]

    def kv_head(out, g):
        q = low(h[at] @ wq[g].T).reshape(n + pad, per, dh)
        if window is not None:
            q = low(rotate(q, at.astype(f32)[:, None] * freqs))
        kg, vg = k[:, g], v[:, g]

        def row_block(j):
            a = jax.lax.dynamic_slice_in_dim(q, j * rb, rb, 0)
            mine = jax.lax.dynamic_slice_in_dim(at, j * rb, rb, 0)
            sc = jnp.einsum("qhd,kd->hqk", a, kg) * dh ** -0.5
            seen = mine[None, :, None] >= keys
            if window is not None:
                seen &= keys > mine[None, :, None] - window
            sc = jnp.where(seen, sc, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(sc, -1), vg)

        o = jax.lax.map(row_block, jnp.arange((n + pad) // rb))
        return out + low(o.reshape(n + pad, per * dh)[:n]) @ wo[:, g].T, \
            None

    return jax.lax.scan(kv_head, jnp.zeros((n, wo.shape[0]), f32),
                        jnp.arange(nkv))[0]


def gated(h, gate, up, down):
    """``(silu(h G) * h U) D``, the matrices ``(out, in)``."""
    return (jax.nn.silu(h @ gate.T) * (h @ up.T)) @ down.T


def shared_experts(h, p, b, s):
    """The mean of the shared experts' outputs, each a gated unit over
    its own slice of the ``shared_*`` leaves, one after the other."""
    f32 = jnp.float32
    n, f = int(s["num_shared_experts"]), int(s["intermediate_size"])
    gate = p[b + "shared_gate_weight"].reshape(n, f, -1)
    up = p[b + "shared_up_weight"].reshape(n, f, -1)
    down = p[b + "shared_down_weight"].reshape(-1, n, f)

    def one(total, i):
        return total + gated(h, gate[i].astype(f32), up[i].astype(f32),
                             down[:, i].astype(f32)), None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(n))[0] / n


def route(scores, s):
    """(picked (T, k) int32, weights (T, k)): the k largest scores (a
    stable descending sort: ties to the lower index), the picked scores
    over their sum."""
    k = int(s["num_experts_per_tok"])
    picked = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, picked, axis=1)
    return picked.astype(jnp.int32), w / w.sum(-1, keepdims=True)


def routed_experts(h, p, b, s, held=None, capacity=CAPACITY, live=None):
    """``sum w_e E_e(h)`` over the picked experts among those HELD
    (``held``: their ids, default ``0 .. num_experts - 1``; leaf ``e<j>``
    is the j-th of them).  An expert multiplies only the rows that
    picked it, gathered in their order into ``capacity`` times an
    expert's mean share of the T rows; an expert picked by more
    multiplies every row under a mask instead (the same sum, dearer).
    ``live`` (T,) marks the rows that are read (:func:`served_gaps`'
    padding is not: a thousand copies of one token pick one expert).
    Returns ``(y, picked, weights)``."""
    f32 = jnp.float32
    t = h.shape[0]
    scores = jax.nn.sigmoid(h @ p[b + "router_weight"].astype(f32).T)
    picked, w = route(scores, s)
    held = range(int(s["num_experts"])) if held is None else held
    room = min(t, int(np.ceil(t * capacity * int(s["num_experts_per_tok"])
                              / int(s["router_width"]))))
    y = jnp.zeros_like(h)
    for j, e in enumerate(held):
        mine = jnp.sum(jnp.where(picked == e, w, 0.0), axis=-1)   # (T,)
        if live is not None:
            mine = jnp.where(live, mine, 0.0)
        # cast where they are used: a float32 copy made out here would
        # be kept for both branches, and 64 experts' copies at once
        leaves = [p["%se%d_%s_weight" % (b, j, m)]
                  for m in ("gate", "up", "down")]

        def gathered(y, mine=mine, leaves=leaves):
            rows = jnp.nonzero(mine > 0, size=room, fill_value=t)[0]
            got = gated(jnp.take(h, rows, axis=0, mode="fill",
                                 fill_value=0),
                        *[a.astype(f32) for a in leaves])
            got = got * jnp.take(mine, rows, mode="fill",
                                 fill_value=0)[:, None]
            return y.at[rows].add(got, mode="drop")

        def masked(y, mine=mine, leaves=leaves):
            return y + mine[:, None] * gated(
                h, *[a.astype(f32) for a in leaves])

        # the sum is handed through, so an expert's (T, d) part is
        # added before the next expert's is made
        y = masked(y) if room >= t else jax.lax.cond(
            jnp.sum(mine > 0) > room, masked, gathered, y)
    return y, picked, w


def decoder_layer(x, p, i, s, low=lambda a: a, ffn=None, live=None,
                  rows=None):
    """Layer ``i`` over one sequence x (T, d): the parallel block.
    ``ffn`` (the tests'): what stands beside the attention in place of
    the expert layer, a function of the normed rows.  ``rows`` (n,):
    the positions whose output is wanted, and only theirs is returned
    (n, d): every key and value of the layer still comes from all T
    rows, but its queries, its output projection and its experts are
    those positions' alone (the last layer of :func:`served_gaps`)."""
    f32 = jnp.float32
    b = "l%d_" % i
    h = low(layer_norm(x, p[b + "norm_gamma"].astype(f32),
                       float(s["layer_norm_eps"])))
    if ffn is None:
        ffn = lambda of: routed_experts(
            of, p, b, s, live=live if rows is None else None)[0] \
            + shared_experts(of, p, b, s)
    kind = s["layer_types"][i]
    if rows is None:
        return x + attention(h, p, b, s, kind, low) + ffn(h)
    return x[rows] + attention(h, p, b, s, kind, low, rows) + ffn(h[rows])


def hidden(p, tokens, cfg, dtype=jnp.float32, live=None, rows=None):
    """The last layer's output for ``tokens`` (T,): (T, d), or (n, d)
    at the positions ``rows`` alone."""
    s = _spec(cfg)
    low = lambda a: a.astype(dtype).astype(jnp.float32)
    x = p["embed_tokens_weight"].astype(jnp.float32)[tokens]
    last = int(s["num_hidden_layers"]) - 1
    for i in range(last + 1):
        x = decoder_layer(x, p, i, s, low, live=live,
                          rows=rows if i == last else None)
    return x


def head(x, p, cfg, dtype=jnp.float32):
    """``LN(x) E^T * logit_scale`` over rows x (n, d)."""
    s = _spec(cfg)
    f32 = jnp.float32
    x = layer_norm(x, p["final_norm_gamma"].astype(f32),
                   float(s["layer_norm_eps"])).astype(dtype).astype(f32)
    return (x @ p["embed_tokens_weight"].astype(f32).T) \
        * float(s.get("logit_scale", 1.0))


def logits(p, tokens, cfg, dtype=jnp.float32):
    """Next-token logits (T, vocab) at every position of ``tokens``
    (T,).  ``dtype`` other than float32 rounds every activation that a
    matrix multiplies to that type (the lower-precision control of the
    tests)."""
    return head(hidden(p, tokens, cfg, dtype), p, cfg, dtype)


def served_gaps(p, tokens, first, served, cfg):
    """How far each served token lies below the reference's best.

    ``tokens`` (T,) is prompt + served tokens, padded; the served token
    ``served[j]`` was produced from position ``first + j`` (``served``
    may be padded: rows past the sequence repeat its last position).
    Returns ``(gap (n,), best (n,))``: the reference's top logit minus
    the served token's logit, and the reference's own first choice.
    Positions past ``first + n`` are padding and no row read depends on
    them (attention is causal), so the forward pass runs over the
    shortest of ``WIDTHS`` that holds them."""
    t, n = tokens.shape[0], served.shape[0]
    widths = sorted({min(t, max(n, int(np.ceil(t * share))))
                     for share in WIDTHS})

    need = first + n                      # positions 0 .. first + n - 1

    def over(width):
        def run(_):
            rows = jnp.clip(first + jnp.arange(n), 0, width - 1)
            z = head(hidden(p, tokens[:width], cfg, rows=rows,
                            live=jnp.arange(width) < need), p, cfg)
            top = jnp.max(z, axis=-1)
            mine = jnp.take_along_axis(z, served[:, None], axis=-1)[:, 0]
            return top - mine, jnp.argmax(z, axis=-1).astype(jnp.int32)
        return run

    pick = jnp.sum(jnp.asarray(widths) < need).clip(0, len(widths) - 1)
    return jax.lax.switch(pick, [over(w) for w in widths], None)
