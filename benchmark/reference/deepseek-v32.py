"""Plain reference of the ``deepseek-v32`` configuration: DeepSeek-V3.2's
decoder (config.json of https://huggingface.co/deepseek-ai/DeepSeek-V3.2;
the ``Indexer`` and ``MLA`` classes of the released ``inference/model.py``
of DeepSeek-V3.2-Exp), given the same share of the model as the program:
``n_routed_experts`` experts HELD of the ``router_width`` the router
scores, the leading slice of the vocabulary, the first layers.  One
whole sequence in one forward pass, ``jax.numpy`` float32: no cache, no
absorbed form, no gather of selected rows, no kernel, nothing of the
program.  Everything but the indexer is ``reference/deepseek-v3.py``'s
(copied here, not imported: that file is another configuration's).

Per layer, ``x`` (T, hidden), RMSNorm eps ``rms_norm_eps``, ``a =
RMSNorm(x)``:

* MLA, PLAIN form.  ``c_q = RMSNorm(a W_qa)``; ``q = c_q W_qb`` -> heads
  x (nope | rope).  ``[c_kv | k_r] = a W_kva``; ``c_kv <- RMSNorm(c_kv)``;
  ``k_r <- RoPE(k_r)``, one row for all heads; ``q_rope <- RoPE(q_rope)``.
  ``[k_nope | v] = c_kv W_kvb`` -> heads x (nope | v).  ``score =
  (q_nope . k_nope + q_rope . k_r) s``, ``s = (nope + rope)^-0.5 m^2``,
  ``m = 0.1 ln(factor) + 1``.  RoPE turns ADJACENT pairs; frequencies
  YaRN-corrected.
* LIGHTNING INDEXER.  ``q_idx = c_q W_iqb`` -> ``index_n_heads`` x
  ``index_head_dim``, a head ``[rope | nope]`` (the rotary part FIRST);
  ``k_idx = LayerNorm(a W_ik)`` (scale and bias, eps 1e-6), ONE head,
  split alike; both rotary parts turned by MLA's frequencies;
  ``w = (a W_iw) heads^-0.5 dim^-0.5``; ``I[t, s] = sum_j w[t, j]
  relu(q_idx[t, j] . k_idx[s])`` for ``s <= t``.
* SELECTION.  ``S_t`` = the positions of the ``min(index_topk, t + 1)``
  largest ``I[t, .]`` (``jax.lax.top_k``: a tie at the last place to
  the lower position).  MLA's softmax runs over ``s in S_t`` alone, the
  other positions at ``-inf``; ``out = (softmax . v) W_o``.
* Dense layers, expert layers (the held share of a group-limited
  sigmoid router, the shared expert) and the head: ``deepseek-v3``'s.

Departures from the released code, each also under ``assumed`` in
``configs/deepseek-v32.json``: (1) no Hadamard rotation of ``q_idx`` and
``k_idx`` (orthogonal: it leaves every ``q . k`` as it is) and no FP8
rounding of them or of the key cache (the v5e multiplies no FP8); (2)
the indexer's rotary turns the two HALVES of the rotary part against
each other (non-interleaved) where MLA's turns adjacent pairs; (3)
LayerNorm's eps 1e-6 (the released ``LayerNorm`` class); (4) no bias on
the three new projections; (5) the multi-token-prediction module left
out; (6) what the absent experts would add is left out, as in the
program (the chip's share of 16-way expert parallelism).

Everything quadratic runs in blocks of heads and of queries, a query
block over the keys up to the end of its SEGMENT of the sequence only
(the causal half is not multiplied), no block for the padding after a
sequence's end and, in the last layer, none before the rows that are
read (``served_gaps``), a routed expert over the rows that picked it
(``expert_layer``), each projection where it is used, and the weights
may come in bfloat16 (each is cast to float32 where it
is used), so that a 23,040-token request fits beside 9.3 GB of weights.
The caller sets ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCK = 16
QUERY_BLOCK = 512
# the indexer's blocks: its selection is a sort, which costs the chip
# the same for 2,048 queries as for 512, so more queries and fewer heads
INDEX_QUERY_BLOCK = 2048
INDEX_HEAD_BLOCK = 4
ROW_BLOCK = 4096        # rows of a feed-forward pass at a time
EXPERT_ROWS = 512       # rows an expert gathers of a block: 4 x the mean
SEGMENTS = 9            # a query block's keys end with its segment
INDEX_NORM_EPS = 1e-6


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
    hi, di = int(s["index_n_heads"]), int(s["index_head_dim"])
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
            b + "o_weight": (d, h * dv), b + "ffn_norm_gamma": (d,),
            b + "idx_q_b_weight": (hi * di, rq),
            b + "idx_k_weight": (di, d),
            b + "idx_k_norm_gamma": (di,), b + "idx_k_norm_beta": (di,),
            b + "idx_w_weight": (hi, d)})
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


def _layer_norm(x, gamma, beta, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gamma + beta


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
    """x (..., T, rope) with ADJACENT pairs turned by ``angle`` (T,
    rope / 2): MLA's rotary."""
    pair = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pair[..., 0], pair[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _rope_halves(x, angle):
    """x (..., rope) with its two HALVES turned against each other by
    ``angle`` (..., rope / 2): the indexer's rotary."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


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


def _if_live(start, size, live, fn, *args):
    """``fn(*args)``, or zeros of its shape where the block of ``size``
    rows that starts at row ``start`` holds none of the ``live`` rows
    ``(lo, hi)`` (None: every row is live).  Rows past a padded
    sequence's end change nothing before them, and in the LAST layer no
    row reaches another, so nothing is computed for the rest."""
    if live is None:
        return fn(*args)
    lo, hi = live
    return jax.lax.cond(
        (start < hi) & (start + size > lo), fn,
        lambda *a: jax.tree_util.tree_map(
            lambda o: jnp.zeros(o.shape, o.dtype),
            jax.eval_shape(fn, *a)), *args)


def _by_rows(fn, x, live=None):
    """``fn`` of ``x`` (T, ...) ``ROW_BLOCK`` rows at a time."""
    t = x.shape[0]
    rb = min(ROW_BLOCK, t)
    pad = -t % rb
    x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    blocks = x.reshape((-1, rb) + x.shape[1:])
    out = jax.lax.map(
        lambda jb: _if_live(jb[0] * rb, rb, live, fn, jb[1]),
        (jnp.arange(blocks.shape[0]), blocks))
    return out.reshape((t + pad,) + out.shape[2:])[:t]


def _by_query_blocks(fn, t, block=None, live=None):
    """``fn(j, keys)`` for every block ``j`` of ``block`` queries,
    ``keys`` (static) the length of the sequence up to the end of the
    block's segment: a query sees no key past it.  ``fn`` returns
    ``(block, ...)`` whatever ``keys``; the blocks' results in order,
    ``(t, ...)``; zeros for a block with none of the ``live`` rows."""
    qb = min(block or QUERY_BLOCK, t)
    blocks = -(-t // qb)
    per = -(-blocks // SEGMENTS)
    out = []
    for first in range(0, blocks, per):
        last = min(first + per, blocks)
        keys = min(last * qb, t)
        got = jax.lax.map(
            lambda j: _if_live(j * qb, qb, live, lambda j: fn(j, keys), j),
            jnp.arange(first, last))
        out.append(got.reshape((-1,) + got.shape[2:]))
    return jnp.concatenate(out)[:t]


def _padded(a, t, block=None):
    qb = min(block or QUERY_BLOCK, t)
    return jnp.pad(a, ((0, -t % qb),) + ((0, 0),) * (a.ndim - 1))


def index_scores(a, cq, p, b, s, angle):
    """``fn(j, keys) -> I (block, keys)`` for :func:`_by_query_blocks`
    in blocks of ``INDEX_QUERY_BLOCK``: the lightning indexer's scores
    of query block ``j`` against the first ``keys`` positions, ``-inf``
    where a query cannot see."""
    f32 = jnp.float32
    t = a.shape[0]
    qb = min(INDEX_QUERY_BLOCK, t)
    hi, di = int(s["index_n_heads"]), int(s["index_head_dim"])
    dr = int(s["qk_rope_head_dim"])
    hb = min(INDEX_HEAD_BLOCK, hi)
    w = lambda name: p[b + name].astype(f32)
    k = _layer_norm(a @ w("idx_k_weight").T, w("idx_k_norm_gamma"),
                    w("idx_k_norm_beta"), INDEX_NORM_EPS)
    k = jnp.concatenate([_rope_halves(k[:, :dr], angle), k[:, dr:]], -1)
    weight = (a @ w("idx_w_weight").T) * (hi ** -0.5 * di ** -0.5)
    cq_p, weight_p, angle_p = (
        _padded(cq, t, INDEX_QUERY_BLOCK),
        _padded(weight, t, INDEX_QUERY_BLOCK),
        _padded(angle, t, INDEX_QUERY_BLOCK))
    wq = w("idx_q_b_weight").reshape(hi // hb, hb * di, -1)

    def fn(j, keys):
        cut = lambda arr: jax.lax.dynamic_slice_in_dim(arr, j * qb, qb, 0)
        c, ang, wt = cut(cq_p), cut(angle_p), cut(weight_p)
        rows = j * qb + jnp.arange(qb)

        def heads(g, total):
            q = (c @ wq[g].T).reshape(qb, hb, di)
            q = jnp.concatenate(
                [_rope_halves(q[..., :dr], ang[:, None, :]), q[..., dr:]],
                -1)
            sc = jax.nn.relu(jnp.einsum("qhd,kd->qhk", q, k[:keys]))
            mine = jax.lax.dynamic_slice_in_dim(wt, g * hb, hb, 1)
            return total + jnp.einsum("qhk,qh->qk", sc, mine)

        total = jax.lax.fori_loop(0, hi // hb, heads,
                                  jnp.zeros((qb, keys), f32))
        return jnp.where(rows[:, None] >= jnp.arange(keys)[None, :],
                         total, -jnp.inf)
    return fn


def selection(scores, keep):
    """The mask ``(Q, K)`` of each query's ``min(keep, seen)`` largest
    scores (``-inf``: not seen), exactly ``jax.lax.top_k``'s set: the
    scores above the last kept one and, of those equal to it, the
    first (lowest positions) that fill the count."""
    k = min(int(keep), scores.shape[1])
    last = jax.lax.top_k(scores, k)[0][:, -1:]
    above = scores > last
    level = (scores == last) & jnp.isfinite(scores)
    room = k - jnp.sum(above, -1, keepdims=True)
    return above | (level & (jnp.cumsum(level, -1) <= room))


def selected(a, cq, p, b, s, angle, live=None):
    """The selection of every query, ``(T, T)`` bool."""
    t = a.shape[0]
    score = index_scores(a, cq, p, b, s, angle)

    def fn(j, keys):
        mask = selection(score(j, keys), int(s["index_topk"]))
        return jnp.pad(mask, ((0, 0), (0, t - keys)))
    return _by_query_blocks(fn, t, INDEX_QUERY_BLOCK, live)


def _attention(cq, c_kv, k_rope, angle, sel, p, b, s, low, live=None):
    """MLA in the PLAIN form over each query's selected positions, in
    blocks of heads (each block's queries, keys and values projected
    where they are used, its share of the output projection added up)
    and of queries.  Returns ``(T, hidden)``."""
    f32 = jnp.float32
    t = cq.shape[0]
    h = int(s["num_attention_heads"])
    dn, dr, dv = (int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
                  int(s["v_head_dim"]))
    m = 0.1 * math.log(float(s["rope_scaling"]["factor"])) + 1.0
    scale = (dn + dr) ** -0.5 * m * m
    hb = min(HEAD_BLOCK, h)
    qb = min(QUERY_BLOCK, t)
    wq = p[b + "q_b_weight"].reshape(h // hb, hb * (dn + dr), -1)
    wkv = p[b + "kv_b_weight"].reshape(h // hb, hb * (dn + dv), -1)
    wo = p[b + "o_weight"].reshape(-1, h // hb, hb * dv)
    sel_p = _padded(sel, t)

    def head_block(g, total):
        q = (cq @ wq[g].astype(f32).T).reshape(t, hb, dn + dr)
        kv = (c_kv @ wkv[g].astype(f32).T).reshape(t, hb, dn + dv)
        qn = _padded(low(q[..., :dn]), t)
        qr = _padded(low(_rope(q[..., dn:], angle[:, None, :])), t)
        kn, vv = low(kv[..., :dn]), low(kv[..., dn:])

        def query_block(j, keys):
            cut = lambda arr: jax.lax.dynamic_slice_in_dim(
                arr, j * qb, qb, 0)
            sc = (jnp.einsum("qhd,khd->hqk", cut(qn), kn[:keys])
                  + jnp.einsum("qhd,kd->hqk", cut(qr), k_rope[:keys])) \
                * scale
            sc = jnp.where(cut(sel_p)[None, :, :keys], sc, -jnp.inf)
            # a padded query row selects nothing: softmax of -inf alone
            # is nan, and the row is cut off below
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1),
                              vv[:keys])

        att = _by_query_blocks(query_block, t, live=live)  # (t, hb, dv)
        return total + low(att.reshape(t, hb * dv)) \
            @ wo[:, g].astype(f32).T

    return jax.lax.fori_loop(0, h // hb, head_block,
                             jnp.zeros((t, wo.shape[0]), f32))


def expert_layer(h, p, b, s, held=None):
    """``shared(h) + sum w_e expert_e(h)`` over the picked experts among
    ``held`` (default: ids ``0 .. n_routed_experts - 1``), a loop over
    the experts.  A held expert is picked by one row in
    ``router_width / num_experts_per_tok`` (32 at the published widths),
    so where ``h`` has more than ``EXPERT_ROWS`` rows an expert runs
    over the rows that picked it, gathered (``EXPERT_ROWS`` of them at
    most), and over every row under a mask only if more did: the same
    sum either way.  Returns ``(y, picked, weights)``."""
    f32 = jnp.float32
    rows = h.shape[0]
    scores = jax.nn.sigmoid(h @ p[b + "router_weight"].astype(f32).T)
    picked, w = route(scores, p[b + "router_bias"].astype(f32), s)
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


def _layers(p, tokens, cfg, dtype, each=None, live=None):
    """The decoder's layers over ``tokens`` (T,): the last hidden state
    ``(T, hidden)``; ``each(i, selection)`` is told every layer's
    ``(T, T)`` selection (the tests).  ``live`` ``(lo, hi)``: rows
    ``lo .. hi - 1`` of the result are read and the rows from ``hi`` on
    are padding.  No block of attention, of the indexer or of a
    feed-forward pass is computed for the padding (the layers are
    causal: what lies after a row does not reach it), nor, in the LAST
    layer, for a block before row ``lo`` (a row's query and its
    feed-forward pass reach no other row; every row's keys and values
    are projected as before).  The rows outside ``lo .. hi - 1`` of the
    result are then not the model's."""
    s = _spec(cfg)
    r = int(s["kv_lora_rank"])
    eps = float(s["rms_norm_eps"])
    f32 = jnp.float32
    t = tokens.shape[0]
    angle = jnp.arange(t, dtype=f32)[:, None] * jnp.asarray(frequencies(s))
    low = lambda a: a.astype(dtype).astype(f32)

    x = p["embed_weight"][tokens].astype(f32)
    layers = int(s["num_hidden_layers"])
    last, before = live, None if live is None else (0, live[1])
    for i in range(layers):
        live = last if i == layers - 1 else before
        b = "l%d_" % i
        w = lambda name: p[b + name].astype(f32)
        a = low(_rms(x, w("attn_norm_gamma"), eps))
        cq = low(_rms(a @ w("q_a_weight").T, w("q_norm_gamma"), eps))
        kv = a @ w("kv_a_weight").T
        c_kv = low(_rms(kv[:, :r], w("kv_norm_gamma"), eps))
        k_rope = low(_rope(kv[:, r:], angle))
        sel = selected(a, cq, p, b, s, angle, live)
        if each is not None:
            each(i, sel)
        x = x + _attention(cq, c_kv, k_rope, angle, sel, p, b, s, low,
                           live)
        f = low(_rms(x, w("ffn_norm_gamma"), eps))
        if i < int(s["first_k_dense_replace"]):
            x = x + _by_rows(lambda g: _swiglu(
                g, w("gate_weight"), w("up_weight"), w("down_weight")), f,
                live)
        else:
            x = x + _by_rows(lambda g: expert_layer(g, p, b, s)[0], f,
                             live)
    return low(_rms(x, p["final_norm_gamma"].astype(f32), eps))


def logits(p, tokens, cfg, dtype=jnp.float32):
    """Next-token logits (T, vocab) at every position of ``tokens``
    (T,).  ``dtype`` other than float32 rounds every activation that a
    matrix multiplies to that type (the lower-precision control of the
    tests)."""
    x = _layers(p, tokens, cfg, dtype)
    return (x @ p["head_weight"].astype(jnp.float32).T).astype(jnp.float32)


def selections(p, tokens, cfg):
    """Every layer's selection, ``[(T, T) bool]`` (the tests' oracle of
    the program's selected sets)."""
    out = []
    _layers(p, tokens, cfg, jnp.float32, each=lambda i, sel: out.append(sel))
    return out


def served_gaps(p, tokens, first, served, cfg):
    """How far each served token lies below the reference's best.

    ``tokens`` (T,) is prompt + served tokens, padded; the served token
    ``served[j]`` was produced from position ``first + j`` (``served``
    may be padded: rows past the sequence repeat its last position).
    Only those rows meet the head; no block of rows past them is
    computed, and in the last layer none before them.  Returns
    ``(gap (n,), best (n,))``: the reference's top logit minus the
    served token's logit, and the reference's own first choice."""
    # the sequence ends within ``served``'s width of its prompt
    x = _layers(p, tokens, cfg, jnp.float32,
                live=(first, first + served.shape[0]))
    at = jnp.clip(first + jnp.arange(served.shape[0]), 0,
                  tokens.shape[0] - 1)
    rows = x[at] @ p["head_weight"].astype(jnp.float32).T
    top = jnp.max(rows, axis=-1)
    mine = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
    return top - mine, jnp.argmax(rows, axis=-1)
