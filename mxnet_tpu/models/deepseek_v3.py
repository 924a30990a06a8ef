"""DeepSeek-V3's decoder as a decode-mode graph for the paged serving
plane: multi-head LATENT attention (MLA) over a latent block pool, and
expert layers that hold this chip's SHARE of the routed experts.

Source: https://huggingface.co/deepseek-ai/DeepSeek-V3 (``config.json``;
technical report arXiv:2412.19437; the released ``inference/model.py``).
``x`` a token's hidden row, RMSNorm everywhere, no bias but the router's
correction bias:

* **MLA.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads x
  (nope | rope).  ``[c_kv | k_r] = x W_kva``; ``c_kv <- RMSNorm(c_kv)``;
  ``k_r <- RoPE(k_r)`` (one row for all heads), ``q_rope <-
  RoPE(q_rope)``.  The cache holds ``[c_kv | k_r]`` a token a layer.
  What runs is the ABSORBED form, for every query length alike:
  ``q_abs = q_nope W_kvb[k]^T``, ``score = (q_abs . c_kv + q_rope . k_r)
  s``, ``o_lat = softmax . c_kv``, ``out = (o_lat W_kvb[v]) W_o``, with
  ``s = (nope + rope)^-0.5 m^2``, ``m = 0.1 ln(factor) + 1`` (YaRN).
  RoPE turns adjacent pairs, frequencies YaRN-corrected.
* **Dense layers** (the first ``first_k_dense_replace``): SwiGLU.
* **Expert layers.** ``sigma = sigmoid(h W_g^T)`` in fp32 over ALL
  ``router_width`` experts, group-limited top-k (``ops/moe.py``), ``y =
  shared(h) + sum w_e expert_e(h)`` where the sum runs over the picked
  experts among the ``n_routed_experts`` HELD here (ids ``0 .. held-1``):
  the chip's part of an expert-parallel layer, without its exchange.
* **Head.** RMSNorm, untied head over this chip's vocabulary slice.

* **Sparse attention** (a spec with the three ``index_*`` keys:
  DeepSeek-V3.2, ``models/deepseek_v32.py``; without them none of it is
  traced).  A lightning indexer beside MLA: ``q_idx = c_q W_iqb`` ->
  ``index_n_heads`` x ``index_head_dim``, a head ``[rope | nope]`` (the
  rotary part FIRST); one index key a token ``k_idx = LayerNorm(h
  W_ik)``, split alike, kept in a second pool leaf; both rotary parts
  turned by MLA's frequencies, as two HALVES and not adjacent pairs;
  head weights ``w = (h W_iw) heads^-0.5 dim^-0.5``; ``I[t, s] = sum_j
  w[t, j] relu(q_idx[t, j] . k_idx[s])`` in fp32; the ``min(index_topk,
  t + 1)`` best positions stay and MLA's softmax runs over them alone.

The multi-token-prediction module is not part of inference (report
section 2.2) and is not here.  Norms, router scores, RoPE and the
softmax run in fp32; products in the weights' dtype, accumulated fp32.
"""
from __future__ import annotations

import math
import re

import numpy as np

from ..base import MXNetError
from .paged import RowGroup, cat, last_logits, pool_write, row_groups
from .transformer_lm import _embed

__all__ = ["serving_spec", "param_shapes", "random_params",
           "required_params", "matmul_weights", "pack_params",
           "quantize_params", "init_pool", "latent_width",
           "paged_step_apply", "paged_step_leaves", "paged_step",
           "paged_step_groups", "rope_frequencies", "softmax_scale",
           "OFFERS",
           "AUX_COUNTERS", "QUANTIZE_TAKES_LEAVES"]

# what of the serving plane this model can be put on besides the paged
# plane with in-graph or host sampling (program_store asks)
OFFERS = frozenset()
# the counters a step returns beside its logits, in order
AUX_COUNTERS = ("moe_tokens", "moe_local_assignments",
                "moe_expert_load_max", "moe_expert_steps",
                "moe_experts_touched", "moe_expert_streams")
# ``quantize_params`` frees each plain leaf as its codes are made: the
# store hands it the caller's only references (program_store asks)
QUANTIZE_TAKES_LEAVES = True

_INT_KEYS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
             "num_attention_heads", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "intermediate_size", "moe_intermediate_size",
             "n_routed_experts", "router_width", "n_shared_experts",
             "num_experts_per_tok", "n_group", "topk_group", "vocab_size")
_ROPE_KEYS = ("beta_fast", "beta_slow", "factor",
              "original_max_position_embeddings")
# the indexer's LayerNorm (the released inference/model.py's own class)
_INDEX_NORM_EPS = 1e-6


def serving_spec(spec):
    """Validated architecture spec (the published ``config.json`` keys;
    ``n_routed_experts`` counts the experts HELD here, ``router_width``
    all the experts the router scores)."""
    spec = dict(spec)
    out = {"arch": "deepseek_v3"}
    missing = [k for k in _INT_KEYS + ("rope_scaling",) if k not in spec]
    if missing:
        raise MXNetError("deepseek_v3 spec is missing %s" % missing)
    for k in _INT_KEYS:
        out[k] = int(spec[k])
    out["routed_scaling_factor"] = float(spec.get(
        "routed_scaling_factor", 1.0))
    out["rms_norm_eps"] = float(spec.get("rms_norm_eps", 1e-6))
    out["rope_theta"] = float(spec.get("rope_theta", 10000.0))
    # None: plain rotary, no YaRN (a model over this one says so)
    rope = spec["rope_scaling"]
    if rope is not None and [k for k in _ROPE_KEYS if k not in rope]:
        raise MXNetError("deepseek_v3 rope_scaling needs %s"
                         % (_ROPE_KEYS,))
    out["rope_scaling"] = None if rope is None else {
        k: float(rope[k]) for k in _ROPE_KEYS}
    if out["router_width"] % out["n_group"] or \
            not 0 < out["n_routed_experts"] <= out["router_width"]:
        raise MXNetError(
            "router_width %d must divide into n_group %d and hold the "
            "%d experts kept here" % (out["router_width"], out["n_group"],
                                      out["n_routed_experts"]))
    if out["topk_group"] > out["n_group"] or out["num_experts_per_tok"] \
            > out["topk_group"] * (out["router_width"] // out["n_group"]):
        raise MXNetError("top-%d over %d of %d groups cannot be picked"
                         % (out["num_experts_per_tok"], out["topk_group"],
                            out["n_group"]))
    if out["qk_rope_head_dim"] % 2 or not \
            0 <= out["first_k_dense_replace"] <= out["num_hidden_layers"]:
        raise MXNetError("deepseek_v3 spec: odd rotary width or more "
                         "dense layers than layers")
    return out


def _is_dense(spec, i):
    return i < spec["first_k_dense_replace"]


def layer_prefixes(spec):
    """``(prefix of its leaves' names, dense?)`` of every decoder layer
    whose leaves the spec holds: ``l<i>_`` and, behind them, ``mtp_``:
    the prediction module's one expert layer, for a spec that loads it
    (``draft_layers``: ``models/pangu_ultra_moe.py``)."""
    out = [("l%d_" % i, _is_dense(spec, i))
           for i in range(spec["num_hidden_layers"])]
    return out + [("mtp_", False)] * bool(spec.get("draft_layers"))


def param_shapes(spec):
    """name -> shape of the checkpoint's leaves: every matrix ``(out,
    in)``, each routed expert's three matrices leaves of their own
    (``l<i>_e<j>_gate_weight`` ...; :func:`pack_params` stacks them).
    A spec with ``sandwich_norm`` has two more norms a layer; one
    without ``router_bias`` (False) no correction bias."""
    D, H = spec["hidden_size"], spec["num_attention_heads"]
    rq, r = spec["q_lora_rank"], spec["kv_lora_rank"]
    dn, dr, dv = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                  spec["v_head_dim"])
    F = spec["moe_intermediate_size"]
    out = {"embed_weight": (spec["vocab_size"], D),
           "final_norm_gamma": (D,),
           "head_weight": (spec["vocab_size"], D)}
    for p, dense in layer_prefixes(spec):
        out.update({
            p + "attn_norm_gamma": (D,), p + "q_a_weight": (rq, D),
            p + "q_norm_gamma": (rq,),
            p + "q_b_weight": (H * (dn + dr), rq),
            p + "kv_a_weight": (r + dr, D), p + "kv_norm_gamma": (r,),
            p + "kv_b_weight": (H * (dn + dv), r),
            p + "o_weight": (D, H * dv), p + "ffn_norm_gamma": (D,)})
        if spec.get("sandwich_norm"):
            out.update({p + "post_attn_norm_gamma": (D,),
                        p + "post_ffn_norm_gamma": (D,)})
        if dense:
            I = spec["intermediate_size"]
            out.update({p + "gate_weight": (I, D), p + "up_weight": (I, D),
                        p + "down_weight": (D, I)})
            continue
        S = F * spec["n_shared_experts"]
        out.update({p + "router_weight": (spec["router_width"], D),
                    p + "shared_gate_weight": (S, D),
                    p + "shared_up_weight": (S, D),
                    p + "shared_down_weight": (D, S)})
        if spec.get("router_bias", True):
            out[p + "router_bias"] = (spec["router_width"],)
        for e in range(spec["n_routed_experts"]):
            q = "%se%d_" % (p, e)
            out.update({q + "gate_weight": (F, D), q + "up_weight": (F, D),
                        q + "down_weight": (D, F)})
    return out


def _packed(prefix):
    return (prefix + "experts_gate_up", prefix + "experts_down")


def required_params(spec):
    """The leaves a step reads: the checkpoint's, with each expert
    layer's routed experts as the two stacks of :func:`pack_params`."""
    names = [n for n in param_shapes(spec)
             if not re.match(r"(l\d+|mtp)_e\d+_", n)]
    for prefix, dense in layer_prefixes(spec):
        if not dense:
            names += _packed(prefix)
    return names


def matmul_weights(spec):
    """The leaves int8 weight-only serving quantizes: every matmul
    weight, the routed experts' stacks among them (norm scales and the
    router's correction bias stay as they are)."""
    return [n for n in required_params(spec)
            if n.endswith("_weight") or "_experts_" in n]


def pack_params(params, spec):
    """Stack each expert layer's routed experts for the grouped
    product, IN PLACE: ``l<i>_e<j>_gate_weight``/``_up_weight`` ``(F,
    D)`` become ``l<i>_experts_gate_up`` ``(held, D, 2F)`` (input
    dimension first, gate then up) and ``_down_weight`` ``(D, F)``
    ``l<i>_experts_down`` ``(held, F, D)``.  The per-expert leaves are
    popped from ``params`` as each stack is made: at the served size the
    experts are 5.6 GB of a 16 GB chip, and a caller who hands over its
    only reference never holds two copies of more than one layer's
    (each layer is waited for: see below).
    A dict that is already packed is left as it is."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stack_t(*ws):
        return jnp.stack([w.T for w in ws])

    E = spec["n_routed_experts"]
    for prefix, dense in layer_prefixes(spec):
        gu, down = _packed(prefix)
        if dense or gu in params:
            continue
        names = ["%se%d_%%s_weight" % (prefix, e) for e in range(E)]
        gate = stack_t(*[params.pop(n % "gate") for n in names])
        up = stack_t(*[params.pop(n % "up") for n in names])
        params[gu] = jnp.concatenate([gate, up], axis=2)
        del gate, up
        params[down] = stack_t(*[params.pop(n % "down") for n in names])
        # dispatch is asynchronous and a result is allocated when its
        # program is ENQUEUED: without this wait every layer's stacks
        # are allocated before the first layer's leaves are freed (6 GB
        # over the weights at lfm2_moe's served size, 16.47 GB of the
        # chip's 16.91; my chip run, PR 31)
        jax.block_until_ready((params[gu], params[down]))
    return params


def quantize_params(params, spec):
    """int8 weight-only transform of a PACKED param dict: every leaf of
    :func:`matmul_weights` becomes a ``QuantizedWeight`` with one
    absmax scale an output channel (a row of an ``(out, in)`` matrix, a
    column of an expert's ``(in, out)`` stack); on the device, a leaf at
    a time."""
    return quantize_leaves(params, matmul_weights(spec))


def quantize_leaves(params, which):
    """:func:`quantize_params` of the leaves named ``which``.  TAKES
    the leaves out of ``params`` as it goes and waits for each: a leaf's
    plain values are freed before the next leaf's codes are made, so a
    model whose plain leaves nearly fill the chip (12.1 GB of
    ``pangu_ultra_moe``'s 16) can still be loaded as 6 GB of codes
    (:data:`QUANTIZE_TAKES_LEAVES`: the store empties the caller's dict
    for such a model, as :func:`pack_params` empties it of what it
    restacks)."""
    import jax
    import jax.numpy as jnp
    from ..pallas_ops.dequant_matmul import QuantizedWeight

    @jax.jit
    def quant(w):
        w = w.astype(jnp.float32)
        # axis 1 is the input dimension of both layouts
        absmax = jnp.max(jnp.abs(w), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        codes = jnp.clip(jnp.rint(w / scale), -127, 127).astype(jnp.int8)
        return codes, (scale[:, 0] if w.ndim == 2 else scale)

    which = set(which)
    out = {}
    for k in list(params):
        v = params.pop(k)
        if k in which:
            v = QuantizedWeight(*jax.block_until_ready(
                quant(jnp.asarray(v))))
        out[k] = v
    return out


def _plain(w, dtype):
    """A weight as a plain array of ``dtype`` (a QuantizedWeight is
    dequantized: the einsums over ``kv_b`` and the grouped product take
    no code/scale pair)."""
    import jax.numpy as jnp
    from ..pallas_ops.dequant_matmul import QuantizedWeight
    if isinstance(w, QuantizedWeight):
        s = jnp.asarray(w.scales, jnp.float32)
        s = s[:, None] if s.ndim == 1 else s
        return (w.codes.astype(jnp.float32) * s).astype(dtype)
    return w.astype(dtype)


def random_params(spec, seed=0):
    """Seeded random weights with :func:`param_shapes`' names: matrices
    N(0, 1 / fan_in), norm scales near one, the router's bias small."""
    return random_leaves(param_shapes(spec), seed)


def random_leaves(shapes, seed):
    """Seeded float32 leaves for ``shapes`` (name -> shape), drawn by
    the end of each name."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(shapes.items()):
        if name == "embed_weight":
            leaf = rs.normal(0, 1.0, shape)
        elif name.endswith("_weight"):
            leaf = rs.normal(0, 1.0 / math.sqrt(shape[1]), shape)
        elif name.endswith("_gamma"):
            leaf = 1.0 + 0.1 * rs.normal(size=shape)
        else:
            leaf = 0.02 * rs.normal(size=shape)
        out[name] = np.asarray(leaf, np.float32)
    return out


def latent_width(spec):
    """Width of a latent pool row: ``kv_lora_rank + qk_rope_head_dim``
    values (576) in whole 128-lane tiles (640).  The TPU tiles the minor
    dimension by 128, so a row-major row of 576 occupies 640 either
    way; declared at 576, XLA keeps the pool tokens-minor instead
    (layout ``{2,3,1,0}``) and copies all of it into row-major for the
    kernel in every program."""
    w = spec["kv_lora_rank"] + spec["qk_rope_head_dim"]
    return -(-w // 128) * 128


def init_pool(spec, num_blocks, block_size, dtype="float32"):
    """The zeroed latent pool: ONE leaf ``(num_layers, 1, num_blocks *
    block_size, latent_width)``, a row ``[c_kv | k_r | 0...]`` — key for
    every head and, its first ``kv_lora_rank`` values, value too.
    Block 0 is the reserved trash block, as in ``transformer_lm``."""
    import jax.numpy as jnp
    return (jnp.zeros((spec["num_hidden_layers"], 1,
                       int(num_blocks) * int(block_size),
                       latent_width(spec)), dtype),)


def rope_frequencies(spec):
    """The rotary frequencies, YaRN-corrected as the released
    ``inference/model.py`` does (``precompute_freqs_cis``): ``(rope /
    2,)`` float32."""
    dim = spec["qk_rope_head_dim"]
    base, sc = spec["rope_theta"], spec["rope_scaling"]
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if sc is None:
        return freqs.astype(np.float32)
    orig = sc["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    smooth = 1 - ramp
    return (freqs / sc["factor"] * (1 - smooth) + freqs * smooth) \
        .astype(np.float32)


def softmax_scale(spec):
    """``(nope + rope)^-0.5 m^2``, ``m = 0.1 ln(factor) + 1`` (1 for
    a spec without ``rope_scaling``)."""
    scale = (spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]) ** -0.5
    if spec["rope_scaling"] is None:
        return scale
    m = 0.1 * math.log(spec["rope_scaling"]["factor"]) + 1.0
    return scale * m * m


def _rope(x, cos, sin):
    """Turn adjacent pairs of the last axis: x ``(..., rope)`` fp32,
    cos/sin broadcastable ``(..., rope / 2)``."""
    import jax.numpy as jnp
    pair = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _rope_halves(x, cos, sin):
    """Turn the two HALVES of the last axis against each other (value
    ``i`` with value ``i + rope / 2``): the indexer's rotary."""
    import jax.numpy as jnp
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _index_project(h, cq, p, spec):
    """The lightning indexer's projections of one layer, a TOKEN each,
    over the step's rows ``h`` ``(N, D)`` (the attention block's normed
    input) and ``cq`` ``(N, rq)`` (MLA's query latent): index queries
    ``(N, Hi, di)`` and index keys ``(N, di)`` in fp32, rotary not yet
    turned, and the head weights ``(N, Hi)`` fp32, scaled."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    Hi, di = spec["index_n_heads"], spec["index_head_dim"]
    qi = _mm(cq.astype(h.dtype), p["idx_q_b_weight"]).astype(f32) \
        .reshape(-1, Hi, di)
    ki = _mm(h, p["idx_k_weight"], f32)
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(jnp.square(ki), -1, keepdims=True)
                            + _INDEX_NORM_EPS) \
        * p["idx_k_norm_gamma"].astype(f32) \
        + p["idx_k_norm_beta"].astype(f32)
    wi = _mm(h, p["idx_w_weight"], f32) * (Hi ** -0.5 * di ** -0.5)
    return qi, ki, wi


def _index_turn(qi, ki, wi, st):
    """:func:`_index_project`'s rows of ONE group ``st``, by sequence:
    queries ``(B, Lq, Hi, di)``, fresh keys ``(B, Lq, di)``, head
    weights ``(B, Lq, Hi)``, the rotary part of queries and keys (the
    FIRST ``qk_rope_head_dim`` values) turned by the group's angles."""
    import jax.numpy as jnp
    shape, dr = (st.B, st.Lq), st.spec["qk_rope_head_dim"]
    cos, sin = st.cos, st.sin
    qi = qi.reshape(shape + qi.shape[1:])
    ki = ki.reshape(shape + ki.shape[1:])
    qi = jnp.concatenate(
        [_rope_halves(qi[..., :dr], cos[:, :, None], sin[:, :, None]),
         qi[..., dr:]], axis=-1)
    ki = jnp.concatenate([_rope_halves(ki[..., :dr], cos, sin),
                          ki[..., dr:]], axis=-1)
    return qi, ki, wi.reshape(shape + wi.shape[1:])


def _rms(x, gamma, eps):
    import jax.numpy as jnp
    from ..ops.nn import _rms_fc
    return _rms_fc({"eps": eps}, x.astype(jnp.float32),
                   gamma.astype(jnp.float32))


def _mm(x2d, w, out=None):
    """``x @ w^T`` in ``x``'s dtype, accumulated fp32 (``out``: handed
    back in that dtype instead: router scores and logits want fp32).
    An int8 weight is dequantized in the graph and multiplied like any
    other: the fused ``dequant_matmul`` kernel tiles by 128 whatever
    the widths, and at 7168 x 18432 that is 8,064 grid steps a decode
    row block — through it a decode step of this model took 0.8 s (my
    chip run, PR 26)."""
    import jax.numpy as jnp
    return jnp.matmul(x2d, _plain(w, x2d.dtype).T,
                      preferred_element_type=out or x2d.dtype)


def _swiglu_ffn(f, gate, up, down):
    import jax
    import jax.numpy as jnp
    g = _mm(f, gate).astype(jnp.float32)
    act = (g * jax.nn.sigmoid(g) * _mm(f, up).astype(jnp.float32))
    return _mm(act.astype(f.dtype), down).astype(jnp.float32)


def expert_layer(f, p, spec, live, eps=0.0):
    """The routed experts' part of an expert layer over the rows ``f``
    ``(N, D)``, ``p`` the layer's leaves: sigmoid scores over
    ``router_width`` in fp32, :func:`ops.moe.route_grouped` (``eps``:
    what the model adds to the picked scores' sum), the held experts'
    grouped product.  Returns ``(y (N, D) fp32, this layer's
    AUX_COUNTERS over the live tokens)``."""
    import jax
    import jax.numpy as jnp
    from ..ops.moe import expert_streams, moe_experts, route_grouped
    f32, cdt = jnp.float32, f.dtype
    scores = jax.nn.sigmoid(_mm(f, p["router_weight"], f32))
    # a router without a correction bias: a zero one changes no choice
    bias = p["router_bias"].astype(f32) if "router_bias" in p \
        else jnp.zeros((spec["router_width"],), f32)
    experts, weights = route_grouped(
        scores, bias,
        spec["num_experts_per_tok"], spec["n_group"],
        spec["topk_group"], spec["routed_scaling_factor"], eps)
    y, per = moe_experts(
        f, _plain(p["experts_gate_up"], cdt),
        _plain(p["experts_down"], cdt), experts, weights, live)
    return y, jnp.stack(
        [jnp.sum(live, dtype=jnp.int32), jnp.sum(per), jnp.max(per),
         jnp.int32(1), jnp.sum(per > 0, dtype=jnp.int32),
         expert_streams(per, experts.size)])


def paged_step_apply(params, pool, tables, tokens, positions, valid, spec,
                     block_size, all_logits=False):
    """:func:`paged_step_leaves` of a model whose pool is the one
    latent leaf: ``(logits, pool, counts)``."""
    logits, (pool,), counts = paged_step_leaves(
        params, (pool,), tables, tokens, positions, valid, spec,
        block_size, all_logits=all_logits)
    return logits, pool, counts


class _Step(RowGroup):
    """What every layer of a paged step shares for ONE group of its
    rows (``paged.RowGroup``): the write plan, the rotary angles, the
    softmax scale, the indexer's selection."""

    def __init__(self, tables, shape, positions, valid, spec, block_size,
                 at=0):
        import jax.numpy as jnp
        super().__init__(tables, shape, positions, valid, block_size, at)
        self.spec = spec
        self.plan = self.write_plan()
        angle = self.angles(rope_frequencies(spec))     # (B, Lq, dr/2)
        self.cos, self.sin = jnp.cos(angle), jnp.sin(angle)
        self.scale = softmax_scale(spec)
        # the indexer's selection: how many positions a query keeps
        self.sparse = "index_topk" in spec
        if self.sparse:
            self.keep = min(spec["index_topk"],
                            self.tables.shape[1] * self.bs)
            # positions a live query keeps: all it sees, up to ``keep``
            self.kept = jnp.where(
                self.live.reshape(shape), jnp.minimum(
                    self.positions[:, None] + self.rows[None] + 1,
                    self.keep),
                0)


def decoder_layer(x, p, pools, layer, dense, steps, first=0):
    """One decoder layer of a paged step over ``x`` ``(N, D)`` fp32, the
    rows of the step's groups laid end to end: ``p`` the layer's leaves
    (their names without the layer's prefix), ``layer`` its index on the
    pool leaves' first axis, ``steps`` a :class:`_Step` a group.  What
    works on a TOKEN (norms, projections, the feed-forward, the experts)
    runs ONCE over all the rows, so a weight is read once a step however
    many groups it has; what works on a SEQUENCE runs a group, with the
    group's shapes: each writes its fresh rows of every leaf and attends
    (keys before ``first`` seen by no query).  A spec with
    ``sandwich_norm`` norms each sublayer's OUTPUT too before it joins
    the residual (``post_attn_norm_gamma``, ``post_ffn_norm_gamma``).
    Returns ``(x, pools, the layer's AUX_COUNTERS or None)``."""
    import jax.numpy as jnp
    from ..ops import attention as _att

    spec, bs = steps[0].spec, steps[0].bs
    H = spec["num_attention_heads"]
    r, dn, dr, dv = (spec["kv_lora_rank"], spec["qk_nope_head_dim"],
                     spec["qk_rope_head_dim"], spec["v_head_dim"])
    eps = spec["rms_norm_eps"]
    sandwich = spec.get("sandwich_norm", False)
    f32 = jnp.float32
    cdt = p["attn_norm_gamma"].dtype            # the weights' dtype
    W = pools[0].shape[3]

    h = _rms(x, p["attn_norm_gamma"], eps).astype(cdt)
    cq = _rms(_mm(h, p["q_a_weight"]), p["q_norm_gamma"], eps)
    q_all = _mm(cq.astype(cdt), p["q_b_weight"])
    kv_all = _mm(h, p["kv_a_weight"]).astype(f32)
    if steps[0].sparse:
        index = _index_project(h, cq, p, spec)
    wkv = _plain(p["kv_b_weight"], cdt).reshape(H, dn + dv, r)
    outs = []
    for st in steps:
        B, Lq, cos, sin = st.B, st.Lq, st.cos, st.sin
        q = q_all[st.span].reshape(B, Lq, H, dn + dr)
        kv = kv_all[st.span].reshape(B, Lq, r + dr)
        latent = jnp.concatenate(
            [_rms(kv[..., :r], p["kv_norm_gamma"], eps),
             _rope(kv[..., r:], cos, sin),
             jnp.zeros((B, Lq, W - r - dr), f32)], axis=-1)
        fresh = (latent[:, None],)
        if st.sparse:
            qi, ki, wi = _index_turn(*(a[st.span] for a in index), st)
            fresh += (ki[:, None],)
        pools = pool_write(pools, layer, fresh, st.plan, bs)
        pool = pools[0]
        q_abs = jnp.einsum("blhd,hdc->bhlc", q[..., :dn], wkv[:, :dn],
                           preferred_element_type=f32)
        q_rope = _rope(q[..., dn:].astype(f32), cos[:, :, None],
                       sin[:, :, None])
        query = jnp.concatenate(
            [q_abs, jnp.transpose(q_rope, (0, 2, 1, 3)),
             jnp.zeros((B, H, Lq, W - r - dr), f32)], axis=-1)
        if st.sparse:
            scores = _att.lightning_index_scores(
                qi.astype(pools[1].dtype), wi, pools[1], layer, st.tables,
                st.positions, bs)
            thr, tie = _att.sparse_select(scores, st.keep)
            o_lat = _att.mla_attention_sparse(
                query.astype(pool.dtype), pool, layer, st.tables,
                st.positions, scores, thr, tie, st.kept, st.keep, bs, r,
                st.scale)
        else:
            o_lat = _att.mla_attention_paged(
                query.astype(pool.dtype), pool, layer, st.tables,
                st.positions, bs, r, st.scale, first=first)
        o = jnp.einsum("bhlc,hdc->blhd", o_lat.astype(cdt), wkv[:, dn:],
                       preferred_element_type=f32)
        outs.append(o.astype(cdt).reshape(B * Lq, H * dv))
    a = _mm(cat(outs), p["o_weight"]).astype(f32)
    if sandwich:
        a = _rms(a, p["post_attn_norm_gamma"], eps)
    x = x + a

    f = _rms(x, p["ffn_norm_gamma"], eps).astype(cdt)
    step = None
    if dense:
        y = _swiglu_ffn(f, p["gate_weight"], p["up_weight"],
                        p["down_weight"])
    else:
        y, step = expert_layer(f, p, spec, cat([st.live for st in steps]))
        y = y + _swiglu_ffn(f, p["shared_gate_weight"],
                            p["shared_up_weight"],
                            p["shared_down_weight"])
    if sandwich:
        # on the chip's PARTIAL sum (shared expert + held experts): in
        # a deployment this norm follows the exchange's combine
        y = _rms(y, p["post_ffn_norm_gamma"], eps)
    return x + y, pools, step


def layer_leaves(params, prefix):
    """The leaves of ``params`` under ``prefix``, named without it."""
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def paged_step_groups(params, pools, groups, spec, block_size,
                      all_logits=False, hidden=False):
    """One PAGED step over the latent pool for a tuple of ROW GROUPS,
    each ``(tables (B, T), tokens (B, Lq), positions (B,), valid
    (B,))`` with a ``B`` and an ``Lq`` of its own (a tick's decode rows,
    ``Lq = 1``, and its prompt chunk's): every layer reads its weights
    ONCE for all the groups' rows (:func:`decoder_layer`), and the
    groups write and attend one after the other, in the order given, as
    that many one-group steps in that order would.  ``params`` is a
    PACKED dict (:func:`pack_params`), plain or int8; ``pools`` the
    leaves of the model's ``init_pool`` (the latent leaf; behind it, for
    a spec with the ``index_*`` keys, the index keys').  The program
    store takes a model that has this name to offer a step over more
    than one group (``program_store.paged_program``).

    Each layer writes a group's fresh rows of every leaf in place
    (``paged.pool_write``: one plan, no scatter, no slice of a pool)
    and attends through the ``mla_attention_paged`` door, ONE
    absorbed-form algorithm for every ``Lq`` — or, with an indexer,
    scores the sequence's index keys (``lightning_index_scores``),
    finds the threshold that keeps each query's ``index_topk`` best
    positions exactly (``sparse_select``) and attends over those alone
    (``mla_attention_sparse``: gathered rows at one query a sequence,
    the walk under the mask for a chunk).

    Returns ``(logits a group, pools, counts)``: a group's logits ``(B,
    vocab)`` fp32 at each row's last valid position (``all_logits``:
    ``(B, Lq, vocab)``), and :data:`AUX_COUNTERS` as one int32 vector
    over the step's LIVE tokens (valid rows of sequences whose table
    owns a block), all groups together: tokens routed x expert layers,
    picks that fell on held experts, the fullest held expert's count
    summed over the layers, expert layers, held experts that got a
    token summed over the layers, and how often the grouped product
    streamed an expert's weights for them (``ops/moe.expert_streams``;
    once a touched expert is the floor).  ``hidden``: a fourth result,
    a group's last layer output BEFORE the final norm, ``(B, Lq, D)``
    fp32 (what a prediction module drafts from:
    ``models/pangu_ultra_moe.py``)."""
    import jax.numpy as jnp

    pools = tuple(pools)
    L, D = spec["num_hidden_layers"], spec["hidden_size"]
    f32 = jnp.float32
    cdt = params["final_norm_gamma"].dtype      # the weights' dtype
    steps, tokens = row_groups(groups, block_size, _Step, spec=spec)
    counts = jnp.zeros((len(AUX_COUNTERS),), jnp.int32)

    x = _embed(params["embed_weight"], tokens).astype(f32)    # (N, D)
    for i in range(L):
        x, pools, step = decoder_layer(
            x, layer_leaves(params, "l%d_" % i), pools, i,
            _is_dense(spec, i), steps)
        if step is not None:
            counts = counts + step
    hN = _rms(x, params["final_norm_gamma"], spec["rms_norm_eps"]) \
        .astype(cdt)
    if all_logits:
        every = _mm(hN, params["head_weight"], f32).astype(f32)
        logits = tuple(every[st.span].reshape(st.B, st.Lq, -1)
                       for st in steps)
    else:
        logits = last_logits(hN, steps, lambda last: _mm(
            last, params["head_weight"], f32).astype(f32))
    out = (logits, pools, counts)
    if hidden:
        out += (tuple(x[st.span].reshape(st.B, st.Lq, D)
                      for st in steps),)
    return out


def paged_step_leaves(params, pools, tables, tokens, positions, valid,
                      spec, block_size, all_logits=False, hidden=False):
    """:func:`paged_step_groups` of ONE group — ``transformer_lm.
    paged_step_apply``'s contract: tokens ``(B, Lq)`` (``Lq = 1`` a
    decode step), positions/valid ``(B,)``, tables ``(B, T)``.  Returns
    ``(logits, pools, counts)`` and, with ``hidden``, the hidden states
    behind them, the one group's own."""
    out = paged_step_groups(
        params, pools, ((tables, tokens, positions, valid),), spec,
        block_size, all_logits=all_logits, hidden=hidden)
    return (out[0][0], out[1], out[2]) + tuple(h[0] for h in out[3:])


def paged_step(params, pools, tables, tokens, positions, valid, spec,
               block_size, scales=None, all_logits=False):
    """The program store's seam: ``(logits, pool leaves, counters)``."""
    if scales is not None:
        raise MXNetError("%s has no int8 latent pool" % spec["arch"])
    return paged_step_leaves(
        params, pools, tables, tokens, positions, valid, spec,
        block_size, all_logits=all_logits)
