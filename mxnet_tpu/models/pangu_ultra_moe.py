"""openPangu-Ultra-MoE's decoder on the paged serving plane: DeepSeek-V3's
layer (``models/deepseek_v3.py``: MLA in the absorbed form over the
latent pool, this chip's share of the routed experts) with Pangu
Ultra's SANDWICH norm, a plain rotary and an ungrouped router — and its
MULTI-TOKEN-PREDICTION module as a SELF-DRAFT: one more expert layer
that proposes the token after next from the target's own hidden state,
its latent cache ONE MORE LAYER of the target's pool leaf.

Source: https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B
(``config.json``, ``model_type: pangu_ultra_moe``; Pangu Ultra MoE,
arXiv:2505.04519; the sandwich norm is Pangu Ultra's, arXiv:2504.07866;
the module after DeepSeek-V3, arXiv:2412.19437 section 2.2, which the
config's ``num_nextn_predict_layers`` names).  Per layer, input ``x``,
RMSNorm everywhere:

    a   = MLA(norm_in(x))            # deepseek_v3's, softmax scale
                                     # (nope + rope)^-0.5, NO YaRN
    y   = x + norm_post_attn(a)      # the sublayer's OUTPUT is normed
    f   = FFN(norm_pre_mlp(y))       # dense SwiGLU, or shared + routed
    out = y + norm_post_mlp(f)
    router: s = sigmoid(W_r h); the ``num_experts_per_tok`` largest (no
            groups, no bias); w = s_i / sum(s_chosen) * scale

and the module (ONE, ``num_nextn_predict_layers`` 1), for position j:

    u_j = W_eh [ norm_h(h_{j-1}) ; norm_e(Emb(x_j)) ]
    v   = Layer_mtp(u)               # one expert layer as above, ITS OWN
                                     # latent cache: layer L of the leaf
    draft logits for position j + 1 = Head(norm_final_mtp(v_j))

``h`` is the target's last layer output BEFORE the final norm; ``Emb``
and ``Head`` are the target's.  THE ROW CONVENTION: the module's cache
row ``j`` is computed from ``h_{j-1}`` and ``x_j``, row 0 is never
written and no query sees it (``first=1`` of ``mla_attention_paged``),
so a block's rows depend only on the tokens up to that block's end and
a shared prefix block carries the module's rows to whoever adopts it.

The module is loaded only for a store that asks (``with_draft``: a spec
with ``draft_layers``); without it the leaves, the pool and every
program are the target's alone.  The post-feed-forward norm is applied
to this chip's PARTIAL sum (shared expert + held experts); in a
deployment it follows the exchange's combine.
"""
from __future__ import annotations

from ..base import MXNetError
from . import deepseek_v3 as _v3
from .deepseek_v3 import (AUX_COUNTERS,  # noqa: F401
                          QUANTIZE_TAKES_LEAVES, pack_params,
                          paged_step_groups)

__all__ = ["serving_spec", "with_draft", "param_shapes", "random_params",
           "required_params", "matmul_weights", "pack_params",
           "quantize_params", "init_pool", "paged_step",
           "paged_step_groups", "draft_step", "OFFERS", "AUX_COUNTERS"]

# a decode step that verifies the module's proposal and yields one or
# two tokens (program_store: ``self_draft``)
OFFERS = frozenset({"self_draft"})

_OWN_KEYS = tuple(k for k in _v3._INT_KEYS
                  if k not in ("n_group", "topk_group"))


def serving_spec(spec):
    """Validated architecture spec (the published ``config.json`` keys;
    ``n_routed_experts`` counts the experts HELD here, ``router_width``
    all the experts the router scores): ``deepseek_v3``'s with no
    groups, no correction bias, no YaRN and four norms a layer."""
    spec = dict(spec)
    missing = [k for k in _OWN_KEYS + ("num_nextn_predict_layers",)
               if k not in spec]
    if missing:
        raise MXNetError("pangu_ultra_moe spec is missing %s" % missing)
    if not spec.get("sandwich_norm", True) or \
            not spec.get("norm_topk_prob", True):
        raise MXNetError("pangu_ultra_moe is written for sandwich_norm "
                         "and norm_topk_prob, both true")
    out = dict(_v3.serving_spec(dict(
        spec, n_group=1, topk_group=1, rope_scaling=None)),
        arch="pangu_ultra_moe", sandwich_norm=True, router_bias=False,
        rms_norm_eps=float(spec.get("rms_norm_eps", 1e-5)),
        num_nextn_predict_layers=int(spec["num_nextn_predict_layers"]))
    if "draft_layers" in spec:
        out = with_draft(out, spec["draft_layers"])
    return out


def with_draft(spec, depth):
    """``spec`` with its prediction module loaded as a self-draft of
    ``depth`` tokens (the store's ``self_draft``): the module's leaves
    and one more layer of the pool.  The model has ONE module."""
    depth = int(depth)
    if not 0 <= depth <= min(spec["num_nextn_predict_layers"], 1):
        raise MXNetError(
            "pangu_ultra_moe drafts %d token(s) a step with its %d "
            "prediction module(s), not %d"
            % (min(spec["num_nextn_predict_layers"], 1),
               spec["num_nextn_predict_layers"], depth))
    return dict(spec, draft_layers=depth) if depth else dict(spec)


def _module_leaves(spec):
    D = spec["hidden_size"]
    if not spec.get("draft_layers"):
        return {}
    return {"mtp_h_norm_gamma": (D,), "mtp_e_norm_gamma": (D,),
            "mtp_eh_weight": (D, 2 * D), "mtp_final_norm_gamma": (D,)}


def param_shapes(spec):
    """``deepseek_v3``'s leaves under this spec (two more norms a layer,
    no router bias) and, with the module loaded, its expert layer
    (``mtp_…``), its two input norms, ``W_eh`` and its final norm."""
    return dict(_v3.param_shapes(spec), **_module_leaves(spec))


def required_params(spec):
    return _v3.required_params(spec) + list(_module_leaves(spec))


def matmul_weights(spec):
    return [n for n in required_params(spec)
            if n.endswith("_weight") or "_experts_" in n]


def quantize_params(params, spec):
    return _v3.quantize_leaves(params, matmul_weights(spec))


def random_params(spec, seed=0):
    return _v3.random_leaves(param_shapes(spec), seed)


def init_pool(spec, num_blocks, block_size, dtype="float32"):
    """The zeroed latent pool, ``deepseek_v3``'s one leaf; with the
    module loaded its cache is layer ``num_hidden_layers`` of the SAME
    leaf, on the same block table: adoption, forks, eviction and
    accounting carry it with the block."""
    layers = spec["num_hidden_layers"] + spec.get("draft_layers", 0)
    return _v3.init_pool(dict(spec, num_hidden_layers=layers),
                         num_blocks, block_size, dtype)


def paged_step(params, pools, tables, tokens, positions, valid, spec,
               block_size, scales=None, all_logits=False, hidden=False):
    """The program store's seam: ``deepseek_v3``'s step over the
    target's layers; ``hidden``: also the last layer's output before
    the final norm, which :func:`draft_step` drafts from."""
    if scales is not None:
        raise MXNetError("%s has no int8 latent pool" % spec["arch"])
    return _v3.paged_step_leaves(
        params, pools, tables, tokens, positions, valid, spec,
        block_size, all_logits=all_logits, hidden=hidden)


def draft_step(params, pools, tables, hidden, tokens, positions, valid,
               spec, block_size):
    """The prediction module over ``Lq`` rows a sequence: row ``r`` of
    sequence ``b`` sits at position ``positions[b] + r`` and is computed
    from ``hidden[b, r]`` — the target's hidden state of the position
    BEFORE it — and ``tokens[b, r]``, the token AT it.  Writes the
    rows' latents into layer ``num_hidden_layers`` of the leaf and
    attends over rows 1 .. there.  Returns ``(logits (B, vocab) fp32 at
    each sequence's last valid row: the draft of the token after it,
    pools, the module's AUX_COUNTERS)``."""
    import jax.numpy as jnp
    f32 = jnp.float32
    B, Lq = tokens.shape
    D, eps = spec["hidden_size"], spec["rms_norm_eps"]
    cdt = params["mtp_final_norm_gamma"].dtype
    st = _v3._Step(tables, (B, Lq), positions, valid, spec, block_size)
    e = _v3._embed(params["embed_weight"], tokens).astype(f32)
    u = jnp.concatenate(
        [_v3._rms(hidden, params["mtp_h_norm_gamma"], eps),
         _v3._rms(e, params["mtp_e_norm_gamma"], eps)], axis=-1)
    u = _v3._mm(u.astype(cdt).reshape(B * Lq, 2 * D),
                params["mtp_eh_weight"]).astype(f32)
    v, pools, counts = _v3.decoder_layer(
        u, _v3.layer_leaves(params, "mtp_"), tuple(pools),
        spec["num_hidden_layers"], False, (st,), first=1)
    last = v.reshape(B, Lq, D)[jnp.arange(B), st.valid - 1]
    vN = _v3._rms(last, params["mtp_final_norm_gamma"], eps).astype(cdt)
    return _v3._mm(vN, params["head_weight"], f32), pools, counts
