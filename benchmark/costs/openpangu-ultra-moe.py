"""Required operations and bytes of the ``openpangu-ultra-moe``
configuration, from shapes and counts (``cfg["spec"]``), and those of
its kernels as a SELF-DRAFTING step calls them.

Hand-worked case (tests), the published widths: MLA 196,584,960
parameters a layer (``q_a`` 1536 x 7680 = 11,796,480, ``q_b`` 24576 x
1536 = 37,748,736, ``kv_a`` 576 x 7680 = 4,423,680, ``kv_b`` 32768 x 512
= 16,777,216, ``o`` 7680 x 16384 = 125,829,120, norms 7680 + 1536 +
512); the sandwich's three further norms 3 x 7680 a layer; a dense
feed-forward 3 x 7680 x 18432 = 424,673,280; one routed expert 3 x 7680
x 2048 = 47,185,920; the router 256 x 7680 = 1,966,080 (no bias); the
shared expert 47,185,920.  So a dense layer is 621,281,280, an expert
layer of 16 held experts 1,000,734,720 (245,760,000 outside its routed
experts), the module that layer + ``W_eh`` 7680 x 15360 = 117,964,800 +
its three norms 23,040 = 1,118,722,560, embedding and head 19,200 x 7680
= 147,456,000 each, the final norm 7,680: 6,037,862,400 in all, 12.08 GB
in bfloat16 (4,919,139,840 without the module).

A self-drafting decode step brings TWO query rows a sequence to each of
the target's layers (the pending token and the module's proposal) and
up to two to the module's layer (a row a token emitted); the latent
rows are read once whatever the queries, so the kernel, at its ridge
with one query a row (242 FLOP/B against the v5e's 240), is bound by
FLOPs with two.
"""
from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def _s(cfg):
    return cfg["spec"]


def draft_layers(cfg):
    """Prediction modules the deployment loads (0 or 1)."""
    return int((cfg.get("deploy") or {}).get("self_draft") or 0)


def layer_parameters(cfg):
    """Parameters of ``(attention with its two norms, a dense layer's
    feed-forward with its two norms, an expert layer outside its routed
    experts with its two norms, one routed expert)``."""
    s = _s(cfg)
    d, h = int(s["hidden_size"]), int(s["num_attention_heads"])
    rq, r = int(s["q_lora_rank"]), int(s["kv_lora_rank"])
    dn, dr, dv = (int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
                  int(s["v_head_dim"]))
    f = int(s["moe_intermediate_size"])
    mla = (rq * d + rq + h * (dn + dr) * rq + (r + dr) * d + r
           + h * (dn + dv) * r + d * h * dv + 2 * d)
    dense = 3 * d * int(s["intermediate_size"]) + 2 * d
    around = int(s["router_width"]) * d \
        + 3 * d * f * int(s["n_shared_experts"]) + 2 * d
    return mla, dense, around, 3 * d * f


def module_parameters(cfg):
    """One prediction module: a whole expert layer, ``W_eh`` and its
    three norms (embedding and head are the target's)."""
    s = _s(cfg)
    d = int(s["hidden_size"])
    mla, _, around, expert = layer_parameters(cfg)
    return mla + around + int(s["n_routed_experts"]) * expert \
        + 2 * d * d + 3 * d


def parameters(cfg):
    """All learned parameters held here, the module's among them where
    the deployment loads it."""
    s = _s(cfg)
    mla, dense, around, expert = layer_parameters(cfg)
    layers, lead = int(s["num_hidden_layers"]), \
        int(s["first_k_dense_replace"])
    d, v = int(s["hidden_size"]), int(s["vocab_size"])
    return (layers * mla + lead * dense + (layers - lead) * (
        around + int(s["n_routed_experts"]) * expert) + 2 * v * d + d
        + draft_layers(cfg) * module_parameters(cfg))


def expected_picks(cfg):
    """Held experts a token picks in one expert layer, on average."""
    s = _s(cfg)
    return int(s["num_experts_per_tok"]) * int(s["n_routed_experts"]) \
        / int(s["router_width"])


def attention_flops(cfg, keys):
    """FLOPs of ONE query row of all heads over ``keys`` latent rows in
    one layer: a score over the row's ``kv_lora_rank + qk_rope_head_dim``
    values and a weighted sum of its first ``kv_lora_rank``."""
    s = _s(cfg)
    return 2 * int(s["num_attention_heads"]) * (
        2 * int(s["kv_lora_rank"]) + int(s["qk_rope_head_dim"])) * keys


def forward_flops_per_token(cfg, context, picks=None):
    """FLOPs one token's pass through the TARGET requires with
    ``context`` rows of the cache visible to it and ``picks`` held
    experts a layer."""
    s = _s(cfg)
    picks = expected_picks(cfg) if picks is None else picks
    mla, dense, around, expert = layer_parameters(cfg)
    d = int(s["hidden_size"])
    norms = 2 * d + int(s["q_lora_rank"]) + int(s["kv_lora_rank"])
    layers, lead = int(s["num_hidden_layers"]), \
        int(s["first_k_dense_replace"])
    matmul = (layers * (mla - norms) + lead * (dense - 2 * d)
              + (layers - lead) * (around - 2 * d + picks * expert)
              + int(s["vocab_size"]) * d)
    return 2 * matmul + layers * attention_flops(cfg, context)


def latent_row_bytes(cfg):
    """Bytes of one token's cache row in one layer as the algorithm
    needs them (576 values; the pool stores them 640 wide)."""
    s = _s(cfg)
    return (int(s["kv_lora_rank"]) + int(s["qk_rope_head_dim"])) \
        * BYTES[cfg["deploy"]["kv_dtype"]]


def decode_step_bytes(cfg, contexts):
    """Bytes a self-drafting decode step over sequences with
    ``contexts`` visible rows has to read at least: every weight once
    (the embedding's rows of the step's tokens, not the table) and each
    sequence's latent rows in every layer, the module's among them."""
    s = _s(cfg)
    d, v = int(s["hidden_size"]), int(s["vocab_size"])
    weights = parameters(cfg) - v * d + len(contexts) * d
    return weights * BYTES[cfg.get("weights_dtype", "float32")] \
        + (int(s["num_hidden_layers"]) + draft_layers(cfg)) \
        * latent_row_bytes(cfg) * int(sum(contexts))


def mla_kernel_cost(cfg, rows, kv_tokens, q_tokens):
    """``(FLOPs, bytes)`` one layer's ``mla_paged_attention`` call
    requires for a dispatch of ``rows`` live sequences whose frontiers
    after the step sum to ``kv_tokens`` and which bring ``q_tokens``
    query rows: ``2 x rows`` in a self-drafting decode step that
    verifies a proposal a row.  The queries sit at the end of their
    sequence, so query j of v sees the frontier less ``v - 1 - j`` rows:
    with the dispatch's means, ``q_tokens x (mean frontier - (mean v -
    1) / 2)`` keys in all.  Bytes: each live sequence's latent rows
    ONCE, however many queries read them (queries and outputs left out;
    the padding of the stored row is not required either)."""
    if not rows:
        return 0.0, 0.0
    v = q_tokens / rows
    keys = q_tokens * max(kv_tokens / rows - (v - 1) / 2.0, 0.0)
    return attention_flops(cfg, keys), latent_row_bytes(cfg) * kv_tokens


def moe_kernel_cost(cfg, assignments, experts_touched):
    """``(FLOPs, bytes)`` one expert layer's grouped products require:
    gate, up and down of every assignment, and the weights of the held
    experts that got a token (activations left out: 15 KB a token
    against 94 MB an expert)."""
    _, _, _, expert = layer_parameters(cfg)
    return (2.0 * expert * assignments,
            float(expert) * experts_touched
            * BYTES[cfg.get("weights_dtype", "float32")])
