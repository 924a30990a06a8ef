"""Required operations and bytes of the ``deepseek-v3`` configuration,
from shapes and counts (``cfg["spec"]``), and those of its two kernels.

Hand-worked case (tests), the published widths: MLA 187,114,496
parameters a layer (``q_a`` 1536 x 7168, ``q_b`` 24576 x 1536, ``kv_a``
576 x 7168, ``kv_b`` 32768 x 512, ``o`` 7168 x 16384, norms 7168 + 1536 +
512); a dense layer 583,483,392 with its feed-forward (3 x 7168 x 18432)
and second norm; an expert layer 232,997,120 outside its routed experts
(router 256 x 7168 + 256, shared expert 3 x 7168 x 2048, norm); one
routed expert 44,040,192; embedding and head 16,160 x 7168 each.  One
dense + four expert layers of 16 experts: 4,565,721,088, 9.13 GB in
bfloat16.

What a token's forward pass requires: 2 FLOPs for every weight of the
matrices it is multiplied by — the absorbed form multiplies by ``kv_b``
exactly once too (``q_abs`` by its key half, ``o_lat`` by its value
half) — with its routed share ``picks`` experts a layer (on average
``num_experts_per_tok * n_routed_experts / router_width``: 0.5 here),
plus attention over ``context`` latent rows: every head multiplies a
row's 576 values into a score and its first 512 into the output.
"""
from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def _s(cfg):
    return cfg["spec"]


def layer_parameters(cfg):
    """Parameters of ``(attention with its norm, a dense layer's
    feed-forward with its norm, an expert layer outside its routed
    experts with its norm, one routed expert)``."""
    s = _s(cfg)
    d, h = int(s["hidden_size"]), int(s["num_attention_heads"])
    rq, r = int(s["q_lora_rank"]), int(s["kv_lora_rank"])
    dn, dr, dv = (int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
                  int(s["v_head_dim"]))
    f = int(s["moe_intermediate_size"])
    mla = (rq * d + rq + h * (dn + dr) * rq + (r + dr) * d + r
           + h * (dn + dv) * r + d * h * dv + d)
    dense = 3 * d * int(s["intermediate_size"]) + d
    width = int(s["router_width"])
    around = width * d + width + 3 * d * f * int(s["n_shared_experts"]) + d
    return mla, dense, around, 3 * d * f


def parameters(cfg):
    """All learned parameters held here."""
    s = _s(cfg)
    mla, dense, around, expert = layer_parameters(cfg)
    layers, lead = int(s["num_hidden_layers"]), \
        int(s["first_k_dense_replace"])
    d, v = int(s["hidden_size"]), int(s["vocab_size"])
    return (layers * mla + lead * dense + (layers - lead) * (
        around + int(s["n_routed_experts"]) * expert) + 2 * v * d + d)


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
    """FLOPs one token's forward pass requires with ``context`` rows of
    the cache visible to it and ``picks`` held experts a layer."""
    s = _s(cfg)
    picks = expected_picks(cfg) if picks is None else picks
    mla, dense, around, expert = layer_parameters(cfg)
    d = int(s["hidden_size"])
    norms = d + int(s["q_lora_rank"]) + int(s["kv_lora_rank"])
    layers, lead = int(s["num_hidden_layers"]), \
        int(s["first_k_dense_replace"])
    width = int(s["router_width"])
    matmul = (layers * (mla - norms) + lead * (dense - d)
              + (layers - lead) * (around - d - width + picks * expert)
              + int(s["vocab_size"]) * d)
    return 2 * matmul + layers * attention_flops(cfg, context)


def latent_row_bytes(cfg):
    """Bytes of one token's cache row in one layer as the algorithm
    needs them (576 values; the pool stores them 640 wide)."""
    s = _s(cfg)
    return (int(s["kv_lora_rank"]) + int(s["qk_rope_head_dim"])) \
        * BYTES[cfg["deploy"]["kv_dtype"]]


def decode_step_bytes(cfg, contexts, experts_touched=None):
    """Bytes a decode step over sequences with ``contexts`` visible rows
    has to read at least: every weight it touches once (the embedding's
    rows of the step's tokens, not the table; ``experts_touched`` routed
    experts a layer, all the held ones by default) and each sequence's
    latent rows in every layer."""
    s = _s(cfg)
    w = BYTES[cfg.get("weights_dtype", "float32")]
    _, _, _, expert = layer_parameters(cfg)
    held = int(s["n_routed_experts"])
    touched = held if experts_touched is None else experts_touched
    d, v = int(s["hidden_size"]), int(s["vocab_size"])
    moe_layers = int(s["num_hidden_layers"]) \
        - int(s["first_k_dense_replace"])
    weights = parameters(cfg) - v * d + len(contexts) * d \
        - moe_layers * (held - touched) * expert
    return weights * w + int(s["num_hidden_layers"]) \
        * latent_row_bytes(cfg) * int(sum(contexts))


def mla_kernel_cost(cfg, rows, kv_tokens, q_tokens):
    """``(FLOPs, bytes)`` one layer's ``mla_paged_attention`` call
    requires for a dispatch of ``rows`` live sequences whose frontiers
    after the step sum to ``kv_tokens`` and which bring ``q_tokens``
    query rows (``rows`` in a decode step).  A chunk's queries sit at
    the end of their sequence, so query j of v sees the frontier less
    ``v - 1 - j`` rows: with the dispatch's means, ``q_tokens x (mean
    frontier - (mean v - 1) / 2)`` keys in all.  Bytes: each live
    sequence's latent rows once (queries and outputs left out; the
    padding of the stored row is not required either)."""
    if not rows:
        return 0.0, 0.0
    v = q_tokens / rows
    keys = q_tokens * max(kv_tokens / rows - (v - 1) / 2.0, 0.0)
    return attention_flops(cfg, keys), latent_row_bytes(cfg) * kv_tokens


def moe_kernel_cost(cfg, assignments, experts_touched):
    """``(FLOPs, bytes)`` one expert layer's grouped products require:
    gate, up and down of every assignment, and the weights of the held
    experts that got a token (activations left out: 14 KB a token
    against 88 MB an expert)."""
    _, _, _, expert = layer_parameters(cfg)
    return (2.0 * expert * assignments,
            float(expert) * experts_touched
            * BYTES[cfg.get("weights_dtype", "float32")])
