"""Required operations and bytes of the ``lfm2-24b-a2b`` configuration,
from shapes and counts (``cfg["spec"]``), and those of its kernels.

Hand-worked case (tests), the published widths: a convolution operator
16,783,360 parameters (``in_proj`` 6144 x 2048, ``out_proj`` 2048 x
2048, 3 x 2048 taps) and an attention operator 10,485,888 (``q`` and
``o`` 2048 x 2048 each, ``k`` and ``v`` 512 x 2048 each, two head norms
of 64), each with the layer's two norms of 2048 beside it; the dense
feed-forward 72,351,744 (3 x 2048 x 11776); an expert layer 131,136
outside its experts (router 64 x 2048 + 64) and 9,437,184 an expert (3
x 2048 x 1536); the embedding 65,536 x 2048, tied.  One dense
convolution layer, six convolution and two attention layers of 64
experts: 5,177,950,976, 10.36 GB in bfloat16.

What a token's forward pass requires: 2 FLOPs for every weight of the
matrices it is multiplied by, with ``num_experts_per_tok`` experts a
layer, the tied embedding once (as the head), 2 FLOPs a tap a channel,
plus attention over ``context`` keys: every query head multiplies a
key's 64 values into a score and a value's 64 into the output.
"""
from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def _s(cfg):
    return cfg["spec"]


def layer_parameters(cfg):
    """Parameters of ``(a convolution operator, an attention operator,
    a layer's two norms, the dense feed-forward, an expert layer
    outside its experts, one expert)``."""
    s = _s(cfg)
    d, dh = int(s["hidden_size"]), int(s["head_dim"])
    h, hkv = int(s["num_attention_heads"]), int(s["num_key_value_heads"])
    conv = 3 * d * d + d * d + int(s["conv_L_cache"]) * d
    attn = 2 * h * dh * d + 2 * hkv * dh * d + 2 * dh
    e = int(s["num_experts"])
    return (conv, attn, 2 * d, 3 * d * int(s["intermediate_size"]),
            e * d + e, 3 * d * int(s["moe_intermediate_size"]))


def _counts(cfg):
    s = _s(cfg)
    kinds = list(s["layer_types"])
    n_conv = kinds.count("conv")
    lead = int(s["num_dense_layers"])
    return n_conv, len(kinds) - n_conv, lead, len(kinds) - lead


def parameters(cfg):
    """All learned parameters held here (the tied embedding once)."""
    s = _s(cfg)
    conv, attn, norms, dense, around, expert = layer_parameters(cfg)
    n_conv, n_attn, lead, moe = _counts(cfg)
    d = int(s["hidden_size"])
    return (n_conv * conv + n_attn * attn + (n_conv + n_attn) * norms
            + lead * dense + moe * (around + int(s["num_experts"]) * expert)
            + int(s["vocab_size"]) * d + d)


def attention_flops(cfg, keys):
    """FLOPs of ONE query row of all heads over ``keys`` keys in one
    attention layer: a score over the head's ``head_dim`` values and a
    weighted sum of as many."""
    s = _s(cfg)
    return 2 * int(s["num_attention_heads"]) * 2 * int(s["head_dim"]) \
        * keys


def forward_flops_per_token(cfg, context):
    """FLOPs one token's forward pass requires with ``context`` keys of
    the cache visible to it."""
    s = _s(cfg)
    conv, attn, _, dense, around, expert = layer_parameters(cfg)
    n_conv, n_attn, lead, moe = _counts(cfg)
    d, e = int(s["hidden_size"]), int(s["num_experts"])
    matmul = (n_conv * conv + n_attn * (attn - 2 * int(s["head_dim"]))
              + lead * dense
              + moe * (around - e + int(s["num_experts_per_tok"]) * expert)
              + int(s["vocab_size"]) * d)
    return 2 * matmul + n_attn * attention_flops(cfg, context)


def kv_row_bytes(cfg):
    """Bytes of one token's K and V, every KV head, in one attention
    layer."""
    s = _s(cfg)
    return 2 * int(s["num_key_value_heads"]) * int(s["head_dim"]) \
        * BYTES[cfg["deploy"]["kv_dtype"]]


def state_bytes_per_sequence(cfg):
    """Bytes of what the convolution layers keep of one sequence."""
    s = _s(cfg)
    return _counts(cfg)[0] * (int(s["conv_L_cache"]) - 1) \
        * int(s["hidden_size"]) * BYTES[cfg["deploy"]["kv_dtype"]]


def gqa_kernel_cost(cfg, rows, kv_tokens, q_tokens):
    """``(FLOPs, bytes)`` one attention layer's ``paged_attention`` call
    requires for a dispatch of ``rows`` live sequences whose frontiers
    after the step sum to ``kv_tokens`` and which bring ``q_tokens``
    query rows (``rows`` in a decode step).  A chunk's queries sit at
    the end of their sequence, so query j of v sees the frontier less
    ``v - 1 - j`` keys: with the dispatch's means, ``q_tokens x (mean
    frontier - (mean v - 1) / 2)`` keys in all.  Bytes: each live
    sequence's K and V rows once (queries and outputs left out).  The
    FLOPs are the heads' own 64-wide products; what the kernel spends
    on the zero half of its padded query is not required."""
    if not rows:
        return 0.0, 0.0
    v = q_tokens / rows
    keys = q_tokens * max(kv_tokens / rows - (v - 1) / 2.0, 0.0)
    return attention_flops(cfg, keys), kv_row_bytes(cfg) * kv_tokens


def moe_kernel_cost(cfg, assignments, experts_touched):
    """``(FLOPs, bytes)`` one expert layer's grouped products require:
    gate, up and down of every assignment, and the weights of the
    experts that got a token (activations left out: 4 KB a token
    against 18.9 MB an expert)."""
    expert = layer_parameters(cfg)[5]
    return (2.0 * expert * assignments,
            float(expert) * experts_touched
            * BYTES[cfg.get("weights_dtype", "float32")])
