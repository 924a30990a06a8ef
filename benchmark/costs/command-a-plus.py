"""Required operations and bytes of the ``command-a-plus`` configuration,
from shapes and counts (``cfg["spec"]``), and those of its kernels.

Hand-worked case (tests), the published widths: a layer outside its
routed experts 344,461,312 parameters (``q`` and ``o`` 16384 x 4096
each, ``k`` and ``v`` 1024 x 4096 each, four shared experts of 3 x 4096
x 4096, the router 128 x 4096, one norm of 4096); one routed expert
50,331,648 (3 x 4096 x 4096), the 16 held here 805,306,368; the tied
embedding 32,768 x 4096 and the final norm.  Four layers (window,
window, window, full): 4,733,292,544, 9.47 GB in bfloat16.

What a token's forward pass requires: 2 FLOPs for every weight of the
matrices it is multiplied by — the shared experts all four, of the
routed ones its share ``picks`` (on average ``num_experts_per_tok *
num_experts / router_width``: 1 here), the tied embedding once (as the
head) — plus attention: every query head multiplies a key's 128 values
into a score and a value's 128 into the output, over the keys its
layer's type lets it see (all of them, or the last ``sliding_window``).
"""
from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def _s(cfg):
    return cfg["spec"]


def layer_parameters(cfg):
    """Parameters of ``(a layer outside its routed experts, one routed
    expert)``."""
    s = _s(cfg)
    d, dh = int(s["hidden_size"]), int(s["head_dim"])
    h, hkv = int(s["num_attention_heads"]), int(s["num_key_value_heads"])
    expert = 3 * d * int(s["intermediate_size"])
    around = (2 * h * dh * d + 2 * hkv * dh * d
              + int(s["num_shared_experts"]) * expert
              + int(s["router_width"]) * d + d)
    return around, expert


def layer_counts(cfg):
    """``(full attention layers, window layers)``."""
    kinds = list(_s(cfg)["layer_types"])
    full = kinds.count("full_attention")
    return full, len(kinds) - full


def parameters(cfg):
    """All learned parameters held here (the tied embedding once)."""
    s = _s(cfg)
    around, expert = layer_parameters(cfg)
    d = int(s["hidden_size"])
    return (int(s["num_hidden_layers"])
            * (around + int(s["num_experts"]) * expert)
            + int(s["vocab_size"]) * d + d)


def expected_picks(cfg):
    """Held experts a token picks in one layer, on average."""
    s = _s(cfg)
    return int(s["num_experts_per_tok"]) * int(s["num_experts"]) \
        / int(s["router_width"])


def attention_flops(cfg, keys):
    """FLOPs of ONE query row of all heads over ``keys`` keys in one
    layer: a score over the head's ``head_dim`` values and a weighted
    sum of as many."""
    s = _s(cfg)
    return 2 * int(s["num_attention_heads"]) * 2 * int(s["head_dim"]) \
        * keys


def forward_flops_per_token(cfg, context, picks=None):
    """FLOPs one token's forward pass requires with ``context`` keys of
    the cache before it and ``picks`` held experts a layer."""
    s = _s(cfg)
    picks = expected_picks(cfg) if picks is None else picks
    around, expert = layer_parameters(cfg)
    d = int(s["hidden_size"])
    full, window = layer_counts(cfg)
    matmul = (full + window) * (around - d + picks * expert) \
        + int(s["vocab_size"]) * d
    return 2 * matmul + full * attention_flops(cfg, context) \
        + window * attention_flops(
            cfg, min(context, int(s["sliding_window"])))


def kv_row_bytes(cfg):
    """Bytes of one token's K and V, every KV head, in one layer."""
    s = _s(cfg)
    return 2 * int(s["num_key_value_heads"]) * int(s["head_dim"]) \
        * BYTES[cfg["deploy"]["kv_dtype"]]


def cache_bytes_per_token(cfg):
    """``(full class, window class)``: bytes a token of context holds
    in each class of cache block, over the class's layers.  A sequence
    holds the window class's for its last ``sliding_window`` tokens
    (and a block's slack) only."""
    full, window = layer_counts(cfg)
    return full * kv_row_bytes(cfg), window * kv_row_bytes(cfg)


def _keys_and_bytes(cfg, rows, kv_tokens, q_tokens):
    v = q_tokens / rows
    keys = q_tokens * max(kv_tokens / rows - (v - 1) / 2.0, 0.0)
    return attention_flops(cfg, keys), kv_row_bytes(cfg) * kv_tokens


def gqa_kernel_cost(cfg, rows, kv_tokens, q_tokens):
    """``(FLOPs, bytes)`` one FULL attention layer's ``paged_attention``
    call requires for a dispatch of ``rows`` live sequences whose
    frontiers after the step sum to ``kv_tokens`` and which bring
    ``q_tokens`` query rows (``rows`` in a decode step).  A chunk's
    queries sit at the end of their sequence, so query j of v sees the
    frontier less ``v - 1 - j`` keys: with the dispatch's means,
    ``q_tokens x (mean frontier - (mean v - 1) / 2)`` keys in all.
    Bytes: each live sequence's K and V rows once (queries and outputs
    left out)."""
    if not rows:
        return 0.0, 0.0
    return _keys_and_bytes(cfg, rows, kv_tokens, q_tokens)


def swa_kernel_cost(cfg, rows, kv_tokens_window, q_tokens):
    """``(FLOPs, bytes)`` one WINDOW layer's ``window_paged_attention``
    call requires for the same dispatch, ``kv_tokens_window`` the sum
    over its live sequences of ``min(frontier, sliding_window)``: the
    keys and values a row's LAST query sees, read once, and the FLOPs
    of as many keys a query (a chunk's earlier queries see a key less
    each only while the sequence is shorter than the window; past it
    every query sees ``sliding_window`` keys, which the mean below
    under-counts by up to ``(v - 1) / 2``: the share can only
    under-read).  What the kernel fetches beside them, the rest of the
    window's first and last groups of blocks and the ``v - 1`` keys
    before the window that a chunk's earlier queries see, is not
    counted as required."""
    if not rows:
        return 0.0, 0.0
    return _keys_and_bytes(cfg, rows, kv_tokens_window, q_tokens)


def moe_kernel_cost(cfg, assignments, experts_touched):
    """``(FLOPs, bytes)`` one expert layer's grouped products require:
    gate, up and down of every assignment, and the weights of the held
    experts that got a token (activations left out: 8 KB a token
    against 101 MB an expert).  The shared experts are plain products
    outside the grouped one and are not in it."""
    expert = layer_parameters(cfg)[1]
    return (2.0 * expert * assignments,
            float(expert) * experts_touched
            * BYTES[cfg.get("weights_dtype", "float32")])
