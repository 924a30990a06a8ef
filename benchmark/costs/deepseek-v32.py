"""Required operations and bytes of the ``deepseek-v32`` configuration,
from shapes and counts (``cfg["spec"]``), and those of its kernels.

Hand-worked case (tests), the published widths: everything of
``costs/deepseek-v3.py`` (MLA 187,114,496 parameters a layer, a dense
layer's feed-forward 396,368,896, an expert layer's router, shared
expert and norm 45,882,624, a routed expert 44,040,192, embedding and head
16,160 x 7168 each: 4,565,721,088 over one dense and four expert layers
of 16 experts) and, a layer, the lightning indexer's 13,959,424
(``wq_b`` 1536 x 8192, ``wk`` 7168 x 128, ``k_norm`` 2 x 128,
``weights_proj`` 7168 x 64): 4,635,518,208, 9.27 GB in bfloat16.

What attention requires here is counted by PAIRS, because a query no
longer reads every key: the indexer scores every (query, key) pair a
query sees — 64 heads x 128 values x 2 = 16,384 FLOP, and 2 x 64 more
for the weighted sum of the heads' ReLUs — against one 256 B key a
cached token a sequence; the selection reads each score once; MLA then
runs over the selected pairs alone, 128 heads x (576 + 512) x 2 =
278,528 FLOP a pair, and has to read at least the selected rows, 1,152
B each, once a sequence whatever the form (a chunk's queries may share
them).
"""
from __future__ import annotations

BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def _s(cfg):
    return cfg["spec"]


def indexer_parameters(cfg):
    """Parameters of one layer's indexer: the query up-projection from
    MLA's query latent, the key projection and its LayerNorm's scale
    and bias, the head weights' projection."""
    s = _s(cfg)
    hi, di = int(s["index_n_heads"]), int(s["index_head_dim"])
    d, rq = int(s["hidden_size"]), int(s["q_lora_rank"])
    return hi * di * rq + di * d + 2 * di + hi * d


def layer_parameters(cfg):
    """Parameters of ``(attention with its norm AND its indexer, a
    dense layer's feed-forward with its norm, an expert layer outside
    its routed experts with its norm, one routed expert)``."""
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
    return mla + indexer_parameters(cfg), dense, around, 3 * d * f


def parameters(cfg):
    """All learned parameters held here."""
    s = _s(cfg)
    att, dense, around, expert = layer_parameters(cfg)
    layers, lead = int(s["num_hidden_layers"]), \
        int(s["first_k_dense_replace"])
    d, v = int(s["hidden_size"]), int(s["vocab_size"])
    return (layers * att + lead * dense + (layers - lead) * (
        around + int(s["n_routed_experts"]) * expert) + 2 * v * d + d)


def expected_picks(cfg):
    """Held experts a token picks in one expert layer, on average."""
    s = _s(cfg)
    return int(s["num_experts_per_tok"]) * int(s["n_routed_experts"]) \
        / int(s["router_width"])


def index_pair_flops(cfg):
    """FLOPs of the indexer for ONE (query, key) pair in one layer:
    every head's product over the key's values, and the weighted sum
    of the heads' ReLUs."""
    s = _s(cfg)
    hi = int(s["index_n_heads"])
    return 2 * hi * int(s["index_head_dim"]) + 2 * hi


def attention_pair_flops(cfg):
    """FLOPs of absorbed-form MLA for ONE (query, key) pair of all
    heads in one layer: a score over the row's ``kv_lora_rank +
    qk_rope_head_dim`` values and a weighted sum of its first
    ``kv_lora_rank``."""
    s = _s(cfg)
    return 2 * int(s["num_attention_heads"]) * (
        2 * int(s["kv_lora_rank"]) + int(s["qk_rope_head_dim"]))


def index_key_bytes(cfg):
    """Bytes of one token's index key in one layer."""
    return int(_s(cfg)["index_head_dim"]) \
        * BYTES[cfg["deploy"]["kv_dtype"]]


def latent_row_bytes(cfg):
    """Bytes of one token's latent row in one layer as the algorithm
    needs them (576 values; the pool stores them 640 wide)."""
    s = _s(cfg)
    return (int(s["kv_lora_rank"]) + int(s["qk_rope_head_dim"])) \
        * BYTES[cfg["deploy"]["kv_dtype"]]


def cache_bytes_per_token(cfg):
    """Bytes a cached token holds over all layers, as stored: the
    latent row in whole 128-lane tiles and the index key."""
    s = _s(cfg)
    row = -(-(int(s["kv_lora_rank"]) + int(s["qk_rope_head_dim"])) // 128) \
        * 128 + int(s["index_head_dim"])
    return int(s["num_hidden_layers"]) * row \
        * BYTES[cfg["deploy"]["kv_dtype"]]


def index_kernel_cost(cfg, rows, index_pairs, kv_tokens):
    """``(FLOPs, bytes)`` one layer's indexer call requires for a
    dispatch of ``rows`` live sequences whose queries see
    ``index_pairs`` (query, key) pairs in all and whose frontiers after
    the step sum to ``kv_tokens``: every pair scored, each live
    sequence's index keys read once (queries, weights and the scores
    written left out)."""
    if not rows:
        return 0.0, 0.0
    return (float(index_pair_flops(cfg)) * index_pairs,
            float(index_key_bytes(cfg)) * kv_tokens)


def select_cost(cfg, index_pairs):
    """``(FLOPs, bytes)`` one layer's selection requires: no product,
    and every score (float32) read once."""
    return 0.0, 4.0 * index_pairs


def sparse_attn_kernel_cost(cfg, rows, keys_selected, kv_tokens,
                            q_tokens):
    """``(FLOPs, bytes)`` one layer's sparse latent attention requires
    for a dispatch of ``rows`` live sequences that bring ``q_tokens``
    queries keeping ``keys_selected`` positions in all (``min(seen,
    index_topk)`` a query), frontiers summing to ``kv_tokens``.
    FLOPs: the selected pairs alone.  Bytes: the least ANY form can
    read, each live sequence's selected rows once however many of its
    queries share them: ``rows`` x the mean a query keeps
    (``keys_selected / q_tokens``; in a decode step exactly the sum
    over the rows of ``min(context, index_topk)``, in a chunk no more
    than it, since a row's last query keeps the most).  A form that
    gathers a copy a query reads ``q_tokens / rows`` times that, so the
    share can only under-read."""
    if not rows or not q_tokens:
        return 0.0, 0.0
    return (float(attention_pair_flops(cfg)) * keys_selected,
            float(latent_row_bytes(cfg)) * rows * keys_selected / q_tokens)


def moe_kernel_cost(cfg, assignments, experts_touched):
    """``(FLOPs, bytes)`` one expert layer's grouped products require:
    gate, up and down of every assignment, and the weights of the held
    experts that got a token (``costs/deepseek-v3.py``'s)."""
    _, _, _, expert = layer_parameters(cfg)
    return (2.0 * expert * assignments,
            float(expert) * experts_touched
            * BYTES[cfg.get("weights_dtype", "float32")])


def forward_flops_per_token(cfg, context, picks=None):
    """FLOPs one token's forward pass requires with ``context`` rows of
    the cache visible to it and ``picks`` held experts a layer: 2 a
    weight of every matrix it is multiplied by, the indexer over every
    visible row and MLA over the ``min(context, index_topk)`` kept."""
    s = _s(cfg)
    picks = expected_picks(cfg) if picks is None else picks
    att, dense, around, expert = layer_parameters(cfg)
    d = int(s["hidden_size"])
    norms = d + int(s["q_lora_rank"]) + int(s["kv_lora_rank"]) \
        + 2 * int(s["index_head_dim"])
    layers, lead = int(s["num_hidden_layers"]), \
        int(s["first_k_dense_replace"])
    width = int(s["router_width"])
    matmul = (layers * (att - norms) + lead * (dense - d)
              + (layers - lead) * (around - d - width + picks * expert)
              + int(s["vocab_size"]) * d)
    return 2 * matmul + layers * (
        index_pair_flops(cfg) * context
        + attention_pair_flops(cfg) * min(context, int(s["index_topk"])))
