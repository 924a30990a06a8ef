"""Required operations and bytes of the ``lm2048`` configuration, from
shapes.  Nothing reads these in a per-layer metric yet (the roofline
shares of the decode and prefill programs need the per-dispatch context
counts of the ``tracing`` issue); they are kept, with a hand-worked
test, so that the first such metric computes with the benchmark's
arithmetic and not the program's.

Hand-worked case (tests): 16 layers of 2048 with a 4x feed-forward and
a vocabulary of 32768 hold 939,790,336 parameters, of which
872,415,232 sit in the matrices a token is multiplied by (all but the
embedding table, the norms and the biases): 1.745 GFLOP a token before
attention.
"""
from __future__ import annotations


def parameters(cfg):
    """(all learned parameters, those in the per-token matmuls)."""
    d, v = int(cfg["num_hidden"]), int(cfg["vocab_size"])
    f = int(cfg["ffn_mult"]) * d
    layer_mm = 4 * d * d + 2 * d * f
    layer_rest = 2 * d + f + d            # two norm scales, two biases
    n = int(cfg["num_layers"])
    matmul = n * layer_mm + v * d         # blocks + output head
    rest = n * layer_rest + v * d + 2 * d + v   # embedding, norm, bias
    return matmul + rest, matmul


def forward_flops_per_token(cfg, context):
    """FLOPs one token's forward pass requires with ``context`` keys
    visible to it: 2 per weight of the matmuls, plus scores and the
    weighted sum of values over the context in every layer."""
    _, matmul = parameters(cfg)
    attention = 4 * int(cfg["num_hidden"]) * int(context)
    return 2 * matmul + int(cfg["num_layers"]) * attention


def decode_step_bytes(cfg, contexts, weight_bytes=4, kv_bytes=4):
    """Bytes a decode step over sequences with ``contexts`` visible keys
    has to read at least: every weight once, and each sequence's keys
    and values."""
    total, _ = parameters(cfg)
    kv = 2 * int(cfg["num_layers"]) * int(cfg["num_hidden"]) * kv_bytes
    return total * weight_bytes + kv * int(sum(contexts))
