"""DeepSeek-V3.2's decoder on the paged serving plane: DeepSeek-V3
(``models/deepseek_v3.py``: MLA in the absorbed form, the group-limited
router, this chip's share of the experts) with DeepSeek sparse
attention — a LIGHTNING INDEXER in every layer scores each cached
position for a query, the ``index_topk`` (2,048) best stay, and MLA's
softmax runs over those rows alone.

Source: https://huggingface.co/deepseek-ai/DeepSeek-V3.2 (``config.json``:
``deepseek-v3``'s key for key plus ``index_n_heads`` 64,
``index_head_dim`` 128, ``index_topk`` 2048); the ``Indexer`` class of
the released ``inference/model.py`` of DeepSeek-V3.2-Exp.  The
equations are in ``deepseek_v3``'s docstring ("Sparse attention"): the
attention block is ONE function for both models, which traces the
indexer for a spec that has the three keys and nothing of it otherwise.
Departures from the released code, as ``benchmark/configs/
deepseek-v32.json`` lists them under ``assumed``: no Hadamard rotation
of the index queries and keys (orthogonal: every ``q . k`` is what it
was) and no FP8 rounding of them (the v5e multiplies no FP8; the leaf
holds bfloat16 rows), the indexer's rotary turns halves, no bias on the
three projections.

What this module adds to the store's seam is the model's second TOKEN
LEAF: the index keys, ``(layers, 1, blocks * block, index_head_dim)``,
beside the latent leaf on the SAME block table and in the same class of
block — written by the same ``write_plan``, forked and adopted with the
block (``decode_engine``'s cache manager knows blocks, not leaves).
"""
from __future__ import annotations

from ..base import MXNetError
from . import deepseek_v3 as _v3
from .deepseek_v3 import (AUX_COUNTERS, OFFERS,  # noqa: F401
                          QUANTIZE_TAKES_LEAVES, pack_params, paged_step,
                          paged_step_groups)

__all__ = ["serving_spec", "param_shapes", "random_params",
           "required_params", "matmul_weights", "pack_params",
           "quantize_params", "init_pool", "paged_step",
           "paged_step_groups", "OFFERS", "AUX_COUNTERS"]

_INDEX_KEYS = ("index_n_heads", "index_head_dim", "index_topk")


def serving_spec(spec):
    """``deepseek_v3``'s validated spec with the indexer's three keys."""
    missing = [k for k in _INDEX_KEYS if k not in spec]
    if missing:
        raise MXNetError("deepseek_v32 spec is missing %s" % missing)
    out = dict(_v3.serving_spec(spec), arch="deepseek_v32")
    for k in _INDEX_KEYS:
        out[k] = int(spec[k])
    if min(out[k] for k in _INDEX_KEYS) < 1 or \
            out["index_head_dim"] < out["qk_rope_head_dim"]:
        raise MXNetError("deepseek_v32 spec: an index head of %d values "
                         "cannot hold the rotary part's %d"
                         % (out["index_head_dim"], out["qk_rope_head_dim"]))
    return out


def param_shapes(spec):
    """``deepseek_v3``'s leaves and, a layer, the indexer's five."""
    out = _v3.param_shapes(spec)
    D, rq = spec["hidden_size"], spec["q_lora_rank"]
    Hi, di = spec["index_n_heads"], spec["index_head_dim"]
    for i in range(spec["num_hidden_layers"]):
        p = "l%d_idx_" % i
        out.update({p + "q_b_weight": (Hi * di, rq),
                    p + "k_weight": (di, D),
                    p + "k_norm_gamma": (di,), p + "k_norm_beta": (di,),
                    p + "w_weight": (Hi, D)})
    return out


def required_params(spec):
    """``deepseek_v3``'s packed leaves and the indexer's."""
    return _v3.required_params(spec) + [
        n for n in param_shapes(spec) if "_idx_" in n]


def matmul_weights(spec):
    """Every matmul weight, the indexer's three a layer among them."""
    return [n for n in required_params(spec)
            if n.endswith("_weight") or "_experts_" in n]


def quantize_params(params, spec):
    return _v3.quantize_leaves(params, matmul_weights(spec))


def random_params(spec, seed=0):
    return _v3.random_leaves(param_shapes(spec), seed)


def init_pool(spec, num_blocks, block_size, dtype="float32"):
    """The zeroed pool: ``(latent, index keys)``, two token leaves on
    one table — ``deepseek_v3``'s latent leaf and ``(num_layers, 1,
    num_blocks * block_size, index_head_dim)``."""
    import jax.numpy as jnp
    latent, = _v3.init_pool(spec, num_blocks, block_size, dtype)
    return latent, jnp.zeros(latent.shape[:3] + (spec["index_head_dim"],),
                             dtype)
