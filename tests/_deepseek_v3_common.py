"""What the ``tests/test_deepseek_v3*.py`` files share (and
tests/test_deepseek_v32*.py and tests/test_pangu_ultra_moe*.py read): the
toy spec and parameters and the benchmark's plain reference."""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.serving.program_store import GenerativeProgramStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC_IN = {
    "arch": "deepseek_v3", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "router_width": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "vocab_size": 96, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"}}
SPEC = ds.serving_spec(SPEC_IN)
CFG = {"spec": SPEC_IN}
PARAMS = ds.random_params(SPEC, seed=5)
BS, CHUNK, KV_MAX = 8, 8, 48
# Program against reference in float32 on the CPU: the same products
# associated differently (absorbed against plain attention, an online
# softmax against a whole one, a grouped product against a masked
# loop); logits are of order 1 and readings were 2e-6 .. 5e-6.
LOGIT_TOL = 1e-4
# prompt buckets bound only the contiguous plane, which this model is
# not on; the default ones pass this toy kv_max
STORE_KW = dict(batch_buckets=(2,), prompt_buckets=(8,), kv_block=BS,
                kv_max=KV_MAX, paged=True, prefill_chunk=CHUNK,
                sample="graph")


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (imports nothing of the
    program), loaded by path."""
    spec = importlib.util.spec_from_file_location(
        "deepseek_v3_reference",
        os.path.join(ROOT, "benchmark", "reference", "deepseek-v3.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_logits(ref, tokens):
    import jax.numpy as jnp
    return np.asarray(ref.logits(
        {k: jnp.asarray(v) for k, v in PARAMS.items()},
        jnp.asarray(np.asarray(tokens, np.int32)), CFG))


def _greedy_continuations(ref, prompt, tokens):
    """What the reference's greedy decoding emits after each prefix of
    ``prompt + tokens``, read off ONE forward of the whole sequence (the
    model is causal): ``tokens == _greedy_continuations(ref, prompt,
    tokens)`` holds exactly for the reference's own greedy continuation,
    by induction, where a loop a token traced the reference anew at
    every length."""
    seq = list(prompt) + list(tokens)
    logits = _ref_logits(ref, seq[:-1])
    return [int(t) for t in np.argmax(logits[len(prompt) - 1:], axis=-1)]


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="ds",
                                  **args)


def _sorted_picks(N, K, held, groups):
    """A routing whose picks ON HELD experts, in sorted order, are the
    given ``groups`` (rows an expert, experts ``0 .. held - 1``): token
    ``t``'s pick ``k`` is the ``t * K + k``-th of the flattened list,
    the rest fall on experts held elsewhere."""
    flat = [e for e, n in enumerate(groups) for _ in range(n)]
    assert len(flat) <= N * K
    flat += [held + i % 4 for i in range(N * K - len(flat))]
    return np.asarray(flat, np.int32).reshape(N, K)


# (id, tokens, picks a token, rows each held expert gets or None for the
# seeded routing, weights' dtype, (row tile, moe_expert_streams by hand)
# or None).  40 x 4 = 160 sorted rows tile by 32 (``row_tile``: a 32nd
# of the rows, at least 32), 21 x 4 = 84 by 28 and 7 x 4 = 28 by 28:
# what ``divisor_block`` leaves of the bound where it divides nothing.
_MOE_CASES = [
    ("seeded", 40, 4, None, "float32", None),
    ("all-to-one", 40, 4, None, "float32", None),
    ("none-held", 40, 4, None, "float32", None),
    # expert 1's 70 rows start at row 5 and reach row 74: tiles 0, 1, 2
    # (3 visits) beside expert 0's one and expert 3's one in tile 2
    ("spans-three-tiles", 40, 4, [5, 70, 0, 9], "float32", (32, 5)),
    # rows 30..33 of expert 1 lie across the edge at 32: 1 + 2 + 1 + 1
    ("straddles-an-edge", 40, 4, [30, 4, 20, 6], "float32", (32, 5)),
    ("no-live-row", 40, 4, [0, 0, 0, 0], "float32", (32, 0)),
    # 84 rows, tiles of 28: expert 1 has rows 0..29 (2 visits), expert
    # 2 rows 30..69 (tiles 1 and 2)
    ("rows-not-a-multiple-of-the-bound", 21, 4, [0, 30, 40, 0],
     "float32", (28, 4)),
    ("one-tile-is-the-whole-axis", 7, 4, [10, 0, 8, 9], "float32",
     (28, 3)),
    ("first-and-last-expert-empty", 40, 4, [0, 50, 37, 0], "float32",
     None),
    ("bfloat16", 40, 4, [17, 33, 2, 40], "bfloat16", None),
]
