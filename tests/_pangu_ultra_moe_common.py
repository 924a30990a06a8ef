"""What the ``tests/test_pangu_ultra_moe*.py`` files share: the toy spec
and parameters and the benchmark's plain reference."""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.models import pangu_ultra_moe as pm
from mxnet_tpu.serving import GenerationEngine, ModelRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC_IN = dict(
    arch="pangu_ultra_moe", num_hidden_layers=3, first_k_dense_replace=1,
    hidden_size=64, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=4, router_width=8, n_shared_experts=1,
    num_experts_per_tok=2, vocab_size=97, routed_scaling_factor=2.5,
    rms_norm_eps=1e-5, rope_theta=25600000.0, num_nextn_predict_layers=1,
    sandwich_norm=True, norm_topk_prob=True)
SPEC = pm.serving_spec(dict(SPEC_IN, draft_layers=1))
CFG = {"spec": SPEC_IN, "deploy": {"self_draft": 1}}
PARAMS = pm.random_params(SPEC, seed=3)
BS, CHUNK, KV_MAX = 8, 8, 96
LOGIT_TOL = 2e-4
STORE_KW = dict(batch_buckets=(4,), prompt_buckets=(64,), kv_block=BS,
                kv_max=KV_MAX, paged=True, prefill_chunk=CHUNK,
                sample="graph")


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (imports nothing of the
    program), loaded by path."""
    spec = importlib.util.spec_from_file_location(
        "openpangu_reference", os.path.join(
            ROOT, "benchmark", "reference", "openpangu-ultra-moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _jnp(params):
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in params.items()}


# ---------------------------------------------------------------------------
# (c): the engine, module on against module off
# ---------------------------------------------------------------------------
class _Drafted:
    """A stream that keeps what the engine says its module proposed."""

    def __init__(self):
        self.drafts = []

    def push(self, token):
        pass

    def close(self):
        pass

    def drafted(self, position, token):
        self.drafts.append((position, token))


def _serve(params, draft, waves, told=None, **kw):
    """``waves`` of (prompt, max_tokens[, eos]) through an engine; a
    wave is submitted when the one before has finished.  Returns
    (results by wave, stats); ``told`` gains, a wave, each request's
    ``(position, token)`` of every proposal the engine told its
    stream."""
    reg = ModelRegistry()
    reg.add_generative_model("lm", dict(params), SPEC_IN, self_draft=draft,
                             **dict(STORE_KW, **kw))
    eng = GenerationEngine(reg)
    try:
        out = []
        for wave in waves:
            streams = [_Drafted() for _ in wave]
            futs = [eng.submit("lm", w[0], max_tokens=w[1], stream=s,
                               eos_id=w[2] if len(w) > 2 else None)
                    for w, s in zip(wave, streams)]
            out.append([f.result(timeout=300) for f in futs])
            if told is not None:
                told.append([s.drafts for s in streams])
        stats = eng.stats()
    finally:
        eng.close()
    return out, stats


# ---------------------------------------------------------------------------
# (d): the ACCEPT path
# ---------------------------------------------------------------------------
def _agreeing_params():
    """Weights under which the module is right every time: with every
    output projection zero a layer adds nothing to the residual, so the
    target's next token is a function of its last token alone,
    ``g(t) = argmax Head(norm(Emb(t)))``; the module, reading only the
    embedding of the token at its row through ``W_eh = [0 | I]`` and
    the target's final norm, computes ``g`` one token on."""
    p = {k: np.array(v) for k, v in PARAMS.items()}
    for name in p:
        if name.endswith(("o_weight", "down_weight")):
            p[name][:] = 0
    D = SPEC["hidden_size"]
    p["mtp_eh_weight"] = np.concatenate(
        [np.zeros((D, D), np.float32), np.eye(D, dtype=np.float32)], 1)
    p["mtp_e_norm_gamma"][:] = 1
    p["mtp_final_norm_gamma"] = p["final_norm_gamma"].copy()
    return p
