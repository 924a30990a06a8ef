"""The cell ``command-a-plus.serve-ragmix-backlog`` as the benchmark
runs it: the benchmark's own tests of this configuration (the costs of
the published widths, the cell's rehearsal, a window dropped under it,
its readers) run in tier-1 from where they live, and the plain
reference its comparison rests on against the published classes of the
installed ``transformers`` (the dense family's)."""
import numpy as np
import pytest

from _cohere2_moe_common import (HF_TOL, SPEC_IN, _hf, _seed_module,
                                 ref)  # noqa: F401

pytest.register_assert_rewrite("benchmark.tests.test_command_a_plus")
from benchmark.tests.test_command_a_plus import *  # noqa: E402,F401,F403


@pytest.mark.parametrize("part", [
    "norm", "sliding_attention", "full_attention",
    "parallel_block_sliding", "parallel_block_full", "tied_head"])
def test_reference_equals_transformers_cohere2(ref, part):
    """The reference's mean-subtracting norm, its attention under the
    window's mask with interleaved rotary and under the causal mask
    with none, the parallel block (attention and the feed-forward read
    the same normed rows) and the tied head times ``logit_scale``,
    against ``Cohere2LayerNorm``, ``Cohere2Attention``,
    ``Cohere2DecoderLayer`` and ``Cohere2ForCausalLM`` on seeded
    weights, at a window of 5 under 13 tokens."""
    import jax
    import jax.numpy as jnp
    torch, modeling, config = _hf()
    n, w = 13, 5
    x = np.random.RandomState(3).randn(1, n, 64).astype(np.float32)
    xt = torch.from_numpy(x)
    pos = torch.arange(n)[None]
    cos_sin = modeling.Cohere2RotaryEmbedding(config)(xt, pos)
    q, k = torch.arange(n)[:, None], torch.arange(n)[None, :]
    causal = torch.zeros(n, n).masked_fill(k > q, float("-inf"))
    masks = {"full_attention": causal[None, None],
             "sliding_attention": causal.masked_fill(
                 k <= q - w, float("-inf"))[None, None]}
    s = dict(SPEC_IN, layer_types=["sliding_attention", "full_attention"],
             num_hidden_layers=2, sliding_window=w)
    jx = jnp.asarray(x[0])

    def attn_leaves(wts, b, pre=""):
        return {b + "q_weight": wts[pre + "q_proj.weight"],
                b + "k_weight": wts[pre + "k_proj.weight"],
                b + "v_weight": wts[pre + "v_proj.weight"],
                b + "o_weight": wts[pre + "o_proj.weight"]}

    with torch.no_grad(), jax.default_matmul_precision("highest"):
        if part == "norm":
            mod = modeling.Cohere2LayerNorm(64, eps=1e-5)
            wts = _seed_module(torch, mod, 1)
            want = mod(xt).numpy()[0]
            got = ref.layer_norm(jx, jnp.asarray(wts["weight"]), 1e-5)
        elif part.endswith("attention"):
            i = 0 if part == "sliding_attention" else 1
            mod = modeling.Cohere2Attention(config, i)
            wts = _seed_module(torch, mod, 2 + i)
            want = mod(xt, cos_sin, masks[part])[0].numpy()[0]
            b = "l%d_" % i
            got = ref.attention(jx, {k_: jnp.asarray(v) for k_, v in
                                     attn_leaves(wts, b).items()}, b, s,
                                part)
        elif part.startswith("parallel_block"):
            i = 0 if part.endswith("sliding") else 1
            mod = modeling.Cohere2DecoderLayer(config, i)
            wts = _seed_module(torch, mod, 4 + i)
            want = mod(xt, cos_sin,
                       attention_mask=masks[s["layer_types"][i]]
                       ).numpy()[0]
            b = "l%d_" % i
            p = {k_: jnp.asarray(v) for k_, v in dict(
                attn_leaves(wts, b, "self_attn."),
                **{b + "norm_gamma": wts["input_layernorm.weight"]})
                .items()}
            mlp = [jnp.asarray(wts["mlp.%s_proj.weight" % m])
                   for m in ("gate", "up", "down")]
            got = ref.decoder_layer(
                jx, p, i, s, ffn=lambda h: ref.gated(h, *mlp))
        else:
            mod = modeling.Cohere2ForCausalLM(config)
            wts = _seed_module(torch, mod, 6)
            assert mod.lm_head.weight is mod.model.embed_tokens.weight
            want = (mod.lm_head(mod.model.norm(xt))
                    * mod.logit_scale).numpy()[0]
            got = ref.head(jx, {
                "final_norm_gamma": jnp.asarray(wts["model.norm.weight"]),
                "embed_tokens_weight": jnp.asarray(
                    wts["model.embed_tokens.weight"])}, {"spec": s})
    assert np.abs(np.asarray(got) - want).max() < HF_TOL * max(
        1.0, np.abs(want).max())
