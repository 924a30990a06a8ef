"""LFM2-MoE's model functions at toy sizes on the CPU: the plain
reference against the published classes of the installed
``transformers``, the expert block with every expert held, the router,
the grouped-query kernel against dense attention, and the paged step
under the kernels and under their twins (its store and engine are
tests/test_lfm2_moe_store.py's, its cell tests/test_lfm2_moe_cell.py's)."""
import importlib.util

import numpy as np
import pytest

from mxnet_tpu.models import lfm2_moe as lfm

from _lfm2_moe_common import (BS, CHUNK, HF_TOL, LOGIT_TOL, PARAMS, SPEC,
                              SPEC_IN, _hf, _seed_module, ref)


@pytest.mark.parametrize("part", ["conv", "full_attention",
                                  "decoder_layer_conv",
                                  "decoder_layer_attention"])
def test_reference_equals_transformers_lfm2(ref, part):
    """The reference's gated short convolution, its grouped-query
    attention with QK-norm and rotary by halves, and a whole decoder
    layer with the dense feed-forward, against ``Lfm2ShortConv``,
    ``Lfm2Attention`` and ``Lfm2DecoderLayer`` on seeded weights."""
    import jax
    import jax.numpy as jnp
    torch, modeling, config = _hf()
    T = 11
    x = np.random.RandomState(3).randn(1, T, 64).astype(np.float32)
    xt = torch.from_numpy(x)
    pos = torch.arange(T)[None]
    cos_sin = modeling.Lfm2RotaryEmbedding(config)(xt, pos)
    mask = torch.full((T, T), float("-inf")).triu(1)[None, None]
    s = dict(SPEC_IN, layer_types=["conv", "full_attention"],
             num_hidden_layers=2, num_dense_layers=2)

    def attn_leaves(w, b, pre=""):
        return {b + "q_weight": w[pre + "q_proj.weight"],
                b + "k_weight": w[pre + "k_proj.weight"],
                b + "v_weight": w[pre + "v_proj.weight"],
                b + "o_weight": w[pre + "out_proj.weight"],
                b + "q_norm_gamma": w[pre + "q_layernorm.weight"],
                b + "k_norm_gamma": w[pre + "k_layernorm.weight"]}

    with torch.no_grad(), jax.default_matmul_precision("highest"):
        if part == "conv":
            mod = modeling.Lfm2ShortConv(config, 0)
            w = _seed_module(torch, mod, 1)
            want = mod.slow_forward(xt).numpy()[0]
            got = ref.short_conv(jnp.asarray(x[0]), w["in_proj.weight"],
                                 w["conv.weight"][:, 0],
                                 w["out_proj.weight"])
        elif part == "full_attention":
            mod = modeling.Lfm2Attention(config, 1)
            w = _seed_module(torch, mod, 2)
            want = mod(xt, cos_sin, mask)[0].numpy()[0]
            got = ref.attention(
                jnp.asarray(x[0]),
                {k: jnp.asarray(v) for k, v in attn_leaves(w, "l1_")
                 .items()}, "l1_", s)
        else:
            i = 0 if part.endswith("conv") else 1
            mod = modeling.Lfm2DecoderLayer(config, i)
            w = _seed_module(torch, mod, 4 + i)
            want = mod(xt, cos_sin, attention_mask=mask).numpy()[0]
            b = "l%d_" % i
            p = {b + "op_norm_gamma": w["operator_norm.weight"],
                 b + "ffn_norm_gamma": w["ffn_norm.weight"],
                 b + "gate_weight": w["feed_forward.w1.weight"],
                 b + "up_weight": w["feed_forward.w3.weight"],
                 b + "down_weight": w["feed_forward.w2.weight"]}
            if i == 0:
                p.update({b + "in_weight": w["conv.in_proj.weight"],
                          b + "conv_weight": w["conv.conv.weight"][:, 0],
                          b + "out_weight": w["conv.out_proj.weight"]})
            else:
                p.update(attn_leaves(w, b, "self_attn."))
            got = ref.decoder_layer(
                jnp.asarray(x[0]),
                {k: jnp.asarray(v) for k, v in p.items()}, i, s)
    assert np.abs(np.asarray(got) - want).max() < HF_TOL * max(
        1.0, np.abs(want).max())


# ---------------------------------------------------------------------------
# (d) the expert block, every expert held
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["0", "2"])
def test_expert_block_with_every_expert_held(monkeypatch, mode):
    """64 = held = scored, 4 a token, at ``n_group = topk_group = 1``:
    the grouped product (interpreted) and the default lowering against
    the dense twin, and every pick lands on a held expert."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe
    monkeypatch.setenv("MXNET_PALLAS", mode)
    rs = np.random.RandomState(4)
    N, D, F, E, K = 24, 32, 16, 64, 4
    x = jnp.asarray(rs.randn(N, D), jnp.float32)
    gu = jnp.asarray(rs.randn(E, D, 2 * F) / np.sqrt(D), jnp.float32)
    down = jnp.asarray(rs.randn(E, F, D) / np.sqrt(F), jnp.float32)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(N, E), jnp.float32))
    bias = jnp.asarray(0.02 * rs.randn(E), jnp.float32)
    experts, weights = moe.route_grouped(scores, bias, K, 1, 1, 1.0,
                                         lfm.ROUTE_EPS)
    top = np.argsort(-(np.asarray(scores) + np.asarray(bias)), axis=1,
                     kind="stable")[:, :K]
    assert np.array_equal(np.asarray(experts), top)
    picked = np.take_along_axis(np.asarray(scores), top, axis=1)
    assert np.allclose(np.asarray(weights), picked / (
        picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    live = jnp.asarray(np.arange(N) % 5 != 0)
    y, counts = moe.moe_experts(x, gu, down, experts, weights, live)
    want, want_counts = moe.moe_experts_reference(x, gu, down, experts,
                                                  weights, live)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-5
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert int(np.asarray(counts).sum()) == K * int(np.asarray(live).sum())


@pytest.mark.parametrize("groups,keep", [(8, 4), (4, 2), (1, 1)])
def test_route_grouped_without_an_epsilon_is_what_it_was(groups, keep):
    """The router's new ``eps`` argument at its default leaves
    ``deepseek_v3``'s weights bit-equal to the picked scores over their
    plain sum."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe
    rs = np.random.RandomState(groups)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(40, 32), jnp.float32))
    bias = jnp.asarray(0.02 * rs.randn(32), jnp.float32)
    experts, weights = moe.route_grouped(scores, bias, 4, groups, keep,
                                         2.5)
    picked = jnp.take_along_axis(scores, experts, axis=1)
    assert np.array_equal(
        np.asarray(weights),
        np.asarray(picked / jnp.sum(picked, axis=-1, keepdims=True) * 2.5))
    again = moe.route_grouped(scores, bias, 4, groups, keep, 2.5, 1e-6)
    assert np.array_equal(np.asarray(again[0]), np.asarray(experts))
    assert not np.array_equal(np.asarray(again[1]), np.asarray(weights))


# ---------------------------------------------------------------------------
# (e) the grouped-query kernel = dense attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lq,positions", [(1, [5, 9, 17]), (8, [0, 3, 16])])
@pytest.mark.parametrize("heads,d,rows", [
    (1, 128, "kv"), (4, 64, "kv"), (4, 128, "kv"), (1, 64, "kv"),
    (4, 64, "k|v"), (1, 64, "k|v")])
def test_grouped_query_kernel_matches_dense_twin(heads, d, rows, lq,
                                                 positions):
    """``flash_attention_paged`` under the interpreter against
    ``paged_attention_reference`` and against plain dense attention
    over the gathered rows: 1 and 4 query heads a pool head, heads of
    64 and 128, K and V in pools of their own and side by side in one
    row under a zero-padded query, two table entries a grid step."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops.paged_attention import (
        flash_attention_paged, paged_attention_reference)
    rs = np.random.RandomState(d + heads + lq)
    B, Hp, bs, T, nb = 3, 2, 8, 4, 14
    H = Hp * heads
    fused = rows == "k|v"
    w = 2 * d if fused else d
    q = rs.randn(B, H, lq, d).astype(np.float32)
    k_pool = jnp.asarray(rs.randn(2, Hp, nb * bs, w), jnp.float32)
    v_pool = None if fused else jnp.asarray(
        rs.randn(2, Hp, nb * bs, d), jnp.float32)
    tables = jnp.asarray(rs.permutation(np.arange(1, nb))[:B * T]
                         .reshape(B, T), jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    query = np.concatenate([q, np.zeros_like(q)], -1) if fused else q
    args = (jnp.asarray(query), k_pool, v_pool, 1, tables, pos, bs)
    got = np.asarray(flash_attention_paged(
        *args, scale=d ** -0.5, interpret=True, group=2))
    twin = np.asarray(paged_attention_reference(*args, scale=d ** -0.5))
    assert np.abs(got - twin).max() < 2e-5
    # plain dense attention, head i over pool head i // heads
    idx = (np.asarray(tables)[:, :, None] * bs + np.arange(bs)).reshape(
        B, T * bs)
    kp = np.asarray(k_pool)[1]
    vp = kp[..., d:] if fused else np.asarray(v_pool)[1]
    out = got[..., d:] if fused else got
    for b in range(B):
        for h in range(H):
            keys = kp[h // heads, idx[b], :d]
            vals = vp[h // heads, idx[b]]
            for r in range(lq):
                n = positions[b] + r + 1
                s = keys[:n] @ q[b, h, r] * d ** -0.5
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ vals[:n]
                assert np.abs(out[b, h, r] - want).max() < 2e-5


_DEFAULT_LOWERING = []     # test_paged_step_same_...: its run, once


@pytest.mark.parametrize("mode", ["0", "2"])
def test_paged_step_same_under_kernels_and_twins(monkeypatch, mode):
    """One chunk and one decode step of the whole model under
    ``MXNET_PALLAS=0`` (twins) and ``=2`` (the kernels, interpreted)
    agree with the default lowering."""
    import jax.numpy as jnp

    def run():
        packed = lfm.pack_params(
            {k: jnp.asarray(v) for k, v in PARAMS.items()}, SPEC)
        kv, state = lfm.init_pool(SPEC, 6, BS)
        tables = np.asarray([[1, 2, 0], [3, 4, 5]], np.int32)
        toks = np.random.RandomState(9).randint(0, 96, (2, CHUNK))
        a, kv, state, _ = lfm.paged_step_apply(
            packed, kv, state, tables, toks, np.asarray([0, 0]),
            np.asarray([5, 8]), SPEC, BS)
        b, kv, state, counts = lfm.paged_step_apply(
            packed, kv, state, tables, toks[:, :1], np.asarray([5, 8]),
            np.asarray([1, 1]), SPEC, BS)
        return np.asarray(a), np.asarray(b), np.asarray(counts)

    # the default lowering's run is the same for both modes: once a file
    if not _DEFAULT_LOWERING:
        _DEFAULT_LOWERING.append(run())
    want = _DEFAULT_LOWERING[0]
    monkeypatch.setenv("MXNET_PALLAS", mode)
    got = run()
    assert np.abs(got[0] - want[0]).max() < LOGIT_TOL
    assert np.abs(got[1] - want[1]).max() < LOGIT_TOL
    assert np.array_equal(got[2], want[2]) and got[2][0] == 4 * 2


@pytest.mark.parametrize("program,lq", [("lfm2_moe", 1),
                                        ("lfm2_moe", CHUNK),
                                        ("transformer_lm", 1)])
def test_step_programs_route_by_heads_a_copy(monkeypatch, program, lq):
    """The route inside the paged kernel, as ``dispatch_stats()`` tells
    it at trace time: this model's decode and chunk programs bring ALL
    pool heads of a block in with one copy (``heads_per_copy=<pool
    heads>``, once an attention layer), ``transformer_lm``'s, one query
    head a pool head, one head a copy."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import dispatch
    monkeypatch.setenv("MXNET_PALLAS", "2")
    tables = np.asarray([[1, 2, 0], [3, 4, 5]], np.int32)
    toks = np.zeros((2, lq), np.int32)
    pos, val = np.asarray([5, 8]), np.asarray([lq, lq])
    if program == "lfm2_moe":
        packed = lfm.pack_params(
            {k: jnp.asarray(v) for k, v in PARAMS.items()}, SPEC)
        pools = lfm.init_pool(SPEC, 6, BS)
        step = lambda: lfm.paged_step_apply(
            packed, *pools, tables, toks, pos, val, SPEC, BS)
        want = {"DotProductAttentionPaged": 2,
                "DotProductAttentionPaged.heads_per_copy=2": 2}
    else:
        lm = importlib.import_module("mxnet_tpu.models.transformer_lm")
        spec = lm.lm_spec(num_layers=2, num_hidden=32, num_heads=4,
                          vocab_size=50)
        params = lm.random_params(spec, seed=3)
        pools = lm.init_pool(spec, 6, BS)
        step = lambda: lm.paged_step_apply(
            params, *pools, tables, toks, pos, val, spec, BS)
        want = {"DotProductAttentionPaged": 2,
                "DotProductAttentionPaged.heads_per_copy=1": 2}
    dispatch.reset_dispatch_stats()
    jax.eval_shape(step)
    got = {k: v for k, v in dispatch.dispatch_stats().items()
           if k.startswith("DotProductAttentionPaged")}
    assert got == want
