"""LFM2-MoE on the serving plane, at toy sizes on the CPU: the plain
reference against the published classes of the installed
``transformers``, the paged programs (convolution state one row a
block beside ``[K | V]`` rows a token) against the reference's full
forward, prefix hits and copy-on-write forks against a cold run, the
expert block with every expert held, the grouped-query kernel against
dense attention, and the state rows' return at retirement
(docs/architecture/decode_engine.md, "State beside the pool").
"""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.models import lfm2_moe as lfm
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
from mxnet_tpu.serving.program_store import GenerativeProgramStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC_IN = {
    "arch": "lfm2_moe", "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "conv_L_cache": 3,
    "vocab_size": 96, "norm_eps": 1e-5, "rope_theta": 1e6,
    "routed_scaling_factor": 1.0}
SPEC = lfm.serving_spec(SPEC_IN)
CFG = {"spec": SPEC_IN}
PARAMS = lfm.random_params(SPEC, seed=7)
BS, CHUNK, KV_MAX = 8, 8, 48
# Program against reference in float32 on the CPU: the same products
# associated differently (an online softmax against a whole one, a
# grouped product against a masked loop, a filter over a carried state
# against one over a padded sequence); logits are of order 10 and
# readings were 1e-5 .. 2e-5.
LOGIT_TOL = 2e-4
STORE_KW = dict(batch_buckets=(2,), prompt_buckets=(8,), kv_block=BS,
                kv_max=KV_MAX, paged=True, prefill_chunk=CHUNK,
                sample="graph")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (imports nothing of the
    program), loaded by path."""
    return _load("lfm2_reference", os.path.join(
        ROOT, "benchmark", "reference", "lfm2-24b-a2b.py"))


def _ref_logits(ref, tokens):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            {k: jnp.asarray(v) for k, v in PARAMS.items()},
            jnp.asarray(np.asarray(tokens, np.int32)), CFG))


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="lfm",
                                  **args)


class _Rows:
    """Two table rows over one pool, stepped through the store's
    logits-out program."""

    def __init__(self):
        self.st = _store()
        self.pools = self.st.new_pool()
        self.tables = np.zeros((2, self.st.table_width()), np.int32)

    def step(self, tokens, pos, val, rows=(0, 1)):
        """``tokens[r]`` at ``pos[r]`` for the rows in ``rows``; the
        others ride outside the dispatch.  Returns the logits."""
        lq = max(len(t) for t in tokens)
        lq = 1 if lq == 1 else CHUNK
        toks = np.zeros((2, lq), np.int32)
        tables = np.zeros_like(self.tables)
        p, v = np.zeros(2, np.int32), np.ones(2, np.int32)
        for r, t, at in zip(rows, tokens, pos):
            toks[r, :len(t)] = t
            tables[r], p[r], v[r] = self.tables[r], at, len(t)
        logits, *self.pools = self.st.run_paged_step(
            *self.pools, tables, toks, p, v)
        return np.asarray(logits)

    def prefill(self, row, seq, start=0):
        """``seq[start:]`` in chunks; the last chunk's logits."""
        out = None
        for at in range(start, len(seq), CHUNK):
            out = self.step([seq[at:at + CHUNK]], [at], None,
                            rows=(row,))[row]
        return out


# ---------------------------------------------------------------------------
# (a) the reference = the published classes
# ---------------------------------------------------------------------------
# float32 on both sides, the same equations in another order of
# summation: readings 2e-7 .. 2e-6 on values of order 1
HF_TOL = 1e-5


def _hf():
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
    config = Lfm2Config(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        norm_eps=1e-5, rope_theta=1e6, conv_L_cache=3, conv_bias=False,
        block_auto_adjust_ff_dim=False,
        layer_types=["conv", "full_attention"])
    config._attn_implementation = "eager"
    return torch, modeling, config


def _seed_module(torch, module, seed):
    gen = torch.Generator().manual_seed(seed)
    with torch.no_grad():
        for p in module.parameters():
            p.copy_(torch.randn(p.shape, generator=gen) * 0.3
                    + (1.0 if p.ndim == 1 else 0.0))
    return {k: v.detach().numpy() for k, v in module.named_parameters()}


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
# (b) chunks, then decode, through both leaves = the full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["shorter-than-a-chunk", "three-blocks",
                                  "rows-at-different-positions"])
def test_chunked_prefill_and_decode_logits_match_reference(ref, case):
    """Every logit row the paged programs give equals the reference's
    full forward of that sequence (teacher-forced): a prompt of 5 in
    one chunk, one of 21 over three blocks and three chunks, and two
    rows of one dispatch at different positions; decode steps after
    each, across block boundaries."""
    assert ref.param_shapes(CFG) == lfm.param_shapes(SPEC)
    rs = np.random.RandomState(0)
    rows = _Rows()
    st = rows.st
    assert st.pool_leaves == 2 and st.state_rows_per_block() == 3
    kv, state = rows.pools
    assert kv.shape == (2, 2, st.pool_blocks * BS, 16)
    assert state.shape == (3, 1, st.pool_blocks, 2 * 64)
    rows.tables[0, :4] = [1, 2, 3, 4]
    rows.tables[1, :4] = [5, 6, 7, 8]
    a = rs.randint(0, 96, 30)
    want = _ref_logits(ref, a)
    n = {"shorter-than-a-chunk": 5, "three-blocks": 21}.get(case, 13)
    got = {n - 1: rows.prefill(0, a[:n])}
    if case == "rows-at-different-positions":
        b = rs.randint(0, 96, 20)
        want_b = _ref_logits(ref, b)
        rows.prefill(1, b[:3])
        # one chunk dispatch, both rows live: A one token, B five
        both = rows.step([a[13:14], b[3:8]], [13, 3], None)
        got[13] = both[0]
        assert np.abs(both[1] - want_b[7]).max() < LOGIT_TOL
        for p in range(8, 12):      # decode steps of both rows
            both = rows.step([a[p + 6:p + 7], b[p:p + 1]], [p + 6, p],
                             None)
            got[p + 6] = both[0]
            assert np.abs(both[1] - want_b[p]).max() < LOGIT_TOL, p
    else:
        for p in range(n, n + 6):
            got[p] = rows.step([a[p:p + 1]], [p], None, rows=(0,))[0]
    for p, row in got.items():
        assert np.abs(row - want[p]).max() < LOGIT_TOL, p


# ---------------------------------------------------------------------------
# (c) a prefix hit and a fork = a cold run
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["hit-1-block", "hit-2-blocks", "fork"])
def test_prefix_hit_and_fork_give_a_cold_runs_logits(ref, case):
    """B adopts A's first one or two whole blocks through its table and
    goes on from the block boundary: the state it brings there is the
    adopted block's row of the state leaf, and its logits are a cold
    run's.  ``fork``: B adopts a block A half filled, copies it
    (``copy_block``: its tokens AND its state row) and decodes on in
    the copy, while A's own rows stay what they were."""
    rs = np.random.RandomState(1)
    a = rs.randint(0, 96, 28)
    rows = _Rows()
    rows.tables[0, :4] = [1, 2, 3, 4]
    if case == "fork":
        rows.prefill(0, a[:20])             # block 3 holds 16..19
        rows.pools = rows.st.copy_block(*rows.pools, 3, 5)
        rows.tables[1, :3] = [1, 2, 5]
        b = np.concatenate([a[:20], rs.randint(0, 96, 4)])
        want = _ref_logits(ref, b)
        for p in range(20, 24):
            got = rows.step([b[p:p + 1]], [p], None, rows=(1,))[1]
            assert np.abs(got - want[p]).max() < LOGIT_TOL, p
        want_a = _ref_logits(ref, a)        # A goes on undisturbed
        for p in range(20, 24):
            got = rows.step([a[p:p + 1]], [p], None, rows=(0,))[0]
            assert np.abs(got - want_a[p]).max() < LOGIT_TOL, p
        return
    j = 1 if case == "hit-1-block" else 2
    rows.prefill(0, a[:21])
    b = np.concatenate([a[:j * BS], rs.randint(0, 96, 11)])
    rows.tables[1, :4] = [1, 2, 6, 7][:j] + [8, 9, 10][:4 - j]
    hit = rows.prefill(1, b, start=j * BS)
    cold = _Rows()
    cold.tables[1, :4] = [1, 2, 3, 4]
    assert np.array_equal(hit, cold.prefill(1, b))
    assert np.abs(hit - _ref_logits(ref, b)[-1]).max() < LOGIT_TOL


def test_engine_restores_state_on_a_prefix_hit(ref):
    """``add_generative_model`` -> ``submit`` -> the paged tick, as the
    other two models go: greedy streams equal the reference's own
    greedy continuation; a request that shares two whole blocks is
    admitted on them with its state (``state_restores``), one that
    repeats a whole prompt reruns from the last block boundary, and
    the expert counters arrive with the sampled tokens."""
    rs = np.random.RandomState(2)
    P = [int(t) for t in rs.randint(0, 96, 19)]
    Q = P[:16] + [int(t) for t in rs.randint(0, 96, 5)]
    reg = ModelRegistry()
    reg.add_generative_model("lfm", dict(PARAMS), SPEC_IN, **STORE_KW)
    eng = GenerationEngine(reg)
    try:
        a = eng.submit("lfm", P, max_tokens=6).result(300)
        b = eng.submit("lfm", Q, max_tokens=6).result(300)
        c = eng.submit("lfm", P, max_tokens=6).result(300)
        stats = eng.stats()
    finally:
        eng.close()
    for prompt, res in ((P, a), (Q, b), (P, c)):
        seq = list(prompt)
        for _ in range(6):
            seq.append(int(np.argmax(_ref_logits(ref, seq)[-1])))
        assert res.tokens == seq[len(prompt):]
    assert stats["prefix_hits"] == 2 == stats["state_restores"]
    # whole blocks only, and never the block of the prompt's last token
    assert stats["prefix_hit_tokens"] == 16 + 16
    assert stats["state_bytes"] == 3 * reg.gen_store("lfm").pool_blocks \
        * 2 * 64 * 4
    # 4 expert layers a step; every live token is routed in each
    assert stats["moe_expert_steps"] == 4 * (
        stats["decode_steps"] + stats["prefill_chunks"])
    assert stats["moe_tokens"] == 4 * (19 + 5 + 3 + 3 * 5)
    assert stats["moe_local_assignments"] == 2 * stats["moe_tokens"]


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

    want = run()
    monkeypatch.setenv("MXNET_PALLAS", mode)
    got = run()
    assert np.abs(got[0] - want[0]).max() < LOGIT_TOL
    assert np.abs(got[1] - want[1]).max() < LOGIT_TOL
    assert np.array_equal(got[2], want[2]) and got[2][0] == 4 * 2


# ---------------------------------------------------------------------------
# (f) retirement returns every state row; the seam
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("requests", [1, 3])
def test_retiring_sequences_returns_every_state_row(requests):
    """One allocator: a state row lives and dies with its block.  After
    the last sequence retires the only blocks held are the prefix
    cache's pins, and with those evicted the allocator's live count —
    and with it ``state_rows_live`` — reads 0."""
    rs = np.random.RandomState(requests)
    reg = ModelRegistry()
    reg.add_generative_model("lfm", dict(PARAMS), SPEC_IN, **STORE_KW)
    eng = GenerationEngine(reg)
    try:
        futs = [eng.submit("lfm", [int(t) for t in rs.randint(0, 96, 11)],
                           max_tokens=7) for _ in range(requests)]
        for f in futs:
            f.result(300)
        st = eng._states["lfm"]
        pinned = len(st.prefix)
        assert eng.stats()["state_rows_live"] == 3 * pinned
        assert st.pool.used() == pinned == 2 * requests
        while st.prefix.evict_one():
            pass
        assert st.pool.used() == 0
        assert eng.stats()["state_rows_live"] == 0
    finally:
        eng.close()


def test_seam_and_the_other_models_pools():
    """``lfm2_moe`` offers the paged plane alone; its int8 control
    quantizes every matmul weight (the tied embedding and the experts'
    stacks among them, not the filter's taps); and a model whose every
    leaf is by token reports no state rows."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    from mxnet_tpu.pallas_ops.dequant_matmul import QuantizedWeight
    with pytest.raises(MXNetError, match="contiguous"):
        _store(paged=False)
    with pytest.raises(MXNetError, match="int8"):
        _store(kv_dtype="int8")
    with pytest.raises(MXNetError, match="layer_types"):
        lfm.serving_spec(dict(SPEC_IN, layer_types=["conv"]))
    q8 = _store(compute_dtype="int8")
    for name in lfm.matmul_weights(SPEC):
        assert isinstance(q8._params[name], QuantizedWeight), name
    assert not isinstance(q8._params["l0_conv_weight"], QuantizedWeight)
    assert q8._params["l1_experts_gate_up"].codes.shape == (8, 64, 64)
    spec = lm_spec(num_layers=1, num_hidden=16, num_heads=2,
                   vocab_size=20)
    lm = GenerativeProgramStore(random_params(spec, 1), spec,
                                batch_buckets=(1,), prompt_buckets=(8,),
                                kv_block=8, kv_max=16, paged=True,
                                prefill_chunk=8)
    assert lm.state_rows_per_block() == 0


# ---------------------------------------------------------------------------
# the benchmark's comparison sees a state restored wrongly
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fault", ["none", "restored-state-zeroed"])
def test_rehearsal_fails_on_a_state_restored_wrongly(capsys, monkeypatch,
                                                     fault):
    """``run.py --rehearse`` of the cell in this process: sound, it is
    ``correct`` with most of its compared requests admitted on a prefix
    hit; with the state row a hit restores from zeroed at admission,
    the requests go on from a wrong state and ``correct`` is false."""
    import importlib
    import json
    from benchmark import harness
    from mxnet_tpu.serving.decode_engine import GenerationEngine
    honest = GenerationEngine._admit_paged
    zeroed = []

    def admit(self, model, dq, store):
        honest(self, model, dq, store)
        st = self._states[model]
        bs = store.kv_block
        for slot, r in enumerate(st.slots):
            at = int(st.prog[slot]) if r is not None else 0
            if at and not st.chunks_done[slot] and id(r) not in zeroed:
                zeroed.append(id(r))
                kv, state = st.pools
                block = int(st.tables[slot, at // bs - 1])
                st.pools = (kv, state.at[:, 0, block].set(0))

    if fault != "none":
        monkeypatch.setattr(GenerationEngine, "_admit_paged", admit)
    run = importlib.import_module("benchmark.run")
    try:
        rc = run.main(["--workload", "lfm2-24b-a2b.serve-agent-backlog",
                       "--seed", "41", "--rehearse"])
    finally:
        harness.REHEARSAL = False
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.strip()]
    said = {k: v for ln in out[:-1] for k, v in ln.items()}
    assert rc == 0 and said["counters"]["state_restores"] > 20
    assert said["requests_compared_sharing_a_prefix"] > 20
    assert out[-1]["correct"] is (fault == "none")
    assert bool(zeroed) is (fault != "none")


def test_costs_of_the_published_widths():
    """``benchmark/costs/lfm2-24b-a2b.py`` against the hand-worked case
    in its docstring, and the configuration file against both: every
    published width unchanged, the cut as ``reduced`` says."""
    import json
    costs = _load("lfm2_costs", os.path.join(
        ROOT, "benchmark", "costs", "lfm2-24b-a2b.py"))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    assert costs.layer_parameters(cfg) == (
        16783360, 10485888, 4096, 72351744, 131136, 9437184)
    assert costs.parameters(cfg) == cfg["parameters"] == 5177950976
    assert costs.kv_row_bytes(cfg) * 2 == 4096      # a token, 2 layers
    assert costs.state_bytes_per_sequence(cfg) == 57344
    # a decode step of one sequence at 2,048 of context: bytes bound
    flops, nbytes = costs.gqa_kernel_cost(cfg, 1, 2048, 1)
    assert (flops, nbytes) == (2 * 32 * 2 * 64 * 2048, 2048 * 2048)
    flops, nbytes = costs.moe_kernel_cost(cfg, 512, 64)
    assert (flops, nbytes) == (2.0 * 9437184 * 512, 9437184.0 * 64 * 2)
    spec, pub = cfg["spec"], cfg["published"]
    for key in ("hidden_size", "num_attention_heads",
                "num_key_value_heads", "intermediate_size",
                "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "conv_L_cache", "vocab_size"):
        assert spec[key] == cfg[key], key
    assert (spec["hidden_size"], spec["head_dim"], spec["num_experts"],
            spec["vocab_size"]) == (2048, 64, 64, 65536)
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"] == list(pub)
    assert cfg["layer_types"] == spec["layer_types"] == \
        pub["layer_types"][:1] + pub["layer_types"][2:10]
    assert lfm.param_shapes(lfm.serving_spec(
        {k: v for k, v in spec.items() if k != "arch"})).keys() >= {
            "l0_gate_weight", "l1_e63_down_weight", "l8_conv_weight"}
