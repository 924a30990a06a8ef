"""openPangu-Ultra-MoE on the serving plane, at toy sizes on the CPU:
the sandwich-normed layers and the prediction module against the plain
reference through the cache, the self-drafting engine against the same
store with the module off (block boundaries, a copy-on-write fork, a
pool at capacity, adoption of a shared prefix), the ACCEPT path with
weights under which the module is right every time, the shares' parts
before the post-feed-forward norm, and what the change must leave as it
was (docs/architecture/decode_engine.md, "A step that yields more than
one token").
"""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.models import pangu_ultra_moe as pm
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
from mxnet_tpu.serving.program_store import (GenerativeProgramStore,
                                             spec_verify)

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
# (a), (b): target and module through the cache = the full forward
# ---------------------------------------------------------------------------
def test_target_and_module_through_the_cache_match_the_reference(
        monkeypatch, ref):
    """A sequence prefilled in chunks and then decoded by self-drafting
    steps of two positions a row, the second a WRONG proposal nearly
    every time (a rejection: its rows are junk the next step writes
    over), the module one position ahead of the target throughout.
    Every logit row of both against the reference's full forward."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv("MXNET_PALLAS", "0")
    assert ref.param_shapes(CFG) == pm.param_shapes(SPEC)
    assert pm.param_shapes(pm.serving_spec(SPEC_IN)) == ref.param_shapes(
        {"spec": SPEC_IN})
    rs = np.random.RandomState(0)
    V = SPEC["vocab_size"]
    seq = rs.randint(0, V, 40)
    want = np.asarray(ref.logits(_jnp(PARAMS), jnp.asarray(seq), CFG))
    want_d = np.asarray(ref.draft_logits(_jnp(PARAMS), jnp.asarray(seq),
                                         CFG))
    params = _jnp(ds.pack_params(dict(PARAMS), SPEC))
    pools = pm.init_pool(SPEC, 9, BS)
    assert pools[0].shape == (4, 1, 9 * BS, ds.latent_width(SPEC))
    tables = np.zeros((2, 6), np.int32)
    tables[0, :6] = [1, 2, 3, 4, 5, 6]

    @jax.jit
    def both(pools, toks, nxt, pos, val):
        logits, pools, counts, hid = pm.paged_step(
            params, pools, tables, toks, pos, val, SPEC, BS,
            all_logits=True, hidden=True)
        drafts, pools, _ = pm.draft_step(
            params, pools, tables, hid, nxt, pos + 1, val, SPEC, BS)
        return logits, drafts, pools, counts

    def step(start, n, width, second=None):
        nonlocal pools
        toks = np.zeros((2, width), np.int32)
        nxt = np.zeros((2, width), np.int32)
        toks[0, :n] = seq[start:start + n]
        nxt[0, :n] = seq[start + 1:start + n + 1]
        if second is not None:
            toks[0, 1] = second     # a proposal the target rejects
        logits, drafts, pools, counts = both(
            pools, toks, nxt, np.array([start, 0], np.int32),
            np.array([n, 1], np.int32))
        return np.asarray(logits)[0], np.asarray(drafts)[0]

    at = 0
    for n in (8, 8, 5):                       # the prompt, 21 tokens
        logits, draft = step(at, n, CHUNK)
        assert np.abs(logits[:n] - want[at:at + n]).max() < LOGIT_TOL
        # the module's last row of the chunk: row at + n, drafting the
        # token after it
        assert np.abs(draft - want_d[at + n]).max() < LOGIT_TOL
        at += n
    while at < 38:
        # a decode step: the pending token, and a proposal that is
        # wrong (valid 2 for the target, 1 for the module)
        wrong = (int(np.argmax(want[at])) + 1) % V
        toks = np.zeros((2, 2), np.int32)
        toks[0] = [seq[at], wrong]
        nxt = np.zeros((2, 2), np.int32)
        nxt[0, 0] = seq[at + 1]
        logits, pools_, _, hid = jax.jit(lambda pl: pm.paged_step(
            params, pl, tables, toks, np.array([at, 0], np.int32),
            np.array([2, 1], np.int32), SPEC, BS, all_logits=True,
            hidden=True))(pools)
        drafts, pools, _ = jax.jit(lambda pl, h: pm.draft_step(
            params, pl, tables, h, nxt, np.array([at + 1, 1], np.int32),
            np.array([1, 1], np.int32), SPEC, BS))(pools_, hid)
        assert np.abs(np.asarray(logits)[0, 0] - want[at]).max() \
            < LOGIT_TOL
        assert np.abs(np.asarray(drafts)[0] - want_d[at + 1]).max() \
            < LOGIT_TOL
        at += 1


def test_spec_seam_and_offers():
    with pytest.raises(MXNetError, match="num_nextn_predict_layers"):
        pm.serving_spec({k: v for k, v in SPEC_IN.items()
                         if k != "num_nextn_predict_layers"})
    with pytest.raises(MXNetError, match="not 2"):
        pm.with_draft(pm.serving_spec(SPEC_IN), 2)
    plain = pm.serving_spec(SPEC_IN)
    assert "draft_layers" not in plain and plain["rope_scaling"] is None
    assert not [n for n in pm.param_shapes(plain) if n.startswith("mtp_")]
    extra = set(pm.required_params(SPEC)) - set(pm.required_params(plain))
    assert extra and all(n.startswith("mtp_") for n in extra)
    assert "mtp_eh_weight" in pm.matmul_weights(SPEC)
    assert "l1_post_ffn_norm_gamma" not in pm.matmul_weights(SPEC)
    assert not [n for n in pm.param_shapes(SPEC) if "router_bias" in n]
    assert pm.init_pool(plain, 3, BS)[0].shape[0] == 3
    assert pm.init_pool(SPEC, 3, BS)[0].shape[0] == 4
    assert ds.softmax_scale(SPEC) == (16 + 8) ** -0.5
    # deepseek_v3's own spec keeps its groups, bias and YaRN
    from test_deepseek_v3 import SPEC as V3
    assert V3["rope_scaling"] is not None
    assert [n for n in ds.param_shapes(V3) if "router_bias" in n]
    assert not [n for n in ds.param_shapes(V3) if "post_attn" in n]


@pytest.mark.parametrize("arch", ["lfm2_moe", "cohere2_moe", "deepseek_v3",
                                  "deepseek_v32"])
def test_other_models_refuse_self_draft(arch):
    """A state that cannot roll back (``lfm2_moe``), and the models
    without a prediction module: asking is refused in the store's
    wording, before a weight is touched."""
    mod = importlib.import_module("test_" + arch)
    with pytest.raises(MXNetError, match="does not offer a self-drafting"):
        GenerativeProgramStore({}, mod.SPEC_IN, name=arch, self_draft=1,
                               **mod.STORE_KW)


def test_self_draft_needs_the_paged_plane_in_graph_mode():
    with pytest.raises(MXNetError, match="in-graph"):
        GenerativeProgramStore(dict(PARAMS), SPEC_IN, self_draft=1,
                               **dict(STORE_KW, sample="host"))


def test_store_warms_exactly_the_four_self_draft_programs():
    st = GenerativeProgramStore(dict(PARAMS), SPEC_IN, self_draft=1,
                                **STORE_KW)
    assert sorted(st.warmup()) == sorted(st.step_programs(4)) == [
        ("paged_draft_chunk", 4, CHUNK), ("paged_draft_step", 4, 2),
        ("paged_self_chunk", 4, CHUNK), ("paged_self_verify", 4, 2)]
    assert st.stats()["compiles"] == 4 and st.stats()["self_draft"] == 1
    off = GenerativeProgramStore(
        {k: v for k, v in PARAMS.items() if not k.startswith("mtp_")},
        SPEC_IN, **STORE_KW)
    # without the module the store is an expert store like another:
    # the decode step, and the one-pass tick in the chunk program's
    # place (the self-drafting store keeps its sequence of programs)
    assert not st.one_pass and off.one_pass
    assert sorted(off.warmup()) == [("paged_step_sample", 4, 1),
                                    ("paged_tick_sample", 4, CHUNK)]
    assert off.new_pool()[0].shape[0] == 3


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


def test_served_tokens_are_the_same_with_the_module_on_and_off():
    """Greedy tokens module on == module off, over block boundaries
    (blocks of 8, prompts and outputs of every remainder), a
    copy-on-write fork (every partial prompt tail is pinned and forked
    at the first decode write), a pool at capacity (13 usable blocks:
    admission waits for retirements) and adoption of a shared prefix
    (the second wave adopts the first's two whole blocks: the module's
    rows come with them, and the hit's last token reruns)."""
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 97, 2 * BS).tolist()
    first = [(shared + rng.integers(0, 97, n).tolist(), m)
             for n, m in ((5, 12), (9, 7), (0, 9))] \
        + [(rng.integers(0, 97, 11).tolist(), 10)]
    second = [(shared + rng.integers(0, 97, n).tolist(), m)
              for n, m in ((3, 20), (8, 5), (1, 16))]
    kw = dict(pool_blocks=14)
    told_on, told_off = [], []
    on, s_on = _serve(PARAMS, 1, [first, second], told_on, **kw)
    off, s_off = _serve(PARAMS, 0, [first, second], told_off, **kw)
    for wave_on, wave_off, drafts_on, drafts_off in zip(
            on, off, told_on, told_off):
        for a, b, drafts, none in zip(wave_on, wave_off, drafts_on,
                                      drafts_off):
            assert a.tokens == b.tokens and a.finish_reason == "length"
            assert not none
            # a proposal a step that goes on, for the position after
            # the pending token's
            at = [p for p, _ in drafts]
            assert at[0] == a.prompt_len + 1 and at == sorted(set(at)) \
                and at[-1] < a.prompt_len + len(a.tokens)
    assert s_on["prefix_hits"] >= 3 and s_on["cow_forks"] >= 5
    assert s_on["prefix_hit_tokens"] == s_off["prefix_hit_tokens"]
    assert s_on["spec_steps"] == s_on["decode_steps"] > 0
    # seeded weights: nearly every proposal is rejected
    assert s_on["spec_accepted"] < s_on["spec_proposed"] // 4
    assert s_on["generated_tokens"] == s_off["generated_tokens"] \
        == sum(len(r.tokens) - 1 for wave in on for r in wave)
    assert s_off["spec_steps"] == 0 and s_off["draft_rows"] == 0
    # the module wrote a row a prompt token computed and a row a token
    # emitted by a step
    assert s_on["draft_rows"] >= s_on["generated_tokens"] - 7
    assert s_on["models"]["lm"]["self_draft"] is True
    assert s_on["models"]["lm"]["spec_k"] == 1
    assert "draft_pool_bytes" not in s_on["models"]["lm"]


def test_the_env_variable_does_not_gate_a_self_draft(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_SPEC", "0")
    rng = np.random.default_rng(1)
    (res,), stats = _serve(PARAMS, 1,
                           [[(rng.integers(0, 97, 9).tolist(), 6)]])
    assert stats["spec_steps"] > 0 and len(res[0].tokens) == 6


def test_sampling_rows_go_through_the_rejection_rule():
    """Temperature > 0 through the self-drafting tick (the module
    proposes its argmax, a one-hot density: ``spec_verify`` accepts it
    with probability ``p(d)`` and resamples without it): requests
    finish at their budgets with tokens of the vocabulary, the same
    seed gives the same stream, and under the agreeing weights a
    sampling row both accepts and rejects."""
    reg = ModelRegistry()
    reg.add_generative_model("lm", _agreeing_params(), SPEC_IN,
                             self_draft=1, **STORE_KW)
    eng = GenerationEngine(reg)
    try:
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, 97, 9).tolist()
        runs = [eng.submit("lm", prompt, max_tokens=24, temperature=0.9,
                           seed=seed).result(timeout=300)
                for seed in (7, 7, 8)]
        stats = eng.stats()
    finally:
        eng.close()
    assert runs[0].tokens == runs[1].tokens != runs[2].tokens
    for r in runs:
        assert len(r.tokens) == 24 and 0 <= min(r.tokens) \
            and max(r.tokens) < 97
    assert 0 < stats["spec_accepted"] < stats["spec_proposed"]
    assert stats["sample_draw_dispatches"] > 0


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


def test_accept_path_two_tokens_a_step():
    """Every proposal accepted: two tokens a step, ``max_tokens`` odd
    and even (the last step of an even budget verifies nothing: one
    token left), and a request that ENDS on the first of a pair (its
    second token is discarded with the slot).  Tokens equal the
    module-off store's throughout."""
    params = _agreeing_params()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 8, 13, 21)]
    budgets = [9, 10, 1, 2]
    wave = list(zip(prompts, budgets))
    (on,), s_on = _serve(params, 1, [wave])
    (off,), s_off = _serve(params, 0, [wave])
    for a, b, m in zip(on, off, budgets):
        assert a.tokens == b.tokens and len(a.tokens) == m
    assert s_on["spec_accepted"] == s_on["spec_proposed"] > 0
    # the first token comes from the prompt's chunk, then pairs: a
    # tick steps every generating row, the longest budget sets the count
    assert s_on["decode_steps"] == max(budgets) // 2
    assert s_off["decode_steps"] == max(budgets) - 1

    # end on the first of a pair: token 1, 3, 5, ... of a stream
    stream = off[1].tokens
    k = next((k for k in range(1, len(stream), 2)
              if stream[k] not in stream[:k]), None)
    assert k is not None, stream
    (cut,), s_cut = _serve(params, 1,
                           [[(prompts[1], budgets[1], stream[k])]])
    assert cut[0].tokens == stream[:k + 1]
    assert cut[0].finish_reason == "eos"
    # the steps emitted k tokens behind the chunk's one; the last
    # step's proposal had been ACCEPTED and its token went with the slot
    assert s_cut["generated_tokens"] == k
    assert s_cut["spec_accepted"] == s_cut["spec_proposed"] == (k + 1) // 2


def test_spec_verify_greedy_branch_equals_the_sampled_one():
    """``spec_verify`` skips the densities when every row is greedy:
    the same tokens and counts as the branch that computes them (one
    sampling row in the batch takes it)."""
    import jax
    import jax.numpy as jnp
    rs = np.random.RandomState(4)
    B, K, V = 6, 2, 11
    logits = jnp.asarray(rs.randn(B, K + 1, V), jnp.float32)
    best = np.argmax(np.asarray(logits), -1)
    props = best[:, :K].copy()
    props[1, 0] = (props[1, 0] + 1) % V          # reject at once
    props[2, 1] = (props[2, 1] + 1) % V          # accept one
    q = jax.nn.one_hot(props, V, dtype=jnp.float32)
    keys = jnp.asarray(rs.randint(0, 2 ** 31, (B, 2)), jnp.uint32)
    valid = np.array([3, 3, 3, 2, 1, 3], np.int32)
    zeros = np.zeros(B, np.float32)
    out, n, carry = spec_verify(logits, props, q, keys, zeros,
                                np.zeros(B, np.int32), valid)
    assert n.tolist() == [3, 1, 2, 2, 1, 3]
    mixed = zeros.copy()
    mixed[5] = 0.7                                # row 5 samples
    out2, n2, carry2 = spec_verify(logits, props, q, keys, mixed,
                                   np.zeros(B, np.int32), valid)
    assert np.array_equal(np.asarray(carry), np.asarray(carry2))
    for b in range(5):
        assert n[b] == n2[b]
        assert np.array_equal(np.asarray(out)[b, :n[b]],
                              np.asarray(out2)[b, :n2[b]])
        assert np.asarray(out)[b, n[b] - 1] == best[b, n[b] - 1]


# ---------------------------------------------------------------------------
# (e): the shares' parts add up BEFORE the post-feed-forward norm
# ---------------------------------------------------------------------------
def test_the_shares_parts_add_up_to_the_uncut_layer(ref):
    """A layer of all 8 experts against its two shares of 4: the
    routed parts of both shares and the shared expert, counted ONCE,
    add up to the uncut layer's output before the post-feed-forward
    norm (which is nonlinear: after it they would not)."""
    import jax.numpy as jnp
    full_in = dict(SPEC_IN, n_routed_experts=8)
    full = pm.random_params(pm.serving_spec(full_in), seed=9)
    p = _jnp(full)
    rs = np.random.RandomState(1)
    h = jnp.asarray(rs.randn(19, SPEC["hidden_size"]), jnp.float32)
    s = full_in
    whole, picked, _ = ref.expert_layer(h, p, "l1_", s)
    assert int(picked.max()) >= 4                 # both shares are hit
    shared, _, _ = ref.expert_layer(h, p, "l1_", s, held=())
    parts = [ref.expert_layer(h, p, "l1_", s, held=held)[0] - shared
             for held in (range(0, 4), range(4, 8))]
    total = shared + parts[0] + parts[1]
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    gamma = p["l1_post_ffn_norm_gamma"]
    normed = [ref._rms(x, gamma, 1e-5) for x in (whole, shared + parts[0],
                                                 shared + parts[1])]
    assert np.abs(np.asarray(
        normed[1] + normed[2] - normed[0])).max() > 1e-2


# ---------------------------------------------------------------------------
# (f): what stays as it was
# ---------------------------------------------------------------------------
def test_deepseek_v3_golden_step_through_the_refactored_layer(monkeypatch):
    """``deepseek_v3``'s step, whose layer is now ``decoder_layer``,
    gives the parent's logits bit for bit, and with ``hidden`` the same
    logits beside the hidden state."""
    import jax
    from test_deepseek_v3 import PARAMS as V3_PARAMS, SPEC as V3_SPEC
    monkeypatch.setenv("MXNET_PALLAS", "0")
    gold = np.load(os.path.join(ROOT, "tests",
                                "golden_deepseek_v3_step.npz"))
    params = _jnp(ds.pack_params(dict(V3_PARAMS), V3_SPEC))
    rs = np.random.RandomState(11)
    tables = np.zeros((2, 6), np.int32)
    tables[0, :4] = [1, 2, 3, 4]
    tables[1, :3] = [5, 6, 7]
    toks = rs.randint(0, V3_SPEC["vocab_size"], (2, 8)).astype(np.int32)
    for hidden in (False, True):
        out = jax.jit(lambda pl: ds.paged_step_leaves(
            params, pl, tables, toks, np.zeros(2, np.int32),
            np.array([8, 5], np.int32), V3_SPEC, 8, all_logits=True,
            hidden=hidden))(ds.init_pool(V3_SPEC, 9, 8))
        assert len(out) == 3 + hidden
        assert np.array_equal(np.asarray(out[0]), gold["chunk"])
    assert out[3].shape == (2, 8, V3_SPEC["hidden_size"])


def test_latent_kernel_first_key_masks_row_zero(monkeypatch):
    """``first=1`` of the latent attention, kernel (interpreted) and
    twin: row 0 of the pool is seen by no query, and ``first=0`` is
    what it always was."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import mla_attention as mla
    rs = np.random.RandomState(5)
    B, H, Lq, D, r, bs = 2, 4, 2, 128, 96, 8
    pool = jnp.asarray(rs.randn(2, 1, 6 * bs, D), jnp.float32)
    q = jnp.asarray(rs.randn(B, H, Lq, D), jnp.float32)
    tables = np.array([[1, 2, 0], [3, 0, 0]], np.int32)
    pos = np.array([9, 1], np.int32)
    for first in (0, 1):
        twin = mla.mla_attention_reference(q, pool, 1, tables, pos, bs,
                                           r, 0.2, first)
        kern = mla.mla_paged_attention(q, pool, 1, tables, pos, bs, r,
                                       0.2, interpret=True, first=first)
        assert np.abs(np.asarray(twin - kern)).max() < 1e-5
    # poison row 0 of each sequence: nothing moves under first=1
    bad = pool.at[1, 0, 1 * bs].set(100.0).at[1, 0, 3 * bs].set(100.0)
    a = mla.mla_attention_reference(q, pool, 1, tables, pos, bs, r, 0.2, 1)
    b = mla.mla_attention_reference(q, bad, 1, tables, pos, bs, r, 0.2, 1)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    c = mla.mla_attention_reference(q, bad, 1, tables, pos, bs, r, 0.2, 0)
    assert np.abs(np.asarray(a - c)).max() > 1e-3
