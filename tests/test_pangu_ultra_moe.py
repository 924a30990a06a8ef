"""openPangu-Ultra-MoE's model functions at toy sizes on the CPU: target
and multi-token-prediction module through the cache against the
reference's full forward, the seam and what it offers, the verify
rule's two branches, the shares' parts, and what stays as it was for
``deepseek_v3`` (its store and engine are
tests/test_pangu_ultra_moe_store.py's)."""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.models import pangu_ultra_moe as pm
from mxnet_tpu.serving.program_store import (GenerativeProgramStore,
                                             spec_verify)

from _pangu_ultra_moe_common import (BS, CFG, CHUNK, LOGIT_TOL, PARAMS,
                                     ROOT, SPEC, SPEC_IN, _jnp, ref)


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
    from _deepseek_v3_common import SPEC as V3
    assert V3["rope_scaling"] is not None
    assert [n for n in ds.param_shapes(V3) if "router_bias" in n]
    assert not [n for n in ds.param_shapes(V3) if "post_attn" in n]


@pytest.mark.parametrize("arch", ["lfm2_moe", "cohere2_moe", "deepseek_v3",
                                  "deepseek_v32"])
def test_other_models_refuse_self_draft(arch):
    """A state that cannot roll back (``lfm2_moe``), and the models
    without a prediction module: asking is refused in the store's
    wording, before a weight is touched."""
    mod = importlib.import_module("_%s_common" % arch)
    with pytest.raises(MXNetError, match="does not offer a self-drafting"):
        GenerativeProgramStore({}, mod.SPEC_IN, name=arch, self_draft=1,
                               **mod.STORE_KW)


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
    from _deepseek_v3_common import PARAMS as V3_PARAMS, SPEC as V3_SPEC
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
