"""DeepSeek-V3 on the serving plane, at toy sizes on the CPU: the latent
(MLA) paged pool against the plain reference's full forward, absorbed
against plain attention, the router against the reference's, the shares
of an expert layer adding up to the whole, both new ops against their
dense twins, and the model seam of the program store
(docs/architecture/decode_engine.md, "The model seam").
"""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
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


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="ds",
                                  **args)


# ---------------------------------------------------------------------------
# (i) chunks, then decode, through the latent pool = the full forward
# ---------------------------------------------------------------------------
def test_chunked_prefill_and_decode_logits_match_reference(ref):
    """Two sequences in one batch: A prefilled in chunks of 8 (8, 8, 5)
    and decoded 5 steps; B sharing A's first two blocks through its
    table and forking A's third (copy-on-write: ``copy_block``) before
    it writes its own continuation there.  Every logit row the paged
    programs give equals the reference's full forward of that sequence
    (teacher-forced), and A's rows are untouched by B's fork."""
    assert ref.param_shapes(CFG) == ds.param_shapes(SPEC)
    st = _store()
    assert st.pool_leaves == 1
    rs = np.random.RandomState(0)
    V = SPEC["vocab_size"]
    a_seq = rs.randint(0, V, 26)
    b_seq = np.concatenate([a_seq[:19], rs.randint(0, V, 7)])
    want = {"a": _ref_logits(ref, a_seq), "b": _ref_logits(ref, b_seq)}
    pools = st.new_pool()
    assert pools[0].shape == (3, 1, st.pool_blocks * BS,
                              ds.latent_width(SPEC))
    T = st.table_width()
    tables = np.zeros((2, T), np.int32)
    tables[0, :4] = [1, 2, 3, 4]

    def step(tokens, pos, val):
        nonlocal pools
        toks = np.zeros((2, tokens.shape[1]), np.int32)
        toks[:] = tokens
        logits, *pools = st.run_paged_step(
            *pools, tables, toks, np.asarray(pos, np.int32),
            np.asarray(val, np.int32))
        return np.asarray(logits)

    # A's prompt of 21 in chunks; row 1 is outside the dispatch
    got_a = {}
    for start in (0, 8, 16):
        n = min(CHUNK, 21 - start)
        toks = np.zeros((2, CHUNK), np.int32)
        toks[0, :n] = a_seq[start:start + n]
        got_a[start + n - 1] = step(toks, [start, 0], [n, 1])[0]
    # B adopts blocks 1, 2 and forks block 3 (tokens 16..18 are shared)
    pools = st.copy_block(*pools, 3, 5)
    tables[1, :4] = [1, 2, 5, 6]
    toks = np.zeros((2, CHUNK), np.int32)
    toks[1, :7] = b_seq[19:26]
    toks[0, 0] = a_seq[21]
    # a chunk dispatch with both rows live: A one token, B seven
    both = step(toks, [21, 19], [1, 7])
    got_a[21] = both[0]
    assert np.abs(both[1] - want["b"][25]).max() < LOGIT_TOL
    for p in range(22, 26):                 # decode steps, B idle
        tables_b = tables[1].copy()
        tables[1] = 0
        got_a[p] = step(a_seq[p].reshape(1, 1), [p, 0], [1, 1])[0]
        tables[1] = tables_b
    for p, row in got_a.items():
        assert np.abs(row - want["a"][p]).max() < LOGIT_TOL, p


def test_engine_serves_shared_prefix_with_fork_and_counts(ref):
    """``add_generative_model`` -> ``submit`` -> the paged tick, as the
    LM goes: greedy streams equal the reference's own greedy
    continuation, a repeated prompt adopts its blocks, decode writes
    into adopted blocks fork them, and the expert counters arrive with
    the sampled tokens."""
    rs = np.random.RandomState(2)
    P = [int(t) for t in rs.randint(0, SPEC["vocab_size"], 12)]
    reg = ModelRegistry()
    reg.add_generative_model("ds", dict(PARAMS), SPEC_IN, **STORE_KW)
    eng = GenerationEngine(reg)
    try:
        a = eng.submit("ds", P, max_tokens=6).result(300)
        b = eng.submit("ds", P, max_tokens=6).result(300)
        stats = eng.stats()
    finally:
        eng.close()
    seq = list(P)
    for _ in range(6):
        seq.append(int(np.argmax(_ref_logits(ref, seq)[-1])))
    assert a.tokens == seq[12:] and b.tokens == a.tokens
    assert stats["prefix_hits"] == 1 and stats["cow_forks"] >= 1
    # 2 expert layers a step; every live token is routed in each
    assert stats["moe_expert_steps"] == 2 * (
        stats["decode_steps"] + stats["prefill_chunks"])
    assert stats["moe_tokens"] == 2 * (12 + 1 + 2 * 5)
    assert 0 < stats["moe_local_assignments"] <= 4 * stats["moe_tokens"]
    assert stats["moe_expert_load_max"] >= \
        stats["moe_local_assignments"] / 4
    assert 0 < stats["moe_experts_touched"] <= \
        4 * stats["moe_expert_steps"]
    # an expert's weights are streamed once a row tile its rows reach:
    # at these sizes (8 x 4 = 32 sorted rows, one tile) exactly once
    assert stats["moe_expert_streams"] == stats["moe_experts_touched"]
    cs = stats["cache_state"]["ds"]
    assert cs["pool_bytes"] == 3 * reg.gen_store("ds").pool_blocks \
        * BS * ds.latent_width(SPEC) * 4


# ---------------------------------------------------------------------------
# (ii) absorbed = plain
# ---------------------------------------------------------------------------
def test_absorbed_attention_equals_plain_form():
    """``softmax((q_nope W_k^T) . c_kv + q_rope . k_r) . c_kv W_v`` over
    the paged latent rows = plain attention over the up-projected keys
    and values."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops.mla_attention import mla_attention_reference
    rs = np.random.RandomState(1)
    H, r, dr, dn, dv, n = 4, 16, 4, 8, 8, 19
    c_kv = rs.randn(n, r).astype(np.float32)
    k_r = rs.randn(n, dr).astype(np.float32)
    q_nope = rs.randn(H, dn).astype(np.float32)
    q_rope = rs.randn(H, dr).astype(np.float32)
    w = rs.randn(H, dn + dv, r).astype(np.float32)
    scale = 0.3
    k_nope = np.einsum("nc,hdc->nhd", c_kv, w[:, :dn])
    v = np.einsum("nc,hdc->nhd", c_kv, w[:, dn:])
    s = (np.einsum("hd,nhd->hn", q_nope, k_nope)
         + np.einsum("hd,nd->hn", q_rope, k_r)) * scale
    p = np.exp(s - s.max(-1, keepdims=True))
    plain = np.einsum("hn,nhd->hd", p / p.sum(-1, keepdims=True), v)

    pool = np.zeros((2, 1, 4 * 8, r + dr), np.float32)
    rows = np.concatenate([np.arange(16, 24), np.arange(8, 16),
                           np.arange(24, 27)])        # blocks 2, 1, 3
    pool[1, 0, rows] = np.concatenate([c_kv, k_r], axis=1)
    q = np.concatenate([np.einsum("hd,hdc->hc", q_nope, w[:, :dn]),
                        q_rope], axis=1)[None, :, None, :]
    o_lat = np.asarray(mla_attention_reference(
        jnp.asarray(q), jnp.asarray(pool), 1,
        jnp.asarray([[2, 1, 3, 0]], jnp.int32),
        jnp.asarray([n - 1], jnp.int32), 8, r, scale))[0, :, 0]
    absorbed = np.einsum("hc,hdc->hd", o_lat, w[:, dn:])
    assert np.abs(absorbed - plain).max() < 1e-5


# ---------------------------------------------------------------------------
# (iii) the router
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["seeded", "ties", "group-limit"])
def test_routing_equals_reference(ref, case):
    """Picks and weights of ``route_grouped`` equal the reference's
    router: on seeded scores, on scores full of ties (both break them
    to the lower index), and where the group limit keeps an expert
    with a lower score over one with a higher."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.moe import route_grouped
    rs = np.random.RandomState(4)
    scores = rs.uniform(0.05, 0.95, (64, 16)).astype(np.float32)
    bias = (0.05 * rs.randn(16)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores * 4) / 4
        bias = np.round(bias * 8) / 8
    if case == "group-limit":
        # groups 0 and 1 hold two strong experts each, group 2 the
        # single strongest: only two groups stay, and a group's score
        # is the sum of its best TWO
        scores = np.full((1, 16), 0.1, np.float32)
        scores[0, [0, 1, 4, 5]] = [0.8, 0.7, 0.75, 0.7]
        scores[0, 8] = 0.9
        bias = np.zeros(16, np.float32)
    got_e, got_w = route_grouped(jnp.asarray(scores), jnp.asarray(bias),
                                 4, 4, 2, 2.5)
    want_e, want_w = ref.route(jnp.asarray(scores), jnp.asarray(bias),
                               SPEC_IN)
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
    assert np.abs(np.asarray(got_w) - np.asarray(want_w)).max() < 1e-6
    assert np.allclose(np.asarray(got_w).sum(-1), 2.5, atol=1e-5)
    if case == "group-limit":
        assert sorted(np.asarray(got_e)[0].tolist()) == [0, 1, 4, 5]


# ---------------------------------------------------------------------------
# (iv) the shares add up
# ---------------------------------------------------------------------------
def test_shares_add_up_to_the_uncut_layer(ref):
    """The partial results of all four shares of an expert layer (each
    chip its four experts of sixteen), the shared expert counted once,
    sum to the uncut reference's layer."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.moe import moe_experts, route_grouped
    uncut = dict(SPEC_IN, n_routed_experts=16)
    p = {k: jnp.asarray(v)
         for k, v in ds.random_params(ds.serving_spec(uncut), 7).items()
         if k.startswith("l1_")}
    h = jnp.asarray(np.random.RandomState(8).randn(24, 64)
                    .astype(np.float32))
    want, picked, _ = ref.expert_layer(h, p, "l1_", uncut)
    assert len(np.unique(np.asarray(picked) // 4)) == 4   # every share
    scores = jax.nn.sigmoid(h @ p["l1_router_weight"].T)
    experts, weights = route_grouped(scores, p["l1_router_bias"], 4, 4,
                                     2, 2.5)
    total = (jax.nn.silu(h @ p["l1_shared_gate_weight"].T)
             * (h @ p["l1_shared_up_weight"].T)) \
        @ p["l1_shared_down_weight"].T
    live = jnp.ones((24,), bool)
    for share in range(4):
        held = range(4 * share, 4 * share + 4)
        gu = jnp.stack([jnp.concatenate(
            [p["l1_e%d_gate_weight" % e].T, p["l1_e%d_up_weight" % e].T],
            axis=1) for e in held])
        down = jnp.stack([p["l1_e%d_down_weight" % e].T for e in held])
        # this share's experts are 0..3 on its own chip
        local = jnp.where((experts >= held[0]) & (experts <= held[-1]),
                          experts - held[0], 16)
        part, counts = moe_experts(h, gu, down, local, weights, live)
        assert int(counts.sum()) == int(
            np.isin(np.asarray(experts), list(held)).sum())
        total = total + part
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5


# ---------------------------------------------------------------------------
# (v), (vi) the two ops against their twins
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lq,positions", [(1, [5, 9, 17]), (4, [0, 3, 12]),
                                          (8, [8, 1, 15])],
                         ids=["decode", "chunk4", "chunk8"])
def test_mla_kernel_matches_dense_twin(lq, positions):
    """``mla_paged_attention`` (interpret mode) against the gather
    twin: ragged frontiers, shared physical blocks, trash entries, junk
    past every frontier, a middle layer of a stack, groups of 1 and 2
    table entries a grid step."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops.mla_attention import (
        mla_attention_reference, mla_paged_attention)
    rs = np.random.RandomState(lq)
    B, H, T, D, bs, rank = 3, 4, 4, 24, 8, 16
    q = jnp.asarray(rs.randn(B, H, lq, D).astype(np.float32))
    pool = jnp.asarray(rs.randn(3, 1, 12 * bs, D).astype(np.float32))
    tables = np.zeros((B, T), np.int32)
    nxt = 2
    for b in range(B):
        for j in range(-(-(positions[b] + lq) // bs)):
            tables[b, j] = 1 if j == 0 else nxt     # block 1 is shared
            nxt += j > 0
    # a fourth sequence is outside the dispatch (its table owns no
    # block): the kernel skips it and hands back zeros
    tables = np.concatenate([tables, np.zeros((1, T), np.int32)])
    q = jnp.concatenate([q, q[:1]])
    tbl = jnp.asarray(tables)
    pos = jnp.asarray(list(positions) + [0], jnp.int32)
    want = np.asarray(mla_attention_reference(q, pool, 1, tbl, pos, bs,
                                              rank, 0.25))
    for group in (1, 2):
        got = np.asarray(mla_paged_attention(
            q, pool, 1, tbl, pos, bs, rank, 0.25, block_q=8,
            group=group, interpret=True))
        assert got.shape == (B + 1, H, lq, rank)
        assert np.abs(got[:B] - want[:B]).max() < 2e-6
        assert not got[B].any()


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


@pytest.mark.parametrize(
    "routing,N,K,groups,dtype,streams",
    _MOE_CASES, ids=[c[0] for c in _MOE_CASES])
def test_moe_experts_matches_dense_twin(monkeypatch, routing, N, K, groups,
                                        dtype, streams):
    """The sorted, grouped product (``MXNET_PALLAS=2``: the repo's own
    kernel under the interpreter) against the masked loop
    (``MXNET_PALLAS=0``, which the door then IS): uneven counts, dead
    rows, a routing that sends every token to one expert (none dropped:
    that expert's count is the token count), one that picks no held
    expert at all, and the kernel's own edges: a group over three row
    tiles, a group across a tile edge, no live row, row counts the tile
    bound does not divide, empty experts at either end, bfloat16
    operands.  ``moe_expert_streams`` against a count by hand."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe
    from mxnet_tpu.pallas_ops.grouped_matmul import row_tile
    rs = np.random.RandomState(6)
    D, F, held = 64, 32, 4
    cdt = jnp.dtype(dtype)
    x = jnp.asarray(rs.randn(N, D).astype(np.float32)).astype(cdt)
    gu = jnp.asarray(rs.randn(held, D, 2 * F).astype(np.float32) / 8) \
        .astype(cdt)
    down = jnp.asarray(rs.randn(held, F, D).astype(np.float32) / 6) \
        .astype(cdt)
    experts = np.stack([rs.permutation(16)[:K] for _ in range(N)])
    live = np.ones(N, bool)
    if groups is not None:
        experts = _sorted_picks(N, K, held, groups)
    elif routing == "all-to-one":
        experts[:] = [2, 9, 12, 15]
    else:
        live[[3, 17, 39]] = False
        if routing == "none-held":
            experts = experts % 12 + 4
    weights = jnp.asarray(rs.uniform(0.1, 1, (N, K)).astype(np.float32))
    args = (x, gu, down, jnp.asarray(experts, jnp.int32), weights,
            jnp.asarray(live))
    want, want_counts = moe.moe_experts_reference(*args)
    monkeypatch.setenv("MXNET_PALLAS", "0")
    off, _ = moe.moe_experts(*args)
    assert np.array_equal(np.asarray(off), np.asarray(want))
    monkeypatch.setenv("MXNET_PALLAS", "2")
    got, counts = moe.moe_experts(*args)
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    if groups is not None:
        assert np.asarray(counts).tolist() == groups
    if dtype == "bfloat16":
        # against the twin at fp32 over the SAME rounded operands.  Both
        # accumulate in fp32; what differs is the one rounding of the
        # activation between the two products to bfloat16 (2**-9
        # relative, which the twin at fp32 does not make), carried
        # through the second contraction of F = 32 terms and the sum of
        # K = 4 picks: 2**-9 x sqrt(F x K) of the result's scale (7
        # here: 0.15; the reading is 2.1e-2).  An expert's rows
        # multiplied by another expert's weights read the scale itself
        want, _ = moe.moe_experts_reference(
            *[a.astype(jnp.float32) for a in args[:3]], *args[3:])
        tol = 2.0 ** -9 * np.sqrt(F * K) * np.abs(np.asarray(want)).max()
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < tol
    else:
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    if routing == "all-to-one":
        assert np.asarray(counts).tolist() == [0, 0, N, 0]
        assert np.abs(np.asarray(got)).min(axis=1).max() > 0
    if routing in ("none-held", "no-live-row"):
        assert not np.asarray(got).any()
    assert not np.asarray(got)[~live].any()
    visits = int(moe.expert_streams(counts, N * K))
    assert visits >= int(np.sum(np.asarray(counts) > 0))
    if streams is not None:
        assert (row_tile(N * K), visits) == streams


@pytest.mark.parametrize("mode", ["0", "2"])
def test_paged_step_same_under_kernels_and_twins(monkeypatch, mode):
    """One chunk and one decode step of the whole model under
    ``MXNET_PALLAS=0`` (twins) and ``=2`` (the kernels, interpreted)
    agree with the default lowering."""
    import jax.numpy as jnp

    def run():
        packed = ds.pack_params(
            {k: jnp.asarray(v) for k, v in PARAMS.items()}, SPEC)
        pool, = ds.init_pool(SPEC, 6, BS)
        tables = np.asarray([[1, 2, 0], [3, 4, 5]], np.int32)
        toks = np.random.RandomState(9).randint(0, 96, (2, CHUNK))
        a, pool, _ = ds.paged_step_apply(
            packed, pool, tables, toks, np.asarray([0, 0]),
            np.asarray([5, 8]), SPEC, BS)
        b, pool, counts = ds.paged_step_apply(
            packed, pool, tables, toks[:, :1], np.asarray([5, 8]),
            np.asarray([1, 1]), SPEC, BS)
        return np.asarray(a), np.asarray(b), np.asarray(counts)

    want = run()
    monkeypatch.setenv("MXNET_PALLAS", mode)
    got = run()
    assert np.abs(got[0] - want[0]).max() < LOGIT_TOL
    assert np.abs(got[1] - want[1]).max() < LOGIT_TOL
    assert np.array_equal(got[2], want[2]) and got[2][0] == 2 * 2


# ---------------------------------------------------------------------------
# (vii) the model seam
# ---------------------------------------------------------------------------
def test_seam_refuses_what_the_model_does_not_offer():
    """The contiguous plane, the int8 pool and the draft plane are the
    LM's; asking them of ``deepseek_v3`` is a clear error, and an
    unknown ``arch`` names the known ones."""
    with pytest.raises(MXNetError, match="contiguous"):
        _store(paged=False)
    with pytest.raises(MXNetError, match="int8"):
        _store(kv_dtype="int8")
    with pytest.raises(MXNetError, match="deepseek_v3"):
        GenerativeProgramStore({}, {"arch": "nope"})
    reg = ModelRegistry()
    reg.add_generative_model("ds", dict(PARAMS), SPEC_IN, warmup=False,
                             **STORE_KW)
    with pytest.raises(MXNetError, match="speculative"):
        reg.add_draft_model("ds", dict(PARAMS), SPEC_IN, spec_k=2)
    missing = dict(PARAMS)
    del missing["l1_router_bias"]
    with pytest.raises(MXNetError, match="l1_router_bias"):
        GenerativeProgramStore(missing, SPEC_IN, name="ds", **STORE_KW)


def test_lm_goes_through_the_same_seam():
    """The LM is the seam's default model: no ``arch`` in its spec, a
    pool of two leaves, and the store's spec as it always read."""
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    spec = lm_spec(num_layers=1, num_hidden=16, num_heads=2,
                   vocab_size=20)
    st = GenerativeProgramStore(random_params(spec, 1), spec,
                                batch_buckets=(1,), prompt_buckets=(8,),
                                kv_block=8, kv_max=16, paged=True,
                                prefill_chunk=8)
    assert st.spec == spec and st.pool_leaves == 2
    assert st.aux_counters == ()
    k, v = st.new_pool()
    k2, v2 = st.copy_block(k, v, 1, 2)
    assert k2.shape == k.shape == (1, 2, st.pool_blocks * 8, 8)


def test_int8_weights_run_and_differ():
    """``compute_dtype='int8'`` (the cell's control) quantizes every
    matmul weight, the experts' stacks among them, and moves the
    logits by more than rounding does."""
    from mxnet_tpu.pallas_ops.dequant_matmul import QuantizedWeight
    full, q8 = _store(), _store(compute_dtype="int8")
    for name in ds.matmul_weights(SPEC):
        assert isinstance(q8._params[name], QuantizedWeight), name
    assert q8._params["l1_experts_gate_up"].codes.shape == (4, 64, 64)
    tables = np.asarray([[1, 2, 0, 0, 0, 0], [0] * 6], np.int32)
    toks = np.random.RandomState(3).randint(0, 96, (2, CHUNK))
    outs = []
    for st in (full, q8):
        logits, _ = st.run_paged_step(
            *st.new_pool(), tables, toks.astype(np.int32),
            np.zeros(2, np.int32), np.asarray([8, 1], np.int32))
        outs.append(np.asarray(logits)[0])
    gap = np.abs(outs[0] - outs[1]).max()
    assert 1e-3 < gap < 0.5
