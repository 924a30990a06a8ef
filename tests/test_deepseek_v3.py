"""DeepSeek-V3's model functions at toy sizes on the CPU: absorbed
attention against the plain form, the router and the shares against the
reference, the two ops against their twins, and the paged step under
the kernels and under their twins (its store and engine are
tests/test_deepseek_v3_store.py's)."""
import numpy as np
import pytest

from mxnet_tpu.models import deepseek_v3 as ds

from _deepseek_v3_common import (BS, CHUNK, LOGIT_TOL, PARAMS, SPEC,
                                 SPEC_IN, _MOE_CASES, _sorted_picks, ref)


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


_DEFAULT_LOWERING = []     # test_paged_step_same_...: its run, once


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

    # the default lowering's run is the same for both modes: once a file
    if not _DEFAULT_LOWERING:
        _DEFAULT_LOWERING.append(run())
    want = _DEFAULT_LOWERING[0]
    monkeypatch.setenv("MXNET_PALLAS", mode)
    got = run()
    assert np.abs(got[0] - want[0]).max() < LOGIT_TOL
    assert np.abs(got[1] - want[1]).max() < LOGIT_TOL
    assert np.array_equal(got[2], want[2]) and got[2][0] == 2 * 2
