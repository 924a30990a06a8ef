"""DeepSeek-V3.2's model functions at toy sizes on the CPU: the selected
sets against the reference's top-k, the adapted block against
``deepseek_v3``'s, and the four kernels against their twins (its store
and engine are tests/test_deepseek_v32_store.py's)."""
import os

import numpy as np
import pytest

from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.models import deepseek_v32 as ds32
from mxnet_tpu.pallas_ops import dispatch

from _deepseek_v3_common import PARAMS as V3_PARAMS, SPEC as V3_SPEC

from _deepseek_v32_common import (BS, CFG, KV_MAX, PARAMS, ROOT, SPEC,
                                  SPEC_IN, TOPK, _jnp, ref)


def test_selected_sets_equal_the_references_topk(monkeypatch, ref):
    """What the program's graph selects, layer by layer, is the
    reference's top-``index_topk`` set of every query at fp32 (a prompt
    of 30 in one chunk and then three decode steps)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import attention as att
    import jax
    monkeypatch.setenv("MXNET_PALLAS", "0")
    from mxnet_tpu.pallas_ops import dsa
    traced = []
    select = att.sparse_select

    def recording(scores, k):
        thr, tie = select(scores, k)
        traced.append(dsa.selected_mask(scores, thr, tie))
        return thr, tie

    monkeypatch.setattr(att, "sparse_select", recording)
    rs = np.random.RandomState(3)
    seq = rs.randint(0, SPEC["vocab_size"], 33)
    want = [np.asarray(m) for m in jax.jit(
        lambda p, t: ref.selections(p, t, CFG))(
            _jnp(PARAMS), jnp.asarray(seq.astype(np.int32)))]
    params = _jnp(ds32.pack_params(dict(PARAMS), SPEC))
    tables = np.arange(1, 7, dtype=np.int32).reshape(1, 6)

    @jax.jit
    def step(pools, toks, pos, val):
        del traced[:]
        _, pools, _ = ds32.paged_step(params, pools, tables, toks, pos,
                                      val, SPEC, BS)
        return pools, list(traced)      # each layer's selection

    pools, picked = step(
        ds32.init_pool(SPEC, 7, BS), seq[None, :30].astype(np.int32),
        np.zeros(1, np.int32), np.full(1, 30, np.int32))
    for p in range(30, 33):
        pools, more = step(pools, seq[None, p:p + 1].astype(np.int32),
                           np.full(1, p, np.int32), np.ones(1, np.int32))
        picked += more
    assert len(picked) == 4 * 3             # (chunk + 3 steps) x layers
    for n, got in enumerate(np.asarray(g) for g in picked):
        layer = n % 3
        first = 0 if n < 3 else 30 + n // 3 - 1
        for j in range(got.shape[1]):
            t = first + j
            # exactly index_topk positions; the ones the query sees
            # are the reference's set (the rest lie past its frontier)
            assert got[0, j].sum() == TOPK
            assert set(np.flatnonzero(got[0, j, :t + 1]).tolist()) == \
                set(np.flatnonzero(want[layer][t]).tolist()), (n, t)
            assert want[layer][t].sum() == min(TOPK, t + 1)


def test_topk_past_every_context_is_dense_attention():
    """With ``index_topk`` at ``kv_max`` the selection keeps every
    position and the logits are ``deepseek_v3``'s to rounding: the
    sparse path is the same attention over a permuted set."""
    import jax.numpy as jnp
    spec = ds32.serving_spec(dict(SPEC_IN, index_topk=KV_MAX))
    params = _jnp(ds32.pack_params(dict(PARAMS), spec))
    rs = np.random.RandomState(4)
    toks = rs.randint(0, spec["vocab_size"], (2, 8)).astype(np.int32)
    tables = np.array([[1, 2, 0, 0, 0, 0], [3, 4, 0, 0, 0, 0]], np.int32)
    pos, val = np.zeros(2, np.int32), np.array([8, 5], np.int32)
    import jax
    sparse = jax.jit(lambda pl: ds32.paged_step(
        params, pl, tables, toks, pos, val, spec, BS,
        all_logits=True)[0])(ds32.init_pool(spec, 5, BS))
    v3 = {k: v for k, v in spec.items() if not k.startswith("index_")}
    dense = jax.jit(lambda pl: ds.paged_step(
        params, pl, tables, toks, pos, val, v3, BS,
        all_logits=True)[0])(ds.init_pool(spec, 5, BS))
    assert np.abs(np.asarray(sparse) - np.asarray(dense))[0].max() < 1e-5
    assert jnp.isfinite(sparse).all()


# ---------------------------------------------------------------------------
# (ii) the adapted block traces what it traced for deepseek_v3
# ---------------------------------------------------------------------------
def test_deepseek_v3_step_is_bit_equal_to_the_parents(monkeypatch):
    """``deepseek_v3``'s step (a spec without the ``index_*`` keys)
    gives, bit for bit, the logits the parent commit's tree gave for
    the same seeded weights and tokens (``golden_deepseek_v3_step.npz``,
    made from that tree), a jitted chunk and then a decode step; and no
    route of the indexer is taken."""
    import jax
    monkeypatch.setenv("MXNET_PALLAS", "0")
    dispatch.reset_dispatch_stats()
    gold = np.load(os.path.join(ROOT, "tests",
                                "golden_deepseek_v3_step.npz"))
    assert not [k for k in V3_SPEC if k.startswith("index_")]
    params = _jnp(ds.pack_params(dict(V3_PARAMS), V3_SPEC))
    rs = np.random.RandomState(11)
    tables = np.zeros((2, 6), np.int32)
    tables[0, :4] = [1, 2, 3, 4]
    tables[1, :3] = [5, 6, 7]
    toks = rs.randint(0, V3_SPEC["vocab_size"], (2, 8)).astype(np.int32)
    # through the seam both models share, jitted as the store's are
    logits, pools, _ = jax.jit(lambda pl: ds32.paged_step(
        params, pl, tables, toks, np.zeros(2, np.int32),
        np.array([8, 5], np.int32), V3_SPEC, BS, all_logits=True))(
            ds.init_pool(V3_SPEC, 9, BS))
    assert np.array_equal(np.asarray(logits), gold["chunk"])
    toks = rs.randint(0, V3_SPEC["vocab_size"], (2, 1)).astype(np.int32)
    logits, pools, counts = jax.jit(lambda pl: ds.paged_step(
        params, pl, tables, toks, np.array([8, 5], np.int32),
        np.ones(2, np.int32), V3_SPEC, BS))(pools)
    assert np.array_equal(np.asarray(logits), gold["decode"])
    assert np.array_equal(np.asarray(counts), gold["counts"])
    assert not {"SparseSelect", "LightningIndexer",
                "LatentAttentionSparse"} & set(dispatch.dispatch_stats())


# ---------------------------------------------------------------------------
# (iv) kernels = twins
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("lq,positions", [(1, [5, 9, 17]), (8, [0, 3, 12]),
                                          (16, [2, 0, 7])])
def test_index_kernel_matches_dense_twin(lq, positions):
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import dsa
    rs = np.random.RandomState(lq)
    B, Hi, d, bs, T, L = 3, 8, 16, 8, 4, 2
    pool = jnp.asarray(rs.normal(size=(L, 1, 13 * bs, d)), jnp.float32)
    tables = np.zeros((B, T), np.int32)
    tables[0] = [3, 7, 1, 9]
    tables[2] = [2, 4, 12, 5]             # row 1 is outside the dispatch
    q = jnp.asarray(rs.normal(size=(B, lq, Hi, d)), jnp.float32)
    w = jnp.asarray(rs.normal(size=(B, lq, Hi)), jnp.float32)
    pos = np.asarray(positions, np.int32)
    for group in (1, 2, 4):
        got = np.asarray(dsa.dsa_index_scores(
            q, w, pool, 1, tables, pos, bs, block_q=4 * Hi, group=group,
            interpret=True))
        want = np.asarray(dsa.dsa_index_scores_reference(
            q, w, pool, 1, tables, pos, bs))
        assert got.shape == (B, lq, T * bs)
        assert np.array_equal(np.isfinite(got), np.isfinite(want))
        assert not np.isfinite(got[1]).any()
        for j in range(lq):               # a query sees pos + j + 1 keys
            assert np.isfinite(got[0, j]).sum() == pos[0] + j + 1
        ok = np.isfinite(want)
        assert np.abs(got[ok] - want[ok]).max() < 1e-4


@pytest.mark.parametrize("block_k", [4, 8, 16])
def test_sparse_attention_kernel_matches_dense_twin(block_k):
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import dsa
    rs = np.random.RandomState(block_k)
    N, H, D, K, rank = 5, 4, 24, 16, 16
    q = jnp.asarray(rs.normal(size=(N, H, D)), jnp.float32)
    rows = jnp.asarray(rs.normal(size=(N, K, D)), jnp.float32)
    counts = np.array([16, 0, 5, 1, 9], np.int32)
    got = np.asarray(dsa.dsa_mla_attention(
        q, rows, counts, rank, 0.3, block_k=block_k, interpret=True))
    want = np.asarray(dsa.dsa_mla_attention_reference(
        q, rows, counts, rank, 0.3))
    assert got.shape == (N, H, rank)
    assert np.abs(got - want).max() < 1e-5
    assert not got[1].any()               # a dead query: zeros
    # the live rows alone decide: the others may hold anything
    other = rows.at[2, 5:].set(1e6)
    again = np.asarray(dsa.dsa_mla_attention(
        q, other, counts, rank, 0.3, block_k=block_k, interpret=True))
    assert np.array_equal(again[2], got[2])


@pytest.mark.parametrize("k", [1, 6, 16, 40])
def test_threshold_selection_is_exactly_top_k(k):
    """``dsa_select_threshold`` (interpreted) and its twin against
    ``jax.lax.top_k``, with ties at the k-th place (scores drawn from
    five values), rows of ``-inf`` tails and a row that is all
    ``-inf``; ``compact_positions`` lists the mask's ones ascending."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import dsa
    rs = np.random.RandomState(k)
    R, S = 12, 40
    scores = rs.normal(size=(R, S)).astype(np.float32)
    scores[4:8] = rs.randint(0, 5, (4, S)) - 2.0      # ties, and -0.0
    scores[5, 3] = -0.0
    scores[8, 9:] = -np.inf
    scores[9, 1:] = -np.inf
    scores[10] = -np.inf
    want = np.zeros((R, S), bool)
    top = np.asarray(jax.lax.top_k(jnp.asarray(scores), k)[1])
    np.put_along_axis(want, top, True, axis=1)
    for thr, tie in (dsa.dsa_select_threshold(jnp.asarray(scores), k,
                                              block_rows=4,
                                              interpret=True),
                     dsa.dsa_select_threshold_reference(
                         jnp.asarray(scores), k)):
        got = np.asarray(dsa.selected_mask(jnp.asarray(scores), thr, tie))
        assert got.sum(1).tolist() == [k] * R
        # top_k breaks ties to the lower position too: the same SET
        assert np.array_equal(got, want)
        pos = np.asarray(dsa.compact_positions(jnp.asarray(got), k,
                                               group=8))
        assert [row.tolist() for row in pos] == \
            [np.flatnonzero(row).tolist() for row in got]


@pytest.mark.parametrize("lq,positions", [(8, [0, 3, 12]), (16, [2, 0, 7])])
def test_masked_attention_kernel_matches_dense_twin(lq, positions):
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import dsa
    rs = np.random.RandomState(lq)
    B, H, D, rank, bs, T, L = 3, 4, 24, 16, 8, 4, 2
    pool = jnp.asarray(rs.normal(size=(L, 1, 13 * bs, D)), jnp.float32)
    tables = np.zeros((B, T), np.int32)
    tables[0] = [3, 7, 1, 9]
    tables[2] = [2, 4, 12, 5]             # row 1 is outside the dispatch
    pos = np.asarray(positions, np.int32)
    q = jnp.asarray(rs.normal(size=(B, H, lq, D)), jnp.float32)
    scores = rs.normal(size=(B, lq, T * bs)).astype(np.float32)
    seen = np.arange(T * bs)[None, None] <= (
        pos[:, None] + np.arange(lq)[None])[..., None]
    scores = jnp.asarray(np.where(seen, scores, -np.inf))
    thr, tie = dsa.dsa_select_threshold_reference(
        scores.reshape(B * lq, -1), 5)
    thr, tie = thr.reshape(B, lq, 1), tie.reshape(B, lq, 1)
    want = np.asarray(dsa.dsa_mla_attention_masked_reference(
        q, pool, 1, tables, pos, scores, thr, tie, bs, rank, 0.3))
    for block_q, group in ((lq, 1), (2 * lq, 2), (512, 4)):
        got = np.asarray(dsa.dsa_mla_attention_masked(
            q, pool, 1, tables, pos, scores, thr, tie, bs, rank, 0.3,
            block_q=block_q, group=group, interpret=True))
        assert np.abs(got[[0, 2]] - want[[0, 2]]).max() < 1e-5
        assert not got[1].any()
    # and it is the gathered form's mathematics: query 0 of sequence 0
    mask = np.asarray(dsa.selected_mask(scores, thr, tie))[0, 0]
    keep = np.flatnonzero(mask[:pos[0] + 1])
    rows = np.asarray(pool)[1, 0][
        tables[0][keep // bs] * bs + keep % bs][None]
    one = np.asarray(dsa.dsa_mla_attention_reference(
        q[0, :, 0][None], jnp.asarray(rows), np.array([len(keep)]), rank,
        0.3))
    assert np.abs(one[0] - want[0, :, 0]).max() < 1e-5
