"""DeepSeek-V3.2 on the serving plane, at toy sizes on the CPU: the
two-leaf pool (latent rows, index keys) against the plain reference's
full forward with an ``index_topk`` SMALLER than the contexts, the
selected sets against the reference's top-k, both new kernels against
their dense twins, prefix adoption and copy-on-write carrying both
leaves, and ``deepseek_v3``'s own step against the parent's
(docs/architecture/decode_engine.md, "Two token leaves on one table").
"""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.models import deepseek_v32 as ds32
from mxnet_tpu.pallas_ops import dispatch
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
from mxnet_tpu.serving.program_store import GenerativeProgramStore

from test_deepseek_v3 import PARAMS as V3_PARAMS, SPEC as V3_SPEC, SPEC_IN \
    as V3_SPEC_IN

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOPK = 6
SPEC_IN = dict(V3_SPEC_IN, arch="deepseek_v32", index_n_heads=4,
               index_head_dim=8, index_topk=TOPK)
SPEC = ds32.serving_spec(SPEC_IN)
CFG = {"spec": SPEC_IN}
PARAMS = ds32.random_params(SPEC, seed=5)
BS, CHUNK, KV_MAX = 8, 8, 48
# as tests/test_deepseek_v3.py: the same products associated
# differently; a selection that differed would move a logit by 1e-2
LOGIT_TOL = 1e-4
STORE_KW = dict(batch_buckets=(2,), prompt_buckets=(8,), kv_block=BS,
                kv_max=KV_MAX, paged=True, prefill_chunk=CHUNK,
                sample="graph")


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (imports nothing of the
    program), loaded by path."""
    spec = importlib.util.spec_from_file_location(
        "deepseek_v32_reference",
        os.path.join(ROOT, "benchmark", "reference", "deepseek-v32.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _jnp(params):
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in params.items()}


def _ref_logits(ref, tokens):
    import jax.numpy as jnp
    return np.asarray(ref.logits(
        _jnp(PARAMS), jnp.asarray(np.asarray(tokens, np.int32)), CFG))


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="ds32",
                                  **args)


# ---------------------------------------------------------------------------
# (i) chunks, then decode, through BOTH leaves = the full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["0", "2"])
def test_chunked_prefill_and_decode_logits_match_reference(monkeypatch,
                                                           ref, mode):
    """``tests/test_deepseek_v3.py``'s two sequences, every context past
    ``index_topk`` = 6 from the first chunk on, so the selection cuts in
    every step: A prefilled in chunks and decoded; B adopting A's first
    two blocks through its table and forking A's third (``copy_block``
    must copy the index keys with the latent rows, or B's indexer
    scores zeros there).  Kernels interpreted (2) and the XLA twins
    (0)."""
    monkeypatch.setenv("MXNET_PALLAS", mode)
    dispatch.reset_dispatch_stats()
    assert ref.param_shapes(CFG) == ds32.param_shapes(SPEC)
    st = _store()
    assert st.pool_leaves == 2 and len(st.cache_classes) == 1
    rs = np.random.RandomState(0)
    V = SPEC["vocab_size"]
    a_seq = rs.randint(0, V, 26)
    b_seq = np.concatenate([a_seq[:19], rs.randint(0, V, 7)])
    want = {"a": _ref_logits(ref, a_seq), "b": _ref_logits(ref, b_seq)}
    pools = st.new_pool()
    assert pools[0].shape == (3, 1, st.pool_blocks * BS,
                              ds.latent_width(SPEC))
    assert pools[1].shape == (3, 1, st.pool_blocks * BS, 8)
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

    got_a = {}
    for start in (0, 8, 16):
        n = min(CHUNK, 21 - start)
        toks = np.zeros((2, CHUNK), np.int32)
        toks[0, :n] = a_seq[start:start + n]
        got_a[start + n - 1] = step(toks, [start, 0], [n, 1])[0]
    pools = st.copy_block(*pools, 3, 5)
    assert len(pools) == 2
    tables[1, :4] = [1, 2, 5, 6]
    toks = np.zeros((2, CHUNK), np.int32)
    toks[1, :7] = b_seq[19:26]
    toks[0, 0] = a_seq[21]
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
    routes = dispatch.dispatch_stats()
    # the three routes are mode 2's alone: 3 layers x (the chunk's
    # trace, whose attention walks under the mask, and the decode
    # step's, whose attention reads gathered rows)
    n = 6 if mode == "2" else 0
    assert [routes.get(r, 0) for r in (
        "LightningIndexer", "SparseSelect", "LatentAttentionSparse")] \
        == [n, n, n]
    assert routes.get("LatentAttentionSparse.masked", 0) == n // 2 \
        == routes.get("LatentAttentionSparse.gathered", 0)
    assert "LatentAttentionPaged" not in routes


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


def test_spec_and_seam():
    with pytest.raises(MXNetError, match="index_n_heads"):
        ds32.serving_spec(V3_SPEC_IN)
    with pytest.raises(MXNetError, match="rotary"):
        ds32.serving_spec(dict(SPEC_IN, index_head_dim=2))
    extra = set(ds32.param_shapes(SPEC)) - set(ds.param_shapes(SPEC))
    assert sorted(n[3:] for n in extra if n.startswith("l0_")) == [
        "idx_k_norm_beta", "idx_k_norm_gamma", "idx_k_weight",
        "idx_q_b_weight", "idx_w_weight"]
    assert set(ds32.required_params(SPEC)) - set(ds.required_params(SPEC)) \
        == extra
    assert "l1_idx_k_norm_beta" not in ds32.matmul_weights(SPEC)
    assert "l1_idx_w_weight" in ds32.matmul_weights(SPEC)
    with pytest.raises(MXNetError, match="int8 latent pool"):
        ds32.paged_step({}, (), None, None, None, None, SPEC, BS,
                        scales=(1, 2))


# ---------------------------------------------------------------------------
# (iii) the engine: adoption, fork, counts
# ---------------------------------------------------------------------------
def test_engine_prefix_hit_and_fork_carry_both_leaves(ref):
    """A request admitted on a prefix hit (its blocks, index keys
    among them, adopted from the store) and its copy-on-write fork of
    the adopted tail serve the tokens of the request that prefilled
    alone, which are the reference's own greedy continuation; the
    spans and ``stats()`` count what the indexer scored and what
    attention read."""
    rs = np.random.RandomState(2)
    P = [int(t) for t in rs.randint(0, SPEC["vocab_size"], 20)]
    reg = ModelRegistry()
    reg.add_generative_model("ds32", dict(PARAMS), SPEC_IN, **STORE_KW)
    eng = GenerationEngine(reg)
    was = profiler.phase_totals()
    try:
        a = eng.submit("ds32", P, max_tokens=6).result(300)
        b = eng.submit("ds32", P, max_tokens=6).result(300)
        stats = eng.stats()
    finally:
        eng.close()
    # teacher-forced: the reference's best at every served position
    best = np.argmax(_ref_logits(ref, P + a.tokens[:-1]), axis=-1)
    assert a.tokens == best[19:].tolist() and b.tokens == a.tokens
    assert stats["prefix_hits"] == 1 and stats["cow_forks"] >= 1
    assert stats["prefix_hit_tokens"] == 20
    # A: 20 prompt queries, B: its last prompt token alone (the rest
    # adopted), 5 decode steps each
    seen = list(range(1, 21)) + [20] + 2 * list(range(21, 26))
    assert stats["dsa_queries"] == len(seen)
    assert stats["dsa_index_pairs"] == sum(seen)
    assert stats["dsa_keys_selected"] == sum(min(s, TOPK) for s in seen)
    spans = profiler.phase_totals(since=was)
    for count, total in (("index_pairs", "dsa_index_pairs"),
                         ("keys_selected", "dsa_keys_selected")):
        assert spans["serve_decode"]["counts"][count] \
            + spans["serve_prefill"]["counts"][count] == stats[total]
    cs = stats["cache_state"]["ds32"]
    store = reg.gen_store("ds32")
    # both leaves are in the pool's bytes: 3 layers of (latent + key)
    assert cs["pool_bytes"] == 3 * store.pool_blocks * BS * (
        ds.latent_width(SPEC) + SPEC["index_head_dim"]) * 4


def test_zeroed_index_keys_of_an_adopted_block_change_the_tokens():
    """The fault the comparison must see: were a block adopted without
    its index keys, the indexer would score zeros there and select
    other rows."""
    import jax.numpy as jnp
    params = _jnp(ds32.pack_params(dict(PARAMS), SPEC))
    rs = np.random.RandomState(6)
    seq = rs.randint(0, SPEC["vocab_size"], 25).astype(np.int32)
    tables = np.arange(1, 7, dtype=np.int32).reshape(1, 6)
    import jax
    pools = jax.jit(lambda pl: ds32.paged_step(
        params, pl, tables, seq[None, :24], np.zeros(1, np.int32),
        np.full(1, 24, np.int32), SPEC, BS)[1])(
            ds32.init_pool(SPEC, 7, BS))
    step = jax.jit(lambda pl: ds32.paged_step(
        params, pl, tables, seq[None, 24:], np.full(1, 24, np.int32),
        np.ones(1, np.int32), SPEC, BS)[0])
    sound = step(pools)
    broken = step((pools[0], pools[1].at[:, :, BS:3 * BS].set(0)))
    assert np.abs(sound - broken).max() > 1e-3


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


def test_the_store_knows_the_arch_by_its_own_name():
    """``deepseek_v32`` is a name of its own in the store's seam (a
    program from before it fails on the cell at once), loaded when a
    spec names it, with nothing of the contiguous, int8-KV or draft
    planes."""
    from mxnet_tpu.serving import program_store
    assert program_store._ARCHS[4] == "deepseek_v32"
    assert program_store._serving_model("deepseek_v32") is ds32
    assert ds32.OFFERS == frozenset()
    with pytest.raises(MXNetError, match="contiguous"):
        _store(paged=False)
    with pytest.raises(MXNetError, match="int8"):
        _store(kv_dtype="int8")
