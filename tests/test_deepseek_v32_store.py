"""DeepSeek-V3.2's store and engine at toy sizes on the CPU: chunks, then
decode, through BOTH leaves against the reference's full forward, the
seam, and the engine's adoption, fork and counts (the model's functions
and kernels are tests/test_deepseek_v32.py's)."""
import numpy as np
import pytest

from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.models import deepseek_v32 as ds32
from mxnet_tpu.pallas_ops import dispatch
from mxnet_tpu.serving import GenerationEngine, ModelRegistry

from _deepseek_v3_common import SPEC_IN as V3_SPEC_IN

from _deepseek_v32_common import (BS, CFG, CHUNK, LOGIT_TOL, PARAMS, SPEC,
                                  SPEC_IN, STORE_KW, TOPK, _jnp,
                                  _ref_logits, _store, ref)


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
