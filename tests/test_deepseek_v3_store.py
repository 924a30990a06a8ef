"""DeepSeek-V3's store and engine at toy sizes on the CPU: chunks, then
decode, through the latent pool against the reference's full forward,
the engine serving a shared prefix with a fork, the model seam, and
int8 weights (the model's functions are tests/test_deepseek_v3.py's)."""
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import deepseek_v3 as ds
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
from mxnet_tpu.serving.program_store import GenerativeProgramStore

from _deepseek_v3_common import (BS, CFG, CHUNK, LOGIT_TOL, PARAMS, SPEC,
                                 SPEC_IN, STORE_KW, _greedy_continuations,
                                 _ref_logits, _store, ref)


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
    assert len(a.tokens) == 6 and b.tokens == a.tokens
    assert a.tokens == _greedy_continuations(ref, P, a.tokens)
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
