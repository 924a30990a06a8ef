"""LFM2-MoE's store and engine at toy sizes on the CPU: the paged programs
(convolution state one row a block beside ``[K | V]`` rows a token)
against the reference's full forward, prefix hits and copy-on-write
forks against a cold run, the engine restoring state on a hit, the state
rows' return at retirement, and the seam (the model's functions are
tests/test_lfm2_moe.py's; docs/architecture/decode_engine.md, "State
beside the pool")."""
import numpy as np
import pytest

from mxnet_tpu.models import lfm2_moe as lfm
from mxnet_tpu.serving import GenerationEngine
from mxnet_tpu.serving.program_store import GenerativeProgramStore

from _lfm2_moe_common import (BS, CFG, LOGIT_TOL, SPEC, SPEC_IN, _Rows,
                              _greedy_continuations, _ref_logits, _store,
                              ref, registry)


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


def test_engine_restores_state_on_a_prefix_hit(ref, registry):
    """``add_generative_model`` -> ``submit`` -> the paged tick, as the
    other two models go: greedy streams equal the reference's own
    greedy continuation; a request that shares two whole blocks is
    admitted on them with its state (``state_restores``), one that
    repeats a whole prompt reruns from the last block boundary, and
    the expert counters arrive with the sampled tokens."""
    rs = np.random.RandomState(2)
    P = [int(t) for t in rs.randint(0, 96, 19)]
    Q = P[:16] + [int(t) for t in rs.randint(0, 96, 5)]
    eng = GenerationEngine(registry)
    try:
        a = eng.submit("lfm", P, max_tokens=6).result(300)
        b = eng.submit("lfm", Q, max_tokens=6).result(300)
        c = eng.submit("lfm", P, max_tokens=6).result(300)
        stats = eng.stats()
    finally:
        eng.close()
    for prompt, res in ((P, a), (Q, b), (P, c)):
        assert len(res.tokens) == 6
        assert res.tokens == _greedy_continuations(ref, prompt, res.tokens)
    assert stats["prefix_hits"] == 2 == stats["state_restores"]
    # whole blocks only, and never the block of the prompt's last token
    assert stats["prefix_hit_tokens"] == 16 + 16
    assert stats["state_bytes"] == 3 \
        * registry.gen_store("lfm").pool_blocks \
        * 2 * 64 * 4
    # 4 expert layers a step; every live token is routed in each
    assert stats["moe_expert_steps"] == 4 * (
        stats["decode_steps"] + stats["prefill_chunks"])
    assert stats["moe_tokens"] == 4 * (19 + 5 + 3 + 3 * 5)
    assert stats["moe_local_assignments"] == 2 * stats["moe_tokens"]


# ---------------------------------------------------------------------------
# (f) retirement returns every state row; the seam
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("requests", [1, 3])
def test_retiring_sequences_returns_every_state_row(requests, registry):
    """One allocator: a state row lives and dies with its block.  After
    the last sequence retires the only blocks held are the prefix
    cache's pins, and with those evicted the allocator's live count —
    and with it ``state_rows_live`` — reads 0."""
    rs = np.random.RandomState(requests)
    eng = GenerationEngine(registry)
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
