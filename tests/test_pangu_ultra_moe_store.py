"""openPangu-Ultra-MoE's self-drafting store and engine at toy sizes on
the CPU: the four programs it warms, the module on against the module
off, the rejection rule for sampling rows, and the accept path (the
model's functions are tests/test_pangu_ultra_moe.py's)."""
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
from mxnet_tpu.serving.program_store import GenerativeProgramStore

from _pangu_ultra_moe_common import (BS, CHUNK, PARAMS, SPEC_IN, STORE_KW,
                                     _agreeing_params, _serve)


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
