"""One program a tick: the one-pass tick that takes a step over row
groups (the step itself is tests/test_paged_kernels.py's), and the tick
ahead (the next tick queued before this one's tokens are fetched)
(docs/architecture/decode_engine.md; helpers in
tests/_paged_common.py)."""
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import GenerationEngine

from _paged_common import (BURST_CHUNK, GROUP_ARCHS,
                           _assert_only_pins_left, _burst_registry,
                           _burst_requests, _generate, _mixed_requests,
                           _spy_order, _submit_at_once, _watch_ticks,
                           _without_groups)


@pytest.mark.parametrize("rows", ["mixed", "greedy", "sampled"])
@pytest.mark.parametrize("arch", GROUP_ARCHS)
def test_one_pass_tick_serves_the_two_program_ticks_tokens(
        arch, rows, monkeypatch):
    """A seeded mix over a shared prefix (greedy rows, seeded draws, or
    both in one batch) through a store that takes the one-pass tick a
    tick AHEAD of its fetches, the pending tokens handed on in the
    device, and through its un-pipelined twin, the same store built
    from the model without its step over row groups: the same tokens,
    request by request (a latent pool, two token leaves, a state leaf
    beside the pool, two classes of block with a window); among the
    one-pass ticks one in which a row retires while a prompt finishes;
    and ``tick_one_pass``, ``tick_programs``, ``tick_ahead`` and
    ``decode_steps`` read what the ticks did: one program a tick, two a
    tick with both kinds of row on the other store."""
    reg = _burst_registry(arch, pool_blocks=0)
    assert reg.gen_store("m").one_pass
    reqs = _mixed_requests(21, reg.gen_store("m").spec["vocab_size"],
                           rows=rows)
    runs = {}
    for path in ("one_pass", "two_programs"):
        if path == "two_programs":
            _without_groups(monkeypatch)
            # (not the cached registry: its store took the path)
            reg = _burst_registry.__wrapped__(arch, pool_blocks=0)
            assert not reg.gen_store("m").one_pass
        eng = GenerationEngine(reg)
        ticks = _watch_ticks(eng)
        try:
            got = [f.result(300).tokens
                   for f in _submit_at_once(eng, reqs)]
            stats = eng.stats()
            _assert_only_pins_left(eng._states["m"])
        finally:
            eng.close()
        runs[path] = got
        assert [len(t) for t in got] == [kw["max_tokens"] for kw in reqs]
        busy = [t for t in ticks if t["dec"] or t["pre"]]
        both = [t for t in busy if t["dec"] and t["pre"]]
        assert both and stats["errors"] == 0
        assert stats["decode_steps"] == sum(1 for t in busy if t["dec"])
        if path == "one_pass":
            assert any(t["retired"] and t["prompts_done"] for t in both)
            assert stats["tick_one_pass"] == stats["prefills"] \
                == sum(1 for t in busy if t["pre"])
            assert stats["tick_programs"] == len(busy)
            # every tick but the first of a run of them was queued on
            # the one before, unfetched; no request ended by eos_id
            assert 0 < stats["tick_ahead"] < len(busy)
            assert stats["decode_rows_wasted"] == 0
        else:
            assert stats["tick_one_pass"] == stats["tick_ahead"] == 0
            assert stats["tick_programs"] == len(busy) + len(both)
    assert runs["one_pass"] == runs["two_programs"]


def test_chunk_only_and_decode_only_ticks():
    """One request alone on a one-pass store: its prompt's ticks have
    no decode row (the decode group rides dead: no ``serve_decode``
    span, no decode step counted), its generation's ticks are the
    decode program's; a program a tick either way, and the stream is
    the two-program store's (``test_a_burst_...`` holds every
    architecture's to that)."""
    from mxnet_tpu import profiler
    reg = _burst_registry("deepseek_v3")
    rs = np.random.RandomState(6)
    prompt = [int(t) for t in rs.randint(0, 96, 11)]
    eng = GenerationEngine(reg)
    ticks = _watch_ticks(eng)
    opened = profiler.phase_totals()
    try:
        got = eng.submit("m", prompt, max_tokens=5).result(300).tokens
        stats = eng.stats()
    finally:
        eng.close()
    spans = profiler.phase_totals(since=opened)
    assert len(got) == 5
    busy = [t for t in ticks if t["dec"] or t["pre"]]
    assert not [t for t in busy if t["dec"] and t["pre"]]
    chunks = -(-len(prompt) // BURST_CHUNK)
    assert stats["tick_one_pass"] == stats["prefills"] == chunks \
        == spans["serve_prefill"]["spans"]
    assert stats["decode_steps"] == 4 == spans["serve_decode"]["spans"]
    assert stats["tick_programs"] == chunks + 4 == len(busy)
    assert spans["serve_decode"]["counts"]["rows"] == 4
    assert spans["serve_prepare"]["spans"] == chunks + 4



def test_a_failed_one_pass_dispatch_fails_both_groups():
    """The one-pass dispatch of a tick with decode rows AND prompt rows
    raises: the requests of both groups get the error and their blocks
    go back; the slots that were in neither (waiting on a sibling's
    block) serve on."""
    reg = _burst_registry("cohere2_moe")
    store = reg.gen_store("m")
    reqs = _burst_requests(13, store.spec["vocab_size"], n=4)
    # one that generates by the time the burst is in its prompt
    first = dict(tokens=[95, 3, 7], max_tokens=40)
    eng = GenerationEngine(reg)
    ticks = _watch_ticks(eng)
    run, lost = store.run_paged_tick_sample, []

    def flaky(*args):
        if ticks[-1]["dec"] and ticks[-1]["pre"] and not lost:
            lost.append(dict(ticks[-1]))
            raise RuntimeError("lost the device")
        return run(*args)

    store.run_paged_tick_sample = flaky
    try:
        a = eng.submit("m", **first)
        while not eng.stats()["decode_steps"]:
            pass
        futs = _submit_at_once(eng, reqs)
        with pytest.raises(MXNetError, match="tick dispatch failed"):
            a.result(300)
        done = []
        for f in futs:
            try:
                done.append(len(f.result(300).tokens))
            except MXNetError as e:
                assert "tick dispatch failed" in str(e)
                done.append(None)
        stats = eng.stats()
        _assert_only_pins_left(eng._states["m"])
    finally:
        store.run_paged_tick_sample = run
        eng.close()
    # the decoding request and the one writer of the shared prefix
    # were in the dispatch; its three siblings waited and were not
    assert len(lost) == 1 and lost[0]["dec"] == 1 and lost[0]["pre"] > 1
    assert done == [None, 4, 4, 4]
    assert stats["errors"] == 2 and stats["finished"] == 3


def test_a_one_pass_store_queues_the_next_tick_before_this_ones_fetch(
        monkeypatch):
    """One request alone, three chunks of prompt and six tokens out, on
    a one-pass store: the launch of tick t + 1 precedes the fetch of
    tick t from the first tick to the last, prompt ticks and decode
    ticks alike, one launch and one fetch a tick; the seventh token is
    not laid out (``max_tokens`` is known at queue time), so the last
    fetch finds nothing queued behind it.  ``tick_ahead`` counts the
    ticks queued on an unfetched one.  The same store without its
    model's step over row groups fetches each tick before it launches
    the next, as it did."""
    rs = np.random.RandomState(8)
    prompt = [int(t) for t in rs.randint(0, 96, 11)]
    chunks, out = -(-len(prompt) // BURST_CHUNK), 6
    runs = {}
    for path in ("ahead", "twin"):
        if path == "twin":
            _without_groups(monkeypatch)
        reg = _burst_registry.__wrapped__("lfm2_moe")
        store = reg.gen_store("m")
        assert store.one_pass == (path == "ahead")
        eng = GenerationEngine(reg)
        log = _spy_order(eng, store)
        try:
            runs[path] = eng.submit(
                "m", prompt, max_tokens=out, temperature=0.7, top_k=5,
                seed=3).result(300).tokens
            stats = eng.stats()
        finally:
            eng.close()
        ticks = chunks + out - 1
        assert stats["tick_programs"] == ticks
        if path == "ahead":
            assert log == ["launch"] + ["launch", "fetch"] * (ticks - 1) \
                + ["fetch"]
            assert stats["tick_ahead"] == ticks - 1
        else:
            assert log == ["launch", "fetch"] * ticks
            assert stats["tick_ahead"] == 0
    assert runs["ahead"] == runs["twin"] and len(runs["ahead"]) == out


def test_a_decode_row_reads_the_devices_token_or_the_hosts():
    """A one-pass store's decode step on two live rows and a dead one:
    with the pending tokens on the device (``host`` False, junk in
    ``tokens``) it samples what it samples from the same tokens sent by
    the host (``host`` True, junk in ``pending``), bit for bit in the
    pool too; a row that ``do``es leaves its token in its slot's place,
    the others' places are untouched."""
    store = _burst_registry("lfm2_moe").gen_store("m")
    assert store.one_pass
    n, width = 8, store.table_width()
    tables = np.zeros((n, width), np.int32)
    tables[0, 0], tables[2, 0] = 1, 2
    feed = np.array([5, 0, 9, 0, 0, 0, 0, 0], np.int32)
    junk = np.full(n, 77, np.int32)
    do = np.zeros(n, bool)
    do[[0, 2]] = True
    keys = np.tile(np.array([[0, 3]], np.uint32), (n, 1))

    def step(tokens, pending, host):
        out = store.run_paged_step_sample(
            *store.new_pool(), tables, tokens[:, None],
            np.zeros(n, np.int32), np.ones(n, np.int32), keys,
            np.full(n, 0.8, np.float32), np.zeros(n, np.int32), do,
            pending, host)
        toks, *pools = out[:1 + store.pool_leaves]
        return [np.asarray(a) for a in (toks[:n], *pools, *out[-2:])]

    on_device = step(junk, feed, np.zeros(n, bool))
    from_host = step(feed, junk, np.ones(n, bool))
    for got, want in zip(on_device[:-1], from_host[:-1]):
        assert np.array_equal(got, want)
    toks, pending = on_device[0], on_device[-1]
    assert np.array_equal(pending[do], toks[do])
    assert np.array_equal(pending[~do], feed[~do])
    assert np.array_equal(from_host[-1][~do], junk[~do])


@pytest.mark.parametrize("arch", ["cohere2_moe", "lfm2_moe"])
def test_eos_ends_a_request_whose_next_row_is_already_queued(arch):
    """A request hits its ``eos_id`` mid-stream on a store that runs a
    tick ahead: it ends AT that token, the row queued for it meanwhile
    delivers nothing (``decode_rows_wasted`` 1), its blocks go back,
    and the request admitted into the freed slot while that row is
    still in flight (one slot: ``max_active`` 1) samples the tokens it
    samples alone: its chain starts from its own seed, not from what
    the wasted row left in the slot, and neither do its window's blocks
    (two classes of block) nor its state rows (a state leaf beside the
    pool), which the wasted row wrote behind the request's end."""
    reg = _burst_registry(arch)
    rs = np.random.RandomState(15)
    a = dict(tokens=[int(t) for t in rs.randint(0, 96, 13)], max_tokens=12,
             temperature=0.9, top_k=0, seed=71)
    b = dict(tokens=[int(t) for t in rs.randint(0, 96, 9)], max_tokens=5,
             temperature=0.9, top_k=7, seed=72)
    (whole,), (b_alone,) = _generate(reg, [a]), _generate(reg, [b])
    # the first token of the stream's middle that did not occur before
    k = next(k for k in range(3, 10) if whole[k] not in whole[:k])
    eng = GenerationEngine(reg, max_active=1)
    try:
        fa = eng.submit("m", eos_id=whole[k], **a)
        fb = eng.submit("m", **b)
        got = fa.result(300)
        assert got.tokens == whole[:k + 1] and got.finish_reason == "eos"
        assert fb.result(300).tokens == b_alone
        stats = eng.stats()
        st = eng._states["m"]
        assert st.flight is None
        _assert_only_pins_left(st)
    finally:
        eng.close()
    assert stats["decode_rows_wasted"] == 1
    assert stats["generated_tokens"] == k + len(b_alone) - 1
    assert stats["finished"] == 2 and stats["errors"] == 0
    assert [seq for _m, seq in eng._admit_log] == [0, 1]


def test_a_fetch_that_raises_fails_both_ticks_in_flight():
    """Two ticks are in flight when a fetch raises.  One request
    decodes; a writer W and two siblings over its prefix, and four
    prompts of their own, are in their prompt: four rows a chunk, so
    the fourth of those waits its turn.  The fetch fails once the
    siblings have adopted the block W registered when ITS tick was
    queued: the rows of both ticks fail, the siblings fail with them
    (what they adopted was never seen computed), nothing those ticks
    registered stays in the prefix cache, and the one slot that was in
    neither tick and adopted nothing serves on: its stream is what it
    is alone, and so is a newcomer's over W's prefix."""
    reg = _burst_registry("deepseek_v3", pool_blocks=0)
    store = reg.gen_store("m")
    shared = _burst_requests(14, 96, n=3)
    rs = np.random.RandomState(16)
    own = [dict(tokens=[40 + i] + [int(t) for t in rs.randint(0, 96, 19)],
                max_tokens=3) for i in range(4)]
    want_last, want_new = _generate(reg, [own[-1]])[0], \
        _generate(reg, [shared[1]])[0]
    eng = GenerationEngine(reg)
    fetch, lost = eng._fetch_decode, []

    def flaky(arr):
        st = eng._states["m"]
        if not lost and eng.stats()["prefix_late_blocks"]:
            lost.append((st.flight is not None, len(st.prefix)))
            raise RuntimeError("lost the device")
        return fetch(arr)

    eng._fetch_decode = flaky
    try:
        first = eng.submit("m", [95, 3, 7], max_tokens=40)
        while not eng.stats()["decode_steps"]:
            pass
        futs = _submit_at_once(eng, shared + own)
        for f in [first] + futs[:-1]:
            with pytest.raises(MXNetError, match="tick dispatch failed"):
                f.result(300)
        assert futs[-1].result(300).tokens == want_last
        st = eng._states["m"]
        # what is registered is what fetched ticks filled: the decoding
        # request's prompt (long before) and the survivor's, 2 whole
        # blocks and a tail; nothing of W's or the other prompts'
        last = own[-1]["tokens"]
        assert {key[1] for key in st.prefix._entries} == {
            (95, 3, 7), tuple(last[:8]), tuple(last[8:16]),
            tuple(last[16:])}
        assert eng.submit("m", **shared[1]).result(300).tokens == want_new
        stats = eng.stats()
        _assert_only_pins_left(st)
    finally:
        eng.close()
    # a tick was queued behind the one whose fetch raised, and the
    # prefix cache held W's block by then
    assert lost == [(True, lost[0][1])] and lost[0][1] > 0
    assert stats["errors"] == 7 and stats["finished"] == 2


def test_a_draining_close_delivers_the_tick_in_flight():
    """``close(drain=True)`` right behind the submits, on a store that
    runs a tick ahead: every token of every request is delivered, the
    last tick's too."""
    reg = _burst_registry("lfm2_moe")
    reqs = _mixed_requests(25, 96, n=6)
    want = [_generate(reg, [kw])[0] for kw in reqs]
    eng = GenerationEngine(reg)
    futs = _submit_at_once(eng, reqs)
    eng.close()
    assert [f.result(0).tokens for f in futs] == want
