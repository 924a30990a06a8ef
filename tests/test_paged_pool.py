"""The paged plane's pool: exhaustion throttling, shedding at admission,
telemetry, the allocator's counts, a failed fetch, a slot in its
prompt held to the prefix cache on every tick, and what a one-pass
store's warm-up compiles
(docs/architecture/decode_engine.md; helpers in tests/_paged_common.py)."""
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import GenerationEngine, ModelRegistry

from _paged_common import (BATCH_BUCKETS, BURST_CHUNK, BURST_PREFIX,
                           KV_BLOCK, SPEC, _add_model,
                           _assert_only_pins_left, _burst_registry,
                           _burst_requests, _generate, _submit_at_once,
                           _without_groups, paged_registry)


# ---------------------------------------------------------------------------
# pool accounting
# ---------------------------------------------------------------------------
def test_pool_exhaustion_throttles_and_completes():
    """A pool smaller than the offered load: admission reservations
    throttle (FIFO, no overtaking) instead of exhausting the pool —
    every stream completes, matches the unconstrained pool, and the
    high-water mark respects capacity."""
    rs = np.random.RandomState(4)
    reqs = [dict(tokens=list(rs.randint(0, 50, 4)), max_tokens=8)
            for _ in range(6)]
    reg = ModelRegistry()
    _add_model(reg, paged=True, prefill_chunk=8)
    want = _generate(reg, reqs)
    # tb+1 = 6 blocks -> capacity 5: at most ~one 2-block request plus
    # its COW headroom in flight at a time
    small = ModelRegistry()
    _add_model(small, paged=True, prefill_chunk=8, pool_blocks=6)
    eng = GenerationEngine(small)
    try:
        futs = [eng.submit("m", **kw) for kw in reqs]
        got = [f.result(180).tokens for f in futs]
        cs = small.gen_store("m").stats()["cache_state"]
        assert cs["pool_blocks_hwm"] <= 5
        assert eng.stats()["shed_pool"] == 0
    finally:
        eng.close()
    assert got == want


def test_oversized_request_sheds_at_admission():
    """A request whose worst-case block need (ceil((prompt+max_tokens)
    / block) plus the self-registration COW block) exceeds pool
    capacity sheds with ServeOverloaded instead of deadlocking the
    admission queue."""
    from mxnet_tpu.serving import ServeOverloaded
    reg = ModelRegistry()
    _add_model(reg, paged=True, prefill_chunk=8, pool_blocks=6)
    eng = GenerationEngine(reg)
    try:
        # 4 + 36 = 40 tokens -> 5 blocks == capacity, but the partial
        # tail self-registers and needs its fork block: 6 > 5
        fut = eng.submit("m", [1, 2, 3, 4], max_tokens=36)
        with pytest.raises(ServeOverloaded):
            fut.result(60)
        assert eng.stats()["shed_pool"] == 1
    finally:
        eng.close()
    # the structural invariant is enforced at store construction: a
    # pool that cannot hold even one full-kv_max sequence is a config
    # error, not a runtime shed
    with pytest.raises(MXNetError):
        _add_model(ModelRegistry(), paged=True, kv_max=80,
                   pool_blocks=6)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def test_paged_telemetry_gauges_counters_and_drop():
    """The paged plane's observability contract: pool gauges +
    serve_prefix_hit_total + the chunks-per-request histogram land in
    the Prometheus exposition; stats()['cache_state'] describes the
    pool; close() drops the engine's per-instance gauge series."""
    from mxnet_tpu import metrics
    reg = ModelRegistry()
    _add_model(reg, paged=True, prefill_chunk=4)
    eng = GenerationEngine(reg)
    try:
        P = [7, 3, 19, 4, 1, 2, 3, 4, 9]
        eng.submit("m", P, max_tokens=4).result(180)
        eng.submit("m", P, max_tokens=4).result(180)
        text = metrics.registry().render_prometheus()
        assert "serve_kv_pool_blocks_used{" in text
        assert "serve_kv_pool_blocks_hwm{" in text
        assert "serve_prefix_hit_total" in text
        assert "serve_prefill_chunks_per_request_bucket" in text
        cs = reg.gen_store("m").stats()["cache_state"]
        for key in ("pool_blocks", "pool_blocks_used",
                    "pool_blocks_hwm", "pool_blocks_shared",
                    "pool_blocks_reserved", "prefix_entries",
                    "block_bytes", "prefill_chunk"):
            assert key in cs, key
        assert cs["pool_blocks_used"] > 0  # prefix pins persist
        lbl = '{engine="%s",model="m"}' % eng._mlabels["engine"]
        assert ("serve_kv_pool_blocks_used%s" % lbl) in text
    finally:
        eng.close()
    after = metrics.registry().render_prometheus()
    assert ("serve_kv_pool_blocks_used%s" % lbl) not in after


def test_paged_store_reports_program_scratch(paged_registry):
    """``stats()['program_temp_bytes']`` names every resident step
    program with the scratch the compiler gave it — what an operator
    holds against one layer of the pool to see that no program carries
    a second one (docs/architecture/decode_engine.md)."""
    st = paged_registry.gen_store("m").stats()
    rows = st["program_temp_bytes"]
    assert [tuple(r[:3]) for r in rows] == \
        [tuple(r) for r in st["programs_resident"]]
    # the warmed store: a decode and a chunk program a batch bucket
    assert {(r[1], r[2]) for r in rows} >= {(bb, lq)
                                            for bb in BATCH_BUCKETS
                                            for lq in (1, 8)}
    assert all(isinstance(r[3], int) and r[3] >= 0 for r in rows)


def test_sampler_counters_follow_what_the_rows_ask(paged_registry):
    """``sample_draw_dispatches`` / ``sample_topk_dispatches`` count the
    paged dispatches for which the in-graph sampler's two ``cond``s
    take their costly branch: none for greedy requests (whatever their
    ``top_k``), every dispatch of a request that samples, and the sort
    only where its ``top_k`` cuts the vocabulary.  The spans carry the
    same flags."""
    from mxnet_tpu import profiler
    vocab = SPEC["vocab_size"]
    eng = GenerationEngine(paged_registry)
    opened = profiler.phase_totals()

    def run(**kw):
        before = eng.stats()
        eng.submit("m", tokens=[3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
                   max_tokens=5, **kw).result(180)
        after = eng.stats()
        delta = {k: after[k] - before[k]
                 for k in ("sample_draw_dispatches",
                           "sample_topk_dispatches", "decode_steps",
                           "prefills")}
        return (delta["sample_draw_dispatches"],
                delta["sample_topk_dispatches"],
                delta["decode_steps"] + delta["prefills"])

    try:
        futs = [eng.submit("m", tokens=[7, i, 2], max_tokens=4, top_k=k)
                for i, k in enumerate((0, 5, vocab))]
        for f in futs:
            f.result(180)
        stats = eng.stats()
        assert stats["decode_steps"] > 0 and stats["prefills"] > 0
        assert stats["sample_draw_dispatches"] == 0
        assert stats["sample_topk_dispatches"] == 0

        draws, sorts, dispatches = run(temperature=0.8, top_k=0, seed=1)
        assert draws == dispatches > 0 and sorts == 0
        draws, sorts, dispatches = run(temperature=0.8, top_k=vocab,
                                       seed=2)
        assert draws == dispatches > 0 and sorts == 0
        draws, sorts, dispatches = run(temperature=0.8, top_k=5, seed=3)
        assert draws == sorts == dispatches > 0
        # the slot's row is greedy again once the request has left it
        assert run() == (0, 0, dispatches)
        stats = eng.stats()
    finally:
        eng.close()
    spans = profiler.phase_totals(since=opened)
    for flag in ("sample_draw", "sample_topk"):
        assert sum(spans[name]["counts"][flag]
                   for name in ("serve_decode", "serve_prefill")) \
            == stats[flag + "_dispatches"]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_block_pool_keeps_the_counts_a_walk_would_give(seed):
    """``pinned_once`` (what the prefix cache's eviction can reclaim)
    and ``shared`` are kept as references come and go; after every
    move of a random schedule of allocations, adoptions, pins, releases
    and evictions they are what a walk over the pool counts."""
    from mxnet_tpu.serving.decode_engine import _BlockPool
    rs = np.random.RandomState(seed)
    pool = _BlockPool(24)
    held, pins = [], set()          # sequences' references; pinned blocks
    for _ in range(600):
        move = rs.randint(5)
        if move == 0:
            b = pool.alloc()
            if b is not None:
                held.append(b)
        elif move == 1 and held:    # another sequence adopts a block
            b = held[rs.randint(len(held))]
            pool.ref(b)
            held.append(b)
        elif move == 2 and held:    # the prefix cache pins a held block
            b = held[rs.randint(len(held))]
            if b not in pins:
                pool.ref(b, pin=True)
                pins.add(b)
        elif move == 3 and held:    # a sequence lets a block go
            pool.deref(held.pop(rs.randint(len(held))))
        elif move == 4 and pins:    # eviction, held by others or not
            b = sorted(pins)[rs.randint(len(pins))]
            pins.discard(b)
            pool.deref(b, pin=True)
        counts = {b: held.count(b) + (b in pins)
                  for b in set(held) | pins}
        assert pool.used() == len(counts)
        assert all(pool.refcount(b) == n for b, n in counts.items())
        assert pool.shared() == sum(n > 1 for n in counts.values())
        assert pool.pinned_once() == sum(counts[b] == 1 for b in pins)


def test_a_fetch_that_raises_fails_its_rows_and_no_others():
    """Both programs of a tick are in flight when the decode step's
    fetch raises: the rows it worked for get the error and give their
    blocks back, the chunk queued behind it still resolves for the row
    in its prompt, and the engine serves on."""
    reg = ModelRegistry()
    store = _add_model(reg, paged=True, prefill_chunk=8)
    rs = np.random.RandomState(9)
    short = [int(t) for t in rs.randint(0, 50, 3)]
    long_ = [int(t) for t in rs.randint(0, 50, 24)]
    eng = GenerationEngine(reg)
    log = []

    def spied(name, fn):
        def call(*a, **kw):
            log.append(name)
            return fn(*a, **kw)
        return call

    store.run_paged_step_sample = spied("step",
                                        store.run_paged_step_sample)
    store.run_paged_chunk_sample = spied("chunk",
                                         store.run_paged_chunk_sample)
    fetch = eng._fetch_decode

    def flaky(arr):
        # the first tick that has a step AND a chunk in flight: the
        # fetch that follows is the step's
        if log[-2:] == ["step", "chunk"] and "lost" not in log:
            log.append("lost")
            raise RuntimeError("lost the device")
        return fetch(arr)

    eng._fetch_decode = flaky
    try:
        a = eng.submit("m", short, max_tokens=6)
        b = eng.submit("m", long_, max_tokens=4)
        with pytest.raises(MXNetError, match="decode dispatch failed"):
            a.result(180)
        assert len(b.result(180).tokens) == 4
        again = eng.submit("m", short, max_tokens=3).result(180)
        assert len(again.tokens) == 3
        st = eng._states["m"]
        assert not st.tables.any() and not st.resv.any()
        assert eng.stats()["errors"] == 1
    finally:
        eng.close()


@pytest.mark.parametrize("arch", ["transformer_lm", "deepseek_v3",
                                  "deepseek_v32", "lfm2_moe",
                                  "cohere2_moe"])
def test_a_burst_over_one_new_prefix_prefills_it_once(arch):
    """Eight requests over one prefix the engine has not seen, all
    submitted at once, a chunk of 4 rows and a pool that holds four
    such requests unshared: the oldest slot writes each block of the
    prefix, the slots that need the same block wait for it and adopt it
    the tick after (and with it what the store has learned since they
    were admitted), and their reservations shrink as they do, which
    lets the rest of the queue in.  Every stream equals the request's
    served alone; the prefix is computed once, not once a slot; nothing
    is left held or reserved."""
    from mxnet_tpu import profiler
    reg = _burst_registry(arch)
    store = reg.gen_store("m")
    assert store.chunk_rows(8) == 4
    reqs = _burst_requests(11, store.spec["vocab_size"])
    want = [_generate(reg, [kw])[0] for kw in reqs]

    eng = GenerationEngine(reg)
    opened = profiler.phase_totals()
    try:
        got = [f.result(300).tokens
               for f in _submit_at_once(eng, reqs)]
        stats = eng.stats()
        spans = profiler.phase_totals(since=opened)
        st = eng._states["m"]
        _assert_only_pins_left(st)
        assert [seq for _m, seq in eng._admit_log] == list(range(8))
    finally:
        eng.close()
    assert got == want
    own = sum(-(-(len(kw["tokens"]) - BURST_PREFIX) // BURST_CHUNK)
              for kw in reqs)
    assert stats["prefill_chunks"] <= BURST_PREFIX // BURST_CHUNK + own
    # the seven followers took the prefix from the store: what of it
    # was there when they were admitted counts as a hit, the rest late
    assert stats["prefix_late_tokens"] > 0
    assert stats["prefix_late_tokens"] + stats["prefix_hit_tokens"] \
        == 7 * BURST_PREFIX
    assert stats["prefix_late_blocks"] + stats["prefix_hit_blocks"] \
        == 7 * BURST_PREFIX // KV_BLOCK
    assert stats["prefill_rows_waited"] > 0
    counts = spans["serve_prepare"]["counts"]
    assert counts["late_tokens"] == stats["prefix_late_tokens"]
    assert counts["late_blocks"] == stats["prefix_late_blocks"]
    assert counts["waited"] == stats["prefill_rows_waited"]
    assert stats["errors"] == stats["shed"] == 0


def test_waiters_outlive_the_writer_of_their_block():
    """The chunk dispatch in which the oldest slot is halfway through
    the shared prefix fails: the rows it worked for get the error, the
    slots that waited on the writer's block were not in it, and the
    oldest of them writes the block the tick after; their streams are
    what they are alone."""
    reg = _burst_registry("transformer_lm")
    store = reg.gen_store("m")
    reqs = _burst_requests(12, store.spec["vocab_size"], n=6)
    want = [_generate(reg, [kw])[0] for kw in reqs]
    eng = GenerationEngine(reg)
    run, calls = store.run_paged_chunk_sample, []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 4:     # the writer is in its second block
            raise RuntimeError("lost the device")
        return run(*a, **kw)

    store.run_paged_chunk_sample = flaky
    try:
        futs = _submit_at_once(eng, reqs)
        with pytest.raises(MXNetError, match="prefill dispatch failed"):
            futs[0].result(300)
        got = [f.result(300).tokens for f in futs[1:]]
        stats = eng.stats()
        _assert_only_pins_left(eng._states["m"])
    finally:
        store.run_paged_chunk_sample = run
        eng.close()
    assert got == want[1:]
    # the writer was alone in that dispatch: no one else saw the error
    assert stats["errors"] == 1 and stats["finished"] == 5
    assert stats["prefill_rows_waited"] > 0
    assert stats["prefix_late_tokens"] > 0


def test_warmup_keeps_two_programs_a_bucket(monkeypatch):
    """``warmup()`` of an expert store returns two programs a bucket,
    the decode step and the one-pass tick IN the chunk program's place;
    ``transformer_lm``'s, an expert store without the step over row
    groups, and one that samples on the host return what they returned
    (a self-drafting store's four: ``tests/test_pangu_ultra_moe.py``)."""
    store = _burst_registry("lfm2_moe").gen_store("m")
    assert store.one_pass and store.stats()["one_pass"]
    assert sorted(store.warmup()) == [
        ("paged_step_sample", 8, 1), ("paged_tick_sample", 8, BURST_CHUNK)]
    assert store.chunk_program(8) == ("paged_tick_sample", 8, BURST_CHUNK)
    assert store.stats()["compiles"] == 2
    lm = _burst_registry("transformer_lm").gen_store("m")
    assert not lm.one_pass
    assert sorted(lm.warmup()) == [
        ("paged_chunk_sample", 8, BURST_CHUNK), ("paged_step_sample", 8, 1)]
    assert lm.stats()["compiles"] == 2
    host = _burst_registry("lfm2_moe", sample="host").gen_store("m")
    assert not host.one_pass
    assert sorted(host.warmup()) == [("paged_step", 4, BURST_CHUNK),
                                     ("paged_step", 8, 1)]
    _without_groups(monkeypatch)
    off = _burst_registry.__wrapped__("lfm2_moe").gen_store("m")
    assert not off.one_pass
    assert sorted(off.warmup()) == sorted(lm.warmup())
