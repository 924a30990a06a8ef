"""The paged GenerationEngine against its parity oracle, the contiguous
plane (greedy AND seeded sampling, copy-on-write prefix sharing under
divergence, the compacted chunk dispatch, the tick that queues both
programs before it fetches), chunked-vs-unchunked prefill equality, and
the ``paged=False`` / ``sample="host"`` keywords and ``MXNET_PALLAS=0``
(docs/architecture/decode_engine.md).  Every test that reads
``contig_registry`` is here: the contiguous plane is kept as this
file's oracle and for nothing else (ROADMAP.md D2, D15)."""
import numpy as np
import pytest

from mxnet_tpu.serving import GenerationEngine, ModelRegistry

from _paged_common import (COMPACT_CHUNK, KV_MAX, _add_model,
                           _compact_registry, _generate, contig_registry,
                           paged_registry)


# ---------------------------------------------------------------------------
# engine: paged plane == contiguous plane
# ---------------------------------------------------------------------------
def test_paged_engine_greedy_matches_contiguous(paged_registry,
                                                contig_registry):
    """Greedy streams through the paged engine — prompts spanning
    partial blocks, multiple blocks, and growth across block
    boundaries — equal the contiguous plane's, token for token."""
    rs = np.random.RandomState(0)
    reqs = [dict(tokens=list(rs.randint(0, 50, n)), max_tokens=mt)
            for n, mt in ((3, 10), (8, 6), (12, 20), (5, 30), (17, 8))]
    want = _generate(contig_registry, reqs)
    got = _generate(paged_registry, reqs)
    assert got == want


def test_paged_engine_seeded_sampling_matches_contiguous(
        paged_registry, contig_registry):
    """The seeded sampler contract survives the paged plane: identical
    (seed, temperature, top_k) produce identical streams on both
    planes (the per-request threefry chain is position-independent)."""
    rs = np.random.RandomState(1)
    reqs = [dict(tokens=list(rs.randint(0, 50, 6)), max_tokens=8,
                 temperature=0.8, top_k=k, seed=s)
            for k, s in ((0, 5), (3, 5), (10, 11))]
    want = _generate(contig_registry, reqs)
    got = _generate(paged_registry, reqs)
    assert got == want


def test_prefill_pad_rows_inert(contig_registry):
    """Bucket padding: a 3-prompt batch padded to bucket 4 gives each
    real row the same first-token logits as serving it alone.  (Here
    since PR 48, from tests/test_decode_engine.py: it drives the
    oracle's own ``prefill`` program by hand, and the paged plane, which
    compacts prompt rows and pads no batch, has no counterpart.)"""
    store = contig_registry.gen_store("m")
    rs = np.random.RandomState(5)
    prompts = [list(rs.randint(0, 50, n)) for n in (3, 4, 2)]
    toks, lens = store.pad_prompts(prompts)
    assert toks.shape == (4, 4) and list(lens[:3]) == [3, 4, 2]
    batch_first = np.asarray(store.run_prefill(toks, lens)[0])
    for i, p in enumerate(prompts):
        t1, l1 = store.pad_prompts([p])
        solo = np.asarray(store.run_prefill(t1, l1)[0])
        assert np.allclose(batch_first[i], solo[0], atol=1e-6)


def test_chunked_prefill_matches_unchunked():
    """prefill_chunk=4 vs prefill_chunk=kv_max (one whole-prompt
    dispatch): same streams — chunking changes scheduling, never
    numbers — and the chunked engine provably dispatched more chunks."""
    rs = np.random.RandomState(2)
    reqs = [dict(tokens=list(rs.randint(0, 50, n)), max_tokens=6)
            for n in (13, 7, 20, 3)]
    outs, chunks = [], []
    for chunk in (4, KV_MAX):
        reg = ModelRegistry()
        _add_model(reg, paged=True, prefill_chunk=chunk)
        eng = GenerationEngine(reg)
        try:
            futs = [eng.submit("m", **kw) for kw in reqs]
            outs.append([f.result(180).tokens for f in futs])
            chunks.append(eng.stats()["prefill_chunks"])
        finally:
            eng.close()
    assert outs[0] == outs[1]
    # 13+7+20+3 tokens at chunk 4 -> 4+2+5+1 chunk rows; unchunked
    # engines pay one row per prompt
    assert chunks[0] == 12 and chunks[1] == 4


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------
def test_prefix_sharing_and_cow_isolation(contig_registry):
    """A repeated prompt adopts the registered blocks (hit counters,
    prefill work skipped); a diverging prompt shares only whole
    matching blocks; decode writes into shared blocks fork (COW), so
    re-running the original prompt still matches the contiguous
    oracle after every divergent stream polluted its own copies."""
    rs = np.random.RandomState(3)
    P = list(rs.randint(0, 50, 12))          # 1 full block + 4-tail
    Pdiv = P[:10] + [(P[10] + 1) % 50, (P[11] + 3) % 50]
    reqs = [dict(tokens=P, max_tokens=6),
            dict(tokens=Pdiv, max_tokens=6),
            dict(tokens=P, max_tokens=6)]
    want = _generate(contig_registry, reqs)

    reg = ModelRegistry()
    _add_model(reg, paged=True, prefill_chunk=8)
    eng = GenerationEngine(reg)
    try:
        a = eng.submit("m", P, max_tokens=6).result(180)
        s0 = eng.stats()
        assert s0["prefix_hits"] == 0
        b = eng.submit("m", P, max_tokens=6).result(180)
        s1 = eng.stats()
        # exact re-prompt: 1 full block + the tail = 12 shared tokens,
        # and only the LAST prompt token re-runs (its logits seed the
        # first sample) -> one single-token chunk instead of two
        assert s1["prefix_hits"] == 1
        assert s1["prefix_hit_blocks"] - s0["prefix_hit_blocks"] == 2
        assert s1["prefix_hit_tokens"] - s0["prefix_hit_tokens"] == 12
        assert s1["prefill_chunks"] - s0["prefill_chunks"] == 1
        c = eng.submit("m", Pdiv, max_tokens=6).result(180)
        s2 = eng.stats()
        # divergent suffix: only the first full block (8 tokens) is
        # shared; its tail is freshly prefilled
        assert s2["prefix_hits"] == 2
        assert s2["prefix_hit_tokens"] - s1["prefix_hit_tokens"] == 8
        d = eng.submit("m", P, max_tokens=6).result(180)
        st = eng.stats()
        # every decode write landing in a shared block forked first
        assert st["cow_forks"] >= 2
        cs = reg.gen_store("m").stats()["cache_state"]
        assert cs["prefix_entries"] >= 2
    finally:
        eng.close()
    assert [a.tokens, c.tokens, d.tokens] == want
    assert b.tokens == a.tokens


# ---------------------------------------------------------------------------
# escape hatches
# ---------------------------------------------------------------------------
def test_paged_escape_hatches_bit_identical(monkeypatch):
    """MXNET_PALLAS=0 (dense gather twin pinned) reproduces the default
    routing bit-for-bit, and paged=False pins the contiguous plane —
    the three configurations agree token-for-token."""
    rs = np.random.RandomState(5)
    reqs = [dict(tokens=list(rs.randint(0, 50, n)), max_tokens=10)
            for n in (6, 11)]
    streams = {}
    for tag, env, paged in (("auto", None, True), ("xla", "0", True),
                            ("contig", None, False)):
        if env is None:
            monkeypatch.delenv("MXNET_PALLAS", raising=False)
        else:
            monkeypatch.setenv("MXNET_PALLAS", env)
        reg = ModelRegistry()
        _add_model(reg, paged=paged, prefill_chunk=8)
        streams[tag] = _generate(reg, reqs)
    assert streams["auto"] == streams["xla"] == streams["contig"]


@pytest.mark.parametrize("temperature", [0.0, 0.9],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("bb", [8, 16])
@pytest.mark.parametrize("arch", ["lm", "deepseek_v3"])
def test_chunk_dispatch_runs_over_the_slots_in_their_prompt(
        arch, bb, temperature, contig_registry):
    """A burst that puts every slot in its prompt, and more requests
    than slots behind it, through a chunk dispatch of 4 rows: every
    stream equals the oracle's (the LM's contiguous twin; for the
    latent pool, which has none, the same store fed one request at a
    time), greedy and seeded; a chunk dispatch advances exactly the key
    chains of the rows that sampled; the rows that go are the ones
    admitted first, so no waiting row is passed over by a later
    admission; and the counters read what that schedule implies."""
    from mxnet_tpu import profiler
    reg = _compact_registry(arch, bb)
    store = reg.gen_store("m")
    width = store.chunk_rows(bb)
    assert width == 4 < bb
    rs = np.random.RandomState(bb)
    # distinct first tokens: no prefix is shared, every prompt token is
    # computed, 3 or 4 chunks a request; two tokens out, so the first
    # slots refill while the high ones are still in their prompt
    reqs = [dict(tokens=[i] + [int(t) for t in
                               rs.randint(0, 50, 8 + i % 8)],
                 max_tokens=2, temperature=temperature, top_k=5,
                 seed=100 + i) for i in range(bb + 6)]
    if arch == "lm":
        want = _generate(contig_registry, reqs)
    else:
        want = [_generate(reg, [kw])[0] for kw in reqs]

    eng = GenerationEngine(reg)
    seen, last = [], {}
    chunk_rows = eng._chunk_rows

    def spy_rows(st, pre, span):
        last["waiting"] = [i for i in st.active() if not st.decoding[i]]
        last["c"] = chunk_rows(st, pre, span)
        return last["c"]

    def spy(method):
        # the method that queues a tick's prompt chunk: the chunk
        # program's, or (a store whose model steps over row groups) the
        # one-pass tick's, which advances its decode rows' chains too
        queue = getattr(eng, method)

        def queued(model, st, *groups):
            if not groups[-1]:      # a one-pass tick without prompt rows
                return queue(model, st, *groups)
            before = np.array(st.keys)
            finish = queue(model, st, *groups)
            c, dec = last["c"], list(groups[0]) if len(groups) > 1 else []
            seen.append(dict(
                rows=[int(i) for i in c.live],
                sampled=[int(i) for i in c.slots[:len(c.rows)][
                    c.do[:len(c.rows)]]] + dec,
                waiting={i: st.slots[i].seq for i in last["waiting"]},
                shape=c.tables.shape,
                counts={"width": c.n, "deferred": c.deferred},
                before=before, after=np.array(st.keys)))
            return finish
        setattr(eng, method, queued)

    assert store.one_pass == (arch != "lm")
    eng._chunk_rows = spy_rows
    spy("_queue_tick" if store.one_pass else "_paged_prefill_chunk")
    opened = profiler.phase_totals()
    try:
        futs = [eng.submit("m", **kw) for kw in reqs]
        got = [f.result(300).tokens for f in futs]
        stats = eng.stats()
        admitted = [seq for _m, seq in eng._admit_log]
    finally:
        eng.close()
    assert got == want

    assert seen and all(d["shape"] == (width, store.table_width())
                        for d in seen)
    deferred = 0
    for d in seen:
        # the chain of a slot that did not sample is bit-equal; the
        # ones that sampled moved
        moved = np.any(d["before"] != d["after"], axis=1)
        assert sorted(np.nonzero(moved)[0]) == sorted(d["sampled"])
        # oldest first by admission: the rows are the first `width` of
        # the waiting slots in the order they were admitted
        order = sorted(d["waiting"],
                       key=lambda i: admitted.index(d["waiting"][i]))
        assert d["rows"] == order[:width]
        assert d["counts"] == {"width": width,
                               "deferred": len(order[width:])}
        deferred += len(order[width:])
    # a later admission did wait behind an earlier one in a HIGHER slot
    assert any(max(d["rows"]) > min(set(d["waiting"]) - set(d["rows"]))
               for d in seen if len(d["waiting"]) > width)
    assert deferred > 0
    assert stats["prefills"] == len(seen)
    assert stats["prefill_chunks"] == sum(len(d["rows"]) for d in seen) \
        == sum(-(-len(kw["tokens"]) // COMPACT_CHUNK) for kw in reqs)
    assert stats["prefill_row_slots"] == width * len(seen)
    assert stats["prefill_rows_deferred"] == deferred
    span = profiler.phase_totals(since=opened)["serve_prefill"]
    assert span["spans"] == len(seen)
    assert span["counts"]["width"] == stats["prefill_row_slots"]
    assert span["counts"]["deferred"] == deferred
    assert span["counts"]["rows"] == stats["prefill_chunks"]


# ---------------------------------------------------------------------------
# a tick queues both programs before it fetches either's tokens
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sample,ahead", [("graph", True), ("host", False)])
def test_tick_queues_the_chunk_before_it_fetches_the_step(
        contig_registry, sample, ahead):
    """With the sampler in the graph a tick that has both a decode
    step and a prompt chunk queues the chunk (on the pool and the key
    chains the step returns, not yet computed) BEFORE it fetches the
    step's tokens, so the device goes from one program to the next
    while the host resolves; the host's sampler moves the key chains
    itself, so there each program is fetched before the next is
    queued.  Either way the streams are the contiguous plane's."""
    reg = ModelRegistry()
    store = _add_model(reg, paged=True, prefill_chunk=8, sample=sample)
    rs = np.random.RandomState(5)
    # the second prompt is still in its chunks while the first decodes
    reqs = [dict(tokens=[int(t) for t in rs.randint(0, 50, n)],
                 max_tokens=6, temperature=0.7, top_k=5, seed=40 + n)
            for n in (3, 24, 20)]
    want = _generate(contig_registry, reqs)
    eng = GenerationEngine(reg)
    log = []

    def spied(name, fn):
        def call(*a, **kw):
            log.append(name)
            return fn(*a, **kw)
        return call

    for name in ("run_paged_step_sample", "run_paged_chunk_sample",
                 "run_paged_step"):
        setattr(store, name, spied(
            "chunk" if "chunk" in name else "step", getattr(store, name)))
    eng._fetch_decode = spied("fetch", eng._fetch_decode)
    try:
        futs = [eng.submit("m", **kw) for kw in reqs]
        got = [f.result(180).tokens for f in futs]
    finally:
        eng.close()
    assert got == want
    seq = " ".join(log)
    if ahead:
        assert "step chunk fetch fetch" in seq
        assert "step fetch chunk" not in seq
    else:
        # one program's name here (the logits program), chunk or step
        assert "step step" not in seq and "fetch fetch" not in seq
