"""Command A+'s store and engine at toy sizes on the CPU: the paged
programs over TWO classes of cache block against the reference's full
forward across a toy window, what ``served_gaps`` reads of it, prefix
hits and copy-on-write forks beyond the window against a cold run, a
hit whose window is no longer whole, the blocks' return at retirement,
the eight shares of the expert layer, and the seam (the model's
functions are tests/test_cohere2_moe.py's;
docs/architecture/decode_engine.md, "Classes of block")."""
import numpy as np
import pytest

from mxnet_tpu.models import cohere2_moe as co
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
from mxnet_tpu.serving.decode_engine import _BlockPool, _PrefixStore
from mxnet_tpu.serving.program_store import GenerativeProgramStore

from _cohere2_moe_common import (BS, CFG, LOGIT_TOL, PARAMS, SPEC, SPEC_IN,
                                 STORE_KW, T, WINDOW, _Rows, _greedy,
                                 _ref_logits, _store, ref)


# ---------------------------------------------------------------------------
# (b) chunks, then decode, through both classes = the full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", [
    "shorter-than-a-chunk", "across-the-window",
    "across-the-window-blocks-released", "rows-at-different-positions"])
def test_chunked_prefill_and_decode_logits_match_reference(ref, case):
    """Every logit row the paged programs give equals the reference's
    full forward of that sequence (teacher-forced): a prompt of 5 in
    one chunk; one of 43, which crosses the window of 16 by three
    blocks of 8, and decode steps after it across two more; the same
    with the window class's entries behind the window at the trash
    block, as the engine leaves them; and two rows of one dispatch, one
    before its window's end and one four blocks past it."""
    assert ref.param_shapes(CFG) == co.param_shapes(SPEC)
    rs = np.random.RandomState(0)
    rows = _Rows()
    st = rows.st
    assert st.pool_leaves == 4 and st.state_rows_per_block() == 0
    assert st.cache_classes == ((None, (0, 1)), (WINDOW, (2, 3)))
    assert st.table_width() == 2 * T == 2 * st.class_width()
    kf, vf, kw, vw = rows.pools
    assert kf.shape == vf.shape == (1, 2, st.pool_blocks * BS, 8)
    assert kw.shape == vw.shape == (3, 2, st.pool_blocks * BS, 8)
    rows.give(0, [1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14, 15, 16])
    a = rs.randint(0, 96, 60)
    want = _ref_logits(ref, a)
    released = case.endswith("released")
    n = 5 if case == "shorter-than-a-chunk" else 43
    got = {n - 1: rows.prefill(0, a[:n], release=released)}
    if case == "rows-at-different-positions":
        rows.give(1, [17, 18, 19], [22, 21, 20])
        rows.tables[0, T:T + 3] = 0         # A is past them
        b = rs.randint(0, 96, 20)
        want_b = _ref_logits(ref, b)
        rows.prefill(1, b[:3])
        # one chunk dispatch, both rows live: A one token, B five
        both = rows.step([a[43:44], b[3:8]], [43, 3])
        got[43] = both[0]
        assert np.abs(both[1] - want_b[7]).max() < LOGIT_TOL
        for p in range(8, 14):      # decode steps of both rows
            both = rows.step([a[p + 36:p + 37], b[p:p + 1]], [p + 36, p])
            got[p + 36] = both[0]
            assert np.abs(both[1] - want_b[p]).max() < LOGIT_TOL, p
    else:
        for p in range(n, n + 14):
            if released:
                rows.release(0, p)
            got[p] = rows.step([a[p:p + 1]], [p], rows=(0,))[0]
    for p, row in got.items():
        assert np.abs(row - want[p]).max() < LOGIT_TOL, p
    if released:
        assert not rows.tables[0, T:T + 5].any()


# ---------------------------------------------------------------------------
# (d) a prefix hit and a fork beyond the window = a cold run
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["hit-4-blocks", "hit-5-blocks", "fork"])
def test_prefix_hit_and_fork_give_a_cold_runs_logits(ref, case):
    """B adopts A's first four or five whole blocks (32 or 40 tokens:
    two windows and more) and goes on with an own part from the block
    boundary: in the full class it adopts every block, in the window
    class only those a query at the boundary still sees (the rest of
    its table is the trash block), and its logits are a cold run's and
    the reference's.  ``fork``: B adopts a block A half filled, copies
    it in BOTH classes (``copy_block`` a class) and decodes on in the
    copies, while A's own rows stay what they were."""
    rs = np.random.RandomState(1)
    a = rs.randint(0, 96, 60)
    rows = _Rows()
    rows.give(0, [1, 2, 3, 4, 5, 6, 7], [11, 12, 13, 14, 15, 16, 17])
    if case == "fork":
        rows.prefill(0, a[:44])             # block index 5 holds 40..43
        rows.pools = rows.st.copy_block(*rows.pools, 6, 8, cls=0)
        rows.pools = rows.st.copy_block(*rows.pools, 16, 18, cls=1)
        rows.give(1, [1, 2, 3, 4, 5, 8], [0, 0, 0, 14, 15, 18])
        b = np.concatenate([a[:44], rs.randint(0, 96, 4)])
        want = _ref_logits(ref, b)
        for p in range(44, 48):
            got = rows.step([b[p:p + 1]], [p], rows=(1,))[1]
            assert np.abs(got - want[p]).max() < LOGIT_TOL, p
        want_a = _ref_logits(ref, a)        # A goes on undisturbed
        for p in range(44, 48):
            got = rows.step([a[p:p + 1]], [p], rows=(0,))[0]
            assert np.abs(got - want_a[p]).max() < LOGIT_TOL, p
        return
    j = 4 if case == "hit-4-blocks" else 5
    rows.prefill(0, a[:45])
    b = np.concatenate([a[:j * BS], rs.randint(0, 96, 13)])
    first = (j * BS - WINDOW + 1) // BS     # the window's first block
    rows.give(1, [1, 2, 3, 4, 5][:j] + [8, 9, 10][:7 - j],
              [0] * first + [11, 12, 13, 14, 15][first:j] + [18, 19, 20])
    hit = rows.prefill(1, b, start=j * BS)
    cold = _Rows()
    cold.give(1, [1, 2, 3, 4, 5, 6, 7])
    assert np.abs(hit - cold.prefill(1, b)).max() < 1e-5
    assert np.abs(hit - _ref_logits(ref, b)[-1]).max() < LOGIT_TOL


def test_engine_serves_hits_beyond_the_window(ref):
    """``add_generative_model`` -> ``submit`` -> the paged tick, as the
    other models go: greedy streams equal the reference's own greedy
    continuation.  P (43 tokens) registers its blocks as they fill, so
    the window class's are pinned before P lets them go; Q shares five
    whole blocks and is admitted on them, every one in the full class
    and the window's last two in the window class; a repeat of P is
    admitted on its tail block and forks it in both classes."""
    rs = np.random.RandomState(2)
    P = [int(t) for t in rs.randint(0, 96, 43)]
    Q = P[:40] + [int(t) for t in rs.randint(0, 96, 9)]
    reg = ModelRegistry()
    reg.add_generative_model("co", dict(PARAMS), SPEC_IN, **STORE_KW)
    eng = GenerationEngine(reg)
    try:
        a = eng.submit("co", P, max_tokens=8).result(300)
        st = eng._states["co"]
        # P's 5 whole blocks and its tail, a pin a class each
        assert len(st.prefix) == 6
        assert [len(lru) for lru in st.prefix._lru] == [6, 6]
        b = eng.submit("co", Q, max_tokens=8).result(300)
        c = eng.submit("co", P, max_tokens=8).result(300)
        stats = eng.stats()
    finally:
        eng.close()
    for prompt, res in ((P, a), (Q, b), (P, c)):
        assert res.tokens == _greedy(ref, prompt, 8)
    assert stats["prefix_hits"] == 2 and stats["prefix_hits_cut"] == 0
    assert stats["prefix_hit_tokens"] == 40 + 43
    # P and its repeat pass blocks 0-3 while they run (a query at 50
    # sees from 35 on: block 4), Q blocks 0-4 of which it never held
    # 0-2: the window class gives back what the full class keeps
    assert stats["window_blocks_released"] == 4 + 2 + (4 - 3)
    # each prompt's partial tail block, pinned where it was filled,
    # forks at the first token written past it: a class each
    assert stats["cow_forks"] == 3 * 2
    live = stats["models"]["co"]["pool_blocks_live"]
    assert len(live) == 2 and live[0] >= live[1] > 0
    assert stats["models"]["co"]["class_windows"] == [None, WINDOW]
    assert 0 < stats["cache_bytes_live"] < stats["cache_bytes_one_table"]
    # 4 expert layers a step; every live token is routed in each
    assert stats["moe_expert_steps"] == 4 * (
        stats["decode_steps"] + stats["prefill_chunks"])
    assert stats["moe_tokens"] == 4 * (43 + 9 + 1 + 3 * 7)


# ---------------------------------------------------------------------------
# (e) a hit whose window is no longer whole
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("gone,blocks,tail,cut", [
    ((), 5, True, False),           # nothing evicted: the whole hit
    ((0, 1), 5, True, False),       # behind every window: not needed
    ((4,), 4, False, True),         # the tail's window broken: 4 blocks
    ((3,), 3, False, True),         # 4 blocks need 2..3: cut to 3
    ((2, 3, 4), 2, False, True),    # 2 blocks need 0..1
    ((0, 1, 2, 3, 4, 5), 0, False, True)])      # every pin gone: refused
def test_prefix_match_is_cut_to_a_whole_window(gone, blocks, tail, cut):
    """``_PrefixStore.match`` over a prompt of five whole blocks and a
    tail, window 16 over blocks of 8: with the window class's pins of
    blocks ``gone`` evicted, the hit is the longest prefix whose last
    16 keys are all still pinned, and says that it was cut.  The full
    class's pins are untouched by the window class's eviction."""
    pools = [_BlockPool(40), _BlockPool(40)]
    store = _PrefixStore(pools, BS, (None, WINDOW))
    prompt = list(range(43))
    pid, mine = 0, []
    for j in range(6):
        pair = [pools[0].alloc(), pools[1].alloc()]
        mine.append(pair)
        pid = store.register(pid, prompt[j * BS:(j + 1) * BS], pair)
    for pair in mine:               # the registering sequence retires
        pools[0].deref(pair[0])
        pools[1].deref(pair[1])
    assert store.evictable(0) == store.evictable(1) == 6
    entries = store.match(prompt)
    assert len(entries[0]) == 5 and entries[1] is not None
    for j in gone:                  # evict exactly those pins
        e = (entries[0] + [entries[1]])[j]
        for other in list(store._lru[1].values()):
            if other is not e:
                store._lru[1].move_to_end(other[0])
        assert store.evict_one(1)
        assert e[2][1] == 0 and e[2][0] != 0
    assert pools[1].used() == 6 - len(gone) and pools[0].used() == 6
    chain, got_tail, was_cut = store.match(prompt)
    assert (len(chain), got_tail is not None, was_cut) == (blocks, tail,
                                                           cut)
    # what it would adopt in the window class is all pinned
    at = min(43 - 1, 43 if got_tail is not None else len(chain) * BS)
    hit = chain + ([got_tail] if got_tail is not None else [])
    assert all(e[2][1] for e in hit[store.first_needed(1, at):])
    # a full-class eviction takes the entry and what it pins elsewhere
    assert store.evict_one(0) and len(store) == 5
    assert pools[0].used() == 5


@pytest.mark.parametrize("gone,held,blocks,tail,cut", [
    ((), 2, 3, True, False),        # the rest of the chain and the tail
    ((0, 1), 2, 3, True, False),    # pins behind what it holds
    ((3,), 4, 1, True, False),      # block 3 is its own: no pin needed
    ((3,), 2, 1, False, True),      # block 3 is not: cut to block 2
    ((4,), 4, 0, False, True)])     # nothing usable behind its own
def test_prefix_match_behind_held_blocks(gone, held, blocks, tail, cut):
    """``_PrefixStore.match`` for a slot in its prompt that has the
    first ``held`` whole blocks already (its own or adopted): the walk
    and the chain start behind them, and a window that reaches back
    into them is whole whatever the store still pins there."""
    pools = [_BlockPool(40), _BlockPool(40)]
    store = _PrefixStore(pools, BS, (None, WINDOW))
    prompt = list(range(43))
    pid = 0
    for j in range(6):
        pair = [pools[0].alloc(), pools[1].alloc()]
        pid = store.register(pid, prompt[j * BS:(j + 1) * BS], pair)
        pools[0].deref(pair[0])
        pools[1].deref(pair[1])
    whole, last, _ = store.match(prompt)
    for j in gone:
        e = (whole + [last])[j]
        for other in list(store._lru[1].values()):
            if other is not e:
                store._lru[1].move_to_end(other[0])
        assert store.evict_one(1) and e[2][1] == 0
    assert store.holds((whole[held - 1][0],
                        tuple(prompt[held * BS:(held + 1) * BS])))
    chain, got_tail, was_cut = store.match(
        prompt, (held, whole[held - 1][0]))
    assert chain == whole[held:held + blocks]
    assert (got_tail is not None, was_cut) == (tail, cut)
    at = min(43 - 1, 43 if got_tail is not None
             else (held + len(chain)) * BS)
    hit = chain + ([got_tail] if got_tail is not None else [])
    assert all(e[2][1] for e in hit[max(
        store.first_needed(1, at) - held, 0):])


def test_engine_counts_a_hit_it_had_to_cut(ref):
    """P's window-class pins are evicted under it (as a full window
    class does): a repeat of P finds its chain whole in the full class
    and its window gone, is admitted cold (``prefix_hits_cut``), and
    its tokens are what they were; registering again restores the
    pins, and the next repeat hits."""
    rs = np.random.RandomState(6)
    P = [int(t) for t in rs.randint(0, 96, 43)]
    reg = ModelRegistry()
    reg.add_generative_model("co", dict(PARAMS), SPEC_IN, **STORE_KW)
    eng = GenerationEngine(reg)
    try:
        a = eng.submit("co", P, max_tokens=5).result(300)
        st = eng._states["co"]
        while st.prefix.evict_one(1):
            pass
        assert st.pool_of[1].used() == 0 and st.pool_of[0].used() == 6
        b = eng.submit("co", P, max_tokens=5).result(300)
        first = eng.stats()
        c = eng.submit("co", P, max_tokens=5).result(300)
        stats = eng.stats()
    finally:
        eng.close()
    assert a.tokens == b.tokens == c.tokens == _greedy(ref, P, 5)
    assert first["prefix_hits_cut"] == 1 and first["prefix_hits"] == 0
    assert stats["prefix_hits_cut"] == 1 and stats["prefix_hits"] == 1
    assert stats["prefix_hit_tokens"] == 43


# ---------------------------------------------------------------------------
# (f) retirement returns every block of both classes; a pool that fills
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("requests", [1, 4])
def test_retiring_sequences_returns_every_block_of_both_classes(requests):
    """After the last sequence retires the only blocks held are the
    prefix cache's pins, a class each; with those evicted both
    allocators read 0."""
    rs = np.random.RandomState(requests)
    reg = ModelRegistry()
    reg.add_generative_model("co", dict(PARAMS), SPEC_IN,
                             **dict(STORE_KW, pool_blocks=40))
    eng = GenerationEngine(reg)
    try:
        futs = [eng.submit("co", [int(t) for t in rs.randint(0, 96, 37)],
                           max_tokens=9) for _ in range(requests)]
        for f in futs:
            f.result(300)
        st = eng._states["co"]
        assert not st.tables.any() and not st.resv.any()
        pinned = len(st.prefix)
        assert pinned == 5 * requests
        assert [p.used() for p in st.pool_of] == [pinned, pinned]
        for c in (1, 0):
            while st.prefix.evict_one(c):
                pass
        assert [p.used() for p in st.pool_of] == [0, 0]
        assert len(st.prefix) == 0
    finally:
        eng.close()


def test_a_pool_that_fills_evicts_and_admits_by_class(ref):
    """A pool of 14 blocks a class under six requests of 37 + 9 tokens
    (6 blocks each in the full class): admission reserves in both
    classes, allocation takes back stale pins (``prefix_evictions``),
    nothing is shed and every stream is the reference's."""
    rs = np.random.RandomState(8)
    prompts = [[int(t) for t in rs.randint(0, 96, 37)] for _ in range(6)]
    reg = ModelRegistry()
    reg.add_generative_model("co", dict(PARAMS), SPEC_IN,
                             **dict(STORE_KW, pool_blocks=15))
    eng = GenerationEngine(reg)
    try:
        futs = [eng.submit("co", p, max_tokens=9) for p in prompts]
        got = [f.result(300) for f in futs]
        stats = eng.stats()
        st = eng._states["co"]
        # what admission reads of the pool without a walk is what a
        # walk over the pins counts
        for c, pool in enumerate(st.pool_of):
            assert st.prefix.evictable(c) == sum(
                pool.refcount(e[2][c]) == 1
                for e in st.prefix._lru[c].values()) > 0
    finally:
        eng.close()
    for p, res in zip(prompts[:2] + prompts[-1:], got[:2] + got[-1:]):
        assert res.tokens == _greedy(ref, p, 9)
    assert stats["shed"] == 0 and stats["finished"] == 6
    assert stats["prefix_evictions"] > 0
    assert stats["window_blocks_released"] == 6 * 3


# ---------------------------------------------------------------------------
# (g) the eight shares of the expert layer
# ---------------------------------------------------------------------------
def test_the_shares_of_the_expert_layer_add_up(ref):
    """A layer's 16 experts over four chips of four: the program's
    routed part for each share (its experts moved to the front of the
    router, as the deployment numbers them), summed, plus the shared
    experts ONCE, is the uncut reference's whole expert layer."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.deepseek_v3 import expert_layer
    uncut_in = dict(SPEC_IN, num_experts=16)
    uncut = co.random_params(co.serving_spec(uncut_in), seed=3)
    rs = np.random.RandomState(7)
    h = jnp.asarray(rs.randn(24, 64), jnp.float32)
    live = jnp.ones(24, bool)
    b = "l2_"
    p = {k: jnp.asarray(v) for k, v in uncut.items()}
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(h, p, b, uncut_in)[0] \
            + ref.shared_experts(h, p, b, uncut_in)
        total = jnp.zeros_like(h)
        assignments = 0
        for share in range(4):
            ids = list(range(4 * share, 4 * share + 4))
            order = ids + [e for e in range(16) if e not in ids]
            gate_up = jnp.stack([jnp.concatenate(
                [p["l2_e%d_gate_weight" % e].T,
                 p["l2_e%d_up_weight" % e].T], axis=1) for e in ids])
            down = jnp.stack([p["l2_e%d_down_weight" % e].T for e in ids])
            layer = {"router_weight": p["l2_router_weight"][
                         jnp.asarray(order)],
                     "experts_gate_up": gate_up, "experts_down": down}
            y, counts = expert_layer(
                h, dict(layer, router_bias=jnp.zeros(16)), SPEC, live)
            total = total + y
            assignments += int(counts[1])
        layer = {k[len(b):]: jnp.asarray(v) for k, v in uncut.items()
                 if k.startswith(b + "shared")}
        from mxnet_tpu.models.deepseek_v3 import _swiglu_ffn
        total = total + _swiglu_ffn(
            h, layer["shared_gate_weight"], layer["shared_up_weight"],
            layer["shared_down_weight"]) / SPEC["num_shared_experts"]
    assert assignments == 24 * 4            # every pick on one share
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < 1e-5


# ---------------------------------------------------------------------------
# (h) the seam
# ---------------------------------------------------------------------------
def test_seam_and_the_other_models_pools():
    """``cohere2_moe`` is the store's fourth architecture and offers
    the paged plane alone; its int8 control quantizes every matmul
    weight (the tied embedding, the shared experts and the experts'
    stacks among them); the three older models keep ONE class of block,
    the table width and the pools they had."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    from mxnet_tpu.pallas_ops.dequant_matmul import QuantizedWeight
    from mxnet_tpu.serving import program_store
    assert program_store._ARCHS[:4] == ("transformer_lm", "deepseek_v3",
                                        "lfm2_moe", "cohere2_moe")
    with pytest.raises(MXNetError, match="contiguous"):
        _store(paged=False)
    with pytest.raises(MXNetError, match="int8"):
        _store(kv_dtype="int8")
    with pytest.raises(MXNetError, match="layer_types"):
        co.serving_spec(dict(SPEC_IN, layer_types=["conv"] * 4))
    with pytest.raises(MXNetError, match="first_k_dense_replace"):
        co.serving_spec(dict(SPEC_IN, first_k_dense_replace=1))
    q8 = _store(compute_dtype="int8")
    for name in co.matmul_weights(SPEC):
        assert isinstance(q8._params[name], QuantizedWeight), name
    assert not isinstance(q8._params["l0_norm_gamma"], QuantizedWeight)
    assert q8._params["l1_experts_gate_up"].codes.shape == (4, 64, 64)
    assert q8._params["l3_shared_down_weight"].codes.shape == (64, 64)
    spec = lm_spec(num_layers=1, num_hidden=16, num_heads=2,
                   vocab_size=20)
    lm = GenerativeProgramStore(random_params(spec, 1), spec,
                                batch_buckets=(1,), prompt_buckets=(8,),
                                kv_block=8, kv_max=16, paged=True,
                                prefill_chunk=8)
    assert lm.cache_classes == ((None, (0, 1)),)
    assert lm.table_width() == lm.class_width() == 2
    assert lm.stats()["cache_classes"] == 1
    assert [a.shape for a in lm.new_pool()] == [(1, 2, 3 * 8, 8)] * 2
    # all full layers, or all window layers: one class
    one = co.serving_spec(dict(SPEC_IN, layer_types=[
        "sliding_attention"] * 4))
    assert co.cache_classes(one) == ((WINDOW, (0, 1)),)
    assert len(co.init_pool(one, 4, BS)) == 2


@pytest.mark.parametrize("prompt,width", [(5, 64), (21, 64), (40, 64),
                                          (40, 48)])
def test_served_gaps_reads_the_full_forwards_rows(ref, prompt, width):
    """``served_gaps`` (a padded sequence cut to the shortest width that
    holds it, the padding kept out of the routing, the last layer asked
    for the served positions alone) against the whole ``logits`` of the
    unpadded sequence: the same gaps, the same first choices, across
    the toy window."""
    import jax
    import jax.numpy as jnp
    rs = np.random.RandomState(prompt)
    n = 7
    seq = rs.randint(0, 96, prompt + n)
    served = seq[prompt:]
    want = _ref_logits(ref, seq)[prompt - 1:prompt - 1 + n]
    padded = np.zeros(width, np.int32)
    padded[:prompt + n - 1] = seq[:-1]
    pad_served = np.zeros(8, np.int32)
    pad_served[:n] = served
    params = {k: jnp.asarray(v) for k, v in PARAMS.items()}
    with jax.default_matmul_precision("highest"):
        gap, best = ref.served_gaps(params, jnp.asarray(padded),
                                    np.int32(prompt - 1),
                                    jnp.asarray(pad_served), CFG)
    gap, best = np.asarray(gap)[:n], np.asarray(best)[:n]
    assert np.array_equal(best, want.argmax(-1))
    mine = want[np.arange(n), served]
    assert np.abs(gap - (want.max(-1) - mine)).max() < 1e-5
