"""What the ``tests/test_paged_*.py`` files share: the toy model, the
warmed registries (module-scoped in each file that asks for one), the
randomized kernel cases and the burst / row-group helpers.  A plain
module, collected by nothing."""
import functools

import numpy as np
import pytest

from mxnet_tpu.models.transformer_lm import lm_spec, random_params
from mxnet_tpu.serving import GenerationEngine, ModelRegistry

SPEC = lm_spec(num_layers=2, num_hidden=32, num_heads=4, vocab_size=50)
PARAMS = random_params(SPEC, seed=3)
BATCH_BUCKETS = (1, 2, 4)
KV_BLOCK, KV_MAX = 8, 40


def _add_model(reg, **kwargs):
    # prompt buckets only bound the CONTIGUOUS oracle (the paged plane
    # chunks prompts); 24 covers the longest comparison prompt
    kw = dict(batch_buckets=BATCH_BUCKETS, prompt_buckets=(4, 8, 24),
              kv_block=KV_BLOCK, kv_max=KV_MAX, warmup_kv_depth=KV_MAX)
    kw.update(kwargs)
    return reg.add_generative_model("m", PARAMS, SPEC, **kw)


@pytest.fixture(scope="module")
def paged_registry():
    """One warmed paged registry (bb x {1, chunk} step programs)."""
    reg = ModelRegistry()
    _add_model(reg, paged=True, prefill_chunk=8)
    return reg


@pytest.fixture(scope="module")
def contig_registry():
    """The contiguous twin of the same model — the oracle of record
    for every paged-vs-contiguous stream comparison."""
    reg = ModelRegistry()
    _add_model(reg, paged=False)
    return reg


def _generate(registry, requests):
    """Run ``requests`` (list of submit kwargs) through one engine;
    returns the token streams in order."""
    eng = GenerationEngine(registry)
    try:
        futs = [eng.submit("m", **kw) for kw in requests]
        return [f.result(180).tokens for f in futs]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------
def _paged_case(seed, B, H, T, D, bs, num_blocks, positions, lq,
                layers=1):
    """One randomized paged attention case: sequences share physical
    blocks, unused table entries point at the trash block 0, and the
    pool rows past every frontier hold junk that must never leak.  The
    pools are the whole ``(layers, H, rows, D)`` stacks the door takes."""
    import jax.numpy as jnp
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, H, lq, D).astype(np.float32))
    k_pool = jnp.asarray(
        rs.randn(layers, H, num_blocks * bs, D).astype(np.float32))
    v_pool = jnp.asarray(
        rs.randn(layers, H, num_blocks * bs, D).astype(np.float32))
    tables = np.zeros((B, T), np.int32)
    pos = np.asarray(positions, np.int32)
    nxt = 1
    for b in range(B):
        nb = -(-int(pos[b] + lq) // bs)
        for j in range(nb):
            if b > 0 and j == 0:
                # every sequence after the first SHARES block 0 of
                # sequence 0 — the prefix-reuse layout
                tables[b, j] = tables[0, 0]
            else:
                tables[b, j] = nxt
                nxt += 1
    assert nxt <= num_blocks, "case needs a bigger pool"
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(pos)


# ---------------------------------------------------------------------------
# all pool heads of a block in ONE copy = one head a copy, bit for bit
# ---------------------------------------------------------------------------
# (id, query heads a pool head, Lq, Q-tile bound, frontiers, table
# width, keyword arguments of the kernel, kind of pool)
HEADS_CASES = [
    ("4-heads-decode", 4, 1, 128, [5, 70, 33], 6, dict(group=2), "kv"),
    ("16-heads-decode", 16, 1, 128, [5, 70, 33], 6, dict(group=2), "kv"),
    ("16-heads-chunk32", 16, 32, 128, [0, 40, 17], 6, dict(group=2),
     "kv"),
    # 4 heads x 6 queries = 24 rows in tiles of 8: a tile spans heads
    ("q-tile-spans-heads", 4, 6, 8, [3, 61, 30], 6, dict(group=2), "kv"),
    ("k|v-rows", 4, 1, 128, [5, 70, 33], 6, dict(group=2), "k|v"),
    ("k|v-rows-chunk32", 4, 32, 128, [0, 40, 17], 6, dict(group=2),
     "k|v"),
    # released entries point at the trash block, which holds poison
    ("window-16", 16, 1, 128, [5, 70, 33], 6,
     dict(group=2, window=16), "kv"),
    ("window-16-chunk", 4, 6, 8, [3, 61, 30], 6,
     dict(group=2, window=16), "kv"),
    ("window-4096", 16, 32, 128, [0, 40, 17], 6,
     dict(group=2, window=4096), "kv"),
    ("int8-pool", 4, 1, 128, [5, 70, 33], 6, dict(group=2), "int8"),
    ("int8-pool-chunk", 16, 6, 8, [3, 61, 30], 6, dict(group=3), "int8"),
    ("group-1", 4, 1, 128, [5, 200, 100], 16, dict(group=1), "kv"),
    ("group-4", 4, 1, 128, [5, 200, 100], 16, dict(group=4), "kv"),
    ("group-16", 4, 1, 128, [5, 200, 100], 16, dict(group=16), "kv"),
    ("group-lowered-by-vmem", 4, 1, 128, [5, 200, 100], 16,
     dict(group=16), "vmem"),
    # sixteen entries of which a row's context fills one to five
    ("table-wider-than-context", 16, 1, 128, [2, 70, 9], 16,
     dict(group=4), "kv"),
]


# ---------------------------------------------------------------------------
# the in-place pool write
# ---------------------------------------------------------------------------
# (id, Lq, positions, valid, tables over 8-token blocks; block 0 trash)
WRITE_CASES = [
    ("decode", 1, [5, 16, 0], [1, 1, 1],
     [[1, 0, 0, 0], [2, 3, 4, 0], [0, 0, 0, 0]]),
    ("chunk-inside-one-block", 4, [2, 9, 0], [4, 4, 4],
     [[1, 0, 0, 0], [2, 3, 0, 0], [4, 0, 0, 0]]),
    # the verify program's case: K+1 rows from any position
    ("chunk-straddles-block-edge", 5, [6, 13, 21], [5, 5, 5],
     [[1, 2, 0, 0], [3, 4, 5, 0], [6, 7, 8, 9]]),
    ("chunk-longer-than-a-block", 12, [7, 0, 3], [12, 12, 12],
     [[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 0, 0]]),
    # pad rows: written to no block a table owns (a whole block of the
    # bound past the last valid row is the trash block's)
    ("valid-below-lq", 8, [6, 8, 30], [3, 1, 2],
     [[1, 2, 0, 0], [3, 4, 5, 0], [6, 7, 8, 9]]),
    # two rows share their prefix blocks 1 and 2 and write their own
    ("shared-prefix-blocks", 4, [16, 17, 4], [4, 3, 4],
     [[1, 2, 3, 0], [1, 2, 4, 0], [5, 6, 0, 0]]),
]


# ---------------------------------------------------------------------------
# the compacted prompt-chunk dispatch
# ---------------------------------------------------------------------------
# DeepSeek-V3 at rehearsal size (tests/test_deepseek_v3.py's widths): the
# second architecture behind the store's model seam, latent pool of one
# leaf, expert counters behind the sampled tokens
DS_SPEC = {
    "arch": "deepseek_v3", "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "router_width": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "vocab_size": 96, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"}}
COMPACT_CHUNK = 4


@functools.lru_cache(maxsize=None)
def _compact_registry(arch, bb):
    """One warmed paged registry an (architecture, slot bucket), kept
    for the module: ONE batch bucket of ``bb`` slots, so the chunk
    dispatch is ``chunk_rows(bb)`` = 4 rows wide from the first tick."""
    reg = ModelRegistry()
    kw = dict(batch_buckets=(bb,), prompt_buckets=(8,),
              kv_block=KV_BLOCK, kv_max=KV_MAX, paged=True,
              prefill_chunk=COMPACT_CHUNK, sample="graph")
    if arch == "lm":
        reg.add_generative_model("m", PARAMS, SPEC, **kw)
    else:
        from mxnet_tpu.models import deepseek_v3 as ds
        params = ds.random_params(ds.serving_spec(DS_SPEC), seed=5)
        reg.add_generative_model("m", params, DS_SPEC, **kw)
    return reg


# ---------------------------------------------------------------------------
# a slot in its prompt is held to the prefix cache on every tick
# ---------------------------------------------------------------------------
BURST_CHUNK, BURST_KV_MAX, BURST_PREFIX = 4, 64, 32     # 4 whole blocks
# rehearsal widths of the other served architectures (their own test
# files' widths): a state leaf beside the pool (lfm2_moe), two classes
# of block with a window of 16 keys (cohere2_moe), two token leaves on
# one table (deepseek_v32)
BURST_SPECS = {
    "deepseek_v32": dict(DS_SPEC, arch="deepseek_v32", index_n_heads=4,
                         index_head_dim=8, index_topk=6),
    "lfm2_moe": {
        "arch": "lfm2_moe", "num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": ["conv", "full_attention", "conv", "conv",
                        "full_attention"],
        "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "conv_L_cache": 3, "vocab_size": 96,
        "norm_eps": 1e-5, "rope_theta": 1e6,
        "routed_scaling_factor": 1.0},
    "cohere2_moe": {
        "arch": "cohere2_moe", "num_hidden_layers": 4,
        "layer_types": ["sliding_attention", "sliding_attention",
                        "sliding_attention", "full_attention"],
        "hidden_size": 64, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 8, "intermediate_size": 32,
        "num_experts": 4, "router_width": 16, "num_experts_per_tok": 4,
        "num_shared_experts": 2, "sliding_window": 16, "vocab_size": 96,
        "layer_norm_eps": 1e-5, "rope_theta": 50000.0,
        "logit_scale": 0.5}}


@functools.lru_cache(maxsize=None)
def _burst_registry(arch, pool_blocks=29, **kwargs):
    """One warmed paged registry an architecture: ONE bucket of 8
    slots, so a chunk dispatch has ``chunk_rows(8)`` = 4 rows, and a
    pool of 28 blocks, which holds four requests of the burst below
    that share nothing (7 blocks each) and all eight once they share
    their prefix."""
    import importlib
    reg = ModelRegistry()
    kw = dict(batch_buckets=(8,), prompt_buckets=(8,), kv_block=KV_BLOCK,
              kv_max=BURST_KV_MAX, paged=True, prefill_chunk=BURST_CHUNK,
              sample="graph", pool_blocks=pool_blocks)
    kw.update(kwargs)
    if arch == "transformer_lm":
        reg.add_generative_model("m", PARAMS, SPEC, **kw)
    else:
        spec = DS_SPEC if arch == "deepseek_v3" else BURST_SPECS[arch]
        mod = importlib.import_module("mxnet_tpu.models." + arch)
        reg.add_generative_model(
            "m", mod.random_params(mod.serving_spec(spec), seed=5), spec,
            **kw)
    return reg


def _burst_requests(seed, vocab, n=8, **kw):
    """``n`` requests that open with one prefix of four whole blocks
    and go on with 3 to 10 tokens of their own (distinct first own
    tokens: nothing else is shared)."""
    rs = np.random.RandomState(seed)
    prefix = [int(t) for t in rs.randint(0, vocab, BURST_PREFIX)]
    return [dict(tokens=prefix + [i] + [int(t) for t in rs.randint(
        0, vocab, 2 + i)], max_tokens=4, **kw) for i in range(n)]


def _submit_at_once(eng, reqs):
    """Every request is in the engine's queue before it admits one."""
    import threading
    gate, admit = threading.Event(), eng._admit_ready

    def gated():
        gate.wait(60)
        admit()

    eng._admit_ready = gated
    futs = [eng.submit("m", **kw) for kw in reqs]
    gate.set()
    return futs


def _assert_only_pins_left(st):
    """No slot holds or reserves a block: what the pool still has
    allocated is what the prefix cache pins, once each."""
    assert not st.tables.any() and not st.resv.any()
    for c, pool in enumerate(st.pool_of):
        assert st.reserved(c) == 0
        assert pool.shared() == 0
        assert pool.used() == pool.pinned_once() == len(pool._pinned)


# ---------------------------------------------------------------------------
# one program a tick: a step over row groups, and the tick that takes it
# ---------------------------------------------------------------------------
GROUP_ARCHS = ["deepseek_v3", "deepseek_v32", "lfm2_moe", "cohere2_moe"]


def _arch(arch):
    """``(model module, validated toy spec)`` of a served architecture."""
    import importlib
    mod = importlib.import_module("mxnet_tpu.models." + arch)
    return mod, mod.serving_spec(
        DS_SPEC if arch == "deepseek_v3" else BURST_SPECS[arch])


def _without_groups(monkeypatch):
    """From here on a store sees its model WITHOUT the step over row
    groups: what switches the one-pass tick off, and nothing else."""
    import types
    from mxnet_tpu.serving import program_store
    find = program_store._serving_model
    monkeypatch.setattr(
        program_store, "_serving_model",
        lambda arch: types.SimpleNamespace(**{
            k: v for k, v in vars(find(arch)).items()
            if k != "paged_step_groups"}))


def _mixed_requests(seed, vocab, n=14, rows="mixed"):
    """A seeded mix: one prefix of two whole blocks under most of the
    prompts, prompts of 3 to 30 tokens, one to six tokens out, greedy
    and seeded draws (``rows``: every second row of each, or all of
    one): slots refill while others decode, so ticks carry decode rows
    and prompt rows together."""
    rs = np.random.RandomState(seed)
    prefix = [int(t) for t in rs.randint(0, vocab, 16)]
    reqs = []
    for i in range(n):
        own = [int(t) for t in rs.randint(0, vocab, 3 + (5 * i) % 14)]
        greedy = i % 2 if rows == "mixed" else rows == "greedy"
        reqs.append(dict(
            tokens=(prefix if i % 3 else []) + [i] + own,
            max_tokens=1 + (3 * i) % 6,
            temperature=0.0 if greedy else 0.8, top_k=4 * (i % 3),
            seed=900 + i))
    return reqs


def _watch_ticks(eng):
    """Record, a tick, how many rows it lays out to decode and how many
    slots are in their prompt, and what the tick retires and finishes
    once its tokens are fetched (a tick ahead: at the NEXT tick's
    delivery, booked to the tick that queued the rows)."""
    ticks, queued = [], {}
    tick, rows, chunk = eng._paged_tick, eng._decode_rows, eng._chunk_rows
    decode, resolve = eng._decode_resolve, eng._chunk_resolve
    queue, deliver = eng._queue_tick, eng._deliver_tick

    def watched(model, st):
        ticks.append(dict(dec=0, pre=0, retired=0, prompts_done=0))
        return tick(model, st)

    def decode_rows(st, dec):
        ticks[-1]["dec"] = len(dec)
        return rows(st, dec)

    def chunk_rows(st, pre, span):
        ticks[-1]["pre"] = len(pre)
        return chunk(st, pre, span)

    def decoded(st, dec, idx, sampled):
        before = len(st.active())
        decode(st, dec, idx, sampled)
        ticks[-1]["retired"] += before - len(st.active())

    def chunked(model, st, c, sampled):
        ticks[-1]["prompts_done"] += int(c.do.sum())
        return resolve(model, st, c, sampled)

    def queue_tick(model, st, dec, pre):
        t = queue(model, st, dec, pre)
        if t is not None:
            queued[id(t)] = ticks[-1]
        return t

    def deliver_tick(model, st, t):
        mine, before = queued.pop(id(t)), len(st.active())
        deliver(model, st, t)
        mine["retired"] += before - len(st.active())
        if t.chunk is not None:
            mine["prompts_done"] += int(t.chunk.do.sum())

    eng._paged_tick, eng._decode_rows, eng._chunk_rows = \
        watched, decode_rows, chunk_rows
    eng._decode_resolve, eng._chunk_resolve = decoded, chunked
    eng._queue_tick, eng._deliver_tick = queue_tick, deliver_tick
    return ticks


# ---------------------------------------------------------------------------
# a tick ahead: the next tick is queued before this one's tokens are fetched
# ---------------------------------------------------------------------------
def _spy_order(eng, store):
    """Log every step program the store launches and every fetch."""
    log = []

    def spied(name, fn):
        def call(*a, **kw):
            log.append(name)
            return fn(*a, **kw)
        return call

    for name in ("run_paged_step_sample", "run_paged_tick_sample",
                 "run_paged_chunk_sample"):
        setattr(store, name, spied("launch", getattr(store, name)))
    eng._fetch_decode = spied("fetch", eng._fetch_decode)
    return log
