"""Decode-plane tests: offset flash kernel parity, decode-vs-one-shot
logits parity (Pallas routed AND escape hatch), cache-pad -1e30 mask
pins, the generative program store's bucket/warmup machinery, and the
continuous-batching GenerationEngine on the plane every default caller
takes, the paged tick (greedy == the one-shot forward, seeded loadgen
FIFO admission, close-mid-generation drain, growth across KV blocks,
seeded sampling) (docs/architecture/decode_engine.md).  The contiguous
plane is the parity oracle of ``tests/test_paged_decode.py`` and is
tested nowhere else."""
import json
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer_lm import (decode_apply, get_symbol,
                                             init_cache, lm_spec,
                                             prefill_apply, random_params)
from mxnet_tpu.serving import (GenerationEngine, ModelRegistry,
                               OpenLoopSchedule, TokenStream,
                               run_gen_loadgen)

SPEC = lm_spec(num_layers=2, num_hidden=32, num_heads=4, vocab_size=50)
PARAMS = random_params(SPEC, seed=3)
BATCH_BUCKETS = (1, 2, 4)
PROMPT_BUCKETS = (4, 8)
KV_BLOCK, KV_MAX, PREFILL_CHUNK = 8, 40, 8


@pytest.fixture(scope="module")
def registry():
    """One warmed generative registry for every engine test, on the
    default (paged) plane: warmup compiles the decode step and the
    prompt chunk of each batch bucket once."""
    reg = ModelRegistry()
    reg.add_generative_model("m", PARAMS, SPEC,
                             batch_buckets=BATCH_BUCKETS,
                             prompt_buckets=PROMPT_BUCKETS,
                             kv_block=KV_BLOCK, kv_max=KV_MAX,
                             warmup_kv_depth=KV_MAX,
                             prefill_chunk=PREFILL_CHUNK)
    return reg


def _one_shot_logits(tokens):
    """Per-position logits of the one-shot symbol forward (the decode
    loop's ground truth): log of the SoftmaxOutput probabilities is
    shift-invariant, so compare softmax-to-softmax instead."""
    B, T = tokens.shape
    net = get_symbol(seq_len=T, **SPEC)
    pred = mx.Predictor(
        net.tojson(), {"arg:%s" % k: v for k, v in PARAMS.items()},
        {"data": (B, T), "softmax_label": (B, T)})
    out = pred.forward(data=tokens.astype(np.float32),
                       softmax_label=np.zeros((B, T), np.float32))
    return out[0].asnumpy().reshape(B, T, SPEC["vocab_size"])


def _softmax(z):
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def _decode_loop_probs(tokens, prefill_len, cache_len=16):
    """Teacher-forced prefill + T-step decode; returns softmax probs at
    every position from prefill_len-1 on."""
    import jax.numpy as jnp
    B, T = tokens.shape
    lens = np.full((B,), prefill_len, np.int32)
    logits, ck, cv = prefill_apply(
        PARAMS, jnp.asarray(tokens[:, :prefill_len]), jnp.asarray(lens),
        cache_len, SPEC)
    rows = [np.asarray(logits)[:, prefill_len - 1]]
    for t in range(prefill_len, T):
        lg, ck, cv = decode_apply(PARAMS, ck, cv,
                                  jnp.asarray(tokens[:, t], jnp.int32),
                                  jnp.asarray(lens), SPEC)
        rows.append(np.asarray(lg))
        lens = lens + 1
    return _softmax(np.stack(rows, axis=1))   # (B, T-P+1, V)


# ---------------------------------------------------------------------------
# kernel / graph parity
# ---------------------------------------------------------------------------
def test_offset_flash_kernel_matches_dense_twin():
    """flash_attention_offset (interpret mode) vs the dense XLA twin
    with per-row offsets — including an odd KV length that exercises
    the divisor block clamp."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _dense_attention
    from mxnet_tpu.pallas_ops.flash_attention import flash_attention_offset

    rs = np.random.RandomState(0)
    for B, H, Lq, Lk, D in ((3, 2, 1, 24, 8), (2, 2, 4, 18, 8)):
        q = jnp.asarray(rs.randn(B, H, Lq, D).astype(np.float32))
        k = jnp.asarray(rs.randn(B, H, Lk, D).astype(np.float32))
        v = jnp.asarray(rs.randn(B, H, Lk, D).astype(np.float32))
        ofs = rs.randint(0, Lk - Lq, B).astype(np.int32)
        got = np.asarray(flash_attention_offset(
            q, k, v, ofs, block_q=4, block_k=8, interpret=True))
        want = np.asarray(_dense_attention(
            q, k, v, True, 1.0 / D ** 0.5, q_offsets=ofs))
        assert np.abs(got - want).max() < 2e-6


def test_decode_parity_xla_escape_hatch(monkeypatch):
    """MXNET_PALLAS=0: a T-step decode loop reproduces the one-shot
    symbol forward's per-position outputs (fp32 tol) — ragged prefill
    lengths included."""
    monkeypatch.setenv("MXNET_PALLAS", "0")
    rs = np.random.RandomState(7)
    B, T, P = 2, 12, 4
    toks = rs.randint(0, SPEC["vocab_size"], (B, T)).astype(np.int32)
    ref = _one_shot_logits(toks)
    got = _decode_loop_probs(toks, P)
    assert np.abs(got - ref[:, P - 1:]).max() < 1e-5


def test_decode_parity_pallas_routed(monkeypatch):
    """MXNET_PALLAS=2: the decode loop routes the OFFSET flash kernel
    (dispatch stats prove it) and still matches the one-shot forward."""
    from mxnet_tpu.pallas_ops import dispatch as pd
    monkeypatch.setenv("MXNET_PALLAS", "2")
    monkeypatch.setenv("MXNET_PALLAS_BLOCK_SEQ", "8")
    pd.reset_dispatch_stats()
    rs = np.random.RandomState(7)
    B, T, P = 2, 12, 4
    toks = rs.randint(0, SPEC["vocab_size"], (B, T)).astype(np.int32)
    got = _decode_loop_probs(toks, P)
    routed = pd.dispatch_stats()
    assert routed.get("DotProductAttentionOffset", 0) > 0, routed
    monkeypatch.setenv("MXNET_PALLAS", "0")
    ref = _one_shot_logits(toks)
    assert np.abs(got - ref[:, P - 1:]).max() < 1e-4


def test_cache_pad_positions_never_leak():
    """Junk planted past every sequence's cache frontier (where pad
    prefill rows and retired tenants leave residue) must not perturb
    decode logits — the -1e30 offset-causal mask pins them out, on the
    dense path bit-for-bit and on the routed kernel within tol."""
    import jax.numpy as jnp
    rs = np.random.RandomState(11)
    B, P, C = 2, 4, 16
    toks = rs.randint(0, SPEC["vocab_size"], (B, P)).astype(np.int32)
    lens = np.full((B,), P, np.int32)
    _, ck, cv = prefill_apply(PARAMS, jnp.asarray(toks),
                              jnp.asarray(lens), C, SPEC)
    junk_k = np.asarray(ck).copy()
    junk_v = np.asarray(cv).copy()
    junk_k[:, :, :, P:, :] = 1e9
    junk_v[:, :, :, P:, :] = -1e9
    nxt = rs.randint(0, SPEC["vocab_size"], B).astype(np.int32)
    # the new token's K/V overwrites position P; everything past it is
    # junk and must stay masked
    clean, _, _ = decode_apply(PARAMS, ck, cv, jnp.asarray(nxt),
                               jnp.asarray(lens), SPEC)
    dirty, _, _ = decode_apply(PARAMS, jnp.asarray(junk_k),
                               jnp.asarray(junk_v), jnp.asarray(nxt),
                               jnp.asarray(lens), SPEC)
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))


# ---------------------------------------------------------------------------
# generative program store
# ---------------------------------------------------------------------------
def test_store_bucket_geometry(registry):
    store = registry.gen_store("m")
    assert store.kv_bucket(1) == KV_BLOCK
    assert store.kv_bucket(8) == 8 and store.kv_bucket(9) == 16
    with pytest.raises(MXNetError):
        store.kv_bucket(KV_MAX + 1)
    assert store.prompt_bucket(5) == 8
    with pytest.raises(MXNetError):
        store.prompt_bucket(9)
    with pytest.raises(MXNetError):
        store.validate_request(8, KV_MAX)  # 8 + KV_MAX > KV_MAX
    store.validate_request(8, KV_MAX - 8)
    # the paged plane chunks prompts: one past every prompt bucket is
    # bounded by kv_max and the pool alone
    store.validate_request(9, KV_MAX - 9)
    assert store.class_width() == store.table_width() == KV_MAX // KV_BLOCK


def test_store_warmup_covers_the_served_programs(registry):
    """Every program the engine dispatches in these tests was compiled
    at warmup — steady-state serving never compiles (AOT promise): the
    decode step and the prompt chunk of every batch bucket, and no
    program of the contiguous plane.  The step's kind tracks the
    store's sample mode: in-graph sampling (the default) serves
    ``paged_step_sample`` programs."""
    store = registry.gen_store("m")
    st = store.stats()
    assert st["generative"] is True and st["paged"] is True
    dkind = ("paged_step_sample" if st["sample_mode"] == "graph"
             else "paged_step")
    kinds = {(k, b, c) for k, b, c in st["programs_resident"]}
    for bb in BATCH_BUCKETS:
        assert (dkind, bb, 1) in kinds
        assert store.chunk_program(bb) in kinds
    compiles = st["compiles"]
    eng = GenerationEngine(registry)
    try:
        futs = [eng.submit("m", [1 + i, 2, 3], max_tokens=4)
                for i in range(BATCH_BUCKETS[-1])]
        for f in futs:
            f.result(60)
    finally:
        eng.close()
    st = store.stats()
    assert st["compiles"] == compiles
    assert not {k for k, _, _ in st["programs_resident"]} \
        & {"prefill", "decode", "decode_sample"}


def test_store_missing_params_rejected():
    from mxnet_tpu.serving import GenerativeProgramStore
    broken = dict(PARAMS)
    broken.pop("blk1_q_weight")
    with pytest.raises(MXNetError, match="missing params"):
        GenerativeProgramStore(broken, SPEC, batch_buckets=(1,),
                               prompt_buckets=(4,), kv_block=8,
                               kv_max=16)


def test_registry_gen_namespace(registry):
    assert "m" in registry
    with pytest.raises(MXNetError):
        registry.add_generative_model("m", PARAMS, SPEC, warmup=False)
    with pytest.raises(MXNetError, match="generative"):
        registry.gen_store("nope")
    # the forward-store accessor must NOT serve a generative model
    with pytest.raises(MXNetError):
        registry.store("m")


# ---------------------------------------------------------------------------
# generation engine
# ---------------------------------------------------------------------------
def _greedy_continuations(prompt, tokens):
    """What greedy decoding emits after each prefix of ``prompt +
    tokens``, read off ONE dense one-shot forward of the sequence
    padded to KV_MAX (the model is causal: a position's logits do not
    see what follows it).  ``tokens == _greedy_continuations(prompt,
    tokens)`` holds exactly for the greedy stream, by induction, and
    asks nothing of either serving plane."""
    seq = np.zeros((1, KV_MAX), np.int32)
    seq[0, :len(prompt) + len(tokens)] = list(prompt) + list(tokens)
    probs = _one_shot_logits(seq)[0]
    first = len(prompt) - 1
    return [int(t) for t in
            np.argmax(probs[first:first + len(tokens)], axis=-1)]


def test_engine_greedy_matches_reference(registry):
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(0, 50, rs.randint(2, 7)))
               for _ in range(6)]
    eng = GenerationEngine(registry)
    try:
        futs = [eng.submit("m", p, max_tokens=10) for p in prompts]
        results = [f.result(120) for f in futs]
    finally:
        eng.close()
    for r, p in zip(results, prompts):
        assert r.tokens == _greedy_continuations(p, r.tokens)
        assert len(r.tokens) == 10
        assert r.finish_reason == "length"
        assert r.prompt_len == len(p)
        assert len(r.token_times) == len(r.tokens)


def test_engine_kv_growth_matches_reference(registry):
    """A generation crossing several kv blocks (its table grows to four
    blocks of the pool under the engine, 4 + 28 tokens of 8 a block)
    matches the dense full-depth reference."""
    prompt = [7, 3, 19, 4]
    eng = GenerationEngine(registry)
    try:
        got = eng.submit("m", prompt, max_tokens=28).result(120)
        pool = eng.stats()["models"]["m"]
    finally:
        eng.close()
    assert got.tokens == _greedy_continuations(prompt, got.tokens)
    assert len(got.tokens) == 28
    blocks = -(-(len(prompt) + 28) // KV_BLOCK)
    assert pool["pool_blocks_hwm"] >= blocks
    assert pool["pool_blocks_used"] < blocks      # retired: given back


def test_engine_eos_stops_early(registry):
    prompt = [1, 2, 3]
    eng = GenerationEngine(registry)
    try:
        ref = eng.submit("m", prompt, max_tokens=12).result(60).tokens
        assert ref == _greedy_continuations(prompt, ref)
        k = ref.index(ref[0])   # first occurrence of the eventual eos token
        hit = eng.submit("m", prompt, max_tokens=12,
                         eos_id=ref[0]).result(60)
        miss_eos = next(t for t in range(SPEC["vocab_size"])
                        if t not in ref)
        miss = eng.submit("m", prompt, max_tokens=12,
                          eos_id=miss_eos).result(60)
    finally:
        eng.close()
    assert hit.finish_reason == "eos"
    assert hit.tokens == ref[:k + 1]
    assert miss.finish_reason == "length"
    assert miss.tokens == ref


def test_engine_seeded_sampling_deterministic(registry):
    eng = GenerationEngine(registry)
    try:
        kw = dict(max_tokens=8, temperature=0.9, top_k=5)
        a = eng.submit("m", [5, 6], seed=42, **kw).result(60)
        b = eng.submit("m", [5, 6], seed=42, **kw).result(60)
        c = eng.submit("m", [5, 6], seed=43, **kw).result(60)
    finally:
        eng.close()
    assert a.tokens == b.tokens
    assert len(a.tokens) == 8 and len(c.tokens) == 8


def test_engine_stream_yields_tokens_in_order(registry):
    eng = GenerationEngine(registry)
    try:
        stream = TokenStream()
        fut = eng.submit("m", [9, 9], max_tokens=6, stream=stream)
        streamed = list(stream)
        res = fut.result(60)
    finally:
        eng.close()
    assert streamed == res.tokens


def test_admit_retire_fifo_under_seeded_loadgen(registry):
    """Admission order == submission order per model under the seeded
    open-loop schedule (continuous batching must never overtake), all
    requests complete, zero drops; and the loadgen summary carries the
    generation metrics."""
    rs = np.random.RandomState(2)
    prompts = [list(rs.randint(0, 50, rs.randint(2, 7)))
               for _ in range(24)]
    eng = GenerationEngine(registry)
    try:
        schedule = OpenLoopSchedule(21, 24, 120.0, gen_tokens=(4, 8))
        summary = run_gen_loadgen(
            lambda i, mt: eng.submit("m", prompts[i], max_tokens=mt),
            schedule)
        admit_seqs = [seq for (m, seq) in eng._admit_log if m == "m"]
    finally:
        eng.close()
    assert summary["ok"] == 24
    assert summary["errors"] == 0 and summary["timeouts"] == 0
    assert summary["tokens"] == int(schedule.max_tokens.sum())
    assert summary["tokens_per_sec"] > 0
    assert summary["ttft_p99_ms"] is not None
    assert summary["itl_mean_ms"] is not None
    assert admit_seqs == sorted(admit_seqs), \
        "continuous batching reordered admissions"


def test_close_drains_mid_generation(registry):
    """close(drain=True) racing a live decode batch completes every
    admitted AND queued generation before the thread exits."""
    eng = GenerationEngine(registry)
    rs = np.random.RandomState(4)
    prompts = [list(rs.randint(0, 50, 3)) for _ in range(6)]
    futs = [eng.submit("m", p, max_tokens=20) for p in prompts]
    time.sleep(0.05)   # let generation start
    eng.close(drain=True)
    for f, p in zip(futs, prompts):
        r = f.result(0)  # must already be resolved
        assert len(r.tokens) == 20
        assert r.finish_reason == "length"


def test_close_nodrain_fails_fast(registry):
    from mxnet_tpu.serving import ServeClosed
    eng = GenerationEngine(registry)
    futs = [eng.submit("m", [1, 2, 3], max_tokens=30) for _ in range(4)]
    time.sleep(0.05)
    eng.close(drain=False)
    failed = 0
    for f in futs:
        try:
            f.result(0)
        except ServeClosed:
            failed += 1
    assert failed >= 1   # anything not already finished fails fast
    with pytest.raises(ServeClosed):
        eng.submit("m", [1], max_tokens=2)


def test_timeout_expires_in_queue(registry, throttle_ticks):
    from mxnet_tpu.serving import ServeTimeout
    eng = GenerationEngine(registry, max_active=1)
    # throttle the ticks so the slot-occupying generation is STILL
    # active when the queued request's deadline is checked (on a warm
    # process 30 unthrottled steps can finish inside the sleep below,
    # letting the queued request admit instead of timing out)
    slowed = throttle_ticks(eng, 0.01)
    try:
        slow = eng.submit("m", [1, 2], max_tokens=30)
        time.sleep(0.05)   # occupy the single slot
        quick = eng.submit("m", [3, 4], max_tokens=2, timeout=0.001)
        with pytest.raises(ServeTimeout):
            quick.result(60)
        slow.result(120)
    finally:
        eng.close()
    assert slowed, "the throttle was never entered"


def test_submit_validation(registry):
    eng = GenerationEngine(registry)
    try:
        with pytest.raises(MXNetError):
            eng.submit("m", [], max_tokens=4)          # empty prompt
        with pytest.raises(MXNetError):
            eng.submit("m", [999], max_tokens=4)       # out of vocab
        # past every prompt bucket AND the chunk: the paged plane
        # chunks it, where the contiguous plane refused it
        long = eng.submit("m", [1] * 9, max_tokens=4).result(60)
        assert long.prompt_len == 9 and len(long.tokens) == 4
        with pytest.raises(MXNetError):
            eng.submit("m", [1, 2], max_tokens=KV_MAX)  # cache overflow
        with pytest.raises(MXNetError):
            eng.submit("ghost", [1], max_tokens=2)     # unknown model
    finally:
        eng.close()


def test_gen_spans_in_profiler_trace(registry, tmp_path):
    """The decode loop's dispatches emit serve_prefill / serve_decode
    phases through the step-phase seam, and the per-step token
    materialization emits serve_sample."""
    trace = str(tmp_path / "gen_trace.json")
    mx.profiler.profiler_set_config(filename=trace)
    mx.profiler.profiler_set_state("run")
    eng = GenerationEngine(registry)
    try:
        eng.submit("m", [2, 4, 6], max_tokens=4).result(60)
    finally:
        eng.close()
        mx.profiler.profiler_set_state("stop")
        mx.profiler.dump_profile()
    with open(trace) as f:
        names = {ev["name"] for ev in json.load(f)["traceEvents"]
                 if isinstance(ev, dict)}
    assert "serve_prefill" in names
    assert "serve_decode" in names
    assert "serve_sample" in names


def test_gen_schedule_determinism():
    a = OpenLoopSchedule(9, 50, 200.0, gen_tokens=(4, 8, 16))
    b = OpenLoopSchedule(9, 50, 200.0, gen_tokens=(4, 8, 16))
    assert np.array_equal(a.arrivals, b.arrivals)
    assert np.array_equal(a.max_tokens, b.max_tokens)
    c = OpenLoopSchedule(10, 50, 200.0, gen_tokens=(4, 8, 16))
    assert not np.array_equal(a.max_tokens, c.max_tokens) or \
        not np.array_equal(a.arrivals, c.arrivals)
    with pytest.raises(MXNetError):
        run_gen_loadgen(lambda i, n: None,
                        OpenLoopSchedule(9, 5, 10.0))  # no gen_tokens
