"""Low-precision serving plane: int8 weight-only (codec, fused
dequant-matmul kernel vs its dense XLA twin, >= 99% greedy top-1
agreement, ~4x resident weight bytes), the bf16 KV decode plane
(relaxed-tol parity incl. ragged prefill lengths, halved cache bytes
per slot) and in-graph sampling (byte-identical token streams vs the
``sample="host"`` keyword, the zero-logits-fetch pin), on the
contiguous plane by the ``paged=False`` keyword where a test holds it
(docs/architecture/serving.md dtype matrix)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer_lm import (decode_apply, init_cache,
                                             lm_spec, prefill_apply,
                                             quantize_lm_params,
                                             random_params)
from mxnet_tpu.pallas_ops import dispatch as pd
from mxnet_tpu.pallas_ops.dequant_matmul import (QuantizedWeight,
                                                 dequant_matmul,
                                                 dequant_matmul_dense,
                                                 dequantize_int8,
                                                 quantize_int8)
from mxnet_tpu.serving import (GenerationEngine, GenerativeProgramStore,
                               ModelRegistry, ProgramStore, host_sample)

SPEC = lm_spec(num_layers=2, num_hidden=32, num_heads=4, vocab_size=50)
PARAMS = random_params(SPEC, seed=3)


# ---------------------------------------------------------------------------
# codec + kernel
# ---------------------------------------------------------------------------
def test_quantize_int8_codec_roundtrip():
    rs = np.random.RandomState(0)
    w = rs.randn(9, 33).astype(np.float32)
    w[3] *= 100.0           # a badly scaled row must not poison others
    w[5] = 0.0              # all-zero row: scale 1, codes 0
    codes, scales = quantize_int8(w, "row")
    assert codes.dtype == np.int8 and scales.shape == (9,)
    assert np.abs(codes).max() <= 127
    deq = np.asarray(dequantize_int8(codes, scales))
    # symmetric absmax round-trip bound: half a quantization step/row
    assert (np.abs(deq - w) <= scales[:, None] / 2 + 1e-7).all()
    assert np.array_equal(deq[5], np.zeros(33))
    # per-row isolates the hot row: row 0's error stays tiny
    assert np.abs(deq[0] - w[0]).max() < np.abs(w[0]).max() / 100
    codes_t, scale_t = quantize_int8(w, "tensor")
    assert np.shape(scale_t) == ()
    with pytest.raises(MXNetError):
        quantize_int8(np.zeros((2, 2, 2)))


def test_dequant_matmul_kernel_matches_dense_twin(monkeypatch):
    """The fused kernel (interpret mode) vs the dense XLA twin — odd
    shapes exercise the divisor block clamp; MXNET_PALLAS=0 routes the
    twin bit-for-bit and counts no kernel route."""
    monkeypatch.setenv("MXNET_PALLAS", "2")
    rs = np.random.RandomState(1)
    for m, n, k in ((5, 7, 12), (16, 32, 64), (3, 130, 24)):
        x = rs.randn(m, k).astype(np.float32)
        codes, scales = quantize_int8(rs.randn(n, k).astype(np.float32))
        pd.reset_dispatch_stats()
        fused = np.asarray(dequant_matmul(x, codes, scales))
        assert pd.dispatch_stats().get("DequantMatmul") == 1
        dense = np.asarray(dequant_matmul_dense(x, codes, scales))
        assert np.abs(fused - dense).max() < 1e-4
        ref = x @ np.asarray(dequantize_int8(codes, scales)).T
        assert np.abs(dense - ref).max() < 1e-3
    monkeypatch.setenv("MXNET_PALLAS", "0")
    pd.reset_dispatch_stats()
    hatch = np.asarray(dequant_matmul(x, codes, scales))
    assert pd.dispatch_stats() == {}
    assert np.array_equal(hatch, dense)


# ---------------------------------------------------------------------------
# int8 forward serving (ProgramStore)
# ---------------------------------------------------------------------------
def _mlp_store(compute_dtype, name, buckets=(1, 4)):
    from mxnet_tpu.serving.loadgen import _smoke_model
    sym, args = _smoke_model(48, 96, 0)
    return ProgramStore(sym, args, {}, {"data": (1, 48)}, name=name,
                        compute_dtype=compute_dtype, buckets=buckets)


def test_int8_forward_store_parity_and_memory(monkeypatch):
    """compute_dtype='int8' on the forward store: FC weights travel as
    (codes, scales) program arguments, outputs track fp32 (same top-1
    on every row), resident weight bytes drop ~4x — measured by
    stats()['weight_bytes'], not asserted from arithmetic."""
    monkeypatch.setenv("MXNET_PALLAS", "2")
    pd.reset_dispatch_stats()
    fp = _mlp_store(None, "fp")
    q8 = _mlp_store("int8", "q8")
    fp.warmup()
    q8.warmup()
    assert pd.dispatch_stats().get("DequantMatmul", 0) > 0
    x = np.random.RandomState(2).uniform(-1, 1, (3, 48)) \
        .astype(np.float32)
    inp, n = fp.canon_inputs({"data": x})
    of = np.asarray(fp.run(inp, n)[0][0])
    oq = np.asarray(q8.run(inp, n)[0][0])
    assert np.array_equal(np.argmax(of, 1), np.argmax(oq, 1))
    assert np.abs(of - oq).max() < 0.05
    wb_fp = fp.stats()["weight_bytes"]
    wb_q8 = q8.stats()["weight_bytes"]
    assert q8.stats()["compute_dtype"] == "int8"
    assert wb_q8["by_dtype"].get("int8", 0) > 0
    assert wb_fp["total"] / wb_q8["total"] >= 3.5


def test_pallas_flip_recompiles_int8_programs(monkeypatch):
    """The dequant kernel fingerprint rides the program-cache key: an
    MXNET_PALLAS flip between dispatches compiles a fresh program
    (never serves the stale lowering), and the =0 program is the dense
    twin — deterministic across repeat runs."""
    monkeypatch.setenv("MXNET_PALLAS", "2")
    store = _mlp_store("int8", "flip", buckets=(2,))
    x = np.random.RandomState(3).uniform(-1, 1, (2, 48)) \
        .astype(np.float32)
    inp, n = store.canon_inputs({"data": x})
    routed = np.asarray(store.run(inp, n)[0][0])
    assert store.stats()["compiles"] == 1
    monkeypatch.setenv("MXNET_PALLAS", "0")
    hatch1 = np.asarray(store.run(inp, n)[0][0])
    assert store.stats()["compiles"] == 2, \
        "PALLAS flip must recompile, not hit the stale program"
    hatch2 = np.asarray(store.run(inp, n)[0][0])
    assert store.stats()["compiles"] == 2  # steady state: cache hit
    assert np.array_equal(hatch1, hatch2)
    assert np.abs(routed - hatch1).max() < 1e-4


# ---------------------------------------------------------------------------
# int8 / bf16 decode parity (teacher-forced, direct graphs)
# ---------------------------------------------------------------------------
def _teacher_forced_argmax(params, toks, pre, cache_len,
                           cache_dtype="float32"):
    """Prefill + T-step decode over a FIXED token grid; per-step argmax
    (top-1) and logits from position pre-1 on."""
    B, T = toks.shape
    lens = np.full((B,), pre, np.int32)
    logits, ck, cv = prefill_apply(params, jnp.asarray(toks[:, :pre]),
                                   jnp.asarray(lens), cache_len, SPEC,
                                   cache_dtype=cache_dtype)
    step = jax.jit(lambda p, k, v, t, l: decode_apply(p, k, v, t, l,
                                                      SPEC))
    rows = [np.asarray(logits)[:, pre - 1]]
    ln = lens.copy()
    for t in range(pre, T):
        lg, ck, cv = step(params, ck, cv, jnp.asarray(toks[:, t]),
                          jnp.asarray(ln))
        rows.append(np.asarray(lg))
        ln = ln + 1
    rows = np.stack(rows, axis=1)          # (B, steps, V)
    return np.argmax(rows, -1), rows


def test_int8_decode_top1_agreement_64_steps():
    """>= 99% greedy top-1 agreement between int8 weight-only and fp32
    over >= 64 teacher-forced decode steps on the pinned seed."""
    rs = np.random.RandomState(7)
    B, T, pre = 2, 72, 8
    toks = rs.randint(0, 50, (B, T)).astype(np.int32)
    a32, _ = _teacher_forced_argmax(PARAMS, toks, pre, 80)
    a8, _ = _teacher_forced_argmax(quantize_lm_params(PARAMS, SPEC),
                                   toks, pre, 80)
    steps = a32.shape[1]
    assert steps >= 64
    agreement = float((a32 == a8).mean())
    assert agreement >= 0.99, "top-1 agreement %.4f" % agreement


def test_bf16_cache_decode_parity_ragged():
    """bf16 KV cache decode tracks the fp32-cache decode at relaxed
    tolerance — ragged prefill lengths included (each row prefills a
    different length, then decodes teacher-forced)."""
    rs = np.random.RandomState(9)
    B, T = 3, 20
    toks = rs.randint(0, 50, (B, T)).astype(np.int32)
    lens = np.asarray([4, 7, 5], np.int32)
    C = 24

    def run(cache_dtype):
        logits, ck, cv = prefill_apply(
            PARAMS, jnp.asarray(toks[:, :8]), jnp.asarray(lens), C,
            SPEC, cache_dtype=cache_dtype)
        assert str(ck.dtype) == cache_dtype
        first = np.asarray(logits)[np.arange(B), lens - 1]
        step = jax.jit(lambda p, k, v, t, l: decode_apply(p, k, v, t,
                                                          l, SPEC))
        rows = [first]
        ln = lens.copy()
        for t in range(8, T):
            lg, ck, cv = step(PARAMS, ck, cv, jnp.asarray(toks[:, t]),
                              jnp.asarray(ln))
            rows.append(np.asarray(lg))
            ln = ln + 1
        return np.stack(rows, 1)

    f32 = run("float32")
    b16 = run("bfloat16")
    # relaxed tol: bf16 has ~3 decimal digits; logits here are O(1)
    assert np.abs(f32 - b16).max() < 0.05
    assert np.argmax(f32, -1).tolist() == np.argmax(b16, -1).tolist()


def test_bf16_cache_bytes_halved():
    """The bf16 KV plane's memory claim, measured: init_cache /
    store.new_cache allocate half the bytes per slot, and the store
    reports its kv_dtype."""
    k32, v32 = init_cache(SPEC, 4, 16, "float32")
    k16, v16 = init_cache(SPEC, 4, 16, "bfloat16")
    assert k16.dtype == jnp.bfloat16
    bytes32 = k32.size * k32.dtype.itemsize
    bytes16 = k16.size * k16.dtype.itemsize
    assert bytes16 * 2 == bytes32
    store = GenerativeProgramStore(
        PARAMS, SPEC, batch_buckets=(2,), prompt_buckets=(8,),
        kv_block=8, kv_max=24, kv_dtype="bfloat16")
    ck, _ = store.new_cache(2, 16)
    assert ck.dtype == jnp.bfloat16
    st = store.stats()
    assert st["kv_dtype"] == "bfloat16"
    with pytest.raises(MXNetError):
        GenerativeProgramStore(PARAMS, SPEC, batch_buckets=(1,),
                               prompt_buckets=(8,), kv_block=8,
                               kv_max=16, kv_dtype="float16")


def test_lm_weight_bytes_4x():
    """int8 generative store: ~4x less resident weight memory than the
    fp32 store (matmul weights as codes+scales; norms/biases fp32).
    Measured at a realistic width — per-row scale + bias overhead is a
    fixed cost that the test-tier 32-wide model exaggerates."""
    spec = lm_spec(num_layers=2, num_hidden=128, num_heads=4,
                   vocab_size=256)
    params = random_params(spec, seed=5)
    kw = dict(batch_buckets=(1,), prompt_buckets=(8,), kv_block=8,
              kv_max=16)
    fp = GenerativeProgramStore(params, spec, **kw)
    q8 = GenerativeProgramStore(params, spec, compute_dtype="int8",
                                **kw)
    wfp = fp.stats()["weight_bytes"]
    wq8 = q8.stats()["weight_bytes"]
    assert wq8["by_dtype"].get("int8", 0) > 0
    assert wfp["total"] / wq8["total"] >= 3.8
    assert q8.stats()["compute_dtype"] == "int8"
    # bf16 store: half the weight bytes
    b16 = GenerativeProgramStore(params, spec,
                                 compute_dtype="bfloat16", **kw)
    assert wfp["total"] / b16.stats()["weight_bytes"]["total"] >= 1.9


# ---------------------------------------------------------------------------
# in-graph vs host sampling (engine level)
# ---------------------------------------------------------------------------
BB, PB, KVB, KVM = (2,), (8,), 8, 24


@pytest.fixture(scope="module")
def engines():
    """One in-graph-sampling engine and one host-hatch engine over the
    same weights (separate registries: the sample mode is a program
    property)."""
    out = {}
    for mode in ("graph", "host"):
        reg = ModelRegistry()
        reg.add_generative_model("m", PARAMS, SPEC, batch_buckets=BB,
                                 prompt_buckets=PB, kv_block=KVB,
                                 kv_max=KVM, warmup_kv_depth=KVM,
                                 sample=mode, paged=False)
        out[mode] = GenerationEngine(reg)
    yield out
    for eng in out.values():
        eng.close()


def _streams(engine, reqs):
    futs = [engine.submit("m", prompt, max_tokens=mt,
                          temperature=temp, top_k=tk, seed=seed)
            for prompt, mt, temp, tk, seed in reqs]
    return [f.result(120).tokens for f in futs]


def test_graph_vs_host_sampling_byte_identical(engines):
    """The parity pin: same seeds => same token streams, in-graph vs
    host sampling, greedy AND seeded temperature/top-k (the shared
    sample_tokens body runs in both places)."""
    rs = np.random.RandomState(11)
    reqs = []
    for i in range(6):
        prompt = list(rs.randint(0, 50, rs.randint(2, 8)))
        if i % 2 == 0:
            reqs.append((prompt, 12, 0.0, 0, 0))          # greedy
        else:
            reqs.append((prompt, 12, 0.8, 5, 100 + i))    # seeded
    graph = _streams(engines["graph"], reqs)
    host = _streams(engines["host"], reqs)
    assert graph == host
    # seeded requests actually sampled (not accidentally greedy)
    greedy = _streams(engines["graph"],
                      [(reqs[1][0], 12, 0.0, 0, 0)])
    assert greedy[0] != graph[1]


def test_graph_sampling_fetches_tokens_not_logits(engines):
    """THE acceptance pin: under in-graph sampling the decode loop's
    per-step host fetch is the (slots,) token vector — never the
    (slots, vocab) logits matrix the host hatch pulls."""
    vocab = SPEC["vocab_size"]
    for mode, per_slot in (("graph", 1), ("host", vocab)):
        eng = engines[mode]
        before = eng.stats()
        futs = [eng.submit("m", [3, 1, 4], max_tokens=6)
                for _ in range(2)]
        for f in futs:
            f.result(120)
        after = eng.stats()
        steps = after["decode_steps"] - before["decode_steps"]
        elems = after["decode_fetch_elems"] - \
            before["decode_fetch_elems"]
        assert steps > 0
        slots = max(BB)
        assert elems == steps * slots * per_slot, \
            ("%s mode fetched %d elems over %d steps (slots=%d, "
             "vocab=%d)" % (mode, elems, steps, slots, vocab))


def test_sample_mode_warm_sets_differ(engines):
    """Warmup compiles the configured decode kind: tokens-out programs
    for graph mode, logits-out for the host hatch (a hatch flip is a
    different program key — never a stale lowering)."""
    for mode, kind in (("graph", "decode_sample"), ("host", "decode")):
        st = engines[mode]._registry.gen_store("m").stats()
        assert st["sample_mode"] == mode
        kinds = {k for k, _b, _c in st["programs_resident"]}
        assert kind in kinds


def test_bf16_engine_cache_hwm_halved():
    """End-to-end bf16 decode: the engine's cache high-water stats
    carry the halved bytes-per-slot evidence (the '2x slots in the
    same budget' claim, introspectable)."""
    hwm = {}
    for tag, kv in (("fp32", "float32"), ("bf16", "bfloat16")):
        reg = ModelRegistry()
        reg.add_generative_model("m", PARAMS, SPEC, batch_buckets=BB,
                                 prompt_buckets=PB, kv_block=KVB,
                                 kv_max=KVM, kv_dtype=kv, paged=False)
        eng = GenerationEngine(reg)
        try:
            for f in [eng.submit("m", [5, 9, 2], max_tokens=6)
                      for _ in range(2)]:
                f.result(120)
            hwm[tag] = eng.stats()["cache_hwm"]["m"]
        finally:
            eng.close()
    assert hwm["bf16"]["cache_dtype"] == "bfloat16"
    assert hwm["bf16"]["cache_bytes_per_slot"] * 2 == \
        hwm["fp32"]["cache_bytes_per_slot"]


# ---------------------------------------------------------------------------
# paged pool x dtype (bf16 pool, int8 codes + scale pools)
# ---------------------------------------------------------------------------
PAGED_KW = dict(batch_buckets=(1,), prompt_buckets=(8,), kv_block=8,
                kv_max=40, paged=True, prefill_chunk=8, sample="graph")


def _paged_store(kv_dtype):
    return GenerativeProgramStore(PARAMS, SPEC, name="p" + kv_dtype,
                                  kv_dtype=kv_dtype, **PAGED_KW)


def _paged_greedy(st, prompt, steps):
    """Plain greedy paged decode at the store level: one prefill chunk
    then lq=1 sample steps; returns (stream, per-step argmax source
    logits row 0)."""
    scales = st.new_scale_pool() if st.kv_int8 else None
    pk, pv = st.new_pool()
    tables = np.zeros((1, st.table_width()), np.int32)
    need = -(-(len(prompt) + steps) // st.kv_block)
    tables[0, :need] = np.arange(1, need + 1)
    tables = jnp.asarray(tables)
    toks = np.zeros((1, st.prefill_chunk), np.int32)
    toks[0, :len(prompt)] = prompt
    out = st.run_paged_step(pk, pv, tables, jnp.asarray(toks),
                            jnp.zeros((1,), jnp.int32),
                            jnp.asarray([len(prompt)], jnp.int32),
                            scales=scales)
    if st.kv_int8:
        logits, pk, pv, *s = out
        scales = tuple(s)
    else:
        logits, pk, pv = out
    rows = [np.asarray(logits)[0]]
    stream = [int(np.argmax(rows[0]))]
    L = len(prompt)
    keys = jnp.zeros((1, 2), jnp.uint32)
    for _ in range(steps - 1):
        out = st.run_paged_step_sample(
            pk, pv, tables, jnp.asarray([[stream[-1]]], jnp.int32),
            jnp.asarray([L], jnp.int32), jnp.ones((1,), jnp.int32),
            keys, jnp.zeros((1,), jnp.float32),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool),
            scales=scales)
        if st.kv_int8:
            t, pk, pv, *s, keys = out
            scales = tuple(s)
        else:
            t, pk, pv, keys = out
        L += 1
        stream.append(int(np.asarray(t)[0]))
    return stream


def test_kv_dtype_reaches_paged_pool():
    """The pool allocation honors kv_dtype: bf16 pools are bf16 (half
    the bytes), int8 pools are int8 codes plus fp32 per-(block, head)
    scale pools initialized to ones; int8 KV is paged-plane-only."""
    b16 = _paged_store("bfloat16")
    pk, _pv = b16.new_pool()
    assert pk.dtype == jnp.bfloat16
    q8 = _paged_store("int8")
    ck, cv = q8.new_pool()
    assert ck.dtype == jnp.int8 and cv.dtype == jnp.int8
    assert q8.kv_int8
    sk, sv = q8.new_scale_pool()
    assert sk.dtype == jnp.float32
    assert sk.shape == (SPEC["num_layers"], SPEC["num_heads"],
                        q8.pool_blocks)
    assert np.array_equal(np.asarray(sk), np.ones(sk.shape))
    with pytest.raises(MXNetError):
        GenerativeProgramStore(PARAMS, SPEC, batch_buckets=(1,),
                               prompt_buckets=(8,), kv_block=8,
                               kv_max=24, paged=False, kv_dtype="int8")


def test_paged_bf16_and_int8_greedy_parity():
    """Paged pool dtype parity vs the fp32 pool on greedy streams: the
    bf16 pool is byte-identical here (logits O(1), 24 steps), and the
    int8 pool — a lossy codec — still agrees on >= 90% of greedy
    steps (the relaxed-tol discipline of the bf16 dense plane applied
    to codes+scales)."""
    prompt = [7, 3, 11, 29, 4]
    f32 = _paged_greedy(_paged_store("float32"), prompt, 24)
    b16 = _paged_greedy(_paged_store("bfloat16"), prompt, 24)
    q8 = _paged_greedy(_paged_store("int8"), prompt, 24)
    assert b16 == f32
    agree = np.mean([a == b for a, b in zip(q8, f32)])
    assert agree >= 0.9, (agree, q8, f32)


def test_paged_int8_kernel_matches_dense_twin(monkeypatch):
    """The int8 paged flash kernel dequantizes codes+scales on-tile to
    the same values the dense twin dequantizes on the host path —
    MXNET_PALLAS=2 and =0 greedy streams are identical (fp32
    accumulation both sides)."""
    prompt = [2, 5, 2, 5, 8]
    monkeypatch.setenv("MXNET_PALLAS", "0")
    twin = _paged_greedy(_paged_store("int8"), prompt, 12)
    monkeypatch.setenv("MXNET_PALLAS", "2")
    if pd.mode() == 0:
        pytest.skip("pallas interpret mode unavailable")
    kern = _paged_greedy(_paged_store("int8"), prompt, 12)
    assert kern == twin


def test_paged_dtype_pool_bytes_in_cache_state():
    """Engine-level memory evidence: stats()['cache_state'] reports
    dtype-aware pool bytes — bf16 halves fp32's bytes per token, int8
    (codes + scale pools) lands at <= 0.3x fp32."""
    bpt = {}
    for kv in ("float32", "bfloat16", "int8"):
        reg = ModelRegistry()
        reg.add_generative_model("m", PARAMS, SPEC, kv_dtype=kv,
                                 **PAGED_KW)
        eng = GenerationEngine(reg)
        try:
            futs = [eng.submit("m", [5, 9, 2, 7], max_tokens=6)
                    for _ in range(2)]
            for f in futs:
                f.result(120)
            cs = eng.stats()["cache_state"]["m"]
        finally:
            eng.close()
        assert cs["pool_bytes_used"] > 0
        assert cs["pool_bytes"] >= cs["pool_bytes_used"]
        bpt[kv] = cs["pool_bytes_per_token"]
        assert cs["cache_dtype"] == ("int8" if kv == "int8" else kv)
    assert bpt["bfloat16"] * 2 == bpt["float32"]
    assert bpt["int8"] <= 0.3 * bpt["float32"]
