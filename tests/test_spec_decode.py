"""Speculative decoding: the in-graph accept/reject rule (greedy =
longest-matching-prefix, seeded = rejection sampling with the
corrected-distribution resample, statistically pinned against the
target density), draft/verify program warm sets, engine-level greedy
byte-identity vs non-speculative decoding on the XLA path AND
MXNET_PALLAS=2, counters + acceptance evidence (target steps per token
<= 0.6x with a perfect draft), EOS/budget clamps, registry validation,
and the int8 paged KV plane riding the same pool update (the fallback
policy and gating are tests/test_spec_decode_policy.py's;
docs/architecture/decode_engine.md)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.pallas_ops.flash_attention import pltpu
from mxnet_tpu.serving import ModelRegistry
from mxnet_tpu.serving.program_store import (GenerativeProgramStore,
                                             _masked_dist, spec_verify)

from _spec_decode_common import (DPARAMS, DSPEC, KW, PARAMS, REQS, SPEC,
                                 _run, greedy_runs)


# ---------------------------------------------------------------------------
# the in-graph rule itself
# ---------------------------------------------------------------------------
def test_spec_verify_greedy_rule():
    """Greedy accept = longest argmax-matching prefix; the first
    mismatch emits the target's argmax; full accept adds the bonus."""
    V, K, B = 11, 3, 4
    rs = np.random.RandomState(0)
    logits = rs.randn(B, K + 1, V).astype(np.float32)
    am = np.argmax(logits, -1)              # am[b, j] follows prop j
    props = np.zeros((B, K), np.int32)
    props[0] = am[0, :K]                    # full accept
    props[1] = [(am[1, 0] + 1) % V, am[1, 1], am[1, 2]]  # reject at 0
    props[2] = [am[2, 0], (am[2, 1] + 1) % V, am[2, 2]]  # reject at 1
    props[3] = am[3, :K]                    # full match, but valid=2
    valid = np.asarray([K + 1, K + 1, K + 1, 2], np.int32)
    out, ne, _ = jax.jit(spec_verify)(
        jnp.asarray(logits), jnp.asarray(props),
        jnp.zeros((B, K, V), jnp.float32),
        jnp.zeros((B, 2), jnp.uint32), jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.asarray(valid))
    out, ne = np.asarray(out), np.asarray(ne)
    assert ne.tolist() == [K + 1, 1, 2, 2]
    assert out[0, :K + 1].tolist() == am[0].tolist()
    assert out[1, 0] == am[1, 0]
    assert out[2, :2].tolist() == [am[2, 0], am[2, 1]]
    # clamped window: one accepted proposal + its bonus, never past
    # valid
    assert out[3, :2].tolist() == [am[3, 0], am[3, 1]]


def test_spec_verify_seeded_matches_target_density():
    """The distribution pin: with proposals drawn from the draft
    density q, the verify's first emitted token follows the TARGET
    density p (accept + corrected-resample), and the token after an
    accepted proposal follows the next target row — total-variation
    distance under 3% at 16k trials."""
    V, K, B = 13, 3, 16384
    rs = np.random.RandomState(1)
    t_row = rs.randn(K + 1, V).astype(np.float32) * 1.5
    q_row = (t_row[:K] + rs.randn(K, V).astype(np.float32))
    ones = jnp.ones((K,), jnp.float32)
    zk = jnp.zeros((K,), jnp.int32)
    q_dist = np.asarray(_masked_dist(jnp.asarray(q_row), ones, zk))
    kk = jax.random.split(jax.random.PRNGKey(42), B + 1)
    keys, pk = kk[:B], kk[B]
    pkeys = jax.random.split(pk, B * K).reshape(B, K, 2)
    props = np.zeros((B, K), np.int32)
    for j in range(K):
        props[:, j] = np.asarray(jax.vmap(
            lambda k, _j=j: jax.random.categorical(
                k, jnp.log(jnp.asarray(q_dist[_j]) + 1e-30)))(
                    pkeys[:, j]))
    out, ne, _ = jax.jit(spec_verify)(
        jnp.asarray(np.broadcast_to(t_row, (B, K + 1, V))),
        jnp.asarray(props),
        jnp.asarray(np.broadcast_to(q_dist, (B, K, V))),
        keys, jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
        jnp.full((B,), K + 1, jnp.int32))
    out, ne = np.asarray(out), np.asarray(ne)
    p = np.asarray(_masked_dist(jnp.asarray(t_row),
                                jnp.ones((K + 1,)),
                                jnp.zeros((K + 1,), jnp.int32)))
    tv0 = 0.5 * np.abs(np.bincount(out[:, 0], minlength=V) / B
                       - p[0]).sum()
    assert tv0 < 0.03, tv0
    acc0 = (ne >= 2) & (out[:, 0] == props[:, 0])
    tv1 = 0.5 * np.abs(
        np.bincount(out[acc0, 1], minlength=V) / acc0.sum()
        - p[1]).sum()
    assert tv1 < 0.04, tv1
    # both accept and reject paths actually exercised
    hist = np.bincount(ne, minlength=K + 2)
    assert hist[1] > 0 and hist[K + 1] > 0


# ---------------------------------------------------------------------------
# store warm sets + registry validation
# ---------------------------------------------------------------------------
def test_warm_spec_programs_and_registry_validation():
    store = GenerativeProgramStore(
        PARAMS, SPEC, batch_buckets=(1,), prompt_buckets=(8,),
        kv_block=8, kv_max=24, paged=True, prefill_chunk=8,
        sample="graph")
    warm = store.warm_spec_programs(2, execute=False)
    assert set(warm) == {("paged_verify", 1, 3)}
    dwarm = store.warm_spec_programs(2, draft=True, execute=False)
    assert set(dwarm) == {("paged_step_sample_p", 1, 1),
                          ("paged_step", 1, 8)}
    contig = GenerativeProgramStore(
        PARAMS, SPEC, batch_buckets=(1,), prompt_buckets=(8,),
        kv_block=8, kv_max=24, paged=False)
    with pytest.raises(MXNetError):
        contig.warm_spec_programs(2)

    reg = ModelRegistry()
    reg.add_generative_model("c", PARAMS, SPEC, batch_buckets=(1,),
                             prompt_buckets=(8,), kv_block=8,
                             kv_max=24, paged=False, warmup=False)
    with pytest.raises(MXNetError):       # spec needs the paged plane
        reg.add_draft_model("c", DPARAMS, DSPEC)
    reg2 = ModelRegistry()
    reg2.add_generative_model("m", PARAMS, SPEC, warmup=False, **KW)
    with pytest.raises(MXNetError):
        reg2.add_draft_model("m", DPARAMS, DSPEC, spec_k=0)
    d = reg2.add_draft_model("m", DPARAMS, DSPEC, spec_k=2,
                             warmup=False)
    assert reg2.draft_store("m") is d and d.spec_k == 2
    assert d.kv_block == 8 and d.pool_blocks == \
        reg2.gen_store("m").pool_blocks
    with pytest.raises(MXNetError):       # one draft per target
        reg2.add_draft_model("m", DPARAMS, DSPEC, warmup=False)
    reg2.remove_model("m")
    assert reg2.draft_store("m") is None


# ---------------------------------------------------------------------------
# engine-level byte identity + acceptance evidence
# ---------------------------------------------------------------------------
def test_spec_greedy_byte_identical(greedy_runs):
    """THE pin: greedy speculative token streams are byte-identical to
    non-speculative — with a perfect draft AND with a random draft
    whose proposals mostly miss (speedup may vanish; correctness must
    not)."""
    base = greedy_runs["base"][0]
    assert greedy_runs["self"][0] == base
    assert greedy_runs["rand"][0] == base


def test_spec_counters_and_steps_per_token(greedy_runs):
    """A perfect (self) draft accepts every proposal and cuts target
    steps per emitted token under 0.6x the non-speculative engine on
    the same schedule; counters carry the evidence."""
    base = greedy_runs["base"][1]
    selfd = greedy_runs["self"][1]
    rand = greedy_runs["rand"][1]
    assert base["spec_steps"] == 0 and base["spec_proposed"] == 0
    assert selfd["spec_proposed"] > 0
    assert selfd["spec_accepted"] == selfd["spec_proposed"]
    assert selfd["decode_steps"] <= 0.6 * base["decode_steps"]
    assert selfd["generated_tokens"] == base["generated_tokens"]
    # graceful degradation: a bad draft still emits >= 1 token per
    # verify step (never slower than one target step per token)
    assert rand["decode_steps"] <= base["decode_steps"]
    assert rand["spec_draft_steps"] >= rand["spec_proposed"]
    d = selfd["models"]["m"]
    assert d["spec_k"] == 3 and d["draft_pool_bytes"] > 0


@pytest.mark.skipif(pltpu is None,
                    reason="pallas TPU backend module unavailable")
def test_spec_greedy_byte_identical_pallas2(monkeypatch):
    """Same pin through the interpret-mode Pallas kernels (the paged
    flash kernel verifies K+1 query rows in one grid)."""
    monkeypatch.setenv("MXNET_PALLAS", "2")
    reqs = [dict(tokens=[7, 3, 11, 29, 4], max_tokens=6, seed=1),
            dict(tokens=[2, 5], max_tokens=5, seed=2)]
    base, _ = _run(None, reqs=reqs)
    spec, st = _run("self", reqs=reqs, spec_k=2)
    assert spec == base
    assert st["spec_accepted"] == st["spec_proposed"] > 0


def test_spec_seeded_deterministic_and_budgeted():
    """Seeded speculative streams are a per-request function of the
    seed (batch composition and acceptance never leak across slots),
    and every stream respects max_tokens exactly like the
    non-speculative engine."""
    a, _ = _run("self", temp=0.8)
    b, _ = _run("self", temp=0.8)
    assert a == b
    for toks, kw in zip(a, REQS):
        assert len(toks) == kw["max_tokens"]


def test_spec_eos_mid_window():
    """An accepted draft token that hits eos_id finishes the request
    mid-window: the remaining accepted tokens are discarded and the
    stream ends at the eos token."""
    req = [dict(tokens=[7, 3, 11, 29, 4], max_tokens=12, seed=1)]
    free, _ = _run(None, reqs=req)
    eos = free[0][2]     # appears inside the greedy stream
    base, _ = _run(None, reqs=req, eos_id=eos)
    spec, _ = _run("self", reqs=req, eos_id=eos)
    assert spec[0] == base[0]
    assert spec[0][-1] == eos and len(spec[0]) < 12


def test_spec_int8_greedy_and_pool_bytes():
    """Speculative decoding over the int8 paged pool: greedy streams
    byte-identical to the int8 non-speculative engine, and the
    dtype-aware cache_state reports pool bytes per token <= 0.3x the
    fp32 plane (codes + per-block scales, the ~4x memory headline)."""
    base8, bst = _run(None, kv_dtype="int8")
    spec8, sst = _run("self", kv_dtype="int8")
    assert spec8 == base8
    assert sst["spec_accepted"] == sst["spec_proposed"] > 0
    _, fst = _run(None, reqs=REQS[:1])
    bpt8 = bst["cache_state"]["m"]["pool_bytes_per_token"]
    bpt32 = fst["cache_state"]["m"]["pool_bytes_per_token"]
    assert bst["cache_state"]["m"]["cache_dtype"] == "int8"
    assert bpt8 <= 0.3 * bpt32, (bpt8, bpt32)
