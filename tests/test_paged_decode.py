"""Paged decode-plane tests: block-table flash kernel vs the dense
gather twin (ragged offsets, partial blocks, shared blocks), the paged
GenerationEngine vs the contiguous plane (greedy AND seeded sampling),
copy-on-write prefix sharing under divergence, chunked-vs-unchunked
prefill equality, pool exhaustion throttling, the MXNET_PALLAS=0 /
paged=False escape hatches and paged telemetry
(docs/architecture/decode_engine.md).
"""
import functools

import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer_lm import lm_spec, random_params
from mxnet_tpu.pallas_ops.flash_attention import pltpu
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


@pytest.mark.skipif(pltpu is None,
                    reason="pallas TPU backend module unavailable")
@pytest.mark.parametrize("layer", [0, 2], ids=["layer0", "last-layer"])
@pytest.mark.parametrize("seed,lq,positions", [(0, 1, [5, 9, 17]),
                                               (1, 4, [0, 3, 12]),
                                               (2, 8, [8, 1, 15])],
                         ids=["decode", "chunk4", "chunk8"])
def test_paged_kernel_matches_dense_twin(seed, lq, positions, layer):
    """flash_attention_paged (interpret mode) vs the gather-based dense
    twin, both through the (whole pool, layer) door: ragged
    per-sequence offsets, partial last blocks, shared physical blocks,
    decode (lq=1) and chunk (lq=4, 8) query lengths, the first and the
    last layer of a three-layer stack — and the twin on the stack
    equals the twin on that layer alone, bit for bit."""
    from mxnet_tpu.pallas_ops.paged_attention import (
        flash_attention_paged, paged_attention_reference)

    q, kp, vp, tbl, pos = _paged_case(
        seed, B=3, H=2, T=4, D=8, bs=8, num_blocks=12,
        positions=positions, lq=lq, layers=3)
    got = np.asarray(flash_attention_paged(
        q, kp, vp, layer, tbl, pos, 8, block_q=4, interpret=True))
    want = np.asarray(paged_attention_reference(
        q, kp, vp, layer, tbl, pos, 8))
    assert np.abs(got - want).max() < 2e-6
    alone = np.asarray(paged_attention_reference(
        q, kp[layer:layer + 1], vp[layer:layer + 1], 0, tbl, pos, 8))
    assert np.array_equal(want, alone)


def test_paged_reference_matches_contiguous_dense():
    """The gather twin against THIS repo's oracle of record: gather the
    pool rows in numpy, then the contiguous dense offset-causal
    attention must agree — the table arithmetic adds nothing."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.attention import _dense_attention
    from mxnet_tpu.pallas_ops.paged_attention import (
        paged_attention_reference)

    q, kp, vp, tbl, pos = _paged_case(
        3, B=2, H=2, T=3, D=8, bs=8, num_blocks=8,
        positions=[6, 13], lq=2)
    got = np.asarray(paged_attention_reference(q, kp, vp, 0, tbl, pos,
                                               8))
    idx = (np.asarray(tbl)[:, :, None] * 8 +
           np.arange(8)[None, None, :]).reshape(2, -1)
    k = jnp.asarray(np.asarray(kp)[0][:, idx].transpose(1, 0, 2, 3))
    v = jnp.asarray(np.asarray(vp)[0][:, idx].transpose(1, 0, 2, 3))
    want = np.asarray(_dense_attention(
        q, k, v, True, 1.0 / 8 ** 0.5,
        q_offsets=np.asarray(pos)))
    assert np.abs(got - want).max() < 2e-6


def test_paged_kernel_ignores_trash_and_junk_blocks():
    """Junk planted in the trash block AND in pool blocks no table
    references must not perturb the output (masking is in logical
    position space; unused table entries point at block 0)."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops.paged_attention import (
        paged_attention_reference)

    q, kp, vp, tbl, pos = _paged_case(
        4, B=2, H=2, T=3, D=8, bs=8, num_blocks=8,
        positions=[4, 10], lq=1)
    base = np.asarray(paged_attention_reference(q, kp, vp, 0, tbl, pos,
                                                8))
    kj, vj = np.asarray(kp).copy(), np.asarray(vp).copy()
    used = set(np.asarray(tbl).ravel()) - {0}
    for blk in set(range(8)) - used:  # trash block 0 + unreferenced
        kj[:, :, blk * 8:(blk + 1) * 8] = 1e4
        vj[:, :, blk * 8:(blk + 1) * 8] = -1e4
    got = np.asarray(paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kj), jnp.asarray(vj), 0, tbl, pos,
        8))
    assert np.abs(got - base).max() < 2e-6


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


@pytest.mark.skipif(pltpu is None,
                    reason="pallas TPU backend module unavailable")
@pytest.mark.parametrize("heads,lq,block_q,positions,T,kw,pool",
                         [c[1:] for c in HEADS_CASES],
                         ids=[c[0] for c in HEADS_CASES])
def test_all_pool_heads_a_copy_equals_one_head_a_copy(
        monkeypatch, heads, lq, block_q, positions, T, kw, pool):
    """The grid that brings ALL pool heads of a block in with one copy
    (``hb = Hp``: what a grouped-query call gets) against the grid of
    one head a copy (``hb = 1``: the kernel as it was), interpreted:
    BIT-equal, head for head, and both within the twin's tolerance of
    ``paged_attention_reference``."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import paged_attention as pa
    from mxnet_tpu.test_utils import pallas_calls

    rs = np.random.RandomState(len(positions) + heads + lq + T)
    B, Hp, d, bs, nb = 3, 2, 16, 16, 40
    window = kw.get("window")
    fused, int8 = pool == "k|v", pool == "int8"
    w = 2 * d if fused else d
    q = rs.randn(B, Hp * heads, lq, d).astype(np.float32)
    if fused:
        q = np.concatenate([q, np.zeros_like(q)], -1)
    if int8:
        k_pool, v_pool = (rs.randint(-127, 128, (2, Hp, nb * bs, w))
                          .astype(np.int8) for _ in range(2))
        scales = tuple(jnp.asarray(rs.rand(2, Hp, nb) * 0.02 + 0.001,
                                   jnp.float32) for _ in range(2))
    else:
        k_pool = rs.randn(2, Hp, nb * bs, w).astype(np.float32)
        v_pool = None if fused else rs.randn(
            2, Hp, nb * bs, w).astype(np.float32)
        k_pool[:, :, :bs] = 1e4         # the trash block: poison
        scales = None
    tables = np.zeros((B, T), np.int32)
    for b in range(B):
        live = -(-(positions[b] + lq) // bs)
        tables[b, :live] = rs.permutation(np.arange(1, nb))[:live]
        if window is not None:          # released behind the window
            tables[b, :max(0, (positions[b] - window + 1) // bs)] = 0
    args = (jnp.asarray(q), jnp.asarray(k_pool),
            None if v_pool is None else jnp.asarray(v_pool), 1,
            jnp.asarray(tables), jnp.asarray(positions, jnp.int32), bs)
    call = functools.partial(pa.flash_attention_paged, *args,
                             block_q=block_q, interpret=True,
                             kv_scales=scales, **kw)
    if pool == "vmem":
        # a budget that holds four entries a step of this shape, not 16
        shape = (Hp, bs, w, heads * lq, 4, 4, True)
        monkeypatch.setattr(pa, "_VMEM_BUDGET",
                            pa.vmem_bytes(4, *shape))
        assert pa.fit_group(T, 16, *shape) == 4
    # (a fresh function a trace: jax keeps a function's trace)
    (_, grid, blocks, _), = pallas_calls(lambda: call())
    all_heads = np.asarray(call())
    with monkeypatch.context() as m:
        m.setattr(pa, "heads_per_copy", lambda heads, hp: 1)
        (_, grid1, blocks1, _), = pallas_calls(lambda: call())
        one = np.asarray(call())
    tile = heads * lq if heads * lq <= block_q else block_q
    group = 4 if pool == "vmem" else kw["group"]
    assert blocks1[1] == (1, 1, bs, w) and blocks[1] == (1, Hp, bs, w)
    assert blocks[0] == (1, Hp, tile, w) == blocks[-1]
    assert len(blocks) == 2 + group * (1 if fused else 2)
    assert grid1 == (B, Hp) + grid[2:] and grid[1] == 1
    assert grid[2] == heads * lq // tile
    assert np.array_equal(all_heads, one)
    twin = np.asarray(pa.paged_attention_reference(
        *args, kv_scales=scales, window=window))
    assert np.abs(all_heads - twin).max() < 2e-5


@pytest.mark.skipif(pltpu is None,
                    reason="pallas TPU backend module unavailable")
@pytest.mark.parametrize("lq,grid", [(1, (4, 16, 1, 16)),
                                     (32, (4, 16, 1, 16))],
                         ids=["decode", "chunk32"])
def test_one_query_head_a_pool_head_keeps_the_grid_it_had(lq, grid):
    """``heads == 1`` (``lm2048``: 16 query heads on 16 pool heads):
    the grid ``(B, Hp, rows // block_q, nk)``, a copy of ONE head's
    block ``(1, 1, bs, D)``, ``group`` of them for K and as many for
    V, no VMEM limit asked for: the kernel as it was, to the
    operand."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import paged_attention as pa
    from mxnet_tpu.test_utils import pallas_calls

    pool = jnp.zeros((2, 16, 65 * 64, 128), jnp.float32)
    (name, got, blocks, limit), = pallas_calls(
        lambda q, t, p: pa.flash_attention_paged(
            q, pool, pool, 1, t, p, 64, interpret=True),
        jnp.zeros((4, 16, lq, 128)), jnp.zeros((4, 16), jnp.int32),
        jnp.zeros((4,), jnp.int32))
    assert name == "paged_attention" and got == grid and limit is None
    assert blocks == [(1, 1, lq, 128), (1, 1, 64, 128), (1, 1, 64, 128),
                      (1, 1, lq, 128)]
    assert pa.heads_per_copy(1, 16) == 1 and pa.heads_per_copy(4, 8) == 8


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


@pytest.mark.parametrize("layer", [0, 2], ids=["layer0", "last-layer"])
@pytest.mark.parametrize("lq,positions,valid,tables",
                         [c[1:] for c in WRITE_CASES],
                         ids=[c[0] for c in WRITE_CASES])
def test_pool_write_matches_row_scatter(lq, positions, valid, tables,
                                        layer):
    """``_pool_write`` (dynamic_update_slices in a loop, in place)
    against what the step graph did before: ``pool.at[layer, :, dest,
    :].set(rows)`` with pad rows sent to the trash block.  Every block
    but the trash block is bit-equal, in every layer; the blocks two
    tables share, and every block no table's write reaches, are
    bit-equal to what they held before the step; what the old write
    sent to the trash block (pad rows, an all-zero table's rows) is now
    written nowhere."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.transformer_lm import _pool_write, _write_plan

    B, H, dh, bs, blocks, layers = 3, 2, 4, 8, 10, 3
    rs = np.random.RandomState(lq)
    pools = [jnp.asarray(rs.randn(layers, H, blocks * bs, dh)
                         .astype(np.float32)) for _ in range(2)]
    fresh = [jnp.asarray(rs.randn(B, H, lq, dh).astype(np.float32))
             for _ in range(2)]
    tbl = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray(positions, jnp.int32)
    val = jnp.asarray(valid, jnp.int32)

    r = np.arange(lq)
    p = np.asarray(positions)[:, None] + r[None, :]
    # a pad row may lie past the table's width: jnp's gather clamps
    col = np.minimum(p // bs, len(tables[0]) - 1)
    dest = np.asarray(tables)[np.arange(B)[:, None], col] * bs + p % bs
    real = r[None, :] < np.asarray(valid)[:, None]
    dest = np.where(real, dest, p % bs).reshape(-1)
    want = [np.asarray(pool.at[layer, :, dest, :].set(
        jnp.transpose(f, (0, 2, 1, 3)).reshape(B * lq, H, dh)))
        for pool, f in zip(pools, fresh)]

    got = jax.jit(lambda pk, pv, k, v: _pool_write(
        pk, pv, layer, k, v, _write_plan(tbl, pos, val, lq, bs),
        bs))(*pools, *fresh)
    written = set((dest[real.reshape(-1)] // bs).tolist())
    for g, w, before in zip(got, want, pools):
        g, before = np.asarray(g), np.asarray(before)
        assert np.array_equal(g[:, :, bs:], w[:, :, bs:])
        untouched = [0] + [b for b in range(blocks) if b not in written]
        for b in untouched:
            assert np.array_equal(g[:, :, b * bs:(b + 1) * bs],
                                  before[:, :, b * bs:(b + 1) * bs]), b


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


@pytest.mark.parametrize("arch", GROUP_ARCHS)
def test_a_step_over_two_groups_is_the_two_steps(arch):
    """``paged_step_groups`` over a decode group (two sequences with 20
    and 9 tokens behind them, the first past ``cohere2_moe``'s window,
    and a dead row) and a chunk group (a fresh sequence, one in its
    second chunk with a ragged end, which reads ``lfm2_moe``'s state
    row, and a dead row) gives, group by group, the logits and, leaf
    by leaf, the pool of the two one-group steps in that order, bit
    for bit; the counters that add up are their sum, the expert steps
    count ONE pass a layer, and an expert both groups touch is touched
    once."""
    import jax
    mod, spec = _arch(arch)
    bs, T = 8, 6
    classes = len(mod.cache_classes(spec)) \
        if hasattr(mod, "cache_classes") else 1
    params = {k: jax.numpy.asarray(v) for k, v in mod.pack_params(
        mod.random_params(spec, seed=5), spec).items()}
    rs = np.random.RandomState(4)
    draw = lambda *shape: rs.randint(  # noqa: E731
        0, spec["vocab_size"], shape).astype(np.int32)

    def table(*blocks):
        row = np.zeros(T, np.int32)
        row[:len(blocks)] = blocks
        return np.tile(row, classes)

    a, b, c, d = table(1, 2, 3), table(4, 5), table(6), table(7, 8)
    dead = table()
    one = jax.jit(lambda pools, *group: mod.paged_step(
        params, pools, *group, spec, bs))
    two = jax.jit(lambda pools, *groups: mod.paged_step_groups(
        params, pools, groups, spec, bs))

    # what the decode rows and the second chunk have behind them
    pools = mod.init_pool(spec, 9, bs)
    for pos, valid in ((0, [8, 8, 8]), (8, [8, 1, 1]), (16, [4, 1, 1])):
        rows = np.stack([a, b if pos < 16 else dead,
                         d if pos < 8 else dead])
        _, pools, _ = one(pools, rows, draw(3, 8),
                          np.full(3, pos, np.int32),
                          np.asarray(valid, np.int32))
    decode = (np.stack([a, dead, b]), draw(3, 1),
              np.array([20, 0, 9], np.int32), np.ones(3, np.int32))
    chunk = (np.stack([c, dead, d]), draw(3, 8),
             np.array([0, 0, 8], np.int32), np.array([8, 1, 5], np.int32))

    want_d, mid, counts_d = one(pools, *decode)
    want_c, want_pools, counts_c = one(mid, *chunk)
    (got_d, got_c), got_pools, counts = two(pools, decode, chunk)
    assert np.array_equal(np.asarray(got_d), np.asarray(want_d))
    assert np.array_equal(np.asarray(got_c), np.asarray(want_c))
    assert len(got_pools) == len(want_pools)
    for got, want in zip(got_pools, want_pools):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    counts, apart = np.asarray(counts), np.asarray(
        [counts_d, counts_c])
    names = list(mod.AUX_COUNTERS)
    for name in ("moe_tokens", "moe_local_assignments"):
        k = names.index(name)
        assert counts[k] == apart[:, k].sum() > 0
    k = names.index("moe_expert_steps")
    assert counts[k] == apart[0, k] == apart[1, k] > 0
    for name in ("moe_expert_load_max", "moe_experts_touched"):
        k = names.index(name)
        assert apart[:, k].max() <= counts[k] <= apart[:, k].sum()
    # ONE group through the same function is the seam's own step
    (alone,), _, _ = two(pools, decode)
    assert np.array_equal(np.asarray(alone), np.asarray(want_d))


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
