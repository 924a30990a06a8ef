"""Paged decode-plane kernels and steps: the block-table flash kernel vs
the dense gather twin (ragged offsets, partial blocks, shared blocks),
all pool heads of a block in one copy against one head a copy, the
in-place pool write against a row scatter, and a model's step over two
row groups against its two one-group steps
(docs/architecture/decode_engine.md; cases and helpers in
tests/_paged_common.py)."""
import functools

import numpy as np
import pytest

from mxnet_tpu.pallas_ops.flash_attention import pltpu

from _paged_common import (GROUP_ARCHS, HEADS_CASES, WRITE_CASES, _arch,
                           _paged_case)


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
