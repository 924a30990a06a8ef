"""Command A+ (``cohere2_moe``)'s model functions at toy sizes on the
CPU: the reference's gather against a dense loop, the window's kernel
against its twin, and the paged step under the kernels and under their
twins (its store and engine are tests/test_cohere2_moe_store.py's; its
cell, and the reference against the published classes,
tests/test_cohere2_moe_cell.py's)."""
import importlib.util

import numpy as np
import pytest

from mxnet_tpu.models import cohere2_moe as co

from _cohere2_moe_common import (BS, CHUNK, LOGIT_TOL, PARAMS, SPEC,
                                 SPEC_IN, T, ref)



def test_reference_gathers_what_a_dense_loop_sums(ref):
    """The reference's expert layer multiplies only the rows that
    picked an expert; a dense loop over every held expert under a mask
    gives the same sum, and a gather too small for an expert's rows
    takes the dense way for that expert and gives the same sum."""
    import jax
    import jax.numpy as jnp
    rs = np.random.RandomState(5)
    h = jnp.asarray(rs.randn(40, 64), jnp.float32)
    p = {k: jnp.asarray(v) for k, v in PARAMS.items()}
    with jax.default_matmul_precision("highest"):
        y, picked, w = ref.routed_experts(h, p, "l1_", SPEC_IN)
        want = jnp.zeros_like(h)
        for e in range(4):
            mine = jnp.sum(jnp.where(picked == e, w, 0.0), axis=-1)
            want = want + mine[:, None] * ref.gated(
                h, p["l1_e%d_gate_weight" % e], p["l1_e%d_up_weight" % e],
                p["l1_e%d_down_weight" % e])
        tight = ref.routed_experts(h, p, "l1_", SPEC_IN, capacity=0.1)[0]
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-5
    assert np.asarray(picked).shape == (40, 4)
    assert np.abs(np.asarray(tight) - np.asarray(want)).max() < 1e-5



# ---------------------------------------------------------------------------
# (c) the window's kernel = its twin = dense attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [None, 13, 16, 40])
@pytest.mark.parametrize("lq,positions", [(1, [5, 29, 47]),
                                          (8, [0, 19, 40])])
def test_window_kernel_matches_its_twin(window, lq, positions):
    """``flash_attention_paged`` under the interpreter against
    ``paged_attention_reference`` and against plain dense attention
    over the gathered rows, at 16 query heads of 128 a KV head (Command
    A+'s tile), two table entries a grid step: no window, a window that
    ends inside a block, one of whole blocks, and one wider than a
    context.  With a window, the table entries wholly behind it point
    at the trash block, which holds what no query may see."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops.paged_attention import (
        flash_attention_paged, paged_attention_reference)
    rs = np.random.RandomState(lq + (window or 0))
    B, Hp, heads, d, bs, nt, nb = 3, 2, 16, 128, 8, 6, 20
    H = Hp * heads
    q = rs.randn(B, H, lq, d).astype(np.float32)
    k_pool = rs.randn(2, Hp, nb * bs, d).astype(np.float32)
    v_pool = rs.randn(2, Hp, nb * bs, d).astype(np.float32)
    k_pool[:, :, :bs] = 1e4             # the trash block: poison
    v_pool[:, :, :bs] = 1e4
    tables = rs.permutation(np.arange(1, nb))[:B * nt].reshape(B, nt)
    whole = tables.copy()
    if window is not None:
        for b in range(B):
            tables[b, :max(0, (positions[b] - window + 1) // bs)] = 0
    pos = jnp.asarray(positions, jnp.int32)
    args = (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool), 1,
            jnp.asarray(tables, jnp.int32), pos, bs)
    got = np.asarray(flash_attention_paged(
        *args, scale=d ** -0.5, interpret=True, group=2, window=window,
        name="window_paged_attention"))
    if window is None or window >= 40 + lq:
        twin = np.asarray(paged_attention_reference(
            *args, scale=d ** -0.5, window=window))
        assert np.abs(got - twin).max() < 2e-5
    idx = (whole[:, :, None] * bs + np.arange(bs)).reshape(B, nt * bs)
    for b in range(B):
        for h in range(0, H, 5):
            keys = k_pool[1, h // heads, idx[b]]
            vals = v_pool[1, h // heads, idx[b]]
            for r in range(lq):
                hi = positions[b] + r + 1
                lo = 0 if window is None else max(0, hi - window)
                s = keys[lo:hi] @ q[b, h, r] * d ** -0.5
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ vals[lo:hi]
                assert np.abs(got[b, h, r] - want).max() < 2e-5


@pytest.mark.parametrize("heads,d", [(1, 128), (4, 64), (16, 128)])
def test_no_window_is_the_kernel_it_was(heads, d):
    """``window=None`` adds nothing to the kernel: a window wider than
    any context is BIT-equal to it (the mask is all true, the walk the
    whole table), as are the twins; and the call's default name is the
    one ``kernel.paged_attn_*`` and ``kernel.gqa_attn_*`` read."""
    import inspect
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops.paged_attention import (
        flash_attention_paged, paged_attention_reference)
    rs = np.random.RandomState(heads)
    B, Hp, bs, nt, nb, lq = 2, 2, 8, 4, 10, 8
    q = jnp.asarray(rs.randn(B, Hp * heads, lq, d), jnp.float32)
    k_pool = jnp.asarray(rs.randn(1, Hp, nb * bs, d), jnp.float32)
    v_pool = jnp.asarray(rs.randn(1, Hp, nb * bs, d), jnp.float32)
    tables = jnp.asarray(rs.permutation(np.arange(1, nb))[:B * nt]
                         .reshape(B, nt), jnp.int32)
    args = (q, k_pool, v_pool, 0, tables, jnp.asarray([3, 17]), bs)
    plain = np.asarray(flash_attention_paged(
        *args, scale=0.3, interpret=True, group=2))
    wide = np.asarray(flash_attention_paged(
        *args, scale=0.3, interpret=True, group=2, window=nt * bs))
    assert np.array_equal(plain, wide)
    assert np.array_equal(
        np.asarray(paged_attention_reference(*args, scale=0.3)),
        np.asarray(paged_attention_reference(*args, scale=0.3,
                                             window=nt * bs)))
    sig = inspect.signature(flash_attention_paged).parameters
    assert sig["window"].default is None
    assert sig["name"].default == "paged_attention"
    assert not co.WINDOW_KERNEL.startswith("paged_attention")


_DEFAULT_LOWERING = []     # test_paged_step_same_...: its run, once


@pytest.mark.parametrize("mode", ["0", "2"])
def test_paged_step_same_under_kernels_and_twins(monkeypatch, mode):
    """One chunk and one decode step of the whole model, a row past its
    window, under ``MXNET_PALLAS=0`` (the twins, with the same window)
    and ``=2`` (the kernels, interpreted) agree with the default
    lowering."""
    import jax.numpy as jnp

    def run():
        packed = co.pack_params(
            {k: jnp.asarray(v) for k, v in PARAMS.items()}, SPEC)
        pools = co.init_pool(SPEC, 12, BS)
        tables = np.zeros((2, 2 * T), np.int32)
        tables[0, :4], tables[0, T:T + 4] = [1, 2, 3, 4], [5, 6, 7, 8]
        tables[1, :2], tables[1, T:T + 2] = [5, 6], [1, 2]
        rs = np.random.RandomState(9)
        out, pos = [], np.zeros(2, np.int32)
        for at in range(0, 24, CHUNK):      # row 0 to 24, row 1 to 8
            toks = rs.randint(0, 96, (2, CHUNK))
            val = np.asarray([CHUNK, CHUNK if at == 0 else 0])
            logits, pools, counts = co.paged_step_apply(
                packed, pools, tables if at == 0 else tables * [[1], [0]],
                toks, pos * [1, at == 0], np.maximum(val, 1), SPEC, BS)
            out.append(np.asarray(logits)[:1 + (at == 0)])
            pos = pos + val
        logits, pools, counts = co.paged_step_apply(
            packed, pools, tables, rs.randint(0, 96, (2, 1)), pos,
            np.asarray([1, 1]), SPEC, BS)
        return out + [np.asarray(logits)], np.asarray(counts)

    # the default lowering's run is the same for both modes: once a file
    if not _DEFAULT_LOWERING:
        _DEFAULT_LOWERING.append(run())
    want, want_counts = _DEFAULT_LOWERING[0]
    monkeypatch.setenv("MXNET_PALLAS", mode)
    got, counts = run()
    for a, b in zip(got, want):
        assert np.abs(a - b).max() < LOGIT_TOL
    assert np.array_equal(counts, want_counts) and counts[0] == 4 * 2


@pytest.mark.parametrize("program,lq", [("cohere2_moe", 1),
                                        ("cohere2_moe", CHUNK),
                                        ("transformer_lm", 1)])
def test_step_programs_route_by_heads_a_copy(monkeypatch, program, lq):
    """The route inside the paged kernel, as ``dispatch_stats()`` tells
    it at trace time: this model's decode and chunk programs bring ALL
    pool heads of a block in with one copy (``heads_per_copy=<pool
    heads>``, once a layer, window or full), ``transformer_lm``'s, one
    query head a pool head, one head a copy."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import dispatch
    monkeypatch.setenv("MXNET_PALLAS", "2")
    toks = np.zeros((2, lq), np.int32)
    pos, val = np.asarray([5, 8]), np.asarray([lq, lq])
    if program == "cohere2_moe":
        packed = co.pack_params(
            {k: jnp.asarray(v) for k, v in PARAMS.items()}, SPEC)
        pools = co.init_pool(SPEC, 12, BS)
        tables = np.zeros((2, 2 * T), np.int32)
        tables[:, :2], tables[:, T:T + 2] = [[1, 2], [3, 4]], [[5, 6],
                                                               [7, 8]]
        step = lambda: co.paged_step_apply(
            packed, pools, tables, toks, pos, val, SPEC, BS)
        want = {"DotProductAttentionPaged": 4,
                "DotProductAttentionPaged.heads_per_copy=2": 4}
    else:
        lm = importlib.import_module("mxnet_tpu.models.transformer_lm")
        spec = lm.lm_spec(num_layers=2, num_hidden=32, num_heads=4,
                          vocab_size=50)
        params = lm.random_params(spec, seed=3)
        pools = lm.init_pool(spec, 6, BS)
        tables = np.asarray([[1, 2, 0], [3, 4, 5]], np.int32)
        step = lambda: lm.paged_step_apply(
            params, *pools, tables, toks, pos, val, spec, BS)
        want = {"DotProductAttentionPaged": 2,
                "DotProductAttentionPaged.heads_per_copy=1": 2}
    dispatch.reset_dispatch_stats()
    jax.eval_shape(step)
    got = {k: v for k, v in dispatch.dispatch_stats().items()
           if k.startswith("DotProductAttentionPaged")}
    assert got == want
