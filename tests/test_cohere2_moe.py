"""Command A+ (``cohere2_moe``) on the serving plane, at toy sizes on the
CPU: the plain reference against the published classes of the installed
``transformers`` (the dense family's), the paged programs over TWO
classes of cache block against the reference's full forward across a
toy window, the window's kernel against its twin, prefix hits and
copy-on-write forks beyond the window against a cold run, a hit whose
window is no longer whole, the blocks' return at retirement, the eight
shares of the expert layer, and the seam
(docs/architecture/decode_engine.md, "Classes of block").
"""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.models import cohere2_moe as co
from mxnet_tpu.serving import GenerationEngine, ModelRegistry
from mxnet_tpu.serving.decode_engine import _BlockPool, _PrefixStore
from mxnet_tpu.serving.program_store import GenerativeProgramStore

# the benchmark's own tests of this configuration (the costs of the
# published widths, the cell's rehearsal, a window dropped under it)
# run in tier-1 from where they live
pytest.register_assert_rewrite("benchmark.tests.test_command_a_plus")
from benchmark.tests.test_command_a_plus import *  # noqa: E402,F401,F403

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WINDOW = 16
SPEC_IN = {
    "arch": "cohere2_moe", "num_hidden_layers": 4,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 32, "num_experts": 4,
    "router_width": 16, "num_experts_per_tok": 4,
    "num_shared_experts": 2, "sliding_window": WINDOW, "vocab_size": 96,
    "layer_norm_eps": 1e-5, "rope_theta": 50000.0, "logit_scale": 0.5}
SPEC = co.serving_spec(SPEC_IN)
CFG = {"spec": SPEC_IN}
PARAMS = co.random_params(SPEC, seed=11)
BS, CHUNK, KV_MAX = 8, 8, 64
T = KV_MAX // BS                # table entries a class
# Program against reference in float32 on the CPU: the same products
# associated differently (an online softmax over the window's groups of
# blocks against a whole one, a grouped product against a gather, one
# gated unit of twice the width against two); logits are of order 1
# and readings were 2e-6 .. 6e-6.
LOGIT_TOL = 1e-4
STORE_KW = dict(batch_buckets=(2,), prompt_buckets=(8,), kv_block=BS,
                kv_max=KV_MAX, paged=True, prefill_chunk=CHUNK,
                sample="graph")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (imports nothing of the
    program), loaded by path."""
    return _load("command_a_plus_reference", os.path.join(
        ROOT, "benchmark", "reference", "command-a-plus.py"))


_REF_FN = {}


def _ref_logits(ref, tokens):
    """The reference's logits at every position of ``tokens``, computed
    over the sequence padded to KV_MAX (it is causal: a position's
    logits do not depend on what follows), so every call is one
    compiled program."""
    import jax
    import jax.numpy as jnp
    if "fn" not in _REF_FN:
        params = {k: jnp.asarray(v) for k, v in PARAMS.items()}
        fn = jax.jit(lambda t: ref.logits(params, t, CFG))
        _REF_FN["fn"] = fn
    seq = np.zeros(KV_MAX, np.int32)
    seq[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF_FN["fn"](jnp.asarray(seq)))[:len(tokens)]


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="co",
                                  **args)


class _Rows:
    """Two sequences over one pool, stepped through the store's
    logits-out program: a table a CLASS for each (class 0 the full
    layer's, class 1 the window layers'), side by side in a row."""

    def __init__(self):
        self.st = _store(pool_blocks=24)
        self.pools = self.st.new_pool()
        self.tables = np.zeros((2, self.st.table_width()), np.int32)

    def give(self, row, full, window=None):
        """Row ``row``'s blocks in the full class, and (by default the
        same numbers, which name other memory) in the window class."""
        window = full if window is None else window
        self.tables[row, :len(full)] = full
        self.tables[row, T:T + len(window)] = window

    def release(self, row, pos):
        """What the engine does as ``row``'s next query sits at
        ``pos``: the window class's entries wholly behind the window
        go to the trash block 0."""
        first = max(0, (pos - WINDOW + 1) // BS)
        self.tables[row, T:T + first] = 0

    def step(self, tokens, pos, rows=(0, 1)):
        """``tokens[r]`` at ``pos[r]`` for the rows in ``rows``; the
        others ride outside the dispatch.  Returns the logits."""
        lq = 1 if max(len(t) for t in tokens) == 1 else CHUNK
        toks = np.zeros((2, lq), np.int32)
        tables = np.zeros_like(self.tables)
        p, v = np.zeros(2, np.int32), np.ones(2, np.int32)
        for r, t, at in zip(rows, tokens, pos):
            toks[r, :len(t)] = t
            tables[r], p[r], v[r] = self.tables[r], at, len(t)
        logits, *self.pools = self.st.run_paged_step(
            *self.pools, tables, toks, p, v)
        return np.asarray(logits)

    def prefill(self, row, seq, start=0, release=False):
        """``seq[start:]`` in chunks; the last chunk's logits."""
        out = None
        for at in range(start, len(seq), CHUNK):
            if release:
                self.release(row, at)
            out = self.step([seq[at:at + CHUNK]], [at], rows=(row,))[row]
        return out


# ---------------------------------------------------------------------------
# (a) the reference = the published classes (the dense family's)
# ---------------------------------------------------------------------------
# float32 on both sides, the same equations in another order of
# summation: readings 1e-7 .. 2e-6 on values of order 1
HF_TOL = 1e-5


def _hf():
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.cohere2.modeling_cohere2")
    from transformers.models.cohere2.configuration_cohere2 import \
        Cohere2Config
    config = Cohere2Config(
        vocab_size=96, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        head_dim=8, layer_norm_eps=1e-5, rope_theta=50000.0,
        sliding_window=5, logit_scale=0.5, attention_bias=False,
        layer_types=["sliding_attention", "full_attention"])
    config._attn_implementation = "eager"
    return torch, modeling, config


def _seed_module(torch, module, seed):
    gen = torch.Generator().manual_seed(seed)
    with torch.no_grad():
        for p in module.parameters():
            p.copy_(torch.randn(p.shape, generator=gen) * 0.3
                    + (1.0 if p.ndim == 1 else 0.0))
    return {k: v.detach().numpy() for k, v in module.named_parameters()}


@pytest.mark.parametrize("part", [
    "norm", "sliding_attention", "full_attention",
    "parallel_block_sliding", "parallel_block_full", "tied_head"])
def test_reference_equals_transformers_cohere2(ref, part):
    """The reference's mean-subtracting norm, its attention under the
    window's mask with interleaved rotary and under the causal mask
    with none, the parallel block (attention and the feed-forward read
    the same normed rows) and the tied head times ``logit_scale``,
    against ``Cohere2LayerNorm``, ``Cohere2Attention``,
    ``Cohere2DecoderLayer`` and ``Cohere2ForCausalLM`` on seeded
    weights, at a window of 5 under 13 tokens."""
    import jax
    import jax.numpy as jnp
    torch, modeling, config = _hf()
    n, w = 13, 5
    x = np.random.RandomState(3).randn(1, n, 64).astype(np.float32)
    xt = torch.from_numpy(x)
    pos = torch.arange(n)[None]
    cos_sin = modeling.Cohere2RotaryEmbedding(config)(xt, pos)
    q, k = torch.arange(n)[:, None], torch.arange(n)[None, :]
    causal = torch.zeros(n, n).masked_fill(k > q, float("-inf"))
    masks = {"full_attention": causal[None, None],
             "sliding_attention": causal.masked_fill(
                 k <= q - w, float("-inf"))[None, None]}
    s = dict(SPEC_IN, layer_types=["sliding_attention", "full_attention"],
             num_hidden_layers=2, sliding_window=w)
    jx = jnp.asarray(x[0])

    def attn_leaves(wts, b, pre=""):
        return {b + "q_weight": wts[pre + "q_proj.weight"],
                b + "k_weight": wts[pre + "k_proj.weight"],
                b + "v_weight": wts[pre + "v_proj.weight"],
                b + "o_weight": wts[pre + "o_proj.weight"]}

    with torch.no_grad(), jax.default_matmul_precision("highest"):
        if part == "norm":
            mod = modeling.Cohere2LayerNorm(64, eps=1e-5)
            wts = _seed_module(torch, mod, 1)
            want = mod(xt).numpy()[0]
            got = ref.layer_norm(jx, jnp.asarray(wts["weight"]), 1e-5)
        elif part.endswith("attention"):
            i = 0 if part == "sliding_attention" else 1
            mod = modeling.Cohere2Attention(config, i)
            wts = _seed_module(torch, mod, 2 + i)
            want = mod(xt, cos_sin, masks[part])[0].numpy()[0]
            b = "l%d_" % i
            got = ref.attention(jx, {k_: jnp.asarray(v) for k_, v in
                                     attn_leaves(wts, b).items()}, b, s,
                                part)
        elif part.startswith("parallel_block"):
            i = 0 if part.endswith("sliding") else 1
            mod = modeling.Cohere2DecoderLayer(config, i)
            wts = _seed_module(torch, mod, 4 + i)
            want = mod(xt, cos_sin,
                       attention_mask=masks[s["layer_types"][i]]
                       ).numpy()[0]
            b = "l%d_" % i
            p = {k_: jnp.asarray(v) for k_, v in dict(
                attn_leaves(wts, b, "self_attn."),
                **{b + "norm_gamma": wts["input_layernorm.weight"]})
                .items()}
            mlp = [jnp.asarray(wts["mlp.%s_proj.weight" % m])
                   for m in ("gate", "up", "down")]
            got = ref.decoder_layer(
                jx, p, i, s, ffn=lambda h: ref.gated(h, *mlp))
        else:
            mod = modeling.Cohere2ForCausalLM(config)
            wts = _seed_module(torch, mod, 6)
            assert mod.lm_head.weight is mod.model.embed_tokens.weight
            want = (mod.lm_head(mod.model.norm(xt))
                    * mod.logit_scale).numpy()[0]
            got = ref.head(jx, {
                "final_norm_gamma": jnp.asarray(wts["model.norm.weight"]),
                "embed_tokens_weight": jnp.asarray(
                    wts["model.embed_tokens.weight"])}, {"spec": s})
    assert np.abs(np.asarray(got) - want).max() < HF_TOL * max(
        1.0, np.abs(want).max())


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

    want, want_counts = run()
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


def _greedy(ref, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_ref_logits(ref, seq)[-1])))
    return seq[len(prompt):]


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
