"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2,
rehearsal 3).  Interpret mode hid three kernels Mosaic refuses for
thirteen PRs; these cases compile every kernel ``chip_smoke.py`` routes
to, at the smoke's widths, for a described ``v5e:2x2`` — about a second
each, no chip time — plus one negative case per eligibility rule that
was tightened to what the compiler accepts.  Nothing runs: a compile
that passes is not a chip run.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.pallas_ops import dispatch, norm
from mxnet_tpu.pallas_ops import grouped_matmul as gm
from mxnet_tpu.pallas_ops import paged_attention as pa
from mxnet_tpu.pallas_ops import softmax_xent as sx

# the package re-exports functions under these modules' names
fa = importlib.import_module("mxnet_tpu.pallas_ops.flash_attention")
dq = importlib.import_module("mxnet_tpu.pallas_ops.dequant_matmul")

pytestmark = pytest.mark.quick

F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
# chip_smoke.py's LM: 16 heads x 128, 2048 wide, vocabulary 32768,
# batch 8 x sequence 1024; serving at batch bucket 8, 64-token blocks
B, H, L, D, W, V, BS, T = 8, 16, 1024, 128, 2048, 32768, 64, 16
ROWS = B * L


@pytest.fixture(scope="module")
def chip():
    """``struct(shape, dtype)`` placing operands on one described v5e
    chip; the persistent compile cache is off around the module (such a
    compile can be written to it but never read back without a chip)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip("cannot describe a v5e topology: %s" % e)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_mode(monkeypatch):
    """Eligibility as it answers on a TPU (the probe sees this CPU)."""
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)


def _kernel_calls(fn, *args):
    """Compile for the described chip; raises what the chip's compiler
    would raise.  Returns how many Mosaic kernels the program holds."""
    return jax.jit(fn).lower(*args).compile().as_text() \
        .count("tpu_custom_call")


def _grad(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(F32)),
                    argnums=argnums)


def _flash(q, k, v):
    return fa.flash_attention(q, k, v, causal=True, interpret=False)


def _offset(q, k, v, o):
    return fa.flash_attention_offset(q, k, v, o, interpret=False)


def _paged(bs):
    # the whole two-layer pool and the layer: what the step graph hands
    return lambda q, k, v, t, p: pa.flash_attention_paged(
        q, k, v, 1, t, p, bs, interpret=False)


def _paged_int8(q, k, v, t, p, sk, sv):
    return pa.flash_attention_paged(q, k, v, 1, t, p, BS,
                                    interpret=False, kv_scales=(sk, sv))


def _rms(x, g):
    return norm.rms_norm(x, g, 1e-6, 8, False)


def _ln(x, g, b):
    return norm.layer_norm(x, g, b, 1e-5, 8, False)


def _dqmm(x, c, s):
    return dq._dqmm_pallas(x, c, s, 128, 128, 128, False)


def _grouped(x, w, c):
    return gm.grouped_matmul(x, w, c, interpret=False)


def _dsa_index(q, w, pool, tables, positions):
    from mxnet_tpu.pallas_ops import dsa
    return dsa.dsa_index_scores(q, w, pool, 1, tables, positions, BS,
                                interpret=False)


def _dsa_attend(q, rows, counts):
    from mxnet_tpu.pallas_ops import dsa
    return dsa.dsa_mla_attention(q, rows, counts, 512, 0.1,
                                 interpret=False)


def _dsa_select(scores):
    from mxnet_tpu.pallas_ops import dsa
    return dsa.dsa_select_threshold(scores, 2048, interpret=False)


def _dsa_masked(q, pool, tables, positions, scores, thr, tie):
    from mxnet_tpu.pallas_ops import dsa
    return dsa.dsa_mla_attention_masked(
        q, pool, 1, tables, positions, scores, thr, tie, BS, 512, 0.1,
        interpret=False)


def _mla(first):
    from mxnet_tpu.pallas_ops import mla_attention as mla
    return lambda q, pool, tables, positions: mla.mla_paged_attention(
        q, pool, 5, tables, positions, BS, 512, 0.07, interpret=False,
        first=first)


def _mla_args(s, rows, lq):
    # openpangu-ultra-moe.serve-reason-backlog: 128 heads, 64 table
    # entries of 64 tokens, the leaf of 2,048 blocks x (5 + 1) layers
    return (s((rows, 128, lq, 640), BF16),
            s((6, 1, 2048 * BS, 640), BF16), s((rows, 64), I32),
            s((rows,), I32))


def _dsa_index_args(s, rows, lq):
    # deepseek-v32.serve-longdoc-backlog: 64 index heads of 128, 360
    # table entries of 64 tokens, the leaf of 6,144 blocks
    return (s((rows, lq, 64, 128), BF16), s((rows, lq, 64)),
            s((2, 1, 6144 * BS, 128), BF16), s((rows, 360), I32),
            s((rows,), I32))


def _paged_args(s, lq, pool_dtype=F32, bs=BS, blocks=B * T + 1):
    pool = s((2, H, blocks * bs, D), pool_dtype)
    return (s((B, H, lq, D)), pool, pool, s((B, T), I32), s((B,), I32))


# (id, fn, operand builder, Mosaic kernels expected in the program)
CASES = [
    ("flash-fwd", _flash, lambda s: (s((B, H, L, D)),) * 3, 1),
    # the backward re-runs the scan twin under vjp: no kernel of its own
    ("flash-bwd", _grad(_flash, (0, 1, 2)),
     lambda s: (s((B, H, L, D)),) * 3, 0),
    ("flash-fwd-bf16", _flash, lambda s: (s((B, H, L, D), BF16),) * 3, 1),
    ("offset-decode", _offset,
     lambda s: (s((B, H, 1, D)), s((B, H, L, D)), s((B, H, L, D)),
                s((B,), I32)), 1),
    # a 1088-token cache: largest divisor 68, tiled by 64
    ("offset-1088", _offset,
     lambda s: (s((B, H, 128, D)), s((B, H, 1088, D)),
                s((B, H, 1088, D)), s((B,), I32)), 1),
    ("paged-decode", _paged(BS), lambda s: _paged_args(s, 1), 1),
    ("paged-chunk", _paged(BS), lambda s: _paged_args(s, 32), 1),
    ("paged-bf16", _paged(BS),
     lambda s: (s((B, H, 1, D), BF16),) + _paged_args(s, 1, BF16)[1:], 1),
    ("paged-int8", _paged_int8,
     lambda s: _paged_args(s, 1, I8)
     + (s((2, H, B * T + 1)), s((2, H, B * T + 1))), 1),
    ("softmax-fwd", lambda x: sx.fused_softmax(x, 8, False),
     lambda s: (s((ROWS, V)),), 1),
    ("softmax-bwd", _grad(lambda x: sx.fused_softmax(x, 8, False) ** 2,
                          0),
     lambda s: (s((ROWS, V)),), 2),
    ("head-fwd", lambda x, l: sx.softmax_output_head(x, l, 1.0, 8, False),
     lambda s: (s((ROWS, V)), s((ROWS,))), 1),
    ("head-bwd",
     _grad(lambda x, l: sx.softmax_output_head(x, l, 1.0, 8, False), 0),
     lambda s: (s((ROWS, V)), s((ROWS,))), 2),
    ("xent-fwd", lambda x, l: sx.softmax_xent_loss(x, l, 8, False),
     lambda s: (s((ROWS, V)), s((ROWS,))), 1),
    ("xent-bwd",
     _grad(lambda x, l: sx.softmax_xent_loss(x, l, 8, False), 0),
     lambda s: (s((ROWS, V)), s((ROWS,))), 1),
    ("rms-fwd", _rms, lambda s: (s((ROWS, W)), s((W,))), 1),
    ("rms-bwd", _grad(_rms, (0, 1)),
     lambda s: (s((ROWS, W)), s((W,))), 1),
    ("rms-bwd-bf16", _grad(_rms, (0, 1)),
     lambda s: (s((ROWS, W), BF16), s((W,), BF16)), 1),
    ("ln-fwd", _ln, lambda s: (s((ROWS, W)), s((W,)), s((W,))), 1),
    ("ln-bwd", _grad(_ln, (0, 1, 2)),
     lambda s: (s((ROWS, W)), s((W,)), s((W,))), 1),
    # the VMEM tile budget's edge: an 8 x 65536 fp32 tile is 2 MiB
    ("ln-bwd-2MiB-tile", _grad(_ln, (0, 1, 2)),
     lambda s: (s((64, 65536)), s((65536,)), s((65536,))), 1),
    ("dqmm-decode", _dqmm,
     lambda s: (s((B, W)), s((4 * W, W), I8), s((4 * W,))), 1),
    ("dqmm-chunk-vocab", _dqmm,
     lambda s: (s((B * 32, W)), s((V, W), I8), s((V,))), 1),
    ("dqmm-ffn2", _dqmm,
     lambda s: (s((B, 4 * W)), s((W, 4 * W), I8), s((W,))), 1),
    # deepseek-v3.serve-docqa-backlog's grouped products, 16 held
    # experts: a chunk program's gate and up (512 tokens x 8 picks) and
    # a decode program's down (64 x 8)
    ("grouped-chunk-gate-up", _grouped,
     lambda s: (s((4096, 7168), BF16), s((16, 7168, 4096), BF16),
                s((16,), I32)), 1),
    ("grouped-decode-down", _grouped,
     lambda s: (s((512, 2048), BF16), s((16, 2048, 7168), BF16),
                s((16,), I32)), 1),
    # deepseek-v32.serve-longdoc-backlog's two kernels, a decode step's
    # 64 rows and a chunk's 16 rows x 32 queries: the indexer over the
    # paged index keys, the selection's threshold over the table's
    # width, attention over 2,048 gathered rows a sequence (a decode
    # step) and under the selection's mask (a chunk)
    ("dsa-index-decode", _dsa_index,
     lambda s: _dsa_index_args(s, 64, 1), 1),
    ("dsa-index-chunk", _dsa_index,
     lambda s: _dsa_index_args(s, 16, 32), 1),
    ("dsa-select-decode", _dsa_select, lambda s: (s((64, 23040)),), 1),
    ("dsa-select-chunk", _dsa_select, lambda s: (s((512, 23040)),), 1),
    ("dsa-attend-decode", _dsa_attend,
     lambda s: (s((64, 128, 640), BF16), s((64, 2048, 640), BF16),
                s((64,), I32)), 1),
    # openpangu-ultra-moe.serve-reason-backlog: the latent kernel at TWO
    # queries a row (a self-drafting step's verify) and, for the
    # prediction module's layer, with row 0 of the cache seen by no
    # query (first=1), a step's rows and a chunk's
    ("mla-verify-two-queries", _mla(0), lambda s: _mla_args(s, 64, 2), 1),
    ("mla-module-step-first-1", _mla(1), lambda s: _mla_args(s, 64, 2), 1),
    ("mla-module-chunk-first-1", _mla(1),
     lambda s: _mla_args(s, 16, 32), 1),
    ("dsa-attend-chunk-masked", _dsa_masked,
     lambda s: (s((16, 128, 32, 640), BF16),
                s((2, 1, 6144 * BS, 640), BF16), s((16, 360), I32),
                s((16,), I32), s((16, 32, 23040)), s((16, 32, 1), I32),
                s((16, 32, 1), I32)), 1),
]


@pytest.mark.parametrize("fn,operands,kernels",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(chip, fn, operands, kernels):
    assert _kernel_calls(fn, *operands(chip)) == kernels


def test_smoke_shapes_are_eligible(compiled_mode):
    """What the cases above compile is what dispatch routes on a TPU."""
    assert dispatch.eligible_attention(B, H, L, L, D, "float32")
    assert dispatch.eligible_attention_offset(B, H, 1, 1088, D, "float32")
    assert dispatch.eligible_attention_paged(B, H, 32, T * BS, D,
                                             "float32", BS)
    for rows in (B, B * 32, ROWS):
        assert dispatch.eligible_rowwise(rows, W, "float32")
    assert dispatch.eligible_rowwise(ROWS, V, "float32")
    assert dispatch.eligible_rowwise(64, 65536, "float32")
    for m, n, k in ((B, 4 * W, W), (B * 32, V, W), (B, W, 4 * W)):
        assert dispatch.eligible_dequant_matmul(m, n, k, "float32")
    # sorted rows (tokens x 8 picks), hidden and expert width
    for rows in (4096, 512):
        assert dispatch.eligible_moe_experts(rows, 7168, 2048, "bfloat16")
    # a budget, not a refusal: 32,768 values of contraction by one lane
    # tile of columns are 8 MiB, twice the kernel's weight tile
    assert not dispatch.eligible_moe_experts(512, 32768, 2048, "bfloat16")


# One case per tightened rule: the compiler refuses the shape, and the
# rule now says so first, so the op takes the XLA lowering openly.
REFUSED = [
    # 12 rows tile by 6: not a multiple of 8, not all the rows
    ("rowwise-6-row-block", _rms, lambda s: (s((12, W)), s((W,))),
     lambda: dispatch.eligible_rowwise(12, W, "float32")),
    # an 8 x 131072 fp32 tile is 4 MiB: the backward runs out of VMEM
    ("rowwise-4MiB-tile", _grad(_ln, (0, 1, 2)),
     lambda s: (s((64, 131072)), s((131072,)), s((131072,))),
     lambda: dispatch.eligible_rowwise(64, 131072, "float32")),
    # 300 = 2^2 * 3 * 5^2 has no divisor that is a multiple of 8
    ("offset-300-token-cache", _offset,
     lambda s: (s((B, H, 1, D)), s((B, H, 300, D)), s((B, H, 300, D)),
                s((B,), I32)),
     lambda: dispatch.eligible_attention_offset(B, H, 1, 300, D,
                                                "float32")),
    ("paged-4-token-blocks", _paged(4),
     lambda s: _paged_args(s, 1, bs=4),
     lambda: dispatch.eligible_attention_paged(B, H, 1, T * 4, D,
                                               "float32", 4)),
    # 36 sorted rows tile by 18: not a multiple of 8, not all the rows
    ("grouped-18-row-tile", _grouped,
     lambda s: (s((36, 256), BF16), s((4, 256, 256), BF16),
                s((4,), I32)),
     lambda: dispatch.eligible_moe_experts(36, 256, 256, "bfloat16")),
    # n = 1000 tiles by 40: the scale row's lane dim is not 128-aligned
    ("dqmm-n-1000", _dqmm,
     lambda s: (s((B, W)), s((1000, W), I8), s((1000,))),
     lambda: dispatch.eligible_dequant_matmul(B, 1000, W, "float32")),
]


@pytest.mark.parametrize("fn,operands,eligible",
                         [c[1:] for c in REFUSED],
                         ids=[c[0] for c in REFUSED])
def test_refused_shape_is_ineligible(chip, compiled_mode, fn, operands,
                                     eligible):
    assert not eligible()
    with pytest.raises(Exception):  # noqa: B017 — the compiler's own
        _kernel_calls(fn, *operands(chip))


def test_flash_block_must_be_mosaic_tileable(compiled_mode, monkeypatch):
    """``MXNET_PALLAS_BLOCK_SEQ=100`` over 200 tokens divides exactly but
    is no block Mosaic tiles: ineligible compiled, fine interpreted."""
    monkeypatch.setenv("MXNET_PALLAS_BLOCK_SEQ", "100")
    assert not dispatch.eligible_attention(2, 4, 200, 200, D, "float32")
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: False)
    assert dispatch.eligible_attention(2, 4, 200, 200, D, "float32")


# The paged step programs at the served models' widths, two layers
# deep, compiled as the store compiles them (``paged_program``): the
# decode step over the slots and the prompt chunk over
# ``chunk_rows(slots)`` rows beside the slots' key chains.  The KV pool
# is addressed in place from entry to exit.  A scatter on the pool, or
# a layer of it sliced out for the kernel, makes the compiler relay the
# whole pool around the program (docs/architecture/decode_engine.md,
# "The pool stays where it is").
LAYERS = 2
_MOVES_THE_POOL = ("copy", "slice", "scatter", "fusion", "gather")


def _lm_program(chip):
    """``lm2048``'s widths (16 heads x 128, 2048 wide), 16 slots of 16
    blocks of 64 tokens, chunks of 32, fp32 pools."""
    lm = importlib.import_module("mxnet_tpu.models.transformer_lm")
    spec = lm.lm_spec(num_layers=LAYERS, num_hidden=W, num_heads=H,
                      vocab_size=V)
    net = lm.get_symbol(seq_len=8, **spec)
    shapes, _, _ = net.infer_shape(data=(1, 8), softmax_label=(1, 8))
    params = {n: chip(s) for n, s in zip(net.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    pool = chip((LAYERS, H, (16 * T + 1) * BS, D))
    return dict(model=lm, spec=spec, params=params, pools=(pool, pool),
                slots=16, width=T, chunk=32, kernels=LAYERS,
                pool_shaped=r"f32\[(?:%d,|1,)?%d,%d,%d\]"
                % ((LAYERS,) + pool.shape[1:]))


def _deepseek_program(chip):
    """DeepSeek-V3's published widths, one dense and one expert layer
    of 16 held experts, 64 slots of 104 blocks of 64 tokens, chunks of
    32, bfloat16 weights and latent pool."""
    from mxnet_tpu.models import deepseek_v3 as ds
    spec = ds.serving_spec({
        "num_hidden_layers": LAYERS, "first_k_dense_replace": 1,
        "hidden_size": 7168, "num_attention_heads": 128,
        "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "n_routed_experts": 16,
        "router_width": 256, "n_shared_experts": 1,
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "vocab_size": 16160,
        "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}})
    packed = jax.eval_shape(lambda: ds.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in ds.param_shapes(spec).items()}, spec))
    params = {k: chip(v.shape, v.dtype) for k, v in packed.items()}
    pool = chip((LAYERS, 1, (64 * 104 + 1) * 64, ds.latent_width(spec)),
                BF16)
    return dict(model=ds, spec=spec, params=params, pools=(pool,),
                slots=64, width=104, chunk=32, kernels=LAYERS,
                pool_shaped=r"bf16\[(?:%d,|1,)?1,%d,%d\]"
                % ((LAYERS,) + pool.shape[2:]))


def _deepseek32_program(chip):
    """``_deepseek_program`` with DeepSeek-V3.2's indexer (64 heads of
    128, 2,048 kept): TWO token leaves on the one table, the latent
    rows and the index keys, 64 slots of 360 blocks over a pool of
    6,144 as ``deepseek-v32.serve-longdoc-backlog`` has them."""
    from mxnet_tpu.models import deepseek_v32 as ds
    m = _deepseek_program(chip)
    spec = ds.serving_spec(dict(
        m["spec"], index_n_heads=64, index_head_dim=128,
        index_topk=2048))
    packed = jax.eval_shape(lambda: ds.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in ds.param_shapes(spec).items()}, spec))
    pools = tuple(chip(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: ds.init_pool(spec, 6144, 64, BF16)))
    assert [p.shape[3] for p in pools] == [640, 128]
    # four calls a layer: the indexer, the selection, the sparse
    # attention, the experts' (the dense layer: three)
    return dict(model=ds, spec=spec,
                params={k: chip(v.shape, v.dtype)
                        for k, v in packed.items()},
                pools=pools, slots=64, width=360, chunk=32,
                kernels=3 * LAYERS,
                pool_shaped=r"bf16\[(?:%d,|1,)?1,%d,(?:640|128)\]"
                % (LAYERS, pools[0].shape[2]))


def _lfm2_program(chip):
    """The cell ``lfm2-24b-a2b.serve-agent-backlog`` as its
    configuration file deploys it: LFM2-24B-A2B's published widths, all
    nine layers (7 convolution, 2 attention, 8 of 64 experts), 128
    slots of 64 blocks of 64 tokens, bfloat16 weights, ``[K | V]`` rows
    and convolution state."""
    import json
    from mxnet_tpu.models import lfm2_moe as lfm
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    deploy = cfg["deploy"]
    spec = lfm.serving_spec({k: v for k, v in cfg["spec"].items()
                             if k != "arch"})
    packed = jax.eval_shape(lambda: lfm.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in lfm.param_shapes(spec).items()}, spec))
    params = {k: chip(v.shape, v.dtype) for k, v in packed.items()}
    slots, = deploy["batch_buckets"]
    width = deploy["kv_max"] // deploy["kv_block"]
    assert deploy["kv_block"] == BS
    pools = tuple(chip(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: lfm.init_pool(spec, slots * width + 1, BS, "bfloat16")))
    return dict(model=lfm, spec=spec, params=params, pools=pools,
                slots=slots, width=width, chunk=deploy["prefill_chunk"],
                kernels=2 + 2 * 8,
                pool_shaped="|".join(
                    r"bf16\[(?:%d,|1,)?%d,%d,%d\]" % a.shape
                    for a in pools))


def _cohere2_program(chip):
    """The cell ``command-a-plus.serve-ragmix-backlog`` as its
    configuration file deploys it: Command A+'s published widths, one
    period (three window layers, one full), 16 of 128 experts, 64 slots
    of 256 blocks of 64 tokens in EACH of the two classes of block (a
    table a class, side by side), bfloat16 weights and ``K``/``V``
    rows."""
    import json
    from mxnet_tpu.models import cohere2_moe as co
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "command-a-plus.json")) as f:
        cfg = json.load(f)
    deploy = cfg["deploy"]
    spec = co.serving_spec(cfg["spec"])
    packed = jax.eval_shape(lambda: co.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in co.param_shapes(spec).items()}, spec))
    params = {k: chip(v.shape, v.dtype) for k, v in packed.items()}
    slots, = deploy["batch_buckets"]
    assert deploy["kv_block"] == BS
    pools = tuple(chip(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: co.init_pool(spec, deploy["pool_blocks"], BS,
                             "bfloat16")))
    return dict(model=co, spec=spec, params=params, pools=pools,
                slots=slots, chunk=deploy["prefill_chunk"],
                width=len(co.cache_classes(spec))
                * (deploy["kv_max"] // BS),
                kernels=4 + 2 * 4,
                pool_shaped="|".join(
                    r"bf16\[(?:%d,|1,)?%d,%d,%d\]" % a.shape
                    for a in pools))


def _paged_program_args(build, chip, kind):
    """``(the build, operands, program, donated)`` of a store's decode
    or compacted prompt-chunk program for the described chip, or
    (``one-pass``) of the tick that runs both as two row groups."""
    from mxnet_tpu.serving.program_store import chunk_rows, paged_program

    m = build(chip)
    slots = m["slots"]
    # a one-pass store's two programs keep the slots' pending tokens on
    # the device (what its engine dispatches: the decode step too)
    pending = hasattr(m["model"], "paged_step_groups")
    if kind == "decode":
        pkind, rows, lq = "paged_step_sample", slots, 1
    else:
        pkind = "paged_tick_sample" if kind == "one-pass" \
            else "paged_chunk_sample"
        rows, lq = chunk_rows(slots), m["chunk"]
        assert rows == slots // 4
    fn, donate = paged_program(m["model"], m["spec"], pkind, lq, BS,
                               len(m["pools"]), pending=pending)
    args = (m["params"],) + m["pools"] + (
        chip((rows, m["width"]), I32), chip((rows, lq), I32),
        chip((rows,), I32), chip((rows,), I32),
        chip((slots, 2), jnp.uint32), chip((rows,)), chip((rows,), I32),
        chip((rows,), jnp.bool_))
    if kind != "decode":
        args += (chip((rows,), I32),)
    if kind == "one-pass":      # the decode group behind the chunk,
        # then the chunk rows' own chains
        args += (chip((slots, m["width"]), I32), chip((slots, 1), I32),
                 chip((slots,), I32), chip((slots,), I32),
                 chip((slots,)), chip((slots,), I32),
                 chip((slots,), jnp.bool_), chip((rows, 2), jnp.uint32))
    if kind == "one-pass" or (kind == "decode" and pending):
        args += (chip((slots,), I32), chip((slots,), jnp.bool_))
    return m, args, fn, donate


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_lm_program, _deepseek_program,
                                   _lfm2_program, _cohere2_program,
                                   _deepseek32_program],
                         ids=["lm2048", "deepseek-v3", "lfm2-24b-a2b",
                              "command-a-plus", "deepseek-v32"])
def test_paged_program_leaves_the_pool_in_place(chip, compiled_mode,
                                                build, kind):
    import re

    m, args, fn, donate = _paged_program_args(build, chip, kind)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    assert fn.__name__ == "paged_" + kind.replace("-", "_")
    # the attention kernel is in it, a call a layer
    assert text.count("tpu_custom_call") >= m["kernels"]
    pool_shaped = re.compile(m["pool_shaped"])
    moved = []
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(",
                       line)
        if hit and hit.group(2) in _MOVES_THE_POOL \
                and pool_shaped.search(hit.group(1)):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    if build is _deepseek32_program:
        # both leaves: the indexer, the selection and the sparse
        # attention a layer and no dense latent walk; the one gather of
        # a leaf hands back a decode step's selected rows, 2,048 a
        # sequence, never a leaf or a layer of one; a chunk walks under
        # the mask and gathers nothing; nothing sorts the table's width
        form = "dsa_mla_attention" + ("" if kind == "decode"
                                      else "_masked")
        for name in ("dsa_index_scores", "dsa_select_threshold", form):
            assert len(re.findall(r"%%%s[.\d]* = " % name, text)) \
                == LAYERS, name
        assert "mla_paged_attention" not in text
        gathers = re.findall(r"= (bf16\[\d+,\d+,640\])\S* gather\(", text)
        assert gathers == (["bf16[64,2048,640]"] * LAYERS
                           if kind == "decode" else [])
        assert not [ln for ln in text.splitlines()
                    if " sort(" in ln and ",23040]" in ln]
    if build is _lm_program:
        pool = m["pools"][0]
        layer_bytes = pool.size // LAYERS * pool.dtype.itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_expert_layer_runs_the_repos_grouped_product(chip, compiled_mode,
                                                     kind):
    """The ``deepseek_v3`` step compiled for the described v5e holds
    the repo's grouped product, twice an expert layer, under the name
    the benchmark's readers look for; no grouped product of another
    origin (XLA's own ``ragged-dot`` is an instruction or a fusion of
    that name, never a ``custom-call`` to ``tpu_custom_call``); and no
    copy, transpose or fusion that hands back something of an expert
    stack's shape: the stacks are read where they lie."""
    import re
    m, args, fn, donate = _paged_program_args(_deepseek_program, chip,
                                              kind)
    text = jax.jit(fn, donate_argnums=donate).lower(*args).compile() \
        .as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * (LAYERS - 1), "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    assert " ragged-dot(" not in text
    stack = re.compile(r"bf16\[16,(?:7168,4096|2048,7168|4096,7168"
                       r"|7168,2048)\]")
    moved = [ln.strip()[:160] for ln in text.splitlines()
             for hit in [re.match(
                 r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(", ln)]
             if hit and hit.group(2) != "parameter"
             and stack.search(hit.group(1))]
    assert not moved, "\n".join(moved)


@pytest.mark.parametrize("build,attention,scratch_gb", [
    (_deepseek_program, {"mla_paged_attention": LAYERS}, 1.0),
    (_lfm2_program, {"paged_attention": 2}, 0.25),
    (_cohere2_program, {"paged_attention": 1,
                        "window_paged_attention": 3}, 0.45),
], ids=["deepseek-v3", "lfm2-24b-a2b", "command-a-plus"])
def test_one_pass_tick_reads_the_experts_once(chip, compiled_mode, build,
                                              attention, scratch_gb):
    """The one-pass tick of the three expert model modules
    (``paged_tick_sample``: the slots' decode rows and the compacted
    prompt chunk as two row groups of one step) compiled for the
    described v5e at each cell's whole size: named so that what counts
    step programs by ``jit_paged_prefill_chunk`` counts it; the grouped
    product twice an expert layer, as in EACH of the two programs it
    stands for, so the experts are streamed once a tick; every
    attention kernel twice a layer, once a group, with the shapes the
    two programs call it with; no pool leaf moved; and the chunk
    program's scratch with the decode group's rows beside it, far from
    the chip's 16 GB."""
    import re
    m, args, fn, donate = _paged_program_args(build, chip, "one-pass")
    assert fn.__name__ == "paged_prefill_chunk_tick"
    slots, rows, lq = m["slots"], m["slots"] // 4, m["chunk"]
    layers = m["spec"]["num_hidden_layers"] \
        - m["spec"]["first_k_dense_replace"]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * layers, "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    # the sorted rows of both groups in one product
    picks = m["spec"]["num_experts_per_tok"]
    assert all("[%d," % ((slots + rows * lq) * picks) in ln
               for ln in named)
    for name, calls in attention.items():
        attn = [ln for ln in text.splitlines() if re.match(
            r"\s*(?:ROOT )?%%%s\S* = " % name, ln)]
        assert len(attn) == 2 * calls, (name, len(attn))
        # a group each: the decode rows' call and the chunk rows'
        firsts = sorted(int(re.search(r"= \w+\[(\d+),", ln).group(1))
                        for ln in attn)
        assert firsts == [rows] * calls + [slots] * calls, (name, firsts)
    pool_shaped = re.compile(m["pool_shaped"])
    moved = []
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(",
                       line)
        if hit and hit.group(2) in _MOVES_THE_POOL \
                and pool_shaped.search(hit.group(1)):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    mem = compiled.memory_analysis()
    print("one-pass %s: arguments %.2f GB, scratch %.2f GB"
          % (build.__name__, mem.argument_size_in_bytes / 1e9,
             mem.temp_size_in_bytes / 1e9))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    assert mem.temp_size_in_bytes < scratch_gb * 1e9


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_lfm2_cell_programs_fit_the_chip(chip, compiled_mode, kind):
    """The cell's two programs at the published widths, compiled for
    the described v5e: arguments (10.36 GB of weights, the 2.15 GB
    ``[K | V]`` leaf, the 0.47 GB state leaf) and scratch under 15 GB
    of the chip's 16; the grouped product eligible at both of this
    model's width pairs and in the program twice an expert layer under
    the name the benchmark's readers look for, none of another origin;
    the attention kernel once an attention layer with all four query
    heads of a KV head in its tile."""
    import re
    m, args, fn, donate = _paged_program_args(_lfm2_program, chip, kind)
    rows = args[1 + len(m["pools"]) + 1].shape
    sorted_rows = rows[0] * rows[1] * m["spec"]["num_experts_per_tok"]
    assert dispatch.eligible_moe_experts(sorted_rows, 2048, 1536,
                                         "bfloat16")
    dispatch.reset_dispatch_stats()
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    # both attention layers' calls bring all 8 pool heads in a copy
    assert dispatch.dispatch_stats()[
        "DotProductAttentionPaged.heads_per_copy=8"] == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    # what the configuration's deploy_notes state: 12.97 GB of
    # arguments, 0.08 / 0.07 GB of scratch (the kernel's wider tiles
    # live in VMEM and add nothing here)
    assert abs(mem.argument_size_in_bytes - 12.97e9) < 0.02e9
    assert mem.temp_size_in_bytes < 0.1e9
    text = compiled.as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * 8, "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    assert " ragged-dot(" not in text
    attn = [ln for ln in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%paged_attention\S* = ", ln)]
    assert len(attn) == 2
    tile = "bf16[%d,8,%d,128]" % (rows[0], 4 * rows[1])
    assert all(tile in ln and "tpu_custom_call" in ln for ln in attn)


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_command_a_plus_cell_programs_fit_the_chip(chip, compiled_mode,
                                                   kind):
    """The cell's two programs at the published widths, compiled for
    the described v5e: arguments (9.47 GB of weights, the full class's
    0.81 GB of ``K`` and ``V`` rows, the window class's 2.42 GB) and
    scratch under 15 GB of the chip's 16; the grouped product in the
    program twice a layer under the name the benchmark's readers look
    for; the attention kernel once a layer with all sixteen query heads
    of a KV head in its tile: the full layer's under the name
    ``kernel.gqa_attn_*`` read, the three window layers' under their
    own, and these walk 5 (a decode step) or 6 (a chunk) groups of 16
    blocks where the full layer's walks the table's 16.  Read here:
    12.69 GB of arguments, 0.15 GB (decode) and 0.20 GB (a chunk of 32;
    0.47 GB at 64) of scratch."""
    import re
    m, args, fn, donate = _paged_program_args(_cohere2_program, chip,
                                              kind)
    rows = args[1 + len(m["pools"]) + 1].shape
    assert args[1 + len(m["pools"])].shape == (rows[0], 2 * 256)
    sorted_rows = rows[0] * rows[1] * m["spec"]["num_experts_per_tok"]
    assert dispatch.eligible_moe_experts(sorted_rows, 4096, 4096,
                                         "bfloat16")
    dispatch.reset_dispatch_stats()
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    # all four layers' calls bring all 8 pool heads in a copy
    assert dispatch.dispatch_stats()[
        "DotProductAttentionPaged.heads_per_copy=8"] == 4
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    # what the configuration's deploy_notes state: 12.69 GB of
    # arguments, 0.15 / 0.20 GB of scratch (the kernel's wider tiles
    # live in VMEM and add nothing here)
    assert abs(mem.argument_size_in_bytes - 12.69e9) < 0.02e9
    assert mem.temp_size_in_bytes < 0.25e9
    text = compiled.as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * 4, "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    assert " ragged-dot(" not in text
    tile = "bf16[%d,8,%d,128]" % (rows[0], 16 * rows[1])
    for name, calls in (("paged_attention", 1),
                        ("window_paged_attention", 3)):
        attn = [ln for ln in text.splitlines() if re.match(
            r"\s*(?:ROOT )?%%%s\S* = " % name, ln)]
        assert len(attn) == calls, (name, len(attn))
        assert all(tile in ln and "tpu_custom_call" in ln for ln in attn)
    print("command-a-plus %s: arguments %.2f GB, scratch %.2f GB"
          % (kind, mem.argument_size_in_bytes / 1e9,
             mem.temp_size_in_bytes / 1e9))


# (build, kind): the attention kernel's calls as the program's trace
# holds them: (name, grid, Q tile, K/V block, K and V operands, whether
# the call asks for more VMEM than every call gets)
KERNEL_CALLS = {
    # one query head a pool head: the grid and the blocks it always had
    ("lm2048", "decode"): [
        ("paged_attention", (16, 16, 1, 16), (1, 1, 1, 128),
         (1, 1, 64, 128), 2, False)] * LAYERS,
    ("lm2048", "prefill-chunk"): [
        ("paged_attention", (4, 16, 1, 16), (1, 1, 32, 128),
         (1, 1, 64, 128), 2, False)] * LAYERS,
    # 4 query heads a pool head, [K | V] rows: all 8 pool heads a copy,
    # 16 table entries of 64 a step
    ("lfm2-24b-a2b", "decode"): [
        ("paged_attention", (128, 1, 1, 4), (1, 8, 4, 128),
         (1, 8, 64, 128), 16, False)] * 2,
    ("lfm2-24b-a2b", "prefill-chunk"): [
        ("paged_attention", (32, 1, 1, 4), (1, 8, 128, 128),
         (1, 8, 64, 128), 16, False)] * 2,
    # 16 query heads a pool head, K and V apart; the window layers walk
    # 5 (a decode step) or 6 (a chunk) groups of the table's 16
    ("command-a-plus", "decode"): [
        ("window_paged_attention", (64, 1, 1, 5), (1, 8, 16, 128),
         (1, 8, 64, 128), 32, False)] * 3 + [
        ("paged_attention", (64, 1, 1, 16), (1, 8, 16, 128),
         (1, 8, 64, 128), 32, False)],
    ("command-a-plus", "prefill-chunk"): [
        ("window_paged_attention", (16, 1, 1, 6), (1, 8, 512, 128),
         (1, 8, 64, 128), 32, True)] * 3 + [
        ("paged_attention", (16, 1, 1, 16), (1, 8, 512, 128),
         (1, 8, 64, 128), 32, True)],
}


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_lm_program, _lfm2_program,
                                   _cohere2_program],
                         ids=["lm2048", "lfm2-24b-a2b", "command-a-plus"])
def test_paged_programs_hand_the_kernel_its_blocks(chip, compiled_mode,
                                                   build, kind):
    """What each served model's two programs hand the paged kernel at
    the cells' shapes (traced, not compiled: the compiles are the tests
    above): ``lm2048``'s the grid ``(rows, 16, 1, 16)`` and blocks of
    ONE head ``(1, 1, 64, 128)`` they always had; the grouped-query
    models' a grid without a head axis and blocks of all 8 pool heads
    ``(1, 8, 64, 128)``, ``KV_GROUP`` 16 of them for K (and for V), the
    VMEM rule lowering nothing and asking for more than Mosaic's 16 MiB
    only for Command A+'s chunk, whose Q tile is 512 rows of 8 heads."""
    from mxnet_tpu.test_utils import pallas_calls

    m, args, fn, _ = _paged_program_args(build, chip, kind)
    calls = [c for c in pallas_calls(fn, *args)
             if c[0].endswith("paged_attention")]
    got = [(name, grid, blocks[0], blocks[1], len(blocks) - 2,
            limit is not None) for name, grid, blocks, limit in calls]
    config = {_lm_program: "lm2048", _lfm2_program: "lfm2-24b-a2b",
              _cohere2_program: "command-a-plus"}[build]
    assert got == KERNEL_CALLS[(config, kind)]
    assert all(blocks[1:-1] == [blocks[1]] * (len(blocks) - 2)
               and blocks[-1] == blocks[0] for _, _, blocks, _ in calls)
    assert all(limit is None or pa._VMEM_DEFAULT < limit
               <= pa._VMEM_BUDGET + pa._VMEM_DEFAULT
               for _, _, _, limit in calls)


def test_sampler_sorts_and_draws_only_in_a_conditional(chip):
    """The sampler at ``lfm2-24b-a2b``'s decode dispatch, 128 rows of
    65,536 logits, compiled for the described v5e: the vocabulary sort
    is a branch computation of a ``conditional`` (of two, nested), and
    ``ENTRY`` produces nothing of the logits' shape: no sort, no random
    bits, no Gumbel, so a greedy dispatch runs an argmax and a key
    split."""
    import re
    from mxnet_tpu.serving.program_store import sample_tokens

    rows, vocab = 128, 65536
    text = jax.jit(sample_tokens).lower(
        chip((rows, vocab)), chip((rows, 2), jnp.uint32), chip((rows,)),
        chip((rows,), I32)).compile().as_text()
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif name is not None:
            bodies[name].append(line)
    branches = set(re.findall(
        r"%([^\s,}]+)", " ".join(re.findall(
            r"branch_computations=\{([^}]*)\}", text))))
    sorts = [n for n, body in bodies.items()
             if any(re.search(r" sort\(", ln) for ln in body)]
    assert sorts and set(sorts) <= branches, (sorts, branches)
    assert sum(" conditional(" in ln for ln in bodies["ENTRY"]) == 1
    assert sum(" conditional(" in ln for body in bodies.values()
               for ln in body) == 2
    inst = re.compile(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(")
    wide = [ln.strip()[:120] for ln in bodies["ENTRY"]
            for hit in [inst.match(ln)]
            if hit and "[%d,%d]" % (rows, vocab) in hit.group(1)
            and hit.group(2) not in ("parameter", "copy-start",
                                     "copy-done", "tuple")]
    assert not wide, "\n".join(wide)


def test_warmup_compiles_the_two_programs_a_burst_dispatches():
    """``warmup()`` compiles exactly the decode program and the
    compacted chunk program of each slot bucket (no slot-wide chunk
    program), and a burst that puts every slot in its prompt compiles
    nothing after it: the benchmark's drivers make
    ``store_compiles_after_warmup == 0`` a condition of a run.  On the
    CPU, at rehearsal size: what is counted is programs, not time."""
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    from mxnet_tpu.serving import GenerationEngine, ModelRegistry

    spec = lm_spec(num_layers=2, num_hidden=32, num_heads=4,
                   vocab_size=50)
    for sample, chunk_kind, rows in (
            ("graph", "paged_chunk_sample", 16),
            ("host", "paged_step", 4)):
        reg = ModelRegistry()
        store = reg.add_generative_model(
            "m", random_params(spec, seed=3), spec, batch_buckets=(16,),
            prompt_buckets=(8,), kv_block=8, kv_max=40, paged=True,
            prefill_chunk=4, sample=sample, warmup=False)
        decode_kind = "paged_step_sample" if sample == "graph" \
            else "paged_step"
        assert store.chunk_rows(16) == 4
        assert set(store.warmup()) == {(decode_kind, 16, 1),
                                       (chunk_kind, rows, 4)}
        warm = store.stats()
        assert warm["compiles"] == 2
        assert [tuple(r) for r in warm["programs_resident"]] == sorted(
            [(decode_kind, 16, 1), (chunk_kind, rows, 4)])
        eng = GenerationEngine(reg)
        try:
            futs = [eng.submit("m", [i, 7, 3, 19, 4, 1, 2, 3, 9],
                               max_tokens=3) for i in range(24)]
            assert all(len(f.result(300).tokens) == 3 for f in futs)
            stats = eng.stats()
        finally:
            eng.close()
        assert stats["prefill_rows_deferred"] > 0
        assert store.stats()["compiles"] == 2, sample
