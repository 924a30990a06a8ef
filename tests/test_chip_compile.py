"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2,
rehearsal 3).  Interpret mode hid three kernels Mosaic refuses for
thirteen PRs; these cases compile every kernel ``chip_smoke.py`` routes
to, at the smoke's widths, for a described ``v5e:2x2`` — about a second
each, no chip time — plus one negative case per eligibility rule that
was tightened to what the compiler accepts.  Nothing runs: a compile
that passes is not a chip run.  (The served models' programs are one
file a model, ``tests/test_chip_compile_<model>.py``; DeepSeek-V3's
one-pass tick is at the end of this one, for the files' balance.)
"""
import importlib

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.pallas_ops import dispatch, norm
from mxnet_tpu.pallas_ops import grouped_matmul as gm
from mxnet_tpu.pallas_ops import paged_attention as pa
from mxnet_tpu.pallas_ops import softmax_xent as sx

from _chip_compile_common import (BF16, BS, B, D, F32, H, I8, I32, L,  # noqa: F401
                                  LAYERS, ROWS, T, V, W, _deepseek_program,
                                  chip, compiled_mode,
                                  one_pass_tick_reads_the_experts_once)

# the package re-exports functions under these modules' names
fa = importlib.import_module("mxnet_tpu.pallas_ops.flash_attention")
dq = importlib.import_module("mxnet_tpu.pallas_ops.dequant_matmul")

pytestmark = pytest.mark.quick


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


@pytest.mark.parametrize("build,attention,scratch_gb", [
    (_deepseek_program, {"mla_paged_attention": LAYERS}, 1.0),
], ids=["deepseek-v3"])
def test_one_pass_tick_reads_the_experts_once(chip, compiled_mode, build,
                                              attention, scratch_gb):
    one_pass_tick_reads_the_experts_once(chip, build, attention,
                                         scratch_gb)
