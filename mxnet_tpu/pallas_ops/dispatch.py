"""Kernel dispatch seam: route eligible op lowerings to Pallas kernels.

The op registry's ``fcompute`` functions ARE the op-lowering layer — the
symbolic Executor, the SPMD step program and the imperative cached-op
path all trace through them — so this one seam covers every execution
plane.  An eligible op pattern (SoftmaxOutput-style loss heads, norm
layers, attention) asks :func:`use_rowwise` / :func:`use_attention` at
trace time; a ``True`` answer routes the lowering to the hand-blocked
kernel (``softmax_xent.py`` / ``norm.py`` / ``flash_attention.py``),
``False`` keeps the plain XLA lowering.

``MXNET_PALLAS`` modes:

* ``1`` (default, "auto") — kernels compile via Mosaic when the backend
  is a TPU; every other backend keeps the plain XLA lowering (interpret
  mode is orders of magnitude slower than compiled XLA on CPU, so it is
  never routed to implicitly);
* ``0`` — escape hatch: plain XLA lowering everywhere, bit-for-bit the
  pre-kernel-plane behavior (pinned by tests/test_pallas_kernels.py);
* ``2`` ("force") — route eligible patterns in interpret mode even
  off-TPU: the parity tests and ``make kernels-smoke`` run the real
  kernel bodies on CPU this way.

Eligibility is static (shapes/dtypes only), so a routing decision is a
property of the traced program.  Programs are cached across the
codebase; every cache that can outlive an env flip carries
:func:`fingerprint` in its key (cached_op LRU, SPMD program LRU).
``jax.jit`` traces LAZILY (at first call, not at jit() time), so a
program built under one env and first called under another would
silently trace with the wrong routing; long-lived program holders
(the Executor, the SPMD step) therefore capture :func:`fingerprint`
when they are CREATED and re-apply it around their traced bodies with
:func:`overriding` — the routing a caller configured at bind time is
the routing the program lowers with, whenever tracing happens.
Rebinding after a flip re-decides.

``dispatch_stats()`` counts routes per op kind at trace time; the bench
rows bank them so an artifact claiming "kernels end-to-end" carries the
proof.
"""
from __future__ import annotations

import contextlib
import threading

from ..base import get_env
from .flash_attention import _on_tpu, divisor_block, mosaic_block_ok
from .softmax_xent import row_block

__all__ = ["mode", "kernels_active", "interpret_mode", "block_rows",
           "block_seq", "fingerprint", "overriding", "use_rowwise",
           "use_attention", "use_attention_paged", "use_mla_paged",
           "use_dsa_index", "use_dsa_select", "use_dsa_attention",
           "use_moe_experts", "use_dequant_matmul",
           "eligible_rowwise", "eligible_attention",
           "eligible_attention_offset", "eligible_attention_paged",
           "eligible_mla_paged", "eligible_dsa_index",
           "eligible_dsa_select", "eligible_dsa_attention",
           "eligible_moe_experts",
           "eligible_dequant_matmul", "dispatch_stats",
           "reset_dispatch_stats"]

MODE_OFF, MODE_AUTO, MODE_FORCE = 0, 1, 2

# bind-time fingerprint re-applied around a traced body (tracing is
# synchronous in the calling thread, so a threadlocal carries it)
_override = threading.local()


@contextlib.contextmanager
def overriding(fp):
    """Pin routing to a captured ``fingerprint()`` for the duration of
    the block: ``mode``/``block_rows``/``block_seq`` (and everything
    built on them) answer from ``fp`` instead of the live environment.
    Long-lived program holders wrap their traced bodies in this so lazy
    tracing lowers with the routing captured when the program was
    created, not whatever the env says at first-call time.  No-op for
    ``fp=None``."""
    if fp is None:
        yield
        return
    prev = getattr(_override, "fp", None)
    _override.fp = fp
    try:
        yield
    finally:
        _override.fp = prev

# one (block_rows, width) fp32 tile, with the kernel's other operands
# and Mosaic's double buffering on top, must fit the 16 MiB of VMEM a
# v5e kernel may use: the backward kernels compile for the chip with a
# 2 MiB tile (8 x 65536) and run out of VMEM with a 4 MiB one
# (tests/test_chip_compile.py)
_VMEM_TILE_BUDGET = 2 * 1024 * 1024
_FLOAT_DTYPES = ("float32", "bfloat16", "float16")


def mode():
    """0 = off (escape hatch), 1 = auto (TPU only), 2 = force-interpret."""
    fp = getattr(_override, "fp", None)
    if fp is not None:
        return fp[0]
    raw = str(get_env("MXNET_PALLAS")).strip().lower()
    if raw in ("0", "off", "false"):
        return MODE_OFF
    if raw in ("2", "force", "interpret"):
        return MODE_FORCE
    return MODE_AUTO


def kernels_active():
    """Would an eligible pattern route to a Pallas kernel right now?"""
    m = mode()
    if m == MODE_OFF:
        return False
    if m == MODE_FORCE:
        return True
    return _on_tpu()


def interpret_mode():
    """Interpret (True) vs compiled Mosaic (False) for a routed kernel —
    flash_attention's auto rule: compiled on TPU, interpret elsewhere."""
    return not _on_tpu()


def block_rows():
    """Row-block bound for the row-wise kernels (softmax/xent/norms)."""
    fp = getattr(_override, "fp", None)
    if fp is not None:
        return fp[1]
    return max(1, int(get_env("MXNET_PALLAS_BLOCK_ROWS") or 8))


def block_seq():
    """Q/K sequence-block bound for the attention kernel."""
    fp = getattr(_override, "fp", None)
    if fp is not None:
        return fp[2]
    return max(8, int(get_env("MXNET_PALLAS_BLOCK_SEQ") or 128))


def row_block_for(rows, width):
    """Row-block bound for a (rows, width) kernel launch: the configured
    bound shrunk until one fp32 tile fits the VMEM budget (the kernels
    further clamp to a divisor of ``rows`` via ``row_block``)."""
    bound = block_rows()
    while bound > 1 and bound * int(width) * 4 > _VMEM_TILE_BUDGET:
        bound //= 2
    return bound


def fingerprint():
    """Hashable routing identity for program caches that can outlive an
    env flip: (mode, block overrides).  Two calls tracing under
    different fingerprints may lower differently and must not share a
    compiled program."""
    return (mode(), block_rows(), block_seq())


# ---------------------------------------------------------------------------
# Eligibility (static shape/dtype rules — docs/architecture/pallas_kernels.md)
# ---------------------------------------------------------------------------
def eligible_rowwise(rows, width, dtype):
    """May a (rows, width) row-wise pattern run as a VMEM-blocked kernel?

    * floating dtype the MXU/VPU handles (fp32/bf16/fp16);
    * width >= 2 (degenerate single-class rows stay with XLA);
    * one fp32 tile within the VMEM budget at SOME divisor block size
      (row_block degrades the block, so rows never disqualify);
    * compiled Mosaic additionally wants the lane dimension aligned
      (width % 128 == 0) and a row block it can tile — a multiple of 8
      or all the rows (``mosaic_block_ok``): 12 rows would tile by 6 and
      are left to XLA.  Interpret mode takes any width and block.
    """
    if str(dtype) not in _FLOAT_DTYPES:
        return False
    rows, width = int(rows), int(width)
    if rows < 1 or width < 2:
        return False
    if width * 4 > _VMEM_TILE_BUDGET:  # even a 1-row tile would not fit
        return False
    if interpret_mode():
        return True
    return width % 128 == 0 and mosaic_block_ok(
        row_block(rows, row_block_for(rows, width)), rows)


def eligible_attention(b, h, lq, lk, d, dtype):
    """May a [B, H, L, D] attention pattern run as the flash kernel?

    Sequence lengths must tile exactly by the (clamped) block size —
    flash_attention asserts divisibility — and compiled Mosaic must
    accept that block (``mosaic_block_ok``); head dim is kept within one
    VMEM-friendly tile.
    """
    if str(dtype) not in _FLOAT_DTYPES:
        return False
    bs = block_seq()
    for length in (int(lq), int(lk)):
        if length < 1 or length % min(bs, length) != 0:
            return False
        if not interpret_mode() and not mosaic_block_ok(min(bs, length),
                                                        length):
            return False
    if int(d) < 1 or int(d) > 512:
        return False
    return int(b) >= 1 and int(h) >= 1


def _decode_attention_ok(b, h, lq, lk, d, dtype):
    """The rules the offset and paged decode kernels share: a floating
    dtype, non-empty shapes, head dim within one VMEM-friendly tile, and
    — compiled — a Q block Mosaic tiles."""
    if str(dtype) not in _FLOAT_DTYPES:
        return False
    if min(int(b), int(h), int(lq), int(lk)) < 1 or not 1 <= int(d) <= 512:
        return False
    return interpret_mode() or mosaic_block_ok(
        divisor_block(lq, block_seq()), int(lq))


def eligible_attention_offset(b, h, lq, lk, d, dtype):
    """May an offset-causal attention pattern (the decode path) run as
    ``flash_attention_offset``?

    Looser than :func:`eligible_attention`: the offset kernel degrades
    its blocks to *divisors* of the sequence lengths
    (``flash_attention.divisor_block``), so KV-cache bucket lengths
    (multiples of ``MXNET_SERVE_KV_BLOCK``, not of the configured
    sequence block) never disqualify in interpret mode.  Compiled Mosaic
    must accept the divisor it lands on (``mosaic_block_ok``): a
    1088-token cache tiles by 64, a 300-token one has no divisor that is
    a multiple of 8 and is left to XLA.
    """
    if not _decode_attention_ok(b, h, lq, lk, d, dtype):
        return False
    return interpret_mode() or mosaic_block_ok(
        divisor_block(lk, block_seq()), int(lk))


def eligible_attention_paged(b, h, lq, lk, d, dtype, block_size):
    """May a paged-KV attention pattern (block tables over a global
    pool) run as ``flash_attention_paged``?

    ``lk`` is the logical length the table addresses (table width ×
    block size).  The K/V tile is one ``block_size``-token pool block
    and the Q tile a divisor of ``lq`` (the rows of a Q tile: a
    grouped-query caller counts the query heads of a pool head into
    it); compiled Mosaic must accept both
    (``mosaic_block_ok`` — ``MXNET_SERVE_KV_BLOCK=4`` is left to XLA).
    """
    bs = int(block_size)
    if bs < 1 or not _decode_attention_ok(b, h, lq, lk, d, dtype):
        return False
    # the pool axis is num_blocks * bs: a block is never "the whole axis"
    # (a two-byte tile packs 16 rows a sublane tile)
    return interpret_mode() or \
        bs % (8 if str(dtype) == "float32" else 16) == 0


def eligible_mla_paged(b, h, lq, lk, d, rank, dtype, block_size):
    """May an absorbed-form latent attention pattern (every head over
    one shared latent row a token) run as ``mla_paged_attention``?

    ``d`` is the latent row's width and ``rank`` the part of it that is
    also the value.  The Q tile is a divisor of ``h * lq`` rows (all
    heads and queries of a sequence flattened), the latent tile one
    pool block.  Compiled Mosaic wants both widths in whole 128-lane
    tiles (an unaligned minor dimension also makes XLA keep the pool
    tokens-minor and relay it for the call: ``deepseek_v3`` pads its
    576-value row to 640), a pool block it can tile, and a Q tile it
    can tile.
    """
    bs = int(block_size)
    if str(dtype) not in _FLOAT_DTYPES or bs < 1:
        return False
    if min(int(b), int(h), int(lq), int(lk)) < 1 or \
            not 1 <= int(rank) <= int(d):
        return False
    if interpret_mode():
        return True
    rows = int(h) * int(lq)
    return (int(d) % 128 == 0 and int(rank) % 128 == 0 and bs % 16 == 0
            and mosaic_block_ok(divisor_block(rows, 512), rows))


def eligible_dsa_index(b, lq, heads, lk, d, dtype, block_size):
    """May the lightning indexer's scores over a paged index-key leaf
    run as ``dsa_index_scores``?  A Q tile is every one of ``heads``
    heads of a divisor of ``lq`` queries (8 of them, or all), the key
    tile one pool block of ``d`` values.  Compiled Mosaic wants ``d``
    in whole 128-lane tiles, the heads in whole sublane tiles, a pool
    block it can tile and whole queries it can tile (8, or the chunk)."""
    bs = int(block_size)
    if str(dtype) not in _FLOAT_DTYPES or bs < 1 or \
            min(int(b), int(lq), int(heads), int(lk), int(d)) < 1:
        return False
    if interpret_mode():
        return True
    return (int(d) % 128 == 0 and int(heads) % 8 == 0 and bs % 16 == 0
            and mosaic_block_ok(divisor_block(lq, 8), int(lq)))


def eligible_dsa_select(rows, width, k, dtype):
    """May the exact top-``k`` of ``rows`` rows of ``width`` fp32
    scores run as ``dsa_select_threshold``?  A tile is 8 rows (or all)
    of the whole width.  Compiled Mosaic wants the width in whole
    128-lane tiles, a row tile it can tile, and the tile (with its
    integer keys and a comparison's result beside it) within the VMEM
    budget."""
    if str(dtype) != "float32" or min(int(rows), int(width)) < 1 \
            or not 1 <= int(k) <= int(width):
        return False
    if interpret_mode():
        return True
    br = divisor_block(rows, 8)
    return (int(width) % 128 == 0 and mosaic_block_ok(br, int(rows))
            and br * int(width) * 4 <= _VMEM_TILE_BUDGET)


def eligible_dsa_attention(n, h, k, d, rank, dtype):
    """May latent attention over ``k`` gathered rows a query (``n``
    queries of ``h`` heads) run as ``dsa_mla_attention``?  The Q tile
    is a query's heads, the row tile a divisor of ``k``.  Compiled
    Mosaic wants both widths in whole 128-lane tiles and heads and row
    tile it can tile."""
    if str(dtype) not in _FLOAT_DTYPES or \
            min(int(n), int(h), int(k)) < 1 or \
            not 1 <= int(rank) <= int(d):
        return False
    if interpret_mode():
        return True
    return (int(d) % 128 == 0 and int(rank) % 128 == 0
            and int(h) % 8 == 0
            and divisor_block(k, 1024) % 16 == 0)


def eligible_moe_experts(n, d, f, dtype):
    """May an expert layer's held part run as the sorted, grouped
    product (``ops/moe.py`` over ``grouped_matmul.py``)?  ``n`` sorted
    rows (tokens x picks), hidden width ``d``, expert width ``f``.
    Compiled, the kernel wants both widths in whole 128-lane tiles, a
    row tile Mosaic can tile, and either contraction, whole, by one
    lane tile of columns inside its weight tile; the interpreter takes
    any shape."""
    if str(dtype) not in _FLOAT_DTYPES or min(int(n), int(d), int(f)) < 1:
        return False
    if interpret_mode():
        return True
    from . import grouped_matmul as gm
    size = 2 if str(dtype) in ("bfloat16", "float16") else 4
    return (int(d) % 128 == 0 and int(f) % 128 == 0
            and mosaic_block_ok(gm.row_tile(int(n)), int(n))
            and gm.contraction_fits(int(d), size)
            and gm.contraction_fits(int(f), size))


def eligible_dequant_matmul(m, n, k, dtype):
    """May an ``x (m, k) @ dequant(codes (n, k))^T`` pattern run as the
    fused int8 dequant-matmul kernel (``dequant_matmul.py``)?

    Blocks degrade to divisors of every dimension
    (``flash_attention.divisor_block``), so odd shapes never disqualify
    — only the activation dtype, a nontrivial reduction (k >= 2; a
    single-column "matmul" stays with XLA) and the VMEM tile budget
    remain.  Compiled Mosaic additionally wants the lane dimensions
    aligned off-interpret: k % 128 == 0 (int8 codes tile at (32, 128)),
    an ``n`` block that is a multiple of 128 or all of ``n`` (it is the
    lane dim of the scale row and of the output), and an ``m`` block it
    can tile (``mosaic_block_ok``).
    """
    if str(dtype) not in _FLOAT_DTYPES:
        return False
    m, n, k = int(m), int(n), int(k)
    if m < 1 or n < 1 or k < 2:
        return False
    bs = block_seq()
    bm, bn, bk = min(bs, m), min(bs, n), min(bs, k)
    # per grid cell: fp32 x tile (bm, bk) + code tile (bn, bk) widened
    # to fp32 on-tile + fp32 accumulator scratch (bm, bn) — the code
    # tile scales with n, not m, so a small-m (decode-step) matmul
    # must still account for it
    if 4 * (bm * bk + bn * bk + bm * bn) > _VMEM_TILE_BUDGET:
        return False
    if interpret_mode():
        return True
    bn = divisor_block(n, bs)
    return (k % 128 == 0 and (bn == n or bn % 128 == 0)
            and mosaic_block_ok(divisor_block(m, bs), m))


# ---------------------------------------------------------------------------
# Routing decisions (+ trace-time counters, banked by the bench rows)
# ---------------------------------------------------------------------------
_stats_lock = threading.Lock()
_stats: dict = {}


def _note(kind):
    with _stats_lock:
        _stats[kind] = _stats.get(kind, 0) + 1


def dispatch_stats():
    """{op kind: times routed to a Pallas kernel at trace time}."""
    with _stats_lock:
        return dict(_stats)


def reset_dispatch_stats():
    with _stats_lock:
        _stats.clear()


def use_rowwise(kind, rows, width, dtype):
    """Route decision for a row-wise pattern; counts a route when taken."""
    if not kernels_active() or not eligible_rowwise(rows, width, dtype):
        return False
    _note(kind)
    return True


def use_dequant_matmul(kind, m, n, k, dtype):
    """Route decision for an int8 dequant-matmul pattern; counts a
    route when taken."""
    if not kernels_active() or not eligible_dequant_matmul(m, n, k,
                                                           dtype):
        return False
    _note(kind)
    return True


def use_attention(kind, b, h, lq, lk, d, dtype, offset=False):
    """Route decision for an attention pattern; counts a route when
    taken.  ``offset=True`` selects the offset-causal decode variant's
    (looser) eligibility rules."""
    elig = eligible_attention_offset if offset else eligible_attention
    if not kernels_active() or not elig(b, h, lq, lk, d, dtype):
        return False
    _note(kind)
    return True


def use_attention_paged(kind, b, h, lq, lk, d, dtype, block_size):
    """Route decision for a paged-KV attention pattern; counts a route
    when taken."""
    if not kernels_active() or not eligible_attention_paged(
            b, h, lq, lk, d, dtype, block_size):
        return False
    _note(kind)
    return True


def use_mla_paged(kind, b, h, lq, lk, d, rank, dtype, block_size):
    """Route decision for a paged latent-attention pattern; counts a
    route when taken."""
    if not kernels_active() or not eligible_mla_paged(
            b, h, lq, lk, d, rank, dtype, block_size):
        return False
    _note(kind)
    return True


def use_dsa_index(kind, b, lq, heads, lk, d, dtype, block_size):
    """Route decision for the lightning indexer's paged scores; counts
    a route when taken."""
    if not kernels_active() or not eligible_dsa_index(
            b, lq, heads, lk, d, dtype, block_size):
        return False
    _note(kind)
    return True


def use_dsa_select(kind, rows, width, k, dtype):
    """Route decision for the exact top-k threshold; counts a route
    when taken."""
    if not kernels_active() or not eligible_dsa_select(rows, width, k,
                                                       dtype):
        return False
    _note(kind)
    return True


def use_dsa_attention(kind, n, h, k, d, rank, dtype):
    """Route decision for latent attention over gathered rows; counts
    a route when taken."""
    if not kernels_active() or not eligible_dsa_attention(
            n, h, k, d, rank, dtype):
        return False
    _note(kind)
    return True


def use_moe_experts(kind, n, d, f, dtype):
    """Route decision for an expert layer's grouped product; counts a
    route when taken."""
    if not kernels_active() or not eligible_moe_experts(n, d, f, dtype):
        return False
    _note(kind)
    return True
