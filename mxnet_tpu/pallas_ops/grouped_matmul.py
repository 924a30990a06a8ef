"""Grouped matrix product over uneven groups of SORTED rows, for the
regime an expert layer's held share lives in: a handful of rows a group
and weights that dwarf the activations.

``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``, where
group ``g`` owns rows ``sum(counts[:g]) .. sum(counts[:g + 1]) - 1``.
At DeepSeek-V3's widths a group's matrix is 59 MB (gate and up) or 29
MB (down) of bfloat16 and its rows some 16 (a prompt chunk) or one or
two (a decode step): the time is the weights' way from HBM, 0.11 ms of
arithmetic against 1.7 ms of bytes a layer.  So the kernel is built
around the weight stream:

* a VISIT is one (row tile, group) pair that holds a live row.  Groups
  are consecutive in the sorted rows, so the visits, in order, walk the
  row tiles once: a group that spans ``t`` row tiles is visited ``t``
  times (its matrix streamed again each time: correct, and rare at a
  few rows a group), a row tile that several groups share is visited
  once a group and stays in VMEM between them.  A group without a row
  is never visited and costs no weight traffic; rows past the last
  group are never written: they hold whatever the buffer held;
* the grid is ``(column tiles, visits)`` and the number of visits is a
  DYNAMIC bound: no grid step exists for a visit that does not.  Each
  visit's group and row tile, and the groups' offsets, ride as
  scalar-prefetch operands and steer the index maps (as the block
  tables do in ``paged_attention.py``);
* the weight tile is the WHOLE contraction by ``block_n`` columns of one
  group's matrix, read in place from the ``(groups, in, out)`` stack.
  A grid step costs some 0.35 us whatever it moves, so the tile is as
  large as :data:`_WEIGHT_TILE_BYTES` allows (3.7 MB at 7168 x 256,
  4.2 MB at 2048 x 1024: 4.5-5 us of DMA at the v5e's 819 GB/s).  One
  product a step, fp32 accumulation inside it over the whole
  contraction: no partial sums between steps, no accumulator;
* the rows of the visit's group — and only those — are stored, so the
  groups that share a row tile fill it in turn.

On a v5e (PR 29's chip runs, 16 groups of bfloat16, 256 live rows of
4,096 and 13 of 512) it streams the touched matrices at 80-87 % of the
HBM's rate where ``jax.lax.ragged_dot`` reads 34-36 %.

:func:`grouped_visits` is the visit count in the graph, what
``moe_expert_streams`` reports.  Forward-only.  The dense twin is
``ops/moe.py::moe_experts_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _resolve_interpret, divisor_block, pltpu

__all__ = ["grouped_matmul", "grouped_visits", "row_tile", "column_tile",
           "contraction_fits", "vmem_bytes"]

_LANES = 128
# one weight tile (it is double-buffered): large enough that its DMA and
# not the grid step sets the pace, small enough that two of them, two
# row tiles and two output tiles stay under 16 MiB of VMEM
_WEIGHT_TILE_BYTES = 4 << 20


def row_tile(rows):
    """Rows a tile: a 32nd of the sorted rows, between 32 and 128.  The
    live rows come first, and a chip that holds a 16th of the experts
    sees a 16th of the rows live: two tiles' worth.  A tile that is too
    small cuts groups in two (each cut streams a matrix again), one
    that is too large multiplies dead rows (128 rows against a weight
    tile already keep the MXU busy half the DMA's time)."""
    return divisor_block(rows, min(128, max(32, rows // 32)))


def contraction_fits(k, itemsize):
    """Does a whole contraction of ``k`` by one lane tile of columns
    fit a weight tile?  (16,384 values of bfloat16.)"""
    return k * itemsize * _LANES <= _WEIGHT_TILE_BYTES


def column_tile(k, n, itemsize):
    """Columns of a weight tile ``(k, columns)``: the most whole lane
    tiles that divide ``n`` within :data:`_WEIGHT_TILE_BYTES`; the whole
    axis where ``n`` is not in lane tiles (the interpreter's shapes)."""
    if n % _LANES:
        return n
    fit = _WEIGHT_TILE_BYTES // (k * itemsize * _LANES)
    return _LANES * divisor_block(n // _LANES, max(1, fit))


def vmem_bytes(rows, k, n, lhs_itemsize, rhs_itemsize):
    """What a call keeps in VMEM: two buffers each of the row tile
    ``(block_m, k)``, the weight tile ``(k, block_n)`` and the fp32
    output tile ``(block_m, block_n)``.  7168 -> 4096 over 4,096 rows of
    bfloat16: 2 x (1.8 + 3.7 + 0.13) MB = 11.3 MB."""
    bm, bn = row_tile(rows), column_tile(k, n, rhs_itemsize)
    return 2 * (bm * k * lhs_itemsize + k * bn * rhs_itemsize
                + bm * bn * 4)


def _visit_plan(counts, rows, block_m):
    """``(offsets (G+1,), group of visit (V,), row tile of visit (V,),
    visits ())``, ``V = rows // block_m + G - 1`` the most there can
    be.  Entries past the last visit are not read."""
    G = counts.shape[0]
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    first = (ends - counts) // block_m
    span = jnp.where(counts > 0, (ends - 1) // block_m - first + 1, 0)
    upto = jnp.cumsum(span)                 # visits of groups 0..g
    v = jnp.arange(rows // block_m + G - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(upto, v, side="right").astype(jnp.int32), G - 1)
    tile = first[group] + v - (upto - span)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile.astype(jnp.int32), upto[-1]


def grouped_visits(counts, rows):
    """(row tile, group) pairs with a live row: how often
    :func:`grouped_matmul` over ``rows`` sorted rows streams a group's
    matrix.  ``sum(counts > 0)`` is its floor."""
    return _visit_plan(counts, rows, row_tile(rows))[3]


def _gmm_kernel(off_ref, grp_ref, tile_ref, x_ref, w_ref, o_ref, *,
                block_m):
    v = pl.program_id(1)
    g = grp_ref[v]
    row = tile_ref[v] * block_m + jax.lax.broadcasted_iota(
        jnp.int32, o_ref.shape, 0)
    mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
    o_ref[...] = jnp.where(
        mine, jnp.dot(x_ref[...], w_ref[0],
                      preferred_element_type=jnp.float32), o_ref[...])


def grouped_matmul(lhs, rhs, counts, interpret=None):
    """``lhs (M, K)`` sorted by group, ``rhs (G, K, N)``, ``counts (G,)``
    int32 rows a group (``sum(counts) <= M``).  Returns ``(M, N)``
    fp32; rows past ``sum(counts)`` hold whatever the buffer held."""
    M, K = lhs.shape
    G, _, N = rhs.shape
    assert rhs.shape[1] == K and counts.shape == (G,)
    wsz = jnp.dtype(rhs.dtype).itemsize
    bm, bn = row_tile(M), column_tile(K, N, wsz)
    offsets, group, tile, visits = _visit_plan(counts, M, bm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N // bn, visits),
        in_specs=[
            pl.BlockSpec((bm, K), lambda n, v, off, grp, til:
                         (til[v], 0)),
            pl.BlockSpec((1, K, bn), lambda n, v, off, grp, til:
                         (grp[v], 0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda n, v, off, grp, til:
                               (til[v], n)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, block_m=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=_resolve_interpret(interpret),
        # the benchmark's readers find the expert layers' product by
        # this prefix (XLA's own grouped product was named so)
        name="ragged-dot_grouped_matmul",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the buffers, and as much again for the product's fp32
            # result and the compiler's own scratch; never under the
            # compiler's default of 16 MiB
            vmem_limit_bytes=max(16 << 20, 2 * vmem_bytes(
                M, K, N, jnp.dtype(lhs.dtype).itemsize, wsz))))(
                    offsets, group, tile, lhs, rhs)
