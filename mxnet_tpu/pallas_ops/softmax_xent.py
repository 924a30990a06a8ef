"""Fused softmax / softmax-cross-entropy Pallas TPU kernels.

The loss head is the second hot spot the step-phase profiler names after
attention: the reference hand-fused it in CUDA (``softmax_output.cu`` —
forward softmax and the implicit ``p - onehot`` loss gradient each run as
one kernel over the class dimension).  The XLA lowering materializes the
[rows, classes] probability tensor in HBM between the row-max, exp, sum
and divide; these kernels pipeline one (block_rows, classes) tile through
VMEM per grid cell instead, so at no point does an HBM-resident
intermediate larger than the kernel's own output exist:

* :func:`fused_softmax`       — row softmax, classic vjp as a kernel;
* :func:`softmax_output_head` — SoftmaxOutput's contract: forward emits
  probabilities, backward IGNORES the head cotangent and emits
  ``(p - onehot(label)) * scale`` directly (the implicit-loss gradient),
  both as one-pass kernels;
* :func:`softmax_xent_loss`   — per-row cross-entropy from logits.  The
  forward computes ``logsumexp(x) - x[label]`` per row and NEVER
  materializes the probability tensor (not even in VMEM beyond one
  tile); the backward recomputes the row softmax blockwise and writes
  ``(softmax(x) - onehot) * g`` straight into the gradient.

All three follow flash_attention's pattern: ``interpret=None`` compiles
through Mosaic on a TPU and interprets elsewhere — the quick tier runs
the real kernel bodies on CPU.  Routing lives in :mod:`.dispatch`; nothing here reads
environment state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import (_resolve_interpret, _vmem_spec as _spec,
                              divisor_block)

__all__ = ["fused_softmax", "softmax_output_head", "softmax_xent_loss",
           "row_block"]


# Row-block size <= ``bound`` that divides ``rows`` (Pallas grids need
# exact tiling; a non-dividing bound degrades gracefully): the sequence
# kernels' divisor rule, under the name the row-wise kernels use.
row_block = divisor_block


def _grid_call(kernel, outs, grid, in_specs, out_specs, interpret, *args):
    # one call site, five kernels: each by its function's name
    name = getattr(kernel, "func", kernel).__name__
    return pl.pallas_call(kernel, out_shape=outs, grid=grid,
                          name=name.strip("_").replace("_kernel", ""),
                          in_specs=in_specs, out_specs=out_specs,
                          interpret=_resolve_interpret(interpret))(*args)


# ---------------------------------------------------------------------------
# Kernel bodies (one (block_rows, classes) VMEM tile per grid cell)
# ---------------------------------------------------------------------------
def _softmax_fwd_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _softmax_bwd_kernel(p_ref, dy_ref, o_ref):
    # classic softmax vjp: dx = p * (dy - sum(dy * p))
    p = p_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    dot = jnp.sum(dy * p, axis=-1, keepdims=True)
    o_ref[...] = (p * (dy - dot)).astype(o_ref.dtype)


def _xent_grad_from_probs_kernel(p_ref, l_ref, o_ref, *, scale):
    # implicit-loss gradient of SoftmaxOutput: (p - onehot(label)) * scale
    p = p_ref[...].astype(jnp.float32)
    lbl = l_ref[...].astype(jnp.int32)                       # (br, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    onehot = (cols == lbl).astype(jnp.float32)
    o_ref[...] = ((p - onehot) * scale).astype(o_ref.dtype)


def _xent_loss_kernel(x_ref, l_ref, o_ref):
    # per-row logsumexp(x) - x[label]; probabilities never materialize
    x = x_ref[...].astype(jnp.float32)
    lbl = l_ref[...].astype(jnp.int32)                       # (br, 1)
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)) + m
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    tgt = jnp.sum(jnp.where(cols == lbl, x, 0.0), axis=-1, keepdims=True)
    o_ref[...] = (lse - tgt).astype(o_ref.dtype)


def _xent_loss_grad_kernel(x_ref, l_ref, g_ref, o_ref):
    # d/dx [logsumexp(x) - x[label]] * g = (softmax(x) - onehot) * g
    x = x_ref[...].astype(jnp.float32)
    lbl = l_ref[...].astype(jnp.int32)                       # (br, 1)
    g = g_ref[...].astype(jnp.float32)                       # (br, 1)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == lbl).astype(jnp.float32)
    o_ref[...] = ((p - onehot) * g).astype(o_ref.dtype)


def _rows_call(kernel, x, extras, out_shapes, block_rows, interpret):
    """Launch ``kernel`` over row blocks of 2D ``x``; ``extras`` are
    per-row (N, 1) companions, ``out_shapes`` (width, dtype) pairs."""
    n, w = x.shape
    br = row_block(n, block_rows)
    in_specs = [_spec((br, w), lambda i: (i, 0))]
    args = [x]
    for e in extras:
        in_specs.append(_spec((br, e.shape[1]), lambda i: (i, 0)))
        args.append(e)
    outs = tuple(jax.ShapeDtypeStruct((n, ow), dt) for ow, dt in out_shapes)
    out_specs = tuple(_spec((br, ow), lambda i: (i, 0))
                      for ow, _ in out_shapes)
    if len(outs) == 1:
        outs, out_specs = outs[0], out_specs[0]
    return _grid_call(kernel, outs, (n // br,), in_specs, out_specs,
                      interpret, *args)


# ---------------------------------------------------------------------------
# fused_softmax: row softmax with a kernel vjp
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fused_softmax(x, block_rows=8, interpret=None):
    """Row softmax of a 2D array as one VMEM-blocked kernel."""
    return _rows_call(_softmax_fwd_kernel, x, (),
                      ((x.shape[1], x.dtype),), block_rows, interpret)


def _fused_softmax_fwd(x, block_rows, interpret):
    p = fused_softmax(x, block_rows, interpret)
    return p, p


def _fused_softmax_bwd(block_rows, interpret, p, dy):
    dx = _rows_call(_softmax_bwd_kernel, p, (dy,),
                    ((p.shape[1], p.dtype),), block_rows, interpret)
    return (dx,)


fused_softmax.defvjp(_fused_softmax_fwd, _fused_softmax_bwd)


# ---------------------------------------------------------------------------
# softmax_output_head: the SoftmaxOutput op's fused lowering
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def softmax_output_head(data, label, scale=1.0, block_rows=8,
                        interpret=None):
    """SoftmaxOutput contract: forward = softmax probabilities, backward
    = implicit loss gradient ``(p - onehot(label)) * scale`` regardless
    of the incoming head cotangent (reference softmax_output.cc)."""
    return _rows_call(_softmax_fwd_kernel, data, (),
                      ((data.shape[1], data.dtype),), block_rows,
                      interpret)


def _head_fwd(data, label, scale, block_rows, interpret):
    p = _rows_call(_softmax_fwd_kernel, data, (),
                   ((data.shape[1], data.dtype),), block_rows, interpret)
    return p, (p, label)


def _head_bwd(scale, block_rows, interpret, res, g):
    p, label = res
    lbl2 = label.reshape(label.shape[0], 1)
    grad = _rows_call(
        functools.partial(_xent_grad_from_probs_kernel, scale=float(scale)),
        p, (lbl2,), ((p.shape[1], p.dtype),), block_rows, interpret)
    return grad, jnp.zeros_like(label)


softmax_output_head.defvjp(_head_fwd, _head_bwd)


# ---------------------------------------------------------------------------
# softmax_xent_loss: per-row cross entropy, probabilities never built
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def softmax_xent_loss(logits, label, block_rows=8, interpret=None):
    """Per-row softmax cross-entropy ``logsumexp(x) - x[label]`` of 2D
    logits; returns shape ``(rows,)`` float32.  Neither pass materializes
    the [rows, classes] probability tensor in HBM."""
    lbl2 = label.reshape(label.shape[0], 1)
    out = _rows_call(_xent_loss_kernel, logits, (lbl2,),
                     ((1, jnp.float32),), block_rows, interpret)
    return out[:, 0]


def _loss_fwd(logits, label, block_rows, interpret):
    return (softmax_xent_loss(logits, label, block_rows, interpret),
            (logits, label))


def _loss_bwd(block_rows, interpret, res, g):
    logits, label = res
    lbl2 = label.reshape(label.shape[0], 1)
    g2 = jnp.broadcast_to(g.reshape(-1, 1),
                          (logits.shape[0], 1)).astype(jnp.float32)
    grad = _rows_call(_xent_loss_grad_kernel, logits, (lbl2, g2),
                      ((logits.shape[1], logits.dtype),), block_rows,
                      interpret)
    return grad, jnp.zeros_like(label)


softmax_xent_loss.defvjp(_loss_fwd, _loss_bwd)
