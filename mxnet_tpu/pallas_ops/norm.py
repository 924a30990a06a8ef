"""Fused RMSNorm / LayerNorm Pallas TPU kernels (forward AND backward).

Normalization layers are pure bandwidth: the XLA lowering runs separate
mean/variance reductions, a normalize, and a scale — each re-reading the
activation from HBM — and the autodiff backward re-reads it three more
times.  These kernels do each pass in ONE trip: a (block_rows, width)
tile is pipelined through VMEM, statistics are computed in fp32 on the
tile, and the backward emits dx from the same tile read while the
weight gradients accumulate in fp32 into one VMEM-resident ``(1, width)``
output block across the (sequential) row-block grid axis — a per-block
``(1, width)`` partial over an ``(nb, width)`` array is a block shape the
chip's compiler refuses.

Backward math (per row; ``w = dy * gamma``):

* RMSNorm   ``y = x * r * gamma``, ``r = rsqrt(mean(x^2) + eps)``:
  ``dx = r*w - r^3 * x * mean(w*x)``;  ``dgamma = sum_rows dy * x * r``.
* LayerNorm ``y = xhat * gamma + beta``, ``xhat = (x - mu) * r``,
  ``r = rsqrt(var + eps)``:
  ``dx = r * (w - mean(w) - xhat * mean(w * xhat))``;
  ``dgamma = sum_rows dy * xhat``;  ``dbeta = sum_rows dy``.

Same backend pattern as flash_attention: ``interpret=None`` compiles
through Mosaic on a TPU and interprets elsewhere, so CPU tests execute
the real kernel bodies.
Routing/eligibility lives in :mod:`.dispatch`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _resolve_interpret, _vmem_spec as _spec
from .softmax_xent import row_block

__all__ = ["rms_norm", "layer_norm"]

# the backward's weight-gradient blocks are revisited by every grid
# step, so the row-block axis must run in order on one core
_ACCUMULATING = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _accumulate(ref, value):
    """``ref += value`` across the grid, zero-initialised on step 0."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        ref[...] = jnp.zeros_like(ref)

    ref[...] += value


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def _rms_fwd_kernel(x_ref, g_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)                       # (1, W)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * r * g).astype(o_ref.dtype)


def _rms_bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dg_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = dy * g
    dx = r * w - (r ** 3) * x * jnp.mean(w * x, axis=-1, keepdims=True)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    _accumulate(dg_ref, jnp.sum(dy * x * r, axis=0, keepdims=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rms_norm(x, gamma, eps=1e-6, block_rows=8, interpret=None):
    """RMS normalization of 2D ``x`` over its last axis, scaled by
    ``gamma`` — one fused kernel each way."""
    n, w = x.shape
    br = row_block(n, block_rows)
    return pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=float(eps)),
        out_shape=jax.ShapeDtypeStruct((n, w), x.dtype),
        grid=(n // br,),
        in_specs=[_spec((br, w), lambda i: (i, 0)),
                  _spec((1, w), lambda i: (0, 0))],
        out_specs=_spec((br, w), lambda i: (i, 0)),
        name="rms_norm_fwd",
        interpret=_resolve_interpret(interpret))(x, gamma.reshape(1, w))


def _rms_fwd(x, gamma, eps, block_rows, interpret):
    return rms_norm(x, gamma, eps, block_rows, interpret), (x, gamma)


def _rms_bwd(eps, block_rows, interpret, res, dy):
    x, gamma = res
    n, w = x.shape
    br = row_block(n, block_rows)
    dx, dg = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=float(eps)),
        out_shape=(jax.ShapeDtypeStruct((n, w), x.dtype),
                   jax.ShapeDtypeStruct((1, w), jnp.float32)),
        grid=(n // br,),
        in_specs=[_spec((br, w), lambda i: (i, 0)),
                  _spec((1, w), lambda i: (0, 0)),
                  _spec((br, w), lambda i: (i, 0))],
        out_specs=(_spec((br, w), lambda i: (i, 0)),
                   _spec((1, w), lambda i: (0, 0))),
        compiler_params=_ACCUMULATING,
        name="rms_norm_bwd",
        interpret=_resolve_interpret(interpret))(
            x, gamma.reshape(1, w), dy)
    return dx, dg.reshape(w).astype(gamma.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------
def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) * (x - mu), axis=-1, keepdims=True)
    xhat = (x - mu) * jax.lax.rsqrt(var + eps)
    o_ref[...] = (xhat * g + b).astype(o_ref.dtype)


def _ln_bwd_kernel(x_ref, g_ref, dy_ref, dx_ref, dg_ref, db_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) * (x - mu), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * r
    w = dy * g
    dx = r * (w - jnp.mean(w, axis=-1, keepdims=True)
              - xhat * jnp.mean(w * xhat, axis=-1, keepdims=True))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    _accumulate(dg_ref, jnp.sum(dy * xhat, axis=0, keepdims=True))
    _accumulate(db_ref, jnp.sum(dy, axis=0, keepdims=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def layer_norm(x, gamma, beta, eps=1e-5, block_rows=8, interpret=None):
    """Layer normalization of 2D ``x`` over its last axis with affine
    ``gamma``/``beta`` — one fused kernel each way."""
    n, w = x.shape
    br = row_block(n, block_rows)
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=float(eps)),
        out_shape=jax.ShapeDtypeStruct((n, w), x.dtype),
        grid=(n // br,),
        in_specs=[_spec((br, w), lambda i: (i, 0)),
                  _spec((1, w), lambda i: (0, 0)),
                  _spec((1, w), lambda i: (0, 0))],
        out_specs=_spec((br, w), lambda i: (i, 0)),
        name="layer_norm_fwd",
        interpret=_resolve_interpret(interpret))(
            x, gamma.reshape(1, w), beta.reshape(1, w))


def _ln_fwd(x, gamma, beta, eps, block_rows, interpret):
    return (layer_norm(x, gamma, beta, eps, block_rows, interpret),
            (x, gamma))


def _ln_bwd(eps, block_rows, interpret, res, dy):
    x, gamma = res
    n, w = x.shape
    br = row_block(n, block_rows)
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=float(eps)),
        out_shape=(jax.ShapeDtypeStruct((n, w), x.dtype),
                   jax.ShapeDtypeStruct((1, w), jnp.float32),
                   jax.ShapeDtypeStruct((1, w), jnp.float32)),
        grid=(n // br,),
        in_specs=[_spec((br, w), lambda i: (i, 0)),
                  _spec((1, w), lambda i: (0, 0)),
                  _spec((br, w), lambda i: (i, 0))],
        out_specs=(_spec((br, w), lambda i: (i, 0)),
                   _spec((1, w), lambda i: (0, 0)),
                   _spec((1, w), lambda i: (0, 0))),
        compiler_params=_ACCUMULATING,
        name="layer_norm_bwd",
        interpret=_resolve_interpret(interpret))(
            x, gamma.reshape(1, w), dy)
    return (dx, dg.reshape(w).astype(gamma.dtype),
            db.reshape(w).astype(gamma.dtype))


layer_norm.defvjp(_ln_fwd, _ln_bwd)
