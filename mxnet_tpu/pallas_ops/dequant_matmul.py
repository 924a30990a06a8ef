"""Int8 weight-only quantization: codec + fused dequant-matmul kernel.

Serving weights are read-only, so their precision is a *storage*
decision: symmetric per-row int8 codes plus an fp32 scale per output
row keep matmul results within ~0.4% of fp32 at a quarter of the
resident bytes (and a quarter of the HBM traffic per tile on a chip).
The plane has three layers:

* **codec** — :func:`quantize_int8` / :func:`dequantize_int8`, a pure
  numpy/jax transform (``kvstore_codec.py``'s discipline: exact size
  accounting, deterministic, no framework state).  Granularity is
  ``'row'`` (one scale per output row, the default — per-row absmax
  keeps badly-scaled rows from poisoning the whole tensor) or
  ``'tensor'`` (one scalar, ``MXNET_SERVE_INT8_GRANULARITY``);
* **carrier** — :class:`QuantizedWeight`, a pytree-registered
  ``(codes, scales)`` pair that travels through program-store param
  dicts, ``tree_map`` spec construction and jit boundaries like any
  array, so quantized weights remain program ARGUMENTS (one resident
  copy shared across every compiled bucket);
* **kernel** — :func:`dequant_matmul`, ``y = x @ dequant(W)^T`` with
  the dequant fused INTO the matmul tile loop: int8 code tiles travel
  to VMEM (4x less bandwidth than fp32 weights), are widened to fp32
  on-tile, accumulated in fp32 across the K grid dimension, and the
  per-row scale is applied ONCE at the final K step — never a
  materialized fp32 copy of the weight.  Compiled Mosaic on TPU,
  interpret mode elsewhere (CPU tests run the real kernel body);
  :func:`dequant_matmul_dense` is the XLA twin (same math, scale after
  the matmul) and the ``MXNET_PALLAS=0`` escape hatch.

Routing follows the plane's idiom: the door consults
``dispatch.use_dequant_matmul`` at trace time, and every program cache
that can outlive an ``MXNET_PALLAS`` flip already carries
``dispatch.fingerprint()`` in its key — a flip recompiles, never serves
a stale lowering.  Forward-only (serving never differentiates through
frozen weights).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..base import MXNetError, get_env
from .flash_attention import (_SEQ_GRID, _vmem_spec as _spec,
                              divisor_block, pltpu)

__all__ = ["quantize_int8", "dequantize_int8", "QuantizedWeight",
           "dequant_matmul", "dequant_matmul_dense"]


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------
def scale_granularity():
    """``'row'`` (default) or ``'tensor'`` —
    ``MXNET_SERVE_INT8_GRANULARITY``."""
    g = str(get_env("MXNET_SERVE_INT8_GRANULARITY") or "row").lower()
    if g not in ("row", "tensor"):
        raise MXNetError(
            "MXNET_SERVE_INT8_GRANULARITY must be 'row' or 'tensor', "
            "got %r" % g)
    return g


def quantize_int8(w, granularity=None):
    """Symmetric absmax int8 quantization of a 2D weight.

    ``granularity='row'`` -> ``codes (N, K) int8``, ``scales (N,) f32``
    (one scale per OUTPUT row — FullyConnected weights are ``(out,
    in)``, so dequant composes with the matmul as a per-column scale of
    the product); ``'tensor'`` -> one scalar scale.  All-zero rows get
    scale 1 (codes are zero anyway).  Exact round-trip bound:
    ``|w - codes*scale| <= scale/2``."""
    w = np.asarray(w, np.float32)
    if w.ndim != 2:
        raise MXNetError("quantize_int8 wants a 2D weight, got shape %s"
                         % (w.shape,))
    g = granularity or scale_granularity()
    absmax = np.abs(w).max(axis=1) if g == "row" else \
        np.asarray(np.abs(w).max())
    scales = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    codes = np.clip(np.rint(w / scales.reshape(-1, 1)
                            if g == "row" else w / scales),
                    -127, 127).astype(np.int8)
    return codes, scales


def dequantize_int8(codes, scales):
    """Exact inverse transform (up to the rounding the encode paid):
    fp32 ``codes * scales`` with row scales broadcast over columns."""
    c = jnp.asarray(codes).astype(jnp.float32)
    s = jnp.asarray(scales, jnp.float32)
    return c * (s.reshape(-1, 1) if s.ndim else s)


@jax.tree_util.register_pytree_node_class
class QuantizedWeight:
    """``(codes int8, scales fp32)`` carrier for a quantized 2D weight.

    Registered as a pytree so it flows through program-store param
    dicts, ``tree_map``-built AOT specs and jit argument lists exactly
    like a plain array; consumers (``FullyConnected``'s lowering, the
    transformer decode graphs) route it through :func:`dequant_matmul`.
    """

    __slots__ = ("codes", "scales")

    def __init__(self, codes, scales):
        self.codes = codes
        self.scales = scales

    @property
    def shape(self):
        return tuple(self.codes.shape)

    @property
    def dtype(self):  # storage dtype, for stats/diagnostics
        return jnp.dtype(jnp.int8)

    def dequantize(self):
        return dequantize_int8(self.codes, self.scales)

    def tree_flatten(self):
        return (self.codes, self.scales), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return "QuantizedWeight(%s, scales=%s)" % (
            getattr(self.codes, "shape", "?"),
            getattr(self.scales, "shape", "?"))


# ---------------------------------------------------------------------------
# fused kernel
# ---------------------------------------------------------------------------
def _dqmm_kernel(x_ref, c_ref, s_ref, o_ref, acc_ref, *, nk):
    """One (m-block, n-block, k-block) grid cell of
    ``y = x @ dequant(codes)^T``.

    The int8 code tile is widened to fp32 on-tile and dotted against
    the x tile with fp32 accumulation in VMEM scratch across the
    sequential k dimension; the per-row scale multiplies the finished
    accumulator ONCE on the last k step (scales distribute over the K
    sum, so late application is exact and saves nk-1 multiplies)."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (BM, BK)
    c = c_ref[...].astype(jnp.float32)          # (BN, BK) widened codes
    acc_ref[:] += jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (BM, BN)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[:] *
                      s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _dqmm_pallas(x, codes, scales, block_m, block_n, block_k, interpret):
    M, K = x.shape
    N = codes.shape[0]
    bm = divisor_block(M, block_m)
    bn = divisor_block(N, block_n)
    bk = divisor_block(K, block_k)
    nk = K // bk
    srow = jnp.broadcast_to(jnp.asarray(scales, jnp.float32).reshape(-1),
                            (N,)).reshape(1, N)

    in_specs = [
        _spec((bm, bk), lambda i, j, k: (i, k)),   # x tile
        _spec((bn, bk), lambda i, j, k: (j, k)),   # int8 code tile
        _spec((1, bn), lambda i, j, k: (0, j)),    # row scales
    ]
    return pl.pallas_call(
        functools.partial(_dqmm_kernel, nk=nk),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        grid=(M // bm, N // bn, nk),
        in_specs=in_specs,
        out_specs=_spec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="dequant_matmul",
        compiler_params=_SEQ_GRID)(x, codes, srow)


def dequant_matmul_dense(x, codes, scales):
    """The XLA twin / ``MXNET_PALLAS=0`` escape hatch: widen-then-dot
    with the scale applied to the product — the SAME association as the
    kernel (scale after the K reduction), so the two lowerings are
    numerical twins."""
    x = jnp.asarray(x).astype(jnp.float32)
    prod = jax.lax.dot_general(
        x, jnp.asarray(codes).astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return prod * jnp.asarray(scales, jnp.float32).reshape(-1)


def dequant_matmul(x, codes, scales, interpret=None):
    """``x (M, K) @ dequant(codes (N, K), scales)^T -> (M, N) fp32`` —
    the door: eligible shapes route to the fused Pallas kernel
    (``dispatch.use_dequant_matmul``), everything else — and
    ``MXNET_PALLAS=0`` — to :func:`dequant_matmul_dense`."""
    from . import dispatch as _pd
    M, K = x.shape
    N = codes.shape[0]
    if _pd.use_dequant_matmul("DequantMatmul", M, N, K, x.dtype):
        if interpret is None:
            interpret = _pd.interpret_mode()
        bs = _pd.block_seq()
        return _dqmm_pallas(x, codes, scales, block_m=bs, block_n=bs,
                            block_k=bs, interpret=bool(interpret))
    return dequant_matmul_dense(x, codes, scales)
