"""Runtime-compiled custom kernels from python.

Reference: ``python/mxnet/rtc.py`` + ``src/common/mxrtc.cc`` — ``mx.rtc``
let users write raw CUDA source in python, NVRTC-compile it and launch it
on NDArrays (``MXRtc::push``).  The TPU-native equivalent of "write your
own kernel without leaving python" is **Pallas**: the kernel is a python
function over VMEM refs, compiled by Mosaic for the TPU (and runnable in
interpret mode anywhere).

    def kern(x_ref, y_ref, o_ref):
        o_ref[:] = x_ref[:] * y_ref[:] + 1.0

    rtc = mx.rtc.PallasKernel("fma1", kern)
    out = rtc.push([x, y], [mx.nd.empty(x.shape)])

``CudaModule``/``MXRtc``-style raw-CUDA entry points raise with guidance,
mirroring how the reference gates rtc on ``MXNET_USE_CUDA``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .base import MXNetError
from .ndarray import NDArray
from .pallas_ops.flash_attention import _on_tpu

__all__ = ["PallasKernel", "MXRtc"]


class PallasKernel:
    """A user-defined kernel launched on NDArrays.

    ``kernel`` takes one ref per input then one ref per output (Pallas
    convention).  Without explicit specs the whole arrays live in VMEM —
    right for small/medium tensors; pass ``in_specs``/``out_specs``/
    ``grid`` for blocked launches (see the Pallas guide)."""

    def __init__(self, name, kernel, grid=None, in_specs=None,
                 out_specs=None, interpret=None):
        self.name = name
        self.kernel = kernel
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.interpret = interpret
        self._cache = {}

    def _build(self, in_shapes, in_dtypes, out_shapes, out_dtypes):
        key = (tuple(in_shapes), tuple(in_dtypes), tuple(out_shapes),
               tuple(out_dtypes))
        if key in self._cache:
            return self._cache[key]
        interpret = self.interpret
        if interpret is None:
            interpret = not _on_tpu()
        kw = {}
        if self.grid is not None:
            kw["grid"] = self.grid
        if self.in_specs is not None:
            kw["in_specs"] = self.in_specs
        else:
            kw["in_specs"] = [pl.BlockSpec(memory_space=pltpu.VMEM)
                              for _ in in_shapes]
        if self.out_specs is not None:
            kw["out_specs"] = self.out_specs
        else:
            out_sp = [pl.BlockSpec(memory_space=pltpu.VMEM)
                      for _ in out_shapes]
            kw["out_specs"] = out_sp if len(out_sp) > 1 else out_sp[0]
        out_shape = [jax.ShapeDtypeStruct(s, d)
                     for s, d in zip(out_shapes, out_dtypes)]
        fn = pl.pallas_call(
            self.kernel,
            out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
            interpret=interpret, **kw)
        fn = jax.jit(fn)
        self._cache[key] = fn
        return fn

    def push(self, ins, outs, grid_dims=None, block_dims=None):
        """Launch on NDArrays; results are written into ``outs`` (reference
        MXRtc.push signature; grid/block dims are CUDA-isms accepted and
        ignored — Pallas grids come from the constructor specs)."""
        if not isinstance(ins, (list, tuple)):
            ins = [ins]
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        in_vals = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
                   for x in ins]
        fn = self._build([v.shape for v in in_vals],
                         [v.dtype for v in in_vals],
                         [o.shape for o in outs],
                         [o._data.dtype for o in outs])
        res = fn(*in_vals)
        if not isinstance(res, (list, tuple)):
            res = [res]
        for o, r in zip(outs, res):
            o._data = r
        return outs[0] if len(outs) == 1 else outs

    def __call__(self, *ins):
        """Functional form: returns new NDArrays shaped like the inputs
        (elementwise-kernel convenience; use push() for differing output
        shapes)."""
        from .ndarray import empty
        outs = [empty(x.shape, dtype=str(x._data.dtype)) for x in ins[:1]]
        return self.push(list(ins), outs)


class MXRtc:
    """Raw-CUDA rtc of the reference (python/mxnet/rtc.py).  There is no
    NVRTC on TPU; kernels are written in Pallas instead."""

    def __init__(self, name, inputs, outputs, kernel):
        raise MXNetError(
            "mx.rtc with CUDA source requires a CUDA device; on TPU write "
            "the kernel in Pallas and wrap it with mx.rtc.PallasKernel "
            "(see mxnet_tpu/rtc.py docstring)")
