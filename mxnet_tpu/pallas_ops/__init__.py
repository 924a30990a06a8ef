"""Hand-written Pallas TPU kernels for the hot ops.

The XLA compiler fuses the vast majority of what the reference hand-wrote
in CUDA (SURVEY.md §2.2 TPU mapping note); these kernels cover the cases
where explicit VMEM blocking beats XLA's default schedule:

* ``flash_attention``  — online-softmax attention (the quadratic-memory
  pattern XLA will not re-block on its own);
* ``softmax_xent``     — fused softmax / softmax-cross-entropy loss
  heads (forward never materializes the probability tensor);
* ``norm``             — fused RMSNorm / LayerNorm, forward and backward
  each one VMEM trip;
* ``dequant_matmul``   — int8 weight-only serving: per-row dequant fused
  into the matmul tile loop (codes travel to VMEM as int8, fp32
  accumulation, scale applied once at the last K step);
* ``paged_attention``  — block-table flash attention over the serving
  decode plane's paged KV pool (scalar-prefetch tables, dynamic block
  skip — the gather XLA cannot re-block on its own);
* ``grouped_matmul``   — an expert layer's grouped product over uneven
  groups of sorted rows: every group with a row streams its matrix
  once a row tile, in tiles of megabytes (a handful of rows against
  59 MB of weights a group is all weight traffic).

``dispatch`` is the routing seam: eligible op lowerings (the registry
``fcompute`` layer every execution plane traces through) ask it whether
to use the kernel or the plain XLA lowering — ``MXNET_PALLAS=0`` is the
escape hatch (docs/architecture/pallas_kernels.md).
"""
from .dequant_matmul import (QuantizedWeight, dequant_matmul,
                             dequant_matmul_dense, dequantize_int8,
                             quantize_int8)
from .flash_attention import flash_attention
from .norm import layer_norm, rms_norm
from .paged_attention import (flash_attention_paged,
                              paged_attention_reference)
from .softmax_xent import (fused_softmax, softmax_output_head,
                           softmax_xent_loss)
from . import dispatch

__all__ = ["flash_attention", "flash_attention_paged",
           "paged_attention_reference", "fused_softmax",
           "softmax_output_head", "softmax_xent_loss", "rms_norm",
           "layer_norm", "dispatch", "quantize_int8", "dequantize_int8",
           "QuantizedWeight", "dequant_matmul", "dequant_matmul_dense"]
