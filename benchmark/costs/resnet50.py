"""Required operations of the ``resnet50`` configuration, from shapes.

Counted: the multiply-accumulates of every convolution and of the
classifier, as 2 FLOPs each.  A training step requires three such
passes per layer — the forward product, the gradient toward the input
and the gradient toward the weight — and every convolution needs all
three here (``bn_data`` in front of the stem has a learned shift, so
even the stem's input gradient is required).  Not counted: BatchNorm,
ReLU, pooling, the loss and the optimizer (bandwidth, not FLOPs), and
anything a compiler chooses to recompute.

Hand-worked case (tests): depth 50 at 3x224x224, 1000 classes, is
4,089,184,256 multiply-accumulates forward, 24.535 GFLOP an image
forward and backward.
"""
from __future__ import annotations

_UNITS = {18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
          101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}


def _out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def forward_macs(cfg):
    """Multiply-accumulates of one image's forward pass."""
    depth = int(cfg["num_layers"])
    bottleneck = depth >= 50
    filters = [64, 256, 512, 1024, 2048] if bottleneck \
        else [64, 64, 128, 256, 512]
    size = int(cfg["image"])
    macs = 0

    def conv(c_in, c_out, kernel, out_size):
        return out_size * out_size * c_out * c_in * kernel * kernel

    size = _out(size, 7, 2, 3)
    macs += conv(int(cfg["channels"]), filters[0], 7, size)
    size = _out(size, 3, 2, 1)                      # max pool
    c_in = filters[0]
    for s, n_units in enumerate(_UNITS[depth]):
        c_out = filters[s + 1]
        for u in range(n_units):
            stride = (1 if s == 0 else 2) if u == 0 else 1
            out_size = _out(size, 3, stride, 1)
            if bottleneck:
                mid = c_out // 4
                macs += conv(c_in, mid, 1, size)
                macs += conv(mid, mid, 3, out_size)
                macs += conv(mid, c_out, 1, out_size)
            else:
                macs += conv(c_in, c_out, 3, out_size)
                macs += conv(c_out, c_out, 3, out_size)
            if u == 0:
                macs += conv(c_in, c_out, 1, out_size)   # shortcut
            c_in, size = c_out, out_size
    return macs + c_in * int(cfg["num_classes"])


def train_flops_per_sample(cfg):
    """FLOPs one image requires in a training step, forward and
    backward, nothing recomputed."""
    return 3 * 2 * forward_macs(cfg)
