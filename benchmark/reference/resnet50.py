"""Plain reference of the ``resnet50`` configuration: pre-activation
ResNet (He et al., arXiv:1603.05027) as MXNet's
``example/image-classification/symbols/resnet.py`` lays it out, written
from that description in ``jax.numpy`` / ``jax.lax`` float32 — no
package op, no kernel, nothing the program made.  Parameters carry the
symbol graph's argument names so that one seeded draw feeds both sides.

Training-mode BatchNorm (batch statistics, biased variance, eps 2e-5;
``bn_data`` has its scale fixed at one), mean softmax cross-entropy,
and SGD with momentum as MXNet defines it::

    g = grad(mean loss) + wd * w      (wd only on *_weight and *_gamma)
    m = momentum * m - lr * g
    w = w + m

The caller sets ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_UNITS = {18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
          101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}
BN_EPS = 2e-5


def layout(cfg):
    """(units per stage, filters [stem, stage1..4], bottleneck?)."""
    depth = int(cfg["num_layers"])
    bottleneck = depth >= 50
    filters = [64, 256, 512, 1024, 2048] if bottleneck \
        else [64, 64, 128, 256, 512]
    return _UNITS[depth], filters, bottleneck


def _convs(cfg):
    """Every convolution as (name, c_in, c_out, kernel, stride, pad),
    in execution order, with the unit structure the forward walks."""
    units, filters, bottleneck = layout(cfg)
    stem = ("conv0", int(cfg["channels"]), filters[0], 7, 2, 3)
    stages = []
    c_in = filters[0]
    for s, n_units in enumerate(units):
        c_out = filters[s + 1]
        stage = []
        for u in range(n_units):
            name = "stage%d_unit%d" % (s + 1, u + 1)
            stride = (1 if s == 0 else 2) if u == 0 else 1
            if bottleneck:
                mid = c_out // 4
                convs = [(name + "_conv1", c_in, mid, 1, 1, 0),
                         (name + "_conv2", mid, mid, 3, stride, 1),
                         (name + "_conv3", mid, c_out, 1, 1, 0)]
            else:
                convs = [(name + "_conv1", c_in, c_out, 3, stride, 1),
                         (name + "_conv2", c_out, c_out, 3, 1, 1)]
            shortcut = None if u > 0 else \
                (name + "_sc", c_in, c_out, 1, stride, 0)
            stage.append((name, c_in, convs, shortcut))
            c_in = c_out
        stages.append(stage)
    return stem, stages, c_in


def param_shapes(cfg):
    """name -> shape of every learned argument of the symbol graph."""
    stem, stages, c_last = _convs(cfg)
    shapes = {}

    def bn(name, c):
        shapes[name + "_gamma"] = (c,)
        shapes[name + "_beta"] = (c,)

    def conv(spec):
        name, c_in, c_out, k, _, _ = spec
        shapes[name + "_weight"] = (c_out, c_in, k, k)

    bn("bn_data", int(cfg["channels"]))
    conv(stem)
    bn("bn0", stem[2])
    for stage in stages:
        for name, c_in, convs, shortcut in stage:
            bn(name + "_bn1", c_in)
            for i, spec in enumerate(convs):
                conv(spec)
                if i + 1 < len(convs):
                    bn("%s_bn%d" % (name, i + 2), spec[2])
            if shortcut is not None:
                conv(shortcut)
    bn("bn1", c_last)
    shapes["fc1_weight"] = (int(cfg["num_classes"]), c_last)
    shapes["fc1_bias"] = (int(cfg["num_classes"]),)
    return shapes


def aux_shapes(cfg):
    """name -> shape of the BatchNorm moving statistics (state the
    training-mode forward never reads)."""
    out = {}
    for name, shape in param_shapes(cfg).items():
        if name.endswith("_gamma"):
            base = name[:-len("_gamma")]
            out[base + "_moving_mean"] = shape
            out[base + "_moving_var"] = shape
    return out


def _bn(p, x, name, fix_gamma=False):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    xhat = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
    gamma = 1.0 if fix_gamma else p[name + "_gamma"].reshape(1, -1, 1, 1)
    return xhat * gamma + p[name + "_beta"].reshape(1, -1, 1, 1)


def _conv(p, x, spec):
    name, _, _, _, stride, pad = spec
    return jax.lax.conv_general_dilated(
        x, p[name + "_weight"], (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def forward(p, x, cfg):
    """Logits of a batch ``x`` (N, C, H, W), training mode."""
    stem, stages, _ = _convs(cfg)
    relu = jax.nn.relu
    x = _bn(p, x, "bn_data", fix_gamma=True)
    x = relu(_bn(p, _conv(p, x, stem), "bn0"))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              ((0, 0), (0, 0), (1, 1), (1, 1)))
    for stage in stages:
        for name, _, convs, shortcut in stage:
            act = relu(_bn(p, x, name + "_bn1"))
            y = act
            for i, spec in enumerate(convs):
                y = _conv(p, y, spec)
                if i + 1 < len(convs):
                    y = relu(_bn(p, y, "%s_bn%d" % (name, i + 2)))
            x = y + (x if shortcut is None else _conv(p, act, shortcut))
    x = relu(_bn(p, x, "bn1"))
    x = jnp.mean(x, axis=(2, 3))
    return x @ p["fc1_weight"].T + p["fc1_bias"]


def loss(p, x, y, cfg):
    """Mean softmax cross-entropy of labels ``y`` (N,) int."""
    logp = jax.nn.log_softmax(forward(p, x, cfg), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, y.astype(jnp.int32)[:, None], axis=-1))


def sgd_step(p, mom, x, y, cfg, lr, momentum, wd):
    """One training step; returns (params, momenta, loss)."""
    value, grads = jax.value_and_grad(loss)(p, x, y, cfg)
    new_p, new_m = {}, {}
    for name, w in p.items():
        decayed = name.endswith("_weight") or name.endswith("_gamma")
        g = grads[name] + (wd * w if decayed else 0.0)
        new_m[name] = momentum * mom[name] - lr * g
        new_p[name] = w + new_m[name]
    return new_p, new_m, value
