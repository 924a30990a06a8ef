"""Seeded weights, drawn on the device by one jitted program.

The benchmark makes the weights; the program and the plain reference are
each handed what :func:`draw` returns for the same ``(shapes, seed)``.
The seed is a run-time argument of the compiled program (two uint32
words, so seeds past 2**32 are fine), never a constant baked into it:
every seed of a cell hits the same entry of the compile cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# How a leaf is drawn, decided by the end of its name.  ``fan_in`` is the
# product of all dimensions but the first (rows of a matmul weight, the
# input patch of a convolution): weights are N(0, gain / fan_in), so
# activations keep their scale through the depth; norm scales sit near
# one and shifts and biases near zero but never AT them, so that a path
# which drops one is seen by the reference.
RULES = (
    ("embed_weight", ("normal", 1.0)),
    ("_weight", ("fan_in", None)),
    ("_gamma", ("around", 1.0, 0.1)),
    ("_beta", ("around", 0.0, 0.1)),
    ("_bias", ("around", 0.0, 0.02)),
    ("_moving_mean", ("const", 0.0)),
    ("_moving_var", ("const", 1.0)),
)


def _rule(name):
    for suffix, rule in RULES:
        if name.endswith(suffix):
            return rule
    raise ValueError("no drawing rule for parameter %r" % name)


def seed_words(seed):
    """``seed`` (any whole number >= 0) as a raw threefry key."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0, got %d" % seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


@functools.lru_cache(maxsize=8)
def _drawer(items, gain, dtype, sharding):
    """The jitted program for one ``(names, shapes)`` set."""
    def fn(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        out = {}
        for i, (name, shape) in enumerate(items):
            rule = _rule(name)
            k = jax.random.fold_in(key, i)
            if rule[0] == "const":
                leaf = jnp.full(shape, rule[1], jnp.float32)
            elif rule[0] == "around":
                leaf = rule[1] + rule[2] * jax.random.normal(
                    k, shape, jnp.float32)
            elif rule[0] == "normal":
                leaf = rule[1] * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = int(np.prod(shape[1:])) if len(shape) > 1 \
                    else int(shape[0])
                leaf = jax.random.normal(k, shape, jnp.float32) \
                    * np.float32(np.sqrt(gain / fan_in))
            out[name] = leaf.astype(dtype)
        return out
    return jax.jit(fn, out_shardings=sharding)


def draw(shapes, seed, gain=1.0, dtype="float32", sharding=None):
    """``{name: device array}`` for ``shapes`` (name -> shape), a pure
    function of ``seed``.  ``gain`` scales the fan-in variance (2.0 in
    front of ReLUs, He et al.)."""
    items = tuple((n, tuple(int(d) for d in shapes[n]))
                  for n in sorted(shapes))
    return _drawer(items, float(gain), str(dtype), sharding)(
        seed_words(seed))
