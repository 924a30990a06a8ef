"""Plain reference of the ``lm2048`` configuration: a decoder-only
transformer — token embedding, no positional signal, ``num_layers``
pre-norm blocks (RMSNorm eps 1e-6 -> full multi-head causal attention
with scale 1/sqrt(head) and bias-free q/k/v/out projections -> residual;
RMSNorm -> ReLU feed-forward at 4x with biases -> residual), a final
LayerNorm (eps 1e-5, scale and shift) and an untied output head with
bias.  One whole sequence in one forward pass, ``jax.numpy`` float32:
no cache, no paging, no batching, no kernel, nothing of the program.
Parameters carry the symbol graph's argument names (a matmul weight is
``(out, in)``, applied as ``x @ W.T``).

The caller sets ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """name -> shape of every learned argument."""
    d, v = int(cfg["num_hidden"]), int(cfg["vocab_size"])
    f = int(cfg["ffn_mult"]) * d
    shapes = {"embed_weight": (v, d), "final_ln_gamma": (d,),
              "final_ln_beta": (d,), "pred_weight": (v, d),
              "pred_bias": (v,)}
    for i in range(int(cfg["num_layers"])):
        b = "blk%d_" % i
        shapes.update({
            b + "ln1_gamma": (d,), b + "q_weight": (d, d),
            b + "k_weight": (d, d), b + "v_weight": (d, d),
            b + "proj_weight": (d, d), b + "ln2_gamma": (d,),
            b + "ffn1_weight": (f, d), b + "ffn1_bias": (f,),
            b + "ffn2_weight": (d, f), b + "ffn2_bias": (d,)})
    return shapes


def _rms(x, gamma):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + 1e-6) * gamma


def logits(p, tokens, cfg, dtype=jnp.float32):
    """Next-token logits (T, vocab) at every position of ``tokens``
    (T,).  ``dtype`` other than float32 computes the whole pass in that
    type (the lower-precision control)."""
    d, h = int(cfg["num_hidden"]), int(cfg["num_heads"])
    dh = d // h
    t = tokens.shape[0]
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    x = p["embed_weight"][tokens]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(int(cfg["num_layers"])):
        b = "blk%d_" % i
        a = _rms(x, p[b + "ln1_gamma"])
        q, k, v = ((a @ p[b + n].T).reshape(t, h, dh)
                   for n in ("q_weight", "k_weight", "v_weight"))
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
            jnp.asarray(dh, dtype))
        s = jnp.where(causal[None], s, -jnp.inf)
        w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)
        att = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, d)
        x = x + att @ p[b + "proj_weight"].T
        f = _rms(x, p[b + "ln2_gamma"])
        f = jax.nn.relu(f @ p[b + "ffn1_weight"].T + p[b + "ffn1_bias"])
        x = x + f @ p[b + "ffn2_weight"].T + p[b + "ffn2_bias"]
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["final_ln_gamma"] \
        + p["final_ln_beta"]
    return (x @ p["pred_weight"].T + p["pred_bias"]).astype(jnp.float32)


def served_gaps(p, tokens, first, served, cfg):
    """How far each served token lies below the reference's best.

    ``tokens`` (T,) is prompt + served tokens, padded; the served token
    ``served[j]`` was produced from position ``first + j`` (``served``
    may be padded: rows past the sequence repeat its last position).
    Returns
    ``(gap (n,), best (n,))``: the reference's top logit minus the
    served token's logit, and the reference's own first choice."""
    z = logits(p, tokens, cfg)
    rows = z[jnp.clip(first + jnp.arange(served.shape[0]), 0,
                      tokens.shape[0] - 1)]
    top = jnp.max(rows, axis=-1)
    mine = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
    return top - mine, jnp.argmax(rows, axis=-1)
