"""Distributed transformer LM: the composition flagship for dp/tp/sp/ep.

No reference counterpart (the reference's sequence model is the LSTM LM,
SURVEY.md §2.7) — this is the beyond-reference long-context/distributed
workload the TPU build treats as first-class.  One training step composes:

* **dp**   — batch sharded over the ``dp`` mesh axis
* **sp**   — sequence sharded over ``sp``; attention is ring attention
             (``sp.ring_attention``: blockwise flash + ppermute K/V ring)
* **tp**   — attention heads and MLP hidden sharded over ``tp``
             (Megatron column/row split, expressed as shardings)
* **ep**   — optional MoE FFN layers with experts sharded over ``tp``
             (expert axis rides the same ICI ring; all-to-all dispatch)

The whole step runs inside ONE ``shard_map`` over the (dp, sp, tp) mesh —
manual collectives only where semantics demand them (ring ppermute, MoE
all_to_all, final grad psums); everything else is local math XLA fuses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from .sp import ring_attention
from .ep import moe_ffn, init_moe_params


__all__ = ["TransformerConfig", "init_transformer_params",
           "transformer_loss", "TransformerTrainer"]


class TransformerConfig:
    def __init__(self, vocab=128, d_model=64, n_heads=4, n_layers=2,
                 d_ff=128, max_len=256, moe_layers=(), n_experts=0,
                 capacity_factor=2.0, dtype=jnp.float32,
                 compute_dtype=None, remat=False):
        assert d_model % n_heads == 0
        self.vocab = vocab
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.d_ff = d_ff
        self.max_len = max_len
        self.moe_layers = set(moe_layers)
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.dtype = dtype
        self.compute_dtype = compute_dtype or dtype
        self.remat = remat
        self.d_head = d_model // n_heads


def _norm_scale_init(shape, dtype):
    return jnp.ones(shape, dtype)


def init_transformer_params(rng, cfg):
    """Parameter pytree. Leading-axis conventions chosen so tp sharding is
    a plain leading/trailing-dim split (see ``param_specs``)."""
    params = {"embed": None, "pos": None, "blocks": [], "ln_f": None}
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    params["embed"] = (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                                         cfg.dtype) * 0.02)
    params["pos"] = (jax.random.normal(keys[1], (cfg.max_len, cfg.d_model),
                                       cfg.dtype) * 0.02)
    params["ln_f"] = _norm_scale_init((cfg.d_model,), cfg.dtype)
    s = (1.0 / cfg.d_model) ** 0.5
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 6)
        blk = {
            "ln1": _norm_scale_init((cfg.d_model,), cfg.dtype),
            "ln2": _norm_scale_init((cfg.d_model,), cfg.dtype),
            # qkv: [d_model, 3, H, d_head] — H is the tp-sharded axis
            "qkv": jax.random.normal(
                k[0], (cfg.d_model, 3, cfg.n_heads, cfg.d_head),
                cfg.dtype) * s,
            # out proj: [H, d_head, d_model] — row-parallel (psum after)
            "proj": jax.random.normal(
                k[1], (cfg.n_heads, cfg.d_head, cfg.d_model),
                cfg.dtype) * s,
        }
        if i in cfg.moe_layers and cfg.n_experts > 0:
            blk["moe"] = init_moe_params(k[2], cfg.d_model, cfg.d_ff,
                                         cfg.n_experts, cfg.dtype)
        else:
            blk["w1"] = jax.random.normal(
                k[3], (cfg.d_model, cfg.d_ff), cfg.dtype) * s
            blk["b1"] = jnp.zeros((cfg.d_ff,), cfg.dtype)
            blk["w2"] = jax.random.normal(
                k[4], (cfg.d_ff, cfg.d_model),
                cfg.dtype) * (1.0 / cfg.d_ff) ** 0.5
            blk["b2"] = jnp.zeros((cfg.d_model,), cfg.dtype)
        params["blocks"].append(blk)
    return params


def param_specs(cfg):
    """PartitionSpec pytree matching ``init_transformer_params`` output:
    heads / ff-hidden / experts sharded over ``tp``, rest replicated."""
    blocks = []
    for i in range(cfg.n_layers):
        blk = {
            "ln1": P(), "ln2": P(),
            "qkv": P(None, None, "tp", None),
            "proj": P("tp", None, None),
        }
        if i in cfg.moe_layers and cfg.n_experts > 0:
            blk["moe"] = {"gate": P(), "w1": P("tp", None, None),
                          "b1": P("tp", None), "w2": P("tp", None, None),
                          "b2": P("tp", None)}
        else:
            blk.update({"w1": P(None, "tp"), "b1": P("tp"),
                        "w2": P("tp", None), "b2": P()})
        blocks.append(blk)
    return {"embed": P(), "pos": P(), "ln_f": P(), "blocks": blocks}


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale


def _block_fn(blk, x, cfg, pos0):
    """One transformer block on the LOCAL shard. x: [B_l, L_l, D].
    Attention heads already tp-local; sequence ring over 'sp'."""
    h = _rmsnorm(x, blk["ln1"])
    qkv = jnp.einsum("bld,dthk->tbhlk", h, blk["qkv"])   # [3,B,H_l,L_l,dh]
    q, k, v = qkv[0], qkv[1], qkv[2]
    att = ring_attention(q, k, v, axis_name="sp", causal=True)
    att = jnp.einsum("bhlk,hkd->bld", att, blk["proj"])
    # heads are tp-sharded -> partial sums; row-parallel reduce over tp
    att = jax.lax.psum(att, "tp")
    x = x + att

    h = _rmsnorm(x, blk["ln2"])
    aux = 0.0
    if "moe" in blk:
        B, L, D = h.shape
        T = B * L
        ep = jax.lax.axis_size("tp")
        rank = jax.lax.axis_index("tp")
        if T % ep != 0:
            raise ValueError(
                "MoE layer: local token count %d (batch %d x seq %d) must "
                "be divisible by the tp/expert axis size %d — trailing "
                "tokens would silently skip the FFN" % (T, B, L, ep))
        chunk = T // ep
        flat = h.reshape(T, D)
        # genuine expert parallelism: each tp rank owns a distinct token
        # chunk (no redundant gating compute, grads come out 1x)
        local = jax.lax.dynamic_slice_in_dim(flat, rank * chunk, chunk, 0)
        y_local, aux = moe_ffn(local, blk["moe"], axis_name="tp",
                               capacity_factor=cfg.capacity_factor,
                               frac_axis_names=("dp", "sp", "tp"))
        # exit `g`: scatter into the full buffer + psum (== all-gather
        # forward, identity backward — each rank's chunk cotangent is 1x)
        y = jnp.zeros((T, D), y_local.dtype)
        y = jax.lax.dynamic_update_slice_in_dim(y, y_local, rank * chunk, 0)
        y = jax.lax.psum(y, "tp").reshape(B, L, D)
        # aux is already pmean'd over the expert axis inside moe_ffn
    else:
        # column-parallel w1 (+sharded bias), row-parallel w2, psum
        y = jax.nn.gelu(jnp.einsum("bld,df->blf", h, blk["w1"])
                        + blk["b1"])
        y = jnp.einsum("blf,fd->bld", y, blk["w2"])
        y = jax.lax.psum(y, "tp") + blk["b2"]
    return x + y, aux


def transformer_loss(params, tokens, targets, cfg):
    """Local-shard loss body — call INSIDE shard_map over (dp, sp, tp).

    tokens/targets: [B_local, L_local] int32, batch over dp, seq over sp.
    Returns mean next-token cross-entropy (psum'd to a global scalar).
    """
    sp_idx = jax.lax.axis_index("sp")
    B, L = tokens.shape
    pos0 = sp_idx * L
    cdt = cfg.compute_dtype
    x = params["embed"][tokens] + jax.lax.dynamic_slice_in_dim(
        params["pos"], pos0, L, 0)
    x = x.astype(cdt)
    aux_total = 0.0
    block = _block_fn
    if cfg.remat:
        block = jax.checkpoint(_block_fn, static_argnums=(2,))
    for blk in params["blocks"]:
        blk = jax.tree_util.tree_map(lambda a: a.astype(cdt)
                                     if jnp.issubdtype(a.dtype, jnp.floating)
                                     else a, blk)
        x, aux = block(blk, x, cfg, pos0)
        aux_total = aux_total + aux
    x = _rmsnorm(x, params["ln_f"].astype(cdt))
    logits = jnp.einsum("bld,vd->blv", x,
                        params["embed"].astype(cdt)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    # global mean over (dp × sp × local) tokens; aux_total is already
    # replicated across every mesh axis (moe_ffn averages the balance
    # fractions over frac_axis_names before forming the Switch product)
    loss = jax.lax.pmean(jax.lax.pmean(jnp.mean(nll), "dp"), "sp")
    return loss + 0.01 * aux_total


class TransformerTrainer:
    """Fused train step for the distributed transformer over a
    (dp, sp, tp) mesh: SGD inside the compiled program, params sharded per
    ``param_specs``, batch over dp, sequence over sp."""

    def __init__(self, cfg, mesh, lr=0.1, seed=0):
        self.cfg = cfg
        self.mesh = mesh
        self.lr = lr
        params = init_transformer_params(jax.random.key(seed), cfg)
        specs = param_specs(cfg)
        self._specs = specs
        self.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            params, specs)
        self._data_spec = P("dp", "sp")

        def step(params, tokens, targets):
            def local_step(params, tokens, targets):
                loss, grads = jax.value_and_grad(transformer_loss)(
                    params, tokens, targets, cfg)
                # Grad-combine rule under JAX's SPMD transpose convention
                # (transpose(psum) = psum: cotangents SUM across ranks,
                # verified empirically): with the loss pmean'd over dp/sp,
                # a param replicated over an axis combines by pmean over
                # that axis; a param SHARDED over an axis comes out
                # inflated by that axis size (the forward psum's transpose
                # summed identical cotangents) -> divide by the size.
                tp_size = jax.lax.axis_size("tp")

                def combine(g, spec):
                    g = jax.lax.pmean(jax.lax.pmean(g, "dp"), "sp")
                    if any(ax == "tp" for ax in jax.tree_util.tree_leaves(
                            tuple(spec))):
                        return g / tp_size
                    return jax.lax.pmean(g, "tp")

                grads = jax.tree_util.tree_map(
                    combine, grads, specs,
                    is_leaf=lambda x: isinstance(x, P))
                new = jax.tree_util.tree_map(
                    lambda p, g: (p - lr * g.astype(p.dtype))
                    if jnp.issubdtype(p.dtype, jnp.floating) else p,
                    params, grads)
                return new, loss

            in_param_specs = specs
            fn = shard_map(
                local_step, mesh=mesh,
                in_specs=(in_param_specs, self._data_spec,
                          self._data_spec),
                out_specs=(in_param_specs, P()), check_vma=False)
            return fn(params, tokens, targets)

        self._step = jax.jit(step, donate_argnums=(0,))

    def step(self, tokens, targets):
        sharding = NamedSharding(self.mesh, self._data_spec)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), sharding)
        targets = jax.device_put(jnp.asarray(targets, jnp.int32), sharding)
        self.params, loss = self._step(self.params, tokens, targets)
        return loss
