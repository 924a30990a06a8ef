"""Expert parallelism: mixture-of-experts FFN with all-to-all dispatch.

No reference counterpart (SURVEY.md §2.3.6 lists expert parallelism as NOT
PRESENT) — this is part of the first-class distributed toolbox of the TPU
build.  Design follows the standard TPU MoE recipe: experts are sharded
over a mesh axis; token→expert dispatch is a dense one-hot contraction
(static shapes, MXU-friendly) followed by ``lax.all_to_all`` over ICI to
move token slots to the devices owning their experts, local expert FFNs,
and the inverse all-to-all + weighted combine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map


__all__ = ["moe_ffn", "top1_dispatch", "init_moe_params"]


def top1_dispatch(x, gate_w, num_experts, capacity):
    """Top-1 gating with capacity: returns (dispatch [T,E,C] one-hot,
    combine [T,E,C] gate-weighted, (frac_tokens [E], frac_probs [E])).

    The caller forms the Switch load-balance loss as
    ``sum(frac_tokens * frac_probs) * E`` — across shards the fractions
    must be averaged over every token-sharding axis BEFORE that product
    (see moe_ffn's frac_axis_names).

    Dense-tensor dispatch (Shazeer-style) — static shapes, no sorting, maps
    straight onto the MXU; tokens overflowing an expert's capacity are
    dropped (standard MoE semantics).
    """
    T = x.shape[0]
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                      # [T]
    gate = jnp.max(probs, axis=-1)                               # [T]

    onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.float32)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0              # [T, E]
    in_cap = (pos < capacity) & (onehot > 0)
    pos_cap = jnp.where(in_cap, pos, 0).astype(jnp.int32)
    slot = jax.nn.one_hot(pos_cap, capacity, dtype=jnp.float32)  # [T, E, C]
    dispatch = slot * in_cap[..., None]
    combine = dispatch * gate[:, None, None]

    # load-balancing fractions (Switch-Transformer aux loss inputs);
    # the caller forms sum(frac_tokens*frac_probs)*E — across shards
    # the fractions must be averaged BEFORE that product (the product
    # of local means is not the product of the global means, which
    # would make the loss layout-dependent)
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return dispatch, combine, (frac_tokens, frac_probs)


def moe_ffn(x, params, axis_name="ep", capacity_factor=2.0,
            activation=jax.nn.gelu, frac_axis_names=None):
    """MoE FFN body — call INSIDE shard_map with experts sharded over
    ``axis_name`` and tokens (batch) sharded over the same axis.

    x: [T_local, D] local tokens.
    params: dict with
        gate  [D, E_total]          (replicated)
        w1    [E_local, D, H]       (expert-sharded)
        b1    [E_local, H]
        w2    [E_local, H, D]
        b2    [E_local, D]
    frac_axis_names: EVERY mesh axis that shards tokens (defaults to
        (axis_name,)).  The Switch aux loss is formed from fractions
        averaged over these axes; leaving a token-sharding axis out
        makes the loss depend on the device layout.
    Returns ([T_local, D], aux_loss) — aux replicated over the named
    axes.
    """
    ep = jax.lax.axis_size(axis_name)
    T, D = x.shape
    e_local = params["w1"].shape[0]
    E = e_local * ep
    capacity = max(1, int(capacity_factor * T / E))

    dispatch, combine, (frac_tokens, frac_probs) = top1_dispatch(
        x, params["gate"], E, capacity)
    # [T,E,C] x [T,D] -> expert inputs [E, C, D]
    exp_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # all-to-all: split expert axis across devices, gather everyone's slots
    # for OUR experts along the capacity axis -> [E_local, ep*C, D]
    exp_in = jax.lax.all_to_all(exp_in, axis_name, split_axis=0,
                                concat_axis=1, tiled=True)
    h = jnp.einsum("ecd,edh->ech", exp_in, params["w1"].astype(jnp.float32))
    h = activation(h + params["b1"][:, None, :])
    out = jnp.einsum("ech,ehd->ecd", h, params["w2"].astype(jnp.float32))
    out = out + params["b2"][:, None, :]
    # inverse all-to-all: send slots back to their home devices
    out = jax.lax.all_to_all(out, axis_name, split_axis=1,
                             concat_axis=0, tiled=True)   # [E, C, D]
    y = jnp.einsum("tec,ecd->td", combine, out)
    # aux loss from GLOBAL fractions: average the per-shard means over
    # EVERY axis that shards tokens (callers with dp/sp axes must name
    # them via frac_axis_names), THEN take the Switch product — the
    # product of local means is not the product of the global means, so
    # anything less makes the loss depend on the device layout
    if isinstance(frac_axis_names, str):
        frac_axis_names = (frac_axis_names,)  # not tuple("dp") -> ('d','p')
    elif not frac_axis_names:   # None and () both mean "just my axis"
        frac_axis_names = (axis_name,)
    axes = tuple(frac_axis_names)
    frac_tokens = jax.lax.pmean(frac_tokens, axes)
    frac_probs = jax.lax.pmean(frac_probs, axes)
    aux = jnp.sum(frac_tokens * frac_probs) * E
    return y.astype(x.dtype), aux


def init_moe_params(rng, d_model, d_hidden, num_experts, dtype=jnp.float32):
    """Global (unsharded) MoE parameter pytree: shard w1/b1/w2/b2 over the
    expert axis before use (leading dim = num_experts)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_hidden) ** 0.5
    return {
        "gate": jax.random.normal(k1, (d_model, num_experts), dtype) * s1,
        "w1": jax.random.normal(k2, (num_experts, d_model, d_hidden),
                                dtype) * s1,
        "b1": jnp.zeros((num_experts, d_hidden), dtype),
        "w2": jax.random.normal(k3, (num_experts, d_hidden, d_model),
                                dtype) * s2,
        "b2": jnp.zeros((num_experts, d_model), dtype),
    }
