"""Mixture-of-experts layer pieces for a chip that holds a SHARE of the
experts (expert parallelism seen from one chip).

:func:`route_grouped` is the router every chip runs alike, over ALL
experts (DeepSeek-V3's ``noaux_tc``: sigmoid scores, a correction bias
that only steers the choice, groups of experts of which the best few
are kept).  :func:`moe_experts` computes the part of the layer's result
that the experts HELD here give: the picks that fell on experts
``0 .. held-1`` are sorted by expert, multiplied as one grouped product
over uneven counts (``pallas_ops/grouped_matmul.py``: each expert that
got a row streams its weights once a row tile it reaches), unsorted and
summed with their routing weights.  No pick is dropped: there is no
capacity, the grouped product takes whatever the counts are.  Picks on
experts held elsewhere cost the sort and nothing more, and what those
experts would have added is left out (the exchange that would fetch it
does not exist on one chip).

:func:`moe_experts_reference` is the dense XLA twin: every held expert
over every token under a mask, the ``MXNET_PALLAS=0`` lowering and the
parity oracle (tests/test_deepseek_v3.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route_grouped", "moe_experts", "moe_experts_reference",
           "expert_counts", "expert_streams"]


def route_grouped(scores, bias, top_k, n_group, topk_group, scale,
                  eps=0.0):
    """Group-limited top-k routing.  ``scores`` ``(N, E)`` fp32 are the
    experts' sigmoid affinities, ``bias`` ``(E,)`` the correction that
    enters the CHOICE only.  A group's score is the sum of its two
    largest choice scores; the ``topk_group`` best groups stay; the
    ``top_k`` best choice scores among them pick the experts; their
    weights are the UNBIASED scores over their sum (plus the model's
    ``eps``, where it has one), times ``scale``.  ``n_group =
    topk_group = 1`` is plain top-k over all experts.
    Ties go to the lower index, in groups and experts alike
    (``jax.lax.top_k``).  Returns ``(experts (N, top_k) int32, weights
    (N, top_k) fp32)``."""
    N, E = scores.shape
    choice = scores + bias.astype(scores.dtype)[None, :]
    per = E // int(n_group)
    grouped = choice.reshape(N, int(n_group), per)
    group_score = jnp.sum(jax.lax.top_k(grouped, min(2, per))[0], axis=-1)
    _, keep = jax.lax.top_k(group_score, int(topk_group))
    kept = jnp.zeros((N, int(n_group)), bool).at[
        jnp.arange(N)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)
    _, experts = jax.lax.top_k(masked, int(top_k))
    picked = jnp.take_along_axis(scores, experts, axis=1)
    total = jnp.sum(picked, axis=-1, keepdims=True)
    weights = picked / (total + eps if eps else total) * scale
    return experts.astype(jnp.int32), weights


def expert_counts(experts, live, held):
    """Tokens each held expert got: ``(held,)`` int32 over the picks of
    the ``live`` tokens."""
    mine = (experts < held) & live[:, None]
    return jnp.sum(
        (experts[..., None] == jnp.arange(held, dtype=jnp.int32))
        & mine[..., None], axis=(0, 1), dtype=jnp.int32)


def expert_streams(counts, rows):
    """How often :func:`moe_experts`' grouped product streams an
    expert's weights for ``counts`` over ``rows`` sorted rows (tokens x
    picks): its (row tile, expert) visits.  ``sum(counts > 0)`` is the
    floor; more means the row tile cuts groups."""
    from ..pallas_ops.grouped_matmul import grouped_visits
    return grouped_visits(counts, rows)


def _swiglu(gu):
    f = gu.shape[-1] // 2
    gate = gu[..., :f].astype(jnp.float32)
    return gate * jax.nn.sigmoid(gate) * gu[..., f:].astype(jnp.float32)


def moe_experts(x, w_gate_up, w_down, experts, weights, live):
    """The held experts' part of an expert layer.

    x ``(N, D)``; ``w_gate_up`` ``(held, D, 2F)`` (gate then up, input
    dimension first) and ``w_down`` ``(held, F, D)``: SwiGLU experts
    ``0 .. held-1`` of the layer; ``experts``/``weights`` ``(N, K)``
    from :func:`route_grouped` over ALL experts; ``live`` ``(N,)`` bool:
    rows that are real tokens (pad rows and the rows of slots outside a
    dispatch route nowhere and cost nothing).  Returns ``(y (N, D)
    fp32, counts (held,) int32)``: ``sum_k w_k * expert_k(x)`` over the
    picks that fell on held experts, and how many each got.

    Eligible shapes take the sorted, grouped product; everything else —
    and ``MXNET_PALLAS=0`` — lowers to :func:`moe_experts_reference`."""
    from ..pallas_ops import dispatch as _pd
    from ..pallas_ops.grouped_matmul import grouped_matmul
    N, D = x.shape
    held = w_gate_up.shape[0]
    K = experts.shape[1]
    if not _pd.use_moe_experts("MoEExperts", N * K, D, w_down.shape[1],
                               x.dtype):
        return moe_experts_reference(x, w_gate_up, w_down, experts,
                                     weights, live)
    mine = ((experts < held) & live[:, None]).reshape(-1)      # (N*K,)
    key = jnp.where(mine, experts.reshape(-1), held)
    order = jnp.argsort(key, stable=True)         # held experts first
    counts = expert_counts(experts, live, held)
    xs = jnp.take(x, order // K, axis=0)                       # (N*K, D)
    interpret = _pd.interpret_mode()
    gu = grouped_matmul(xs, w_gate_up, counts, interpret=interpret)
    act = _swiglu(gu).astype(x.dtype)
    ys = grouped_matmul(act, w_down, counts, interpret=interpret)
    # rows past the last group belong to no expert: the product never
    # wrote them, and whatever they hold is not part of the result
    ys = jnp.where((jnp.arange(N * K) < jnp.sum(counts))[:, None], ys, 0)
    back = jnp.argsort(order)                     # assignment -> row
    y = jnp.take(ys, back, axis=0).reshape(N, K, D)
    w = jnp.where(mine.reshape(N, K), weights, 0).astype(jnp.float32)
    return jnp.einsum("nkd,nk->nd", y, w), counts


def moe_experts_reference(x, w_gate_up, w_down, experts, weights, live):
    """Dense XLA twin of :func:`moe_experts`: each held expert over
    every token, kept where the token picked it."""
    held = w_gate_up.shape[0]
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(held):
        w = jnp.sum(jnp.where((experts == e) & live[:, None], weights, 0),
                    axis=-1).astype(jnp.float32)
        gu = jnp.matmul(x, w_gate_up[e],
                        preferred_element_type=jnp.float32)
        out = jnp.matmul(_swiglu(gu).astype(x.dtype), w_down[e],
                         preferred_element_type=jnp.float32)
        y = y + w[:, None] * out
    return y, expert_counts(experts, live, held)
