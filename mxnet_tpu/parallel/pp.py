"""Pipeline parallelism over a mesh axis (GPipe schedule, SPMD-style).

The reference's "model parallelism" is operator-level device placement —
``ctx_group`` attrs + the ``PlaceDevice`` pass splicing ``_CrossDeviceCopy``
nodes at cut edges, with the async engine providing natural cross-device
pipelining of LSTM timesteps (SURVEY.md §2.3.3).  The TPU-native analog is
a *scheduled* SPMD pipeline: every device runs the SAME program holding ONE
stage's parameters; activations hop stage→stage over ICI via
``lax.ppermute`` inside a ``lax.scan`` over microbatch ticks.  XLA compiles
the whole schedule — bubbles, collectives and all — into one program, and
``jax.grad`` of the scan yields the reverse pipeline automatically.

Schedule: classic GPipe — M microbatches through S stages in M + S - 1
ticks; bubble fraction (S-1)/(M+S-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map


__all__ = ["spmd_pipeline", "pipelined", "stack_stage_params"]


def spmd_pipeline(stage_fn, stage_params, x, axis_name="pp",
                  num_microbatches=None):
    """Run the pipeline body — call INSIDE shard_map over ``axis_name``.

    stage_fn: (params, microbatch) -> microbatch (same signature every
        stage; per-stage weights make stages differ, exactly like scanned
        transformer blocks).
    stage_params: this device's stage weights (pytree).
    x: [M, mb, ...] microbatched input, replicated across stages (only
        stage 0 actually consumes it).
    Returns [M, mb, ...]: outputs of the last stage (valid on every device
        after the closing broadcast).
    """
    S = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = x.shape[0] if num_microbatches is None else num_microbatches
    assert M == x.shape[0], \
        ("num_microbatches=%d != leading microbatch axis %d — would "
         "silently truncate or re-inject microbatches" % (M, x.shape[0]))
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    state = jnp.zeros_like(x[0])
    outbuf = jnp.zeros((M,) + x.shape[1:], x.dtype)

    def tick(carry, t):
        state, outbuf = carry
        # stage 0 injects microbatch t (while t < M); later stages consume
        # whatever arrived from the left neighbor last tick
        inject = x[jnp.minimum(t, M - 1)]
        inp = jnp.where(stage == 0, inject, state)
        out = stage_fn(stage_params, inp)
        # last stage banks its result for microbatch t-(S-1)
        mb_done = t - (S - 1)
        valid = jnp.logical_and(stage == S - 1, mb_done >= 0)
        outbuf = jax.lax.cond(
            valid,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, out, jnp.maximum(mb_done, 0), 0),
            lambda b: b, outbuf)
        state = jax.lax.ppermute(out, axis_name, perm)
        return (state, outbuf), None

    (state, outbuf), _ = jax.lax.scan(tick, (state, outbuf), jnp.arange(T))
    # broadcast the last stage's collected outputs to every stage so the
    # caller (loss, metrics) sees them uniformly
    last = jnp.where(stage == S - 1, 1.0, 0.0)
    outbuf = jax.lax.psum(outbuf * last.astype(outbuf.dtype), axis_name)
    return outbuf


def stack_stage_params(per_stage_params):
    """[S trees] -> one tree with a leading stage axis (shard over 'pp')."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def pipelined(stage_fn, mesh, axis_name="pp", num_microbatches=4):
    """Wrap ``stage_fn`` into f(stacked_params, x) running the pipeline
    over ``mesh[axis_name]``.

    stacked_params: trees with leading stage axis S (see
        ``stack_stage_params``) — sharded one-stage-per-device.
    x: [M, mb, ...] microbatched input.
    """
    def body(params, x):
        # shard_map gives us params with leading axis 1 (this stage)
        local = jax.tree_util.tree_map(lambda a: a[0], params)
        return spmd_pipeline(stage_fn, local, x, axis_name=axis_name,
                             num_microbatches=num_microbatches)

    pspec = P(axis_name)

    def run(stacked_params, x):
        in_param_specs = jax.tree_util.tree_map(
            lambda _: pspec, stacked_params)
        fn = shard_map(body, mesh=mesh,
                       in_specs=(in_param_specs, P()),
                       out_specs=P(), check_vma=False)
        return fn(stacked_params, x)

    return run
