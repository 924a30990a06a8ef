"""The in-graph sampler does for a dispatch only what its rows ask
(``program_store.sample_tokens``): the draw sits under a ``cond`` on
"a row samples", the top-k threshold under a second on "a sampling row
cuts the vocabulary".  Tokens and keys stay bit-equal to the plain twin
below, which runs all of it for every dispatch, as the sampler did
before it adapted (docs/architecture/decode_engine.md, "Sampling").
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.serving.program_store import (host_sample,
                                             host_sample_chunk,
                                             sample_chunk_rows,
                                             sample_tokens)

ROWS = 6


def _twin_tokens(logits, keys, temps, top_ks):
    """The unconditional sampler: sort, draw and select, every call."""
    logits = jnp.asarray(logits, jnp.float32)
    n_vocab = logits.shape[-1]
    keys = jnp.asarray(keys, jnp.uint32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    pairs = jax.vmap(jax.random.split)(keys)
    carry, use = pairs[:, 0], pairs[:, 1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = logits / jnp.maximum(temps, 1e-6)[:, None]
    k = jnp.clip(jnp.where(top_ks <= 0, n_vocab, top_ks), 1, n_vocab)
    kth = jnp.take_along_axis(-jnp.sort(-z, axis=-1),
                              (k - 1)[:, None], axis=-1)
    z = jnp.where(z >= kth, z, -jnp.inf)
    sampled = jax.vmap(jax.random.categorical)(use, z).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled), carry


def _twin_chunk_rows(logits, keys, temps, top_ks, do_sample, slots):
    keys = jnp.asarray(keys, jnp.uint32)
    slots = jnp.asarray(slots, jnp.int32)
    toks, carry = _twin_tokens(logits, keys[slots], temps, top_ks)
    dest = jnp.where(jnp.asarray(do_sample), slots, keys.shape[0])
    return toks, keys.at[dest].set(carry, mode="drop")


# (temperature, top_k) of each of the ROWS rows; "V" is the vocabulary
MIXES = {
    "greedy": [(0.0, 0)] * ROWS,
    "greedy_with_top_k": [(0.0, 5), (0.0, 1), (-1.0, 3)] * 2,
    "full_vocab": [(0.8, 0), (1.3, 0), (0.2, -4)] * 2,
    "top_k_1": [(0.8, 1)] * ROWS,
    "top_k_k": [(0.8, 5), (1.5, 3), (0.3, 7)] * 2,
    "top_k_V": [(0.8, "V"), (1.1, "V")] * 3,
    "top_k_over_V": [(0.8, "V+9"), (0.5, "V+1")] * 3,
    "mixed": [(0.0, 0), (0.8, 0), (0.9, 5), (0.0, 4), (1.2, "V"),
              (0.7, 1)],
    "one_sampling_row": [(0.0, 0)] * (ROWS - 1) + [(0.8, 0)],
    "one_top_k_row": [(0.0, 0), (0.8, 0)] * 2 + [(0.0, 2), (0.6, 2)],
}


def _dispatch(mix, vocab, tied):
    rs = np.random.RandomState(0)
    logits = rs.randn(ROWS, vocab).astype(np.float32) * 3.0
    if tied:
        # ties at the top (the argmax and the k-th value both land on
        # one) and a run of equal values further down
        logits = np.round(logits)
        logits[:, 3] = logits[:, 7] = logits.max(axis=-1)
    keys = rs.randint(0, 2 ** 32, (ROWS, 2), dtype=np.uint64) \
        .astype(np.uint32)
    sized = {"V": vocab, "V+1": vocab + 1, "V+9": vocab + 9}
    temps = np.array([t for t, _ in MIXES[mix]], np.float32)
    top_ks = np.array([sized.get(k, k) for _, k in MIXES[mix]], np.int32)
    return logits, keys, temps, top_ks


def _same(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("vocab", [16, 50], ids=["v16", "v50"])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sampler_bit_equal_to_its_unconditional_twin(mix, vocab, tied):
    """Tokens and keys of every dispatch mix, traced (as the step
    programs hold it), jitted standalone (the host hatch) and through
    the compacted chunk's gather and scatter."""
    logits, keys, temps, top_ks = _dispatch(mix, vocab, tied)
    want = jax.jit(_twin_tokens)(logits, keys, temps, top_ks)
    _same(host_sample(logits, keys, temps, top_ks), want)
    _same(sample_tokens(logits, keys, temps, top_ks), want)

    # the chunk's rows work for slots 5, 0, 2, 2(padding: do False) ...
    # of 8 key chains; rows that do not sample leave their chain alone
    slots = np.array([5, 0, 2, 7, 0, 3], np.int32)
    do = np.array([True, True, False, True, False, True])
    chains = np.random.RandomState(1).randint(
        0, 2 ** 32, (8, 2), dtype=np.uint64).astype(np.uint32)
    want = jax.jit(_twin_chunk_rows)(logits, chains, temps, top_ks, do,
                                     slots)
    _same(host_sample_chunk(logits, chains, temps, top_ks, do, slots),
          want)
    _same(sample_chunk_rows(logits, chains, temps, top_ks, do, slots),
          want)
    np.testing.assert_array_equal(np.asarray(want[1])[[1, 4, 6]],
                                  chains[[1, 4, 6]])


def _primitives(jaxpr, depth=0, out=None):
    """``(primitive name, how many cond branches enclose it)`` of every
    equation, through every nested jaxpr."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, depth))
        inner = depth + (eqn.primitive.name == "cond")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, inner, out)
    return out


def test_sort_and_draw_trace_only_inside_cond_branches():
    """No dispatch pays for the sort or the random bits at the top
    level of the program: the draw is a ``cond``'s branch and the sort
    a branch of a second inside it; the argmax and the key split
    (threefry too, but (S, 2) wide) stay outside."""
    logits, keys, temps, top_ks = _dispatch("mixed", 50, False)
    for fn, args in ((sample_tokens, (logits, keys, temps, top_ks)),
                     (sample_chunk_rows,
                      (logits, keys[:4], temps, top_ks,
                       np.ones(ROWS, bool),
                       np.array([0, 1, 2, 3, 0, 1], np.int32)))):
        prims = _primitives(jax.make_jaxpr(fn)(*args).jaxpr)
        depths = {}
        for name, depth in prims:
            depths.setdefault(name, set()).add(depth)
        assert depths["sort"] == {2}
        assert depths["random_bits"] == depths["log"] == {1}
        assert depths["div"] == {1}
        assert depths["cond"] == {0, 1}
        assert 0 in depths["argmax"] and depths["random_split"] == {0}
        assert "threefry2x32" not in depths or \
            0 not in depths["threefry2x32"]
