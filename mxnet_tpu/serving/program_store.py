"""AOT program store: compiled-ahead-of-time inference per shape bucket.

The training side compiles lazily (``cached_op.py``'s tiered LRU,
``executor.py``'s bind-time jit) because training shapes are stable after
step one.  A serving process is the opposite regime: request sizes vary
per call and the first request of a new shape must NOT pay a multi-second
XLA compile.  So the store

* quantizes request batch sizes into configured **bucket edges**
  (``MXNET_SERVE_BUCKETS``): a request of ``n`` rows is zero-padded up to
  the smallest edge ``>= n``, runs the bucket's program, and the pad rows
  are sliced back off every batch-major output.  Inference graphs are
  row-independent (``is_train=False`` — BatchNorm reads running stats,
  softmax is per-row), so the pad rows cannot perturb the real rows and
  fp32 bucketed outputs are **bit-equal** to an unbatched forward
  (pinned by ``tests/test_serving.py``);
* compiles each bucket's program **ahead of time** —
  ``jax.jit(fwd).lower(specs...).compile()`` — normally at model load
  (:meth:`ProgramStore.warmup`), so steady-state dispatch never traces;
* holds the executables in a bounded LRU keyed like ``cached_op.py``'s
  (``(model, bucket, input avals, dtype)``), ``MXNET_SERVE_PROGRAM_CACHE``
  entries, with hit/compile/eviction stats.

Parameters are **arguments** of the compiled programs (not baked
constants like ``deploy.py``'s export), so all buckets share one
device-resident copy of the weights and a model upgrade swaps arrays
without recompiling.  ``compute_dtype='bfloat16'`` casts the floating
weights once at load (half the serving memory) and casts inputs inside
the program; ``compute_dtype='int8'`` quantizes the FullyConnected
weights once at load into ``(int8 codes, fp32 scales)`` pairs (~4x
less resident weight memory — ``stats()["weight_bytes"]`` measures it)
that dequantize INSIDE the programs through the fused dequant-matmul
door (``pallas_ops/dequant_matmul.py``; dense XLA twin off the kernel
route); outputs always come back float32.
"""
from __future__ import annotations

import logging
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics as _metrics
from ..analysis.lockcheck import make_lock
from ..base import MXNetError, get_env, hot_path
from ..pallas_ops import dispatch as _pallas_dispatch

def _cache_event(event):
    """Process-wide program-cache counter (every store feeds it; the
    per-store split stays on each store's stats() tree)."""
    return _metrics.cached_counter(
        "serve_program_cache_%s_total" % event,
        help="AOT serving-program LRU events across all stores")
from ..pallas_ops.dequant_matmul import QuantizedWeight, quantize_int8

__all__ = ["ProgramStore", "GenerativeProgramStore", "bucket_edges",
           "bucket_for", "sample_tokens", "sample_tokens_p",
           "spec_verify", "host_sample"]

log = logging.getLogger(__name__)


def bucket_edges(edges=None, env_var="MXNET_SERVE_BUCKETS"):
    """Resolve bucket edges: an explicit iterable, or the ``env_var``
    comma list (batch buckets by default; the prefill programs pass
    ``MXNET_SERVE_PROMPT_BUCKETS``); returned sorted, deduplicated,
    all positive."""
    if edges is None:
        raw = get_env(env_var)
        edges = [int(tok) for tok in str(raw).split(",") if tok.strip()]
    out = sorted({int(e) for e in edges})
    if not out or out[0] < 1:
        raise MXNetError("serving bucket edges must be positive ints, "
                         "got %r" % (edges,))
    return tuple(out)


def bucket_for(n, edges):
    """Smallest edge >= n, or None when n exceeds the largest edge."""
    for e in edges:
        if n <= e:
            return e
    return None


def _as_device_array(v):
    """Model parameter -> jax array WITHOUT a host round-trip when the
    value is already device-resident (NDArray / jax.Array)."""
    data = getattr(v, "_data", v)  # NDArray unwraps; numpy/jax pass through
    return data if isinstance(data, jax.Array) else jnp.asarray(data)


def _fc_weight_only_params(symbol):
    """Variables consumed EXCLUSIVELY as FullyConnected weight inputs —
    the int8-quantizable set of a symbol graph.  Any other consumer
    (a norm, an elementwise op, an output head) would receive the
    ``(codes, scales)`` pair it does not understand, so shared
    variables stay full precision."""
    fc_w, other = set(), set()
    for node in symbol._nodes():
        if node.is_variable:
            continue
        is_fc = node.op.name == "FullyConnected"
        for idx, (s, _oi) in enumerate(node.arg_inputs()):
            if s.is_variable:
                (fc_w if is_fc and idx == 1 else other).add(s.name)
    for n, _oi in symbol._outputs:
        if n.is_variable:
            other.add(n.name)
    return fc_w - other


def _weight_bytes(tree):
    """Resident bytes of a param/aux pytree grouped by storage dtype —
    the measurement behind the int8 ~4x / bf16 2x weight-memory claims
    (``stats()["weight_bytes"]``; the bench rows read this instead of
    recomputing)."""
    by_dtype = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        dt = str(leaf.dtype)
        by_dtype[dt] = by_dtype.get(dt, 0) + \
            int(leaf.size) * int(leaf.dtype.itemsize)
    return {"total": sum(by_dtype.values()), "by_dtype": by_dtype}


# ---------------------------------------------------------------------------
# Token sampling: ONE pure function for both serving modes.
# ---------------------------------------------------------------------------
def sample_tokens(logits, keys, temps, top_ks):
    """One sampling step over a ``(S, V)`` logits batch.

    Per slot: ``temps[s] <= 0`` is greedy (argmax); otherwise seeded
    temperature sampling over the ``top_ks[s]`` highest logits
    (``top_ks[s] <= 0`` = full vocab) via ``jax.random.categorical``.
    ``keys`` is the per-slot threefry key data ``(S, 2) uint32``, split
    once per step (counter-based, so the stream is a pure function of
    the request seed and the step index); returns ``(tokens (S,) int32,
    new_keys (S, 2))``.

    A dispatch pays only for what its rows ask, decided in the program
    from ``temps`` and ``top_ks``: the key split and the argmax always
    run; the draw (a Gumbel for each of ``S x V`` logits) only where a
    row samples; the top-k threshold (a sort of every row) only where
    a sampling row's ``top_k`` cuts the vocabulary.  A branch not taken
    computed what the final ``where`` threw away (greedy rows), or a
    mask at the row's minimum, which masks nothing (full-vocabulary
    rows): tokens and keys are bit-equal to running all of it always
    (``tests/test_sampler.py`` holds them to that twin).

    PURE and shared: the SAME body traces into the ``decode_sample``
    program (in-graph sampling, ``MXNET_SERVE_SAMPLE=graph``) and jits
    standalone over host-fetched logits for the ``host`` escape hatch —
    identical ops on identical values, so the two modes emit
    byte-identical token streams from the same seeds (pinned)."""
    logits = jnp.asarray(logits, jnp.float32)
    n_vocab = logits.shape[-1]
    keys = jnp.asarray(keys, jnp.uint32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    pairs = jax.vmap(jax.random.split)(keys)        # (S, 2, 2)
    carry, use = pairs[:, 0], pairs[:, 1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy_row = temps <= 0.0

    def threshold(z):
        k = jnp.clip(jnp.where(top_ks <= 0, n_vocab, top_ks), 1, n_vocab)
        kth = jnp.take_along_axis(-jnp.sort(-z, axis=-1),
                                  (k - 1)[:, None], axis=-1)
        return jnp.where(z >= kth, z, -jnp.inf)

    def draw():
        z = logits / jnp.maximum(temps, 1e-6)[:, None]
        cuts = ~greedy_row & (top_ks > 0) & (top_ks < n_vocab)
        z = jax.lax.cond(jnp.any(cuts), threshold, lambda z: z, z)
        sampled = jax.vmap(jax.random.categorical)(use, z)
        return jnp.where(greedy_row, greedy, sampled.astype(jnp.int32))

    toks = jax.lax.cond(jnp.all(greedy_row), lambda: greedy, draw)
    return toks, carry


def sample_chunk_rows(logits, keys, temps, top_ks, do_sample, slots,
                      row_keys=None):
    """:func:`sample_tokens` for the ``R`` rows of a compacted
    prompt-chunk dispatch.  Row ``k`` works for slot ``slots[k]``: it
    draws with that slot's chain out of ``keys (S, 2)``, and the chain
    advances only where ``do_sample[k]`` is set (the rows finishing
    their prompt).  Every other slot's key comes back bit-equal: a row
    that does not sample is sent past the slot axis and dropped by the
    scatter, so a padding row (slot 0, ``do_sample`` False) cannot
    collide with the live row of slot 0.  ``temps`` and ``top_ks`` are
    per row.  ``row_keys (R, 2)``: the chains the rows draw with, from
    a caller that has them (a row that samples draws its request's
    FIRST token, so its chain is the request's seed key, which the host
    knows), in place of ``keys[slots]``.  Returns ``(tokens (R,) int32,
    new_keys (S, 2))``."""
    keys = jnp.asarray(keys, jnp.uint32)
    slots = jnp.asarray(slots, jnp.int32)
    toks, carry = sample_tokens(
        logits, keys[slots] if row_keys is None else row_keys, temps,
        top_ks)
    dest = jnp.where(jnp.asarray(do_sample), slots, keys.shape[0])
    return toks, keys.at[dest].set(carry, mode="drop")


# the host escape hatch's samplers: the same functions, jitted
# standalone (jax re-specializes per logits shape; the decode engine
# calls them on the fetched (rows, vocab) matrix)
host_sample = jax.jit(sample_tokens)
host_sample_chunk = jax.jit(sample_chunk_rows)


def _masked_dist(logits, temps, top_ks):
    """The categorical distribution :func:`sample_tokens` draws from,
    as explicit probabilities over ``(S, V)`` rows: temperature + top-k
    masked softmax (``jax.random.categorical`` over masked ``z`` IS
    ``softmax(z)``); greedy rows (``temps <= 0``) are one-hot at the
    argmax.  The speculative plane's shared density: the draft's
    proposal distribution q and the target's acceptance distribution p
    both come from THIS function on their respective logits, so the
    rejection rule compares exactly the densities the two samplers
    use."""
    logits = jnp.asarray(logits, jnp.float32)
    n_vocab = logits.shape[-1]
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    z = logits / jnp.maximum(temps, 1e-6)[:, None]
    k = jnp.clip(jnp.where(top_ks <= 0, n_vocab, top_ks), 1, n_vocab)
    kth = jnp.take_along_axis(-jnp.sort(-z, axis=-1),
                              (k - 1)[:, None], axis=-1)
    z = jnp.where(z >= kth, z, -jnp.inf)
    probs = jax.nn.softmax(z, axis=-1)
    onehot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), n_vocab,
                            dtype=jnp.float32)
    return jnp.where((temps <= 0.0)[:, None], onehot, probs)


def sample_tokens_p(logits, keys, temps, top_ks):
    """:func:`sample_tokens` that ALSO returns the per-slot proposal
    distribution ``q (S, V)`` the token was drawn from — the draft
    model's sampling step in speculative decoding (the verify program
    needs q(d) for the acceptance test ``u * q(d) <= p(d)``).  Returns
    ``(tokens, new_keys, q)``; token/key behavior is byte-identical to
    :func:`sample_tokens`."""
    toks, carry = sample_tokens(logits, keys, temps, top_ks)
    return toks, carry, _masked_dist(logits, temps, top_ks)


def spec_verify(logits_all, prop_toks, prop_q, keys, temps, top_ks,
                valid):
    """In-graph speculative accept/reject (standard rejection-sampling
    rule) over one verify step's logits.

    logits_all: (B, K+1, V) fp32 — the target's logits at the K+1
    verified positions (row j conditions on the prompt + the first j
    draft tokens); prop_toks: (B, K) int32 draft proposals; prop_q:
    (B, K, V) fp32 — the draft's proposal distribution for each
    proposal (:func:`sample_tokens_p`); keys: (B, 2) uint32 per-slot
    threefry chains; valid: (B,) int32 — row b verifies
    ``valid[b] - 1`` proposals (``1 <= valid <= K+1``; a row's window
    shrinks near its token budget).

    Per slot: greedy rows (``temps <= 0``) accept the longest prefix of
    proposals matching the target argmax and emit the target argmax at
    the first mismatch — byte-identical to non-speculative greedy
    decoding.  Sampled rows draw one uniform per position off the
    slot's key chain and accept proposal j iff ``u_j * q_j(d_j) <=
    p_j(d_j)``; the first rejection resamples from the corrected
    residual ``max(p - q, 0)`` (renormalized; p itself when the
    residual vanishes, i.e. q covers p), and a fully-accepted window
    draws the bonus token directly from p — the classic proof gives
    token streams DISTRIBUTION-identical to sampling from p alone.

    Returns ``(out_toks (B, K+1) int32, n_emit (B,) int32, new_keys
    (B, 2))``: row b emits ``out_toks[b, :n_emit[b]]`` (accepted
    proposals + the corrected/bonus token), ``1 <= n_emit <= valid``."""
    logits_all = jnp.asarray(logits_all, jnp.float32)
    B, K1, V = logits_all.shape
    K = K1 - 1
    prop_toks = jnp.asarray(prop_toks, jnp.int32)
    prop_q = jnp.asarray(prop_q, jnp.float32)
    keys = jnp.asarray(keys, jnp.uint32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    # per-slot chain: carry + K accept draws + 1 resample draw (one
    # split per verify keeps the chain counter-based like sample_tokens)
    allk = jax.vmap(lambda kk: jax.random.split(kk, K + 2))(keys)
    carry, res_keys = allk[:, 0], allk[:, K + 1]
    greedy_all = jnp.argmax(logits_all, axis=-1).astype(jnp.int32)
    rows = jnp.arange(B)
    in_window = jnp.arange(K, dtype=jnp.int32)[None, :] + 1 < \
        valid[:, None]

    def accepted(acc):
        return jnp.sum(jnp.cumprod(
            (acc & in_window).astype(jnp.int32), axis=1), axis=1)

    def emit(a, corrected):
        out = jnp.concatenate(
            [prop_toks, jnp.zeros((B, 1), jnp.int32)], axis=1)
        return out.at[rows, a].set(corrected), (a + 1).astype(jnp.int32)

    def greedy():
        # every row greedy: no density is needed (``sampled`` sorts
        # every position's logits for one, and its ``where`` on the
        # temperatures then throws the draw away)
        a = accepted(prop_toks == greedy_all[:, :K])
        return emit(a, greedy_all[rows, a])

    def sampled():
        p_full = _masked_dist(
            logits_all.reshape(B * K1, V), jnp.repeat(temps, K1),
            jnp.repeat(top_ks, K1)).reshape(B, K1, V)
        if K:
            acc_keys = allk[:, 1:K + 1].reshape(B * K, 2)
            u = jax.vmap(jax.random.uniform)(acc_keys).reshape(B, K)
            pd = jnp.take_along_axis(p_full[:, :K], prop_toks[..., None],
                                     -1)[..., 0]
            qd = jnp.take_along_axis(prop_q, prop_toks[..., None],
                                     -1)[..., 0]
            a = accepted(jnp.where((temps <= 0.0)[:, None],
                                   prop_toks == greedy_all[:, :K],
                                   u * qd <= pd))
        else:  # pragma: no cover - K=0 degenerates to a plain sample
            a = jnp.zeros((B,), jnp.int32)
        p_a = p_full[rows, a]                               # (B, V)
        q_ext = jnp.concatenate(
            [prop_q, jnp.zeros((B, 1, V), jnp.float32)], axis=1)
        # the bonus position (full accept, a == valid-1) has no
        # proposal: its residual is p itself
        q_a = jnp.where((a >= valid - 1)[:, None], 0.0, q_ext[rows, a])
        res = jnp.maximum(p_a - q_a, 0.0)
        tot = jnp.sum(res, axis=-1, keepdims=True)
        res = jnp.where(tot > 0.0, res / jnp.where(tot > 0.0, tot, 1.0),
                        p_a)
        drawn = jax.vmap(jax.random.categorical)(
            res_keys, jnp.log(jnp.maximum(res, 1e-30))).astype(jnp.int32)
        return emit(a, jnp.where(temps <= 0.0, greedy_all[rows, a],
                                 drawn))

    out, n_emit = jax.lax.cond(jnp.all(temps <= 0.0), greedy, sampled) \
        if K else sampled()
    return out, n_emit, carry


class _Program:
    __slots__ = ("fn", "bucket", "out_batch_major", "compile_ms",
                 "temp_bytes")

    def __init__(self, fn, bucket, out_batch_major, compile_ms,
                 temp_bytes=None):
        self.fn = fn
        self.bucket = bucket
        self.out_batch_major = out_batch_major
        self.compile_ms = compile_ms
        # a paged step program's scratch (memory_analysis): what says
        # that it holds no second copy of the KV pool
        self.temp_bytes = temp_bytes


class ProgramStore:
    """Bucketed AOT-compiled inference programs for one model.

    Parameters
    ----------
    symbol : Symbol
        The inference graph.
    arg_params, aux_params : dict
        name -> array (NDArray / jax / numpy).  Non-input arguments
        missing from ``arg_params`` whose shape is inferable are baked
        as zeros (unused loss-head labels, same policy as ``deploy.py``).
    input_shapes : dict
        name -> full shape; axis 0 of every input is the batch axis the
        store buckets on (the leading dim given here is only a shape
        template — requests of any bucketable size are accepted).
    name : str
        Cache-key / diagnostics tag.
    compute_dtype : str, optional
        ``'bfloat16'`` casts floating weights once at load and inputs
        inside the program; ``'int8'`` quantizes the FC weights once at
        load (scale-per-row symmetric, ``quantize_int8``) into
        ``(codes, scales)`` program arguments that dequantize in-graph
        through the fused dequant-matmul door; outputs return float32
        either way.  None = master dtype (fp32 bit-equal serving).
    buckets : iterable of int, optional
        Bucket edges; overrides ``MXNET_SERVE_BUCKETS``.
    max_programs : int, optional
        LRU bound; overrides ``MXNET_SERVE_PROGRAM_CACHE``.
    input_dtypes : dict, optional
        name -> numpy dtype of the wire inputs (default float32).
    device : jax.Device, optional
        Pin weights (and hence the compiled programs, which follow
        their committed arguments) to this device; default leaves
        placement to jax's default device.
    """

    def __init__(self, symbol, arg_params, aux_params, input_shapes,
                 name="model", compute_dtype=None, buckets=None,
                 max_programs=None, input_dtypes=None, device=None):
        self._symbol = symbol
        self.name = name
        self._edges = bucket_edges(buckets)
        self._quant8 = str(compute_dtype).lower() == "int8" \
            if compute_dtype else False
        self._cdt = (None if self._quant8 or not compute_dtype
                     else jnp.dtype(compute_dtype))
        # cache-key / stats tag for the serving dtype (int8 has no jnp
        # compute dtype — activations stay fp32, weights are codes)
        self._dtype_tag = ("int8" if self._quant8 else
                           str(self._cdt) if self._cdt is not None
                           else None)
        self._input_names = list(input_shapes)
        if not self._input_names:
            raise MXNetError("serving needs at least one input")
        self._input_tails = {n: tuple(input_shapes[n])[1:]
                             for n in self._input_names}
        self._input_dtypes = {
            n: np.dtype((input_dtypes or {}).get(n, "float32"))
            for n in self._input_names}
        self._device = device
        # bucketing correctness requires every output to carry a leading
        # batch axis: pad rows are sliced off outputs, and the batcher
        # hands each request its row range — an output computed over the
        # WHOLE batch (a mean/sum head) would mix pad rows and, under
        # continuous batching, other requests' rows into every result.
        # Probe the symbol at two distinct batch sizes: batch-major
        # outputs track the batch, anything else is rejected at load.
        out_names = symbol.list_outputs()
        probes = []
        for b in (self._edges[-1], self._edges[-1] + 1):
            probe = {n: (b,) + self._input_tails[n]
                     for n in self._input_names}
            _, out_shapes, _ = symbol.infer_shape_partial(**probe)
            probes.append(out_shapes)
        for i, oname in enumerate(out_names):
            s1, s2 = probes[0][i], probes[1][i]
            if s1 is None or s2 is None or not len(s1) or not len(s2) \
                    or s1[0] != self._edges[-1] \
                    or s2[0] != self._edges[-1] + 1:
                raise MXNetError(
                    "output %r of serving model %r is not batch-major "
                    "(shape %s at batch size %d): bucket padding and "
                    "continuous batching require row-independent "
                    "batch-major outputs — serve this model with the "
                    "classic Predictor instead"
                    % (oname, name, s1, self._edges[-1]))

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        aux_params = aux_params or {}
        self._param_names = [n for n in arg_names
                             if n not in input_shapes and n in arg_params]
        self._zero_args = [n for n in arg_names
                           if n not in input_shapes
                           and n not in arg_params]

        # int8: quantize exactly the variables every consumer of which
        # is a FullyConnected WEIGHT input (the matmul door understands
        # the pair; nothing else does) — in an MLP/classifier head that
        # is the overwhelming share of the bytes
        self._quant_names = (_fc_weight_only_params(symbol)
                             if self._quant8 else frozenset())
        self._aux_names = list(aux_names)

        self._params = {n: self._load_param(arg_params[n], n)
                        for n in self._param_names}
        aux = []
        # aux states missing from the checkpoint keep predictor.py's
        # policy: zero-filled at their inferred shape
        shapes = {n: tuple(input_shapes[n]) for n in self._input_names}
        _, _, aux_shapes = symbol.infer_shape_partial(**shapes)
        for n, shape in zip(aux_names, aux_shapes):
            if n in aux_params:
                aux.append(self._load_param(aux_params[n]))
            elif shape is not None:
                z = jnp.zeros(tuple(shape), self._cdt or jnp.float32)
                aux.append(z if device is None
                           else jax.device_put(z, device))
            else:
                raise MXNetError("auxiliary state %r is not in the params "
                                 "and its shape cannot be inferred" % n)
        self._aux = tuple(aux)
        # the PUBLISHED weight set: dispatch reads this tuple exactly
        # once per run, so a hot swap (swap_params) is atomic per
        # request — every in-flight request executes against exactly
        # one (params, aux, version) snapshot, never a mix
        self._version = 1
        self._live = (self._params, self._aux, self._version)

        if max_programs is None:
            max_programs = int(get_env("MXNET_SERVE_PROGRAM_CACHE"))
        self.max_programs = max(1, int(max_programs))
        if self.max_programs < len(self._edges):
            # warmup can't keep every bucket resident: the LRU evicts
            # early buckets before traffic, and the first request for
            # one pays a compile AT DISPATCH — the stall AOT exists to
            # prevent.  Legal (eviction tests rely on it) but worth a
            # loud heads-up in a serving process.
            log.warning(
                "serving model %r: program cache (%d) is smaller than "
                "the bucket count (%d); warmed buckets will be evicted "
                "and recompile inside served requests — raise "
                "MXNET_SERVE_PROGRAM_CACHE or trim MXNET_SERVE_BUCKETS",
                name, self.max_programs, len(self._edges))
        self._programs = OrderedDict()   # key -> _Program
        self._lock = make_lock("serving.program_store")
        self._stats = {"hits": 0, "compiles": 0, "evictions": 0,
                       "compile_ms_total": 0.0}

    def _load_param(self, v, name=None):
        """One parameter through the serving dtype policy: int8-quantize
        the FC-weight-only set, cast floats to the compute dtype, pin to
        the store's device.  Shared by load-time and swap-time paths so
        a swapped weight set goes through EXACTLY the original
        pipeline."""
        a = _as_device_array(v)
        if name in self._quant_names and a.ndim == 2 and \
                jnp.issubdtype(a.dtype, jnp.floating):
            codes, scales = quantize_int8(np.asarray(a))
            c, s = jnp.asarray(codes), jnp.asarray(scales)
            if self._device is not None:
                c = jax.device_put(c, self._device)
                s = jax.device_put(s, self._device)
            return QuantizedWeight(c, s)
        if self._cdt is not None and a.dtype != self._cdt and \
                jnp.issubdtype(a.dtype, jnp.floating):
            a = a.astype(self._cdt)
        if self._device is not None:
            # committed params pin the compiled programs' placement
            # (uncommitted request inputs follow them)
            a = jax.device_put(a, self._device)
        return a

    # -- hot weight swap -----------------------------------------------
    def swap_params(self, arg_params, aux_params=None):
        """Atomically republish the device-resident weight arguments.

        ``arg_params`` must cover every non-input argument the store
        serves (same names/shapes/dtypes as the loaded checkpoint —
        the AOT programs were lowered against those avals and are NOT
        recompiled).  ``aux_params=None`` keeps the current auxiliary
        states.  The new set goes through the same dtype pipeline as
        load (bf16 cast / int8 quantization / device pinning), then ONE
        reference assignment publishes ``(params, aux, version)``;
        requests dispatched before the swap keep the old snapshot,
        requests after get the new one, and no request ever sees a mix
        (``run`` reads the snapshot exactly once).  Returns the new
        version (monotonic, reported by ``stats()['version']``)."""
        missing = [n for n in self._param_names if n not in arg_params]
        if missing:
            raise MXNetError("swap_params for %r is missing %s"
                             % (self.name, sorted(missing)))
        new_params = {}
        for n in self._param_names:
            a = self._load_param(arg_params[n], n)
            old = self._params[n]
            quant = isinstance(old, QuantizedWeight)
            if quant != isinstance(a, QuantizedWeight):
                pairs = None
            elif quant:
                pairs = ((a.codes, old.codes), (a.scales, old.scales))
            else:
                pairs = ((a, old),)
            if pairs is None or any(
                    x.shape != y.shape or x.dtype != y.dtype
                    for x, y in pairs):
                raise MXNetError(
                    "swap_params for %r: parameter %r does not match "
                    "the compiled programs' signature (the serving "
                    "programs are not recompiled on swap)" % (self.name,
                                                              n))
            new_params[n] = a
        if aux_params is None:
            new_aux = self._aux
        else:
            new_aux = []
            for n, old in zip(self._aux_names, self._aux):
                if n not in aux_params:
                    new_aux.append(old)
                    continue
                a = self._load_param(aux_params[n])
                if a.shape != old.shape or a.dtype != old.dtype:
                    raise MXNetError(
                        "swap_params for %r: auxiliary state %r does "
                        "not match the compiled programs' signature"
                        % (self.name, n))
                new_aux.append(a)
            new_aux = tuple(new_aux)
        with self._lock:
            self._params = new_params
            self._aux = new_aux
            self._version += 1
            # single reference assignment = the atomic publish point
            self._live = (new_params, new_aux, self._version)
        return self._version

    @property
    def version(self):
        return self._version

    def param_snapshot(self):
        """Opaque handle to the live weight set, for
        :meth:`restore_params`.  The rolling weight swap captures one
        per replica before swapping so a failed re-probe can roll the
        already-swapped replicas back to exactly the weights they
        served (device-resident, already through the dtype pipeline)."""
        params, aux, _ = self._live
        return (params, aux)

    def restore_params(self, snap):
        """Atomically republish a :meth:`param_snapshot` — the
        rolling-swap ABORT path.  No dtype pipeline and no signature
        check (the snapshot came from this store).  Bumps the version
        like any swap: versions stay monotonic even when the weights
        roll back, so 'version changed' remains a reliable swap
        witness."""
        params, aux = snap
        with self._lock:
            self._params = dict(params)
            self._aux = aux
            self._version += 1
            self._live = (self._params, self._aux, self._version)
        return self._version

    # -- geometry ------------------------------------------------------
    @property
    def edges(self):
        return self._edges

    def max_bucket(self):
        return self._edges[-1]

    @property
    def input_names(self):
        return list(self._input_names)

    def output_names(self):
        return self._symbol.list_outputs()

    def canon_inputs(self, inputs):
        """Validate + canonicalize one request's inputs (client-thread
        work: np conversion, dtype cast, shape checks).  Returns
        ``(dict name -> np.ndarray, n_rows)``."""
        got, want = set(inputs), set(self._input_names)
        if got != want:
            raise MXNetError("serving inputs mismatch for %r: got %s, "
                             "want %s" % (self.name, sorted(got),
                                          sorted(want)))
        out = {}
        n = None
        for name in self._input_names:
            a = np.asarray(inputs[name], dtype=self._input_dtypes[name])
            tail = self._input_tails[name]
            if a.ndim != len(tail) + 1 or tuple(a.shape[1:]) != tail:
                raise MXNetError(
                    "input %r has shape %s; want (n,%s)"
                    % (name, a.shape, ",".join(map(str, tail))))
            if n is None:
                n = int(a.shape[0])
            elif int(a.shape[0]) != n:
                raise MXNetError("inputs disagree on batch rows: %d vs %d"
                                 % (n, a.shape[0]))
            out[name] = a
        if n < 1:
            raise MXNetError("empty request (0 rows)")
        if bucket_for(n, self._edges) is None:
            raise MXNetError(
                "request of %d rows exceeds the largest serving bucket "
                "(%d); raise MXNET_SERVE_BUCKETS or split the request"
                % (n, self._edges[-1]))
        return out, n

    # -- compilation ---------------------------------------------------
    def _key(self, bucket):
        sig = tuple((n, (bucket,) + self._input_tails[n],
                     str(self._input_dtypes[n]))
                    for n in self._input_names)
        # the Pallas dispatch fingerprint rides in the key like in the
        # cached-op and SPMD program caches: bucket forwards trace
        # through the op-lowering seam, and this LRU outlives an
        # MXNET_PALLAS flip — the escape hatch must recompile, not
        # serve the stale lowering
        return ("serve", self.name, bucket, sig, self._dtype_tag,
                _pallas_dispatch.fingerprint())

    def _build_forward(self, bucket):
        """Pure ``fwd(params, aux, inputs)`` for one bucket: the
        ``deploy.py`` DAG walk, with params/aux as *arguments* instead
        of baked constants."""
        symbol = self._symbol
        nodes = symbol._nodes()
        head = [(id(n), oi) for n, oi in symbol._outputs]
        aux_names = symbol.list_auxiliary_states()
        aux_set = set(aux_names)
        aux_order = {n: i for i, n in enumerate(aux_names)}
        shapes = {n: (bucket,) + self._input_tails[n]
                  for n in self._input_names}
        arg_shapes, _, _ = symbol.infer_shape_partial(**shapes)
        zero_shapes = {}
        for n, s in zip(symbol.list_arguments(), arg_shapes):
            if n in self._zero_args:
                if s is None:
                    raise MXNetError(
                        "argument %r is neither an input nor in the "
                        "params and its shape cannot be inferred" % n)
                zero_shapes[n] = tuple(s)
        from ..executor import shape_overrides
        known = dict(shapes)
        known.update({n: tuple(a.shape) for n, a in self._params.items()})
        overrides = shape_overrides(symbol, known)
        cdt = self._cdt
        input_set = set(self._input_names)

        def fwd(params, aux, inputs):
            vals = {}
            for node in nodes:
                if node.is_variable:
                    nm = node.name
                    if nm in aux_set:
                        v = aux[aux_order[nm]]
                    elif nm in input_set:
                        v = inputs[nm]
                        if cdt is not None and v.dtype != cdt and \
                                jnp.issubdtype(v.dtype, jnp.floating):
                            v = v.astype(cdt)
                    elif nm in zero_shapes:
                        v = jnp.zeros(zero_shapes[nm],
                                      cdt or jnp.float32)
                    else:
                        v = params[nm]
                    vals[(id(node), 0)] = v
                    continue
                ins = [vals[(id(s), oi)] for s, oi in node.arg_inputs()]
                aux_in = tuple(vals[(id(s), oi)]
                               for s, oi in node.aux_inputs())
                outs, _ = node.op.apply(
                    overrides.get(id(node), node.attrs), ins, aux_in,
                    False, None)
                for oi, o in enumerate(outs):
                    vals[(id(node), oi)] = o
            outs = tuple(vals[k] for k in head)
            if cdt is not None:
                outs = tuple(
                    o.astype(jnp.float32)
                    if jnp.issubdtype(o.dtype, jnp.floating)
                    and o.dtype != jnp.float32 else o
                    for o in outs)
            return outs

        return fwd

    def _compile(self, bucket):
        tic = time.perf_counter()
        fwd = self._build_forward(bucket)
        # AOT specs carry the placement: without it the executable
        # compiles for the default device and rejects device-pinned
        # params at call time
        sh = (jax.sharding.SingleDeviceSharding(self._device)
              if self._device is not None else None)
        spec = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            (self._params, self._aux))
        in_spec = {n: jax.ShapeDtypeStruct(
            (bucket,) + self._input_tails[n],
            jnp.dtype(self._input_dtypes[n]), sharding=sh)
            for n in self._input_names}
        compiled = jax.jit(fwd).lower(spec[0], spec[1], in_spec).compile()
        out_avals = jax.eval_shape(fwd, spec[0], spec[1], in_spec)
        flags = tuple(len(o.shape) > 0 and o.shape[0] == bucket
                      for o in out_avals)
        ms = (time.perf_counter() - tic) * 1e3
        return _Program(compiled, bucket, flags, ms)

    def _acquire(self, bucket):
        """LRU lookup/compile for one bucket (cached_op.acquire shape:
        compile outside the lock, re-check for a race on insert)."""
        key = self._key(bucket)
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                self._stats["hits"] += 1
                _cache_event("hits").inc()
                return prog
        prog = self._compile(bucket)
        with self._lock:
            raced = self._programs.get(key)
            if raced is not None:
                self._stats["hits"] += 1
                _cache_event("hits").inc()
                return raced
            self._stats["compiles"] += 1
            self._stats["compile_ms_total"] += prog.compile_ms
            _cache_event("compiles").inc()
            while len(self._programs) >= self.max_programs:
                self._programs.popitem(last=False)
                self._stats["evictions"] += 1
                _cache_event("evictions").inc()
            self._programs[key] = prog
            return prog

    def warmup(self, execute=True):
        """Compile — and by default EXECUTE once on zeros — every
        configured bucket ahead of traffic (warmup-at-load).  The
        execution matters: a freshly compiled XLA executable pays
        tens of ms of one-time setup (buffer/thread-pool init) on its
        first run, which must not land inside a served request.
        Returns the per-bucket compile times (ms)."""
        out = {}
        for b in self._edges:
            prog = self._acquire(b)
            out[b] = prog.compile_ms
            if execute:
                feed = {n: np.zeros((b,) + self._input_tails[n],
                                    self._input_dtypes[n])
                        for n in self._input_names}
                params, aux, _v = self._live
                jax.block_until_ready(prog.fn(params, aux, feed))
        return out

    # -- execution -----------------------------------------------------
    @hot_path
    def run(self, inputs, n=None, slice_outputs=True):
        """Run ``n`` rows of canonicalized inputs through the bucketed
        program.  Returns ``(outputs, bucket, batch_major)``:
        batch-major outputs come sliced back to ``n`` rows (device-side
        lazy slice, no host sync); ``batch_major`` flags which outputs
        carry a leading batch axis.  ``slice_outputs=False`` returns
        the raw bucket-shaped outputs (pad rows included) — the
        scheduler uses it because it re-slices per request anyway, and
        the intermediate ``[:n]`` would compile one XLA slice program
        per distinct row count.  Called from the serving engine's
        dispatch loop — everything here is enqueue-only device work
        plus cheap host padding."""
        if n is None:
            n = int(inputs[self._input_names[0]].shape[0])
        bucket = bucket_for(n, self._edges)
        if bucket is None:
            raise MXNetError(
                "request of %d rows exceeds the largest serving bucket "
                "(%d)" % (n, self._edges[-1]))
        prog = self._acquire(bucket)
        feed = {}
        for name in self._input_names:
            v = inputs[name]
            if v.shape[0] != bucket:
                pad = np.zeros((bucket,) + tuple(v.shape[1:]), v.dtype)
                pad[:n] = v
                v = pad
            feed[name] = v
        # ONE read of the published (params, aux, version) snapshot:
        # the hot-swap atomicity guarantee — this request runs entirely
        # against one weight version
        params, aux, _v = self._live
        outs = prog.fn(params, aux, feed)
        if slice_outputs:
            outs = [o[:n] if bm and n != bucket else o
                    for o, bm in zip(outs, prog.out_batch_major)]
        else:
            outs = list(outs)
        return outs, bucket, prog.out_batch_major

    # -- introspection -------------------------------------------------
    def stats(self):
        """Compile-cache stats: hits/compiles/evictions/size plus the
        currently-resident buckets."""
        with self._lock:
            out = dict(self._stats)
            out["size"] = len(self._programs)
            out["max_programs"] = self.max_programs
            out["buckets_resident"] = sorted(
                p.bucket for p in self._programs.values())
        out["edges"] = list(self._edges)
        out["compute_dtype"] = self._dtype_tag
        params, aux, version = self._live
        out["version"] = version
        out["weight_bytes"] = _weight_bytes((params, aux))
        return out

    def reset_stats(self):
        with self._lock:
            for k in ("hits", "compiles", "evictions"):
                self._stats[k] = 0
            self._stats["compile_ms_total"] = 0.0


def cache_donate_argnums(nums):
    """Donate the KV-cache arguments off-CPU only — PJRT:CPU has no
    donation (the same never-on-CPU guard as the training planes'
    donation seams; donating there only warns, once per compiled
    bucket).  Callers rebind their cache references to the program
    outputs either way, so behavior is identical."""
    return () if jax.default_backend() == "cpu" else tuple(nums)


# ---------------------------------------------------------------------------
# Generative (autoregressive) program store: the prefill/decode split.
#
# A generation workload is two programs, not one.  PREFILL runs a
# padded prompt batch once, fills the KV cache and emits the logits the
# first generated token samples from; DECODE consumes ONE token per
# sequence against the cache.  Both are AOT-compiled and warmed exactly
# like the forward store's bucket programs, with the program key space
#
#   prefill: (batch-bucket, prompt-bucket)   -> cache sized for the bucket
#   decode:  (batch-bucket, cache-bucket)    -> cache bucket = a multiple
#                                               of MXNET_SERVE_KV_BLOCK
#
# so arbitrary request shapes and growing sequences hit a small fixed
# set of executables.  The KV cache itself is SERVING STATE living
# beside the params (one device-resident copy, owned by whoever drives
# the programs — the GenerationEngine attaches its live state here for
# introspection); the programs stay pure — cache in, updated cache out —
# with both cache arguments DONATED, so the per-step update lowers to an
# in-place dynamic_update_slice on the resident buffers.
# ---------------------------------------------------------------------------
# The decode-mode model modules the store can run.  A spec picks one
# with its ``arch`` key (default: the repo's own LM); what the module
# offers under the seam's names (``serving_spec``, ``required_params``,
# ``pack_params``, ``quantize_params``, ``init_pool``, ``paged_step``,
# ``OFFERS``, ``AUX_COUNTERS``; models/transformer_lm.py, bottom; and,
# where the pool's blocks come in more than one class, ``cache_classes``:
# models/cohere2_moe.py) is all the store knows of an architecture.
# A pool may hold several leaves of ONE class (``deepseek_v32``: latent
# rows and index keys): they ride one table and one allocator.  A module
# that also has ``paged_step_groups`` offers ONE step over several row
# groups, each with a row count and a query length of its own, reading
# its weights once for all of them (the expert models:
# ``GenerativeProgramStore.one_pass``).
_ARCHS = ("transformer_lm", "deepseek_v3", "lfm2_moe", "cohere2_moe",
          "deepseek_v32", "pangu_ultra_moe")


def _serving_model(arch):
    import importlib
    arch = arch or "transformer_lm"
    if arch not in _ARCHS:
        raise MXNetError("unknown generative arch %r (has: %s)"
                         % (arch, ", ".join(_ARCHS)))
    return importlib.import_module("..models." + arch, __package__)


def chunk_rows(bb):
    """How many rows the prompt-chunk dispatch of a ``bb``-slot bucket
    has.  A chunk program costs by its rows, live or dead, and a
    steady batch keeps about a fifth of its slots in their prompt; the
    decode program stays ``bb`` wide.  A quarter of the slots, never
    under four rows while the bucket has them (PERF.md section 6, PR 27: the
    sweep over an eighth, a quarter and a half)."""
    return max(min(bb, 4), bb // 4)


PAGED_KINDS = ("paged_step", "paged_step_sample", "paged_step_sample_p",
               "paged_chunk_sample", "paged_verify", "paged_tick_sample")
# a SELF-DRAFTING store's four (``self_draft``): the target's verify of
# K + 1 positions a row and its prompt chunk, each handing its hidden
# states to the prediction module's own program behind it
SELF_DRAFT_KINDS = ("paged_self_verify", "paged_self_chunk",
                    "paged_draft_step", "paged_draft_chunk")
# on the profiler's module line a self-draft's kind goes by its own name
# (``jit_`` in front), but every program that carries a prompt chunk
# keeps the chunk's prefix: what counts the step programs' executions,
# or reads prefill's share of the device, by module name goes on
# counting them
_CHUNK_NAMES = {"paged_self_chunk": "paged_prefill_chunk_self",
                "paged_draft_chunk": "paged_prefill_chunk_draft",
                "paged_tick_sample": "paged_prefill_chunk_tick"}
# where the decode group's arguments start among ``paged_tick_sample``'s
# own: behind the compacted chunk's nine.  Behind the decode group's
# seven: the chunk rows' own chains, then the slots' pending tokens and
# which decode rows take the host's token instead
_TICK_DECODE_AT = 9
_TICK_ROW_KEYS_AT = _TICK_DECODE_AT + 7
# results in front of the pool's leaves in a kind's flat return
_HEADS = {"paged_step_sample_p": 2, "paged_verify": 2,
          "paged_self_verify": 4, "paged_self_chunk": 3}


def paged_program(model, spec, kind, lq, kv_block, nleaf, int8=False,
                  pending=False):
    """The function one paged step program compiles, named as the
    profiler's module line shows it, and the positions of the
    arguments it donates: ``(fn, donate)``.

    ONE unified step for the paged plane: ``lq`` is the query length (1
    = a decode step; prefill_chunk = one prompt chunk; spec_k+1 = a
    speculative verify).  Write-then-attend over the global pool
    through ``(rows, table_width)`` block tables (a model with several
    classes of block takes a table a class, side by side in that one
    array: ``GenerativeProgramStore.table_width``); rows not taking part
    in a dispatch ride with all-zero tables (they reach only the
    reserved trash block 0) and their outputs are discarded host-side.
    ``fn(params, *pool leaves, tables, tokens, positions, valid,
    *the kind's own)``; on the int8 plane every kind gains the two
    donated scale pools right after the ``nleaf`` code pools, in
    arguments AND returns.  ``model`` is a decode-mode model module
    (``_serving_model``), ``spec`` its serving spec.

    ``pending`` (a :attr:`GenerativeProgramStore.one_pass` store's
    ``paged_step_sample``; its ``paged_tick_sample`` always): the
    slots' PENDING TOKENS live on the device as their key chains do.
    The program takes two arguments more, ``pending (S,) int32``
    (donated, handed back behind the chains) and ``host (S,) bool``: a
    decode row reads its input token from ``pending`` unless ``host``
    says the host owns it (then ``tokens``, as every other store's),
    and a row that samples (a decode row that ``do``es, a chunk row
    that finishes its prompt) writes its token to its slot's place, so
    the next program can be queued before this one's tokens are
    fetched."""
    npool = nleaf + (2 if int8 else 0)
    pending = pending and kind == "paged_step_sample"
    pool_donate = tuple(range(1, 1 + npool))
    name = _CHUNK_NAMES.get(kind) or (
        kind if kind in SELF_DRAFT_KINDS + ("paged_verify",)
        else "paged_decode" if int(lq) == 1
        and kind != "paged_chunk_sample" else "paged_prefill_chunk")

    def step(params, pls, tables, tokens, positions, valid,
             all_logits=False):
        # the model's paged step with the donated leaves threaded
        # through uniformly: returns (logits, new leaves, the model's
        # counters or None)
        return model.paged_step(
            params, pls[:nleaf], tables, tokens, positions, valid, spec,
            kv_block, scales=tuple(pls[nleaf:]) if int8 else None,
            all_logits=all_logits)

    if kind in ("paged_step_sample", "paged_step_sample_p",
                "paged_chunk_sample"):
        # in-graph sampling with a per-row enable mask: a chunk
        # dispatch samples ONLY the rows finishing their prompt this
        # tick (do_sample), everyone else's PRNG chain must not
        # advance.  The _p variant additionally emits the proposal
        # distribution q — the draft model's step in speculative
        # decoding.  paged_chunk_sample is the COMPACTED prompt chunk:
        # its rows are the slots in their prompt, fewer than the slots
        # whose (S, 2) key chains it takes and hands back, and
        # ``slots`` (last argument) says which slot each row works for.
        with_q = kind == "paged_step_sample_p"
        compact = kind == "paged_chunk_sample"

        def fn(params, *rest):
            pls = rest[:npool]
            (tables, tokens, positions, valid, keys, temps, top_ks,
             do_sample) = rest[npool:npool + 8]
            if pending:
                held, host = rest[npool + 8:]
                tokens = jnp.where(host, tokens[:, 0], held)[:, None]
            logits, new_pools, aux = step(
                params, pls, tables, tokens, positions, valid)
            if compact:
                toks, new_keys = sample_chunk_rows(
                    logits, keys, temps, top_ks, do_sample,
                    rest[npool + 8])
            else:
                if with_q:
                    toks, carry, q = sample_tokens_p(
                        logits, keys, temps, top_ks)
                else:
                    toks, carry = sample_tokens(logits, keys, temps,
                                                top_ks)
                new_keys = jnp.where(do_sample[:, None], carry, keys)
            tail = (new_keys,)
            if pending:
                tail += (jnp.where(do_sample, toks, held),)
            if aux is not None:
                # the model's counters ride behind the tokens: one
                # small array, one fetch
                toks = jnp.concatenate([toks, aux.astype(toks.dtype)])
            head = (toks, q) if with_q else (toks,)
            return head + new_pools + tail

        donate = pool_donate + (1 + npool + 4,) + (
            (1 + npool + 8,) if pending else ())
    elif kind == "paged_tick_sample":
        # the ONE-PASS tick of a model with a step over row groups: the
        # bucket's decode rows (one query each) and its compacted
        # prompt chunk as two groups of ONE step, so the experts and
        # the dense weights are read once for both.  The chunk's nine
        # arguments as ``paged_chunk_sample`` takes them, then the
        # decode group's tables, tokens, positions, valid, temps,
        # top_ks and do_sample; the slots' key chains once.  A slot is
        # in one group, so the two samplers' chains never meet.  Then
        # the chunk rows' OWN chains (a row that samples starts its
        # request's chain: the host sends the seed key, and admission
        # writes nothing to the device), the slots' pending tokens and
        # the decode rows' ``host`` flags (``pending``, above).  ONE
        # int32 array comes back: the decode rows' tokens, the chunk
        # rows', the model's counters once.
        def fn(params, *rest):
            pls = rest[:npool]
            (tables, tokens, positions, valid, keys, temps, top_ks,
             do_sample, slots) = rest[npool:npool + _TICK_DECODE_AT]
            (dtables, dtokens, dpositions, dvalid, dtemps, dtop_ks,
             ddo) = rest[npool + _TICK_DECODE_AT:npool + _TICK_ROW_KEYS_AT]
            row_keys, held, host = rest[npool + _TICK_ROW_KEYS_AT:]
            dtokens = jnp.where(host, dtokens[:, 0], held)[:, None]
            (dlogits, logits), new_pools, aux = model.paged_step_groups(
                params, pls, ((dtables, dtokens, dpositions, dvalid),
                              (tables, tokens, positions, valid)),
                spec, kv_block)
            dtoks, carry = sample_tokens(dlogits, keys, dtemps, dtop_ks)
            toks, new_keys = sample_chunk_rows(
                logits, jnp.where(ddo[:, None], carry, keys), temps,
                top_ks, do_sample, slots, row_keys)
            # a chunk row that samples hands its slot its first token
            # (rows that do not are sent past the slot axis, as their
            # chains are)
            dest = jnp.where(do_sample, slots, held.shape[0])
            held = jnp.where(ddo, dtoks, held).at[dest].set(
                toks, mode="drop")
            return (jnp.concatenate([dtoks, toks, aux.astype(toks.dtype)]),
                    ) + tuple(new_pools) + (new_keys, held)

        donate = pool_donate + (1 + npool + 4,
                                1 + npool + _TICK_ROW_KEYS_AT + 1)
    elif kind == "paged_verify":
        # speculative verify: all lq=K+1 positions' logits stay
        # in-graph, the rejection rule runs beside them (spec_verify),
        # and the host fetch is two small integer vectors — never
        # logits.  tokens[:, 0] is the slot's pending next token,
        # tokens[:, 1:] the K draft proposals; prop_q is the draft's
        # (bb, K, vocab) proposal distribution from
        # paged_step_sample_p.
        def fn(params, *rest):
            pls = rest[:npool]
            (tables, tokens, positions, valid, prop_q, keys, temps,
             top_ks, do_sample) = rest[npool:]
            logits_all, new_pools, _ = step(
                params, pls, tables, tokens, positions, valid,
                all_logits=True)
            out, n_emit, carry = spec_verify(
                logits_all, tokens[:, 1:], prop_q, keys, temps, top_ks,
                valid)
            new_keys = jnp.where(do_sample[:, None], carry, keys)
            return (out, n_emit) + new_pools + (new_keys,)

        donate = pool_donate + (1 + npool + 5,)
    elif kind in ("paged_self_verify", "paged_self_chunk"):
        # the TARGET's half of a self-drafting dispatch.  The verify:
        # tokens[:, 0] the slot's pending token, tokens[:, 1] the
        # module's proposal (its argmax: a one-hot density, so a
        # sampling row accepts it with probability p(d)); the same
        # rejection rule, and the step's hidden states, emitted tokens
        # and counts stay on the device for the module's program.  The
        # chunk: the compacted prompt chunk, and beside its hidden
        # states the token AFTER each row (the next prompt token,
        # ``after`` behind the chunk's last, the sampled one where the
        # prompt ends), which is what the module's row there takes.
        verify = kind == "paged_self_verify"

        def fn(params, *rest):
            pls = rest[:npool]
            (tables, tokens, positions, valid, keys, temps, top_ks,
             do_sample) = rest[npool:npool + 8]
            logits, new_pools, aux, hid = model.paged_step(
                params, pls[:nleaf], tables, tokens, positions, valid,
                spec, kv_block, all_logits=verify, hidden=True)
            if verify:
                out, n_emit, carry = spec_verify(
                    logits, tokens[:, 1:], jax.nn.one_hot(
                        tokens[:, 1:], logits.shape[-1],
                        dtype=jnp.float32),
                    keys, temps, top_ks, valid)
                new_keys = jnp.where(do_sample[:, None], carry, keys)
                head = (jnp.concatenate([out.reshape(-1), n_emit,
                                         aux.astype(jnp.int32)]),
                        out, n_emit, hid)
            else:
                slots, after = rest[npool + 8:]
                toks, new_keys = sample_chunk_rows(
                    logits, keys, temps, top_ks, do_sample, slots)
                nxt = jnp.concatenate(
                    [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)
                nxt = nxt.at[jnp.arange(tokens.shape[0]), valid - 1].set(
                    jnp.where(do_sample, toks, after))
                head = (jnp.concatenate([toks, aux.astype(toks.dtype)]),
                        nxt, hid)
            return head + new_pools + (new_keys,)

        donate = pool_donate + (1 + npool + 4,)
    elif kind in ("paged_draft_step", "paged_draft_chunk"):
        # the prediction MODULE behind either: its row at position p + 1
        # from the target's hidden state at p and the token at p + 1
        # (``positions`` are the target's); the proposal is its argmax
        # at each sequence's last valid row.  ``head``, what the
        # target's program packed for the host, rides through: one
        # array, one fetch a dispatch.
        def fn(params, *rest):
            pls = rest[:npool]
            tables, tokens, positions, valid, hid, head = rest[npool:]
            logits, new_pools, aux = model.draft_step(
                params, pls[:nleaf], tables, hid, tokens, positions + 1,
                valid, spec, kv_block)
            prop = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (jnp.concatenate(
                [head, prop, aux.astype(jnp.int32)]),) + new_pools

        donate = pool_donate
    else:   # paged_step (logits out — the host-sampling hatch)
        def fn(params, *rest):
            pls = rest[:npool]
            tables, tokens, positions, valid = rest[npool:]
            logits, new_pools, _ = step(
                params, pls, tables, tokens, positions, valid)
            return (logits,) + new_pools

        donate = pool_donate
    fn.__name__ = name
    return fn, donate


class GenerativeProgramStore:
    """AOT prefill/decode programs for one autoregressive LM.

    Parameters
    ----------
    params : dict
        name -> array, the ``transformer_lm`` symbol graph's trained
        arguments (``embed_weight``, ``blk*_*``, ``final_ln_*``,
        ``pred_*``).
    spec : dict
        ``transformer_lm.lm_spec(...)`` architecture spec, or another
        decode-mode model's with its name under ``arch``
        (``"deepseek_v3"``: ``models/deepseek_v3.serving_spec``,
        ``"lfm2_moe"``: ``models/lfm2_moe.serving_spec``; paged plane
        only, no int8 pool).  A model that restacks leaves at
        load (``deepseek_v3``'s routed experts) pops them from
        ``params`` as it goes, and with ``compute_dtype="int8"`` its
        family takes every leaf (each is freed as its codes are made):
        hand it a copy to keep yours.
    batch_buckets / prompt_buckets : iterable of int, optional
        Bucket edges; default ``MXNET_SERVE_BUCKETS`` /
        ``MXNET_SERVE_PROMPT_BUCKETS``.
    kv_block / kv_max : int, optional
        Cache-length quantum and cap; default ``MXNET_SERVE_KV_BLOCK``
        / ``MXNET_SERVE_KV_MAX``.
    compute_dtype : str, optional
        None (fp32, the parity baseline), ``'bfloat16'`` (weights cast
        once at load, decode-mode compute follows them, logits return
        fp32) or ``'int8'`` (matmul weights quantized once at load into
        ``(codes, scales)`` pairs — ``transformer_lm.
        quantize_lm_params`` — dequantized in-program through the fused
        dequant-matmul door; ~4x less resident weight memory).
    kv_dtype : str, optional
        KV-cache element dtype: ``'float32'`` or ``'bfloat16'``
        (halves cache bytes per slot, so the same ``MXNET_SERVE_KV_
        MAX`` memory budget holds twice the concurrent sequences);
        default ``MXNET_SERVE_KV_DTYPE``.  Attention over the cache
        accumulates fp32 in the kernel AND the dense twin regardless.
    sample : str, optional
        ``'graph'`` (default via ``MXNET_SERVE_SAMPLE``) compiles
        sampling INTO the decode programs (``decode_sample`` kind:
        per-slot PRNG keys ride as a donated argument, the host fetch
        shrinks from (slots, vocab) logits to (slots,) tokens);
        ``'host'`` keeps the logits-returning decode programs — the
        escape hatch, byte-identical token streams (shared
        :func:`sample_tokens`).
    paged : bool, optional
        Paged KV plane (default ``MXNET_SERVE_PAGED``): cache memory
        becomes a global pool of ``kv_block``-token blocks addressed
        through per-slot block tables; the decode engine runs unified
        ``paged_step`` programs (chunked prefill + decode) with
        copy-on-write prefix sharing instead of the prefill/decode
        pair over per-slot cache rectangles.  ``paged=False`` is the
        contiguous escape hatch (bit-identical token streams, pinned
        by tests/test_paged_decode.py).
    prefill_chunk : int, optional
        Chunked-prefill quantum of the paged plane (default
        ``MXNET_SERVE_PREFILL_CHUNK``; clamped to ``kv_max``).
    pool_blocks : int, optional
        Physical block count of the paged pool, including the
        reserved trash block 0 (default ``MXNET_SERVE_KV_POOL_
        BLOCKS``; 0 = auto-size for the largest batch bucket at full
        ``kv_max`` depth).
    max_programs : int, optional
        LRU bound; default is sized to hold every warmable program
        (never smaller than ``MXNET_SERVE_PROGRAM_CACHE``).
    device : jax.Device, optional
        Pin params (and hence programs + cache) to this device.
    self_draft : int, optional
        Tokens the model's OWN prediction module drafts a decode step
        (0/None: the module is not loaded; 1: every decode step
        verifies one proposal and yields one or two tokens).  The
        module's cache is one more layer of the model's pool leaf, on
        the same block tables; the configuration decides, no
        environment variable does (``models/pangu_ultra_moe.py``).
    """

    def __init__(self, params, spec, name="lm", batch_buckets=None,
                 prompt_buckets=None, kv_block=None, kv_max=None,
                 compute_dtype=None, kv_dtype=None, sample=None,
                 paged=None, prefill_chunk=None, pool_blocks=None,
                 max_programs=None, device=None, self_draft=None):
        spec = dict(spec)
        self._model = _serving_model(spec.pop("arch", None))
        self._spec = self._model.serving_spec(spec)  # validates
        self.aux_counters = tuple(self._model.AUX_COUNTERS)
        self.name = name
        self._device = device
        self._compute = None
        if compute_dtype:
            c = str(compute_dtype).lower()
            if c in ("float32", "fp32"):
                c = None
            elif c not in ("bfloat16", "int8"):
                raise MXNetError(
                    "generative compute_dtype must be None/'float32'/"
                    "'bfloat16'/'int8', got %r" % compute_dtype)
            self._compute = c
        kv = str(kv_dtype if kv_dtype is not None
                 else get_env("MXNET_SERVE_KV_DTYPE") or "float32")
        if kv not in ("float32", "bfloat16", "int8"):
            raise MXNetError("kv_dtype must be 'float32', 'bfloat16' or "
                             "'int8', got %r" % kv)
        # int8 KV: pool blocks hold int8 codes with per-(layer, head,
        # block) fp32 absmax scales riding as a parallel donated scale
        # pool — a paged-plane feature (the contiguous plane has no
        # block granularity to hang the scales on)
        self.kv_int8 = kv == "int8"
        self.kv_dtype = jnp.dtype(kv)
        if self.kv_int8:
            self._need("int8_kv", "an int8 KV pool (kv_dtype='int8')")
        sm = str(sample if sample is not None
                 else get_env("MXNET_SERVE_SAMPLE") or "graph").lower()
        if sm not in ("graph", "host"):
            raise MXNetError("MXNET_SERVE_SAMPLE must be 'graph' or "
                             "'host', got %r" % sm)
        self.sample_mode = sm
        self._batch_edges = bucket_edges(batch_buckets)
        self._prompt_edges = bucket_edges(
            prompt_buckets, env_var="MXNET_SERVE_PROMPT_BUCKETS")
        self.kv_block = int(kv_block if kv_block is not None
                            else get_env("MXNET_SERVE_KV_BLOCK"))
        self.kv_max = int(kv_max if kv_max is not None
                          else get_env("MXNET_SERVE_KV_MAX"))
        if self.kv_block < 1 or self.kv_max < self.kv_block:
            raise MXNetError("need 1 <= kv_block <= kv_max, got %d/%d"
                             % (self.kv_block, self.kv_max))
        if self._prompt_edges[-1] > self.kv_max:
            raise MXNetError(
                "largest prompt bucket (%d) exceeds MXNET_SERVE_KV_MAX "
                "(%d)" % (self._prompt_edges[-1], self.kv_max))

        # paged KV plane: cache memory as a global pool of kv_block-
        # token blocks addressed through per-slot block tables
        # (docs/architecture/decode_engine.md).  MXNET_SERVE_PAGED=0
        # (or paged=False) keeps the contiguous per-slot plane.
        self.paged = bool(int(get_env("MXNET_SERVE_PAGED"))
                          if paged is None else paged)
        if not self.paged:
            # D2: the contiguous plane is not taught new models
            self._need("contiguous", "the contiguous plane (paged=False)")
        if self.kv_int8 and not self.paged:
            raise MXNetError(
                "kv_dtype='int8' needs the paged KV plane (the scales "
                "are per pool block); set MXNET_SERVE_PAGED=1 or use "
                "'float32'/'bfloat16' on the contiguous plane")
        self.self_draft = int(self_draft or 0)
        if self.self_draft:
            self._need("self_draft", "a self-drafting decode step "
                       "(self_draft=%d)" % self.self_draft)
            if not self.paged or sm != "graph":
                raise MXNetError(
                    "self_draft needs the paged plane with in-graph "
                    "sampling (paged=True, sample='graph')")
            self._spec = self._model.with_draft(self._spec,
                                                self.self_draft)
        # a tick's decode rows and its prompt chunk's rows as ONE
        # program (``paged_tick_sample``, in the chunk program's place):
        # where the model offers a step over row groups and the tick is
        # the plain two-program one.  Host sampling, an int8 pool and a
        # self-drafting store keep their sequence of programs
        self.one_pass = bool(
            self.paged and sm == "graph" and not self.kv_int8
            and not self.self_draft
            and hasattr(self._model, "paged_step_groups"))
        chunk = int(prefill_chunk if prefill_chunk is not None
                    else get_env("MXNET_SERVE_PREFILL_CHUNK"))
        if chunk < 1:
            raise MXNetError("prefill_chunk must be >= 1, got %d"
                             % chunk)
        self.prefill_chunk = min(chunk, self.kv_max)
        nb = int(pool_blocks if pool_blocks is not None
                 else get_env("MXNET_SERVE_KV_POOL_BLOCKS"))
        if nb <= 0:
            # auto: the largest batch bucket at full kv_max depth,
            # plus the reserved trash block 0
            nb = self._batch_edges[-1] * self.class_width() + 1
        if self.paged and nb < self.class_width() + 1:
            raise MXNetError(
                "paged KV pool of %d blocks cannot hold one full-"
                "depth sequence (%d blocks + the reserved trash "
                "block); raise MXNET_SERVE_KV_POOL_BLOCKS"
                % (nb, self.class_width()))
        self.pool_blocks = nb
        self._copy_fn = {}     # lazily jitted COW block copy, a class
        # the pool's leaves as the model shapes them: (k, v) for the
        # LM, one latent leaf for deepseek_v3, the latent leaf and the
        # indexer's keys (two TOKEN leaves on one table) for
        # deepseek_v32, [K | V] rows and the convolution state (one row
        # a BLOCK) for lfm2_moe
        self._pool_avals = tuple(jax.eval_shape(
            lambda: self._model.init_pool(self._spec, nb, self.kv_block,
                                          dtype=self.kv_dtype)))
        # the pool's CLASSES of block, ``(window, leaves)`` each: a
        # model's blocks are all of one class (every leaf rides one
        # table, a sequence keeps every block) unless it says otherwise
        # (``cache_classes``: models/cohere2_moe.py).  ``pool_blocks``
        # counts the blocks of EACH class
        classes = getattr(self._model, "cache_classes", None)
        self.cache_classes = tuple(
            (w, tuple(leaves)) for w, leaves in classes(self._spec)) \
            if classes else ((None, tuple(range(len(self._pool_avals)))),)

        self._params = self._load_params(params)
        missing = [k for k in self._required_params()
                   if k not in self._params]
        if missing:
            raise MXNetError("generative model %r is missing params %s"
                             % (name, missing))
        self._version = 1

        # one warm sweep must fit the LRU or AOT is a lie (the forward
        # store logs the same hazard; here we just size for it).  The
        # paged plane's warm set is two step programs a batch bucket:
        # the decode step and the compacted prompt chunk (a one-pass
        # store's: the chunk WITH the decode rows, in its place).
        if self.paged:
            n_warm = (4 if self.self_draft else 2) * len(self._batch_edges)
        else:
            n_warm = (len(self._batch_edges) * len(self._prompt_edges) +
                      len(self._batch_edges) *
                      len({self.kv_bucket(p) for p in self._prompt_edges}))
        if max_programs is None:
            max_programs = max(int(get_env("MXNET_SERVE_PROGRAM_CACHE")),
                               2 * n_warm)
        self.max_programs = max(1, int(max_programs))
        if self.max_programs < n_warm:
            log.warning(
                "generative model %r: program cache (%d) is smaller "
                "than the warm set (%d); warmed programs will be "
                "evicted and recompile inside served requests",
                name, self.max_programs, n_warm)
        self._programs = OrderedDict()
        self._lock = make_lock("serving.gen_program_store")
        self._stats = {"hits": 0, "compiles": 0, "evictions": 0,
                       "compile_ms_total": 0.0}
        # live decode state (attached by the GenerationEngine): the
        # cache lives here, beside the params — registry-owned serving
        # state, introspectable via stats()
        self.cache_state = None

    def _need(self, what, wording):
        if what not in self._model.OFFERS:
            raise MXNetError("generative arch %r does not offer %s"
                             % (self._model.__name__.rsplit(".", 1)[-1],
                                wording))

    def _load_params(self, params):
        """The trained weight dict through the model's restacking
        (``pack_params``), the serving dtype policy (fp32 pass-through
        / bf16 cast / int8 matmul-weight quantization) and device
        pinning; shared by load and :meth:`swap_params` so both produce
        identical trees."""
        device = self._device
        params = self._model.pack_params(params, self._spec)

        def load(v):
            a = _as_device_array(v)
            if self._compute == "bfloat16" and \
                    jnp.issubdtype(a.dtype, jnp.floating) and \
                    a.dtype != jnp.bfloat16:
                a = a.astype(jnp.bfloat16)
            if device is not None:
                a = jax.device_put(a, device)
            return a

        if self._compute == "int8":
            out = {}
            leaves = {k: _as_device_array(v) for k, v in params.items()}
            if getattr(self._model, "QUANTIZE_TAKES_LEAVES", False):
                # a quantizer that frees each plain leaf as its codes
                # are made gets the ONLY references: the caller's dict
                # is emptied, as ``pack_params`` empties it of what it
                # restacks (hand over a copy to keep yours)
                params.clear()
            for k, v in self._model.quantize_params(
                    leaves, self._spec).items():
                if isinstance(v, QuantizedWeight):
                    c, s = jnp.asarray(v.codes), jnp.asarray(v.scales)
                    if device is not None:
                        c = jax.device_put(c, device)
                        s = jax.device_put(s, device)
                    out[k] = QuantizedWeight(c, s)
                else:
                    out[k] = load(v)
            return out
        return {k: load(v) for k, v in params.items()}

    # -- hot weight swap -----------------------------------------------
    def swap_params(self, params):
        """Atomically republish the decode plane's weight arguments
        (same contract as :meth:`ProgramStore.swap_params`: identical
        names/shapes/dtypes, no recompile, one reference assignment).
        Each program DISPATCH binds one version — a prefill or a decode
        step is never torn — but a multi-step generation that straddles
        the swap continues on the NEW weights from its next step (its
        KV cache holds old-version context); latency-sensitive
        deployments that need whole-generation pinning should drain
        before swapping.  Returns the new version."""
        new_params = self._load_params(params)
        missing = [k for k in self._required_params()
                   if k not in new_params]
        if missing:
            raise MXNetError("swap_params for %r is missing %s"
                             % (self.name, sorted(missing)))
        old_leaves = jax.tree_util.tree_leaves(
            {k: self._params[k] for k in sorted(self._params)})
        new_leaves = jax.tree_util.tree_leaves(
            {k: new_params[k] for k in sorted(self._params)
             if k in new_params})
        if sorted(new_params) != sorted(self._params) or any(
                a.shape != b.shape or a.dtype != b.dtype
                for a, b in zip(new_leaves, old_leaves)):
            raise MXNetError(
                "swap_params for %r: the new weight set does not match "
                "the compiled programs' signature (the decode programs "
                "are not recompiled on swap)" % self.name)
        with self._lock:
            self._params = new_params
            self._version += 1
        return self._version

    @property
    def version(self):
        return self._version

    def param_snapshot(self):
        """Opaque live-weight handle for :meth:`restore_params` (same
        contract as ``ProgramStore.param_snapshot``)."""
        with self._lock:
            return dict(self._params)

    def restore_params(self, snap):
        """Republish a :meth:`param_snapshot` (rolling-swap abort
        path); bumps the version."""
        with self._lock:
            self._params = dict(snap)
            self._version += 1
        return self._version

    def _required_params(self):
        return self._model.required_params(self._spec)

    # -- geometry ------------------------------------------------------
    @property
    def spec(self):
        return dict(self._spec)

    @property
    def batch_edges(self):
        return self._batch_edges

    @property
    def prompt_edges(self):
        return self._prompt_edges

    def max_slots(self):
        return self._batch_edges[-1]

    def batch_bucket(self, n):
        b = bucket_for(n, self._batch_edges)
        if b is None:
            raise MXNetError("batch of %d sequences exceeds the largest "
                             "serving bucket (%d)"
                             % (n, self._batch_edges[-1]))
        return b

    def prompt_bucket(self, p):
        b = bucket_for(p, self._prompt_edges)
        if b is None:
            raise MXNetError(
                "prompt of %d tokens exceeds the largest prompt bucket "
                "(%d); raise MXNET_SERVE_PROMPT_BUCKETS or truncate"
                % (p, self._prompt_edges[-1]))
        return b

    def kv_bucket(self, length):
        """Cache length quantized UP to the kv-block quantum."""
        length = max(1, int(length))
        c = -(-length // self.kv_block) * self.kv_block
        if c > self.kv_max:
            raise MXNetError(
                "sequence needs a %d-token cache, past MXNET_SERVE_KV_"
                "MAX (%d)" % (c, self.kv_max))
        return c

    chunk_rows = staticmethod(chunk_rows)

    def chunk_program(self, bb):
        """``(kind, bucket, lq)`` of the prompt-chunk program of slot
        bucket ``bb``, the one chunk program that bucket dispatches: in
        graph mode a program of the bucket itself (it takes the
        bucket's key chains; a :attr:`one_pass` store's carries the
        bucket's decode rows too, and is the whole tick's), in host
        mode the logits-out step at the chunk's width."""
        if self.one_pass:
            return ("paged_tick_sample", bb, self.prefill_chunk)
        if self.sample_mode == "graph":
            return ("paged_chunk_sample", bb, self.prefill_chunk)
        return ("paged_step", self.chunk_rows(bb), self.prefill_chunk)

    def step_programs(self, bb):
        """``(kind, bucket, lq)`` of the four programs a SELF-DRAFTING
        store dispatches for slot bucket ``bb``, and the only ones it
        warms: the verify of ``self_draft + 1`` positions a row and the
        prompt chunk, each with the module's program behind it."""
        k1 = self.self_draft + 1
        return (("paged_self_verify", bb, k1), ("paged_draft_step", bb, k1),
                ("paged_self_chunk", bb, self.prefill_chunk),
                ("paged_draft_chunk", bb, self.prefill_chunk))

    def class_width(self):
        """Table entries of ONE class of block: logical blocks needed
        to address a full kv_max-token sequence."""
        return -(-self.kv_max // self.kv_block)

    def table_width(self):
        """Block-table width of the paged plane: :meth:`class_width`
        entries for each of the model's classes of block, side by side
        (one class, so that many entries, for every model but one that
        names ``cache_classes``)."""
        return len(self.cache_classes) * self.class_width()

    def validate_request(self, prompt_len, max_tokens):
        """Reject at submit anything whose cache could outgrow kv_max
        mid-flight.  On the contiguous plane the prompt must also fit
        a prompt bucket; the paged plane chunks prompts, so only the
        kv_max total and the pool's physical capacity bound it."""
        need = int(prompt_len) + max(1, int(max_tokens))
        if need > self.kv_max:
            raise MXNetError(
                "prompt_len %d + max_tokens %d exceeds MXNET_SERVE_KV_"
                "MAX (%d)" % (prompt_len, max_tokens, self.kv_max))
        if self.paged:
            blocks = -(-need // self.kv_block)
            if blocks > self.pool_blocks - 1:
                raise MXNetError(
                    "request needs %d KV blocks, past the paged pool's "
                    "%d usable blocks (MXNET_SERVE_KV_POOL_BLOCKS)"
                    % (blocks, self.pool_blocks - 1))
        else:
            self.prompt_bucket(int(prompt_len))

    def new_cache(self, batch, cache_len):
        k, v = self._model.init_cache(self._spec, batch, cache_len,
                                      dtype=self.kv_dtype)
        if self._device is not None:
            k = jax.device_put(k, self._device)
            v = jax.device_put(v, self._device)
        return k, v

    def new_pool(self):
        """The zeroed paged pool, a tuple of the model's leaves —
        ``(k, v)``, each ``(num_layers, num_heads, pool_blocks *
        kv_block, head_dim)``, for the LM; one latent leaf
        ``(num_layers, 1, pool_blocks * kv_block, width)`` for
        ``deepseek_v3``, that leaf and the index keys' ``(num_layers,
        1, pool_blocks * kv_block, index_head_dim)`` for
        ``deepseek_v32`` — block 0 is the reserved trash block zero
        table entries point at."""
        return self._placed(self._model.init_pool(
            self._spec, self.pool_blocks, self.kv_block,
            dtype=self.kv_dtype))

    def _placed(self, leaves):
        if self._device is None:
            return tuple(leaves)
        return tuple(jax.device_put(a, self._device) for a in leaves)

    def new_scale_pool(self):
        """Per-(layer, head, physical block) fp32 absmax scale pools
        for the int8 paged plane — a ``(num_layers, num_heads,
        pool_blocks)`` pair of ones riding beside :meth:`new_pool`'s
        int8 code pools as donated program state."""
        return self._placed(self._model.init_scale_pool(
            self._spec, self.pool_blocks))

    def copy_block(self, *args, scales=None, cls=0):
        """Copy-on-write fork: ``copy_block(*pool leaves, src, dst)``
        duplicates physical block ``src``'s rows into block ``dst`` in
        every leaf of block class ``cls`` (one jitted program a class,
        leaves donated off-CPU — callers rebind to the outputs; a
        model of one class has every leaf in it).  With ``scales`` (the
        int8 plane's ``(scale_k, scale_v)`` pools) the per-block scales
        fork WITH the codes — a block is only decodable as codes+scale
        together — and the return grows by the two scale pools."""
        *pools, src, dst = args
        leaves = self._pool_args(tuple(pools), scales)
        fn = self._copy_fn.get(cls)
        if fn is None:
            nb = self.pool_blocks
            # the class's leaves, and the scale pools behind them
            mine = set(self.cache_classes[cls][1]) | set(
                range(len(pools), len(leaves)))

            def copy_block(leaves, s, d):
                # axis 2 counts a block's rows: kv_block tokens in a
                # token leaf, one row in a state leaf or a scale pool
                out = []
                for i, leaf in enumerate(leaves):
                    if i in mine:
                        n = leaf.shape[2] // nb
                        leaf = jax.lax.dynamic_update_slice_in_dim(
                            leaf, jax.lax.dynamic_slice_in_dim(
                                leaf, s * n, n, 2), d * n, 2)
                    out.append(leaf)
                return tuple(out)

            fn = self._copy_fn[cls] = jax.jit(
                copy_block, donate_argnums=cache_donate_argnums((0,)))
        return fn(leaves, np.int32(src), np.int32(dst))

    # -- compilation ---------------------------------------------------
    def _sds(self, shape, dtype):
        sh = (jax.sharding.SingleDeviceSharding(self._device)
              if self._device is not None else None)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def _param_spec(self):
        # tree_map descends QuantizedWeight pairs to their code/scale
        # leaves (registered pytree), so int8 params spec like arrays
        return jax.tree_util.tree_map(
            lambda a: self._sds(a.shape, a.dtype), self._params)

    def _cache_spec(self, batch, cache_len):
        s = self._spec
        dh = s["num_hidden"] // s["num_heads"]
        shape = (s["num_layers"], batch, s["num_heads"],
                 int(cache_len), dh)
        return self._sds(shape, self.kv_dtype)

    @property
    def pool_leaves(self):
        """How many leaves the model's pool has (2 for the LM's ``(k,
        v)``, 1 for a latent pool): the run methods take that many
        right after ``self``."""
        return len(self._pool_avals)

    @property
    def state_avals(self):
        """Avals of the pool's STATE leaves, the ones with one row a
        block and not ``kv_block`` (what a model keeps per sequence,
        as it stood after the block's last token: ``models/paged.py``);
        empty for a model whose every leaf is by token."""
        if self.kv_block == 1:
            return ()
        return tuple(a for a in self._pool_avals
                     if a.shape[2] == self.pool_blocks)

    def state_rows_per_block(self):
        """Rows a block holds in the state leaves: their layers."""
        return sum(a.shape[0] * a.shape[1] for a in self.state_avals)

    def _pool_spec(self):
        """Avals of every donated pool argument: the model's leaves,
        then the int8 plane's two scale pools."""
        leaves = tuple(self._sds(a.shape, a.dtype)
                       for a in self._pool_avals)
        if self.kv_int8:
            s = self._spec
            leaves += (self._sds((s["num_layers"], s["num_heads"],
                                  self.pool_blocks), jnp.float32),) * 2
        return leaves

    def _paged_avals(self, kind, bb, lq):
        """``(shape, dtype)`` of a paged program's own arguments, the
        ones after params and pools: tables, tokens, positions, valid,
        then the kind's.  ``bb`` is the SLOT bucket.  Every kind is bb
        rows wide but the compacted prompt chunk: ``chunk_rows(bb)``
        rows beside the bb slots' key chains, and which slot each row
        works for."""
        compact = kind in ("paged_chunk_sample", "paged_self_chunk",
                           "paged_draft_chunk", "paged_tick_sample")
        rows = self.chunk_rows(bb) if compact else bb
        avals = [((rows, self.table_width()), np.int32),
                 ((rows, int(lq)), np.int32),
                 ((rows,), np.int32), ((rows,), np.int32)]
        if kind in ("paged_draft_step", "paged_draft_chunk"):
            # the target's hidden states, and what it packed for the
            # host: the verify's tokens, counts and counters, or the
            # chunk's sampled tokens and counters
            packed = (rows if compact else (int(lq) + 1) * rows) \
                + len(self.aux_counters)
            return avals + [
                ((rows, int(lq), self._spec["hidden_size"]), np.float32),
                ((packed,), np.int32)]
        if kind == "paged_verify":     # the draft's proposal densities
            avals.append(((bb, int(lq) - 1, self._spec["vocab_size"]),
                          np.float32))
        if kind != "paged_step":       # keys, temps, top_ks, do_sample
            avals += [((bb, 2), np.uint32), ((rows,), np.float32),
                      ((rows,), np.int32), ((rows,), np.bool_)]
        if compact:
            avals.append(((rows,), np.int32))
        if kind == "paged_self_chunk":  # the token after the chunk
            avals.append(((rows,), np.int32))
        if kind == "paged_tick_sample":
            # the decode group behind the chunk: bb rows of one query,
            # and their temps, top_ks, do_sample; the chunk rows' own
            # chains
            assert len(avals) == _TICK_DECODE_AT
            avals += [((bb, self.table_width()), np.int32),
                      ((bb, 1), np.int32), ((bb,), np.int32),
                      ((bb,), np.int32), ((bb,), np.float32),
                      ((bb,), np.int32), ((bb,), np.bool_),
                      ((rows, 2), np.uint32)]
        if kind == "paged_tick_sample" or (
                kind == "paged_step_sample" and self.one_pass):
            # the slots' pending tokens, and the rows that take the
            # host's instead
            avals += [((bb,), np.int32), ((bb,), np.bool_)]
        return avals

    def _key(self, kind, bb, lb):
        # (kind, batch bucket, length bucket) + the serving dtypes +
        # the dispatch fingerprint (prefill/decode trace through
        # sdp_attention, the rowwise norm kernels and the dequant-
        # matmul door — an MXNET_PALLAS flip must recompile, not serve
        # the stale lowering; the dtypes are per-store constants, in
        # the key as insurance)
        return ("gen", self.name, kind, int(bb), int(lb),
                self._compute, str(self.kv_dtype),
                _pallas_dispatch.fingerprint())

    def _compile(self, kind, bb, lb):
        model = self._model
        tic = time.perf_counter()
        spec = self._spec
        kv = self.kv_dtype
        if kind in PAGED_KINDS + SELF_DRAFT_KINDS:
            args = (self._param_spec(),) + self._pool_spec() + tuple(
                self._sds(shape, dtype)
                for shape, dtype in self._paged_avals(kind, bb, lb))
            fn, donate = paged_program(model, spec, kind, lb,
                                       self.kv_block, self.pool_leaves,
                                       self.kv_int8, pending=self.one_pass)
            compiled = jax.jit(
                fn, donate_argnums=cache_donate_argnums(donate)) \
                .lower(*args).compile()
            ms = (time.perf_counter() - tic) * 1e3
            mem = compiled.memory_analysis()
            return _Program(compiled, (bb, lb), (), ms,
                            getattr(mem, "temp_size_in_bytes", None))
        if kind == "prefill":
            cache_len = self.kv_bucket(lb)

            def prefill(params, tokens, lengths):
                logits, ck, cv = model.prefill_apply(
                    params, tokens, lengths, cache_len, spec,
                    cache_dtype=kv)
                first = logits[jnp.arange(bb), (lengths - 1)
                               .astype(jnp.int32)]
                return first, ck, cv

            args = (self._param_spec(),
                    self._sds((bb, lb), jnp.int32),
                    self._sds((bb,), jnp.int32))
            compiled = jax.jit(prefill).lower(*args).compile()
        elif kind == "decode_sample":
            # in-graph sampling: the decode step emits TOKENS, not
            # logits — per-slot PRNG keys ride beside the caches and
            # are donated with them (split in-graph each step)

            def decode_sample(params, cache_k, cache_v, tokens, lengths,
                              keys, temps, top_ks):
                logits, ck, cv = model.decode_apply(
                    params, cache_k, cache_v, tokens, lengths, spec)
                toks, new_keys = sample_tokens(logits, keys, temps,
                                               top_ks)
                return toks, ck, cv, new_keys

            args = (self._param_spec(),
                    self._cache_spec(bb, lb), self._cache_spec(bb, lb),
                    self._sds((bb,), jnp.int32),
                    self._sds((bb,), jnp.int32),
                    self._sds((bb, 2), jnp.uint32),
                    self._sds((bb,), jnp.float32),
                    self._sds((bb,), jnp.int32))
            compiled = jax.jit(
                decode_sample,
                donate_argnums=cache_donate_argnums((1, 2, 5))) \
                .lower(*args).compile()
        else:  # decode (logits out — the MXNET_SERVE_SAMPLE=host hatch)

            def decode(params, cache_k, cache_v, tokens, lengths):
                return model.decode_apply(params, cache_k, cache_v,
                                          tokens, lengths, spec)

            args = (self._param_spec(),
                    self._cache_spec(bb, lb), self._cache_spec(bb, lb),
                    self._sds((bb,), jnp.int32),
                    self._sds((bb,), jnp.int32))
            # the caches are DONATED (off-CPU): the per-step K/V write
            # is an in-place dynamic_update_slice on the one resident
            # copy — callers MUST rebind their cache references to the
            # outputs
            compiled = jax.jit(
                decode, donate_argnums=cache_donate_argnums((1, 2))) \
                .lower(*args).compile()
        ms = (time.perf_counter() - tic) * 1e3
        return _Program(compiled, (bb, lb), (), ms)

    def _acquire(self, kind, bb, lb):
        key = self._key(kind, bb, lb)
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                self._stats["hits"] += 1
                _cache_event("hits").inc()
                return prog
        prog = self._compile(kind, bb, lb)
        with self._lock:
            raced = self._programs.get(key)
            if raced is not None:
                self._stats["hits"] += 1
                _cache_event("hits").inc()
                return raced
            self._stats["compiles"] += 1
            self._stats["compile_ms_total"] += prog.compile_ms
            _cache_event("compiles").inc()
            while len(self._programs) >= self.max_programs:
                self._programs.popitem(last=False)
                self._stats["evictions"] += 1
                _cache_event("evictions").inc()
            self._programs[key] = prog
            return prog

    def warmup(self, execute=True, kv_depth=None):
        """Compile — and by default execute once on zeros — every
        (batch, prompt) prefill program and every (batch, cache-bucket)
        decode program reachable from the prompt buckets, ahead of
        traffic.  ``kv_depth`` additionally warms every cache bucket up
        to that length (a sequence *growing* past its prompt's quantum
        otherwise pays that decode compile at its first step into the
        new bucket — serving processes that know their generation caps
        should pass ``kv_depth=prompt_max + max_tokens_max``).  Returns
        {(kind, bb, lb): compile_ms}."""
        out = {}
        if self.paged:
            # the paged plane's whole program space: two step
            # programs a batch bucket — the bb-wide lq=1 decode step
            # and the prompt chunk over chunk_rows(bb) rows (a one-pass
            # store's with the bb decode rows beside them: the tick
            # that has prompt rows, and no chunk program apart).  kv_depth
            # is moot: the table width is a store constant, so cache
            # depth never changes the program.  Warmup executes on a
            # throwaway zero pool with all-zero tables (every write
            # lands in the trash block).
            pkind = ("paged_step_sample" if self.sample_mode == "graph"
                     else "paged_step")
            pools = None        # ONE throwaway pool through all of them
            for bb in self._batch_edges:
                for key in self.step_programs(bb) if self.self_draft \
                        else ((pkind, bb, 1), self.chunk_program(bb)):
                    if key in out:      # two buckets, one chunk width
                        continue
                    prog = self._acquire(*key)
                    out[key] = prog.compile_ms
                    if execute:
                        pools = self._exec_paged_zeros(*key, prog, pools)
            if execute:
                # the copy-on-write fork is a program of the tick too:
                # left to its first use it compiles under traffic
                n = self.pool_leaves
                for c in range(len(self.cache_classes)):
                    pools = jax.block_until_ready(self.copy_block(
                        *pools[:n], 0, 0, scales=pools[n:] or None,
                        cls=c))
            return out
        cache_buckets = {self.kv_bucket(p) for p in self._prompt_edges}
        if kv_depth is not None:
            top = self.kv_bucket(kv_depth)
            cache_buckets.update(
                range(self.kv_block, top + 1, self.kv_block))
        # the decode kind the engine will dispatch: tokens-out
        # (in-graph sampling) or logits-out (the host hatch)
        dkind = ("decode_sample" if self.sample_mode == "graph"
                 else "decode")
        for bb in self._batch_edges:
            for pb in self._prompt_edges:
                prog = self._acquire("prefill", bb, pb)
                out[("prefill", bb, pb)] = prog.compile_ms
                if execute:
                    toks = np.zeros((bb, pb), np.int32)
                    lens = np.ones((bb,), np.int32)
                    jax.block_until_ready(
                        prog.fn(self._params, toks, lens))
            for cb in sorted(cache_buckets):
                prog = self._acquire(dkind, bb, cb)
                out[(dkind, bb, cb)] = prog.compile_ms
                if execute:
                    ck, cv = self.new_cache(bb, cb)
                    toks = np.zeros((bb,), np.int32)
                    lens = np.zeros((bb,), np.int32)
                    if dkind == "decode_sample":
                        jax.block_until_ready(prog.fn(
                            self._params, ck, cv, toks, lens,
                            np.zeros((bb, 2), np.uint32),
                            np.zeros((bb,), np.float32),
                            np.zeros((bb,), np.int32)))
                    else:
                        jax.block_until_ready(
                            prog.fn(self._params, ck, cv, toks, lens))
        return out

    def _exec_paged_zeros(self, kind, bb, lq, prog, pools=None):
        """Execute one paged program once on a throwaway zero pool with
        all-zero tables (every write lands in the trash block): the
        one-time XLA executable setup must not land inside a served
        request.  Returns the pool it ran on (the program's donated
        leaves, handed back) for the next program to warm on: a pool a
        program is 2.7 GB beside 9 GB of weights for ``deepseek_v3``."""
        if pools is None:
            pools = self.new_pool()
            if self.kv_int8:
                pools = pools + self.new_scale_pool()
        own = [np.zeros(shape, dtype)
               for shape, dtype in self._paged_avals(kind, bb, lq)]
        own[3][:] = 1       # one valid token a row
        if kind == "paged_tick_sample":
            own[_TICK_DECODE_AT + 3][:] = 1     # and a decode row
        out = jax.block_until_ready(
            prog.fn(self._params, *pools, *own))
        head = _HEADS.get(kind, 1)
        return tuple(out[head:head + len(pools)])

    def warm_spec_programs(self, spec_k, draft=False, execute=True):
        """Warm the speculative-decoding program kinds ahead of
        traffic: the TARGET's verify programs (lq = spec_k + 1), or —
        ``draft=True`` — the DRAFT's proposal programs (lq=1
        ``paged_step_sample_p``) plus its logits-discarded
        prefill-mirror chunks (lq = prefill_chunk ``paged_step``, as
        wide as the target's compacted chunk: ``chunk_rows(bb)``).
        ``registry.add_draft_model`` warms both sides, so attaching a
        draft never compiles inside a served request.  Returns
        {(kind, bb, lq): compile_ms}."""
        if not self.paged:
            raise MXNetError(
                "speculative decoding needs the paged plane (store %r "
                "has paged=False)" % self.name)
        self._need("draft", "speculative decoding")
        out = {}
        for bb in self._batch_edges:
            keys = ([("paged_step_sample_p", bb, 1),
                     ("paged_step", self.chunk_rows(bb),
                      self.prefill_chunk)] if draft
                    else [("paged_verify", bb, int(spec_k) + 1)])
            for key in keys:
                if key in out:
                    continue
                prog = self._acquire(*key)
                out[key] = prog.compile_ms
                if execute:
                    self._exec_paged_zeros(*key, prog)
        return out

    # -- execution -----------------------------------------------------
    @hot_path
    def run_prefill(self, tokens, lengths):
        """Dispatch one padded prompt batch.  ``tokens`` (bb, pb) int32
        and ``lengths`` (bb,) int32 must already be bucket-shaped
        (``pad_prompts``).  Returns device-resident
        ``(first_logits (bb, vocab), k_cache, v_cache)`` — enqueue-only,
        fetch on the caller's side."""
        bb, pb = tokens.shape
        prog = self._acquire("prefill", bb, pb)
        return prog.fn(self._params, tokens, lengths)

    @hot_path
    def run_decode(self, cache_k, cache_v, tokens, lengths):
        """Dispatch one logits-out decode step over a bucket-shaped
        cache (the ``MXNET_SERVE_SAMPLE=host`` hatch and the test
        references).  BOTH cache arguments are consumed (donated) —
        callers must rebind their references to the returned caches."""
        bb = int(tokens.shape[0])
        cb = int(cache_k.shape[3])
        prog = self._acquire("decode", bb, cb)
        return prog.fn(self._params, cache_k, cache_v, tokens, lengths)

    @hot_path
    def run_decode_sample(self, cache_k, cache_v, tokens, lengths,
                          keys, temps, top_ks):
        """Dispatch one decode step with IN-GRAPH sampling: returns
        ``(tokens (bb,) int32, new_k, new_v, new_keys)``.  The caches
        AND the per-slot PRNG key state are consumed (donated) —
        callers rebind all three; the only host-sized fetch left per
        step is the token vector."""
        bb = int(tokens.shape[0])
        cb = int(cache_k.shape[3])
        prog = self._acquire("decode_sample", bb, cb)
        return prog.fn(self._params, cache_k, cache_v, tokens, lengths,
                       keys, temps, top_ks)

    def _pool_args(self, pools, scales):
        """The pool-argument tuple of one paged dispatch: the int8
        plane threads its donated scale pools right after the code
        pools (and gets them back in the same slots of the return)."""
        if self.kv_int8:
            if scales is None:
                raise MXNetError(
                    "int8 paged store %r needs its (scale_k, scale_v) "
                    "pools on every dispatch" % self.name)
            return tuple(pools) + tuple(scales)
        return tuple(pools)

    def _run_paged(self, kind, args, scales, bb=None):
        """Dispatch program ``kind`` on ``args``: the pool's leaves
        (``pool_leaves`` of them) and then the program's own arguments,
        tables first and tokens second.  ``bb``: the slot bucket of a
        compacted program that takes no key chains to tell it by."""
        n = self.pool_leaves
        rows, lq = args[n + 1].shape
        if kind in ("paged_chunk_sample", "paged_self_chunk",
                    "paged_tick_sample"):
            # a program of its slot bucket: the key chains' axis
            bb = args[n + 4].shape[0]
        prog = self._acquire(kind, int(bb or rows), int(lq))
        return prog.fn(self._params, *(self._pool_args(args[:n], scales)
                                       + tuple(args[n:])))

    @hot_path
    def run_paged_step(self, *args, scales=None):
        """Dispatch one logits-out paged step (the host-sampling
        hatch and the draft's prefill mirror): ``run_paged_step(*pool
        leaves, tables, tokens, positions, valid)`` with ``tokens``
        (bb, lq) int32 — lq=1 is a decode step, lq=prefill_chunk a
        prompt chunk.  Returns ``(logits (bb, vocab) at each row's last
        valid position, *pool leaves)`` — ``(logits, pool_k, pool_v)``
        for the LM; int8 stores take and return the scale pools too,
        ``(logits, pool_k, pool_v, scale_k, scale_v)``.  The pools are
        consumed (donated) — callers rebind."""
        return self._run_paged('paged_step', args, scales)

    @hot_path
    def run_paged_step_sample(self, *args, scales=None):
        """Dispatch one paged step with IN-GRAPH sampling:
        ``run_paged_step_sample(*pool leaves, tables, tokens,
        positions, valid, keys, temps, top_ks, do_sample)`` returns
        ``(tokens (bb,) int32, *pool leaves, new_keys)`` (int8 stores:
        the scale pools before ``new_keys``).  A model with
        ``aux_counters`` appends them to the token vector (``(bb +
        len(aux_counters),)``: one array, one fetch).  Rows with
        ``do_sample`` False keep their PRNG keys (their sampled token
        is garbage the caller discards); pools and keys are consumed
        (donated) — callers rebind.  A :attr:`one_pass` store's takes
        ``pending (S,) int32`` and ``host (S,) bool`` behind
        ``do_sample`` and returns the new ``pending`` behind
        ``new_keys`` (:func:`paged_program`, ``pending``)."""
        return self._run_paged('paged_step_sample', args, scales)

    @hot_path
    def run_paged_chunk_sample(self, *args, scales=None):
        """Dispatch one COMPACTED prompt chunk with in-graph sampling:
        ``run_paged_chunk_sample(*pool leaves, tables, tokens,
        positions, valid, keys, temps, top_ks, do_sample, slots)``.
        The dispatch arrays have ``chunk_rows(S)`` rows, row ``k``
        working for slot ``slots[k]``; ``keys`` is all ``S`` slots'
        ``(S, 2)`` chains.  Returns ``(tokens (rows,) int32 [+ the
        model's counters], *pool leaves, new_keys (S, 2))``: only the
        slots of rows with ``do_sample`` set advance their chain
        (:func:`sample_chunk_rows`).  Pools and keys are consumed
        (donated) — callers rebind."""
        return self._run_paged('paged_chunk_sample', args, scales)

    @hot_path
    def run_paged_tick_sample(self, *args):
        """Dispatch one ONE-PASS tick (:attr:`one_pass`): the compacted
        prompt chunk and the slots' decode step as two row groups of
        one program.  ``run_paged_tick_sample(*pool leaves,
        *run_paged_chunk_sample's own nine, tables, tokens, positions,
        valid, temps, top_ks, do_sample, row_keys, pending, host)``:
        seven ``(S, ...)`` arrays of the decode group (``tokens`` ``(S,
        1)``; the chains ``keys (S, 2)`` go in once, with the chunk's),
        the chunk rows' own chains ``(rows, 2)`` (the seed key of a
        row that samples), the slots' pending tokens ``(S,) int32`` and
        the decode rows that take ``tokens`` instead ``(S,) bool``.
        Returns ``(tokens (S + rows,) int32 + the model's counters,
        *pool leaves, new_keys (S, 2), new_pending (S,))``: the decode
        rows' tokens first; a chain advances, and a pending token is
        written, where its slot's row of either group has ``do_sample``
        set.  Pools, keys and pending tokens are consumed (donated) —
        callers rebind."""
        return self._run_paged('paged_tick_sample', args, None)

    @hot_path
    def run_paged_step_sample_p(self, *args, scales=None):
        """The DRAFT model's proposal step: one lq=1 paged step with
        in-graph sampling that also returns the proposal distribution.
        Returns ``(tokens (bb,), q (bb, vocab), pool_k, pool_v,
        new_keys)`` (int8: scale pools before new_keys).  ``q`` should
        stay device-resident — the verify program consumes it directly,
        the host never fetches a distribution."""
        return self._run_paged('paged_step_sample_p', args, scales)

    @hot_path
    def run_paged_verify(self, *args, scales=None):
        """The TARGET model's speculative verify: ``tokens`` (bb, K+1)
        holds each slot's pending next token followed by its K draft
        proposals, ``prop_q`` (bb, K, vocab) the draft's proposal
        distributions (device-resident from
        :meth:`run_paged_step_sample_p`), ``valid`` = per-slot window
        + 1.  All K+1 positions run in ONE program; accept/reject and
        the corrected resample happen in-graph (``spec_verify``).
        Returns ``(out_toks (bb, K+1), n_emit (bb,), pool_k, pool_v,
        new_keys)`` (int8: scale pools before new_keys) — row b emits
        ``out_toks[b, :n_emit[b]]``.  Pools and keys are consumed
        (donated) — callers rebind."""
        return self._run_paged('paged_verify', args, scales)

    @hot_path
    def run_paged_self_verify(self, *args):
        """The TARGET's half of a self-drafting decode step:
        ``run_paged_self_verify(*pool leaves, tables, tokens, positions,
        valid, keys, temps, top_ks, do_sample)``, ``tokens`` (bb, K+1)
        each slot's pending token and the module's proposals behind it,
        ``valid`` = proposals to verify + 1.  Returns ``(packed, out
        (bb, K+1), n_emit (bb,), hidden (bb, K+1, D), *pool leaves,
        new_keys)``; ``packed`` is ``[out, n_emit, the model's
        counters]`` in one int32 vector and, like ``out``, ``n_emit``
        and ``hidden``, goes to :meth:`run_paged_draft_step` unfetched."""
        return self._run_paged("paged_self_verify", args, None)

    @hot_path
    def run_paged_self_chunk(self, *args):
        """:meth:`run_paged_chunk_sample` of a self-drafting store, one
        argument more (``after`` (rows,): the prompt token behind each
        row's chunk).  Returns ``(packed, tokens for the module (rows,
        lq), hidden (rows, lq, D), *pool leaves, new_keys)``, ``packed``
        the sampled tokens and the model's counters."""
        return self._run_paged("paged_self_chunk", args, None)

    @hot_path
    def run_paged_draft_step(self, *args):
        """The prediction MODULE behind a verify: ``run_paged_draft_step(
        *pool leaves, tables, out, positions, n_emit, hidden, packed)``
        (``positions`` the verify's).  Returns ``(packed + proposals
        (bb,) + the module's counters, *pool leaves)``: the one array a
        self-drafting decode tick fetches."""
        return self._run_paged("paged_draft_step", args, None)

    @hot_path
    def run_paged_draft_chunk(self, *args, slots):
        """The module behind a prompt chunk of a ``slots``-slot bucket:
        as :meth:`run_paged_draft_step`, ``chunk_rows(slots)`` rows."""
        return self._run_paged("paged_draft_chunk", args, None, bb=slots)

    def pad_prompts(self, prompts):
        """Host-side canonicalization: a list of token id sequences ->
        bucket-shaped ``(tokens (bb, pb) int32, lengths (bb,) int32)``.
        Pad rows get length 1 over token 0 (their logits are discarded;
        length >= 1 keeps the first-token gather in bounds)."""
        n = len(prompts)
        if n < 1:
            raise MXNetError("empty prompt batch")
        lens = [len(p) for p in prompts]
        if min(lens) < 1:
            raise MXNetError("empty prompt (0 tokens)")
        bb = self.batch_bucket(n)
        pb = self.prompt_bucket(max(lens))
        toks = np.zeros((bb, pb), np.int32)
        lengths = np.ones((bb,), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :lens[i]] = np.asarray(p, np.int32)
            lengths[i] = lens[i]
        return toks, lengths

    # -- introspection -------------------------------------------------
    def stats(self):
        with self._lock:
            out = dict(self._stats)
            out["size"] = len(self._programs)
            out["max_programs"] = self.max_programs
            temp = sorted(((k[2], k[3], k[4], p.temp_bytes)
                           for k, p in self._programs.items()),
                          key=lambda row: row[:3])
            out["programs_resident"] = [row[:3] for row in temp]
        out["generative"] = True
        out["version"] = self._version
        out["batch_buckets"] = list(self._batch_edges)
        out["prompt_buckets"] = list(self._prompt_edges)
        out["kv_block"] = self.kv_block
        out["kv_max"] = self.kv_max
        out["compute_dtype"] = self._compute
        out["kv_dtype"] = str(self.kv_dtype)
        out["sample_mode"] = self.sample_mode
        out["paged"] = self.paged
        out["self_draft"] = self.self_draft
        out["one_pass"] = self.one_pass
        if self.paged:
            out["prefill_chunk"] = self.prefill_chunk
            out["pool_blocks"] = self.pool_blocks
            out["table_width"] = self.table_width()
            out["cache_classes"] = len(self.cache_classes)
            # (kind, batch bucket, lq, scratch bytes) of each resident
            # step program: a program that addresses the pool in place
            # needs far less than one layer of it (cache_state's
            # pool_bytes / 2 / num_layers); one that relays the pool
            # holds a second pool here
            out["program_temp_bytes"] = temp
        out["weight_bytes"] = _weight_bytes(self._params)
        state = self.cache_state
        if state is not None:
            out["cache_state"] = state.describe()
        return out

    def reset_stats(self):
        with self._lock:
            for k in ("hits", "compiles", "evictions"):
                self._stats[k] = 0
            self._stats["compile_ms_total"] = 0.0
