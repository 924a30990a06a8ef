"""What the ``tests/test_lfm2_moe*.py`` files share: the toy spec and
parameters, the benchmark's plain reference, the store and the rows
stepped through it."""
import functools
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.models import lfm2_moe as lfm
from mxnet_tpu.serving import ModelRegistry
from mxnet_tpu.serving.program_store import GenerativeProgramStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC_IN = {
    "arch": "lfm2_moe", "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv",
                    "full_attention"],
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "conv_L_cache": 3,
    "vocab_size": 96, "norm_eps": 1e-5, "rope_theta": 1e6,
    "routed_scaling_factor": 1.0}
SPEC = lfm.serving_spec(SPEC_IN)
CFG = {"spec": SPEC_IN}
PARAMS = lfm.random_params(SPEC, seed=7)
BS, CHUNK, KV_MAX = 8, 8, 48
# Program against reference in float32 on the CPU: the same products
# associated differently (an online softmax against a whole one, a
# grouped product against a masked loop, a filter over a carried state
# against one over a padded sequence); logits are of order 10 and
# readings were 1e-5 .. 2e-5.
LOGIT_TOL = 2e-4
STORE_KW = dict(batch_buckets=(2,), prompt_buckets=(8,), kv_block=BS,
                kv_max=KV_MAX, paged=True, prefill_chunk=CHUNK,
                sample="graph")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (imports nothing of the
    program), loaded by path."""
    return _load("lfm2_reference", os.path.join(
        ROOT, "benchmark", "reference", "lfm2-24b-a2b.py"))


def _ref_logits(ref, tokens):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(
            {k: jnp.asarray(v) for k, v in PARAMS.items()},
            jnp.asarray(np.asarray(tokens, np.int32)), CFG))


def _greedy_continuations(ref, prompt, tokens):
    """What the reference's greedy decoding emits after each prefix of
    ``prompt + tokens``, read off ONE forward of the whole sequence (the
    model is causal): ``tokens == _greedy_continuations(ref, prompt,
    tokens)`` holds exactly for the reference's own greedy continuation,
    by induction, where a loop a token traced the reference anew at
    every length."""
    seq = list(prompt) + list(tokens)
    logits = _ref_logits(ref, seq[:-1])
    return [int(t) for t in np.argmax(logits[len(prompt) - 1:], axis=-1)]


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="lfm",
                                  **args)


@functools.lru_cache(maxsize=None)
def _shared_store():
    """``_store()`` once a file: a store keeps its programs and no
    pool, so the :class:`_Rows` of every case step through the same
    compiles, each over a pool of its own."""
    return _store()


@pytest.fixture(scope="module")
def registry():
    """The toy model registered and warmed once a file for the engine
    tests: an engine keeps its pool, prefix cache and counters to
    itself."""
    reg = ModelRegistry()
    reg.add_generative_model("lfm", dict(PARAMS), SPEC_IN, **STORE_KW)
    return reg


class _Rows:
    """Two table rows over one pool, stepped through the store's
    logits-out program."""

    def __init__(self):
        self.st = _shared_store()
        self.pools = self.st.new_pool()
        self.tables = np.zeros((2, self.st.table_width()), np.int32)

    def step(self, tokens, pos, val, rows=(0, 1)):
        """``tokens[r]`` at ``pos[r]`` for the rows in ``rows``; the
        others ride outside the dispatch.  Returns the logits."""
        lq = max(len(t) for t in tokens)
        lq = 1 if lq == 1 else CHUNK
        toks = np.zeros((2, lq), np.int32)
        tables = np.zeros_like(self.tables)
        p, v = np.zeros(2, np.int32), np.ones(2, np.int32)
        for r, t, at in zip(rows, tokens, pos):
            toks[r, :len(t)] = t
            tables[r], p[r], v[r] = self.tables[r], at, len(t)
        logits, *self.pools = self.st.run_paged_step(
            *self.pools, tables, toks, p, v)
        return np.asarray(logits)

    def prefill(self, row, seq, start=0):
        """``seq[start:]`` in chunks; the last chunk's logits."""
        out = None
        for at in range(start, len(seq), CHUNK):
            out = self.step([seq[at:at + CHUNK]], [at], None,
                            rows=(row,))[row]
        return out


# ---------------------------------------------------------------------------
# (a) the reference = the published classes
# ---------------------------------------------------------------------------
# float32 on both sides, the same equations in another order of
# summation: readings 2e-7 .. 2e-6 on values of order 1
HF_TOL = 1e-5


def _hf():
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
    config = Lfm2Config(
        vocab_size=96, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        norm_eps=1e-5, rope_theta=1e6, conv_L_cache=3, conv_bias=False,
        block_auto_adjust_ff_dim=False,
        layer_types=["conv", "full_attention"])
    config._attn_implementation = "eager"
    return torch, modeling, config


def _seed_module(torch, module, seed):
    gen = torch.Generator().manual_seed(seed)
    with torch.no_grad():
        for p in module.parameters():
            p.copy_(torch.randn(p.shape, generator=gen) * 0.3
                    + (1.0 if p.ndim == 1 else 0.0))
    return {k: v.detach().numpy() for k, v in module.named_parameters()}
