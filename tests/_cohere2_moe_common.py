"""What the ``tests/test_cohere2_moe*.py`` files share: the toy spec and
parameters, the benchmark's plain reference, the store and the rows
stepped through it."""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.models import cohere2_moe as co
from mxnet_tpu.serving.program_store import GenerativeProgramStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WINDOW = 16
SPEC_IN = {
    "arch": "cohere2_moe", "num_hidden_layers": 4,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"],
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 32, "num_experts": 4,
    "router_width": 16, "num_experts_per_tok": 4,
    "num_shared_experts": 2, "sliding_window": WINDOW, "vocab_size": 96,
    "layer_norm_eps": 1e-5, "rope_theta": 50000.0, "logit_scale": 0.5}
SPEC = co.serving_spec(SPEC_IN)
CFG = {"spec": SPEC_IN}
PARAMS = co.random_params(SPEC, seed=11)
BS, CHUNK, KV_MAX = 8, 8, 64
T = KV_MAX // BS                # table entries a class
# Program against reference in float32 on the CPU: the same products
# associated differently (an online softmax over the window's groups of
# blocks against a whole one, a grouped product against a gather, one
# gated unit of twice the width against two); logits are of order 1
# and readings were 2e-6 .. 6e-6.
LOGIT_TOL = 1e-4
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
    return _load("command_a_plus_reference", os.path.join(
        ROOT, "benchmark", "reference", "command-a-plus.py"))


_REF_FN = {}


def _ref_logits(ref, tokens):
    """The reference's logits at every position of ``tokens``, computed
    over the sequence padded to KV_MAX (it is causal: a position's
    logits do not depend on what follows), so every call is one
    compiled program."""
    import jax
    import jax.numpy as jnp
    if "fn" not in _REF_FN:
        params = {k: jnp.asarray(v) for k, v in PARAMS.items()}
        fn = jax.jit(lambda t: ref.logits(params, t, CFG))
        _REF_FN["fn"] = fn
    seq = np.zeros(KV_MAX, np.int32)
    seq[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(_REF_FN["fn"](jnp.asarray(seq)))[:len(tokens)]


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="co",
                                  **args)


class _Rows:
    """Two sequences over one pool, stepped through the store's
    logits-out program: a table a CLASS for each (class 0 the full
    layer's, class 1 the window layers'), side by side in a row."""

    def __init__(self):
        self.st = _store(pool_blocks=24)
        self.pools = self.st.new_pool()
        self.tables = np.zeros((2, self.st.table_width()), np.int32)

    def give(self, row, full, window=None):
        """Row ``row``'s blocks in the full class, and (by default the
        same numbers, which name other memory) in the window class."""
        window = full if window is None else window
        self.tables[row, :len(full)] = full
        self.tables[row, T:T + len(window)] = window

    def release(self, row, pos):
        """What the engine does as ``row``'s next query sits at
        ``pos``: the window class's entries wholly behind the window
        go to the trash block 0."""
        first = max(0, (pos - WINDOW + 1) // BS)
        self.tables[row, T:T + first] = 0

    def step(self, tokens, pos, rows=(0, 1)):
        """``tokens[r]`` at ``pos[r]`` for the rows in ``rows``; the
        others ride outside the dispatch.  Returns the logits."""
        lq = 1 if max(len(t) for t in tokens) == 1 else CHUNK
        toks = np.zeros((2, lq), np.int32)
        tables = np.zeros_like(self.tables)
        p, v = np.zeros(2, np.int32), np.ones(2, np.int32)
        for r, t, at in zip(rows, tokens, pos):
            toks[r, :len(t)] = t
            tables[r], p[r], v[r] = self.tables[r], at, len(t)
        logits, *self.pools = self.st.run_paged_step(
            *self.pools, tables, toks, p, v)
        return np.asarray(logits)

    def prefill(self, row, seq, start=0, release=False):
        """``seq[start:]`` in chunks; the last chunk's logits."""
        out = None
        for at in range(start, len(seq), CHUNK):
            if release:
                self.release(row, at)
            out = self.step([seq[at:at + CHUNK]], [at], rows=(row,))[row]
        return out


# ---------------------------------------------------------------------------
# (a) the reference = the published classes (the dense family's)
# ---------------------------------------------------------------------------
# float32 on both sides, the same equations in another order of
# summation: readings 1e-7 .. 2e-6 on values of order 1
HF_TOL = 1e-5


def _hf():
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.cohere2.modeling_cohere2")
    from transformers.models.cohere2.configuration_cohere2 import \
        Cohere2Config
    config = Cohere2Config(
        vocab_size=96, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=2,
        head_dim=8, layer_norm_eps=1e-5, rope_theta=50000.0,
        sliding_window=5, logit_scale=0.5, attention_bias=False,
        layer_types=["sliding_attention", "full_attention"])
    config._attn_implementation = "eager"
    return torch, modeling, config


def _seed_module(torch, module, seed):
    gen = torch.Generator().manual_seed(seed)
    with torch.no_grad():
        for p in module.parameters():
            p.copy_(torch.randn(p.shape, generator=gen) * 0.3
                    + (1.0 if p.ndim == 1 else 0.0))
    return {k: v.detach().numpy() for k, v in module.named_parameters()}


def _greedy(ref, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_ref_logits(ref, seq)[-1])))
    return seq[len(prompt):]
