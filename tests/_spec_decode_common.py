"""What the ``tests/test_spec_decode*.py`` files share: the toy target and
draft, the registries (warmed once a file) and one engine lifecycle
over them."""
import functools

import pytest

from mxnet_tpu.models.transformer_lm import lm_spec, random_params
from mxnet_tpu.serving import GenerationEngine, ModelRegistry

SPEC = lm_spec(num_layers=2, num_hidden=32, num_heads=4, vocab_size=50)
PARAMS = random_params(SPEC, seed=3)
DSPEC = lm_spec(num_layers=1, num_hidden=16, num_heads=2, vocab_size=50)
DPARAMS = random_params(DSPEC, seed=7)

KW = dict(batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 24),
          kv_block=8, kv_max=64, paged=True, prefill_chunk=8,
          sample="graph")

REQS = [dict(tokens=[7, 3, 11, 29, 4], max_tokens=12, seed=1),
        dict(tokens=[7, 3, 11, 29, 4], max_tokens=9, seed=2),
        dict(tokens=[2, 5], max_tokens=14, seed=3),
        dict(tokens=[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], max_tokens=7,
             seed=4)]


@functools.lru_cache(maxsize=None)
def _registry(draft, kv_dtype="float32", spec_k=3):
    """The warmed registry of one (draft, pool dtype, window): the
    target alone, or with itself (``self``) or the small random model
    (``rand``) as its draft.  Built once a module: an engine keeps its
    pool, prefix cache and acceptance EMA to itself, so every engine
    lifecycle over it starts as over a registry of its own, less the
    warm-up."""
    reg = ModelRegistry()
    reg.add_generative_model("m", PARAMS, SPEC, kv_dtype=kv_dtype,
                             **KW)
    if draft == "self":
        reg.add_draft_model("m", PARAMS, SPEC, spec_k=spec_k)
    elif draft == "rand":
        reg.add_draft_model("m", DPARAMS, DSPEC, spec_k=spec_k)
    return reg


def _run(draft, kv_dtype="float32", temp=0.0, reqs=REQS, spec_k=3,
         **submit_kw):
    """One engine lifecycle over :func:`_registry`'s registry:
    generate, return (streams, stats)."""
    eng = GenerationEngine(_registry(draft, kv_dtype, spec_k))
    try:
        futs = [eng.submit("m", temperature=temp, **submit_kw, **kw)
                for kw in reqs]
        toks = [f.result(180).tokens for f in futs]
        stats = eng.stats()
    finally:
        eng.close()
    return toks, stats


@pytest.fixture(scope="module")
def greedy_runs():
    """The three greedy engine runs every byte-identity/counters test
    reads: no draft (oracle), a random small draft (acceptance may
    collapse — graceful degradation), and a self-draft (acceptance
    100% — the steps-per-token upper bound)."""
    return {tag: _run(d) for tag, d in
            (("base", None), ("rand", "rand"), ("self", "self"))}
