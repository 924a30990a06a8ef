"""What the ``tests/test_deepseek_v32*.py`` files share: the toy spec and
parameters over ``deepseek_v3``'s, and the benchmark's plain reference."""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu.models import deepseek_v32 as ds32
from mxnet_tpu.serving.program_store import GenerativeProgramStore

from _deepseek_v3_common import SPEC_IN as V3_SPEC_IN

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOPK = 6
SPEC_IN = dict(V3_SPEC_IN, arch="deepseek_v32", index_n_heads=4,
               index_head_dim=8, index_topk=TOPK)
SPEC = ds32.serving_spec(SPEC_IN)
CFG = {"spec": SPEC_IN}
PARAMS = ds32.random_params(SPEC, seed=5)
BS, CHUNK, KV_MAX = 8, 8, 48
# as tests/test_deepseek_v3.py: the same products associated
# differently; a selection that differed would move a logit by 1e-2
LOGIT_TOL = 1e-4
STORE_KW = dict(batch_buckets=(2,), prompt_buckets=(8,), kv_block=BS,
                kv_max=KV_MAX, paged=True, prefill_chunk=CHUNK,
                sample="graph")


@pytest.fixture(scope="module")
def ref():
    """The benchmark's plain reference (imports nothing of the
    program), loaded by path."""
    spec = importlib.util.spec_from_file_location(
        "deepseek_v32_reference",
        os.path.join(ROOT, "benchmark", "reference", "deepseek-v32.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _jnp(params):
    import jax.numpy as jnp
    return {k: jnp.asarray(v) for k, v in params.items()}


def _ref_logits(ref, tokens):
    import jax.numpy as jnp
    return np.asarray(ref.logits(
        _jnp(PARAMS), jnp.asarray(np.asarray(tokens, np.int32)), CFG))


def _store(**kw):
    args = dict(STORE_KW)
    args.update(kw)
    return GenerativeProgramStore(dict(PARAMS), SPEC_IN, name="ds32",
                                  **args)
