"""chip_smoke.py off the chip, and the seams that keep a failed or CPU run
from looking like a chip run.

The smoke itself only passes on a TPU.  Here: it must FAIL without one,
its plumbing must hold at toy widths through the same ``run(phases,
sizes)``, and the device-picking code it leans on must raise where it
used to fall back.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError, use_compile_cache

pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# chip_smoke.FULL's shape at toy widths (prompts 4 and 5 share a prefix
# of one whole 64-token block, so the prefix store has something to hit)
TOY = {
    "lm": {"num_layers": 1, "num_hidden": 32, "num_heads": 2,
           "vocab_size": 64, "seq_len": 16, "batch": 2, "steps": 2},
    "serve": {"prompt_lens": (5, 9, 17, 20, 70, 75), "shared_prefix": 64,
              "max_tokens": 3},
}


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name + "_under_test", os.path.join(REPO, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout, proc.stdout[-500:]
    assert "needs a TPU" in proc.stderr


def test_smoke_plumbing_at_toy_widths():
    """train/lm -> checkpoint -> serve/lm (fp32 and int8) end to end,
    kernels off: every check that does not need a compiled kernel."""
    lines = []
    recs = _load("chip_smoke").run(["train/lm", "serve/lm"], TOY,
                                   kernels=False, emit=lines.append)
    assert [r["phase"] for r in recs] == ["train/lm", "serve/lm"]
    assert all(r["ok"] for r in recs)
    assert [json.loads(line)["phase"] for line in lines] == \
        ["train/lm", "serve/lm"]
    train, serve = recs
    assert "fused trainer taken" in train["asserted"]
    assert train["grad_rel_diff"] == 0.0     # same lowering both times
    for side in ("fp32", "int8"):
        assert serve[side]["argmax_margin"] <= 1e-4
        assert "%s: zero compilations after warm-up" % side \
            in serve["asserted"]


def test_smoke_deepseek_kernels_phase_at_toy_widths():
    """kernels/deepseek-v3 at toy widths: on the CPU the doors lower to
    the twins, so this drives the phase's plumbing (tables with a shared
    block, ragged contexts, counts) and not the kernels."""
    toy = {"deepseek": {"heads": 4, "rank": 16, "rope": 8, "row": 24,
                        "kv_block": 8, "contexts": (3, 20, 41),
                        "chunk": 8, "hidden": 32, "expert": 16, "held": 4,
                        "routed": 16, "top_k": 4, "tokens": (6, 40)}}
    rec, = _load("chip_smoke").run(["kernels/deepseek-v3"], toy,
                                   kernels=False, emit=lambda line: None)
    assert rec["ok"] and rec["mla_gap_lq1"] == 0.0
    assert rec["moe_assignments_n40"] > 0
    assert "experts, 40 tokens: the same counts" in rec["asserted"]


def test_smoke_phase_failure_propagates():
    """serve/lm without the checkpoint train/lm saves is an error, not a
    skipped phase."""
    smoke = _load("chip_smoke")
    with pytest.raises(smoke.SmokeFailure):
        smoke.run(["serve/lm"], TOY, kernels=False, emit=lambda _: None)


def test_compile_cache_left_to_the_variable(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        want = os.path.join(REPO, ".jax_cache")
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:   # the tests stay cache-free
        jax.config.update("jax_compilation_cache_dir", before)


def test_accelerator_id_past_the_last_device_raises():
    assert mx.tpu(0).jax_device() is not None
    with pytest.raises(MXNetError, match="out of range"):
        mx.tpu(99).jax_device()
    with pytest.raises(MXNetError, match="out of range"):
        mx.gpu(len(jax.devices())).jax_device()
    # cpu ids stay labels for host memory
    assert mx.cpu(99).jax_device().platform == "cpu"


def test_duplicate_contexts_raise_instead_of_replicating():
    """Two contexts on one device used to train on the replication loop
    with a host updater and exit 0."""
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2),
        name="softmax")
    it = mx.io.NDArrayIter(np.zeros((8, 4), np.float32),
                           np.zeros(8, np.float32), batch_size=4)
    mod = mx.Module(net, context=[mx.cpu(0), mx.cpu(0)])
    with pytest.raises(MXNetError, match="duplicate"):
        mod.fit(it, num_epoch=1)


def test_rowwise_kernels_pick_interpret_by_platform():
    """``interpret`` defaults to None — by platform — so a direct caller
    on a chip cannot get the interpreter by omission; here it interprets
    and matches the dense lowering."""
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops import norm
    from mxnet_tpu.pallas_ops.flash_attention import _resolve_interpret
    assert _resolve_interpret(None) is True       # this is a CPU
    assert _resolve_interpret(False) is False
    x = jnp.asarray(np.random.RandomState(0).randn(16, 128), jnp.float32)
    g = jnp.ones((128,), jnp.float32)
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(norm.rms_norm(x, g), want, rtol=1e-5,
                               atol=1e-6)
