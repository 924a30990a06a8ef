"""Pallas kernel plane: kernel parity, dispatch seam, escape hatch.

Every kernel runs its REAL body in Pallas interpret mode on CPU
(flash_attention's pattern), pinned against the plain XLA lowering:
forward AND gradients within tolerance, the MXNET_PALLAS=0 escape hatch
bit-for-bit, the routing counters proving the kernel path was actually
taken, and the cached-op/SPMD caches keyed on the dispatch fingerprint
so an env flip can never serve a stale lowering."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import cached_op
from mxnet_tpu.pallas_ops import (dispatch, flash_attention, fused_softmax,
                                  layer_norm, rms_norm, softmax_output_head,
                                  softmax_xent_loss)
from mxnet_tpu.pallas_ops.softmax_xent import row_block
from mxnet_tpu.test_utils import assert_almost_equal

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(dtype))


# ---------------------------------------------------------------------------
# Direct kernel parity (interpret mode on CPU = the real kernel bodies)
# ---------------------------------------------------------------------------
def test_fused_softmax_parity():
    x = _rand((24, 96), 0)
    dy = _rand((24, 96), 1)
    p = fused_softmax(x, 8, True)
    assert_almost_equal(np.asarray(p), np.asarray(jax.nn.softmax(x, -1)),
                        rtol=1e-5, atol=1e-6)
    dx = jax.grad(lambda a: jnp.sum(fused_softmax(a, 8, True) * dy))(x)
    dx_ref = jax.grad(lambda a: jnp.sum(jax.nn.softmax(a, -1) * dy))(x)
    assert_almost_equal(np.asarray(dx), np.asarray(dx_ref),
                        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("scale", [1.0, 0.25])
def test_softmax_output_head_implicit_grad(scale):
    """The head's backward is the implicit loss gradient
    (p - onehot) * scale, IGNORING the incoming cotangent — the
    SoftmaxOutput contract."""
    x = _rand((16, 32), 2)
    lbl = jnp.asarray(np.random.RandomState(3).randint(0, 32, (16,))
                      .astype(np.float32))
    out, vjp = jax.vjp(
        lambda d: softmax_output_head(d, lbl, scale, 8, True), x)
    assert_almost_equal(np.asarray(out),
                        np.asarray(jax.nn.softmax(x, -1)),
                        rtol=1e-5, atol=1e-6)
    # cotangent of 7s: must not scale the implicit gradient
    grad = vjp(jnp.full_like(out, 7.0))[0]
    ref = (jax.nn.softmax(x, -1) -
           jax.nn.one_hot(lbl.astype(jnp.int32), 32)) * scale
    assert_almost_equal(np.asarray(grad), np.asarray(ref),
                        rtol=1e-5, atol=1e-6)


def test_softmax_xent_loss_parity():
    x = _rand((24, 64), 4)
    lbl = jnp.asarray(np.random.RandomState(5).randint(0, 64, (24,))
                      .astype(np.float32))
    loss = softmax_xent_loss(x, lbl, 8, True)
    ref = -jnp.take_along_axis(jax.nn.log_softmax(x, -1),
                               lbl.astype(jnp.int32)[:, None], 1)[:, 0]
    assert_almost_equal(np.asarray(loss), np.asarray(ref),
                        rtol=1e-5, atol=1e-5)
    gl = jax.grad(
        lambda a: jnp.sum(softmax_xent_loss(a, lbl, 8, True) * 0.5))(x)
    gref = jax.grad(
        lambda a: jnp.sum(-jnp.take_along_axis(
            jax.nn.log_softmax(a, -1),
            lbl.astype(jnp.int32)[:, None], 1) * 0.5))(x)
    assert_almost_equal(np.asarray(gl), np.asarray(gref),
                        rtol=1e-4, atol=1e-5)


def test_rms_norm_parity():
    x, g = _rand((24, 96), 6), _rand((96,), 7) * 0.1 + 1.0
    dy = _rand((24, 96), 8)

    def ref(x_, g_):
        r = jax.lax.rsqrt(jnp.mean(x_ * x_, -1, keepdims=True) + 1e-6)
        return x_ * r * g_

    assert_almost_equal(np.asarray(rms_norm(x, g, 1e-6, 8, True)),
                        np.asarray(ref(x, g)), rtol=1e-5, atol=1e-5)
    got = jax.vjp(lambda *a: rms_norm(*a, 1e-6, 8, True), x, g)[1](dy)
    want = jax.vjp(ref, x, g)[1](dy)
    for a, b, nm in zip(got, want, ("dx", "dgamma")):
        assert_almost_equal(np.asarray(a), np.asarray(b),
                            rtol=1e-4, atol=1e-4, names=(nm, nm + "_ref"))


def test_layer_norm_parity():
    x = _rand((24, 96), 9)
    g, b = _rand((96,), 10) * 0.1 + 1.0, _rand((96,), 11)
    dy = _rand((24, 96), 12)

    def ref(x_, g_, b_):
        mu = jnp.mean(x_, -1, keepdims=True)
        v = jnp.var(x_, -1, keepdims=True)
        return (x_ - mu) * jax.lax.rsqrt(v + 1e-5) * g_ + b_

    assert_almost_equal(np.asarray(layer_norm(x, g, b, 1e-5, 8, True)),
                        np.asarray(ref(x, g, b)), rtol=1e-5, atol=1e-5)
    got = jax.vjp(lambda *a: layer_norm(*a, 1e-5, 8, True), x, g, b)[1](dy)
    want = jax.vjp(ref, x, g, b)[1](dy)
    for a, c, nm in zip(got, want, ("dx", "dgamma", "dbeta")):
        assert_almost_equal(np.asarray(a), np.asarray(c),
                            rtol=1e-4, atol=1e-4, names=(nm, nm + "_ref"))


def test_kernels_accept_bf16():
    x = _rand((16, 128), 13).astype(jnp.bfloat16)
    g = (_rand((128,), 14) * 0.1 + 1.0).astype(jnp.bfloat16)
    out = rms_norm(x, g, 1e-6, 8, True)
    assert out.dtype == jnp.bfloat16
    p = fused_softmax(x, 8, True)
    assert p.dtype == jnp.bfloat16
    assert_almost_equal(np.asarray(p, dtype=np.float32),
                        np.asarray(jax.nn.softmax(
                            x.astype(jnp.float32), -1)),
                        rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Dispatch seam: eligibility, modes, fingerprint
# ---------------------------------------------------------------------------
def test_row_block_divisors():
    assert row_block(24, 8) == 8
    assert row_block(20, 8) == 5
    assert row_block(7, 8) == 7
    assert row_block(13, 8) == 1
    # budget shrink: a huge width halves the bound
    assert dispatch.row_block_for(64, 4 * 1024 * 1024 // 4) == 1


def test_dispatch_modes(monkeypatch):
    monkeypatch.setenv("MXNET_PALLAS", "0")
    assert not dispatch.kernels_active()
    monkeypatch.setenv("MXNET_PALLAS", "2")
    assert dispatch.kernels_active()
    monkeypatch.setenv("MXNET_PALLAS", "1")
    # auto on CPU: off (compiled Mosaic needs the TPU backend)
    assert dispatch.kernels_active() == (jax.default_backend() == "tpu")
    fp0 = dispatch.fingerprint()
    monkeypatch.setenv("MXNET_PALLAS_BLOCK_ROWS", "16")
    assert dispatch.fingerprint() != fp0


def test_eligibility_rules(monkeypatch):
    assert dispatch.eligible_rowwise(16, 64, "float32")
    assert not dispatch.eligible_rowwise(16, 64, "int32")
    assert not dispatch.eligible_rowwise(16, 1, "float32")
    assert not dispatch.eligible_rowwise(16, 2 * 1024 * 1024, "float32")
    # compiled Mosaic (TPU) additionally wants 128-aligned lanes
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    assert dispatch.eligible_rowwise(16, 256, "float32")
    assert not dispatch.eligible_rowwise(16, 96, "float32")
    monkeypatch.undo()
    assert dispatch.eligible_attention(2, 4, 64, 64, 64, "float32")
    # L <= block clamps to one exact block: eligible by construction
    assert dispatch.eligible_attention(2, 4, 65, 65, 64, "float32")
    assert not dispatch.eligible_attention(2, 4, 64, 64, 64, "int32")
    monkeypatch.setenv("MXNET_PALLAS_BLOCK_SEQ", "16")
    assert not dispatch.eligible_attention(2, 4, 24, 24, 64, "float32")
    assert dispatch.eligible_attention(2, 4, 32, 32, 64, "float32")


# ---------------------------------------------------------------------------
# Op-level routing and the escape hatch
# ---------------------------------------------------------------------------
def _routed(monkeypatch, mode, fn):
    if mode is None:
        monkeypatch.delenv("MXNET_PALLAS", raising=False)
    else:
        monkeypatch.setenv("MXNET_PALLAS", mode)
    dispatch.reset_dispatch_stats()
    out = fn()
    return out, dispatch.dispatch_stats()


def test_softmax_output_op_routes(monkeypatch):
    rs = np.random.RandomState(0)
    d = mx.nd.array(rs.randn(16, 32).astype("float32"))
    lbl = mx.nd.array(rs.randint(0, 32, (16,)).astype("float32"))

    def call():
        return mx.nd.SoftmaxOutput(d, lbl).asnumpy()

    ref, st = _routed(monkeypatch, None, call)
    assert "SoftmaxOutput" not in st      # auto on CPU: XLA lowering
    forced, st = _routed(monkeypatch, "2", call)
    assert st.get("SoftmaxOutput", 0) >= 1
    assert_almost_equal(forced, ref, rtol=1e-5, atol=1e-6)
    off, _ = _routed(monkeypatch, "0", call)
    assert np.array_equal(off, ref)       # escape hatch: bit-for-bit


def test_norm_ops_route_with_grads(monkeypatch):
    """LayerNorm/RMSNorm symbols: forced-kernel executor matches the
    XLA executor on outputs AND weight/input gradients."""
    rs = np.random.RandomState(1)
    d = rs.randn(12, 48).astype("float32")

    def run():
        x = mx.sym.Variable("x")
        out = mx.sym.RMSNorm(mx.sym.LayerNorm(x, name="ln"), name="rms")
        ex = out.simple_bind(mx.cpu(), x=(12, 48))
        for name, arr in ex.arg_dict.items():
            if name != "x":
                arr[:] = mx.nd.array(rs.rand(*arr.shape)
                                     .astype("float32") + 0.5)
        ex.forward(is_train=True, x=mx.nd.array(d))
        grads = ex.backward()
        return ([ex.outputs[0].asnumpy()] +
                [g.asnumpy() for g in grads])

    rs = np.random.RandomState(1)
    ref, st = _routed(monkeypatch, "0", run)
    rs = np.random.RandomState(1)
    forced, st = _routed(monkeypatch, "2", run)
    assert st.get("LayerNorm", 0) >= 1 and st.get("RMSNorm", 0) >= 1
    for a, b in zip(forced, ref):
        assert_almost_equal(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_attention_op_parity(causal, monkeypatch):
    rs = np.random.RandomState(2)
    q, k, v = (mx.nd.array(rs.randn(2, 2, 16, 8).astype("float32"))
               for _ in range(3))

    def call():
        return mx.nd.DotProductAttention(q, k, v, causal=causal).asnumpy()

    ref, _ = _routed(monkeypatch, "0", call)
    forced, st = _routed(monkeypatch, "2", call)
    assert st.get("DotProductAttention", 0) >= 1
    assert_almost_equal(forced, ref, rtol=1e-4, atol=1e-5)


def test_executor_pins_bind_time_routing(monkeypatch):
    """jit traces lazily: an executor BOUND under MXNET_PALLAS=2 whose
    first forward happens after the env is restored must still lower
    with the kernels routed (the bind-time fingerprint is re-applied
    around tracing), and the stats must count the routes."""
    from mxnet_tpu.pallas_ops import dispatch
    rs = np.random.RandomState(5)
    d = rs.randn(8, 32).astype("float32")
    x = mx.sym.Variable("x")
    out = mx.sym.RMSNorm(x, name="rms")

    with monkeypatch.context() as m:
        m.setenv("MXNET_PALLAS", "2")
        ex = out.simple_bind(mx.cpu(), x=(8, 32))
        ex.arg_dict["rms_gamma"][:] = mx.nd.array(
            rs.rand(32).astype("float32") + 0.5)
    # env restored (auto mode -> CPU would NOT route); trace now
    dispatch.reset_dispatch_stats()
    got = ex.forward(is_train=False, x=mx.nd.array(d))[0].asnumpy()
    assert dispatch.dispatch_stats().get("RMSNorm", 0) >= 1
    r = 1.0 / np.sqrt((d * d).mean(axis=1, keepdims=True) + 1e-6)
    ref = d * r * ex.arg_dict["rms_gamma"].asnumpy()
    assert_almost_equal(got, ref, rtol=1e-5, atol=1e-6)


def test_escape_hatch_bit_for_bit_on_training(monkeypatch):
    """MXNET_PALLAS=0 must reproduce the default CPU training step
    bit-for-bit (both are the plain XLA lowering)."""
    from mxnet_tpu.test_utils import smoke_mlp
    rs = np.random.RandomState(3)
    d = rs.randn(32, 32).astype("float32")
    lbl = rs.randint(0, 10, (32,)).astype("float32")

    def run():
        mx.random.seed(7)
        ex = smoke_mlp().simple_bind(mx.cpu(), data=(32, 32),
                                     softmax_label=(32,))
        for name, arr in ex.arg_dict.items():
            if name not in ("data", "softmax_label"):
                arr[:] = mx.nd.array(np.random.RandomState(
                    hash(name) % 2 ** 31).uniform(
                        -0.05, 0.05, arr.shape).astype("float32"))
        ex.forward(is_train=True, data=mx.nd.array(d),
                   softmax_label=mx.nd.array(lbl))
        grads = ex.backward()
        return ([ex.outputs[0].asnumpy()] +
                [g.asnumpy() for g in grads])

    ref, _ = _routed(monkeypatch, None, run)
    off, _ = _routed(monkeypatch, "0", run)
    for a, b in zip(off, ref):
        assert np.array_equal(a, b)


def test_cached_op_fingerprint_in_key(monkeypatch):
    """Flipping MXNET_PALLAS between calls of the SAME op/shape must
    miss the imperative cache (stale-lowering hazard), not hit."""
    cached_op.configure(threshold=1)
    try:
        rs = np.random.RandomState(4)
        d = mx.nd.array(rs.randn(8, 32).astype("float32"))
        lbl = mx.nd.array(rs.randint(0, 32, (8,)).astype("float32"))
        monkeypatch.setenv("MXNET_PALLAS", "0")
        mx.nd.SoftmaxOutput(d, lbl).asnumpy()
        misses0 = cached_op.stats()["misses"]
        monkeypatch.setenv("MXNET_PALLAS", "2")
        mx.nd.SoftmaxOutput(d, lbl).asnumpy()
        assert cached_op.stats()["misses"] > misses0
    finally:
        cached_op.configure()


# ---------------------------------------------------------------------------
# Transformer symbol: every kernel end-to-end through one train step
# ---------------------------------------------------------------------------
def test_transformer_symbol_kernels_end_to_end(monkeypatch):
    B, L, V = 4, 16, 32
    sym = mx.models.transformer_lm(seq_len=L, num_layers=1,
                                   num_hidden=16, num_heads=2,
                                   vocab_size=V)
    rs = np.random.RandomState(5)
    d = rs.randint(0, V, (B, L)).astype("float32")
    lbl = np.roll(d, -1, axis=1)

    def run():
        mx.random.seed(11)
        ex = sym.simple_bind(mx.cpu(), data=(B, L),
                             softmax_label=(B, L))
        for name, arr in ex.arg_dict.items():
            if name not in ("data", "softmax_label"):
                arr[:] = mx.nd.array(np.random.RandomState(
                    hash(name) % 2 ** 31).uniform(
                        -0.1, 0.1, arr.shape).astype("float32"))
        ex.forward(is_train=True, data=mx.nd.array(d),
                   softmax_label=mx.nd.array(lbl))
        grads = ex.backward()
        return ([ex.outputs[0].asnumpy()] +
                [g.asnumpy() for g in grads])

    ref, _ = _routed(monkeypatch, "0", run)
    forced, st = _routed(monkeypatch, "2", run)
    for kind in ("RMSNorm", "LayerNorm", "DotProductAttention",
                 "SoftmaxOutput"):
        assert st.get(kind, 0) >= 1, (kind, st)
    for a, b in zip(forced, ref):
        assert_almost_equal(a, b, rtol=2e-3, atol=2e-4)
