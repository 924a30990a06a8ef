"""Parallelism tests on the virtual 8-device CPU mesh.

Mirrors the reference's multi-device test strategy (SURVEY.md §4: CPU
contexts stand in for the device mesh — ``test_multi_device_exec.py``,
``test_kvstore.py``): every sharded path is checked numerically against a
single-device serial oracle.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import mxnet_tpu as mx
from mxnet_tpu.parallel import (
    make_mesh, DataParallelTrainer, MeshTrainer, ShardingRules,
    ring_attention, blockwise_attention, spmd_pipeline, pipelined,
    stack_stage_params, moe_ffn, init_moe_params,
)


def _ref_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        L = q.shape[2]
        mask = np.tril(np.ones((L, L), bool))
        s = np.where(mask, s, -1e30)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_dense(causal):
    rng = np.random.RandomState(0)
    q = rng.randn(2, 2, 16, 8).astype(np.float32)
    k = rng.randn(2, 2, 16, 8).astype(np.float32)
    v = rng.randn(2, 2, 16, 8).astype(np.float32)
    out = blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal, block_size=4)
    np.testing.assert_allclose(np.asarray(out),
                               _ref_attention(q, k, v, causal),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    rng = np.random.RandomState(1)
    B, H, L, D = 2, 2, 32, 8
    q = rng.randn(B, H, L, D).astype(np.float32)
    k = rng.randn(B, H, L, D).astype(np.float32)
    v = rng.randn(B, H, L, D).astype(np.float32)

    spec = P(None, None, "sp", None)
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = jax.jit(fn)(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out),
                               _ref_attention(q, k, v, causal),
                               rtol=1e-4, atol=1e-4)


def test_ring_attention_grads_match_dense():
    mesh = make_mesh({"sp": 4}, jax.devices()[:4])
    rng = np.random.RandomState(2)
    B, H, L, D = 1, 2, 16, 4
    q = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
    spec = P(None, None, "sp", None)

    def ring_loss(q, k, v):
        fn = shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return jnp.sum(fn(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)


def test_spmd_pipeline_matches_serial():
    S, M, mb, D = 4, 8, 2, 16
    mesh = make_mesh({"pp": S}, jax.devices()[:S])
    rng = np.random.RandomState(3)
    stage_w = [rng.randn(D, D).astype(np.float32) * 0.3 for _ in range(S)]
    x = rng.randn(M, mb, D).astype(np.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    run = pipelined(stage_fn, mesh, "pp", num_microbatches=M)
    stacked = stack_stage_params([{"w": jnp.asarray(w)} for w in stage_w])
    out = jax.jit(lambda p, x: run(p, x))(stacked, jnp.asarray(x))

    ref = x.copy()
    for w in stage_w:
        ref = np.tanh(ref @ w)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_pipeline_grads_flow():
    S, M, mb, D = 2, 4, 2, 8
    mesh = make_mesh({"pp": S}, jax.devices()[:S])
    rng = np.random.RandomState(4)
    ws = [jnp.asarray(rng.randn(D, D).astype(np.float32)) * 0.3
          for _ in range(S)]
    x = jnp.asarray(rng.randn(M, mb, D).astype(np.float32))

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    run = pipelined(stage_fn, mesh, "pp", num_microbatches=M)
    stacked = stack_stage_params([{"w": w} for w in ws])

    def loss(p, x):
        return jnp.sum(run(p, x) ** 2)

    def serial_loss(ws, x):
        h = x
        for i in range(S):
            h = jnp.tanh(h @ ws[i])
        return jnp.sum(h ** 2)

    g = jax.grad(loss)(stacked, x)
    g_ref = jax.grad(serial_loss)([w for w in ws], x)
    for i in range(S):
        np.testing.assert_allclose(np.asarray(g["w"][i]),
                                   np.asarray(g_ref[i]),
                                   rtol=1e-4, atol=1e-4)


def test_moe_ffn_matches_single_device():
    """8-way expert-parallel MoE == 1-way (all experts local) oracle."""
    ep = 4
    mesh = make_mesh({"ep": ep}, jax.devices()[:ep])
    rng = jax.random.key(5)
    D, H, E, T = 8, 16, 8, 32          # T tokens per device
    params = init_moe_params(rng, D, H, E)
    x = jax.random.normal(jax.random.key(6), (ep * T, D), jnp.float32)

    # sharded run: tokens and experts both over 'ep'
    ep_params_spec = {"gate": P(), "w1": P("ep", None, None),
                      "b1": P("ep", None), "w2": P("ep", None, None),
                      "b2": P("ep", None)}
    fn = shard_map(
        lambda x, p: moe_ffn(x, p, axis_name="ep", capacity_factor=8.0)[0],
        mesh=mesh, in_specs=(P("ep", None), ep_params_spec),
        out_specs=P("ep", None), check_vma=False)
    y = jax.jit(fn)(x, params)

    # oracle: same math on one device (ep=1 mesh)
    mesh1 = make_mesh({"ep": 1}, jax.devices()[:1])
    fn1 = shard_map(
        lambda x, p: moe_ffn(x, p, axis_name="ep", capacity_factor=8.0)[0],
        mesh=mesh1, in_specs=(P("ep", None), ep_params_spec),
        out_specs=P("ep", None), check_vma=False)
    y1 = jax.jit(fn1)(x, params)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1),
                               rtol=1e-4, atol=1e-4)


def test_mesh_trainer_matches_dp_trainer():
    """tp-sharded training == replicated training, numerically."""
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=8)
    out = mx.sym.SoftmaxOutput(fc2, name="softmax")

    shapes = {"data": (8, 12)}
    lshapes = {"softmax_label": (8,)}
    kw = dict(optimizer="sgd",
              optimizer_params={"learning_rate": 0.1},
              initializer=mx.initializer.Xavier())

    dp_mesh = make_mesh({"dp": 8})
    dp_tr = DataParallelTrainer(out, shapes, lshapes, mesh=dp_mesh, **kw)

    rules = ShardingRules([
        (r"fc1_weight", P("tp", None)), (r"fc1_bias", P("tp")),
        (r"fc2_weight", P(None, "tp")),
    ])
    tp_mesh = make_mesh({"dp": 2, "tp": 4})
    tp_tr = MeshTrainer(out, shapes, lshapes, mesh=tp_mesh, rules=rules,
                        **kw)
    # identical start
    arg0, aux0 = dp_tr.get_params()
    tp_tr.set_params(arg0, aux0)

    rng = np.random.RandomState(7)
    data_np = rng.randn(8, 12).astype(np.float32)
    label_np = rng.randint(0, 8, (8,)).astype(np.float32)
    for _ in range(3):
        o1 = dp_tr.step(data_np, label_np)
        o2 = tp_tr.step(data_np, label_np)
    a1, _ = dp_tr.get_params()
    a2, _ = tp_tr.get_params()
    for name in a1:
        np.testing.assert_allclose(a1[name].asnumpy(), a2[name].asnumpy(),
                                   rtol=2e-4, atol=2e-4)


def test_transformer_trainer_composes_dp_sp_tp():
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, TransformerTrainer)
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=2,
                            d_ff=32, max_len=16, moe_layers=(1,),
                            n_experts=4)
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    tr = TransformerTrainer(cfg, mesh, lr=0.1, seed=0)
    rng = np.random.RandomState(8)
    toks = rng.randint(0, 32, (4, 16))
    tgts = rng.randint(0, 32, (4, 16))
    l0 = float(tr.step(toks, tgts))
    losses = [float(tr.step(toks, tgts)) for _ in range(5)]
    assert np.isfinite(l0) and all(np.isfinite(l) for l in losses)
    assert losses[-1] < l0, (l0, losses)


def test_transformer_sharded_matches_single_device():
    """(dp=2, sp=2, tp=2) loss == (1,1,1) loss on the same batch."""
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, TransformerTrainer)
    cfg = TransformerConfig(vocab=16, d_model=8, n_heads=2, n_layers=1,
                            d_ff=16, max_len=8)
    rng = np.random.RandomState(9)
    toks = rng.randint(0, 16, (2, 8))
    tgts = rng.randint(0, 16, (2, 8))

    mesh8 = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    mesh1 = make_mesh({"dp": 1, "sp": 1, "tp": 1}, jax.devices()[:1])
    tr8 = TransformerTrainer(cfg, mesh8, lr=0.1, seed=3)
    tr1 = TransformerTrainer(cfg, mesh1, lr=0.1, seed=3)
    for i in range(3):
        l8 = float(tr8.step(toks, tgts))
        l1 = float(tr1.step(toks, tgts))
        np.testing.assert_allclose(l8, l1, rtol=1e-4, atol=1e-5)


def test_moe_transformer_trains_with_parity_vs_single_device():
    """VERDICT r3 #8: the full dp x sp x tp x ep composition must TRAIN
    equivalently to a single device, not merely execute.

    Phase 1 (parity): the same fixed batch is trained for 10 steps on
    the 8-device mesh and on one device; per-step losses must track to
    fp tolerance (stepwise equality implies gradient parity at every
    step) and the parameters must match leaf-for-leaf afterwards.
    This gate caught two real layout-dependence bugs in the Switch aux
    loss (local-mean products formed before the cross-shard average).

    Phase 2 (convergence): the sharded trainer continues alone; the
    loss must drop below half its initial value — "it trains", not
    "it executes".  (The reference analog is the closed-form dist
    kvstore test, tests/nightly/dist_sync_kvstore.py.)
    """
    from mxnet_tpu.parallel.transformer import (
        TransformerConfig, TransformerTrainer)
    # capacity_factor high enough that no expert overflows in either
    # layout: capacity truncation is LAYOUT-DEPENDENT by design (each
    # shard drops against its local queue - GShard semantics), so exact
    # parity is only defined in the no-drop regime
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=2,
                            d_ff=32, max_len=16, moe_layers=(1,),
                            n_experts=4, capacity_factor=8.0)
    rng = np.random.RandomState(11)
    toks = rng.randint(0, 32, (4, 16))
    tgts = rng.randint(0, 32, (4, 16))

    mesh8 = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    mesh1 = make_mesh({"dp": 1, "sp": 1, "tp": 1}, jax.devices()[:1])
    tr8 = TransformerTrainer(cfg, mesh8, lr=0.3, seed=4)
    tr1 = TransformerTrainer(cfg, mesh1, lr=0.3, seed=4)

    losses8 = []
    for step in range(10):
        l8 = float(tr8.step(toks, tgts))
        l1 = float(tr1.step(toks, tgts))
        losses8.append(l8)
        # tolerance loosens with step: fp divergence compounds
        # (chaotically) through the parameter trajectory
        np.testing.assert_allclose(l8, l1, rtol=1e-4 * (step + 1) ** 2,
                                   atol=1e-6, err_msg="step %d" % step)

    flat8, _ = jax.tree_util.tree_flatten(tr8.params)
    flat1, _ = jax.tree_util.tree_flatten(tr1.params)
    assert len(flat8) == len(flat1) and len(flat8) > 0
    for a, b in zip(flat8, flat1):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)),
                                   rtol=5e-3, atol=1e-4)

    for _ in range(25):
        losses8.append(float(tr8.step(toks, tgts)))
    assert losses8[-1] < 0.5 * losses8[0], (losses8[0], losses8[-1])


def test_zero1_optimizer_state_sharding_parity():
    """ZeRO-1 (beyond-reference): optimizer state sharded over dp must
    (a) actually shard — per-rank shards hold 1/dp of axis 0 — and
    (b) train bit-comparably to the replicated path."""
    net = mx.models.mlp(num_classes=8)
    mesh = make_mesh({"dp": 8})
    kw = dict(data_shapes={"data": (32, 64)},
              label_shapes={"softmax_label": (32,)}, mesh=mesh,
              optimizer="adam", optimizer_params={"learning_rate": 1e-2},
              initializer=mx.initializer.Xavier())
    mx.random.seed(0)
    repl = DataParallelTrainer(net, **kw)
    mx.random.seed(0)
    zero = DataParallelTrainer(net, shard_optimizer_state=True, **kw)

    sharded = 0
    for name, state in zero.opt_state.items():
        for t in state:
            if t.ndim and t.shape[0] % 8 == 0 and t.shape[0] >= 8:
                shard = t.addressable_shards[0].data
                assert shard.shape[0] == t.shape[0] // 8, (name, t.shape)
                sharded += 1
    assert sharded > 0, "no optimizer-state tensor was sharded"

    rs = np.random.RandomState(0)
    data = jnp.asarray(rs.randn(32, 64), jnp.float32)
    label = jnp.asarray(rs.randint(0, 8, (32,)), jnp.float32)
    for _ in range(5):
        repl.step(data, label)
        zero.step(data, label)
    for n in repl.params:
        np.testing.assert_allclose(
            np.asarray(jax.device_get(repl.params[n])),
            np.asarray(jax.device_get(zero.params[n])),
            rtol=2e-5, atol=1e-6)


def test_batch_placement_cache_semantics():
    """Steady-state batch placement (_place_cached): the same immutable
    jax buffer re-fed across steps is uploaded once (the synthetic
    --benchmark protocol; a per-step host-to-device copy of the batch
    would otherwise sit on every step), a new buffer misses, and mutable
    numpy sources are never cached so in-place edits are honored."""
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="fc"),
        name="softmax")
    tr = DataParallelTrainer(net, data_shapes={"data": (8, 6)},
                             label_shapes={"softmax_label": (8,)},
                             optimizer="sgd")
    rs = np.random.RandomState(0)
    d = jnp.asarray(rs.randn(8, 6).astype("float32"))
    lab = jnp.asarray(np.zeros(8, "float32"))
    tr.step(d, lab)
    placed = tr._placement_cache["data"][1]
    tr.step(d, lab)
    assert tr._placement_cache["data"][1] is placed, "same-buffer re-upload"
    d2 = jnp.asarray(rs.randn(8, 6).astype("float32"))
    tr.step(d2, lab)
    assert tr._placement_cache["data"][1] is not placed, "stale cache hit"

    host = rs.randn(8, 6).astype("float32")
    tr.step(host, lab)
    tr.step(host, lab)
    # a mutable numpy source is never cached AND evicts the stale jax
    # entry for its name — otherwise the retired device batch would pin
    # ~a batch of HBM for the trainer's lifetime (ADVICE r5)
    assert "data" not in tr._placement_cache, \
        "numpy-path step must evict the placement-cache entry"
    tr.step(d2, lab)
    assert "data" in tr._placement_cache, "jax source re-caches"
    tr.clear_placement_cache()
    assert tr._placement_cache == {}, "unbind/rebind clears the cache"
