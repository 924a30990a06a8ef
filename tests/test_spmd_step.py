"""The ONE SPMD step program (parallel/spmd.py) and its two frontends.

Runs on the virtual 8-device CPU mesh (conftest forces
``--xla_force_host_platform_device_count=8``), the same stand-in the rest
of the parallel suite uses.  Pins the PR-7 contract:

* numerical equivalence — dp=8 sharded training tracks the single-device
  fused trainer's loss trajectory to fp32 tolerance, and a dp2×mp2 mesh
  (tensor-parallel rules) matches pure dp=4;
* ONE compiled executable serves both the fused-trainer frontend and the
  executor-group frontend for the same (symbol, mesh, shapes, optimizer)
  — the shared program cache, plus the no-retrace pin;
* ``MXNET_SPMD=0`` escape hatch: the classic per-device replication path
  (host gradient aggregation + host updater) is restored bit-for-bit and
  trainers compile privately;
* the in-process multi-device variant of ``tests/dist_fused_dp.py``:
  the sharded data-parallel step's closed-form SGD recursion, exercised
  on every change (the subprocess variant keeps its jaxlib CPU skip).
"""
import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.module import Module
from mxnet_tpu.parallel import (DataParallelTrainer, FusedDPTrainer,
                                MeshTrainer, ShardingRules, make_mesh,
                                program_cache_stats, reset_program_cache)
from mxnet_tpu.parallel import spmd as spmd_mod


BATCH, FEAT, HID, NCLS = 32, 12, 16, 4


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=HID)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=NCLS)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _toy(seed=0, n=BATCH):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-1, 1, (n, FEAT)).astype("float32")
    y = rng.randint(0, NCLS, (n,)).astype("float32")
    return X, y


def _xent(probs, y):
    idx = y.astype(int)
    p = probs[np.arange(len(idx)), idx]
    return float(-np.log(np.clip(p, 1e-12, None)).mean())


def _trainer(sym, mesh, **kw):
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("optimizer_params", {"learning_rate": 0.1})
    kw.setdefault("initializer", mx.initializer.Xavier())
    cls = kw.pop("cls", DataParallelTrainer)
    return cls(sym, {"data": (BATCH, FEAT)},
               {"softmax_label": (BATCH,)}, mesh=mesh, **kw)


# ---------------------------------------------------------------------------
# numerical equivalence
# ---------------------------------------------------------------------------
def test_dp8_loss_trajectory_matches_single_device(monkeypatch):
    """dp=8 sharded step == single-device fused step, per-step losses to
    fp32 tolerance over 20+ steps (the all-reduce only reassociates the
    batch mean)."""
    sym = _mlp()
    t1 = _trainer(sym, make_mesh({"dp": 1}, jax.devices()[:1]))
    t8 = _trainer(sym, make_mesh({"dp": 8}))
    a0, x0 = t1.get_params()
    t8.set_params(a0, x0)

    rng = np.random.RandomState(3)
    losses1, losses8 = [], []
    for step in range(22):
        X = rng.uniform(-1, 1, (BATCH, FEAT)).astype("float32")
        y = rng.randint(0, NCLS, (BATCH,)).astype("float32")
        o1 = np.asarray(t1.step(X, y)[0])
        o8 = np.asarray(t8.step(X, y)[0])
        losses1.append(_xent(o1, y))
        losses8.append(_xent(o8, y))
    assert losses1[-1] < losses1[0]          # it actually learns
    np.testing.assert_allclose(losses1, losses8, rtol=1e-4, atol=1e-5)
    a1, _ = t1.get_params()
    a8, _ = t8.get_params()
    for name in a1:
        np.testing.assert_allclose(a1[name].asnumpy(), a8[name].asnumpy(),
                                   rtol=2e-4, atol=2e-5)


def test_dp2xmp2_matches_dp4():
    """dp2×mp2 (tensor-parallel rules on the mp axis) == pure dp=4: the
    param-axis shardings change the collectives XLA inserts, never the
    math."""
    sym = _mlp()
    t_dp = _trainer(sym, make_mesh({"dp": 4}, jax.devices()[:4]))
    rules = ShardingRules([
        (r"fc1_weight", P("tp", None)), (r"fc1_bias", P("tp")),
        (r"fc2_weight", P(None, "tp")),
    ])
    t_mp = _trainer(sym, make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4]),
                    cls=MeshTrainer, rules=rules)
    a0, x0 = t_dp.get_params()
    t_mp.set_params(a0, x0)

    rng = np.random.RandomState(4)
    for step in range(20):
        X = rng.uniform(-1, 1, (BATCH, FEAT)).astype("float32")
        y = rng.randint(0, NCLS, (BATCH,)).astype("float32")
        o_dp = np.asarray(t_dp.step(X, y)[0])
        o_mp = np.asarray(t_mp.step(X, y)[0])
        np.testing.assert_allclose(_xent(o_dp, y), _xent(o_mp, y),
                                   rtol=2e-4)
    a1, _ = t_dp.get_params()
    a2, _ = t_mp.get_params()
    for name in a1:
        np.testing.assert_allclose(a1[name].asnumpy(), a2[name].asnumpy(),
                                   rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# the in-process multi-device variant of tests/dist_fused_dp.py
# (the subprocess variant keeps its jaxlib CPU skip; this one runs on
# every change)
# ---------------------------------------------------------------------------
def test_sharded_dp_closed_form_in_process():
    """8 fake devices, one process: the sharded step's weights must
    follow the closed-form SGD recursion — the gradient mean is a
    genuine 8-shard all-reduce inside the compiled step."""
    LR, STEPS = 0.05, 5
    data = mx.sym.Variable("data")
    net = mx.sym.LinearRegressionOutput(
        mx.sym.FullyConnected(data, num_hidden=1, no_bias=True,
                              name="fc"), name="lro")
    rs = np.random.RandomState(3)
    X = rs.randn(16, 3).astype(np.float32)
    y = rs.randn(16, 1).astype(np.float32)

    tr = DataParallelTrainer(
        net, data_shapes={"data": (16, 3)},
        label_shapes={"lro_label": (16, 1)},
        mesh=make_mesh({"dp": 8}), optimizer="sgd",
        optimizer_params={"learning_rate": LR, "momentum": 0.0, "wd": 0.0},
        initializer=mx.initializer.Zero())
    for _ in range(STEPS):
        tr.step(X, y)
    w = np.asarray(tr.params["fc_weight"]).reshape(-1)
    wr = np.zeros((1, 3), np.float32)
    for _ in range(STEPS):
        gw = (X @ wr.T - y).T @ X
        wr = wr - LR * (gw / 16)
    np.testing.assert_allclose(w, wr.ravel(), rtol=1e-4)

    # ZeRO-1 momentum over the same in-process mesh: sharded optimizer
    # state stays numerically identical to the replicated recursion
    mom = 0.9
    tz = DataParallelTrainer(
        net, data_shapes={"data": (16, 3)},
        label_shapes={"lro_label": (16, 1)},
        mesh=make_mesh({"dp": 8}), optimizer="sgd",
        optimizer_params={"learning_rate": LR, "momentum": mom, "wd": 0.0},
        initializer=mx.initializer.Zero(), shard_optimizer_state=True)
    for _ in range(STEPS):
        tz.step(X, y)
    wz = np.asarray(tz.params["fc_weight"]).reshape(-1)
    wm = np.zeros((1, 3), np.float32)
    vm = np.zeros((1, 3), np.float32)
    for _ in range(STEPS):
        g = ((X @ wm.T - y).T @ X) / 16
        vm = mom * vm - LR * g
        wm = wm + vm
    np.testing.assert_allclose(wz, wm.ravel(), rtol=1e-4)


# ---------------------------------------------------------------------------
# one program, many frontends
# ---------------------------------------------------------------------------
def _fit_module(sym, X, y, contexts, epochs=2, kvstore="device"):
    it = NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(sym, context=contexts)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(0)
    mod.init_params(initializer=mx.initializer.Uniform(0.07))
    mod.fit(it, num_epoch=epochs, kvstore=kvstore, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, eval_metric="acc")
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}, mod


def test_one_executable_serves_both_frontends(monkeypatch):
    """The shared-cache acceptance pin: the fused-trainer frontend and
    the executor-group frontend with the same (symbol, mesh, shapes,
    optimizer statics) run ONE compiled program — the second frontend is
    a cache hit, never a second compile."""
    sym = _mlp()
    X, y = _toy(seed=1, n=2 * BATCH)
    reset_program_cache()

    monkeypatch.setenv("MXNET_MODULE_FUSED", "1")
    ctxs = [mx.cpu(i) for i in range(8)]
    a_fused, m1 = _fit_module(sym, X, y, ctxs)
    assert m1._fused is not None
    s1 = program_cache_stats()
    assert s1["size"] == 1 and s1["misses"] == 1

    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    a_spmd, m2 = _fit_module(sym, X, y, ctxs)
    assert m2._fused is None and m2._exec_group.spmd_active
    s2 = program_cache_stats()
    assert s2["size"] == 1, "frontends did not share the program"
    assert s2["misses"] == 1 and s2["hits"] > s1["hits"]
    assert (m2._exec_group.spmd_trainer._train_step
            is m1._fused._train_step)

    # both frontends trained the same trajectory
    for k in a_fused:
        np.testing.assert_allclose(a_fused[k], a_spmd[k],
                                   rtol=2e-6, atol=2e-7)


def test_no_retrace_across_steps_and_frontends(monkeypatch):
    """One jit cache entry across 20 steps AND across a second frontend
    sharing the program (spmd._cache_size()==1, train_step retrace
    count==1)."""
    sym = _mlp()
    reset_program_cache()
    mesh = make_mesh({"dp": 8})
    tr = _trainer(sym, mesh)
    rng = np.random.RandomState(5)
    for _ in range(20):
        X = rng.uniform(-1, 1, (BATCH, FEAT)).astype("float32")
        y = rng.randint(0, NCLS, (BATCH,)).astype("float32")
        tr.step(X, y)
    assert spmd_mod._cache_size() == 1

    # a second trainer over the same setup shares the entry
    tr2 = _trainer(sym, mesh)
    X, y = _toy(seed=6)
    tr2.step(X, y)
    assert spmd_mod._cache_size() == 1
    assert tr2._train_step is tr._train_step

    # the step body was traced exactly once for 21 dispatches across
    # two frontends (the executable-cache entry count is polluted by
    # fastpath bookkeeping, so the pin is on the trace counter)
    assert tr._program.trace_counts["train"] == 1


def test_program_cache_is_bounded_lru():
    reset_program_cache(max_size=1)
    sym = _mlp()
    mesh = make_mesh({"dp": 8})
    _trainer(sym, mesh)
    t2 = DataParallelTrainer(
        sym, {"data": (2 * BATCH, FEAT)}, {"softmax_label": (2 * BATCH,)},
        mesh=mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1})
    stats = program_cache_stats()
    assert stats["size"] == 1 and stats["evictions"] == 1
    reset_program_cache()


# ---------------------------------------------------------------------------
# executor-group frontend behavior
# ---------------------------------------------------------------------------
def test_exec_group_frontend_trains_and_scores(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    sym = _mlp()
    X, y = _toy(seed=2, n=2 * BATCH)
    ctxs = [mx.cpu(i) for i in range(4)]
    _, mod = _fit_module(sym, X, y, ctxs, epochs=3)
    assert mod._exec_group.spmd_active
    assert mod._updater is None and mod._kvstore is None
    it = NDArrayIter(X, y, batch_size=BATCH)
    acc = mod.score(it, "acc")[0][1]
    assert 0.0 <= acc <= 1.0
    # outputs flow through the one program's predict twin
    it.reset()
    mod.forward(next(iter(it)), is_train=False)
    outs = mod.get_outputs()
    assert outs[0].shape == (BATCH, NCLS)


def test_exec_group_frontend_monitor_falls_back(monkeypatch):
    """Installing a monitor needs per-op executor access: the group
    leaves the one-program path, carrying params + optimizer state into
    the host-updater machinery, and training continues."""
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    sym = _mlp()
    X, y = _toy(seed=7)
    it = NDArrayIter(X, y, batch_size=BATCH)
    ctxs = [mx.cpu(i) for i in range(2)]
    mod = Module(sym, context=ctxs)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    mod.init_optimizer(kvstore="device", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod._exec_group.spmd_active
    b0 = next(iter(it))
    mod.forward_backward(b0)
    mod.update()

    from mxnet_tpu.monitor import Monitor
    mod.install_monitor(Monitor(1))
    assert not mod._exec_group.spmd_active
    assert mod._updater is not None          # host update path rebuilt
    # momentum carried over into the per-device updater layout
    n_par = len(mod._exec_group.param_names)
    assert len(mod._updater.states) == n_par * len(ctxs)
    it.reset()
    mod.forward_backward(next(iter(it)))
    mod.update()
    args, _ = mod.get_params()
    for v in args.values():
        assert np.isfinite(v.asnumpy()).all()


def test_exec_group_frontend_optimizer_state_roundtrip(tmp_path,
                                                       monkeypatch):
    """.states files written by the exec-group SPMD frontend load into
    the fused frontend and back (same plain param-index layout)."""
    sym = _mlp()
    X, y = _toy(seed=8)
    ctxs = [mx.cpu(i) for i in range(2)]

    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    _, mod = _fit_module(sym, X, y, ctxs, epochs=2)
    assert mod._exec_group.spmd_active
    fname = str(tmp_path / "spmd.states")
    mod.save_optimizer_states(fname)

    monkeypatch.setenv("MXNET_MODULE_FUSED", "1")
    it = NDArrayIter(X, y, batch_size=BATCH)
    mod2 = Module(sym, context=ctxs)
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod2.init_params()
    mod2.init_optimizer(kvstore="device", optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9})
    assert mod2._fused is not None
    mod2.load_optimizer_states(fname)
    st = mod2._fused.get_updater_states()
    ref = mod._exec_group.spmd_trainer.get_updater_states()
    assert set(st) == set(ref)
    # the writer ran momentum=0 (its state serializes as None); the
    # momentum=0.9 loader must keep its fresh zero momentum buffers,
    # never materialize NaNs from the None entries
    for v in mod2._fused.opt_state.values():
        for s in v:
            assert np.isfinite(np.asarray(s)).all()


# ---------------------------------------------------------------------------
# the escape hatch
# ---------------------------------------------------------------------------
def test_spmd_escape_hatch_restores_classic_path_bit_for_bit(monkeypatch):
    """MXNET_SPMD=0 must reproduce the pre-PR per-device replication
    machinery exactly: same code path as a force-classic run, so params
    after N identical steps are BIT-equal, and no program enters the
    shared cache."""
    sym = _mlp()
    X, y = _toy(seed=9, n=2 * BATCH)
    ctxs = [mx.cpu(i) for i in range(2)]
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")

    monkeypatch.setenv("MXNET_SPMD", "0")
    reset_program_cache()
    a_hatch, m_hatch = _fit_module(sym, X, y, ctxs)
    assert not m_hatch._exec_group.spmd_active
    assert m_hatch._update_on_kvstore is not None
    assert program_cache_stats()["size"] == 0     # nothing shared

    # the pre-PR reference: the classic path pinned via the module-level
    # latch, with SPMD globally on
    monkeypatch.setenv("MXNET_SPMD", "1")
    it = NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(sym, context=ctxs)
    mod._fused_disabled = True
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(0)
    mod.init_params(initializer=mx.initializer.Uniform(0.07))
    mod.fit(it, num_epoch=2, kvstore="device", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, eval_metric="acc")
    a_ref, _ = mod.get_params()
    for k, v in a_ref.items():
        assert np.array_equal(a_hatch[k], v.asnumpy()), \
            "escape hatch diverged from the classic path on %s" % k


def test_spmd_escape_hatch_trainer_compiles_privately(monkeypatch):
    monkeypatch.setenv("MXNET_SPMD", "0")
    reset_program_cache()
    sym = _mlp()
    tr = _trainer(sym, make_mesh({"dp": 8}))
    X, y = _toy(seed=10)
    tr.step(X, y)
    assert program_cache_stats()["size"] == 0
    assert program_cache_stats()["misses"] == 0


def test_spmd_numerics_match_classic_at_fp32_tol(monkeypatch):
    """The SPMD step and the classic host-updater path train the same
    trajectory (all-reduce + in-graph update only reassociate the
    reductions)."""
    sym = _mlp()
    X, y = _toy(seed=11, n=2 * BATCH)
    ctxs = [mx.cpu(i) for i in range(4)]
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    monkeypatch.setenv("MXNET_SPMD", "1")
    a_spmd, m_spmd = _fit_module(sym, X, y, ctxs)
    assert m_spmd._exec_group.spmd_active
    monkeypatch.setenv("MXNET_SPMD", "0")
    a_classic, m_classic = _fit_module(sym, X, y, ctxs)
    assert not m_classic._exec_group.spmd_active
    for k in a_spmd:
        np.testing.assert_allclose(a_spmd[k], a_classic[k],
                                   rtol=1e-4, atol=1e-5)
