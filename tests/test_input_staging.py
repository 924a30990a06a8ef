"""Overlapped device input staging + Executor donation/bf16 seams (PR 4).

Pins the four contracts of the "feed the MXU" pass:

* staging moves only WHERE the host->device upload happens — training
  results are bit-identical with ``MXNET_IO_STAGE=0`` on both the fused
  and the executor-group path;
* ``MXNET_EXEC_DONATE=0`` is a true escape hatch (parity, and the flag
  plumbing resolves: donation never engages on CPU);
* ``compute_dtype='bfloat16'`` works through the classic
  ``Module``/Executor path: fp32 master weights, checkpoint interop,
  and a loss curve tracking fp32;
* under injected per-batch host latency the stager overlaps data
  production with compute: fit steps/sec >= 1.5x the blocking baseline.
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import DelayedIter, smoke_mlp


def _mlp(hidden=32):
    return smoke_mlp(num_hidden=hidden)


def _bn_mlp():
    data = mx.sym.Variable("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, num_hidden=32, name="fc1"),
        act_type="relu")
    h = mx.sym.BatchNorm(h, name="bn1")
    return mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, num_hidden=10, name="fc2"),
        name="softmax")


def _toy(n=256, feat=20, seed=0):
    rs = np.random.RandomState(seed)
    X = rs.uniform(-1, 1, (n, feat)).astype("float32")
    y = rs.randint(0, 10, (n,)).astype("float32")
    return X, y


def _fit_params(sym, X, y, epochs=2, compute_dtype=None, batch=32):
    mx.random.seed(7)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.Module(sym, context=mx.cpu(), compute_dtype=compute_dtype)
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            eval_metric="acc")
    args, auxs = mod.get_params()
    return ({k: v.asnumpy() for k, v in args.items()},
            {k: v.asnumpy() for k, v in auxs.items()})


def _assert_same_params(a, b):
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(a[k], b[k]), k


# ---------------------------------------------------------------------------
# bit-exactness: staging only moves the upload
# ---------------------------------------------------------------------------
def test_staged_vs_blocking_bit_exact_fused(monkeypatch):
    X, y = _toy()
    monkeypatch.setenv("MXNET_IO_STAGE", "1")
    a1, x1 = _fit_params(_bn_mlp(), X, y)
    monkeypatch.setenv("MXNET_IO_STAGE", "0")
    a0, x0 = _fit_params(_bn_mlp(), X, y)
    _assert_same_params(a1, a0)
    _assert_same_params(x1, x0)


def test_staged_vs_blocking_bit_exact_executor_group(monkeypatch):
    # JIT threshold pinned to 1: the tiered imperative dispatch would
    # otherwise run the host-updater path eagerly on early sightings
    # and compiled later — an in-process warmup artifact that differs
    # at the 1e-10 level between back-to-back runs (pre-existing,
    # staging-independent)
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    monkeypatch.setenv("MXNET_IMPERATIVE_JIT_THRESHOLD", "1")
    X, y = _toy()
    monkeypatch.setenv("MXNET_IO_STAGE", "1")
    a1, x1 = _fit_params(_bn_mlp(), X, y)
    monkeypatch.setenv("MXNET_IO_STAGE", "0")
    a0, x0 = _fit_params(_bn_mlp(), X, y)
    _assert_same_params(a1, a0)
    _assert_same_params(x1, x0)


def test_staging_does_not_retrace_fused_step(monkeypatch):
    """Staged batches land pre-sharded; the fused train step must stay
    ONE compiled executable across epochs (a second trace would mean
    the stager changed the avals/sharding the step was traced for)."""
    monkeypatch.setenv("MXNET_IO_STAGE", "1")
    X, y = _toy()
    mx.random.seed(7)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1}, eval_metric="acc")
    assert mod._fused is not None
    cache_size = getattr(mod._fused._train_step, "_cache_size", None)
    if cache_size is None:
        pytest.skip("jit cache introspection unavailable on this jax")
    assert cache_size() == 1


# ---------------------------------------------------------------------------
# donation escape hatch
# ---------------------------------------------------------------------------
def test_donation_escape_hatch_parity(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    monkeypatch.setenv("MXNET_IMPERATIVE_JIT_THRESHOLD", "1")
    X, y = _toy()
    monkeypatch.setenv("MXNET_EXEC_DONATE", "1")
    a1, x1 = _fit_params(_bn_mlp(), X, y)
    monkeypatch.setenv("MXNET_EXEC_DONATE", "0")
    a0, x0 = _fit_params(_bn_mlp(), X, y)
    _assert_same_params(a1, a0)
    _assert_same_params(x1, x0)


def test_donation_gated_off_on_cpu_and_custom_ops(monkeypatch):
    """The donation decision mirrors dp.py/cached_op.py: never on the
    CPU backend (PJRT:CPU has no donation), never with Custom host
    callbacks, and MXNET_EXEC_DONATE=0 always wins."""
    import jax
    ex = _bn_mlp().simple_bind(mx.cpu(), grad_req="write",
                               data=(8, 20), softmax_label=(8,))
    if jax.default_backend() == "cpu":
        assert ex._donate_aux is False
    monkeypatch.setenv("MXNET_EXEC_DONATE", "0")
    ex2 = _bn_mlp().simple_bind(mx.cpu(), grad_req="write",
                                data=(8, 20), softmax_label=(8,))
    assert ex2._donate_aux is False


def test_repeated_backward_with_donation_flag_advances_aux_once():
    """With aux donation on, forward->backward->backward must leave the
    BN moving stats advanced exactly ONCE (the MXNET_EXEC_DONATE=0
    semantics): the re-run takes the lazily-jitted non-donating
    executable and skips the aux write-back.  CPU has no real donation,
    so the flag is forced to exercise the control flow."""
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (8, 20)).astype("float32")
    y = np.zeros((8,), "float32")

    def run(flag, n_backward):
        mx.random.seed(3)
        ex = _bn_mlp().simple_bind(mx.cpu(), grad_req="write",
                                   data=(8, 20), softmax_label=(8,))
        ex._donate_aux = flag   # off-CPU decision, simulated
        ex.arg_dict["data"][:] = X
        ex.arg_dict["softmax_label"][:] = y
        ex.forward(is_train=True)
        for _ in range(n_backward):
            grads = ex.backward()
        return ({k: v.asnumpy() for k, v in ex.aux_dict.items()},
                [g.asnumpy() for g in grads])

    aux_ref, grads_ref = run(False, 2)   # pre-donation semantics
    aux_don, grads_don = run(True, 2)
    for k in aux_ref:
        np.testing.assert_array_equal(aux_ref[k], aux_don[k])
    for a, b in zip(grads_ref, grads_don):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# bf16 through the classic Executor path
# ---------------------------------------------------------------------------
def test_bf16_executor_master_weights_and_loss_curve(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (512, 20)).astype("float32")
    w = rs.uniform(-1, 1, (20,))
    y = ((X @ w > 0) & (np.abs(X).sum(1) > 4)).astype("float32")

    def run(cdt):
        mx.random.seed(7)
        it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True)
        mod = mx.Module(_bn_mlp(), context=mx.cpu(), compute_dtype=cdt)
        mod.fit(it, num_epoch=4, optimizer="sgd",
                optimizer_params={"learning_rate": 0.3, "momentum": 0.9},
                eval_metric="acc")
        acc = dict(mod.score(mx.io.NDArrayIter(X, y, batch_size=64),
                             "acc"))["accuracy"]
        return acc, mod

    acc32, _ = run(None)
    accbf, mod = run("bfloat16")
    # master weights and aux (BN moving stats) stay fp32
    args, auxs = mod.get_params()
    for name, arr in list(args.items()) + list(auxs.items()):
        assert arr.dtype == np.float32, (name, arr.dtype)
    # loss-curve sanity: bf16 learns the same small task
    assert accbf > 0.8
    assert abs(acc32 - accbf) < 0.1

    # checkpoint interop: params saved from the bf16 module load into a
    # plain fp32 module and score identically (fp32 end to end)
    fname = str(tmp_path / "bf16_ckpt.params")
    mod.save_params(fname)
    mx.random.seed(7)
    it = mx.io.NDArrayIter(X, y, batch_size=64)
    mod32 = mx.Module(_bn_mlp(), context=mx.cpu())
    mod32.bind(data_shapes=it.provide_data,
               label_shapes=it.provide_label, for_training=True)
    mod32.init_params()
    mod32.load_params(fname)
    acc_re = dict(mod32.score(mx.io.NDArrayIter(X, y, batch_size=64),
                              "acc"))["accuracy"]
    assert abs(acc_re - accbf) < 0.02


def test_bf16_executor_uses_exec_group_not_fused(monkeypatch):
    """The point of the PR: bf16 must reach users who are NOT on the
    fused fast path."""
    monkeypatch.setenv("MXNET_MODULE_FUSED", "0")
    X, y = _toy()
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.Module(_mlp(), context=mx.cpu(), compute_dtype="bfloat16")
    mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric="acc")
    assert mod._fused is None
    ex = mod._exec_group.execs[0]
    import jax.numpy as jnp
    assert ex._compute_dtype == jnp.bfloat16
    # labels are pinned to master dtype
    assert "softmax_label" in ex._keep_dtype


# ---------------------------------------------------------------------------
# overlap: the acceptance gate
# ---------------------------------------------------------------------------
def test_staging_overlap_speedup(monkeypatch, capsys):
    """Batch t+1 uploads while step t computes.  Read on the spans, not
    on the clock's rate (a wall-clock speedup gate fails on a loaded
    host): with an upload held to 30 ms a batch on the producer thread
    and a step held to 20 ms, a LATER batch's ``h2d_stage`` interval
    intersects step t's ``compute`` interval for every step but the
    last (no batch is left to upload then); one more miss is allowed.
    The rate is printed beside what a blocking loop could reach."""
    from mxnet_tpu import profiler
    from mxnet_tpu.io.stager import DeviceStager
    batches, batch = 12, 32
    upload_s, step_s = 0.030, 0.020
    X, y = _toy(n=batch * batches)
    monkeypatch.setenv("MXNET_IO_STAGE", "1")
    place_one = DeviceStager._place_one

    def slow_place(self, arr):
        time.sleep(upload_s / 2)            # data and label: two a batch
        return place_one(self, arr)

    monkeypatch.setattr(DeviceStager, "_place_one", slow_place)
    mod = mx.Module(_mlp(), context=mx.cpu())
    update = mod.update

    def slow_update():
        time.sleep(step_s)
        update()

    monkeypatch.setattr(mod, "update", slow_update)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    profiler.profiler_set_state("run")
    try:
        mod.fit(it, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1}, eval_metric="acc")
        mx.nd.waitall()
        spans = list(profiler._state["profiler"].records)
    finally:
        profiler.profiler_set_state("stop")

    def intervals(name):
        return sorted((t0, t1) for n, t0, t1, _tid, cat in spans
                      if n == name and cat == "step_phase")

    compute, staged = intervals("compute"), intervals("h2d_stage")
    assert len(compute) == len(staged) == batches
    fit_threads = {tid for n, _, _, tid, _ in spans if n == "compute"}
    assert not fit_threads & {tid for n, _, _, tid, _ in spans
                              if n == "h2d_stage"}
    overlapped = sum(
        any(s0 < c1 and c0 < s1 for s0, s1 in staged[t + 1:])
        for t, (c0, c1) in enumerate(compute))
    warm = 2                                # the first steps compile
    rate = (batches - warm - 1) / (
        (compute[-1][0] - compute[warm][0]) * 1e-9)     # start to start
    with capsys.disabled():
        print("\nstaged fit: %.1f steps/s, %d of %d steps overlapped an "
              "upload (a blocking loop could reach %.1f steps/s)"
              % (rate, overlapped, batches, 1.0 / (upload_s + step_s)))
    assert overlapped >= batches - 2, (overlapped, compute, staged)


# ---------------------------------------------------------------------------
# stager mechanics
# ---------------------------------------------------------------------------
def test_stager_preserves_batch_attrs_and_values():
    from mxnet_tpu.io.stager import DeviceStager
    import jax
    X, y = _toy(n=96)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    dev = mx.cpu().jax_device()
    stager = DeviceStager(it, lambda a: jax.device_put(a, dev), depth=2)
    seen = 0
    for batch, (ref, _) in zip(stager, [(i, None) for i in range(3)]):
        assert batch.pad == 0
        np.testing.assert_array_equal(
            batch.data[0].asnumpy(), X[ref * 32:(ref + 1) * 32])
        np.testing.assert_array_equal(
            batch.label[0].asnumpy(), y[ref * 32:(ref + 1) * 32])
        seen += 1
    assert seen == 3
    # reset rewinds the source; iteration restarts at batch 0
    stager.reset()
    first = next(stager)
    np.testing.assert_array_equal(first.data[0].asnumpy(), X[:32])
    stager.close()


def test_stager_surfaces_producer_errors():
    from mxnet_tpu.io.stager import DeviceStager

    class Exploding:
        def __iter__(self):
            return self

        def __next__(self):
            raise RuntimeError("decode failed")

        def reset(self):
            pass

    stager = DeviceStager(Exploding(), lambda a: a)
    with pytest.raises(mx.MXNetError, match="decode failed"):
        next(stager)


def test_stager_records_h2d_and_fit_records_phases(tmp_path, monkeypatch):
    """The four step phases land in a Chrome trace as cat=step_phase
    spans, and the aggregation tools/step_profile.py uses reconstructs
    the per-step breakdown from them."""
    from mxnet_tpu import profiler
    monkeypatch.setenv("MXNET_IO_STAGE", "1")
    trace = str(tmp_path / "trace.json")
    X, y = _toy()
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.Module(_mlp(), context=mx.cpu())
    profiler.profiler_set_config(filename=trace)
    profiler.profiler_set_state("run")
    try:
        mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric="acc")
        mx.nd.waitall()
    finally:
        profiler.profiler_set_state("stop")
    profiler.dump_profile()
    report = profiler.aggregate_phase_trace(trace)
    assert report["steps"] == 8
    for phase in profiler.PHASES:
        if phase in ("data_next", "comm_overlap"):
            # data_next is only emitted by the record pipeline's
            # consumer seam (ThreadedBatchPipeline; this fit feeds an
            # NDArrayIter), comm_overlap only by the dist_mesh
            # bucketed-reduce step (parallel/mesh_reduce.py)
            continue
        assert phase in report["phases"], phase
        assert report["phases"][phase]["spans"] >= 8 - 1
    # h2d_stage overlaps compute: excluded from the pct base
    assert report["phases"]["h2d_stage"]["pct"] is None
    assert report["phases"]["compute"]["pct"] > 0


def test_step_phase_collector_inline():
    """The lightweight collector (an in-window instrument)
    aggregates without a trace file."""
    from mxnet_tpu import profiler
    profiler.start_step_profile()
    X, y = _toy(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.Module(_mlp(), context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd", eval_metric="acc")
    report = profiler.stop_step_profile()
    assert report["steps"] == 4
    assert set(("data_wait", "compute", "metric_fetch")) <= \
        set(report["phases"])
    # collector uninstalled: further phases are dropped
    assert profiler.stop_step_profile() is None


def test_placement_cache_popped_on_numpy_path_and_cleared_on_rebind():
    """dp.py placement-cache lifecycle (ADVICE r5): a host-numpy batch
    pops the per-name entry, and leaving the fused path clears the
    cache so retired trainers pin no batch HBM."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import DataParallelTrainer
    X, y = _toy(n=32)
    trainer = DataParallelTrainer(
        _mlp(), data_shapes={"data": (32, 20)},
        label_shapes={"softmax_label": (32,)})
    dev_batch = {"data": jnp.asarray(X[:32]),
                 "softmax_label": jnp.asarray(y[:32])}
    trainer._shard_batch(dev_batch)
    assert "data" in trainer._placement_cache
    # numpy source: entry must be dropped, not served stale
    trainer._shard_batch({"data": X[:32], "softmax_label": y[:32]})
    assert "data" not in trainer._placement_cache
    trainer._shard_batch(dev_batch)
    assert trainer._placement_cache
    trainer.clear_placement_cache()
    assert trainer._placement_cache == {}


def test_speedometer_metricless_drain_fetches_output():
    """Metric-less Speedometer windows must close on a dependent-byte
    fetch of a recent output (via BatchEndParam.locals), not bare
    waitall (ADVICE r5: waitall can return at enqueue-ack over remote
    PJRT)."""
    from mxnet_tpu.callback import Speedometer

    class _Out:
        def __init__(self):
            self.fetches = 0

        def __getitem__(self, key):
            return self

        def asnumpy(self):
            self.fetches += 1
            return np.zeros((1,))

    class _Mod:
        def __init__(self):
            self.out = _Out()

        def get_outputs(self):
            return [self.out]

    mod = _Mod()

    class _Param:
        eval_metric = None
        epoch = 0
        nbatch = 0
        locals = {"self": mod}

    spd = Speedometer(batch_size=4, frequent=1)
    p = _Param()
    spd(p)          # window opens on a drain
    p.nbatch = 1
    spd(p)          # window closes on a drain
    assert mod.out.fetches >= 2
