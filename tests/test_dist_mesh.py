"""The collectives kvstore (``create('dist_mesh')``) and its data plane
(docs/architecture/dist_mesh.md):

* factory: 'dist_mesh' builds ``KVStoreMesh``, unknown names still
  raise; the classic push/pull API stays closed-form correct with the
  PS wire replaced by bucket collectives;
* the acceptance pin: the SAME ``Module.fit`` script runs unmodified
  with ``kvstore='dist_sync'`` (parameter servers) and
  ``kvstore='dist_mesh'`` (one SPMD program, bucketed in-graph
  reduction) — fp32 parity on the trained weights;
* reduce_mode='bucket' vs the fused single-psum step: bit-exact (the
  per-bucket sum only reassociates the cross-shard reduction);
* overlapped bucket collectives beat the barrier variant >= 1.3x under
  injected per-collective latency (the ``mesh.collective`` faultinject
  seam), and the submit->drain window lands as the ``comm_overlap``
  step phase;
* the multi-host ``mesh_for_contexts`` seam: canonical global device
  order, duplicate-device rejection, dp×mp axes round-trip through the
  program-cache key (reduce_mode and MXNET_KVSTORE_BUCKET_BYTES key
  separately);
* ``tools/launch.py --mesh``: DMLC_* scrubbed / mesh identity pinned
  env, plus the subprocess boot smoke (skips where jaxlib's CPU
  backend cannot run multiprocess computations).

``make mesh-smoke`` runs this file with a hard timeout (ci.yaml
per-change stage).
"""
import os
import sys
import threading

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import faultinject, profiler
from mxnet_tpu import kvstore as kvs
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.module import Module
from mxnet_tpu.parallel import (DataParallelTrainer, make_mesh,
                                program_cache_stats, reset_program_cache)
from mxnet_tpu.parallel import mesh as mesh_mod
from mxnet_tpu.parallel.mesh_reduce import MeshCollectiveLauncher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BATCH, FEAT, HID, NCLS = 32, 12, 16, 4


@pytest.fixture(autouse=True)
def _no_leftover_fault_plan():
    yield
    faultinject.install(None)


def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=HID)
    act = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.sym.FullyConnected(act, name="fc2", num_hidden=NCLS)
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _trainer(sym, mesh, **kw):
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("optimizer_params", {"learning_rate": 0.1})
    kw.setdefault("initializer", mx.initializer.Xavier())
    return DataParallelTrainer(sym, {"data": (BATCH, FEAT)},
                               {"softmax_label": (BATCH,)}, mesh=mesh,
                               **kw)


# ---------------------------------------------------------------------------
# factory + classic push/pull data plane
# ---------------------------------------------------------------------------
def test_factory_dist_mesh():
    kv = kvs.create("dist_mesh")
    assert isinstance(kv, kvs.KVStoreMesh)
    assert kv.type == "dist_mesh"
    # single-process launch: this worker is the whole mesh
    assert kv.rank == 0 and kv.num_workers == 1
    kv.close()
    with pytest.raises(MXNetError):
        kvs.create("dist_mesh_async")


def test_push_pull_closed_form(monkeypatch):
    """Classic API over the collective data plane: pushes accumulate
    (default updater) exactly, partial rounds are force-launched at
    pull, and un-initialized keys are rejected — same contract as the
    PS store with zero server processes."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "1024")
    kv = kvs.create("dist_mesh")
    keys = [3, 9, 44, 110]
    sizes = [4, 200, 7, 64]          # 200*4B=800B: keys split buckets
    for k, n in zip(keys, sizes):
        kv.init(k, mx.nd.zeros((n,)))
    assert len({kv._plan.bucket_of(k) for k in keys}) > 1
    ones = [mx.nd.ones((n,)) for n in sizes]
    for _ in range(2):               # two full rounds before any pull
        kv.push(keys, ones)
    outs = [mx.nd.zeros((n,)) for n in sizes]
    kv.pull(keys, outs)
    for o, n in zip(outs, sizes):
        np.testing.assert_array_equal(o.asnumpy(),
                                      np.full((n,), 2.0, np.float32))
    # a partial round (one member of a shared bucket) resolves at pull
    kv.push(keys[0], ones[0])
    kv.pull(keys[0], outs[0])
    np.testing.assert_array_equal(outs[0].asnumpy(),
                                  np.full((sizes[0],), 3.0, np.float32))
    with pytest.raises(MXNetError):
        kv.push(777, mx.nd.ones((4,)))
    kv.close()


def test_push_launches_ready_buckets_eagerly(monkeypatch):
    """A bucket's collective launches as soon as its LAST member key is
    pushed — tail buckets overlap earlier ones instead of waiting for
    one end-of-step barrier."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "1024")
    kv = kvs.create("dist_mesh")
    kv.init(0, mx.nd.zeros((8,)))
    kv.init(1, mx.nd.zeros((8,)))
    kv.init(2, mx.nd.zeros((250,)))   # 1000B: overflows into bucket 2
    assert kv._plan.bucket_of(0) == kv._plan.bucket_of(1)
    assert kv._plan.bucket_of(2) != kv._plan.bucket_of(0)
    kv.push(0, mx.nd.ones((8,)))
    assert not kv._launcher._pending        # bucket 0 not complete yet
    kv.push(1, mx.nd.ones((8,)))
    assert len(kv._launcher._pending) == 1  # ...now it is: launched
    kv.push(2, mx.nd.ones((250,)))
    assert len(kv._launcher._pending) == 2
    kv.flush()
    assert not kv._launcher._pending
    kv.close()


def test_push_pull_with_optimizer_and_compression(monkeypatch):
    """``set_optimizer`` runs the update locally on the reduced
    gradient (there is no server to ship it to) and 2-bit compression
    applies to this worker's contribution before the collective, with
    the same error-feedback residual as the PS path."""
    kv = kvs.create("dist_mesh")
    kv.init("w", mx.nd.zeros((16,)))
    from mxnet_tpu import optimizer as opt
    kv.set_optimizer(opt.Optimizer.create_optimizer(
        "sgd", learning_rate=0.5, rescale_grad=1.0))
    kv.push("w", mx.nd.ones((16,)))
    out = mx.nd.zeros((16,))
    kv.pull("w", out)
    np.testing.assert_allclose(out.asnumpy(),
                               np.full((16,), -0.5, np.float32),
                               rtol=1e-6)
    kv.close()

    kv2 = kvs.create("dist_mesh")
    kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv2.init("w", mx.nd.zeros((16,)))
    kv2.push("w", mx.nd.full((16,), 0.7))
    out2 = mx.nd.zeros((16,))
    kv2.pull("w", out2)     # default accumulate of the quantized grad
    np.testing.assert_allclose(out2.asnumpy(),
                               np.full((16,), 0.5, np.float32), rtol=1e-6)
    kv2.close()


# ---------------------------------------------------------------------------
# THE acceptance pin: one fit script, backend picked by string
# ---------------------------------------------------------------------------
def _fit_unmodified(kv_name, epochs=4):
    """The one training script of the acceptance criterion — only the
    kvstore string differs between the PS and the collectives run."""
    X = np.random.RandomState(0).randn(256, FEAT).astype("float32")
    y = (X.sum(axis=1) > 0).astype("float32") + \
        (X[:, 0] > 0).astype("float32")
    it = NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(0)
    mod.init_params(initializer=mx.initializer.Uniform(0.07))
    mod.fit(it, num_epoch=epochs, kvstore=kv_name, optimizer="sgd",
            optimizer_params={"learning_rate": 0.25}, eval_metric="acc")
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}, mod


def test_same_fit_script_ps_and_mesh_parity(monkeypatch):
    """fp32 parity between ``kvstore='dist_sync'`` (in-process parameter
    servers, server-side optimizer) and ``kvstore='dist_mesh'`` (the
    one-SPMD-program path with bucketed in-graph reduction) on an
    integer-friendly schedule — same script, same init, same data."""
    import socket
    import threading

    from mxnet_tpu import kvstore_dist as ksd

    # collectives run first: it must see no PS role vars
    for k in list(os.environ):
        if k.startswith("DMLC_"):
            monkeypatch.delenv(k, raising=False)
    a_mesh, mod = _fit_unmodified("dist_mesh")
    # routing: dist_mesh IS the fused one-program path — no PS client
    # was built, and the trainer runs the bucket-reduce step variant
    assert mod._fused is not None and mod._kvstore is None
    assert mod._fused._reduce_mode == "bucket"

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    for k, v in {"DMLC_ROLE": "worker",
                 "DMLC_PS_ROOT_URI": "127.0.0.1",
                 "DMLC_PS_ROOT_PORT": str(port),
                 "DMLC_NUM_WORKER": "1",
                 "DMLC_NUM_SERVER": "1"}.items():
        monkeypatch.setenv(k, v)
    threading.Thread(target=ksd.run_scheduler, daemon=True).start()
    threading.Thread(target=ksd.run_server, daemon=True).start()
    a_ps, mod_ps = _fit_unmodified("dist_sync")
    if mod_ps._kvstore is not None:
        mod_ps._kvstore.close()

    assert set(a_mesh) == set(a_ps)
    for k in a_ps:
        np.testing.assert_allclose(a_mesh[k], a_ps[k], rtol=1e-4,
                                   atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# bucketed reduction == fused single-psum step, bit for bit
# ---------------------------------------------------------------------------
def test_bucket_reduce_bitexact_vs_fused(monkeypatch):
    """Per-bucket sum(0) collectives + separate apply program produce
    the IDENTICAL arrays as the fused end-of-backward psum: the split
    only reassociates the cross-shard reduction, and the rng threading
    (fold_in per param) is preserved exactly."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "1024")
    sym = _mlp()
    mesh = make_mesh({"dp": 8})
    ta = _trainer(sym, mesh)                          # fused
    tb = _trainer(sym, mesh, reduce_mode="bucket")
    assert tb._reduce_mode == "bucket"
    assert len(tb._program.buckets) >= 2              # actually bucketed
    a0, x0 = ta.get_params()
    tb.set_params(a0, x0)

    rng = np.random.RandomState(7)
    for _ in range(5):
        X = rng.uniform(-1, 1, (BATCH, FEAT)).astype("float32")
        y = rng.randint(0, NCLS, (BATCH,)).astype("float32")
        oa = np.asarray(ta.step(X, y)[0])
        ob = np.asarray(tb.step(X, y)[0])
        np.testing.assert_array_equal(oa, ob)
    aa, _ = ta.get_params()
    ab, _ = tb.get_params()
    for name in aa:
        np.testing.assert_array_equal(aa[name].asnumpy(),
                                      ab[name].asnumpy(), err_msg=name)


def test_overlap_beats_barrier_live(monkeypatch):
    """Overlap against barrier, live, held by counts and not by the
    clock: with per-collective latency injected at the
    ``mesh.collective`` seam, the overlapped launcher has EVERY
    bucket's reduce of a step inside its delay at once (each waits, in
    the seam, for all the others: it pays ~max(delay)), and the barrier
    variant never has two (it pays n_buckets x delay); both cross the
    seam once a bucket a step."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "256")
    sym = _mlp()
    tr = _trainer(sym, make_mesh({"dp": 8}), reduce_mode="bucket")
    n_buckets = len(tr._program.buckets)
    assert n_buckets >= 3
    X, y = (np.zeros((BATCH, FEAT), np.float32),
            np.zeros((BATCH,), np.float32))
    tr.step(X, y)                     # compile outside the fault window

    lock = threading.Lock()
    inside, most, crossed = [0], [0], [0]
    together = [None]                 # overlap: the step's rendezvous
    hook = faultinject.hook

    def counted(seam, **meta):
        with lock:
            inside[0] += 1
            crossed[0] += 1
            most[0] = max(most[0], inside[0])
        try:
            if together[0] is not None:
                together[0].wait(60)  # BrokenBarrierError fails drain()
            return hook(seam, **meta)
        finally:
            with lock:
                inside[0] -= 1

    monkeypatch.setattr(faultinject, "hook", counted)

    def run(overlap, steps=3):
        tr._launcher = MeshCollectiveLauncher(overlap=overlap)
        together[0] = threading.Barrier(n_buckets) if overlap else None
        most[0] = crossed[0] = 0
        for _ in range(steps):
            tr.step(X, y)
        return most[0], crossed[0]

    faultinject.install({"rules": [
        {"seam": "mesh.collective", "nth": 1, "count": "inf",
         "action": "delay", "seconds": 0.002}]})
    try:
        assert run(True) == (n_buckets, 3 * n_buckets)
        assert run(False) == (1, 3 * n_buckets)
    finally:
        faultinject.install(None)


def test_comm_overlap_phase_recorded(monkeypatch):
    """The submit->drain window of the bucket collectives lands as the
    ``comm_overlap`` step phase (nested inside spmd_step, excluded from
    the additive breakdown) so tools/step_profile.py can attribute it."""
    assert "comm_overlap" in profiler.PHASES
    assert "comm_overlap" in profiler._NON_ADDITIVE_PHASES
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "1024")
    tr = _trainer(_mlp(), make_mesh({"dp": 8}), reduce_mode="bucket")
    X, y = (np.zeros((BATCH, FEAT), np.float32),
            np.zeros((BATCH,), np.float32))
    profiler.start_step_profile()
    try:
        tr.step(X, y)
    finally:
        report = profiler.stop_step_profile()
    assert "comm_overlap" in report["phases"]
    assert "spmd_step" in report["phases"]
    assert report["phases"]["comm_overlap"]["total_ms"] > 0


# ---------------------------------------------------------------------------
# the multi-host mesh seam
# ---------------------------------------------------------------------------
class _StubDev:
    def __init__(self, process_index, dev_id):
        self.process_index = process_index
        self.id = dev_id


def test_global_device_order_is_process_major():
    devs = [_StubDev(1, 0), _StubDev(0, 3), _StubDev(1, 2),
            _StubDev(0, 0), _StubDev(0, 1)]
    ordered = mesh_mod.global_device_order(devs)
    assert [(d.process_index, d.id) for d in ordered] == \
        [(0, 0), (0, 1), (0, 3), (1, 0), (1, 2)]
    # devices without a process_index (CPU stubs) sort by id alone
    bare = mesh_mod.global_device_order(jax.devices()[::-1])
    assert [d.id for d in bare] == sorted(d.id for d in jax.devices())


def test_mesh_for_contexts_rejects_duplicate_devices():
    with pytest.raises(MXNetError, match="duplicate"):
        mesh_mod.mesh_for_contexts([mx.cpu(0), mx.cpu(0)])


def test_mesh_for_contexts_multihost_single_process_axes():
    """Single-process launch: multihost=True is a no-op extension (the
    global census IS the local one), and a dp×mp axes dict round-trips
    through the factory."""
    ctxs = [mx.cpu(i) for i in range(8)]
    m = mesh_mod.mesh_for_contexts(ctxs, multihost=True)
    assert m.devices.size == 8 and m.axis_names == ("dp",)
    m2 = mesh_mod.mesh_for_contexts(ctxs, axes={"dp": 2, "mp": -1},
                                    multihost=True)
    assert dict(m2.shape) == {"dp": 2, "mp": 4}


def test_distributed_init_noop_without_env(monkeypatch):
    monkeypatch.delenv("MXNET_MESH_COORDINATOR", raising=False)
    assert mesh_mod.distributed_init_from_env() is False


def test_dist_mesh_cache_key_roundtrip(monkeypatch):
    """reduce_mode and the bucket-layout knob are program-cache key
    fields: fused vs bucket vs re-bucketed never collide, identical
    configs re-hit — including on a dp×mp mesh."""
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "1024")
    reset_program_cache()
    sym = _mlp()
    mesh8 = make_mesh({"dp": 8})
    _trainer(sym, mesh8)                               # fused
    assert program_cache_stats()["size"] == 1
    tb = _trainer(sym, mesh8, reduce_mode="bucket")
    s = program_cache_stats()
    assert s["size"] == 2 and s["misses"] == 2
    tb2 = _trainer(sym, mesh8, reduce_mode="bucket")   # re-hit
    s2 = program_cache_stats()
    assert s2["size"] == 2 and s2["hits"] > s["hits"]
    assert tb2._program is tb._program
    # dp×mp axes round-trip: separate key, then re-hit
    mesh2x4 = make_mesh({"dp": 2, "mp": 4})
    tmp = _trainer(sym, mesh2x4, reduce_mode="bucket")
    assert program_cache_stats()["size"] == 3
    tmp2 = _trainer(sym, mesh2x4, reduce_mode="bucket")
    assert tmp2._program is tmp._program
    assert program_cache_stats()["size"] == 3
    # the layout knob is in the key: a resized bucket plan recompiles
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_BYTES", "512")
    tb3 = _trainer(sym, mesh8, reduce_mode="bucket")
    assert tb3._program is not tb._program
    reset_program_cache()


# ---------------------------------------------------------------------------
# tools/launch.py --mesh: env coherence + multi-process boot smoke
# ---------------------------------------------------------------------------
def _launch_mod():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import launch
    finally:
        sys.path.pop(0)
    return launch


def test_mesh_env_scrubs_ps_roles_and_pins_identity():
    """The satellite-6 coherence fix: a mesh process must carry mesh
    identity ONLY — every DMLC_* var is scrubbed (a restarted worker
    would otherwise rejoin with a stale PS rank) while MXNET_AUTO_RESUME
    and the rest of the environment pass through, and a respawn of
    process i re-exports the SAME process id."""
    launch = _launch_mod()
    base = {"DMLC_ROLE": "server", "DMLC_PS_ROOT_URI": "10.0.0.1",
            "DMLC_NUM_WORKER": "4", "PATH": "/usr/bin",
            "MXNET_AUTO_RESUME": "ckpt/run1"}
    e = launch.mesh_env(base, "127.0.0.1:4567", 2, 1)
    assert not any(k.startswith("DMLC_") for k in e)
    assert e["MXNET_MESH_COORDINATOR"] == "127.0.0.1:4567"
    assert e["MXNET_MESH_NUM_PROCESSES"] == "2"
    assert e["MXNET_MESH_PROCESS_ID"] == "1"
    assert e["PATH"] == "/usr/bin"
    assert e["MXNET_AUTO_RESUME"] == "ckpt/run1"
    # stable identity across a supervised respawn
    assert launch.mesh_env(base, "127.0.0.1:4567", 2, 1) == e


def test_launch_mesh_single_process_end_to_end():
    """--mesh 1: the whole boot path (coordinator env, jax.distributed
    init, Module.fit over kvstore='dist_mesh') runs end-to-end in a
    supervised subprocess — no multiprocess XLA needed, so this leg of
    the smoke never skips."""
    launch = _launch_mod()
    env = {"JAX_PLATFORMS": "cpu"}
    rc = launch.launch_mesh(
        1, [sys.executable, os.path.join(REPO, "tests",
                                         "dist_mesh_worker.py")],
        env=env)
    assert rc == 0


def test_launch_mesh_multiprocess_smoke():
    """--mesh 2: two processes, one global 8-device mesh, the same fit
    script.  XLA:CPU cannot run cross-process computations, so on CPU
    hosts this skips with the backend named (never fails) — on TPU
    hosts it exercises the real multi-host boot."""
    if jax.default_backend() == "cpu":
        pytest.skip("jaxlib XLA:CPU backend: multiprocess computations "
                    "aren't implemented on the CPU backend (jax %s) — "
                    "multi-process dist_mesh runs on TPU hosts only"
                    % jax.__version__)
    launch = _launch_mod()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)        # worker pins its own device count
    rc = launch.launch_mesh(
        2, [sys.executable, os.path.join(REPO, "tests",
                                         "dist_mesh_worker.py")],
        env=env)
    assert rc == 0
