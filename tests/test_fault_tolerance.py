"""Fault-tolerant distributed KVStore (docs/architecture/fault_tolerance.md):

* retry/backoff policy math and circuit-breaker state transitions (pure);
* atomic checkpoint writes (crash mid-save never corrupts the last good
  checkpoint) and the latest-epoch auto-resume helpers;
* server snapshot save/restore round-trip including updater state;
* fanout error aggregation naming every failed shard;
* an in-process scheduler+server+worker cluster driven through seeded
  fault injection (dropped messages -> deadline -> backoff -> reconnect,
  with retries visible as profiler events);
* the end-to-end subprocess scenario: a server SIGKILLed mid-push by a
  seeded schedule, restarted under DMLC_PS_RECOVERY_RANK, restoring its
  snapshot — the final pulled values byte-match the no-fault run
  (`make dist-smoke` runs this one under a hard timeout).
"""
import json
import os
import pickle
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject
from mxnet_tpu import kvstore_dist as ksd
from mxnet_tpu import ndarray as nd
from mxnet_tpu.base import MXNetError, atomic_write

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leftover_fault_plan():
    yield
    faultinject.install(None)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Retry / backoff policy math
# ---------------------------------------------------------------------------
class _FixedRng:
    def __init__(self, value):
        self.value = value

    def random(self):
        return self.value


def test_backoff_delay_exponential_and_capped():
    assert ksd.backoff_delay(0, 0.1, 10.0) == pytest.approx(0.1)
    assert ksd.backoff_delay(3, 0.1, 10.0) == pytest.approx(0.8)
    # growth is monotone until the cap, then flat
    delays = [ksd.backoff_delay(k, 0.1, 10.0) for k in range(12)]
    assert delays == sorted(delays)
    assert ksd.backoff_delay(20, 0.1, 10.0) == pytest.approx(10.0)


def test_backoff_delay_equal_jitter_bounds():
    # jitter maps d into [d/2, d]
    assert ksd.backoff_delay(2, 0.1, 10.0, _FixedRng(0.0)) \
        == pytest.approx(0.2)
    assert ksd.backoff_delay(2, 0.1, 10.0, _FixedRng(1.0)) \
        == pytest.approx(0.4)
    mid = ksd.backoff_delay(2, 0.1, 10.0, _FixedRng(0.5))
    assert 0.2 <= mid <= 0.4


def test_retry_policy_env_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_RPC_TIMEOUT", "7.5")
    monkeypatch.setenv("MXNET_KVSTORE_RPC_RETRIES", "5")
    monkeypatch.setenv("MXNET_KVSTORE_RPC_BACKOFF", "0.25")
    monkeypatch.setenv("MXNET_KVSTORE_RPC_BACKOFF_CAP", "2")
    p = ksd.RetryPolicy()
    assert (p.timeout, p.retries, p.backoff, p.cap) == (7.5, 5, 0.25, 2.0)
    # a fault plan's seed makes the jitter stream reproducible
    faultinject.install({"seed": 42, "rules": []})
    d1 = [ksd.RetryPolicy().delay(k) for k in range(4)]
    d2 = [ksd.RetryPolicy().delay(k) for k in range(4)]
    assert d1 == d2


def test_circuit_breaker_state_transitions():
    clock = [0.0]
    cb = ksd.CircuitBreaker(fail_threshold=2, reset_after=5.0,
                            clock=lambda: clock[0])
    assert cb.state == cb.CLOSED and cb.allow()
    cb.record_failure(OSError("x"))
    assert cb.state == cb.CLOSED and cb.allow()     # below threshold
    cb.record_failure(OSError("y"))
    assert cb.state == cb.OPEN and not cb.allow()   # opened, fail fast
    clock[0] = 4.9
    assert not cb.allow()
    clock[0] = 5.0
    assert cb.allow()                               # half-open trial
    assert cb.state == cb.HALF_OPEN
    # exactly ONE trial: concurrent callers keep failing fast until the
    # in-flight trial reports back (no stampede on a dead endpoint)
    assert not cb.allow()
    cb.record_failure(OSError("z"))                 # trial failed
    assert cb.state == cb.OPEN and not cb.allow()
    clock[0] = 10.0
    assert cb.allow()
    assert not cb.allow()                           # again single-trial
    cb.record_success()                             # trial succeeded
    assert cb.state == cb.CLOSED and cb.failures == 0
    assert cb.allow() and cb.allow()                # closed: all pass


# ---------------------------------------------------------------------------
# Atomic checkpoints + auto-resume
# ---------------------------------------------------------------------------
def test_atomic_write_crash_keeps_previous_contents(tmp_path):
    path = str(tmp_path / "ckpt.bin")
    with atomic_write(path, "w") as f:
        f.write("good")
    with pytest.raises(RuntimeError):
        with atomic_write(path, "w") as f:
            f.write("half-writ")
            raise RuntimeError("crash mid-save")
    with open(path) as f:
        assert f.read() == "good"
    assert os.listdir(tmp_path) == ["ckpt.bin"]  # no tmp litter


def test_nd_save_crash_never_corrupts_last_checkpoint(tmp_path, monkeypatch):
    fname = str(tmp_path / "weights.params")
    v1 = {"arg:w": nd.array(np.arange(6, dtype=np.float32))}
    nd.save(fname, v1)
    nd.waitall()

    def _torn_savez(fobj, **kw):
        fobj.write(b"partial garbage")
        raise OSError("disk died mid-write")

    monkeypatch.setattr(ksd.np, "savez", _torn_savez)  # same np module
    nd.save(fname, {"arg:w": nd.zeros((6,))})
    with pytest.raises(MXNetError, match="async save failed"):
        nd.waitall()
    monkeypatch.undo()
    got = nd.load(fname)
    np.testing.assert_array_equal(got["arg:w"].asnumpy(),
                                  np.arange(6, dtype=np.float32))


def test_latest_checkpoint_auto_resume(tmp_path):
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.model import (latest_checkpoint, load_latest_checkpoint,
                                 save_checkpoint)
    prefix = str(tmp_path / "run")
    assert latest_checkpoint(prefix) is None
    assert load_latest_checkpoint(prefix) is None
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fc")
    for epoch, scale in ((1, 1.0), (2, 2.0)):
        save_checkpoint(prefix, epoch, net,
                        {"fc_weight": nd.ones((4, 3)) * scale}, {})
    nd.waitall()
    assert latest_checkpoint(prefix) == 2
    _, args, _, epoch = load_latest_checkpoint(prefix)
    assert epoch == 2
    np.testing.assert_array_equal(args["fc_weight"].asnumpy(),
                                  np.full((4, 3), 2.0, np.float32))


def test_module_load_latest(tmp_path):
    from mxnet_tpu import symbol as sym
    net = sym.SoftmaxOutput(sym.FullyConnected(
        sym.Variable("data"), num_hidden=3, name="fc"), name="softmax")
    X = np.random.randn(64, 8).astype("float32")
    y = (np.arange(64) % 3).astype("float32")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd")
    prefix = str(tmp_path / "model")
    assert mx.Module.load_latest(prefix) is None
    mod.save_checkpoint(prefix, 1)
    mod.save_checkpoint(prefix, 2)
    nd.waitall()
    loaded, epoch = mx.Module.load_latest(prefix, context=mx.cpu())
    assert epoch == 2
    np.testing.assert_array_equal(
        loaded._arg_params["fc_weight"].asnumpy(),
        mod.get_params()[0]["fc_weight"].asnumpy())


# ---------------------------------------------------------------------------
# Server snapshot round-trip
# ---------------------------------------------------------------------------
class _FakeConn:
    def __init__(self):
        self.sent = []

    def send(self, msg):
        self.sent.append(msg)


def test_server_snapshot_roundtrip_with_updater(tmp_path, monkeypatch):
    from mxnet_tpu import optimizer as opt
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("MXNET_KVSTORE_SNAPSHOT_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_KVSTORE_SNAPSHOT_INTERVAL", "5")
    s = ksd.Server()
    try:
        s._reg.rank = 0
        conn = _FakeConn()
        s._serve_one(("init", 3, np.zeros(4, np.float32)), conn)
        s._serve_one(
            ("command", 0, pickle.dumps(
                opt.Optimizer.create_optimizer(
                    "sgd", learning_rate=0.5, momentum=0.9))), conn)
        s._serve_one(("push", 3, np.ones(4, np.float32)), conn)
        s._serve_one(("push", 3, np.ones(4, np.float32)), conn)
        assert s.save_snapshot()
        assert not s.save_snapshot()  # unchanged: skipped

        t = ksd.Server()
        try:
            t._reg.rank = 0
            assert t.restore_snapshot()
            np.testing.assert_array_equal(t.store[3], s.store[3])
            assert t.sync_mode == s.sync_mode
            assert t.updater is not None
            # updater state (momentum buffers) survived the round-trip
            assert pickle.loads(t.updater.get_states()).keys() \
                == pickle.loads(s.updater.get_states()).keys()
            # the recovered server keeps updating consistently
            t._serve_one(("push", 3, np.ones(4, np.float32)), conn)
            s._serve_one(("push", 3, np.ones(4, np.float32)), conn)
            np.testing.assert_allclose(t.store[3], s.store[3], rtol=1e-6)
        finally:
            t.listener.close()
    finally:
        s.listener.close()


def test_push_dedup_by_rank_incarnation_seq(monkeypatch):
    """A retried push whose ack was lost must not double-apply; a
    recovery replacement (new incarnation) must not be falsely deduped
    against its dead predecessor's watermarks."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.delenv("MXNET_KVSTORE_SNAPSHOT_DIR", raising=False)
    s = ksd.Server()
    try:
        conn = _FakeConn()
        s._serve_one(("init", 3, np.zeros(4, np.float32)), conn)
        one = np.ones(4, np.float32)
        s._serve_one(("push", 3, one, 0, 1, "inc-a"), conn)
        s._serve_one(("push", 3, one, 0, 1, "inc-a"), conn)  # resend
        np.testing.assert_array_equal(s.store[3], one)       # applied once
        assert conn.sent[-1] == ("ok",)                      # but acked
        s._serve_one(("push", 3, one, 0, 1, "inc-b"), conn)  # replacement
        np.testing.assert_array_equal(s.store[3], one * 2)
        # bare 3-tuple pushes (no identity) skip dedup entirely
        s._serve_one(("push", 3, one), conn)
        s._serve_one(("push", 3, one), conn)
        np.testing.assert_array_equal(s.store[3], one * 4)
    finally:
        s.listener.close()


def test_sync_push_retry_does_not_double_count(monkeypatch):
    """dist_sync merge: worker 0's resend into an open round refreshes
    its release channel instead of counting as a second contribution."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    monkeypatch.delenv("MXNET_KVSTORE_SNAPSHOT_DIR", raising=False)
    s = ksd.Server()
    try:
        conn0, conn0b, conn1 = _FakeConn(), _FakeConn(), _FakeConn()
        s._serve_one(("init", 3, np.zeros(2, np.float32)), conn0)
        s._handle_command("sync_mode", b"")
        one = np.ones(2, np.float32)
        s._serve_one(("push", 3, one, 0, 1, "a"), conn0)
        s._serve_one(("push", 3, one, 0, 1, "a"), conn0b)   # retry, rank 0
        assert conn0b.sent == []                            # round still open
        s._serve_one(("push", 3, one * 3, 1, 1, "b"), conn1)
        np.testing.assert_array_equal(s.store[3], one * 4)  # 1 + 3, not 2·1+3
        assert conn0b.sent == [("ok",)] and conn1.sent[-1] == ("ok",)
    finally:
        s.listener.close()


# ---------------------------------------------------------------------------
# Fanout error aggregation
# ---------------------------------------------------------------------------
def test_fanout_names_every_failed_shard():
    c = ksd.WorkerClient.__new__(ksd.WorkerClient)
    shards = [(0, (9, 0), 0, 10), (1, (9, 1), 10, 20), (2, (9, 2), 20, 30)]

    def fn(shard):
        if shard[0] != 1:
            raise OSError("server %d unreachable" % shard[0])

    with pytest.raises(MXNetError) as ei:
        c._fanout(shards, fn)
    msg = str(ei.value)
    assert "2 of 3 shards failed" in msg
    assert "server 0" in msg and "server 2" in msg
    # single failure keeps its original exception type
    with pytest.raises(OSError):
        c._fanout(shards[:2], lambda s: (_ for _ in ()).throw(
            OSError("x")) if s[0] == 0 else None)


# ---------------------------------------------------------------------------
# In-process cluster: drop -> deadline -> retry -> reconnect
# ---------------------------------------------------------------------------
def _inprocess_cluster(monkeypatch, **env):
    base = {
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(_free_port()),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "MXNET_KVSTORE_HEARTBEAT_INTERVAL": "0.2",
        "MXNET_KVSTORE_RPC_TIMEOUT": "0.3",
        "MXNET_KVSTORE_RPC_RETRIES": "4",
        "MXNET_KVSTORE_RPC_BACKOFF": "0.02",
        "MXNET_KVSTORE_RPC_BACKOFF_CAP": "0.1",
    }
    base.update(env)
    for k, v in base.items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("DMLC_PS_RECOVERY_RANK", raising=False)
    sched = ksd.Scheduler()
    threading.Thread(target=sched.run, daemon=True).start()
    server = ksd.Server()
    threading.Thread(target=server.run, daemon=True).start()
    return ksd.WorkerClient()


def test_dropped_reply_retries_and_profiles(monkeypatch, tmp_path):
    from mxnet_tpu import profiler
    client = _inprocess_cluster(monkeypatch)
    client.init(1, np.zeros(4, np.float32))
    profiler.profiler_set_config(filename=str(tmp_path / "trace.json"))
    profiler.profiler_set_state("run")
    try:
        faultinject.install({"seed": 1, "rules": [
            {"seam": "worker.send", "kind": "pull", "nth": 1,
             "action": "drop"}]})
        client.push(1, np.ones(4, np.float32))
        out = client.pull(1, 4)
    finally:
        profiler.profiler_set_state("stop")
        faultinject.install(None)
    np.testing.assert_array_equal(out, np.ones(4, np.float32))
    cats = {r[4] for r in profiler._state["profiler"].records}
    assert "rpc_retry" in cats      # the backoff sleep was profiled
    assert "rpc_reconnect" in cats  # and the redial
    client.finalize(True)


def test_server_sever_recovers_via_reconnect(monkeypatch):
    """An injected 'error' at server.recv severs the connection (no err
    reply, like a real broken socket): the worker sees EOF, reconnects,
    resends, and the call succeeds."""
    client = _inprocess_cluster(monkeypatch)
    client.init(1, np.full(4, 5.0, np.float32))
    faultinject.install({"rules": [
        {"seam": "server.recv", "kind": "pull", "nth": 1,
         "action": "error"}]})
    out = client.pull(1, 4)
    faultinject.install(None)
    np.testing.assert_array_equal(out, np.full(4, 5.0, np.float32))
    client.finalize(True)


def test_lost_reply_resend_is_exactly_once(monkeypatch):
    """Drop the REPLY to a push (server already applied it): the worker
    times out and resends, and the server's (rank, incarnation, seq)
    watermark dedupes the retry — the gradient lands exactly once."""
    client = _inprocess_cluster(monkeypatch)
    client.init(1, np.zeros(4, np.float32))
    faultinject.install({"rules": [
        {"seam": "worker.recv", "kind": "push", "nth": 1,
         "action": "drop"}]})
    client.push(1, np.ones(4, np.float32))
    faultinject.install(None)
    np.testing.assert_array_equal(client.pull(1, 4),
                                  np.ones(4, np.float32))
    client.finalize(True)


def test_latest_checkpoint_five_digit_epoch(tmp_path):
    from mxnet_tpu.model import latest_checkpoint
    prefix = str(tmp_path / "run")
    for epoch in (9999, 10001):
        with open("%s-%04d.params.npz" % (prefix, epoch), "wb"):
            pass
    assert latest_checkpoint(prefix) == 10001


def test_circuit_breaker_fails_fast_on_dead_endpoint(monkeypatch):
    client = _inprocess_cluster(
        monkeypatch,
        MXNET_KVSTORE_RPC_TIMEOUT="0.15",
        MXNET_KVSTORE_RPC_RETRIES="1",
        MXNET_KVSTORE_RPC_CB_FAILS="2",
        MXNET_KVSTORE_RPC_CB_RESET="60",
    )
    client.init(1, np.zeros(4, np.float32))
    faultinject.install({"rules": [
        {"seam": "worker.send", "nth": 1, "count": "inf",
         "action": "drop"}]})
    with pytest.raises(MXNetError, match="failed after 2 attempts"):
        client.push(1, np.ones(4, np.float32))
    # breaker is now open: the next call must fail fast, not re-eat the
    # full timeout * retries cycle
    t0 = time.monotonic()
    with pytest.raises(MXNetError, match="circuit breaker open"):
        client.push(1, np.ones(4, np.float32))
    assert time.monotonic() - t0 < 0.1
    # clean shutdown: plan off, fresh breaker so stop reaches the server
    faultinject.install(None)
    client.breakers[0] = ksd.CircuitBreaker()
    client.finalize(True)


def _dist_kv_cluster(monkeypatch, **env):
    """Full KVStoreDist (bucketing + pipeline + compression-capable
    data plane) over an in-process scheduler+server — the layer above
    the bare WorkerClient the older cluster helper returns."""
    from mxnet_tpu import kvstore as kvs
    base = {
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(_free_port()),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "MXNET_KVSTORE_HEARTBEAT_INTERVAL": "0.2",
        "MXNET_KVSTORE_RPC_TIMEOUT": "0.3",
        "MXNET_KVSTORE_RPC_RETRIES": "6",
        "MXNET_KVSTORE_RPC_BACKOFF": "0.02",
        "MXNET_KVSTORE_RPC_BACKOFF_CAP": "0.1",
        "MXNET_KVSTORE_BUCKET_BYTES": "2048",  # several buckets in play
    }
    base.update(env)
    for k, v in base.items():
        monkeypatch.setenv(k, v)
    monkeypatch.delenv("DMLC_PS_RECOVERY_RANK", raising=False)
    sched = ksd.Scheduler()
    threading.Thread(target=sched.run, daemon=True).start()
    server = ksd.Server()
    threading.Thread(target=server.run, daemon=True).start()
    return kvs.create("dist_async")


_PLANE_SIZES = [64, 64, 96, 64, 2048, 64, 64, 512, 64, 64]


def _run_data_plane_schedule(kv, compress, steps=4):
    """A deterministic multi-step push/pull schedule over a mixed key
    census; returns the final pulled values."""
    if compress:
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    keys = list(range(len(_PLANE_SIZES)))
    for k, n in zip(keys, _PLANE_SIZES):
        kv.init(k, mx.nd.zeros((n,)))
    outs = [mx.nd.zeros((n,)) for n in _PLANE_SIZES]
    for step in range(steps):
        grads = [mx.nd.ones((n,)) * (0.25 + 0.5 * step)
                 for n in _PLANE_SIZES]
        kv.push(keys, grads, priority=[-k for k in keys])
        kv.pull(keys, outs, priority=[-k for k in keys])
        kv.flush()
    return [o.asnumpy().copy() for o in outs]


def test_seeded_drop_retry_with_compression_and_bucketing(monkeypatch):
    """The hard correctness core of the async data plane: seeded drops
    force deadline->retry->dedup while compressed, bucket-coalesced,
    pipelined traffic is in flight — the final values must byte-match
    the same schedule's no-fault run (exactly-once under the pipeline,
    deterministic error-feedback stream)."""
    kv = _dist_kv_cluster(monkeypatch)
    clean = _run_data_plane_schedule(kv, compress=True)
    kv.close()

    kv2 = _dist_kv_cluster(monkeypatch)
    faultinject.install({"seed": 11, "rules": [
        # two lost push replies (server applied them: resend must dedup)
        {"seam": "worker.recv", "kind": "push", "nth": 1, "count": 2,
         "action": "drop"},
        {"seam": "worker.recv", "kind": "push_multi", "nth": 1,
         "action": "drop"},
        # one dropped pull request (deadline fires, retry re-asks)
        {"seam": "worker.send", "kind": "pull_multi", "nth": 2,
         "action": "drop"},
    ]})
    try:
        faulted = _run_data_plane_schedule(kv2, compress=True)
    finally:
        faultinject.install(None)
    kv2.close()
    for a, b in zip(clean, faulted):
        np.testing.assert_array_equal(a, b)


def test_pipeline_profiler_spans(monkeypatch, tmp_path):
    """The data plane is observable: wire batches show as
    kvstore_push/kvstore_pull spans and each submit->flush window as
    one comm_overlap span."""
    from mxnet_tpu import profiler
    kv = _dist_kv_cluster(monkeypatch)
    profiler.profiler_set_config(filename=str(tmp_path / "trace.json"))
    profiler.profiler_set_state("run")
    try:
        _run_data_plane_schedule(kv, compress=False, steps=2)
    finally:
        profiler.profiler_set_state("stop")
    kv.close()
    cats = {r[4] for r in profiler._state["profiler"].records}
    assert {"kvstore_push", "kvstore_pull", "comm_overlap"} <= cats, cats


def test_wire_bytes_2bit_at_most_eighth_of_fp32(monkeypatch):
    """Exact bytes-on-wire accounting on the same schedule: compressed
    gradient pushes must cost at most 1/8 of the fp32 payload (2 bits
    vs 32 per element leaves 4x headroom for headers) — the dist-smoke
    CI gate for the codec's size claim."""
    kv = _dist_kv_cluster(monkeypatch)
    _run_data_plane_schedule(kv, compress=False)
    fp32 = kv.wire_stats()
    kv.close()
    kv2 = _dist_kv_cluster(monkeypatch)
    _run_data_plane_schedule(kv2, compress=True)
    two_bit = kv2.wire_stats()
    kv2.close()
    assert fp32["push_bytes"] == sum(4 * n for n in _PLANE_SIZES) * 4
    assert two_bit["push_bytes"] * 8 <= fp32["push_bytes"], (two_bit,
                                                             fp32)
    # pulls (weights) stay lossless in both runs
    assert two_bit["pull_bytes"] == fp32["pull_bytes"]
    # and bucketing actually coalesced: far fewer push RPCs than
    # steps x keys
    assert two_bit["push_rpcs"] < 4 * len(_PLANE_SIZES)


def test_bucketed_compressed_snapshot_restore_roundtrip(monkeypatch,
                                                        tmp_path):
    """Server snapshots are per-key and therefore bucket-layout
    independent: a snapshot taken under compressed+bucketed traffic
    restores into a fresh server byte-identically (the restart
    compatibility contract of the deterministic bucket plan)."""
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("MXNET_KVSTORE_SNAPSHOT_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_KVSTORE_SNAPSHOT_INTERVAL", "5")
    from mxnet_tpu import kvstore_codec as codec
    s = ksd.Server()
    try:
        s._reg.rank = 0
        conn = _FakeConn()
        s._serve_one(("init", (3, 0), np.zeros(64, np.float32)), conn)
        s._serve_one(("init", (4, 0), np.zeros(64, np.float32)), conn)
        cg = codec.GradientCompression(
            {"type": "2bit", "threshold": 0.5}).compress(
                3, np.ones(64, np.float32))
        s._serve_one(("push_multi",
                      [((3, 0), cg.wire(), 1),
                       ((4, 0), np.full(64, 2.0, np.float32), 1)],
                      0, "inc-a"), conn)
        assert conn.sent[-1] == ("ok",)
        np.testing.assert_array_equal(s.store[(3, 0)],
                                      np.full(64, 0.5, np.float32))
        assert s.save_snapshot()
        t = ksd.Server()
        try:
            t._reg.rank = 0
            assert t.restore_snapshot()
            for key in ((3, 0), (4, 0)):
                np.testing.assert_array_equal(t.store[key], s.store[key])
            # dedup watermarks restored: the same (rank, inc, seq)
            # resend after recovery must not double-apply
            t._serve_one(("push_multi", [((3, 0), cg.wire(), 1)],
                          0, "inc-a"), conn)
            np.testing.assert_array_equal(t.store[(3, 0)],
                                          np.full(64, 0.5, np.float32))
        finally:
            t.listener.close()
    finally:
        s.listener.close()


def test_faultinject_inactive_without_env(monkeypatch):
    monkeypatch.delenv("MXNET_FAULT_INJECT", raising=False)
    faultinject.install(None)
    assert not faultinject.active()
    assert faultinject.seed() is None
    assert faultinject.hook("worker.send", kind="push") is None


# ---------------------------------------------------------------------------
# End-to-end: seeded server death mid-push + snapshot recovery
# ---------------------------------------------------------------------------
def _run_recovery_job(tmp_path, fault, compress=False):
    """One scheduler+server+worker job of dist_fault_recovery.py; in
    fault mode the server dies on its 4th push (seeded schedule) and is
    relaunched under DMLC_PS_RECOVERY_RANK=0.  ``compress`` runs the
    same scenario over the compressed+bucketed+pipelined data plane.
    Returns the FINAL line."""
    script = os.path.join(REPO, "tests", "dist_fault_recovery.py")
    snapdir = tmp_path / ("snap-fault" if fault else "snap-clean")
    snapdir.mkdir()
    base = dict(os.environ)
    base.pop("MXNET_FAULT_INJECT", None)
    base.pop("DMLC_PS_RECOVERY_RANK", None)
    base.update({
        "JAX_PLATFORMS": "cpu",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(_free_port()),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        "MXNET_KVSTORE_HEARTBEAT_INTERVAL": "0.2",
        "MXNET_KVSTORE_BARRIER_TIMEOUT": "60",
    })
    if compress:
        base["TEST_KVSTORE_GRAD_COMPRESS"] = "1"
        # the 6-element test key must negotiate compression
        base["MXNET_KVSTORE_COMPRESS_LOWER_BOUND"] = "4"
    server_env = dict(base, MXNET_KVSTORE_SNAPSHOT_DIR=str(snapdir),
                      MXNET_KVSTORE_SNAPSHOT_INTERVAL="0")
    if fault:
        server_env["MXNET_FAULT_INJECT"] = json.dumps({
            "seed": 7,
            "rules": [{"seam": "server.recv", "kind": "push", "nth": 4,
                       "action": "die"}]})
    worker_env = dict(base,
                      MXNET_KVSTORE_RPC_TIMEOUT="1",
                      MXNET_KVSTORE_RPC_RETRIES="15",
                      MXNET_KVSTORE_RPC_BACKOFF="0.05",
                      MXNET_KVSTORE_RPC_BACKOFF_CAP="0.5",
                      MXNET_KVSTORE_RPC_CB_FAILS="1000")

    def spawn(role, env, **kw):
        e = dict(env)
        e["DMLC_ROLE"] = role
        return subprocess.Popen([sys.executable, script], env=e, **kw)

    procs = []
    try:
        procs.append(spawn("scheduler", base))
        server = spawn("server", server_env)
        procs.append(server)
        worker = spawn("worker", worker_env, stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT, text=True)
        procs.append(worker)
        if fault:
            # the seeded schedule kills the server on push #4 (exit 137,
            # as if SIGKILLed) with exactly 3 pushes snapshotted
            assert server.wait(timeout=120) == 137, \
                "server should have died on the scheduled push"
            recovered_env = dict(server_env, DMLC_PS_RECOVERY_RANK="0")
            recovered_env.pop("MXNET_FAULT_INJECT")
            procs.append(spawn("server", recovered_env))
        out, _ = worker.communicate(timeout=180)
        assert worker.returncode == 0, out[-2000:]
        final = [ln for ln in out.splitlines() if ln.startswith("FINAL")]
        assert final, out[-2000:]
        return final[0]
    finally:
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    p.kill()


def test_seeded_fault_recovery_matches_no_fault_run(tmp_path):
    clean = _run_recovery_job(tmp_path, fault=False)
    faulted = _run_recovery_job(tmp_path, fault=True)
    # worker pushed 10 gradients of ones; the server died mid-push #4 and
    # recovered from its snapshot — nothing lost, nothing double-applied
    assert faulted == clean
    assert clean == "FINAL " + " ".join(["10.000000"] * 6)


def test_seeded_fault_recovery_compressed_bucketed(tmp_path):
    """The same server-death-mid-push scenario with the fast data plane
    on (2-bit compression + buckets + async pipeline): the recovered
    run's final values still byte-match the no-fault run — retry/dedup
    and snapshot restore are payload-agnostic, and the worker-side
    error-feedback stream is deterministic.  Each push of ones delivers
    exactly +threshold (0.5), so the closed form is N_PUSH * 0.5."""
    clean = _run_recovery_job(tmp_path, fault=False, compress=True)
    faulted = _run_recovery_job(tmp_path, fault=True, compress=True)
    assert faulted == clean
    assert clean == "FINAL " + " ".join(["5.000000"] * 6)
