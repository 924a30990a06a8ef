"""Serving-plane tests: AOT program store (bucket pad/unpad exactness,
LRU eviction/recompile stats), continuous batching scheduler (flush
ordering under the seeded loadgen, timeout/cancel, multi-model
isolation, graceful-shutdown drain), serving Predictor fast path,
device-resident from_checkpoint, and the to_serving artifact roundtrip
(docs/architecture/serving.md)."""
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (ModelRegistry, OpenLoopSchedule,
                               ProgramStore, ServeClosed, ServeTimeout,
                               ServingEngine, bucket_for, bucket_edges,
                               run_loadgen)

BUCKETS = (1, 2, 4, 8)


def _conv_model(seed=0, num_hidden=3):
    """Tiny deterministic convnet (conv+BN-free so fp32 is bit-stable)."""
    rs = np.random.RandomState(seed)
    data = mx.sym.Variable("data")
    conv = mx.sym.Convolution(data, kernel=(3, 3), num_filter=4,
                              pad=(1, 1), name="conv1")
    act = mx.sym.Activation(conv, act_type="relu")
    fc = mx.sym.FullyConnected(mx.sym.Flatten(act), num_hidden=num_hidden,
                               name="fc1")
    net = mx.sym.SoftmaxOutput(fc, name="softmax")
    shapes, _, _ = net.infer_shape(data=(2, 3, 8, 8), softmax_label=(2,))
    args = {}
    for name, shape in zip(net.list_arguments(), shapes):
        if name not in ("data", "softmax_label"):
            args[name] = rs.uniform(-0.2, 0.2, shape).astype("float32")
    return net, args


def _classic_forward(net, args, x):
    pred = mx.Predictor(net.tojson(),
                        {"arg:%s" % k: v for k, v in args.items()},
                        {"data": x.shape})
    return pred.forward(data=x)[0].asnumpy()


def _assert_matches_classic(net, args, x, got, bucket):
    """What "pad/unpad changes nothing" means for ``x``'s rows served out
    of ``bucket``: BIT-equal to the classic forward of the same padded
    batch (zero rows, as ``ProgramStore.run`` pads), so padding rows do
    not leak into live rows.  Against the classic forward of the
    UNPADDED batch bit equality holds only where the batch is a bucket:
    XLA's CPU convolution is not bit-stable across batch sizes.  Over 300
    seeded inputs of 3 rows in a bucket of 4 the outputs differ by 1-8
    ulp (2 the mode, 5 at this file's seed 5); 5-7 rows in a bucket of 8
    by none.  A padded batch is held to the 8 that was seen."""
    n = x.shape[0]
    assert got.shape[0] == n
    ref = _classic_forward(net, args, x)
    if n == bucket:
        assert np.array_equal(got, ref), "n=%d not bit-equal" % n
        return
    padded = np.zeros((bucket,) + x.shape[1:], x.dtype)
    padded[:n] = x
    assert np.array_equal(got, _classic_forward(net, args, padded)[:n]), \
        "n=%d in bucket %d not bit-equal to the padded classic forward" \
        % (n, bucket)
    np.testing.assert_array_max_ulp(got, ref, maxulp=8)


def _mkstore(net, args, **kw):
    kw.setdefault("buckets", BUCKETS)
    return ProgramStore(net, args, {}, {"data": (1, 3, 8, 8)}, **kw)


def _mkengine(reg, **kw):
    kw.setdefault("max_delay_ms", 20.0)
    kw.setdefault("max_batch", 8)
    return ServingEngine(reg, **kw)


# ---------------------------------------------------------------------------
# bucket policy + program store
# ---------------------------------------------------------------------------
def test_bucket_edges_and_lookup():
    assert bucket_edges((8, 2, 2, 1)) == (1, 2, 8)
    assert bucket_for(1, (1, 2, 8)) == 1
    assert bucket_for(3, (1, 2, 8)) == 8
    assert bucket_for(8, (1, 2, 8)) == 8
    assert bucket_for(9, (1, 2, 8)) is None
    with pytest.raises(MXNetError):
        bucket_edges((0, 2))


def test_bucket_pad_unpad_bit_equal_fp32():
    """Bucketed outputs must be BIT-equal to the classic unbatched
    Predictor at every bucket-sized batch, and to the classic forward of
    the same padded batch in between (``_assert_matches_classic``)."""
    net, args = _conv_model()
    store = _mkstore(net, args)
    store.warmup()
    rs = np.random.RandomState(1)
    for n in (1, 2, 3, 5, 7, 8):
        x = rs.uniform(-1, 1, (n, 3, 8, 8)).astype("float32")
        outs, bucket, bm = store.run({"data": x})
        assert bucket == bucket_for(n, BUCKETS) and bm == (True,)
        _assert_matches_classic(net, args, x, np.asarray(outs[0]), bucket)


def test_store_oversize_and_bad_inputs():
    net, args = _conv_model()
    store = _mkstore(net, args)
    rs = np.random.RandomState(2)
    with pytest.raises(MXNetError):
        store.canon_inputs(
            {"data": rs.rand(9, 3, 8, 8).astype("float32")})
    with pytest.raises(MXNetError):
        store.canon_inputs({"wrong": rs.rand(1, 3, 8, 8)})
    with pytest.raises(MXNetError):
        store.canon_inputs({"data": rs.rand(1, 3, 4, 4)})
    with pytest.raises(MXNetError):
        store.canon_inputs(
            {"data": np.zeros((0, 3, 8, 8), "float32")})


def test_store_lru_eviction_and_recompile_stats():
    net, args = _conv_model()
    store = _mkstore(net, args, max_programs=2)
    rs = np.random.RandomState(3)

    def run_n(n):
        store.run({"data": rs.rand(n, 3, 8, 8).astype("float32")})

    run_n(1)   # compile b1
    run_n(2)   # compile b2
    run_n(4)   # compile b4 -> evicts b1
    st = store.stats()
    assert st["compiles"] == 3 and st["evictions"] == 1
    assert st["size"] == 2 and st["buckets_resident"] == [2, 4]
    run_n(2)   # hit
    run_n(1)   # recompile (was evicted) -> evicts b... LRU = b4? no, b2
    st = store.stats()
    assert st["compiles"] == 4 and st["evictions"] == 2
    assert st["hits"] >= 1
    assert st["max_programs"] == 2


def test_store_key_carries_pallas_fingerprint(monkeypatch):
    """The serving program LRU outlives an MXNET_PALLAS flip like the
    cached-op and SPMD caches do: its key must carry the dispatch
    fingerprint so the escape hatch recompiles instead of serving the
    stale lowering."""
    net, args = _conv_model()
    store = _mkstore(net, args)
    monkeypatch.setenv("MXNET_PALLAS", "1")
    k1 = store._key(2)
    monkeypatch.setenv("MXNET_PALLAS", "0")
    k0 = store._key(2)
    assert k1 != k0
    monkeypatch.setenv("MXNET_PALLAS", "1")
    assert store._key(2) == k1


def test_store_rejects_non_batch_major_output():
    """A whole-batch reduction output (no leading batch axis) cannot be
    served through buckets: pad rows and batch-mates would leak into
    every request's result.  Rejected at load, not mis-served."""
    rs = np.random.RandomState(12)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc1")
    net = mx.sym.sum(fc)   # scalar output over the whole batch
    shapes, _, _ = net.infer_shape(data=(2, 8))
    args = {n: rs.rand(*s).astype("float32")
            for n, s in zip(net.list_arguments(), shapes) if n != "data"}
    with pytest.raises(MXNetError, match="not batch-major"):
        ProgramStore(net, args, {}, {"data": (1, 8)}, buckets=(1, 2))


def test_store_device_pinning():
    """device= pins weights and compiled programs (the serving
    Predictor passes its ctx through, honoring dev_id)."""
    import jax
    devs = jax.devices("cpu")
    if len(devs) < 2:
        pytest.skip("needs the multi-device virtual CPU mesh")
    net, args = _conv_model()
    store = _mkstore(net, args, device=devs[1])
    assert all(devs[1] in p.devices() for p in store._params.values())
    outs, _, _ = store.run(
        {"data": np.zeros((2, 3, 8, 8), "float32")})
    assert devs[1] in outs[0].devices()
    sp = mx.Predictor(net.tojson(),
                      {"arg:%s" % k: v for k, v in args.items()},
                      {"data": (1, 3, 8, 8)}, dev_id=1, serving=True,
                      buckets=(1, 2))
    out = sp.forward(data=np.zeros((1, 3, 8, 8), "float32"))[0]
    assert devs[1] in out._data.devices()


def test_registry_unregisters_on_warmup_failure(monkeypatch):
    net, args = _conv_model()
    reg = ModelRegistry()
    monkeypatch.setattr(ProgramStore, "warmup",
                        lambda self, execute=True: (_ for _ in ()).throw(
                            MXNetError("compile boom")))
    with pytest.raises(MXNetError, match="compile boom"):
        reg.add_model("m", net, args, {},
                      input_shapes={"data": (1, 3, 8, 8)},
                      buckets=BUCKETS)
    assert "m" not in reg   # broken model is not left serveable
    monkeypatch.undo()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=(1,))   # name is free for the corrected retry
    assert "m" in reg


def test_warmup_compiles_all_buckets():
    net, args = _conv_model()
    store = _mkstore(net, args)
    times = store.warmup()
    assert sorted(times) == list(BUCKETS)
    st = store.stats()
    assert st["compiles"] == len(BUCKETS)
    assert st["buckets_resident"] == list(BUCKETS)
    # warmed: serving a request is all hits
    store.run({"data": np.zeros((3, 3, 8, 8), "float32")})
    assert store.stats()["compiles"] == len(BUCKETS)


def test_store_bf16_weight_cast():
    net, args = _conv_model()
    store = _mkstore(net, args, compute_dtype="bfloat16")
    import jax.numpy as jnp
    assert all(p.dtype == jnp.bfloat16 for p in store._params.values())
    x = np.random.RandomState(4).uniform(
        -1, 1, (2, 3, 8, 8)).astype("float32")
    outs, _, _ = store.run({"data": x})
    got = np.asarray(outs[0])
    assert got.dtype == np.float32          # outputs come back fp32
    ref = _classic_forward(net, args, x)    # fp32 master reference
    np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)
    # the serving cast must not have touched the caller's fp32 params
    assert all(v.dtype == np.float32 for v in args.values())


# ---------------------------------------------------------------------------
# serving Predictor fast path + device-resident from_checkpoint
# ---------------------------------------------------------------------------
def test_serving_predictor_matches_classic_bit_equal():
    net, args = _conv_model()
    params = {"arg:%s" % k: v for k, v in args.items()}
    sp = mx.Predictor(net.tojson(), params, {"data": (1, 3, 8, 8)},
                      serving=True, buckets=BUCKETS)
    rs = np.random.RandomState(5)
    for n in (1, 3, 8):
        x = rs.uniform(-1, 1, (n, 3, 8, 8)).astype("float32")
        sp.forward(data=x)
        got = sp.get_output(0)
        assert sp.get_output_shape(0) == got.shape
        _assert_matches_classic(net, args, x, got, bucket_for(n, BUCKETS))
    st = sp.serving_stats()
    assert st["compiles"] == len(BUCKETS)  # warmup-at-load, then hits
    assert st["hits"] >= 3


def test_from_checkpoint_no_host_roundtrip(tmp_path, monkeypatch):
    """Satellite pin: loading a checkpoint into a Predictor must not
    bounce every param through .asnumpy() (host) and back."""
    net, args = _conv_model()
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 1, net,
                             {k: mx.nd.array(v) for k, v in args.items()},
                             {})
    calls = []
    real = mx.nd.NDArray.asnumpy

    def spy(self):
        calls.append(1)
        return real(self)

    monkeypatch.setattr(mx.nd.NDArray, "asnumpy", spy)
    pred = mx.Predictor.from_checkpoint(prefix, 1, {"data": (2, 3, 8, 8)})
    assert not calls, "from_checkpoint round-tripped params via asnumpy"
    monkeypatch.undo()
    x = np.random.RandomState(6).uniform(
        -1, 1, (2, 3, 8, 8)).astype("float32")
    assert np.array_equal(pred.forward(data=x)[0].asnumpy(),
                          _classic_forward(net, args, x))


def test_from_checkpoint_serving_kwargs(tmp_path):
    net, args = _conv_model()
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 1, net,
                             {k: mx.nd.array(v) for k, v in args.items()},
                             {})
    pred = mx.Predictor.from_checkpoint(
        prefix, 1, {"data": (1, 3, 8, 8)}, serving=True, buckets=(1, 4))
    x = np.random.RandomState(7).uniform(
        -1, 1, (3, 3, 8, 8)).astype("float32")
    _assert_matches_classic(net, args, x,
                            pred.forward(data=x)[0].asnumpy(), 4)
    x = x[:1]                               # a bucket-sized batch
    _assert_matches_classic(net, args, x,
                            pred.forward(data=x)[0].asnumpy(), 1)


# ---------------------------------------------------------------------------
# continuous batching engine
# ---------------------------------------------------------------------------
def test_engine_results_match_direct_and_batches_form():
    net, args = _conv_model()
    reg = ModelRegistry()
    store = reg.add_model("m", net, args, {},
                          input_shapes={"data": (1, 3, 8, 8)},
                          buckets=BUCKETS)
    eng = _mkengine(reg)
    try:
        rs = np.random.RandomState(8)
        xs = [rs.uniform(-1, 1, (1, 3, 8, 8)).astype("float32")
              for _ in range(6)]
        futs = [eng.submit("m", data=x) for x in xs]
        got = [np.asarray(f.result(30)[0]) for f in futs]
        # bit-equal to the same rows run through the bucketed program
        # directly (the engine adds batching, not arithmetic)...
        ref_outs, _, _ = store.run({"data": np.concatenate(xs)})
        ref = np.asarray(ref_outs[0])
        for i, (x, g) in enumerate(zip(xs, got)):
            assert g.shape == (1, 3)
            assert np.array_equal(g, ref[i:i + 1])
            # ...and float-close to the per-request classic Predictor
            # (XLA CPU conv is not bit-stable across BATCH-1 vs batch-8
            # program variants; row math is the same to 1 ulp)
            np.testing.assert_allclose(
                g, _classic_forward(net, args, x), rtol=1e-6, atol=1e-7)
        st = eng.stats()
        assert st["requests"] == 6 and st["rows"] == 6
        assert st["batches"] < 6  # continuous batching actually batched
    finally:
        eng.close()


def test_engine_flush_ordering_under_seeded_loadgen():
    """Per-model FIFO: under a seeded arrival schedule the batches must
    partition the submit order (no request overtakes an earlier one of
    the same model), and every batch respects max_batch."""
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    eng = _mkengine(reg, max_delay_ms=5.0, max_batch=4)
    batches = []
    eng._dispatch_hook = lambda model, live: batches.append(
        [id(r.future) for r in live])
    try:
        sched = OpenLoopSchedule(seed=3, n_requests=20, qps=2000.0)
        x = np.zeros((1, 3, 8, 8), "float32")
        order = []

        def submit(i, n):
            f = eng.submit("m", data=x)
            order.append(id(f))
            return f

        res = run_loadgen(submit, sched, fetch=True)
        assert res["ok"] == 20
        flat = [fid for b in batches for fid in b]
        assert flat == order, "batch formation reordered same-model FIFO"
        assert max(len(b) for b in batches) <= 4
        assert len(batches) < 20  # actually coalesced
        # the counters a batch leaves: pad rows are bucket - rows of
        # every dispatch, the high-water mark is the largest batch
        st = eng.stats()
        assert st["padded_rows"] == sum(
            bucket_for(len(b), BUCKETS) - len(b) for b in batches)
        assert st["max_rows_in_batch"] == max(len(b) for b in batches)
    finally:
        eng.close()


def test_engine_no_overtake_past_parked_oversize():
    """A same-model request parked because it didn't fit the forming
    batch must not be overtaken by a YOUNGER same-model request that
    does fit (batches partition per-model submit order even with mixed
    row counts routed through the pending deque)."""
    import threading
    net_x, args_x = _conv_model(seed=0)
    net_y, args_y = _conv_model(seed=1)
    reg = ModelRegistry()
    for name, net, args in (("x", net_x, args_x), ("y", net_y, args_y)):
        reg.add_model(name, net, args, {},
                      input_shapes={"data": (1, 3, 8, 8)}, buckets=BUCKETS)
    eng = _mkengine(reg, max_delay_ms=20.0, max_batch=8)
    release = threading.Event()
    stalled = threading.Event()
    batches = []

    def hook(model, live):
        batches.append((model, [id(r.future) for r in live]))
        stalled.set()
        release.wait(10)

    eng._dispatch_hook = hook
    try:
        def x(n):
            rs = np.random.RandomState(n)
            return rs.uniform(-1, 1, (n, 3, 8, 8)).astype("float32")

        # head X stalls in its dispatch hook...
        f_x1 = eng.submit("x", data=x(1))
        assert stalled.wait(10)
        # ...so these queue up: X2 (whose batch-forming cycle parks the
        # Y's into pending), then Y a(4) / big(6) / c(2).  With cap 8,
        # Y-big doesn't fit behind Y-a — Y-c must NOT slip past it.
        f_x2 = eng.submit("x", data=x(1))
        y_subs = [eng.submit("y", data=x(n)) for n in (4, 6, 2)]
        release.set()
        for f in [f_x1, f_x2] + y_subs:
            f.result(30)
        y_order = [fid for model, ids in batches if model == "y"
                   for fid in ids]
        assert y_order == [id(f) for f in y_subs], \
            "younger same-model request overtook a parked one"
    finally:
        release.set()
        eng.close()


def test_engine_timeout_zero_expires():
    """timeout=0 means 'already due', not 'no deadline'."""
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    eng = _mkengine(reg, max_delay_ms=0.0, max_batch=1)
    eng._dispatch_hook = lambda model, live: time.sleep(0.05)
    try:
        x = np.zeros((1, 3, 8, 8), "float32")
        blocker = eng.submit("m", data=x)   # stalls in the hook
        time.sleep(0.02)
        doomed = eng.submit("m", timeout=0, data=x)
        with pytest.raises(ServeTimeout):
            doomed.result(30)
        blocker.result(30)
    finally:
        eng.close()


def test_engine_timeout_and_cancel():
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    # max_batch=1: each dispatch carries one request, so the hook's
    # stall holds later requests in the queue past their deadlines
    eng = _mkengine(reg, max_delay_ms=0.0, max_batch=1)
    eng._dispatch_hook = lambda model, live: time.sleep(0.15)
    try:
        x = np.zeros((1, 3, 8, 8), "float32")
        blocker = eng.submit("m", data=x)
        time.sleep(0.02)  # blocker reached its (stalled) dispatch
        timed = eng.submit("m", timeout=0.01, data=x)
        cancelled = eng.submit("m", data=x)
        assert cancelled.cancel()
        with pytest.raises(ServeTimeout):
            timed.result(30)
        assert blocker.result(30)[0].shape == (1, 3)
        assert cancelled.cancelled()
        # allow the engine to tally the skipped request
        deadline = time.time() + 5
        while eng.stats()["cancelled"] < 1 and time.time() < deadline:
            time.sleep(0.01)
        st = eng.stats()
        assert st["timeouts"] == 1 and st["cancelled"] == 1
    finally:
        eng.close()


def test_engine_multi_model_isolation():
    net_a, args_a = _conv_model(seed=0)
    net_b, args_b = _conv_model(seed=42, num_hidden=5)
    reg = ModelRegistry()
    reg.add_model("a", net_a, args_a, {},
                  input_shapes={"data": (1, 3, 8, 8)}, buckets=BUCKETS)
    reg.add_model("b", net_b, args_b, {},
                  input_shapes={"data": (1, 3, 8, 8)}, buckets=BUCKETS)
    assert sorted(reg.models()) == ["a", "b"]
    eng = _mkengine(reg)
    batch_models = []
    eng._dispatch_hook = lambda model, live: batch_models.append(
        (model, len(live)))
    try:
        rs = np.random.RandomState(9)
        subs = []
        for i in range(10):
            name = "a" if i % 2 == 0 else "b"
            x = rs.uniform(-1, 1, (1, 3, 8, 8)).astype("float32")
            subs.append((name, x, eng.submit(name, data=x)))
        for name, x, f in subs:
            got = np.asarray(f.result(30)[0])
            net, args = (net_a, args_a) if name == "a" else (net_b, args_b)
            np.testing.assert_allclose(
                got, _classic_forward(net, args, x), rtol=1e-6,
                atol=1e-7,
                err_msg="cross-tenant contamination on %r" % name)
        assert all(m in ("a", "b") for m, _ in batch_models)
        st = reg.stats()
        assert set(st) == {"a", "b"}
    finally:
        eng.close()
    with pytest.raises(MXNetError):
        eng.submit("unknown", data=np.zeros((1, 3, 8, 8), "float32"))


def test_engine_mixed_sizes_slices_correctly():
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    eng = _mkengine(reg, max_delay_ms=30.0, max_batch=8)
    try:
        rs = np.random.RandomState(10)
        xs = [rs.uniform(-1, 1, (n, 3, 8, 8)).astype("float32")
              for n in (2, 1, 3)]
        futs = [eng.submit("m", data=x) for x in xs]
        for x, f in zip(xs, futs):
            got = np.asarray(f.result(30)[0])
            assert got.shape == (x.shape[0], 3)
            np.testing.assert_allclose(
                got, _classic_forward(net, args, x), rtol=1e-6,
                atol=1e-7)
    finally:
        eng.close()


def test_engine_graceful_shutdown_drains():
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    eng = _mkengine(reg, max_delay_ms=50.0, max_batch=2)
    eng._dispatch_hook = lambda model, live: time.sleep(0.05)
    x = np.zeros((1, 3, 8, 8), "float32")
    futs = [eng.submit("m", data=x) for _ in range(7)]
    eng.close()  # drain=True: everything already submitted completes
    for f in futs:
        assert np.asarray(f.result(0)[0]).shape == (1, 3)
    with pytest.raises(ServeClosed):
        eng.submit("m", data=x)
    eng.close()  # idempotent


def test_engine_close_without_drain_fails_queued():
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    eng = _mkengine(reg, max_delay_ms=0.0, max_batch=1)
    eng._dispatch_hook = lambda model, live: time.sleep(0.1)
    x = np.zeros((1, 3, 8, 8), "float32")
    futs = [eng.submit("m", data=x) for _ in range(5)]
    eng.close(drain=False)
    outcomes = {"ok": 0, "closed": 0}
    for f in futs:
        try:
            f.result(0)
            outcomes["ok"] += 1
        except ServeClosed:
            outcomes["closed"] += 1
    assert outcomes["closed"] >= 1  # queued work failed fast
    assert outcomes["ok"] + outcomes["closed"] == 5


def test_engine_serve_spans_in_profiler_trace(tmp_path):
    """Runtime face of the span-coverage manifest entry: one scheduler
    cycle must emit serve_wait / serve_batch / serve_compute."""
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    trace = str(tmp_path / "serve_trace.json")
    mx.profiler.profiler_set_config(filename=trace)
    mx.profiler.profiler_set_state("run")
    eng = _mkengine(reg)
    try:
        eng.submit("m", data=np.zeros((1, 3, 8, 8),
                                      "float32")).result(30)
    finally:
        eng.close()
        mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    with open(trace) as f:
        names = {ev["name"] for ev in json.load(f)["traceEvents"]
                 if ev.get("cat") == "step_phase"}
    assert set(mx.profiler.SERVE_PHASES) <= names


def test_model_registry_add_remove():
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=(1, 2), warmup=False)
    assert "m" in reg and len(reg) == 1
    with pytest.raises(MXNetError):
        reg.add_model("m", net, args, {},
                      input_shapes={"data": (1, 3, 8, 8)})
    reg.remove_model("m")
    assert "m" not in reg
    with pytest.raises(MXNetError):
        reg.store("m")
    with pytest.raises(MXNetError):
        reg.remove_model("m")


# ---------------------------------------------------------------------------
# deploy.to_serving artifact + loadgen determinism
# ---------------------------------------------------------------------------
def test_to_serving_artifact_roundtrip(tmp_path):
    net, args = _conv_model()
    from mxnet_tpu.deploy import to_serving
    path = str(tmp_path / "model.mxsrv")
    to_serving(net, args, {}, {"data": (1, 3, 8, 8)}, path,
               bucket_edges=(1, 2, 4), compute_dtype=None)
    reg = ModelRegistry()
    store = reg.load_artifact("m", path)
    assert store.edges == (1, 2, 4)
    rs = np.random.RandomState(11)
    x = rs.uniform(-1, 1, (3, 3, 8, 8)).astype("float32")
    outs, bucket, _ = store.run({"data": x})
    assert bucket == 4
    _assert_matches_classic(net, args, x, np.asarray(outs[0]), bucket)
    x = rs.uniform(-1, 1, (4, 3, 8, 8)).astype("float32")
    outs, bucket, _ = store.run({"data": x})
    _assert_matches_classic(net, args, x, np.asarray(outs[0]), bucket)


def test_to_serving_checkpoint_and_overrides(tmp_path):
    net, args = _conv_model()
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 2, net,
                             {k: mx.nd.array(v) for k, v in args.items()},
                             {})
    from mxnet_tpu.deploy import read_serving_artifact, \
        to_serving_checkpoint
    path = str(tmp_path / "ckpt.mxsrv")
    to_serving_checkpoint(prefix, 2, {"data": (1, 3, 8, 8)}, path,
                          bucket_edges=(1, 8))
    sym, arg_params, aux_params, meta = read_serving_artifact(path)
    assert meta["bucket_edges"] == [1, 8]
    assert meta["output_names"] == net.list_outputs()
    assert set(arg_params) == set(args)
    reg = ModelRegistry()
    store = reg.load_artifact("m", path, buckets=(2,))  # override wins
    assert store.edges == (2,)


def test_loadgen_schedule_deterministic():
    a = OpenLoopSchedule(seed=5, n_requests=50, qps=500.0, sizes=(1, 2, 4),
                         size_weights=(0.5, 0.25, 0.25))
    b = OpenLoopSchedule(seed=5, n_requests=50, qps=500.0, sizes=(1, 2, 4),
                         size_weights=(0.5, 0.25, 0.25))
    c = OpenLoopSchedule(seed=6, n_requests=50, qps=500.0)
    assert np.array_equal(a.arrivals, b.arrivals)
    assert np.array_equal(a.sizes, b.sizes)
    assert not np.array_equal(a.arrivals, c.arrivals)
    assert a.arrivals[-1] > 0 and (np.diff(a.arrivals) >= 0).all()


def test_loadgen_summary_fields():
    net, args = _conv_model()
    reg = ModelRegistry()
    reg.add_model("m", net, args, {}, input_shapes={"data": (1, 3, 8, 8)},
                  buckets=BUCKETS)
    eng = _mkengine(reg, max_delay_ms=2.0)
    try:
        sched = OpenLoopSchedule(seed=7, n_requests=12, qps=600.0)
        x = np.zeros((1, 3, 8, 8), "float32")
        res = run_loadgen(lambda i, n: eng.submit("m", data=x), sched)
    finally:
        eng.close()
    assert res["ok"] == 12 and res["errors"] == 0
    assert res["p50_ms"] > 0 and res["p99_ms"] >= res["p50_ms"]
    assert res["qps_achieved"] > 0 and res["seed"] == 7
