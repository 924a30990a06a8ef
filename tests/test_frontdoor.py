"""Serving front-door tests: HTTP endpoint (JSON + npz wire formats,
deadline propagation, structured status mapping), shared-nothing
multi-replica failover (seeded kill at the serve.dispatch faultinject
seam, breaker-gated balancing, probe-driven recovery), hot weight swap
under traffic (exact old-xor-new partition, version counter), overload
shedding (ServeOverloaded / HTTP 429), the shared retry-policy module,
and the ServeClosed consistency pins
(docs/architecture/serving_frontdoor.md)."""
import json
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (HttpClient, HttpFrontDoor, ModelRegistry,
                               NoLiveReplicas, OpenLoopSchedule,
                               ReplicaDied, ReplicaSet, ServeClosed,
                               ServeOverloaded, ServeTimeout,
                               ServingEngine, run_loadgen)
from mxnet_tpu.test_utils import smoke_mlp

FEAT = 8


def _mlp_model(seed=0, feat=FEAT, hidden=16):
    sym = smoke_mlp(num_hidden=hidden)
    shapes, _, _ = sym.infer_shape(data=(1, feat), softmax_label=(1,))
    rs = np.random.RandomState(seed)
    args = {n: rs.uniform(-0.5, 0.5, s).astype(np.float32)
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    return sym, args


def _registry(args_override=None, buckets=(1, 2, 4), feat=FEAT):
    sym, args = _mlp_model(feat=feat)
    reg = ModelRegistry()
    reg.add_model("m", sym,
                  {k: v.copy() for k, v in
                   (args_override or args).items()},
                  {}, input_shapes={"data": (1, feat)}, buckets=buckets)
    return reg


@pytest.fixture()
def fresh_faults():
    faultinject.install(None)
    yield
    faultinject.install(None)


# ---------------------------------------------------------------------------
# satellite: shared retry module
# ---------------------------------------------------------------------------
def test_retry_primitives_are_shared_between_planes():
    """kvstore_dist re-exports the SAME objects retry.py defines — the
    PR-2 fault plane and the serving failover plane run one policy
    implementation, not drifting copies."""
    from mxnet_tpu import retry
    from mxnet_tpu import kvstore_dist as kvd
    assert kvd.CircuitBreaker is retry.CircuitBreaker
    assert kvd.RetryPolicy is retry.RetryPolicy
    assert kvd.backoff_delay is retry.backoff_delay
    # policy math is unchanged (the PR-2 unit tests pin it in depth)
    assert retry.backoff_delay(0, 0.1, 1.0) == pytest.approx(0.1)
    assert retry.backoff_delay(5, 0.1, 1.0) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# satellite: ServeClosed consistency
# ---------------------------------------------------------------------------
def test_submit_after_close_raises_serveclosed_everywhere():
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    eng.close()
    with pytest.raises(ServeClosed):
        eng.submit("m", data=np.zeros((1, FEAT), "float32"))
    # even a BAD payload gets ServeClosed after close, not a
    # validation error (the early gate)
    with pytest.raises(ServeClosed):
        eng.submit("nope", wrong="inputs")


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_crashed_dispatch_loop_fails_accepted_requests():
    """The satellite's silent-drop hole, pinned: if the dispatch loop
    exits abnormally, the request it had already taken off the queue —
    and everything still queued — resolves with ServeClosed instead of
    hanging, and later submits raise ServeClosed."""
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    # warm so the crash is the only event in flight
    eng.submit("m", data=np.zeros((1, FEAT), "float32")).result(30)

    def boom(_head):
        raise RuntimeError("injected dispatch-loop crash")

    eng._collect = boom
    fut = eng.submit("m", data=np.zeros((1, FEAT), "float32"))
    with pytest.raises(ServeClosed):
        fut.result(10)   # resolved by the exit sweep, not a hang
    deadline = time.monotonic() + 5
    while eng._thread.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not eng._thread.is_alive()
    with pytest.raises(ServeClosed):
        eng.submit("m", data=np.zeros((1, FEAT), "float32"))
    eng._completer.close()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_crashed_loop_fails_whole_collected_batch():
    """The sweep must cover EVERY request of a collected batch, not
    just the head: a crash between batch forming and resolution (here:
    the dispatch hook raising) may strand several accepted requests at
    once."""
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=200.0, max_batch=4)
    eng.submit("m", data=np.zeros((1, FEAT), "float32")).result(30)

    def boom(_m, _live):
        raise RuntimeError("injected crash with a formed batch")

    eng._dispatch_hook = boom
    futs = [eng.submit("m", data=np.zeros((1, FEAT), "float32"))
            for _ in range(3)]
    for f in futs:
        with pytest.raises(ServeClosed):
            f.result(10)
    eng._completer.close()


def test_close_no_drain_fails_forming_batch_fast():
    """close(drain=False) landing while the engine waits out a batch's
    latency budget fails the forming batch with ServeClosed instead of
    serving it."""
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=2000.0, max_batch=4)
    fut = eng.submit("m", data=np.zeros((1, FEAT), "float32"))
    # once the queue is drained the engine holds the head inside
    # _collect, waiting out the 2s latency budget
    deadline = time.monotonic() + 10
    while not eng._queue.empty() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng._queue.empty()
    tic = time.monotonic()
    eng.close(drain=False)
    assert time.monotonic() - tic < 1.5   # did not wait out the budget
    with pytest.raises(ServeClosed):
        fut.result(10)


def test_gen_engine_submit_after_close_raises_serveclosed():
    from mxnet_tpu.serving import GenerationEngine
    reg = ModelRegistry()   # no models needed: the gate fires first
    eng = GenerationEngine(reg)
    eng.close()
    with pytest.raises(ServeClosed):
        eng.submit("nope", [1, 2, 3])


# ---------------------------------------------------------------------------
# admission control / overload shedding
# ---------------------------------------------------------------------------
def test_overload_sheds_with_structured_429():
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0, max_inflight=2)
    gate = threading.Event()
    eng._dispatch_hook = lambda m, reqs: gate.wait(10)
    x = np.zeros((1, FEAT), "float32")
    f1, f2 = eng.submit("m", data=x), eng.submit("m", data=x)
    with pytest.raises(ServeOverloaded):
        eng.submit("m", data=x)
    assert eng.stats()["shed"] == 1
    gate.set()
    f1.result(30), f2.result(30)
    # budget frees as requests resolve
    eng.submit("m", data=x).result(30)
    assert eng.stats()["inflight"] == 0
    eng.close()


def test_overload_keeps_accepted_latency_flat_under_6x():
    """The collapse witness, in miniature and in counts: at 6x capacity
    with a bounded inflight budget, the front shed requests are 429s
    while every ACCEPTED request is served, none aging into a timeout
    (the collapse: every request queued, every one late).  The service
    rate is pinned by a per-batch dispatch-hook throttle so the
    capacity (and hence the overload factor) is host-independent; a
    latency is a chip run's to read, not a test's on a shared CPU."""
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0, max_batch=1,
                        max_inflight=6)
    # deterministic service time: ~4ms per dispatch, one request per
    # batch -> capacity ~250/s regardless of host speed
    eng._dispatch_hook = lambda m, reqs: time.sleep(0.004)
    x = np.zeros((1, FEAT), "float32")
    try:
        eng.submit("m", data=x).result(30)
        cap = 1.0 / 0.0045
        # baseline: a quarter of capacity, no shedding (the budget
        # holds 6: a host that stalls the loop under 100 ms sheds none)
        base = run_loadgen(
            lambda i, n: eng.submit("m", data=x),
            OpenLoopSchedule(5, 30, cap * 0.25, sizes=(1,)))
        assert base["errors"] == 0 and base["timeouts"] == 0
        shed_before = eng.stats()["shed"]
        assert shed_before == 0
        # 6x offered: the budget sheds the excess as structured 429s
        over = run_loadgen(
            lambda i, n: eng.submit("m", data=x),
            OpenLoopSchedule(5, 150, cap * 6.0, sizes=(1,)))
        shed = eng.stats()["shed"]
        inflight = eng.stats()["inflight"]
    finally:
        eng.close()
    assert shed > 0, "6x offered load never hit the inflight budget"
    assert over["ok"] > 0 and over["errors"] == 0
    assert over["timeouts"] == 0
    assert over["ok"] + over["shed"] == over["n"]
    assert over["shed"] == shed
    assert inflight == 0               # the budget came back whole


# ---------------------------------------------------------------------------
# HTTP front door
# ---------------------------------------------------------------------------
@pytest.fixture()
def door_stack():
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    door = HttpFrontDoor(eng)
    client = HttpClient(door.address, threads=3)
    yield reg, eng, door, client
    client.close()
    door.close()
    if eng.alive():
        eng.close()


def test_http_npz_predict_is_bit_exact(door_stack):
    reg, eng, door, client = door_stack
    x = np.random.RandomState(1).uniform(
        -1, 1, (2, FEAT)).astype(np.float32)
    ref = np.asarray(eng.submit("m", data=x).result(30)[0])
    out = client.submit("m", {"data": x}).result(30)
    assert np.array_equal(np.asarray(out[0]), ref)
    # JSON round-trips through python floats: exact for fp32-in-double
    outj = client.submit_json("m", {"data": x}).result(30)
    assert np.array_equal(np.asarray(outj[0], np.float32), ref)


def test_http_healthz_stats_and_errors(door_stack):
    reg, eng, door, client = door_stack
    code, body = client.healthz()
    assert code == 200 and body["status"] == "ok" and body["models"] == [
        "m"]
    st = client.stats()
    assert st["models"]["m"]["version"] == 1
    assert "inflight" in st
    # unknown model -> 400 MXNetError (not retryable)
    with pytest.raises(MXNetError) as ei:
        client.submit("ghost", {"data": np.zeros((1, FEAT),
                                                 "float32")}).result(30)
    assert not isinstance(ei.value, (ServeClosed, ServeTimeout,
                                     ServeOverloaded))


def test_http_deadline_maps_to_504(door_stack):
    reg, eng, door, client = door_stack
    gate, entered = threading.Event(), threading.Event()

    def stall(_m, _reqs):
        entered.set()
        gate.wait(5)

    eng._dispatch_hook = stall
    x = np.zeros((1, FEAT), "float32")
    blocker = client.submit("m", {"data": x})
    assert entered.wait(5)   # blocker dispatched ALONE, engine stalled
    fut = client.submit("m", {"data": x}, timeout=0.05)
    # release the engine AFTER the deadline has certainly expired: the
    # queued request then fails ServeTimeout at batch-forming -> 504
    t = threading.Timer(0.3, gate.set)
    t.daemon = True
    t.start()
    with pytest.raises(ServeTimeout):
        fut.result(30)
    blocker.result(30)


def test_http_close_maps_to_503_and_overload_to_429():
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0, max_inflight=1)
    door = HttpFrontDoor(eng)
    client = HttpClient(door.address, threads=3)
    try:
        x = np.zeros((1, FEAT), "float32")
        gate = threading.Event()
        eng._dispatch_hook = lambda m, reqs: gate.wait(10)
        blocker = client.submit("m", {"data": x})
        # wait until the budget is actually consumed
        deadline = time.monotonic() + 5
        while eng.stats()["inflight"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(ServeOverloaded):
            client.submit("m", {"data": x}).result(30)
        gate.set()
        blocker.result(30)
        eng.close()
        code, _body = client.healthz()
        assert code == 503
        with pytest.raises(ServeClosed):
            client.submit("m", {"data": x}).result(30)
    finally:
        client.close()
        door.close()


def test_http_loadgen_rides_the_shared_driver(door_stack):
    """The transport adapter contract: run_loadgen drives the HTTP
    front door through the same _drive_schedule machinery as
    in-process targets — seeded schedule, zero drops."""
    reg, eng, door, client = door_stack
    pool = np.random.RandomState(2).uniform(
        -1, 1, (4, 1, FEAT)).astype(np.float32)
    s = run_loadgen(
        lambda i, n: client.submit("m", {"data": pool[i % 4]}),
        OpenLoopSchedule(7, 40, 60.0, sizes=(1,)))
    assert s["ok"] == 40 and s["errors"] == 0 and s["timeouts"] == 0
    assert s["p99_ms"] is not None


def test_frontdoor_spans_in_profiler_trace(tmp_path, door_stack):
    """Runtime face of the span-coverage manifest entries: the HTTP
    handler emits serve_http; a replica-set dispatch emits
    serve_dispatch."""
    reg, eng, door, client = door_stack
    trace = str(tmp_path / "frontdoor_trace.json")
    mx.profiler.profiler_set_config(filename=trace)
    mx.profiler.profiler_set_state("run")
    try:
        client.submit("m", {"data": np.zeros((1, FEAT),
                                             "float32")}).result(30)
        with ReplicaSet(lambda i: _registry(), n_replicas=1,
                        probe_interval=0, max_delay_ms=0) as rset:
            rset.submit("m", data=np.zeros((1, FEAT),
                                           "float32")).result(30)
    finally:
        mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()
    with open(trace) as f:
        names = {ev["name"] for ev in json.load(f)["traceEvents"]
                 if ev.get("cat") == "step_phase"}
    assert set(mx.profiler.FRONTDOOR_PHASES) <= names


# ---------------------------------------------------------------------------
# replica set: balancing, failover, probes
# ---------------------------------------------------------------------------
def test_replica_set_balances_and_serves(fresh_faults):
    with ReplicaSet(lambda i: _registry(), n_replicas=2,
                    probe_interval=0, max_delay_ms=0) as rset:
        x = np.zeros((1, FEAT), "float32")
        futs = [rset.submit("m", data=x) for _ in range(8)]
        for f in futs:
            f.result(30)
        st = rset.stats()
        assert st["submitted"] == 8 and st["dispatched"] >= 8
        assert st["live"] == [0, 1]
        assert set(st["replicas"]) == {0, 1}


def test_injected_die_kills_replica_not_process(fresh_faults):
    """The serve.dispatch die handler: a seeded SIGKILL takes down ONE
    replica; the request that triggered it fails over and succeeds."""
    faultinject.install({"seed": 3, "rules": [
        {"seam": "serve.dispatch", "kind": "forward", "nth": 1,
         "action": "die"}]})
    with ReplicaSet(lambda i: _registry(), n_replicas=2,
                    probe_interval=0, max_delay_ms=0) as rset:
        x = np.zeros((1, FEAT), "float32")
        before = mx.profiler.phase_totals()
        out = rset.submit("m", data=x).result(30)
        assert out is not None
        assert len(rset.live_replicas()) == 1
        st = rset.stats()
        assert st["retries"] >= 1
        # serve_retry is the FAILED placement, pick to exception, and
        # nothing else: the attempt that placed the request cancels it
        spans = mx.profiler.phase_totals(since=before)
        assert spans["serve_retry"]["spans"] == st["retries"]
        assert spans["serve_dispatch"]["spans"] == 1
        assert 0 < spans["serve_retry"]["ns"] < spans["serve_dispatch"]["ns"]
        # the dead replica's engine is really gone
        dead = [r for r in rset.replicas() if not r.alive][0]
        with pytest.raises(ServeClosed):
            dead.engine.submit("m", data=x)


def test_kill_one_replica_under_load_drains(fresh_faults):
    """THE acceptance scenario: one of 3 replicas SIGKILLed by a seeded die
    under open-loop load — 100% of accepted requests resolve, zero client
    hangs, and the balancer converges to the survivors (the post-kill
    rate is ``tools/serve_smoke.py``'s to report, not a test's to
    assert)."""
    from mxnet_tpu.serving.loadgen import failover_protocol
    r = failover_protocol(smoke=True)
    s = r["summary"]
    assert r["killed"], "the seeded die never fired"
    assert r["resolved"] == s["n"], "client hang: %d of %d unresolved" \
        % (s["n"] - r["resolved"], s["n"])
    assert r["dropped"] == 0, "accepted requests dropped: %d" \
        % r["dropped"]
    assert len(r["live_after"]) == 2
    assert r["failovers"] + r["retries"] >= 1


def test_breaker_opens_on_sever_and_probe_revives(fresh_faults):
    """Transient severance: injected errors open the breaker (the
    balancer routes around the replica); a later successful probe
    closes it and the replica returns to rotation."""
    faultinject.install({"seed": 5, "rules": [
        {"seam": "serve.dispatch", "kind": "forward", "sid": 0,
         "nth": 1, "count": 2, "action": "error"}]})
    with ReplicaSet(lambda i: _registry(), n_replicas=2,
                    probe_interval=0, cb_fails=1, cb_reset=0.0,
                    max_delay_ms=0) as rset:
        x = np.zeros((1, FEAT), "float32")
        rset.submit("m", data=x).result(30)   # severed on 0 -> served by 1
        r0 = rset.replicas()[0]
        assert r0.breaker.state == r0.breaker.OPEN
        assert r0.alive   # severed, not dead
        rset.probe_once()   # probe succeeds (rule matches forward only)
        assert r0.breaker.state == r0.breaker.CLOSED
        rset.submit("m", data=x).result(30)
        assert rset.stats()["probe_failures"] == 0


def test_no_live_replicas_is_structured(fresh_faults):
    with ReplicaSet(lambda i: _registry(), n_replicas=1,
                    probe_interval=0, max_delay_ms=0) as rset:
        rset.kill_replica(0)
        fut = rset.submit("m", data=np.zeros((1, FEAT), "float32"))
        with pytest.raises(NoLiveReplicas):
            fut.result(30)
        assert rset.stats()["no_live"] == 1


# ---------------------------------------------------------------------------
# hot weight swap under traffic
# ---------------------------------------------------------------------------
def test_swap_under_load_bit_consistency():
    """THE swap acceptance: every response bit-matches exactly one of
    {old, new} forward outputs (zero torn reads), the version counter
    increments once, and traffic straddles the swap."""
    from mxnet_tpu.serving.loadgen import swap_protocol
    r = swap_protocol(smoke=True)
    assert r["neither"] == 0, "%d torn reads" % r["neither"]
    assert r["old"] > 0 and r["new"] > 0, r
    assert r["old"] + r["new"] == r["n"]
    assert r["version_increments"] == 1
    assert r["version_before"] == 1 and r["version_after"] == 2


def test_swap_params_validates_signature():
    reg = _registry()
    store = reg.store("m")
    sym, args = _mlp_model()
    bad = {k: v.astype(np.float64) for k, v in args.items()}
    with pytest.raises(MXNetError):
        reg.swap_params("m", {})           # missing params
    good_version = store.version
    wrong_shape = {k: (np.zeros((3, 3), np.float32)
                       if k == "fc1_weight" else v)
                   for k, v in args.items()}
    with pytest.raises(MXNetError):
        reg.swap_params("m", wrong_shape)  # shape mismatch
    assert store.version == good_version   # failed swaps don't publish
    with pytest.raises(MXNetError):
        reg.swap_params("ghost", args)


def test_swap_fans_out_to_live_replicas_only(fresh_faults):
    sym, args = _mlp_model()
    args2 = {k: v + 1.0 for k, v in args.items()}
    with ReplicaSet(lambda i: _registry(), n_replicas=3,
                    probe_interval=0, max_delay_ms=0) as rset:
        rset.kill_replica(2)
        vers = rset.swap_params("m", args2)
        assert sorted(vers) == [0, 1] and set(vers.values()) == {2}
        x = np.zeros((1, FEAT), "float32")
        out = np.asarray(rset.submit("m", data=x).result(30)[0])
        # served from a swapped replica: matches a version-2 forward
        ref = np.asarray(
            _registry(args_override=args2).store("m").run(
                {"data": x})[0][0])
        assert np.array_equal(out, ref)


# ---------------------------------------------------------------------------
# generation through the front door + replica death
# ---------------------------------------------------------------------------
def _tiny_lm():
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    spec = lm_spec(num_layers=1, num_hidden=32, num_heads=2,
                   vocab_size=64)
    params = random_params(spec, seed=4)
    return spec, params


def _gen_registry(spec, params):
    reg = ModelRegistry()
    reg.add_generative_model(
        "lm", {k: np.asarray(v).copy() for k, v in params.items()},
        spec, batch_buckets=(2,), prompt_buckets=(8,), kv_block=8,
        kv_max=32, warmup_kv_depth=32)
    return reg


@pytest.fixture(scope="module")
def gen_reg():
    """One warmed generative registry shared by the generation tests
    (warmup compiles the prefill/decode program set once; engines come
    and go per test, stores are engine-independent)."""
    spec, params = _tiny_lm()
    return _gen_registry(spec, params)


def test_gen_submit_invalid_param_does_not_leak_inflight(gen_reg):
    """A malformed sampling parameter must fail BEFORE the admission
    bookkeeping: leaking the inflight slot would wedge a budgeted
    engine into permanent 429s."""
    from mxnet_tpu.serving import GenerationEngine
    eng = GenerationEngine(gen_reg, max_inflight=1)
    try:
        for _ in range(3):
            with pytest.raises(MXNetError):
                eng.submit("lm", [1], max_tokens=2, temperature="abc")
        # the budget is untouched: a real request still admits
        eng.submit("lm", [1, 2], max_tokens=2).result(60)
        assert eng.stats()["inflight"] == 0
    finally:
        eng.close()


def test_http_generate_end_to_end(gen_reg):
    from mxnet_tpu.serving import GenerationEngine
    reg = gen_reg
    gen = GenerationEngine(reg)
    door = HttpFrontDoor(ServingEngine(ModelRegistry(), max_delay_ms=0),
                         gen_target=gen)
    client = HttpClient(door.address, threads=2)
    try:
        ref = gen.submit("lm", [1, 2, 3], max_tokens=6).result(60)
        res = client.generate("lm", [1, 2, 3], max_tokens=6).result(60)
        assert res.tokens == ref.tokens            # greedy == greedy
        assert res.finish_reason == ref.finish_reason
        assert len(res.token_times) == len(res.tokens)
    finally:
        client.close()
        door.close()
        gen.close()
        door.target.close()


def test_generation_fails_fast_when_replica_dies(fresh_faults, gen_reg,
                                                 throttle_ticks):
    """Post-admission replica death: the generation's KV state died
    with the replica — the client gets a structured ReplicaDied fast,
    no transparent regenerate, no hang."""
    from mxnet_tpu.serving import TokenStream
    with ReplicaSet([gen_reg], gen=True,
                    probe_interval=0, max_delay_ms=0) as rset:
        # throttle decode steps so the kill deterministically lands
        # while the generation is still in flight
        slowed = throttle_ticks(rset.replicas()[0].gen_engine, 0.02)
        stream = TokenStream()
        fut = rset.submit_gen("lm", [1, 2, 3], max_tokens=24,
                              stream=stream)
        first = next(iter(stream))   # generation is definitely admitted
        assert isinstance(first, int)
        rset.kill_replica(0)
        with pytest.raises(ReplicaDied):
            fut.result(30)
        assert rset.stats()["gen_aborted"] == 1
        assert slowed, "the throttle was never entered"


# ---------------------------------------------------------------------------
# TLS front door
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tls_pair(tmp_path_factory):
    """Self-signed cert + key for 127.0.0.1 (SAN-pinned so a client
    verifying against the cert itself passes hostname checks)."""
    import shutil
    import subprocess
    openssl = shutil.which("openssl")
    if openssl is None:
        pytest.skip("no openssl binary to mint a test certificate")
    d = tmp_path_factory.mktemp("tls")
    cert, key = str(d / "cert.pem"), str(d / "key.pem")
    subprocess.run(
        [openssl, "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "2",
         "-subj", "/CN=127.0.0.1",
         "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True)
    return cert, key


def test_tls_round_trip_self_signed(tls_pair):
    """The satellite's TLS pin: a front door armed with a self-signed
    cert serves https (scheme in .url), an HttpClient pinning that
    cert round-trips npz forwards bit-exactly, and the verify="0"
    escape hatch also connects."""
    cert, key = tls_pair
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    x = np.arange(FEAT, dtype=np.float32).reshape(1, FEAT) / FEAT
    try:
        want = eng.submit("m", data=x.copy()).result(60)
        with HttpFrontDoor(eng, tls_cert=cert, tls_key=key) as fd:
            assert fd.tls and fd.url.startswith("https://")
            # PEM-pinned verification (the self-signed deployment)
            with HttpClient(fd.url, threads=2, tls_verify=cert) as cl:
                got = cl.submit("m", {"data": x.copy()}).result(60)
                np.testing.assert_array_equal(got[0], want[0])
                code, payload = cl.healthz()
                assert code == 200 and payload["models"] == ["m"]
            # verification disabled (lab hatch) still talks TLS
            with HttpClient(fd.url, threads=1, tls_verify="0") as cl:
                got = cl.submit("m", {"data": x.copy()}).result(60)
                np.testing.assert_array_equal(got[0], want[0])
    finally:
        eng.close()


def test_tls_default_verify_rejects_self_signed(tls_pair):
    """MXNET_SERVE_TLS_VERIFY's default ("1", system trust store) must
    REJECT the self-signed cert — trust is opt-in via the PEM pin, not
    granted to whoever answers the port."""
    import ssl
    cert, key = tls_pair
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    x = np.zeros((1, FEAT), np.float32)
    try:
        with HttpFrontDoor(eng, tls_cert=cert, tls_key=key) as fd:
            with HttpClient(fd.url, threads=1, tls_verify="1") as cl:
                with pytest.raises(ssl.SSLError):
                    cl.submit("m", {"data": x}).result(60)
    finally:
        eng.close()


def test_tls_half_config_raises(tls_pair, monkeypatch):
    """Cert without key (either argument or env) is a config error —
    never silent plaintext on an endpoint the operator asked to arm."""
    cert, _key = tls_pair
    reg = _registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    try:
        with pytest.raises(MXNetError):
            HttpFrontDoor(eng, tls_cert=cert)
        monkeypatch.setenv("MXNET_SERVE_TLS_KEY", "/nope/key.pem")
        monkeypatch.delenv("MXNET_SERVE_TLS_CERT", raising=False)
        with pytest.raises(MXNetError):
            HttpFrontDoor(eng)
        # an unreadable pair fails loudly too (and releases the port)
        monkeypatch.setenv("MXNET_SERVE_TLS_CERT", "/nope/cert.pem")
        with pytest.raises(MXNetError):
            HttpFrontDoor(eng)
    finally:
        eng.close()
