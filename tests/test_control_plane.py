"""Serving control-plane tests: the SLO-driven AutoScaler state machine
(clock-free via evaluate_once), the controller thread lifecycle, the
warm spare-registry pool (build-once scale-up, recycle-on-drain,
spares follow hot swaps), ServeClosed carrying the dead replica's index
through kill/close, the hot-swap vs /metrics-scrape vs in-flight
generation race, priority-tier preemption, per-tenant quotas, bearer-
token auth on the front door and shaped-schedule determinism
(docs/architecture/serving.md, control-plane section)."""
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import metrics as _metrics
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (AutoScaler, HttpClient, HttpFrontDoor,
                               ModelRegistry, NoLiveReplicas,
                               OpenLoopSchedule, ReplicaSet, ServeClosed,
                               ServeOverloaded, ServingEngine)
from mxnet_tpu.serving.scheduler import _H_QWAIT
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


def _registry(args_override=None, buckets=(1,), feat=FEAT):
    sym, args = _mlp_model(feat=feat)
    reg = ModelRegistry()
    reg.add_model("m", sym,
                  {k: v.copy() for k, v in
                   (args_override or args).items()},
                  {}, input_shapes={"data": (1, feat)}, buckets=buckets)
    return reg


def _x():
    return np.zeros((1, FEAT), "float32")


def _ref_forward(args_override, x):
    return np.asarray(_registry(args_override=args_override)
                      .store("m").run({"data": x})[0][0])


# ---------------------------------------------------------------------------
# AutoScaler: the state machine, clock-free
# ---------------------------------------------------------------------------
def test_autoscaler_state_machine_clock_free():
    """evaluate_once(now=...) drives the whole up/cooldown/down cycle
    without a controller thread or a wall clock: a shed triggers scale
    up, cooldown gates the next action even when the trigger persists,
    the idle hysteresis band scales back down, and min_replicas is a
    floor."""
    with ReplicaSet(lambda i: _registry(), n_replicas=1,
                    probe_interval=0, max_delay_ms=0,
                    max_inflight=8) as rset:
        sc = AutoScaler(rset, slo_ms=50.0, min_replicas=1,
                        max_replicas=3, interval=0.05, cooldown=10.0,
                        start=False)
        base = time.monotonic()
        # empty window, zero sheds, zero util, but n == min: hold
        r = sc.evaluate_once(now=base)
        assert r["action"] == "hold" and r["n_replicas"] == 1

        # admission shed since the last tick => saturated NOW => up
        rset._stats.inc("shed")
        r = sc.evaluate_once(now=base + 1.0)
        assert r["action"] == "up" and r["shed_delta"] == 1
        assert rset.n_replicas() == 2

        # still over (queue-wait p95 far above the 50ms SLO) but the
        # cooldown from the scale-up gates the action
        _H_QWAIT.observe(10.0)
        r = sc.evaluate_once(now=base + 2.0)
        assert r["action"] == "hold"
        assert r["p95_ms"] is not None and r["p95_ms"] > 50.0

        # cooled down + idle window (no observations, no sheds, zero
        # util): the hysteresis band scales back down
        r = sc.evaluate_once(now=base + 20.0)
        assert r["action"] == "down" and r["p95_ms"] is None
        assert rset.n_replicas() == 1

        # at the min_replicas floor an idle set holds
        r = sc.evaluate_once(now=base + 40.0)
        assert r["action"] == "hold" and rset.n_replicas() == 1

        acts = [(a, n) for _, a, n in sc.actions()]
        assert acts == [("up", 2), ("down", 1)]
        assert sc.replica_seconds(now=base + 41.0) > 0
        sc.close()


def test_autoscaler_thread_lifecycle_and_guards():
    """start=True runs the non-daemon mxt-serve-autoscale thread;
    close() joins it and is idempotent.  A list-built set (no factory)
    with headroom to grow is rejected at CONSTRUCTION, not at the first
    scale-up tick inside the thread."""
    with ReplicaSet(lambda i: _registry(), n_replicas=1,
                    probe_interval=0, max_delay_ms=0) as rset:
        sc = AutoScaler(rset, slo_ms=50.0, min_replicas=1,
                        max_replicas=2, interval=0.02, cooldown=60.0,
                        start=True)
        names = [t.name for t in threading.enumerate()]
        assert "mxt-serve-autoscale" in names
        assert not sc._thread.daemon
        time.sleep(0.08)   # a few ticks on an idle set must be benign
        sc.close()
        sc.close()   # idempotent
        assert "mxt-serve-autoscale" not in \
            [t.name for t in threading.enumerate()]

    with ReplicaSet([_registry()], probe_interval=0,
                    max_delay_ms=0) as fixed:
        with pytest.raises(MXNetError, match="build_registry"):
            AutoScaler(fixed, slo_ms=50.0, min_replicas=1,
                       max_replicas=3, start=False)


# ---------------------------------------------------------------------------
# warm spare pool
# ---------------------------------------------------------------------------
def test_spare_pool_prebuilds_recycles_and_skips_killed():
    """spares=1 pays one extra factory build up front; add_replica joins
    from the pool without building, a cleanly-drained replica's registry
    is recycled, and a KILLED replica's registry is NOT — the next
    scale-up past the pool rebuilds from the factory."""
    calls = []

    def build(i):
        calls.append(i)
        return _registry()

    with ReplicaSet(build, n_replicas=1, probe_interval=0,
                    max_delay_ms=0, spares=1) as rset:
        assert len(calls) == 2   # 1 replica + 1 spare, all up front
        assert rset.load_signals()["n_spares"] == 1

        idx = rset.add_replica()          # from the pool: no build
        assert len(calls) == 2
        assert rset.load_signals()["n_spares"] == 0

        rset.remove_replica(index=idx)    # drained: recycled
        assert rset.load_signals()["n_spares"] == 1
        idx2 = rset.add_replica()         # pool again: still no build
        assert len(calls) == 2

        rset.kill_replica(idx2)
        rset.remove_replica(index=idx2)   # killed: NOT recycled
        assert rset.load_signals()["n_spares"] == 0
        rset.add_replica()                # pool empty: factory build
        assert len(calls) == 3


def test_spares_follow_hot_swap():
    """A spare that joins the rotation AFTER swap_params must serve the
    NEW weights: the swap fans out to the pool, so a post-swap scale-up
    cannot resurrect the old version."""
    _, args = _mlp_model()
    args2 = {k: v + 1.0 for k, v in args.items()}
    with ReplicaSet(lambda i: _registry(), n_replicas=1,
                    probe_interval=0, max_delay_ms=0,
                    spares=1) as rset:
        vers = rset.swap_params("m", args2)
        assert set(vers.values()) == {2}
        idx = rset.add_replica()          # joins from the swapped pool
        rset.kill_replica(0)              # only the pool-joined serves
        x = _x()
        out = np.asarray(rset.submit("m", data=x).result(30)[0])
        assert np.array_equal(out, _ref_forward(args2, x))
        assert rset.replicas()[-1].index == idx
        assert rset.replicas()[-1].registry.store("m").version == 2


# ---------------------------------------------------------------------------
# satellite: ServeClosed carries the dead replica's index
# ---------------------------------------------------------------------------
def _stall_and_backlog(rset):
    """Dispatch one request into a gate-stalled hook, then queue two
    more behind it.  Returns (gate, dispatched_future, queued_futures).
    The dispatched request is device work a real SIGKILL would also let
    finish; the queued two are what the fail-fast close must resolve."""
    gate = threading.Event()
    taken = threading.Event()

    def hook(_model, _reqs):
        taken.set()
        gate.wait(30)

    rset.replicas()[0].engine._dispatch_hook = hook
    head = rset.submit("m", data=_x())
    assert taken.wait(10), "engine never took the head request"
    queued = [rset.submit("m", data=_x()) for _ in range(2)]
    return gate, head, queued


def _assert_closed_with_index(futs):
    for fut in futs:
        with pytest.raises(ServeClosed) as ei:
            fut.result(30)
        assert ei.value.replica_index == 0
        assert "[replica 0]" in str(ei.value)


def test_kill_resolves_inflight_with_replica_index():
    """kill_replica: queued requests resolve (no hang, no silent drop)
    with a structured ServeClosed NAMING the dead replica — the retry
    layer and the flight recorder both key on it.  Already-dispatched
    device work completes, the in-process analog of a SIGKILL leaving
    the accelerator step finishing."""
    rset = ReplicaSet([_registry()], probe_interval=0, max_delay_ms=0,
                      retries=0)
    try:
        gate, head, queued = _stall_and_backlog(rset)
        # kill() joins the engine thread, which is parked in the hook:
        # run it from a side thread and release the gate under it
        killer = threading.Thread(target=rset.kill_replica, args=(0,))
        killer.start()
        deadline = time.monotonic() + 10
        while not rset.replicas()[0].engine._closed \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        gate.set()
        killer.join(30)
        assert not killer.is_alive()
        assert len(head.result(30)) == 1   # dispatched work finished
        _assert_closed_with_index(queued)
        with pytest.raises(ServeClosed):
            rset.replicas()[0].engine.submit("m", data=_x())
    finally:
        rset.close()


def test_close_without_drain_resolves_inflight_with_replica_index():
    """ReplicaSet.close(drain=False): same contract as kill — the
    fail-fast close resolves queued work with ServeClosed carrying the
    replica index instead of dropping it, and later submits raise
    ServeClosed."""
    rset = ReplicaSet([_registry()], probe_interval=0, max_delay_ms=0,
                      retries=0)
    gate, head, queued = _stall_and_backlog(rset)
    closer = threading.Thread(target=rset.close,
                              kwargs={"drain": False})
    closer.start()
    deadline = time.monotonic() + 10
    while not rset.replicas()[0].engine._closed \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    gate.set()
    closer.join(30)
    assert not closer.is_alive()
    assert len(head.result(30)) == 1
    _assert_closed_with_index(queued)
    with pytest.raises((ServeClosed, NoLiveReplicas)):
        rset.submit("m", data=_x()).result(10)


# ---------------------------------------------------------------------------
# satellite: hot swap races /metrics scrape and in-flight generation
# ---------------------------------------------------------------------------
def test_swap_races_metrics_scrape_and_inflight_generation(throttle_ticks):
    """swap_params under a concurrent Prometheus scrape loop AND an
    in-flight generation on the same replica: the rolling swap's drain
    window expires (the generation outlives drain_timeout), the store
    swap lands anyway (atomic per dispatch), every scrape parses, the
    generation completes, and forwards serve the new weights."""
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    spec = lm_spec(num_layers=1, num_hidden=32, num_heads=2,
                   vocab_size=64)
    params = random_params(spec, seed=4)
    reg = _registry()
    reg.add_generative_model(
        "lm", {k: np.asarray(v).copy() for k, v in params.items()},
        spec, batch_buckets=(2,), prompt_buckets=(8,), kv_block=8,
        kv_max=64, warmup_kv_depth=64)
    _, args = _mlp_model()
    args2 = {k: v - 0.25 for k, v in args.items()}

    rset = ReplicaSet([reg], gen=True, probe_interval=0.05,
                      max_delay_ms=0)
    door = HttpFrontDoor(rset)
    client = HttpClient(door.address, threads=2)
    stop = threading.Event()
    scrapes, scrape_errors = [0], []

    def scraper():
        while not stop.is_set():
            try:
                text = client.metrics_text()
                assert "serve_queue_wait_seconds" in text
                scrapes[0] += 1
            except BaseException as e:  # noqa: BLE001
                scrape_errors.append(e)
                return

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    try:
        # slow the decode steps so the generation provably spans the
        # swaps (same throttle as the frontdoor replica-death test)
        slowed = throttle_ticks(rset.replicas()[0].gen_engine, 0.01)
        gen_fut = rset.submit_gen("lm", [1, 2, 3], max_tokens=48)
        for _ in range(3):   # three rolls while the generation runs
            rset.swap_params("m", args2, drain_timeout=0.05)
        res = gen_fut.result(60)
        assert len(res.tokens) > 0
        assert slowed, "the throttle was never entered"
        x = _x()
        out = np.asarray(rset.submit("m", data=x).result(30)[0])
        assert np.array_equal(out, _ref_forward(args2, x))
        assert reg.store("m").version == 4   # 1 + three swaps
    finally:
        stop.set()
        t.join(10)
        client.close()
        door.close()
        rset.close()
    assert not scrape_errors
    assert scrapes[0] > 0


# ---------------------------------------------------------------------------
# priority tiers + per-tenant quotas
# ---------------------------------------------------------------------------
def test_latency_tier_preempts_queued_batch_requests():
    """Tier preemption at the dispatch loop: with batch requests queued
    ahead of them, latency-tier requests dispatch first; FIFO holds
    within each tier; tiers never share a dispatch batch."""
    eng = ServingEngine(_registry(), max_delay_ms=0, max_batch=1)
    gate = threading.Event()
    orders = []

    def hook(_model, reqs):
        orders.append([r.priority for r in reqs])
        gate.wait(10)

    eng._dispatch_hook = hook
    try:
        futs = [eng.submit("m", data=_x())]        # stalls in the hook
        time.sleep(0.1)    # let the engine take it before the backlog
        futs += [eng.submit("m", data=_x(), priority="batch")
                 for _ in range(2)]
        futs += [eng.submit("m", data=_x(), priority="latency")
                 for _ in range(2)]
        gate.set()
        for fut in futs:
            fut.result(30)
    finally:
        gate.set()
        eng.close()
    flat = [p for batch in orders for p in batch]
    assert flat == ["batch", "latency", "latency", "batch", "batch"]
    assert all(len(set(batch)) == 1 for batch in orders)


def test_tenant_quota_sheds_noisy_tenant_alone():
    """Per-tenant inflight-row quotas: the noisy tenant over budget is
    shed (ServeOverloaded + serve_tenant_shed_total), the quiet tenant
    admits untouched, and the rows drain back to zero."""
    eng = ServingEngine(_registry(), max_delay_ms=0, max_batch=1,
                        tenant_quotas={"noisy": 2})
    gate = threading.Event()
    eng._dispatch_hook = lambda _model, _reqs: gate.wait(10)
    shed0 = _metrics.cached_counter("serve_tenant_shed_total",
                                    labels={"tenant": "noisy"}).value
    try:
        futs = [eng.submit("m", data=_x(), tenant="noisy")
                for _ in range(2)]
        with pytest.raises(ServeOverloaded, match="inflight row quota"):
            eng.submit("m", data=_x(), tenant="noisy")
        futs.append(eng.submit("m", data=_x(), tenant="quiet"))
        assert eng.stats()["tenant_rows"] == {"noisy": 2, "quiet": 1}
        gate.set()
        for fut in futs:
            fut.result(30)
        assert eng.stats()["tenant_rows"] == {}
        assert eng.stats()["tenant_quotas"] == {"noisy": 2}
    finally:
        gate.set()
        eng.close()
    shed1 = _metrics.cached_counter("serve_tenant_shed_total",
                                    labels={"tenant": "noisy"}).value
    assert shed1 - shed0 == 1


def test_unknown_priority_tier_rejected_everywhere():
    """A bogus tier is a validation error, not a silent default — at
    the engine and as HTTP 400 through the front door."""
    eng = ServingEngine(_registry(), max_delay_ms=0)
    door = HttpFrontDoor(eng)
    client = HttpClient(door.address, threads=1)
    try:
        with pytest.raises(MXNetError, match="priority tier"):
            eng.submit("m", data=_x(), priority="urgent")
        fut = client.submit("m", {"data": _x()}, priority="urgent")
        with pytest.raises(MXNetError, match="HTTP 400"):
            fut.result(30)
    finally:
        client.close()
        door.close()
        eng.close()


# ---------------------------------------------------------------------------
# satellite: bearer-token auth on the front door
# ---------------------------------------------------------------------------
def test_frontdoor_bearer_token_auth():
    """With auth_token set: tokenless/wrong-token submits get the
    structured 401; /healthz and /metrics stay exempt (probes and
    scrapers need no credentials); the right token serves."""
    eng = ServingEngine(_registry(), max_delay_ms=0)
    door = HttpFrontDoor(eng, auth_token="s3cret")
    anon = HttpClient(door.address, threads=1)
    wrong = HttpClient(door.address, threads=1, auth_token="nope")
    authed = HttpClient(door.address, threads=1, auth_token="s3cret")
    try:
        for client in (anon, wrong):
            with pytest.raises(MXNetError, match="HTTP 401"):
                client.submit("m", {"data": _x()}).result(30)
        # exempt routes, no credentials
        code, payload = anon.healthz()
        assert code == 200 and payload["status"] == "ok"
        assert "serve_" in anon.metrics_text()
        # /stats is NOT exempt
        with pytest.raises(MXNetError, match="401"):
            anon.stats()
        out = authed.submit("m", {"data": _x()}).result(30)
        assert out[0].shape == (1, 10)
    finally:
        anon.close()
        wrong.close()
        authed.close()
        door.close()
        eng.close()


# ---------------------------------------------------------------------------
# shaped schedules
# ---------------------------------------------------------------------------
def test_shaped_schedules_are_seed_deterministic():
    """diurnal/bursty schedules: same seed => byte-identical arrivals,
    strictly increasing; different seeds diverge; the shape tag rides
    the schedule."""
    for maker in (OpenLoopSchedule.diurnal, OpenLoopSchedule.bursty):
        a = maker(seed=7, n_requests=200)
        b = maker(seed=7, n_requests=200)
        c = maker(seed=8, n_requests=200)
        assert np.array_equal(a.arrivals, b.arrivals)
        assert not np.array_equal(a.arrivals, c.arrivals)
        assert np.all(np.diff(a.arrivals) > 0)
    assert OpenLoopSchedule.diurnal(seed=1).shape == "diurnal"
    assert OpenLoopSchedule.bursty(seed=1).shape == "bursty"
    # a diurnal swing concentrates arrivals mid-period (the crest):
    # the middle third must be denser than the first third
    d = OpenLoopSchedule.diurnal(seed=3, n_requests=300, low_qps=5.0,
                                 high_qps=100.0, period_s=6.0)
    span = d.arrivals[-1]
    first = np.sum(d.arrivals < span / 3.0)
    mid = np.sum((d.arrivals >= span / 3.0)
                 & (d.arrivals < 2.0 * span / 3.0))
    assert mid > first
