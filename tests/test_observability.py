"""Unified telemetry plane tests (docs/architecture/observability.md):
the single-trace span-tree pin over one HTTP ``:generate`` (including
across a seeded replica-die retry), log-bucketed histogram quantile
accuracy vs ``numpy.percentile``, deterministic seeded trace sampling,
the flight-recorder postmortem naming the dying replica, ``GET
/metrics`` Prometheus text, the cached ``/stats`` ``age_ms`` contract,
legacy-stats-read-through-registry pins, and the live telemetry
overhead smoke."""
import json
import os
import re
import types

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 — package import wires the planes
from mxnet_tpu import faultinject, metrics, profiler, tracing
from mxnet_tpu.serving import (GenerationEngine, HttpClient,
                               HttpFrontDoor, ModelRegistry, ReplicaSet,
                               ServingEngine)
from mxnet_tpu.test_utils import smoke_mlp

FEAT = 8


def _mlp_registry(seed=0, feat=FEAT, hidden=16):
    sym = smoke_mlp(num_hidden=hidden)
    shapes, _, _ = sym.infer_shape(data=(1, feat), softmax_label=(1,))
    rs = np.random.RandomState(seed)
    args = {n: rs.uniform(-0.5, 0.5, s).astype(np.float32)
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    reg = ModelRegistry()
    reg.add_model("m", sym, args, {}, input_shapes={"data": (1, feat)},
                  buckets=(1, 2, 4))
    return reg


def _gen_registry():
    from mxnet_tpu.models.transformer_lm import lm_spec, random_params
    spec = lm_spec(num_layers=1, num_hidden=32, num_heads=2,
                   vocab_size=64)
    params = random_params(spec, seed=4)
    reg = ModelRegistry()
    reg.add_generative_model(
        "lm", {k: np.asarray(v).copy() for k, v in params.items()},
        spec, batch_buckets=(2,), prompt_buckets=(8,), kv_block=8,
        kv_max=32, warmup_kv_depth=32)
    return reg


@pytest.fixture()
def fresh_faults():
    faultinject.install(None)
    yield
    faultinject.install(None)


@pytest.fixture()
def jsonl_sink(tmp_path):
    path = str(tmp_path / "traces.jsonl")
    tracing.set_jsonl_sink(path)
    yield path
    tracing.set_jsonl_sink(None)


def _read_traces(path):
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------
def test_histogram_quantiles_match_numpy_within_bucket_error():
    """The log-bucketed histogram's p50/p95/p99 track numpy.percentile
    within the documented relative bucket error bound."""
    h = metrics.Histogram("t_seconds")
    rs = np.random.RandomState(7)
    vals = rs.lognormal(mean=-5.0, sigma=1.5, size=20000)
    for v in vals:
        h.observe(v)
    assert h.count == len(vals)
    for q in (0.50, 0.95, 0.99):
        est = h.quantile(q)
        true = float(np.percentile(vals, q * 100))
        assert abs(est - true) <= true * metrics.QUANTILE_REL_ERROR, \
            "q=%s est=%s true=%s" % (q, est, true)


def test_histogram_quantile_edge_cases():
    h = metrics.Histogram("e_seconds", lo=1e-3, hi=10.0)
    assert h.quantile(0.5) is None          # empty
    h.observe(1e-9)                          # below lo -> first bucket
    assert h.quantile(0.5) == pytest.approx(h.lo)
    h2 = metrics.Histogram("e2_seconds", lo=1e-3, hi=10.0)
    h2.observe(1e6)                          # above hi -> overflow
    assert h2.quantile(0.99) == pytest.approx(h2.hi)


def test_render_prometheus_parses():
    """Every sample line of the exposition parses; histogram buckets
    are cumulative and +Inf equals the count."""
    reg = metrics.MetricsRegistry()
    reg.counter("x_total", help="an x", labels={"k": "v"}).inc(3)
    reg.gauge("g").set(2.5)
    h = reg.histogram("lat_seconds")
    for v in (0.001, 0.01, 0.01, 4.0):
        h.observe(v)
    text = reg.render_prometheus()
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+$')
    cum = None
    for line in text.strip().split("\n"):
        if line.startswith("#"):
            continue
        assert sample_re.match(line), line
        if line.startswith("lat_seconds_bucket"):
            n = int(line.rsplit(" ", 1)[1])
            assert cum is None or n >= cum
            cum = n
    assert 'x_total{k="v"} 3' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text


def test_counterdict_reads_through_registry_and_drop_keeps_reader():
    reg = metrics.registry()
    labels = {"engine": "testxyz"}
    cd = metrics.CounterDict("obs_test_", ("a", "b"), labels=labels)
    cd.inc("a")
    cd.inc("b", 5)
    assert reg.value("obs_test_a_total", labels=labels) == 1
    assert reg.value("obs_test_b_total", labels=labels) == 5
    assert cd.as_dict() == {"a": 1, "b": 5}
    assert metrics.drop(labels) == 2
    # the registry forgot the series; the owner's reads still work
    assert reg.value("obs_test_a_total", labels=labels) is None
    assert cd["a"] == 1


def test_engine_stats_read_through_registry():
    """The serving engine's legacy stats() tree and the scrape read the
    SAME counters (the read-through contract)."""
    reg = _mlp_registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    try:
        x = np.zeros((1, FEAT), np.float32)
        for _ in range(3):
            eng.submit("m", data=x).result(60)
        s = eng.stats()
        assert s["requests"] == 3
        assert metrics.registry().value(
            "serve_requests_total", labels=eng._mlabels) == 3
    finally:
        eng.close()
    # close retires the labeled series from the scrape, but the
    # engine's own stats() keeps reading its references
    assert metrics.registry().value(
        "serve_requests_total", labels=eng._mlabels) is None
    assert eng.stats()["requests"] == 3


# ---------------------------------------------------------------------------
# trace sampling
# ---------------------------------------------------------------------------
def test_sample_decision_is_deterministic_and_rate_faithful():
    a = [tracing.sample_decision(i, 0.3, seed=11) for i in range(5000)]
    b = [tracing.sample_decision(i, 0.3, seed=11) for i in range(5000)]
    assert a == b                                  # same seed: identical
    c = [tracing.sample_decision(i, 0.3, seed=12) for i in range(5000)]
    assert a != c                                  # seed matters
    assert abs(sum(a) / 5000.0 - 0.3) < 0.03       # rate is honored
    assert not any(tracing.sample_decision(i, 0.0) for i in range(100))
    assert all(tracing.sample_decision(i, 1.0) for i in range(100))


def test_trace_sample_zero_records_no_spans(monkeypatch, jsonl_sink):
    monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0")
    reg = _mlp_registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    try:
        eng.submit("m", data=np.zeros((1, FEAT), np.float32)).result(60)
    finally:
        eng.close()
    assert _read_traces(jsonl_sink) == []          # nothing exported
    tr = tracing.start_trace("x")
    assert not tr.sampled
    assert tr.add_span("s", 0, 1) is None
    tr.finish()
    assert _read_traces(jsonl_sink) == []


def test_shed_request_exports_trace_with_status(jsonl_sink):
    """A shed submit still exports its self-minted trace (status =
    ServeOverloaded): overload is exactly the condition the telemetry
    plane exists to diagnose."""
    import time as _time

    from mxnet_tpu.serving import ServeOverloaded
    reg = _mlp_registry()
    eng = ServingEngine(reg, max_delay_ms=0, max_inflight=1)
    try:
        eng._dispatch_hook = lambda m, live: _time.sleep(0.2)
        first = eng.submit("m", data=np.zeros((1, FEAT), np.float32))
        with pytest.raises(ServeOverloaded):
            eng.submit("m", data=np.zeros((1, FEAT), np.float32))
        first.result(60)
    finally:
        eng._dispatch_hook = None
        eng.close()
    shed = [t for t in _read_traces(jsonl_sink)
            if t["status"] == "ServeOverloaded"]
    assert len(shed) == 1 and shed[0]["name"] == "serve.forward"


def test_inprocess_submit_mints_and_finishes_trace(jsonl_sink):
    reg = _mlp_registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    try:
        eng.submit("m", data=np.zeros((1, FEAT), np.float32)).result(60)
    finally:
        eng.close()
    traces = [t for t in _read_traces(jsonl_sink)
              if t["name"] == "serve.forward"]
    assert len(traces) == 1
    t = traces[0]
    assert t["status"] == "ok"
    assert "serve_compute" in [s["name"] for s in t["spans"]]


# ---------------------------------------------------------------------------
# THE propagation pin: one HTTP :generate -> one connected span tree,
# across a seeded replica die + placement retry
# ---------------------------------------------------------------------------
def test_http_generate_single_trace_tree_across_replica_retry(
        fresh_faults, jsonl_sink):
    regs = [_gen_registry(), _gen_registry()]
    faultinject.install({"seed": 5, "rules": [
        {"seam": "serve.dispatch", "kind": "gen", "nth": 1,
         "action": "die"}]})
    rset = ReplicaSet(regs, gen=True, probe_interval=0, max_delay_ms=0)
    door = HttpFrontDoor(rset)
    client = HttpClient(door.address, threads=2)
    try:
        res = client.generate("lm", [1, 2, 3], max_tokens=4).result(60)
        assert len(res.tokens) == 4
        stats = rset.stats()
        assert stats["retries"] >= 1           # the die really fired
        assert len(stats["live"]) == 1
        mtext = client.metrics_text()
        flight_view = client.debug_flight()
    finally:
        client.close()
        door.close()
        rset.close()
        faultinject.install(None)

    traces = [t for t in _read_traces(jsonl_sink)
              if t["name"] == "http.generate"]
    assert len(traces) == 1, "exactly one ingress trace"
    t = traces[0]
    assert t["status"] == "ok"
    names = [s["name"] for s in t["spans"]]
    # the whole path under ONE trace id: front door -> replica
    # placement -> engine prefill -> decode -> sample
    for phase in ("serve_http", "serve_dispatch", "serve_prefill",
                  "serve_decode", "serve_sample"):
        assert phase in names, "missing %s in %s" % (phase, names)
    # connected tree: every parent id resolves to the root (0) or to
    # another span of this trace
    ids = {0} | {s["span_id"] for s in t["spans"]}
    assert all(s["parent_id"] in ids for s in t["spans"])

    # the scrape the acceptance names: Prometheus text with TTFT/ITL
    # histograms and shed/retry counters, all sample lines parseable
    assert "serve_ttft_seconds_bucket" in mtext
    assert "serve_itl_seconds" in mtext
    assert "serve_rs_retries_total" in mtext
    assert "serve_shed_total" in mtext or "serve_gen_shed_total" in mtext
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+$')
    for line in mtext.strip().split("\n"):
        assert line.startswith("#") or sample_re.match(line), line
    # TTFT/ITL actually observed for this generation
    ttft = metrics.registry().get("serve_ttft_seconds")
    assert ttft is not None and ttft.count >= 1

    # the flight ring is readable over HTTP and saw the death
    kinds = [e["kind"] for e in flight_view["events"]]
    assert "replica_died" in kinds


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_flight_dump_after_seeded_die_names_dead_replica(
        tmp_path, monkeypatch, fresh_faults):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    tracing.reset_flight()
    try:
        faultinject.install({"seed": 3, "rules": [
            {"seam": "serve.dispatch", "kind": "forward", "nth": 2,
             "action": "die"}]})
        rset = ReplicaSet([_mlp_registry(), _mlp_registry(),
                           _mlp_registry()],
                          probe_interval=0, max_delay_ms=0)
        try:
            x = np.zeros((1, FEAT), np.float32)
            for _ in range(4):
                rset.submit("m", data=x).result(60)
            dead = [r.index for r in rset.replicas() if not r.alive]
            assert len(dead) == 1
        finally:
            rset.close()
            faultinject.install(None)
        dumps = sorted(tmp_path.glob("flight.*.json"))
        assert dumps, "the die path must leave a postmortem artifact"
        doc = json.loads(dumps[0].read_text())
        # the artifact names the dying replica
        assert str(dead[0]) in doc["reason"]
        died = [e for e in doc["events"] if e["kind"] == "replica_died"]
        assert died and died[0]["sid"] == dead[0]
        assert "metrics" in doc and "events" in doc
    finally:
        tracing.reset_flight()


def test_flight_ring_is_bounded_and_disableable(monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_CAPACITY", "8")
    tracing.reset_flight()
    try:
        fl = tracing.flight()
        for i in range(50):
            fl.record("event", "e%d" % i)
        evs = fl.events()
        assert len(evs) == 8 and evs[-1]["name"] == "e49"
        monkeypatch.setenv("MXNET_FLIGHT_CAPACITY", "0")
        tracing.reset_flight()
        fl = tracing.flight()
        fl.record("event", "ignored")
        assert fl.events() == []
        assert fl.dump(path=None) is None      # no dir, no capacity
    finally:
        tracing.reset_flight()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_engine_loop_crash_dumps_flight(tmp_path, monkeypatch):
    """A crashed dispatch loop leaves a postmortem naming the error
    (beside the existing fail-queued-with-ServeClosed sweep).  The
    injected crash intentionally escapes the engine thread (that IS
    the scenario), so the thread-exception warning is expected."""
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    tracing.reset_flight()
    try:
        reg = _mlp_registry()
        eng = ServingEngine(reg, max_delay_ms=0)
        try:
            def boom(model, live):
                raise RuntimeError("injected loop crash")
            eng._dispatch_hook = boom
            with pytest.raises(Exception):
                eng.submit("m", data=np.zeros((1, FEAT),
                                              np.float32)).result(30)
            eng._thread.join(30)
            dumps = sorted(tmp_path.glob("flight.*.json"))
            assert dumps
            doc = json.loads(dumps[0].read_text())
            assert "crashed" in doc["reason"]
        finally:
            eng._dispatch_hook = None
            eng.close()
    finally:
        tracing.reset_flight()


# ---------------------------------------------------------------------------
# cached /stats
# ---------------------------------------------------------------------------
def test_stats_snapshot_is_cached_with_age(monkeypatch):
    reg = _mlp_registry()
    eng = ServingEngine(reg, max_delay_ms=0)
    door = HttpFrontDoor(eng)
    client = HttpClient(door.address, threads=1)
    walks = [0]
    real = eng.stats

    def counting_stats():
        walks[0] += 1
        return real()

    monkeypatch.setattr(eng, "stats", counting_stats)
    monkeypatch.setenv("MXNET_SERVE_STATS_TTL_MS", "60000")
    try:
        with profiler.phase("obs_stats_phase", rows=3):
            pass
        s1 = client.stats()
        s2 = client.stats()
        assert walks[0] == 1               # second poll hit the cache
        # the process's span totals ride along under "phases"
        assert s1["phases"]["obs_stats_phase"]["counts"]["rows"] >= 3
        assert s1["age_ms"] >= 0.0
        assert s2["age_ms"] > 0.0          # and says how stale it is
        assert s2["requests"] == s1["requests"]
        # TTL <= 0 restores a walk per poll
        monkeypatch.setenv("MXNET_SERVE_STATS_TTL_MS", "0")
        client.stats()
        client.stats()
        assert walks[0] == 3
    finally:
        client.close()
        door.close()
        eng.close()


# ---------------------------------------------------------------------------
# training-side surfaces
# ---------------------------------------------------------------------------
def test_metricslogger_callback_logs_registry(caplog):
    import logging

    from mxnet_tpu.callback import MetricsLogger
    metrics.counter("fit_steps_total").inc(3)
    cb = MetricsLogger(period=1)
    param = types.SimpleNamespace(epoch=0, nbatch=2, eval_metric=None,
                                  locals=None)
    with caplog.at_level(logging.INFO):
        cb(param)
    assert any("fit_steps_total" in r.message for r in caplog.records)


def test_record_phase_feeds_phase_histogram(monkeypatch):
    h = metrics.registry().histogram("phase_seconds",
                                     labels={"phase": "obs_test_phase"})
    before = h.count
    with profiler.phase("obs_test_phase"):
        pass
    assert h.count == before + 1
    # the ambient feed silences under MXNET_METRICS=0
    monkeypatch.setenv("MXNET_METRICS", "0")
    with profiler.phase("obs_test_phase"):
        pass
    assert h.count == before + 1


# ---------------------------------------------------------------------------
# the span seam: profiler.phase() and the lifetime totals
# ---------------------------------------------------------------------------
# cow_fork lies inside serve_prepare
TICK_CHILDREN = ("serve_admit", "serve_prepare", "serve_decode",
                 "serve_prefill", "serve_sample", "serve_resolve")


def test_phase_counts_nest_and_add():
    before = profiler.phase_totals()
    with profiler.phase("obs_outer", rows=2) as outer:
        with profiler.phase("obs_inner", rows=3):
            pass
        outer.add(done=1)
        outer.add(done=1, rows=5)
    got = profiler.phase_totals(since=before)
    assert got["obs_outer"]["spans"] == got["obs_inner"]["spans"] == 1
    assert got["obs_outer"]["counts"] == {"rows": 7, "done": 2}
    assert got["obs_inner"]["counts"] == {"rows": 3}
    assert 0 <= got["obs_inner"]["ns"] <= got["obs_outer"]["ns"]
    # a label names the annotation and is summed nowhere; a cancelled
    # span reports to no sink
    with profiler.phase("obs_outer", labels={"ordinal": 7}):
        pass
    with profiler.phase("obs_inner") as span:
        span.cancel()
    got = profiler.phase_totals(since=before)
    assert got["obs_outer"]["spans"] == 2
    assert got["obs_outer"]["counts"] == {"rows": 7, "done": 2}
    assert got["obs_inner"]["spans"] == 1


def test_serve_tick_self_time_and_kv_tokens_by_hand():
    """Two requests through a toy paged engine: the tick's children
    lie inside it (their time sums to no more than the ticks'), every
    decode step and prompt chunk is one span, and ``kv_tokens`` — the
    K/V tokens attention has to read, a row's frontier after its step —
    equals the count made by hand, whatever order the ticks took."""
    reg = _gen_registry()
    chunk = reg.gen_store("lm").prefill_chunk
    jobs = [([1, 2, 3, 4, 5], 6), ([9, 8, 7, 6, 5, 4, 3, 2, 1, 11, 12], 4)]
    before = profiler.phase_totals()
    eng = GenerationEngine(reg)
    try:
        futs = [eng.submit("lm", p, max_tokens=n) for p, n in jobs]
        results = [f.result(120) for f in futs]
        stats = eng.stats()
    finally:
        eng.close()
    assert [len(r.tokens) for r in results] == [n for _, n in jobs]
    got = profiler.phase_totals(since=before)
    tick = got["serve_tick"]
    # the ordinal labels the annotation; nothing sums it: the starved
    # clock's count is all a tick carries (tests/test_starved_clock.py)
    assert tick["spans"] >= 1 and set(tick["counts"]) == {"starved_ns"}
    assert sum(got[c]["ns"] for c in TICK_CHILDREN if c in got) \
        <= tick["ns"]
    assert got["serve_sample"]["ns"] < tick["ns"]
    assert got["serve_decode"]["spans"] == stats["decode_steps"]
    # by hand: a prompt of P tokens in chunks of `chunk` reads up to each
    # chunk's end; the last chunk samples token 1 and each of the n-1
    # decode steps after it reads P + j tokens
    prefill = sum(min(end, len(p)) for p, _ in jobs
                  for end in range(chunk, len(p) + chunk, chunk))
    decode = sum(len(p) + j for p, n in jobs for j in range(1, n))
    assert got["serve_prefill"]["counts"]["kv_tokens"] == prefill
    assert got["serve_decode"]["counts"]["kv_tokens"] == decode
    assert got["serve_decode"]["counts"]["rows"] == \
        sum(n - 1 for _, n in jobs)
    assert got["serve_admit"]["counts"]["admitted"] == 2
    resolved = got["serve_resolve"]["counts"]
    assert resolved["tokens"] == sum(n for _, n in jobs)
    assert resolved["finished"] == 2
    for r in results:
        # admitted after it was submitted, before its first token
        assert r.t_submit <= r.t_admit <= r.token_times[0]
        assert r.queue_wait_s == r.t_admit - r.t_submit


def test_phase_totals_survive_engine_close():
    """A reader that runs after the driver freed the engine still finds
    what the engine did: the totals are the process's, not the
    engine's (whose counters ``close()`` drops from the registry)."""
    before = profiler.phase_totals()
    eng = GenerationEngine(_gen_registry())
    eng.submit("lm", [3, 1, 4, 1, 5], max_tokens=3).result(120)
    eng.close()
    del eng
    got = profiler.phase_totals(since=before)
    assert got["serve_tick"]["spans"] >= 1
    assert got["serve_decode"]["counts"]["kv_tokens"] == 6 + 7
    assert got["serve_idle"]["spans"] >= 1   # it waited for the request


def test_stager_thread_span_lands_in_window_collector():
    """``h2d_stage`` is opened on the stager's producer thread; the
    window collector the fit thread installed sums it all the same."""
    import threading
    import jax
    from mxnet_tpu.io.stager import DeviceStager
    X = np.zeros((96, 4), np.float32)
    it = mx.io.NDArrayIter(X, np.zeros((96,), np.float32), batch_size=32)
    dev = mx.cpu().jax_device()
    threads = set()

    def place(a):
        threads.add(threading.get_ident())
        return jax.device_put(a, dev)

    col = profiler.start_step_profile()
    try:
        stager = DeviceStager(it, place, depth=2)
        assert len(list(stager)) == 3
        stager.close()
    finally:
        profiler.stop_step_profile()
    assert threads and threading.get_ident() not in threads
    assert col.spans["h2d_stage"] == 3 and col.totals["h2d_stage"] > 0


def test_step_profile_metrics_mode(capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "step_profile_obs", os.path.join(os.path.dirname(__file__),
                                         "..", "tools",
                                         "step_profile.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--json", "--metrics", "--batches", "4"])
    assert rc == 0
    out = capsys.readouterr().out.strip().split("\n")[-1]
    report = json.loads(out)
    assert "metrics" in report
    hists = report["metrics"]["histograms"]
    assert any(k.startswith("phase_seconds") and "compute" in k
               for k in hists)


# ---------------------------------------------------------------------------
# overhead gates
# ---------------------------------------------------------------------------
def test_live_overhead_smoke():
    """A quick live re-measurement with generous bounds (CPU hosts are
    noisy): full
    telemetry must stay within 0.7x capacity, drop nothing, and
    actually export traces."""
    from mxnet_tpu.serving.loadgen import observability_protocol
    r = observability_protocol(smoke=True)
    assert r["qps_full_vs_baseline"] >= 0.7
    assert r["qps_sample0_vs_baseline"] >= 0.7
    assert r["full"]["dropped"] == 0
    assert r["traces_exported"] > 0
