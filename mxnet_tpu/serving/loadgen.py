"""Seeded open-loop load generator for the serving plane.

The "millions of users" scenario is open-loop: requests arrive on their
own schedule whether or not the server keeps up (closed-loop harnesses
hide queueing collapse — a saturated server just slows its own clients).
Real arrival processes are not reproducible in CI, so — exactly like
``faultinject.py`` turns real failures into a seeded schedule — the
generator draws the whole arrival process (exponential inter-arrival
gaps + request sizes) ONCE from a seed into a concrete
:class:`OpenLoopSchedule`; the same seed replays the same offered load
byte-for-byte, making the p50/p99/QPS a scenario reports
CPU-deterministic up to host timing noise.

:func:`run_loadgen` drives any ``submit(i, n) -> Future`` target on the
schedule and reports per-request latency percentiles and achieved QPS;
completion timestamps are taken AFTER a dependent-byte host fetch
(``test_utils.fetch_sync`` — the honest-timing discipline of
docs/perf.md) on a waiter thread, never on the engine thread.

:func:`latency_protocol` is the scenario ``make serve-smoke`` and the
tests share: measure per-request ``Predictor.forward`` closed-loop
(service latency + capacity), then drive BOTH a per-request server and
the continuous batcher under the same seeded open-loop schedule at a
multiple of that capacity.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from ..base import MXNetError

__all__ = ["OpenLoopSchedule", "run_loadgen", "latency_protocol",
           "run_gen_loadgen", "frontdoor_protocol", "failover_protocol",
           "swap_protocol",
           "observability_protocol", "autoscale_protocol",
           "rolling_swap_protocol", "chaos_protocol"]


class OpenLoopSchedule:
    """Deterministic seeded arrival schedule.

    ``arrivals[i]`` — seconds after t0 request ``i`` is offered (cumsum
    of exponential gaps at ``qps``); ``sizes[i]`` — its row count, drawn
    from ``sizes``/``size_weights``.  For generation workloads,
    ``gen_tokens`` draws a per-request ``max_tokens[i]`` the same way
    (None for non-generative schedules).  Same seed => identical
    schedule.
    """

    def __init__(self, seed=0, n_requests=100, qps=100.0, sizes=(1,),
                 size_weights=None, gen_tokens=None,
                 gen_token_weights=None):
        if qps <= 0 or n_requests < 1:
            raise MXNetError("schedule needs qps > 0 and n_requests >= 1")
        rs = np.random.RandomState(int(seed))
        self.arrivals = np.cumsum(
            rs.exponential(1.0 / float(qps), int(n_requests)))
        p = None
        if size_weights is not None:
            p = np.asarray(size_weights, np.float64)
            p = p / p.sum()
        self.sizes = rs.choice(np.asarray(sizes, np.int64),
                               int(n_requests), p=p)
        self.max_tokens = None
        if gen_tokens is not None:
            pg = None
            if gen_token_weights is not None:
                pg = np.asarray(gen_token_weights, np.float64)
                pg = pg / pg.sum()
            self.max_tokens = rs.choice(
                np.asarray(gen_tokens, np.int64), int(n_requests), p=pg)
        self.seed = int(seed)
        self.qps = float(qps)
        self.n = int(n_requests)
        self.shape = "poisson"

    @classmethod
    def _modulated(cls, shape, rate_of_t, seed, n_requests, mean_qps,
                   **kwargs):
        """Shared non-homogeneous-Poisson generator: draw each gap at
        the instantaneous rate ``rate_of_t(t)`` (one RandomState, so the
        same seed replays the same shaped load byte-for-byte)."""
        sched = cls(seed=seed, n_requests=n_requests, qps=mean_qps,
                    **kwargs)
        rs = np.random.RandomState(int(seed) ^ 0x5C4ED)
        t = 0.0
        arrivals = np.empty(int(n_requests))
        for i in range(int(n_requests)):
            t += rs.exponential(1.0 / max(1e-9, float(rate_of_t(t))))
            arrivals[i] = t
        sched.arrivals = arrivals
        sched.qps = float(n_requests) / float(arrivals[-1])
        sched.shape = shape
        return sched

    @classmethod
    def diurnal(cls, seed=0, n_requests=400, low_qps=10.0,
                high_qps=100.0, period_s=4.0, **kwargs):
        """A diurnal swing: the instantaneous rate follows a raised
        cosine from ``low_qps`` up to ``high_qps`` and back once per
        ``period_s`` (starting at the trough) — the autoscaler protocol
        walks a replica set up the ramp and back down it."""
        span = float(high_qps) - float(low_qps)

        def rate(t):
            return low_qps + span * 0.5 * (
                1.0 - np.cos(2.0 * np.pi * t / float(period_s)))

        return cls._modulated("diurnal", rate, seed, n_requests,
                              (low_qps + high_qps) / 2.0, **kwargs)

    @classmethod
    def bursty(cls, seed=0, n_requests=400, idle_qps=5.0,
               burst_qps=100.0, burst_s=1.0, idle_s=2.0, **kwargs):
        """An on/off burst train: ``burst_qps`` for ``burst_s`` seconds,
        ``idle_qps`` for ``idle_s``, repeating (burst first).  The
        step edges are what hysteresis and cooldown exist for — a
        controller without them flaps a replica on every cycle."""
        cycle = float(burst_s) + float(idle_s)

        def rate(t):
            return burst_qps if (t % cycle) < float(burst_s) else idle_qps

        mean = (burst_qps * burst_s + idle_qps * idle_s) / cycle
        return cls._modulated("bursty", rate, seed, n_requests, mean,
                              **kwargs)


def _drive_schedule(submit, schedule, on_success, settle_s, thread_name):
    """Shared open-loop driver behind :func:`run_loadgen` and
    :func:`run_gen_loadgen`.

    Offers ``submit(i)`` at the schedule's arrival times (open-loop: a
    request is offered on time even when earlier ones are still in
    flight), classifies completions on a waiter thread —
    ``on_success(result, t_submit)`` turns a successful Future into the
    per-record payload (and does any completion-clock host fetch) —
    and returns ``(records, counts, span_s, slip_s)`` where
    ``records[i] = (status, payload_or_None, t_submit)``."""
    n = schedule.n
    done_q = queue.Queue()
    records = [None] * n
    t_last_done = [0.0]

    def waiter():
        got = 0
        while got < n:
            i, t_sub, fut = done_q.get()
            try:
                records[i] = ("ok", on_success(fut.result(), t_sub),
                              t_sub)
            except Exception as e:  # noqa: BLE001 — tallied by class
                from .scheduler import ServeOverloaded, ServeTimeout
                if fut.cancelled():
                    status = "cancelled"
                elif isinstance(e, ServeTimeout):
                    status = "timeout"
                elif isinstance(e, ServeOverloaded):
                    # admission-control shed: structured backpressure,
                    # counted apart from hard errors
                    status = "shed"
                else:
                    status = "error"
                records[i] = (status, None, t_sub)
            t_last_done[0] = time.perf_counter()
            got += 1

    w = threading.Thread(target=waiter, name=thread_name, daemon=True)
    w.start()
    slip = 0.0
    t0 = time.perf_counter()
    for i in range(n):
        due = schedule.arrivals[i]
        now = time.perf_counter() - t0
        if due > now:
            time.sleep(due - now)
        else:
            slip = max(slip, now - due)
        t_sub = time.perf_counter()
        try:
            fut = submit(i)
        except Exception as e:  # noqa: BLE001 — submission refusals
            fut = _failed_future(e)  # classified by the waiter (a shed
            #                          keeps its ServeOverloaded class)
        fut.add_done_callback(
            lambda f, i=i, t=t_sub: done_q.put((i, t, f)))
    w.join(settle_s)
    if w.is_alive():
        raise MXNetError("loadgen waiter did not drain within %.0fs "
                         "(requests lost?)" % settle_s)
    counts = {}
    for r in records:
        counts[r[0] if r else "lost"] = counts.get(
            r[0] if r else "lost", 0) + 1
    span = max(t_last_done[0] - t0, 1e-9)
    return records, counts, span, slip


def run_loadgen(submit, schedule, fetch=True, settle_s=60.0,
                return_records=False):
    """Drive ``submit(i, n_rows) -> Future`` on an open-loop schedule.

    Returns a summary dict: latency percentiles over successful
    requests (submit -> result fetched to host), achieved vs offered
    QPS, and failure counters.  ``max_submit_slip_ms`` reports how far
    the submitting thread itself fell behind the schedule (pacing
    credibility).  ``return_records=True`` additionally returns the
    per-request ``(status, latency_s, t_submit)`` records (perf_counter
    clock) — the failover protocol windows pre/post-kill QPS from them.
    """
    from ..test_utils import fetch_sync

    def on_success(res, t_sub):
        if fetch and res:
            fetch_sync(res[0])
        return time.perf_counter() - t_sub

    records, counts, span, slip = _drive_schedule(
        lambda i: submit(i, int(schedule.sizes[i])), schedule,
        on_success, settle_s, "mxt-loadgen-wait")
    lats = np.asarray([r[1] for r in records if r and r[0] == "ok"])
    ok = counts.get("ok", 0)
    out = {
        "n": schedule.n,
        "ok": ok,
        "timeouts": counts.get("timeout", 0),
        "cancelled": counts.get("cancelled", 0),
        "shed": counts.get("shed", 0),
        "errors": counts.get("error", 0) + counts.get("lost", 0),
        # never-resolved slots on their own (also inside errors for
        # back-compat): the failover protocol's client-hang evidence
        "lost": counts.get("lost", 0),
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3)
        if ok else None,
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3)
        if ok else None,
        "mean_ms": round(float(lats.mean()) * 1e3, 3) if ok else None,
        "max_ms": round(float(lats.max()) * 1e3, 3) if ok else None,
        "qps_offered": round(schedule.qps, 2),
        "qps_achieved": round(ok / span, 2),
        "rows": int(schedule.sizes.sum()),
        "duration_s": round(span, 3),
        "max_submit_slip_ms": round(slip * 1e3, 3),
        "seed": schedule.seed,
    }
    if return_records:
        return out, records
    return out


def _failed_future(exc=None):
    from concurrent.futures import Future
    f = Future()
    f.set_exception(exc if exc is not None
                    else MXNetError("submit refused"))
    return f


class _PerRequestServer:
    """The per-request baseline under open-loop load: one worker thread
    services a FIFO queue by calling ``Predictor.forward`` for every
    request individually (no batching, no buckets) — exactly what a
    naive deployment of ``predictor.py`` does.  Same Future interface
    as the ServingEngine so :func:`run_loadgen` drives both."""

    def __init__(self, predictor, input_name="data"):
        self._pred = predictor
        self._input = input_name
        self._q = queue.Queue()
        self._thread = threading.Thread(target=self._work,
                                        name="mxt-serial-serve",
                                        daemon=True)
        self._thread.start()

    def submit(self, x):
        from concurrent.futures import Future
        fut = Future()
        self._q.put((x, fut))
        return fut

    def _work(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            x, fut = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                outs = self._pred.forward(**{self._input: x})
                # resolve with the device array; the loadgen waiter
                # fetch-syncs it, the same completion clock the
                # batcher's futures get
                fut.set_result([outs[0]._data])
            except BaseException as e:  # noqa: BLE001 — to the future
                fut.set_exception(e)

    def close(self):
        self._q.put(None)
        self._thread.join(30)


def _smoke_model(feat, hidden, seed):
    """Deterministic tiny-MLP symbol + params (shared smoke protocol
    model, test_utils.smoke_mlp shape family)."""
    from ..test_utils import smoke_mlp
    sym = smoke_mlp(num_hidden=hidden)
    shapes, _, _ = sym.infer_shape(data=(1, feat), softmax_label=(1,))
    rs = np.random.RandomState(seed)
    args = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name not in ("data", "softmax_label"):
            args[name] = np.asarray(
                rs.uniform(-0.3, 0.3, shape), np.float32)
    return sym, args


def latency_protocol(mode="fp32", smoke=False, seed=11, offered_mult=6.0,
                     max_delay_ms=2.0, max_batch=32):
    """The serving latency scenario (CPU-deterministic).

    1. **Per-request baseline, closed loop**: ``Predictor.forward`` +
       output fetch back-to-back over deterministic inputs — service
       latency and the per-request capacity ``C`` (QPS ceiling of the
       no-batching deployment).
    2. **Per-request baseline, open loop**: the same Predictor behind a
       FIFO worker, driven by the seeded schedule at
       ``offered_mult x C`` — shows queueing collapse (p99 explodes,
       achieved QPS saturates at ~C).
    3. **Continuous batcher**: registry + ServingEngine (same weights,
       ``mode`` = 'fp32', 'bf16' or 'int8' serving dtype — int8 is
       weight-only through the fused dequant-matmul door) under the
       SAME schedule — achieved QPS tracks the offered load with p99
       far below the saturated baseline.

    Returns ``{"serial_closed", "serial_open", "batch", ...}`` with
    ``qps_vs_per_request`` = batcher achieved QPS / open-loop baseline
    achieved QPS (the >= 3x acceptance figure).
    """
    import mxnet_tpu as mx
    from .registry import ModelRegistry
    from .scheduler import ServingEngine

    if mode not in ("fp32", "bf16", "int8"):
        raise MXNetError("mode must be fp32, bf16 or int8, got %r"
                         % mode)
    # the model must be COMPUTE-dominated for the row to mean anything:
    # at this size a batch-32 forward costs about the same wall time as
    # batch-1 on CPU (the matmuls stream the weights; extra rows ride
    # the vector units), so batching converts per-request service time
    # into pure capacity — the same economics as a TPU serving stack.
    # A faster model would also push the open-loop offered rate past
    # what the submitting thread can pace on a small CPU host.
    feat, hidden = 512, 2048
    n_serial = 40 if smoke else 120
    n_load = 120 if smoke else 400
    sym, args = _smoke_model(feat, hidden, seed)
    rs = np.random.RandomState(seed + 1)
    pool = [np.asarray(rs.uniform(-1, 1, (1, feat)), np.float32)
            for _ in range(16)]

    pred = mx.Predictor(sym.tojson(),
                        {"arg:%s" % k: v for k, v in args.items()},
                        {"data": (1, feat)})
    # closed-loop service measurement (warm first: bind-time compile)
    for i in range(5):
        pred.forward(data=pool[i % len(pool)])
        pred.get_output(0)
    lats = np.empty(n_serial)
    tic = time.perf_counter()
    for i in range(n_serial):
        t = time.perf_counter()
        pred.forward(data=pool[i % len(pool)])
        pred.get_output(0)          # host fetch: the client-visible value
        lats[i] = time.perf_counter() - t
    serial_qps = n_serial / (time.perf_counter() - tic)
    serial_closed = {
        "qps": round(serial_qps, 2),
        "p50_ms": round(float(np.percentile(lats, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 3),
        "n": n_serial,
    }

    offered = serial_qps * float(offered_mult)
    schedule = OpenLoopSchedule(seed, n_load, offered, sizes=(1,))

    # open-loop per-request baseline (fresh schedule replay, same seed)
    serial_srv = _PerRequestServer(pred)
    try:
        serial_open = run_loadgen(
            lambda i, n: serial_srv.submit(pool[i % len(pool)]),
            schedule, fetch=True)
    finally:
        serial_srv.close()

    # continuous batcher on the same seeded schedule
    registry = ModelRegistry()
    registry.add_model(
        "m", sym, args, {}, input_shapes={"data": (1, feat)},
        compute_dtype={"bf16": "bfloat16", "int8": "int8",
                       "fp32": None}[mode],
        warmup=True)
    engine = ServingEngine(registry, max_delay_ms=max_delay_ms,
                           max_batch=max_batch)
    try:
        # warm the batched dispatch path (first multi-request batch pays
        # one-time executable/runtime init that warmup-at-load's
        # compiles don't cover), mirroring the baseline's warmup
        for _ in range(3):
            for f in [engine.submit("m", data=pool[i % len(pool)])
                      for i in range(max_batch)]:
                f.result(60)
        batch = run_loadgen(
            lambda i, n: engine.submit("m", data=pool[i % len(pool)]),
            schedule, fetch=True)
        batch["engine"] = engine.stats()
    finally:
        engine.close()
    ratio = (batch["qps_achieved"] / serial_open["qps_achieved"]
             if serial_open["qps_achieved"] else None)
    return {
        "mode": mode,
        "seed": seed,
        "model": {"feat": feat, "hidden": hidden},
        "serial_closed": serial_closed,
        "serial_open": serial_open,
        "batch": batch,
        "offered_mult": float(offered_mult),
        "max_delay_ms": float(max_delay_ms),
        "max_batch": int(max_batch),
        "qps_vs_per_request": round(ratio, 3) if ratio else None,
        "p99_vs_per_request": (
            round(batch["p99_ms"] / serial_open["p99_ms"], 4)
            if batch["p99_ms"] and serial_open["p99_ms"] else None),
    }


# ---------------------------------------------------------------------------
# Generation loadgen: the decode-plane protocol.
# ---------------------------------------------------------------------------
def run_gen_loadgen(submit, schedule, settle_s=180.0):
    """Drive ``submit(i, max_tokens) -> Future[GenerationResult]`` on an
    open-loop schedule (which must carry ``gen_tokens``).

    Latency clocks come from the result's host-side ``token_times``
    (stamped by the serving engine as each token is sampled), so the
    summary reports the three generation service metrics without
    streaming machinery: **TTFT** (submit -> first token), **ITL**
    (mean/percentile inter-token gap) and **tokens/sec** (total
    generated tokens over the span)."""
    if schedule.max_tokens is None:
        raise MXNetError("run_gen_loadgen needs a schedule built with "
                         "gen_tokens=...")
    records, counts, span, slip = _drive_schedule(
        lambda i: submit(i, int(schedule.max_tokens[i])), schedule,
        lambda res, t_sub: res, settle_s, "mxt-genload-wait")
    ok_recs = [(res, t_sub) for (s, res, t_sub) in
               (r for r in records if r) if s == "ok" and res is not None]
    ok = len(ok_recs)
    n = schedule.n
    ttfts = np.asarray([res.token_times[0] - t_sub
                        for res, t_sub in ok_recs])
    itls = np.asarray([g for res, _ in ok_recs for g in res.itl_s()])
    total_tokens = int(sum(len(res.tokens) for res, _ in ok_recs))
    e2e = np.asarray([res.token_times[-1] - t_sub
                      for res, t_sub in ok_recs])

    def _pct(arr, q):
        return round(float(np.percentile(arr, q)) * 1e3, 3) \
            if arr.size else None

    return {
        "n": n,
        "ok": ok,
        "timeouts": counts.get("timeout", 0),
        "cancelled": counts.get("cancelled", 0),
        "shed": counts.get("shed", 0),
        "errors": counts.get("error", 0) + counts.get("lost", 0),
        "tokens": total_tokens,
        "tokens_per_sec": round(total_tokens / span, 2),
        "ttft_p50_ms": _pct(ttfts, 50),
        "ttft_p99_ms": _pct(ttfts, 99),
        "itl_mean_ms": round(float(itls.mean()) * 1e3, 3)
        if itls.size else None,
        "itl_p99_ms": _pct(itls, 99),
        "e2e_p50_ms": _pct(e2e, 50),
        "e2e_p99_ms": _pct(e2e, 99),
        "qps_offered": round(schedule.qps, 2),
        "qps_achieved": round(ok / span, 2),
        "duration_s": round(span, 3),
        "max_submit_slip_ms": round(slip * 1e3, 3),
        "seed": schedule.seed,
    }


# ---------------------------------------------------------------------------
# Front-door protocols: HTTP overhead, kill-one failover, swap consistency.
# ---------------------------------------------------------------------------
def _frontdoor_model(seed, feat=512, hidden=2048):
    """Shared front-door smoke model + request pool (the latency
    protocol's compute-dominated MLP so batching economics hold)."""
    sym, args = _smoke_model(feat, hidden, seed)
    rs = np.random.RandomState(seed + 1)
    pool = [np.asarray(rs.uniform(-1, 1, (1, feat)), np.float32)
            for _ in range(16)]
    return sym, args, pool, feat


def _engine_capacity(submit_result, n):
    """Closed-loop requests/sec of one submit->result roundtrip loop
    (the pacing anchor the open-loop schedules scale from)."""
    tic = time.perf_counter()
    for i in range(n):
        submit_result(i)
    return n / (time.perf_counter() - tic)


def frontdoor_protocol(smoke=False, seed=17, offered_mult=2.0):
    """HTTP-overhead protocol: the SAME engine, the SAME seeded
    open-loop schedule, driven twice — in-process ``submit`` futures
    vs the HTTP front door through :class:`~.frontdoor.HttpClient`'s
    npz transport.  The delta is pure front-door cost (parse + HTTP +
    npz round-trip); the offered rate is a moderate multiple of the
    closed-loop per-request capacity so neither side saturates and the
    p50/p99 gap reads as overhead, not queueing."""
    from .frontdoor import HttpClient, HttpFrontDoor
    from .registry import ModelRegistry
    from .scheduler import ServingEngine

    sym, args, pool, feat = _frontdoor_model(seed)
    n_closed = 30 if smoke else 80
    n_load = 120 if smoke else 400
    registry = ModelRegistry()
    registry.add_model("m", sym, args, {},
                       input_shapes={"data": (1, feat)}, warmup=True)
    engine = ServingEngine(registry, max_delay_ms=2.0)
    door = HttpFrontDoor(engine)
    client = HttpClient(door.address, threads=8)
    try:
        # both transports warm before any measurement
        for _ in range(2):
            engine.submit("m", data=pool[0]).result(60)
            client.submit("m", {"data": pool[0]}).result(60)
        closed_qps = _engine_capacity(
            lambda i: engine.submit(
                "m", data=pool[i % len(pool)]).result(60), n_closed)
        http_closed_qps = _engine_capacity(
            lambda i: client.submit(
                "m", {"data": pool[i % len(pool)]}).result(60), n_closed)
        # anchor on the SLOWER transport's closed-loop capacity: both
        # sides must sustain the offered rate, or the HTTP side's p99
        # measures queueing collapse instead of transport overhead
        offered = min(closed_qps, http_closed_qps) * float(offered_mult)
        schedule = OpenLoopSchedule(seed, n_load, offered, sizes=(1,))
        inproc = run_loadgen(
            lambda i, n: engine.submit("m", data=pool[i % len(pool)]),
            schedule, fetch=True)
        http = run_loadgen(
            lambda i, n: client.submit(
                "m", {"data": pool[i % len(pool)]}),
            schedule, fetch=True)
        stats = engine.stats()
    finally:
        client.close()
        door.close()
        engine.close()
    return {
        "seed": seed,
        "closed_loop_qps": round(closed_qps, 2),
        "http_closed_loop_qps": round(http_closed_qps, 2),
        "offered_mult": float(offered_mult),
        "inproc": inproc,
        "http": http,
        "engine": stats,
        "http_p50_overhead_ms": (
            round(http["p50_ms"] - inproc["p50_ms"], 3)
            if http["p50_ms"] is not None and inproc["p50_ms"] is not None
            else None),
        "http_p99_vs_inproc": (
            round(http["p99_ms"] / inproc["p99_ms"], 3)
            if http["p99_ms"] and inproc["p99_ms"] else None),
        "http_qps_vs_inproc": (
            round(http["qps_achieved"] / inproc["qps_achieved"], 3)
            if inproc["qps_achieved"] else None),
    }


def failover_protocol(smoke=False, seed=19, n_replicas=3,
                      offered_mult=2.0, kill_frac=0.4,
                      probe_interval=0.15):
    """Kill-one-replica-under-load: N shared-nothing replicas behind
    the least-loaded balancer, the seeded open-loop schedule offering
    a multiple of closed-loop capacity, and a seeded ``die`` at the
    ``serve.dispatch`` faultinject seam SIGKILLing whichever replica
    serves the ``kill_frac``-th dispatch.  Acceptance (the
    ``serve_smoke --kill-one`` gate): 100% of accepted requests
    resolve (zero drops, zero hangs), the balancer converges to the
    survivors, and achieved QPS over the post-kill window (beginning
    one probe interval after the kill) recovers to >= 2/3 of the
    pre-kill steady state."""
    from .. import faultinject
    from .registry import ModelRegistry
    from .replica_set import ReplicaSet

    sym, args, pool, feat = _frontdoor_model(seed)
    n_closed = 20 if smoke else 60
    n_load = 150 if smoke else 400

    def build(_i):
        reg = ModelRegistry()
        # each replica loads its OWN weight copy: shared-nothing
        reg.add_model("m", sym, {k: v.copy() for k, v in args.items()},
                      {}, input_shapes={"data": (1, feat)}, warmup=True)
        return reg

    rset = ReplicaSet(build, n_replicas=n_replicas,
                      probe_interval=probe_interval, max_delay_ms=2.0)
    kill_t = [None]
    die_inner = rset._injected_die

    def noting_die(meta):
        if kill_t[0] is None:
            kill_t[0] = time.perf_counter()
        die_inner(meta)

    try:
        for _ in range(2):
            rset.submit("m", data=pool[0]).result(60)
        closed_qps = _engine_capacity(
            lambda i: rset.submit(
                "m", data=pool[i % len(pool)]).result(60), n_closed)
        # the run must span several probe intervals with completions on
        # both sides of the kill, or the pre/post windows are too thin
        # to read a recovery from — floor the duration
        min_duration = 4.0 if smoke else 8.0
        offered = min(closed_qps * float(offered_mult),
                      n_load / min_duration)
        schedule = OpenLoopSchedule(seed, n_load, offered, sizes=(1,))
        kill_nth = max(2, int(n_load * float(kill_frac)))
        faultinject.install({"seed": seed, "rules": [
            {"seam": "serve.dispatch", "kind": "forward",
             "nth": kill_nth, "action": "die"}]})
        faultinject.register_die_handler("serve.dispatch", noting_die)
        summary, records = run_loadgen(
            lambda i, n: rset.submit("m", data=pool[i % len(pool)]),
            schedule, fetch=True, return_records=True)
        stats = rset.stats()
        live_after = rset.live_replicas()
    finally:
        faultinject.install(None)
        # drop the kill-time-noting wrapper so rset.close()'s
        # own-handler check cannot leave it dangling
        faultinject.register_die_handler("serve.dispatch", None)
        rset.close()

    # window the achieved QPS around the kill moment (completion clock
    # = t_submit + latency on the shared perf_counter timeline)
    done_ts = sorted(t_sub + lat for status, lat, t_sub in
                     (r for r in records if r) if status == "ok")
    out = {
        "seed": seed,
        "n_replicas": n_replicas,
        "probe_interval_s": probe_interval,
        "closed_loop_qps": round(closed_qps, 2),
        "offered_mult": float(offered_mult),
        "kill_nth_dispatch": kill_nth,
        "summary": summary,
        # a shed IS a resolution (structured 429, not a hang) but is
        # reported on its own — it is neither a success nor a drop.
        # "lost" slots (a future that never resolved) are the client
        # hangs the acceptance forbids, so they are NOT resolved
        "resolved": summary["ok"] + summary["timeouts"] +
        summary["cancelled"] + summary["errors"] + summary["shed"] -
        summary["lost"],
        "shed": summary["shed"],
        "dropped": summary["timeouts"] + summary["errors"] +
        summary["cancelled"],
        "failovers": stats["failovers"], "retries": stats["retries"],
        "live_after": live_after,
    }
    if kill_t[0] is not None and done_ts:
        k = kill_t[0]
        pre = [t for t in done_ts if t < k]
        post = [t for t in done_ts if t >= k + probe_interval]
        pre_qps = (len(pre) / max(pre[-1] - done_ts[0], 1e-9)
                   if len(pre) > 1 else None)
        post_qps = (len(post) / max(done_ts[-1] - (k + probe_interval),
                                    1e-9)
                    if len(post) > 1 else None)
        nxt = next((t for t in done_ts if t >= k), None)
        out.update({
            "killed": True,
            "pre_kill_qps": round(pre_qps, 2) if pre_qps else None,
            "post_kill_qps": round(post_qps, 2) if post_qps else None,
            "post_vs_pre_qps": (round(post_qps / pre_qps, 3)
                                if pre_qps and post_qps else None),
            "recovery_ms": (round((nxt - k) * 1e3, 3)
                            if nxt is not None else None),
        })
    else:
        out["killed"] = kill_t[0] is not None
    return out


def observability_protocol(smoke=False, seed=29, offered_mult=2.0):
    """Telemetry overhead protocol: the SAME model and the SAME seeded
    open-loop schedule, served three times with different telemetry settings —

    1. **baseline** — everything off (``MXNET_METRICS=0``,
       ``MXNET_TRACE_SAMPLE=0``, ``MXNET_FLIGHT_CAPACITY=0``): the
       untelemetered engine;
    2. **full** — the DEFAULTS (metrics on, trace sampling 1.0, flight
       ring on) plus a live JSONL trace sink, i.e. every request fully
       traced and exported;
    3. **sample0** — metrics on but ``MXNET_TRACE_SAMPLE=0``: the
       sampling knob's escape hatch.

    Each side measures closed-loop capacity (best of two passes —
    the direct overhead evidence: every submit/resolve pays the
    telemetry cost back to back) and the open-loop p50/p99 on the
    shared schedule.  Acceptance: full/baseline capacity >= 0.95 and
    p99 <= 1.10; sample0 restores baseline within noise."""
    import os
    import tempfile

    from .. import tracing as tracing_mod
    from .registry import ModelRegistry
    from .scheduler import ServingEngine

    _ENV_KEYS = ("MXNET_METRICS", "MXNET_TRACE_SAMPLE",
                 "MXNET_FLIGHT_CAPACITY", "MXNET_TRACE_JSONL")
    sym, args = _smoke_model(512, 2048, seed)
    feat = 512
    rs = np.random.RandomState(seed + 1)
    pool = [np.asarray(rs.uniform(-1, 1, (1, feat)), np.float32)
            for _ in range(16)]
    n_closed = 30 if smoke else 80
    n_load = 100 if smoke else 300

    def run_side(env, sink=None):
        saved = {k: os.environ.pop(k, None) for k in _ENV_KEYS}
        os.environ.update(env)
        tracing_mod.reset_flight()
        tracing_mod.set_jsonl_sink(sink)
        try:
            registry = ModelRegistry()
            registry.add_model("m", sym,
                               {k: v.copy() for k, v in args.items()},
                               {}, input_shapes={"data": (1, feat)},
                               warmup=True)
            engine = ServingEngine(registry, max_delay_ms=2.0)
            try:
                for _ in range(3):
                    for f in [engine.submit("m",
                                            data=pool[i % len(pool)])
                              for i in range(8)]:
                        f.result(60)
                closed = max(_engine_capacity(
                    lambda i: engine.submit(
                        "m", data=pool[i % len(pool)]).result(60),
                    n_closed) for _ in range(2))
                schedule = OpenLoopSchedule(seed, n_load, offered,
                                            sizes=(1,))
                open_sum = run_loadgen(
                    lambda i, n: engine.submit(
                        "m", data=pool[i % len(pool)]),
                    schedule, fetch=True)
            finally:
                engine.close()
        finally:
            tracing_mod.set_jsonl_sink(None)
            os.environ.update(
                {k: v for k, v in saved.items() if v is not None})
            for k in _ENV_KEYS:
                if saved.get(k) is None:
                    os.environ.pop(k, None)
            tracing_mod.reset_flight()
        return {"closed_qps": round(closed, 2),
                "p50_ms": open_sum["p50_ms"],
                "p99_ms": open_sum["p99_ms"],
                "qps_achieved": open_sum["qps_achieved"],
                "dropped": open_sum["timeouts"] + open_sum["errors"] +
                open_sum["cancelled"]}

    # anchor the shared offered rate BELOW saturation so the open-loop
    # sides compare overhead, not queueing (a quick untelemetered
    # capacity probe sets it)
    probe_reg = ModelRegistry()
    probe_reg.add_model("m", sym, args, {},
                        input_shapes={"data": (1, feat)}, warmup=True)
    probe = ServingEngine(probe_reg, max_delay_ms=2.0)
    try:
        for f in [probe.submit("m", data=pool[i % len(pool)])
                  for i in range(8)]:
            f.result(60)
        offered = _engine_capacity(
            lambda i: probe.submit(
                "m", data=pool[i % len(pool)]).result(60),
            n_closed) * float(offered_mult)
    finally:
        probe.close()

    baseline = run_side({"MXNET_METRICS": "0", "MXNET_TRACE_SAMPLE": "0",
                         "MXNET_FLIGHT_CAPACITY": "0"})
    sink = os.path.join(tempfile.mkdtemp(prefix="mxt_obs_"),
                        "traces.jsonl")
    full = run_side({}, sink=sink)
    traces = 0
    if os.path.exists(sink):
        with open(sink) as f:
            traces = sum(1 for _ in f)
    sample0 = run_side({"MXNET_TRACE_SAMPLE": "0"})

    def ratio(a, b, inv=False):
        if not a or not b:
            return None
        return round((a / b) if not inv else (b / a), 4)

    return {
        "seed": seed,
        "offered_mult": float(offered_mult),
        "n_load": n_load,
        "baseline": baseline,
        "full": full,
        "sample0": sample0,
        "traces_exported": traces,
        # capacity ratios >= is better; p99 ratios <= is better
        "qps_full_vs_baseline": ratio(full["closed_qps"],
                                      baseline["closed_qps"]),
        "p99_full_vs_baseline": ratio(full["p99_ms"],
                                      baseline["p99_ms"]),
        "qps_sample0_vs_baseline": ratio(sample0["closed_qps"],
                                         baseline["closed_qps"]),
        "p99_sample0_vs_baseline": ratio(sample0["p99_ms"],
                                         baseline["p99_ms"]),
    }


def swap_protocol(smoke=False, seed=23):
    """Hot-swap-under-traffic bit-consistency: one engine under
    concurrent submit threads while ``swap_params`` republishes a
    second weight set mid-stream.  Geometry is bucket-pinned (single
    batch bucket) so every response is bit-comparable to reference
    forwards of the two versions; the acceptance is an exact
    partition — every response bit-matches the OLD or the NEW weights'
    forward, none matches neither (a torn read would), and the store's
    version counter advances exactly once per swap."""
    from .registry import ModelRegistry
    from .scheduler import ServingEngine

    sym, args, pool, feat = _frontdoor_model(seed, feat=128, hidden=256)
    rs = np.random.RandomState(seed + 7)
    args2 = {k: np.asarray(v + rs.uniform(0.05, 0.1, v.shape),
                           np.float32) for k, v in args.items()}
    n_requests = 120 if smoke else 400
    x = pool[0]
    registry = ModelRegistry()
    # single bucket edge: every dispatch runs the same program at the
    # same batch geometry, so fp32 outputs are bit-comparable across
    # the whole run (cross-bucket XLA fusion differences would muddy
    # the exact old-xor-new partition this protocol asserts)
    store = registry.add_model("m", sym, args, {},
                               input_shapes={"data": (1, feat)},
                               buckets=(1,), warmup=True)
    engine = ServingEngine(registry, max_delay_ms=0)
    try:
        ref_old = np.asarray(
            engine.submit("m", data=x).result(60)[0])
        version_before = store.stats()["version"]
        # a submitter thread streams the traffic while the main thread
        # swaps once a third of the RESPONSES have resolved (swapping
        # at a submission index is meaningless — on a warm host the
        # whole stream can enqueue before the engine serves anything):
        # the first third is guaranteed old-version, the last third is
        # submitted only after the swap returned so it is guaranteed
        # new-version, and the middle third lands on whichever side of
        # the publish its dispatch read — every response must still
        # bit-match exactly one side
        futs = []
        done = [0]
        done_lock = threading.Lock()

        def on_done(_f):
            with done_lock:
                done[0] += 1

        swapped = threading.Event()

        def submitter():
            for i in range(n_requests):
                if i == (2 * n_requests) // 3:
                    swapped.wait(60)
                f = engine.submit("m", data=x)
                f.add_done_callback(on_done)
                futs.append(f)
                time.sleep(0.001)

        t = threading.Thread(target=submitter, name="mxt-swap-submit")
        t.start()
        deadline = time.monotonic() + 60
        while done[0] < n_requests // 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        registry.swap_params("m", args2)
        swapped.set()
        t.join(60)
        ref_new = np.asarray(
            engine.submit("m", data=x).result(60)[0])
        counts = {"old": 0, "new": 0, "neither": 0}
        for f in futs:
            r = np.asarray(f.result(60)[0])
            if np.array_equal(r, ref_old):
                counts["old"] += 1
            elif np.array_equal(r, ref_new):
                counts["new"] += 1
            else:
                counts["neither"] += 1
        version_after = store.stats()["version"]
    finally:
        engine.close()
    return {
        "seed": seed,
        "n": n_requests,
        "old": counts["old"], "new": counts["new"],
        "neither": counts["neither"],
        "version_before": version_before,
        "version_after": version_after,
        "version_increments": version_after - version_before,
    }


# ---------------------------------------------------------------------------
# Control-plane protocols: autoscaling, rolling swap, chaos campaign.
# ---------------------------------------------------------------------------
def autoscale_protocol(smoke=False, seed=31, shape="diurnal",
                       max_replicas=3):
    """SLO-driven autoscaling vs static max-size provisioning.

    The data plane is pinned to per-request service (``max_batch=1``)
    with a PACED dispatch hook: every replica's engine sleeps a fixed
    ``service_s`` per dispatch (the engine's test seam, on the engine
    thread — it releases the GIL), modeling a replica-private
    accelerator.  A compute-bound model cannot prove replica scaling
    on a small CI host — N engine threads would share the same cores
    and N replicas would add no capacity; the paced floor makes
    capacity genuinely linear in the replica count, so one replica's
    capacity IS the measured closed-loop anchor and the shaped
    schedules (``OpenLoopSchedule.diurnal`` /
    ``OpenLoopSchedule.bursty``) overload it deterministically at peak:
    the peak rate needs more than one replica, the trough fits in one.
    The SAME seeded schedule is served twice —

    1. **autoscaled**: a 1-replica set under an :class:`~.controller.
       AutoScaler` (bounded ``max_replicas``), which must walk the set
       up the ramp and back down it;
    2. **static**: ``max_replicas`` replicas for the whole run — the
       provisioning the autoscaler's replica-seconds are priced
       against.

    The autoscaled side runs with a warm spare pool
    (``ReplicaSet(spares=max_replicas - 1)``): scale-up joins a
    prebuilt registry in milliseconds instead of compiling on the
    controller thread mid-swing.  Spares are idle weights — no engine
    threads — so the replica-seconds comparison still prices live
    serving capacity.

    Acceptance (the ``chaos_campaign`` autoscale gate): the
    autoscaled side's queue-wait p95 stays under the SLO, with zero
    lost requests and strictly fewer replica-seconds than static
    max-size provisioning over the same span."""
    from .. import metrics as _metrics
    from .controller import AutoScaler
    from .registry import ModelRegistry
    from .replica_set import ReplicaSet
    from .scheduler import _H_QWAIT

    sym, args, pool, feat = _frontdoor_model(seed)
    n_closed = 20 if smoke else 40
    cap_inflight = 32
    # the per-dispatch service floor: ~50 req/s per replica, cheap on
    # the CPU (the engine thread sleeps, the GIL is free), and long
    # enough that the 2.2x peak rate is trivially pace-able for the
    # open-loop submit thread
    service_s = 0.02

    def build(_i):
        reg = ModelRegistry()
        reg.add_model("m", sym, {k: v.copy() for k, v in args.items()},
                      {}, input_shapes={"data": (1, feat)}, warmup=True)
        return reg

    def _paced_hook(_model, _reqs):
        time.sleep(service_s)

    class _PacedSet(ReplicaSet):
        # every replica — initial, spare-grown, factory-grown — gets
        # the paced dispatch floor the moment its engine exists
        def _new_replica(self, index, reg):
            r = ReplicaSet._new_replica(self, index, reg)
            r.engine._dispatch_hook = _paced_hook
            return r

    def make_set(n, spares=0):
        return _PacedSet(build, n_replicas=n, probe_interval=0.1,
                         max_delay_ms=2.0, max_batch=1,
                         max_inflight=cap_inflight, spares=spares)

    # single-replica per-request capacity: the schedule's rate anchor.
    # np.asarray on the output BLOCKS on the device value — without it
    # the loop would clock the async dispatch rate, not service
    probe = make_set(1)
    try:
        for _ in range(2):
            np.asarray(probe.submit("m", data=pool[0]).result(60)[0])
        closed_qps = _engine_capacity(
            lambda i: np.asarray(probe.submit(
                "m", data=pool[i % len(pool)]).result(60)[0]),
            n_closed)
    finally:
        probe.close()

    high = closed_qps * 2.2       # > one replica, < max_replicas
    low = closed_qps * 0.25       # the trough fits in one
    duration = 4.0 if smoke else 8.0
    mean = (low + high) / 2.0
    n_load = int(min(2500, max(200, mean * duration)))
    if shape == "diurnal":
        period = max(duration, n_load / mean)
        schedule = OpenLoopSchedule.diurnal(
            seed, n_load, low_qps=low, high_qps=high, period_s=period)
    elif shape == "bursty":
        span = max(duration, n_load / mean)
        schedule = OpenLoopSchedule.bursty(
            seed, n_load, idle_qps=low, burst_qps=high,
            burst_s=span / 4.0, idle_s=span / 4.0)
    else:
        raise MXNetError("shape must be 'diurnal' or 'bursty', got %r"
                         % (shape,))
    # SLO: a generous multiple of the time one replica needs to drain a
    # full admission window serially — capacity-relative, so the gate
    # holds on slow CI hosts too
    slo_ms = max(100.0, 2.5 * cap_inflight * 1e3 / closed_qps)

    def run_side(rset, scaler=None):
        t0 = time.monotonic()
        window = _metrics.HistogramWindow(_H_QWAIT)
        summary = run_loadgen(
            lambda i, n: rset.submit("m", data=pool[i % len(pool)]),
            schedule, fetch=True)
        _, _, quantile = window.tick()
        p95 = quantile(0.95)
        summary["qwait_p95_ms"] = (None if p95 is None
                                   else round(p95 * 1e3, 3))
        if scaler is not None:
            # let the controller walk back down before the books close
            deadline = time.monotonic() + (2.0 if smoke else 4.0)
            while rset.n_replicas() > 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            summary["replica_seconds"] = round(
                scaler.replica_seconds(), 3)
        else:
            summary["replica_seconds"] = round(
                rset.n_replicas() * (time.monotonic() - t0), 3)
        return summary

    # side 1: autoscaled from one replica, spares prebuilt so the
    # controller's scale-up is instant
    rset = make_set(1, spares=max_replicas - 1)
    scaler = AutoScaler(rset, slo_ms=slo_ms, min_replicas=1,
                        max_replicas=max_replicas, interval=0.05,
                        cooldown=0.25, start=True)
    try:
        for _ in range(2):
            rset.submit("m", data=pool[0]).result(60)
        auto = run_side(rset, scaler)
        actions = [(a, n) for _t, a, n in scaler.actions()]
    finally:
        scaler.close()
        rset.close()

    # side 2: static max-size provisioning, same schedule
    static = make_set(max_replicas)
    try:
        for _ in range(2):
            static.submit("m", data=pool[0]).result(60)
        static_sum = run_side(static)
    finally:
        static.close()

    n_peak = max([n for _a, n in actions] or [1])
    return {
        "seed": seed,
        "shape": schedule.shape,
        "closed_loop_qps": round(closed_qps, 2),
        "low_qps": round(low, 2), "high_qps": round(high, 2),
        "n_load": n_load,
        "slo_ms": round(slo_ms, 1),
        "max_replicas": max_replicas,
        "auto": auto,
        "static": static_sum,
        "actions": actions,
        "n_peak_replicas": n_peak,
        "scaled_up": any(a == "up" for a, _n in actions),
        "scaled_down": any(a == "down" for a, _n in actions),
        "p95_under_slo": (auto["qwait_p95_ms"] is not None
                          and auto["qwait_p95_ms"] <= slo_ms),
        "replica_seconds_vs_static": (
            round(auto["replica_seconds"] /
                  static_sum["replica_seconds"], 3)
            if static_sum["replica_seconds"] else None),
    }


def rolling_swap_protocol(smoke=False, seed=37, n_replicas=3):
    """Rolling-swap-under-traffic coherence: the replica set's
    drain -> swap -> re-probe roll under a concurrent submit stream.

    Same bucket-pinned bit-consistency discipline as
    :func:`swap_protocol`, lifted to N shared-nothing replicas: a
    submitter thread streams requests through the balancer while the
    main thread performs ONE rolling ``swap_params``.  Acceptance:
    ZERO failed requests (the drained replica's share rides the rest of
    the rotation), every response bit-matches the old or the new
    weights' reference forward (never a mix — coherent weight sets all
    the way through the roll), and every live replica's store advanced
    exactly one version."""
    from .registry import ModelRegistry
    from .replica_set import ReplicaSet

    sym, args, pool, feat = _frontdoor_model(seed, feat=128, hidden=256)
    rs = np.random.RandomState(seed + 7)
    args2 = {k: np.asarray(v + rs.uniform(0.05, 0.1, v.shape),
                           np.float32) for k, v in args.items()}
    n_requests = 120 if smoke else 400
    x = pool[0]

    def build(_i):
        reg = ModelRegistry()
        # single batch bucket: every replica compiles the same program
        # at the same geometry, so fp32 outputs are bit-comparable
        # across replicas AND across the swap
        reg.add_model("m", sym, {k: v.copy() for k, v in args.items()},
                      {}, input_shapes={"data": (1, feat)},
                      buckets=(1,), warmup=True)
        return reg

    rset = ReplicaSet(build, n_replicas=n_replicas, probe_interval=0.1,
                      max_delay_ms=0)
    try:
        ref_old = np.asarray(rset.submit("m", data=x).result(60)[0])
        futs = []
        done = [0]
        done_lock = threading.Lock()

        def on_done(_f):
            with done_lock:
                done[0] += 1

        swapped = threading.Event()

        def submitter():
            for i in range(n_requests):
                if i == (2 * n_requests) // 3:
                    swapped.wait(60)
                f = rset.submit("m", data=x)
                f.add_done_callback(on_done)
                futs.append(f)
                time.sleep(0.001)

        t = threading.Thread(target=submitter,
                             name="mxt-rollswap-submit")
        t.start()
        deadline = time.monotonic() + 60
        while done[0] < n_requests // 3 and time.monotonic() < deadline:
            time.sleep(0.001)
        versions = rset.swap_params("m", args2)
        swapped.set()
        t.join(60)
        ref_new = np.asarray(rset.submit("m", data=x).result(60)[0])
        counts = {"old": 0, "new": 0, "neither": 0, "failed": 0}
        for f in futs:
            try:
                r = np.asarray(f.result(60)[0])
            except Exception:  # noqa: BLE001 — the zero-failed gate
                counts["failed"] += 1
                continue
            if np.array_equal(r, ref_old):
                counts["old"] += 1
            elif np.array_equal(r, ref_new):
                counts["new"] += 1
            else:
                counts["neither"] += 1
        stats = rset.stats()
    finally:
        rset.close()
    return {
        "seed": seed,
        "n": n_requests,
        "n_replicas": n_replicas,
        "old": counts["old"], "new": counts["new"],
        "neither": counts["neither"], "failed": counts["failed"],
        "versions": versions,
        "replicas_swapped": len(versions),
        "retries": stats["retries"],
    }


def chaos_protocol(smoke=False, seed=41, n_replicas=3,
                   offered_mult=1.5, recovery_slo_ms=2000.0):
    """Multi-fault chaos campaign against the full serving stack:
    ``HttpClient`` -> :class:`~.frontdoor.HttpFrontDoor` ->
    autoscaled :class:`~.replica_set.ReplicaSet` -> engines.

    One seeded faultinject schedule composes THREE faults at the
    ``serve.dispatch`` seam mid-run: a ``straggler`` (two slow
    dispatches), a ``die`` (SIGKILL of whichever replica serves the
    targeted dispatch), and an ``error`` burst (two severed-connection
    dispatches).  An :class:`~.controller.AutoScaler` rides along, so
    the shed/utilization signals may replace the killed capacity.

    Gates (``tools/chaos_campaign.py`` and ``make chaos-smoke`` enforce
    them): every fault in the schedule fired; ZERO lost requests (every
    accepted future resolved — structured sheds/timeouts are
    resolutions); first post-kill completion inside ``recovery_slo_ms``;
    and retried requests keep CONNECTED traces — with tracing at full
    sampling, at least one exported trace carries the failed placement
    AND the successful one under one trace id (a ``serve_retry`` span
    next to a ``serve_dispatch`` span, or two or more
    ``serve_dispatch`` spans when the failover re-dispatched) whenever
    the balancer retried at all."""
    import json as _json
    import os
    import tempfile

    from .. import faultinject
    from .. import tracing as tracing_mod
    from .controller import AutoScaler
    from .frontdoor import HttpClient, HttpFrontDoor
    from .registry import ModelRegistry
    from .replica_set import ReplicaSet

    sym, args, pool, feat = _frontdoor_model(seed)
    n_closed = 20 if smoke else 40
    n_load = 150 if smoke else 400

    def build(_i):
        reg = ModelRegistry()
        reg.add_model("m", sym, {k: v.copy() for k, v in args.items()},
                      {}, input_shapes={"data": (1, feat)}, warmup=True)
        return reg

    sink = os.path.join(tempfile.mkdtemp(prefix="mxt_chaos_"),
                        "traces.jsonl")
    saved_sample = os.environ.pop("MXNET_TRACE_SAMPLE", None)
    os.environ["MXNET_TRACE_SAMPLE"] = "1"
    tracing_mod.set_jsonl_sink(sink)
    rset = ReplicaSet(build, n_replicas=n_replicas, probe_interval=0.1,
                      max_delay_ms=2.0, max_inflight=32)
    scaler = AutoScaler(rset, slo_ms=200.0, min_replicas=n_replicas,
                        max_replicas=n_replicas + 1, interval=0.1,
                        cooldown=0.4, start=True)
    door = HttpFrontDoor(rset)
    client = HttpClient(door.address, threads=8)
    kill_t = [None]
    die_inner = rset._injected_die

    def noting_die(meta):
        if kill_t[0] is None:
            kill_t[0] = time.perf_counter()
        die_inner(meta)

    try:
        for _ in range(2):
            client.submit("m", {"data": pool[0]}).result(60)
        closed_qps = _engine_capacity(
            lambda i: client.submit(
                "m", {"data": pool[i % len(pool)]}).result(60), n_closed)
        min_duration = 4.0 if smoke else 8.0
        offered = min(closed_qps * float(offered_mult),
                      n_load / min_duration)
        schedule = OpenLoopSchedule(seed, n_load, offered, sizes=(1,))
        # the composed fault schedule, in dispatch order: slow, kill,
        # sever — one seeded spec, replayable byte-for-byte
        faults = [
            {"seam": "serve.dispatch", "kind": "forward",
             "nth": max(2, int(n_load * 0.15)), "count": 2,
             "action": "straggler", "seconds": 0.25},
            {"seam": "serve.dispatch", "kind": "forward",
             "nth": max(3, int(n_load * 0.35)), "action": "die"},
            {"seam": "serve.dispatch", "kind": "forward",
             "nth": max(4, int(n_load * 0.55)), "count": 2,
             "action": "error"},
        ]
        plan = faultinject.install({"seed": seed, "rules": faults})
        faultinject.register_die_handler("serve.dispatch", noting_die)
        summary, records = run_loadgen(
            lambda i, n: client.submit(
                "m", {"data": pool[i % len(pool)]}, timeout=30.0),
            schedule, fetch=True, return_records=True)
        fired = list(plan.log)
        stats = rset.stats()
        live_after = rset.live_replicas()
        actions = [(a, n) for _t, a, n in scaler.actions()]
    finally:
        faultinject.install(None)
        faultinject.register_die_handler("serve.dispatch", None)
        scaler.close()
        client.close()
        door.close()
        rset.close()
        tracing_mod.set_jsonl_sink(None)
        if saved_sample is None:
            os.environ.pop("MXNET_TRACE_SAMPLE", None)
        else:
            os.environ["MXNET_TRACE_SAMPLE"] = saved_sample

    # trace connectivity: parse the JSONL sink; a retried request's
    # placement attempts are spans of ONE trace — the failed attempt
    # leaves a serve_retry span, the serving one a serve_dispatch span
    # (a failover's re-dispatch leaves a second serve_dispatch)
    traces = []
    if os.path.exists(sink):
        with open(sink) as f:
            for line in f:
                try:
                    traces.append(_json.loads(line))
                except ValueError:
                    pass
    http_traces = [t for t in traces if t.get("name") == "http.predict"]

    def _connected_retry(t):
        names = [s.get("name") for s in t.get("spans", [])]
        dispatches = sum(1 for n in names if n == "serve_dispatch")
        return dispatches >= 2 or (dispatches >= 1
                                   and "serve_retry" in names)

    multi_dispatch = [t for t in http_traces if _connected_retry(t)]
    recovery_ms = None
    if kill_t[0] is not None:
        done_ts = sorted(t_sub + lat for status, lat, t_sub in
                         (r for r in records if r) if status == "ok")
        nxt = next((t for t in done_ts if t >= kill_t[0]), None)
        if nxt is not None:
            recovery_ms = round((nxt - kill_t[0]) * 1e3, 3)
    fired_actions = sorted(a for _s, _k, _r, _sid, a in fired)
    gates = {
        "all_faults_fired": fired_actions == sorted(
            f["action"] for f in faults for _ in range(f.get("count", 1))),
        "zero_lost": summary["lost"] == 0,
        "recovery_within_slo": (recovery_ms is not None
                                and recovery_ms <= recovery_slo_ms),
        "retry_traces_connected": (stats["retries"] == 0
                                   or len(multi_dispatch) >= 1),
    }
    return {
        "seed": seed,
        "n_replicas": n_replicas,
        "closed_loop_qps": round(closed_qps, 2),
        "offered_mult": float(offered_mult),
        "summary": summary,
        "resolved": summary["ok"] + summary["timeouts"] +
        summary["cancelled"] + summary["errors"] + summary["shed"] -
        summary["lost"],
        "faults_fired": fired,
        "killed": kill_t[0] is not None,
        "recovery_ms": recovery_ms,
        "recovery_slo_ms": float(recovery_slo_ms),
        "retries": stats["retries"],
        "failovers": stats["failovers"],
        "live_after": live_after,
        "autoscale_actions": actions,
        "traces_exported": len(traces),
        "retried_traces_connected": len(multi_dispatch),
        "gates": gates,
        "passed": all(gates.values()),
    }
