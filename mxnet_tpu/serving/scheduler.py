"""Continuous batching scheduler: the serving engine thread.

One engine thread drains a request queue into serving dispatches:

* the queue head opens a batch and starts its **latency budget** clock
  (``MXNET_SERVE_MAX_DELAY_MS``, measured from the head's submit time —
  a request is never delayed longer than the budget for the sake of a
  fuller batch);
* while the budget lasts, later requests for the *same model* join until
  the batch reaches ``MXNET_SERVE_MAX_BATCH`` rows (or the model's
  largest shape bucket, whichever is smaller); requests for other models
  park in a pending deque, keeping per-model FIFO order;
* the batch is concatenated, padded to its bucket by the program store,
  and dispatched through the AOT-compiled program; per-request row
  slices resolve each request's Future.  Everything on the engine thread
  is enqueue-only device work (``@hot_path`` — graft-lint rejects host
  syncs here); clients fetch results on their own threads.

Requests carry optional deadlines (``timeout=``): one that expires while
queued gets :class:`ServeTimeout` instead of compute.  ``Future.cancel()``
on a queued request is honored at batch-forming time.  ``close()``
drains: everything already submitted still runs, then the thread joins;
later submits raise :class:`ServeClosed`.

Profiler: each cycle emits ``serve_wait`` (blocked on the queue),
``serve_batch`` (batch forming, the latency-budget wait) and
``serve_compute`` (dispatch + future resolution) spans through the
step-phase seam (``profiler.phase``), so a Chrome trace shows the
batcher's duty cycle against the op spans inside it.
"""
from __future__ import annotations

import collections
import queue
import sys
import threading
import time
from concurrent.futures import Future, InvalidStateError

import jax
import numpy as np

from .. import metrics as _metrics
from .. import profiler as _profiler
from .. import tracing as _tracing
from ..analysis import racecheck
from ..analysis.lockcheck import make_lock
from ..base import MXNetError, _uid, get_env, hot_path

# Aggregate serving histograms (process-wide: every engine feeds them;
# per-engine counts live on the labeled serve_*_total counters).  The
# ambient observes are gated on MXNET_METRICS like the phase feed.
_H_LATENCY = _metrics.histogram(
    "serve_latency_seconds",
    help="forward request latency, submit to resolution")
_H_QWAIT = _metrics.histogram(
    "serve_queue_wait_seconds",
    help="forward request time-in-queue, submit to dispatch")
_H_BATCH = _metrics.histogram(
    "serve_batch_fill_rows", lo=1.0, hi=65536.0,
    help="rows coalesced into one serving dispatch")

__all__ = ["ServingEngine", "ServeRequest", "ServeTimeout", "ServeClosed",
           "ServeOverloaded", "FutureCompleter", "TIERS"]

_STOP = object()


class FutureCompleter:
    """Future resolution on a dedicated thread (shared by the forward
    batcher and the generation engine).

    ``set_result`` runs client done-callbacks and wakes every thread
    blocked in ``Future.result()``, and each wake costs the resolving
    thread a GIL handoff (up to the 5ms switch interval) — a 32-request
    batch resolved on a dispatch thread stalled it ~50ms, 40x the
    actual compute.  Dispatch loops only enqueue (fut, result, exc)
    triples here."""

    def __init__(self, name="mxt-serve-done"):
        self._q = queue.Queue()
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        self._thread.start()

    def resolve(self, fut, result=None, exc=None):
        self._q.put((fut, result, exc))

    def _loop(self):
        while True:
            item = self._q.get()
            if item is _STOP:
                return
            fut, result, exc = item
            try:
                if exc is not None:
                    fut.set_exception(exc)
                else:
                    fut.set_result(result)
            except InvalidStateError:
                # a client cancel() can land at any point before the
                # set (exception resolutions target still-PENDING
                # futures): the cancel wins, the resolution is dropped
                pass

    def close(self, timeout=60.0):
        """Stop after everything already enqueued has resolved."""
        self._q.put(_STOP)
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise MXNetError("serving completer thread failed to stop "
                             "within %.0fs" % timeout)

# Per-request rows are cut out of the batch output with a jitted
# dynamic slice whose OFFSET is a traced argument: a static ``o[a:b]``
# would compile one XLA slice program per distinct offset (dozens on
# the first full batch, each a multi-ms stall of the dispatch loop),
# while here jax caches one executable per (rows, output aval).
_SLICERS = {}


def _row_slice(arr, ofs, n):
    fn = _SLICERS.get(n)
    if fn is None:
        def f(x, i, _n=n):
            return jax.lax.dynamic_slice_in_dim(x, i, _n, 0)
        fn = _SLICERS.setdefault(n, jax.jit(f))
    return fn(arr, ofs)


class ServeTimeout(MXNetError):
    """The request's deadline expired while it waited for dispatch."""


class ServeClosed(MXNetError):
    """The engine is shut down (or shutting down without drain).

    ``replica_index`` names the owning replica when the engine belongs
    to a :class:`~.replica_set.ReplicaSet` (``None`` for bare engines):
    the flight recorder and the replica set's retry layer both want to
    know WHICH replica died out from under an in-flight request."""

    def __init__(self, msg, replica_index=None):
        if replica_index is not None:
            msg = "%s [replica %d]" % (msg, int(replica_index))
        super().__init__(msg)
        self.replica_index = replica_index


class ServeOverloaded(MXNetError):
    """Admission control shed the request: the engine's inflight budget
    (``MXNET_SERVE_MAX_INFLIGHT``) is full.  Structured overload — the
    HTTP front door maps it to 429 — instead of queueing into timeout
    collapse; clients should back off and retry."""


# Admission priority tiers, highest first.  "latency" requests preempt
# "batch" ones at bucket formation (the engine serves the oldest parked
# latency request before any batch request); FIFO order holds WITHIN a
# (model, tier) stream, never across tiers.
TIERS = ("latency", "batch")


class ServeRequest:
    """One queued inference request (internal; clients hold the Future)."""

    __slots__ = ("model", "inputs", "n", "future", "deadline", "t_submit",
                 "priority", "tenant", "trace", "trace_parent")

    def __init__(self, model, inputs, n, future, deadline, t_submit,
                 priority="batch", tenant=None):
        self.model = model
        self.inputs = inputs      # dict name -> np.ndarray (canonical)
        self.n = n                # rows
        self.future = future
        self.deadline = deadline  # monotonic seconds, or None
        self.t_submit = t_submit
        self.priority = priority  # one of TIERS
        self.tenant = tenant      # quota/metrics key, or None
        # the request's trace context, captured on the submitting
        # thread (tracing.current_context) and re-activated by the
        # engine thread around its dispatch — the cross-thread span
        # propagation handshake
        self.trace = None
        self.trace_parent = None


class ServingEngine:
    """Continuous batcher over a :class:`~.registry.ModelRegistry`.

    ``submit(model, timeout=None, **inputs)`` returns a
    ``concurrent.futures.Future`` resolving to the list of output arrays
    for exactly the submitted rows (device arrays — fetch on the caller's
    thread).  One engine serves every model in the registry; batches
    never mix models.
    """

    def __init__(self, registry, max_delay_ms=None, max_batch=None,
                 max_inflight=None, owner_index=None, tenant_quotas=None):
        self._registry = registry
        # which ReplicaSet replica owns this engine (None = bare): every
        # ServeClosed the engine mints carries it, so the retry layer
        # and the flight recorder know which replica failed the request
        self._owner_index = owner_index
        # per-tenant admission quotas: tenant id -> max inflight ROWS
        # for that tenant; a submit that would exceed its tenant's
        # budget is shed alone — the noisy tenant backs off, everyone
        # else keeps being served
        self._tenant_quotas = dict(tenant_quotas or {})
        # tenant ledger + lifecycle flags live in racecheck containers
        # (plain dict / SimpleNamespace with the detector off): under
        # MXNET_RACE_CHECK=1 any access that skipped the _submit_lock
        # edge raises DataRaceError instead of silently going stale
        self._tenant_rows = racecheck.shared_map("serving.tenant_rows")
        if max_delay_ms is None:
            max_delay_ms = float(get_env("MXNET_SERVE_MAX_DELAY_MS"))
        self._max_delay = max(0.0, float(max_delay_ms)) / 1e3
        if max_batch is None:
            max_batch = int(get_env("MXNET_SERVE_MAX_BATCH"))
        self._max_batch = max(1, int(max_batch))
        if max_inflight is None:
            max_inflight = int(get_env("MXNET_SERVE_MAX_INFLIGHT"))
        self._max_inflight = max(0, int(max_inflight))  # 0 = unbounded
        self._inflight = 0
        self._queue = queue.Queue()
        self._pending = collections.deque()
        self._life = racecheck.shared_state(
            "serving.fwd.lifecycle", closed=False, drain_on_stop=True)
        self._inflight_reqs = ()
        self._submit_lock = make_lock("serving.submit")
        self._stats_lock = make_lock("serving.stats")
        # counters live in the process metrics registry (one labeled
        # series per engine); stats() reads THROUGH them, so the legacy
        # tree and GET /metrics can never disagree
        self._mlabels = {"engine": "fwd%d" % _uid()}
        self._stats = _metrics.CounterDict(
            "serve_", ("requests", "batches", "rows", "padded_rows",
                       "timeouts", "cancelled", "errors", "shed"),
            labels=self._mlabels, help="forward serving engine counter")
        self._g_inflight = _metrics.gauge(
            "serve_inflight", labels=self._mlabels,
            help="accepted-but-unresolved forward requests")
        self._max_rows = 0
        # test seam (faultinject spirit): called with (model, live_reqs)
        # right before each dispatch; tests install sleeps/recorders here
        self._dispatch_hook = None
        self._completer = FutureCompleter("mxt-serve-done")
        self._thread = threading.Thread(target=self._serve_loop,
                                        name="mxt-serve", daemon=True)
        self._thread.start()

    def _closed_exc(self, msg):
        return ServeClosed(msg, replica_index=self._owner_index)

    # lifecycle flags route through the shared_state container so the
    # race detector sees every access; call sites keep the field names
    @property
    def _closed(self):
        return self._life.closed

    @_closed.setter
    def _closed(self, v):
        self._life.closed = v

    @property
    def _drain_on_stop(self):
        return self._life.drain_on_stop

    @_drain_on_stop.setter
    def _drain_on_stop(self, v):
        self._life.drain_on_stop = v

    # -- client side ---------------------------------------------------
    def submit(self, model, timeout=None, priority=None, tenant=None,
               **inputs):
        """Enqueue one request; returns its Future.

        ``timeout`` (seconds) bounds time-in-queue: an expired request
        fails with :class:`ServeTimeout` instead of computing.  Input
        validation/canonicalization (np conversion, dtype, shapes)
        happens here on the caller's thread.

        Admission control: when ``MXNET_SERVE_MAX_INFLIGHT`` (or the
        constructor's ``max_inflight``) is set, a submit that would
        push the number of accepted-but-unresolved requests past the
        budget is SHED with :class:`ServeOverloaded` instead of queued
        — under sustained overload the queue would otherwise grow
        without bound and every request would time out (the loadgen's
        collapse phase); shedding keeps the accepted requests' latency
        flat and gives clients a structured back-off signal.

        ``priority`` ("latency" or "batch", default "batch") picks the
        admission tier: latency requests preempt batch requests at
        bucket formation.  ``tenant`` names the submitting tenant for
        quota accounting and per-tenant metrics; with a quota
        configured (constructor ``tenant_quotas``), a tenant over its
        inflight-row budget is shed alone with
        :class:`ServeOverloaded`."""
        with self._submit_lock:
            # early gate (under the lock that orders it against
            # close()) so EVERY post-close submit raises ServeClosed —
            # not a validation error about its payload
            if self._closed:
                raise self._closed_exc("serving engine is closed")
        priority = "batch" if priority is None else str(priority)
        if priority not in TIERS:
            raise MXNetError("unknown priority tier %r (want one of %s)"
                             % (priority, "/".join(TIERS)))
        tenant = None if tenant is None else str(tenant)
        store = self._registry.store(model)
        canon, n = store.canon_inputs(inputs)
        fut = Future()
        now = time.monotonic()
        req = ServeRequest(model, canon, n, fut,
                           now + timeout if timeout is not None else None,
                           now, priority=priority, tenant=tenant)
        # trace context: an ingress trace already active on this thread
        # (HTTP handler, replica-set dispatch) is captured onto the
        # request; a bare in-process submit mints its own and finishes
        # it when the future resolves
        ctx = _tracing.current_context()
        owned = None
        if ctx is None:
            owned = _tracing.start_trace("serve.forward", model=model)
            ctx = (owned, owned.root_id)
        req.trace, req.trace_parent = ctx
        try:
            with self._submit_lock:
                if self._closed:
                    raise self._closed_exc("serving engine is closed")
                if self._max_inflight \
                        and self._inflight >= self._max_inflight:
                    self._stats.inc("shed")
                    raise ServeOverloaded(
                        "serving engine is at its inflight budget (%d); "
                        "request shed — back off and retry"
                        % self._max_inflight)
                quota = self._tenant_quotas.get(tenant) \
                    if tenant is not None else None
                if quota is not None \
                        and self._tenant_rows.get(tenant, 0) + n > quota:
                    # the noisy tenant sheds alone: everyone else's
                    # admission is untouched
                    self._stats.inc("shed")
                    _metrics.cached_counter(
                        "serve_tenant_shed_total",
                        labels={"tenant": tenant},
                        help="requests shed by per-tenant quota").inc()
                    raise ServeOverloaded(
                        "tenant %r is over its inflight row quota (%d); "
                        "request shed — back off and retry"
                        % (tenant, quota))
                self._inflight += 1
                if tenant is not None:
                    self._tenant_rows[tenant] = \
                        self._tenant_rows.get(tenant, 0) + n
                self._g_inflight.set(self._inflight)
                self._queue.put(req)
        except (ServeClosed, ServeOverloaded) as e:
            # a self-minted trace still exports (status = the shed/
            # closed class): overload is exactly the condition the
            # telemetry plane exists to diagnose.  Finished OUTSIDE
            # the lock — the JSONL append must not serialize sheds.
            if owned is not None:
                owned.finish(status=type(e).__name__)
            raise
        # exactly one resolution per accepted request (result, error or
        # cancel) ends its inflight accounting
        fut.add_done_callback(
            lambda f, t=tenant, rows=n: self._note_resolved(t, rows))
        if _metrics.phase_on():
            fut.add_done_callback(
                lambda f, t=now: _H_LATENCY.observe(time.monotonic() - t))
        if owned is not None:
            fut.add_done_callback(_tracing.finish_on_done(owned))
        self._stats.inc("requests")
        _metrics.cached_counter(
            "serve_tier_requests_total", labels={"tier": priority},
            help="forward requests accepted, by priority tier").inc()
        if tenant is not None:
            _metrics.cached_counter(
                "serve_tenant_requests_total", labels={"tenant": tenant},
                help="forward requests accepted, by tenant").inc()
        return fut

    def _note_resolved(self, tenant, rows):
        with self._submit_lock:
            self._inflight -= 1
            if tenant is not None:
                left = self._tenant_rows.get(tenant, 0) - rows
                if left > 0:
                    self._tenant_rows[tenant] = left
                else:
                    self._tenant_rows.pop(tenant, None)
            self._g_inflight.set(self._inflight)

    def alive(self):
        """Liveness witness (the front door's /healthz reads it): the
        dispatch loop is running and accepting submits."""
        with self._submit_lock:
            closed = self._closed
        return not closed and self._thread.is_alive()

    def stats(self):
        """Scheduler counters plus each model's program-store stats,
        with a cross-model resident-weight rollup by storage dtype (the
        bf16/int8 memory claims' one-stop measurement — bench rows and
        serve_smoke read this instead of recomputing)."""
        out = self._stats.as_dict()
        with self._stats_lock:
            out["max_rows_in_batch"] = self._max_rows
        with self._submit_lock:
            out["inflight"] = self._inflight
            out["tenant_rows"] = dict(self._tenant_rows)
        out["max_inflight"] = self._max_inflight
        out["tenant_quotas"] = dict(self._tenant_quotas)
        out["models"] = self._registry.stats()
        rollup = {}
        for m in out["models"].values():
            for dt, n in m.get("weight_bytes", {}).get(
                    "by_dtype", {}).items():
                rollup[dt] = rollup.get(dt, 0) + n
        out["weight_bytes_by_dtype"] = rollup
        return out

    def close(self, drain=True, timeout=60.0):
        """Stop the engine.  ``drain=True`` (default) completes every
        request already submitted before the thread exits;
        ``drain=False`` fails queued requests with :class:`ServeClosed`.
        Idempotent; joins the engine thread."""
        with self._submit_lock:
            if not self._closed:
                self._closed = True
                self._drain_on_stop = bool(drain)
                self._queue.put(_STOP)
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise MXNetError("serving engine thread failed to stop "
                             "within %.0fs" % timeout)
        # every resolution the drain enqueued precedes the sentinel
        self._completer.close(timeout)
        # retire this engine's labeled series from the process scrape
        # (stats() keeps reading through its own references)
        _metrics.drop(self._mlabels)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _resolve(self, fut, result=None, exc=None):
        self._completer.resolve(fut, result, exc)

    # -- engine thread -------------------------------------------------
    def _serve_loop(self):
        try:
            while self._dispatch_once():
                pass
        finally:
            # a crashed loop (anything but the clean close() exit)
            # leaves a postmortem: the flight ring dumps with the
            # failure named, before the sweep below fails the queue
            exc = sys.exc_info()[1]
            if exc is not None:
                fl = _tracing.flight()
                fl.record("crash", "serving engine loop",
                          error=repr(exc))
                fl.dump(reason="serving engine dispatch loop "
                        "crashed: %r" % (exc,))
            # the dispatch loop is exiting — normally (close()) or
            # because a cycle raised something unexpected.  Either way
            # the queue must never again accept a request that nothing
            # will serve: latch closed FIRST (submit raises ServeClosed
            # from here on), then fail whatever is still queued.  On a
            # clean close() the sweep finds nothing; on a crashed loop
            # it turns silently-dropped requests into ServeClosed.
            with self._submit_lock:
                self._closed = True
            self._fail_remaining()

    def _fail_remaining(self):
        """Resolve everything still parked or queued with ServeClosed
        (nothing will ever dispatch it) — including the whole batch the
        loop had already taken off the queue when it crashed."""
        inflight = self._inflight_reqs
        self._inflight_reqs = ()
        for r in inflight:
            # double-resolution of an already-served request is
            # harmless: the completer swallows InvalidStateError
            self._resolve(r.future, exc=self._closed_exc(
                "serving engine dispatch loop exited before this "
                "request could be served"))
        while True:
            if self._pending:
                head = self._pending.popleft()
            else:
                try:
                    head = self._queue.get_nowait()
                except queue.Empty:
                    return
            if head is _STOP:
                continue
            self._resolve(head.future, exc=self._closed_exc(
                "serving engine dispatch loop exited before this "
                "request could be served"))

    @hot_path
    def _dispatch_once(self):
        """One scheduler cycle: wait for a head request, form the batch
        within the head's latency budget, dispatch it.  Returns False
        when the engine should exit (after draining)."""
        with _profiler.phase("serve_wait"):
            head = self._take()
        if head is _STOP:
            self._shutdown()
            return False
        # from here until their batch resolves, the head — and then
        # every request _collect gathers around it — lives in neither
        # the queue nor the pending deque: track the whole set so a
        # crashing cycle cannot silently drop ANY accepted request
        # (the exit sweep resolves them with ServeClosed)
        self._inflight_reqs = (head,)
        if self._failfast():
            # close(drain=False): queued work ahead of the STOP
            # sentinel fails fast instead of being served out
            self._resolve(head.future, exc=self._closed_exc(
                "serving engine closed before dispatch"))
            self._inflight_reqs = ()
            return True
        with _profiler.phase("serve_batch"):
            reqs, rows, stop = self._collect(head)
            self._inflight_reqs = tuple(reqs)
        if self._failfast():
            # close(drain=False) landed while the batch was forming:
            # fail-fast semantics apply to the whole collected batch,
            # not just heads taken after the flag flipped
            for r in reqs:
                self._resolve(r.future, exc=self._closed_exc(
                    "serving engine closed before dispatch"))
        else:
            self._dispatch_batch(head.model, reqs, rows)
        self._inflight_reqs = ()
        if stop:
            self._shutdown()
            return False
        return True

    def _take(self):
        """Next request, latency tier first.

        New arrivals are drained behind the parked set (preserving
        arrival order), then the OLDEST latency-tier request anywhere in
        the backlog is served before any batch-tier request: latency
        traffic preempts batch traffic at bucket formation instead of
        queueing behind it.  FIFO order still holds within each
        (model, tier) stream.  With no backlog, block on the queue
        (close() unblocks via the _STOP sentinel)."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is _STOP:
                # re-queue the sentinel: nothing can be submitted after
                # close() latched, so it stays last and the drained
                # backlog is served out first
                self._queue.put(item)
                break
            self._pending.append(item)
        for i, r in enumerate(self._pending):
            if r.priority == TIERS[0]:
                del self._pending[i]
                return r
        if self._pending:
            return self._pending.popleft()
        return self._queue.get()

    def _collect(self, head):
        """Grow ``head``'s batch to the largest bucket that fits within
        its latency budget.  Returns ``(reqs, rows, stop_seen)``."""
        try:
            cap = min(self._max_batch,
                      self._registry.store(head.model).max_bucket())
        except MXNetError as e:  # model removed after submit
            self._resolve(head.future, exc=e)
            return [], 0, False
        reqs = [head]
        rows = head.n
        # batches never mix models OR tiers: a latency bucket stays
        # small and dispatches on its own clock instead of absorbing
        # batch-tier rows.  Within the head's (model, tier) stream,
        # parked requests keep their arrival order; once one doesn't
        # fit, NOTHING younger of that stream may join past it
        # (everything later in pending — and everything still in the
        # queue — is younger), or batches would reorder the stream FIFO
        stream = (head.model, head.priority)
        keep = collections.deque()
        blocked = False
        while self._pending:
            r = self._pending.popleft()
            if (r.model, r.priority) == stream and not blocked \
                    and rows + r.n <= cap and rows < cap:
                reqs.append(r)
                rows += r.n
            else:
                keep.append(r)
                if (r.model, r.priority) == stream:
                    blocked = True
        self._pending = keep
        if blocked:
            # the batch cannot legally grow (any same-model arrival is
            # younger than the parked one) — waiting out the latency
            # budget could only add overtakers, so flush now
            return reqs, rows, False
        deadline = head.t_submit + self._max_delay
        stop = False
        while rows < cap:
            # the budget bounds WAITING, never taking: a backlogged
            # queue still fills the bucket via non-blocking gets even
            # when the head is already past its delay budget (otherwise
            # a backlog degenerates into one-request batches — the
            # exact regime continuous batching exists for)
            remaining = deadline - time.monotonic()
            try:
                item = self._queue.get(timeout=remaining) \
                    if remaining > 0 else self._queue.get_nowait()
            except queue.Empty:
                break
            if item is _STOP:
                stop = True
                break
            if (item.model, item.priority) == stream \
                    and rows + item.n <= cap:
                reqs.append(item)
                rows += item.n
            else:
                self._pending.append(item)
                if (item.model, item.priority) == stream:
                    break  # same stream but over cap: flush now
        return reqs, rows, stop

    @hot_path
    def _dispatch_batch(self, model, reqs, rows):
        """Concatenate live requests, run the bucketed program, resolve
        per-request futures with row slices (lazy device slices — no
        host sync on this thread)."""
        if not reqs:
            return
        now = time.monotonic()
        mets = _metrics.phase_on()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                self._resolve(r.future, exc=ServeTimeout(
                    "request for %r timed out after %.1f ms in queue"
                    % (r.model, (now - r.t_submit) * 1e3)))
                self._stats.inc("timeouts")
            elif r.future.set_running_or_notify_cancel():
                live.append(r)
                if mets:
                    _H_QWAIT.observe(now - r.t_submit)
            else:
                self._stats.inc("cancelled")
        if not live:
            return
        rows = sum(r.n for r in live)
        # the batch's compute span belongs to EVERY member's trace:
        # activate them all, so serve_compute lands in each as a child
        # of that request's ingress span
        with _tracing.activate_many(
                [(r.trace, r.trace_parent) for r in live]):
            # the span closes BEFORE the resolutions enqueue: a resolved
            # future finishes its minter's trace, and a span landing
            # after finish would be dropped from the export
            with _profiler.phase("serve_compute"):
                if self._dispatch_hook is not None:
                    self._dispatch_hook(model, live)
                if len(live) == 1:
                    inputs = live[0].inputs
                else:
                    names = live[0].inputs.keys()
                    inputs = {k: np.concatenate([r.inputs[k] for r in live])
                              for k in names}
                try:
                    store = self._registry.store(model)
                    outs, bucket, batch_major = store.run(
                        inputs, n=rows, slice_outputs=False)
                except BaseException as e:  # noqa: BLE001 — to the futures
                    exc = e if isinstance(e, MXNetError) \
                        else MXNetError("serving dispatch failed: %r" % (e,))
                    _tracing.flight().record(
                        "error", "serve_dispatch_failed", model=model,
                        error=repr(e), requests=len(live))
                    for r in live:
                        self._resolve(r.future, exc=exc)
                    self._stats.inc("errors", len(live))
                    return
                # outs are bucket-shaped (pad rows still on); every request
                # gets its rows via the shared traced-offset slicer, so no
                # per-batch or per-offset slice program ever compiles here
                ofs = 0
                sliced = []
                for r in live:
                    res = []
                    for o, bm in zip(outs, batch_major):
                        if bm and r.n != bucket:
                            o = _row_slice(o, ofs, r.n)
                        res.append(o)
                    sliced.append(res)
                    ofs += r.n
            for r, res in zip(live, sliced):
                self._resolve(r.future, res)
        if mets:
            _H_BATCH.observe(rows)
        self._stats.inc("batches")
        self._stats.inc("rows", rows)
        self._stats.inc("padded_rows", bucket - rows)
        with self._stats_lock:
            if rows > self._max_rows:
                self._max_rows = rows

    def _failfast(self):
        """close(drain=False) landed?  Read under the lock that orders
        the flags against close() — the engine polls this every cycle,
        long before any _STOP sentinel provides a queue edge."""
        with self._submit_lock:
            return self._closed and not self._drain_on_stop

    def _shutdown(self):
        """Drain everything already submitted (or fail it when
        ``close(drain=False)``), then let the loop exit."""
        with self._submit_lock:
            drain = self._drain_on_stop
        while True:
            if self._pending:
                head = self._pending.popleft()
            else:
                try:
                    head = self._queue.get_nowait()
                except queue.Empty:
                    return
            if head is _STOP:
                continue
            if not drain:
                self._resolve(head.future, exc=self._closed_exc(
                    "serving engine closed before dispatch"))
                continue
            self._inflight_reqs = (head,)
            reqs, rows, _ = self._collect_ready(head)
            self._inflight_reqs = tuple(reqs)
            self._dispatch_batch(head.model, reqs, rows)
            self._inflight_reqs = ()

    def _collect_ready(self, head):
        """Shutdown-time batch forming: same-model coalescing, but only
        over requests already queued — no latency-budget waiting."""
        try:
            cap = min(self._max_batch,
                      self._registry.store(head.model).max_bucket())
        except MXNetError as e:
            self._resolve(head.future, exc=e)
            return [], 0, False
        reqs = [head]
        rows = head.n
        stream = (head.model, head.priority)
        keep = collections.deque()
        # same FIFO discipline as _collect: a same-stream request that
        # didn't fit blocks every younger one from joining this batch
        blocked = False
        while self._pending:
            r = self._pending.popleft()
            if (r.model, r.priority) == stream and not blocked \
                    and rows + r.n <= cap:
                reqs.append(r)
                rows += r.n
            else:
                keep.append(r)
                if (r.model, r.priority) == stream:
                    blocked = True
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is _STOP:
                continue
            if (item.model, item.priority) == stream and not blocked \
                    and rows + item.n <= cap:
                reqs.append(item)
                rows += item.n
            else:
                keep.append(item)
                if (item.model, item.priority) == stream:
                    blocked = True
        self._pending = keep
        return reqs, rows, False
