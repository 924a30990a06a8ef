"""Shared-nothing multi-replica serving with health-checked failover.

One replica = one private :class:`~.registry.ModelRegistry` plus its own
:class:`~.scheduler.ServingEngine` (and optionally a
:class:`~.decode_engine.GenerationEngine`): no weights, caches, program
stores or queues are shared between replicas, so a replica dying takes
down exactly its own state — the shared-nothing failure unit the
training side's parameter servers already are.

:class:`ReplicaSet` fronts N replicas with a **least-loaded balancer**:

* every dispatch (request or health probe) crosses the
  ``serve.dispatch`` faultinject seam, so seeded schedules can drop /
  delay / sever / SIGKILL a replica deterministically (``die`` at this
  seam kills the targeted REPLICA in-process via the registered die
  handler instead of exiting the test process);
* each replica carries a :class:`~..retry.CircuitBreaker` (the PR-2
  kvstore plane's breaker, factored into ``mxnet_tpu/retry.py``):
  consecutive dispatch/probe failures open it and the balancer routes
  around the replica without paying its failure latency;
* **forward** requests are idempotent (pure bucketed forward), so a
  dispatch that fails retryably — the replica died, its engine closed,
  the connection severed — is retried with bounded
  exponential backoff (``mxnet_tpu.retry.backoff_delay``;
  ``MXNET_SERVE_RETRIES`` / ``MXNET_SERVE_RETRY_BACKOFF``) onto a
  SURVIVING replica, excluding every replica already observed failing
  for that request;
* **generation** requests fail fast once admitted: their KV cache died
  with the replica and silently regenerating would replay the sampled
  stream from scratch — the client gets a structured, retryable
  :class:`ReplicaDied` and decides (before admission — the dispatch
  itself failing — they retry like forwards, nothing is lost yet);
* a **prober** thread re-probes every replica each
  ``MXNET_SERVE_PROBE_INTERVAL`` seconds: probe failures open the
  breaker (a dead replica leaves the rotation within one interval),
  probe successes close it again (a transiently severed replica
  returns).

Hot weight swap ROLLS: :meth:`ReplicaSet.swap_params` republishes the
new weights one replica at a time — take the replica out of rotation
(while the others carry the traffic), drain its inflight requests, swap,
re-probe, restore — with abort-and-rollback when a re-probe fails, so a
bad weight set never takes more than one replica out.  Each replica's
store-level swap stays atomic per request (``program_store.swap_params``),
which is what lets a one-replica set swap in place.

The set is ELASTIC: :meth:`ReplicaSet.add_replica` /
:meth:`ReplicaSet.remove_replica` grow and shrink it under traffic
(replica indices are monotonic and never reused), which is the actuator
arm of the serving autoscaler (``serving/controller.py``) —
:meth:`ReplicaSet.load_signals` is its sensor arm.

Admission control composes: each replica's engine sheds with
:class:`~.scheduler.ServeOverloaded` at its ``MXNET_SERVE_MAX_INFLIGHT``
budget; the balancer treats a shed as "try the next replica" and only
surfaces 429 to the client when EVERY live replica is at budget.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, InvalidStateError

from .. import faultinject
from .. import metrics as _metrics
from .. import profiler as _profiler
from .. import tracing as _tracing
from ..analysis import racecheck
from ..analysis.lockcheck import make_lock
from ..base import MXNetError, _uid, get_env
from ..retry import CircuitBreaker, backoff_delay

# breaker-state gauge encoding (serve_replica_breaker{replica=...})
_BREAKER_STATES = {"closed": 0, "half-open": 1, "half_open": 1,
                   "open": 2}
from .registry import ModelRegistry
from .scheduler import (ServeClosed, ServeOverloaded, ServeTimeout,
                        ServingEngine)

__all__ = ["Replica", "ReplicaSet", "ReplicaDied", "NoLiveReplicas"]

SEAM = "serve.dispatch"


class ReplicaDied(MXNetError):
    """The replica serving (or about to serve) this request died.

    Retryable by contract: the balancer retries forward requests onto a
    survivor automatically; a generation request admitted to the dead
    replica surfaces this to the client (its KV state is gone — the
    client owns the resubmit decision)."""


class NoLiveReplicas(MXNetError):
    """Every replica is dead, breaker-open, or excluded by this
    request's failure history; nothing can serve it."""


class Replica:
    """One shared-nothing serving unit: a private registry + engines.

    ``populate(registry)`` happened before construction — the caller
    builds and fills the registry (each replica loads its OWN copy of
    the weights; nothing is shared).  ``gen=True`` also starts a
    GenerationEngine over the same registry."""

    def __init__(self, index, registry, gen=False, max_delay_ms=None,
                 max_batch=None, max_inflight=None, breaker=None,
                 tenant_quotas=None):
        self.index = int(index)
        self.registry = registry
        # owner_index: every ServeClosed this replica's engines mint
        # names the replica, so the retry layer and flight recorder
        # know exactly which replica died out from under a request
        self.engine = ServingEngine(registry, max_delay_ms=max_delay_ms,
                                    max_batch=max_batch,
                                    max_inflight=max_inflight,
                                    owner_index=self.index,
                                    tenant_quotas=tenant_quotas)
        self.gen_engine = None
        if gen:
            from .decode_engine import GenerationEngine
            self.gen_engine = GenerationEngine(
                registry, max_inflight=max_inflight,
                owner_index=self.index, tenant_quotas=tenant_quotas)
        if breaker is None:
            # default from the SERVING knobs — the shared
            # CircuitBreaker's own constructor defaults belong to the
            # kvstore plane
            breaker = CircuitBreaker(
                fail_threshold=int(get_env("MXNET_SERVE_CB_FAILS")),
                reset_after=float(get_env("MXNET_SERVE_CB_RESET")))
        self.breaker = breaker
        self.inflight = 0           # balancer-tracked, set-lock guarded
        # liveness flags live in a racecheck.shared_state container,
        # read/written only through the lock-guarded properties below:
        # kill()/close() (any thread), the prober, the balancer's
        # comprehensions and the rolling swap all order through
        # _life_lock, and MXNET_RACE_CHECK=1 flags any future path
        # that skips it.  RLock: kill/close read-modify under it while
        # the properties re-acquire
        self._rc = racecheck.shared_state(
            "serving.replica%d" % self.index, alive=True, draining=False)
        self._life_lock = make_lock("serving.replica", rlock=True)

    @property
    def alive(self):
        with self._life_lock:
            return self._rc.alive

    @alive.setter
    def alive(self, v):
        with self._life_lock:
            self._rc.alive = bool(v)

    @property
    def draining(self):
        with self._life_lock:
            return self._rc.draining

    @draining.setter
    def draining(self, v):
        with self._life_lock:
            self._rc.draining = bool(v)

    def kill(self):
        """Simulated SIGKILL: the replica stops abruptly.  Queued and
        forming work fails fast with ServeClosed (the balancer maps it
        to a retryable failover); in-flight generations lose their KV
        state.  Idempotent; callable from any non-engine thread."""
        with self._life_lock:
            if not self.alive:
                return
            self.alive = False
        # drain=False: fail-fast close, the in-process analog of the
        # process vanishing (dispatched device work completes — a real
        # SIGKILL would also leave the accelerator step finishing)
        self.engine.close(drain=False)
        if self.gen_engine is not None:
            self.gen_engine.close(drain=False)

    def close(self, drain=True):
        """Graceful stop (drains by default); used by ReplicaSet.close."""
        with self._life_lock:
            already_dead = not self.alive
            self.alive = False
        if already_dead:
            return
        self.engine.close(drain=drain)
        if self.gen_engine is not None:
            self.gen_engine.close(drain=drain)


class ReplicaSet:
    """Least-loaded balancer + failover over N shared-nothing replicas.

    Parameters
    ----------
    build_registry : callable(index) -> ModelRegistry, or list
        Factory producing each replica's PRIVATE registry (load the
        same checkpoint N times — replicas share nothing), or an
        explicit list of pre-built registries.
    n_replicas : int
        Replica count (ignored when a list is passed).
    gen : bool
        Also run a GenerationEngine per replica.
    retries / backoff : int / float, optional
        Forward failover policy; default ``MXNET_SERVE_RETRIES`` /
        ``MXNET_SERVE_RETRY_BACKOFF`` (backoff cap is 16x the base).
    cb_fails / cb_reset : optional
        Per-replica breaker thresholds; default ``MXNET_SERVE_CB_FAILS``
        / ``MXNET_SERVE_CB_RESET``.
    probe_interval : float, optional
        Health-probe period (seconds); default
        ``MXNET_SERVE_PROBE_INTERVAL``.  ``<= 0`` disables the prober.
    max_delay_ms / max_batch / max_inflight :
        Passed through to every replica's engine(s).
    spares : int, optional
        Warm spare-registry pool size.  ``spares`` extra registries are
        built (weights loaded, programs compiled) at construction;
        :meth:`add_replica` joins one to the rotation WITHOUT compiling
        on the caller's thread — the autoscaler's scale-up completes in
        milliseconds instead of a weight-load.  :meth:`remove_replica`
        recycles the drained registry back into the pool (up to
        ``spares``), so a diurnal swing pays the build cost once.
        Requires a callable ``build_registry``; spare builds see a
        provisional index (the factory's index argument is advisory).
    """

    def __init__(self, build_registry, n_replicas=3, gen=False,
                 retries=None, backoff=None, cb_fails=None, cb_reset=None,
                 probe_interval=None, max_delay_ms=None, max_batch=None,
                 max_inflight=None, tenant_quotas=None, spares=0):
        if retries is None:
            retries = int(get_env("MXNET_SERVE_RETRIES"))
        if backoff is None:
            backoff = float(get_env("MXNET_SERVE_RETRY_BACKOFF"))
        if cb_fails is None:
            cb_fails = int(get_env("MXNET_SERVE_CB_FAILS"))
        if cb_reset is None:
            cb_reset = float(get_env("MXNET_SERVE_CB_RESET"))
        if probe_interval is None:
            probe_interval = float(get_env("MXNET_SERVE_PROBE_INTERVAL"))
        self._retries = max(0, int(retries))
        self._backoff = max(0.0, float(backoff))
        self._probe_interval = float(probe_interval)
        # the factory and engine knobs are KEPT: add_replica() builds
        # new replicas from them (elastic sizing needs to reload the
        # weights — replicas share nothing)
        self._build = None if isinstance(build_registry, (list, tuple)) \
            else build_registry
        self._gen = bool(gen)
        self._cb_fails = int(cb_fails)
        self._cb_reset = float(cb_reset)
        self._max_delay_ms = max_delay_ms
        self._max_batch = max_batch
        self._max_inflight = max_inflight
        self._tenant_quotas = tenant_quotas
        if self._build is None:
            registries = list(build_registry)
        else:
            registries = [build_registry(i) for i in range(n_replicas)]
        if not registries:
            raise MXNetError("a ReplicaSet needs at least one replica")
        for i, reg in enumerate(registries):
            if not isinstance(reg, ModelRegistry):
                raise MXNetError("replica %d: build_registry must yield "
                                 "a ModelRegistry, got %r" % (i, reg))
        self._replicas = [self._new_replica(i, reg)
                          for i, reg in enumerate(registries)]
        # replica indices are monotonic and NEVER reused across
        # grow/shrink: metrics labels, flight records and faultinject
        # sid matches stay unambiguous over the set's whole life
        self._next_index = len(registries)
        self._spare_cap = max(0, int(spares))
        if self._spare_cap and self._build is None:
            raise MXNetError(
                "a spare pool needs a callable build_registry "
                "(spares are prebuilt from the factory)")
        self._spares = [self._build(self._next_index + k)
                        for k in range(self._spare_cap)]
        for k, reg in enumerate(self._spares):
            if not isinstance(reg, ModelRegistry):
                raise MXNetError("spare %d: build_registry must yield "
                                 "a ModelRegistry, got %r" % (k, reg))
        self._lock = make_lock("serving.replica_set")
        # counters live in the process metrics registry (labeled per
        # set); stats() reads THROUGH them.  Per-replica liveness and
        # breaker state are gauges keyed by replica index.
        self._mlabels = {"rset": "rs%d" % _uid()}
        self._stats = _metrics.CounterDict(
            "serve_rs_",
            ("submitted", "dispatched", "retries", "failovers", "shed",
             "no_live", "probe_failures", "gen_submitted",
             "gen_aborted", "replica_deaths"),
            labels=self._mlabels, help="serving replica-set counter")
        for r in self._replicas:
            self._note_breaker(r)
        self._closed = False
        # the in-process SIGKILL: a scheduled `die` at the
        # serve.dispatch seam kills the TARGETED replica (meta carries
        # sid) and fails the triggering dispatch like a severed
        # connection — os._exit would take the whole test process
        faultinject.register_die_handler(SEAM, self._injected_die)
        self._probe_stop = threading.Event()
        self._prober = None
        if self._probe_interval > 0:
            self._prober = threading.Thread(target=self._probe_loop,
                                            name="mxt-serve-probe",
                                            daemon=True)
            self._prober.start()

    def _new_replica(self, index, reg):
        return Replica(index, reg, gen=self._gen,
                       max_delay_ms=self._max_delay_ms,
                       max_batch=self._max_batch,
                       max_inflight=self._max_inflight,
                       tenant_quotas=self._tenant_quotas,
                       breaker=CircuitBreaker(
                           fail_threshold=self._cb_fails,
                           reset_after=self._cb_reset))

    def _replica(self, index):
        """Replica by its STABLE index (not list position — grow/shrink
        reorders the list); None when no such replica remains."""
        with self._lock:
            for r in self._replicas:
                if r.index == index:
                    return r
        return None

    def _note_breaker(self, r):
        """Publish one replica's breaker state + liveness as gauges
        (called on probe sweeps and failure transitions — the scrape's
        view of the rotation)."""
        labels = dict(self._mlabels, replica=str(r.index))
        _metrics.gauge("serve_replica_breaker", labels=labels,
                       help="0=closed 1=half-open 2=open").set(
            _BREAKER_STATES.get(str(r.breaker.state), -1))
        _metrics.gauge("serve_replica_alive", labels=labels,
                       help="1 while the replica can serve").set(
            1 if r.alive else 0)

    def _note_death(self, index, how):
        """One replica died: count it, flight-record it, and dump the
        postmortem artifact NAMING the dead replica (the PR-13
        kill-one-under-load scenario's readable evidence)."""
        self._stats.inc("replica_deaths")
        fl = _tracing.flight()
        fl.record("replica_died", "replica %s" % index,
                  sid=index, how=how,
                  live=[r.index for r in self._replicas if r.alive])
        fl.dump(reason="replica %s died (%s)" % (index, how))

    # -- faultinject ---------------------------------------------------
    def _injected_die(self, meta):
        sid = meta.get("sid")
        r = self._replica(int(sid)) if sid is not None else None
        if r is not None:
            was_alive = r.alive
            r.kill()
            if was_alive:
                self._note_death(r.index, "injected die at %s" % SEAM)
                self._note_breaker(r)
        raise ReplicaDied("replica %s died (injected at %s)"
                          % (sid, SEAM))

    # -- balancer ------------------------------------------------------
    def _pick(self, excluded):
        """Least-loaded live replica whose breaker admits a call; None
        when nothing is eligible.  Iterates load-ordered so at most the
        chosen replica consumes a half-open trial slot."""
        with self._lock:
            order = sorted(
                (r for r in self._replicas
                 if r.alive and not r.draining
                 and r.index not in excluded),
                key=lambda r: (r.inflight, r.index))
        for r in order:
            if r.breaker.allow():
                return r
        return None

    def replicas(self):
        return list(self._replicas)

    def alive(self):
        """Liveness witness (the front door's /healthz reads it): at
        least one replica can serve."""
        return not self._closed and any(r.alive for r in self._replicas)

    def live_replicas(self):
        return [r.index for r in self._replicas if r.alive]

    def kill_replica(self, index):
        """Kill one replica (tests / chaos drills); the balancer
        converges to the survivors within one probe interval."""
        r = self._replica(index)
        if r is None:
            raise MXNetError("no replica with index %r" % (index,))
        was_alive = r.alive
        r.kill()
        if was_alive:
            self._note_death(r.index, "kill_replica")
            self._note_breaker(r)

    # -- elastic sizing ------------------------------------------------
    def add_replica(self):
        """Grow the set by one replica (the autoscaler's scale-up arm):
        take a registry from the warm spare pool if one is ready,
        otherwise build a fresh one from the constructor's factory —
        loading its OWN weight copy, outside the set lock — and join it
        to the rotation.  Returns the new replica's index (monotonic,
        never reused)."""
        if self._build is None:
            raise MXNetError(
                "this ReplicaSet was built from a fixed registry list; "
                "pass a callable build_registry to allow growth")
        with self._lock:
            if self._closed:
                raise ServeClosed("replica set is closed")
            index = self._next_index
            self._next_index += 1
            reg = self._spares.pop() if self._spares else None
        from_pool = reg is not None
        if reg is None:
            reg = self._build(index)
            if not isinstance(reg, ModelRegistry):
                raise MXNetError("replica %d: build_registry must yield "
                                 "a ModelRegistry, got %r" % (index, reg))
        r = self._new_replica(index, reg)
        with self._lock:
            closed = self._closed
            if not closed:
                self._replicas.append(r)
        if closed:
            # close() raced the build: never leak a running replica
            r.close(drain=False)
            raise ServeClosed("replica set is closed")
        self._note_breaker(r)
        _tracing.flight().record(
            "replica_added", "replica %d joined" % index, sid=index,
            from_pool=from_pool, live=self.live_replicas())
        return index

    def remove_replica(self, index=None, drain=True):
        """Shrink the set by one replica (the autoscaler's scale-down
        arm): take it out of rotation, then close it — draining its
        inflight requests by default, so scale-down under traffic loses
        nothing.  ``index=None`` removes the youngest live replica.
        The LAST replica is never removable.  Returns the removed
        index."""
        with self._lock:
            if len(self._replicas) <= 1:
                raise MXNetError(
                    "cannot remove the last replica of the set")
            if index is None:
                live = [r for r in self._replicas if r.alive]
                victim = max(live or self._replicas,
                             key=lambda r: r.index)
            else:
                victim = next((r for r in self._replicas
                               if r.index == index), None)
                if victim is None:
                    raise MXNetError("no replica with index %r"
                                     % (index,))
            # out of the list first: _pick stops routing to it before
            # the (possibly slow) drain below
            self._replicas.remove(victim)
            was_alive = victim.alive
        victim.close(drain=drain)
        # a cleanly drained registry goes back into the warm pool (a
        # KILLED replica's does not — its death is the point); the next
        # scale-up reuses the loaded weights and compiled programs
        with self._lock:
            if (was_alive and not self._closed
                    and len(self._spares) < self._spare_cap):
                self._spares.append(victim.registry)
        # retire the removed replica's gauges; its index is never
        # reused, so a stale series would claim a replica that cannot
        # come back
        _metrics.drop(dict(self._mlabels, replica=str(victim.index)))
        _tracing.flight().record(
            "replica_removed", "replica %d left" % victim.index,
            sid=victim.index, live=self.live_replicas())
        return victim.index

    def n_replicas(self):
        with self._lock:
            return len(self._replicas)

    def load_signals(self):
        """One sample of the sensor signals the autoscaler ticks on:
        replica counts, total balancer-tracked inflight, the aggregate
        inflight capacity (None when any engine is unbounded) and the
        cumulative shed count (set-level surfaced sheds plus every
        replica engine's admission sheds — the controller windows the
        deltas)."""
        with self._lock:
            live = [r for r in self._replicas
                    if r.alive and not r.draining]
            n_replicas = len(self._replicas)
            n_spares = len(self._spares)
            inflight = sum(r.inflight for r in live)
        caps = [r.engine._max_inflight for r in live]
        capacity = sum(caps) if caps and all(caps) else None
        shed = self._stats.as_dict().get("shed", 0)
        for r in live:
            shed += r.engine._stats.as_dict().get("shed", 0)
        return {"n_replicas": n_replicas, "n_live": len(live),
                "n_spares": n_spares, "inflight": inflight,
                "capacity": capacity, "shed_total": shed}

    # -- forward requests ----------------------------------------------
    def submit(self, model, timeout=None, priority=None, tenant=None,
               **inputs):
        """Balanced forward submit; returns a Future resolving to the
        output arrays.  ``timeout`` is the END-TO-END deadline: it
        propagates into each attempt's queue budget and bounds the
        whole retry chain.  ``priority`` / ``tenant`` ride through to
        the chosen replica's engine admission (tier preemption and
        per-tenant quotas — ``scheduler.ServingEngine.submit``)."""
        fut = Future()
        # trace context: captured here (an HTTP ingress trace, or a
        # fresh mint for bare in-process callers) and re-activated by
        # every placement attempt — retries on other replicas stay
        # spans of the SAME trace
        ctx = _tracing.current_context()
        owned = None
        if ctx is None:
            owned = _tracing.start_trace("serve.forward", model=model)
            ctx = (owned, owned.root_id)
        state = {
            "model": model, "inputs": inputs, "future": fut,
            "deadline": (time.monotonic() + timeout
                         if timeout is not None else None),
            "attempt": 0, "excluded": set(), "last_exc": None,
            "priority": priority, "tenant": tenant,
            "trace": ctx[0], "trace_parent": ctx[1],
        }
        if owned is not None:
            fut.add_done_callback(_tracing.finish_on_done(owned))
        self._stats.inc("submitted")
        self._dispatch(state)
        return fut

    def _dispatch(self, state):
        """One placement attempt: pick a replica, cross the faultinject
        seam, submit to its engine.  Retryable failures (replica died /
        engine closed / severed) reroute; ServeOverloaded excludes the
        replica and tries the next immediately; when nothing is left
        the request resolves with the structured last error.  Runs on
        the submitting thread or a retry timer thread — never on an
        engine thread."""
        with _tracing.activate(state["trace"], state["trace_parent"]), \
                _profiler.phase("serve_dispatch"):
            self._dispatch_traced(state)

    def _dispatch_traced(self, state):
        while True:
            # a FAILED attempt leaves a span in the request's trace (we
            # are inside its activation): a retried request's trace
            # shows every placement it tried and how long each took.
            # Every other way out of the attempt cancels the span.
            with _profiler.phase("serve_retry") as attempt:
                if state["deadline"] is not None \
                        and time.monotonic() > state["deadline"]:
                    attempt.cancel()
                    self._resolve(state["future"], exc=ServeTimeout(
                        "request deadline expired during replica failover "
                        "(last error: %r)" % (state["last_exc"],)))
                    return
                r = self._pick(state["excluded"])
                if r is None:
                    attempt.cancel()
                    self._resolve_no_replica(state)
                    return
                try:
                    faultinject.hook(SEAM, kind="forward", sid=r.index,
                                     model=state["model"])
                    if not r.alive:
                        raise ReplicaDied("replica %d is dead" % r.index)
                    remaining = None
                    if state["deadline"] is not None:
                        remaining = max(0.0,
                                        state["deadline"] - time.monotonic())
                    inner = r.engine.submit(state["model"], timeout=remaining,
                                            priority=state["priority"],
                                            tenant=state["tenant"],
                                            **state["inputs"])
                except ServeOverloaded as e:
                    # this replica is at budget — others may have room.
                    # The structured shed proves the engine is ALIVE, so
                    # report success to the breaker (a consumed half-open
                    # trial slot must be released or the replica wedges
                    # out of rotation when the prober is disabled)
                    attempt.cancel()
                    r.breaker.record_success()
                    state["excluded"].add(r.index)
                    state["last_exc"] = e
                    continue
                except (ReplicaDied, ServeClosed, OSError) as e:
                    r.breaker.record_failure(e)
                    self._note_breaker(r)
                    state["excluded"].add(r.index)
                    state["last_exc"] = e
                except MXNetError as e:
                    # validation/config errors are not retryable, and this
                    # may run on a retry-timer thread — resolve, never
                    # raise.  The replica answered: healthy for the breaker
                    attempt.cancel()
                    r.breaker.record_success()
                    self._resolve(state["future"], exc=e)
                    return
                else:
                    attempt.cancel()
                    with self._lock:
                        r.inflight += 1
                    self._stats.inc("dispatched")
                    inner.add_done_callback(
                        lambda f, s=state, rep=r: self._inner_done(s, rep, f))
                    return
            # the placement failed; its span is closed before a
            # resolution can finish the request's trace
            if not self._schedule_retry(state):
                return

    def _schedule_retry(self, state):
        """Count one failover attempt; False = budget exhausted and the
        request was resolved with its last error."""
        state["attempt"] += 1
        self._stats.inc("retries")
        if state["attempt"] > self._retries:
            self._resolve(state["future"], exc=state["last_exc"])
            return False
        return True

    def _resolve_no_replica(self, state):
        last = state["last_exc"]
        if isinstance(last, ServeOverloaded):
            self._stats.inc("shed")
        else:
            self._stats.inc("no_live")
        if isinstance(last, ServeOverloaded):
            exc = last  # every live replica is at its inflight budget
        else:
            exc = NoLiveReplicas(
                "no live replica can serve this request (last error: %r)"
                % (last,))
        self._resolve(state["future"], exc=exc)

    def _inner_done(self, state, r, inner):
        """Completion of one replica attempt (runs on the replica
        engine's completer thread — schedule, never sleep, here)."""
        with self._lock:
            r.inflight -= 1
        if inner.cancelled():
            state["future"].cancel()
            return
        exc = inner.exception()
        if exc is None:
            r.breaker.record_success()
            self._resolve(state["future"], result=inner.result())
            return
        if isinstance(exc, (ReplicaDied, ServeClosed, OSError)):
            # the replica accepted the request but could not serve it
            # (killed / closed under us): a forward is idempotent —
            # fail over to a survivor after backoff
            r.breaker.record_failure(exc)
            self._note_breaker(r)
            state["excluded"].add(r.index)
            state["last_exc"] = exc
            self._stats.inc("failovers")
            if not self._schedule_retry(state):
                return
            delay = backoff_delay(state["attempt"] - 1, self._backoff,
                                  self._backoff * 16.0)
            timer = threading.Timer(delay, self._dispatch, args=(state,))
            timer.daemon = True
            timer.start()
            return
        # non-retryable (ServeTimeout, validation errors): as-is
        self._resolve(state["future"], exc=exc)

    def _resolve(self, fut, result=None, exc=None):
        try:
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)
        except InvalidStateError:
            pass  # client cancel raced the resolution: the cancel wins

    # -- generation requests -------------------------------------------
    def submit_gen(self, model, tokens, **kwargs):
        """Balanced generation submit; returns a Future resolving to a
        GenerationResult.  Placement failures retry like forwards
        (nothing is lost before admission), but once a replica accepts
        the request there is NO transparent retry: if the replica dies,
        its KV cache — and the partially sampled stream — died with it,
        and the future fails fast with :class:`ReplicaDied` so the
        client owns the resubmit decision."""
        fut = Future()
        state = {"attempt": 0, "excluded": set(), "last_exc": None}
        self._stats.inc("gen_submitted")
        # same trace discipline as forwards: the whole placement loop —
        # and the engine submit inside it — runs under the request's
        # trace, so placement retries stay spans of ONE trace
        ctx = _tracing.current_context()
        owned = None
        if ctx is None:
            owned = _tracing.start_trace("serve.generate", model=model)
            ctx = (owned, owned.root_id)
        if owned is not None:
            fut.add_done_callback(_tracing.finish_on_done(owned))
        with _tracing.activate(ctx[0], ctx[1]), \
                _profiler.phase("serve_dispatch"):
            return self._submit_gen_traced(model, tokens, fut, state,
                                           **kwargs)

    def _submit_gen_traced(self, model, tokens, fut, state, **kwargs):
        while True:
            r = self._pick(state["excluded"])
            if r is None:
                last = state["last_exc"]
                self._resolve(fut, exc=last if isinstance(
                    last, ServeOverloaded) else NoLiveReplicas(
                    "no live replica can serve this generation "
                    "(last error: %r)" % (last,)))
                return fut
            if r.gen_engine is None:
                raise MXNetError("this ReplicaSet was built without "
                                 "generation engines (gen=True)")
            try:
                faultinject.hook(SEAM, kind="gen", sid=r.index,
                                 model=model)
                if not r.alive:
                    raise ReplicaDied("replica %d is dead" % r.index)
                inner = r.gen_engine.submit(model, tokens, **kwargs)
            except ServeOverloaded as e:
                r.breaker.record_success()   # alive, just at budget
                state["excluded"].add(r.index)
                state["last_exc"] = e
                continue
            except (ReplicaDied, ServeClosed, OSError) as e:
                r.breaker.record_failure(e)
                self._note_breaker(r)
                state["excluded"].add(r.index)
                state["last_exc"] = e
                state["attempt"] += 1
                self._stats.inc("retries")
                if state["attempt"] > self._retries:
                    self._resolve(fut, exc=e)
                    return fut
                continue
            except MXNetError as e:
                r.breaker.record_success()   # the replica answered
                self._resolve(fut, exc=e)
                return fut
            with self._lock:
                r.inflight += 1
            self._stats.inc("dispatched")
            inner.add_done_callback(
                lambda f, rep=r: self._gen_done(fut, rep, f))
            return fut

    def _gen_done(self, fut, r, inner):
        with self._lock:
            r.inflight -= 1
        if inner.cancelled():
            fut.cancel()
            return
        exc = inner.exception()
        if exc is None:
            r.breaker.record_success()
            self._resolve(fut, result=inner.result())
            return
        if isinstance(exc, (ServeClosed, OSError)) and not r.alive:
            r.breaker.record_failure(exc)
            self._note_breaker(r)
            self._stats.inc("gen_aborted")
            exc = ReplicaDied(
                "generation was lost with replica %d (its KV state "
                "died); resubmit to regenerate" % r.index)
        self._resolve(fut, exc=exc)

    # -- health probing ------------------------------------------------
    def _probe_loop(self):
        while not self._probe_stop.wait(self._probe_interval):
            self.probe_once()

    def probe_once(self):
        """One health sweep (the prober's body; tests call it directly
        for clock-free determinism).  A probe crosses the same
        ``serve.dispatch`` seam as requests — seeded fault schedules
        see ``kind='probe'`` events — and the engine's ``alive()``
        (dispatch loop running, accepting submits) is the liveness
        witness; failures open the breaker, successes close it."""
        with self._lock:
            replicas = list(self._replicas)
        for r in replicas:
            try:
                faultinject.hook(SEAM, kind="probe", sid=r.index)
                if not r.alive:
                    raise ReplicaDied("replica %d is dead" % r.index)
                if not r.engine.alive():
                    # the engine's dispatch loop is gone (crashed or
                    # closed under us) even though nobody called
                    # kill(): the probe must NOT re-close the breaker
                    # or the set would flap this replica back into
                    # rotation every interval
                    raise ReplicaDied(
                        "replica %d's engine dispatch loop has exited"
                        % r.index)
                r.breaker.record_success()
            except BaseException as e:  # noqa: BLE001 — health verdict
                r.breaker.record_failure(e)
                self._stats.inc("probe_failures")
            self._note_breaker(r)

    # -- management ----------------------------------------------------
    def swap_params(self, name, arg_params, aux_params=None, rate=None,
                    drain_timeout=None):
        """Zero-downtime ROLLING hot weight swap.

        One live replica at a time: take it out of rotation (only while
        the others can carry the traffic — a one-replica set swaps in
        place, the store swap is atomic per dispatch), wait up to
        ``drain_timeout`` seconds (``MXNET_SERVE_SWAP_DRAIN_S``) for its
        inflight requests to finish, swap its registry, re-probe it
        (the ``serve.dispatch`` seam with ``kind='swap_probe'`` plus an
        engine liveness check), restore it to rotation, then pause
        ``rate`` seconds (``MXNET_SERVE_SWAP_RATE``) before the next
        replica.  A failed re-probe ABORTS the roll: every
        already-swapped replica is rolled back to the exact weight set
        it served (``registry.restore_params``) and the abort raises —
        a bad weight push never takes out more than the replica it was
        probed on.

        Traffic during the roll sees only coherent weight sets — old or
        new, never a mix — and never fails for the roll's sake: the
        drained replica's share is carried by the rest of the rotation.
        Returns ``{replica_index: new_version}`` over the replicas that
        were live when the roll started (ones that die mid-roll are
        skipped); raises :class:`NoLiveReplicas` when there is nothing
        to swap."""
        if rate is None:
            rate = float(get_env("MXNET_SERVE_SWAP_RATE"))
        if drain_timeout is None:
            drain_timeout = float(get_env("MXNET_SERVE_SWAP_DRAIN_S"))
        with self._lock:
            targets = [r for r in self._replicas if r.alive]
        if not targets:
            raise NoLiveReplicas("no live replica to swap %r on" % name)
        fl = _tracing.flight()
        out = {}
        swapped = []   # (replica, pre-swap snapshot), for rollback
        for pos, r in enumerate(targets):
            if not r.alive:
                continue   # died mid-roll: the prober's problem, not ours
            with self._lock:
                # park only while another replica can serve: _pick
                # skips draining replicas, so parking the sole survivor
                # would fail traffic instead of protecting it
                r.draining = any(o.alive and not o.draining
                                 and o is not r for o in self._replicas)
            try:
                if r.draining:
                    deadline = time.monotonic() + max(0.0, drain_timeout)
                    while time.monotonic() < deadline:
                        with self._lock:
                            busy = r.inflight
                        if not busy:
                            break
                        time.sleep(0.001)
                snap = r.registry.param_snapshot(name)
                out[r.index] = r.registry.swap_params(name, arg_params,
                                                      aux_params)
                swapped.append((r, snap))
                self._reprobe(r)
            except BaseException as e:  # noqa: BLE001 — abort the roll
                with self._lock:
                    r.draining = False
                self._rollback_swap(name, swapped)
                fl.record("swap_aborted", "rolling swap of %r" % name,
                          sid=r.index, error=repr(e),
                          rolled_back=[x.index for x, _ in swapped])
                raise MXNetError(
                    "rolling swap of %r aborted at replica %d (%r); "
                    "every swapped replica was rolled back to the old "
                    "weights" % (name, r.index, e)) from e
            with self._lock:
                r.draining = False
            fl.record("swap_rolled", "replica %d -> v%s"
                      % (r.index, out[r.index]), sid=r.index)
            if rate > 0 and pos + 1 < len(targets):
                time.sleep(rate)
        if not out:
            raise NoLiveReplicas("no live replica to swap %r on" % name)
        # the warm pool must follow the roll: a spare joining the
        # rotation AFTER a successful swap would otherwise serve the
        # old weights.  Spares have nothing in flight, so this is a
        # plain publish (best-effort — a spare that cannot take the
        # weights is dropped from the pool rather than served stale).
        with self._lock:
            spares = list(self._spares)
        for sreg in spares:
            try:
                sreg.swap_params(name, arg_params, aux_params)
            except BaseException as e:  # noqa: BLE001
                with self._lock:
                    if sreg in self._spares:
                        self._spares.remove(sreg)
                fl.record("swap_spare_dropped",
                          "spare registry dropped on swap failure",
                          error=repr(e))
        return out

    def _reprobe(self, r):
        """Post-swap readiness gate: the swap seam event (seeded
        schedules fail it deterministically) plus the same liveness
        witness the prober uses."""
        faultinject.hook(SEAM, kind="swap_probe", sid=r.index)
        if not r.alive or not r.engine.alive():
            raise ReplicaDied("replica %d failed its post-swap re-probe"
                              % r.index)
        r.breaker.record_success()

    def _rollback_swap(self, name, swapped):
        """Abort path: republish each swapped replica's pre-swap
        snapshot, newest first.  Best-effort per replica — a replica
        that died after its swap has nothing to roll back."""
        for r, snap in reversed(swapped):
            if not r.alive:
                continue
            try:
                r.registry.restore_params(name, snap)
            except BaseException as e:  # noqa: BLE001 — keep rolling back
                _tracing.flight().record(
                    "swap_rollback_failed", "replica %d" % r.index,
                    sid=r.index, error=repr(e))

    def stats(self):
        out = self._stats.as_dict()
        with self._lock:
            replicas = list(self._replicas)
            inflight = {r.index: r.inflight for r in replicas}
        out["replicas"] = {
            r.index: {"alive": r.alive, "breaker": r.breaker.state,
                      "draining": r.draining,
                      "inflight": inflight[r.index],
                      "engine": r.engine.stats()}
            for r in replicas}
        out["live"] = self.live_replicas()
        return out

    def close(self, drain=True, timeout=60.0):
        """Stop the prober, close every replica (draining by default),
        release the die-handler seam.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._probe_stop.set()
        if self._prober is not None:
            self._prober.join(timeout)
        # deregister only OUR handler: a newer ReplicaSet may have
        # installed its own, and clobbering it would send the next
        # scheduled die through os._exit (the whole-process kill the
        # handler exists to avoid)
        if faultinject.die_handler(SEAM) is self._injected_die:
            faultinject.register_die_handler(SEAM, None)
        with self._lock:
            replicas = list(self._replicas)
            self._spares = []   # registries only — nothing to join
        for r in replicas:
            r.close(drain=drain)
        # retire this set's labeled series (incl. per-replica gauges)
        _metrics.drop(self._mlabels)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
