"""HTTP front door: the serving plane's network surface.

Everything behind it — the continuous batcher, the replica set, the
decode engine — speaks ``submit(...) -> Future``; this module puts a
thin, dependency-free HTTP skin on that contract (stdlib
``http.server`` only, mirroring the kvstore plane's stdlib transport
choice) so real traffic can reach it:

* ``POST /v1/models/<name>:predict`` — one forward request.  Two wire
  formats, chosen by Content-Type: ``application/json`` (``{"inputs":
  {name: nested-lists}, "timeout_ms": ...}`` -> ``{"outputs": [...],
  "shapes": ..., "dtypes": ..., "version": ...}``) for curl-ability,
  and ``application/x-npz`` (an ``np.savez`` archive of the inputs;
  reply is an npz of ``output_0..output_k``) for bit-exact binary
  transport — the loadgen's HTTP adapter uses npz so the HTTP rows
  measure transport, not float/JSON round-tripping.
* ``POST /v1/models/<name>:generate`` — one generation request (JSON
  only: token ids are small).
* ``GET /healthz`` — liveness of the target (a balancer's probe
  surface: 200 while something can serve, 503 after).
* ``GET /stats`` — the target's ``stats()`` dict (scheduler counters,
  program-store compile stats, weight versions, replica/breaker state)
  and, under ``phases``, the process's span totals.

**Deadline propagation**: ``timeout_ms`` (JSON body) or the
``X-Mxnet-Timeout-Ms`` header rides into the engine's queue-time
deadline, so an expired request sheds server-side exactly like an
in-process one.  **Structured failure mapping** (the fault contract
clients program against):

==========================  ======  =========
exception                   status  retryable
==========================  ======  =========
ServeTimeout                504     yes
ServeOverloaded             429     yes (back off)
ServeClosed                 503     yes (elsewhere)
NoLiveReplicas              503     yes (elsewhere)
ReplicaDied (generation)    503     yes (resubmit regenerates)
other MXNetError            400     no
anything else               500     no
==========================  ======  =========

:class:`HttpClient` is the matching client AND the loadgen transport
adapter: ``submit(...)`` returns a ``concurrent.futures.Future``
resolved by a small worker pool holding persistent connections, with
HTTP failure statuses mapped BACK to the exception classes above — so
``loadgen.run_loadgen`` drives an HTTP target through the same shared
``_drive_schedule`` driver, classifying timeouts/sheds/errors
identically to in-process targets (the ``serving.frontdoor.*`` bench
rows ride this).
"""
from __future__ import annotations

import hmac
import io
import json
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from .. import metrics as _metrics
from .. import profiler as _profiler
from .. import tracing as _tracing
from ..base import MXNetError, get_env
from .replica_set import NoLiveReplicas, ReplicaDied
from .scheduler import ServeClosed, ServeOverloaded, ServeTimeout

__all__ = ["HttpFrontDoor", "HttpClient"]

# exception class <-> (HTTP status, retryable): the structured failure
# contract, shared by the server's encoder and the client's decoder
_STATUS = (
    (ServeTimeout, 504, True),
    (ServeOverloaded, 429, True),
    (ReplicaDied, 503, True),
    (NoLiveReplicas, 503, True),
    (ServeClosed, 503, True),
)
_KIND_TO_EXC = {cls.__name__: cls for cls, _s, _r in _STATUS}


def _encode_error(exc):
    """(status, json_body) for one serving exception."""
    for cls, status, retryable in _STATUS:
        if isinstance(exc, cls):
            return status, {"error": str(exc), "kind": cls.__name__,
                            "retryable": retryable}
    if isinstance(exc, MXNetError):
        return 400, {"error": str(exc), "kind": "MXNetError",
                     "retryable": False}
    return 500, {"error": "%s: %s" % (type(exc).__name__, exc),
                 "kind": type(exc).__name__, "retryable": False}


def _decode_error(status, body):
    """The client-side inverse: an exception instance from an error
    reply (unknown kinds degrade to MXNetError with the status)."""
    try:
        d = json.loads(body.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        d = {"error": body[:200].decode("utf-8", "replace"),
             "kind": None}
    cls = _KIND_TO_EXC.get(d.get("kind"), MXNetError)
    return cls("HTTP %d from serving front door: %s"
               % (status, d.get("error")))


class _Handler(BaseHTTPRequestHandler):
    # one request per connection keep-alive: the loadgen clients hold
    # persistent connections
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # quiet: CI drives 100s of reqs
        pass

    @property
    def _door(self):
        return self.server.frontdoor

    # -- plumbing ------------------------------------------------------
    def _reply(self, status, payload, content_type="application/json"):
        if content_type == "application/json":
            body = json.dumps(payload).encode("utf-8")
        else:
            body = payload
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_error(self, exc):
        status, body = _encode_error(exc)
        self._reply(status, body)

    def _read_body(self):
        n = int(self.headers.get("Content-Length", 0))
        return self.rfile.read(n) if n else b""

    def _timeout_s(self, payload=None):
        """Deadline from the JSON body (timeout_ms) or the
        X-Mxnet-Timeout-Ms header; None = no deadline."""
        ms = None
        if payload is not None and payload.get("timeout_ms") is not None:
            ms = float(payload["timeout_ms"])
        else:
            h = self.headers.get("X-Mxnet-Timeout-Ms")
            if h:
                ms = float(h)
        return None if ms is None else max(0.0, ms) / 1e3

    def _tier_tenant(self, payload=None):
        """Admission metadata from the JSON body (``priority`` /
        ``tenant``) or — the npz transport's only channel — the
        ``X-Mxnet-Priority`` / ``X-Mxnet-Tenant`` headers.  Unknown
        tiers fail in the engine with a structured 400."""
        priority = tenant = None
        if payload is not None:
            priority = payload.get("priority")
            tenant = payload.get("tenant")
        if priority is None:
            priority = self.headers.get("X-Mxnet-Priority") or None
        if tenant is None:
            tenant = self.headers.get("X-Mxnet-Tenant") or None
        return priority, tenant

    def _authorized(self):
        """Bearer-token gate (``MXNET_SERVE_AUTH_TOKEN``).  No token
        configured = open door (in-cluster default).  ``/healthz`` and
        ``/metrics`` stay exempt so balancer probes and scrapers need
        no credential plumbing.  Failures get a structured 401 the
        client maps like every other serving error."""
        tok = self._door.auth_token
        if not tok or self.path in ("/healthz", "/metrics"):
            return True
        h = self.headers.get("Authorization") or ""
        # constant-time compare: the token must not leak via timing
        if h.startswith("Bearer ") and hmac.compare_digest(
                h[len("Bearer "):], tok):
            return True
        self._reply(401, {"error": "missing or invalid bearer token "
                                   "(Authorization: Bearer <token>)",
                          "kind": "Unauthorized", "retryable": False})
        return False

    # -- routes --------------------------------------------------------
    def do_GET(self):
        try:
            if not self._authorized():
                return
            if self.path == "/healthz":
                alive = self._door.healthy()
                self._reply(200 if alive else 503, {
                    "status": "ok" if alive else "dead",
                    "models": self._door.models(),
                })
            elif self.path == "/stats":
                self._reply(200, self._door.target_stats())
            elif self.path == "/metrics":
                # Prometheus text exposition of the process metrics
                # registry (docs/architecture/observability.md)
                self._reply(200,
                            _metrics.render_prometheus().encode("utf-8"),
                            content_type="text/plain; version=0.0.4")
            elif self.path == "/debug/flight":
                fl = _tracing.flight()
                self._reply(200, {"capacity": fl.capacity,
                                  "events": fl.events()})
            else:
                self._reply(404, {"error": "unknown path %r" % self.path,
                                  "kind": "NotFound", "retryable": False})
        except BrokenPipeError:
            pass
        except BaseException as e:  # noqa: BLE001 — reply, never crash
            self._safe_error(e)

    def do_POST(self):
        try:
            if not self._authorized():
                return
            model, verb = self._split_path()
            if verb == "predict":
                self._serve_predict(model)
            elif verb == "generate":
                self._serve_generate(model)
            else:
                self._reply(404, {"error": "unknown verb %r" % verb,
                                  "kind": "NotFound", "retryable": False})
        except BrokenPipeError:
            pass
        except BaseException as e:  # noqa: BLE001 — reply, never crash
            self._safe_error(e)

    def _safe_error(self, exc):
        try:
            self._reply_error(exc)
        except (BrokenPipeError, ConnectionResetError, OSError):
            pass

    def _split_path(self):
        # /v1/models/<name>:predict
        prefix = "/v1/models/"
        if not self.path.startswith(prefix) or ":" not in self.path:
            raise MXNetError("unknown path %r (want %s<model>:predict "
                             "or :generate)" % (self.path, prefix))
        name, verb = self.path[len(prefix):].rsplit(":", 1)
        return name, verb

    def _serve_predict(self, model):
        """One forward request end to end: parse (JSON or npz), submit
        with the propagated deadline, wait, encode.  Submit to reply is
        the ``serve_http`` profiler phase — HTTP overhead is the gap
        between it and the engine's serve_* phases."""
        ctype = (self.headers.get("Content-Type") or "").split(";")[0]
        body = self._read_body()
        npz = ctype == "application/x-npz"
        try:
            if npz:
                payload = None
                with np.load(io.BytesIO(body), allow_pickle=False) as z:
                    inputs = {k: z[k] for k in z.files}
            else:
                payload = json.loads(body.decode("utf-8"))
                inputs = {k: np.asarray(v)
                          for k, v in payload.get("inputs", {}).items()}
            timeout = self._timeout_s(payload)
            priority, tenant = self._tier_tenant(payload)
        except MXNetError:
            raise
        except Exception as e:  # noqa: BLE001 — client-caused: 400
            raise MXNetError("invalid request body: %s: %s"
                             % (type(e).__name__, e))
        # the request's trace is minted HERE — the network ingress —
        # and stays active across the submit, so every downstream span
        # (balancer dispatch, batch compute) is a child of this trace
        tr = _tracing.start_trace("http.predict", model=model)
        status = "error"
        try:
            with _tracing.activate(tr), _profiler.phase("serve_http"):
                try:
                    fut = self._door.target.submit(model, timeout=timeout,
                                                   priority=priority,
                                                   tenant=tenant,
                                                   **inputs)
                    outs = fut.result(self._door.wait_budget(timeout))
                except BaseException as e:  # noqa: BLE001 — structured
                    err = self._door.as_serving_error(e)
                    status = type(err).__name__
                    self._reply_error(err)
                    return
                outs = [np.asarray(o) for o in outs]
                if npz:
                    buf = io.BytesIO()
                    np.savez(buf, **{"output_%d" % i: o
                                     for i, o in enumerate(outs)})
                    self._reply(200, buf.getvalue(),
                                content_type="application/x-npz")
                else:
                    self._reply(200, {
                        "outputs": [o.tolist() for o in outs],
                        "shapes": [list(o.shape) for o in outs],
                        "dtypes": [str(o.dtype) for o in outs],
                    })
                status = "ok"
        finally:
            tr.finish(status=status)

    def _serve_generate(self, model):
        try:
            payload = json.loads(self._read_body().decode("utf-8"))
            timeout = self._timeout_s(payload)
            tokens = payload["tokens"]
            kwargs = {}
            for k in ("max_tokens", "temperature", "top_k", "seed",
                      "eos_id"):
                if payload.get(k) is not None:
                    kwargs[k] = payload[k]
            priority, tenant = self._tier_tenant(payload)
            if priority is not None:
                kwargs["priority"] = priority
            if tenant is not None:
                kwargs["tenant"] = tenant
        except Exception as e:  # noqa: BLE001 — client-caused: 400
            raise MXNetError("invalid request body: %s: %s"
                             % (type(e).__name__, e))
        # generation ingress mints the trace too: the prefill/decode/
        # sample spans of THIS request — across replica placement
        # retries — land under one trace id (the propagation pin)
        tr = _tracing.start_trace("http.generate", model=model)
        status = "error"
        try:
            with _tracing.activate(tr), _profiler.phase("serve_http"):
                try:
                    fut = self._door.gen_submit(model, tokens,
                                                timeout=timeout, **kwargs)
                    res = fut.result(self._door.wait_budget(timeout))
                except BaseException as e:  # noqa: BLE001 — structured
                    err = self._door.as_serving_error(e)
                    status = type(err).__name__
                    self._reply_error(err)
                    return
                self._reply(200, {
                    "model": res.model,
                    "tokens": [int(t) for t in res.tokens],
                    "finish_reason": res.finish_reason,
                    "prompt_len": int(res.prompt_len),
                    # host perf_counter stamps (CLOCK_MONOTONIC:
                    # comparable across processes on one host) so
                    # same-host clients — and the loadgen — derive
                    # TTFT/ITL exactly like in-process
                    "t_submit": res.t_submit,
                    "token_times": list(res.token_times),
                })
                status = "ok"
        finally:
            tr.finish(status=status)


class _Server(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True


class HttpFrontDoor:
    """HTTP surface over a serving target.

    ``target`` — anything speaking the serving submit contract: a
    :class:`~.scheduler.ServingEngine` or a
    :class:`~.replica_set.ReplicaSet` (whose ``submit_gen`` also backs
    ``:generate``).  ``gen_target`` — an optional separate
    :class:`~.decode_engine.GenerationEngine` when the forward target
    is a bare engine.  ``port=0`` binds an ephemeral port
    (``.address`` reports it).  ``max_wait`` bounds how long a handler
    thread waits on a future with no client deadline.  ``auth_token``
    (default ``MXNET_SERVE_AUTH_TOKEN``; empty = open) requires
    ``Authorization: Bearer <token>`` on every route except
    ``/healthz`` and ``/metrics``.  ``tls_cert`` / ``tls_key``
    (defaults ``MXNET_SERVE_TLS_CERT`` / ``MXNET_SERVE_TLS_KEY``) wrap
    the listening socket in TLS — both PEM paths or neither (one
    without the other is a config error, not silent plaintext);
    ``.url`` reports the scheme."""

    def __init__(self, target, host="127.0.0.1", port=0, gen_target=None,
                 max_wait=300.0, auth_token=None, tls_cert=None,
                 tls_key=None):
        self.target = target
        self._gen_target = gen_target
        if auth_token is None:
            auth_token = get_env("MXNET_SERVE_AUTH_TOKEN") or None
        self.auth_token = auth_token or None
        if tls_cert is None:
            tls_cert = get_env("MXNET_SERVE_TLS_CERT") or None
        if tls_key is None:
            tls_key = get_env("MXNET_SERVE_TLS_KEY") or None
        if bool(tls_cert) != bool(tls_key):
            raise MXNetError(
                "TLS needs BOTH a certificate and a key (set "
                "MXNET_SERVE_TLS_CERT and MXNET_SERVE_TLS_KEY "
                "together); refusing a half-configured endpoint")
        self.tls = bool(tls_cert)
        self._max_wait = float(max_wait)
        self._server = _Server((host, int(port)), _Handler)
        self._server.frontdoor = self
        if self.tls:
            import ssl
            try:
                ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
                ctx.load_cert_chain(tls_cert, tls_key)
                self._server.socket = ctx.wrap_socket(
                    self._server.socket, server_side=True)
            except MXNetError:
                raise
            except Exception as e:
                self._server.server_close()
                raise MXNetError("failed to arm TLS on the front "
                                 "door: %s: %s"
                                 % (type(e).__name__, e)) from e
        # /stats snapshot cache: one stats-tree walk per
        # MXNET_SERVE_STATS_TTL_MS window no matter how many pollers
        # (replies carry age_ms); /healthz's model listing shares it
        self._stats_cache = None
        self._stats_cache_t = 0.0
        self._stats_lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="mxt-http",
            daemon=True)
        self._thread.start()
        self._closed = False

    # -- target shims (handler-side helpers) ---------------------------
    @property
    def address(self):
        host, port = self._server.server_address[:2]
        return (host, port)

    @property
    def url(self):
        return "%s://%s:%d" % ((("https",) if self.tls else ("http",))
                               + self.address)

    def healthy(self):
        alive = getattr(self.target, "alive", None)
        return bool(alive()) if callable(alive) else True

    def models(self):
        # health pollers share the cached stats snapshot when it is
        # fresh instead of re-walking registries per probe
        with self._stats_lock:
            cached, t = self._stats_cache, self._stats_cache_t
        if cached is not None \
                and time.monotonic() - t <= self._stats_ttl():
            m = self._models_from_snapshot(cached)
            if m is not None:
                return m
        t = self.target
        reg = getattr(t, "_registry", None)
        if reg is not None:
            return reg.models()
        reps = getattr(t, "replicas", None)
        if callable(reps):
            for r in reps():
                if r.alive:
                    return r.registry.models()
        return []

    @staticmethod
    def _models_from_snapshot(cached):
        """Model names out of a cached stats tree, for either target
        shape: an engine's top-level ``models`` dict, or a replica
        set's ``replicas -> {i: {alive, engine: {models}}}`` nesting
        (first live replica wins — replicas are shared-nothing copies
        of the same registry).  None = shape unknown, walk instead."""
        m = cached.get("models")
        if isinstance(m, dict):
            return sorted(m)
        reps = cached.get("replicas")
        if isinstance(reps, dict):
            for r in reps.values():
                if not isinstance(r, dict) or not r.get("alive", False):
                    continue
                em = r.get("engine", {}).get("models")
                if isinstance(em, dict):
                    return sorted(em)
            return []
        return None

    @staticmethod
    def _stats_ttl():
        return max(0.0, float(get_env("MXNET_SERVE_STATS_TTL_MS"))) / 1e3

    def target_stats(self):
        """The target's stats tree, served from a TTL-bounded cache:
        a poll within ``MXNET_SERVE_STATS_TTL_MS`` of the last walk
        returns the cached snapshot (its ``age_ms`` field says how
        stale) instead of re-walking every engine/replica/store stats
        surface per request."""
        now = time.monotonic()
        with self._stats_lock:
            if self._stats_cache is None \
                    or now - self._stats_cache_t > self._stats_ttl():
                # "phases": every span the process closed, by name
                # (the engine's ticks, admissions and fetches with
                # their counts; profiler.phase_totals)
                self._stats_cache = dict(
                    self.target.stats(),
                    phases=_profiler.phase_totals())
                self._stats_cache_t = now
            out = dict(self._stats_cache)
            out["age_ms"] = round((now - self._stats_cache_t) * 1e3, 3)
        return out

    def gen_submit(self, model, tokens, **kwargs):
        # an EXPLICIT gen_target wins over the forward target's own
        # submit_gen (a forward-only ReplicaSet can front a separate
        # generation engine)
        if self._gen_target is not None:
            return self._gen_target.submit(model, tokens, **kwargs)
        if hasattr(self.target, "submit_gen"):
            return self.target.submit_gen(model, tokens, **kwargs)
        raise MXNetError("this front door serves no generation target")

    def wait_budget(self, timeout):
        """How long a handler thread waits on the future: the client's
        deadline plus compute grace, else the server-wide cap."""
        if timeout is None:
            return self._max_wait
        return timeout + self._max_wait

    def as_serving_error(self, exc):
        """Normalize waiting errors: a Future.result timeout becomes
        ServeTimeout (the handler out-waited the deadline + grace)."""
        import concurrent.futures
        if isinstance(exc, concurrent.futures.TimeoutError):
            return ServeTimeout("request did not complete within the "
                                "front door's wait budget")
        if isinstance(exc, concurrent.futures.CancelledError):
            return ServeClosed("request was cancelled")
        return exc

    def close(self, timeout=30.0):
        """Stop accepting, join the acceptor thread.  In-flight handler
        threads (daemon) finish their replies on their own."""
        if self._closed:
            return
        self._closed = True
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# Client + loadgen transport adapter
# ---------------------------------------------------------------------------
class HttpClient:
    """Future-returning HTTP client for the front door.

    A pool of worker threads holds one persistent connection each;
    ``submit`` / ``generate`` enqueue a request and return a
    ``concurrent.futures.Future``, so the SAME seeded
    ``OpenLoopSchedule`` + ``run_loadgen`` machinery that drives
    in-process engines drives an HTTP front door — the transport is the
    only variable (the ``serving.frontdoor.http_overhead`` bench row's
    whole point).  Error replies map back to the serving exception
    classes, so the loadgen's timeout/error classification is
    transport-invariant.  ``auth_token`` (default
    ``MXNET_SERVE_AUTH_TOKEN``) rides every request as a bearer
    credential.  ``tls`` turns the connections into TLS (inferred from
    an ``https://`` address string, e.g. a TLS front door's ``.url``);
    ``tls_verify`` (default ``MXNET_SERVE_TLS_VERIFY``) is ``"1"`` for
    the system trust store, ``"0"`` to skip verification, or a PEM
    path pinning the accepted CA/certificate (how a client trusts a
    self-signed front door without disabling verification)."""

    def __init__(self, address, threads=8, connect_timeout=120.0,
                 auth_token=None, tls=None, tls_verify=None):
        if isinstance(address, str):
            if tls is None and address.startswith("https://"):
                tls = True
            host, port = address.rsplit(":", 1)
            address = (host.replace("https://", "")
                       .replace("http://", "").strip("/"), int(port))
        self._addr = (address[0], int(address[1]))
        self._tls = bool(tls)
        self._ssl_ctx = self._tls_context(tls_verify) if self._tls \
            else None
        if auth_token is None:
            auth_token = get_env("MXNET_SERVE_AUTH_TOKEN") or None
        self._auth_token = auth_token or None
        self._timeout = float(connect_timeout)
        self._closed = False
        self._close_lock = threading.Lock()
        self._q = queue.Queue()
        self._threads = []
        for i in range(int(threads)):
            t = threading.Thread(target=self._worker,
                                 name="mxt-http-client-%d" % i,
                                 daemon=True)
            t.start()
            self._threads.append(t)

    # -- public --------------------------------------------------------
    def submit(self, model, inputs, timeout=None, priority=None,
               tenant=None):
        """One forward request over npz transport; returns a Future
        resolving to the list of output arrays (bit-exact: no JSON
        float round-trip).  ``priority`` / ``tenant`` ride the
        ``X-Mxnet-Priority`` / ``X-Mxnet-Tenant`` headers into the
        engine's tiered admission."""
        buf = io.BytesIO()
        np.savez(buf, **{k: np.asarray(v) for k, v in inputs.items()})
        headers = {"Content-Type": "application/x-npz"}
        if timeout is not None:
            headers["X-Mxnet-Timeout-Ms"] = "%g" % (timeout * 1e3)
        if priority is not None:
            headers["X-Mxnet-Priority"] = str(priority)
        if tenant is not None:
            headers["X-Mxnet-Tenant"] = str(tenant)
        return self._enqueue("POST", "/v1/models/%s:predict" % model,
                             buf.getvalue(), headers, self._parse_npz)

    def submit_json(self, model, inputs, timeout=None, priority=None,
                    tenant=None):
        """The curl-shaped JSON variant (lists in, lists out)."""
        payload = {"inputs": {k: np.asarray(v).tolist()
                              for k, v in inputs.items()}}
        if timeout is not None:
            payload["timeout_ms"] = timeout * 1e3
        if priority is not None:
            payload["priority"] = priority
        if tenant is not None:
            payload["tenant"] = tenant
        return self._enqueue(
            "POST", "/v1/models/%s:predict" % model,
            json.dumps(payload).encode("utf-8"),
            {"Content-Type": "application/json"}, self._parse_json)

    def generate(self, model, tokens, timeout=None, **kwargs):
        """One generation request; the Future resolves to a
        :class:`~.decode_engine.GenerationResult` rebuilt from the
        reply (token_times are host-monotonic stamps, comparable on
        the same host)."""
        payload = {"tokens": [int(t) for t in tokens]}
        payload.update(kwargs)
        if timeout is not None:
            payload["timeout_ms"] = timeout * 1e3
        # retryable=False: a generation is NOT idempotent — a
        # redial-resend after the server already admitted it would
        # double-execute (the replica set's own no-retry-after-
        # admission contract, applied to the transport)
        return self._enqueue(
            "POST", "/v1/models/%s:generate" % model,
            json.dumps(payload).encode("utf-8"),
            {"Content-Type": "application/json"}, self._parse_gen,
            retryable=False)

    def healthz(self):
        """Synchronous health check: (status_code, payload dict)."""
        fut = self._enqueue("GET", "/healthz", None, {}, self._parse_raw)
        return fut.result(self._timeout)

    def stats(self):
        fut = self._enqueue("GET", "/stats", None, {}, self._parse_raw)
        code, payload = fut.result(self._timeout)
        if code != 200:
            raise MXNetError("stats failed: HTTP %d" % code)
        return payload

    def metrics_text(self):
        """``GET /metrics``: the Prometheus text exposition."""
        fut = self._enqueue("GET", "/metrics", None, {},
                            lambda status, body: (status, body))
        code, body = fut.result(self._timeout)
        if code != 200:
            raise MXNetError("metrics failed: HTTP %d" % code)
        return body.decode("utf-8")

    def debug_flight(self):
        """``GET /debug/flight``: the server's flight-recorder ring."""
        fut = self._enqueue("GET", "/debug/flight", None, {},
                            self._parse_raw)
        code, payload = fut.result(self._timeout)
        if code != 200:
            raise MXNetError("debug/flight failed: HTTP %d" % code)
        return payload

    def close(self):
        with self._close_lock:
            # the lock orders every _enqueue strictly before or after
            # the flag: after it, _enqueue raises, so nothing can land
            # behind the sentinels or after the drain below
            self._closed = True
            for _ in self._threads:
                self._q.put(None)
        for t in self._threads:
            t.join(30)
        # anything enqueued before close() but behind a sentinel is
        # unreachable by the workers: fail its future instead of
        # leaving the caller pending forever
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if item is not None:
                try:
                    item[-1].set_exception(
                        ServeClosed("HttpClient is closed"))
                except InvalidStateError:
                    pass    # caller cancelled while we drained

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- worker pool ---------------------------------------------------
    @staticmethod
    def _tls_context(verify):
        """Client-side SSL context from the verify knob: ``"1"`` =
        system trust store, ``"0"`` = no verification (lab use),
        anything else = a PEM path pinning the accepted certificate
        chain (the self-signed deployment's knob)."""
        import ssl
        if verify is None:
            verify = get_env("MXNET_SERVE_TLS_VERIFY")
        verify = str(verify if verify is not None else "1") or "1"
        if verify == "0":
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
            ctx.check_hostname = False
            ctx.verify_mode = ssl.CERT_NONE
            return ctx
        if verify == "1":
            return ssl.create_default_context()
        try:
            return ssl.create_default_context(cafile=verify)
        except Exception as e:
            raise MXNetError(
                "MXNET_SERVE_TLS_VERIFY=%r is neither 0/1 nor a "
                "readable PEM: %s: %s"
                % (verify, type(e).__name__, e)) from e

    def _connect(self):
        """One fresh connection honoring the TLS mode."""
        import http.client
        if self._tls:
            return http.client.HTTPSConnection(
                *self._addr, timeout=self._timeout,
                context=self._ssl_ctx)
        return http.client.HTTPConnection(*self._addr,
                                          timeout=self._timeout)

    def _enqueue(self, method, path, body, headers, parse,
                 retryable=True):
        if self._auth_token and "Authorization" not in headers:
            headers = dict(headers)
            headers["Authorization"] = "Bearer %s" % self._auth_token
        with self._close_lock:
            if self._closed:
                raise ServeClosed("HttpClient is closed")
            fut = Future()
            self._q.put((method, path, body, headers, parse, retryable,
                         fut))
        return fut

    @staticmethod
    def _parse_npz(status, body):
        if status != 200:
            raise _decode_error(status, body)
        with np.load(io.BytesIO(body), allow_pickle=False) as z:
            return [z["output_%d" % i] for i in range(len(z.files))]

    @staticmethod
    def _parse_json(status, body):
        if status != 200:
            raise _decode_error(status, body)
        d = json.loads(body.decode("utf-8"))
        return [np.asarray(o, dtype=dt).reshape(sh) for o, sh, dt in
                zip(d["outputs"], d["shapes"], d["dtypes"])]

    @staticmethod
    def _parse_gen(status, body):
        if status != 200:
            raise _decode_error(status, body)
        d = json.loads(body.decode("utf-8"))
        from .decode_engine import GenerationResult
        return GenerationResult(d["model"], d["prompt_len"], d["tokens"],
                                d["finish_reason"], d["t_submit"],
                                d["token_times"])

    @staticmethod
    def _parse_raw(status, body):
        try:
            return status, json.loads(body.decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            return status, None

    def _worker(self):
        import http.client
        conn = None
        while True:
            item = self._q.get()
            if item is None:
                if conn is not None:
                    conn.close()
                return
            method, path, body, headers, parse, retryable, fut = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                if retryable:
                    for attempt in (0, 1):
                        if conn is None:
                            conn = self._connect()
                        try:
                            conn.request(method, path, body=body,
                                         headers=headers)
                            resp = conn.getresponse()
                            payload = resp.read()
                            break
                        except (http.client.HTTPException, OSError):
                            # stale persistent connection: redial once
                            # (idempotent requests only — a resend
                            # cannot double-execute a pure forward)
                            conn.close()
                            conn = None
                            if attempt:
                                raise
                else:
                    # non-idempotent (:generate): ONE attempt on a
                    # FRESH connection — no stale-keepalive failure
                    # mode, and never a retransmit the server might
                    # have already admitted
                    c2 = self._connect()
                    try:
                        c2.request(method, path, body=body,
                                   headers=headers)
                        resp = c2.getresponse()
                        payload = resp.read()
                    finally:
                        c2.close()
                fut.set_result(parse(resp.status, payload))
            except BaseException as e:  # noqa: BLE001 — to the future
                try:
                    fut.set_exception(e)
                except Exception:  # InvalidStateError: cancel raced
                    pass
