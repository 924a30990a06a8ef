"""Serving-plane smoke gate: seeded loadgen p50/p99 + QPS floor.

Runs the shared serving latency protocol
(``mxnet_tpu.serving.loadgen.latency_protocol``) in smoke mode on CPU:

1. per-request ``Predictor.forward`` closed-loop (service baseline),
2. the same Predictor behind a FIFO worker under the seeded open-loop
   schedule (the no-batching deployment under overload),
3. the continuous batcher under the SAME schedule.

``--dtype`` selects the serving dtype (fp32 / bf16 / int8 weight-only
via the fused dequant-matmul door) or ``all`` to cycle the whole dtype
matrix through the SAME seeded schedule — one command demonstrates
fp32, bf16 and int8 serving end to end, printing each side's resident
weight bytes beside its latency table.

Gates (exit 1 on failure, per dtype):

* the batcher's achieved QPS >= ``--qps-floor`` (default 3.0) times the
  per-request deployment's achieved QPS — the ratio is host-relative, so
  the gate holds on any machine;
* the batcher's p99 is no worse than the per-request deployment's p99
  under the same offered load ("equal p99" comparison);
* zero timeouts/errors/lost requests on either side.

Deterministic: the arrival schedule and request contents derive from
``--seed`` (faultinject-style); residual wall-clock noise moves the
measured numbers, not the schedule.

Front-door modes (``make frontdoor-smoke`` runs all three; each is a
seeded deterministic scenario over the shared loadgen protocols in
``serving/loadgen.py``):

* ``--http`` — HTTP front door vs in-process on the SAME schedule
  (gates: zero drops on both transports, achieved QPS tracks offered);
* ``--kill-one`` (with ``--replicas N``) — one of N shared-nothing
  replicas SIGKILLed by a seeded ``die`` at the ``serve.dispatch``
  faultinject seam under open-loop load (gates: 100% of accepted
  requests resolve, zero drops, balancer converges to N-1 survivors,
  post-kill achieved QPS >= 2/3 of pre-kill);
* ``--swap`` — hot weight swap under concurrent traffic (gates: every
  response bit-matches exactly one of {old, new} weights — zero torn
  reads — and the version counter advances exactly once).

Usage::

    python tools/serve_smoke.py [--seed 11] [--qps-floor 3.0] [--full]
        [--dtype fp32|bf16|int8|all]
        [--replicas 3] [--kill-one] [--swap] [--http]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def run_mode(mode, args):
    """One dtype through the shared protocol; returns the failure list
    (empty = this side's gates hold)."""
    from mxnet_tpu.serving.loadgen import latency_protocol
    r = latency_protocol(mode=mode, smoke=not args.full, seed=args.seed)
    if args.json:
        print(json.dumps(r, indent=1))

    sc, so, b = r["serial_closed"], r["serial_open"], r["batch"]

    def f(v, spec="%.2f"):
        # a side with zero successful requests reports None percentiles
        # — the gate below turns that into a FAIL, not a TypeError
        return ("n/a" if v is None else spec % v).rjust(10)

    wb = b.get("engine", {}).get("weight_bytes_by_dtype", {})
    print("serve-smoke (%s, seed %d, offered %.0fx capacity, "
          "resident weights: %s)"
          % (mode, args.seed, r["offered_mult"],
             " + ".join("%d B %s" % (n, dt)
                        for dt, n in sorted(wb.items())) or "?"))
    print("  %-28s %10s %10s %10s" % ("", "qps", "p50 ms", "p99 ms"))
    print("  %-28s %s %s %s"
          % ("per-request closed-loop", f(sc["qps"], "%.1f"),
             f(sc["p50_ms"]), f(sc["p99_ms"])))
    print("  %-28s %s %s %s"
          % ("per-request under load", f(so["qps_achieved"], "%.1f"),
             f(so["p50_ms"]), f(so["p99_ms"])))
    print("  %-28s %s %s %s"
          % ("continuous batcher", f(b["qps_achieved"], "%.1f"),
             f(b["p50_ms"]), f(b["p99_ms"])))
    print("  batcher QPS vs per-request: %s (floor %.1fx); "
          "p99 ratio: %s" % (f(r["qps_vs_per_request"]).strip(),
                             args.qps_floor,
                             f(r["p99_vs_per_request"], "%.3f").strip()))

    failures = []
    for tag, side in (("per-request", so), ("batcher", b)):
        bad = side["timeouts"] + side["errors"] + side["cancelled"]
        if bad:
            failures.append("%s side dropped %d of %d requests"
                            % (tag, bad, side["n"]))
    if r["qps_vs_per_request"] is None:
        failures.append("QPS ratio unavailable (a side had zero "
                        "successful requests)")
    elif r["qps_vs_per_request"] < args.qps_floor:
        failures.append("QPS ratio %.2f below the %.1fx floor"
                        % (r["qps_vs_per_request"], args.qps_floor))
    if b["p99_ms"] is not None and so["p99_ms"] is not None \
            and b["p99_ms"] > so["p99_ms"]:
        failures.append("batcher p99 %.1fms worse than per-request "
                        "%.1fms at the same offered load"
                        % (b["p99_ms"], so["p99_ms"]))
    return ["%s: %s" % (mode, msg) for msg in failures]


def run_http(args):
    """HTTP-vs-in-process on the same seeded schedule; returns the
    failure list."""
    from mxnet_tpu.serving.loadgen import frontdoor_protocol
    r = frontdoor_protocol(smoke=not args.full, seed=args.seed + 6)
    if args.json:
        print(json.dumps(r, indent=1))
    h, ip = r["http"], r["inproc"]

    def f(v):
        # a side with zero successes reports None percentiles: keep
        # the report printable so the FAIL lines below still emit
        return "n/a" if v is None else "%.2f" % v

    print("frontdoor-http (seed %d): in-process p50/p99 %s/%s ms, "
          "HTTP %s/%s ms (p99 ratio %s), achieved %s vs %s qps"
          % (args.seed + 6, f(ip["p50_ms"]), f(ip["p99_ms"]),
             f(h["p50_ms"]), f(h["p99_ms"]), r["http_p99_vs_inproc"],
             h["qps_achieved"], ip["qps_achieved"]))
    failures = []
    for tag, side in (("in-process", ip), ("http", h)):
        bad = side["timeouts"] + side["errors"] + side["cancelled"]
        if bad:
            failures.append("http: %s side dropped %d of %d"
                            % (tag, bad, side["n"]))
    if r["http_qps_vs_inproc"] is None or r["http_qps_vs_inproc"] < 0.8:
        failures.append("http: achieved QPS over HTTP is %s of "
                        "in-process (want >= 0.8 below saturation)"
                        % r["http_qps_vs_inproc"])
    return failures


def run_kill_one(args):
    """Kill-one-of-N drain scenario; returns the failure list."""
    from mxnet_tpu.serving.loadgen import failover_protocol
    r = failover_protocol(smoke=not args.full, seed=args.seed + 8,
                          n_replicas=args.replicas)
    if args.json:
        print(json.dumps(r, indent=1))
    s = r["summary"]
    print("frontdoor-kill-one (seed %d, %d replicas): %d/%d resolved, "
          "%d dropped, failovers %d, live after %s, post/pre qps %s, "
          "recovery %s ms"
          % (args.seed + 8, r["n_replicas"], r["resolved"], s["n"],
             r["dropped"], r["failovers"], r["live_after"],
             r.get("post_vs_pre_qps"), r.get("recovery_ms")))
    failures = []
    if not r["killed"]:
        failures.append("kill-one: the seeded die never fired")
    if r["resolved"] != s["n"]:
        failures.append("kill-one: %d of %d requests never resolved "
                        "(client hang)" % (s["n"] - r["resolved"],
                                           s["n"]))
    if r["dropped"]:
        failures.append("kill-one: %d accepted requests dropped"
                        % r["dropped"])
    if len(r["live_after"]) != args.replicas - 1:
        failures.append("kill-one: balancer did not converge to %d "
                        "survivors (live: %s)"
                        % (args.replicas - 1, r["live_after"]))
    ratio = r.get("post_vs_pre_qps")
    if ratio is not None and ratio < 2.0 / 3.0:
        failures.append("kill-one: post-kill QPS %.2f of pre-kill "
                        "(want >= 2/3)" % ratio)
    return failures


def run_swap(args):
    """Hot-swap bit-consistency scenario; returns the failure list."""
    from mxnet_tpu.serving.loadgen import swap_protocol
    r = swap_protocol(smoke=not args.full, seed=args.seed + 12)
    if args.json:
        print(json.dumps(r, indent=1))
    print("frontdoor-swap (seed %d): %d responses -> %d old + %d new + "
          "%d neither; version %d -> %d"
          % (args.seed + 12, r["n"], r["old"], r["new"], r["neither"],
             r["version_before"], r["version_after"]))
    failures = []
    if r["neither"]:
        failures.append("swap: %d responses matched NEITHER weight "
                        "version (torn read)" % r["neither"])
    if not (r["old"] and r["new"]):
        failures.append("swap: traffic did not straddle the swap "
                        "(old=%d new=%d)" % (r["old"], r["new"]))
    if r["version_increments"] != 1:
        failures.append("swap: version counter advanced %d times "
                        "(want exactly 1)" % r["version_increments"])
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--qps-floor", type=float, default=3.0,
                    help="min batcher/per-request achieved-QPS ratio")
    ap.add_argument("--full", action="store_true",
                    help="full-size protocol (smoke=False)")
    ap.add_argument("--dtype", default="fp32",
                    choices=("fp32", "bf16", "int8", "all"),
                    help="serving dtype, or 'all' to cycle the whole "
                         "fp32/bf16/int8 matrix on the same schedule")
    ap.add_argument("--mode", dest="dtype",
                    choices=("fp32", "bf16", "int8"),
                    help=argparse.SUPPRESS)  # pre-dtype-matrix alias
    ap.add_argument("--replicas", type=int, default=3,
                    help="replica count for --kill-one")
    ap.add_argument("--kill-one", action="store_true",
                    help="kill-one-replica-under-load drain gate")
    ap.add_argument("--swap", action="store_true",
                    help="hot-weight-swap bit-consistency gate")
    ap.add_argument("--http", action="store_true",
                    help="HTTP front door vs in-process gate")
    ap.add_argument("--json", action="store_true",
                    help="dump the full protocol result as JSON")
    args = ap.parse_args(argv)

    from mxnet_tpu.base import use_compile_cache
    use_compile_cache()
    failures = []
    ran = []
    frontdoor_only = args.kill_one or args.swap or args.http
    if args.http:
        failures += run_http(args)
        ran.append("http")
    if args.kill_one:
        failures += run_kill_one(args)
        ran.append("kill-one")
    if args.swap:
        failures += run_swap(args)
        ran.append("swap")
    if not frontdoor_only:
        modes = (("fp32", "bf16", "int8") if args.dtype == "all"
                 else (args.dtype,))
        for mode in modes:
            failures += run_mode(mode, args)
        ran += list(modes)
    if failures:
        for msg in failures:
            print("FAIL: %s" % msg)
        return 1
    print("serve-smoke: OK (%s)" % ", ".join(ran))
    return 0


if __name__ == "__main__":
    sys.exit(main())
