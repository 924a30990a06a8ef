"""Driver ``serve-hybrid``: ``drivers/serve-arch.py`` for a model that
keeps a per-sequence state beside its paged cache, every request due at
0 (a backlog; it has no open loop).  The ramp, the window, the stamps,
the seeded weights drawn a layer at a time and the reference's pass are
that driver's and ``drivers/serve.py``'s, imported, not copied.  What
differs:

* ``LIMITS`` below were read from ``lfm2-24b-a2b.serve-agent-backlog``;
* the engine's counters are read every ``SLICE_S`` seconds of the
  window, so that a run shows whether its window lay on a steady state
  (``decode_batch_by_slice``), and the state counters with them;
* a traced run hands the readers the program's span totals OVER THE
  TRACED SECONDS (``host["traced_phases"]``), so that a roofline
  multiplies nothing taken over the whole window by a count taken over
  three seconds of it;
* how many of the compared requests were admitted on a prefix hit is
  said: a state restored wrongly has to fail ``correct``.
"""
from __future__ import annotations

import gc
import importlib
import statistics
import threading
import time

import numpy as np

from benchmark import harness, traffic
from benchmark.drivers.serve import (Stamps, _sleep_until, decision_gaps,
                                     tokens_in)

SLICE_S = 5.0
# deltas of GenerationEngine.stats() over the window, beside the ones
# every serve driver reads; a program that lacks one leaves it out
COUNTERS = ("decode_steps", "generated_tokens", "prefill_chunks",
            "prefix_hit_tokens", "prefix_hits", "requests", "finished",
            "shed", "errors", "cow_forks", "prefill_row_slots",
            "prefill_rows_deferred", "prompt_tokens_admitted",
            "state_restores", "moe_tokens", "moe_local_assignments",
            "moe_expert_load_max", "moe_expert_steps",
            "moe_experts_touched", "moe_expert_streams")

# Limits of the comparison with the plain reference (float32 at
# "highest" over the same bfloat16-rounded weights), each beside the
# readings it was set from: my chip runs, PR 31, of
# ``lfm2-24b-a2b.serve-agent-backlog`` (PERF.md section 2): 48 or 49
# requests a run, the longest among them, 34 to 40 of them admitted on
# a prefix hit, 12,000 to 13,600 served tokens and as many decisions
# (seeded weights under a tied head of 65,536 rows loop over nothing).
# SOUND is the cell as committed, 20 runs over 14 seeds; CONTROL int8
# weights (``run.py --control``; PERF.md has all three runs).
LIMITS = {
    # Share of the decisions in which the served token is not the
    # reference's best.  It is HIGH here by the model's nature and says
    # so: 64 experts of which 4 are picked in each of 8 layers put a
    # 4th-against-5th near-tie in front of bfloat16 activations in
    # about every second token, the token after it was computed with
    # another expert, and the reference's first two logits lie 0.2
    # apart (the gap between the two largest of 65,536 unit normals).
    # SOUND 0.189 .. 0.207, CONTROL 0.54: the number the lower
    # precision has to fail, 1.45x above the one and 1.8x under the
    # other; a binomial of 0.2 over 12,000 strays by 0.004.
    "flip_share": 0.30,
    # Mean gap of the flips (fewer than FLIPS_MIN averaged as that
    # many; a run has 2,300 to 2,700, so the mean strays by 0.006).
    # SOUND 0.205 .. 0.229, CONTROL 0.40: the lower precision fails it
    # too.  Held against a fault of every token (a state restored
    # wrongly, a wrong scale, a dropped layer).
    "flip_gap_mean": 0.30,
    # The widest gap of any token (SOUND 1.56 .. 2.47, CONTROL 3.04) is
    # held against a token altered where it is produced, whose gap is
    # what the reference's best has over a token taken blindly: 4.3,
    # the largest of 65,536 unit normals, give or take one.
    "token_gap_max": 3.5,
}
FLIPS_MIN = 10


def compare(sample, gaps):
    """The checks of ``correct`` (``serve-arch.compare`` under this
    driver's ``LIMITS``): ``sample`` holds (prompt, served tokens) and
    ``gaps`` the reference's (gap, best token) arrays of each sampled
    request."""
    decisions = [d for (_, served), (g, b) in zip(sample, gaps)
                 for d in decision_gaps(g, b, served)]
    if not decisions:
        return [harness.check("decisions_compared", 0, 0, ok=False)]
    flips = sorted(g for g, flipped in decisions if flipped)
    harness.say(decisions_compared=len(decisions), flips=len(flips),
                decision_gap_mean=statistics.fmean(
                    g for g, _ in decisions),
                flip_gap_p50=flips[len(flips) // 2] if flips else 0.0,
                flip_gap_p90=flips[len(flips) * 9 // 10] if flips else 0.0)
    return [harness.check("flip_share", len(flips) / len(decisions),
                          LIMITS["flip_share"]),
            harness.check("flip_gap_mean",
                          sum(flips) / max(len(flips), FLIPS_MIN),
                          LIMITS["flip_gap_mean"]),
            harness.check("token_gap_max", max(g.max() for g, _ in gaps),
                          LIMITS["token_gap_max"])]


def _phases(since=None):
    """The program's span totals (what they gained over the earlier
    reading ``since``), {} from a program without them."""
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return {}
    read = getattr(profiler, "phase_totals", None)
    return read(since=since) if read else {}


def run(cell, devices, args, t0):
    import jax
    from mxnet_tpu.serving import GenerationEngine, ModelRegistry
    arch = harness.load_module(cell.bench, "drivers/serve-arch.py")
    cfg, mix = cell.config, cell.traffic
    arrival = mix["arrival"]
    if arrival["kind"] != "backlog":
        raise harness.BenchError("driver serve-hybrid offers a backlog, "
                                 "not %r" % arrival["kind"])
    ref = cell.module("reference")
    compiles = harness.CompileCounter()
    trace = harness.DeviceTrace() if args.trace else None
    seconds, ramp = float(args.seconds), float(mix["ramp_s"])

    spec = cfg["spec"]          # whole, its "arch" names the model
    try:
        importlib.import_module("mxnet_tpu.models." + spec["arch"])
    except ImportError as e:
        # a program from before the model fails here, at once
        raise harness.BenchError("the program has no model %r: %s"
                                 % (spec["arch"], e))
    reqs = traffic.make_requests(mix, arrival, int(spec["vocab_size"]),
                                 args.seed, ramp + seconds)
    params = arch._draw(ref, cfg, args.seed)
    registry = ModelRegistry()
    store = registry.add_generative_model(
        "lm", params, spec, compute_dtype=cfg.get("compute_dtype"),
        warmup=False, **cfg["deploy"])
    # the store's copy is the only one BEFORE the programs run once
    # (serve-arch.py tells why)
    del params
    store.warmup()
    warm_compiles = store.stats()["compiles"]
    engine = GenerationEngine(registry)

    # ---- traffic: ramp, window ------------------------------------------
    slices = []                     # engine.stats() every SLICE_S
    t_start = time.perf_counter()
    t_open, t_close = t_start + ramp, t_start + ramp + seconds

    def at_slice():
        slices.append(engine.stats())

    spans = {}

    def at_open():
        at_slice()
        spans["open"] = _phases()
        compiles.mark()

    def at_close():
        compiles.freeze()
        spans["window"] = _phases(since=spans["open"])
        at_slice()

    events = [(t_open, at_open), (t_close, at_close)]
    events += [(t_open + k * SLICE_S, at_slice)
               for k in range(1, int(np.ceil(seconds / SLICE_S)))]
    events.sort(key=lambda e: e[0])
    traced = {}
    tracer = None
    if trace is not None:
        def tracing():
            _sleep_until(t_open + float(mix["trace_after_s"]))
            trace.start()
            was = _phases()
            _sleep_until(trace.t_start + float(mix["trace_seconds"]))
            traced.update(_phases(since=was))
            trace.stop()
        tracer = threading.Thread(target=tracing, name="bench-trace")
        tracer.start()
    sent = []                       # (request, sent, future, stamps)
    for req in reqs:                # every request is due at 0
        stamps = Stamps()
        with jax.profiler.TraceAnnotation("engine.submit"):
            now = time.perf_counter()
            fut = engine.submit("lm", req.prompt,
                                max_tokens=req.max_tokens, stream=stamps)
        sent.append((req, now, fut, stamps))
    for due, fn in events:
        _sleep_until(due)
        fn()
    for row in sent:
        row[2].cancel()                        # still queued: not served
    rows = []       # (request, stamps, tokens, finished)
    failed_exc = 0
    for req, at, fut, stamps in sent:
        n = len(stamps.times)                  # the engine may go on
        ok = fut.done() and not fut.cancelled() \
            and fut.exception() is None
        if fut.done() and not fut.cancelled() and not ok:
            failed_exc += 1
            harness.say(request_failed=repr(fut.exception())[:200])
        rows.append((req, stamps.times[:n], stamps.tokens[:n], ok))
    # a future leads back to the engine and through it to the store's
    # weights: none may outlive this line, or the reference (which needs
    # the chip the program held) finds 10 GB of it taken
    offered = len(sent)
    del sent
    fut = row = None
    if tracer is not None:
        tracer.join()
    stats_end = engine.stats()
    peak = harness.memory_peak_bytes(devices)
    late_compiles = store.stats()["compiles"] - warm_compiles
    engine.close(drain=False)
    compiles.close()

    # ---- what the window held ------------------------------------------
    first, last = slices[0], slices[-1]
    delta = {k: last[k] - first[k] for k in COUNTERS if k in first}
    if "state_rows_live" in first:
        # a level, not a count: its mean over the window's readings
        delta["state_rows_live"] = statistics.fmean(
            s["state_rows_live"] for s in slices)
        delta["state_bytes"] = last["state_bytes"]
    by_slice = [(b["generated_tokens"] - a["generated_tokens"])
                / max(b["decode_steps"] - a["decode_steps"], 1)
                for a, b in zip(slices, slices[1:])]
    stamped = [(0.0, r[1]) for r in rows]
    done = [r for r in rows if r[3] and t_open <= r[1][-1] < t_close]
    end_to_end = {"setup_s": t_open - t0,
                  "serve_tokens_per_s": tokens_in(stamped, t_open, t_close)
                  / seconds}
    harness.say(requests_completed_in_window=len(done),
                completed_requests_per_s=len(done) / seconds,
                offered=offered,
                started_by_the_close=sum(1 for r in rows if r[1]),
                never_started=sum(1 for r in rows if not r[1]),
                decode_batch_by_slice=by_slice,
                tokens_by_slice=[
                    tokens_in(stamped, t_open + k * SLICE_S,
                              min(t_open + (k + 1) * SLICE_S, t_close))
                    for k in range(len(by_slice))])
    if not harness.REHEARSAL:
        # where the engine thread's time went, a span of each kind
        harness.say(window_spans={
            name: {"spans": got["spans"],
                   "ms_a_span": 1e-6 * got["ns"] / got["spans"]}
            for name, got in spans["window"].items()
            if name.startswith(("serve_", "cow_")) and got["spans"]})
    harness.say(window_s=seconds, ramp_s=ramp, counters=delta,
                compiles_total=compiles.total,
                compile_or_fetch_s=compiles.seconds,
                compiles_in_window=compiles.in_window,
                store_compiles_after_warmup=late_compiles,
                peak_bytes_in_use=peak,
                pool=stats_end["cache_state"].get("lm"))
    if compiles.in_window:
        raise harness.BenchError("%d compilations inside the window"
                                 % compiles.in_window)

    # ---- free the program, then the reference ---------------------------
    finished = [r for r in rows if r[3]]
    rng = np.random.default_rng(int(args.seed))
    picks = set(rng.choice(len(finished),
                           min(int(mix["check_requests"]), len(finished)),
                           replace=False).tolist()) if finished else set()
    if finished:
        picks.add(max(range(len(finished)), key=lambda i: len(
            finished[i][0].prompt) + len(finished[i][2])))
    sample = [(finished[i][0].prompt, finished[i][2])
              for i in sorted(picks)]
    on_a_hit = sum(1 for i in picks if finished[i][0].shared)
    del engine, registry, store, slices, first, last, stats_end
    gc.collect()
    # the reference needs the chip the program held: say what is left
    harness.say(bytes_in_use_before_reference=(
        devices[0].memory_stats() or {}).get("bytes_in_use"))
    with jax.profiler.TraceAnnotation("check.reference"):
        tic = time.perf_counter()
        gaps = arch.reference_gaps(cell, args.seed, sample)
        exact = sum(int((np.asarray(s[1]) == b).sum())
                    for s, (_, b) in zip(sample, gaps))
        harness.say(reference_s=time.perf_counter() - tic,
                    requests_compared=len(sample),
                    requests_compared_sharing_a_prefix=on_a_hit,
                    tokens_compared=sum(len(s[1]) for s in sample),
                    tokens_equal_reference_argmax=exact)
    checks = compare(sample, gaps)
    short = [r for r in finished if len(r[2]) != r[0].max_tokens]
    checks.append(harness.check("requests_cut_short", len(short), 0))

    reduced = trace.reduce(cell.bench) if trace else None
    return {"end_to_end": end_to_end, "attempted": len(done) + failed_exc,
            "failed": failed_exc, "checks": checks,
            "memory_peak_bytes": peak, "counters": delta,
            "host": {"window_s": seconds, "traced_phases": traced},
            "trace": reduced}
