"""Driver ``serve-arch``: ``drivers/serve.py`` for a model the store
picks by its spec's ``arch`` — seeded requests into
``GenerationEngine.submit``, the same ramp, window, stamps and
accounting (that file's docstring tells them; its ``Stamps``,
``latency_summary``, ``tokens_in`` and ``decision_gaps`` are imported,
not copied).  What differs:

* the spec is the configuration file's ``spec`` section, whole; the
  vocabulary the traffic draws from is its (sliced) ``vocab_size``;
* weights are drawn in the configuration's ``weights_dtype`` and handed
  over before the programs are warmed, so that a model that restacks
  leaves at load never holds them twice (``deepseek_v3``'s experts are
  5.6 GB of the chip's 16);
* the model's own counters (``moe_*``) are read with the engine's;
* the plain reference gets the SAME rounded weights and upcasts them
  itself; ``LIMITS`` below are this driver's own.
"""
from __future__ import annotations

import gc
import importlib
import statistics
import threading
import time

import numpy as np

from benchmark import harness, traffic, weights
from benchmark.drivers.serve import (Stamps, _sleep_until, decision_gaps,
                                     latency_summary, tokens_in)

# counters of GenerationEngine.stats() that only an expert model moves
MODEL_COUNTERS = ("moe_tokens", "moe_local_assignments",
                  "moe_expert_load_max", "moe_expert_steps",
                  "moe_experts_touched")

# Limits of the comparison with the plain reference (float32 at
# "highest" over the same bfloat16-rounded weights), each beside the
# readings it was set from: my chip runs, PR 26, of
# ``deepseek-v3.serve-docqa-backlog`` (PERF.md section 2): every
# request a run finished, 13 of them, 1,107 served tokens, some 1,100
# decisions.  SOUND is the cell as committed over 14 seeds,
# CONTROL int8 weights (``run.py --control``) over three.
LIMITS = {
    # Share of the decisions in which the served token is not the
    # reference's best.  A decision flips where the reference's margin
    # between its first two tokens is under the program's logit error,
    # so with a thousand decisions a run the share reads that error and
    # little else (a binomial of 2 % over 1,100 strays by 0.4 points).
    # SOUND 1.2e-2 .. 2.8e-2 (mean 1.9e-2), CONTROL 7.7e-2, 1.0e-1,
    # 1.1e-1: the number the lower precision has to fail, 1.4x above
    # the one's largest and 1.9x under the other's smallest.
    "flip_share": 0.04,
    # Mean gap of the flips (fewer than FLIPS_MIN averaged as that
    # many).  NOT the lower precision's to fail here: SOUND 1.2e-2 ..
    # 8.6e-2, CONTROL 4.8e-2 .. 5.1e-2.  bfloat16 activations put a
    # routing score on the other side of a near-tie now and then, the
    # token that follows was computed with another expert, and its gap
    # is of another order than rounding's (the 90th-percentile flip of a
    # run reads 2.7e-2 .. 2.7e-1 where the median flip reads 4e-3 ..
    # 2.3e-2): a few such flips of some twenty carry the mean.  Held
    # against a fault of every token (a wrong scale, a dropped layer),
    # 2.9x the largest sound reading.
    "flip_gap_mean": 0.25,
    # The widest gap of any token, which swings with those same routing
    # flips (SOUND 4.5e-2 .. 6.5e-1, CONTROL 3.7e-1 .. 4.1e-1), is held
    # against a token altered where it is produced, whose gap is the
    # logits' spread (3 and more at a vocabulary of 16,160): 3.1x the
    # largest sound reading.
    "token_gap_max": 2.0,
}
FLIPS_MIN = 10


def _draw(ref, cfg, seed):
    """The configuration's seeded weights, drawn a layer at a time: one
    program for all 4.6 G parameters peaked at 15.8 GB of the chip's
    16 (9.1 GB of leaves beside the float32 normals they are rounded
    from; my chip run, PR 26).  The program and the reference both get
    what THIS function draws, so how it groups the leaves is theirs
    alike."""
    shapes = ref.param_shapes(cfg)
    groups = {}
    for name in shapes:
        head = name.split("_", 1)[0]
        key = head if head[:1] == "l" and head[1:].isdigit() else ""
        groups.setdefault(key, {})[name] = shapes[name]
    out = {}
    for key in sorted(groups):
        out.update(weights.draw(groups[key], seed, gain=cfg["init_gain"],
                                dtype=cfg.get("weights_dtype", "float32")))
    return out


def compare(sample, gaps):
    """The checks of ``correct``: ``sample`` holds (prompt, served
    tokens) and ``gaps`` the reference's (gap, best token) arrays of
    each sampled request.  A decision is a distinct (reference's best,
    served token) pair within a request, counted once at its widest
    (``drivers/serve.decision_gaps``); a flip is a decision in which
    the two differ."""
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


def reference_gaps(cell, seed, sample):
    """Gap of every served token of ``sample`` (prompt, served tokens)
    under the plain reference, the same rounded weights drawn again
    from the seed."""
    import jax
    import jax.numpy as jnp
    ref = cell.module("reference")
    cfg, mix = cell.config, cell.traffic
    width = int(mix["limit"])
    most = int(mix["output"]["hi"])
    with jax.default_matmul_precision("highest"):
        params = _draw(ref, cfg, seed)
        fn = jax.jit(lambda p, t, f, s: ref.served_gaps(p, t, f, s, cfg))
        out = []
        for prompt, served in sample:
            seq = np.zeros(width, np.int32)
            n = len(prompt) + len(served) - 1
            seq[:n] = (list(prompt) + list(served))[:n]
            pad = np.zeros(most, np.int32)
            pad[:len(served)] = served
            gap, best = fn(params, jnp.asarray(seq),
                           np.int32(len(prompt) - 1), jnp.asarray(pad))
            out.append((np.asarray(gap)[:len(served)],
                        np.asarray(best)[:len(served)]))
    return out


def run(cell, devices, args, t0):
    import jax
    from mxnet_tpu.serving import GenerationEngine, ModelRegistry
    cfg, mix = cell.config, cell.traffic
    arrival = mix["arrival"]
    open_loop = arrival["kind"] == "poisson"
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
    params = _draw(ref, cfg, args.seed)
    registry = ModelRegistry()
    store = registry.add_generative_model(
        "lm", params, spec, compute_dtype=cfg.get("compute_dtype"),
        warmup=False, **cfg["deploy"])
    # the store's copy is the only one BEFORE the programs run once
    # (each on a throwaway pool): a model that restacks leaves at load
    # took them out of ``params`` as it went, the rest goes here
    del params
    store.warmup()
    warm_compiles = store.stats()["compiles"]
    engine = GenerationEngine(registry)

    # ---- traffic: ramp, window, settle --------------------------------
    marks = {}
    t_start = time.perf_counter()
    t_open, t_close = t_start + ramp, t_start + ramp + seconds

    def at_open():
        marks["open"] = engine.stats()
        compiles.mark()

    def at_close():
        compiles.freeze()
        marks["close"] = engine.stats()

    events = [(t_open, None, at_open), (t_close, None, at_close)]
    events += [(t_start + r.due, r, None) for r in reqs
               if t_start + r.due < t_close]
    events.sort(key=lambda e: e[0])
    tracer = None
    if trace is not None:
        def traced():
            _sleep_until(t_open + float(mix["trace_after_s"]))
            trace.start()
            _sleep_until(trace.t_start + float(mix["trace_seconds"]))
            trace.stop()
        tracer = threading.Thread(target=traced, name="bench-trace")
        tracer.start()
    sent = []                       # (request, due, sent, future, stamps)
    for due, req, fn in events:
        _sleep_until(due)
        if fn is not None:
            fn()
            continue
        stamps = Stamps()
        with jax.profiler.TraceAnnotation("engine.submit"):
            now = time.perf_counter()
            fut = engine.submit("lm", req.prompt,
                                max_tokens=req.max_tokens, stream=stamps)
        sent.append((req, due, now, fut, stamps))
    for row in sent:
        row[3].cancel()                        # still queued: not served
    if open_loop:
        # requests due inside the window get settle_s to show a first
        # token; the gaps between tokens are read up to that moment
        waiting = [row[4] for row in sent
                   if t_open <= row[1] < t_close
                   and not row[3].cancelled()]
        t_settle = t_close + float(mix["settle_s"])
        while time.perf_counter() < t_settle and \
                not all(w.times or w.closed for w in waiting):
            time.sleep(0.02)
    t_settled = time.perf_counter()
    rows = []  # (request, due, sent, stamps, tokens, finished, broke)
    failed_exc = 0
    for req, due, at, fut, stamps in sent:
        n = len(stamps.times)                  # the engine may go on
        ok = fut.done() and not fut.cancelled() \
            and fut.exception() is None
        if fut.done() and not fut.cancelled() and not ok:
            failed_exc += 1
            harness.say(request_failed=repr(fut.exception())[:200])
        rows.append((req, due, at, stamps.times[:n], stamps.tokens[:n],
                     ok, fut.done() and not fut.cancelled() and not ok))
    # a future leads back to the engine and through it to the store's
    # weights: none may outlive this line, or the reference (which needs
    # the chip the program held) finds 9 GB of it taken
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
    stamped = [(r[1], r[3]) for r in rows]
    in_window = [r for r in rows if t_open <= r[1] < t_close]
    # counters a parent program lacks are left out, not read as 0
    delta = {k: marks["close"][k] - marks["open"][k]
             for k in ("decode_steps", "generated_tokens",
                       "prefill_chunks", "prefix_hit_tokens",
                       "prefix_hits", "requests", "finished", "shed",
                       "errors", "cow_forks") + MODEL_COUNTERS
             if k in marks["open"]}
    delta["prompt_tokens_submitted"] = sum(
        len(r[0].prompt) for r in rows if t_open <= r[2] < t_close)
    end_to_end = {"setup_s": t_open - t0}
    host = {"window_s": seconds,
            "late_ms": [1e3 * (r[2] - r[1]) for r in in_window]}
    if open_loop:
        ttft, itl, missed = latency_summary(
            [(r[1], None if r[6] else r[3]) for r in in_window],
            t_settled)
        end_to_end["ttft_p95_ms"] = harness.percentile(ttft, 95)
        end_to_end["itl_p95_ms"] = harness.percentile(itl, 95)
        attempted, failed = len(in_window), missed
        harness.say(requests_due_in_window=len(in_window), missed=missed,
                    ttft_p50_ms=statistics.median(ttft),
                    itl_p50_ms=statistics.median(itl),
                    itl_gaps=len(itl),
                    late_p95_ms=harness.percentile(host["late_ms"], 95),
                    unresolved_at_open=marks["open"]["inflight"],
                    unresolved_at_close=marks["close"]["inflight"],
                    settled_after_s=t_settled - t_close)
    else:
        done = [r for r in rows
                if r[5] and t_open <= r[3][-1] < t_close]
        end_to_end["serve_tokens_per_s"] = tokens_in(
            stamped, t_open, t_close) / seconds
        attempted, failed = len(done) + failed_exc, failed_exc
        harness.say(requests_completed_in_window=len(done),
                    completed_requests_per_s=len(done) / seconds,
                    offered=offered,
                    never_started=sum(1 for r in rows if not r[3]))
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
    finished = [r for r in rows if r[5]]
    rng = np.random.default_rng(int(args.seed))
    picks = set(rng.choice(len(finished),
                           min(int(mix["check_requests"]), len(finished)),
                           replace=False).tolist()) if finished else set()
    if finished:
        picks.add(max(range(len(finished)), key=lambda i: len(
            finished[i][0].prompt) + len(finished[i][4])))
    sample = [(finished[i][0].prompt, finished[i][4])
              for i in sorted(picks)]
    del engine, registry, store, marks, stats_end
    gc.collect()
    # the reference needs the chip the program held: say what is left
    harness.say(bytes_in_use_before_reference=(
        devices[0].memory_stats() or {}).get("bytes_in_use"))
    with jax.profiler.TraceAnnotation("check.reference"):
        tic = time.perf_counter()
        gaps = reference_gaps(cell, args.seed, sample)
        exact = sum(int((np.asarray(s[1]) == b).sum())
                    for s, (_, b) in zip(sample, gaps))
        harness.say(reference_s=time.perf_counter() - tic,
                    requests_compared=len(sample),
                    tokens_compared=sum(len(s[1]) for s in sample),
                    tokens_equal_reference_argmax=exact)
    checks = compare(sample, gaps)
    short = [r for r in finished if len(r[4]) != r[0].max_tokens]
    checks.append(harness.check("requests_cut_short", len(short), 0))

    reduced = trace.reduce(cell.bench) if trace else None
    return {"end_to_end": end_to_end, "attempted": attempted,
            "failed": failed, "checks": checks,
            "memory_peak_bytes": peak, "counters": delta, "host": host,
            "trace": reduced}
