"""Driver ``serve``: seeded requests into ``GenerationEngine.submit``.

Set-up draws the weights on the device, registers the model (which
compiles or fetches the cell's two programs and runs each once) and
starts the engine.  Traffic starts ``ramp_s`` before the window opens:
the ramp fills the batch and takes the engine through every path the
window uses (admission, chunked prefill, copy-on-write forks), so the
window opens on a steady state and nothing compiles inside it.  Then
``--seconds`` of window; requests due later are never sent and requests
still queued when it closes are cancelled.

Token stamps are taken by the benchmark's own stream object, which the
engine calls the moment a token is sampled (a client's view; they lie
microseconds after the engine's ``token_times``), so that requests the
window's end finds unfinished are counted too.

Open loop (``arrival.kind == "poisson"``): a request is timed from when
it was DUE, not from when it was sent; how late the generator ran is
reported; a request due inside the window that was shed, failed or has
shown no first token ``settle_s`` after the window closed misses and
counts as the worst.  Backlog: everything is due at 0, the window counts
tokens, and what is still queued at its end is cancelled.

After the engine and its store are freed the plain reference draws the
same weights again and runs once over a seeded sample of finished
requests (the longest among them), prompt and served tokens in one pass
at "highest" precision; ``correct`` compares how far the served token's
logit lies below the reference's best where the two differ.
"""
from __future__ import annotations

import gc
import statistics
import threading
import time

import numpy as np

from benchmark import harness, traffic, weights

# Limits of the comparison with the plain reference (at "highest"), each
# beside its reason.  The readings they were set from are in PERF.md,
# section 2 ("Limits of correct").  The control is int8 weights
# (compute_dtype="int8"): the configuration holds float32 and multiplies
# in ONE bf16 pass, so bfloat16 lies nearer to it than int8 does.
LIMITS = {
    # Mean gap of the FLIPS: the decisions in which the served token is
    # not the reference's best (a decision is a distinct pair of the two
    # within a request, counted once at its widest: decision_gaps).  A
    # flip happens where the reference's margin between its first two
    # tokens is under the program's logit error, so how wide the flips
    # are reads that error and nothing else; how MANY near-ties a seed's
    # weights and loops put in front of the program cancels.  (The mean
    # over ALL decisions, held before, is the product of both: 2.1e-4 ..
    # 1.1e-3 over 34 sound seeds, a seed of 135 decisions and 38 flips
    # the highest, against a limit of 1.5e-3 that 19 seeds had set.)
    # Sound runs 2.1e-3 .. 7.1e-3 (38 seeds), int8 weights 2.1e-2 ..
    # 3.5e-2 (11), bfloat16 1.5e-2 .. 1.7e-2 (5).  The number the lower
    # precision has to fail: 1.7x above the one, 1.7x under the other.
    "flip_gap_mean": 1.2e-2,
    # the widest gap of any sampled token swings by its nature (5.7e-3 ..
    # 2.4e-2 in sound runs, 3.8e-2 .. 5.5e-2 with bfloat16, 3.5e-2 ..
    # 1.2e-1 with int8 weights) and is not the lower precision's to
    # fail; it is held against a token altered where it is produced,
    # whose gap is of the order of the logits' spread (1 and more): four
    # times the sound runs' largest, a tenth of the fault's.
    "token_gap_max": 1e-1,
}
# Fewer flips than this are averaged as if they were this many: a mean
# of two or three says little, and sound runs have read as few as 5
# where the control has never read under 12.
FLIPS_MIN = 10


class Stamps:
    """Stands where a ``TokenStream`` would: the engine hands it every
    token the moment it is sampled (right after taking its own stamp),
    so the stamps are read even of requests the window's end finds
    unfinished."""

    __slots__ = ("times", "tokens", "closed")

    def __init__(self):
        self.times, self.tokens, self.closed = [], [], False

    def push(self, token):
        self.times.append(time.perf_counter())
        self.tokens.append(int(token))

    def close(self):
        self.closed = True


def _sleep_until(t):
    import jax
    with jax.profiler.TraceAnnotation("loadgen.sleep"):
        while True:
            left = t - time.perf_counter()
            if left <= 0:
                return
            time.sleep(left)


def latency_summary(rows, t_settled):
    """TTFT and inter-token gaps of open-loop requests from their
    stamps.  ``rows`` holds ``(due, token_times or None)``; a request
    with no first token misses and counts as the worst.  Returns
    ``(ttft_ms list, itl_ms list, missed)``."""
    ttft, itl, missed = [], [], []
    for due, stamps in rows:
        if not stamps:
            missed.append(t_settled - due)
            continue
        ttft.append(stamps[0] - due)
        itl.extend(b - a for a, b in zip(stamps, stamps[1:]))
    worst = max(ttft + missed) if ttft or missed else float("nan")
    ttft += [max(worst, m) for m in missed]
    return ([1e3 * v for v in ttft], [1e3 * v for v in itl], len(missed))


def tokens_in(rows, t_open, t_close):
    """Token stamps inside ``[t_open, t_close)`` over all requests."""
    return sum(1 for _, stamps in rows for t in stamps or ()
               if t_open <= t < t_close)


def decision_gaps(gap, best, served):
    """One request's token gaps, a repeated decision counted once: a
    decision is a distinct (reference's choice, served token) pair, and
    a decoding loop that holds it repeats it with the same gap each time
    round.  Returns ``(widest gap, flipped)`` of each decision; a flip
    is a decision whose served token is not the reference's choice."""
    widest = {}
    for g, b, t in zip(gap, best, served):
        key = (int(b), int(t))
        widest[key] = max(widest.get(key, 0.0), float(g))
    return [(g, b != t) for (b, t), g in widest.items()]


def compare(sample, gaps):
    """The checks of ``correct``: ``sample`` holds (prompt, served
    tokens) and ``gaps`` the reference's (gap, best token) arrays of
    each sampled request."""
    decisions = [d for (_, served), (g, b) in zip(sample, gaps)
                 for d in decision_gaps(g, b, served)]
    if not decisions:
        return [harness.check("decisions_compared", 0, 0, ok=False)]
    flips = [g for g, flipped in decisions if flipped]
    harness.say(decisions_compared=len(decisions), flips=len(flips),
                decision_gap_mean=statistics.fmean(
                    g for g, _ in decisions))
    return [harness.check("flip_gap_mean",
                          sum(flips) / max(len(flips), FLIPS_MIN),
                          LIMITS["flip_gap_mean"]),
            harness.check("token_gap_max", max(g.max() for g, _ in gaps),
                          LIMITS["token_gap_max"])]


def reference_gaps(cell, seed, sample):
    """Gap of every served token of ``sample`` (prompt, served tokens)
    under the plain reference, weights drawn again from the seed."""
    import jax
    import jax.numpy as jnp
    ref = cell.module("reference")
    cfg, mix = cell.config, cell.traffic
    width = int(mix["limit"])
    most = int(mix["output"]["hi"])
    with jax.default_matmul_precision("highest"):
        params = weights.draw(ref.param_shapes(cfg), seed,
                              gain=cfg["init_gain"])
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
    from mxnet_tpu.models.transformer_lm import lm_spec
    from mxnet_tpu.serving import GenerationEngine, ModelRegistry
    cfg, mix = cell.config, cell.traffic
    arrival = mix["arrival"]
    open_loop = arrival["kind"] == "poisson"
    ref = cell.module("reference")
    compiles = harness.CompileCounter()
    trace = harness.DeviceTrace() if args.trace else None
    seconds, ramp = float(args.seconds), float(mix["ramp_s"])

    spec = lm_spec(**{k: cfg[k] for k in ("num_layers", "num_hidden",
                                          "num_heads", "vocab_size")})
    reqs = traffic.make_requests(mix, arrival, spec["vocab_size"],
                                 args.seed, ramp + seconds)
    params = weights.draw(ref.param_shapes(cfg), args.seed,
                          gain=cfg["init_gain"])
    registry = ModelRegistry()
    store = registry.add_generative_model(
        "lm", params, spec, compute_dtype=cfg.get("compute_dtype"),
        **cfg["deploy"])
    del params            # the store's copy is the only one
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
    delta = {k: marks["close"][k] - marks["open"][k]
             for k in ("decode_steps", "generated_tokens",
                       "prefill_chunks", "prefix_hit_tokens",
                       "prefix_hits", "requests", "finished", "shed",
                       "errors", "cow_forks")}
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
                    offered=len(sent),
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
