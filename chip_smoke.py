#!/usr/bin/env python3
"""The quickest proof that training and serving still start on the chip.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # the data-parallel path, four chips

One process drives the system's main paths once through the entry points
a user calls, at the full width of the repo's own models (depth cut,
weights random from ``--seed``), and checks what comes out by the repo's
own means — the dense XLA twins every Pallas kernel keeps as its parity
oracle:

* ``train/resnet50`` — ``Module.fit`` of ResNet-50 at 3x224x224, batch
  32, then checkpoint save -> ``Module.load`` -> ``score`` agreement;
* ``train/lm`` — ``Module.fit`` of the transformer LM (4 layers, 2048
  wide, 16 heads x 128, vocabulary 32768, sequence 1024, batch 8) with
  ``MXNET_PALLAS`` at its default, against the same first step with the
  kernels off;
* ``serve/lm`` — that checkpoint behind a ``GenerationEngine`` on the
  default paged plane (plus one request through ``HttpFrontDoor``),
  fp32 and int8 weights, against one-shot dense logits;
* ``deepseek-v3``'s two kernels (latent attention over a paged pool,
  the held experts' grouped product) against their dense twins at the
  published widths, so that every kernel the benchmark runs is covered.

``--chips 4`` runs only ``train/resnet50-dp``: ``Module.fit`` over
``[mx.tpu(i) for i in range(4)]`` with ``kvstore="device"`` at global
batch 128, and the same seed and batch on ``mx.tpu(0)`` alone.

Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
There is no CPU fallback, no retry and no stale record: without a TPU,
or when any check fails or anything raises, the exit code is not 0 and
that line is not printed.  ``run(phases, sizes)`` is importable so the
tests can push toy sizes through the same plumbing on the CPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

# Full sizes: the widths the repo's models are defined at; depth is what
# is cut.  tests/test_chip_smoke.py passes toy sizes of the same shape.
FULL = {
    "resnet": {"num_layers": 50, "num_classes": 1000, "image": 224,
               "batch": 32, "steps": 5},
    "lm": {"num_layers": 4, "num_hidden": 2048, "num_heads": 16,
           "vocab_size": 32768, "seq_len": 1024, "batch": 8, "steps": 3},
    # prompts[4] and prompts[5] share their first `shared_prefix` tokens
    "serve": {"prompt_lens": (17, 45, 96, 130, 300, 333, 512, 700),
              "shared_prefix": 256, "max_tokens": 32},
    "dp": {"chips": 4, "batch": 128, "steps": 5},
    # DeepSeek-V3's published widths (benchmark/configs/deepseek-v3.json):
    # 128 heads over a 512 + 64 latent row stored 640 wide, SwiGLU
    # experts 7168 x 2048 of which 16 are held, 8 of 256 a token
    "deepseek": {"heads": 128, "rank": 512, "rope": 64, "row": 640,
                 "kv_block": 64, "contexts": (37, 1500, 4100, 6591),
                 "chunk": 64, "hidden": 7168, "expert": 2048, "held": 16,
                 "routed": 256, "top_k": 8, "tokens": (64, 2048)},
}

# Stated tolerances, each several times what the chip showed (PERF.md,
# Findings, PR 21).  On a TPU an fp32 XLA matmul runs at the default
# (bf16-pass) precision while the kernels accumulate true fp32 on tile,
# so a kernel and its twin agree to parts in a hundred elementwise on
# gradients, not to fp32 round-off (at "highest" matmul precision the
# same comparison read 5e-4).
TOL_SCORE = 1e-4      # saved -> loaded cross-entropy, relative
TOL_LOSS = 1e-4       # first-step loss, kernels on vs off, relative
TOL_GRAD_NORM = 2e-3  # first-step gradient norm, on vs off, relative
TOL_GRAD_DIFF = 1e-1  # |g_on - g_off| / |g_off|, all weights
TOL_LOGIT = 5e-2      # serving logits vs the one-shot dense reference
TOL_DP_LOSS = 1e-2    # per-step loss, four chips vs one, relative
TOL_LATENT = 3e-2     # bfloat16 o_lat, kernel vs twin, of its largest value
TOL_EXPERTS = 2e-2    # bfloat16 experts' part, grouped vs masked loop, same

FUTURE_TIMEOUT_S = 600.0


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


class _CompileClock:
    """jax.monitoring listener: seconds spent in the backend compiler —
    or, on a persistent-cache hit, fetching the program instead."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


class _Phase:
    """One phase's record: what ran, what was checked, what it cost
    (``compile_s`` is the compile clock's share of ``wall_s``)."""

    def __init__(self, name, shapes, state):
        self.rec = {"phase": name, "ok": False, "shapes": shapes,
                    "compile_s": None, "run_s": None, "asserted": []}
        self._clock = state["compile_clock"]
        self._compiled = self._clock.seconds
        self._t0 = time.perf_counter()

    def check(self, cond, what, detail=None):
        if not cond:
            raise SmokeFailure("%s: %s — %s" % (self.rec["phase"], what,
                                                detail))
        self.rec["asserted"].append(what)

    def note(self, **kv):
        self.rec.update(kv)

    def done(self):
        self.rec["ok"] = True
        self.rec["compile_s"] = round(self._clock.seconds - self._compiled,
                                      2)
        self.rec["wall_s"] = round(time.perf_counter() - self._t0, 2)
        return self.rec


class _StepLog:
    """batch_end_callback: per-batch metric value (a host fetch, so the
    stamp is taken after the step really finished) and its time.
    ``after_first`` runs once between the first two steps, off the
    clock."""

    def __init__(self, after_first=None):
        self.values, self.times = [], []
        self.first_done = None
        self._after_first = after_first

    def __call__(self, param):
        _, value = param.eval_metric.get()
        self.values.append(float(value))
        param.eval_metric.reset()
        if self.first_done is None:
            self.first_done = time.perf_counter()
            if self._after_first is not None:
                # dropped once called: the closure holds the module,
                # and the log outlives it
                self._after_first()
                self._after_first = None
        self.times.append(time.perf_counter())


def _timing(t_start, log):
    """(first_step_s, run_s, step_s): the first step carries bind, init
    and the compile; the rest is steady state (median step, unrounded)."""
    steps = np.diff(log.times)
    return (round(log.first_done - t_start, 2),
            round(float(log.times[-1] - log.times[0]), 3),
            float(statistics.median(steps)) if len(steps) else None)


# ---------------------------------------------------------------------------
# train/resnet50 and train/resnet50-dp
# ---------------------------------------------------------------------------
def _resnet_data(cfg, batch, steps, seed):
    rs = np.random.RandomState(seed)
    n = batch * steps
    x = rs.rand(n, 3, cfg["image"], cfg["image"]).astype(np.float32)
    y = rs.randint(0, cfg["num_classes"], n).astype(np.float32)
    return x, y


def _fit_resnet(cfg, contexts, kvstore, x, y, batch, seed):
    import mxnet_tpu as mx
    net = mx.models.resnet(
        num_classes=cfg["num_classes"], num_layers=cfg["num_layers"],
        image_shape=(3, cfg["image"], cfg["image"]))
    it = mx.io.NDArrayIter(x, y, batch_size=batch)
    mod = mx.Module(net, context=contexts)
    log = _StepLog()
    mx.random.seed(seed)
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=1, eval_metric="ce", kvstore=kvstore,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            batch_end_callback=log)
    return mod, it, log, t0


def _check_fused(ph, mod, devices):
    """The fused trainer was taken, traced once, and its parameters
    live on exactly ``devices``."""
    trainer = mod.fused_trainer
    ph.check(trainer is not None, "fused trainer taken")
    ph.check(trainer.trace_counts["train"] == 1,
             "step program traced once", trainer.trace_counts)
    held = {d for a in trainer.params.values() for d in a.devices()}
    ph.check(held == set(devices), "parameters live on the asked devices",
             sorted(map(str, held)))
    return trainer


def train_resnet50(sizes, state, _kernels):
    import mxnet_tpu as mx
    cfg = sizes["resnet"]
    batch, steps = cfg["batch"], cfg["steps"]
    ph = _Phase("train/resnet50",
                {"data": [batch, 3, cfg["image"], cfg["image"]],
                 "num_layers": cfg["num_layers"], "steps": steps}, state)
    ctx = mx.tpu(0)
    x, y = _resnet_data(cfg, batch, steps, state["seed"])
    mod, it, log, t0 = _fit_resnet(cfg, ctx, "local", x, y, batch,
                                   state["seed"])
    first_step_s, run_s, step_s = _timing(t0, log)
    ph.note(first_step_s=first_step_s, run_s=run_s, step_s=step_s,
            losses=log.values)
    ph.check(len(log.values) == steps, "all steps ran", len(log.values))
    ph.check(bool(np.all(np.isfinite(log.values))), "loss finite",
             log.values)
    _check_fused(ph, mod, [ctx.jax_device()])

    # the verify skill's flow: save -> Module.load -> score agrees
    prefix = os.path.join(state["tmp"], "resnet50")
    mod.save_checkpoint(prefix, 1)
    loaded = mx.Module.load(prefix, 1, context=ctx)
    loaded.bind(it.provide_data, it.provide_label, for_training=False)
    one = mx.io.NDArrayIter(x[:batch], y[:batch], batch_size=batch)
    kept = dict(mod.score(one, "ce"))["cross-entropy"]
    back = dict(loaded.score(one, "ce"))["cross-entropy"]
    ph.note(score_trained=kept, score_loaded=back)
    ph.check(np.isfinite(kept) and abs(kept - back) <= TOL_SCORE * abs(kept),
             "checkpoint save/load scores agree (rel %g)" % TOL_SCORE,
             (kept, back))
    return ph.done()


def train_resnet50_dp(sizes, state, _kernels):
    """The README quick start's path: N contexts, kvstore='device'."""
    import mxnet_tpu as mx
    cfg, dp = sizes["resnet"], sizes["dp"]
    chips, batch, steps = dp["chips"], dp["batch"], dp["steps"]
    ph = _Phase("train/resnet50-dp",
                {"data": [batch, 3, cfg["image"], cfg["image"]],
                 "chips": chips, "steps": steps}, state)
    x, y = _resnet_data(cfg, batch, steps, state["seed"])
    ctxs = [mx.tpu(i) for i in range(chips)]
    devices = [c.jax_device() for c in ctxs]
    ph.check(len(set(devices)) == chips, "distinct devices in the mesh",
             list(map(str, devices)))

    mod, _, log, t0 = _fit_resnet(cfg, ctxs, "device", x, y, batch,
                                  state["seed"])
    first_step_s, run_s, step_s = _timing(t0, log)
    trainer = _check_fused(ph, mod, devices)
    ph.check(set(trainer.mesh.devices.flat) == set(devices),
             "mesh spans the asked devices")
    stats = [d.memory_stats() for d in devices]
    if devices[0].platform == "tpu" or all(stats):
        in_use = [s["bytes_in_use"] for s in stats]
        ph.note(bytes_in_use=in_use)
        ph.check(min(in_use) > 0, "memory_stats shows bytes on each chip",
                 in_use)
    text = trainer.lower_step({"data": x[:batch]},
                              {"softmax_label": y[:batch]}) \
        .compile().as_text()
    ph.check("all-reduce" in text, "all-reduce in the compiled step")
    del mod, trainer      # one chip cannot hold both runs' state
    gc.collect()

    solo, _, solo_log, solo_t0 = _fit_resnet(cfg, mx.tpu(0), "device", x,
                                             y, batch, state["seed"])
    _check_fused(ph, solo, devices[:1])
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(log.values, solo_log.values))
    ph.note(first_step_s=first_step_s, run_s=run_s, step_s=step_s,
            losses=log.values, losses_one_chip=solo_log.values,
            one_chip_step_s=_timing(solo_t0, solo_log)[2],
            loss_rel_diff_max=worst)
    ph.check(bool(np.all(np.isfinite(log.values))), "loss finite")
    ph.check(len(log.values) == steps and worst <= TOL_DP_LOSS,
             "per-step losses equal the one-chip run (rel %g)"
             % TOL_DP_LOSS, worst)
    return ph.done()


# ---------------------------------------------------------------------------
# train/lm
# ---------------------------------------------------------------------------
def _kernels_off():
    from mxnet_tpu.pallas_ops import dispatch
    return dispatch.overriding((dispatch.MODE_OFF, dispatch.block_rows(),
                                dispatch.block_seq()))


def _lm_spec(cfg):
    return {k: cfg[k] for k in ("num_layers", "num_hidden", "num_heads",
                                "vocab_size")}


_LM_LR = 1.0


def _fit_lm(cfg, tokens, before, steps):
    """``Module.fit`` of the LM from the weights ``before`` for ``steps``
    batches.  Plain SGD on the per-token mean loss: one step moves each
    weight by lr * gradient, so the first update read back from the
    device IS the first gradient (at lr 1 it clears fp32 round-off of
    the weights by four digits and still moves them by well under 1%)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer_lm import get_symbol
    net = get_symbol(seq_len=cfg["seq_len"], **_lm_spec(cfg))
    n = cfg["batch"] * steps
    it = mx.io.NDArrayIter(tokens[:n, :-1], tokens[:n, 1:],
                           batch_size=cfg["batch"])
    mod = mx.Module(net, context=mx.tpu(0))
    grad = {}

    def first_update():
        for k, v in mod.fused_trainer.params.items():
            grad[k] = (np.asarray(v) - before[k]) / -_LM_LR

    log = _StepLog(first_update)
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=1, eval_metric="ce", optimizer="sgd",
            optimizer_params={
                "learning_rate": _LM_LR,
                "rescale_grad": 1.0 / (cfg["batch"] * cfg["seq_len"])},
            arg_params={k: mx.nd.array(v) for k, v in before.items()},
            batch_end_callback=log)
    return mod, log, t0, grad


def _norm(arrays):
    return float(np.sqrt(sum(float(np.vdot(v, v)) for v in arrays)))


def _lm_init(cfg, seed):
    """Seeded Xavier weights under the symbol graph's argument names."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer_lm import get_symbol
    net = get_symbol(seq_len=cfg["seq_len"], **_lm_spec(cfg))
    grid = (cfg["batch"], cfg["seq_len"])
    shapes, _, _ = net.infer_shape(data=grid, softmax_label=grid)
    mx.random.seed(seed)
    init = mx.init.Xavier(magnitude=2.0)
    out = {}
    for name, shape in zip(net.list_arguments(), shapes):
        if name not in ("data", "softmax_label"):
            arr = mx.nd.zeros(shape)
            init(mx.init.InitDesc(name), arr)
            out[name] = arr.asnumpy()
    return out


def train_lm(sizes, state, kernels):
    import mxnet_tpu as mx
    from mxnet_tpu.pallas_ops import dispatch
    cfg = sizes["lm"]
    steps = cfg["steps"]
    ph = _Phase("train/lm", {"data": [cfg["batch"], cfg["seq_len"]],
                             "steps": steps, **_lm_spec(cfg)}, state)
    rs = np.random.RandomState(state["seed"] + 1)
    tokens = rs.randint(0, cfg["vocab_size"],
                        (cfg["batch"] * steps, cfg["seq_len"] + 1)) \
        .astype(np.int32)
    before = _lm_init(cfg, state["seed"])

    dispatch.reset_dispatch_stats()
    mod, log, t0, g_on = _fit_lm(cfg, tokens, before, steps)
    first_step_s, run_s, step_s = _timing(t0, log)
    routed = dispatch.dispatch_stats()
    ph.note(first_step_s=first_step_s, run_s=run_s, step_s=step_s,
            losses=log.values, routed=routed)
    ph.check(len(log.values) == steps
             and bool(np.all(np.isfinite(log.values))),
             "all steps ran, loss finite", log.values)
    trainer = _check_fused(ph, mod, [mx.tpu(0).jax_device()])
    if kernels:
        ph.check(not dispatch.interpret_mode(),
                 "kernels compile (no interpret mode)")
        for kind in ("DotProductAttention", "RMSNorm", "LayerNorm",
                     "SoftmaxOutput"):
            ph.check(routed.get(kind, 0) > 0,
                     "%s routed to its kernel" % kind, routed)
        n = cfg["batch"]
        calls = trainer.lower_step({"data": tokens[:n, :-1]},
                                   {"softmax_label": tokens[:n, 1:]}) \
            .as_text().count("tpu_custom_call")
        ph.note(tpu_custom_calls=calls)
        ph.check(calls > 0, "lowered step holds tpu_custom_calls", calls)

    prefix = os.path.join(state["tmp"], "lm")
    mod.save_checkpoint(prefix, 1)
    state["lm_checkpoint"] = (prefix, 1)
    del mod, trainer      # the chip holds the run and its twin in turn
    gc.collect()

    # the same first step on the dense XLA twins: same chip, same seed
    with _kernels_off():
        _, twin_log, _, g_off = _fit_lm(cfg, tokens, before, 1)
    n_on, n_off = _norm(g_on.values()), _norm(g_off.values())
    diff = _norm(g_on[k] - g_off[k] for k in g_on) / n_off
    loss_rel = abs(log.values[0] - twin_log.values[0]) \
        / abs(twin_log.values[0])
    ph.note(loss_twin=twin_log.values[0], loss_rel_diff=loss_rel,
            grad_norm=n_on, grad_norm_twin=n_off, grad_rel_diff=diff)
    ph.check(loss_rel <= TOL_LOSS,
             "first-step loss equals the kernels-off twin (rel %g)"
             % TOL_LOSS, loss_rel)
    ph.check(np.isfinite(n_on) and n_on > 0
             and abs(n_on - n_off) <= TOL_GRAD_NORM * n_off,
             "gradient norm equals the twin's (rel %g)" % TOL_GRAD_NORM,
             (n_on, n_off))
    ph.check(diff <= TOL_GRAD_DIFF,
             "gradient equals the twin's (|diff|/|g| <= %g)"
             % TOL_GRAD_DIFF, diff)
    return ph.done()


# ---------------------------------------------------------------------------
# serve/lm
# ---------------------------------------------------------------------------
def _prompts(cfg, vocab, seed):
    rs = np.random.RandomState(seed + 2)
    prompts = [rs.randint(0, vocab, n).tolist() for n in cfg["prompt_lens"]]
    shared = prompts[4][:cfg["shared_prefix"]]
    prompts[5][:cfg["shared_prefix"]] = shared
    return prompts


def _one_shot_logits(params, spec, seqs, kv_block, kernels):
    """Teacher-forced logits of whole sequences in ONE paged step over a
    fresh pool — the reference the engine's chunked, incremental, batched
    answers are held to.  ``kernels=False`` lowers it on the dense XLA
    twins.  Returns a device array (len(seqs), padded length, vocab)."""
    import contextlib
    import jax
    from mxnet_tpu.models.transformer_lm import init_pool, paged_step_apply
    n = len(seqs)
    width = -(-max(map(len, seqs)) // kv_block)       # blocks per row
    tokens = np.zeros((n, width * kv_block), np.int32)
    for i, seq in enumerate(seqs):
        tokens[i, :len(seq)] = seq
    # row i owns blocks 1 + i*width ..; block 0 is the trash block
    tables = 1 + np.arange(n * width, dtype=np.int32).reshape(n, width)
    pool_k, pool_v = init_pool(spec, n * width + 1, kv_block)

    def fn(params, pool_k, pool_v):
        return paged_step_apply(
            params, pool_k, pool_v, tables, tokens,
            np.zeros(n, np.int32), np.asarray(list(map(len, seqs)),
                                              np.int32),
            spec, kv_block, all_logits=True)[0]

    with contextlib.nullcontext() if kernels else _kernels_off():
        return jax.block_until_ready(jax.jit(fn)(params, pool_k, pool_v))


def _generate(ph, registry, name, prompts, max_tokens, tag):
    """Answer ``prompts`` in process, then prompts[0] again over HTTP on
    127.0.0.1 port 0.  Returns (results, the HTTP result, seconds the
    in-process batch took, seconds of them the device had nothing
    queued: the engine's own count, ``phases.device_starved`` of
    ``GET /stats``)."""
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import GenerationEngine
    from mxnet_tpu.serving.frontdoor import HttpClient, HttpFrontDoor
    store = registry.gen_store(name)
    warm = store.stats()["compiles"]
    engine = GenerationEngine(registry)
    try:
        opened = profiler.phase_totals()
        t0 = time.perf_counter()
        futures = [engine.submit(name, p, max_tokens=max_tokens)
                   for p in prompts]
        results = [f.result(FUTURE_TIMEOUT_S) for f in futures]
        run_s = time.perf_counter() - t0
        starved_s = 1e-9 * profiler.phase_totals(since=opened).get(
            "device_starved", {"ns": 0})["ns"]
        with HttpFrontDoor(engine, port=0, gen_target=engine) as door:
            client = HttpClient(door.address, threads=1)
            try:
                over_http = client.generate(
                    name, prompts[0], max_tokens=max_tokens) \
                    .result(FUTURE_TIMEOUT_S)
            finally:
                client.close()
        stats = engine.stats()
    finally:
        engine.close()
    ph.check(all(len(r.tokens) == max_tokens for r in results)
             and len(over_http.tokens) == max_tokens,
             "%s: every future resolved with max_tokens tokens" % tag)
    ph.check(stats["errors"] == 0 and stats["timeouts"] == 0
             and stats["finished"] == len(prompts) + 1,
             "%s: no request failed (donated pools rebound)" % tag, stats)
    ph.check(stats["prefix_hits"] >= 1, "%s: a shared prefix was reused"
             % tag, stats["prefix_hits"])
    ph.check(store.stats()["compiles"] == warm,
             "%s: zero compilations after warm-up" % tag,
             (warm, store.stats()["compiles"]))
    return results, over_http, run_s, starved_s


def _greedy_margin(gen, ref):
    """How far below the reference's best logit the chosen tokens sit
    (0 = every token is the reference's argmax)."""
    return max(float(ref[i].max() - ref[i, tok])
               for i, tok in enumerate(gen))


def _serve_side(ph, tag, checkpoint, weights, spec, prompts, cfg, kernels,
                compute_dtype):
    """One weight dtype end to end: load, warm, answer, hold the answers
    to the one-shot dense reference (built from the host ``weights``)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.transformer_lm import quantize_lm_params
    from mxnet_tpu.pallas_ops import dispatch
    from mxnet_tpu.serving import ModelRegistry
    max_tokens = cfg["max_tokens"]
    dispatch.reset_dispatch_stats()
    registry = ModelRegistry()
    t0 = time.perf_counter()
    # one batch bucket: eight concurrent requests is the whole traffic
    store = registry.load_generative_checkpoint(
        "lm", *checkpoint, spec, batch_buckets=(len(prompts),),
        compute_dtype=compute_dtype)
    load_s = time.perf_counter() - t0
    routed = dispatch.dispatch_stats()
    results, over_http, run_s, starved_s = _generate(
        ph, registry, "lm", prompts, max_tokens, tag)
    if kernels:
        kinds = ["DotProductAttentionPaged", "RMSNorm", "LayerNorm"]
        if compute_dtype == "int8":
            kinds.append("DequantMatmul")
        for kind in kinds:
            ph.check(routed.get(kind, 0) > 0,
                     "%s: %s routed to its kernel" % (tag, kind), routed)
        from mxnet_tpu.serving.program_store import cache_donate_argnums
        ph.check(cache_donate_argnums((1, 2)) == (1, 2),
                 "%s: KV pools are donated" % tag)

    # reference: prompt + answer in one dense step, position p predicts
    # token p + 1
    params = jax.tree_util.tree_map(
        jnp.asarray, quantize_lm_params(weights, spec)
        if compute_dtype == "int8" else weights)
    seqs = [p + list(r.tokens[:-1]) for p, r in zip(prompts, results)]
    rows = [slice(len(p) - 1, len(p) - 1 + max_tokens) for p in prompts]
    dense = _one_shot_logits(params, spec, seqs, store.kv_block, False)
    ref = [np.asarray(dense[i, r]) for i, r in enumerate(rows)]
    margin = max(_greedy_margin(r.tokens, ref[i])
                 for i, r in enumerate(results))
    exact = sum(int(ref[i][t].argmax() == tok)
                for i, r in enumerate(results)
                for t, tok in enumerate(r.tokens))
    ph.check(margin <= TOL_LOGIT,
             "%s: greedy tokens are the dense reference's argmax "
             "(within %g where logits tie)" % (tag, TOL_LOGIT), margin)
    # the HTTP answer repeats prompts[0]: equal, or parted at a tie
    http_gen, gen = list(over_http.tokens), list(results[0].tokens)
    split = next((t for t in range(max_tokens) if http_gen[t] != gen[t]),
                 None)
    ph.check(split is None
             or _greedy_margin(http_gen[split:split + 1],
                               ref[0][split:split + 1]) <= TOL_LOGIT,
             "%s: the HTTP answer equals the in-process one" % tag, split)
    side = {"load_and_warm_s": round(load_s, 2),
            "run_s": round(run_s, 3),
            "device_starved_share": round(starved_s / run_s, 4),
            "routed": routed,
            "argmax_margin": margin, "argmax_exact": exact,
            "tokens": max_tokens * len(prompts),
            "http_equal": split is None}
    if kernels:
        # the same step on the compiled kernels: logits agree directly
        kernel = _one_shot_logits(params, spec, seqs, store.kv_block, True)
        side["kernel_vs_twin_logit_diff"] = max(
            float(np.abs(np.asarray(kernel[i, r]) - ref[i]).max())
            for i, r in enumerate(rows))
        ph.check(side["kernel_vs_twin_logit_diff"] <= TOL_LOGIT,
                 "%s: kernel logits equal the dense twin's (abs %g)"
                 % (tag, TOL_LOGIT), side["kernel_vs_twin_logit_diff"])
    return side


def serve_lm(sizes, state, kernels):
    import mxnet_tpu as mx
    cfg = sizes["serve"]
    spec = _lm_spec(sizes["lm"])
    ph = _Phase("serve/lm", {"prompt_lens": list(cfg["prompt_lens"]),
                             "shared_prefix": cfg["shared_prefix"],
                             "max_tokens": cfg["max_tokens"], **spec},
                state)
    if "lm_checkpoint" not in state:
        raise SmokeFailure("serve/lm loads the checkpoint train/lm saves: "
                           "run both")
    checkpoint = state["lm_checkpoint"]
    weights = {k: v.asnumpy() for k, v in
               mx.model.load_checkpoint(*checkpoint)[1].items()}
    prompts = _prompts(cfg, spec["vocab_size"], state["seed"])
    for tag, dtype in (("fp32", None), ("int8", "int8")):
        side = _serve_side(ph, tag, checkpoint, weights, spec, prompts,
                           cfg, kernels, dtype)
        ph.note(**{tag: side})
        gc.collect()
    ph.note(run_s=ph.rec["fp32"]["run_s"] + ph.rec["int8"]["run_s"])
    return ph.done()


def kernels_deepseek(sizes, state, kernels):
    """The two kernels ``deepseek-v3``'s cell runs, each against its
    dense XLA twin at the published widths, in bfloat16: latent
    attention over a paged pool (a decode step and a prompt chunk, ragged
    contexts, one shared block) and the held experts' grouped product (a
    decode step's handful of tokens and a chunk's thousands)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops.attention import mla_attention_paged
    from mxnet_tpu.pallas_ops import dispatch
    from mxnet_tpu.pallas_ops.mla_attention import mla_attention_reference
    cfg = sizes["deepseek"]
    ph = _Phase("kernels/deepseek-v3", dict(cfg), state)
    rs = np.random.RandomState(state["seed"])
    bs, H, W, rank = cfg["kv_block"], cfg["heads"], cfg["row"], cfg["rank"]
    ctx = list(cfg["contexts"])
    B = len(ctx)
    T = -(-(max(ctx) + cfg["chunk"]) // bs)
    tables = np.zeros((B, T), np.int32)
    nxt = 2
    for b, n in enumerate(ctx):
        for j in range(-(-(n + cfg["chunk"]) // bs)):
            tables[b, j] = 1 if j == 0 else nxt   # block 1 is shared
            nxt += j > 0
    pool = np.zeros((2, 1, nxt * bs, W), np.float32)
    pool[..., :rank + cfg["rope"]] = rs.randn(
        2, 1, nxt * bs, rank + cfg["rope"])
    pool = jnp.asarray(pool, jnp.bfloat16)
    scale = (rank / 4 + cfg["rope"]) ** -0.5
    before = dispatch.dispatch_stats()
    for lq in (1, cfg["chunk"]):
        q = np.zeros((B, H, lq, W), np.float32)
        q[..., :rank + cfg["rope"]] = rs.randn(B, H, lq,
                                               rank + cfg["rope"]) / 4
        q = jnp.asarray(q, jnp.bfloat16)
        args = (q, pool, 1, jnp.asarray(tables), jnp.asarray(ctx), bs,
                rank, scale)
        got = jax.jit(lambda q, pool, t, p: mla_attention_paged(
            q, pool, 1, t, p, bs, rank, scale))(q, pool, args[3], args[4])
        want = mla_attention_reference(*args)
        gap = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        top = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
        ph.check(gap <= TOL_LATENT * top,
                 "latent attention, %d queries a row: kernel = twin" % lq,
                 (gap, top))
        ph.note(**{"mla_gap_lq%d" % lq: gap, "mla_top_lq%d" % lq: top})
    D, F, held = cfg["hidden"], cfg["expert"], cfg["held"]
    gu = jnp.asarray(rs.randn(held, D, 2 * F) / np.sqrt(D), jnp.bfloat16)
    down = jnp.asarray(rs.randn(held, F, D) / np.sqrt(F), jnp.bfloat16)
    for n in cfg["tokens"]:
        x = jnp.asarray(rs.randn(n, D), jnp.bfloat16)
        experts = jnp.asarray(np.stack(
            [rs.permutation(cfg["routed"])[:cfg["top_k"]]
             for _ in range(n)]), jnp.int32)
        weights = jnp.asarray(rs.uniform(0.1, 0.6, experts.shape),
                              jnp.float32)
        live = jnp.asarray(rs.uniform(size=n) < 0.9)
        got, counts = jax.jit(moe.moe_experts)(x, gu, down, experts,
                                               weights, live)
        want, want_counts = jax.jit(moe.moe_experts_reference)(
            x, gu, down, experts, weights, live)
        gap = float(jnp.max(jnp.abs(got - want)))
        top = float(jnp.max(jnp.abs(want)))
        ph.check(np.array_equal(np.asarray(counts),
                                np.asarray(want_counts)),
                 "experts, %d tokens: the same counts" % n)
        ph.check(gap <= TOL_EXPERTS * top,
                 "experts, %d tokens: grouped product = masked loop" % n,
                 (gap, top))
        ph.note(**{"moe_gap_n%d" % n: gap, "moe_top_n%d" % n: top,
                   "moe_assignments_n%d" % n: int(np.asarray(counts)
                                                   .sum())})
    if kernels:
        routed = dispatch.dispatch_stats()
        for kind in ("LatentAttentionPaged", "MoEExperts"):
            ph.check(routed.get(kind, 0) > before.get(kind, 0),
                     "%s routed to its kernel" % kind, routed)
    return ph.done()


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
PHASES = {"train/resnet50": train_resnet50, "train/lm": train_lm,
          "serve/lm": serve_lm, "train/resnet50-dp": train_resnet50_dp,
          "kernels/deepseek-v3": kernels_deepseek}
ONE_CHIP = ("train/resnet50", "train/lm", "serve/lm",
            "kernels/deepseek-v3")
FOUR_CHIPS = ("train/resnet50-dp",)


def run(phases, sizes, kernels=True, seed=0, emit=print):
    """Run ``phases`` in order at ``sizes`` on whatever device JAX has;
    ``emit`` gets each phase's JSON line.  ``kernels=False`` drops the
    checks that only compiled Pallas kernels can meet (the CPU tests);
    everything else — and any exception — holds at every size.  Returns
    the phase records."""
    import jax
    records = []
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            state = {"seed": int(seed), "tmp": tmp, "compile_clock": clock}
            for name in phases:
                records.append(PHASES[name](sizes, state, kernels))
                emit(json.dumps(records[-1], default=float))
                gc.collect()
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the data-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from mxnet_tpu import native
    from mxnet_tpu.base import use_compile_cache
    from mxnet_tpu.pallas_ops import dispatch
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chip_smoke: needs a TPU, JAX reports %r — no CPU fallback"
              % dev.platform, file=sys.stderr)
        return 1
    if not dispatch.kernels_active():
        print("chip_smoke: MXNET_PALLAS turns the kernels off; the smoke "
              "runs them at their default", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    print(json.dumps({"phase": "setup",
                      "compile_cache": use_compile_cache(),
                      "native_runtime": native.status(),
                      "jax": jax.__version__}), flush=True)
    run(FOUR_CHIPS if args.chips == 4 else ONE_CHIP, FULL, kernels=True,
        seed=args.seed, emit=lambda line: print(line, flush=True))
    print(json.dumps({"phase": "total",
                      "wall_s": round(time.perf_counter() - t0, 1)}),
          flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
