"""Benchmark artifact: multi-row performance sweep mirroring BASELINE.md.

Rows (each guarded — one failure becomes a structured error row, the
sweep goes on, and the exit code is 1 at the end):

* training images/sec for resnet-50 / inception-v3 / alexnet through the
  real ``Module.fit`` loop on synthetic data — the reference's
  ``train_imagenet.py --benchmark 1`` protocol (`docs/how_to/perf.md:179-188`)
* resnet-50 through ``parallel.DataParallelTrainer`` directly (the round-1
  headline protocol, kept for continuity; fused-fit should be within ±10%)
* the 6-network inference sweep of ``benchmark_score.py``
  (`docs/how_to/perf.md:138-147`)
* LSTM-bucketing training throughput (`example/rnn/lstm_bucketing.py`)
* all-reduce bandwidth over the device mesh (`tools/bandwidth/measure.py`,
  `tools/bandwidth/README.md:30-57`) — or HBM stream bandwidth when only a
  single chip is visible (ICI is meaningless at n=1)

Every throughput row reports analytic-model MFU against the chip's peak
bf16 FLOP/s (chip kind read from PJRT; peak from a lookup table).

The sweep measures on a TPU and fails without one; ``JAX_PLATFORMS=cpu``
asks for a plumbing run explicitly (``BENCH_SMOKE=1`` shrinks it).  A
failed backend init or an errored row exits non-zero.

Prints ONE JSON line.  Top-level keys keep the driver contract
{"metric", "value", "unit", "vs_baseline"} (headline = resnet-50
trainer-direct images/sec vs 181.53 × n_dev, the 1×P100 anchor in
BASELINE.md); full sweep under "rows", chip info under "chip".
"""
from __future__ import annotations

import contextlib
import json
import os
import time
import traceback

import numpy as np

_SCRIPT_DIR = os.path.dirname(os.path.abspath(__file__))

# 1×P100 anchors from BASELINE.md (docs/how_to/perf.md)
TRAIN_BASELINE = {"resnet-50": 181.53, "inception-v3": 129.98,
                  "alexnet": 1869.69}
INFER_BASELINE = {"alexnet": 4883.77, "vgg": 854.4, "inception-bn": 1197.74,
                  "inception-v3": 493.72, "resnet-50": 713.17,
                  "resnet-152": 294.17}
ALLREDUCE_BASELINE_GBS = 11.1  # device kvstore, 2 GPUs (tools/bandwidth)

# Analytic forward FLOPs per image at 224x224 (2 x MACs; mul+add counted
# separately, matching how accelerator peak FLOP/s are quoted).  Training
# step ~= 3x forward.  Approximations from the standard architecture
# definitions — good to ~10%, used only for the MFU diagnostic column.
FWD_GFLOPS = {"alexnet": 1.43, "vgg": 31.0, "inception-bn": 4.1,
              "inception-v3": 11.4, "resnet-50": 8.2, "resnet-152": 23.1}

def _chip_info():
    import jax
    # single source for the peak table: mxnet_tpu/flops.py (the MFU-proxy
    # columns and tools/step_profile.py read the same one)
    from mxnet_tpu.flops import peak_bf16_flops
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", str(dev.platform))
    peak = peak_bf16_flops(kind)
    return {"device_kind": kind, "platform": dev.platform,
            "n_devices": len(jax.devices()),
            "peak_bf16_flops_per_device": peak}


def _mfu(flops_per_item, items_per_sec, chip):
    peak = chip["peak_bf16_flops_per_device"]
    if peak is None or flops_per_item is None:
        return None
    return round(flops_per_item * items_per_sec /
                 (peak * chip["n_devices"]), 4)


def _cost_columns(cost, steps_per_sec, chip):
    """Measured-FLOPs columns for a train row: model FLOPs per step from
    the COMPILED program's cost_analysis() (not the hand table) and the
    MFU proxy against table peak.  ``cost`` may be None (backend
    declined) — the columns then report null, never fail the row."""
    from mxnet_tpu.flops import mfu_proxy
    flops = (cost or {}).get("flops")
    cols = {
        "model_gflops_per_step":
            round(flops / 1e9, 3) if flops else None,
        "mfu_proxy": mfu_proxy(flops, steps_per_sec,
                               chip["peak_bf16_flops_per_device"],
                               chip["n_devices"]),
    }
    if cost and cost.get("temp_bytes") is not None:
        cols["program_temp_mb"] = round(cost["temp_bytes"] / 2 ** 20, 2)
    return cols


@contextlib.contextmanager
def _managed_env(set_vars, clear=()):
    """Pop every key in ``set_vars`` | ``clear`` from the environment,
    apply ``set_vars``, restore all of them on exit.  THE way a bench
    row controls trace-time knobs: listing a var in ``clear`` makes
    "baseline = this knob absent" explicit, so an ambient setting (e.g.
    MXNET_REMAT_POLICY exported in the measuring shell) can never leak
    into a row that claims to measure without it."""
    keys = set(set_vars) | set(clear)
    saved = {k: os.environ.pop(k, None) for k in keys}
    os.environ.update(set_vars)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


_REMAT_VARS = ("MXNET_REMAT_POLICY", "MXNET_BACKWARD_DO_MIRROR")


def _fetch_sync(outs):
    """Force TRUE device completion by fetching dependent bytes to host.

    Shared honest-timing primitive, now packaged as
    ``mxnet_tpu.test_utils.fetch_sync`` so every harness
    (benchmark_score.py, ad-hoc scripts) imports one implementation
    instead of reaching into this script via sys.path; see its
    docstring for why every timed window ends on a fetch of dependent
    bytes."""
    from mxnet_tpu.test_utils import fetch_sync
    fetch_sync(outs)


try:
    import jax
except Exception:  # pragma: no cover
    jax = None


def bench_calibration(chip, smoke=False, seconds_target=8.0):
    """Empirical peak: bf16 matmul chain with analytically-known FLOPs,
    fetch-timed.  This row is the credibility anchor for every MFU
    column — a workload row whose implied FLOP/s exceeds this measured
    ceiling indicates a timing artifact, not a fast chip."""
    import jax
    import jax.numpy as jnp

    n, k = (256, 4) if smoke else (4096, 16)
    if smoke:
        seconds_target = 1.0
    rep_cap = 2000  # a noisy probe must not unbound the loop
    rs = np.random.RandomState(0)
    # generate per-slice in float32: a float64 (k, n, n) temporary would
    # transiently cost 4x the bf16 payload on the bench host
    host_ws = np.empty((k, n, n), np.float32)
    for i in range(k):
        host_ws[i] = rs.uniform(-1, 1, (n, n)).astype(np.float32) \
            / np.float32(np.sqrt(n))
    ws = jnp.asarray(host_ws, dtype=jnp.bfloat16)
    del host_ws

    @jax.jit
    def chain(x, ws):
        def body(x, w):
            return x @ w, None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    x0 = jnp.asarray(rs.uniform(-1, 1, (n, n)), dtype=jnp.bfloat16)
    x = chain(x0, ws)
    _fetch_sync(x[:1, :1])
    flops_per_chain = k * 2 * n ** 3
    # fetch-roundtrip baseline on an already-ready buffer: the rep
    # sizing and the final window must amortize on compute-only time,
    # not on the host's dispatch + device-to-host latency
    tic = time.perf_counter()
    _fetch_sync(x[:1, :1])
    rtt = time.perf_counter() - tic
    tic = time.perf_counter()
    x = chain(x, ws)
    _fetch_sync(x[:1, :1])
    probe = max(time.perf_counter() - tic - rtt, 1e-4)
    reps = max(4, min(int(seconds_target / probe), rep_cap))
    tic = time.perf_counter()
    for _ in range(reps):
        x = chain(x, ws)
    _fetch_sync(x[:1, :1])
    dt = max(time.perf_counter() - tic - rtt, 1e-6)
    tflops = flops_per_chain * reps / dt / 1e12
    peak = chip.get("peak_bf16_flops_per_device")
    return {"metric": "calibration.matmul_bf16",
            "value": round(tflops, 2), "unit": "TFLOP/s",
            "vs_baseline": None,
            "fraction_of_table_peak":
                round(tflops * 1e12 / peak, 4) if peak else None,
            "reps": reps}


def _error_row(metric, exc):
    tb = traceback.format_exc().strip().splitlines()
    return {"metric": metric, "value": 0.0, "unit": "error",
            "vs_baseline": 0.0, "error": "%s: %s" % (type(exc).__name__,
                                                     exc),
            "traceback_tail": tb[-6:]}


def _net_symbol(name, mx, smoke=False):
    """Model-zoo symbol for a BASELINE.md network name.

    ``smoke`` (BENCH_SMOKE=1) swaps in tiny stand-ins — for validating the
    harness plumbing on CPU, never for reported numbers."""
    if smoke:
        return mx.models.resnet(num_classes=100, num_layers=20,
                                image_shape="3,28,28")
    if name == "resnet-50":
        return mx.models.resnet(num_classes=1000, num_layers=50)
    if name == "resnet-152":
        return mx.models.resnet(num_classes=1000, num_layers=152)
    if name == "inception-v3":
        return mx.models.inception_v3(num_classes=1000)
    if name == "inception-bn":
        return mx.models.inception_bn(num_classes=1000)
    if name == "alexnet":
        return mx.models.alexnet(num_classes=1000)
    if name == "vgg":
        return mx.models.vgg(num_classes=1000, num_layers=16)
    raise ValueError(name)


def bench_fit(name, per_dev_batch, iters, warmup, chip, smoke=False):
    """Training images/sec through the real ``Module.fit`` loop (synthetic
    data, accuracy metric, Speedometer-equivalent timing — the reference's
    ``train_imagenet.py --benchmark 1`` protocol)."""
    import jax
    import mxnet_tpu as mx
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "examples", "image-classification"))
    from common.data import SyntheticDataIter

    n_dev = chip["n_devices"]
    if smoke:
        per_dev_batch = 8
    batch = per_dev_batch * n_dev
    image_shape = (3, 28, 28) if smoke else (3, 224, 224)
    num_classes = 100 if smoke else 1000
    sym = _net_symbol(name, mx, smoke)
    # mx.tpu(i) falls back to host device i in CPU-only environments
    devs = [mx.tpu(i) for i in range(n_dev)]
    mod = mx.Module(symbol=sym, context=devs, compute_dtype="bfloat16")
    train = SyntheticDataIter(num_classes, (batch,) + image_shape,
                              max_iter=warmup + iters)
    # The fit loop dispatches asynchronously: batch-end callbacks fire at
    # DISPATCH time, so callback timestamps measure host enqueue rate,
    # not device throughput (on a 1-core CPU smoke they overstated by
    # 20x).  Instead, drain the device queue at the warmup boundary to
    # start the clock clean, and drain again after fit so the clock
    # stops when compute actually finishes.
    seen = [0]
    t0 = [None]
    t1 = [None]

    def cb(param):
        seen[0] += 1
        # the clock brackets the steady-state loop (the reference's
        # Speedometer protocol): epoch-end get_params/set_params sync
        # is host/transfer work outside the training hot path
        if seen[0] == warmup or seen[0] == warmup + iters:
            mx.nd.waitall()
            _fetch_sync(mod.get_outputs()[0])
            (t0 if seen[0] == warmup else t1)[0] = time.perf_counter()

    # step-phase attribution rides along: the collector is a few dict
    # updates per batch (profiler.phase) — unlike the Chrome
    # profiler it never synchronizes dispatch, so it is safe INSIDE the
    # timed window.  The first spans include compile; the column is a
    # diagnostic shape, not a second clock.
    from mxnet_tpu import profiler as _prof
    _prof.start_step_profile()
    try:
        mod.fit(train, num_epoch=1, eval_metric="accuracy",
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "wd": 1e-4},
                initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                                  factor_type="in",
                                                  magnitude=2),
                kvstore="device", batch_end_callback=cb)
    finally:
        phase_report = _prof.stop_step_profile()
    assert seen[0] == warmup + iters and None not in (t0[0], t1[0]), \
        "expected %d batches, saw %d" % (warmup + iters, seen[0])
    ips = batch * iters / (t1[0] - t0[0])
    gflops = FWD_GFLOPS.get(name)
    phases = {k: v["per_step_ms"]
              for k, v in (phase_report or {}).get("phases", {}).items()}
    # measured-FLOPs MFU proxy from the compiled fused step (the fit fast
    # path's trainer); None on the executor-group fallback
    cost = None
    trainer = mod._one_program_trainer()
    if trainer is not None:
        train.reset()
        b0 = next(iter(train))
        cost = trainer.step_cost_analysis(b0.data[0], b0.label[0])
    row = {"metric": "train.%s.module_fit" % name,
           "value": round(ips, 2), "unit": "images/sec",
           "vs_baseline": round(ips / (TRAIN_BASELINE[name] * n_dev), 3),
           "batch_size": batch,
           "phase_ms_per_step": phases,
           "mfu": _mfu(3 * gflops * 1e9 if gflops else None, ips, chip)}
    row.update(_cost_columns(cost, ips / batch, chip))
    return row


def bench_trainer_direct(iters, warmup, chip, smoke=False,
                         per_dev_batch=32):
    """resnet-50 through DataParallelTrainer directly (round-1 protocol).

    ``per_dev_batch=256`` variant: the reference's training table pins
    batch 32 (docs/how_to/perf.md:179-188), which under-feeds a v5e MXU;
    the large-batch row shows the chip's ceiling on the same model."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import DataParallelTrainer

    n_dev = chip["n_devices"]
    batch = (8 if smoke else per_dev_batch) * n_dev
    image_shape = (3, 28, 28) if smoke else (3, 224, 224)
    num_classes = 100 if smoke else 1000
    net = _net_symbol("resnet-50", mx, smoke)
    trainer = DataParallelTrainer(
        net, data_shapes={"data": (batch,) + image_shape},
        label_shapes={"softmax_label": (batch,)},
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2),
        compute_dtype="bfloat16")
    rng = np.random.RandomState(0)
    data = jax.device_put(
        jnp.asarray(rng.uniform(-1, 1, (batch,) + image_shape),
                    dtype=jnp.bfloat16), trainer._batched)
    label = jax.device_put(
        jnp.asarray(rng.randint(0, num_classes, (batch,)),
                    dtype=jnp.float32), trainer._batched)
    for _ in range(warmup):
        outs = trainer.step(data, label)
    _fetch_sync(outs)
    tic = time.perf_counter()
    for _ in range(iters):
        outs = trainer.step(data, label)
    _fetch_sync(outs)
    ips = batch * iters / (time.perf_counter() - tic)
    tag = "train.resnet-50.trainer_direct" + (
        "" if per_dev_batch == 32 else "_b%d" % per_dev_batch)
    row = {"metric": tag,
           "value": round(ips, 2), "unit": "images/sec",
           # the P100 anchor is a batch-32 protocol; larger-batch rows
           # report throughput/MFU only
           "vs_baseline": round(ips / (TRAIN_BASELINE["resnet-50"] * n_dev),
                                3) if per_dev_batch == 32 else None,
           "batch_size": batch,
           "mfu": _mfu(3 * FWD_GFLOPS["resnet-50"] * 1e9, ips, chip)}
    row.update(_cost_columns(trainer.step_cost_analysis(data, label),
                             ips / batch, chip))
    return row


def bench_inference(name, iters, chip, smoke=False):
    """Forward-only scoring (benchmark_score.py protocol, batch 32)."""
    import mxnet_tpu as mx

    batch = 8 if smoke else 32
    image_shape = (3, 28, 28) if smoke else (3, 224, 224)
    sym = _net_symbol(name, mx, smoke)
    mod = mx.Module(symbol=sym, context=mx.current_context(),
                    label_names=None)
    mod.bind(for_training=False,
             data_shapes=[("data", (batch,) + image_shape)])
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=2.0))
    rs = np.random.RandomState(0)
    batch_data = mx.io.DataBatch(
        data=[mx.nd.array(rs.uniform(-1, 1, (batch,) + image_shape)
                          .astype("float32"))], label=[])
    for _ in range(2):
        mod.forward(batch_data, is_train=False)
    _fetch_sync(mod.get_outputs()[0])
    tic = time.perf_counter()
    for _ in range(iters):
        mod.forward(batch_data, is_train=False)
    _fetch_sync(mod.get_outputs()[0])
    ips = iters * batch / (time.perf_counter() - tic)
    gflops = FWD_GFLOPS.get(name)
    return {"metric": "inference.%s" % name, "value": round(ips, 2),
            "unit": "images/sec",
            "vs_baseline": round(ips / INFER_BASELINE[name], 3),
            "batch_size": batch,
            "mfu": _mfu(gflops * 1e9 if gflops else None, ips, chip)}


def bench_lstm_bucketing(iters, warmup, chip, smoke=False):
    """LSTM-bucketing LM training throughput (BASELINE LSTM workload:
    3-layer LSTM, hidden/embed 200, batch 32, bucket len 32)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.lstm_lm import sym_gen_factory

    batch, seq_len, vocab = (8, 8, 100) if smoke else (32, 32, 10000)
    # the drain-bounded window needs at least 2 measured batches
    # (BENCH_ITERS=1 sweeps would otherwise fail this row's assert)
    iters = max(iters, 2)
    rs = np.random.RandomState(0)
    sent = [list(rs.randint(1, vocab, seq_len))
            for _ in range(batch * (warmup + iters))]
    data = mx.rnn.BucketSentenceIter(sent, batch, buckets=[seq_len],
                                     invalid_label=0)
    nl, nh = (1, 32) if smoke else (3, 200)
    sym_gen = sym_gen_factory(num_layers=nl, num_hidden=nh, num_embed=nh,
                              vocab_size=vocab)
    mod = mx.module.BucketingModule(
        sym_gen=sym_gen, default_bucket_key=data.default_bucket_key,
        context=mx.current_context())
    # same drain-bounded protocol as bench_fit: dispatch timestamps
    # overstate async throughput
    seen = [0]
    t0 = [None]
    t1 = [None]
    n_batches = warmup + iters

    def cb(param):
        seen[0] += 1
        # steady-state bracket; epoch-end sync stays outside (see
        # bench_fit)
        if seen[0] == warmup or seen[0] == n_batches:
            mx.nd.waitall()
            _fetch_sync(mod.get_outputs()[0])
            (t0 if seen[0] == warmup else t1)[0] = time.perf_counter()

    mod.fit(data, num_epoch=1,
            eval_metric=mx.metric.Perplexity(ignore_label=0),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.01, "momentum": 0.0,
                              "wd": 1e-5},
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.34),
            kvstore="device", batch_end_callback=cb)
    assert seen[0] == n_batches and None not in (t0[0], t1[0]), \
        "expected %d batches, saw %d" % (n_batches, seen[0])
    sps = batch * iters / (t1[0] - t0[0])
    return {"metric": "train.lstm-bucketing.module_fit",
            "value": round(sps, 2), "unit": "samples/sec",
            "vs_baseline": None, "batch_size": batch, "seq_len": seq_len,
            "mfu": None}


def bench_flash_attention(chip, smoke=False):
    """Pallas flash-attention forward throughput vs XLA dense attention.

    On TPU the kernel runs compiled by Mosaic (CPU tests run it in
    interpret mode; `chip_smoke.py` is the quick proof that it compiles
    and agrees with its dense twin on the chip) — no reference
    counterpart, its attention era was RNNs."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.pallas_ops.flash_attention import flash_attention

    if not smoke and chip["platform"] != "tpu":
        # interpret mode at the full shape is hours of wall time; the
        # smoke tier covers the off-chip plumbing check
        return {"metric": "pallas.flash_attention", "value": 0.0,
                "unit": "skipped", "vs_baseline": None,
                "note": "full-shape interpret mode off-chip; "
                        "BENCH_SMOKE=1 runs the plumbing check"}
    b, h, l, d = (1, 2, 256, 64) if smoke else (4, 16, 2048, 64)
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.uniform(-1, 1, (b, h, l, d)),
                           dtype=jnp.bfloat16) for _ in range(3))

    # the cross-rep anti-DCE chain (k perturbed by the previous output)
    # lives INSIDE the jitted programs: computed eagerly per rep it
    # added two dispatches of overhead to BOTH timed paths (ADVICE r5)
    def _chain_k(k, prev):
        return prev[..., :d] * 0 + k

    @jax.jit
    def dense(q, k, v, prev):
        k = _chain_k(k, prev)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          jax.nn.softmax(s, axis=-1), v)

    flash = jax.jit(
        lambda q, k, v, prev: flash_attention(q, _chain_k(k, prev), v))
    # 2 matmuls of 2*L^2*D each per (batch, head)
    flops = 4 * b * h * l * l * d
    out = {}
    for name, fn in (("flash", flash), ("dense_xla", dense)):
        o = fn(q, k, v, v)
        _fetch_sync(o[:1, :1, :1, :1])
        reps = 2 if smoke else 30
        tic = time.perf_counter()
        for _ in range(reps):
            o = fn(q, k, v, o)  # chain: no cross-rep DCE
        _fetch_sync(o[:1, :1, :1, :1])
        dt = time.perf_counter() - tic
        out[name] = flops * reps / dt / 1e12
    return {"metric": "pallas.flash_attention",
            "value": round(out["flash"], 4), "unit": "TFLOP/s",
            "vs_baseline": None,
            "dense_xla_tflops": round(out["dense_xla"], 4),
            "speedup_vs_dense": round(out["flash"] / out["dense_xla"], 3)
            if out["dense_xla"] else None,
            "shape": [b, h, l, d]}


def bench_imperative_dispatch(op_name, chip, smoke=False):
    """Small-op imperative dispatch throughput: eager vs cached-op JIT.

    The reference's headline design runs *imperative* NDArray code through
    cached engine ops (MXImperativeInvoke → CachedOp); this row family
    measures that dispatch layer (`mxnet_tpu/cached_op.py`) directly on a
    repeated composite op — CPU-runnable, so the win shows in the bench
    trajectory without a TPU window.  Reported: cached ops/sec, eager
    ops/sec, speedup, and post-warmup cache hit rate."""
    import mxnet_tpu as mx
    from mxnet_tpu import engine

    eng = engine.get()
    reps = 60 if smoke else 400
    warmup = 5
    if op_name == "softmax":
        x = mx.nd.array(np.random.RandomState(0)
                        .uniform(-1, 1, (16, 64) if smoke else (256, 256))
                        .astype("float32"))

        def call():
            return mx.nd.softmax(x)
    elif op_name == "batchnorm":
        shape = (8, 4, 4, 4) if smoke else (32, 16, 8, 8)
        rs = np.random.RandomState(0)
        d = mx.nd.array(rs.uniform(-1, 1, shape).astype("float32"))
        c = (shape[1],)
        gamma, beta = mx.nd.ones(c), mx.nd.zeros(c)
        mm, mv = mx.nd.zeros(c), mx.nd.ones(c)

        def call():
            return mx.nd.BatchNorm(d, gamma, beta, mm, mv)
    else:
        raise ValueError(op_name)

    def rate():
        for _ in range(warmup):
            out = call()
        out.wait_to_read()
        tic = time.perf_counter()
        for _ in range(reps):
            out = call()
        out.wait_to_read()
        _fetch_sync(out)
        return reps / (time.perf_counter() - tic)

    prev = eng.imperative_jit
    try:
        eng.set_imperative_jit(False)
        eager_rate = rate()
        eng.set_imperative_jit(True)
        from mxnet_tpu import cached_op
        for _ in range(warmup):  # warm the cache, then count hits only
            call().wait_to_read()
        cached_op.reset_stats()
        cached_rate = rate()
        st = eng.imperative_cache_stats()
    finally:
        eng.set_imperative_jit(prev)
    seen = st["hits"] + st["misses"]
    return {"metric": "imperative.dispatch.%s" % op_name,
            "value": round(cached_rate, 2), "unit": "ops/sec",
            "vs_baseline": None,
            "eager_ops_per_sec": round(eager_rate, 2),
            "speedup_vs_eager": round(cached_rate / eager_rate, 3)
            if eager_rate else None,
            "cache_hit_rate": round(st["hits"] / seen, 4) if seen else None,
            "cache_evictions": st["evictions"]}


def _kvstore_step_rate(mode, sizes, steps, warmup, delay_s,
                       kv_name="dist_async"):
    """One in-process PS cluster (scheduler+server threads + this
    process as the worker) driven through full training-shaped
    push+pull+flush steps, with ``delay_s`` of injected latency on
    every server-received message (the faultinject 'delay' seam — the
    same seam the fault tests schedule, here standing in for network
    RTT so overlap is measurable on one CPU host).

    mode: 'serial_fp32' (pipeline off — the PR-2 blocking
    per-parameter push-then-pull baseline), 'fp32' (async pipeline +
    bucketing), '2bit' (pipeline + bucketing + 2-bit compression).
    ``kv_name`` picks the store ('dist_async' default; 'dist_sync' is
    the bulk-synchronous PS baseline the dist_mesh row compares to).
    Returns (steps_per_sec, payload_bytes_per_step)."""
    import socket
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import faultinject
    from mxnet_tpu import kvstore as kvs
    from mxnet_tpu import kvstore_dist as ksd

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    managed = {
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": "1",
        "DMLC_NUM_SERVER": "1",
        # several buckets instead of one catch-all so the row exercises
        # multi-RPC pipelining, not one giant message
        "MXNET_KVSTORE_BUCKET_BYTES": str(256 * 1024),
        "MXNET_KVSTORE_PIPELINE": "0" if mode == "serial_fp32" else "1",
    }
    saved = {k: os.environ.get(k) for k in managed}
    os.environ.update(managed)
    try:
        sched = threading.Thread(target=ksd.run_scheduler, daemon=True)
        sched.start()
        server = threading.Thread(target=ksd.run_server, daemon=True)
        server.start()
        kv = kvs.create(kv_name)
        if mode == "2bit":
            kv.set_gradient_compression({"type": "2bit",
                                         "threshold": 0.5})
        rs = np.random.RandomState(0)
        arrays = [mx.nd.array(rs.uniform(-1, 1, (n,)).astype("float32"))
                  for n in sizes]
        keys = list(range(len(sizes)))
        prios = [-k for k in keys]
        for k, a in zip(keys, arrays):
            kv.init(k, a)
        outs = [mx.nd.zeros((n,)) for n in sizes]
        faultinject.install({"rules": [
            {"seam": "server.recv", "nth": 1, "count": "inf",
             "action": "delay", "seconds": delay_s}]})
        try:
            def step():
                kv.push(keys, arrays, priority=prios)
                kv.pull(keys, outs, priority=prios)
                kv.flush()

            for _ in range(warmup):
                step()
            stats0 = kv.wire_stats()
            tic = time.perf_counter()
            for _ in range(steps):
                step()
            dt = time.perf_counter() - tic
            stats1 = kv.wire_stats()
        finally:
            faultinject.install(None)
        kv.close()
        bytes_per_step = (stats1["push_bytes"] - stats0["push_bytes"]
                          + stats1["pull_bytes"]
                          - stats0["pull_bytes"]) / steps
        sched.join(timeout=10)
        server.join(timeout=10)
        return steps / dt, bytes_per_step
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


_KV_SERIAL_BASELINE = {}


def bench_kvstore_push_pull(mode, chip, smoke=False):
    """Dist-KVStore data-plane throughput: training-shaped push+pull
    steps over an injected per-RPC latency, pipelined (bucketing +
    bounded in-flight window, and optionally 2-bit compression) vs the
    serialized per-parameter baseline.  CPU-deterministic — the overlap
    and bytes-on-wire wins need no accelerator to reproduce."""
    # resnet-ish parameter census: many small bias/gamma/beta + a few
    # conv blocks + one big fc — smoke shrinks counts, not the shape mix
    if smoke:
        sizes = [256] * 12 + [16384] * 3 + [262144]
        steps, warmup, delay = 3, 1, 0.002
    else:
        sizes = [256] * 40 + [4096] * 10 + [65536] * 4 + [1048576]
        steps, warmup, delay = 6, 1, 0.002
    pipelined, bps = _kvstore_step_rate(mode, sizes, steps, warmup, delay)
    # the serialized baseline is mode-independent; measure it once and
    # share it across the fp32 and 2bit rows
    cache_key = (tuple(sizes), steps, warmup, delay)
    if cache_key not in _KV_SERIAL_BASELINE:
        _KV_SERIAL_BASELINE[cache_key] = _kvstore_step_rate(
            "serial_fp32", sizes, steps, warmup, delay)
    serial, serial_bps = _KV_SERIAL_BASELINE[cache_key]
    row = {"metric": "kvstore.push_pull.%s" % mode,
           "value": round(pipelined, 2), "unit": "steps/sec",
           "vs_baseline": None,
           "serialized_steps_per_sec": round(serial, 2),
           "speedup_vs_serialized": round(pipelined / serial, 3)
           if serial else None,
           "payload_bytes_per_step": int(bps),
           "fp32_payload_bytes_per_step": int(serial_bps),
           "injected_rpc_delay_ms": delay * 1e3,
           "n_params": len(sizes)}
    if mode == "2bit":
        # pulls (weights) are always lossless, so the whole-step ratio
        # understates the push-side codec; report both
        row["bytes_reduction_vs_fp32"] = round(serial_bps / bps, 2) \
            if bps else None
        fp32_push = sum(4 * n for n in sizes)
        push_bytes = bps - sum(4 * n for n in sizes)  # step = push + pull
        row["push_bytes_reduction_vs_fp32"] = \
            round(fp32_push / push_bytes, 2) if push_bytes > 0 else None
        row["note"] = ("gradient pushes ~16x smaller (2 bits/elem + "
                       "headers); weight pulls stay lossless fp32.  On "
                       "this CPU protocol the numpy quantize/pack cost "
                       "trades against only %gms of injected RTT — on a "
                       "real wire the byte reduction is the win" % (
                           delay * 1e3))
    return row


def _dist_mesh_step_rate(sizes, steps, warmup, delay_s, overlap,
                         bucket_bytes):
    """Training-shaped push+pull+flush steps through the collectives
    kvstore (``create('dist_mesh')``), with ``delay_s`` of injected
    latency on every per-bucket collective (the ``mesh.collective``
    faultinject seam — DCN-ish all-reduce RTT, so overlap is measurable
    on one CPU host).  ``overlap=False`` swaps in the barrier launcher:
    collectives run serially in submit order, paying
    ``n_buckets x delay`` where the overlapped plane pays ~one delay.
    Returns (steps_per_sec, n_buckets)."""
    import mxnet_tpu as mx
    from mxnet_tpu import faultinject
    from mxnet_tpu import kvstore as kvs
    from mxnet_tpu.parallel.mesh_reduce import MeshCollectiveLauncher

    managed = {"MXNET_KVSTORE_BUCKET_BYTES": str(bucket_bytes)}
    saved = {k: os.environ.get(k) for k in managed}
    os.environ.update(managed)
    try:
        kv = kvs.create("dist_mesh")
        kv._launcher = MeshCollectiveLauncher(overlap=overlap)
        rs = np.random.RandomState(0)
        arrays = [mx.nd.array(rs.uniform(-1, 1, (n,)).astype("float32"))
                  for n in sizes]
        keys = list(range(len(sizes)))
        prios = [-k for k in keys]
        for k, a in zip(keys, arrays):
            kv.init(k, a)
        outs = [mx.nd.zeros((n,)) for n in sizes]
        n_buckets = len(set(kv._plan.bucket_of(k) for k in keys))
        faultinject.install({"rules": [
            {"seam": "mesh.collective", "nth": 1, "count": "inf",
             "action": "delay", "seconds": delay_s}]})
        try:
            def step():
                kv.push(keys, arrays, priority=prios)
                kv.pull(keys, outs, priority=prios)
                kv.flush()

            for _ in range(warmup):
                step()
            tic = time.perf_counter()
            for _ in range(steps):
                step()
            dt = time.perf_counter() - tic
        finally:
            faultinject.install(None)
        kv.close()
        return steps / dt, n_buckets
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_kvstore_dist_mesh(mode, chip, smoke=False):
    """Collectives-vs-PS data plane (docs/architecture/dist_mesh.md):
    the same training-shaped step schedule under the same injected
    latency budget, through the two wires the ``kvstore=`` string picks
    between.  CPU-deterministic.

    'fp32': ``dist_mesh`` (overlapped bucket collectives, pull is a
    local replica copy) vs the ``dist_sync`` parameter server (push RPC
    + pull RPC per bucket, latency on every server-received message).
    'overlap': overlapped vs barrier collective launch at the same
    per-collective delay — the bucketed-reduction overlap win in
    isolation."""
    if smoke:
        sizes = [8192] * 6
        steps, warmup, delay = 3, 1, 0.01
    else:
        sizes = [8192] * 12
        steps, warmup, delay = 6, 1, 0.01
    bucket_bytes = 64 * 1024          # 32KB keys -> 2 per bucket
    if mode == "overlap":
        rate, n_buckets = _dist_mesh_step_rate(
            sizes, steps, warmup, delay, True, bucket_bytes)
        barrier, _ = _dist_mesh_step_rate(
            sizes, steps, warmup, delay, False, bucket_bytes)
        return {"metric": "kvstore.dist_mesh.overlap",
                "value": round(rate, 2), "unit": "steps/sec",
                "vs_baseline": None,
                "barrier_steps_per_sec": round(barrier, 2),
                "speedup_vs_barrier": round(rate / barrier, 3)
                if barrier else None,
                "injected_collective_delay_ms": delay * 1e3,
                "n_params": len(sizes), "n_buckets": n_buckets}
    rate, n_buckets = _dist_mesh_step_rate(
        sizes, steps, warmup, delay, True, bucket_bytes)
    ps, _ = _kvstore_step_rate("fp32", sizes, steps, warmup, delay,
                               kv_name="dist_sync")
    return {"metric": "kvstore.dist_mesh.fp32",
            "value": round(rate, 2), "unit": "steps/sec",
            "vs_baseline": None,
            "ps_steps_per_sec": round(ps, 2),
            "speedup_vs_ps": round(rate / ps, 3) if ps else None,
            "injected_latency_ms": delay * 1e3,
            "n_params": len(sizes), "n_buckets": n_buckets,
            "note": ("same schedule, same injected latency: the PS "
                     "pays it per server-received RPC (push and pull "
                     "legs), the mesh per bucket collective — "
                     "overlapped, with the pull leg gone entirely "
                     "(local replica copy)")}


def _staleness_run(mode, steps, delay_s, sizes):
    """One 2-worker in-process cluster (worker threads + scheduler +
    server) where worker 1 is a persistent straggler (the seeded
    ``straggler`` fault kind sleeps ``delay_s`` on each of its RPCs).
    ``mode``: 'sync' (dist_sync merge rounds — every round waits for
    the straggler) or 's<N>' (dist_async under staleness bound N).
    Returns (fast-worker steps/sec, fast-worker wire stats/step)."""
    import socket
    import threading

    from mxnet_tpu import faultinject
    from mxnet_tpu import kvstore_dist as ksd

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    staleness = -1 if mode == "sync" else int(mode[1:])
    managed = {
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": "2",
        "DMLC_NUM_SERVER": "1",
        "MXNET_KVSTORE_HEARTBEAT_INTERVAL": "0.1",
        "MXNET_KVSTORE_MEMBERSHIP_TTL": "0.05",
        "MXNET_KVSTORE_MAX_STALENESS": str(staleness),
    }
    saved = {k: os.environ.get(k) for k in managed}
    os.environ.update(managed)
    try:
        sched = threading.Thread(target=ksd.run_scheduler, daemon=True)
        sched.start()
        server = ksd.Server()
        threading.Thread(target=server.run, daemon=True).start()
        fast, slow = ksd.WorkerClient(), ksd.WorkerClient()
        if mode == "sync":
            server._handle_command("sync_mode", b"")
            fast.sync_push = slow.sync_push = True
        else:
            server._handle_command("async_mode", b"")
        keys = list(range(len(sizes)))
        for k, n in zip(keys, sizes):
            fast.init(k, np.zeros(n, np.float32))
        grads = [np.ones(n, np.float32) for n in sizes]
        faultinject.install({"seed": 5, "rules": [
            {"seam": "worker.send", "rank": 1, "action": "straggler",
             "seconds": delay_s}]})
        elapsed = [None]
        fast.reset_wire_stats()

        def run(client, timer):
            tic = time.perf_counter()
            for _ in range(steps):
                for k, g in zip(keys, grads):
                    client.push(k, g)
                for k, n in zip(keys, sizes):
                    client.pull(k, n)
            if timer:
                elapsed[0] = time.perf_counter() - tic

        ts = [threading.Thread(target=run, args=(fast, True), daemon=True),
              threading.Thread(target=run, args=(slow, False), daemon=True)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=600)
        faultinject.install(None)
        stats = fast.wire_stats()
        fast.finalize(False)
        slow.finalize(True)
        return steps / elapsed[0], {k: v / steps for k, v in stats.items()}
    finally:
        faultinject.install(None)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


_STALENESS_SYNC_BASELINE = {}


def bench_kvstore_async_staleness(mode, chip, smoke=False):
    """Elastic-async PS throughput under one straggler: the fast
    worker's steps/sec over a bounded window, dist_sync vs dist_async
    at staleness bounds s=0 / s=4 on the same seeded schedule
    (docs/architecture/elastic_ps.md).  The straggler sleeps per RPC
    (>= 5x slower per step than the fast worker); in sync mode every
    merge round waits for it, at s=4 the fast worker runs through it up
    to 4 steps ahead; s=0 reproduces sync pacing through the read gate.
    CPU-deterministic; wire-stats columns as in kvstore.push_pull."""
    sizes = [256] * 3 if smoke else [256] * 6
    steps, delay = 7, 0.03
    rate, wire = _staleness_run(mode, steps, delay, sizes)
    cache_key = (tuple(sizes), steps, delay)
    if cache_key not in _STALENESS_SYNC_BASELINE:
        if mode == "sync":
            _STALENESS_SYNC_BASELINE[cache_key] = (rate, wire)
        else:
            _STALENESS_SYNC_BASELINE[cache_key] = _staleness_run(
                "sync", steps, delay, sizes)
    sync_rate, _ = _STALENESS_SYNC_BASELINE[cache_key]
    row = {"metric": "kvstore.async_staleness.%s" % mode,
           "value": round(rate, 2), "unit": "steps/sec",
           "vs_baseline": None,
           "staleness_bound": -1 if mode == "sync" else int(mode[1:]),
           "sync_steps_per_sec": round(sync_rate, 2),
           "speedup_vs_sync": round(rate / sync_rate, 3)
           if sync_rate else None,
           "straggler_rpc_delay_ms": delay * 1e3,
           "window_steps": steps,
           "push_bytes_per_step": int(wire["push_bytes"]),
           "pull_bytes_per_step": int(wire["pull_bytes"]),
           "push_rpcs_per_step": round(wire["push_rpcs"], 2),
           "pull_rpcs_per_step": round(wire["pull_rpcs"], 2),
           "n_params": len(sizes)}
    if mode == "s4":
        row["note"] = ("bounded-staleness SSP: the fast worker reads at "
                       "most 4 steps ahead of the straggler instead of "
                       "fencing every merge round on it; over the "
                       "%d-step window that is the elastic claim the "
                       "elastic-smoke gate pins at >= 2x" % steps)
    return row


def bench_serving_latency(mode, chip, smoke=False):
    """Serving-plane p50/p99 + QPS: the continuous batcher
    (serving/scheduler.py over AOT bucket programs) vs a per-request
    ``Predictor.forward`` deployment, both driven by the SAME seeded
    open-loop arrival schedule at a multiple of the per-request
    capacity (serving/loadgen.py latency_protocol — the protocol
    ``make serve-smoke`` gates on).  CPU-deterministic: the schedule
    and request contents derive from the seed; batching economics
    (one bucket dispatch amortizes per-forward overhead across
    requests) reproduce without an accelerator."""
    from mxnet_tpu.serving.loadgen import latency_protocol

    r = latency_protocol(mode=mode, smoke=smoke)
    so, b = r["serial_open"], r["batch"]
    eng = b.pop("engine", {})
    row = {"metric": "serving.latency.%s" % mode,
           "value": b["qps_achieved"], "unit": "qps",
           "vs_baseline": None,
           "p50_ms": b["p50_ms"], "p99_ms": b["p99_ms"],
           "per_request_qps": so["qps_achieved"],
           "per_request_p50_ms": so["p50_ms"],
           "per_request_p99_ms": so["p99_ms"],
           "qps_vs_per_request": r["qps_vs_per_request"],
           "p99_vs_per_request": r["p99_vs_per_request"],
           "closed_loop_qps": r["serial_closed"]["qps"],
           "offered_mult": r["offered_mult"],
           "max_delay_ms": r["max_delay_ms"],
           "max_batch": r["max_batch"],
           "n_requests": b["n"],
           "dropped": b["timeouts"] + b["errors"] + b["cancelled"],
           "batches": eng.get("batches"),
           "padded_rows": eng.get("padded_rows"),
           "weight_bytes_by_dtype": eng.get("weight_bytes_by_dtype"),
           "seed": r["seed"]}
    if mode == "bf16":
        row["note"] = ("bf16 serving weights (half the resident memory); "
                       "fp32 serving stays bit-equal to the classic "
                       "Predictor — the accuracy row is "
                       "tests/test_serving.py's bit-equality pin")
    elif mode == "int8":
        row["note"] = ("int8 weight-only serving: FC weights quantized "
                       "once at load (scale-per-row symmetric) and "
                       "dequantized in-graph through the fused "
                       "dequant-matmul door (~4x less resident weight "
                       "memory — weight_bytes_by_dtype is the "
                       "measurement; top-1 parity is "
                       "tests/test_quant_serving.py's pin)")
    return row


def bench_serving_frontdoor(which, chip, smoke=False):
    """Front-door rows (serving/frontdoor.py + replica_set.py, the
    protocols ``make frontdoor-smoke`` gates on):

    * ``http_overhead`` — the SAME engine under the SAME seeded
      open-loop schedule, driven in-process and over the HTTP front
      door (npz transport, persistent connections): the p50/p99 delta
      is pure front-door cost, measured below either side's
      saturation.
    * ``failover`` — 3 shared-nothing replicas behind the least-loaded
      balancer; a seeded ``die`` at the serve.dispatch faultinject
      seam SIGKILLs one mid-run.  Acceptance: 100% of accepted
      requests resolve (zero drops), and post-kill achieved QPS
      (windowed from one probe interval after the kill) >= 2/3 of the
      pre-kill steady state."""
    from mxnet_tpu.serving.loadgen import (failover_protocol,
                                           frontdoor_protocol)

    if which == "http_overhead":
        r = frontdoor_protocol(smoke=smoke)
        h, ip = r["http"], r["inproc"]
        return {
            "metric": "serving.frontdoor.http_overhead",
            "value": h["qps_achieved"], "unit": "qps",
            "vs_baseline": None,
            "p50_ms": h["p50_ms"], "p99_ms": h["p99_ms"],
            "inproc_qps": ip["qps_achieved"],
            "inproc_p50_ms": ip["p50_ms"], "inproc_p99_ms": ip["p99_ms"],
            "http_p50_overhead_ms": r["http_p50_overhead_ms"],
            "http_p99_vs_inproc": r["http_p99_vs_inproc"],
            "http_qps_vs_inproc": r["http_qps_vs_inproc"],
            "closed_loop_qps": r["closed_loop_qps"],
            "http_closed_loop_qps": r["http_closed_loop_qps"],
            "offered_mult": r["offered_mult"],
            "n_requests": h["n"],
            "dropped": h["timeouts"] + h["errors"] + h["cancelled"],
            "inproc_dropped": ip["timeouts"] + ip["errors"] +
            ip["cancelled"],
            "seed": r["seed"],
            "note": ("one engine, one seeded schedule, two transports: "
                     "the p50/p99 delta is the HTTP front door's cost "
                     "(http.server + npz round-trip) below saturation "
                     "— achieved QPS tracks offered on both sides"),
        }
    r = failover_protocol(smoke=smoke)
    s = r["summary"]
    return {
        "metric": "serving.frontdoor.failover",
        "value": r.get("post_vs_pre_qps"), "unit": "ratio",
        "vs_baseline": None,
        "n_replicas": r["n_replicas"],
        "n_requests": s["n"], "resolved": r["resolved"],
        "dropped": r["dropped"], "shed": r["shed"],
        "pre_kill_qps": r.get("pre_kill_qps"),
        "post_kill_qps": r.get("post_kill_qps"),
        "recovery_ms": r.get("recovery_ms"),
        "probe_interval_s": r["probe_interval_s"],
        "kill_nth_dispatch": r["kill_nth_dispatch"],
        "failovers": r["failovers"], "retries": r["retries"],
        "live_after": r["live_after"],
        "p99_ms": s["p99_ms"],
        "seed": r["seed"],
        "note": ("one of %d shared-nothing replicas SIGKILLed by a "
                 "seeded die at the serve.dispatch seam under open-loop "
                 "load: every accepted request resolves (dropped=0 is "
                 "the zero-drop evidence), forwards fail over with "
                 "backoff onto survivors, and the balancer converges "
                 "within one probe interval (acceptance: post/pre QPS "
                 ">= 2/3)" % r["n_replicas"]),
    }


def bench_observability(chip, smoke=False):
    """Telemetry overhead row (serving/loadgen.py
    observability_protocol): the SAME engine+schedule served with
    telemetry fully ON (default trace sampling, metrics, flight ring,
    live JSONL export) vs fully OFF, plus the MXNET_TRACE_SAMPLE=0
    hatch.  The capacity ratio is the direct overhead evidence; the
    open-loop p99 ratio shows the tail cost under load."""
    from mxnet_tpu.serving.loadgen import observability_protocol

    r = observability_protocol(smoke=smoke)
    return {
        "metric": "serving.observability.overhead",
        "value": r["qps_full_vs_baseline"], "unit": "ratio",
        "vs_baseline": None,
        "baseline_closed_qps": r["baseline"]["closed_qps"],
        "full_closed_qps": r["full"]["closed_qps"],
        "sample0_closed_qps": r["sample0"]["closed_qps"],
        "baseline_p99_ms": r["baseline"]["p99_ms"],
        "full_p99_ms": r["full"]["p99_ms"],
        "sample0_p99_ms": r["sample0"]["p99_ms"],
        "p99_full_vs_baseline": r["p99_full_vs_baseline"],
        "qps_sample0_vs_baseline": r["qps_sample0_vs_baseline"],
        "p99_sample0_vs_baseline": r["p99_sample0_vs_baseline"],
        "traces_exported": r["traces_exported"],
        "dropped": r["full"]["dropped"],
        "n_requests": r["n_load"],
        "seed": r["seed"],
        "note": ("full tracing (sample=1.0, JSONL export) + metrics + "
                 "flight ring vs the untelemetered engine on one "
                 "seeded schedule; acceptance: capacity ratio >= 0.95, "
                 "p99 ratio <= 1.10, and MXNET_TRACE_SAMPLE=0 back "
                 "within noise (tests/test_observability.py pins the "
                 "banked figures)"),
    }


def bench_racecheck_overhead(chip, smoke=False):
    """Race-detector cost row (serving/loadgen.py
    racecheck_overhead_protocol): closed-loop capacity of the same
    forward engine with the happens-before detector off (the shipping
    default — structurally zero-cost, spy-pinned by
    tests/test_racecheck.py) vs armed at runtime.  The armed ratio is
    what the ``make racecheck`` CI stage pays; banking it keeps the
    claim measured rather than asserted."""
    from mxnet_tpu.serving.loadgen import racecheck_overhead_protocol

    r = racecheck_overhead_protocol(smoke=smoke)
    return {
        "metric": "serving.observability.racecheck_overhead",
        "value": r["qps_armed_vs_off"], "unit": "ratio",
        "vs_baseline": None,
        "off_closed_qps": r["off_closed_qps"],
        "armed_closed_qps": r["armed_closed_qps"],
        "n_requests": r["n_closed"],
        "seed": r["seed"],
        "note": ("MXNET_RACE_CHECK off vs armed on one engine; the OFF "
                 "side is the zero-cost contract (plain dict/"
                 "SimpleNamespace/Lock, unpatched stdlib — spy-pinned "
                 "by tests/test_racecheck.py), the armed ratio is the "
                 "CI-stage price (docs/architecture/"
                 "static_analysis.md)"),
    }


def bench_serving_control(which, chip, smoke=False):
    """Control-plane rows (serving/controller.py + replica_set.py, the
    protocols ``make chaos-smoke`` gates on):

    * ``autoscale_diurnal`` / ``autoscale_bursty`` — the SLO-driven
      AutoScaler walks a replica set up a seeded shaped swing and back
      down.  Acceptance: scaled up AND down, queue-wait p95 under the
      capacity-relative SLO, zero lost requests, and FEWER
      replica-seconds than static max-size provisioning (the banked
      ratio is the savings).
    * ``rolling_swap`` — one rolling ``swap_params`` under a concurrent
      submit stream: zero failed requests, every response bit-matches
      exactly one coherent weight set, every live replica +1 version.
    * ``chaos`` — the composed seeded multi-fault schedule (straggler
      pair + replica kill + injected-error pair at serve.dispatch)
      against HTTP front door -> autoscaled replicas -> engines: every
      gate must hold (faults fired, zero lost, SLO-bounded recovery,
      connected retry traces)."""
    from mxnet_tpu.serving.loadgen import (autoscale_protocol,
                                           chaos_protocol,
                                           rolling_swap_protocol)

    if which in ("autoscale_diurnal", "autoscale_bursty"):
        shape = which.split("_", 1)[1]
        r = autoscale_protocol(smoke=smoke, shape=shape)
        return {
            "metric": "serving.control.%s" % which,
            "value": r["replica_seconds_vs_static"], "unit": "ratio",
            "vs_baseline": None,
            "shape": r["shape"],
            "slo_ms": r["slo_ms"],
            "p95_ms": r["auto"]["qwait_p95_ms"],
            "p95_under_slo": r["p95_under_slo"],
            "scaled_up": r["scaled_up"], "scaled_down": r["scaled_down"],
            "actions": r["actions"],
            "n_peak_replicas": r["n_peak_replicas"],
            "max_replicas": r["max_replicas"],
            "replica_seconds": r["auto"]["replica_seconds"],
            "static_replica_seconds": r["static"]["replica_seconds"],
            "lost": r["auto"]["lost"],
            "shed": r["auto"].get("shed", 0),
            "n_requests": r["n_load"],
            "seed": r["seed"],
            "note": ("SLO-driven autoscaler over the seeded %s swing vs "
                     "static max-size provisioning on the same "
                     "schedule; the ratio < 1 is the replica-seconds "
                     "saving at a held p95" % shape),
        }
    if which == "rolling_swap":
        r = rolling_swap_protocol(smoke=smoke)
        return {
            "metric": "serving.control.rolling_swap",
            "value": r["n"], "unit": "requests",
            "vs_baseline": None,
            "n_requests": r["n"], "n_replicas": r["n_replicas"],
            "old": r["old"], "new": r["new"],
            "torn": r["neither"], "failed": r["failed"],
            "replicas_swapped": r["replicas_swapped"],
            "versions": {str(k): v for k, v in r["versions"].items()},
            "retries": r["retries"],
            "seed": r["seed"],
            "note": ("one rolling swap_params (drain -> swap -> "
                     "re-probe per replica) under a concurrent submit "
                     "stream: zero failures, every response bit-matches "
                     "old or new weights (torn=0), every replica +1 "
                     "version"),
        }
    r = chaos_protocol(smoke=smoke)
    return {
        "metric": "serving.control.chaos",
        "value": r["recovery_ms"], "unit": "ms",
        "vs_baseline": None,
        "gates": r["gates"],
        "lost": r["summary"]["lost"],
        "n_requests": r["summary"]["n"],
        "n_faults": len(r["faults_fired"]),
        "recovery_ms": r["recovery_ms"],
        "recovery_slo_ms": r["recovery_slo_ms"],
        "retries": r["retries"], "failovers": r["failovers"],
        "retried_traces_connected": r["retried_traces_connected"],
        "traces_exported": r["traces_exported"],
        "live_after": r["live_after"],
        "autoscale_actions": r["autoscale_actions"],
        "seed": r["seed"],
        "note": ("composed seeded faults (straggler pair + replica kill "
                 "+ injected-error pair at serve.dispatch) against the "
                 "full HTTP -> autoscaled-replicas -> engine stack: "
                 "every scheduled fault fired, zero lost requests, "
                 "first post-kill completion inside the recovery SLO, "
                 "and every retried request kept a connected trace"),
    }


# the generation protocol runs both sides (re-prefill baseline +
# continuous-batching engine) in one sweep; cache it so the two
# serving.decode.* rows don't pay it twice
_GEN_PROTOCOL_CACHE = {}

# same for the paged-KV protocol's six sides / three banked rows
_PAGED_PROTOCOL_CACHE = {}

# and the speculative-decoding protocol's six sides / three banked rows
_SPEC_PROTOCOL_CACHE = {}


def bench_serving_decode_paged(which, chip, smoke=False):
    """Paged-KV decode rows: block-table attention + copy-on-write
    prefix sharing + chunked prefill vs the contiguous plane, same
    weights, same seeded open-loop schedules (serving/loadgen.py
    paged_generation_protocol).  CPU-deterministic.  Acceptance:
    ``flat`` >= 0.9x contiguous tokens/sec on a prefix-free schedule;
    ``prefix`` serves the contiguous side's peak concurrency out of a
    pool capped at HALF its KV bytes with zero pool sheds (>= 2x
    concurrent sequences per byte) while skipping most prefill chunks
    via prefix hits; ``chunked`` cuts co-running streams' p99 ITL vs
    whole-prompt prefill (ratio < 1)."""
    from mxnet_tpu.serving.loadgen import paged_generation_protocol

    r = _PAGED_PROTOCOL_CACHE.get(bool(smoke))
    if r is None:
        r = paged_generation_protocol(smoke=smoke)
        _PAGED_PROTOCOL_CACHE[bool(smoke)] = r
    side = {"flat": r["flat_paged"], "prefix": r["prefix_paged"],
            "chunked": r["mixed_chunked"]}[which]
    row = {"metric": "serving.decode.paged.%s" % which,
           "value": side["tokens_per_sec"], "unit": "tokens/sec",
           "vs_baseline": None,
           "ttft_p50_ms": side["ttft_p50_ms"],
           "ttft_p99_ms": side["ttft_p99_ms"],
           "itl_mean_ms": side["itl_mean_ms"],
           "itl_p99_ms": side["itl_p99_ms"],
           "qps_achieved": side["qps_achieved"],
           "n_requests": side["n"],
           "tokens": side["tokens"],
           "dropped": side["timeouts"] + side["errors"] +
           side["cancelled"],
           "offered_mult": r["offered_mult"],
           "kv_block": r["kv_block"],
           "counters": side.get("counters"),
           "seed": r["seed"]}
    cs = side.get("store", {}).get("cache_state") or {}
    row.update({"pool_blocks": cs.get("pool_blocks"),
                "pool_blocks_hwm": cs.get("pool_blocks_hwm"),
                "prefill_chunk": cs.get("prefill_chunk")})
    if which == "flat":
        row.update({
            "kv_max": r["kv_max_flat"],
            "tokens_per_sec_vs_contiguous":
                r["tokens_per_sec_vs_contiguous"],
            "contig_tokens_per_sec":
                r["flat_contig"]["tokens_per_sec"],
            "note": ("prefix-FREE schedule at matched geometry: the "
                     "paged plane's block-table gather + per-tick "
                     "chunk scheduling costs <= 10% tokens/sec vs "
                     "the contiguous plane (acceptance >= 0.9x)"),
        })
    elif which == "prefix":
        row.update({
            "kv_max": r["kv_max_long"],
            "seqs_per_kv_byte_vs_contiguous":
                r["seqs_per_kv_byte_vs_contiguous"],
            "paged_pool_bytes": r["paged_pool_bytes"],
            "contig_cache_bytes": r["contig_cache_bytes"],
            "contig_bytes_per_slot": r["contig_bytes_per_slot"],
            "paged_bytes_per_active_seq":
                r["paged_bytes_per_active_seq"],
            "paged_max_active": r["paged_max_active"],
            "contig_max_active": r["contig_max_active"],
            "prefill_chunk_savings": r["prefill_chunk_savings"],
            "prefill_chunks_dispatched":
                r["prefill_chunks_dispatched"],
            "prefill_chunks_cold": r["prefill_chunks_cold"],
            "contig_tokens_per_sec":
                r["prefix_contig"]["tokens_per_sec"],
            "note": ("every prompt = shared 96-token system prefix + "
                     "unique suffix; the paged pool is CAPPED at half "
                     "the contiguous side's banked cache bytes and "
                     "still serves the same peak concurrency with "
                     "zero pool sheds (>= 2x concurrent sequences "
                     "per KV byte), with prefix hits skipping the "
                     "shared blocks' prefill chunks (savings = 1 - "
                     "dispatched/cold)"),
        })
    else:
        row.update({
            "kv_max": r["kv_max_long"],
            "itl_p99_chunked_vs_unchunked":
                r["itl_p99_chunked_vs_unchunked"],
            "unchunked_itl_p99_ms":
                r["mixed_unchunked"]["itl_p99_ms"],
            "unchunked_tokens_per_sec":
                r["mixed_unchunked"]["tokens_per_sec"],
            "note": ("every 8th request is a unique 98-token prompt: "
                     "chunked prefill (16-token chunks interleaved "
                     "with decode steps) vs one whole-prompt dispatch "
                     "— co-running streams' p99 inter-token latency "
                     "(acceptance: ratio < 1)"),
        })
    return row


def bench_serving_decode_spec(which, chip, smoke=False):
    """Speculative-decoding + int8-KV decode rows: a draft model
    proposes K tokens per tick, the target verifies them in ONE
    in-graph call (serving/loadgen.py spec_generation_protocol), same
    weights, same seeded open-loop schedule as the non-speculative
    denominator.  CPU-deterministic.  Acceptance: ``greedy`` and
    ``sampled`` run <= 0.6x target steps per emitted token with the
    draft-friendly draft; the protocol's adversarial side (banked on
    every row) holds >= 0.95x base tokens/sec when acceptance
    collapses (the MXNET_SERVE_SPEC=auto fallback); ``int8`` pins the
    quantised KV pool at <= 0.3x fp32 pool bytes per token."""
    from mxnet_tpu.serving.loadgen import spec_generation_protocol

    r = _SPEC_PROTOCOL_CACHE.get(bool(smoke))
    if r is None:
        r = spec_generation_protocol(smoke=smoke)
        _SPEC_PROTOCOL_CACHE[bool(smoke)] = r
    side = {"greedy": r["spec_greedy"], "sampled": r["spec_sampled"],
            "int8": r["paged_int8"]}[which]
    base = r["base_sampled"] if which == "sampled" else r["base"]
    metric = ("serving.decode.paged_int8" if which == "int8"
              else "serving.decode.spec.%s" % which)
    row = {"metric": metric,
           "value": side["tokens_per_sec"], "unit": "tokens/sec",
           "vs_baseline": None,
           "ttft_p50_ms": side["ttft_p50_ms"],
           "ttft_p99_ms": side["ttft_p99_ms"],
           "itl_mean_ms": side["itl_mean_ms"],
           "itl_p99_ms": side["itl_p99_ms"],
           "qps_achieved": side["qps_achieved"],
           "n_requests": side["n"],
           "tokens": side["tokens"],
           "dropped": side["timeouts"] + side["errors"] +
           side["cancelled"],
           "offered_mult": r["offered_mult"],
           "kv_block": r["kv_block"],
           "kv_max": r["kv_max"],
           "counters": side.get("counters"),
           "base_tokens_per_sec": base["tokens_per_sec"],
           "base_steps_per_token": base["steps_per_token"],
           "seed": r["seed"]}
    if which in ("greedy", "sampled"):
        adv = r["spec_adversarial"]
        row.update({
            "spec_k": r["spec_k"],
            "steps_per_token": side["steps_per_token"],
            "steps_per_token_vs_base":
                r["steps_per_token_vs_base_%s" % which],
            "tokens_per_sec_vs_base":
                r["tokens_per_sec_vs_base_%s" % which],
            "acceptance_rate": side["acceptance_rate"],
            "adversarial_tokens_per_sec_vs_base":
                r["tokens_per_sec_vs_base_adversarial"],
            "adversarial_acceptance_rate": adv["acceptance_rate"],
            "adversarial_fallback_steps":
                adv["counters"]["spec_fallback_steps"],
            "draft_pool_bytes":
                side.get("model", {}).get("draft_pool_bytes"),
            "note": ("draft-friendly draft (target weights + 3%% "
                     "relative noise) proposing K=%d per tick, "
                     "verified by ONE target call: target steps per "
                     "emitted token <= 0.6x the non-speculative side "
                     "on the same seeded schedule (%s decoding); the "
                     "adversarial side (independent random draft, "
                     "acceptance collapses) banks the "
                     "MXNET_SERVE_SPEC=auto graceful-degradation "
                     "acceptance >= 0.95x base tokens/sec"
                     % (r["spec_k"],
                        "greedy" if which == "greedy"
                        else "seeded top-k sampling")),
        })
    else:
        cs = side.get("cache_state", {})
        fp_cs = base.get("cache_state", {})
        row.update({
            "kv_dtype": cs.get("cache_dtype"),
            "pool_bytes": cs.get("pool_bytes"),
            "pool_bytes_used": cs.get("pool_bytes_used"),
            "pool_bytes_per_token": cs.get("pool_bytes_per_token"),
            "fp32_pool_bytes_per_token":
                fp_cs.get("pool_bytes_per_token"),
            "pool_bytes_per_token_vs_fp32":
                r["pool_bytes_per_token_vs_fp32"],
            "tokens_per_sec_vs_fp32":
                r["tokens_per_sec_vs_base_int8"],
            "note": ("int8 paged KV pool (per-(block, head) scale "
                     "pools beside the code pool, dequant inside the "
                     "attention kernel): <= 0.3x fp32 pool bytes per "
                     "token from stats()['cache_state'] at matched "
                     "tokens/sec on the same seeded schedule"),
        })
    return row


def bench_serving_decode(which, chip, smoke=False):
    """Decode-plane tokens/sec + TTFT + inter-token latency: the
    continuous-batching generation engine (serving/decode_engine.py —
    prefill/decode split over the donated KV cache) vs the naive
    re-prefill-per-token deployment, both generating greedily from the
    SAME weights under the SAME seeded open-loop schedule
    (serving/loadgen.py generation_protocol).  CPU-deterministic: the
    batching economics (one decode step advances every in-flight
    sequence) reproduce without an accelerator.  Acceptance:
    continuous >= 2x the re-prefill baseline's tokens/sec at no worse
    p99 TTFT, zero drops (``make decode-smoke`` pins it per change)."""
    from mxnet_tpu.serving.loadgen import generation_protocol

    r = _GEN_PROTOCOL_CACHE.get(bool(smoke))
    if r is None:
        r = generation_protocol(smoke=smoke)
        _GEN_PROTOCOL_CACHE[bool(smoke)] = r
    side = r["reprefill_open"] if which == "reprefill" else \
        r["batch"] if which == "continuous" else r[which]
    row = {"metric": "serving.decode.%s" % which,
           "value": side["tokens_per_sec"], "unit": "tokens/sec",
           "vs_baseline": None,
           "ttft_p50_ms": side["ttft_p50_ms"],
           "ttft_p99_ms": side["ttft_p99_ms"],
           "itl_mean_ms": side["itl_mean_ms"],
           "itl_p99_ms": side["itl_p99_ms"],
           "qps_achieved": side["qps_achieved"],
           "n_requests": side["n"],
           "tokens": side["tokens"],
           "dropped": side["timeouts"] + side["errors"] +
           side["cancelled"],
           "offered_mult": r["offered_mult"],
           "kv_block": r["kv_block"],
           "kv_max": r["kv_max"],
           "seed": r["seed"]}
    eng = side.get("engine", {})
    if which != "reprefill":
        # fetch-footprint evidence: elements the engine pulled to host
        # per decode step (tokens under in-graph sampling; the host
        # hatch pulls the whole (slots, vocab) logits matrix)
        steps = eng.get("decode_steps") or 0
        row["decode_fetch_elems_per_step"] = (
            round(eng.get("decode_fetch_elems", 0) / steps, 1)
            if steps else None)
        row["sample_mode"] = side.get("store", {}).get("sample_mode")
    if which == "continuous":
        row.update({
            "tokens_per_sec_vs_reprefill":
                r["tokens_per_sec_vs_reprefill"],
            "ttft_p99_vs_reprefill": r["ttft_p99_vs_reprefill"],
            "itl_mean_vs_host_sample": r["itl_mean_vs_host_sample"],
            "host_sample_itl_mean_ms":
                r["host_sample"]["itl_mean_ms"],
            "decode_steps": eng.get("decode_steps"),
            "generated_tokens": eng.get("generated_tokens"),
            "max_active": eng.get("max_active"),
            "cache_grows": eng.get("cache_grows"),
            "note": ("one compiled decode step advances every in-flight "
                     "sequence against the donated KV cache, sampling "
                     "in-graph (the per-step host transfer is the "
                     "(slots,) token vector); the baseline re-pays a "
                     "full prefill per token (acceptance: >= 2x "
                     "tokens/sec at no worse p99 TTFT, zero drops, ITL "
                     "no worse than the host-sampling hatch)"),
        })
    elif which in ("bf16", "int8"):
        st = side.get("store", {})
        fp_st = r["batch"].get("store", {})
        hwm = eng.get("cache_hwm", {}).get("m", {})
        fp_hwm = r["batch"].get("engine", {}).get(
            "cache_hwm", {}).get("m", {})
        row.update({
            "compute_dtype": st.get("compute_dtype"),
            "kv_dtype": st.get("kv_dtype"),
            "weight_bytes": st.get("weight_bytes", {}).get("total"),
            "fp32_weight_bytes":
                fp_st.get("weight_bytes", {}).get("total"),
            "cache_bytes_per_slot": hwm.get("cache_bytes_per_slot"),
            "fp32_cache_bytes_per_slot":
                fp_hwm.get("cache_bytes_per_slot"),
            "tokens_per_sec_vs_fp32": (
                round(side["tokens_per_sec"] /
                      r["batch"]["tokens_per_sec"], 3)
                if r["batch"]["tokens_per_sec"] else None),
        })
        if which == "bf16":
            row["note"] = ("bf16 weights AND bf16 KV cache: cache "
                           "bytes per slot halved vs the fp32 row "
                           "(cache_bytes_per_slot vs fp32_cache_"
                           "bytes_per_slot), so the same cache budget "
                           "holds 2x the concurrent sequences; decode "
                           "parity pinned at relaxed tol")
        else:
            row["note"] = ("int8 weight-only decode: matmul weights "
                           "travel as (codes, scales) program "
                           "arguments through the fused dequant-"
                           "matmul door — ~4x less resident weight "
                           "memory (weight_bytes vs fp32_weight_"
                           "bytes); >= 99% greedy top-1 agreement "
                           "pinned by tests/test_quant_serving.py")
    return row


def bench_input_staging(chip, smoke=False):
    """Overlapped device input staging through the real ``Module.fit``
    loop: steps/sec with the DeviceStager on vs ``MXNET_IO_STAGE=0``,
    under an injected per-batch host latency (the faultinject-delay
    pattern standing in for slow decode/augmentation).  The injected
    delay is calibrated to ~the measured per-step compute, the regime
    where double buffering pays the most (ideal speedup 2x; the CI gate
    in tests/test_input_staging.py asserts >= 1.5x).  CPU-deterministic:
    the overlap needs no accelerator to reproduce."""
    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import DelayedIter, smoke_mlp

    batches, batch, feat = (8, 32, 64) if smoke else (14, 64, 256)
    warmup = 2
    sym = smoke_mlp(num_hidden=feat)
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (batch * batches, feat)).astype("float32")
    y = rs.randint(0, 10, (batch * batches,)).astype("float32")

    def fit_sps(stage, delay):
        """Steps/sec of the drain-bounded steady-state window (same
        protocol as bench_fit)."""
        with _managed_env({"MXNET_IO_STAGE": stage}):
            mx.random.seed(0)
            it = mx.io.NDArrayIter(X, y, batch_size=batch)
            if delay > 0:
                it = DelayedIter(it, delay)
            mod = mx.Module(sym, context=mx.current_context())
            seen, t0, t1 = [0], [None], [None]

            def cb(param):
                seen[0] += 1
                if seen[0] in (warmup, batches):
                    mx.nd.waitall()
                    _fetch_sync(mod.get_outputs()[0])
                    (t0 if seen[0] == warmup else t1)[0] = \
                        time.perf_counter()

            mod.fit(it, num_epoch=1, eval_metric="accuracy",
                    optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1},
                    batch_end_callback=cb)
            assert None not in (t0[0], t1[0])
            return (batches - warmup) / (t1[0] - t0[0])

    # calibrate the injected latency to the measured per-step compute
    compute_s = 1.0 / fit_sps("0", 0.0)
    delay = min(max(compute_s, 0.01), 0.2)
    blocking = fit_sps("0", delay)
    staged = fit_sps("1", delay)
    return {"metric": "io.input_staging",
            "value": round(staged, 2), "unit": "steps/sec",
            "vs_baseline": None,
            "blocking_steps_per_sec": round(blocking, 2),
            "speedup_vs_blocking": round(staged / blocking, 3)
            if blocking else None,
            "injected_host_latency_ms": round(delay * 1e3, 1),
            "per_step_compute_ms": round(compute_s * 1e3, 1),
            "batch_size": batch}


def _sharded_bench_rec(tmp, n, size):
    """Seeded synthetic recordio + idx sidecar (pixel/label = record id)."""
    from mxnet_tpu.io import recordio
    from mxnet_tpu.io.image_util import encode_image
    rec = os.path.join(tmp, "bench.rec")
    idx = os.path.join(tmp, "bench.idx")
    rs = np.random.RandomState(0)
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(n):
        img = rs.randint(0, 255, (size, size, 3)).astype(np.uint8)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0),
            encode_image(img, quality=90)))
    w.close()
    return rec, idx


def bench_sharded_stream(mode, chip, smoke=False):
    """Checkpointable sharded streaming pipeline rows
    (docs/architecture/data_pipeline.md), CPU-deterministic: seeded
    synthetic recordio + an injected per-record decode latency (the
    faultinject-delay pattern standing in for heavy JPEG/augment work).

    * ``throughput``: images/sec of the seeded sharded+shuffled pipeline
      (4 decode threads behind the double-buffered batch queue) vs the
      same records decoded serially — the parser-pool overlap.
    * ``resume_overhead``: wall time to resume mid-epoch (fresh iterator
      + ``load_state`` + first batch out) vs one epoch's wall time; the
      production gate is <5% of an epoch (tests pin the banked row)."""
    import shutil
    import tempfile
    import mxnet_tpu as mx

    # the injected latency dominates decode (sleeps release the GIL, so
    # the overlap measurement is stable even on a 2-core host where the
    # numpy half of decode serializes); the resume-mode epoch is sized
    # to ~2s of wall so the resume cost (iterator construction +
    # load_state + first batch, tens of ms) sits well under the 5%
    # acceptance gate even on a loaded CI host
    if smoke:
        n, size, batch = 96, 16, 8
    else:
        n, size, batch = (512, 20, 16) if mode == "throughput" \
            else (1536, 20, 16)
    delay_s = 0.004 if mode == "throughput" and not smoke else 0.002
    shape = (3, size, size)
    tmp = tempfile.mkdtemp(prefix="mxt-bench-data-")
    try:
        rec, idx = _sharded_bench_rec(tmp, n, size)

        class _DelayedRecordIter(mx.io.ImageRecordIter):
            """Injected per-record decode latency (subclass override so
            the pipeline's bound decode carries the delay from record
            zero — no mid-flight swap)."""

            def _decode_one(self, s, meta):
                time.sleep(delay_s)
                return super()._decode_one(s, meta)

        def make_iter(threads=4):
            return _DelayedRecordIter(
                path_imgrec=rec, path_imgidx=idx, data_shape=shape,
                batch_size=batch, shuffle=True, preprocess_threads=threads,
                seed=11)

        def drain_epoch(it):
            t0 = time.perf_counter()
            imgs = 0
            for b in it:
                imgs += b.data[0].shape[0] - (b.pad or 0)
            return time.perf_counter() - t0, imgs

        if mode == "throughput":
            from mxnet_tpu.data import ShardedRecordDataset
            from mxnet_tpu.io import recordio as rio
            from mxnet_tpu.io.image_util import decode_record_image
            ds = ShardedRecordDataset(rec, idx, shuffle=True, seed=11)
            t0 = time.perf_counter()
            serial = 0
            while True:
                item = ds.read()
                if item is None:
                    break
                header, img_bytes = rio.unpack(item[0])
                time.sleep(delay_s)
                decode_record_image(img_bytes, shape)
                serial += 1
            t_serial = time.perf_counter() - t0
            ds.close()
            it = make_iter(4)
            t_pipe, imgs = drain_epoch(it)
            it.close()
            assert imgs == serial == n
            return {"metric": "io.sharded_stream.throughput",
                    "value": round(imgs / t_pipe, 1),
                    "unit": "images/sec", "vs_baseline": None,
                    "serial_images_per_sec": round(serial / t_serial, 1),
                    "speedup_vs_serial": round(t_serial / t_pipe, 3),
                    "records": n, "batch_size": batch,
                    "decode_threads": 4,
                    "injected_decode_latency_ms": delay_s * 1e3,
                    "note": "seeded shuffle + sharding-capable plan; the "
                            "same chain is checkpointable mid-epoch "
                            "(state_dict/load_state)"}

        # resume_overhead: epoch wall vs (fresh iterator + load_state +
        # first batch)
        it = make_iter(4)
        t_epoch, imgs = drain_epoch(it)
        it.close()
        part = make_iter(4)
        for _ in range(max(1, (n // batch) // 2)):
            next(part)
        state = part.state_dict()
        part.close()
        t0 = time.perf_counter()
        fresh = make_iter(4)
        fresh.load_state(state)
        next(fresh)
        t_resume = time.perf_counter() - t0
        fresh.close()
        ratio = t_resume / t_epoch
        return {"metric": "io.sharded_stream.resume_overhead",
                "value": round(t_resume * 1e3, 2), "unit": "ms",
                "vs_baseline": None,
                "epoch_ms": round(t_epoch * 1e3, 1),
                "overhead_vs_epoch": round(ratio, 4),
                "acceptance": "resume overhead < 5% of one epoch",
                "passes": bool(ratio < 0.05),
                "records": n, "batch_size": batch}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _spmd_exec_group_rate(n_ctx, spmd, steps, warmup, batch_per_dev=16,
                          feat=64):
    """Steps/sec of multi-device ``Module`` training driven through the
    executor-group frontend on the smoke MLP: ``spmd=True`` routes the
    ONE sharded step program (parallel/spmd.py — XLA all-reduce inside
    the step, in-graph optimizer update, device-resident params),
    ``spmd=False`` pins the classic path (per-device executor
    replication + host gradient aggregation + host ``Updater`` round
    trip) via the MXNET_SPMD=0 escape hatch.  Same module protocol,
    same contexts, same batch — only the dispatch plane differs."""
    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import fetch_sync, smoke_mlp

    managed = {"MXNET_MODULE_FUSED": "0",
               "MXNET_SPMD": "1" if spmd else "0"}
    saved = {k: os.environ.pop(k, None) for k in managed}
    os.environ.update(managed)
    try:
        batch = batch_per_dev * n_ctx
        sym = smoke_mlp(num_hidden=feat)
        rs = np.random.RandomState(0)
        X = rs.uniform(-1, 1, (batch, feat)).astype("float32")
        y = rs.randint(0, 10, (batch,)).astype("float32")
        it = mx.io.NDArrayIter(X, y, batch_size=batch)
        mx.random.seed(0)
        mod = mx.Module(sym, context=[mx.cpu(i) for i in range(n_ctx)])
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params()
        mod.init_optimizer(kvstore="device", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        assert mod._exec_group.spmd_active == spmd
        b0 = next(iter(it))

        def sync():
            # force the whole in-flight chain: the last step's outputs
            # depend on its forward, whose params depend on every prior
            # update (per-exec form works on both dispatch planes)
            fetch_sync(mod.get_outputs(merge_multi_context=False)[0][0])

        for _ in range(warmup):
            mod.forward_backward(b0)
            mod.update()
        sync()
        tic = time.perf_counter()
        for _ in range(steps):
            mod.forward_backward(b0)
            mod.update()
        sync()
        return steps / (time.perf_counter() - tic)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _spmd_trainer_rate(mesh_axes, rules, steps, warmup, batch=64, feat=64):
    """Steps/sec of the fused-trainer frontend over an arbitrary mesh
    (the dp×mp row the Module frontend cannot express)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import (DataParallelTrainer, MeshTrainer,
                                    make_mesh)
    from mxnet_tpu.test_utils import fetch_sync, smoke_mlp

    n = 1
    for v in mesh_axes.values():
        n *= v
    mesh = make_mesh(dict(mesh_axes), jax.devices()[:n])
    sym = smoke_mlp(num_hidden=feat)
    kw = dict(optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    if rules is not None:
        tr = MeshTrainer(sym, {"data": (batch, feat)},
                         {"softmax_label": (batch,)}, mesh=mesh,
                         rules=rules, **kw)
    else:
        tr = DataParallelTrainer(sym, {"data": (batch, feat)},
                                 {"softmax_label": (batch,)}, mesh=mesh,
                                 **kw)
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (batch, feat)).astype("float32")
    y = rs.randint(0, 10, (batch,)).astype("float32")
    for _ in range(warmup):
        out = tr.step(X, y)
    fetch_sync(out[0])
    tic = time.perf_counter()
    for _ in range(steps):
        out = tr.step(X, y)
    fetch_sync(out[0])
    return steps / (time.perf_counter() - tic)


def bench_spmd_step(config, chip, smoke=False):
    """One-SPMD-step-program rows: the sharded fused step over a global
    mesh vs the classic ``DataParallelExecutorGroup`` replication path,
    same smoke-MLP ``Module`` protocol (``config`` = dp2/dp4/dp8), plus
    the dp2xmp2 mesh through the fused-trainer frontend (model-parallel
    rules the Module frontend cannot express).  CPU-deterministic under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the win is
    deleting the per-device Python dispatch loop + host updater round
    trip, which needs no accelerator to reproduce."""
    import jax
    from mxnet_tpu.parallel import ShardingRules
    from jax.sharding import PartitionSpec as P

    steps, warmup = (10, 2) if smoke else (40, 5)
    need = {"dp2": 2, "dp4": 4, "dp8": 8, "dp2xmp2": 4}[config]
    if jax.device_count() < need:
        return {"metric": "spmd.step.%s" % config, "value": 0.0,
                "unit": "skipped", "vs_baseline": None,
                "reason": "%d devices visible, %d needed (run under "
                          "XLA_FLAGS=--xla_force_host_platform_device_"
                          "count=8 on CPU)" % (jax.device_count(), need)}
    if config == "dp2xmp2":
        rules = ShardingRules([
            (r"fc1_weight", P("tp", None)), (r"fc1_bias", P("tp")),
            (r"fc2_weight", P(None, "tp")),
        ])
        sharded = _spmd_trainer_rate({"dp": 2, "tp": 2}, rules, steps,
                                     warmup)
        classic = _spmd_exec_group_rate(4, False, steps, warmup)
        note = ("dp2×mp2 mesh through the fused-trainer frontend "
                "(megatron-style tp rules); classic reference is the "
                "4-device replication path at the same global batch")
    else:
        sharded = _spmd_exec_group_rate(need, True, steps, warmup)
        classic = _spmd_exec_group_rate(need, False, steps, warmup)
        note = ("identical Module/executor-group protocol; only the "
                "dispatch plane differs (one sharded program vs "
                "per-device replication + host updater)")
    return {"metric": "spmd.step.%s" % config,
            "value": round(sharded, 2), "unit": "steps/sec",
            "vs_baseline": None,
            "classic_steps_per_sec": round(classic, 2),
            "speedup_vs_classic": round(sharded / classic, 3)
            if classic else None,
            "n_devices": need, "batch_per_device": 16,
            "steps": steps, "note": note}


def _transformer_shapes(chip, smoke):
    """(batch, seq_len, layers, hidden, heads, vocab, iters, warmup).
    Off-TPU the Pallas path runs in interpret mode — a correctness
    vehicle, so shapes stay tiny; on chip the row uses MXU-feeding
    dims."""
    if chip["platform"] == "tpu" and not smoke:
        return (16 * chip["n_devices"], 256, 4, 512, 8, 8192, 20, 3)
    return (8, 32, 2, 64, 4, 256, 6, 2)


_TRANSFORMER_CACHE = {}


def _transformer_fit_rate(mode, chip, smoke):
    """samples/sec of the transformer LM through the real Module.fit
    loop (drain-bounded clock, bench_fit protocol), with the Pallas
    kernel plane on ('pallas': compiled Mosaic on TPU, forced interpret
    mode elsewhere) or off ('xla': MXNET_PALLAS=0, the plain lowering).
    Returns (sps, kernels_routed, cost) and caches per (mode, shapes)."""
    import mxnet_tpu as mx
    from mxnet_tpu.pallas_ops import dispatch

    shapes = _transformer_shapes(chip, smoke)
    ck = (mode, shapes)
    if ck in _TRANSFORMER_CACHE:
        return _TRANSFORMER_CACHE[ck]
    batch, seq_len, layers, hidden, heads, vocab, iters, warmup = shapes
    if mode == "pallas":
        pallas = "1" if chip["platform"] == "tpu" else "2"
    else:
        pallas = "0"
    # remat knobs cleared too: the banked transformer headline measures
    # the kernel plane alone, never an ambient remat setting
    with _managed_env({"MXNET_PALLAS": pallas}, clear=_REMAT_VARS):
        sym = mx.models.transformer_lm(
            seq_len=seq_len, num_layers=layers, num_hidden=hidden,
            num_heads=heads, vocab_size=vocab)
        rs = np.random.RandomState(0)
        n = batch * (warmup + iters)
        X = rs.randint(0, vocab, (n, seq_len)).astype("float32")
        y = np.roll(X, -1, axis=1)
        mx.random.seed(0)
        it = mx.io.NDArrayIter(X, y, batch_size=batch)
        devs = [mx.tpu(i) for i in range(chip["n_devices"])] \
            if chip["platform"] == "tpu" else [mx.current_context()]
        mod = mx.Module(sym, context=devs)
        seen, t0, t1 = [0], [None], [None]

        def cb(param):
            seen[0] += 1
            if seen[0] in (warmup, warmup + iters):
                mx.nd.waitall()
                _fetch_sync(mod.get_outputs()[0])
                (t0 if seen[0] == warmup else t1)[0] = time.perf_counter()

        dispatch.reset_dispatch_stats()
        mod.fit(it, num_epoch=1,
                eval_metric=mx.metric.Perplexity(ignore_label=None),
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05,
                                  "momentum": 0.9},
                initializer=mx.initializer.Xavier(),
                kvstore="device", batch_end_callback=cb)
        routed = dispatch.dispatch_stats()
        assert seen[0] == warmup + iters and None not in (t0[0], t1[0])
        sps = batch * iters / (t1[0] - t0[0])
        cost = None
        trainer = mod._one_program_trainer()
        if trainer is not None:
            it.reset()
            b0 = next(iter(it))
            cost = trainer.step_cost_analysis(b0.data[0], b0.label[0])
        _TRANSFORMER_CACHE[ck] = (sps, routed, cost)
        return _TRANSFORMER_CACHE[ck]


def bench_transformer_train(mode, chip, smoke=False):
    """Transformer-LM train rows: the MFU headline workload next to
    ResNet (ROADMAP item 2).  'pallas' runs flash attention + the fused
    RMSNorm/LayerNorm/SoftmaxOutput kernels end-to-end through
    Module.fit (the banked ``kernels_routed`` counters are the proof);
    'xla' is the same protocol with MXNET_PALLAS=0.  Off-TPU the kernel
    path runs in Pallas INTERPRET mode — a correctness/protocol row
    whose throughput is expected to trail XLA; on chip the compiled
    Mosaic kernels compete for real and the row carries the
    measured-FLOPs MFU proxy the next TPU run is judged against."""
    batch, seq_len, layers, hidden, heads, vocab, iters, warmup = \
        _transformer_shapes(chip, smoke)
    sps, routed, cost = _transformer_fit_rate(mode, chip, smoke)
    row = {"metric": "transformer.train.%s" % mode,
           "value": round(sps, 2), "unit": "samples/sec",
           "vs_baseline": None,
           "tokens_per_sec": round(sps * seq_len, 1),
           "batch_size": batch, "seq_len": seq_len,
           "num_layers": layers, "hidden": hidden, "heads": heads,
           "vocab": vocab,
           "kernels_routed": routed}
    row.update(_cost_columns(cost, sps / batch, chip))
    if mode == "pallas":
        x_sps, _, _ = _transformer_fit_rate("xla", chip, smoke)
        row["xla_samples_per_sec"] = round(x_sps, 2)
        row["speedup_vs_xla"] = round(sps / x_sps, 3) if x_sps else None
        if chip["platform"] != "tpu":
            row["note"] = ("off-TPU the kernels run in Pallas interpret "
                           "mode (correctness vehicle, slower than XLA "
                           "by design); the compiled-Mosaic comparison "
                           "needs the chip")
    return row


def bench_remat_batch_scaling(chip, smoke=False):
    """Remat batch scaling: MXNET_REMAT_POLICY on the classic Executor
    (bf16 compute, the PR 4 recipe) shrinks the residual stash the
    split train forward keeps alive for backward — measured via
    ``compiled.memory_analysis()`` on the SAME bound shapes, at pinned
    loss parity over real update steps.  The residual stash scales
    ~linearly with batch, so its reduction ratio is the batch headroom
    the policy buys at fixed activation HBM."""
    import mxnet_tpu as mx

    _, seq_len, layers, hidden, heads, vocab, _, _ = \
        _transformer_shapes(chip, smoke)
    seq_len = max(seq_len, 32)
    batches = (8, 16) if (smoke or chip["platform"] != "tpu") else (32, 64)
    policies = ("nothing_saveable", "dots_with_no_batch_dims_saveable")
    sym = mx.models.transformer_lm(
        seq_len=seq_len, num_layers=layers, num_hidden=hidden,
        num_heads=heads, vocab_size=vocab)

    def bind(policy, batch):
        # policy=None is the remat-OFF baseline: BOTH remat knobs must
        # be absent during bind (remat config is captured there), or an
        # ambient MXNET_REMAT_POLICY in the measuring shell would remat
        # the baseline too and collapse the banked reduction toward 1x
        managed = {} if policy is None else {"MXNET_REMAT_POLICY": policy}
        with _managed_env(managed, clear=_REMAT_VARS):
            ex = sym.simple_bind(mx.current_context(),
                                 data=(batch, seq_len),
                                 softmax_label=(batch, seq_len),
                                 compute_dtype="bfloat16",
                                 keep_dtype=("softmax_label",))
        rs = np.random.RandomState(7)
        for name, arr in ex.arg_dict.items():
            if name not in ("data", "softmax_label"):
                arr[:] = mx.nd.array(rs.uniform(-0.1, 0.1, arr.shape)
                                     .astype("float32"))
        return ex

    def losses(ex, batch, steps=3, lr=0.1):
        """Mean NLL per step over `steps` real SGD updates."""
        rs = np.random.RandomState(11)
        out = []
        for _ in range(steps):
            d = rs.randint(0, vocab, (batch, seq_len)).astype("float32")
            lbl = np.roll(d, -1, axis=1)
            ex.forward(is_train=True, data=mx.nd.array(d),
                       softmax_label=mx.nd.array(lbl))
            probs = ex.outputs[0].asnumpy()
            flat = lbl.reshape(-1).astype(int)
            nll = -np.log(np.maximum(
                probs[np.arange(flat.size), flat], 1e-9)).mean()
            out.append(float(nll))
            ex.backward()
            for name, g in ex.grad_dict.items():
                if name not in ("data", "softmax_label"):
                    ex.arg_dict[name][:] = \
                        ex.arg_dict[name] - lr * g
        return out

    # the remat-off baseline is policy-independent: bind/cost/train it
    # once per batch, not once per (policy, batch) — on TPU shapes that
    # is several multi-second XLA compiles saved per bench run
    base = {}
    for batch in batches:
        ex_off = bind(None, batch)
        base[batch] = (ex_off.program_cost("fwd_res"),
                       losses(ex_off, batch))
    sweep = []
    for policy in policies:
        for batch in batches:
            c_off, l_off = base[batch]
            ex_on = bind(policy, batch)
            c_on = ex_on.program_cost("fwd_res")
            l_on = losses(ex_on, batch)
            diff = max(abs(a - b) for a, b in zip(l_off, l_on))
            sweep.append({
                "policy": policy, "batch": batch,
                "residual_bytes_off": c_off["output_bytes"],
                "residual_bytes_on": c_on["output_bytes"],
                "residual_reduction":
                    round(c_off["output_bytes"] / c_on["output_bytes"],
                          3),
                "loss_max_abs_diff": round(diff, 6),
                "loss_per_step_off": [round(x, 5) for x in l_off],
            })
    best = max(sweep, key=lambda c: c["residual_reduction"])
    return {"metric": "transformer.remat_batch_scaling",
            "value": best["residual_reduction"],
            "unit": "x residual memory", "vs_baseline": None,
            "best_policy": best["policy"],
            "batch_headroom_note":
                "the residual stash scales ~linearly with batch: a %.2fx "
                "reduction at fixed activation HBM is ~%.2fx batch "
                "headroom at pinned loss parity" % (
                    best["residual_reduction"],
                    best["residual_reduction"]),
            "compute_dtype": "bfloat16",
            "seq_len": seq_len, "num_layers": layers, "hidden": hidden,
            "sweep": sweep}


def bench_host_transfer(chip, smoke=False):
    """Host<->device transfer: upload/download bandwidth and small-fetch
    round-trip latency.  These bound any per-step host staging — this
    row is the context for interpreting fit-row vs direct-row gaps.

    jax.Array caches its host copy after the first np.asarray, so every
    timed fetch here reads a DISTINCT array."""
    import jax
    import jax.numpy as jnp

    mb = 4 if smoke else 32
    n = mb * 1024 * 1024 // 4
    host = np.random.RandomState(0).uniform(-1, 1, n).astype(np.float32)
    reps = 3
    # warm BOTH timed computations (the big device_put and the [:1]
    # completion slice) so no trace/compile lands on the clock
    _fetch_sync(jax.device_put(host)[:1])

    # small-fetch RTT first (its estimate de-noises the upload loop):
    # distinct resident tiny arrays, one uncached fetch each
    tinies = [jnp.zeros((1,), jnp.float32) + i for i in range(8)]
    jax.block_until_ready(tinies)  # residency only; clock starts below
    tic = time.perf_counter()
    for t in tinies:
        np.asarray(t)
    rtt = (time.perf_counter() - tic) / len(tinies)

    tic = time.perf_counter()
    for _ in range(reps):
        dev = jax.device_put(host)
        _fetch_sync(dev[:1])  # new slice array: forces upload, no cache
    elapsed = time.perf_counter() - tic
    adj = elapsed - reps * rtt
    # a noisy RTT estimate must degrade to the raw (conservative)
    # figure, not explode the denominator
    rtt_adjusted = adj > 0.05 * elapsed
    up_bw = mb * reps / (adj if rtt_adjusted else elapsed)

    downs = [jax.device_put(host) for _ in range(reps)]
    for d in downs:
        _fetch_sync(d[:1])  # resident before the clock
    tic = time.perf_counter()
    for d in downs:
        np.asarray(d)  # first (only) full fetch of each distinct array
    down_bw = mb * reps / max(time.perf_counter() - tic, 1e-9)
    return {"metric": "comm.host_transfer",
            "value": round(up_bw, 2), "unit": "MB/s upload",
            "vs_baseline": None,
            "download_mb_s": round(down_bw, 2),
            "fetch_rtt_ms": round(rtt * 1e3, 2),
            "rtt_adjusted": rtt_adjusted,
            "payload_mb": mb}


def bench_comm(chip):
    """All-reduce bandwidth over the mesh (n>1), else HBM stream BW."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    n = chip["n_devices"]
    if n > 1:
        # resnet-50-sized gradient set: ~25.5M floats (102 MB)
        total = 25_500_000
        devs = jax.devices()
        mesh = Mesh(np.array(devs), ("dp",))

        @jax.jit
        def allreduce(x):
            return shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                             in_specs=P("dp"), out_specs=P("dp"))(x)

        rs = np.random.RandomState(0)
        host = rs.uniform(-1, 1, (n, total)).astype(np.float32)
        x = jax.device_put(jnp.asarray(host), NamedSharding(mesh, P("dp")))
        out = allreduce(x)
        _fetch_sync(out[:1, :1])  # warm the slice program outside the clock
        expect = host.sum(axis=0)
        err = float(np.abs(np.asarray(out)[0] - expect).max() /
                    max(1e-12, np.abs(expect).max()))
        iters = 10
        tic = time.perf_counter()
        o = x
        for _ in range(iters):
            # chain through the output itself: a pure data dependency that
            # forces sequential collectives without extra HBM traffic
            o = allreduce(o)
        _fetch_sync(o[:1, :1])
        dt = (time.perf_counter() - tic) / iters
        bw = 2 * (n - 1) / n * total * 4 / dt / 1e9
        return {"metric": "comm.allreduce_bw", "value": round(bw, 2),
                "unit": "GB/s/device",
                "vs_baseline": round(bw / ALLREDUCE_BASELINE_GBS, 3),
                "n_devices": n, "reduce_error": err}
    # single chip: HBM stream (y = a*x + y over 256 MB, 3 accesses/elem)
    total = 64_000_000
    x = jnp.zeros((total,), jnp.float32) + 1.0
    y = jnp.zeros((total,), jnp.float32)

    @jax.jit
    def triad(x, y):
        return 1.0001 * x + y

    out = triad(x, y)
    _fetch_sync(out[:1])
    iters = 20
    tic = time.perf_counter()
    for _ in range(iters):
        y = triad(x, y)
    _fetch_sync(y[:1])
    dt = (time.perf_counter() - tic) / iters
    bw = 3 * total * 4 / dt / 1e9
    return {"metric": "comm.hbm_stream_bw", "value": round(bw, 2),
            "unit": "GB/s", "vs_baseline": None, "n_devices": 1,
            "note": "single chip visible; ICI all-reduce not measurable"}


# timing-protocol generation; bump GEN (and retag) when the measurement
# discipline changes in a way that invalidates earlier rows
PROTOCOL = "fetch-forced-v2"
PROTOCOL_GEN = 2


def main():
    t0 = time.time()
    smoke = os.environ.get("BENCH_SMOKE", "0") == "1"
    row_filter = os.environ.get("BENCH_ROWS")
    row_filter = row_filter.split(",") if row_filter else None

    from mxnet_tpu.base import use_compile_cache
    # the sweep is compile-dominated (~60-120 s per network on chip):
    # a second run must spend its minutes measuring, not recompiling
    use_compile_cache()
    from mxnet_tpu.context import require_tpu
    require_tpu("bench.py")   # raises when no backend comes up, too
    chip = _chip_info()

    iters = max(1, int(os.environ.get("BENCH_ITERS",
                                      "5" if smoke else "20")))
    # >= 1: the drain-bounded fit clock starts at the warmup-th batch
    # callback (and batch 1 pays the compile anyway)
    warmup = max(1, int(os.environ.get("BENCH_WARMUP",
                                       "2" if smoke else "3")))
    rows = []

    def want(tag):
        return row_filter is None or any(f in tag for f in row_filter)

    def guard(tag, fn, *args):
        if not want(tag):
            return
        try:
            row = fn(*args)
            row["seconds"] = round(time.time() - t0, 1)
            rows.append(row)
        except Exception as e:
            rows.append(_error_row(tag, e))
        print("# %s" % json.dumps(rows[-1]), flush=True)

    # Row order = evidence value per minute: the credibility anchor and
    # the cheap context rows before the long compile-heavy tail
    guard("calibration", bench_calibration, chip, smoke)
    guard("imperative.dispatch.softmax", bench_imperative_dispatch,
          "softmax", chip, smoke)
    guard("imperative.dispatch.batchnorm", bench_imperative_dispatch,
          "batchnorm", chip, smoke)
    # CPU-deterministic dist data-plane rows (injected-latency protocol)
    guard("kvstore.push_pull.fp32", bench_kvstore_push_pull, "fp32", chip,
          smoke)
    guard("kvstore.push_pull.2bit", bench_kvstore_push_pull, "2bit", chip,
          smoke)
    # collectives-vs-PS data plane + overlap-vs-barrier reduction
    # (CPU-deterministic injected-latency protocol; acceptance-pinned
    # by tests/test_dist_mesh.py against the banked artifact)
    guard("kvstore.dist_mesh.fp32", bench_kvstore_dist_mesh, "fp32",
          chip, smoke)
    guard("kvstore.dist_mesh.overlap", bench_kvstore_dist_mesh,
          "overlap", chip, smoke)
    # elastic-async PS rows: sync vs bounded-staleness async under one
    # injected straggler (CPU-deterministic seeded protocol)
    for st_mode in ("sync", "s0", "s4"):
        guard("kvstore.async_staleness.%s" % st_mode,
              bench_kvstore_async_staleness, st_mode, chip, smoke)
    guard("io.input_staging", bench_input_staging, chip, smoke)
    # CPU-deterministic checkpointable-data-plane rows (seeded synthetic
    # recordio + injected decode latency), banked as BENCH_data_cpu
    guard("io.sharded_stream.throughput", bench_sharded_stream,
          "throughput", chip, smoke)
    guard("io.sharded_stream.resume_overhead", bench_sharded_stream,
          "resume_overhead", chip, smoke)
    # CPU-deterministic one-SPMD-step-program rows (need >=8 visible
    # devices: XLA_FLAGS=--xla_force_host_platform_device_count=8 on
    # CPU, or a real multi-chip slice; skipped rows otherwise)
    for cfg in ("dp2", "dp4", "dp8", "dp2xmp2"):
        guard("spmd.step.%s" % cfg, bench_spmd_step, cfg, chip, smoke)
    # CPU-deterministic serving-plane rows (seeded open-loop protocol)
    guard("serving.latency.fp32", bench_serving_latency, "fp32", chip,
          smoke)
    guard("serving.latency.bf16", bench_serving_latency, "bf16", chip,
          smoke)
    guard("serving.latency.int8", bench_serving_latency, "int8", chip,
          smoke)
    # front-door rows: HTTP transport overhead on the same schedule,
    # and the kill-one-of-3-replicas failover drain (zero drops,
    # post-kill QPS recovery)
    guard("serving.frontdoor.http_overhead", bench_serving_frontdoor,
          "http_overhead", chip, smoke)
    guard("serving.frontdoor.failover", bench_serving_frontdoor,
          "failover", chip, smoke)
    # telemetry-plane overhead row: full tracing+metrics+flight at
    # default sampling vs the untelemetered engine on the same seeded
    # schedule (acceptance: <= 5% capacity, <= 10% p99; sample=0
    # restores baseline within noise)
    guard("serving.observability.overhead", bench_observability, chip,
          smoke)
    # race-detector cost row: MXNET_RACE_CHECK off (zero-cost,
    # spy-pinned) vs armed at runtime on the same engine
    guard("serving.observability.racecheck_overhead",
          bench_racecheck_overhead, chip, smoke)
    # control-plane rows: the SLO-driven autoscaler vs static
    # provisioning over seeded diurnal/bursty swings, the rolling
    # weight swap under traffic, and the composed-fault chaos campaign
    # (the gates `make chaos-smoke` enforces, banked at full scale)
    for ctl in ("autoscale_diurnal", "autoscale_bursty",
                "rolling_swap", "chaos"):
        guard("serving.control.%s" % ctl, bench_serving_control, ctl,
              chip, smoke)
    # decode-plane generation rows: continuous batching over the KV
    # cache vs the naive re-prefill-per-token baseline, same seeded
    # open-loop schedule (tokens/sec + TTFT + inter-token latency),
    # plus the low-precision decode sides (bf16 cache+weights, int8
    # weight-only) on the same schedule
    guard("serving.decode.continuous", bench_serving_decode,
          "continuous", chip, smoke)
    guard("serving.decode.reprefill", bench_serving_decode,
          "reprefill", chip, smoke)
    guard("serving.decode.bf16", bench_serving_decode, "bf16", chip,
          smoke)
    guard("serving.decode.int8", bench_serving_decode, "int8", chip,
          smoke)
    # paged-KV decode rows: block-table attention + copy-on-write
    # prefix sharing + chunked prefill vs the contiguous plane on
    # matched seeded schedules — flat (prefix-free throughput parity),
    # prefix (>= 2x concurrent sequences per KV byte, prefill chunks
    # provably skipped), chunked (long-prompt p99 ITL relief)
    guard("serving.decode.paged.flat", bench_serving_decode_paged,
          "flat", chip, smoke)
    guard("serving.decode.paged.prefix", bench_serving_decode_paged,
          "prefix", chip, smoke)
    guard("serving.decode.paged.chunked", bench_serving_decode_paged,
          "chunked", chip, smoke)
    # speculative-decoding rows: draft-proposed K-token windows
    # verified by one in-graph target call vs the plain paged plane on
    # matched seeded schedules (<= 0.6x target steps per emitted token
    # draft-friendly, >= 0.95x base tokens/sec when the adversarial
    # draft collapses acceptance), plus the int8 paged KV pool
    # (<= 0.3x fp32 pool bytes per token)
    guard("serving.decode.spec.greedy", bench_serving_decode_spec,
          "greedy", chip, smoke)
    guard("serving.decode.spec.sampled", bench_serving_decode_spec,
          "sampled", chip, smoke)
    guard("serving.decode.paged_int8", bench_serving_decode_spec,
          "int8", chip, smoke)
    # transformer MFU headline (flash attention + the fused Pallas
    # kernels end-to-end through Module.fit) + the remat batch-scaling
    # row; CPU-deterministic protocol, banked as BENCH_transformer_cpu
    guard("transformer.train.pallas", bench_transformer_train, "pallas",
          chip, smoke)
    guard("transformer.train.xla", bench_transformer_train, "xla", chip,
          smoke)
    guard("transformer.remat_batch_scaling", bench_remat_batch_scaling,
          chip, smoke)
    guard("train.resnet-50.trainer_direct", bench_trainer_direct, iters,
          warmup, chip, smoke)
    if not smoke:  # smoke pins batch 8 — a duplicate row, skip
        # headline row (chip ceiling on the real model)
        guard("train.resnet-50.trainer_direct_b256", bench_trainer_direct,
              iters, warmup, chip, smoke, 256)
    guard("train.resnet-50.module_fit", bench_fit, "resnet-50", 32, iters,
          warmup, chip, smoke)
    guard("comm.host_transfer", bench_host_transfer, chip, smoke)
    guard("pallas.flash_attention", bench_flash_attention, chip, smoke)
    guard("comm", bench_comm, chip)
    guard("train.inception-v3.module_fit", bench_fit, "inception-v3", 32,
          iters, warmup, chip, smoke)
    guard("train.alexnet.module_fit", bench_fit, "alexnet", 256, iters,
          warmup, chip, smoke)
    for net in ("alexnet", "vgg", "inception-bn", "inception-v3",
                "resnet-50", "resnet-152"):
        guard("inference.%s" % net, bench_inference, net, iters, chip,
              smoke)
    guard("train.lstm-bucketing", bench_lstm_bucketing, iters, warmup,
          chip, smoke)

    out = _assemble_out(rows, chip, smoke, t0)
    print(json.dumps(out))
    errored = [r["metric"] for r in rows if r.get("unit") == "error"]
    if errored:
        raise SystemExit("bench.py: %d row(s) errored: %s"
                         % (len(errored), ", ".join(errored)))


def _assemble_out(rows, chip, smoke, t0):
    """Driver-contract output dict from whatever rows exist so far.

    Headline: trainer-direct resnet-50 (round-1 protocol continuity),
    falling back to the Module.fit row if the direct row errored."""
    headline = None
    # headline preference: the large-batch direct row shows what the
    # chip can actually do (batch 32/chip under-feeds a v5e MXU and the
    # round-5 verdict judges MFU against the calibrated ceiling); the
    # batch-32 rows remain for anchor continuity
    for m in ("train.resnet-50.trainer_direct_b256",
              "train.resnet-50.trainer_direct",
              "train.resnet-50.module_fit"):
        for r in rows:
            if r["metric"] == m and r.get("unit") != "error":
                headline = r
                break
        if headline:
            break
    fit_vs_direct = None
    fit_vs_direct_reason = None
    rows = list(rows)  # the ratio row appended below stays out of main()'s
    by_metric = {r["metric"]: r for r in rows}
    d = by_metric.get("train.resnet-50.trainer_direct")
    f = by_metric.get("train.resnet-50.module_fit")
    if d and f and d.get("unit") != "error" and f.get("unit") != "error" \
            and d["value"]:
        fit_vs_direct = round(f["value"] / d["value"], 3)
    else:
        # a bare null voided the ratio on partial sweeps;
        # emit a structured reason row so partial sweeps stay
        # machine-readable: which input was missing/errored/zero
        reasons = []
        for tag, r in (("train.resnet-50.trainer_direct", d),
                       ("train.resnet-50.module_fit", f)):
            if r is None:
                reasons.append({"input": tag, "status": "missing"})
            elif r.get("unit") == "error":
                reasons.append({"input": tag, "status": "error",
                                "error": r.get("error")})
            elif not r["value"]:
                reasons.append({"input": tag, "status": "zero_value"})
        fit_vs_direct_reason = reasons
        rows.append({"metric": "ratio.fit_vs_direct", "value": 0.0,
                     "unit": "unavailable", "vs_baseline": None,
                     "reason": reasons})

    # serving-plane summary: the continuous batcher's QPS multiple over
    # the per-request deployment at the same offered load (the >= 3x
    # acceptance figure), surfaced per serving dtype when the rows ran
    serving = {}
    for mode in ("fp32", "bf16", "int8"):
        r = by_metric.get("serving.latency.%s" % mode)
        if r and r.get("unit") not in ("error", "skipped"):
            serving[mode] = {
                "qps": r["value"],
                "qps_vs_per_request": r.get("qps_vs_per_request"),
                "p99_ms": r.get("p99_ms"),
            }
    r = by_metric.get("serving.frontdoor.http_overhead")
    if r and r.get("unit") not in ("error", "skipped"):
        serving["frontdoor"] = {
            "qps": r["value"],
            "http_p99_vs_inproc": r.get("http_p99_vs_inproc"),
            "http_p50_overhead_ms": r.get("http_p50_overhead_ms"),
        }
    r = by_metric.get("serving.frontdoor.failover")
    if r and r.get("unit") not in ("error", "skipped"):
        serving["failover"] = {
            "post_vs_pre_qps": r["value"],
            "dropped": r.get("dropped"),
            "recovery_ms": r.get("recovery_ms"),
        }
    r = by_metric.get("serving.decode.continuous")
    if r and r.get("unit") not in ("error", "skipped"):
        serving["decode"] = {
            "tokens_per_sec": r["value"],
            "tokens_per_sec_vs_reprefill":
                r.get("tokens_per_sec_vs_reprefill"),
            "ttft_p99_ms": r.get("ttft_p99_ms"),
            "itl_mean_ms": r.get("itl_mean_ms"),
            "itl_mean_vs_host_sample":
                r.get("itl_mean_vs_host_sample"),
        }
    for mode in ("bf16", "int8"):
        r = by_metric.get("serving.decode.%s" % mode)
        if r and r.get("unit") not in ("error", "skipped"):
            serving["decode_%s" % mode] = {
                "tokens_per_sec": r["value"],
                "tokens_per_sec_vs_fp32":
                    r.get("tokens_per_sec_vs_fp32"),
                "weight_bytes": r.get("weight_bytes"),
                "cache_bytes_per_slot": r.get("cache_bytes_per_slot"),
            }
    r = by_metric.get("serving.decode.paged.flat")
    if r and r.get("unit") not in ("error", "skipped"):
        serving["decode_paged"] = {
            "tokens_per_sec": r["value"],
            "tokens_per_sec_vs_contiguous":
                r.get("tokens_per_sec_vs_contiguous"),
        }
    r = by_metric.get("serving.decode.paged.prefix")
    if r and r.get("unit") not in ("error", "skipped"):
        serving.setdefault("decode_paged", {}).update({
            "seqs_per_kv_byte_vs_contiguous":
                r.get("seqs_per_kv_byte_vs_contiguous"),
            "prefill_chunk_savings": r.get("prefill_chunk_savings"),
        })
    r = by_metric.get("serving.decode.paged.chunked")
    if r and r.get("unit") not in ("error", "skipped"):
        serving.setdefault("decode_paged", {}).update({
            "itl_p99_chunked_vs_unchunked":
                r.get("itl_p99_chunked_vs_unchunked"),
        })
    for mode in ("greedy", "sampled"):
        r = by_metric.get("serving.decode.spec.%s" % mode)
        if r and r.get("unit") not in ("error", "skipped"):
            serving["decode_spec_%s" % mode] = {
                "tokens_per_sec": r["value"],
                "steps_per_token_vs_base":
                    r.get("steps_per_token_vs_base"),
                "acceptance_rate": r.get("acceptance_rate"),
                "adversarial_tokens_per_sec_vs_base":
                    r.get("adversarial_tokens_per_sec_vs_base"),
            }
    r = by_metric.get("serving.decode.paged_int8")
    if r and r.get("unit") not in ("error", "skipped"):
        serving["decode_paged_int8"] = {
            "tokens_per_sec": r["value"],
            "pool_bytes_per_token_vs_fp32":
                r.get("pool_bytes_per_token_vs_fp32"),
            "tokens_per_sec_vs_fp32": r.get("tokens_per_sec_vs_fp32"),
        }

    out = {
        "metric": "resnet50_train_images_per_sec",
        "value": headline["value"] if headline else 0.0,
        "unit": "images/sec",
        "vs_baseline": headline["vs_baseline"] if headline else 0.0,
        "chip": chip,
        "smoke": smoke,
        "protocol": PROTOCOL,
        "protocol_gen": PROTOCOL_GEN,
        "fit_vs_direct": fit_vs_direct,
        "total_seconds": round(time.time() - t0, 1),
        "rows": rows,
    }
    if serving:
        out["serving"] = serving
    if fit_vs_direct_reason is not None:
        out["fit_vs_direct_reason"] = fit_vs_direct_reason
    if smoke and fit_vs_direct is not None:
        # tiny-net smoke steps are overhead-dominated; the ratio is
        # plumbing validation, not the on-chip parity gate
        out["fit_vs_direct_note"] = ("smoke mode: tiny stand-in nets, "
                                     "not the +/-10%% parity gate")
    # this sweep defines no benchmark cell and claims no gain
    out["claim"] = None
    return out


if __name__ == "__main__":
    main()
