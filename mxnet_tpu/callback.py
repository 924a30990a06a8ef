"""Training callbacks.

Role parity with the reference's ``python/mxnet/callback.py``
(do_checkpoint / module_checkpoint / log_train_metric / Speedometer /
ProgressBar, same BatchEndParam contract), restructured around small
helpers: one metric-logging function shared by the periodic loggers,
and a windowed timer inside Speedometer.
"""
from __future__ import annotations

import logging
import math
import sys
import time

__all__ = ["module_checkpoint", "do_checkpoint", "batch_checkpoint",
           "log_train_metric", "MetricsLogger", "Speedometer",
           "ProgressBar"]


def _log_metric(prefix_fmt, prefix_args, metric, reset=False):
    """Emit one log line per (name, value) of an EvalMetric."""
    for name, value in metric.get_name_value():
        logging.info(prefix_fmt + "\tTrain-%s=%f",
                     *(prefix_args + (name, value)))
    if reset:
        metric.reset()


def module_checkpoint(mod, prefix, period=1, save_optimizer_states=False,
                      data_iter=None):
    """Epoch-end callback saving a Module checkpoint every ``period``
    epochs (optimizer state included when asked).  Saves are atomic
    (temp file + rename), so a crash mid-epoch-N-save leaves epoch N-1
    loadable — resume with ``Module.load_latest(prefix)``.

    ``data_iter`` (the training iterator) additionally persists the
    iterator state beside the params, like ``do_checkpoint`` — this is
    the epoch-end callback to pair with ``batch_checkpoint`` when the
    resume should restore optimizer state too."""
    period = max(1, int(period))

    def _callback(iter_no, sym=None, arg=None, aux=None):
        if (iter_no + 1) % period == 0:
            state = None
            if data_iter is not None:
                from .data.checkpoint import state_dict_of
                state = state_dict_of(data_iter)
            mod.save_checkpoint(prefix, iter_no + 1, save_optimizer_states,
                                data_state=state)
    return _callback


def do_checkpoint(prefix, period=1, data_iter=None):
    """Epoch-end callback saving (symbol, params) the model.py way —
    atomic like ``module_checkpoint``; pair with
    ``model.load_latest_checkpoint(prefix)`` for auto-resume.

    ``data_iter`` (the training iterator handed to ``fit``) also
    persists the iterator state beside the params: at an epoch boundary
    that is an ``eof`` frontier the dataset rolls forward into the next
    epoch on resume, so ``fit(begin_epoch=<returned epoch>,
    resume_data_state=...)`` continues the exact record/shuffle stream
    across the restart (docs/architecture/data_pipeline.md).  Safe here
    because the fit loop fires epoch-end callbacks after the epoch
    drained: any staging/prefetch wrappers sit at the same frontier as
    the source."""
    from .model import save_checkpoint
    period = max(1, int(period))

    def _callback(iter_no, sym, arg, aux):
        if (iter_no + 1) % period == 0:
            state = None
            if data_iter is not None:
                from .data.checkpoint import state_dict_of
                state = state_dict_of(data_iter)
            save_checkpoint(prefix, iter_no + 1, sym, arg, aux,
                            data_state=state)
    return _callback


def batch_checkpoint(mod, prefix, period=50, save_optimizer_states=True):
    """Batch-end callback checkpointing MID-epoch: every ``period``
    batches it saves the module's params (+ optimizer state) as
    ``prefix-<epoch>.params`` together with the training iterator's
    consumer-frontier state — the iterator actually driven by the fit
    loop (read from ``BatchEndParam.locals``, so a ``DeviceStager``
    wrapper reports the trained-through frontier, never staged
    read-ahead).  A SIGKILLed run relaunched via
    ``Module.load_latest(prefix)`` + ``fit(begin_epoch=epoch,
    resume_data_state=bundle.data_state)`` replays zero and skips zero
    records (tests/test_data_pipeline.py pins byte-identical streams).

    File numbering: epoch N's mid-epoch saves overwrite
    ``prefix-NNNN.*`` with progressively later frontiers — the same
    "file N = a position within epoch N" convention the epoch-end
    ``do_checkpoint`` produces (its end-of-epoch-(N-1) save is file N
    at frontier zero)."""
    period = max(1, int(period))

    def _callback(param):
        if (param.nbatch + 1) % period:
            return
        state = None
        it = (param.locals or {}).get("train_data")
        if it is not None:
            from .data.checkpoint import state_dict_of
            state = state_dict_of(it)
        mod.save_checkpoint(prefix, param.epoch, save_optimizer_states,
                            data_state=state)
    return _callback


def log_train_metric(period, auto_reset=False):
    """Batch-end callback logging the training metric every ``period``
    batches."""
    def _callback(param):
        if param.nbatch % period == 0 and param.eval_metric is not None:
            _log_metric("Iter[%d] Batch[%d]", (param.epoch, param.nbatch),
                        param.eval_metric, reset=auto_reset)
    return _callback


class MetricsLogger:
    """Batch-end callback logging the process metrics registry
    (mxnet_tpu/metrics.py) every ``period`` batches: counters/gauges
    whose names match one of ``prefixes`` plus every histogram's
    count/p50/p95/p99 — the training-script view of the same registry
    the serving front door scrapes at ``GET /metrics``.

    ``prefixes=None`` logs the fit-loop family (``fit_``,
    ``phase_seconds`` — step counts and the per-phase latency
    histograms the step loop feeds through ``profiler.phase``);
    pass e.g. ``("kvstore_",)`` to watch the data plane, or ``()`` for
    everything."""

    def __init__(self, period=50, prefixes=None, logger=None):
        self.period = max(1, int(period))
        self.prefixes = ("fit_", "phase_seconds") if prefixes is None \
            else tuple(prefixes)
        self.logger = logger or logging

    def _want(self, key):
        return not self.prefixes or any(key.startswith(p)
                                        for p in self.prefixes)

    def __call__(self, param):
        if param.nbatch % self.period:
            return
        from . import metrics
        snap = metrics.snapshot()
        parts = []
        for key, v in snap["counters"].items():
            if self._want(key):
                parts.append("%s=%d" % (key, v))
        for key, v in snap["gauges"].items():
            if self._want(key):
                parts.append("%s=%g" % (key, v))
        for key, d in snap["histograms"].items():
            if self._want(key) and d["count"]:
                parts.append("%s{n=%d p50=%.4g p95=%.4g p99=%.4g}"
                             % (key, d["count"], d["p50"] or 0,
                                d["p95"] or 0, d["p99"] or 0))
        if parts:
            self.logger.info("Metrics[%d][%d]\t%s", param.epoch,
                             param.nbatch, "  ".join(parts))


class Speedometer:
    """Batch-end callback logging samples/sec (and the running metric)
    every ``frequent`` batches."""

    def __init__(self, batch_size, frequent=50):
        self.batch_size = batch_size
        self.frequent = frequent
        self._window_start = None   # perf-clock at the window's opening
        self._prev_nbatch = 0

    @staticmethod
    def _drain(param):
        """Force completed-through-here before reading the clock:
        dispatch is asynchronous and device-side metrics never sync, so
        callback-to-callback time measures host ENQUEUE rate, not
        throughput (docs/perf.md, measuring honestly).  The metric's
        host read data-depends on every accumulated batch, so it is a
        true fetch-forced sync.  Without a metric, fetch a byte of the
        most recent output instead (exposed through
        ``BatchEndParam.locals`` — the fit loop's ``self`` is the
        module): a fetch of dependent bytes cannot return before the
        device is done.  ``waitall`` remains the last resort when no
        output is reachable.  Returns the name/value
        pairs when the metric was fetched."""
        if param.eval_metric is not None:
            return param.eval_metric.get_name_value()
        loc = getattr(param, "locals", None) or {}
        mod = loc.get("self")
        if mod is not None:
            try:
                out = mod.get_outputs()[0]
                # one row's first element: bytes that data-depend on
                # the step — forces real completion, tiny transfer
                out[0:1].asnumpy()
                return None
            except Exception:
                pass  # no outputs yet / exotic module: fall through
        from . import ndarray as _nd
        _nd.waitall()
        return None

    def __call__(self, param):
        if param.nbatch < self._prev_nbatch:
            self._window_start = None   # new epoch: restart the window
        self._prev_nbatch = param.nbatch

        if self._window_start is None:
            self._drain(param)          # windows START on a sync too
            self._window_start = time.time()
            return
        if param.nbatch % self.frequent != 0:
            return
        name_values = self._drain(param)
        elapsed = max(1e-12, time.time() - self._window_start)
        speed = self.frequent * self.batch_size / elapsed
        if name_values is not None:
            for name, value in name_values:
                logging.info(
                    "Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec"
                    "\tTrain-%s=%f",
                    param.epoch, param.nbatch, speed, name, value)
            param.eval_metric.reset()
        else:
            logging.info("Iter[%d] Batch [%d]\tSpeed: %.2f samples/sec",
                         param.epoch, param.nbatch, speed)
        self._window_start = time.time()


class ProgressBar:
    """Batch-end callback drawing an in-place progress bar."""

    def __init__(self, total, length=80):
        self.total = total
        self.length = length

    def __call__(self, param):
        frac = min(1.0, param.nbatch / float(self.total))
        filled = int(round(self.length * frac))
        bar = "=" * filled + "-" * (self.length - filled)
        sys.stdout.write("[%s] %d%%\r" % (bar, math.ceil(frac * 100)))
