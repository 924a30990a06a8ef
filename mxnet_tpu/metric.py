"""Evaluation metrics.

Reference: ``python/mxnet/metric.py`` — EvalMetric registry: Accuracy,
TopKAccuracy, F1, Perplexity, MAE/MSE/RMSE, CrossEntropy, CompositeEvalMetric,
CustomMetric + ``np`` wrapper.  Metric math runs on host (numpy); the
``asnumpy()`` calls are the implicit engine sync points, as in the reference
fit loop.
"""
from __future__ import annotations

import math

import numpy as _np

from .base import MXNetError
from .ndarray import NDArray

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
           "F1", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy", "Loss",
           "Torch", "Caffe", "CustomMetric", "np", "create"]


def check_label_shapes(labels, preds, shape=0):
    if shape == 0:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError("Shape of labels {} does not match shape of "
                         "predictions {}".format(label_shape, pred_shape))


class EvalMetric:
    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self._dev_state = None
        self._dev_stat_jit = None
        self._dev_accum_jit = None
        self.reset()

    def update(self, labels, preds):
        raise NotImplementedError()

    # -- device-side accumulation (TPU fast path) --------------------------
    #
    # The reference fit loop syncs every batch (update_metric's asnumpy).
    # A per-batch host sync stalls async dispatch (the host waits for
    # the device instead of running ahead), so metrics that can be
    # expressed as a pure
    # (labels, preds) -> [stat_sum, inst_count] reduction accumulate
    # on device — the sum lane in f32, the count lane in i32 (exact up
    # to 2^31 instances; an f32 count lane starts rounding at 2^24).
    # The host fetches the state only when the value is actually read
    # (epoch end / Speedometer), keeping the training loop fetch-free.

    def device_stat_fn(self):
        """Pure jax fn ``(labels, preds) -> f32[2]`` of [sum, count], or
        None when this metric has no device fast path."""
        return None

    def update_device(self, labels, preds):
        """Accumulate on device without a host sync.  Returns False when
        unsupported (caller must fall back to host ``update``)."""
        if self.num is not None or len(labels) != len(preds):
            return False
        if getattr(self, "_dev_unsupported", False):
            # a previous attempt failed at trace time: don't pay a failed
            # jit trace + exception on every batch of the hot loop
            return False
        fn = self.device_stat_fn()
        if fn is None:
            return False
        import jax
        try:
            labels = tuple(x._data if isinstance(x, NDArray) else x
                           for x in labels)
            preds = tuple(x._data if isinstance(x, NDArray) else x
                          for x in preds)
            if self._dev_stat_jit is None:
                import jax.numpy as jnp

                def split(ls, ps):
                    stat = fn(ls, ps)
                    return stat[0], stat[1].astype(jnp.int32)

                def accum(state, ls, ps):
                    s, c = split(ls, ps)
                    # saturate the count lane on i32 wrap (sum of
                    # non-negatives got smaller) so overflow is always
                    # detectable at drain, no matter how many batches
                    # accumulate past it
                    nc = state[1] + c
                    nc = jnp.where(nc < state[1], jnp.int32(2**31 - 1),
                                   nc)
                    return state[0] + s, nc

                self._dev_stat_jit = jax.jit(split)
                self._dev_accum_jit = jax.jit(accum)
            if self._dev_state is None:
                self._dev_state = self._dev_stat_jit(labels, preds)
            else:
                self._dev_state = self._dev_accum_jit(self._dev_state,
                                                      labels, preds)
        except Exception:  # odd dtypes/shapes: host update handles them
            self._dev_unsupported = True  # sticky until reset()
            return False
        return True

    def _drain_device(self):
        if self._dev_state is not None:
            s, c = self._dev_state
            c = int(c)
            # the i32 count lane saturates to INT32_MAX on wrap (see
            # accum above), so any overflow of the accumulation window
            # between get() calls surfaces here — fail loudly, before
            # mutating any state, instead of corrupting the statistics
            if c < 0 or c == 2**31 - 1:
                raise OverflowError(
                    "device metric count lane overflowed int32: drain "
                    "(get()) at least once per 2**31 accumulated "
                    "instances")
            self._dev_state = None
            self.sum_metric += float(s)
            self.num_inst += c

    def reset(self):
        self._dev_state = None
        self._dev_unsupported = False
        if self.num is None:
            self.num_inst = 0
            self.sum_metric = 0.0
        else:
            self.num_inst = [0] * self.num
            self.sum_metric = [0.0] * self.num

    def get(self):
        if self.num is None:
            self._drain_device()
            if self.num_inst == 0:
                return (self.name, float("nan"))
            return (self.name, self.sum_metric / self.num_inst)
        names = ["%s_%d" % (self.name, i) for i in range(self.num)]
        values = [x / y if y != 0 else float("nan")
                  for x, y in zip(self.sum_metric, self.num_inst)]
        return (names, values)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))

    def __str__(self):
        return "EvalMetric: {}".format(dict(self.get_name_value()))


class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics=None, **kwargs):
        super().__init__("composite", **kwargs)
        if metrics is None:
            metrics = []
        self.metrics = [create(m) if isinstance(m, str) else m
                        for m in metrics]

    def add(self, metric):
        self.metrics.append(create(metric) if isinstance(metric, str)
                            else metric)

    def get_metric(self, index):
        try:
            return self.metrics[index]
        except IndexError:
            return ValueError("Metric index {} is out of range 0 and {}"
                              .format(index, len(self.metrics)))

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def update_device(self, labels, preds):
        # all-or-nothing: a mixed device/host split would double-count
        # when the caller falls back to host update for the whole set.
        # A member's sticky _dev_unsupported also fails the whole set up
        # front — otherwise every batch would re-accumulate the earlier
        # members only to roll them back below.
        if any(m.num is not None or m.device_stat_fn() is None
               or getattr(m, "_dev_unsupported", False)
               for m in self.metrics):
            return False
        snapshots = [m._dev_state for m in self.metrics]
        for i, m in enumerate(self.metrics):
            if not m.update_device(labels, preds):
                # a member failed at trace/run time after earlier members
                # already accumulated: roll those back so the caller's
                # whole-composite host fallback cannot double-count
                for mm, state in zip(self.metrics[:i + 1], snapshots):
                    mm._dev_state = state
                return False
        return True

    def reset(self):
        try:
            for metric in self.metrics:
                metric.reset()
        except AttributeError:
            pass

    def get(self):
        names = []
        results = []
        for metric in self.metrics:
            result = metric.get()
            names.append(result[0])
            results.append(result[1])
        return (names, results)


def _to_np(x):
    return x.asnumpy() if isinstance(x, NDArray) else _np.asarray(x)


class Accuracy(EvalMetric):
    def __init__(self, axis=1):
        super().__init__("accuracy")
        self.axis = axis

    def device_stat_fn(self):
        axis = self.axis

        def fn(labels, preds):
            import jax.numpy as jnp
            correct = jnp.float32(0.0)
            count = 0
            for label, pred in zip(labels, preds):
                if pred.ndim != label.ndim:
                    pred = jnp.argmax(pred, axis=axis)
                p = pred.reshape(-1).astype(jnp.int32)
                lbl = label.reshape(-1).astype(jnp.int32)
                correct = correct + (p == lbl).sum().astype(jnp.float32)
                count += p.shape[0]
            return jnp.stack([correct,
                              jnp.asarray(count, jnp.float32)])
        return fn

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            label = _to_np(label)
            if hasattr(pred_label, "_data") and \
                    tuple(pred_label.shape) != tuple(label.shape):
                # reduce on DEVICE before the host sync: transferring the
                # (batch,) argmax instead of (batch, num_classes) logits
                # keeps the per-batch device-to-host copy small
                # (the reference's update_metric pays a full
                # output copy; we don't have to)
                import jax.numpy as jnp
                pred_label = _np.asarray(
                    jnp.argmax(pred_label._data, axis=self.axis))
            else:
                pred_label = _to_np(pred_label)
                if pred_label.shape != label.shape:
                    pred_label = _np.argmax(pred_label, axis=self.axis)
            pred_label = pred_label.astype("int32").flatten()
            label = label.astype("int32").flatten()
            check_label_shapes(label, pred_label)
            self.sum_metric += (pred_label == label).sum()
            self.num_inst += len(pred_label)


class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1):
        super().__init__("top_k_accuracy")
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += "_%d" % self.top_k

    def device_stat_fn(self):
        top_k = self.top_k

        def fn(labels, preds):
            import jax
            import jax.numpy as jnp
            correct = jnp.float32(0.0)
            count = 0
            for label, pred in zip(labels, preds):
                lbl = label.reshape(-1).astype(jnp.int32)
                if pred.ndim == 2:
                    k = min(pred.shape[1], top_k)
                    _, idx = jax.lax.top_k(pred.astype(jnp.float32), k)
                    hits = (idx.astype(jnp.int32) ==
                            lbl[:, None]).sum()
                else:
                    hits = (pred.reshape(-1).astype(jnp.int32)
                            == lbl).sum()
                correct = correct + hits.astype(jnp.float32)
                count += lbl.shape[0]
            return jnp.stack([correct,
                              jnp.asarray(count, jnp.float32)])
        return fn

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            assert len(pred_label.shape) <= 2, "Predictions should be no " \
                "more than 2 dims"
            pred_label = _np.argsort(_to_np(pred_label).astype("float32"),
                                    axis=1)
            label = _to_np(label).astype("int32")
            check_label_shapes(label, pred_label)
            num_samples = pred_label.shape[0]
            num_dims = len(pred_label.shape)
            if num_dims == 1:
                self.sum_metric += (pred_label.flatten() == label).sum()
            elif num_dims == 2:
                num_classes = pred_label.shape[1]
                top_k = min(num_classes, self.top_k)
                for j in range(top_k):
                    self.sum_metric += (
                        pred_label[:, num_classes - 1 - j].flatten() ==
                        label).sum()
            self.num_inst += num_samples


class F1(EvalMetric):
    def __init__(self):
        super().__init__("f1")

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            pred = _to_np(pred)
            label = _to_np(label).astype("int32")
            pred_label = _np.argmax(pred, axis=1)
            check_label_shapes(label, pred)
            if len(_np.unique(label)) > 2:
                raise ValueError("F1 currently only supports binary "
                                 "classification.")
            true_positives, false_positives, false_negatives = 0., 0., 0.
            for y_pred, y_true in zip(pred_label, label):
                if y_pred == 1 and y_true == 1:
                    true_positives += 1.
                elif y_pred == 1 and y_true == 0:
                    false_positives += 1.
                elif y_pred == 0 and y_true == 1:
                    false_negatives += 1.
            if true_positives + false_positives > 0:
                precision = true_positives / (true_positives +
                                              false_positives)
            else:
                precision = 0.
            if true_positives + false_negatives > 0:
                recall = true_positives / (true_positives + false_negatives)
            else:
                recall = 0.
            if precision + recall > 0:
                f1_score = 2 * precision * recall / (precision + recall)
            else:
                f1_score = 0.
            self.sum_metric += f1_score
            self.num_inst += 1


class Perplexity(EvalMetric):
    """Perplexity over a softmax output (reference Perplexity; ignore_label
    for padding)."""

    def __init__(self, ignore_label, axis=-1):
        super().__init__("Perplexity")
        self.ignore_label = ignore_label
        self.axis = axis

    def device_stat_fn(self):
        ignore_label = self.ignore_label

        def fn(labels, preds):
            import jax.numpy as jnp
            loss = jnp.float32(0.0)
            num = jnp.float32(0.0)
            for label, pred in zip(labels, preds):
                lbl = label.reshape(-1).astype(jnp.int32)
                probs = pred.reshape(-1, pred.shape[-1])[
                    jnp.arange(lbl.shape[0]), lbl]
                n = jnp.float32(lbl.shape[0])
                if ignore_label is not None:
                    ignore = (lbl == ignore_label).astype(probs.dtype)
                    n = n - ignore.sum().astype(jnp.float32)
                    probs = probs * (1 - ignore) + ignore
                loss = loss - jnp.log(
                    jnp.maximum(1e-10, probs)).sum().astype(jnp.float32)
                num = num + n
            # per-update exp, exactly the host semantics: accumulating raw
            # loss and exp-ing at drain time would make the reported value
            # depend on how often get() is called
            return jnp.stack([jnp.exp(loss / num) * num, num])
        return fn

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        loss = 0.
        num = 0
        for label, pred in zip(labels, preds):
            label = _to_np(label)
            pred = _to_np(pred)
            assert label.size == pred.size / pred.shape[-1], \
                "shape mismatch"
            label = label.reshape((label.size,)).astype("int32")
            probs = pred.reshape(-1, pred.shape[-1])[
                _np.arange(label.size), label]
            if self.ignore_label is not None:
                ignore = (label == self.ignore_label).astype(probs.dtype)
                num -= int(_np.sum(ignore))
                probs = probs * (1 - ignore) + ignore
            loss -= _np.sum(_np.log(_np.maximum(1e-10, probs)))
            num += label.size
        self.sum_metric += _np.exp(loss / num) * num
        self.num_inst += num


def _as_columns(label, pred):
    """numpy views with 1-D sides reshaped to (n, 1): a (n,1)-(n,)
    subtraction would broadcast into an (n,n) matrix."""
    label = _to_np(label)
    pred = _to_np(pred)
    if len(label.shape) == 1:
        label = label.reshape(label.shape[0], 1)
    if len(pred.shape) == 1:
        pred = pred.reshape(pred.shape[0], 1)
    return label, pred


def _regression_device_stat(err_fn):
    """Device stat for MAE/MSE/RMSE host semantics: per (label, pred)
    pair, sum_metric += batch error, num_inst += 1."""
    def fn(labels, preds):
        import jax.numpy as jnp
        total = jnp.float32(0.0)
        pairs = 0
        for label, pred in zip(labels, preds):
            if label.ndim == 1:
                label = label.reshape(-1, 1)
            if pred.ndim == 1:
                pred = pred.reshape(-1, 1)
            total = total + err_fn(label.astype(jnp.float32),
                                   pred.astype(jnp.float32))
            pairs += 1
        return jnp.stack([total, jnp.asarray(pairs, jnp.float32)])
    return fn


class MAE(EvalMetric):
    def __init__(self):
        super().__init__("mae")

    def device_stat_fn(self):
        import jax.numpy as jnp
        return _regression_device_stat(
            lambda lbl, p: jnp.abs(lbl - p).mean())

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label, pred = _as_columns(label, pred)
            self.sum_metric += _np.abs(label - pred).mean()
            self.num_inst += 1


class MSE(EvalMetric):
    def __init__(self):
        super().__init__("mse")

    def device_stat_fn(self):
        return _regression_device_stat(
            lambda lbl, p: ((lbl - p) ** 2.0).mean())

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label, pred = _as_columns(label, pred)
            self.sum_metric += ((label - pred) ** 2.0).mean()
            self.num_inst += 1


class RMSE(EvalMetric):
    def __init__(self):
        super().__init__("rmse")

    def device_stat_fn(self):
        import jax.numpy as jnp
        return _regression_device_stat(
            lambda lbl, p: jnp.sqrt(((lbl - p) ** 2.0).mean()))

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label, pred = _as_columns(label, pred)
            self.sum_metric += _np.sqrt(((label - pred) ** 2.0).mean())
            self.num_inst += 1


class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-8):
        super().__init__("cross-entropy")
        self.eps = eps

    def device_stat_fn(self):
        eps = self.eps

        def fn(labels, preds):
            import jax.numpy as jnp
            loss = jnp.float32(0.0)
            count = 0
            for label, pred in zip(labels, preds):
                lbl = label.reshape(-1).astype(jnp.int32)
                prob = pred[jnp.arange(lbl.shape[0]), lbl]
                loss = loss - jnp.log(prob + eps).sum().astype(jnp.float32)
                count += lbl.shape[0]
            return jnp.stack([loss, jnp.asarray(count, jnp.float32)])
        return fn

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = _to_np(label)
            pred = _to_np(pred)
            label = label.ravel()
            assert label.shape[0] == pred.shape[0]
            prob = pred[_np.arange(label.shape[0]), _np.int64(label)]
            self.sum_metric += (-_np.log(prob + self.eps)).sum()
            self.num_inst += label.shape[0]


class Loss(EvalMetric):
    """Average of the raw outputs (for MakeLoss-style heads)."""

    def __init__(self):
        super().__init__("loss")

    def update(self, _, preds):
        for pred in preds:
            self.sum_metric += _to_np(pred).sum()
            self.num_inst += pred.size


class Torch(EvalMetric):
    """Average of torch-criterion outputs.

    Deliberately NOT wired to ``plugin.torch_bridge``: the reference's
    ``metric.Torch`` is itself a dummy ("Dummy metric for torch
    criterions", python/mxnet/metric.py:349-357) that just averages the
    already-computed criterion outputs fed to it — the criterion runs as
    an op (here, via ``plugin.torch_bridge.TorchLoss``), not inside the
    metric.  Semantics match the reference exactly: per-output mean,
    one instance counted per ``update`` call.
    """

    def __init__(self, name="torch"):
        super().__init__(name)

    def update(self, _, preds):
        for pred in preds:
            self.sum_metric += float(_to_np(pred).mean())
        self.num_inst += 1


class Caffe(Torch):
    """Average of caffe-criterion outputs (same dummy contract as
    :class:`Torch`, reference metric.py:359-362)."""

    def __init__(self):
        super().__init__("caffe")


class CustomMetric(EvalMetric):
    def __init__(self, feval, name=None, allow_extra_outputs=False):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label = _to_np(label)
            pred = _to_np(pred)
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy eval function into a CustomMetric."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def create(metric, **kwargs):
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite_metric = CompositeEvalMetric()
        for child_metric in metric:
            composite_metric.add(child_metric)
        return composite_metric
    metrics = {
        "acc": Accuracy, "accuracy": Accuracy, "ce": CrossEntropy,
        "f1": F1, "mae": MAE, "mse": MSE, "rmse": RMSE,
        "top_k_accuracy": TopKAccuracy, "cross-entropy": CrossEntropy,
        "loss": Loss, "torch": Torch, "caffe": Caffe,
        "perplexity": Perplexity,
    }
    try:
        return metrics[metric.lower()](**kwargs)
    except KeyError:
        raise ValueError("Metric must be either callable or in {}".format(
            sorted(metrics)))
