"""BaseModule: the high-level training interface.

Reference: ``python/mxnet/module/base_module.py`` — the ``fit`` north-star
loop (SURVEY.md §3.2): bind → init_params → init_optimizer → per batch
forward_backward/update/update_metric → epoch callbacks → eval.
"""
from __future__ import annotations

import logging
import time

import numpy as np

from .. import metric as metric_mod
from .. import ndarray as nd
from .. import profiler
from ..base import MXNetError
from ..io.io import DataBatch


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, list):
        return obj
    return [obj]


def _check_input_names(symbol, names, typ, throw):
    args = symbol.list_arguments()
    for name in names:
        if name not in args:
            msg = "\033[91mYou created Module with Module(..., %s_names=%s) "\
                "but input with name '%s' is not found in symbol.list_"\
                "arguments(). \033[0m" % (typ, str(names), name)
            if throw:
                raise ValueError(msg)
            logging.warning(msg)


class BaseModule:
    """The module API contract (role of the reference's
    ``mxnet.module.BaseModule``): a trainable/predictable computation
    with bound data shapes, parameters and optimizer state.  High-level
    ``fit``/``score``/``predict`` are implemented here on top of the
    abstract ``bind``/``forward``/``backward``/``update`` primitives
    that concrete modules provide."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high-level --------------------------------------------------------
    def forward_backward(self, data_batch):
        """Run ``forward(is_train=True)`` then ``backward`` on one
        batch."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate ``eval_metric`` over ``eval_data`` (forward-only)
        and return ``[(metric_name, value), ...]``."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = _BatchEndParam(epoch=epoch, nbatch=nbatch,
                                        eval_metric=eval_metric, locals=None)
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = _BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                    eval_metric=eval_metric, locals=None)
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Yield ``(outputs, batch_index, batch)`` per batch of
        forward-only prediction, with padding rows stripped."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad]
                       for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Forward the whole ``eval_data`` and return the collected
        outputs — one NDArray when the net has a single output and
        ``merge_batches`` (default), else a list (of lists).  A bare
        NDArray/numpy input is wrapped in an NDArrayIter first."""
        assert self.binded and self.params_initialized
        if isinstance(eval_data, (nd.NDArray, np.ndarray)):
            from ..io.io import NDArrayIter
            eval_data = NDArrayIter(eval_data)
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad or 0
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None,
            kvstore="local", optimizer="sgd",
            optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, resume_data_state=None):
        """Train the module (reference base_module.py:368).

        ``resume_data_state`` — an iterator-state envelope from
        ``model.load_latest_checkpoint(...).data_state`` /
        ``Module.load_latest(...).data_state``: it is loaded into
        ``train_data`` before the first batch, so a killed run resumes
        MID-epoch with zero replayed and zero skipped records (pair
        with ``begin_epoch`` = the checkpoint's epoch;
        docs/architecture/data_pipeline.md)."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform
        if initializer is None:
            initializer = Uniform(0.01)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        # dist training: shard the record plan by this worker's kvstore
        # rank/size (a no-op for iterators without set_partition or when
        # the user partitioned explicitly — auto never overrides)
        kv = getattr(self, "_kvstore", None)
        if kv is not None and getattr(kv, "num_workers", 1) > 1 and \
                hasattr(train_data, "set_partition"):
            train_data.set_partition(kv.rank, kv.num_workers, auto=True)

        if resume_data_state is None:
            # hands-off crash resume: a (re)launched worker under
            # tools/launch.py --auto-resume picks up the latest .dstate
            # envelope for the exported prefix without the training
            # script threading it by hand
            from ..base import get_env
            auto_prefix = str(get_env("MXNET_AUTO_RESUME") or "")
            if auto_prefix:
                from ..model import latest_checkpoint
                epoch = latest_checkpoint(auto_prefix)
                if epoch is not None and epoch != begin_epoch:
                    # fast-forwarding the iterator to another epoch's
                    # frontier under fresh params would silently skip
                    # training data — the frontier only pairs with the
                    # checkpoint it was saved beside
                    logging.warning(
                        "ignoring MXNET_AUTO_RESUME=%s: latest "
                        "checkpoint is epoch %d but fit begins at "
                        "epoch %d — load params via Module.load_latest"
                        " and pass begin_epoch to resume it",
                        auto_prefix, epoch, begin_epoch)
                elif epoch is not None:
                    from ..data.checkpoint import load_data_state
                    resume_data_state = load_data_state(auto_prefix,
                                                        epoch)
        if resume_data_state is not None:
            from ..data.checkpoint import load_state_into
            load_state_into(train_data, resume_data_state)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)

        # overlapped device input staging (io/stager.py): batch t+1
        # uploads while step t computes.  Wrapped AFTER init_optimizer
        # so the module knows its target placement (fused-trainer
        # sharding vs executor device); identity when MXNET_IO_STAGE=0
        # or the module has no staging target.
        source_data, train_data = train_data, \
            self._stage_train_data(train_data)
        try:
            self._fit_epochs(train_data, eval_data, eval_metric,
                             validation_metric, epoch_end_callback,
                             batch_end_callback, eval_end_callback,
                             eval_batch_end_callback, monitor,
                             begin_epoch, num_epoch)
        finally:
            if train_data is not source_data:
                train_data.close()

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, monitor, begin_epoch,
                    num_epoch):
        """The fit epoch/batch loop (split out so ``fit`` can scope the
        input stager's lifetime around it)."""
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nbatch = 0
            data_iter = iter(train_data)
            while True:
                # step-phase attribution (profiler.phase): data_wait =
                # blocked on the iterator (the stager hides source
                # latency here), compute = step dispatch, metric_fetch =
                # metric update incl. any host fetch, callback = the
                # caller's batch-end code (one that fetches waits here,
                # not in the next data_wait).
                with profiler.phase("data_wait") as wait:
                    data_batch = next(data_iter, None)
                    if data_batch is None:
                        wait.cancel()   # the epoch's end is no wait
                if data_batch is None:
                    break
                if monitor is not None:
                    monitor.tic()
                with profiler.phase("compute"):
                    self.prepare(data_batch)
                    self.forward_backward(data_batch)
                    self.update()
                with profiler.phase("metric_fetch"):
                    self.update_metric(eval_metric, data_batch.label)
                profiler.mark_step()
                if monitor is not None:
                    monitor.toc_print()
                if batch_end_callback is not None:
                    batch_end_params = _BatchEndParam(
                        epoch=epoch, nbatch=nbatch, eval_metric=eval_metric,
                        locals=locals())
                    with profiler.phase("callback"):
                        for callback in _as_list(batch_end_callback):
                            callback(batch_end_params)
                nbatch += 1

            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))

            arg_params_, aux_params_ = self._epoch_end_param_sync()
            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params_, aux_params_)

            if eval_data:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                     name, val)

            train_data.reset()

    # -- abstract ----------------------------------------------------------
    @property
    def data_names(self):
        """Names of the data inputs this module consumes."""
        raise NotImplementedError()

    @property
    def output_names(self):
        """Names of the outputs this module produces."""
        raise NotImplementedError()

    @property
    def data_shapes(self):
        """Bound data DataDescs (valid after ``bind``)."""
        raise NotImplementedError()

    @property
    def label_shapes(self):
        """Bound label DataDescs (None/[] when the module takes no
        labels)."""
        raise NotImplementedError()

    @property
    def output_shapes(self):
        """(name, shape) of each output under the bound input
        shapes."""
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        """Gradients w.r.t. the data inputs from the last ``backward``
        (requires binding with ``inputs_need_grad=True``)."""
        raise NotImplementedError()

    @property
    def symbol(self):
        """The Symbol this module computes (None for python-defined
        modules)."""
        return self._symbol

    def get_params(self):
        """Return ``(arg_params, aux_params)``: name -> NDArray dicts
        of the current parameters and auxiliary states."""
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        """Initialize parameters: values from ``arg_params`` /
        ``aux_params`` when given, else drawn from ``initializer``
        (missing names allowed only with ``allow_missing``).  A no-op
        when already initialized unless ``force_init``."""
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        """Assign parameter values directly (an ``init_params`` with
        no initializer)."""
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Allocate the executor(s) for the given input shapes.  On
        TPU this is where the fused forward/backward XLA program is
        traced and compiled; ``shared_module`` reuses another module's
        parameter/pool memory (bucketing), ``grad_req`` in
        write/add/null controls gradient accumulation."""
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Create the optimizer and hook it to the kvstore (by name
        or instance); must follow ``bind`` + ``init_params``."""
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        """Run the forward pass on one ``DataBatch``
        (``is_train=None`` follows the bound ``for_training`` flag).
        Outputs are read back with ``get_outputs``."""
        raise NotImplementedError()

    def backward(self, out_grads=None):
        """Run the backward pass (``out_grads`` seeds the head
        gradients when the net does not end in a loss op)."""
        raise NotImplementedError()

    def update(self):
        """Apply one optimizer step to the parameters from the
        gradients accumulated by the last ``backward``."""
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        """Outputs of the last ``forward`` as a list of NDArrays
        (``merge_multi_context`` concatenates per-device shards)."""
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        """Feed the last forward's outputs and ``labels`` into
        ``eval_metric`` (device-side accumulation when the metric
        supports it)."""
        raise NotImplementedError()

    def install_monitor(self, mon):
        """Attach a ``Monitor`` that records intermediate
        activations/gradients for debugging."""
        raise NotImplementedError()

    def get_states(self, merge_multi_context=True):
        """Values of the module's state arrays (reference
        base_module.py:674); modules without states return []."""
        assert self.binded and self.params_initialized
        return []

    def set_states(self, states=None, value=None):
        """Set state arrays (reference base_module.py:698)."""
        assert self.binded and self.params_initialized
        assert states is None and value is None, \
            "this module has no states"

    def prepare(self, data_batch):
        """Per-batch preparation hook, called by the fit loop before
        ``forward_backward`` (reference base_module.py:719; a no-op for
        dense modules — BucketingModule binds the batch's bucket here)."""

    def _stage_train_data(self, train_data):
        """Hook for overlapped device input staging: return an iterator
        whose batches are already placed on device (``io.DeviceStager``)
        or ``train_data`` unchanged.  Base modules have no placement
        target, so the default is the identity."""
        return train_data

    def _epoch_end_param_sync(self):
        """Epoch-end device->host sync + device write-back (reference
        fit's ``get_params``/``set_params`` pair, base_module.py:460-461).
        The write-back re-broadcasts the host-averaged state — per-device
        BatchNorm moving stats diverge under multi-executor data
        parallelism and this is what reconverges them each epoch.
        Subclasses whose device state cannot diverge (one compiled mesh
        program with replicated aux) override to skip the re-upload."""
        arg_params_, aux_params_ = self.get_params()
        self.set_params(arg_params_, aux_params_)
        return arg_params_, aux_params_


class _BatchEndParam:
    def __init__(self, epoch, nbatch, eval_metric, locals):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = locals


BatchEndParam = _BatchEndParam
