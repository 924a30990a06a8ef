"""DataParallelExecutorGroup: one executor per device, batch sliced across.

Reference: ``python/mxnet/module/executor_group.py:77-648`` —
``decide_slices`` (:207), per-device ``simple_bind`` with shared memory
(:537), forward fan-out, backward, gradient landing in per-exec grad arrays
for KVStore reduction.

TPU note: with a single TPU context this degenerates to one fused-XLA
executor.  For multi-device training the group is a thin frontend over
the ONE shared SPMD step program (``parallel/spmd.py``): when Module
enables it (``enable_spmd``), forward_backward+update run as a single
jitted fwd+bwd+in-graph-update program over the contexts' mesh —
gradient reduction is an XLA all-reduce inside the step and parameters
stay device-resident — instead of the per-device replication loop +
host updater below.  ``MXNET_SPMD=0`` (or any setup the single program
cannot express: monitor, explicit backward, grad_req!='write', states,
input grads, dist kvstore) keeps full reference replication semantics
(works over cpu/tpu context lists, as the reference test suite does
with cpu stand-ins).
"""
from __future__ import annotations

import logging

import numpy as np

from .. import ndarray as nd
from ..base import MXNetError, hot_path
from ..io.io import DataDesc


def _split_input_slice(batch_size, work_load_list):
    """Slice the batch by workload (reference executor_manager.py:14).

    Floors per-device counts then distributes the remainder, so an
    indivisible batch never produces an empty slice (the reference raises
    'Too many slices' there; giving the first devices one extra row keeps
    every executor non-empty)."""
    total = sum(work_load_list)
    exact = [batch_size * w / total for w in work_load_list]
    batch_num_list = [int(e) for e in exact]
    rem = batch_size - sum(batch_num_list)
    by_frac = sorted(range(len(exact)),
                     key=lambda i: exact[i] - batch_num_list[i],
                     reverse=True)
    for i in range(rem):
        batch_num_list[by_frac[i]] += 1
    if min(batch_num_list) == 0:
        raise MXNetError(
            "Too many slices: batch size %d cannot cover %d devices"
            % (batch_size, len(work_load_list)))
    slices = []
    start = 0
    for n in batch_num_list:
        slices.append(slice(start, start + n))
        start += n
    return slices


def _batched0(desc, batch_size):
    """Is this input batched along axis 0 with the group batch size?

    A desc whose layout carries no 'N' (e.g. layout="") is explicitly
    non-batch; a leading dim differing from the batch size (rcnn's (R,5)
    rois next to (B,...) images) is treated the same.  Both replicate
    whole instead of slicing."""
    from ..io.io import DataDesc
    axis = DataDesc.get_batch_axis(getattr(desc, "layout", None))
    shape = desc.shape if hasattr(desc, "shape") else desc[1]
    return axis == 0 and len(shape) > 0 and shape[0] == batch_size


def _load_general(data, targets):
    """Copy list-of-batch-arrays into per-exec target arrays
    (reference executor_group.py:14-50).

    Device-resident sources are sliced and copied device-side: an
    ``asnumpy`` here would fetch the whole batch over the TPU
    interconnect every step and re-upload it."""
    for d_src, d_targets in zip(data, targets):
        dev_src = d_src._data if hasattr(d_src, "_data") else None
        np_src = None
        for slice_idx, target in d_targets:
            if dev_src is not None:
                start = slice_idx.start or 0
                full = start == 0 and (slice_idx.stop is None or
                                       slice_idx.stop >= dev_src.shape[0])
                target[:] = dev_src if full else dev_src[slice_idx]
            else:
                if np_src is None:
                    np_src = np.asarray(d_src)
                target[:] = np_src[slice_idx]


def _pack_global_batch(data_batch, data_descs, label_descs, label_names,
                       arg_shapes=None, fill_missing_labels=False):
    """{name: array} dict of one GLOBAL (unsliced) batch for the fused /
    SPMD step programs.

    batch.data follows the ITERATOR's provide_data order, which is what
    the module was bound with — not necessarily the constructor's
    data_names order (NDArrayIter sorts dict inputs).  Zipping
    constructor order against iterator order silently swaps same-shaped
    inputs (e.g. user/item in matrix factorization)."""
    def _names(descs):
        # descriptors may be DataDesc or classic (name, shape) tuples
        return [d.name if hasattr(d, "name") else d[0] for d in descs]

    provide = getattr(data_batch, "provide_data", None)
    dnames = _names(provide if provide else data_descs)
    batch = {}
    for name, arr in zip(dnames, data_batch.data):
        batch[name] = arr
    labels = getattr(data_batch, "label", None) or []
    provide_l = getattr(data_batch, "provide_label", None)
    lnames = (_names(provide_l) if provide_l
              else _names(label_descs or []) or list(label_names))
    for name, arr in zip(lnames, labels):
        batch[name] = arr
    if fill_missing_labels:
        # forward-only consumers (score/predict through a training
        # symbol) may omit labels the traced program still takes as
        # arguments; zeros keep the avals stable without affecting
        # outputs at is_train=False
        for name in label_names:
            if name not in batch and arg_shapes and name in arg_shapes:
                batch[name] = nd.zeros(arg_shapes[name])
    return batch


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", state_names=None, compute_dtype=None):
        """``compute_dtype='bfloat16'`` threads the mixed-precision
        policy into each bound Executor (fp32 master weights, compute-
        dtype MXU math); labels are pinned to their master dtype."""
        # SPMD frontend state (``enable_spmd``): the embedded trainer
        # holding device-resident params/opt-state over the contexts'
        # mesh, the packed global batch a forward_backward stashed for
        # the next ``spmd_step``, and that step's outputs.  While the
        # trainer is live the per-exec arrays below are STALE mirrors;
        # ``disable_spmd`` reconverges them.
        self._spmd = None
        self._spmd_batch = None
        self._spmd_outputs = None
        # Module hook: rebuild the host kvstore/updater (with optimizer
        # state carried over) when the group has to leave SPMD mode
        self.on_spmd_disable = None
        self.symbol = symbol
        self.contexts = contexts
        self.compute_dtype = compute_dtype
        self.workload = workload or [1] * len(contexts)
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.logger = logger
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.state_names = list(state_names or [])
        self.execs = []
        self.data_shapes = None
        self.label_shapes = None
        self.slices = None
        self.shared_group = shared_group
        # called before executors run a forward: Module points this at
        # kvstore.flush so lazily-issued weight pulls (the async dist
        # pipeline) resolve exactly when the next forward binds the
        # parameters — never later
        self.pre_forward_sync = None

        if isinstance(grad_req, str):
            self.grad_req = {}
            for k in self.arg_names:
                if k in self.param_names:
                    self.grad_req[k] = ("null" if k in self.fixed_param_names
                                        or not for_training else grad_req)
                elif k in [d.name for d in data_shapes]:
                    self.grad_req[k] = grad_req if inputs_need_grad else \
                        "null"
                else:
                    self.grad_req[k] = "null"
        else:
            self.grad_req = dict(grad_req)

        self.bind_exec(data_shapes, label_shapes, shared_group)

    # ------------------------------------------------------------------
    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        self.data_shapes = data_shapes
        self.label_shapes = label_shapes
        batch_size = data_shapes[0].shape[
            DataDesc.get_batch_axis(getattr(data_shapes[0], "layout",
                                            "NCHW"))]
        self.batch_size = batch_size
        self.slices = _split_input_slice(batch_size, self.workload)
        self.execs = []
        for i, ctx in enumerate(self.contexts):
            islice = self.slices[i]
            n_i = islice.stop - islice.start
            shapes = {}
            # only inputs batched along axis 0 with the data batch size
            # are sliced across devices; others (rcnn's (R,5) rois, descs
            # whose layout has no 'N') are replicated whole on every exec
            for d in data_shapes:
                shapes[d.name] = ((n_i,) + tuple(d.shape[1:])
                                  if _batched0(d, batch_size)
                                  else tuple(d.shape))
            if label_shapes:
                for l in label_shapes:
                    shapes[l.name] = ((n_i,) + tuple(l.shape[1:])
                                      if _batched0(l, batch_size)
                                      else tuple(l.shape))
            keep = tuple(l.name for l in (label_shapes or []))
            ex = self.symbol.simple_bind(ctx, grad_req=self.grad_req,
                                         compute_dtype=self.compute_dtype,
                                         keep_dtype=keep, **shapes)
            if shared_group is not None and i < len(shared_group.execs):
                # Share parameter/aux NDArray handles with the shared group
                # (reference: shared memory pool in InitDataEntryMemory;
                # here handle-sharing makes cross-bucket updates visible
                # with zero copies, since executors read handles per call).
                src = shared_group.execs[i]
                for name in self.param_names:
                    if name in ex.arg_dict and name in src.arg_dict and \
                            ex.arg_dict[name].shape == \
                            src.arg_dict[name].shape:
                        ex.arg_arrays[ex._arg_names.index(name)] = \
                            src.arg_dict[name]
                for name in self.aux_names:
                    if name in ex.aux_dict and name in src.aux_dict and \
                            ex.aux_dict[name].shape == \
                            src.aux_dict[name].shape:
                        ex.aux_arrays[ex._aux_names.index(name)] = \
                            src.aux_dict[name]
            self.execs.append(ex)

        self.data_names = [d.name for d in data_shapes]
        self.label_names = [l.name for l in (label_shapes or [])]
        self._make_arrays()

    def _make_arrays(self):
        def _in_slices(descs, name):
            # non-batch inputs load whole on every exec
            desc = {d.name: d for d in descs}[name]
            if _batched0(desc, self.batch_size):
                return self.slices
            return [slice(0, desc.shape[0])] * len(self.execs)

        self.data_arrays = [
            [(_in_slices(self.data_shapes, name)[i], e.arg_dict[name])
             for i, e in enumerate(self.execs)]
            for name in self.data_names if name in self.arg_names]
        self.label_arrays = [
            [(_in_slices(self.label_shapes or [], name)[i],
              e.arg_dict[name])
             for i, e in enumerate(self.execs)]
            for name in self.label_names if name in self.arg_names]
        self.param_arrays = [
            [e.arg_dict[name] for e in self.execs]
            for name in self.param_names if name in self.arg_names]
        self.grad_arrays = [
            [e.grad_dict.get(name) for e in self.execs]
            for name in self.param_names if name in self.arg_names] \
            if self.for_training else []
        self.aux_arrays = [
            [e.aux_dict[name] for e in self.execs]
            for name in self.aux_names]
        data_names_set = set(self.data_names)
        if self.inputs_need_grad:
            self.input_grad_arrays = [
                [e.grad_dict.get(name) for e in self.execs]
                for name in self.data_names]

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and \
                label_shapes == self.label_shapes:
            return
        if self._spmd is not None:
            # recompile at the new shapes over the SAME device-resident
            # state (share_state_with: the program cache makes this one
            # lookup when the shape was seen before); a batch the mesh
            # does not divide falls back to replication
            batch0 = data_shapes[0].shape[
                DataDesc.get_batch_axis(getattr(data_shapes[0], "layout",
                                                "NCHW"))]
            if batch0 % len(self.contexts) == 0:
                new = self._build_spmd_trainer(
                    data_shapes, label_shapes, self._spmd.optimizer,
                    share_state_with=self._spmd)
                self._spmd.clear_placement_cache()
                self._spmd = new
                self._spmd_batch = None
                self._spmd_outputs = None
            else:
                self.disable_spmd("reshape to an inexpressible shape")
        self.bind_exec(data_shapes, label_shapes, reshape=True)

    # -- SPMD frontend -------------------------------------------------
    # One shared step program (parallel/spmd.py) instead of the
    # per-device replication loop: train dispatch becomes ONE jitted
    # fwd+bwd+in-graph-update over the contexts' mesh, gradients reduce
    # as an XLA all-reduce inside the step, and parameters/optimizer
    # state stay device-resident across the run.  Module enables this
    # for qualifying multi-device setups; anything the one program
    # cannot express hands back to full replication semantics via
    # ``disable_spmd``.
    @property
    def spmd_active(self):
        """Is train dispatch currently routed through the shared SPMD
        step program?"""
        return self._spmd is not None

    @property
    def spmd_trainer(self):
        """The embedded state-holding trainer while SPMD is active
        (optimizer-state interop: Updater.states layout via its
        ``get/set_updater_states``), else None."""
        return self._spmd

    def _build_spmd_trainer(self, data_shapes, label_shapes, optimizer,
                            share_state_with=None):
        """Embedded ``DataParallelTrainer`` over this group's contexts —
        the state holder whose compiled step comes from the shared
        program cache (so the fused-Module frontend and this group
        frontend run the SAME executable for the same setup)."""
        from ..parallel.dp import DataParallelTrainer
        from ..parallel.mesh import mesh_for_contexts
        mesh = (share_state_with.mesh if share_state_with is not None
                else mesh_for_contexts(self.contexts))
        data_map = {d.name: tuple(d.shape) for d in data_shapes}
        label_map = {d.name: tuple(d.shape)
                     for d in (label_shapes or [])}
        return DataParallelTrainer(
            self.symbol, data_map, label_map or None, mesh=mesh,
            optimizer=optimizer, compute_dtype=self.compute_dtype,
            fixed_params=tuple(self.fixed_param_names),
            share_state_with=share_state_with)

    def enable_spmd(self, optimizer, arg_params, aux_params):
        """Route this group's training through the one SPMD step
        program, seeding the device-resident state from the given host
        params.  Module qualifies the setup first
        (``_spmd_optimizer``); a mesh or compile error here is a bug to
        see and propagates."""
        trainer = self._build_spmd_trainer(
            self.data_shapes, self.label_shapes, optimizer)
        if self._spmd is not None:
            # force re-init: retire the previous trainer's pinned
            # input-placement buffers before swapping it out
            self._spmd.clear_placement_cache()
        trainer.set_params(arg_params, aux_params)
        self._spmd = trainer
        self._spmd_batch = None
        self._spmd_outputs = None

    def disable_spmd(self, reason):
        """Leave the SPMD step program: reload the per-exec param/aux
        arrays from the trainer's device state and notify Module (the
        ``on_spmd_disable`` hook rebuilds the host kvstore/updater with
        optimizer state carried over), so training continues under full
        replication semantics."""
        trainer = self._spmd
        if trainer is None:
            return
        self._spmd = None
        self._spmd_batch = None
        self._spmd_outputs = None
        trainer.clear_placement_cache()
        self.logger.info("leaving SPMD step program (%s)", reason)
        args, aux = trainer.get_params()
        self.set_params(args, aux)
        if self.on_spmd_disable is not None:
            self.on_spmd_disable(trainer, reason)

    @hot_path
    def spmd_step(self):
        """Run the one compiled train step (fwd+bwd+all-reduce+update)
        on the batch the last ``forward_backward`` stashed; Module's
        ``update`` dispatches here instead of the host updater."""
        batch = self._spmd_batch
        assert batch is not None, "call forward_backward before update"
        outs = self._spmd.step(batch)
        self._spmd_outputs = [nd.NDArray(o) for o in outs]
        self._spmd_batch = None
        return self._spmd_outputs

    def _spmd_get_outputs(self):
        if self._spmd_outputs is None:
            assert self._spmd_batch is not None, "no forward has been run"
            # update() not called yet: forward-only outputs for the
            # stashed batch (params unchanged, so the later step still
            # computes the same gradients)
            outs = self._spmd.predict(self._spmd_batch)
            self._spmd_outputs = [nd.NDArray(o) for o in outs]
        return self._spmd_outputs

    # ------------------------------------------------------------------
    def set_params(self, arg_params, aux_params):
        if self._spmd is not None:
            # the trainer owns the live state; execs reconverge on
            # disable_spmd
            self._spmd.set_params(arg_params, aux_params)
            return
        for ex in self.execs:
            ex.copy_params_from(arg_params, aux_params,
                                allow_extra_params=True)

    def get_params(self, arg_params, aux_params):
        """Average params over devices into the given dicts (reference
        sync_params_from_devices path)."""
        if self._spmd is not None:
            args, aux = self._spmd.get_params()
            for name, v in args.items():
                arg_params[name] = v
            for name, v in aux.items():
                aux_params[name] = v
            return
        for name, block in zip(self.param_names, self.param_arrays):
            weight = sum(w.asnumpy() for w in block) / len(block)
            arg_params[name] = nd.array(weight)
        for name, block in zip(self.aux_names, self.aux_arrays):
            weight = sum(w.asnumpy() for w in block) / len(block)
            aux_params[name] = nd.array(weight)

    # ------------------------------------------------------------------
    def _load_batch(self, data_batch):
        _load_general(data_batch.data, self.data_arrays)
        if self.for_training and getattr(data_batch, "label", None):
            if self.label_arrays:
                _load_general(data_batch.label, self.label_arrays)

    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = self.for_training
        if self._spmd is not None:
            if is_train:
                # explicit per-op training access is outside the one-
                # program contract; hand back to replication
                self.disable_spmd("explicit forward(is_train=True)")
            else:
                batch = _pack_global_batch(
                    data_batch, self.data_shapes, self.label_shapes,
                    self.label_names, arg_shapes=self._spmd._arg_shapes,
                    fill_missing_labels=True)
                outs = self._spmd.predict(batch)
                self._spmd_outputs = [nd.NDArray(o) for o in outs]
                # a pending forward_backward stash stays valid: update()
                # recomputes from it with unchanged params
                return
        self._load_batch(data_batch)
        if self.pre_forward_sync is not None:
            self.pre_forward_sync()
        if not is_train and getattr(data_batch, "label", None) and \
                self.label_arrays:
            _load_general(data_batch.label, self.label_arrays)
        for ex in self.execs:
            ex.forward(is_train=is_train)

    def backward(self, out_grads=None):
        if not self.for_training:
            raise MXNetError("re-bind with for_training=True to run "
                             "backward")
        if self._spmd is not None:
            self.disable_spmd("explicit backward()")
        for i, ex in enumerate(self.execs):
            if out_grads is None:
                ex.backward()
            else:
                sliced = [g.slice(self.slices[i].start, self.slices[i].stop)
                          for g in out_grads]
                ex.backward(sliced)

    def forward_backward(self, data_batch):
        """Fused train step: one XLA program per device (forward+backward)."""
        if self._spmd is not None:
            # stash the GLOBAL batch; the whole fwd+bwd+all-reduce+update
            # runs as one program at ``spmd_step`` (Module.update), so
            # weights still change only at update — skip-step patterns
            # (NaN guards) keep reference semantics
            self._spmd_batch = _pack_global_batch(
                data_batch, self.data_shapes, self.label_shapes,
                self.label_names)
            self._spmd_outputs = None
            return
        self._load_batch(data_batch)
        if self.pre_forward_sync is not None:
            self.pre_forward_sync()
        for ex in self.execs:
            ex.forward_backward()

    # ------------------------------------------------------------------
    @staticmethod
    def _merge_multi_context(groups):
        """Per-name lists of per-executor arrays -> batch-concatenated
        arrays (the kvstore-free merge every getter shares).

        Per-exec arrays are committed to DIFFERENT devices; an eager
        concatenate over mixed devices is a jax error, so parts are
        gathered onto the first exec's device before merging."""
        import jax

        def _gather(parts):
            dev = next(iter(parts[0]._data.devices()))
            datas = [p._data if p._data.devices() == {dev}
                     else jax.device_put(p._data, dev) for p in parts]
            return nd.NDArray(jax.numpy.concatenate(datas, axis=0))

        return [_gather(parts) if len(parts) > 1 else parts[0]
                for parts in groups]

    def get_outputs(self, merge_multi_context=True):
        if self._spmd is not None:
            outs = self._spmd_get_outputs()
            return outs if merge_multi_context else [[o] for o in outs]
        outputs = [[ex.outputs[i] for ex in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            return self._merge_multi_context(outputs)
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [[e.grad_dict[name] for e in self.execs]
                 for name in self.data_names]
        if merge_multi_context:
            return self._merge_multi_context(grads)
        return grads

    def get_states(self, merge_multi_context=True):
        """Current values of the state arrays (reference
        executor_group.py:417 — states are batch-sliced inputs the caller
        carries across batches, e.g. stateful-RNN hidden state)."""
        states = [[e.arg_dict[name] for e in self.execs]
                  for name in self.state_names]
        if merge_multi_context:
            return self._merge_multi_context(states)
        return states

    def set_states(self, states=None, value=None):
        """Set state arrays from merged values or a scalar fill
        (reference executor_group.py:438)."""
        if states is not None:
            assert value is None, "only one of states/value"
            for name, merged in zip(self.state_names, states):
                for i, ex in enumerate(self.execs):
                    islice = self.slices[i]
                    src = merged[i] if isinstance(merged, (list, tuple)) \
                        else merged.slice(islice.start, islice.stop)
                    ex.arg_dict[name][:] = src
        else:
            assert value is not None, "one of states/value required"
            for name in self.state_names:
                for ex in self.execs:
                    ex.arg_dict[name][:] = value

    def update_metric(self, eval_metric, labels):
        if self._spmd is not None:
            outs = self._spmd_get_outputs()
            # one global output set, not per-exec slices; device-side
            # accumulation keeps the hot loop free of host syncs (the
            # fused frontend's policy), host update as fallback
            if not eval_metric.update_device(labels, outs):
                eval_metric.update(labels, outs)
            return
        for i, ex in enumerate(self.execs):
            islice = self.slices[i]
            labels_slice = [label.slice(islice.start, islice.stop)
                            if label.shape[0] == self.batch_size else label
                            for label in labels]
            eval_metric.update(labels_slice, ex.outputs)

    def install_monitor(self, mon):
        if self._spmd is not None:
            # per-op intermediate access needs real executors
            self.disable_spmd("monitor installed")
        for ex in self.execs:
            mon.install(ex)
