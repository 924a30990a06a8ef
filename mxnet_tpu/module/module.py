"""Module: the standard intermediate-level training module.

Reference: ``python/mxnet/module/module.py`` — bind via
DataParallelExecutorGroup, init_params with InitDesc dispatch,
init_optimizer with kvstore routing (``_create_kvstore``), update via
kvstore push/pull with layer-priority overlap (``model.py:88-118``),
save/load_checkpoint with optimizer states.

TPU fast path: when the training setup is expressible as one compiled XLA
program — local/device kvstore semantics, ``grad_req='write'``, an
optimizer with an in-graph equivalent, uniform workload — ``init_optimizer``
routes ``fit``'s forward_backward/update through a fused
``parallel.DataParallelTrainer`` step (forward+backward+psum+update in one
program over the device mesh), which is what makes ``Module.fit`` hit the
benchmark numbers.  Anything that needs per-op access (monitor, explicit
``forward(is_train=True)``/``backward()``, shared bind, dist kvstore)
keeps or falls back to full executor-group reference semantics.
"""
from __future__ import annotations

import logging
import os
import pickle

from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError, get_env
from ..context import cpu, current_context
from ..initializer import InitDesc, Uniform
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None,
                 compute_dtype=None):
        """``compute_dtype='bfloat16'`` (TPU extension) trains in mixed
        precision: fp32 master weights and optimizer state, bf16 MXU
        compute — the role the reference's ``*_fp16`` symbol variants
        play on GPU.  Applied on BOTH the fused fast path
        (``parallel/dp.py``) and the executor-group fallback (the
        policy threads through ``Executor.bind``), so checkpoints stay
        fp32 either way."""
        super().__init__(logger=logger)
        self._compute_dtype = compute_dtype
        if context is None:
            context = [current_context()]
        if not isinstance(context, (list, tuple)):
            context = [context]
        self._context = list(context)
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names else []
        label_names = list(label_names) if label_names else []
        state_names = list(state_names) if state_names else []
        fixed_param_names = list(fixed_param_names) if fixed_param_names \
            else []
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None

        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

        self._fused = None
        self._fused_disabled = False
        self._fused_batch = None
        self._fused_outputs = None
        self._fused_stash = None     # trainer kept across transient defuse
        self._on_defuse = None       # BucketingModule coordination hook
        self._monitor = None
        self._grad_req = "write"
        self._kvstore_arg = None

    # -- checkpointing -----------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a ``save_checkpoint`` prefix/epoch
        (symbol + params; optimizer states restored lazily at
        ``init_optimizer`` when requested)."""
        if load_optimizer_states:
            states = "%s-%04d.states" % (prefix, epoch)
            if not os.path.exists(states):
                # fail HERE, not deep inside a later fit's
                # init_optimizer: this checkpoint was saved without
                # save_optimizer_states (e.g. the model-level
                # do_checkpoint callback — use module_checkpoint /
                # batch_checkpoint for states-carrying saves)
                raise MXNetError(
                    "checkpoint epoch %d under %r has no optimizer "
                    "states (%s missing); it was saved without "
                    "save_optimizer_states — load with "
                    "load_optimizer_states=False, or checkpoint via "
                    "module_checkpoint/batch_checkpoint"
                    % (epoch, prefix, states))
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    @staticmethod
    def load_latest(prefix, load_optimizer_states=False, **kwargs):
        """Auto-resume: load the newest epoch checkpointed under
        ``prefix``.  Returns ``(module, epoch)`` — with the mid-epoch
        iterator state, if one was saved beside the params, as
        ``.data_state`` on the returned bundle (pass it to
        ``fit(resume_data_state=...)``) — or None when no checkpoint
        exists yet; the caller starts training from epoch 0 then."""
        from ..data.checkpoint import load_data_state
        from ..model import CheckpointBundle, latest_checkpoint
        epoch = latest_checkpoint(prefix)
        if epoch is None:
            return None
        return CheckpointBundle(
            (Module.load(prefix, epoch, load_optimizer_states,
                         **kwargs), epoch),
            load_data_state(prefix, epoch))

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        data_state=None):
        """Write ``prefix-symbol.json`` + ``prefix-NNNN.params`` (and
        ``.states`` when asked) — the reference checkpoint format.
        ``data_state`` persists an iterator chain's ``state_dict()``
        beside the params (versioned ``.dstate`` envelope, written
        after them) so training can resume mid-epoch; None removes any
        stale envelope for this epoch."""
        from ..data.checkpoint import save_data_state
        # the envelope is the checkpoint set's COMMIT POINT: any stale
        # one is removed BEFORE the params/state files are overwritten
        # and the new one is written last, after the (asynchronous)
        # params write landed — a kill anywhere inside the save leaves
        # a no-envelope set (resume falls back to the epoch head, which
        # never skips data), never a frontier paired with files from a
        # different save
        save_data_state(prefix, epoch, None)
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info("Saved optimizer state to \"%s\"", state_name)
        if data_state is not None:
            nd._wait_pending_write(param_name)
        save_data_state(prefix, epoch, data_state)
        logging.info("Saved checkpoint to \"%s\"", param_name)

    def save_params(self, fname):
        """Save current parameters (``arg:``/``aux:`` key convention,
        interoperable with reference ``.params`` files)."""
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        """Load parameters written by ``save_params``."""
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def save_optimizer_states(self, fname):
        """Pickle the optimizer state (momentum etc.) to ``fname``;
        layout matches update_on_kvstore (shared state per param).
        Writes are atomic (temp file + rename) so a crash mid-save never
        corrupts the previous states file."""
        from ..base import atomic_write
        assert self.optimizer_initialized
        trainer = self._one_program_trainer()
        if trainer is not None:
            # Updater.states layout keyed by plain param index — the
            # update_on_kvstore layout, which the one-program paths
            # semantically are (one shared update per parameter).  Like
            # the reference, files are not portable to the
            # update_on_kvstore=False multi-device host-updater layout
            # (index*num_device+k).  Written as the v2 envelope so the
            # optimizer's update counters (Adam bias-correction
            # schedule) resume too.
            from ..optimizer import _state_to_host, pack_updater_states
            states = {i: _state_to_host(v) for i, v in
                      trainer.get_updater_states().items()}
            with atomic_write(fname, "wb") as fout:
                fout.write(pack_updater_states(states, self._optimizer))
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with atomic_write(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """Restore optimizer state written by
        ``save_optimizer_states``."""
        assert self.optimizer_initialized
        trainer = self._one_program_trainer()
        if trainer is not None:
            from ..optimizer import unpack_updater_states
            with open(fname, "rb") as f:
                states, counts, num_update = \
                    unpack_updater_states(f.read())
            trainer.set_updater_states(states)
            if counts is not None:
                self._optimizer._index_update_count = dict(counts)
                self._optimizer.num_update = num_update
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())

    # -- properties --------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        """Names of the label inputs (may be empty for label-free
        nets)."""
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        outputs = self._exec_group.get_outputs()
        return list(zip(self._output_names, [o.shape for o in outputs]))

    # -- params ------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if initializer is None and (arg_params is None or force_init is
                                    False):
            initializer = Uniform(0.01)

        if self._arg_params is None:
            self._arg_params = {
                name: nd.zeros(ex0.shape, dtype=str(ex0.dtype))
                for name, ex0 in self._param_shapes().items()}
        if self._aux_params is None:
            self._aux_params = {
                name: nd.zeros(shape, dtype=str(dtype))
                for name, (shape, dtype) in self._aux_shapes().items()}

        attrs = self._symbol.attr_dict()
        for name, arr in self._arg_params.items():
            if arg_params is not None and name in arg_params:
                arr[:] = arg_params[name]
            else:
                if not allow_missing and arg_params is not None and \
                        initializer is None:
                    raise RuntimeError("%s is not presented" % name)
                if initializer is not None:
                    desc = InitDesc(name, attrs.get(name))
                    initializer(desc, arr)
        for name, arr in self._aux_params.items():
            if aux_params is not None and name in aux_params:
                arr[:] = aux_params[name]
            else:
                if initializer is not None:
                    desc = InitDesc(name, attrs.get(name))
                    initializer(desc, arr)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)
        if self._fused is not None:
            self._fused.set_params(self._arg_params, self._aux_params)

    def _param_shapes(self):
        ex0 = self._exec_group.execs[0]
        return {name: ex0.arg_dict[name]
                for name in self._param_names}

    def _aux_shapes(self):
        ex0 = self._exec_group.execs[0]
        return {name: (ex0.aux_dict[name].shape, ex0.aux_dict[name].dtype)
                for name in self._aux_names}

    def _epoch_end_param_sync(self):
        """Epoch-end write-back policy (pinned by
        tests/test_module.py::test_epoch_end_param_sync_routing): the
        fused fast path AND single-device executor groups skip the
        device re-upload — fused state is one replicated program that
        cannot diverge per device, and a single device has nothing to
        reconverge, so the reference's set_params would re-upload every
        parameter unchanged (two full parameter-set transfers per epoch
        over a remote PJRT device).  Both sync down only.  Only
        MULTI-device executor groups keep the reference
        get_params/set_params pair — the host-averaged write-back is
        what reconverges per-device BatchNorm moving stats each
        epoch."""
        if (self._fused is not None or len(self._context) == 1 or
                (self._exec_group is not None and
                 self._exec_group.spmd_active)):
            # the SPMD step program keeps ONE sharded/replicated state —
            # nothing can diverge per device, so sync down only
            return self.get_params()
        return super()._epoch_end_param_sync()

    def _sync_params_from_devices(self):
        if self._fused is not None:
            self._sync_from_trainer(self._fused)
            return
        if self._kvstore is not None:
            # lazily-issued pulls must land before device params are read
            self._kvstore.flush()
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # -- binding -----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        self._grad_req = grad_req
        if shared_module is not None:
            # shared-memory bind (bucketing) keeps executor-group semantics
            self._fused_disabled = True

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = [x if hasattr(x, "name") else
                             _as_data_desc(x) for x in data_shapes]
        self._label_shapes = [x if hasattr(x, "name") else
                              _as_data_desc(x) for x in (label_shapes or [])]

        shared_group = None
        if shared_module is not None:
            assert shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names,
            compute_dtype=self._compute_dtype)

        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _reset_bind(self):
        if self._fused is not None:
            # cached input placements pin ~a batch of HBM per name
            self._fused.clear_placement_cache()
        if self._exec_group is not None and \
                self._exec_group.spmd_trainer is not None:
            self._exec_group.spmd_trainer.clear_placement_cache()
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused = None
        self._fused_batch = None
        self._fused_outputs = None

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind to new input shapes keeping the current parameters
        (new shapes trigger one fresh XLA compile, then cache)."""
        assert self.binded
        if self._fused is not None or self._exec_group.spmd_active:
            self._sync_params_from_devices()
        self._data_shapes = [x if hasattr(x, "name") else _as_data_desc(x)
                             for x in data_shapes]
        self._label_shapes = [x if hasattr(x, "name") else _as_data_desc(x)
                              for x in (label_shapes or [])]
        self._exec_group.reshape(self._data_shapes, self._label_shapes)
        self._exec_group.set_params(self._arg_params, self._aux_params)
        if self._fused is not None:
            # rebuild the compiled step for the new shapes, carrying
            # parameters and optimizer state over; if the new shapes no
            # longer qualify (e.g. batch not divisible across contexts),
            # fall back to full executor-group semantics
            old = self._fused
            old.clear_placement_cache()
            trainer = None
            batch = self._exec_group.batch_size
            if batch % len(self._context) == 0:
                states = old.get_updater_states()
                self._fused = None
                trainer = self._build_fused(old.optimizer)
                if trainer is not None:
                    trainer.set_updater_states(states)
            if trainer is not None:
                self._fused = trainer
            else:
                self._fused = old
                self._defuse("reshape incompatible with fused step")

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        self._kvstore_arg = kvstore
        if ((self._fused is not None or self._exec_group.spmd_active)
                and self._params_dirty):
            # force_init re-init: pull current device params back before
            # the trainer (and its optimizer state) is rebuilt
            self._sync_params_from_devices()
        fused_opt = self._fusible_optimizer(kvstore, optimizer,
                                            optimizer_params)
        if fused_opt is not None:
            trainer = self._build_fused(fused_opt)
            if trainer is not None:
                self._fused = trainer
                self._optimizer = fused_opt
                self._kvstore = None
                self._update_on_kvstore = False
                self._updater = None
                self.optimizer_initialized = True
                if self._preload_opt_states is not None:
                    self.load_optimizer_states(self._preload_opt_states)
                    self._preload_opt_states = None
                return

        # executor-group frontend over the ONE shared SPMD step program
        # (parallel/spmd.py): when the fused fast path is off
        # (MXNET_MODULE_FUSED=0) but the multi-device setup is still
        # expressible as a single program, training dispatches through
        # exec_group.spmd_step — XLA all-reduce inside the step, params
        # device-resident — instead of the per-device replication loop +
        # host updater below.  MXNET_SPMD=0 restores the classic path
        # bit-for-bit.
        spmd_opt = self._spmd_optimizer(kvstore, optimizer,
                                        optimizer_params)
        if spmd_opt is not None:
            self._exec_group.enable_spmd(spmd_opt, self._arg_params,
                                         self._aux_params)
            self._exec_group.on_spmd_disable = self._on_spmd_disable
            self._optimizer = spmd_opt
            self._kvstore = None
            self._update_on_kvstore = False
            self._updater = None
            self.optimizer_initialized = True
            if self._preload_opt_states is not None:
                self.load_optimizer_states(self._preload_opt_states)
                self._preload_opt_states = None
            return

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)

        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and \
                "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._exec_group.param_names))
            else:
                for k in range(len(self._context)):
                    idx2name.update(
                        {i * len(self._context) + k: n for i, n
                         in enumerate(self._exec_group.param_names)})
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None

        if kvstore:
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        # lazily-issued kvstore pulls must resolve exactly when the next
        # forward binds the parameters (async dist data plane)
        self._exec_group.pre_forward_sync = \
            kvstore.flush if kvstore is not None else None
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)

        self.optimizer_initialized = True

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- fused fast path ---------------------------------------------------
    @property
    def fused_trainer(self):
        """The :class:`~..parallel.dp.DataParallelTrainer` driving this
        module while the fused fast path is taken (fwd+bwd+update as ONE
        compiled step), else None — the read-only way to ask which path
        ``fit`` is on."""
        return self._fused

    def _fusible_optimizer(self, kvstore, optimizer, optimizer_params):
        """If the training setup qualifies for the fused in-graph
        fast path (``MXNET_MODULE_FUSED``), return the (possibly
        constructed) Optimizer instance; else None."""
        if not get_env("MXNET_MODULE_FUSED") or self._fused_disabled:
            return None
        return self._one_program_optimizer(kvstore, optimizer,
                                           optimizer_params)

    def _spmd_optimizer(self, kvstore, optimizer, optimizer_params):
        """Like ``_fusible_optimizer`` but for the executor-group SPMD
        frontend: multi-device only (a single device has no replication
        loop to delete), never under a shared bind (bucketing shares
        executor memory, not trainer state), and never with Custom host
        callbacks (they deadlock inside one donated program, same as the
        fused path)."""
        from ..parallel.spmd import spmd_enabled
        if not spmd_enabled() or len(self._context) == 1:
            return None
        # _fused_disabled is the module-level "keep reference executor
        # semantics" latch (shared binds, permanent defuse, tests
        # pinning the classic path) — it covers this frontend too
        if self._fused_disabled or self._exec_group.shared_group is not None:
            return None
        if self._symbol.has_custom_ops():
            return None
        return self._one_program_optimizer(kvstore, optimizer,
                                           optimizer_params)

    def _one_program_optimizer(self, kvstore, optimizer, optimizer_params):
        """If the training setup is expressible as ONE compiled step
        program, return the (possibly constructed) Optimizer instance;
        else None.  Shared qualification for the fused fast path and the
        executor-group SPMD frontend.

        Qualifying = local/device kvstore semantics (single process),
        grad_req='write', no monitor / input grads / states / shared bind,
        uniform workload, batch divisible across contexts, batch-major
        layouts, and an optimizer with an exact in-graph equivalent
        (parallel.ingraph_opt)."""
        from ..parallel.ingraph_opt import supports_ingraph
        if (self._monitor is not None or
                self._state_names or self.inputs_need_grad or
                not self.for_training or self._grad_req != "write"):
            return None
        kv_type = kvstore.type if hasattr(kvstore, "type") else kvstore
        if kv_type is not None and not isinstance(kv_type, str):
            return None
        # dist_mesh IS the one-program path: its reduction is the
        # in-graph collective, so the same fit script swaps PS for
        # collectives by string (docs/architecture/dist_mesh.md).  The
        # ps-backed dist_* types keep the classic kvstore loop.
        if kv_type is not None and "dist" in kv_type and \
                kv_type != "dist_mesh":
            return None
        if len(set(self._work_load_list)) > 1:
            return None
        if self._exec_group.batch_size % len(self._context) != 0:
            return None
        for desc in (self._data_shapes + (self._label_shapes or [])):
            layout = getattr(desc, "layout", None)
            if layout is not None and layout.find("N") != 0:
                return None
        batch_size = self._exec_group.batch_size
        if isinstance(optimizer, str):
            idx2name = dict(enumerate(self._exec_group.param_names))
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = 1.0 / batch_size
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        elif not isinstance(optimizer, opt.Optimizer):
            return None
        if not supports_ingraph(optimizer):
            return None
        return optimizer

    def _build_fused(self, optimizer, share_from=None):
        """Build the DataParallelTrainer over a mesh of this module's
        contexts, seeded with current params.  Returns None only for a
        graph with Custom ops (executor-group semantics); a mesh,
        compile or placement error in the fused step is a bug to see and
        propagates.  ``share_from`` makes the new trainer a shape
        variant over another trainer's state (bucketing: reference
        bucketing_module.py:302-330 shares executor memory the same
        way)."""
        from ..parallel.dp import DataParallelTrainer
        from ..parallel.mesh import mesh_for_contexts
        if self._symbol.has_custom_ops():
            # CustomOp callbacks inside the single fused program deadlock
            # the runtime (callback blocks materializing an input while
            # the program holds the execution stream — observed
            # deterministically on XLA:CPU).  The executor-group path
            # keeps callbacks in separate smaller programs, which is also
            # how the reference serializes custom ops (custom-inl.h
            # worker thread).
            self.logger.info("graph contains Custom ops; using executor "
                             "group instead of the fused fast path")
            return None
        kv = getattr(self, "_kvstore_arg", None)
        kv_type = kv.type if hasattr(kv, "type") else kv
        mesh_backend = kv_type == "dist_mesh"
        # THE mesh factory (parallel/mesh.py): one place constructs
        # every module-level mesh, one place grows multi-host axes —
        # dist_mesh spans every process's devices of a
        # jax.distributed launch
        mesh = mesh_for_contexts(self._context, multihost=mesh_backend)
        data_shapes = {d.name: tuple(d.shape) for d in self._data_shapes}
        label_shapes = {d.name: tuple(d.shape)
                        for d in (self._label_shapes or [])}
        trainer = DataParallelTrainer(
            self._symbol, data_shapes, label_shapes or None, mesh=mesh,
            optimizer=optimizer,
            compute_dtype=self._compute_dtype,
            fixed_params=tuple(self._fixed_param_names),
            share_state_with=share_from,
            # dist_mesh: reduce-per-bucket overlapped collectives
            # (MXNET_MESH_REDUCE=fused restores the one-psum step)
            # and ZeRO-1 sharded optimizer state
            reduce_mode=(str(get_env("MXNET_MESH_REDUCE"))
                         if mesh_backend else "fused"),
            shard_optimizer_state=mesh_backend)
        if share_from is None:
            trainer.set_params(self._arg_params, self._aux_params)
        return trainer

    def _adopt_fused_from(self, other):
        """Run this module's fused step over ``other``'s trainer state
        (bucketing: per-bucket compiled steps, one shared parameter/
        optimizer pool).  Returns True on success."""
        if other._fused is None:
            return False
        trainer = self._build_fused(other._optimizer,
                                    share_from=other._fused)
        if trainer is None:
            return False
        self._fused = trainer
        self._optimizer = other._optimizer
        self._kvstore = None
        self._update_on_kvstore = False
        self._updater = None
        self._kvstore_arg = other._kvstore_arg
        self.optimizer_initialized = True
        return True

    def _defuse(self, reason, transient=False):
        """Leave the fused fast path: sync params + optimizer state over to
        the executor-group / host-updater path (full reference semantics)
        and continue training there.

        ``transient`` causes (an explicit forward/backward pair, a one-off
        eval) keep the compiled trainer stashed so ``forward_backward`` can
        re-fuse without recompiling; permanent causes (monitor install)
        disable the fast path for good."""
        trainer = self._fused
        trainer.clear_placement_cache()
        self._fused = None
        self._fused_disabled = True
        # re-fuse only outside bucketing coordination (buckets defuse as a
        # group; re-fusing one would desync the shared state)
        self._fused_stash = trainer if (transient and
                                        self._on_defuse is None) else None
        self.logger.info("leaving fused fast path (%s)", reason)
        self._sync_from_trainer(trainer)
        self._exec_group.set_params(self._arg_params, self._aux_params)
        if not self.optimizer_initialized:
            return
        self._rebuild_host_update_path(trainer)
        if self._on_defuse is not None:
            self._on_defuse(self)

    def _rebuild_host_update_path(self, trainer):
        """Rebuild the classic kvstore/host-updater machinery after
        leaving a one-program path (fused fast path or the exec-group
        SPMD frontend), carrying the trainer's optimizer state over into
        the host updater's per-device layout."""
        (kvstore, _) = _create_kvstore(
            self._kvstore_arg, len(self._context), self._arg_params)
        self._kvstore = kvstore
        self._update_on_kvstore = False
        if kvstore:
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=False)
        self._exec_group.pre_forward_sync = \
            kvstore.flush if kvstore is not None else None
        num_device = len(self._context)
        # host updater indexes params as index*num_device + k; remap the
        # optimizer's idx2name, update counts, and replicate per-device
        # state copies
        self._optimizer.idx2name = {
            i * num_device + k: name
            for i, name in enumerate(self._exec_group.param_names)
            for k in range(num_device)}
        old_counts = dict(self._optimizer._index_update_count)
        self._optimizer._index_update_count = {
            i * num_device + k: c for i, c in old_counts.items()
            for k in range(num_device)}
        self._updater = opt.get_updater(self._optimizer)
        states = trainer.get_updater_states()
        for i, state in states.items():
            for k in range(num_device):
                # per-device state copy on device k, like create_state
                # allocates next to its weight
                self._updater.states[i * num_device + k] = \
                    _place_state(_clone_state(state), self._context[k])

    def _one_program_trainer(self):
        """The state-holding trainer when training runs as one compiled
        step program — the fused fast path's, or the executor-group SPMD
        frontend's — else None."""
        if self._fused is not None:
            return self._fused
        if self._exec_group is not None:
            return self._exec_group.spmd_trainer
        return None

    def _on_spmd_disable(self, trainer, reason):
        """exec_group.disable_spmd hook: the group already reconverged
        its per-exec arrays from the trainer; re-sync the host param
        copies and rebuild the kvstore/updater so training continues
        under full replication semantics with optimizer state carried
        over."""
        self._sync_from_trainer(trainer)
        if self.optimizer_initialized:
            self._rebuild_host_update_path(trainer)

    def _maybe_refuse(self):
        """Return to the fused fast path after a transient defuse: the
        stashed trainer (jit cache intact) is re-seeded with the current
        host params and optimizer state, and the host optimizer's
        index layout is restored to the fused (update_on_kvstore-like)
        convention."""
        trainer = self._fused_stash
        if (trainer is None or self._monitor is not None or
                not self.optimizer_initialized):
            return False
        if self._params_dirty:
            self._sync_params_from_devices()
        num_device = len(self._context)
        # invert the _defuse remap: host layout index*num_device+k -> index
        self._optimizer.idx2name = dict(
            enumerate(self._exec_group.param_names))
        counts = self._optimizer._index_update_count
        self._optimizer._index_update_count = {
            i: counts.get(i * num_device, 0)
            for i in range(len(self._exec_group.param_names))
            if i * num_device in counts}
        states = {}
        if self._updater is not None:
            for i in range(len(self._exec_group.param_names)):
                s = self._updater.states.get(i * num_device)
                if s is not None:
                    states[i] = s
        trainer.set_params(self._arg_params, self._aux_params)
        if states:
            trainer.set_updater_states(states)
        self._fused = trainer
        self._fused_stash = None
        self._fused_disabled = False
        self._kvstore = None
        self._update_on_kvstore = False
        self._updater = None
        self.logger.info("re-entering fused fast path")
        return True

    def _stage_train_data(self, train_data):
        """Overlapped device input staging for the fit loop: wrap the
        iterator in a ``DeviceStager`` uploading toward this module's
        placement — the fused trainer's batch sharding, or the executor
        group's device.  Identity when MXNET_IO_STAGE=0 (bit-exact
        pre-stager behavior), under multi-process jax (the trainer
        shards from HOST buffers there), or when a monitor wants eager
        per-op access anyway."""
        import jax
        from ..io.stager import DeviceStager, staging_enabled
        if not staging_enabled() or self._monitor is not None:
            return train_data
        spmd = self._exec_group.spmd_trainer if self._exec_group else None
        if self._fused is not None or spmd is not None:
            if jax.process_count() > 1:
                return train_data
            # staged arrays land pre-sharded on the batch axis, hitting
            # _shard_batch's already-placed fast path
            target = (self._fused or spmd)._batched
        else:
            try:
                target = self._context[0].jax_device()
            except Exception:
                return train_data

        def place(arr):
            # device_put canonicalizes host dtypes (float64 -> float32)
            # exactly like nd.array would on the blocking path
            return jax.device_put(arr, target)
        return DeviceStager(train_data, place)

    def _sync_from_trainer(self, trainer):
        args, aux = trainer.get_params()
        for n, v in args.items():
            self._arg_params[n][:] = v
        for n, v in aux.items():
            self._aux_params[n][:] = v
        self._params_dirty = False

    def _fused_pack_batch(self, data_batch, fill_missing_labels=False):
        """One global {name: array} dict for the fused step — the
        shared order-sensitive packing (iterator provide_data order,
        NOT constructor order) lives in
        ``executor_group._pack_global_batch``."""
        from .executor_group import _pack_global_batch
        return _pack_global_batch(
            data_batch, self._data_shapes, self._label_shapes,
            self._label_names, arg_shapes=self._fused._arg_shapes,
            fill_missing_labels=fill_missing_labels)

    def _fused_get_outputs(self):
        if self._fused_outputs is None:
            assert self._fused_batch is not None, \
                "no forward has been run"
            # update() not called yet: forward-only outputs for the
            # stashed batch (params unchanged, so the later fused step
            # still computes the same gradients)
            outs = self._fused.predict(self._fused_batch)
            self._fused_outputs = [nd.NDArray(o) for o in outs]
        return self._fused_outputs

    # -- computation -------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if self._fused is not None:
            if is_train or (is_train is None and self.for_training):
                self._defuse("explicit forward(is_train=True)",
                             transient=True)
            else:
                batch = self._fused_pack_batch(data_batch,
                                               fill_missing_labels=True)
                outs = self._fused.predict(batch)
                self._fused_outputs = [nd.NDArray(o) for o in outs]
                # a pending forward_backward stash stays valid: update()
                # recomputes from it with unchanged params
                return
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._fused is not None:
            self._defuse("explicit backward()", transient=True)
        self._exec_group.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        assert self.binded and self.params_initialized
        if self._fused is None and self._fused_stash is not None:
            self._maybe_refuse()
        if self._fused is not None:
            self._fused_batch = self._fused_pack_batch(data_batch)
            self._fused_outputs = None
            return
        self._exec_group.forward_backward(data_batch)

    def update(self):
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._fused is not None:
            assert self._fused_batch is not None, \
                "call forward_backward before update"
            outs = self._fused.step(self._fused_batch)
            self._fused_outputs = [nd.NDArray(o) for o in outs]
            self._fused_batch = None
            return
        if self._exec_group.spmd_active:
            # the whole step (fwd+bwd+all-reduce+in-graph update) runs
            # here as the one compiled program, on the batch
            # forward_backward stashed
            self._exec_group.spmd_step()
            return
        if self._update_on_kvstore:
            # pushes and pulls are submitted asynchronously (dist
            # pipeline) and return immediately; the wire overlaps the
            # rest of this step — metric update, data loading — until
            # the next forward's pre_forward_sync resolves the pulls.
            # Weights change only here, never in forward_backward, so
            # skip-step patterns (e.g. NaN-loss guards) keep reference
            # semantics
            _update_params_on_kvstore(self._exec_group.param_arrays,
                                      self._exec_group.grad_arrays,
                                      self._kvstore)
        else:
            _update_params(self._exec_group.param_arrays,
                           self._exec_group.grad_arrays,
                           updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._fused is not None:
            outs = self._fused_get_outputs()
            return outs if merge_multi_context else [[o] for o in outs]
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def get_states(self, merge_multi_context=True):
        """Values of the ``state_names`` arrays (reference
        module.py:618); stateful setups never take the fused path, so
        the executor group always holds them."""
        assert self.binded and self.params_initialized
        return self._exec_group.get_states(merge_multi_context)

    def set_states(self, states=None, value=None):
        """Set the ``state_names`` arrays from merged values or a scalar
        (reference module.py:641)."""
        assert self.binded and self.params_initialized
        self._exec_group.set_states(states, value)

    def update_metric(self, eval_metric, labels):
        if self._fused is not None:
            outs = self._fused_get_outputs()
            # device-side accumulation keeps the hot loop free of host
            # syncs (a per-batch fetch stalls async dispatch: the host
            # waits for the device instead of running ahead of it);
            # metrics without a device path fall back to the reference's
            # host update
            if not eval_metric.update_device(labels, outs):
                eval_metric.update(labels, outs)
            return
        self._exec_group.update_metric(eval_metric, labels)

    def install_monitor(self, mon):
        assert self.binded
        self._monitor = mon
        if self._fused is not None:
            self._defuse("monitor installed")
        self._exec_group.install_monitor(mon)


def _as_data_desc(x):
    from ..io.io import DataDesc
    if isinstance(x, (list, tuple)) and len(x) == 2:
        return DataDesc(x[0], x[1])
    raise MXNetError("cannot interpret %r as DataDesc" % (x,))


def _clone_state(state):
    """Deep-copy an Updater-layout optimizer state (per-device copies)."""
    if state is None:
        return None
    if isinstance(state, (tuple, list)):
        return tuple(_clone_state(s) for s in state)
    if isinstance(state, nd.NDArray):
        return state.copy()
    return state


def _place_state(state, ctx):
    """Commit an Updater-layout state onto a context's device."""
    if state is None:
        return None
    if isinstance(state, (tuple, list)):
        return tuple(_place_state(s, ctx) for s in state)
    if isinstance(state, nd.NDArray):
        return state.copyto(ctx)
    return state
