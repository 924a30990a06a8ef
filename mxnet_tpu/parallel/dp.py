"""Sharded data-parallel trainer: the TPU fast path for kvstore='device'.

Reference semantics being replaced (SURVEY.md §2.3.1-2): per-device
executors + Comm::Reduce gradient all-reduce + updater + Comm::Broadcast.
Here the WHOLE training step — forward, backward, gradient all-reduce, and
optimizer update — is ONE compiled XLA program over a ``jax.sharding.Mesh``:
parameters are replicated, the batch is sharded over the ``dp`` axis, and
XLA inserts the ICI all-reduce where the replicated-parameter gradients
meet the sharded batch (the ``psum`` that subsumes kvstore push+pull).
``update_on_kvstore`` ≡ the optimizer living inside the compiled step.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from .. import ndarray as nd
from .. import profiler
from ..base import MXNetError, hot_path
from ..initializer import InitDesc, Uniform
from ..ndarray import NDArray
from .mesh import local_mesh

__all__ = ["DataParallelTrainer", "FusedDPTrainer"]


from .ingraph_opt import InGraphOptimizer


class _TrainerState:
    """Shared mutable holder for (params, opt_state, aux) jax pytrees.

    Bucketing shares ONE state across many shape-specialized compiled
    steps (the reference shares executor memory pools across buckets,
    bucketing_module.py:302-330; here the shared resource is the
    parameter/optimizer arrays while each bucket keeps its own jit cache
    entry)."""

    __slots__ = ("params", "opt_state", "aux")


class DataParallelTrainer:
    """Compiled data-parallel training over a mesh.

    >>> trainer = DataParallelTrainer(softmax_sym, batch_size=256,
    ...                               data_shapes={'data': (256, 3, 224, 224)},
    ...                               label_shapes={'softmax_label': (256,)})
    >>> outputs = trainer.step(data, label)   # one fused XLA step
    """

    def __init__(self, symbol, data_shapes, label_shapes=None, mesh=None,
                 optimizer="sgd", optimizer_params=None, initializer=None,
                 batch_axis="dp", dtype="float32", compute_dtype=None,
                 fixed_params=(), share_state_with=None,
                 shard_optimizer_state=False, reduce_mode="fused"):
        """``compute_dtype='bfloat16'`` enables mixed precision: parameters
        and optimizer state stay fp32 (master weights), the traced forward/
        backward runs in bf16 on the MXU, and gradients emerge fp32 through
        the cast's vjp — the TPU-idiomatic replacement for the reference's
        fp16 model variants (symbols/*_fp16.py).

        ``shard_optimizer_state=True`` (ZeRO-1, beyond-reference):
        optimizer state of replicated parameters is sharded over the
        batch axis instead of replicated — each rank updates its shard
        and XLA all-gathers the new weights, cutting optimizer-state HBM
        by the dp degree (1/8 on a v5e-8; for Adam that is 2x params'
        worth of memory back).  Numerically identical to the replicated
        path (tests/test_parallel.py asserts parity).

        ``reduce_mode='bucket'`` (the dist_mesh data plane): the step
        compiles as grad program + one collective per
        MXNET_KVSTORE_BUCKET_BYTES bucket + apply program, and
        ``step()`` launches bucket reduces through
        :class:`..parallel.mesh_reduce.MeshCollectiveLauncher`
        (overlapped unless MXNET_MESH_OVERLAP=0) instead of relying on
        the fused step's single end-of-backward psum."""
        self.symbol = symbol
        self.mesh = mesh if mesh is not None else local_mesh(batch_axis)
        self.batch_axis = batch_axis
        self._fixed = set(fixed_params)
        self._compute_dtype = (jnp.dtype(compute_dtype)
                               if compute_dtype else None)
        self._zero1 = bool(shard_optimizer_state)
        self._reduce_mode = reduce_mode

        shapes = dict(data_shapes)
        if label_shapes:
            shapes.update(label_shapes)
        self._data_shapes_map = {k: tuple(v) for k, v in
                                 data_shapes.items()}
        self._label_shapes_map = {k: tuple(v) for k, v in
                                  (label_shapes or {}).items()}
        self.data_names = list(data_shapes)
        self.label_names = list(label_shapes or {})
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shapes)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.param_names = [n for n in self.arg_names
                            if n not in shapes]
        self._arg_shapes = dict(zip(self.arg_names, arg_shapes))
        self._aux_shapes = dict(zip(self.aux_names, aux_shapes))
        self._dtype = dtype

        # a real host Optimizer instance drives hyperparameters (schedulers,
        # lr/wd multipliers, update counts); its update math is compiled
        # into the step via InGraphOptimizer (reference: update_on_kvstore
        # runs the python optimizer server-side — here it runs in-graph)
        from .. import optimizer as opt_mod
        if isinstance(optimizer, str):
            opt_params = dict(optimizer_params or {})
            batch = next(iter(data_shapes.values()))[0]
            opt_params.setdefault("rescale_grad", 1.0 / batch)
            optimizer = opt_mod.create(
                optimizer, param_idx2name=dict(enumerate(self.param_names)),
                sym=symbol, **opt_params)
        self.optimizer = optimizer
        self._ingraph = InGraphOptimizer(optimizer)
        self._opt_init = self._ingraph.init_state
        self._opt_update = self._ingraph.update
        # indices (positions in param_names) that actually get updates
        self._live_idx = [i for i, n in enumerate(self.param_names)
                          if n not in self._fixed]

        self._replicated = NamedSharding(self.mesh, P())
        self._batched = NamedSharding(self.mesh, P(batch_axis))

        if share_state_with is not None:
            # bucketing: this trainer is a shape variant compiled over the
            # SAME parameter/optimizer/aux arrays as the primary trainer
            other = share_state_with
            if (set(self.param_names) != set(other.param_names) or
                    set(self.aux_names) != set(other.aux_names)):
                raise MXNetError(
                    "share_state_with requires identical param/aux sets")
            for n in self.param_names:
                if self._arg_shapes[n] != other._arg_shapes[n]:
                    raise MXNetError("param %s shape mismatch across "
                                     "shared trainers" % n)
            # the shared opt state's layout is the primary's decision;
            # a mismatched flag here would silently re-place it
            self._zero1 = other._zero1
            self._st = other._st
        else:
            self._st = _TrainerState()
            self._init_params(initializer or Uniform(0.01))
        self._compile()

    # shared-state accessors: all bucket trainers observe each other's steps
    @property
    def params(self):
        return self._st.params

    @params.setter
    def params(self, v):
        self._st.params = v

    @property
    def opt_state(self):
        return self._st.opt_state

    @opt_state.setter
    def opt_state(self, v):
        self._st.opt_state = v

    @property
    def aux(self):
        return self._st.aux

    @aux.setter
    def aux(self, v):
        self._st.aux = v

    # ------------------------------------------------------------------
    def _sharding_for(self, name):
        """Sharding of parameter ``name`` (replicated for pure DP;
        MeshTrainer overrides with tensor-parallel rules)."""
        return self._replicated

    def _opt_sharding_for(self, name, state_shape):
        """Sharding for one optimizer-state tensor (ZeRO-1 seam).

        Shard axis 0 over the batch axis when (a) the flag is on,
        (b) the owning parameter is replicated (tensor-parallel params
        keep state co-sharded with the weight), and (c) axis 0 divides
        evenly — otherwise fall back to the parameter's sharding."""
        base = self._sharding_for(name)
        if not self._zero1 or base.spec != P():
            return base
        dp = self.mesh.shape[self.batch_axis]
        if (state_shape and state_shape[0] % dp == 0 and
                state_shape[0] >= dp):
            return NamedSharding(
                self.mesh,
                P(self.batch_axis, *([None] * (len(state_shape) - 1))))
        return base

    @staticmethod
    def _place(value, sharding):
        """Place a host value onto a (possibly cross-process) sharding.

        Staged through host memory: a committed jax array device_put
        directly onto a sharding that spans OTHER processes' devices is
        a cross-host transfer (unsupported on the CPU/gloo backend).
        Under multi-process jax.distributed, device_put also rejects
        non-addressable shardings outright, so each process hands the
        full host value to make_array_from_process_local_data
        (global_shape == local shape tells it every process holds the
        whole array) and fills only its own shards."""
        if jax.process_count() == 1:
            return jax.device_put(value, sharding)
        if (hasattr(value, "dtype")
                and jnp.issubdtype(value.dtype, jax.dtypes.prng_key)):
            # typed PRNG keys cannot cross host memory directly; move
            # the underlying uint32 data and re-wrap
            data = DataParallelTrainer._place(
                jax.random.key_data(value), sharding)
            return jax.random.wrap_key_data(
                data, impl=jax.random.key_impl(value))
        host = np.asarray(value)
        return jax.make_array_from_process_local_data(
            sharding, host, global_shape=host.shape)

    def _init_params(self, initializer):
        attrs = self.symbol.attr_dict()
        params = {}
        for name in self.param_names:
            arr = nd.zeros(self._arg_shapes[name], dtype=self._dtype)
            initializer(InitDesc(name, attrs.get(name)), arr)
            params[name] = self._place(arr._data,
                                       self._sharding_for(name))
        self.params = params
        self.opt_state = {n: tuple(
            self._place(s, self._opt_sharding_for(n, s.shape))
            for s in self._opt_init(params[n])) for n in self.param_names}
        aux = {}
        for name in self.aux_names:
            arr = nd.zeros(self._aux_shapes[name], dtype=self._dtype)
            initializer(InitDesc(name, attrs.get(name)), arr)
            aux[name] = self._place(arr._data, self._replicated)
        self.aux = aux

    def _compile(self):
        """Fetch (or compile) the shared SPMD step program for this
        trainer's (symbol, mesh, shapes, dtype, optimizer, rules) — the
        trainer holds state and placement; the program is owned by
        ``parallel/spmd.py``'s cache and shared with every other
        frontend keyed the same."""
        from . import spmd
        shardings = {n: self._sharding_for(n) for n in self.param_names}
        self._program = spmd.get_step_program(
            self.symbol, self.mesh,
            data_shapes=self._data_shapes_map,
            label_shapes=self._label_shapes_map or None,
            dtype=self._dtype, compute_dtype=self._compute_dtype,
            optimizer=self.optimizer,
            fixed_params=tuple(sorted(self._fixed)),
            shard_optimizer_state=self._zero1,
            param_shardings=shardings,
            reduce_mode=self._reduce_mode,
            batch_axis=self.batch_axis)
        self._rng_at_eval = self._program.rng_at_eval
        self._train_step = self._program.train_step
        self._predict_step = self._program.predict_step
        # reduce_mode may have been downgraded (Custom-op graphs keep
        # the fused single-psum step)
        self._reduce_mode = self._program.reduce_mode
        if self._program.reduce_mode == "bucket":
            from .mesh_reduce import MeshCollectiveLauncher
            self._launcher = MeshCollectiveLauncher()

    # ------------------------------------------------------------------
    def _shard_batch(self, batch):
        out = {}
        for k, v in batch.items():
            if jax.process_count() > 1:
                # each process holds the full global batch; hand the
                # HOST buffer over directly (no device round-trip) and
                # fill only the addressable shards
                host = np.asarray(v._data if isinstance(v, NDArray)
                                  else v)
                out[k] = jax.make_array_from_process_local_data(
                    self._batched, host, global_shape=host.shape)
            else:
                was_jax = isinstance(v, NDArray) or isinstance(v, jax.Array)
                arr = (v._data if isinstance(v, NDArray)
                       else jnp.asarray(v))
                # already laid out (steady-state loops feed pre-sharded
                # arrays): skip the ~0.1ms/array device_put round-trip
                if getattr(arr, "sharding", None) == self._batched:
                    out[k] = arr
                elif was_jax:
                    out[k] = self._place_cached(k, arr)
                else:
                    # mutable host source (plain numpy): placement must
                    # not be cached — in-place edits would be served
                    # stale.  Also drop any stale cache entry for this
                    # name: an iterator that switched from a steady
                    # device buffer to host batches would otherwise pin
                    # a dead batch of HBM for the trainer's lifetime
                    cache = getattr(self, "_placement_cache", None)
                    if cache is not None:
                        cache.pop(k, None)
                    out[k] = jax.device_put(arr, self._batched)
        return out

    def clear_placement_cache(self):
        """Drop all cached input placements (each entry pins ~a batch of
        HBM per input name).  Module calls this on unbind/rebind and
        when it leaves the fused fast path, so a retired trainer never
        holds batch buffers alive."""
        self._placement_cache = {}

    def _place_cached(self, name, arr):
        """device_put with a per-input placement cache.

        An iterator that re-feeds the SAME buffer every step (the
        reference's synthetic --benchmark 1 protocol, or a small dataset
        an NDArrayIter cycles through) would otherwise pay a full
        host->device upload per step, a copy of the whole batch that
        the step then waits on.  jax arrays are immutable, so
        identity of the buffer is a sound cache key; the cached source
        reference keeps the id from being recycled."""
        cache = getattr(self, "_placement_cache", None)
        if cache is None:
            cache = self._placement_cache = {}
        hit = cache.get(name)
        if hit is not None and hit[0] is arr:
            return hit[1]
        placed = jax.device_put(arr, self._batched)
        cache[name] = (arr, placed)
        return placed

    @hot_path
    def step(self, data, label=None, rng=None):
        """Run one fused training step; returns output jax arrays."""
        batch = dict(data) if isinstance(data, dict) else \
            {self.data_names[0]: data}
        if label is not None:
            if isinstance(label, dict):
                batch.update(label)
            else:
                batch[self.label_names[0]] = label
        batch = self._shard_batch(batch)
        if rng is None:
            rng = self._carry_rng()
        lrs, wds = self._host_hyper()
        from .. import engine as _engine
        # spmd_step attributes the sharded-program dispatch inside the
        # fit loop's "compute" phase (nested span; excluded from pct)
        with profiler.phase("spmd_step"):
            if self._reduce_mode == "bucket":
                self.params, self.opt_state, self.aux, outs, rng_next = \
                    self._step_bucketed(batch, lrs, wds, rng)
            else:
                self.params, self.opt_state, self.aux, outs, rng_next = \
                    _engine.get().dispatch(
                        "fused_train_step", self._train_step,
                        self.params, self.opt_state, self.aux, batch,
                        lrs, wds, rng)
        self._rng_dev = rng_next
        return outs

    def _step_bucketed(self, batch, lrs, wds, rng):
        """Reduce-per-bucket step: grad program, then one collective per
        bucket launched through the overlap engine (tail buckets' reduces
        run while earlier ones are still in flight), then the apply
        program on the reduced grads.  Everything stays async XLA
        dispatch — no host sync."""
        from .. import engine as _engine
        eng = _engine.get()
        program = self._program
        grads, new_aux, outs, rng_use, rng_next = eng.dispatch(
            "mesh_grad_step", program.grad_step, self.params, self.aux,
            batch, rng)
        results = self._launcher.launch(
            [(i, tuple(grads[n] for n in names))
             for i, names in enumerate(program.buckets)],
            lambda i, payload: eng.dispatch(
                "mesh_bucket_reduce", program.bucket_reduces[i], *payload))
        reduced = {}
        for names, res in zip(program.buckets, results):
            for n, g in zip(names, res):
                reduced[n] = g
        new_params, new_opt = eng.dispatch(
            "mesh_apply_step", program.apply_step, self.params,
            self.opt_state, reduced, lrs, wds, rng_use)
        return new_params, new_opt, new_aux, outs, rng_next

    def _carry_rng(self):
        """Device-resident PRNG key threaded through the compiled step
        (successor keys come back as a step output — no per-step host
        split or upload).  A later mx.random.seed() invalidates the
        carried key so reseeded runs stay reproducible."""
        from .. import random as _random
        gen = _random.generation()
        rng = getattr(self, "_rng_dev", None)
        if rng is None or getattr(self, "_rng_gen", None) != gen:
            # commit the fresh key to the replicated layout the carried
            # successor keys come back with — otherwise the second step
            # sees a different arg sharding and recompiles the whole
            # fused program
            rng = self._rng_dev = self._place(_random.next_key(),
                                              self._replicated)
            self._rng_gen = gen
        return rng

    def _host_hyper(self):
        """Per-step (lr, wd) vectors over param_names positions, computed
        from the host optimizer (schedulers/multipliers/update counts) —
        dynamic jit args, so lr changes don't retrace."""
        lr_list, wd_list = self._ingraph.host_hyper(self._live_idx)
        key = (tuple(lr_list), tuple(wd_list))
        cached = getattr(self, "_hyper_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        lrs = np.zeros(len(self.param_names), np.float32)
        wds = np.zeros(len(self.param_names), np.float32)
        for i, lr, wd in zip(self._live_idx, lr_list, wd_list):
            lrs[i] = lr
            wds[i] = wd
        dev = (jnp.asarray(lrs), jnp.asarray(wds))
        # constant-lr steps would otherwise pay two host->device
        # transfers per batch; schedulers that do change lr miss the
        # cache and re-upload
        self._hyper_cache = (key, dev)
        return dev

    @property
    def trace_counts(self):
        """``{"train": n, "predict": n}`` — how often the step program's
        python bodies were traced; a steady-state loop stays at 1."""
        return dict(self._program.trace_counts)

    def _step_args(self, data, label=None):
        """The fused step's argument tuple at this trainer's state."""
        batch = dict(data) if isinstance(data, dict) else \
            {self.data_names[0]: data}
        if label is not None:
            if isinstance(label, dict):
                batch.update(label)
            else:
                batch[self.label_names[0]] = label
        batch = self._shard_batch(batch)
        lrs, wds = self._host_hyper()
        return (self.params, self.opt_state, self.aux, batch, lrs, wds,
                self._carry_rng())

    def lower_step(self, data, label=None):
        """THE fused step lowered at this trainer's shapes (a
        ``jax.stages.Lowered``): ``.as_text()`` shows which kernels it
        holds (``tpu_custom_call``), ``.compile().as_text()`` which
        collectives the compiler put in.  A diagnostic — it re-traces
        the step body, so never call it per step."""
        return self._train_step.lower(*self._step_args(data, label))

    def predict(self, data, rng=None):
        batch = dict(data) if isinstance(data, dict) else \
            {self.data_names[0]: data}
        batch = self._shard_batch(batch)
        if rng is None:
            if getattr(self, "_rng_at_eval", False):
                # graph samples at inference: every call needs fresh draws
                from .. import random as _random
                rng = _random.next_key()
            else:
                # dropout-only graphs are identity at inference: reuse the
                # carried key — deterministic eval, no per-call host split
                rng = self._carry_rng()
        return self._predict_step(self.params, self.aux, batch, rng)

    def get_params(self):
        """Host-synced {name: NDArray} dicts (arg, aux)."""
        args = {n: nd.array(np.asarray(jax.device_get(v)))
                for n, v in self.params.items()}
        aux = {n: nd.array(np.asarray(jax.device_get(v)))
               for n, v in self.aux.items()}
        return args, aux

    def set_params(self, arg_params, aux_params=None):
        for n, v in arg_params.items():
            if n in self.params:
                self.params[n] = self._place(
                    v._data if isinstance(v, NDArray) else jnp.asarray(v),
                    self._replicated)
        for n, v in (aux_params or {}).items():
            if n in self.aux:
                self.aux[n] = self._place(
                    v._data if isinstance(v, NDArray) else jnp.asarray(v),
                    self._replicated)

    # -- optimizer-state interop (Updater.states layout) ----------------
    def get_updater_states(self):
        """Optimizer state as the host ``Updater.states`` dict
        {param_index: state-in-create_state-layout}; interoperates with
        ``.states`` checkpoints and the host update path."""
        return {i: self._ingraph.state_to_host(self.opt_state[name])
                for i, name in enumerate(self.param_names)
                if name not in self._fixed}

    def set_updater_states(self, states):
        for i, name in enumerate(self.param_names):
            if i in states and name not in self._fixed:
                if states[i] is None:
                    # a stateless entry (momentum=0 sgd serializes its
                    # state as None): keep this trainer's freshly
                    # initialized state — feeding None through
                    # state_from_host would materialize a NaN scalar
                    # (jnp.asarray(None)) that poisons the first update
                    continue
                arrs = [jnp.asarray(s._data if isinstance(s, NDArray)
                                    else s)
                        for s in self._ingraph.state_from_host(states[i])]
                self.opt_state[name] = tuple(
                    self._place(a, self._opt_sharding_for(name, a.shape))
                    for a in arrs)


# The name the SPMD step-program design docs use for the fused-trainer
# frontend (docs/architecture/spmd_step.md): same class, clearer role.
FusedDPTrainer = DataParallelTrainer
