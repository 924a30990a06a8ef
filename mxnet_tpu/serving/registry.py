"""Multi-model tenancy: N models served from one process.

Each registered model gets its own :class:`~.program_store.ProgramStore`
(its own bucket programs, weights and compile-cache stats); the
continuous batcher (:class:`~.scheduler.ServingEngine`) schedules across
all of them, never mixing models in one batch.  Models can be added from
live arrays, a ``save_checkpoint`` prefix/epoch pair, or a
``deploy.to_serving`` artifact, and removed at runtime (in-flight
requests for a removed model fail cleanly at dispatch).

Serving weight dtype: ``compute_dtype='bfloat16'`` (or the
``MXNET_SERVE_DTYPE`` default) casts floating weights once at load —
half the resident memory per tenant, the PR-4 ``compute_dtype`` policy
applied to the serving plane — and ``compute_dtype='int8'`` quantizes
FC weights once at load into ``(codes, scales)`` program arguments
(~4x less resident memory, dequantized in-graph through the fused
dequant-matmul door).  Both apply to generative models too
(``add_generative_model``), which additionally take ``kv_dtype`` /
``sample`` (``MXNET_SERVE_KV_DTYPE`` / ``MXNET_SERVE_SAMPLE``) for the
decode plane's cache precision and sampling placement.
"""
from __future__ import annotations

from ..analysis.lockcheck import make_lock
from ..base import MXNetError, get_env
from .program_store import GenerativeProgramStore, ProgramStore

__all__ = ["ModelRegistry"]


class ModelRegistry:
    """name -> :class:`ProgramStore` with thread-safe add/remove.

    Generative (autoregressive) models register through
    :meth:`add_generative_model` into their own namespace of
    :class:`GenerativeProgramStore` — same name space (a name is either
    a forward model or a generative one, never both), separate
    accessor (:meth:`gen_store`), because the two are driven by
    different engines (:class:`~.scheduler.ServingEngine` vs
    :class:`~.decode_engine.GenerationEngine`)."""

    def __init__(self):
        self._stores = {}
        self._gen_stores = {}
        self._drafts = {}       # target name -> draft GenerativeProgramStore
        self._lock = make_lock("serving.registry")

    def add_model(self, name, symbol, arg_params, aux_params=None,
                  input_shapes=None, compute_dtype=None, buckets=None,
                  max_programs=None, input_dtypes=None, device=None,
                  warmup=True):
        """Register a model; compiles every bucket ahead of traffic
        unless ``warmup=False``.  Returns the model's ProgramStore."""
        if input_shapes is None:
            raise MXNetError("add_model needs input_shapes "
                             "(name -> (batch, ...) template)")
        if compute_dtype is None:
            compute_dtype = get_env("MXNET_SERVE_DTYPE") or None
        store = ProgramStore(symbol, arg_params, aux_params or {},
                             input_shapes, name=name,
                             compute_dtype=compute_dtype, buckets=buckets,
                             max_programs=max_programs,
                             input_dtypes=input_dtypes, device=device)
        with self._lock:
            if name in self._stores or name in self._gen_stores:
                raise MXNetError("model %r is already registered" % name)
            self._stores[name] = store
        if warmup:
            try:
                store.warmup()
            except BaseException:
                # a model whose programs don't compile must not stay
                # registered (serveable-but-broken, and blocking the
                # name for a corrected retry)
                with self._lock:
                    self._stores.pop(name, None)
                raise
        return store

    def load_checkpoint(self, name, prefix, epoch, input_shapes, **kwargs):
        """Register from a ``prefix-symbol.json`` + ``prefix-NNNN.params``
        pair (``model.save_checkpoint`` layout); params are loaded once
        and stay device-resident."""
        from ..model import load_checkpoint
        sym, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return self.add_model(name, sym, arg_params, aux_params,
                              input_shapes, **kwargs)

    def load_artifact(self, name, path, **overrides):
        """Register from a ``deploy.to_serving`` artifact (symbol-json +
        params + shape-bucket metadata in one zip); keyword overrides
        win over the artifact's recorded settings."""
        from ..deploy import read_serving_artifact
        sym, arg_params, aux_params, meta = read_serving_artifact(path)
        kwargs = {
            "input_shapes": {k: tuple(v)
                             for k, v in meta["input_shapes"].items()},
            "input_dtypes": meta.get("input_dtypes"),
            "buckets": meta.get("bucket_edges"),
            "compute_dtype": meta.get("compute_dtype"),
        }
        kwargs.update(overrides)
        return self.add_model(name, sym, arg_params, aux_params, **kwargs)

    def add_generative_model(self, name, params, spec, warmup=True,
                             warmup_kv_depth=None, **kwargs):
        """Register an autoregressive LM for the decode plane.

        ``params`` — the ``transformer_lm`` symbol graph's trained
        argument arrays (a ``save_checkpoint``'s arg_params works
        directly); ``spec`` — ``transformer_lm.lm_spec(...)``, or
        another decode-mode model's spec naming it under ``arch``
        (``"deepseek_v3"``, paged plane only;
        :class:`GenerativeProgramStore`).  Keyword
        args (``batch_buckets``, ``prompt_buckets``, ``kv_block``,
        ``kv_max``, ``compute_dtype``, ``kv_dtype``, ``sample``,
        ``max_programs``, ``device``) pass through to
        :class:`GenerativeProgramStore`; like :meth:`add_model`, an
        unset ``compute_dtype`` falls back to the ``MXNET_SERVE_DTYPE``
        default.  Compiles + executes every prefill/decode bucket
        program ahead of traffic unless ``warmup=False``.  Returns the
        store."""
        if kwargs.get("compute_dtype") is None:
            kwargs["compute_dtype"] = get_env("MXNET_SERVE_DTYPE") or None
        store = GenerativeProgramStore(params, spec, name=name, **kwargs)
        with self._lock:
            if name in self._stores or name in self._gen_stores:
                raise MXNetError("model %r is already registered" % name)
            self._gen_stores[name] = store
        if warmup:
            try:
                store.warmup(kv_depth=warmup_kv_depth)
            except BaseException:
                with self._lock:
                    self._gen_stores.pop(name, None)
                raise
        return store

    def add_draft_model(self, target_name, params, spec, spec_k=None,
                        warmup=True, compute_dtype=None, device=None):
        """Attach a small DRAFT LM to generative model ``target_name``
        for speculative decoding (``MXNET_SERVE_SPEC``).

        The draft gets its own :class:`GenerativeProgramStore` with the
        target's pool geometry COPIED (``kv_block``, ``kv_max``,
        ``pool_blocks``, ``prefill_chunk``, batch buckets, ``kv_dtype``,
        paged + in-graph sampling) so the decode engine can drive both
        planes through the same block tables — the draft holds its own
        pool arrays but shares the target's block allocator.  Warms the
        speculative program kinds on BOTH sides (the draft's lq=1
        proposal + prefill-mirror chunks, the target's lq=spec_k+1
        verify), so attaching a draft never compiles inside a served
        request.  ``spec_k`` defaults to ``MXNET_SERVE_SPEC_K``.
        Returns the draft store."""
        target = self.gen_store(target_name)
        if not target.paged or target.sample_mode != "graph":
            raise MXNetError(
                "speculative decoding needs model %r on the paged "
                "plane with in-graph sampling (paged=True, "
                "sample='graph'); got paged=%s sample=%r"
                % (target_name, target.paged, target.sample_mode))
        target._need("draft", "speculative decoding")
        if spec_k is None:
            spec_k = int(get_env("MXNET_SERVE_SPEC_K"))
        if spec_k < 1:
            raise MXNetError("spec_k must be >= 1, got %d" % spec_k)
        if compute_dtype is None:
            compute_dtype = get_env("MXNET_SERVE_DTYPE") or None
        draft = GenerativeProgramStore(
            params, spec, name="%s.draft" % target_name,
            batch_buckets=target._batch_edges,
            prompt_buckets=target._prompt_edges,
            kv_block=target.kv_block, kv_max=target.kv_max,
            compute_dtype=compute_dtype,
            kv_dtype=str(target.kv_dtype), sample="graph",
            paged=True, prefill_chunk=target.prefill_chunk,
            pool_blocks=target.pool_blocks, device=device)
        # the engine reads the attached window size off the draft —
        # the verify programs are warmed for exactly this lq
        draft.spec_k = spec_k
        with self._lock:
            if target_name in self._drafts:
                raise MXNetError("model %r already has a draft attached"
                                 % target_name)
            self._drafts[target_name] = draft
        if warmup:
            try:
                draft.warm_spec_programs(spec_k, draft=True)
                target.warm_spec_programs(spec_k)
            except BaseException:
                with self._lock:
                    self._drafts.pop(target_name, None)
                raise
        return draft

    def draft_store(self, name):
        """Generative model ``name``'s attached draft store, or None
        when no draft is registered (the engine's spec gate)."""
        with self._lock:
            return self._drafts.get(name)

    def load_generative_checkpoint(self, name, prefix, epoch, spec,
                                   **kwargs):
        """Register a generative model from a ``save_checkpoint``
        prefix/epoch pair (the symbol json is ignored — the decode
        graphs reuse the trained ARG arrays by name)."""
        from ..model import load_checkpoint
        _, arg_params, _ = load_checkpoint(prefix, epoch)
        return self.add_generative_model(name, arg_params, spec, **kwargs)

    def store(self, name):
        """The model's ProgramStore; raises MXNetError when unknown."""
        with self._lock:
            store = self._stores.get(name)
            known = sorted(self._stores) if store is None else None
        if store is None:
            raise MXNetError("unknown serving model %r (registered: %s)"
                             % (name, known))
        return store

    def gen_store(self, name):
        """The model's GenerativeProgramStore; raises when unknown."""
        with self._lock:
            store = self._gen_stores.get(name)
            known = sorted(self._gen_stores) if store is None else None
        if store is None:
            raise MXNetError(
                "unknown generative serving model %r (registered: %s)"
                % (name, known))
        return store

    def swap_params(self, name, arg_params, aux_params=None):
        """Hot weight swap under traffic: atomically republish model
        ``name``'s device-resident weight arguments (the programs take
        params as ARGUMENTS — no recompile).  Works for forward stores
        (``aux_params`` optionally refreshes auxiliary states) and
        generative stores (``aux_params`` must be None).  Every
        in-flight request executes against exactly one version — see
        the stores' ``swap_params`` docstrings; the new version shows
        up in ``stats()``.  Returns the new version number."""
        with self._lock:
            store = self._stores.get(name)
            gstore = self._gen_stores.get(name)
        if store is not None:
            return store.swap_params(arg_params, aux_params)
        if gstore is not None:
            if aux_params is not None:
                raise MXNetError("generative models have no auxiliary "
                                 "states to swap")
            return gstore.swap_params(arg_params)
        raise MXNetError("unknown serving model %r" % name)

    def param_snapshot(self, name):
        """Opaque handle to model ``name``'s live weight set (forward
        or generative store), for :meth:`restore_params` — captured by
        the replica set's rolling swap before each per-replica swap so
        a failed re-probe can roll back."""
        with self._lock:
            store = self._stores.get(name)
            gstore = self._gen_stores.get(name)
        if store is not None:
            return store.param_snapshot()
        if gstore is not None:
            return gstore.param_snapshot()
        raise MXNetError("unknown serving model %r" % name)

    def restore_params(self, name, snap):
        """Republish a :meth:`param_snapshot` (rolling-swap abort
        path).  Returns the new — still monotonic — version."""
        with self._lock:
            store = self._stores.get(name)
            gstore = self._gen_stores.get(name)
        if store is not None:
            return store.restore_params(snap)
        if gstore is not None:
            return gstore.restore_params(snap)
        raise MXNetError("unknown serving model %r" % name)

    def remove_model(self, name):
        with self._lock:
            self._drafts.pop(name, None)
            if self._stores.pop(name, None) is None and \
                    self._gen_stores.pop(name, None) is None:
                raise MXNetError("unknown serving model %r" % name)

    def models(self):
        with self._lock:
            return sorted(list(self._stores) + list(self._gen_stores))

    def stats(self):
        """Per-model program-store stats (compile cache, buckets)."""
        with self._lock:
            stores = dict(self._stores)
            stores.update(self._gen_stores)
        return {name: s.stats() for name, s in stores.items()}

    def __contains__(self, name):
        with self._lock:
            return name in self._stores or name in self._gen_stores

    def __len__(self):
        with self._lock:
            return len(self._stores) + len(self._gen_stores)
