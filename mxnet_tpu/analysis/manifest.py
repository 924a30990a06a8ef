"""Manifests the graft-lint rules consult.

Paths are repo-root-relative POSIX paths; functions are dotted
qualnames (``Class.method`` or a bare module-level name).  Keep these
lists sorted so diffs stay reviewable.

Entries here are load-bearing: a manifest path/qualname that no longer
resolves in its file is itself reported as a violation (rule
``span-coverage`` / ``host-sync``), so a refactor cannot silently
retire a guarded entry point.
"""

# ---------------------------------------------------------------------------
# host-sync rule: functions that are hot-path by fiat (in addition to
# anything carrying the @hot_path decorator).  These are the per-step
# loops where one stray block_until_ready / np.asarray / .item() turns
# the async engine back into a synchronous one.
# ---------------------------------------------------------------------------
HOT_PATHS = (
    ("mxnet_tpu/kvstore_pipeline.py", "CommPipeline.submit"),
    ("mxnet_tpu/module/base_module.py", "BaseModule._fit_epochs"),
    ("mxnet_tpu/module/executor_group.py",
     "DataParallelExecutorGroup.spmd_step"),
    ("mxnet_tpu/parallel/dp.py", "DataParallelTrainer.step"),
)

# Calls forbidden inside a hot-path function.  Terminal attribute /
# callable names; `float(x)` is flagged only for non-constant x.
HOST_SYNC_CALLS = frozenset([
    "block_until_ready",   # jax.block_until_ready / arr.block_until_ready
    "asnumpy",             # NDArray host fetch
    "asscalar",
    "wait_to_read",
    "waitall",
    "item",
])
HOST_SYNC_NP_FUNCS = frozenset(["asarray", "array"])  # np./numpy./onp.

# ---------------------------------------------------------------------------
# span-coverage rule: public engine / kvstore / stager entry points that
# must emit a profiler span (directly, or through a helper defined in
# the same module — one hop).
# ---------------------------------------------------------------------------
SPAN_ENTRY_POINTS = (
    ("mxnet_tpu/cached_op.py", "_run"),
    ("mxnet_tpu/engine.py", "Engine.dispatch"),
    ("mxnet_tpu/io/pipeline.py", "ThreadedBatchPipeline.next_batch"),
    ("mxnet_tpu/io/stager.py", "DeviceStager._stage_batch"),
    ("mxnet_tpu/kvstore_dist.py", "Server._install_bucket"),
    ("mxnet_tpu/kvstore_dist.py", "Server._migrate_out"),
    ("mxnet_tpu/kvstore_dist.py", "Server._refresh_membership_locked"),
    ("mxnet_tpu/kvstore_dist.py", "WorkerClient._rpc_locked"),
    ("mxnet_tpu/kvstore_dist.py", "WorkerClient.migrate_bucket"),
    ("mxnet_tpu/kvstore_pipeline.py", "CommPipeline._worker"),
    ("mxnet_tpu/kvstore_pipeline.py", "CommPipeline.flush"),
    ("mxnet_tpu/module/base_module.py", "BaseModule._fit_epochs"),
    ("mxnet_tpu/parallel/dp.py", "DataParallelTrainer.step"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._admit_paged"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._dispatch_decode"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._dispatch_decode_sample"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._dispatch_prefill"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._paged_decode_step"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._paged_dispatch"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._paged_prefill_chunk"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._paged_tick"),
    ("mxnet_tpu/serving/decode_engine.py",
     "GenerationEngine._queue_tick"),
    ("mxnet_tpu/serving/frontdoor.py", "_Handler._serve_generate"),
    ("mxnet_tpu/serving/frontdoor.py", "_Handler._serve_predict"),
    ("mxnet_tpu/serving/replica_set.py", "ReplicaSet._dispatch"),
    ("mxnet_tpu/serving/replica_set.py", "ReplicaSet.submit_gen"),
    ("mxnet_tpu/serving/scheduler.py", "ServingEngine._dispatch_once"),
)

# Terminal callable names that count as "emits a span".
SPAN_EMITTERS = frozenset([
    "record",          # Profiler.record / StepPhaseCollector.record
    "phase",           # profiler.phase, the one span seam
    "mark_step",
    "_recorder",       # CommPipeline's injected recorder callback
    "_prof_record",    # kvstore_dist module-level helper
])

# ---------------------------------------------------------------------------
# thread-discipline rule: receivers whose .acquire()/.release() and
# with-blocks are treated as lock operations (last attribute/name
# component, case-insensitive regex).
# ---------------------------------------------------------------------------
LOCKISH_NAME_RE = r"(?i)(^|_)(lock|locked|mutex|sem|sema|cv|cond|condition)s?$"

# ---------------------------------------------------------------------------
# unguarded-shared-mutation rule: function names that ARE thread
# run-loops (the bodies threads execute concurrently with the public
# API).  A direct ``self.<field> = ...`` in one of these outside a
# ``with <lock>`` block is a write racing every caller-side read;
# route it through a lock or a ``racecheck.shared_state()`` container.
# ---------------------------------------------------------------------------
RUN_LOOP_NAME_RE = (r"(?i)^(run|_run|_worker|_serve|_accept|"
                    r"[a-z0-9_]*_loop)$")

# ---------------------------------------------------------------------------
# atomic-publish rule: fields that are multi-value SNAPSHOTS published
# by one reference assignment (the swap_params pattern).  Entries are
# (path, field, allowed publisher qualnames); assigning the field
# anywhere but ``__init__``/the listed publishers, unpacking it as a
# tuple target, or mutating it in place tears the snapshot for
# concurrent readers.
# ---------------------------------------------------------------------------
ATOMIC_PUBLISH = (
    ("mxnet_tpu/serving/program_store.py", "_live",
     ("ProgramStore.swap_params", "ProgramStore.restore_params")),
    ("mxnet_tpu/serving/program_store.py", "_params",
     ("ProgramStore.swap_params", "ProgramStore.restore_params",
      "GenerativeProgramStore.swap_params",
      "GenerativeProgramStore.restore_params")),
)

# Method names that mutate their receiver in place (atomic-publish
# flags these on a published field: build a new object and republish).
MUTATOR_METHODS = frozenset([
    "append", "extend", "insert", "remove", "pop", "popitem", "clear",
    "update", "setdefault", "add", "discard", "sort", "reverse",
])
