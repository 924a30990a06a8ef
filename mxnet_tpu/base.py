"""Core shared infrastructure: errors, env-var config registry, misc helpers.

TPU-native rebuild of the roles played by the reference's ``python/mxnet/base.py``
(ctypes loading, ``MXNetError``, ``check_call``) and its env-var config tier
(``dmlc::GetEnv`` sites documented in ``docs/how_to/env_var.md``).  There is no C
ABI to load here — the compute path is JAX/XLA — so this module keeps only the
semantic surface: the error type, the typed environment-variable registry, and
name/registry helpers used across the package.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading

__all__ = [
    "MXNetError",
    "EnvVar",
    "env_registry",
    "register_env",
    "get_env",
    "use_compile_cache",
    "atomic_write",
    "hot_path",
    "string_types",
    "numeric_types",
]

string_types = (str,)
numeric_types = (int, float)


class MXNetError(Exception):
    """Framework error type (reference: ``python/mxnet/base.py`` MXNetError)."""


# ---------------------------------------------------------------------------
# Environment-variable config registry.
#
# The reference reads ~30 env vars ad-hoc via dmlc::GetEnv and documents them
# centrally in docs/how_to/env_var.md.  We invert that: vars are *registered*
# with a type, default and docstring, so `mxnet_tpu.base.env_registry` is the
# central, queryable documentation.
# ---------------------------------------------------------------------------
class EnvVar:
    __slots__ = ("name", "type", "default", "doc")

    def __init__(self, name, type_, default, doc=""):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc

    def get(self):
        raw = os.environ.get(self.name)
        if raw is None:
            return self.default
        if self.type is bool:
            return raw.lower() not in ("0", "false", "off", "")
        try:
            return self.type(raw)
        except (TypeError, ValueError):
            return self.default


env_registry: dict = {}
_env_lock = threading.Lock()


def register_env(name, type_, default, doc=""):
    """Register a typed environment variable; returns the EnvVar handle."""
    with _env_lock:
        var = env_registry.get(name)
        if var is None:
            var = EnvVar(name, type_, default, doc)
            env_registry[name] = var
        return var


def get_env(name, default=None):
    """Read a registered env var (falling back to raw os.environ lookup)."""
    var = env_registry.get(name)
    if var is not None:
        return var.get()
    return os.environ.get(name, default)


def use_compile_cache():
    """Point JAX's persistent compilation cache at a fixed place and
    return it.  Entry points call this (``chip_smoke.py``,
    ``benchmark/run.py``, the tools, the examples' ``fit``) — never
    ``import mxnet_tpu``, so the tests stay cache-free.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
    is configured in code.  Unset: ``<checkout>/.jax_cache``.  The path
    is part of the cache key, so it is never made from a temp name, a
    pid or the time — a directory that moves never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# Core runtime knobs, mirroring the reference's documented set where the
# concept survives on TPU (docs/how_to/env_var.md).
register_env("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
             "Execution mode: 'NaiveEngine' forces synchronous dispatch "
             "(block after every op) for debugging; anything else uses JAX's "
             "native async dispatch.")
register_env("MXNET_EXEC_BULK_EXEC_TRAIN", bool, True,
             "Whether to compile whole training graphs as one XLA program "
             "(the TPU analogue of bulk-exec segments).")
register_env("MXNET_BACKWARD_DO_MIRROR", bool, False,
             "Trade compute for memory in backward (jax.checkpoint/remat on "
             "eligible subgraphs; reference: graph_executor.cc:210-223).")
register_env("MXNET_PROFILER_AUTOSTART", bool, False,
             "Start the Chrome-trace profiler at import time.")
register_env("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
             "Threshold (elements) above which dist kvstore shards a value "
             "across servers/hosts (reference default 1e6).")
register_env("MXNET_IMPERATIVE_JIT", bool, True,
             "Route imperative NDArray dispatch (registry ops, dunders, "
             "in-place writes) through the bounded jax.jit compilation "
             "cache (cached_op.py).  '0' restores the eager "
             "primitive-by-primitive path bit-for-bit.")
register_env("MXNET_IMPERATIVE_JIT_CACHE_SIZE", int, 1024,
             "Max compiled executables held by the imperative cached-op "
             "LRU; least-recently-used entries are evicted beyond it.")
register_env("MXNET_IMPERATIVE_JIT_THRESHOLD", int, 2,
             "Sightings of a cache key before it compiles (tiered "
             "dispatch): below it calls run eagerly, so one-off shapes "
             "never pay a trace+compile.  1 compiles immediately.")
register_env("MXNET_IMPERATIVE_JIT_DONATE", bool, True,
             "Allow the cached imperative path to donate dead input "
             "buffers (optimizer mutate ops, __setitem__) to XLA on "
             "backends that support donation.  '0' disables donation "
             "while keeping cached dispatch.")
register_env("MXNET_KVSTORE_BARRIER_TIMEOUT", float, 600.0,
             "Seconds a worker waits at a barrier (and the reply "
             "deadline for dist_sync pushes, which block on the "
             "slowest peer) before concluding a peer died.")
register_env("MXNET_KVSTORE_RPC_TIMEOUT", float, 60.0,
             "Deadline (seconds) a dist-kvstore worker waits for one "
             "server/scheduler RPC reply before treating the endpoint as "
             "failed and retrying.  0 disables deadlines (block forever, "
             "the pre-fault-tolerance behavior).")
register_env("MXNET_KVSTORE_RPC_RETRIES", int, 3,
             "Retries after the first failed attempt of a dist-kvstore "
             "RPC (timeout or severed connection); each retry backs off "
             "exponentially and reconnects through the scheduler's "
             "current server address table.")
register_env("MXNET_KVSTORE_RPC_BACKOFF", float, 0.1,
             "Base (seconds) of the exponential retry backoff: attempt k "
             "sleeps min(cap, base*2^k), jittered into [d/2, d].")
register_env("MXNET_KVSTORE_RPC_BACKOFF_CAP", float, 10.0,
             "Upper bound (seconds) on one retry backoff sleep.")
register_env("MXNET_KVSTORE_RPC_CB_FAILS", int, 8,
             "Consecutive RPC failures to one endpoint before its "
             "circuit breaker opens and calls fail fast with MXNetError "
             "instead of hanging fanout threads.")
register_env("MXNET_KVSTORE_RPC_CB_RESET", float, 30.0,
             "Seconds an open circuit breaker waits before letting one "
             "half-open trial RPC probe the endpoint again.")
register_env("MXNET_KVSTORE_SNAPSHOT_DIR", str, "",
             "Directory where dist-kvstore servers snapshot their "
             "key->value store and updater state (atomic tmp+rename); "
             "empty disables snapshots.  A restarted server restores "
             "from it and rejoins under DMLC_PS_RECOVERY_RANK.")
register_env("MXNET_KVSTORE_SNAPSHOT_INTERVAL", float, 5.0,
             "Seconds between server snapshot writes (skipped when "
             "nothing changed); <= 0 snapshots synchronously after "
             "every mutation, before the push reply is sent.")
register_env("MXNET_KVSTORE_BUCKET_BYTES", int, 4 * 1024 * 1024,
             "Capacity (bytes) of one dist-kvstore fusion bucket: small "
             "parameters are coalesced in init order into buckets of at "
             "most this many fp32 payload bytes, and one push/pull RPC "
             "carries a whole bucket (kvstore_codec.BucketPlan).")
register_env("MXNET_KVSTORE_PIPELINE", bool, True,
             "Route dist-kvstore push/pull through the asynchronous "
             "priority pipeline (bounded in-flight window, bucket "
             "coalescing, lazy pull resolution at the next forward).  "
             "'0' restores the blocking per-parameter push-then-pull "
             "round trips.")
register_env("MXNET_KVSTORE_INFLIGHT", int, 4,
             "Max in-flight wire operations of the dist-kvstore "
             "pipeline (its worker-thread window).  Higher overlaps "
             "more RPC latency at the cost of more queued gradient "
             "memory.")
register_env("MXNET_KVSTORE_CONNS_PER_SERVER", int, 4,
             "Pooled connections each dist-kvstore worker keeps per "
             "server (multiprocessing.Connection is one-request-at-a-"
             "time, so the pipeline needs one connection per concurrent "
             "RPC to the same server).")
register_env("MXNET_KVSTORE_COMPRESS_LOWER_BOUND", int, 16,
             "Minimum elements before an enabled gradient compression "
             "applies to a key's pushes; smaller keys (and any non-fp32 "
             "payload: indices, aux state) stay lossless.")
register_env("MXNET_IO_STAGE", bool, True,
             "Overlapped device input staging: Module.fit stages batch "
             "t+1 onto the device (host->device upload on a background "
             "thread, double-buffered) while step t computes "
             "(io/stager.py).  '0' restores the per-step blocking "
             "upload bit-for-bit.")
register_env("MXNET_IO_STAGE_DEPTH", int, 2,
             "Bound on batches staged ahead of compute by the device "
             "input stager (the double-buffer depth).  Each slot pins "
             "one batch of device memory; 2 is classic double "
             "buffering.")
register_env("MXNET_DATA_SEED", int, 0,
             "Deterministic data-plane seed (data/sharded.py): epoch "
             "shuffle permutations derive from Philox(seed, epoch) — "
             "identical on every worker and restart — and record "
             "augmentation draws from a per-record generator keyed on "
             "(seed, epoch, ordinal), so a mid-epoch resume replays "
             "shuffle AND augmentation exactly.  0/unset = legacy "
             "behavior bit-for-bit: order and augmentation come from "
             "the module-global numpy RNG.")
register_env("MXNET_EXEC_DONATE", bool, True,
             "Donate dead auxiliary-state buffers (BatchNorm moving "
             "stats) into the symbolic Executor's jitted train "
             "programs so XLA updates them in place in HBM.  Applies "
             "off-CPU only (CPU PJRT has no donation), never when the "
             "graph holds Custom host callbacks.  '0' disables.")
register_env("MXNET_FAULT_INJECT", str, "",
             "Deterministic fault-injection schedule for the dist "
             "kvstore: inline JSON or a path to a JSON file (see "
             "mxnet_tpu/faultinject.py).  Unset = all fault hooks are "
             "no-ops.")
register_env("MXNET_MIRROR_SEGMENT", int, 0,
             "Ops per jax.checkpoint segment when "
             "MXNET_BACKWARD_DO_MIRROR=1 (the rematerialization chunk "
             "size).  0 = the sqrt(op_count) heuristic.")
register_env("MXNET_SPMD", bool, True,
             "Route multi-device training through the ONE shared SPMD "
             "step program (parallel/spmd.py): forward+backward+in-graph "
             "optimizer update compiled once over a jax.sharding.Mesh, "
             "batch sharded on the dp axis, gradient reduction as an XLA "
             "all-reduce inside the step.  '0' restores the classic "
             "per-device executor replication path (host gradient "
             "aggregation + host updater) bit-for-bit and makes trainers "
             "compile privately instead of sharing the program cache.")
register_env("MXNET_SPMD_PROGRAM_CACHE", int, 64,
             "Max compiled SPMD step programs held by the shared "
             "program LRU (one per (symbol, mesh, shapes, dtype, "
             "optimizer statics, sharding rules) key); least-recently-"
             "used programs are dropped beyond it and recompile on "
             "next use.")
register_env("MXNET_MODULE_FUSED", bool, True,
             "Fused Module.fit fast path (forward+backward+psum+update "
             "as one XLA program).  '0' falls back to full "
             "executor-group semantics.")
register_env("MXNET_USE_NATIVE_IO", bool, True,
             "Use the C++ RecordIO reader/prefetcher when the native "
             "toolchain is available.  '0' forces the pure-python "
             "fallback backend.")
register_env("MXNET_ASYNC_CHECKPOINT", bool, True,
             "Queue nd.save checkpoint writes onto the native host "
             "engine (serialized per destination) instead of blocking "
             "the caller.  '0' writes synchronously.")
register_env("MXNET_CPU_WORKER_NTHREADS", int, os.cpu_count() or 4,
             "Worker threads of the native host-task engine (IO, "
             "decode, async checkpoint writes).")
register_env("MXNET_PROFILER_JAX_LOGDIR", str, "",
             "When set, profiler_set_state('run') also starts a "
             "jax.profiler trace into this directory (real XLA/TPU "
             "kernel timelines beside the Chrome trace).")
register_env("MXNET_KVSTORE_HEARTBEAT_INTERVAL", float, 1.0,
             "Seconds between liveness beats a dist-kvstore node sends "
             "the scheduler on its dedicated heartbeat connection "
             "(feeds get_num_dead_node).")
register_env("MXNET_KVSTORE_MAX_STALENESS", int, -1,
             "Bounded-staleness knob for dist_async (SSP): a worker's "
             "pull blocks on the server until its own per-key version "
             "is at most this many update steps ahead of the slowest "
             "live worker's.  0 degenerates to sync-read semantics; "
             "negative disables the bound (pure hogwild, the "
             "pre-elastic dist_async behavior).")
register_env("MXNET_KVSTORE_DEAD_TIMEOUT", float, 15.0,
             "Heartbeat silence (seconds) before the scheduler's "
             "epoched membership view declares a worker dead: the "
             "epoch bumps, barrier counts shrink, and servers retire "
             "the dead rank's version-vector entries so it can never "
             "stall the bounded-staleness frontier.")
register_env("MXNET_KVSTORE_MEMBERSHIP_TTL", float, 0.5,
             "Seconds a dist-kvstore server caches the scheduler's "
             "epoched membership view while gating stale pulls; also "
             "the re-check tick of a blocked staleness wait.")
register_env("MXNET_LOCK_CHECK", bool, False,
             "Dynamic lock-discipline checking (analysis/lockcheck.py): "
             "locks created at the engine/kvstore/stager seams record "
             "per-thread acquisition orders and raise on a lock-order "
             "cycle (potential deadlock) or on guarded shared state "
             "mutated without its lock held.  Debug/CI aid; off by "
             "default.")
register_env("MXNET_RACE_CHECK", bool, False,
             "Happens-before data-race detection (analysis/"
             "racecheck.py): per-thread vector clocks over the queue/"
             "event/future/thread/make_lock seams plus shared_state() "
             "tracked fields; an access unordered against an earlier "
             "conflicting access raises DataRaceError naming both "
             "threads, stacks and the field.  Debug/CI aid (make "
             "racecheck); off by default — hot paths pay zero cost "
             "when unset.")
register_env("MXNET_SCHED_SEED", int, -1,
             "Pin the deterministic schedule explorer (analysis/"
             "schedules.py) to ONE seeded interleaving: a test body "
             "under schedules.explore() replays exactly the schedule "
             "this seed generated (a failing schedule prints it).  "
             "Negative (default) = not pinned.")
register_env("MXNET_SCHED_EXPLORE", int, 0,
             "Number of distinct seeded PCT-style schedules "
             "schedules.explore() replays a test body under (priority "
             "preemption at every queue/event/future/lock/"
             "shared_state yield point).  0/1 = a single schedule; "
             "CI arms it on the interleaving-sensitive protocol "
             "tests.")
register_env("MXNET_SERVE_BUCKETS", str, "1,2,4,8,16,32",
             "Comma-separated batch-size bucket edges of the serving "
             "program store (serving/program_store.py): a request of n "
             "rows is padded up to the smallest edge >= n and runs the "
             "AOT-compiled program for that bucket, so arbitrary "
             "request sizes hit a small fixed set of compiled "
             "programs.")
register_env("MXNET_SERVE_MAX_DELAY_MS", float, 5.0,
             "Per-request latency budget (milliseconds) of the "
             "continuous batching scheduler: a batch is flushed no "
             "later than this long after its OLDEST member was "
             "submitted, even if the largest bucket has not filled.  "
             "0 dispatches every request immediately (no batching "
             "delay).")
register_env("MXNET_SERVE_MAX_BATCH", int, 32,
             "Upper bound on rows the continuous batcher coalesces "
             "into one serving dispatch (further capped by the "
             "largest configured shape bucket).")
register_env("MXNET_SERVE_PROGRAM_CACHE", int, 32,
             "Max AOT-compiled serving programs held per model by the "
             "program store's LRU (one per shape bucket signature); "
             "least-recently-used executables are dropped beyond it "
             "and recompile on next use (stats count the evictions).")
register_env("MXNET_PALLAS", str, "1",
             "Pallas kernel dispatch at the op-lowering seam "
             "(pallas_ops/dispatch.py): '1' (default) routes eligible "
             "patterns (SoftmaxOutput-style loss heads, LayerNorm/"
             "RMSNorm, DotProductAttention) to the hand-blocked Mosaic "
             "kernels when the backend is a TPU; '0' is the escape "
             "hatch (plain XLA lowering everywhere, bit-for-bit); '2' "
             "forces interpret-mode kernels even off-TPU (parity tests "
             "and make kernels-smoke).")
register_env("MXNET_PALLAS_BLOCK_ROWS", int, 8,
             "Row-block bound of the row-wise Pallas kernels (fused "
             "softmax/cross-entropy, RMSNorm, LayerNorm): rows per VMEM "
             "tile, clamped to a divisor of the row count and to the "
             "VMEM tile budget.")
register_env("MXNET_PALLAS_BLOCK_SEQ", int, 128,
             "Sequence-block bound of the Pallas flash-attention "
             "kernel (block_q/block_k); sequence lengths must tile "
             "exactly by the clamped block for the kernel route to "
             "qualify.")
register_env("MXNET_REMAT_POLICY", str, "",
             "Named jax.checkpoint rematerialization policy for train "
             "programs (mxnet_tpu/remat.py): one of nothing_saveable, "
             "everything_saveable, dots_saveable, "
             "dots_with_no_batch_dims_saveable.  On the classic "
             "Executor it selects the policy of the chunked "
             "MXNET_BACKWARD_DO_MIRROR remat path (and activates it); "
             "on the SPMD step program it wraps the loss under "
             "jax.checkpoint(policy=...) and is part of the program-"
             "cache key.  Empty disables.")
register_env("MXNET_SERVE_DTYPE", str, "",
             "Default serving compute dtype for models registered "
             "without an explicit compute_dtype ('bfloat16' halves "
             "weight memory and feeds the MXU; outputs are returned "
             "as float32 either way).  Empty keeps the checkpoint "
             "dtype (fp32 serving, bit-equal to the classic "
             "Predictor).")
register_env("MXNET_SERVE_KV_BLOCK", int, 64,
             "Tokens per KV-cache block on the serving decode plane "
             "(serving/program_store.py GenerativeProgramStore): cache "
             "lengths are quantized UP to block multiples, so one "
             "decode-step program per (batch-bucket, cache-bucket) "
             "covers a whole block of sequence lengths and the cache "
             "grows block-at-a-time instead of per token.")
register_env("MXNET_SERVE_KV_MAX", int, 1024,
             "Upper bound on a served sequence's KV-cache length "
             "(prompt + generated tokens).  Generation requests whose "
             "prompt_len + max_tokens exceed it are rejected at "
             "submit, so a decode batch can never outgrow its cache "
             "mid-flight.")
register_env("MXNET_SERVE_KV_DTYPE", str, "float32",
             "KV-cache element dtype on the serving decode plane "
             "('float32', 'bfloat16' or 'int8').  bfloat16 halves "
             "cache bytes per slot — the same cache memory budget "
             "holds 2x the concurrent sequences.  'int8' (paged plane "
             "only, MXNET_SERVE_PAGED=1) stores pool blocks as int8 "
             "codes with per-(block, head) fp32 absmax scales riding "
             "as a parallel donated scale pool — ~4x fewer cache "
             "bytes per token than fp32, dequantized on-tile inside "
             "the paged flash kernel AND identically in its dense "
             "twin.  Attention over the cache accumulates fp32 on "
             "every path; decode parity is pinned at relaxed "
             "tolerance (tests/test_quant_serving.py, "
             "tests/test_spec_decode.py).")
register_env("MXNET_SERVE_PAGED", int, 1,
             "Paged KV cache on the serving decode plane ('1', "
             "default): cache memory is a global pool of "
             "MXNET_SERVE_KV_BLOCK-token blocks addressed through "
             "per-slot block tables, with copy-on-write prefix "
             "sharing and chunked prefill "
             "(docs/architecture/decode_engine.md).  '0' is the "
             "escape hatch: the contiguous per-slot cache plane, "
             "bit-for-bit the pre-paging behavior (pinned by "
             "tests/test_paged_decode.py).")
register_env("MXNET_SERVE_PREFILL_CHUNK", int, 32,
             "Chunked-prefill quantum of the paged decode plane: a "
             "prompt is consumed this many tokens per engine tick, "
             "interleaved with the running decode batch's steps, so "
             "one long prompt cannot stall every other stream's "
             "inter-token latency for its whole prefill.  Clamped to "
             "MXNET_SERVE_KV_MAX; only the paged plane "
             "(MXNET_SERVE_PAGED=1) chunks.")
register_env("MXNET_SERVE_KV_POOL_BLOCKS", int, 0,
             "Physical block count of the paged KV pool (including "
             "the reserved trash block 0 that zero table entries "
             "point at).  0 (default) sizes the pool so the largest "
             "batch bucket can hold full-depth sequences: "
             "max_batch_bucket * ceil(kv_max / kv_block) + 1.  The "
             "pool — not per-slot max-length reservations — bounds "
             "admission: requests that cannot fit shed with "
             "ServeOverloaded.")
register_env("MXNET_SERVE_SAMPLE", str, "graph",
             "Where generation sampling runs: 'graph' (default) "
             "compiles greedy + seeded temperature/top-k INTO the "
             "decode programs (per-slot jax.random key state rides as "
             "a donated program argument; the per-step host transfer "
             "shrinks from the (slots, vocab) logits matrix to the "
             "(slots,) token vector); 'host' is the escape hatch — "
             "logits-out decode programs plus the SAME jitted sampler "
             "on the fetched logits, byte-identical token streams.")
register_env("MXNET_SERVE_SPEC", str, "auto",
             "Speculative decoding on the paged decode plane "
             "(serving/decode_engine.py): 'auto' (default) turns it "
             "on for any generative model that has a draft attached "
             "via registry.add_draft_model AND runs paged in-graph "
             "sampling (MXNET_SERVE_PAGED=1, MXNET_SERVE_SAMPLE="
             "graph), and ADAPTS — when the rolling acceptance EMA "
             "collapses below the floor the engine falls back to "
             "plain decode ticks (probing speculation periodically "
             "so a friendlier workload re-engages it); '1'/'force' "
             "always drafts regardless of acceptance; '0' disables "
             "even with a draft registered.  The draft proposes "
             "MXNET_SERVE_SPEC_K tokens per tick, the target "
             "verifies all K+1 positions in ONE program call with "
             "the accept/reject rule in-graph — token streams stay "
             "distribution-identical to non-speculative decoding "
             "(greedy: byte-identical), speedup comes only from "
             "fewer target-model steps.")
register_env("MXNET_SERVE_SPEC_K", int, 4,
             "Draft tokens proposed per speculative-decoding tick "
             "(the target verifies K+1 positions per program call).  "
             "Larger K amortizes more target steps when acceptance "
             "is high but wastes draft steps when it collapses; the "
             "verify program shape is lq=K+1, warmed at "
             "add_draft_model time.")
register_env("MXNET_SERVE_INT8_GRANULARITY", str, "row",
             "Scale granularity of int8 weight-only serving "
             "quantization (pallas_ops/dequant_matmul.quantize_int8): "
             "'row' (default) keeps one fp32 scale per output row — "
             "per-row absmax isolates badly scaled rows — 'tensor' "
             "keeps a single scalar scale per weight.")
register_env("MXNET_SERVE_PROMPT_BUCKETS", str, "16,32,64,128",
             "Comma-separated prompt-length bucket edges of the "
             "serving prefill programs: a prompt of p tokens is "
             "zero-padded up to the smallest edge >= p and runs the "
             "AOT-compiled prefill program for that (batch, prompt) "
             "bucket pair.")
register_env("MXNET_SERVE_MAX_INFLIGHT", int, 0,
             "Admission-control budget of a serving engine: the max "
             "number of accepted-but-unresolved requests (forward or "
             "generation) it holds before SHEDDING new submits with a "
             "structured ServeOverloaded (HTTP 429 at the front door) "
             "instead of queueing them into timeout collapse.  0 "
             "(default) = unbounded.  Per engine, so per replica in a "
             "ReplicaSet (serving/replica_set.py).")
register_env("MXNET_SERVE_PROBE_INTERVAL", float, 0.25,
             "Health-probe period (seconds) of the serving ReplicaSet's "
             "prober thread: every interval each replica is probed "
             "through the serve.dispatch seam and its circuit breaker "
             "updated — a dead replica leaves the balancer rotation "
             "within one interval, a recovered one returns.  <= 0 "
             "disables the prober (tests drive probe_once() directly).")
register_env("MXNET_SERVE_RETRIES", int, 2,
             "Failover budget of the serving ReplicaSet: how many times "
             "one forward request may be re-dispatched onto a surviving "
             "replica after a retryable failure (replica died, engine "
             "closed, connection severed) before its last error is "
             "surfaced.  Forward requests are idempotent; generation "
             "requests only retry placement failures — once admitted "
             "they fail fast (their KV state dies with the replica).")
register_env("MXNET_SERVE_RETRY_BACKOFF", float, 0.02,
             "Base (seconds) of the ReplicaSet's failover backoff: "
             "retry k of a failed-over request sleeps "
             "backoff_delay(k, base, 16*base) (mxnet_tpu/retry.py — "
             "the kvstore plane's exponential policy math) before "
             "re-dispatching.")
register_env("MXNET_SERVE_CB_FAILS", int, 2,
             "Consecutive dispatch/probe failures that open one serving "
             "replica's circuit breaker (mxnet_tpu/retry.py "
             "CircuitBreaker): an open breaker takes the replica out of "
             "the balancer rotation without paying its failure latency "
             "per request.")
register_env("MXNET_SERVE_CB_RESET", float, 1.0,
             "Cool-down (seconds) before an OPEN serving-replica "
             "breaker admits one half-open trial (the next probe or "
             "request): trial success re-closes the breaker and the "
             "replica rejoins the rotation, failure re-opens it.")
register_env("MXNET_SERVE_AUTOSCALE", int, 0,
             "1 starts the serving autoscaler thread when an AutoScaler "
             "is attached to a ReplicaSet without an explicit start= "
             "argument (serving/controller.py): each tick it reads the "
             "metrics registry (windowed queue-wait p95 vs "
             "MXNET_SERVE_SLO_MS, shed deltas, inflight utilization) "
             "and grows/shrinks the replica set between "
             "MXNET_SERVE_MIN_REPLICAS and MXNET_SERVE_MAX_REPLICAS.  "
             "0 (default) leaves sizing manual; evaluate_once() still "
             "works for explicitly driven controllers.")
register_env("MXNET_SERVE_SLO_MS", float, 50.0,
             "The serving latency SLO target (milliseconds) the "
             "autoscaler defends: queue-wait p95 over the last tick "
             "window above this scales up; p95 under half of it (with "
             "no sheds and low utilization) is the hysteresis band "
             "that allows scale-down.")
register_env("MXNET_SERVE_MIN_REPLICAS", int, 1,
             "Autoscaler floor: the replica set is never shrunk below "
             "this many replicas, regardless of how idle the signals "
             "look.")
register_env("MXNET_SERVE_MAX_REPLICAS", int, 8,
             "Autoscaler ceiling: the replica set is never grown past "
             "this many replicas, regardless of queue pressure — the "
             "overload path beyond it is admission shedding "
             "(MXNET_SERVE_MAX_INFLIGHT), not more capacity.")
register_env("MXNET_SERVE_AUTOSCALE_INTERVAL", float, 0.25,
             "Seconds between autoscaler evaluation ticks (the metric "
             "window length: each tick judges the histogram/counter "
             "deltas since the previous tick).")
register_env("MXNET_SERVE_AUTOSCALE_COOLDOWN", float, 1.0,
             "Minimum seconds between autoscaler scale ACTIONS (up or "
             "down).  Ticks keep observing during the cool-down; only "
             "actions are rate-limited, so one burst cannot slam the "
             "set from min to max and back within a window.")
register_env("MXNET_SERVE_SWAP_RATE", float, 0.0,
             "Rolling weight swap rate limit: seconds to pause between "
             "finishing one replica's drain→swap→re-probe cycle and "
             "starting the next (ReplicaSet.swap_params).  0 (default) "
             "rolls as fast as the drains allow; the roll is still one "
             "replica at a time.")
register_env("MXNET_SERVE_SWAP_DRAIN_S", float, 5.0,
             "Per-replica drain budget (seconds) of the rolling weight "
             "swap: how long to wait for a rotation-removed replica's "
             "inflight requests to finish before swapping anyway (the "
             "store-level swap is atomic per dispatch, so exceeding "
             "the budget risks nothing worse than a request crossing "
             "the version boundary between its retries).")
register_env("MXNET_SERVE_AUTH_TOKEN", str, "",
             "Bearer token the HTTP front door requires when set: "
             "requests must carry 'Authorization: Bearer <token>' or "
             "they get a structured 401 (GET /healthz and GET /metrics "
             "stay open for probes and scrapers).  Empty (default) "
             "disables auth; pair with MXNET_SERVE_TLS_CERT/_KEY (or "
             "a terminating proxy) so the token never crosses the "
             "wire in cleartext.")
register_env("MXNET_SERVE_TLS_CERT", str, "",
             "Path to a PEM certificate chain for the HTTP front "
             "door: set together with MXNET_SERVE_TLS_KEY to wrap "
             "the stdlib server socket in TLS (ssl.SSLContext, "
             "PROTOCOL_TLS_SERVER) — the front door's url becomes "
             "https:// and HttpClient speaks TLS to it.  Empty "
             "(default) serves plain HTTP.  Setting only one of the "
             "pair is a configuration error.")
register_env("MXNET_SERVE_TLS_KEY", str, "",
             "Path to the PEM private key matching "
             "MXNET_SERVE_TLS_CERT (may be the same file when the "
             "key is appended to the cert).  Both set = TLS on; "
             "both empty = plain HTTP.")
register_env("MXNET_SERVE_TLS_VERIFY", str, "1",
             "How HttpClient verifies the front door's TLS "
             "certificate: '1' (default) uses the system trust "
             "store; '0' disables verification (self-signed dev "
             "certs — the connection is still encrypted but not "
             "authenticated); a path verifies against that CA/cert "
             "PEM file (the self-signed round-trip test pins its "
             "own cert this way).")
register_env("MXNET_TRACE_SAMPLE", float, 1.0,
             "Per-request trace sampling rate in [0, 1] "
             "(mxnet_tpu/tracing.py): each trace minted at the serving "
             "front door (or at submit for in-process callers) is "
             "sampled deterministically from (MXNET_TRACE_SEED, mint "
             "sequence); unsampled traces keep their id but record no "
             "spans.  0 restores the untraced fast path; 1 (default) "
             "traces every request.")
register_env("MXNET_TRACE_SEED", int, 0,
             "Seed of the deterministic per-trace sampling hash: the "
             "same (seed, sequence, rate) samples the same requests on "
             "every host and run (tracing.sample_decision).")
register_env("MXNET_TRACE_JSONL", str, "",
             "Path of the structured per-trace JSONL sink: every "
             "finished SAMPLED trace appends one JSON line (trace id, "
             "status, span tree with parent ids and ms timings).  "
             "Empty disables the sink (spans still reach the Chrome "
             "trace when the profiler runs, and the flight ring "
             "either way).")
register_env("MXNET_METRICS", bool, True,
             "Ambient metrics instrumentation (mxnet_tpu/metrics.py): "
             "'0' silences the phase() histogram feed and other "
             "ambient observation seams.  Explicitly created "
             "instruments — the counters legacy stats() trees read "
             "through — keep counting either way.")
register_env("MXNET_FLIGHT_CAPACITY", int, 2048,
             "Events held by the crash flight recorder's bounded ring "
             "(mxnet_tpu/tracing.py FlightRecorder: recent spans/"
             "events/errors, fixed memory, dumped on engine-loop "
             "crash, on the serve.dispatch faultinject die path, and "
             "on demand via GET /debug/flight or flight.dump()).  0 "
             "disables recording entirely.")
register_env("MXNET_FLIGHT_DIR", str, "",
             "Directory where flight-recorder postmortems are written "
             "(flight.<pid>.<n>.json via base.atomic_write) when an "
             "engine loop crashes or a serving replica is killed.  "
             "Empty disables the on-disk dumps; the in-memory ring "
             "stays readable (GET /debug/flight).")
register_env("MXNET_SERVE_STATS_TTL_MS", float, 250.0,
             "Max age (milliseconds) of the serving front door's "
             "cached /stats snapshot: within it, polls are served "
             "from the cache (with an age_ms field) instead of "
             "re-walking the full stats tree per request.  <= 0 "
             "re-walks every poll (the pre-cache behavior).")
register_env("MXNET_AUTO_RESUME", str, "",
             "Checkpoint prefix for hands-off crash resume: when set, "
             "Module.fit() with no explicit resume_data_state loads "
             "the latest .dstate envelope saved under this prefix "
             "(data/checkpoint.py) before the first batch.  "
             "tools/launch.py --auto-resume exports it to (re)launched "
             "workers so a restarted process picks up the mid-epoch "
             "frontier without the training script threading it by "
             "hand.  Empty disables.")
register_env("MXNET_MESH_COORDINATOR", str, "",
             "host:port of the jax.distributed coordinator for the "
             "dist_mesh collectives backend.  tools/launch.py --mesh N "
             "exports it (plus MXNET_MESH_NUM_PROCESSES / "
             "MXNET_MESH_PROCESS_ID) to every spawned process; "
             "parallel.mesh.distributed_init_from_env() reads the "
             "triple and boots this process into the one global mesh.  "
             "Empty means single-process (the 8-fake-device CI shape).")
register_env("MXNET_MESH_NUM_PROCESSES", int, 0,
             "Process census for jax.distributed.initialize under "
             "tools/launch.py --mesh; 0 (unset) means single-process.")
register_env("MXNET_MESH_PROCESS_ID", int, 0,
             "This process's stable rank under tools/launch.py --mesh; "
             "a crashed worker restarted by --auto-resume supervision "
             "re-exports the SAME id so it rejoins its old mesh slot.")
register_env("MXNET_MESH_REDUCE", str, "bucket",
             "Gradient-reduction variant for the dist_mesh one-program "
             "path: 'bucket' (default) compiles the reduce-per-bucket "
             "step (grad program + one collective per "
             "MXNET_KVSTORE_BUCKET_BYTES bucket + apply program) so "
             "tail-layer communication overlaps head-layer work; "
             "'fused' keeps the single fused train step (one in-graph "
             "psum at step end).  A program-cache key field, so both "
             "variants coexist compiled.")
register_env("MXNET_MESH_OVERLAP", bool, True,
             "Whether dist_mesh bucket collectives launch concurrently "
             "(overlapped, default) or serialize behind one another "
             "(barrier semantics — the baseline the live overlap test "
             "of tests/test_dist_mesh.py compares against).")
register_env("MXNET_KVSTORE_REBALANCE", bool, False,
             "Arm the automatic load-driven PS rebalance trigger: the "
             "rank-0 dist worker samples rebalance_signal() every "
             "MXNET_KVSTORE_REBALANCE_INTERVAL seconds and migrates "
             "one hot bucket to the coldest server whenever imbalance "
             "exceeds MXNET_KVSTORE_REBALANCE_THRESHOLD (the manual "
             "migrate_bucket handshake, now closed-loop).")
register_env("MXNET_KVSTORE_REBALANCE_THRESHOLD", float, 2.0,
             "Hot-server imbalance ratio (hottest server's windowed "
             "push bytes over the mean) above which the rebalance "
             "trigger migrates a bucket; <= 1.0 would thrash and is "
             "clamped to 1.1.")
register_env("MXNET_KVSTORE_REBALANCE_INTERVAL", float, 2.0,
             "Seconds between rebalance-trigger evaluations (each one "
             "reads the per-server wire-byte counters from the metrics "
             "registry and migrates at most one bucket).")
register_env("MXNET_KVSTORE_REBALANCE_MIN_BYTES", int, 1 << 20,
             "Minimum windowed push traffic (bytes across all servers) "
             "before the rebalance trigger acts — keeps idle or "
             "drained clusters from migrating on noise.")


def hot_path(fn):
    """Mark ``fn`` as part of a latency-critical loop (the fit step loop,
    cached-op dispatch, pipeline submit).  Purely declarative at runtime;
    ``tools/lint.py``'s ``host-sync`` rule rejects host-synchronizing
    calls (``block_until_ready``, ``np.asarray``, ``.item()``, ...)
    inside any function carrying this decorator
    (docs/architecture/static_analysis.md).
    """
    fn.__hot_path__ = True
    return fn


_ATOMIC_WRITE_SEQ = itertools.count()


@contextlib.contextmanager
def atomic_write(path, mode="wb"):
    """Crash-safe file write: yields a handle onto a temp file in the
    same directory, fsyncs, then ``os.replace``s it over ``path`` — a
    reader never observes a half-written file and a crash mid-write
    leaves the previous contents intact (checkpoints, server snapshots).
    Temp names are unique per write, so concurrent writers of the same
    path each land a complete file (last replace wins) instead of
    interleaving into a corrupt one.
    """
    tmp = "%s.tmp%d.%d" % (path, os.getpid(), next(_ATOMIC_WRITE_SEQ))
    f = open(tmp, mode)
    try:
        yield f
        f.flush()
        os.fsync(f.fileno())
    except BaseException:
        f.close()
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    f.close()
    os.replace(tmp, path)


_UID_LOCK = threading.Lock()
_UID_COUNT = [0]


def _uid():
    with _UID_LOCK:
        _UID_COUNT[0] += 1
        return _UID_COUNT[0]
