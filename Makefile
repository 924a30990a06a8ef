# Local entry points for the CI stages defined in ci.yaml.
PY ?= python

.PHONY: test quick build dist convergence dist-smoke elastic-smoke serve-smoke frontdoor-smoke decode-smoke spmd-smoke mesh-smoke kernels-smoke data-smoke obs-smoke chaos-smoke step-profile ci-quick ci-full docs chip-smoke hygiene lint lockcheck racecheck

# fail if any binary / scratch artifact is tracked (ci.yaml per-change
# `hygiene` stage; the lazy builder regenerates *.so)
hygiene:
	@bad=$$(git ls-files | grep -E '\.(so|log|o|a|dylib|pyc|bin)$$' || true); \
	if [ -n "$$bad" ]; then \
		echo "tracked binary/scratch artifacts (git rm them):"; \
		echo "$$bad"; exit 1; \
	fi; echo "hygiene: clean"

# project-specific static analysis (env-knob registry sync, donation
# safety, host-sync-in-hot-path, thread discipline, profiler-span
# coverage); rule catalog + suppression syntax in
# docs/architecture/static_analysis.md.  Zero-violation gate.
lint:
	$(PY) tools/lint.py mxnet_tpu tools

# dynamic lock-order race detector (analysis/lockcheck.py) armed over
# the suites that exercise all three thread pools: the device input
# stager and the kvstore data-plane pipeline.  A lock-order cycle or an
# unlocked seam mutation fails the run at acquisition time.
lockcheck:
	timeout -k 10 300 env MXNET_LOCK_CHECK=1 JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/test_input_staging.py \
		tests/test_kvstore_codec.py -q

# happens-before data-race detector (analysis/racecheck.py) armed over
# the serving/PS concurrency planes: an unsynchronized write racing
# any access of a tracked field raises DataRaceError naming both
# threads and stacks.  The explorer's own suite (seeded cooperative
# schedules, the PR-16 rank-race fixture) runs first.
racecheck:
	timeout -k 10 420 env MXNET_RACE_CHECK=1 JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/test_racecheck.py \
		tests/test_decode_engine.py tests/test_frontdoor.py \
		tests/test_elastic_ps.py -q -m 'not slow'

quick:
	$(PY) -m pytest tests/ -m quick -q

build:
	$(PY) -m pytest tests/ -m build -q

dist:
	$(PY) -m pytest tests/ -m dist -q

# seeded fault-injection recovery scenarios (server SIGKILLed mid-push,
# snapshot restore, worker retry/reconnect — plain AND with the
# compressed+bucketed data plane enabled) plus the bytes-on-wire
# assertion (2bit pushes <= 1/8 of fp32 payload on the same schedule),
# under a hard timeout so a kvstore robustness regression fails fast
# instead of hanging CI
# MXNET_LOCK_CHECK=1: the recovery scenarios double as the lock-order
# audit of the kvstore pipeline + conn-pool under retry/reconnect load
dist-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu MXNET_LOCK_CHECK=1 \
		$(PY) -m pytest tests/test_fault_tolerance.py -q \
		-k "seeded or wire_bytes"

# elastic-async PS gate (docs/architecture/elastic_ps.md): the
# straggler scenario (dist_async s=4 runs ahead of one injected
# straggler to the bound, dist_sync waits for it every round, by
# counts + the staleness-bound property + s=0 sync parity), elastic
# membership (heartbeat death epochs, worker join at the frontier) and live bucket rebalancing under traffic (exactly-
# once across the migration, capacity add/remove).  MXNET_LOCK_CHECK=1
# arms the lock-order race detector over the new staleness/membership/
# migration lock paths; hard timeout like dist-smoke
elastic-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu MXNET_LOCK_CHECK=1 \
		$(PY) -m pytest tests/test_elastic_ps.py -q

# serving-plane smoke gate: the continuous batcher (AOT bucket programs
# + latency-budget scheduler) vs a per-request Predictor deployment
# under the SAME seeded open-loop arrival schedule (serving/loadgen.py).
# Gates: batcher achieved QPS >= 3x the per-request deployment's, p99
# no worse, zero dropped requests.  Deterministic seed; the ratio is
# host-relative so the gate holds on any machine.
serve-smoke:
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
		$(PY) tools/serve_smoke.py --seed 11 --qps-floor 3.0

# serving front-door gate (docs/architecture/serving_frontdoor.md):
# HTTP endpoint vs in-process on the SAME seeded schedule (zero drops,
# achieved tracks offered), kill-one-of-3-replicas under load (100% of
# accepted requests resolve, balancer converges to survivors, post-kill
# QPS >= 2/3 pre-kill) and hot weight swap under traffic (every
# response bit-matches exactly one weight version, version counter +1).
# Hard timeout like the other smokes.
frontdoor-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu \
		$(PY) tools/serve_smoke.py --seed 11 --replicas 3 \
		--http --kill-one --swap

# decode-plane gate (docs/architecture/decode_engine.md): the offset
# flash kernel vs its dense twin, decode-vs-one-shot logits parity
# (MXNET_PALLAS routed AND the =0 escape hatch), the -1e30 cache-pad
# mask pin, the generative program store's AOT warm set, and the
# continuous-batching GenerationEngine — greedy == reference, seeded-
# loadgen FIFO admission, close-mid-generation drain, KV-cache growth
# — and the low-precision serving plane
# (tests/test_quant_serving.py): int8 weight-only (fused dequant-matmul vs dense twin, >= 99% greedy top-1 agreement,
# ~4x weight bytes), bf16 KV decode (relaxed-tol parity, halved cache
# bytes/slot), in-graph vs host sampling byte-identical streams and
# the zero-logits-fetch pin
decode-smoke:
	timeout -k 10 1200 env JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/test_decode_engine.py \
		tests/test_paged_decode.py tests/test_paged_kernels.py \
		tests/test_paged_pool.py tests/test_paged_one_pass.py \
		tests/test_quant_serving.py \
		tests/test_spec_decode.py tests/test_spec_decode_policy.py \
		-q -m quick

# one-SPMD-step-program gate under 8 fake host devices: numerical
# equivalence (dp8 vs single device, dp2xmp2 vs dp4, closed-form SGD),
# the shared-program-cache pin across frontends and the MXNET_SPMD=0
# escape hatch
spmd-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu \
		XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest tests/test_spmd_step.py -q

# collectives-kvstore gate under 8 fake host devices: dist_mesh
# push/pull closed forms, the SAME-Module.fit-script PS/mesh parity,
# bucket-reduce bit-exactness vs the fused step, every bucket's reduce
# in flight at once when overlapped and one at a time when barriered,
# under injected collective latency, the dist_mesh program-
# cache key, launch.py --mesh end-to-end (multi-process leg skips on
# CPU jaxlib)
mesh-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu \
		XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest tests/test_dist_mesh.py -q

# Pallas kernel plane + remat policy gate, deterministic on CPU: every
# kernel's REAL body runs in interpret mode — CPU parity only; whether
# the chip's compiler accepts the kernels is tests/test_chip_compile.py,
# whether they run is `make chip-smoke` — (fused softmax/xent, RMSNorm,
# LayerNorm, flash attention) pinned against the plain XLA lowering —
# forward AND gradients — plus the MXNET_PALLAS=0 bit-for-bit escape
# hatch, the dispatch-fingerprint cache keys and the remat policies'
# residual-memory reduction at pinned numerics
kernels-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/test_pallas_kernels.py \
		tests/test_remat_policy.py -q

# checkpointable-data-plane gate (docs/architecture/data_pipeline.md):
# the state_dict/load_state round-trip property over every shipped
# DataIter, seeded mid-epoch fit resume with a byte-identical remaining
# stream (also under num_parts=2 sharding) and the subprocess
# SIGKILL-mid-epoch scenario.
# The conftest thread-leak gate covers the pipeline/stager/prefetch
# threads; hard timeout like the other smokes
data-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/test_data_pipeline.py -q

# telemetry-plane gate (docs/architecture/observability.md): the
# trace-id propagation pin (one HTTP :generate yields a connected
# frontdoor->replica->engine->prefill->decode span tree under a single
# trace id, across a replica retry), log-bucketed histogram quantile
# accuracy vs numpy.percentile, deterministic seeded trace sampling,
# the flight-recorder postmortem after the seeded replica-die scenario
# (artifact names the dying replica), GET /metrics Prometheus parse,
# the cached /stats age_ms contract, stats()-reads-through-registry
# pins, and the live telemetry overhead smoke
obs-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/test_observability.py -q

# serving control-plane chaos campaign (tools/chaos_campaign.py): the
# composed seeded multi-fault schedule (straggler pair + replica kill
# + injected-error pair at the serve.dispatch seam) against the full
# stack — HTTP front door -> autoscaled replicas -> engines — gated on
# zero lost requests, SLO-bounded recovery and a connected trace for
# every retried request; plus the SLO-driven autoscaler over seeded
# diurnal/bursty swings (up AND down, p95 under SLO, fewer
# replica-seconds than static max-size provisioning) and the rolling
# weight swap under traffic (zero failures, zero torn reads).
# MXNET_LOCK_CHECK on: the controller/prober/engine lock discipline is
# part of the gate; hard timeout like the other smokes
chaos-smoke:
	timeout -k 10 420 env JAX_PLATFORMS=cpu MXNET_LOCK_CHECK=1 \
		$(PY) tools/chaos_campaign.py --seed 41

# smoke fit under the profiler -> per-step phase breakdown
# (data_wait/h2d_stage/compute/metric_fetch) from the dumped trace, so
# the report format tools/step_profile.py emits cannot rot
step-profile:
	timeout -k 10 180 env JAX_PLATFORMS=cpu \
		$(PY) tools/step_profile.py --delay-ms 5

convergence:
	$(PY) -m pytest tests/ -m convergence -q

test:
	$(PY) -m pytest tests/ -q

docs:
	$(PY) tools/docgen.py
	$(PY) tools/docgen_python.py
	$(PY) tools/gen_cpp_ops.py

docs-check:
	$(PY) tools/docgen.py --check
	$(PY) tools/docgen_python.py --check
	$(PY) tools/gen_cpp_ops.py --check

ci-quick: hygiene lint quick docs-check

ci-full: build dist convergence quick docs-check
	JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# needs one TPU chip and fails without one: Module.fit of ResNet-50 and
# the transformer LM, then the LM behind the generation engine, each
# held to its dense XLA twin (--chips 4: the data-parallel path)
chip-smoke:
	$(PY) chip_smoke.py
