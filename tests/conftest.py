"""Test configuration: force a virtual 8-device CPU platform.

Mirrors the reference's test strategy (SURVEY.md §4): CPU contexts stand in
for the device mesh, so multi-device/sharding tests run anywhere; the
benchmark (benchmark/) runs on real TPU hardware separately.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import threading  # noqa: E402
import time  # noqa: E402

import numpy as _np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _no_leaked_nondaemon_threads():
    """Runtime face of graft-lint's thread-discipline rule: at session
    teardown every non-daemon worker thread must have been joined.  A
    leaked one would hang interpreter exit in production (atexit waits
    on it), so fail the whole run and NAME the leaker."""
    yield

    def offenders():
        main = threading.main_thread()
        return [t for t in threading.enumerate()
                if t.is_alive() and not t.daemon and t is not main]

    deadline = time.time() + 3.0   # grace for joins racing teardown
    while offenders() and time.time() < deadline:
        time.sleep(0.05)
    bad = offenders()
    if bad:
        names = ", ".join(
            "%r (target=%s)" % (t.name,
                                getattr(getattr(t, "_target", None),
                                        "__qualname__", "?"))
            for t in bad)
        pytest.fail(
            "non-daemon thread(s) leaked past session teardown: %s — "
            "give each worker a stop-event + join or daemon=True "
            "(docs/architecture/static_analysis.md)" % names)


@pytest.fixture(autouse=True)
def _seed_rngs():
    """Deterministic tests (reference test suite seeds similarly)."""
    _np.random.seed(0)
    import mxnet_tpu as _mx
    _mx.random.seed(0)
    yield


@pytest.fixture
def throttle_ticks():
    """``throttle_ticks(engine, seconds)`` slows a GenerationEngine so a
    generation provably outlives what the test does to it: it wraps
    ``_paged_tick``, what the serve loop calls every tick on the plane
    every default caller takes, with a sleep.  Returns the list of the
    models it slowed, one entry a tick: the caller asserts it is not
    empty, so a rename of the tick cannot make the throttle dead
    again (as ``_decode_and_sample``, which only the contiguous
    ``_decode_tick`` calls, was for three tests until PR 48)."""
    def throttle(engine, seconds):
        tick = engine._paged_tick     # AttributeError if it is renamed
        entered = []

        def slow_tick(model, st):
            entered.append(model)
            time.sleep(seconds)
            return tick(model, st)

        engine._paged_tick = slow_tick
        return entered
    return throttle


# ---------------------------------------------------------------------------
# Test tiers (reference: Jenkinsfile stages split quick sanity from the
# full matrix).  Every test gets exactly one tier marker:
#   quick       -- every subsystem: every file that is in no other tier
#                  (1,156 of tier-1's 1,230 tests at PR 48, most of its
#                  seconds: the CI's per-change stages run it by file)
#   convergence -- example workloads + training-to-accuracy tiers
#   build       -- compiles the native C++ runtime / C ABI
#   dist        -- multi-process parameter-server protocol
# Selection: pytest -m quick | -m "not quick" | -m "convergence or dist"
# (ci.yaml and the Makefile select on these).  Tier-1 itself is
# `-m 'not slow'`, all four tiers: pytest.ini says what may carry `slow`.
# A file that is split keeps its tier: a tier goes by file NAME below.
# ---------------------------------------------------------------------------
_TIER_BY_FILE = {
    "test_train_tier.py": "convergence",
    "test_doc_snippets.py": "convergence",
    "test_deploy.py": "build",
    "test_native.py": "build",
    "test_dist_kvstore.py": "dist",
}
# slow training-parity tests inside otherwise-quick files.
# test_ssd_train_step was promoted OUT of this list (PR 10): the whole
# SSD/RNN surface now rides the quick tier, proving the checkpointable
# data pipeline's non-classification shapes on every change.
_CONVERGENCE_TESTS = {
    "test_transformer_trainer_composes_dp_sp_tp",
    "test_ring_attention_grads_match_dense",
    "test_moe_transformer_trains_with_parity_vs_single_device",
    "test_transformer_sharded_matches_single_device",
    "test_pipeline_grads_flow",
}
# one cheap example stays quick so the example-runner + CustomOp path is
# covered in the quick tier
_QUICK_EXAMPLES = {"test_numpy_ops_custom_softmax"}


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = os.path.basename(str(item.fspath))
        base = item.name.split("[")[0]
        if fname.startswith("test_examples"):   # seven files by family
            tier = "quick" if base in _QUICK_EXAMPLES else "convergence"
        elif base in _CONVERGENCE_TESTS:
            tier = "convergence"
        else:
            tier = _TIER_BY_FILE.get(fname, "quick")
        item.add_marker(getattr(pytest.mark, tier))
