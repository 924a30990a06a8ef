"""graft-lint: per-rule fixtures (positive / negative / suppression)
plus the dynamic lockcheck detector and the repo-clean gate.

Each static rule is driven through ``lint_source`` with a small
injected LintContext (fixture registry + manifests), so the tests pin
the *rules*, not the current state of the tree; the one repo-wide test
(`test_repo_is_lint_clean`) is the ``make lint`` acceptance gate in
test form.
"""
import os
import textwrap
import threading

import pytest

from mxnet_tpu.analysis import lockcheck
from mxnet_tpu.analysis.graft_lint import (LintContext, lint_paths,
                                           lint_source, repo_checks)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ctx(**kw):
    kw.setdefault("registry", {"MXNET_KNOWN": 1})
    kw.setdefault("documented", {})
    kw.setdefault("hot_paths", ())
    kw.setdefault("span_entry_points", ())
    return LintContext(**kw)


def run_lint(src, relpath="pkg/fixture.py", **kw):
    return lint_source(_ctx(**kw), textwrap.dedent(src), relpath)


def rules(violations):
    return [v.rule for v in violations]


# ---------------------------------------------------------------------------
# rule: env-knob
# ---------------------------------------------------------------------------
def test_env_raw_read_flagged():
    vs = run_lint("""
        import os
        x = os.environ.get("MXNET_FOO")
        y = os.getenv("MXNET_BAR", "1")
        z = os.environ["MXNET_BAZ"]
    """)
    assert rules(vs) == ["env-knob"] * 3


def test_env_wrapper_launder_flagged():
    vs = run_lint("""
        def _env(name, default=None):
            import os
            return os.environ.get(name, default)
        x = _env("MXNET_FOO", "1")
        ok = _env("DMLC_ROLE")
    """)
    assert rules(vs) == ["env-knob"]


def test_env_get_env_registered_ok_unregistered_flagged():
    vs = run_lint("""
        from mxnet_tpu.base import get_env
        a = get_env("MXNET_KNOWN")
        b = get_env("MXNET_NEVER_REGISTERED")
    """)
    assert rules(vs) == ["env-knob"]
    assert "MXNET_NEVER_REGISTERED" in vs[0].msg


def test_env_non_mxnet_and_writes_ignored():
    vs = run_lint("""
        import os
        a = os.environ.get("JAX_PLATFORMS")
        os.environ["MXNET_FOO"] = "1"     # write, not a read
        os.environ.pop("MXNET_FOO", None)
    """)
    assert vs == []


def test_env_suppression_with_reason():
    vs = run_lint("""
        import os
        # graft-lint: disable=env-knob — fixture save/restore
        a = os.environ.get("MXNET_FOO")
        b = os.environ.get("MXNET_BAR")  # graft-lint: disable=env-knob — inline reason
    """)
    assert vs == []


def test_env_suppression_without_reason_is_error():
    vs = run_lint("""
        import os
        a = os.environ.get("MXNET_FOO")  # graft-lint: disable=env-knob
    """)
    assert sorted(rules(vs)) == ["bad-suppression", "env-knob"]


def test_suppression_mention_in_docstring_ignored():
    vs = run_lint('''
        def f():
            """Suppress with '# graft-lint: disable=env-knob'."""
            return 1
    ''')
    assert vs == []


def test_env_doc_rows_only_name_column_counts(tmp_path):
    from mxnet_tpu.analysis.graft_lint import _parse_doc_rows
    md = tmp_path / "env_vars.md"
    md.write_text(
        "| Variable | Default | Meaning |\n"
        "|---|---|---|\n"
        "| `MXNET_OWN_ROW` | 0 | enables X under MXNET_OTHER_KNOB=1 |\n")
    rows = _parse_doc_rows(str(md))
    assert "MXNET_OWN_ROW" in rows
    # a mention in another row's description is NOT documentation
    assert "MXNET_OTHER_KNOB" not in rows


def test_env_doc_sync_repo_checks():
    ctx = _ctx(registry={"MXNET_A": 10, "MXNET_B": 20},
               documented={"MXNET_A": 5, "MXNET_C": 7})
    vs = repo_checks(ctx)
    msgs = sorted(v.msg for v in vs)
    assert len(vs) == 2
    assert "MXNET_B" in msgs[1] and "no docs/env_vars.md row" in msgs[1]
    assert "MXNET_C" in msgs[0] and "not registered" in msgs[0]


# ---------------------------------------------------------------------------
# rule: donation-safety
# ---------------------------------------------------------------------------
def test_donation_read_after_donate_flagged():
    vs = run_lint("""
        import jax
        def f(g, x, y):
            step = jax.jit(g, donate_argnums=(0,))
            out = step(x, y)
            return x + out     # x's buffer was donated
    """)
    assert rules(vs) == ["donation-safety"]
    assert "'x'" in vs[0].msg and "step" in vs[0].msg


def test_donation_reassign_is_clean():
    vs = run_lint("""
        import jax
        def f(g, x, y):
            step = jax.jit(g, donate_argnums=(0,))
            x = step(x, y)
            return x + 1
    """)
    assert vs == []


def test_donation_exclusive_branches_clean():
    # a read in the *else* arm of the donating arm's if is not "after"
    vs = run_lint("""
        import jax
        def f(g, x, y, train):
            step = jax.jit(g, donate_argnums=(0,))
            if train:
                out = step(x, y)
            else:
                out = x + 1
            return out
    """)
    assert vs == []


def test_donation_read_after_join_flagged():
    vs = run_lint("""
        import jax
        def f(g, x, y, train):
            step = jax.jit(g, donate_argnums=(0,))
            if train:
                out = step(x, y)
            else:
                out = x + 1
            return x      # dead on the train path
    """)
    assert rules(vs) == ["donation-safety"]


def test_donation_dispatch_idiom_and_self_attr():
    vs = run_lint("""
        import jax
        class T:
            def build(self, fn):
                self._step = jax.jit(fn, donate_argnums=(0, 1))
            def step(self, eng, state, opt, batch):
                state, opt = eng.dispatch("step", self._step,
                                          state, opt, batch)
                return state, opt
            def bad_step(self, eng, state, opt, batch):
                out = eng.dispatch("step", self._step, state, opt, batch)
                return state
    """)
    assert rules(vs) == ["donation-safety"]
    assert vs[0].line and "'state'" in vs[0].msg


def test_donation_loop_carried_flagged():
    # the canonical step-loop bug: donate state every iteration,
    # forget to re-stash the output
    vs = run_lint("""
        import jax
        def f(g, x, batches):
            step = jax.jit(g, donate_argnums=(0,))
            for b in batches:
                y = step(x)
        def ok(g, x, batches):
            step = jax.jit(g, donate_argnums=(0,))
            for b in batches:
                x = step(x)    # reassigned each iteration: fine
    """)
    assert rules(vs) == ["donation-safety"]
    assert "already" in vs[0].msg and "'x'" in vs[0].msg


def test_donation_module_level_jit_collected():
    vs = run_lint("""
        import jax
        def _impl(a, b):
            return a + b
        step = jax.jit(_impl, donate_argnums=(0,))
        def caller(x, y):
            out = step(x, y)
            return x + out
    """)
    assert rules(vs) == ["donation-safety"]
    assert "'x'" in vs[0].msg


def test_donation_attribute_chain_read_flagged():
    vs = run_lint("""
        import jax
        class T:
            def build(self, fn):
                self._step = jax.jit(fn, donate_argnums=(0,))
            def go(self, b):
                self._step(self.state, b)
                return self.state.mean()    # reads the donated buffer
        def f(g, x, y):
            step = jax.jit(g, donate_argnums=(0,))
            out = step(x, y)
            return x.shape                  # so does .shape
    """)
    assert rules(vs) == ["donation-safety"] * 2
    assert "'self.state'" in vs[0].msg and "self.state.mean" in vs[0].msg
    assert "'x'" in vs[1].msg


def test_donation_double_donate_flagged():
    vs = run_lint("""
        import jax
        def f(g, x, y):
            step = jax.jit(g, donate_argnums=(0,))
            a = step(x)
            b = step(x)
    """)
    assert rules(vs) == ["donation-safety"]


def test_donation_suppression():
    vs = run_lint("""
        import jax
        def f(g, x, y):
            step = jax.jit(g, donate_argnums=(0,))
            out = step(x, y)
            # graft-lint: disable=donation-safety — x is CPU-backed here
            return x + out
    """)
    assert vs == []


# ---------------------------------------------------------------------------
# rule: host-sync
# ---------------------------------------------------------------------------
def test_host_sync_decorated_flagged():
    vs = run_lint("""
        import jax
        import numpy as np
        from mxnet_tpu.base import hot_path

        @hot_path
        def step(arr):
            jax.block_until_ready(arr)
            h = np.asarray(arr)
            s = arr.item()
            v = float(arr)
            return h, s, v
    """)
    assert rules(vs) == ["host-sync"] * 4


def test_host_sync_undecorated_not_flagged():
    vs = run_lint("""
        import numpy as np
        def setup(arr):
            return np.asarray(arr)
    """)
    assert vs == []


def test_host_sync_float_of_constant_ok():
    vs = run_lint("""
        from mxnet_tpu.base import hot_path
        @hot_path
        def step(q):
            return float("inf"), q.get()
    """)
    assert vs == []


def test_host_sync_manifest_and_rot():
    manifest = (("pkg/fixture.py", "Loop.run"),
                ("pkg/fixture.py", "Loop.gone"))
    vs = run_lint("""
        class Loop:
            def run(self, arr):
                return arr.asnumpy()
    """, hot_paths=manifest)
    assert rules(vs) == ["host-sync", "host-sync"]
    assert any("asnumpy" in v.msg for v in vs)
    assert any("Loop.gone" in v.msg and "manifest" in v.msg for v in vs)


def test_host_sync_suppression():
    vs = run_lint("""
        import jax
        from mxnet_tpu.base import hot_path
        @hot_path
        def step(arr, profiling):
            if profiling:
                # graft-lint: disable=host-sync — profiling measures execution
                jax.block_until_ready(arr)
            return arr
    """)
    assert vs == []


# ---------------------------------------------------------------------------
# rule: thread-discipline
# ---------------------------------------------------------------------------
def test_thread_bare_thread_flagged_daemon_or_join_ok():
    vs = run_lint("""
        import threading
        def leak(fn):
            t = threading.Thread(target=fn)
            t.start()
        def ok_daemon(fn):
            threading.Thread(target=fn, daemon=True).start()
        def ok_joined(fn):
            ts = [threading.Thread(target=fn) for _ in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    """)
    assert rules(vs) == ["thread-discipline"]
    assert "leak" in vs[0].msg


def test_thread_str_join_does_not_mask_leak():
    vs = run_lint("""
        import threading
        def leaky(fn, names):
            t = threading.Thread(target=fn)
            t.start()
            return ", ".join(names) + sep.join(names)
        def ok(fn, timeout_kw):
            t = threading.Thread(target=fn)
            t.start()
            t.join(timeout=timeout_kw)
    """)
    assert rules(vs) == ["thread-discipline"]
    assert "leaky" in vs[0].msg


def test_thread_bare_acquire_flagged_tryfinally_ok():
    vs = run_lint("""
        def bad(self):
            self._lock.acquire()
            self.state += 1
            self._lock.release()
        def good(self):
            self._lock.acquire()
            try:
                self.state += 1
            finally:
                self._lock.release()
        def good_with(self):
            with self._lock:
                self.state += 1
    """)
    assert rules(vs) == ["thread-discipline"]
    assert "bad" in vs[0].msg


def test_thread_acquire_first_inside_try_ok():
    vs = run_lint("""
        def good(self):
            try:
                self._lock.acquire()
                self.state += 1
            finally:
                self._lock.release()
        def bad(self):
            try:
                self.prep()
                self._lock.acquire()   # not first: prep() may raise
            finally:                   # after acquire... and nothing
                self.cleanup()         # here releases anyway
    """)
    assert rules(vs) == ["thread-discipline"]
    assert "bad" in vs[0].msg


def test_thread_non_lock_acquire_not_flagged():
    # cached_op's LRU has a 3-arg acquire(key, op, builder) — not a lock
    vs = run_lint("""
        def dispatch(cache, key, op, builder):
            return cache.acquire(key, op, builder)
    """)
    assert vs == []


def test_thread_sleep_under_lock_flagged():
    vs = run_lint("""
        import time
        def bad(self):
            with self._lock:
                time.sleep(0.1)
        def good(self, delay):
            time.sleep(delay)
            with self._lock:
                self.state += 1
    """)
    assert rules(vs) == ["thread-discipline"]
    assert "sleep" in vs[0].msg


# ---------------------------------------------------------------------------
# rule: span-coverage
# ---------------------------------------------------------------------------
def test_span_direct_and_one_hop_ok_missing_flagged():
    manifest = (("pkg/fixture.py", "Eng.dispatch"),
                ("pkg/fixture.py", "Eng.silent"),
                ("pkg/fixture.py", "Eng.via_helper"))
    vs = run_lint("""
        import time
        class Eng:
            def dispatch(self, fn):
                t0 = time.perf_counter_ns()
                out = fn()
                self._prof.record("op", t0, time.perf_counter_ns())
                return out
            def silent(self, fn):
                return fn()
            def via_helper(self, fn):
                out = fn()
                self._emit("op")
                return out
            def _emit(self, name):
                with phase(name):
                    pass
    """, span_entry_points=manifest)
    assert rules(vs) == ["span-coverage"]
    assert "silent" in vs[0].msg


def test_span_manifest_rot_flagged():
    vs = run_lint("""
        def present():
            with phase("x"):
                pass
    """, span_entry_points=(("pkg/fixture.py", "absent"),))
    assert rules(vs) == ["span-coverage"]
    assert "absent" in vs[0].msg and "manifest" in vs[0].msg


# ---------------------------------------------------------------------------
# rule: unguarded-shared-mutation
# ---------------------------------------------------------------------------
def test_shared_mutation_run_loop_flagged_lock_and_container_ok():
    vs = run_lint("""
        class W:
            def _worker(self):
                self.state = "hot"              # bare: flagged
                self.counts["x"] = 1            # bare subscript: flagged
                with self._lock:
                    self.guarded = 1            # under the seam lock: ok
                self._st.field = 2              # through shared_state: ok
            def helper(self):
                self.state = "cold"             # not a run-loop: ok
    """)
    assert rules(vs) == ["unguarded-shared-mutation"] * 2
    assert "self.state" in vs[0].msg and "self.counts" in vs[1].msg


def test_shared_mutation_nested_def_and_augassign():
    vs = run_lint("""
        class W:
            def drain_loop(self):
                self.n += 1                     # AugAssign: flagged
                def cb():
                    self.inner = 1              # other call stack: ok
                cb()
    """)
    assert rules(vs) == ["unguarded-shared-mutation"]
    assert "self.n" in vs[0].msg


def test_shared_mutation_suppression():
    vs = run_lint("""
        class W:
            def run(self):
                # single-threaded bring-up, published by start() below
                self.x = 1  # graft-lint: disable=unguarded-shared-mutation — set before any reader thread exists
    """)
    assert vs == []


# ---------------------------------------------------------------------------
# rule: atomic-publish
# ---------------------------------------------------------------------------
_PUB = (("pkg/fixture.py", "_live", ("Store.swap",)),)


def test_atomic_publish_allowed_publishers_ok():
    vs = run_lint("""
        class Store:
            def __init__(self):
                self._live = (None, 0)
            def swap(self, params, ver):
                with self._lock:
                    self._live = (params, ver)
            def snapshot(self):
                return self._live
    """, atomic_publish=_PUB)
    assert vs == []


def test_atomic_publish_foreign_assign_and_tear_flagged():
    vs = run_lint("""
        class Store:
            def __init__(self):
                self._live = (None, 0)
            def refresh(self, p, v):
                self._live = (p, v)             # not an allowed publisher
            def bump(self):
                self._live, x = (1, 2), 3       # tuple-target tear
                self._live[0] = None            # subscript tear
                self._live.append(4)            # in-place mutation
    """, atomic_publish=_PUB)
    assert rules(vs) == ["atomic-publish"] * 4


def test_atomic_publish_manifest_rot_flagged():
    vs = run_lint("""
        class Store:
            pass
    """, atomic_publish=_PUB)
    assert rules(vs) == ["atomic-publish"]
    assert "manifest" in vs[0].msg


# ---------------------------------------------------------------------------
# rule: future-discipline
# ---------------------------------------------------------------------------
def test_future_unguarded_flagged_guard_variants_ok():
    vs = run_lint("""
        from concurrent.futures import Future, InvalidStateError
        def bad(fut, exc):
            fut.set_exception(exc)              # no guard: flagged
        def guarded(fut, val):
            try:
                fut.set_result(val)             # try/except ISE: ok
            except InvalidStateError:
                pass
        def running(fut, val):
            if not fut.set_running_or_notify_cancel():
                return
            fut.set_result(val)                 # RUNNING: cancel lost
        def fresh(exc):
            f = Future()
            f.set_exception(exc)                # local, unescaped: ok
            return f
    """)
    assert rules(vs) == ["future-discipline"]
    assert vs[0].line == 4


def test_future_resolve_under_lock_flagged():
    vs = run_lint("""
        def publish(self, fut, val):
            with self._lock:
                try:
                    fut.set_result(val)         # callbacks under lock
                except InvalidStateError:
                    pass
    """)
    assert rules(vs) == ["future-discipline"]
    assert "lock" in vs[0].msg


def test_future_handler_body_not_inherited_guard():
    vs = run_lint("""
        def work(fut, job):
            try:
                fut.set_result(job())           # guarded by handler
            except BaseException as e:
                fut.set_exception(e)            # handler body: NOT guarded
    """)
    assert rules(vs) == ["future-discipline"]
    assert vs[0].line == 6


# ---------------------------------------------------------------------------
# the acceptance gate: the tree itself is clean
# ---------------------------------------------------------------------------
def test_repo_is_lint_clean():
    vs = lint_paths(ROOT, ["mxnet_tpu", "tools"])
    assert vs == [], "\n".join(map(repr, vs))


def test_missing_lint_target_is_loud():
    # a typo'd/renamed path must fail the gate, not pass it vacuously
    from mxnet_tpu.analysis.graft_lint import MissingPathError
    with pytest.raises(MissingPathError, match="mxnet_tpo"):
        lint_paths(ROOT, ["mxnet_tpo"])
    with pytest.raises(MissingPathError, match="nope.py"):
        lint_paths(ROOT, ["nope.py"])


# ---------------------------------------------------------------------------
# dynamic lockcheck
# ---------------------------------------------------------------------------
@pytest.fixture
def clean_lock_graph():
    lockcheck.reset()
    yield
    lockcheck.reset()


def test_make_lock_plain_when_disabled(monkeypatch):
    monkeypatch.delenv("MXNET_LOCK_CHECK", raising=False)
    lk = lockcheck.make_lock("x")
    assert not isinstance(lk, lockcheck.CheckedLock)
    with lk:
        pass


def test_lockcheck_abba_cycle_names_both_locks_and_stacks(clean_lock_graph):
    A = lockcheck.CheckedLock("lock-A")
    B = lockcheck.CheckedLock("lock-B")

    def a_then_b():
        with A:
            with B:
                pass

    t = threading.Thread(target=a_then_b, daemon=True)
    t.start()
    t.join()

    with pytest.raises(lockcheck.LockOrderError) as ei:
        with B:
            with A:   # closes the cycle: A->B recorded, now B->A
                pass
    msg = str(ei.value)
    assert "lock-A" in msg and "lock-B" in msg
    assert "this acquisition" in msg and "earlier acquisition" in msg
    # both stacks present: ours (a_then_b's inner acquire) and the
    # current one — each rendered as traceback frames
    assert msg.count('File "') >= 2
    assert "a_then_b" in msg


def test_lockcheck_transitive_cycle_reports_full_chain(clean_lock_graph):
    A = lockcheck.CheckedLock("tri-A")
    B = lockcheck.CheckedLock("tri-B")
    C = lockcheck.CheckedLock("tri-C")

    def record(first, second):
        with first:
            with second:
                pass

    for pair in ((A, B), (B, C)):   # A->B, B->C recorded
        t = threading.Thread(target=record, args=pair, daemon=True)
        t.start()
        t.join()

    with pytest.raises(lockcheck.LockOrderError) as ei:
        record(C, A)                # C->A closes A->B->C
    msg = str(ei.value)
    # every lock on the cycle is named, and each recorded edge's stack
    # is shown (A-after-nothing... i.e. edges A->B and B->C), not a
    # fabricated direct A<->C inversion
    assert "tri-A" in msg and "tri-B" in msg and "tri-C" in msg
    assert msg.count("earlier acquisition") == 2


def test_lockcheck_consistent_order_is_silent(clean_lock_graph):
    A = lockcheck.CheckedLock("ord-A")
    B = lockcheck.CheckedLock("ord-B")

    def a_then_b():
        with A:
            with B:
                pass

    t = threading.Thread(target=a_then_b, daemon=True)
    t.start()
    t.join()
    a_then_b()  # same order again: no cycle, no error


def test_lockcheck_rlock_reentrancy(clean_lock_graph):
    R = lockcheck.CheckedLock("re-R", rlock=True)
    with R:
        with R:
            assert R._is_owned()
    assert not R._is_owned()


def test_lockcheck_condition_wait_notify(clean_lock_graph):
    cv = threading.Condition(lockcheck.CheckedLock("cv"))
    hits = []

    def waiter():
        with cv:
            while not hits:
                cv.wait(timeout=5)

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    with cv:
        hits.append(1)
        cv.notify_all()
    t.join(timeout=5)
    assert not t.is_alive()


def test_lockcheck_check_owned(clean_lock_graph):
    L = lockcheck.CheckedLock("guard")
    with pytest.raises(lockcheck.LockDisciplineError) as ei:
        lockcheck.check_owned(L, "the counters")
    assert "the counters" in str(ei.value) and "guard" in str(ei.value)
    with L:
        lockcheck.check_owned(L, "the counters")  # holding: fine
    # plain locks are a no-op seam
    lockcheck.check_owned(threading.Lock(), "anything")
