"""Elastic asynchronous parameter server (docs/architecture/elastic_ps.md):

* factory regression: ``dist_async`` now arms the REAL async server mode
  (version vectors + staleness gating), ``dist_sync`` unchanged, unknown
  names still raise;
* bounded staleness (SSP): a property check that no admitted pull ever
  observes a violation of ``MXNET_KVSTORE_MAX_STALENESS``, and that
  ``s=0`` byte-matches the dist_sync merge on the same schedule;
* straggler scenario: one worker injected persistently slow via the new
  seeded ``straggler`` fault kind — ``dist_async`` at s=4 sustains >= 2x
  the steps/sec of ``dist_sync`` on the same schedule;
* epoched elastic membership: heartbeat death bumps the epoch, retires
  the dead rank's version entries from the staleness frontier (no
  stall), and shrinks the barrier target (the in-process quick-tier
  variant of tests/dist_dead_node.py);
* elastic join: a worker joining mid-run enters the version vectors at
  the frontier and the final values byte-match the static-membership
  run;
* live shard rebalancing: bucket migration between servers under
  traffic — zero lost or duplicated pushes (the dedup watermarks
  migrate with the bucket, surviving a lost-reply resend that crosses
  the migration), including server capacity add/remove mid-run;
* ``straggler`` fault-kind determinism: two runs of the same seeded
  schedule produce identical fault logs.

``make elastic-smoke`` runs this file under MXNET_LOCK_CHECK=1 with a
hard timeout (ci.yaml per-change stage).
"""
import socket
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject
from mxnet_tpu import kvstore_codec as codec
from mxnet_tpu import kvstore_dist as ksd
from mxnet_tpu.base import MXNetError

REPO_KEY = 7          # the key most scenarios train on
SIZE = 8              # elements per key


@pytest.fixture(autouse=True)
def _no_leftover_fault_plan():
    yield
    faultinject.install(None)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Cluster:
    """In-process scheduler + N servers; workers are created on demand
    (bare WorkerClients or full KVStoreDist stores)."""

    def __init__(self, monkeypatch, n_workers=1, n_servers=1, **env):
        base = {
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(_free_port()),
            "DMLC_NUM_WORKER": str(n_workers),
            "DMLC_NUM_SERVER": str(n_servers),
            "MXNET_KVSTORE_HEARTBEAT_INTERVAL": "0.1",
            "MXNET_KVSTORE_DEAD_TIMEOUT": "2.0",
            "MXNET_KVSTORE_MEMBERSHIP_TTL": "0.05",
            "MXNET_KVSTORE_BARRIER_TIMEOUT": "30",
        }
        base.update({k: str(v) for k, v in env.items()})
        for k, v in base.items():
            monkeypatch.setenv(k, v)
        monkeypatch.delenv("DMLC_PS_RECOVERY_RANK", raising=False)
        monkeypatch.delenv("MXNET_KVSTORE_SNAPSHOT_DIR", raising=False)
        self.sched = ksd.Scheduler()
        threading.Thread(target=self.sched.run, daemon=True).start()
        self.servers = []
        for _ in range(n_servers):
            self.add_server()
        self.clients = []

    def add_server(self):
        """Spin one more server (beyond DMLC_NUM_SERVER = a capacity
        add: it registers, the scheduler's address table grows, and
        buckets migrate onto it via the versioned plan)."""
        server = ksd.Server()
        threading.Thread(target=server.run, daemon=True).start()
        # serialize registration: the scheduler assigns ranks in arrival
        # order, so without this wait two back-to-back add_server calls
        # race and self.servers[i].rank == i does not hold (the old
        # dst-store-empty flake in the migration tests — the wrong
        # Server OBJECT was inspected, not a lost migration)
        server.wait_registered()
        self.servers.append(server)
        return server

    def client(self, plan_sizes=None):
        c = ksd.WorkerClient()
        if plan_sizes is not None:
            plan = codec.BucketPlan(bucket_bytes=4096)
            for k, n in plan_sizes:
                plan.add(k, n)
            c.plan = plan
        self.clients.append(c)
        return c

    def finalize(self):
        for i, c in enumerate(self.clients):
            try:
                c.finalize(i == len(self.clients) - 1)
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass


def _wait_until(pred, timeout=15.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError("timed out waiting for %s" % what)


# ---------------------------------------------------------------------------
# Satellite: factory regression — dist_async routes to the async mode
# ---------------------------------------------------------------------------
def test_factory_dist_async_arms_async_server(monkeypatch):
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=1)
    kv = mx.create_kvstore("dist_async")
    try:
        assert isinstance(kv, mx.kvstore.KVStoreDist)
        _wait_until(lambda: cl.servers[0].async_mode,
                    what="async_mode command")
        assert not cl.servers[0].sync_mode
    finally:
        kv.close()


def test_factory_dist_sync_unchanged(monkeypatch):
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=1)
    kv = mx.create_kvstore("dist_sync")
    try:
        _wait_until(lambda: cl.servers[0].sync_mode,
                    what="sync_mode command")
        assert not cl.servers[0].async_mode
    finally:
        kv.close()


def test_factory_unknown_names_still_raise():
    with pytest.raises(MXNetError):
        mx.create_kvstore("dist_bogus")
    with pytest.raises(TypeError):
        mx.create_kvstore(3)


# ---------------------------------------------------------------------------
# Bounded staleness: property check + s=0 sync parity
# ---------------------------------------------------------------------------
def _run_workers(workers):
    """Run each worker loop in a thread; re-raise the first failure."""
    errs = []

    def run(fn):
        try:
            fn()
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            errs.append(exc)

    ts = [threading.Thread(target=run, args=(fn,), daemon=True)
          for fn in workers]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
        assert not t.is_alive(), "worker loop wedged"
    if errs:
        raise errs[0]


def test_staleness_bound_never_violated(monkeypatch):
    """Property: every ADMITTED gated pull satisfies
    my_version - slowest_live_version <= s, even with one worker
    running much slower than the other (seeded jitter)."""
    s = 2
    cl = _Cluster(monkeypatch, n_workers=2, n_servers=1,
                  MXNET_KVSTORE_MAX_STALENESS=s)
    server = cl.servers[0]
    server.stale_log = []
    a, b = cl.client(), cl.client()
    server._handle_command("async_mode", b"")
    a.init(REPO_KEY, np.zeros(SIZE, np.float32))
    rng = np.random.RandomState(11)
    steps = 8

    def loop(client, slow):
        for _ in range(steps):
            if slow:
                time.sleep(float(rng.uniform(0.01, 0.03)))
            client.push(REPO_KEY, np.ones(SIZE, np.float32))
            client.pull(REPO_KEY, SIZE)

    _run_workers([lambda: loop(a, False), lambda: loop(b, True)])
    out = a.pull(REPO_KEY, SIZE)
    np.testing.assert_array_equal(
        out, np.full(SIZE, 2.0 * steps, np.float32))
    assert server.stale_log, "no gated pulls were observed"
    lags = [my - slowest for _, _, my, slowest in server.stale_log]
    assert max(lags) <= s, server.stale_log
    # the fast worker actually ran ahead (the bound did real work)
    assert any(lag > 0 for lag in lags)
    cl.finalize()


def _push_pull_schedule(cluster, n_workers, steps, keys):
    """Deterministic integer-valued schedule all parity runs share."""
    clients = [cluster.client() for _ in range(n_workers)]
    clients[0].init(keys[0], np.zeros(SIZE, np.float32))
    for k in keys[1:]:
        clients[0].init(k, np.zeros(SIZE, np.float32))

    def loop(client, r):
        for step in range(steps):
            for k in keys:
                client.push(k, np.full(SIZE, float(r + 1), np.float32))
            for k in keys:
                client.pull(k, SIZE)

    _run_workers([lambda c=c, r=r: loop(c, r)
                  for r, c in enumerate(clients)])
    finals = [clients[0].pull(k, SIZE).copy() for k in keys]
    return finals


def test_s0_byte_matches_dist_sync(monkeypatch):
    """s=0 degenerates to sync-read semantics: on an integer-valued
    schedule the final values byte-match the dist_sync merge of the
    same schedule (accumulate updater; fp32-exact values)."""
    steps, keys = 3, [1, 2]
    sync = _Cluster(monkeypatch, n_workers=2, n_servers=1)
    sync.servers[0]._handle_command("sync_mode", b"")
    sync_finals = _push_pull_schedule(sync, 2, steps, keys)
    sync.finalize()

    async_ = _Cluster(monkeypatch, n_workers=2, n_servers=1,
                      MXNET_KVSTORE_MAX_STALENESS=0)
    async_.servers[0]._handle_command("async_mode", b"")
    async_finals = _push_pull_schedule(async_, 2, steps, keys)
    async_.finalize()

    expected = np.full(SIZE, float(steps * (1 + 2)), np.float32)
    for sv, av in zip(sync_finals, async_finals):
        np.testing.assert_array_equal(sv, av)
        np.testing.assert_array_equal(av, expected)


# ---------------------------------------------------------------------------
# Straggler scenario: async s=4 runs ahead of the straggler, dist_sync
# waits for it every round
# ---------------------------------------------------------------------------
def _straggler_run(cluster, mode, steps, straggler_s):
    """Two workers; worker 1 is made a persistent straggler by the
    seeded ``straggler`` fault kind at its send seam.  Returns, for
    each of worker 0's steps, how many of the straggler's pushes the
    value it pulled held (what worker 0 waited for), and the server's
    log of admitted gated pulls."""
    a, b = cluster.client(), cluster.client()
    server = cluster.servers[0]
    server.stale_log = []
    if mode == "sync":
        server._handle_command("sync_mode", b"")
        a.sync_push = b.sync_push = True
    else:
        server._handle_command("async_mode", b"")
    a.init(REPO_KEY, np.zeros(SIZE, np.float32))
    faultinject.install({"seed": 5, "rules": [
        {"seam": "worker.send", "rank": 1, "action": "straggler",
         "seconds": straggler_s}]})
    held = []

    def fast():
        for step in range(1, steps + 1):
            a.push(REPO_KEY, np.ones(SIZE, np.float32))
            # pushes add 1.0: the value is the pushes applied, and all
            # past this worker's own are the straggler's
            held.append(int(a.pull(REPO_KEY, SIZE)[0]) - step)

    def slow():
        for _ in range(steps):
            b.push(REPO_KEY, np.ones(SIZE, np.float32))
            b.pull(REPO_KEY, SIZE)

    try:
        _run_workers([fast, slow])
    finally:
        faultinject.install(None)
    final = a.pull(REPO_KEY, SIZE)
    np.testing.assert_array_equal(
        final, np.full(SIZE, 2.0 * steps, np.float32))
    cluster.finalize()
    return held, server.stale_log


def test_straggler_async_s4_at_least_2x_dist_sync(monkeypatch):
    """Acceptance, held by counts and not by the clock: one worker slow
    (every RPC of rank 1 sleeps a straggler delay) over a bounded
    window of 7 steps.  Under dist_sync every merge round waits for the
    straggler: each value the fast worker reads holds as many of the
    straggler's pushes as of its own.  Under dist_async s=4 the fast
    worker runs ahead of it, to the bound and never past: it reads
    values that miss the straggler's pushes, so it did not wait for
    them."""
    steps, delay = 7, 0.02
    sync_cl = _Cluster(monkeypatch, n_workers=2, n_servers=1)
    held, _ = _straggler_run(sync_cl, "sync", steps, delay)
    assert held == list(range(1, steps + 1)), held
    async_cl = _Cluster(monkeypatch, n_workers=2, n_servers=1,
                        MXNET_KVSTORE_MAX_STALENESS=4)
    held, log = _straggler_run(async_cl, "async", steps, delay)
    lags = [my - slowest for _, rank, my, slowest in log if rank == 0]
    assert max(lags) <= 4, log
    assert max(lags) >= 2, log         # ahead of the straggler ...
    # ... on a value that misses two and more of its pushes
    assert any(h <= step - 2 for step, h in enumerate(held, 1)), held


# ---------------------------------------------------------------------------
# Epoched membership: heartbeat death (in-process dist_dead_node variant)
# ---------------------------------------------------------------------------
def test_heartbeat_death_bumps_epoch_and_unstalls_frontier(monkeypatch):
    """The quick-tier promotion of tests/dist_dead_node.py: worker 1
    goes silent mid-run — the epoch bumps, get_num_dead_node sees it,
    the server retires its version entries so a s=0 pull does NOT
    stall, and the barrier releases without the dead peer."""
    cl = _Cluster(monkeypatch, n_workers=2, n_servers=1,
                  MXNET_KVSTORE_MAX_STALENESS=0,
                  MXNET_KVSTORE_DEAD_TIMEOUT="0.6")
    server = cl.servers[0]
    a, b = cl.client(), cl.client()
    server._handle_command("async_mode", b"")
    a.init(REPO_KEY, np.zeros(SIZE, np.float32))
    one = np.ones(SIZE, np.float32)
    a.push(REPO_KEY, one)
    b.push(REPO_KEY, one)
    a.pull(REPO_KEY, SIZE)          # balanced: admitted immediately
    epoch0, live0 = a.membership()
    assert sorted(r for r, _ in live0) == [0, 1]

    # worker 1 "dies": heartbeats stop, no clean finalize
    b._hb_stop.set()
    time.sleep(0.3)                 # let the last queued beat drain

    # a keeps training: at s=0 this pull would stall on b forever were
    # the dead rank not retired from the frontier
    a.push(REPO_KEY, one)
    t0 = time.monotonic()
    out = a.pull(REPO_KEY, SIZE)
    assert time.monotonic() - t0 < 10.0, "staleness frontier stalled"
    np.testing.assert_array_equal(out, one * 3)

    assert a.get_num_dead_node(4, timeout=0.6) >= 1
    epoch1, live1 = a.membership(timeout=0.6)
    assert epoch1 > epoch0
    assert sorted(r for r, _ in live1) == [0]
    # frontier retirement: the dead rank's version entries are gone
    _wait_until(lambda: 1 not in server._versions.get((REPO_KEY, 0), {}),
                what="dead rank's version retirement")
    # the barrier path reads the same epoched view: no hang on the dead
    # peer
    t0 = time.monotonic()
    a.barrier(timeout=20)
    assert time.monotonic() - t0 < 10.0
    cl.finalize()


def test_revived_worker_resumes_true_version_count(monkeypatch):
    """A swept-dead rank that HEARTBEATS again (GC pause, not a crash)
    must resume its retired version count — re-entering at zero would
    drag the staleness frontier back to the start line and stall every
    peer for ~N rounds."""
    cl = _Cluster(monkeypatch, n_workers=2, n_servers=1,
                  MXNET_KVSTORE_MAX_STALENESS=4,
                  MXNET_KVSTORE_DEAD_TIMEOUT="0.5")
    server = cl.servers[0]
    a, b = cl.client(), cl.client()
    server._handle_command("async_mode", b"")
    a.init(REPO_KEY, np.zeros(SIZE, np.float32))
    one = np.ones(SIZE, np.float32)
    for _ in range(6):
        a.push(REPO_KEY, one)
        b.push(REPO_KEY, one)
    wire = (REPO_KEY, 0)
    assert server._versions[wire][1] == 6
    # b pauses long enough to be declared dead; frontier retires it
    b._hb_stop.set()
    a.push(REPO_KEY, one)               # keeps the membership sweep hot
    _wait_until(lambda: (a.pull(REPO_KEY, SIZE) is not None
                         and 1 not in server._versions.get(wire, {})),
                what="retirement of the paused rank")
    assert server._retired_versions[wire][1] == 6   # stashed, not lost
    # b revives: heartbeats resume, then it pushes again
    b._hb_stop = threading.Event()
    ksd._start_heartbeat("worker", b.rank, b._hb_stop)
    _wait_until(lambda: a.get_num_dead_node(4, timeout=0.5) == 0,
                what="revival via heartbeat")
    b.push(REPO_KEY, one)
    assert server._versions[wire][1] == 7   # resumed at 6+1, not at 1
    cl.finalize()


# ---------------------------------------------------------------------------
# Elastic join: mid-run joiner enters at the frontier, values converge
# ---------------------------------------------------------------------------
def test_worker_join_mid_run_matches_static_run(monkeypatch):
    """A worker joining a 1-worker group mid-run (rank beyond
    DMLC_NUM_WORKER => late) bootstraps via pull, enters the version
    vectors at the current frontier (no staleness stall in either
    direction), and the final values byte-match the static run where
    both pushed from the start."""
    t1, t2 = 4, 3
    one = np.ones(SIZE, np.float32)

    def elastic_run():
        cl = _Cluster(monkeypatch, n_workers=1, n_servers=1,
                      MXNET_KVSTORE_MAX_STALENESS=0)
        server = cl.servers[0]
        a = cl.client()
        assert not a.late_join
        server._handle_command("async_mode", b"")
        a.init(REPO_KEY, np.zeros(SIZE, np.float32))
        for _ in range(t1):
            a.push(REPO_KEY, one)
            a.pull(REPO_KEY, SIZE)      # never stalls: group is {0}
        frontier = max(server._versions[(REPO_KEY, 0)].values())
        b = cl.client()
        assert b.late_join
        boot = b.pull(REPO_KEY, SIZE)   # bootstrap read at the frontier
        np.testing.assert_array_equal(boot, one * t1)
        # post-join the group trains together; at s=0 the gated pulls
        # admit exactly because the joiner entered at the FRONTIER
        # (entering at zero would stall a; counting from zero would
        # stall b)
        for _ in range(t2):
            b.push(REPO_KEY, one)
            a.push(REPO_KEY, one)
            a.pull(REPO_KEY, SIZE)
            b.pull(REPO_KEY, SIZE)
        # the joiner entered at the frontier, not at zero
        assert server._versions[(REPO_KEY, 0)][1] == frontier + t2
        out = a.pull(REPO_KEY, SIZE).copy()
        cl.finalize()
        return out

    def static_run():
        cl = _Cluster(monkeypatch, n_workers=2, n_servers=1,
                      MXNET_KVSTORE_MAX_STALENESS=-1)
        cl.servers[0]._handle_command("async_mode", b"")
        a, b = cl.client(), cl.client()
        a.init(REPO_KEY, np.zeros(SIZE, np.float32))
        for _ in range(t1 + t2):
            a.push(REPO_KEY, one)
        for _ in range(t2):
            b.push(REPO_KEY, one)
        out = a.pull(REPO_KEY, SIZE).copy()
        cl.finalize()
        return out

    np.testing.assert_array_equal(elastic_run(), static_run())


# ---------------------------------------------------------------------------
# Live shard rebalancing
# ---------------------------------------------------------------------------
_BUCKET_KEYS = [(0, SIZE), (1, SIZE)]   # one small fusion bucket


def _pusher(client, keys, n, delta, start_evt):
    def loop():
        start_evt.wait()
        for _ in range(n):
            for k in keys:
                client.push(k, np.full(SIZE, delta, np.float32))
    return loop


def test_server_rank_follows_bringup_order_deterministic(monkeypatch):
    """Deterministic regression for the bring-up rank race behind the
    old ~10% dst-store-empty flake in
    test_bucket_migration_under_traffic_exactly_once: server rank is
    assigned in registration ARRIVAL order, so when the first server's
    registration was slow the second overtook it, cl.servers[i].rank
    no longer matched i, and the migration asserts inspected the WRONG
    Server object (the data plane was exactly-once throughout).  The
    fix is the wait_registered() handshake serialized into add_server;
    this test forces the adversarial timing — the first server's
    registration delayed long enough that, unserialized, the second
    ALWAYS wins the race — and pins rank == creation index."""
    orig_run = ksd.Server.run
    delayed = []

    def slow_first_run(self):
        if not delayed:                 # only the first server is slow
            delayed.append(self)
            time.sleep(0.3)
        orig_run(self)

    monkeypatch.setattr(ksd.Server, "run", slow_first_run)
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=2)
    assert [s.rank for s in cl.servers] == [0, 1]
    # the identity the migration tests rely on: index == routing sid
    c = cl.client(plan_sizes=_BUCKET_KEYS)
    for k, sz in _BUCKET_KEYS:
        c.init(k, np.zeros(sz, np.float32))
    owner = c.server_for_bucket(0)
    assert (0, 0) in cl.servers[owner].store
    cl.finalize()


def test_bucket_migration_under_traffic_exactly_once(monkeypatch):
    """Migrate the bucket between two servers while a pusher hammers
    it, with a lost push reply scheduled so a dedup-protected resend
    CROSSES the migration: zero lost, zero duplicated pushes — the
    final values equal the static run's exactly.

    MXNET_SCHED_EXPLORE=N re-runs the body under N seeded jitter
    schedules (analysis/schedules.py, strict=False: the socket planes
    here can't be cooperatively owned) — each seed perturbs thread
    timing reproducibly-in-distribution, widening the interleavings
    this one CI run exercises."""
    from mxnet_tpu.analysis import schedules
    from mxnet_tpu.base import get_env
    n_expl = int(get_env("MXNET_SCHED_EXPLORE"))
    if n_expl > 0:
        schedules.explore(
            lambda: _bucket_migration_body(monkeypatch), n=n_expl,
            strict=False, watchdog=120.0)
    else:
        _bucket_migration_body(monkeypatch)


def _bucket_migration_body(monkeypatch):
    n = 30
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=2)
    for srv in cl.servers:
        srv._handle_command("async_mode", b"")
    c = cl.client(plan_sizes=_BUCKET_KEYS)
    for k, sz in _BUCKET_KEYS:
        c.init(k, np.zeros(sz, np.float32))
    src = c.server_for_bucket(0)
    dst = 1 - src
    # drop one push REPLY mid-stream: the server applies it, the worker
    # resends — and the resend may land on the post-migration owner,
    # whose migrated watermark must dedupe it
    faultinject.install({"seed": 3, "rules": [
        {"seam": "worker.recv", "kind": "push", "nth": 10,
         "action": "drop"}]})
    start = threading.Event()
    t = threading.Thread(target=_pusher(c, [k for k, _ in _BUCKET_KEYS],
                                        n, 1.0, start), daemon=True)
    t.start()
    start.set()
    time.sleep(0.05)                     # migration lands mid-traffic
    version = c.migrate_bucket(0, dst)
    assert version >= 1
    t.join(timeout=60)
    assert not t.is_alive()
    faultinject.install(None)
    for k, _ in _BUCKET_KEYS:
        out = c.pull(k, SIZE)
        np.testing.assert_array_equal(
            out, np.full(SIZE, float(n), np.float32))
        # state actually moved: target serves, source redirects
        assert (k, 0) in cl.servers[dst].store
        assert (k, 0) in cl.servers[src]._moved
        assert (k, 0) not in cl.servers[src].store
    cl.finalize()


def test_capacity_add_and_remove_mid_run(monkeypatch):
    """Server capacity add (a server registering beyond
    DMLC_NUM_SERVER) and remove (migrating its buckets away) mid-run:
    traffic retargets through the versioned plan and the final values
    byte-match the static single-server run."""
    n_before, n_on_new, n_after = 8, 8, 8
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=1)
    cl.servers[0]._handle_command("async_mode", b"")
    c = cl.client(plan_sizes=_BUCKET_KEYS)
    keys = [k for k, _ in _BUCKET_KEYS]
    for k, sz in _BUCKET_KEYS:
        c.init(k, np.zeros(sz, np.float32))
    one = np.ones(SIZE, np.float32)
    for _ in range(n_before):
        for k in keys:
            c.push(k, one)
    # -- capacity add: new server joins the running cluster ------------
    added = cl.add_server()
    _wait_until(lambda: added.rank is not None, what="server join")
    assert added.rank == 1
    c.migrate_bucket(0, 1)
    assert len(c.servers) == 2           # pools grew with the census
    for _ in range(n_on_new):
        for k in keys:
            c.push(k, one)
    assert all((k, 0) in added.store for k in keys)
    # the migrated updater-less store kept exact counts so far
    np.testing.assert_array_equal(
        c.pull(keys[0], SIZE),
        np.full(SIZE, float(n_before + n_on_new), np.float32))
    # -- capacity remove: drain the bucket off, then stop the server ---
    c.migrate_bucket(0, 0)
    assert all((k, 0) not in added.store for k in keys)
    for _ in range(n_after):
        for k in keys:
            c.push(k, one)
    total = float(n_before + n_on_new + n_after)
    for k in keys:
        np.testing.assert_array_equal(
            c.pull(k, SIZE), np.full(SIZE, total, np.float32))
    cl.finalize()


def test_migrated_bucket_carries_updater_state(monkeypatch):
    """Server-side optimizer state (momentum) migrates with the bucket:
    post-migration updates continue the SAME momentum stream as an
    unmigrated run."""
    import pickle

    from mxnet_tpu import optimizer as opt

    def run(migrate):
        cl = _Cluster(monkeypatch, n_workers=1, n_servers=2)
        for srv in cl.servers:
            srv._handle_command("async_mode", b"")
        c = cl.client(plan_sizes=_BUCKET_KEYS)
        c.send_command(0, pickle.dumps(opt.Optimizer.create_optimizer(
            "sgd", learning_rate=0.1, momentum=0.9)))
        for k, sz in _BUCKET_KEYS:
            c.init(k, np.zeros(sz, np.float32))
        g = np.full(SIZE, 0.5, np.float32)
        for _ in range(3):
            c.push(0, g)
        if migrate:
            c.migrate_bucket(0, 1 - c.server_for_bucket(0))
        for _ in range(3):
            c.push(0, g)
        out = c.pull(0, SIZE).copy()
        cl.finalize()
        return out

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6)


# ---------------------------------------------------------------------------
# Satellite: straggler fault kind is seeded-deterministic
# ---------------------------------------------------------------------------
_STRAGGLER_SPEC = {"seed": 13, "rules": [
    {"seam": "worker.send", "rank": 1, "action": "straggler",
     "seconds": 0.005},
    {"seam": "server.recv", "kind": "push", "nth": 3, "count": 2,
     "action": "straggler", "seconds": 0.005},
    {"seam": "worker.recv", "kind": "pull", "nth": 2, "action": "drop"},
]}


def _drive_plan(spec):
    plan = faultinject.install(dict(spec))
    seq = [("worker.send", {"kind": "push", "rank": 1, "sid": 0}),
           ("worker.send", {"kind": "push", "rank": 0, "sid": 0}),
           ("server.recv", {"kind": "push", "rank": 0}),
           ("server.recv", {"kind": "push", "rank": 0}),
           ("server.recv", {"kind": "push", "rank": 0}),
           ("server.recv", {"kind": "pull", "rank": 0}),
           ("server.recv", {"kind": "push", "rank": 0}),
           ("worker.recv", {"kind": "pull", "rank": 1, "sid": 0}),
           ("worker.recv", {"kind": "pull", "rank": 1, "sid": 0}),
           ("worker.send", {"kind": "pull", "rank": 1, "sid": 0})]
    out = []
    for seam, meta in seq:
        try:
            out.append((seam, faultinject.hook(seam, **meta)))
        except OSError as exc:
            out.append((seam, "raised:%s" % type(exc).__name__))
    log = list(plan.log)
    faultinject.install(None)
    return out, log


def test_straggler_fault_kind_deterministic():
    """Two runs of the same seeded schedule over the same event
    sequence produce identical fault logs and identical hook outcomes;
    straggler rules default to count=inf (persistent) unlike delay."""
    out1, log1 = _drive_plan(_STRAGGLER_SPEC)
    out2, log2 = _drive_plan(_STRAGGLER_SPEC)
    assert out1 == out2
    assert log1 == log2 and log1
    # straggler fired on EVERY matching event (persistent), delay-style
    # kinds stay bounded by their count
    straggler_hits = [e for e in log1 if e[4] == "straggler"
                      and e[0] == "worker.send"]
    assert len(straggler_hits) == 2     # BOTH rank-1 sends (count=inf)
    # and the seeded retry jitter is reproducible under the same plan
    faultinject.install(dict(_STRAGGLER_SPEC))
    d1 = [ksd.RetryPolicy().delay(k) for k in range(4)]
    faultinject.install(dict(_STRAGGLER_SPEC))
    d2 = [ksd.RetryPolicy().delay(k) for k in range(4)]
    faultinject.install(None)
    assert d1 == d2


def test_straggler_actually_sleeps():
    faultinject.install({"rules": [
        {"seam": "server.recv", "action": "straggler", "seconds": 0.05}]})
    t0 = time.perf_counter()
    assert faultinject.hook("server.recv", kind="push") is None
    assert time.perf_counter() - t0 >= 0.05
    faultinject.install(None)


# ---------------------------------------------------------------------------
# Satellite: the elastic-PS rebalance load signal (plumbing only)
# ---------------------------------------------------------------------------
def test_rebalance_signal_windows_per_server_load(monkeypatch):
    """``rebalance_signal`` reads the per-server wire-byte series out
    of the process metrics registry, WINDOWED per call, and names the
    hot and cold server.  The policy stays manual: the test (the
    driver) migrates the hot bucket itself and the next window flips
    the signal to the new owner."""
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=2)
    for srv in cl.servers:
        srv._handle_command("async_mode", b"")
    c = cl.client(plan_sizes=_BUCKET_KEYS)
    for k, sz in _BUCKET_KEYS:
        c.init(k, np.zeros(sz, np.float32))
    src = c.server_for_bucket(0)
    dst = 1 - src
    c.rebalance_signal()               # arm the window
    one = np.ones(SIZE, np.float32)
    for _ in range(10):
        for k, _sz in _BUCKET_KEYS:
            c.push(k, one)
    sig = c.rebalance_signal()
    assert sig["total"] > 0
    assert sig["hot"] == src and sig["cold"] == dst
    assert sig["per_server"][dst] == 0
    assert sig["imbalance"] is not None and sig["imbalance"] > 1.0
    # act on the evidence (manually — the signal never migrates)
    c.migrate_bucket(0, dst)
    for _ in range(10):
        for k, _sz in _BUCKET_KEYS:
            c.push(k, one)
    sig2 = c.rebalance_signal()
    assert sig2["hot"] == dst and sig2["per_server"][src] == 0
    cl.finalize()


# ---------------------------------------------------------------------------
# Satellite: automatic load-driven rebalance (kvstore_rebalance.py closes
# the sensor->migrate loop the previous test drives by hand)
# ---------------------------------------------------------------------------
from mxnet_tpu.kvstore_rebalance import RebalanceTrigger

# ~2400B per key with the 4096B client plan: every key is its own
# migratable fusion bucket, so ownership can actually spread
_REBAL_KEYS = [(k, 600) for k in range(4)]


def _rebal_cluster(monkeypatch):
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=2)
    for srv in cl.servers:
        srv._handle_command("async_mode", b"")
    c = cl.client(plan_sizes=_REBAL_KEYS)
    for k, sz in _REBAL_KEYS:
        c.init(k, np.zeros(sz, np.float32))
    return cl, c


def _owners(c):
    n = len(c.servers)
    return {b: c.plan.owner_of(b, n)
            for b, _ in c.plan.layout() if isinstance(b, int)}


def test_rebalance_trigger_converges_then_holds(monkeypatch):
    """Skewed traffic (a fixed key set that initially all lands on one
    server) drives the closed loop: one bucket migrates per tick until
    the windowed imbalance drops under the threshold, then the plan is
    STABLE — further windows of the same traffic decide 'hold', and the
    final ownership is the balanced split (the anti-thrash pin: the
    controller converges instead of oscillating)."""
    cl, c = _rebal_cluster(monkeypatch)
    trig = RebalanceTrigger(c, threshold=1.5, interval=9, min_bytes=1)
    start = _owners(c)
    hot0 = max(set(start.values()),
               key=lambda s: sum(1 for v in start.values() if v == s))
    keys = [k for k, _ in _REBAL_KEYS]
    grads = {k: np.ones(sz, np.float32) for k, sz in _REBAL_KEYS}
    c.rebalance_signal()                       # arm the window
    decisions = []
    for _tick in range(8):
        for _ in range(3):                     # one window of traffic
            for k in keys:
                c.push(k, grads[k])
        decisions.append(trig.evaluate_once()["action"])
    # converged: the last windows all held, and ownership is balanced
    assert decisions[-3:] == ["hold"] * 3, decisions
    final = _owners(c)
    per = [sum(1 for v in final.values() if v == s) for s in (0, 1)]
    assert per == [2, 2], (start, final, decisions)
    # exactly the migrations the initial skew required, all hot->cold
    need = sum(1 for v in start.values() if v == hot0) - 2
    assert len(trig.actions) == need, (trig.actions, start)
    assert all(src == hot0 and dst == 1 - hot0
               for _b, src, dst, _v in trig.actions)
    # and the moved state is really on the new owner
    for b, _src, dst, _v in trig.actions:
        for k in c.plan.members(b):
            assert (k, 0) in cl.servers[dst].store
    trig.close()
    cl.finalize()


def test_rebalance_trigger_holds_on_balanced_and_tiny_traffic(monkeypatch):
    """The hold gates: balanced traffic never migrates, sub-min_bytes
    windows never migrate (imbalance on noise is not evidence), and a
    hot server holding a single bucket is left alone — moving its only
    bucket just relabels the hot spot."""
    cl, c = _rebal_cluster(monkeypatch)
    keys = [k for k, _ in _REBAL_KEYS]
    grads = {k: np.ones(sz, np.float32) for k, sz in _REBAL_KEYS}
    # lay the plan out 2-2 by hand (the crc32 hash happens to pile all
    # four buckets onto one server) so balanced traffic IS balanced load
    buckets = sorted(_owners(c))
    for b in buckets[:2]:
        if _owners(c)[b] != 0:
            c.migrate_bucket(b, 0)
    for b in buckets[2:]:
        if _owners(c)[b] != 1:
            c.migrate_bucket(b, 1)
    assert sorted(_owners(c).values()) == [0, 0, 1, 1]
    trig = RebalanceTrigger(c, threshold=1.5, min_bytes=1)
    c.rebalance_signal()
    for _ in range(3):
        for k in keys:                    # uniform traffic, 2-2 plan
            c.push(k, grads[k])
    assert trig.evaluate_once()["action"] == "hold"
    assert trig.actions == []
    # tiny window: below min_bytes no migration regardless of skew
    big = RebalanceTrigger(c, threshold=1.5, min_bytes=1 << 30)
    c.rebalance_signal()
    for k in c.plan.members(buckets[0]):  # maximally skewed...
        c.push(k, grads[k])
    assert big.evaluate_once()["action"] == "hold"   # ...but tiny
    # one-bucket hot server: drain server 0 down to a single bucket,
    # then skew every push onto it — the policy must not relabel
    c.migrate_bucket(buckets[1], 1)
    owners = _owners(c)
    assert sum(1 for v in owners.values() if v == 0) == 1
    lone = next(b for b, s in owners.items() if s == 0)
    c.rebalance_signal()
    for _ in range(3):
        for k in c.plan.members(lone):
            c.push(k, grads[k])
    out = trig.evaluate_once()
    assert out["action"] == "hold" and out["signal"]["hot"] == 0
    assert trig.actions == []
    trig.close()
    big.close()
    cl.finalize()


def test_rebalance_threshold_floor_and_thread_discipline():
    """<=1.0 thresholds are clamped (some server is always 'hotter than
    the mean' — an un-floored threshold would migrate every tick
    forever), and the interval thread is stop-event + join disciplined:
    close() leaves no live controller thread behind."""

    class _Still:
        plan = codec.BucketPlan(bucket_bytes=4096)
        servers = [0, 1]
        calls = []

        def rebalance_signal(self):
            self.calls.append(time.monotonic())
            return {"imbalance": None, "total": 0, "hot": None,
                    "cold": None, "per_server": {}}

        def migrate_bucket(self, b, dst):  # pragma: no cover
            raise AssertionError("hold window must not migrate")

    assert RebalanceTrigger(_Still(), threshold=0.5,
                            min_bytes=0).threshold == 1.1
    trig = RebalanceTrigger(_Still(), threshold=2.0, interval=0.02,
                            min_bytes=0, start=True)
    _wait_until(lambda: len(_Still.calls) >= 2,
                what="controller ticks")
    assert trig._thread.is_alive() and not trig._thread.daemon
    trig.close()
    assert not trig._thread.is_alive()
    trig.close()                               # idempotent


def test_rebalance_armed_by_env_on_rank0(monkeypatch):
    """MXNET_KVSTORE_REBALANCE=1 arms the controller on the rank-0
    worker of a dist kvstore and close() tears it down with the
    store."""
    cl = _Cluster(monkeypatch, n_workers=1, n_servers=2)
    monkeypatch.setenv("MXNET_KVSTORE_REBALANCE", "1")
    monkeypatch.setenv("MXNET_KVSTORE_REBALANCE_INTERVAL", "0.05")
    kv = mx.create_kvstore("dist_async")
    try:
        assert kv._rebalance is not None
        assert kv._rebalance._thread.is_alive()
    finally:
        kv.close()
    assert not kv._rebalance._thread.is_alive()
    # and OFF by default: no controller unless the knob asks for one
    monkeypatch.setenv("MXNET_KVSTORE_REBALANCE", "0")
    cl2 = _Cluster(monkeypatch, n_workers=1, n_servers=1)
    kv2 = mx.create_kvstore("dist_async")
    try:
        assert kv2._rebalance is None
    finally:
        kv2.close()
