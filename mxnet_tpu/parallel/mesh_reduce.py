"""Overlapped bucketed mesh collectives (the dist_mesh data plane).

The PS data plane hides RPC latency by pipelining per-bucket push/pull
pairs (kvstore_pipeline.py); the collectives data plane hides all-reduce
latency the same way: gradients are coalesced into the deterministic
``kvstore_codec.BucketPlan`` layout and each bucket's reduce launches as
soon as its members exist, so tail-layer communication runs under
head-layer work instead of serializing behind one barrier all-reduce.

:class:`MeshCollectiveLauncher` is the host-side engine shared by the
two frontends — ``KVStoreMesh`` (classic push/pull API: ``submit`` per
ready bucket at push time, ``drain`` at flush) and the
``reduce_mode='bucket'`` SPMD step variant (parallel/dp.py: one
``launch`` per step).  Each bucket launch crosses the
``mesh.collective`` faultinject seam (where the bench injects
per-collective DCN-ish latency) and the whole submit→drain window is
recorded as the ``comm_overlap`` step phase that tools/step_profile.py
aggregates.

XLA dispatch is already async, so on a real fabric the overlap win
comes from issuing the collectives early; on the CPU fake-device CI
mesh the win is made measurable by the injected seam latency — the
barrier variant pays ``n_buckets × delay`` serialized, the overlapped
variant pays ~``max(delay)``.
"""
from __future__ import annotations

import threading

import jax

from .. import faultinject, profiler
from ..base import get_env

__all__ = ["MeshCollectiveLauncher", "process_sum"]

SEAM = "mesh.collective"

# Overlapped launches carry the collective's LATENCY window (the seam
# sleep here, the fabric RTT on real hardware) concurrently, but the
# local dispatch of the compiled reduce is serialized: jaxlib's
# host-platform client can deadlock when 3+ host threads execute
# sharded programs at once (all stuck in pxla __call__), and enqueueing
# is the cheap async part anyway — it is not what overlap needs to hide.
_dispatch_lock = threading.Lock()


def process_sum(value):
    """Sum an array over every process of the global mesh.

    Single-process (the 8-fake-device CI shape): identity — the
    device-group merge already happened locally.  Multi-process: an
    all-gather over the jax.distributed mesh followed by a local sum,
    which is the collective the PS push RPC is replaced by."""
    if jax.process_count() <= 1:
        return value
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(value)
    return gathered.sum(axis=0)


class _Launch(object):
    __slots__ = ("bucket_id", "thread", "result", "error")

    def __init__(self, bucket_id):
        self.bucket_id = bucket_id
        self.thread = None
        self.result = None
        self.error = None


class MeshCollectiveLauncher(object):
    """Launch per-bucket reduce collectives, overlapped or barriered.

    ``overlap=None`` reads MXNET_MESH_OVERLAP.  Overlapped mode runs
    each bucket's reduce on its own daemon thread (all joined in
    ``drain``, so nothing leaks past the step/flush boundary); barrier
    mode runs them serially in submit order — the measurable baseline
    the ``kvstore.dist_mesh.overlap`` bench row compares against."""

    def __init__(self, overlap=None):
        self.overlap = bool(get_env("MXNET_MESH_OVERLAP")) \
            if overlap is None else bool(overlap)
        self._pending = []
        self._window = None     # the open comm_overlap span

    def submit(self, bucket_id, payload, reduce_fn):
        """Launch ``reduce_fn(bucket_id, payload)`` for one bucket; the
        result is available from :meth:`drain`.  The call crosses the
        ``mesh.collective`` faultinject seam first (injected latency
        lands per-collective, inside the worker thread, so overlap
        genuinely hides it)."""
        if self._window is None:
            # submit -> drain is one span over several calls of one
            # thread: opened here, closed in drain()
            self._window = profiler.phase("comm_overlap")
            self._window.__enter__()
        launch = _Launch(bucket_id)

        def run():
            try:
                faultinject.hook(SEAM, bucket=bucket_id)
                with _dispatch_lock:
                    launch.result = reduce_fn(bucket_id, payload)
            except BaseException as exc:   # re-raised at drain
                launch.error = exc

        if self.overlap:
            t = threading.Thread(target=run, daemon=True,
                                 name="mesh-reduce-%s" % (bucket_id,))
            launch.thread = t
            t.start()
        else:
            run()
        self._pending.append(launch)
        return launch

    def drain(self):
        """Join every outstanding launch; returns results in submit
        order (and records the whole submit→drain window as the
        ``comm_overlap`` phase).  Re-raises the first launch error."""
        launches, self._pending = self._pending, []
        window, self._window = self._window, None
        for launch in launches:
            if launch.thread is not None:
                launch.thread.join()
        if window is not None:
            window.__exit__(None, None, None)
        for launch in launches:
            if launch.error is not None:
                raise launch.error
        return [launch.result for launch in launches]

    def launch(self, buckets, reduce_fn):
        """One-shot batch: submit every ``(bucket_id, payload)`` then
        drain — the per-step shape the bucketed SPMD trainer uses."""
        for bucket_id, payload in buckets:
            self.submit(bucket_id, payload, reduce_fn)
        return self.drain()
