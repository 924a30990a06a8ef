"""Overlapped device input staging: upload batch t+1 while step t computes.

Reference: ``src/io/iter_prefetcher.h`` — the reference wraps every data
iterator in a ``PrefetcherIter`` whose background thread keeps the NEXT
batch ready so the training loop never blocks on IO.  On TPU the expensive
half of "ready" is the host->device transfer itself (a 19 MB image batch
is a copy the step would otherwise wait on), so the stager prefetches
*onto the device*: a producer thread pulls batches from the source iterator and
``jax.device_put``s their arrays toward the consumer's placement (a device
or a mesh sharding), parking the staged batches in a bounded queue.  While
step t runs its compiled program, the producer is already uploading batch
t+1 — the classic double buffer (``MXNET_IO_STAGE_DEPTH`` slots).

Donation safety: jax arrays are immutable and every staged batch gets
fresh device buffers, so a program that donates its input buffers (the
executor's aux donation, dp.py's whole-state donation) can never alias a
buffer the stager still holds — the rotation is safe by construction.

``MXNET_IO_STAGE=0`` bypasses staging entirely: ``Module.fit`` then feeds
the source iterator's batches straight to the step, bit-for-bit the
pre-stager behavior (values are unchanged either way — staging only moves
WHERE the upload happens; tests/test_input_staging.py pins exactness).

Profiler: each upload records an ``h2d_stage`` span (step-phase seam,
``profiler.phase``); because it runs on the producer thread it
OVERLAPS the consumer's ``compute`` span — seeing the two side by side in
a Chrome trace is the visual evidence of the overlap.
"""
from __future__ import annotations

import copy
import queue
import threading

import jax

from .. import profiler as _profiler
from ..base import MXNetError, get_env, hot_path
from ..ndarray import NDArray
from .pipeline import put_interruptible

__all__ = ["DeviceStager", "staging_enabled"]

_EOF = object()


def staging_enabled():
    """Is overlapped input staging on (MXNET_IO_STAGE)?"""
    return bool(get_env("MXNET_IO_STAGE"))


class DeviceStager:
    """Iterator wrapper staging each batch's arrays onto the device.

    Parameters
    ----------
    source : DataIter (or any iterable with ``reset()``)
        Yields ``DataBatch``es; consumed on the producer thread.
    place_fn : array-like -> jax.Array
        Commits one array to its target placement (``jax.device_put``
        onto a device or NamedSharding).  Runs on the producer thread.
    depth : int, optional
        Staged-batch bound; defaults to ``MXNET_IO_STAGE_DEPTH``.
    """

    def __init__(self, source, place_fn, depth=None):
        self._source = source
        self._place = place_fn
        if depth is None:
            # registered default 2; 0/negative degrade to single-buffer
            # (minimum pinned device memory), never silently back to 2
            depth = int(get_env("MXNET_IO_STAGE_DEPTH"))
        self._depth = max(1, depth)
        self._queue = None
        self._producer = None
        self._stop = threading.Event()
        # consumer-frontier data state: each staged batch carries the
        # source's state_dict() captured right after the producer pulled
        # it, and state_dict() reports the last batch the CONSUMER took
        # — batches staged ahead are never reflected (checkpointable-
        # iterator protocol, docs/architecture/data_pipeline.md)
        self._frontier = None

    # -- producer -------------------------------------------------------
    def _start(self):
        # each producer gets its OWN stop event and queue: a reset that
        # raced a producer stuck inside next(source) must never leave
        # the old thread feeding (or un-stopping) the new epoch's run
        self._stop = threading.Event()
        self._queue = queue.Queue(maxsize=self._depth)
        # producer is parked: the source position IS the frontier until
        # the consumer takes the first staged batch
        self._frontier = self._source_state(self._source)
        self._producer = threading.Thread(
            target=self._produce, args=(self._queue, self._stop),
            name="mxt-stage", daemon=True)
        self._producer.start()

    @staticmethod
    def _source_state(source):
        from ..data.checkpoint import state_dict_of
        return state_dict_of(source)

    def _produce(self, q, stop):
        src = iter(self._source)
        try:
            while not stop.is_set():
                try:
                    batch = next(src)
                except StopIteration:
                    q.put((_EOF, self._source_state(self._source)))
                    return
                staged = self._stage_batch(batch)
                staged._mxt_data_state = self._source_state(self._source)
                # bounded hand-off: blocks when the consumer is `depth`
                # batches behind, stop-aware so reset() always wins the
                # race against a full queue
                put_interruptible(q, stop, staged)
        except BaseException as e:  # surface producer errors to the consumer
            q.put(e)

    def _stage_batch(self, batch):
        """Shallow-copy the batch with its data/label arrays placed on
        device; every other attribute (pad, index, bucket_key,
        provide_*) rides along untouched."""
        with _profiler.phase("h2d_stage"):
            staged = copy.copy(batch)
            placed = []
            if getattr(batch, "data", None):
                staged.data = [self._place_one(a) for a in batch.data]
                placed += staged.data
            if getattr(batch, "label", None):
                staged.label = [self._place_one(a) for a in batch.label]
                placed += staged.label
            if placed:
                # wait for the transfers on THIS (producer) thread: the
                # h2d_stage span then covers the upload, not just its
                # enqueue, and the consumer receives resident buffers —
                # the whole point of staging
                jax.block_until_ready([a._data for a in placed])
        return staged

    def _place_one(self, arr):
        src = arr._data if isinstance(arr, NDArray) else arr
        return NDArray(self._place(src))

    # -- consumer -------------------------------------------------------
    def __iter__(self):
        return self

    @hot_path
    def __next__(self):
        if self._producer is None:
            # lazy start: staging begins at the first consumer read, so
            # an epoch-end reset() never pre-consumes a source epoch
            # that is not going to run
            self._start()
        item = self._queue.get()
        if isinstance(item, BaseException):
            raise MXNetError("input staging worker failed: %r"
                             % (item,)) from item
        batch, state = item if isinstance(item, tuple) else (item, None)
        if batch is _EOF:
            if state is not None:
                self._frontier = state
            raise StopIteration
        st = getattr(batch, "_mxt_data_state", None)
        if st is not None:
            self._frontier = st
        return batch

    next = __next__

    def reset(self):
        """Stop in-flight staging, rewind the source (new epoch; the
        producer restarts lazily at the next read).  Batches staged past
        the consumer are discarded — the source is rewound to its own
        start anyway."""
        self._halt()
        self._source.reset()

    def close(self):
        """Stop the producer for good (fit-scope teardown)."""
        self._halt()

    def _halt(self):
        if self._producer is None:
            return
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._producer.join(timeout=30)
        if self._producer.is_alive():
            # the producer is stuck inside next(source); resetting the
            # source now would race its cursor from two threads and
            # silently eat the new epoch's first batch when the stuck
            # call returns.  Fail loudly instead.
            raise MXNetError(
                "input staging producer stuck in the source iterator "
                "for >30s; cannot safely reset/close the stager")
        self._producer = None

    # -- checkpoint protocol --------------------------------------------
    def state_dict(self):
        """Consumer-frontier state: the source position after the last
        batch the consumer pulled THROUGH the stager (staged-ahead
        batches are discarded on resume, so they must not count)."""
        if self._producer is None:
            return self._source_state(self._source)
        return self._frontier

    def load_state(self, state):
        """Stop staging, restore the source position; the producer
        restarts lazily at the next read."""
        self._halt()
        self._source.load_state(state)
        self._frontier = None

    def __getattr__(self, name):
        # provide_data / provide_label / batch_size etc. pass through
        return getattr(self._source, name)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
