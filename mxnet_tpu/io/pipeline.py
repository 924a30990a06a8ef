"""Threaded decode/augment pipeline with double-buffered batches.

Reference: ``src/io/iter_image_recordio_2.cc:495-557`` — recordio chunks are
decoded + augmented by an OMP thread pool behind a ``dmlc::ThreadedIter``
double buffer, so the training loop never waits on JPEG decode.  Python
analog: a producer thread reads raw records (the native C++ prefetcher
already overlaps disk IO), fans decode work out to a thread pool with a
bounded in-flight window (order-preserving), assembles batches, and parks
them in a bounded queue the iterator pops from.  PIL's JPEG decode releases
the GIL, so pool threads genuinely overlap.

Checkpointability (``stateful=True``): the raw source then returns
``(raw, meta)`` pairs (``meta`` = per-record decode context: ordinal,
epoch), reads are strictly sequential, and the producer snapshots
``snapshot_fn()`` right after each batch-tail read — so the pipeline
tracks the **consumer frontier**: the source position after the last
batch :meth:`next_batch` returned, never in-flight decode work.
``state_dict()`` therefore always describes a position the training
loop has actually reached: a resume from it replays zero and skips zero
records, however far the producer had read ahead
(docs/architecture/data_pipeline.md, drain-to-a-consistent-frontier).

Thread discipline: each producer generation owns its OWN stop event and
queue (the ``stager.py`` treatment) — a ``reset()`` racing a producer
stuck inside ``read_fn`` can never cross-feed epochs, and a producer
stuck >30s makes reset/close raise instead of racing the source cursor.
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from concurrent.futures import ThreadPoolExecutor

from .. import faultinject, profiler
from ..base import MXNetError, hot_path

__all__ = ["ThreadedBatchPipeline", "put_interruptible"]

_EOF = object()


def put_interruptible(q, stop, item, timeout=0.1):
    """Bounded queue put that a halt can always win against: blocks in
    short slices, re-checking ``stop`` between them.  Returns False
    once stopped (the item is dropped — the halting side owns the
    queue).  Shared by the pipeline producer, the device stager, and
    the prefetch readers so the shutdown-race primitive cannot drift
    between them again."""
    while not stop.is_set():
        try:
            q.put(item, timeout=timeout)
            return True
        except queue.Full:
            continue
    return False


class ThreadedBatchPipeline:
    """Producer/consumer batch pipeline.

    Parameters
    ----------
    read_fn : () -> raw | None, or () -> (raw, meta) | None when stateful
        Sequential raw-record source; None signals end of epoch.  In
        stateful mode ``meta`` (``ordinal``, ``epoch``, ...) rides to
        ``decode_fn`` — per-record decode context, not position state.
    decode_fn : raw -> sample, or (raw, meta) -> sample when stateful
        CPU-bound per-record work (decode + augment); runs in pool threads.
    assemble_fn : (samples, pad) -> batch
        Builds the final batch object on the producer thread.
    reset_fn : () -> None
        Rewinds the raw source for the NEXT epoch (epoch counter
        advances there).
    snapshot_fn : () -> state, optional
        The source's ``state_dict`` — called while the producer is
        parked (initial frontier / after a reload) and synchronously
        after each batch-tail read; required when stateful.
    """

    def __init__(self, read_fn, decode_fn, assemble_fn, reset_fn,
                 batch_size, preprocess_threads=4, prefetch=4,
                 pad_last=True, stateful=False, snapshot_fn=None):
        self._read = read_fn
        self._decode = decode_fn
        self._assemble = assemble_fn
        self._reset_src = reset_fn
        self.batch_size = batch_size
        self._threads = max(1, int(preprocess_threads))
        self._prefetch = max(1, int(prefetch))
        self._pad_last = pad_last
        self._stateful = bool(stateful)
        if self._stateful and snapshot_fn is None:
            raise MXNetError("stateful pipeline needs snapshot_fn")
        self._snapshot = snapshot_fn or (lambda: None)
        self._pool = ThreadPoolExecutor(
            max_workers=self._threads,
            thread_name_prefix="mxt-decode")
        self._queue = None
        self._producer = None
        self._stop = threading.Event()
        self._frontier = None       # state of the last CONSUMED batch
        self.batches_consumed = 0   # since epoch start / last load_state
        self._closed = False
        self._start()

    # -- producer -------------------------------------------------------
    def _start(self):
        # each producer generation gets its OWN stop event and queue: a
        # reset that raced a producer stuck inside read_fn must never
        # leave the old thread feeding (or un-stopping) the new epoch
        self._stop = threading.Event()
        self._queue = queue.Queue(maxsize=self._prefetch)
        # the producer is parked right now: this snapshot IS the
        # consumer frontier until the first batch lands
        self._frontier = self._snapshot()
        self._producer = threading.Thread(
            target=self._produce, args=(self._queue, self._stop),
            name="mxt-pipeline", daemon=True)
        self._producer.start()

    def _put_interruptible(self, q, stop, item):
        return put_interruptible(q, stop, item)

    def _produce(self, q, stop):
        try:
            futures = deque()       # (future, state|None) in read order
            window = self._threads * 2
            samples = []
            last_state = None       # source state after a batch's tail
            reads = 0
            eof = False
            while not stop.is_set():
                while not eof and len(futures) < window:
                    item = self._read()
                    if item is None:
                        eof = True
                        break
                    if self._stateful:
                        raw, meta = item
                        reads += 1
                        # reads are strictly sequential, so record k is
                        # a batch tail iff k is a batch_size multiple —
                        # snapshot the source ONLY there (a per-record
                        # capture would put O(state) work on every read;
                        # the windowed shuffle's state alone is
                        # O(shuffle_window))
                        state = self._snapshot() \
                            if reads % self.batch_size == 0 else None
                        fut = self._pool.submit(self._decode, raw, meta)
                    else:
                        state = None
                        fut = self._pool.submit(self._decode, item)
                    futures.append((fut, state))
                if futures:
                    fut, state = futures.popleft()
                    samples.append(fut.result())
                    if state is not None:
                        last_state = state
                    if len(samples) == self.batch_size:
                        batch = self._assemble(samples, 0)
                        if not self._put_interruptible(
                                q, stop, (batch, last_state)):
                            return
                        samples = []
                    continue
                # end of stream: the post-final-record snapshot is the
                # frontier of both the padded partial batch and the
                # eof stamp, which lets an epoch-boundary checkpoint
                # resume into the NEXT epoch
                tail_state = self._snapshot() if self._stateful else None
                if samples and self._pad_last:
                    pad = self.batch_size - len(samples)
                    samples = samples + [samples[-1]] * pad
                    batch = self._assemble(samples, pad)
                    if not self._put_interruptible(
                            q, stop, (batch, tail_state)):
                        return
                eof_state = None
                if self._stateful:
                    eof_state = dict(tail_state)
                    eof_state["eof"] = True
                self._put_interruptible(q, stop, (_EOF, eof_state))
                return
        except BaseException as e:  # surface worker errors to the consumer
            self._put_interruptible(q, stop, e)

    # -- consumer -------------------------------------------------------
    @hot_path
    def next_batch(self):
        """Next assembled batch; raises StopIteration at epoch end.

        This is the pipeline's consumer seam: the seeded fault plan's
        ``data.next`` kill-point fires here (``action: die`` = the
        process vanishes mid-epoch, ``delay`` = a slow input stall;
        ``drop`` is meaningless for a batch and proceeds), and the
        ``data_next`` span feeds the profiler's data_wait attribution
        (it nests inside the fit loop's ``data_wait`` phase, so it is
        reported as overlapped, not additive)."""
        faultinject.hook("data.next", kind="batch")
        with profiler.phase("data_next"):
            item = self._queue.get()
        if isinstance(item, BaseException):
            raise MXNetError("data pipeline worker failed: %r" % (item,)) \
                from item
        batch, state = item
        if state is not None:
            self._frontier = state
        if batch is _EOF:
            raise StopIteration
        self.batches_consumed += 1
        return batch

    def reset(self):
        """Stop in-flight work, advance the source to its next epoch,
        restart the producer."""
        self._halt()
        self._reset_src()
        self.batches_consumed = 0
        self._start()

    def reload(self, mutate_fn=None):
        """Same-position restart: halt the producer, let ``mutate_fn``
        reposition/reconfigure the source (``load_state``,
        ``set_partition``), restart.  Producer read-ahead the consumer
        never saw is discarded — the mutation owns the cursor."""
        self._halt()
        if mutate_fn is not None:
            mutate_fn()
        self._start()

    # -- checkpoint protocol --------------------------------------------
    def state_dict(self):
        """Consumer-frontier state: the source position after the last
        batch :meth:`next_batch` returned plus the epoch batch counter."""
        if not self._stateful:
            raise MXNetError("pipeline built without stateful=True has "
                             "no checkpointable state")
        return {"version": 1, "source": self._frontier,
                "batches": self.batches_consumed}

    def load_state(self, state, mutate_fn):
        """Restore a :meth:`state_dict` capture: ``mutate_fn`` loads
        ``state['source']`` into the raw source while the producer is
        parked."""
        if not self._stateful:
            raise MXNetError("pipeline built without stateful=True has "
                             "no checkpointable state")
        self._halt()
        mutate_fn()
        src = state.get("source") or {}
        # an eof frontier rolled the source into the next epoch: the
        # batch counter restarts with it
        self.batches_consumed = 0 if src.get("eof") \
            else int(state.get("batches", 0))
        self._start()

    # -- teardown -------------------------------------------------------
    def _halt(self):
        if self._producer is None:
            return
        self._stop.set()
        # drain so a producer blocked on a full queue observes the stop
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._producer.join(timeout=30)
        if self._producer.is_alive():
            # stuck inside read_fn: repositioning the source now would
            # race its cursor from two threads — fail loudly instead
            raise MXNetError(
                "data pipeline producer stuck in the record source for "
                ">30s; cannot safely reset/reload the pipeline")
        self._producer = None

    def close(self):
        if self._closed:
            return
        self._closed = True
        try:
            self._halt()
        except MXNetError:
            # best-effort teardown: the stuck-producer guard protects
            # reset/reload (repositioning a live cursor is unsafe), but
            # close() must not mask the caller's original failure —
            # detach the stuck daemon thread and move on
            self._producer = None
        finally:
            self._pool.shutdown(wait=False)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
