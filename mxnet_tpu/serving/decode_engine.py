"""Autoregressive generation engine: continuous batching on the decode
plane.

The forward batcher (``scheduler.ServingEngine``) amortizes ONE program
dispatch across requests; generation needs the same economics across
*tokens*.  A naive deployment re-runs the full forward for every
generated token (re-paying attention over the whole prefix — the
``serving.decode.reprefill`` bench baseline); this engine runs the
prompt ONCE (prefill, filling the KV cache) and then advances every
in-flight sequence one token per compiled decode step, admitting newly
prefilled sequences into the running batch between steps and retiring
finished ones (EOS / ``max_tokens``) — continuous batching, the regime
where decode throughput stops being per-request and becomes
per-step.

One engine thread owns the loop:

* **pump** — drain the submit queue into per-model FIFO waiting deques
  (blocking only when there is no admitted work at all);
* **admit** — take waiting requests (FIFO, never overtaking — pinned by
  the seeded-loadgen test), run one bucketed prefill batch
  (``serve_prefill`` phase), sample each sequence's first token, and
  copy its cache rows into free decode slots;
* **decode** — one compiled step per model with active slots
  (``serve_decode`` phase): the batch's next-token vector goes in, the
  donated KV cache is updated in place, and — in the default
  ``MXNET_SERVE_SAMPLE=graph`` mode — sampling (greedy, or seeded
  temperature/top-k per request) runs INSIDE the program over per-slot
  PRNG key state that rides as another donated argument, so the only
  per-step host transfer is the ``(slots,)`` token vector.
  ``MXNET_SERVE_SAMPLE=host`` is the escape hatch: the logits-out
  decode program plus the SAME jitted sampler on the host-fetched
  ``(slots, vocab)`` matrix — byte-identical token streams, one big
  fetch per step (``stats()["decode_fetch_elems"]`` counts the
  difference; the profiler's ``serve_sample`` phase brackets it);
* **retire** — a sequence hitting its ``eos_id`` or ``max_tokens``
  resolves its Future with a :class:`GenerationResult` (and closes its
  :class:`TokenStream`, if streaming); its slot frees for the next
  admission.

The KV cache is registry-owned serving state: it lives beside the
params on the model's :class:`~.program_store.GenerativeProgramStore`
(one device-resident copy in the store's ``kv_dtype`` —
``MXNET_SERVE_KV_DTYPE=bfloat16`` halves the bytes per slot;
``stats()`` describes it) and is threaded through the pure decode
programs cache-in/cache-out with donation, so the per-step write is an
in-place ``dynamic_update_slice`` on the resident buffers (donation is
skipped on the CPU backend, matching the training planes' donation
guards).

On the default PAGED plane (``MXNET_SERVE_PAGED=1``) the cache is a
single global pool of ``MXNET_SERVE_KV_BLOCK``-token blocks addressed
through per-slot block tables (:class:`_PagedModelState`): admission
reserves each request's worst-case block need up front (throttling
FIFO when the pool runs short — the pool can never exhaust
mid-flight), completed prefills register their blocks in a
copy-on-write prefix cache (:class:`_PrefixStore` — an identical
prompt prefix adopts the shared blocks instead of re-prefilling;
writes into shared blocks fork first), and prompts prefill in
``MXNET_SERVE_PREFILL_CHUNK``-token chunks AFTER each tick's decode
step so long prompts stop spiking co-running streams' inter-token
latency.  ``paged=False`` (or ``MXNET_SERVE_PAGED=0``) keeps the
contiguous per-slot plane above, bit-identical streams
(docs/architecture/decode_engine.md).

``close(drain=True)`` finishes every admitted AND queued generation
before the thread exits; ``close(drain=False)`` fails everything fast
with :class:`~.scheduler.ServeClosed`.
"""
from __future__ import annotations

import collections
import contextlib
import queue
import sys
import threading
import time
import types
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics as _metrics
from .. import profiler as _profiler
from .. import tracing as _tracing
from ..analysis import racecheck
from ..analysis.lockcheck import make_lock
from ..base import MXNetError, _uid, get_env, hot_path
from .scheduler import (FutureCompleter, ServeClosed, ServeOverloaded,
                        ServeTimeout, TIERS, _H_QWAIT)

# Aggregate generation histograms (process-wide; gated on
# MXNET_METRICS like every ambient observation seam).  TTFT and ITL
# are THE generation service metrics — the /metrics scrape carries
# their p50/p95/p99 without storing a sample per token.
_H_TTFT = _metrics.histogram(
    "serve_ttft_seconds",
    help="generation time-to-first-token, submit to first sample")
_H_ITL = _metrics.histogram(
    "serve_itl_seconds",
    help="generation inter-token latency, gap between samples")
_H_CHUNKS = _metrics.histogram(
    "serve_prefill_chunks_per_request",
    help="chunked-prefill dispatches one admitted request's prompt "
         "took on the paged decode plane", lo=1, hi=1e4)
_H_SPEC = _metrics.histogram(
    "serve_spec_emitted_per_step",
    help="tokens emitted per speculative verify step (1..K+1: "
         "accepted draft tokens + the bonus/corrected token; "
         "acceptance rate is (emitted-1) over the proposal window)",
    lo=1, hi=64)

# MXNET_SERVE_SPEC=auto's graceful-degradation policy: when the rolling
# acceptance EMA falls below the floor (the draft is fighting the
# target — adversarial prompts, mismatched domains), the engine stops
# paying for drafts and serves plain decode steps, PROBING one
# speculative tick every _SPEC_PROBE_EVERY ticks so a recovered draft
# re-engages.  Probes catch the draft's KV frontier up in
# prefill_chunk-sized teacher-forced dispatches, so a probe costs a few
# draft calls, not one per skipped token — and FAILED probes back off
# exponentially (doubling the cadence up to _SPEC_PROBE_MAX; recovery
# resets it), so a persistently hostile workload converges to
# near-zero speculation overhead instead of paying a fixed probe tax.
_SPEC_EMA_DECAY = 0.75
_SPEC_EMA_FLOOR = 0.125
_SPEC_PROBE_EVERY = 128
_SPEC_PROBE_MAX = 2048

__all__ = ["GenerationEngine", "GenerationResult", "TokenStream"]

_STOP = object()

# What the prefix cache has of a prompt and what adopting it leaves
# (GenerationEngine._prefix_cover): ``hit`` the entries to adopt, root
# first (the tail block's last), ``whole`` the prompt's whole blocks
# had with them and ``last_id`` the entry of the last, ``cut`` whether
# a window class shortened the hit, ``covered`` the prompt tokens the
# cache stands in for, ``prog`` where the slot resumes, ``needed`` the
# blocks it has yet to allocate, a class ``caps`` the most it holds at
# once and ``firsts`` the first logical block it adopts
_Cover = collections.namedtuple(
    "_Cover", "hit whole last_id cut covered prog needed caps firsts")


def _seed_key(seed):
    """The threefry key data a request's chain starts from, ``(2,)
    uint32``: byte-identical to ``jax.random.PRNGKey(seed)``, for
    32-bit seeds without paying a threefry dispatch on the host's hot
    path."""
    if 0 <= seed < 2 ** 32:
        return np.array((0, seed), np.uint32)
    return np.asarray(jax.random.PRNGKey(seed), np.uint32)


class GenerationResult:
    """One finished generation (what the request's Future resolves to).

    ``tokens`` — the generated ids (prompt excluded); ``finish_reason``
    — ``'eos'`` or ``'length'``; ``token_times`` — host
    ``perf_counter()`` stamps taken as each token was sampled, so
    clients (and the loadgen) derive TTFT (``token_times[0] -
    t_submit``) and inter-token latency without streaming machinery;
    ``t_admit`` — the same clock when the request got its decode slot
    (None where the result was rebuilt from a reply that lacks it)."""

    __slots__ = ("model", "prompt_len", "tokens", "finish_reason",
                 "t_submit", "token_times", "t_admit")

    def __init__(self, model, prompt_len, tokens, finish_reason,
                 t_submit, token_times, t_admit=None):
        self.model = model
        self.prompt_len = prompt_len
        self.tokens = tokens
        self.finish_reason = finish_reason
        self.t_submit = t_submit
        self.token_times = token_times
        self.t_admit = t_admit

    @property
    def queue_wait_s(self):
        """Submit -> admission into a decode slot (seconds)."""
        return self.t_admit - self.t_submit

    @property
    def ttft_s(self):
        """Submit -> first generated token (seconds)."""
        return self.token_times[0] - self.t_submit

    def itl_s(self):
        """Inter-token gaps (seconds), one per token after the first."""
        return [b - a for a, b in zip(self.token_times,
                                      self.token_times[1:])]

    def __repr__(self):
        return ("GenerationResult(model=%r, %d tokens, %s)"
                % (self.model, len(self.tokens), self.finish_reason))


class TokenStream:
    """Blocking per-sequence token iterator.

    Construct one and pass it to :meth:`GenerationEngine.submit`
    (``stream=``): the engine pushes each sampled token id as it is
    generated and closes the stream when the sequence retires, so
    ``for tok in stream: ...`` sees tokens at inter-token latency
    instead of waiting for the Future."""

    _CLOSE = object()

    def __init__(self):
        self._q = queue.Queue()

    def push(self, token):
        self._q.put(int(token))

    def close(self):
        self._q.put(self._CLOSE)

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._CLOSE:
            raise StopIteration
        return item


class _GenRequest:
    __slots__ = ("model", "prompt", "max_tokens", "temperature", "top_k",
                 "seed", "eos_id", "stream", "future", "deadline",
                 "t_submit", "t_admit", "tokens", "token_times", "seq",
                 "priority", "tenant", "trace", "trace_parent")

    def __init__(self, model, prompt, max_tokens, temperature, top_k,
                 seed, eos_id, stream, future, deadline, t_submit, seq,
                 priority="batch", tenant=None):
        self.model = model
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.seed = int(seed)
        self.eos_id = eos_id
        self.stream = stream
        self.future = future
        self.deadline = deadline
        self.t_submit = t_submit
        self.t_admit = None
        self.tokens = []
        self.token_times = []
        self.seq = seq
        self.priority = priority  # admission tier (scheduler.TIERS)
        self.tenant = tenant      # quota/metrics key, or None
        # trace context captured on the submitting thread and
        # re-activated around this request's prefill/decode dispatches
        self.trace = None
        self.trace_parent = None


class _ModelState:
    """Live decode batch of one model: slot table + the KV cache +
    per-slot sampling state (PRNG key chain, temperature, top-k)."""

    # no tick of this plane is ever queued ahead of its fetch
    # (:class:`_PagedModelState`, ``flight``)
    flight = None

    def __init__(self, store):
        self.store = store
        self.slots = []                      # _GenRequest or None
        self.lengths = np.zeros(0, np.int32)   # cache frontier per slot
        self.next_tok = np.zeros(0, np.int32)  # next token to consume
        self.temps = np.zeros(0, np.float32)   # <= 0 means greedy
        self.top_ks = np.zeros(0, np.int32)
        self.keys = jnp.zeros((0, 2), jnp.uint32)  # threefry key data
        self.cache_k = None
        self.cache_v = None
        self.C = 0                           # current cache bucket

    def active(self):
        return [i for i, r in enumerate(self.slots) if r is not None]

    def free_slot(self):
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def describe(self):
        act = self.active()
        d = {"slots": len(self.slots), "active": len(act),
             "cache_len": self.C,
             "sample_mode": self.store.sample_mode}
        if self.cache_k is not None:
            total = 2 * self.cache_k.size * self.cache_k.dtype.itemsize
            d["cache_mb"] = round(total / 2**20, 3)
            d["cache_dtype"] = str(self.cache_k.dtype)
            # the bf16 claim's measurement: bytes one slot's cache rows
            # occupy at the current bucket depth (halved vs fp32)
            if self.slots:
                d["cache_bytes_per_slot"] = total // len(self.slots)
        return d


class _BlockPool:
    """Host-side allocator over the paged KV pool's physical blocks.

    A model whose pool has several CLASSES of block (``models/cohere2_
    moe.py``: the full layers' leaves, the window layers' leaves) has
    one allocator a class, all of them in the one cache manager
    (:class:`_PagedModelState`).
    Block 0 is the reserved trash block (zero table entries point at
    it; non-participating dispatch rows reach no other block) and is
    never allocated.  Every allocated block carries a refcount: a sequence
    holding it in its table counts one, each prefix-cache pin counts
    one — a block frees when the last reference drops."""

    def __init__(self, num_blocks):
        self.num_blocks = int(num_blocks)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._ref = {}
        self._hwm = 0
        # kept as references come and go, so that no tick walks the
        # pool to count them: the blocks the prefix cache pins, how
        # many of those nobody else holds (a pin that would FREE its
        # block: what eviction can reclaim), and the blocks with more
        # than one reference
        self._pinned = set()
        self._pinned_once = 0
        self._shared = 0
        # the engine thread mutates the allocator (admission /
        # retirement); stats() -> describe() reads it from client
        # threads.  The lock makes those reads coherent; the coarse
        # shared_state revision marker lets MXNET_RACE_CHECK=1 catch
        # any future unlocked path through the pool
        self._lock = make_lock("serving.gen.block_pool")
        self._rc = racecheck.shared_state("serving.gen.block_pool",
                                          rev=0)

    def capacity(self):
        return self.num_blocks - 1

    @property
    def hwm(self):
        with self._lock:
            _ = self._rc.rev
            return self._hwm

    def used(self):
        with self._lock:
            _ = self._rc.rev
            return self.capacity() - len(self._free)

    def free_count(self):
        with self._lock:
            _ = self._rc.rev
            return len(self._free)

    def refcount(self, b):
        with self._lock:
            _ = self._rc.rev
            return self._ref.get(b, 0)

    def held_once(self, blocks):
        """How many of ``blocks`` have ONE reference (under one lock:
        a hit of a hundred blocks is an admission's, with the device
        idle meanwhile)."""
        with self._lock:
            _ = self._rc.rev
            ref = self._ref
            return sum(1 for b in blocks if ref.get(b, 0) == 1)

    def pinned_once(self):
        """Pinned blocks that only their pin holds."""
        with self._lock:
            _ = self._rc.rev
            return self._pinned_once

    def alloc(self):
        """One fresh block at refcount 1, or None when exhausted."""
        with self._lock:
            self._rc.rev += 1
            if not self._free:
                return None
            b = self._free.pop()
            self._ref[b] = 1
            used = self.capacity() - len(self._free)
            if used > self._hwm:
                self._hwm = used
            return b

    def ref(self, b, pin=False):
        """One more reference on ``b``; ``pin``: the prefix cache's (a
        block has at most one)."""
        with self._lock:
            self._rc.rev += 1
            was = self._ref[b]
            self._ref[b] = was + 1
            self._shared += was == 1
            self._pinned_once -= was == 1 and b in self._pinned
            if pin:
                self._pinned.add(b)

    def deref(self, b, pin=False):
        """Drop a reference on ``b`` (``pin``: the prefix cache's);
        the block frees with its last."""
        with self._lock:
            self._rc.rev += 1
            r = self._ref[b] - 1
            self._shared -= r == 1
            self._pinned_once -= r == 0 and b in self._pinned
            if pin:
                self._pinned.discard(b)
            self._pinned_once += r == 1 and b in self._pinned
            if r <= 0:
                del self._ref[b]
                self._free.append(b)
            else:
                self._ref[b] = r
            return r

    def shared(self):
        """Blocks currently referenced more than once."""
        with self._lock:
            _ = self._rc.rev
            return self._shared


class _PrefixStore:
    """Copy-on-write prefix cache: exact prompt prefixes -> pinned
    pool blocks, a block a class of the pool.

    An entry stands for one block of a prompt and is keyed by (the
    entry of the block before it, this block's own tokens): the chain
    from the root IS the token prefix, so a match is exact (no hash
    collisions) and costs the prompt's length once, not once a block.
    Whole blocks are registered as a prefill fills them, a partial
    tail block at its end.  An entry pins one refcount on its block in
    EACH class, so shared prefixes survive their registering
    sequence's retirement — and, in a class with a window, survive the
    sequence's own release of the block as it falls behind
    (:meth:`GenerationEngine._release_behind`).  Matching walks whole
    blocks and takes the tail only on an exact whole-prompt match — N
    requests with the same system prompt pay its prefill once.

    Pins whose block would free are evictable, least recently used
    first, a class at a time (:meth:`evict_one`): in a class whose
    sequences keep every block the entry goes with its pin (a prefix
    is of no use without its first blocks, and what hangs below it can
    no longer be reached and is evicted in its turn); in a class with a
    window only that class's pin goes and the entry stays.  So a hit
    may find the chain whole but a window block gone: it is then CUT
    to the longest prefix whose last ``window`` keys are all still
    pinned (:meth:`match`), or refused."""

    def __init__(self, pools, block_size, windows=(None,)):
        self._pools = list(pools)
        self._bs = int(block_size)
        self._windows = tuple(windows)
        # (parent entry's id, the block's tokens) -> [id, key, [block
        # a class, 0: not pinned]]
        self._entries = {}
        # a class: id -> entry, the entries with a pin in the class,
        # least recently used first
        self._lru = [collections.OrderedDict() for _ in self._pools]
        self._next_id = 1

    def __len__(self):
        return len(self._entries)

    def _touch(self, entries):
        """Mark ``entries`` (a chain, root first) used: the deepest
        first, so that eviction takes a chain from its end."""
        for e in reversed(entries):
            for lru in self._lru:
                if e[0] in lru:
                    lru.move_to_end(e[0])

    def first_needed(self, c, pos):
        """The first logical block of class ``c`` a query at ``pos``
        still sees."""
        w = self._windows[c]
        return 0 if w is None else max(0, (pos - w + 1) // self._bs)

    def holds(self, key):
        """Whether the block keyed ``(parent entry's id, its tokens)``
        is registered: the one probe a tick a slot in its prompt makes
        for its next block (:meth:`GenerationEngine._next_key`)."""
        return key in self._entries

    def match(self, prompt, held=(0, 0)):
        """Longest usable shared prefix of ``prompt``: ``(chain, tail,
        cut)`` — the entries of the whole shared blocks, root first,
        plus the tail block's entry on an exact whole-prompt match
        (else None); an entry's ``[2]`` holds its physical block a
        class.  ``held``: the asker already has the prompt's first
        ``held[0]`` whole blocks (the last under the entry ``held[1]``:
        a slot in its prompt, asking again), and the walk and the
        chain start behind them.  In a class with a window the adopter
        needs the blocks from :meth:`first_needed` of where it resumes
        on, no more; where one of those is neither held nor still
        pinned the hit is shortened until they are (``cut`` True; the
        chain may come back empty).  Touches the walked entries' LRU
        position; refcounts are NOT taken (the caller refs what it
        actually adopts)."""
        bs = self._bs
        n0, pid = held
        chain = []
        while (n0 + len(chain) + 1) * bs <= len(prompt):
            j = n0 + len(chain)
            e = self._entries.get((pid, tuple(prompt[j * bs:(j + 1) * bs])))
            if e is None:
                break
            chain.append(e)
            pid = e[0]
        tail = None
        if len(prompt) % bs and n0 + len(chain) == len(prompt) // bs:
            tail = self._entries.get(
                (pid, tuple(prompt[(n0 + len(chain)) * bs:])))
        self._touch(chain + ([tail] if tail else []))
        found = (len(chain), tail)
        for c, w in enumerate(self._windows):
            if w is None:
                continue
            # run[i]: whole blocks up to chain[i] in a row that are
            # held, or have a pin here
            run, n = [], n0
            for e in chain:
                n = n + 1 if e[2][c] else 0
                run.append(n)
            if tail is not None and not (
                    tail[2][c] and n0 + len(chain) - self.first_needed(
                        c, len(prompt) - 1) <= n):
                tail = None
            if tail is None:
                j = len(chain)
                while j and run[j - 1] < n0 + j - self.first_needed(
                        c, min((n0 + j) * bs, len(prompt) - 1)):
                    j -= 1
                chain = chain[:j]
        return chain, tail, (len(chain), tail) != found

    def _pin(self, e, blocks, pins):
        """Pin ``blocks`` (a block a class, 0: none) under entry ``e``
        in the classes where it has no pin."""
        for c, b in enumerate(blocks):
            if b and not e[2][c]:
                self._pools[c].ref(b, pin=True)
                e[2][c] = b
                self._lru[c][e[0]] = e
                if pins is not None:
                    pins.append((e, c))

    def unpin(self, pins):
        """Take back ``pins`` (``(entry, class)``, as :meth:`register`
        noted them): what a program that then FAILED was to fill.  An
        entry left without a pin goes.  Returns the blocks unpinned, a
        set a class."""
        blocks = [set() for _ in self._pools]
        for e, c in pins:
            if e[2][c]:
                blocks[c].add(e[2][c])
                self._pools[c].deref(e[2][c], pin=True)
                e[2][c] = 0
                del self._lru[c][e[0]]
            if not any(e[2]) and self._entries.get(e[1]) is e:
                del self._entries[e[1]]
        return blocks

    def register(self, parent, tokens, blocks, pins=None):
        """Pin one block of a prefill for future sharing under the
        entry ``parent`` (an id; 0: the prompt's first block):
        ``tokens`` the block's own (fewer than a block's: the prompt's
        tail), ``blocks`` its physical block in each class (+1
        refcount a class a NEW pin; a prefix already registered —
        possibly against other physical blocks — keeps them, and gets
        back the pins of a class with a window that eviction took).
        ``pins``: a list that takes ``(entry, class)`` of every NEW pin,
        for :meth:`unpin`.  Returns the entry's id, the parent of the
        block after."""
        key = (parent, tuple(tokens))
        e = self._entries.get(key)
        if e is None:
            e = self._entries[key] = [self._next_id, key,
                                      [0] * len(self._pools)]
            self._next_id += 1
        self._pin(e, blocks, pins)
        return e[0]

    def evictable(self, cls=0):
        """Pins of class ``cls`` whose block would FREE on eviction
        (refcount 1)."""
        return self._pools[cls].pinned_once()

    def evict_one(self, cls=0):
        """Drop the least-recently-used pin of class ``cls`` whose
        block frees; pins on blocks that live sequences still hold are
        passed over and count as used now, so that the next walk does
        not meet them again.  True when a block was reclaimed."""
        pool, lru = self._pools[cls], self._lru[cls]
        held, found = [], None
        for e in lru.values():
            if pool.refcount(e[2][cls]) == 1:
                found = e
                break
            held.append(e[0])
        for i in held:
            lru.move_to_end(i)
        if found is None:
            return False
        # without its block of a class that keeps every block the
        # entry is of no use: all its pins go
        for c in (range(len(self._pools))
                  if self._windows[cls] is None else (cls,)):
            if found[2][c]:
                self._pools[c].deref(found[2][c], pin=True)
                found[2][c] = 0
                del self._lru[c][found[0]]
        if not any(found[2]):
            del self._entries[found[1]]
        return True


class _Tick:
    """One tick of a loop that runs a tick AHEAD of its fetches
    (:meth:`GenerationEngine._tick_ahead`), queued and not yet
    fetched: ``rows`` every ``(slot, request)`` it works for, the
    decode group's first (``dec`` their slots), ``chunk`` its prompt
    chunk (None: the tick had no prompt row), ``slots`` how many slots
    the state had (the fetched array's layout), ``pins`` what it
    registered with the prefix cache, ``toks_dev`` the array to fetch
    and ``queued`` the starved clock's number of its dispatch."""

    __slots__ = ("rows", "dec", "chunk", "slots", "traces", "pins",
                 "toks_dev", "queued")


class _PagedModelState:
    """Live paged decode batch of one model: slot table + per-slot
    block tables over the global KV pool + the prefix cache.

    Unlike the contiguous :class:`_ModelState`, this PERSISTS across
    batch drains — the prefix cache's pinned blocks are the point of
    keeping it — so ``store.cache_state`` stays attached until the
    engine closes."""

    paged = True

    def __init__(self, store, draft=None, spec_k=0):
        self.store = store
        # the pool's classes of block, one allocator each: a sequence
        # has a table a class, side by side in its row of ``tables``
        # (``tw`` entries each), and gives the blocks of a class with a
        # WINDOW back as they fall behind it (docs/architecture/
        # decode_engine.md, "Classes of block")
        self.windows = tuple(w for w, _ in store.cache_classes)
        self.pool_of = [_BlockPool(store.pool_blocks)
                        for _ in self.windows]
        self.pool = self.pool_of[0]
        self.prefix = _PrefixStore(self.pool_of, store.kv_block,
                                   self.windows)
        self.tw = store.class_width()
        # the pool is an opaque tuple of donated leaves, as the model
        # shapes it: (k, v) for the LM, (latent,) for deepseek_v3
        self.pools = store.new_pool()
        # int8 plane: the per-(layer, head, block) fp32 absmax scale
        # pools ride beside the code pools through every dispatch
        self.scales = (store.new_scale_pool() if store.kv_int8
                       else None)
        self.tb = store.table_width()
        # the narrowest window (the spans' ``kv_tokens_window``), None
        # where every class keeps every block
        self.window = min((w for w in self.windows if w is not None),
                          default=None)
        # positions a query keeps of those its indexer scores (a model
        # with learned sparse attention: the spans' ``index_pairs`` and
        # ``keys_selected``), None where attention reads every key
        self.index_topk = store.spec.get("index_topk")
        # bytes of one block of each class, over the class's leaves
        # (the int8 plane's scale pools with the one class it has)
        nb = store.pool_blocks
        self.block_bytes_of = [
            sum(self.pools[i].size * self.pools[i].dtype.itemsize
                for i in leaves) // nb
            for _, leaves in store.cache_classes]
        self.block_bytes_of[0] += sum(
            a.size * a.dtype.itemsize for a in self.scales or ()) // nb
        # rows a block holds in the model's state leaves, and the
        # leaves' bytes (0: every leaf is by token)
        self.state_rows = store.state_rows_per_block()
        self.state_bytes = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in store.state_avals)
        self.slots = []                        # _GenRequest or None
        self.tables = np.zeros((0, self.tb), np.int32)
        self.lengths = np.zeros(0, np.int32)   # KV frontier per slot
        self.prog = np.zeros(0, np.int32)      # prompt tokens consumed
        self.decoding = np.zeros(0, bool)      # prompt done, generating
        self.chunks_done = np.zeros(0, np.int32)
        self.next_tok = np.zeros(0, np.int32)
        self.temps = np.zeros(0, np.float32)
        self.top_ks = np.zeros(0, np.int32)
        nc = len(self.windows)
        # a class: blocks the slot may still take from the pool, the
        # most it holds at once (a window bounds it), and how many
        # logical blocks it has given back behind the window
        self.resv = np.zeros((0, nc), np.int32)
        self.cap = np.zeros((0, nc), np.int32)
        self.passed = np.zeros((0, nc), np.int32)
        # the prompt's whole blocks the prefix cache has from this
        # slot, and the entry of the last (the parent of the next)
        self.reg_n = np.zeros(0, np.int32)
        self.reg_id = np.zeros(0, np.int64)
        # a slot: (reg_n, the prefix cache's key of the prompt block
        # after those), kept until reg_n moves (the engine's _next_key)
        self.next_key = []
        # the logical block the slot's last decode write was made
        # ready in (-1: none yet): only prompt blocks are ever pinned
        # or adopted, so that block stays the slot's own and the next
        # write into it has nothing to allocate or fork
        self.ready = np.zeros(0, np.int32)
        self.keys = jnp.zeros((0, 2), jnp.uint32)
        # a ONE-PASS store's loop runs a tick ahead of its fetches
        # (GenerationEngine._tick_ahead): the slots' pending tokens
        # stay on the device beside their key chains (``next_tok`` -1:
        # the device has it), and ``flight`` is the tick queued and
        # not yet fetched.  (No model that steps over row groups offers
        # the draft plane, so such a store never has a ``draft``.)
        self.ahead = store.one_pass
        # (a host array until the first slots exist: a store that does
        # not run ahead never puts it on the device)
        self.pending = np.zeros(0, np.int32)
        self.flight = None
        self.g_used = None                     # pool gauges (engine)
        self.g_hwm = None
        self.g_bytes = None
        # speculative decoding: the draft model's OWN pool arrays ride
        # the target's block tables (one allocator, two KV planes) —
        # dlen is the draft's per-slot KV frontier, dkeys its
        # independent per-slot PRNG chains
        self.draft = draft
        self.spec_k = int(spec_k)
        # a SELF-drafting store: the proposal each generating slot
        # carries to its next step (the module's, for the position
        # after its pending token; -1: none), no second pool
        self.self_draft = bool(store.self_draft)
        self.prop = np.zeros(0, np.int32)
        # the proposals the newest prompt chunk's fetch brought, a row
        self.chunk_props = None
        if draft is not None:
            self.dpools = draft.new_pool()
            self.dscales = (draft.new_scale_pool() if draft.kv_int8
                            else None)
            self.dlen = np.zeros(0, np.int32)
            # host-resident between spec ticks: admission writes
            # single rows, and only a spec tick's sampler needs the
            # device copy (it converts back when it finishes)
            self.dkeys = np.zeros((0, 2), np.uint32)
            # auto-mode degradation state: rolling acceptance EMA +
            # the probe countdown while speculating is suspended
            self.spec_ema = 1.0
            self.spec_probe = _SPEC_PROBE_EVERY
            self.spec_probe_every = _SPEC_PROBE_EVERY
            self.spec_forced = False

    @staticmethod
    def _split(out, head, pools, scales):
        """A program's flat return — ``head`` leading results, the
        pool's leaves, the int8 plane's two scale pools, the rest — as
        ``(head + rest, leaves, scales)``."""
        n = head + len(pools)
        m = n + (0 if scales is None else 2)
        return (tuple(out[:head]) + tuple(out[m:]), tuple(out[head:n]),
                None if scales is None else tuple(out[n:m]))

    def take(self, out, head=1):
        """Rebind the target's donated leaves from a program's flat
        return and hand back what is not a leaf."""
        rest, self.pools, self.scales = self._split(
            out, head, self.pools, self.scales)
        return rest

    def take_draft(self, out, head=1):
        """:meth:`take` for the draft plane's leaves."""
        rest, self.dpools, self.dscales = self._split(
            out, head, self.dpools, self.dscales)
        return rest

    def spec_mirror(self):
        """Whether prefill chunks mirror into the draft KV plane:
        always while speculating, skipped while the auto-mode fallback
        has speculation suspended (probe catch-up rebuilds the draft
        KV from the prompt when needed)."""
        return self.spec_forced or self.spec_ema >= _SPEC_EMA_FLOOR

    def active(self):
        return [i for i, r in enumerate(self.slots) if r is not None]

    def free_slot(self):
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def class_rows(self, c):
        """Class ``c``'s part of every slot's table row (a view)."""
        return self.tables[:, c * self.tw:(c + 1) * self.tw]

    def reserved(self, c):
        """Blocks of class ``c`` the admitted slots may still take.
        Where a window bounds what a slot holds at once, no more than
        that less what it holds now: it gives a block back for every
        block it takes from then on."""
        want = self.resv[:, c]
        if self.windows[c] is not None:
            held = np.count_nonzero(self.class_rows(c), axis=1)
            want = np.minimum(want, np.maximum(self.cap[:, c] - held, 0))
        return int(want.sum())

    def window_cap(self, c):
        """The most blocks of class ``c`` a slot holds at once: its
        window's keys and the rows of the longest dispatch, wherever
        the block boundaries fall."""
        rows = max(self.store.prefill_chunk, self.spec_k + 1)
        return (self.windows[c] + rows - 2) // self.store.kv_block + 2

    def bytes_used(self):
        """Bytes behind the allocated blocks of every class."""
        return sum(p.used() * b
                   for p, b in zip(self.pool_of, self.block_bytes_of))

    def describe(self):
        act = self.active()
        # dtype-aware pool bytes: int8 code pools carry their fp32
        # scale pools — a block is only decodable as codes+scale, so
        # the memory claim counts both (the PR-12 weight_bytes
        # discipline applied to the KV plane)
        pool_bytes = sum(a.size * a.dtype.itemsize
                         for a in self.pools + (self.scales or ()))
        per_class = self.block_bytes_of
        per_block = sum(per_class)
        used = [p.used() for p in self.pool_of]
        bytes_used = self.bytes_used()
        d = {"slots": len(self.slots), "active": len(act),
             "paged": True,
             "sample_mode": self.store.sample_mode,
             "block_size": self.store.kv_block,
             "prefill_chunk": self.store.prefill_chunk,
             "pool_blocks": self.pool.capacity(),
             "pool_blocks_used": self.pool.used(),
             "pool_blocks_hwm": self.pool.hwm,
             "pool_blocks_shared": self.pool.shared(),
             "pool_blocks_reserved": self.reserved(0),
             "prefix_entries": len(self.prefix),
             "cache_mb": round(pool_bytes / 2**20, 3),
             "pool_bytes": pool_bytes,
             "pool_bytes_used": bytes_used,
             "pool_bytes_per_token":
                 per_block / self.store.kv_block,
             "block_bytes": per_block,
             "cache_dtype": str(self.pools[0].dtype)}
        if len(used) > 1:
            # a class: allocated blocks (live sequences' and the prefix
            # cache's pins), a block's bytes, the window
            d["pool_blocks_live"] = used
            d["class_block_bytes"] = list(per_class)
            d["class_windows"] = list(self.windows)
        if self.state_rows:
            # what the model keeps per sequence lies one row a block in
            # the pool's state leaves (models/paged.py): rows held by
            # the allocated blocks, and the leaves' bytes (counted in
            # pool_bytes too: one pool, one allocator)
            d["state_rows_live"] = self.pool.used() * self.state_rows
            d["state_bytes"] = self.state_bytes
        if self.self_draft:
            d["spec_k"] = self.spec_k
            d["self_draft"] = True
        if self.draft is not None:
            dbytes = sum(a.size * a.dtype.itemsize
                         for a in self.dpools + (self.dscales or ()))
            d["spec_k"] = self.spec_k
            d["draft_pool_bytes"] = dbytes
            d["spec_acceptance_ema"] = round(float(self.spec_ema), 4)
        if act:
            # the paged memory claim's measurement: pool bytes
            # actually BACKING the live sequences, per sequence —
            # shared prefix blocks are paid once, so prefix-heavy
            # schedules drive this far under the contiguous plane's
            # cache_bytes_per_slot
            d["cache_bytes_per_active_seq"] = bytes_used // len(act)
        return d


class GenerationEngine:
    """Continuous-batching autoregressive generation over a
    :class:`~.registry.ModelRegistry`'s generative models.

    ``submit(model, tokens, ...)`` returns a
    ``concurrent.futures.Future`` resolving to a
    :class:`GenerationResult`.  One engine serves every generative
    model in the registry; prefill batches and decode steps never mix
    models.
    """

    def __init__(self, registry, max_active=None, max_inflight=None,
                 owner_index=None, tenant_quotas=None):
        self._registry = registry
        self._max_active = (int(max_active) if max_active is not None
                            else None)
        if max_inflight is None:
            max_inflight = int(get_env("MXNET_SERVE_MAX_INFLIGHT"))
        self._max_inflight = max(0, int(max_inflight))  # 0 = unbounded
        self._inflight = 0
        # owning replica index (None = bare engine): every ServeClosed
        # minted here carries it — see scheduler.ServeClosed
        self._owner_index = owner_index
        # per-tenant admission quotas: tenant id -> max inflight TOKENS
        # (prompt + max_tokens over the tenant's unresolved requests)
        self._tenant_quotas = dict(tenant_quotas or {})
        # tenant ledger + lifecycle flags live in racecheck containers
        # (plain dict / SimpleNamespace with the detector off): under
        # MXNET_RACE_CHECK=1 any access that skipped the _submit_lock
        # edge raises DataRaceError instead of silently going stale
        self._tenant_tokens = racecheck.shared_map(
            "serving.gen.tenant_tokens")
        self._queue = queue.Queue()
        self._waiting = {}     # model -> deque[_GenRequest]
        self._states = {}      # model -> _ModelState
        self._life = racecheck.shared_state(
            "serving.gen.lifecycle", closed=False, drain_on_stop=True)
        self._seq = 0
        self._submit_lock = make_lock("serving.gen_submit")
        self._stats_lock = make_lock("serving.gen_stats")
        # counters live in the process metrics registry (one labeled
        # series per engine); stats() reads THROUGH them —
        # decode_fetch_elems counts host elements fetched from
        # decode-step outputs (tokens in graph-sampling mode, logits in
        # host mode): per decode_step it is the per-step fetch
        # footprint the in-graph sampler shrinks from (slots, vocab)
        # to (slots,) — pinned by tests
        self._mlabels = {"engine": "gen%d" % _uid()}
        self._stats = _metrics.CounterDict(
            "serve_gen_",
            ("requests", "prefills", "prefill_seqs", "decode_steps",
             "generated_tokens", "finished", "timeouts", "cancelled",
             "errors", "shed", "cache_grows", "slot_grows",
             "decode_fetch_elems",
             # paged-plane counters (zero on contiguous engines):
             # prefix_hits counts admissions that reused shared
             # blocks, *_blocks/_tokens their sizes; cow_forks the
             # copy-on-write block duplications; prefill_chunks the
             # rows chunk dispatches worked for, prefill_row_slots the
             # rows they had (chunk_rows(slots) a dispatch: the first
             # over the second is a chunk program's live share),
             # prefill_rows_deferred the rows that waited a tick
             # because more slots were in their prompt than a
             # dispatch has rows; shed_pool the requests too large
             # for the pool.  prefix_late_tokens / _blocks: what slots
             # adopted from the prefix cache AFTER admission, in the
             # tick (beside prefix_hit_*, not inside them);
             # prefill_rows_waited the rows that waited a tick because
             # a row of the dispatch was filling the block they need
             "prefix_hits", "prefix_hit_blocks", "prefix_hit_tokens",
             "prefix_late_tokens", "prefix_late_blocks",
             "cow_forks", "prefill_chunks", "prefill_row_slots",
             "prefill_rows_deferred", "prefill_rows_waited", "shed_pool",
             # paged dispatches (decode steps and prompt chunks) for
             # which the sampler drew (a row with temperature > 0) and
             # for which it also sorted the vocabulary (a sampling row
             # whose top_k cuts it): what sample_tokens' two conds
             # took, counted from the host's copy of their inputs
             "sample_draw_dispatches", "sample_topk_dispatches",
             # step programs of the store a paged tick queued (a decode
             # step, a prompt chunk, a self-draft's two each; not a
             # speculative draft's own, spec_draft_steps), and the
             # ticks queued as ONE program for decode rows and chunk
             # rows alike (store.one_pass): tick_programs over the
             # ticks is "programs a tick"
             "tick_programs", "tick_one_pass",
             # a store whose loop runs a tick ahead of its fetches
             # (_tick_ahead): the ticks queued while the one before was
             # still unfetched (over tick_programs: 1.0 in a backlog),
             # and the decode rows computed for a request that had
             # ended by eos_id a tick before (thrown away)
             "tick_ahead", "decode_rows_wasted",
             # admissions whose per-sequence state (a model with state
             # leaves) came from the prefix cache with the blocks;
             # prompt_tokens_admitted is what prefix_hit_tokens is a
             # share of
             "state_restores", "prompt_tokens_admitted",
             # a pool with classes of block (zero for a model of one
             # class): window_blocks_released counts the references
             # sequences dropped to blocks that fell behind their
             # window, prefix_hits_cut the hits shortened or refused
             # because a window's blocks were no longer all pinned,
             # prefix_evictions the pins allocation took back (every
             # model); cache_bytes_live and cache_bytes_one_table sum,
             # a tick, the bytes the live sequences' tables hold in
             # every class and what one table for all layers would
             # hold for the same sequences
             "window_blocks_released", "prefix_hits_cut",
             "prefix_evictions", "cache_bytes_live",
             "cache_bytes_one_table",
             # speculative decoding (zero without a draft attached):
             # spec_steps counts verify dispatches (each is ONE target
             # step emitting 1..K+1 tokens), spec_proposed/spec_
             # accepted the draft tokens offered/accepted, spec_draft_
             # steps the draft micro-dispatches (catch-up + proposal)
             # an expert model's routing, summed over its steps' live
             # tokens (zero for a model without expert layers; the
             # program returns them behind the sampled tokens):
             # moe_tokens counts tokens x expert layers routed, moe_
             # local_assignments the picks that fell on experts held
             # here, moe_expert_load_max the fullest held expert's
             # count summed over steps and layers, moe_expert_steps
             # steps x expert layers, moe_experts_touched the held
             # experts that got a token, summed likewise, moe_expert_
             # streams how often the grouped product streamed an
             # expert's weights for them (over touched: 1.0 is the
             # floor, more means its row tile cuts groups)
             "moe_tokens", "moe_local_assignments",
             "moe_expert_load_max", "moe_expert_steps",
             "moe_experts_touched", "moe_expert_streams",
             # learned sparse attention (zero for a model without an
             # indexer), a layer: dsa_queries the query rows the
             # dispatches brought, dsa_index_pairs the (query, key)
             # pairs their indexer scored (every position a query
             # sees), dsa_keys_selected the pairs attention then read
             # (min(seen, index_topk) a query)
             "dsa_queries", "dsa_index_pairs", "dsa_keys_selected",
             "spec_steps", "spec_proposed", "spec_accepted",
             "spec_draft_steps", "spec_fallback_steps",
             # rows a self-drafting model's prediction module wrote
             # into its layer of the pool (prompt chunks and steps)
             "draft_rows"),
            labels=self._mlabels, help="generation engine counter")
        self._g_inflight = _metrics.gauge(
            "serve_gen_inflight", labels=self._mlabels,
            help="accepted-but-unresolved generation requests")
        self._max_active_seen = 0   # high-water mark (stats)
        # high-water cache geometry per model (survives the cache being
        # dropped when a batch drains — the bf16 bytes-per-slot bench
        # evidence reads this instead of racing a live batch)
        self._cache_hwm = {}
        # test seam: (model, seq) admission order; bounded so a
        # long-lived serving process never accumulates it
        self._admit_log = collections.deque(maxlen=4096)
        self._admit_fns = {}   # (prefill shape, cache shape) -> jitted
        # the engine thread's: for how long the device had nothing
        # queued (every dispatch, entered and returned, and every
        # fetch below tells it)
        self._starved = _profiler.StarvedClock()
        self._completer = FutureCompleter("mxt-gen-done")
        self._thread = threading.Thread(target=self._serve_loop,
                                        name="mxt-gen", daemon=True)
        self._thread.start()

    def _closed_exc(self, msg):
        return ServeClosed(msg, replica_index=self._owner_index)

    # lifecycle flags route through the shared_state container so the
    # race detector sees every access; call sites keep the field names
    @property
    def _closed(self):
        return self._life.closed

    @_closed.setter
    def _closed(self, v):
        self._life.closed = v

    @property
    def _drain_on_stop(self):
        return self._life.drain_on_stop

    @_drain_on_stop.setter
    def _drain_on_stop(self, v):
        self._life.drain_on_stop = v

    # -- client side ---------------------------------------------------
    def submit(self, model, tokens, max_tokens=16, temperature=0.0,
               top_k=0, seed=0, eos_id=None, stream=None, timeout=None,
               priority=None, tenant=None):
        """Enqueue one generation request; returns its Future.

        ``tokens`` — prompt token ids (non-empty); ``max_tokens`` —
        generation cap (>= 1; the prompt+generation total must fit
        ``MXNET_SERVE_KV_MAX``); ``temperature <= 0`` is greedy,
        otherwise seeded temperature sampling over the ``top_k``
        highest logits (``top_k=0`` = full vocab) — the token stream is
        a pure function of ``seed`` (a per-request threefry key chain,
        split once per token), identical under in-graph AND host
        sampling and invariant to batch composition; ``eos_id`` stops
        early; ``stream`` — an optional :class:`TokenStream` receiving
        tokens as they are sampled; ``timeout`` (seconds) bounds
        time-to-admission.

        ``priority`` ("latency"/"batch", default "batch") orders the
        waiting deque: latency requests admit before batch requests of
        the same model.  ``tenant`` keys the per-tenant TOKEN quota
        (constructor ``tenant_quotas``: prompt+max_tokens over the
        tenant's unresolved requests) — a tenant over budget is shed
        alone with :class:`ServeOverloaded`."""
        with self._submit_lock:
            # early gate (under the lock that orders it against
            # close()): every post-close submit raises ServeClosed,
            # never a validation error about its payload
            if self._closed:
                raise self._closed_exc("generation engine is closed")
        priority = "batch" if priority is None else str(priority)
        if priority not in TIERS:
            raise MXNetError("unknown priority tier %r (want one of %s)"
                             % (priority, "/".join(TIERS)))
        tenant = None if tenant is None else str(tenant)
        store = self._registry.gen_store(model)
        # coerce EVERY request field up front, mapping coercion errors
        # to MXNetError (the front door's 400 class — a malformed body
        # is a client error, not a 500) and, crucially, BEFORE the
        # admission bookkeeping: a ValueError after the inflight
        # increment would leak the budget slot forever (no future ever
        # carries the decrement)
        try:
            prompt = [int(t) for t in tokens]
            max_tokens = int(max_tokens)
            temperature = float(temperature)
            top_k = int(top_k)
            seed = int(seed)
            eos_id = None if eos_id is None else int(eos_id)
            timeout = None if timeout is None else float(timeout)
        except (TypeError, ValueError) as e:
            raise MXNetError("invalid generation parameter: %s" % e)
        if not prompt:
            raise MXNetError("empty prompt")
        vocab = store.spec["vocab_size"]
        if min(prompt) < 0 or max(prompt) >= vocab:
            raise MXNetError("prompt token out of range [0, %d)" % vocab)
        if max_tokens < 1:
            raise MXNetError("max_tokens must be >= 1")
        store.validate_request(len(prompt), max_tokens)
        fut = Future()
        now = time.monotonic()
        # trace context: an ingress trace active on this thread (HTTP
        # handler, replica-set placement) rides the request; a bare
        # in-process submit mints its own
        ctx = _tracing.current_context()
        owned = None
        if ctx is None:
            owned = _tracing.start_trace("serve.generate", model=model)
            ctx = (owned, owned.root_id)
        cost = len(prompt) + max_tokens   # the tenant-quota unit
        try:
            with self._submit_lock:
                if self._closed:
                    raise self._closed_exc("generation engine is closed")
                if self._max_inflight \
                        and self._inflight >= self._max_inflight:
                    self._stats.inc("shed")
                    raise ServeOverloaded(
                        "generation engine is at its inflight budget "
                        "(%d); request shed — back off and retry"
                        % self._max_inflight)
                quota = self._tenant_quotas.get(tenant) \
                    if tenant is not None else None
                if quota is not None and \
                        self._tenant_tokens.get(tenant, 0) + cost > quota:
                    # only the noisy tenant sheds; other tenants'
                    # admission is untouched
                    self._stats.inc("shed")
                    _metrics.cached_counter(
                        "serve_tenant_shed_total",
                        labels={"tenant": tenant},
                        help="requests shed by per-tenant quota").inc()
                    raise ServeOverloaded(
                        "tenant %r is over its inflight token quota "
                        "(%d); request shed — back off and retry"
                        % (tenant, quota))
                self._inflight += 1
                if tenant is not None:
                    self._tenant_tokens[tenant] = \
                        self._tenant_tokens.get(tenant, 0) + cost
                self._g_inflight.set(self._inflight)
                req = _GenRequest(
                    model, prompt, max_tokens, temperature,
                    top_k, seed, eos_id, stream, fut,
                    now + timeout if timeout is not None else None,
                    time.perf_counter(), self._seq,
                    priority=priority, tenant=tenant)
                req.trace, req.trace_parent = ctx
                self._seq += 1
                self._queue.put(req)
        except (ServeClosed, ServeOverloaded) as e:
            # export the self-minted trace with the shed/closed status
            # (outside the lock) instead of dropping it unfinished
            if owned is not None:
                owned.finish(status=type(e).__name__)
            raise
        fut.add_done_callback(
            lambda f, t=tenant, c=cost: self._note_resolved(t, c))
        if owned is not None:
            fut.add_done_callback(_tracing.finish_on_done(owned))
        self._stats.inc("requests")
        _metrics.cached_counter(
            "serve_gen_tier_requests_total", labels={"tier": priority},
            help="generation requests accepted, by priority tier").inc()
        if tenant is not None:
            _metrics.cached_counter(
                "serve_gen_tenant_requests_total",
                labels={"tenant": tenant},
                help="generation requests accepted, by tenant").inc()
        return fut

    def _note_resolved(self, tenant, cost):
        with self._submit_lock:
            self._inflight -= 1
            if tenant is not None:
                left = self._tenant_tokens.get(tenant, 0) - cost
                if left > 0:
                    self._tenant_tokens[tenant] = left
                else:
                    self._tenant_tokens.pop(tenant, None)
            self._g_inflight.set(self._inflight)

    def alive(self):
        """Liveness witness (the front door's /healthz reads it)."""
        with self._submit_lock:
            closed = self._closed
        return not closed and self._thread.is_alive()

    def stats(self):
        out = self._stats.as_dict()
        with self._stats_lock:
            out["max_active"] = self._max_active_seen
            out["cache_hwm"] = dict(self._cache_hwm)
        with self._submit_lock:
            out["inflight"] = self._inflight
            out["tenant_tokens"] = dict(self._tenant_tokens)
        out["max_inflight"] = self._max_inflight
        out["tenant_quotas"] = dict(self._tenant_quotas)
        out["models"] = {m: st.describe()
                         for m, st in dict(self._states).items()}
        # per-sequence state held beside the pool, over the models
        # (zero for models whose every leaf is by token)
        for k in ("state_rows_live", "state_bytes"):
            out[k] = sum(d.get(k, 0) for d in out["models"].values())
        # the KV memory claims as measurable evidence (the PR-12
        # weight_bytes discipline): dtype-aware cache/pool BYTES per
        # model — int8 pools count codes + scale pools together
        out["cache_state"] = {
            m: {k: d[k] for k in ("cache_dtype", "cache_mb",
                                  "pool_bytes", "pool_bytes_used",
                                  "pool_bytes_per_token", "block_bytes",
                                  "cache_bytes_per_slot",
                                  "cache_bytes_per_active_seq",
                                  "draft_pool_bytes") if k in d}
            for m, d in out["models"].items()}
        return out

    def close(self, drain=True, timeout=120.0):
        """Stop the engine.  ``drain=True`` (default) runs every
        admitted AND queued generation to completion first —
        kill-the-server-under-load keeps its promises; ``drain=False``
        fails queued and in-flight work fast with ServeClosed.
        Idempotent; joins the engine thread."""
        with self._submit_lock:
            if not self._closed:
                self._closed = True
                self._drain_on_stop = bool(drain)
                self._queue.put(_STOP)
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise MXNetError("generation engine thread failed to stop "
                             "within %.0fs" % timeout)
        self._completer.close(timeout)
        # retire this engine's labeled series from the process scrape
        _metrics.drop(self._mlabels)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- engine thread -------------------------------------------------
    def _serve_loop(self):
        self._starved.install()
        try:
            stopping = False
            ticks = 0
            while True:
                stopping = self._pump(stopping) or stopping
                if stopping and not self._drain_on_stop:
                    self._fail_all()
                    return
                if self._has_work():    # else: a draining close's last
                    ticks += 1
                    with _profiler.phase("serve_tick",
                                         labels={"tick": ticks}):
                        self._admit_ready()
                        self._decode_tick()
                if stopping and not self._has_work():
                    return
        finally:
            # same exit contract as the forward engine: the loop is
            # gone (clean close OR crash), so latch closed and fail
            # anything still queued/waiting/in-flight — an accepted
            # request is never silently dropped.  A crash additionally
            # dumps the flight ring as a postmortem naming the failure.
            exc = sys.exc_info()[1]
            if exc is not None:
                fl = _tracing.flight()
                fl.record("crash", "generation engine loop",
                          error=repr(exc))
                fl.dump(reason="generation engine loop crashed: %r"
                        % (exc,))
            with self._submit_lock:
                self._closed = True
            while True:
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    break
                if item is not _STOP:
                    self._fail_request(item, self._closed_exc(
                        "generation engine dispatch loop exited before "
                        "this request could be served"))
            self._fail_all()

    def _has_work(self):
        if any(self._waiting.values()):
            return True
        # (a tick in flight is work: its tokens are yet to deliver)
        return any(st.active() or st.flight is not None
                   for st in self._states.values())

    def _pump(self, stopping):
        """Move queued requests into the per-model FIFO waiting deques.
        Blocks only when the engine is otherwise idle (close() unblocks
        via the _STOP sentinel).  Returns True when _STOP was seen."""
        stop_seen = False
        block = not stopping and not self._has_work()
        while True:
            try:
                if block:
                    # idle that is the traffic's, not the engine's: the
                    # starved clock stands until the wait returns
                    self._starved.pause()
                    with _profiler.phase("serve_idle"):
                        item = self._queue.get()
                    self._starved.resume()
                else:
                    item = self._queue.get_nowait()
            except queue.Empty:
                break
            block = False
            if item is _STOP:
                stop_seen = True
                continue
            dq = self._waiting.setdefault(item.model,
                                          collections.deque())
            if item.priority == TIERS[0]:
                # each waiting deque is kept [latency..., batch...]:
                # a latency arrival admits before every parked batch
                # request (after older latency ones — FIFO holds
                # within a tier)
                pos = len(dq)
                for i, parked in enumerate(dq):
                    if parked.priority != TIERS[0]:
                        pos = i
                        break
                dq.insert(pos, item)
            else:
                dq.append(item)
        return stop_seen

    # -- admission (prefill) -------------------------------------------
    def _admit_ready(self):
        for model in list(self._waiting):
            dq = self._waiting.get(model)
            if dq:
                self._admit_model(model, dq)
            if not self._waiting.get(model):
                self._waiting.pop(model, None)

    def _admit_model(self, model, dq):
        try:
            store = self._registry.gen_store(model)
        except MXNetError as e:  # model removed after submit
            while dq:
                self._fail_request(dq.popleft(), e)
            return
        if getattr(store, "paged", False):
            self._admit_paged(model, dq, store)
            return
        with _profiler.phase("serve_admit") as span:
            self._admit_contiguous(model, dq, store, span)

    def _note_admitted(self, r):
        r.t_admit = time.perf_counter()
        if _metrics.phase_on():
            _H_QWAIT.observe(r.t_admit - r.t_submit)

    def _admit_contiguous(self, model, dq, store, span):
        st = self._states.get(model)
        cap = store.max_slots()
        if self._max_active is not None:
            cap = min(cap, self._max_active)
        active = len(st.active()) if st else 0
        free = cap - active
        group = []
        now = time.monotonic()
        while dq and len(group) < free:
            r = dq.popleft()
            if r.deadline is not None and now > r.deadline:
                self._fail_request(r, ServeTimeout(
                    "generation request for %r timed out after %.1f ms "
                    "in queue" % (model, (now - r.t_submit) * 1e3)),
                    kind="timeouts")
            elif r.future.set_running_or_notify_cancel():
                group.append(r)
                self._note_admitted(r)
            else:
                self._stats.inc("cancelled")
        if not group:
            return
        span.add(admitted=len(group))
        toks, lens = store.pad_prompts([r.prompt for r in group])
        try:
            # one prefill serves the whole admitted group: its span
            # lands in every member's trace
            with _tracing.activate_many(
                    [(r.trace, r.trace_parent) for r in group]):
                first_logits, pk, pv = self._dispatch_prefill(
                    store, toks, lens)
            logits = np.asarray(first_logits)
            self._starved.fetched()
        except BaseException as e:  # noqa: BLE001 — forwarded to futures
            exc = e if isinstance(e, MXNetError) \
                else MXNetError("prefill dispatch failed: %r" % (e,))
            _tracing.flight().record(
                "error", "prefill_dispatch_failed", model=model,
                error=repr(e), requests=len(group))
            for r in group:
                self._fail_request(r, exc, running=True)
            return
        self._stats.inc("prefills")
        self._stats.inc("prefill_seqs", len(group))
        # first generated token (the TTFT moment): one shared-sampler
        # call over the FULL prefill bucket's rows (pad rows sample
        # junk harmlessly — constant shapes mean the jitted sampler
        # compiles once per batch bucket, never inside steady-state
        # admissions) with each request's INITIAL key; the carry keys
        # seed the per-slot chains, so decode steps — in-graph or
        # host — continue the same deterministic stream
        from .program_store import host_sample
        bb = logits.shape[0]
        keys0 = np.zeros((bb, 2), np.uint32)
        temps0 = np.zeros((bb,), np.float32)
        tks0 = np.zeros((bb,), np.int32)
        for i, r in enumerate(group):
            keys0[i] = np.asarray(jax.random.PRNGKey(r.seed))
            temps0[i] = r.temperature
            tks0[i] = r.top_k
        first_toks, carry = host_sample(logits, keys0, temps0, tks0)
        first_toks = np.asarray(first_toks)
        carry = np.asarray(carry)
        survivors = []
        for i, r in enumerate(group):
            self._admit_log.append((model, r.seq))
            tok = int(first_toks[i])
            self._push_token(r, tok)
            if self._finished_reason(r, tok):
                self._finish(r, self._finished_reason(r, tok))
            else:
                survivors.append((i, r))
        if not survivors:
            return
        if st is None:
            st = self._states[model] = _ModelState(store)
            store.cache_state = st
        need = len(st.active()) + len(survivors)
        if need > len(st.slots):
            self._grow_slots(st, store, store.batch_bucket(need))
        Cp = int(pk.shape[3])
        if st.cache_k is None:
            st.cache_k, st.cache_v = store.new_cache(len(st.slots), Cp)
            st.C = Cp
        elif Cp > st.C:
            self._grow_cache(st, store.kv_bucket(Cp))
        # np.array COPIES: asarray of a jax array is a read-only view
        slot_keys = np.array(st.keys, np.uint32)
        for i, r in survivors:
            slot = st.free_slot()
            self._admit_row(st, pk, pv, i, slot)
            st.slots[slot] = r
            st.lengths[slot] = len(r.prompt)
            st.next_tok[slot] = r.tokens[-1]
            st.temps[slot] = r.temperature
            st.top_ks[slot] = r.top_k
            slot_keys[slot] = carry[i]
        st.keys = jnp.asarray(slot_keys)
        self._note_cache_hwm(model, st)
        with self._stats_lock:
            if len(st.active()) > self._max_active_seen:
                self._max_active_seen = len(st.active())

    def _note_cache_hwm(self, model, st):
        d = st.describe()
        with self._stats_lock:
            prev = self._cache_hwm.get(model)
            if prev is None or d.get("cache_mb", 0.0) >= \
                    prev.get("cache_mb", 0.0):
                self._cache_hwm[model] = d

    def _admit_row(self, st, pk, pv, row, slot):
        """Copy one prefilled sequence's cache rows into a decode slot
        (device-side; the batch cache is consumed and rebound)."""
        key = (tuple(pk.shape), tuple(st.cache_k.shape))
        fn = self._admit_fns.get(key)
        if fn is None:
            Cp, C = int(pk.shape[3]), int(st.cache_k.shape[3])

            def f(ck, cv, pk_, pv_, slot_, row_):
                rk = jax.lax.dynamic_slice_in_dim(pk_, row_, 1, 1)
                rv = jax.lax.dynamic_slice_in_dim(pv_, row_, 1, 1)
                pad = ((0, 0), (0, 0), (0, 0), (0, C - Cp), (0, 0))
                rk = jnp.pad(rk, pad)
                rv = jnp.pad(rv, pad)
                ck = jax.lax.dynamic_update_slice(
                    ck, rk, (0, slot_, 0, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, rv, (0, slot_, 0, 0, 0))
                return ck, cv

            from .program_store import cache_donate_argnums
            fn = jax.jit(f, donate_argnums=cache_donate_argnums((0, 1)))
            self._admit_fns[key] = fn
        self._starved.launching()
        st.cache_k, st.cache_v = fn(st.cache_k, st.cache_v, pk, pv,
                                    np.int32(slot), np.int32(row))
        self._starved.dispatched()

    def _grow_slots(self, st, store, new_bb):
        grow = new_bb - len(st.slots)
        st.slots.extend([None] * grow)
        st.lengths = np.concatenate(
            [st.lengths, np.zeros(grow, np.int32)])
        st.next_tok = np.concatenate(
            [st.next_tok, np.zeros(grow, np.int32)])
        st.temps = np.concatenate(
            [st.temps, np.zeros(grow, np.float32)])
        st.top_ks = np.concatenate(
            [st.top_ks, np.zeros(grow, np.int32)])
        st.keys = jnp.concatenate(
            [st.keys, jnp.zeros((grow, 2), jnp.uint32)])
        if st.cache_k is not None:
            pad = ((0, 0), (0, grow), (0, 0), (0, 0), (0, 0))
            self._starved.launching()
            st.cache_k = jnp.pad(st.cache_k, pad)
            st.cache_v = jnp.pad(st.cache_v, pad)
            self._starved.dispatched()
        self._stats.inc("slot_grows")

    def _grow_cache(self, st, new_c):
        pad = ((0, 0), (0, 0), (0, 0), (0, new_c - st.C), (0, 0))
        self._starved.launching()
        st.cache_k = jnp.pad(st.cache_k, pad)
        st.cache_v = jnp.pad(st.cache_v, pad)
        self._starved.dispatched()
        st.C = new_c
        self._stats.inc("cache_grows")
        self._note_cache_hwm(st.store.name, st)

    # -- paged plane ---------------------------------------------------
    def _paged_state(self, model, store):
        st = self._states.get(model)
        if st is None:
            draft, spec_k, forced = self._draft_gate(model, store)
            st = self._states[model] = _PagedModelState(
                store, draft=draft, spec_k=spec_k)
            if draft is not None:
                st.spec_forced = forced
            store.cache_state = st
            lbl = dict(self._mlabels, model=model)
            st.g_used = _metrics.gauge(
                "serve_kv_pool_blocks_used", labels=lbl,
                help="paged KV pool blocks currently allocated")
            st.g_hwm = _metrics.gauge(
                "serve_kv_pool_blocks_hwm", labels=lbl,
                help="paged KV pool allocation high-water mark")
            st.g_bytes = _metrics.gauge(
                "serve_kv_pool_bytes_used", labels=lbl,
                help="dtype-aware bytes backing the allocated paged "
                     "KV pool blocks (int8 counts codes + scales)")
        return st

    def _draft_gate(self, model, store):
        """Who drafts for ``model``, resolved ONCE at state creation,
        for both speculative planes: ``(the draft's store or None, the
        proposals a step verifies, whether it always drafts)``.  A
        store with a prediction module of its own (``self_draft``)
        drafts with it on every decode step: the configuration decides,
        no variable does, and it takes no second store.  Otherwise a
        draft attached via registry.add_draft_model + in-graph sampling
        + MXNET_SERVE_SPEC != 0: auto (default) degrades to plain
        decode when the rolling acceptance collapses, on/force always
        drafts.  Attach drafts before the model's first request — a
        draft added under traffic is picked up at the next engine (or
        the next state, once the engine restarts)."""
        if store.self_draft:
            return None, store.self_draft, True
        spec = str(get_env("MXNET_SERVE_SPEC") or "auto").lower()
        if spec in ("0", "off", "false") or store.sample_mode != "graph":
            return None, 0, False
        draft = getattr(self._registry, "draft_store",
                        lambda _m: None)(model)
        if draft is None:
            return None, 0, False
        # the window the draft's verify programs were warmed for
        # (add_draft_model's spec_k)
        spec_k = int(getattr(draft, "spec_k",
                             int(get_env("MXNET_SERVE_SPEC_K"))))
        return draft, spec_k, spec in ("1", "on", "force", "always")

    def _paged_gauges(self, st):
        st.g_used.set(st.pool.used())
        st.g_hwm.set(st.pool.hwm)
        st.g_bytes.set(st.bytes_used())

    def _paged_alloc(self, st, c=0):
        """One fresh pool block of class ``c``, evicting LRU prefix
        pins if the free list is dry.  Exhaustion raises — admission
        reservations exist to make that unreachable."""
        pool = st.pool_of[c]
        b = pool.alloc()
        while b is None and st.prefix.evict_one(c):
            self._stats.inc("prefix_evictions")
            b = pool.alloc()
        if b is None:
            raise MXNetError(
                "paged KV pool exhausted (%d blocks) — admission "
                "reservations should have prevented this"
                % pool.capacity())
        return b

    def _release_behind(self, st, i):
        """Give back slot i's blocks that lie wholly behind its window,
        in the classes that have one: a DEREFERENCE — a block the
        prefix cache pins stays until the cache evicts it.  The next
        query sits at ``lengths[i]``; the table entries go to the trash
        block 0, which the window's kernel never reads."""
        for c, w in enumerate(st.windows):
            if w is None:
                continue
            first = st.prefix.first_needed(c, int(st.lengths[i]))
            if first <= st.passed[i, c]:
                continue
            row = st.class_rows(c)[i]
            for j in range(int(st.passed[i, c]), first):
                if row[j]:
                    st.pool_of[c].deref(int(row[j]))
                    row[j] = 0
                    self._stats.inc("window_blocks_released")
            st.passed[i, c] = first

    def _register_filled(self, st, i, pins=None):
        """Pin the whole prompt blocks slot i has filled since its last
        registration (and, at the prompt's end, its partial tail) in
        the prefix cache: as they fill, so that a class with a window
        is pinned before :meth:`_release_behind` lets the block go.
        ``pins``: as :meth:`_PrefixStore.register` takes it."""
        r = st.slots[i]
        bs = st.store.kv_block
        n, pid = int(st.reg_n[i]), int(st.reg_id[i])
        done = int(st.prog[i])
        while (n + 1) * bs <= done:
            pid = st.prefix.register(
                pid, r.prompt[n * bs:(n + 1) * bs],
                [int(st.class_rows(c)[i, n])
                 for c in range(len(st.windows))], pins)
            n += 1
        if done == len(r.prompt) and n * bs < done:
            st.prefix.register(
                pid, r.prompt[n * bs:],
                [int(st.class_rows(c)[i, n])
                 for c in range(len(st.windows))], pins)
        st.reg_n[i], st.reg_id[i] = n, pid

    def _prefix_cover(self, st, r, held=(0, 0)):
        """What the prefix cache has of ``r.prompt`` behind the
        ``held`` whole blocks its asker already has (``(count, entry
        of the last)``: none for a request at admission, ``reg_n`` and
        ``reg_id`` for a slot in its prompt), and what adopting it
        leaves the slot to compute and to take from the pool: the ONE
        place of the state, tail, last-token and window rules, for
        admission and for the tick (:meth:`_adopt_late`)."""
        bs = st.store.kv_block
        plen = len(r.prompt)
        total_blocks = -(-(plen + r.max_tokens) // bs)
        blocks, tail, cut = st.prefix.match(r.prompt, held)
        if st.state_rows:
            # a state leaf holds the state after a block's LAST
            # token: a hit restores it at a block boundary, and
            # the prompt's last token reruns from there (no
            # tail, and not the block that holds that token)
            blocks = blocks[:max((plen - 1) // bs - held[0], 0)]
        if st.state_rows or st.self_draft:
            # (a self-draft: the module's row after a hit needs the
            # target's hidden state of the hit's LAST token: whole
            # blocks only, and that token reruns, below)
            tail = None
        whole = held[0] + len(blocks)
        # a partially-filled last prompt block gets pinned by the
        # prefix cache at registration, so the first decode write
        # into it MUST copy-on-write-fork — one allocation past
        # total_blocks.  A tail HIT already counts its fork target
        # in total_blocks (the borrowed block is free).
        fork_extra = int(plen % bs != 0 and tail is None)
        # shared tokens skip recomputation, but the LAST prompt
        # token always reruns: its logits seed the first sample
        covered = plen if tail is not None else whole * bs
        prog = min(covered, plen - 1)
        if st.self_draft and whole > held[0]:
            prog = min(prog, covered - 1)
        # a class: the most blocks the slot holds at once (a
        # window's keys and a dispatch's rows, and one more
        # while a shared tail block forks), and of the hit the
        # entries it adopts: all of them, or with a window
        # those a query at ``prog`` still sees
        caps, firsts = [], []
        for c, w in enumerate(st.windows):
            caps.append(total_blocks + fork_extra if w is None
                        else min(total_blocks + fork_extra,
                                 st.window_cap(c) + int(plen % bs != 0)))
            firsts.append(st.prefix.first_needed(c, prog))
        return _Cover(blocks + ([tail] if tail is not None else []),
                      whole, blocks[-1][0] if blocks else held[1], cut,
                      covered, prog,
                      total_blocks - whole + fork_extra, caps, firsts)

    def _adopt(self, st, i, cover, held=0):
        """Hold slot i to ``cover``: its tables point at the hit's
        blocks behind the ``held`` whole blocks it keeps (+1 refcount
        each, in every class from the first a query at the new
        frontier still sees; a block of its own at such a place is
        dereferenced), its frontier, its registration and its
        reservation move to what is left.  Returns the blocks adopted
        a class."""
        adopted = []
        for c, pool in enumerate(st.pool_of):
            row = st.class_rows(c)[i]
            lo = max(cover.firsts[c], held)
            for j in range(lo, held + len(cover.hit)):
                b = int(cover.hit[j - held][2][c])
                pool.ref(b)
                if row[j]:
                    pool.deref(int(row[j]))
                row[j] = b
            adopted.append(max(held + len(cover.hit) - lo, 0))
        st.prog[i] = st.lengths[i] = cover.prog
        # every block it will now never allocate goes back
        st.resv[i] = cover.needed
        st.cap[i] = cover.caps
        # what the prefix cache has of this prompt already
        st.reg_n[i] = cover.whole
        st.reg_id[i] = cover.last_id
        if st.draft is not None:
            # the draft's KV frontier starts at the shared-prefix
            # coverage like the target's (its pool was mirrored
            # when those blocks were first prefilled).
            # While the auto-mode fallback has the mirror off, the
            # adopted blocks' draft rows are unwritten: claim NO
            # coverage of them so a probe's catch-up rebuilds from the
            # prompt instead of trusting garbage
            st.dlen[i] = cover.prog if st.spec_mirror() \
                else min(int(st.dlen[i]), held * st.store.kv_block)
        # its own blocks that fell behind a window meanwhile
        self._release_behind(st, i)
        return adopted

    def _next_key(self, st, i):
        """The prefix cache's key of the block slot i's prompt fills
        next — the whole block behind the ``reg_n`` it has registered
        or adopted, or the prompt's partial tail — and None where
        admission would adopt no such block: past the prompt, and for
        a model with state leaves from the block that holds the last
        prompt token on.  Built once a block (``st.next_key``)."""
        n = int(st.reg_n[i])
        got = st.next_key[i]
        if got is None or got[0] != n:
            prompt, bs = st.slots[i].prompt, st.store.kv_block
            stop = (len(prompt) - 1) // bs if st.state_rows \
                else -(-len(prompt) // bs)
            got = st.next_key[i] = (n, None if n >= stop else (
                int(st.reg_id[i]), tuple(prompt[n * bs:(n + 1) * bs])))
        return got[1]

    def _adopt_late(self, st, i):
        """Slot i, in its prompt, finds its next block in the prefix
        cache (another slot registered it since i was admitted): it
        adopts what admission would adopt had it arrived now, behind
        the blocks it has.  Returns ``(tokens, blocks)`` it is spared
        (0, 0: a window class lost a pin the hit needs, or only the
        last prompt token is left, which reruns anyway)."""
        held = int(st.reg_n[i])
        cover = self._prefix_cover(st, st.slots[i],
                                   (held, int(st.reg_id[i])))
        tokens = cover.prog - int(st.prog[i])
        if tokens <= 0:
            return 0, 0
        self._adopt(st, i, cover, held)
        return tokens, len(cover.hit)

    def _admit_paged(self, model, dq, store):
        """Paged admission: no prefill dispatch here — a slot is
        claimed, its block table seeded from the prefix cache (shared
        blocks adopted at +1 refcount each), and the prompt's
        remaining tokens left for the tick loop to chunk through;
        what the cache learns of the prompt AFTER this, the slot
        adopts in the tick (:meth:`_paged_prefill_chunk`) and its
        reservation shrinks with it, which is what lets the next
        request in.
        FIFO, never overtaking: the head request waiting on pool
        space blocks everyone behind it."""
        st = self._paged_state(model, store)
        cap = store.max_slots()
        if self._max_active is not None:
            cap = min(cap, self._max_active)
        with _profiler.phase("serve_admit") as span:
            admitted = 0
            while dq:
                now = time.monotonic()
                r = dq[0]
                if r.deadline is not None and now > r.deadline:
                    dq.popleft()
                    self._fail_request(r, ServeTimeout(
                        "generation request for %r timed out after %.1f ms "
                        "in queue" % (model, (now - r.t_submit) * 1e3)),
                        kind="timeouts")
                    continue
                if len(st.active()) >= cap:
                    break
                cover = self._prefix_cover(st, r)
                if max(cover.caps) > st.pool.capacity():
                    # can never fit, even against an empty pool: shed
                    dq.popleft()
                    self._stats.inc("shed_pool")
                    self._stats.inc("shed")
                    self._fail_request(r, ServeOverloaded(
                        "request needs %d KV blocks, past the paged "
                        "pool's %d usable blocks — shed"
                        % (max(cover.caps), st.pool.capacity())))
                    continue
                # what eviction could free (the pool keeps the count: no
                # walk over the pins, which cost 21 ms at 2,300 of them
                # with the device idle; PERF.md section 6, PR 31) less
                # the adopted blocks that only their pin holds, which
                # stop being evictable
                fits = True
                for c, pool in enumerate(st.pool_of):
                    budget = pool.free_count() - st.reserved(c)
                    want = min(cover.needed, cover.caps[c])
                    if want > budget and want + pool.held_once(
                            e[2][c] for e in cover.hit[cover.firsts[c]:]) > \
                            budget + st.prefix.evictable(c):
                        fits = False
                if not fits:
                    break   # wait for retirements; no overtaking
                dq.popleft()
                if not r.future.set_running_or_notify_cancel():
                    self._stats.inc("cancelled")
                    continue
                slot = st.free_slot()
                if slot is None:
                    need = len(st.active()) + 1
                    self._grow_paged_slots(st, store,
                                           store.batch_bucket(need))
                    slot = st.free_slot()
                st.tables[slot] = 0
                st.slots[slot] = r
                adopted = self._adopt(st, slot, cover)
                covered = cover.covered
                self._stats.inc("prompt_tokens_admitted", len(r.prompt))
                if cover.cut:
                    self._stats.inc("prefix_hits_cut")
                if covered:
                    self._stats.inc("prefix_hits")
                    self._stats.inc("prefix_hit_blocks", len(cover.hit))
                    self._stats.inc("prefix_hit_tokens", covered)
                    if st.state_rows:
                        self._stats.inc("state_restores")
                    _metrics.cached_counter(
                        "serve_prefix_hit_total",
                        help="admissions that reused shared paged-KV "
                             "prefix blocks").inc()
                st.decoding[slot] = False
                st.chunks_done[slot] = 0
                st.next_tok[slot] = 0
                st.temps[slot] = r.temperature
                st.top_ks[slot] = r.top_k
                st.ready[slot] = -1
                st.prop[slot] = -1
                if not st.ahead:
                    # (a tick ahead: the chain starts in the program
                    # that draws the first token, _queue_tick; a fetch
                    # of st.keys here would wait for the tick in flight)
                    keys = np.array(st.keys, np.uint32)
                    keys[slot] = _seed_key(r.seed)
                    st.keys = jnp.asarray(keys)
                if st.draft is not None:
                    # the draft's PRNG chain is an independent fold of
                    # the request seed — target and draft draws never
                    # correlate.
                    # salted threefry key derived on HOST: the draft's
                    # constant hi word can never equal a target key's, so
                    # the chains stay decorrelated — the jax.random
                    # fold_in this replaces cost a threefry dispatch plus
                    # a device round-trip PER ADMISSION, charged even
                    # while the fallback regime never drafts at all
                    st.dkeys[slot] = (
                        np.uint32(0x5bec5bec),
                        np.uint32(r.seed & 0xffffffff)
                        ^ np.uint32(0x9e3779b9))
                self._admit_log.append((model, r.seq))
                self._note_admitted(r)
                # blocks_alloc: what admission reserved of the pool (the
                # blocks themselves are taken as rows are written)
                span.add(admitted=1, prefix_hit_tokens=covered,
                         prompt_tokens=len(r.prompt),
                         blocks_alloc=cover.needed,
                         state_restored=int(bool(covered
                                                 and st.state_rows)))
                if len(adopted) > 1:
                    span.add(**{"blocks_adopted_c%d" % c: n
                                for c, n in enumerate(adopted)})
                admitted += 1
            if admitted:
                self._stats.inc("prefill_seqs", admitted)
                self._note_cache_hwm(model, st)
                with self._stats_lock:
                    if len(st.active()) > self._max_active_seen:
                        self._max_active_seen = len(st.active())
            self._paged_gauges(st)

    def _grow_paged_slots(self, st, store, new_bb):
        grow = new_bb - len(st.slots)
        st.slots.extend([None] * grow)
        st.next_key.extend([None] * grow)
        st.tables = np.concatenate(
            [st.tables, np.zeros((grow, st.tb), np.int32)])
        for name in ("lengths", "prog", "chunks_done", "next_tok",
                     "top_ks", "resv", "cap", "passed", "reg_n",
                     "reg_id", "ready", "prop"):
            arr = getattr(st, name)
            setattr(st, name, np.concatenate(
                [arr, np.zeros((grow,) + arr.shape[1:], arr.dtype)]))
        st.decoding = np.concatenate(
            [st.decoding, np.zeros(grow, bool)])
        st.temps = np.concatenate(
            [st.temps, np.zeros(grow, np.float32)])
        st.keys = jnp.concatenate(
            [st.keys, jnp.zeros((grow, 2), jnp.uint32)])
        if st.ahead:
            st.pending = jnp.concatenate(
                [st.pending, np.zeros(grow, np.int32)])
        if st.draft is not None:
            st.dlen = np.concatenate(
                [st.dlen, np.zeros(grow, np.int32)])
            st.dkeys = np.concatenate(
                [np.array(st.dkeys, np.uint32),
                 np.zeros((grow, 2), np.uint32)])
        self._stats.inc("slot_grows")

    def _release_paged_slot(self, st, i):
        """Drop slot i's block references and bookkeeping (retire and
        failure paths; the prefix cache's pins keep shared blocks
        alive past this)."""
        for c, pool in enumerate(st.pool_of):
            for b in st.class_rows(c)[i]:
                if b:
                    pool.deref(int(b))
        st.tables[i, :] = 0
        st.slots[i] = None
        st.lengths[i] = 0
        st.prog[i] = 0
        st.decoding[i] = False
        st.chunks_done[i] = 0
        st.next_tok[i] = 0
        st.temps[i] = 0.0
        st.top_ks[i] = 0
        st.resv[i] = 0
        st.cap[i] = 0
        st.passed[i] = 0
        st.reg_n[i] = 0
        st.reg_id[i] = 0
        st.next_key[i] = None
        st.ready[i] = -1
        st.prop[i] = -1
        if st.draft is not None:
            st.dlen[i] = 0

    def _paged_tick(self, model, st):
        """One engine tick of the paged plane: ONE decode step over
        the slots for the generating ones, then ONE prompt chunk over
        the prefilling ones, compacted to ``chunk_rows(slots)`` rows —
        long prompts advance prefill_chunk tokens per tick INTERLEAVED
        with everyone else's decode steps, so a long prefill stops
        spiking co-running streams' inter-token latency.  With more
        slots in their prompt than the chunk has rows, the ones
        admitted first go and the rest wait a tick: by admission, not
        by slot index, which would starve the high slots while low
        ones refill.  A slot in its prompt is held to the prefix cache
        on EVERY tick, not only at admission: it computes no block the
        cache has or another row of the dispatch is computing
        (:meth:`_paged_prefill_chunk`), so a burst over one new
        document prefills it once.

        A store whose model steps over row groups (``store.one_pass``)
        takes ONE program and ONE fetch a tick, whatever rows it has,
        and its loop runs a tick AHEAD of its fetches
        (:meth:`_tick_ahead`).  Every other store (``transformer_lm``,
        host sampling, an int8 pool, a speculative draft, a self-draft)
        queues the decode program, then the chunk program, as below."""
        if st.ahead:
            busy = self._tick_ahead(model, st)
        else:
            dec = [i for i in st.active() if st.decoding[i]]
            pre = sorted((i for i in st.active() if not st.decoding[i]),
                         key=lambda i: st.slots[i].t_admit)
            busy = bool(dec or pre)
            # both programs are dispatched before either's tokens are
            # fetched: the chunk takes the pool and the key chains the
            # decode step returns (arrays not yet computed), which rows
            # are in their prompt does not hang on what the decode step
            # samples, and a slot's blocks are reserved at admission.
            # So the device goes from one program to the next while the
            # host resolves the first one's tokens, and not after it.
            resolve = []
            if dec:
                if st.self_draft:
                    resolve.append(
                        self._paged_self_draft_step(model, st, dec))
                elif st.draft is not None and self._spec_active(st):
                    self._paged_spec_step(model, st, dec)
                else:
                    resolve.append(self._paged_decode_step(model, st, dec))
            if pre:
                resolve.append(self._paged_prefill_chunk(model, st, pre))
            for finish in resolve:
                if finish is not None:
                    finish()
        if busy:
            self._paged_gauges(st)
            if len(st.windows) > 1:
                # the bytes the live sequences' tables hold a tick (a
                # shared block once a holder), and what they would hold
                # if every leaf rode the first class's table (the full
                # layers': every block kept, for every layer)
                held = [int(np.count_nonzero(st.class_rows(c)))
                        for c in range(len(st.windows))]
                self._stats.inc("cache_bytes_live", sum(
                    n * b for n, b in zip(held, st.block_bytes_of)))
                self._stats.inc("cache_bytes_one_table",
                                held[0] * sum(st.block_bytes_of))

    def _paged_write_ready(self, st, i, positions, fork=True):
        """Make slot i's tables writable at ``positions``, in every
        class: allocate entries still at 0 and copy-on-write-fork any
        covering block someone else also references (refcount > 1 — a
        shared prefix tail, or a block pinned by the prefix cache).
        Generation writes past the registered prompt MUST fork;
        recomputed prompt positions rewrite shared blocks with
        bit-identical values, so a prompt chunk passes ``fork=False``."""
        bs = st.store.kv_block
        blocks = sorted({p // bs for p in positions})
        for c, pool in enumerate(st.pool_of):
            row = st.class_rows(c)[i]
            for j in blocks:
                b = int(row[j])
                if b == 0:
                    row[j] = self._paged_alloc(st, c)
                elif fork and pool.refcount(b) > 1:
                    nb = self._paged_alloc(st, c)
                    with _profiler.phase("cow_fork", blocks=1):
                        self._starved.launching()
                        self._paged_fork(st, b, nb, c)
                        self._starved.dispatched()
                    pool.deref(b)
                    row[j] = nb
                    # the one block more the slot was let hold for it
                    st.cap[i, c] -= 1
                    self._stats.inc("cow_forks")
                else:
                    continue
                st.resv[i, c] = max(0, int(st.resv[i, c]) - 1)

    @staticmethod
    def _paged_fork(st, b, nb, c=0):
        """Duplicate physical block ``b`` into ``nb`` in every leaf of
        class ``c``."""
        # int8: codes and per-block scales fork together
        st.take(st.store.copy_block(*st.pools, b, nb, scales=st.scales,
                                    cls=c), head=0)
        if st.draft is not None:
            # the draft plane shares the block TABLES, so its pool must
            # fork the same physical block
            st.take_draft(st.draft.copy_block(*st.dpools, b, nb,
                                              scales=st.dscales), head=0)

    def _paged_work(self, st, pos, val, live, slots=None, **counts):
        """What one group of a dispatch (a decode step's rows or a
        prompt chunk's) has to do, as its span tells it, and the
        sampler's inputs for its rows: ``(temps, top_ks, work)``.
        ``work``: ``rows`` (``live``, the rows of the arrays the
        dispatch works for), ``kv_tokens``, the sum of their frontiers
        after the step, and ``q_tokens``, the query rows they bring
        (one each in a decode step); the caller's ``counts`` beside
        them, and ``sample_draw`` / ``sample_topk``: whether the
        sampler draws, and sorts, for this group (``sample_tokens``'
        two predicates, taken from the host's copy of its inputs)."""
        temps, top_ks = st.temps, st.top_ks
        if slots is not None:
            temps, top_ks = temps[slots], top_ks[slots]
        draws = ~(temps <= 0.0)
        sample_draw = bool(draws.any())
        sample_topk = sample_draw and bool((draws & (top_ks > 0) & (
            top_ks < st.store.spec["vocab_size"])).any())
        if sample_draw:
            self._stats.inc("sample_draw_dispatches")
        if sample_topk:
            self._stats.inc("sample_topk_dispatches")
        work = dict(counts, rows=len(live),
                    kv_tokens=int((pos[live] + val[live]).sum()),
                    q_tokens=int(val[live].sum()),
                    sample_draw=int(sample_draw),
                    sample_topk=int(sample_topk))
        if st.window is not None:
            # what a window layer's attention reads of it
            work["kv_tokens_window"] = int(np.minimum(
                pos[live] + val[live], st.window).sum())
        if st.index_topk is not None:
            # what the indexer scores and what attention reads of it:
            # query j of a row sees pos + j + 1 positions and keeps
            # index_topk of them at most (host arithmetic, a layer)
            j = np.arange(int(val[live].max(initial=0)))
            seen = np.where(j < val[live][:, None],
                            pos[live][:, None] + j + 1, 0)
            work["index_pairs"] = int(seen.sum())
            work["keys_selected"] = int(
                np.minimum(seen, st.index_topk).sum())
            self._stats.inc("dsa_queries", work["q_tokens"])
            self._stats.inc("dsa_index_pairs", work["index_pairs"])
            self._stats.inc("dsa_keys_selected", work["keys_selected"])
        return temps, top_ks, work

    def _paged_dispatch(self, st, tables, toks, pos, val, do, phase,
                        live, slots=None, after=None, **counts):
        """Queue one unified paged step (decode OR prompt chunk —
        ``phase`` names it for the profiler/traces) and hand back the
        FETCH of its one sampled token per ``do`` row (a call that
        blocks until the program is through and returns the host-side
        np result): the caller may queue the next program before it
        asks.  Same graph/host sampling
        split as the contiguous plane's ``_decode_and_sample``.  A
        decode step's rows are the slots; a prompt chunk's are
        compacted, row ``k`` working for slot ``slots[k]``.  The span
        carries what attention has to read (:meth:`_paged_work`, the
        caller's ``counts`` beside it).  A SELF-DRAFTING store's chunk
        (``after``: the prompt token behind each row's chunk) queues the
        prediction module's program behind the target's, unfetched; the
        one array fetched carries the module's proposals too, left in
        ``st.chunk_props`` a row."""
        temps, top_ks, work = self._paged_work(st, pos, val, live, slots,
                                               **counts)
        self._stats.inc("tick_programs", 1 if after is None else 2)
        if st.store.sample_mode == "graph":
            with _profiler.phase(phase, **work):
                self._starved.launching()
                if slots is None:
                    out = st.store.run_paged_step_sample(
                        *st.pools, tables, toks, pos, val, st.keys,
                        temps, top_ks, do, scales=st.scales)
                elif after is not None:
                    packed, mtoks, hid, st.keys = st.take(
                        st.store.run_paged_self_chunk(
                            *st.pools, tables, toks, pos, val, st.keys,
                            temps, top_ks, do, slots, after), head=3)
                    self._starved.dispatched()
                    self._starved.launching()
                    out = st.store.run_paged_draft_chunk(
                        *st.pools, tables, mtoks, pos, val, hid, packed,
                        slots=len(st.slots)) + (st.keys,)
                else:
                    out = st.store.run_paged_chunk_sample(
                        *st.pools, tables, toks, pos, val, st.keys,
                        temps, top_ks, do, slots, scales=st.scales)
                queued = self._starved.dispatched()
                toks_dev, st.keys = st.take(out)

            def fetch():
                with _profiler.phase("serve_sample"):
                    out = self._fetch_decode(toks_dev)
                    self._starved.fetched(queued)
                # a model's own counters ride behind the sampled
                # tokens (store.aux_counters names them): same array,
                # same fetch; behind them a self-draft's proposals and
                # its module's counters
                rows, aux = len(tables), st.store.aux_counters
                self._count_aux(aux, out[rows:rows + len(aux)])
                if after is not None:
                    st.chunk_props = out[rows + len(aux):][:rows]
                    self._count_aux(aux, out[2 * rows + len(aux):])
                return out[:rows]
            return fetch
        # the host's sampler moves the key chains itself: nothing of
        # this dispatch is left for later
        with _profiler.phase(phase, **work):
            self._starved.launching()
            logits_dev, = st.take(st.store.run_paged_step(
                *st.pools, tables, toks, pos, val, scales=st.scales))
            self._starved.dispatched()
        with _profiler.phase("serve_sample"):
            logits = self._fetch_decode(logits_dev)
            self._starved.fetched()
            from .program_store import host_sample, host_sample_chunk
            if slots is None:
                toks_out, carry = host_sample(logits, st.keys, temps,
                                              top_ks)
                st.keys = jnp.where(jnp.asarray(do)[:, None], carry,
                                    st.keys)
            else:
                toks_out, st.keys = host_sample_chunk(
                    logits, st.keys, temps, top_ks, do, slots)
            sampled = np.asarray(toks_out)
        return lambda: sampled

    def _count_aux(self, names, values):
        for name, n in zip(names, values):
            self._stats.inc(name, int(n))

    def _paged_failed(self, model, st, slots, e, what):
        """A dispatch (or its fetch) raised: to the futures of the
        ``slots`` it worked for, whose blocks go back."""
        exc = e if isinstance(e, MXNetError) \
            else MXNetError("%s dispatch failed: %r" % (what, e))
        _tracing.flight().record(
            "error", "%s_dispatch_failed" % what, model=model,
            error=repr(e), slots=len(slots))
        for i in slots:
            r = st.slots[i]
            self._release_paged_slot(st, i)
            self._fail_request(r, exc, running=True)

    def _decode_rows(self, st, dec):
        """Lay out a tick's decode group (inside the caller's
        ``serve_prepare``): every generating slot of ``dec`` brings its
        pending token at its frontier, the write position made ready
        first.  Slots mid-prefill (and empty slots) ride with all-zero
        tables — they reach only the trash block and their outputs are
        discarded.  Returns ``(idx, (tables, toks, pos, val, do),
        traces)``."""
        idx = np.asarray(dec, np.intp)
        # the write position this step: COW-fork or allocate first,
        # for the rows that enter a block (st.ready)
        at = st.lengths[idx] // st.store.kv_block
        for i in idx[at != st.ready[idx]]:
            self._paged_write_ready(st, int(i), [int(st.lengths[i])])
        st.ready[idx] = at
        n = len(st.slots)
        tables = np.zeros((n, st.tb), np.int32)
        toks = np.zeros((n, 1), np.int32)
        pos = np.zeros((n,), np.int32)
        val = np.ones((n,), np.int32)
        do = np.zeros((n,), bool)
        tables[idx] = st.tables[idx]
        toks[idx, 0] = st.next_tok[idx]
        pos[idx] = st.lengths[idx]
        do[idx] = True
        traces = [(st.slots[i].trace, st.slots[i].trace_parent)
                  for i in dec]
        return idx, (tables, toks, pos, val, do), traces

    def _decode_resolve(self, st, dec, idx, sampled):
        """The decode group's tokens, fetched, to their requests."""
        with _profiler.phase("serve_resolve",
                             tokens=len(dec)) as span:
            st.lengths[idx] += 1
            st.next_tok[idx] = sampled[idx]
            for i, tok in zip(dec, sampled[idx].tolist()):
                if not self._deliver(st, i, st.slots[i], tok, span) \
                        and st.window is not None:
                    self._release_behind(st, i)
        self._stats.inc("decode_steps")
        self._stats.inc("generated_tokens", len(dec))

    def _paged_decode_step(self, model, st, dec):
        """Advance every generating slot one token (serve_decode
        phase).  Returns what is left to do once the program is queued
        — fetch the tokens and resolve them — for :meth:`_paged_tick`
        to call after it has queued the prompt chunk too (None: the
        dispatch failed)."""
        with _profiler.phase("serve_prepare"):
            idx, group, traces = self._decode_rows(st, dec)
        try:
            with _tracing.activate_many(traces):
                fetch = self._paged_dispatch(
                    st, *group, "serve_decode", dec)
        except BaseException as e:  # noqa: BLE001 — to the futures
            self._paged_failed(model, st, dec, e, "decode")
            return None

        def finish():
            try:
                with _tracing.activate_many(traces):
                    sampled = fetch()
            except BaseException as e:  # noqa: BLE001
                self._paged_failed(model, st, dec, e, "decode")
                return
            self._decode_resolve(st, dec, idx, sampled)
        return finish

    def _tick_ahead(self, model, st):
        """One tick of a ONE-PASS store (``st.ahead``), whose loop
        runs ONE tick ahead of its fetches: this tick's program is
        queued BEFORE the tick before's tokens are fetched, so the
        device goes from one program to the next, and the fetch's
        return, the delivery, admission, the next preparation and its
        launch all lie under a running program and not between two.

        What makes it possible: the token a decode row feeds does not
        pass through the host (the slots' pending tokens live on the
        device, ``st.pending``: the program that samples one writes it
        to its slot's place and the next reads it there), and a tick is
        split into what needs POSITIONS and what needs token VALUES.
        At queue time, right behind the launch (:meth:`_queue_tick`),
        everything the host knows without the tokens moves: frontiers,
        prompt progress, the prefix cache's registrations (a block
        registered now is read only by programs queued later, and the
        device runs them in order: what a two-program tick leans on for
        the pool), window releases, and a slot whose prompt ends turns
        to decoding.  At fetch time, one program later
        (:meth:`_deliver_tick`), what needs values: tokens to their
        requests, EOS, retirement.

        A request that ends by ``max_tokens`` is known at queue time
        and not laid out again.  One that ends by ``eos_id`` is known
        only at its fetch: its row in the tick queued meanwhile is
        computed and thrown away (``decode_rows_wasted``; it wrote into
        the request's own block, forked as every generation write is,
        which went back to the pool when the request ended; whoever
        takes the block next writes it in a LATER program).  A freed
        slot is refilled by the admission that follows its delivery:
        it rides dead for one tick.  Returns whether the tick did
        anything."""
        dec, pre = [], []
        for i in st.active():
            r = st.slots[i]
            if not st.decoding[i]:
                pre.append(i)
            elif st.lengths[i] + 1 < len(r.prompt) + r.max_tokens:
                dec.append(i)       # (else: its last token is queued)
        pre.sort(key=lambda i: st.slots[i].t_admit)
        tick = self._queue_tick(model, st, dec, pre) if dec or pre \
            else None
        # (a launch that failed took the tick in flight with it)
        before, st.flight = st.flight, tick
        if before is not None:
            self._deliver_tick(model, st, before)
        return bool(dec or pre or before)

    def _queue_tick(self, model, st, dec, pre):
        """Lay out, launch and ADVANCE one tick of :meth:`_tick_ahead`:
        the decode group of ``dec`` and the chunk of ``pre`` laid out
        as the two-program tick lays them out, in its order
        (:meth:`_decode_rows`, then :meth:`_chunk_rows` with its late
        adoption and its one writer a block), queued as ONE program
        (``paged_tick_sample``: two row groups of one step; without
        prompt rows ``paged_step_sample``), then everything moved that
        needs no token value.  The dispatch is told by BOTH spans, each
        with its own group's counts, the one launch inside them: what
        reads a kernel's required work from ``serve_decode`` and
        ``serve_prefill`` reads what it read (neither where its group
        has no row).  Returns the tick in flight (None: the launch
        raised, and the slots of this tick and of the one in flight
        have failed)."""
        with _profiler.phase("serve_prepare") as span:
            idx, (dtables, dtoks, dpos, dval, ddo), dtraces = \
                self._decode_rows(st, dec)
            # a decode row whose token the host does not have (-1)
            # reads the device's
            host = dtoks[:, 0] >= 0
            c = self._chunk_rows(st, pre, span) if pre else None
            if c is not None:
                # a row that samples starts its request's chain
                row_keys = np.zeros((c.n, 2), np.uint32)
                for k, (_i, r, _p0, _ntok) in enumerate(c.rows):
                    if c.do[k]:
                        row_keys[k] = _seed_key(r.seed)
        tick = _Tick()
        tick.dec, tick.chunk, tick.slots = dec, c, len(dtables)
        tick.rows = [(i, st.slots[i]) for i in dec] + (
            [(i, r) for i, r, _p0, _ntok in c.rows] if c else [])
        tick.traces = dtraces + (c.traces if c else [])
        tick.pins = []
        try:
            with contextlib.ExitStack() as spans:
                if dec:
                    spans.enter_context(_tracing.activate_many(dtraces))
                    spans.enter_context(_profiler.phase(
                        "serve_decode",
                        **self._paged_work(st, dpos, dval, dec)[2]))
                if c is not None:
                    temps, top_ks, work = self._paged_work(
                        st, c.pos, c.val, np.arange(len(c.rows)), c.slots,
                        width=c.n, deferred=c.deferred)
                    spans.enter_context(_tracing.activate_many(c.traces))
                    spans.enter_context(
                        _profiler.phase("serve_prefill", **work))
                self._starved.launching()
                if c is None:
                    out = st.store.run_paged_step_sample(
                        *st.pools, dtables, dtoks, dpos, dval, st.keys,
                        st.temps, st.top_ks, ddo, st.pending, host)
                else:
                    out = st.store.run_paged_tick_sample(
                        *st.pools, c.tables, c.toks, c.pos, c.val,
                        st.keys, temps, top_ks, c.do, c.slots, dtables,
                        dtoks, dpos, dval, st.temps, st.top_ks, ddo,
                        row_keys, st.pending, host)
                tick.queued = self._starved.dispatched()
                tick.toks_dev, st.keys, st.pending = st.take(out)
        except BaseException as e:  # noqa: BLE001 — to the futures
            self._ahead_failed(model, st, tick, e)
            return None
        self._stats.inc("tick_programs")
        if st.flight is not None:
            self._stats.inc("tick_ahead")
        with _profiler.phase("serve_resolve"):
            st.lengths[idx] += 1
            if st.window is not None:
                for i in dec:
                    self._release_behind(st, i)
            if c is not None:
                self._stats.inc("tick_one_pass")
                self._chunk_advance(model, st, c, tick.pins)
        return tick

    def _deliver_tick(self, model, st, tick):
        """Fetch ``tick``'s ONE array (decode rows' tokens, chunk rows',
        the model's counters) and do what needed the values: every
        token to its request, in the two-program tick's order (decode
        rows, then the chunk rows that finished their prompt: the TTFT
        moment), and the requests that end with it retire.  A decode
        row whose request ended a tick before is thrown away.  A fetch
        that raises fails this tick's slots and those of the tick
        queued behind it."""
        try:
            with _tracing.activate_many(tick.traces), \
                    _profiler.phase("serve_sample"):
                out = self._fetch_decode(tick.toks_dev)
                self._starved.fetched(tick.queued)
        except BaseException as e:  # noqa: BLE001 — to the futures
            self._ahead_failed(model, st, tick, e)
            return
        c, n = tick.chunk, tick.slots
        self._count_aux(st.store.aux_counters,
                        out[n + (c.n if c else 0):])
        with _profiler.phase("serve_resolve") as span:
            given = 0
            for i, r in tick.rows[:len(tick.dec)]:
                if st.slots[i] is r:
                    self._deliver(st, i, r, int(out[i]), span)
                    given += 1
            firsts = 0
            for k, (i, r, _p0, _ntok) in enumerate(c.rows if c else ()):
                if c.do[k]:
                    self._deliver(st, i, r, int(out[n + k]), span)
                    firsts += 1
            span.add(tokens=given + firsts)
        if tick.dec:
            self._stats.inc("decode_steps")
            self._stats.inc("generated_tokens", given)
            self._stats.inc("decode_rows_wasted", len(tick.dec) - given)

    def _ahead_failed(self, model, st, tick, e):
        """``tick``'s launch or fetch raised: its slots fail, and those
        of the tick in flight beside it (queued on what this one was to
        return, or before it on the same device: neither's results are
        trusted).  What they registered with the prefix cache at queue
        time goes, and a slot of neither tick that has ADOPTED such a
        block since fails with them; every other slot serves on."""
        ticks = [tick]
        if st.flight is not None and st.flight is not tick:
            ticks.append(st.flight)
        st.flight = None
        lost = {i for t in ticks for i, r in t.rows if st.slots[i] is r}
        blocks = st.prefix.unpin([p for t in ticks for p in t.pins])
        for i in st.active():
            if i not in lost and any(
                    bad and np.isin(st.class_rows(c)[i], list(bad)).any()
                    for c, bad in enumerate(blocks)):
                lost.add(i)
        self._paged_failed(model, st, sorted(lost), e, "tick")

    def _spec_active(self, st):
        """The MXNET_SERVE_SPEC=auto degradation gate, checked once
        per tick: speculate while the rolling acceptance EMA holds,
        otherwise serve plain decode steps (identical token streams —
        greedy is byte-identical either way, seeded draws stay
        distribution-identical) and probe a speculative tick on an
        exponential-backoff cadence to notice recovery."""
        if st.spec_forced or st.spec_ema >= _SPEC_EMA_FLOOR:
            st.spec_probe_every = _SPEC_PROBE_EVERY
            st.spec_probe = _SPEC_PROBE_EVERY
            return True
        st.spec_probe -= 1
        if st.spec_probe <= 0:
            # this probe's verdict lands in the EMA before the next
            # tick re-checks the gate: a recovered draft re-engages
            # (and resets the cadence above), a still-hostile one
            # waits twice as long for the next probe
            st.spec_probe_every = min(2 * st.spec_probe_every,
                                      _SPEC_PROBE_MAX)
            st.spec_probe = st.spec_probe_every
            return True
        self._stats.inc("spec_fallback_steps")
        return False

    def _spec_catch_up(self, st, dec, gap):
        """Teacher-forced chunked catch-up of the draft KV frontier:
        after fallback ticks (or a mid-stream draft lag > 1) the gap
        between the target's frontier and the draft's can span many
        tokens — replaying them one micro-step each would cost a draft
        dispatch per skipped token.  The tokens are all KNOWN (already
        emitted), so feed them through the draft's logits-discarded
        prefill-mirror program in ``prefill_chunk``-sized dispatches
        (per-row ``valid`` masks ragged gaps), exactly like the prompt
        mirror, and as wide: ``chunk_rows(slots)`` rows, the slots
        that lag taken that many at a time.  Leaves every slot at gap
        0."""
        draft = st.draft
        n = draft.chunk_rows(len(st.slots))
        chunk = draft.prefill_chunk
        behind = [i for i in dec if gap[i] > 0]
        for lo in range(0, len(behind), n):
            group = behind[lo:lo + n]
            for done in range(0, max(gap[i] for i in group), chunk):
                tables = np.zeros((n, st.tb), np.int32)
                toks = np.zeros((n, chunk), np.int32)
                pos = np.zeros((n,), np.int32)
                val = np.ones((n,), np.int32)
                for k, i in enumerate(group):
                    rem = gap[i] - done
                    if rem <= 0:
                        continue
                    r = st.slots[i]
                    take = min(chunk, rem)
                    base = int(st.dlen[i]) + done
                    plen = len(r.prompt)
                    for c in range(take):
                        # a lazily-mirrored slot catches up from inside
                        # its prompt; past plen the replay is the
                        # emitted stream (idx L-1 at most — index
                        # len(tokens)-2)
                        idx = base + c
                        toks[k, c] = (r.prompt[idx] if idx < plen
                                      else r.tokens[idx - plen])
                    tables[k] = st.tables[i]
                    pos[k] = base
                    val[k] = take
                self._starved.launching()
                st.take_draft(draft.run_paged_step(
                    *st.dpools, tables, toks, pos, val,
                    scales=st.dscales))
                self._starved.dispatched()
                self._stats.inc("spec_draft_steps")
        for i in dec:
            st.dlen[i] += gap[i]
            gap[i] = 0

    def _spec_propose(self, st, dec, win):
        """Draft micro-steps of one speculative tick: first catch each
        slot's draft KV frontier up to the target's (re-feeding
        already-emitted tokens with ``do_sample`` off — the draft's
        PRNG chain must not advance on catch-up rows), then sample
        ``win[i]`` proposal tokens.  Returns ``(props, prop_q)``:
        per-slot proposal token lists and the DEVICE-resident
        ``(slots, K, vocab)`` proposal distributions the verify
        program consumes — distributions never cross to the host."""
        draft = st.draft
        n = len(st.slots)
        K = st.spec_k
        plen = {i: len(st.slots[i].prompt) for i in dec}
        gap = {i: int(st.lengths[i]) - int(st.dlen[i]) for i in dec}
        if max(gap.values()) > 1:
            # a fallback stretch left the draft far behind: chunked
            # teacher-forced catch-up instead of one micro-step per
            # skipped token (gap stays <= 1 in steady speculation —
            # exactly the full-accept bonus token)
            self._spec_catch_up(st, dec, gap)
        steps = {i: gap[i] + win[i] for i in dec}
        total = max(steps.values())
        props = {i: [] for i in dec}
        q_rows = []
        for t in range(total):
            tables = np.zeros((n, st.tb), np.int32)
            toks = np.zeros((n, 1), np.int32)
            pos = np.zeros((n,), np.int32)
            val = np.ones((n,), np.int32)
            do = np.zeros((n,), bool)
            live = []
            for i in dec:
                if t >= steps[i]:
                    continue
                r = st.slots[i]
                idx = int(st.dlen[i]) + t  # token index fed this step
                L = int(st.lengths[i])
                if idx < plen[i]:
                    # inside the prompt: a lazily-mirrored slot's
                    # catch-up (mirror skipped during fallback)
                    tok = r.prompt[idx]
                elif idx <= L:
                    # emitted history (idx == L is next_tok: the last
                    # emitted token, r.tokens[-1])
                    tok = r.tokens[idx - plen[i]]
                else:
                    tok = props[i][idx - L - 1]
                tables[i] = st.tables[i]
                toks[i, 0] = tok
                pos[i] = idx
                do[i] = t >= gap[i]
                live.append(i)
            self._starved.launching()
            t_dev, q_dev, st.dkeys = st.take_draft(
                draft.run_paged_step_sample_p(
                    *st.dpools, tables, toks, pos, val, st.dkeys,
                    st.temps, st.top_ks, do, scales=st.dscales), head=2)
            self._starved.dispatched()
            sampled = self._fetch_decode(t_dev)
            self._starved.fetched()
            q_rows.append(q_dev)
            for i in live:
                if t >= gap[i]:
                    props[i].append(int(sampled[i]))
            self._stats.inc("spec_draft_steps", len(live))
        # the sampler returned advanced keys on device; pull them
        # back (np.array: asarray of a device buffer is read-only)
        # so admissions between spec ticks stay numpy-only
        st.dkeys = np.array(st.dkeys, np.uint32)
        for i in dec:
            st.dlen[i] += steps[i]   # draft frontier = L + win[i]
        if not q_rows:
            return props, jnp.zeros(
                (n, K, draft._spec["vocab_size"]), jnp.float32)
        # device-side gather: slot i's K proposal distributions are
        # micro-steps gap[i]..gap[i]+win[i]-1 (rows past win[i] are
        # clamped garbage the verify's per-slot `valid` masks off)
        qs = jnp.stack(q_rows, axis=1)          # (n, S, vocab)
        g = np.zeros((n,), np.int32)
        for i in dec:
            g[i] = gap[i]
        idx = np.minimum(
            g[:, None] + np.arange(K, dtype=np.int32)[None, :],
            len(q_rows) - 1)
        return props, qs[np.arange(n)[:, None], idx]

    def _paged_spec_step(self, model, st, dec):
        """One speculative decode tick: the draft proposes up to
        ``spec_k`` tokens per generating slot, the target verifies all
        K+1 positions in ONE dispatch with the accept/reject rule
        in-graph, and each slot emits 1..K+1 tokens.  Rejected
        proposals roll back by table arithmetic alone — ``lengths``
        just doesn't advance past the emitted count, and pool rows
        beyond the frontier are junk the paged kernels never read
        (rewritten by later steps; no pool copies)."""
        K = st.spec_k
        win = {}
        for i in dec:
            r = st.slots[i]
            # never propose past the request's budget: the verify step
            # emits at most remaining tokens (window + bonus)
            win[i] = max(0, min(K, r.max_tokens - len(r.tokens) - 1))
            L = int(st.lengths[i])
            # the verify writes positions L..L+W: COW-fork or allocate
            # first (the draft micro-steps write the same blocks)
            self._paged_write_ready(st, i,
                                    list(range(L, L + win[i] + 1)))
        n = len(st.slots)
        try:
            with _tracing.activate_many(
                    [(st.slots[i].trace, st.slots[i].trace_parent)
                     for i in dec]):
                props, prop_q = self._spec_propose(st, dec, win)
                tables = np.zeros((n, st.tb), np.int32)
                vtoks = np.zeros((n, K + 1), np.int32)
                pos = np.zeros((n,), np.int32)
                val = np.ones((n,), np.int32)
                do = np.zeros((n,), bool)
                for i in dec:
                    tables[i] = st.tables[i]
                    vtoks[i, 0] = st.next_tok[i]
                    for j, tok in enumerate(props[i]):
                        vtoks[i, 1 + j] = tok
                    pos[i] = st.lengths[i]
                    val[i] = win[i] + 1
                    do[i] = True
                with _profiler.phase(
                        "serve_decode", rows=len(dec),
                        kv_tokens=int((pos[dec] + val[dec]).sum())):
                    self._starved.launching()
                    out_dev, ne_dev, st.keys = st.take(
                        st.store.run_paged_verify(
                            *st.pools, tables, vtoks, pos, val, prop_q,
                            st.keys, st.temps, st.top_ks, do,
                            scales=st.scales), head=2)
                    self._starved.dispatched()
                self._stats.inc("tick_programs")
                with _profiler.phase("serve_sample"):
                    out_toks = self._fetch_decode(out_dev)
                    n_emit = self._fetch_decode(ne_dev)
                    self._starved.fetched()
        except BaseException as e:  # noqa: BLE001 — to the futures
            exc = e if isinstance(e, MXNetError) \
                else MXNetError("speculative dispatch failed: %r"
                                % (e,))
            _tracing.flight().record(
                "error", "spec_dispatch_failed", model=model,
                error=repr(e), slots=len(dec))
            for i in dec:
                r = st.slots[i]
                self._release_paged_slot(st, i)
                self._fail_request(r, exc, running=True)
            return
        def survived(i):
            # draft KV is valid only while its tokens match the
            # accepted stream: clamp to the new frontier after a
            # rejection (full accept leaves a 1-token catch-up gap
            # for the bonus token)
            st.dlen[i] = min(int(st.dlen[i]), int(st.lengths[i]))

        proposed, accepted = self._spec_resolve(st, dec, out_toks, n_emit,
                                                win, survived)
        if proposed:
            st.spec_ema = (_SPEC_EMA_DECAY * st.spec_ema +
                           (1.0 - _SPEC_EMA_DECAY) *
                           (accepted / proposed))

    def _spec_resolve(self, st, dec, out_toks, n_emit, win, survived):
        """What BOTH speculative planes do with a verify's result: slot
        ``i`` of ``dec`` emits ``out_toks[i, :n_emit[i]]`` (the accepted
        proposals of the ``win[i]`` it offered, then the corrected or
        bonus token), its frontier moves by as many, a sequence that
        ends inside its window is retired there and the rest of the
        window discarded with the slot, and ``survived(i)`` is called
        for each slot that goes on.  Rejected positions roll back by
        table arithmetic alone: the frontier does not pass the emitted
        count.  Counts the step (``spec_*``); returns ``(proposed,
        accepted)``."""
        emitted = 0
        proposed = 0
        accepted = 0
        with _profiler.phase("serve_resolve") as span:
            for i in dec:
                r = st.slots[i]
                ne = int(n_emit[i])
                proposed += win[i]
                accepted += ne - 1
                if _metrics.phase_on():
                    _H_SPEC.observe(ne)
                for j in range(ne):
                    tok = int(out_toks[i, j])
                    self._push_token(r, tok)
                    st.lengths[i] += 1
                    emitted += 1
                    st.next_tok[i] = tok
                    reason = self._finished_reason(r, tok)
                    if reason:
                        # mid-window EOS: the remaining accepted tokens
                        # are discarded with the slot
                        self._release_paged_slot(st, i)
                        self._finish(r, reason)
                        span.add(finished=1)
                        break
                else:
                    survived(i)
                    if st.window is not None:
                        self._release_behind(st, i)
            span.add(tokens=emitted, proposed=proposed,
                     accepted=accepted)
        self._stats.inc("decode_steps")
        self._stats.inc("spec_steps")
        self._stats.inc("spec_proposed", proposed)
        self._stats.inc("spec_accepted", accepted)
        self._stats.inc("generated_tokens", emitted)
        _metrics.cached_counter(
            "serve_spec_proposed_total",
            help="draft tokens offered to speculative verify").inc(
                proposed)
        _metrics.cached_counter(
            "serve_spec_accept_total",
            help="draft tokens accepted by speculative verify").inc(
                accepted)
        return proposed, accepted

    def _note_draft(self, st, i, r, position, token):
        """Slot i's pending proposal: the module's token for
        ``position``, told to a stream that asks (``drafted``) whether
        or not the next step takes it."""
        st.prop[i] = token
        told = getattr(r.stream, "drafted", None)
        if told is not None:
            told(position, token)

    def _paged_self_draft_step(self, model, st, dec):
        """One SELF-DRAFTING decode tick: every generating slot's
        pending token and the proposal its slot carries go through the
        target in ONE dispatch of two positions a row, the rejection
        rule in the graph beside them, and the model's prediction
        module, in a program of its own queued behind it unfetched,
        writes its rows for the emitted tokens (one or two a row) and
        proposes for the position after them from the hidden state of
        the last one accepted.  One fetch: emitted tokens, counts, next
        proposals, both programs' counters.  The module's cache is one
        more layer of the pool's leaf on the SAME tables: nothing to
        mirror, fork or catch up.  Returns what is left once both
        programs are queued, as :meth:`_paged_decode_step` does."""
        K = st.spec_k
        bs = st.store.kv_block
        with _profiler.phase("serve_prepare"):
            n = len(st.slots)
            tables = np.zeros((n, st.tb), np.int32)
            vtoks = np.zeros((n, K + 1), np.int32)
            pos = np.zeros((n,), np.int32)
            val = np.ones((n,), np.int32)
            do = np.zeros((n,), bool)
            win = {}
            for i in dec:
                r = st.slots[i]
                L = int(st.lengths[i])
                # never propose past the request's budget, and nothing
                # where the slot carries no proposal
                left = r.max_tokens - len(r.tokens) - 1
                win[i] = w = max(0, min(K, left)) if st.prop[i] >= 0 else 0
                # the target writes L .. L + w, the module the rows of
                # the tokens emitted, L + 1 .. L + 1 + w: COW-fork or
                # allocate first, for the rows that enter a block
                top = min(L + w + 1,
                          len(r.prompt) + r.max_tokens - 1) // bs
                if top > st.ready[i]:
                    self._paged_write_ready(
                        st, i, range(L, min(L + w + 2, (top + 1) * bs)))
                    st.ready[i] = top
                tables[i] = st.tables[i]
                vtoks[i, 0] = st.next_tok[i]
                if w:
                    vtoks[i, 1] = st.prop[i]
                pos[i] = L
                val[i] = w + 1
                do[i] = True
            traces = [(st.slots[i].trace, st.slots[i].trace_parent)
                      for i in dec]
        try:
            with _tracing.activate_many(traces):
                with _profiler.phase(
                        "serve_decode", rows=len(dec),
                        kv_tokens=int((pos[dec] + val[dec]).sum()),
                        q_tokens=int(val[dec].sum()),
                        proposed=sum(win.values())):
                    self._starved.launching()
                    packed, out_dev, ne_dev, hid, st.keys = st.take(
                        st.store.run_paged_self_verify(
                            *st.pools, tables, vtoks, pos, val, st.keys,
                            st.temps, st.top_ks, do), head=4)
                    self._starved.dispatched()
                    self._starved.launching()
                    got_dev, = st.take(st.store.run_paged_draft_step(
                        *st.pools, tables, out_dev, pos, ne_dev, hid,
                        packed))
                    queued = self._starved.dispatched()
            self._stats.inc("tick_programs", 2)
        except BaseException as e:  # noqa: BLE001 — to the futures
            self._paged_failed(model, st, dec, e, "self-draft")
            return None

        def finish():
            try:
                with _tracing.activate_many(traces), \
                        _profiler.phase("serve_sample"):
                    got = self._fetch_decode(got_dev)
                    self._starved.fetched(queued)
            except BaseException as e:  # noqa: BLE001
                self._paged_failed(model, st, dec, e, "self-draft")
                return
            aux = st.store.aux_counters
            at = (K + 2) * n
            out_toks = got[:(K + 1) * n].reshape(n, K + 1)
            n_emit = got[(K + 1) * n:at]
            self._count_aux(aux, got[at:at + len(aux)])
            props = got[at + len(aux):][:n]
            self._count_aux(aux, got[at + len(aux) + n:])
            self._stats.inc("draft_rows", int(n_emit[dec].sum()))

            def survived(i):
                r = st.slots[i]
                self._note_draft(st, i, r, len(r.prompt) + len(r.tokens),
                                 int(props[i]))

            self._spec_resolve(st, dec, out_toks, n_emit, win, survived)
        return finish

    def _chunk_rows(self, st, pre, span):
        """Choose and lay out a tick's prompt chunk (inside the caller's
        ``serve_prepare``, ``span``): the first of the prefilling slots ``pre``
        (oldest admission first) advance one chunk; the rest wait a
        tick.  Before the rows are chosen every slot is held to the
        prefix cache as admission held it: one whose next block the
        cache has by now adopts it and what follows
        (:meth:`_adopt_late`), and one whose next block a row already
        chosen is filling waits for it (ONE writer a block: the older
        slot writes, :meth:`_register_filled` pins, the waiter adopts
        next tick; chosen anew every tick, so a writer that fails or
        retires blocks no one).  The dispatch is compacted:
        ``chunk_rows(slots)`` rows, row ``k`` working for the ``k``-th
        slot chosen; rows past the live ones ride as a decode step's
        dead rows do (zero table, one valid token, no sampling).
        Returns the chunk: its arrays, ``rows`` (slot, request, start,
        tokens a live row), ``live`` (their slots), the rows it has
        (``n``) and the slots that wait."""
        store = st.store
        chunk = store.prefill_chunk
        n = store.chunk_rows(len(st.slots))
        # no slot computes a block the prefix cache has, or a row
        # of this dispatch is computing: the keys of the blocks the
        # chosen rows fill, and the slots that wait on one of them
        chosen, writing = [], set()
        waited = deferred = late_tokens = late_blocks = 0
        for i in pre:
            key = self._next_key(st, i)
            if key is not None and st.prefix.holds(key):
                tokens, blocks = self._adopt_late(st, i)
                late_tokens += tokens
                late_blocks += blocks
                key = self._next_key(st, i)
            if key is not None and key in writing:
                waited += 1
            elif len(chosen) < n:
                chosen.append(i)
                writing.add(key)
            else:
                deferred += 1
        if late_blocks or waited:
            self._stats.inc("prefix_late_tokens", late_tokens)
            self._stats.inc("prefix_late_blocks", late_blocks)
            span.add(late_tokens=late_tokens, late_blocks=late_blocks,
                     waited=waited)
        rows = []
        for i in chosen:
            r = st.slots[i]
            p0 = int(st.prog[i])
            ntok = min(chunk, len(r.prompt) - p0)
            # new blocks only: recomputed shared positions rewrite
            # shared blocks with identical values (same tokens,
            # same prefix) and must not fork.  A self-draft's
            # module writes its row one position further
            self._paged_write_ready(
                st, i, range(p0, p0 + ntok + st.self_draft),
                fork=False)
            rows.append((i, r, p0, ntok))
        c = types.SimpleNamespace(
            rows=rows, n=n, deferred=deferred, waited=waited,
            tables=np.zeros((n, st.tb), np.int32),
            toks=np.zeros((n, chunk), np.int32),
            pos=np.zeros((n,), np.int32), val=np.ones((n,), np.int32),
            do=np.zeros((n,), bool), slots=np.zeros((n,), np.int32),
            # a self-drafting store's chunk: the prompt token behind
            # each row's chunk, which the module's last row takes
            more={"after": np.zeros((n,), np.int32)}
            if st.self_draft else {},
            traces=[(r.trace, r.trace_parent)
                    for _i, r, _p, _n in rows],
            live=[i for i, _r, _p, _n in rows])
        for k, (i, r, p0, ntok) in enumerate(rows):
            c.tables[k] = st.tables[i]
            c.toks[k, :ntok] = r.prompt[p0:p0 + ntok]
            c.pos[k] = p0
            c.val[k] = ntok
            c.do[k] = (p0 + ntok == len(r.prompt))
            c.slots[k] = i
            if c.more and not c.do[k]:
                c.more["after"][k] = r.prompt[p0 + ntok]
        return c

    def _chunk_advance(self, model, st, c, pins=None):
        """The chunk's rows, once their program is queued, move by
        their tokens and register the blocks they fill with the prefix
        cache (``pins``: as :meth:`_PrefixStore.register` takes it); a
        row finishing its prompt turns to decoding, its first token not
        the host's yet (``next_tok`` -1).  Nothing here needs a token's
        value."""
        self._stats.inc("prefills")
        self._stats.inc("prefill_chunks", len(c.rows))
        self._stats.inc("prefill_row_slots", c.n)
        self._stats.inc("prefill_rows_deferred", c.deferred)
        self._stats.inc("prefill_rows_waited", c.waited)
        for k, (i, _r, p0, ntok) in enumerate(c.rows):
            st.prog[i] = st.lengths[i] = p0 + ntok
            if st.draft is not None and st.spec_mirror():
                st.dlen[i] = p0 + ntok
            st.chunks_done[i] += 1
            self._register_filled(st, i, pins)
            if c.do[k]:
                if _metrics.phase_on():
                    _H_CHUNKS.observe(int(st.chunks_done[i]))
                st.decoding[i] = True
                st.next_tok[i] = -1
            if st.window is not None:
                self._release_behind(st, i)
        self._note_cache_hwm(model, st)

    def _deliver(self, st, i, r, tok, span):
        """Slot i's fetched token to its request, which retires with it
        where it ends (True)."""
        self._push_token(r, tok)
        reason = self._finished_reason(r, tok)
        if reason:
            self._release_paged_slot(st, i)
            self._finish(r, reason)
            span.add(finished=1)
        return bool(reason)

    def _chunk_resolve(self, model, st, c, sampled):
        """The chunk's rows, through the device, to their slots
        (:meth:`_chunk_advance`); rows finishing their prompt take
        their first token (the TTFT moment) from ``sampled``."""
        with _profiler.phase("serve_resolve") as span:
            self._chunk_advance(model, st, c)
            for k, (i, r, _p0, _ntok) in enumerate(c.rows):
                if not c.do[k]:
                    continue
                tok = int(sampled[k])
                span.add(tokens=1)
                if self._deliver(st, i, r, tok, span):
                    continue
                st.next_tok[i] = tok
                if st.self_draft:
                    # the module's first proposal, for the position
                    # after the sampled token's
                    self._note_draft(st, i, r, len(r.prompt) + 1,
                                     int(st.chunk_props[k]))
        if st.self_draft:
            self._stats.inc("draft_rows",
                            sum(row[3] for row in c.rows))

    def _paged_prefill_chunk(self, model, st, pre):
        """Advance the first of the prefilling slots ``pre`` one
        prompt chunk (serve_prefill phase; :meth:`_chunk_rows` chooses
        and lays them out, :meth:`_chunk_resolve` takes the result to
        the slots).  Returns what is left once the program is queued,
        as :meth:`_paged_decode_step` does."""
        with _profiler.phase("serve_prepare") as span:
            c = self._chunk_rows(st, pre, span)
        try:
            with _tracing.activate_many(c.traces):
                fetch = self._paged_dispatch(
                    st, c.tables, c.toks, c.pos, c.val, c.do,
                    "serve_prefill", np.arange(len(c.rows)), c.slots,
                    width=c.n, deferred=c.deferred, **c.more)
                if st.draft is not None and st.spec_mirror():
                    # mirror the chunk into the draft's KV plane
                    # (logits unfetched, discarded): same tables, same
                    # tokens — the draft pool ends bit-deterministic
                    # with the prompt, so prefix-shared blocks are
                    # valid draft KV for every adopter.  While the
                    # auto-mode fallback has speculation suspended the
                    # mirror is skipped (zero draft cost per tick); a
                    # probe's catch-up rebuilds the draft KV from the
                    # prompt instead
                    self._starved.launching()
                    st.take_draft(st.draft.run_paged_step(
                        *st.dpools, c.tables, c.toks, c.pos, c.val,
                        scales=st.dscales))
                    self._starved.dispatched()
        except BaseException as e:  # noqa: BLE001 — to the futures
            self._paged_failed(model, st, c.live, e, "prefill")
            return None

        def finish():
            try:
                with _tracing.activate_many(c.traces):
                    sampled = fetch()
            except BaseException as e:  # noqa: BLE001
                self._paged_failed(model, st, c.live, e, "prefill")
                return
            self._chunk_resolve(model, st, c, sampled)
        return finish

    # -- decode --------------------------------------------------------
    def _decode_tick(self):
        for model, st in list(self._states.items()):
            if getattr(st, "paged", False):
                self._paged_tick(model, st)
                continue
            act = st.active()
            if not act:
                # batch drained: drop the cache (and its memory) until
                # the next admission starts fresh
                self._states.pop(model)
                st.store.cache_state = None
                continue
            needed = int(st.lengths[act].max()) + 1
            if needed > st.C:
                self._grow_cache(st, st.store.kv_bucket(needed))
            toks = np.ascontiguousarray(st.next_tok)
            lens = np.ascontiguousarray(st.lengths)
            try:
                # one decode step advances every active slot: its
                # serve_decode/serve_sample spans land in each slot's
                # trace
                with _tracing.activate_many(
                        [(st.slots[i].trace, st.slots[i].trace_parent)
                         for i in act]):
                    sampled = self._decode_and_sample(st, toks, lens)
            except BaseException as e:  # noqa: BLE001 — to the futures
                exc = e if isinstance(e, MXNetError) \
                    else MXNetError("decode dispatch failed: %r" % (e,))
                _tracing.flight().record(
                    "error", "decode_dispatch_failed", model=model,
                    error=repr(e), slots=len(act))
                for i in act:
                    r = st.slots[i]
                    st.slots[i] = None
                    self._fail_request(r, exc, running=True)
                continue
            with _profiler.phase("serve_resolve", tokens=len(act)) as span:
                for i in act:
                    r = st.slots[i]
                    st.lengths[i] += 1
                    tok = int(sampled[i])
                    self._push_token(r, tok)
                    st.next_tok[i] = tok
                    reason = self._finished_reason(r, tok)
                    if reason:
                        st.slots[i] = None
                        st.lengths[i] = 0
                        st.next_tok[i] = 0
                        st.temps[i] = 0.0
                        st.top_ks[i] = 0
                        self._finish(r, reason)
                        span.add(finished=1)
            self._stats.inc("decode_steps")
            self._stats.inc("generated_tokens", len(act))

    def _decode_and_sample(self, st, toks, lens):
        """One decode step + one token per slot, host-side np result.

        ``graph`` mode dispatches the sampling decode program (tokens
        out; the per-slot PRNG keys are donated alongside the caches
        and rebound) and fetches ONLY the ``(slots,)`` token vector;
        ``host`` mode dispatches the logits program, fetches the whole
        ``(slots, vocab)`` matrix and runs the SAME jitted sampler on
        it.  Either way the fetch + sampling is bracketed by the
        ``serve_sample`` phase and counted in ``decode_fetch_elems``."""
        if st.store.sample_mode == "graph":
            toks_dev = self._dispatch_decode_sample(st, toks, lens)
            with _profiler.phase("serve_sample"):
                sampled = self._fetch_decode(toks_dev)
                self._starved.fetched()
                return sampled
        logits_dev = self._dispatch_decode(st, toks, lens)
        with _profiler.phase("serve_sample"):
            logits = self._fetch_decode(logits_dev)
            self._starved.fetched()
            from .program_store import host_sample
            toks_out, st.keys = host_sample(logits, st.keys, st.temps,
                                            st.top_ks)
            return np.asarray(toks_out)

    def _fetch_decode(self, arr):
        """THE host fetch of the decode loop — one np conversion whose
        element count feeds ``decode_fetch_elems`` (the zero-logits-
        fetch acceptance pin reads it; tests also spy the shapes
        here).  When it returns the device is through with the
        dispatch ``arr`` came out of and all before it: the caller
        tells the starved clock which."""
        a = np.asarray(arr)
        self._stats.inc("decode_fetch_elems", int(a.size))
        return a

    @hot_path
    def _dispatch_prefill(self, store, tokens, lengths):
        """Enqueue-only prompt-batch dispatch (serve_prefill phase);
        the logits fetch happens on the caller side."""
        with _profiler.phase("serve_prefill"):
            self._starved.launching()
            out = store.run_prefill(tokens, lengths)
            self._starved.dispatched()
        return out

    @hot_path
    def _dispatch_decode(self, st, tokens, lengths):
        """Enqueue-only logits-out decode dispatch (serve_decode phase;
        the MXNET_SERVE_SAMPLE=host hatch).  The donated caches are
        rebound to the program's outputs before anything can read the
        consumed buffers."""
        with _profiler.phase("serve_decode"):
            self._starved.launching()
            logits, st.cache_k, st.cache_v = st.store.run_decode(
                st.cache_k, st.cache_v, tokens, lengths)
            self._starved.dispatched()
        return logits

    @hot_path
    def _dispatch_decode_sample(self, st, tokens, lengths):
        """Enqueue-only sampling decode dispatch (serve_decode phase):
        tokens come out sampled in-graph; the donated caches AND the
        per-slot PRNG key state are rebound to the program's outputs."""
        with _profiler.phase("serve_decode"):
            self._starved.launching()
            toks, st.cache_k, st.cache_v, st.keys = \
                st.store.run_decode_sample(st.cache_k, st.cache_v, tokens,
                                           lengths, st.keys, st.temps,
                                           st.top_ks)
            self._starved.dispatched()
        return toks

    # -- retirement ----------------------------------------------------
    @staticmethod
    def _finished_reason(req, tok):
        if req.eos_id is not None and tok == req.eos_id:
            return "eos"
        if len(req.tokens) >= req.max_tokens:
            return "length"
        return None

    def _push_token(self, req, tok):
        now = time.perf_counter()
        if _metrics.phase_on():
            if not req.token_times:
                _H_TTFT.observe(now - req.t_submit)
            else:
                _H_ITL.observe(now - req.token_times[-1])
        req.tokens.append(tok)
        req.token_times.append(now)
        if req.stream is not None:
            req.stream.push(tok)

    def _finish(self, req, reason):
        if req.stream is not None:
            req.stream.close()
        res = GenerationResult(req.model, len(req.prompt),
                               list(req.tokens), reason, req.t_submit,
                               list(req.token_times), req.t_admit)
        self._completer.resolve(req.future, res)
        self._stats.inc("finished")

    def _fail_request(self, req, exc, kind="errors", running=False):
        if not running and not req.future.set_running_or_notify_cancel():
            self._stats.inc("cancelled")
            return
        if req.stream is not None:
            req.stream.close()
        self._completer.resolve(req.future, exc=exc)
        self._stats.inc(kind)

    def _fail_all(self):
        """close(drain=False): everything waiting or in flight fails
        fast — with the owning replica named, so the retry layer and
        the flight recorder see WHICH replica's kill lost the KV
        state."""
        exc = self._closed_exc(
            "generation engine closed before completion")
        for dq in self._waiting.values():
            while dq:
                self._fail_request(dq.popleft(), exc)
        self._waiting.clear()
        for model, st in list(self._states.items()):
            if st.flight is not None:
                # the tick in flight first: its tokens are computed.
                # (This runs in the loop's ``finally`` too: whatever a
                # delivery raises there, the slots below still fail)
                tick, st.flight = st.flight, None
                try:
                    self._deliver_tick(model, st, tick)
                except Exception:  # noqa: BLE001 — to the futures, below
                    pass
            for i in st.active():
                r = st.slots[i]
                st.slots[i] = None
                self._fail_request(r, exc, running=True)
            st.store.cache_state = None
        self._states.clear()
