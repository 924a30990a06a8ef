"""The one request generator of the ``serve`` driver; a traffic mix is
a data file of its parameters.

Work is dealt in BLOCKS, and the mix fixes all of a block but the token
ids: every seed and every block holds the same requests in the same
order, due at the same times.  A block is ``block`` requests whose
prompt lengths are the stratified quantiles of the mix's log-normal
(``median``, ``sigma``, clipped to ``[lo, hi]``), whose output lengths
are the same quantiles of theirs, of which exactly ``share`` open with
one of ``prefixes`` shared system prompts of ``prefix_len`` tokens (taken
in turn) put in front of their own prompt, and whose arrival gaps are
the stratified quantiles of the unit exponential (mean exactly 1) — each
of these in an order shuffled once, by the mix's ``shape_seed``.  The
run's seed draws the token ids (and the weights, elsewhere) and nothing
else: measured on the chip, two runs of one seed agreed to 0.2 % where
six seeds that each entered the cycle at another offset spread by 4 %
(PERF.md, PR 23), so how much work a window holds, and when, is not the
seed's to change.  Prompt + output never passes ``limit``.

``arrival`` is ``{"kind": "backlog", "requests_per_s": r}`` — r x the
horizon requests, all due at 0 — or ``{"kind": "poisson", "rate": r}``:
the blocks' gaps at ``r`` requests a second, open loop.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


class Request:
    __slots__ = ("due", "prompt", "max_tokens", "shared")

    def __init__(self, due, prompt, max_tokens, shared):
        self.due, self.prompt = float(due), prompt
        self.max_tokens, self.shared = int(max_tokens), bool(shared)


def _quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def _lengths(spec, n):
    normal = statistics.NormalDist()
    draw = [spec["median"] * math.exp(spec["sigma"] * normal.inv_cdf(q))
            for q in _quantiles(n)]
    return np.clip(np.rint(draw), spec["lo"], spec["hi"]).astype(np.int64)


def block(mix):
    """The mix's one block: ``(prompt_len, output_len, shared)`` rows
    and unit-rate arrival gaps — a function of the mix alone."""
    n = int(mix["block"])
    rng = np.random.default_rng(int(mix["shape_seed"]))
    prompt = rng.permutation(_lengths(mix["prompt"], n))
    output = rng.permutation(_lengths(mix["output"], n))
    shared = np.zeros(n, bool)
    shared[:int(round(n * mix["share"]))] = True
    rng.shuffle(shared)
    prompt = np.where(shared,
                      np.minimum(prompt + int(mix["prefix_len"]),
                                 mix["prompt"]["hi"]), prompt)
    output = np.minimum(output, int(mix["limit"]) - prompt)
    if output.min() < 1:
        raise ValueError("mix leaves a request no room for output")
    gaps = rng.permutation([-math.log(1.0 - q) for q in _quantiles(n)])
    return prompt, output, shared, gaps * (n / gaps.sum())


def make_requests(mix, arrival, vocab, seed, horizon_s):
    """The requests of one run, sorted by due time: a pure function of
    ``(mix, arrival, vocab, seed, horizon_s)``.  A Poisson schedule
    runs a block past the horizon so that the window never runs dry."""
    size = int(mix["block"])
    if arrival["kind"] == "backlog":
        blocks = math.ceil(arrival["requests_per_s"] * horizon_s / size)
    elif arrival["kind"] == "poisson":
        blocks = math.ceil(arrival["rate"] * horizon_s / size) + 1
    else:
        raise ValueError("unknown arrival kind %r" % arrival["kind"])
    prompt, output, shared, gaps = block(mix)
    rng = np.random.default_rng(int(seed))
    plen = int(mix["prefix_len"])
    prefixes = rng.integers(0, vocab, (int(mix["prefixes"]), plen))
    out, clock, turn = [], 0.0, 0
    for _ in range(blocks):
        for i in range(size):
            if arrival["kind"] == "poisson":
                clock += gaps[i] / arrival["rate"]
            own = rng.integers(0, vocab, int(prompt[i]))
            if shared[i]:
                own[:plen] = prefixes[turn % len(prefixes)]
                turn += 1
            out.append(Request(clock, own.tolist(), output[i],
                               shared[i]))
    return out
