"""Driver ``serve-mtp``: ``drivers/serve-hybrid.py`` for a model that
DRAFTS for itself (a multi-token-prediction module verified in every
decode step), every request due at 0.  That driver's ``run`` is imported
whole, as ``drivers/serve-window.py`` imports it: the ramp, the window,
the slices, the traced totals, the seeded weights and the sample of
finished requests are its.  What differs:

* the limits of ``correct`` are the configuration's (``limits`` in its
  file, beside the readings each was set from): serve-hybrid's three
  and ``draft_flip_share``;
* ``correct`` holds the DRAFT to the reference too.  The engine tells a
  request's stream every proposal its module makes and the position it
  is for (``drafted``), taken or not; the reference runs its own module
  teacher-forced over the served stream IN THE SAME PASS that gives the
  served tokens' gaps (``served_both``), and the share of proposals
  that are not the reference module's best token stays under
  ``draft_flip_share``.  Without it a broken module would only lower an
  acceptance that seeded weights put near zero anyway, and nothing
  would notice;
* the engine's ``spec_*`` counters and the module's rows are read with
  the others.

HOW the fourth check gets in is a patch, and is said here so that a
``benchmark`` PR can replace it with a seam: ``serve-hybrid``'s ``run``
takes no extra check and no other reference pass, so this driver
executes a PRIVATE copy of that file (``_hybrid``), replaces its
``LIMITS``, ``COUNTERS``, ``Stamps`` and ``compare``, swaps the shared
``serve-arch.reference_gaps`` for the length of the run (restored in a
``finally``), and carries a request's proposals to ``compare`` on a
``list`` subclass whose slices keep them (``_Served``).  A ``compare``
that took a list of further checks and a ``run`` that took the
reference's pass as an argument would make all four unnecessary; both
files are the accepted benchmark's and not this PR's to edit.
"""
from __future__ import annotations

import importlib.util
import statistics

import numpy as np

from benchmark import harness

# deltas of GenerationEngine.stats() over the window, beside
# serve-hybrid's
DRAFT_COUNTERS = ("spec_steps", "spec_proposed", "spec_accepted",
                  "draft_rows")
DRAFT_LIMIT = "draft_flip_share"


def _hybrid(cell):
    """``drivers/serve-hybrid.py`` as a module of this cell's own: its
    ``LIMITS``, ``COUNTERS``, ``Stamps`` and ``compare`` are replaced
    below, and the copy other cells import stays what it is."""
    path = harness.find_file(cell.bench, "drivers/serve-hybrid.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_drivers_serve_hybrid_of_serve_mtp", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Served(list):
    """A request's served tokens; a slice of it keeps the request's
    proposals, so they reach ``compare`` with the sample."""

    drafts = ()

    def __getitem__(self, at):
        got = list.__getitem__(self, at)
        if isinstance(at, slice):
            got = _Served(got)
            got.drafts = self.drafts
        return got


def _stamps(base):
    class DraftStamps(base):
        """``Stamps`` that also keeps what the engine says it drafted:
        ``(position, token)`` of every proposal."""

        __slots__ = ("drafts",)

        def __init__(self):
            base.__init__(self)
            self.drafts = []
            self.tokens = _Served()
            self.tokens.drafts = self.drafts

        def drafted(self, position, token):
            self.drafts.append((int(position), int(token)))

    return DraftStamps


def _proposed(prompt, served, width):
    """``proposed[j]``: the token drafted for the position after
    ``served[j]``'s (-1: none, or past the compared tokens)."""
    out = np.full(width, -1, np.int32)
    for position, token in getattr(served, "drafts", ()):
        j = position - len(prompt) - 1
        if 0 <= j < len(served):
            out[j] = token
    return out


def reference_both(cell, arch, seed, sample, found):
    """``serve-arch.reference_gaps`` through the reference's
    ``served_both``: one pass a request gives the served tokens' gaps
    (returned) and the proposals' (left in ``found``: ``(gap, best,
    proposed)`` a request, over the proposals made)."""
    import jax
    import jax.numpy as jnp
    ref = cell.module("reference")
    cfg, mix = cell.config, cell.traffic
    width = int(mix["limit"])
    most = int(mix["output"]["hi"])
    # a deployment that does not load the module has none to hold
    drafting = bool((cfg.get("deploy") or {}).get("self_draft"))
    with jax.default_matmul_precision("highest"):
        params = arch._draw(ref, cfg, seed)
        fn = jax.jit(lambda p, t, f, s, d: ref.served_both(
            p, t, f, s, d if drafting else None, cfg))
        out = []
        for prompt, served in sample:
            # the LAST served token too: the proposal behind it is the
            # module's row at its position
            seq = np.zeros(width, np.int32)
            n = min(len(prompt) + len(served), width)
            seq[:n] = (list(prompt) + list(served))[:n]
            pad = np.zeros(most, np.int32)
            pad[:len(served)] = served
            prop = _proposed(prompt, served, most)
            if n < len(prompt) + len(served):
                prop[len(served) - 1] = -1
            got = fn(params, jnp.asarray(seq), np.int32(len(prompt) - 1),
                     jnp.asarray(pad), jnp.asarray(prop))
            out.append((np.asarray(got[0])[:len(served)],
                        np.asarray(got[1])[:len(served)]))
            if drafting:
                made = prop >= 0
                found.append((np.asarray(got[2])[made],
                              np.asarray(got[3])[made], prop[made]))
    return out


def draft_check(found, limit):
    """The check of the proposals: the share that are not the reference
    module's best token."""
    made = sum(len(p) for _, _, p in found)
    if not made:
        return harness.check("proposals_compared", 0, 0, ok=False)
    flips = [float(g) for gap, best, prop in found
             for g, b, p in zip(gap, best, prop) if b != p]
    harness.say(proposals_compared=made, draft_flips=len(flips),
                draft_flip_gap_mean=statistics.fmean(flips)
                if flips else 0.0)
    return harness.check(DRAFT_LIMIT, len(flips) / made, limit)


def run(cell, devices, args, t0):
    hybrid = _hybrid(cell)
    limits = cell.config.get("limits")
    if not limits or set(limits) != set(hybrid.LIMITS) | {DRAFT_LIMIT}:
        raise harness.BenchError(
            "driver serve-mtp takes %s from the configuration's 'limits'"
            % sorted(set(hybrid.LIMITS) | {DRAFT_LIMIT}))
    hybrid.LIMITS = {k: float(limits[k]) for k in hybrid.LIMITS}
    hybrid.COUNTERS = hybrid.COUNTERS + DRAFT_COUNTERS
    hybrid.Stamps = _stamps(hybrid.Stamps)
    arch = harness.load_module(cell.bench, "drivers/serve-arch.py")
    honest_gaps, honest_compare = arch.reference_gaps, hybrid.compare
    found = []
    hybrid.compare = lambda sample, gaps: honest_compare(sample, gaps) + [
        draft_check(found, float(limits[DRAFT_LIMIT]))]
    # serve-hybrid's run asks the driver it loads for the reference's
    # pass; for this run that is the one pass over both
    arch.reference_gaps = lambda cell, seed, sample: reference_both(
        cell, arch, seed, sample, found)
    try:
        return hybrid.run(cell, devices, args, t0)
    finally:
        arch.reference_gaps = honest_gaps
