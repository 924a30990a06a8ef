"""Share of the self-draft's proposals the target took:
``spec_accepted / spec_proposed``, deltas of ``GenerationEngine.stats()``
over the window (a proposal a generating row a decode step, but for a
row at its last token).  Under SEEDED weights the module agrees with the
target by chance, one in the vocabulary's rows: the cell drafts anyway
and this reads what a self-drafting step costs, not what it gains;
trained weights read 85-90 % (DeepSeek-V3's report for its one module).
None from a program that counts no proposals.  Layer: serving planes
(``decode_engine.py``, the self-drafting step)."""


def read(run):
    c = run["counters"]
    if not c.get("spec_proposed"):
        return None
    return 100.0 * c.get("spec_accepted", 0) / c["spec_proposed"]
