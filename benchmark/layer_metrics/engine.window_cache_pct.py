"""What the classes of cache block save: the bytes the live sequences'
tables hold in every class (a block two sequences share once for each)
over what ONE table for all layers would hold for the same sequences
(the full class's entries x a block's bytes over all classes), both
summed a tick over the window: ``cache_bytes_live /
cache_bytes_one_table``, deltas of ``GenerationEngine.stats()``.  100
while no sequence has passed its window; with every sequence far past
it the share tends to (full layers + window layers x window / context)
over all layers.  None from a program or a model with one class of
block.  Layer: serving planes (the cache manager,
``decode_engine.py``)."""


def read(run):
    c = run["counters"]
    if not c.get("cache_bytes_one_table"):
        return None
    return 100.0 * c["cache_bytes_live"] / c["cache_bytes_one_table"]
