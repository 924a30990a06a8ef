"""State rows held beside the paged cache: ``GenerationEngine.stats()
["state_rows_live"]`` (allocated blocks x the model's state layers: one
row a block a layer, whatever the block's tokens), the mean of the
driver's readings every five seconds of the window.  None from a
program or a model without state leaves.  Layer: serving planes (the
cache manager, ``decode_engine.py``)."""


def read(run):
    return run["counters"].get("state_rows_live") or None
