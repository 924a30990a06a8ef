"""Model FLOP/s utilization of the traced run: the operations the model
requires per sample (``costs/<config>.py``, from shapes, forward and
backward, nothing recomputed) times samples a second, over chips times
the bf16 peak of ``peaks.json``.  It is ``train_samples_per_s`` times a
constant, so it is a per-layer number and never an end-to-end one.
Layer: kernels (XLA's fusions, ``pallas_ops/``)."""


def read(run):
    rate = run["end_to_end"].get("train_samples_per_s")
    if rate is None or run["peaks"] is None:
        return None
    flops = run["cell"].module("costs").train_flops_per_sample(
        run["config"])
    return 100.0 * flops * rate / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
