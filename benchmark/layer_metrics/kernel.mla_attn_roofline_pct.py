"""The latent-attention kernel's share of its roofline: the least time
the chip could take for what the traced dispatches required — the
larger of required bytes over the HBM's rate and required FLOPs over
the bf16 peak — over the kernel's seconds in the trace.

Required, by ``costs/<config>.py``'s ``mla_kernel_cost``: for each step
program, the mean dispatch of its phase (``rows``, ``kv_tokens`` and
``q_tokens`` a ``serve_decode``/``serve_prefill`` span, from
``mxnet_tpu.profiler.phase_totals()``: the process's lifetime means,
the ramp's dispatches among them) x that module's executions in the
trace x the layers.  A decode step is bound by both at once (242
FLOP/B against the chip's ridge of 240), a chunk by FLOPs.  Live
context counted once, nothing for dead rows or the stored row's
padding, so the share can only under-read; a reading over 100 means
the count is wrong.  None if any part is missing (a program without
the kernel or without ``q_tokens`` on its spans).  Layer: kernels
(``pallas_ops/mla_attention.py``)."""
import re

KERNEL = re.compile(r"^%mla_paged_attention")
PROGRAMS = {"serve_decode": "jit_paged_decode",
            "serve_prefill": "jit_paged_prefill_chunk"}


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not trace["devices"] or not peaks:
        return None
    try:
        from mxnet_tpu import profiler
    except ImportError:
        return None
    totals = getattr(profiler, "phase_totals", lambda: {})()
    first = trace["devices"][0]
    cfg = run["config"]
    costs = run["cell"].module("costs")
    layers = int(cfg["spec"]["num_hidden_layers"])
    least = 0.0
    for phase, module in PROGRAMS.items():
        spans = totals.get(phase)
        if not spans or not spans["spans"] or not \
                {"rows", "kv_tokens", "q_tokens"} <= set(spans["counts"]):
            return None
        ran = sum(count for name, (count, _) in first["modules"].items()
                  if name.startswith(module))
        mean = {k: spans["counts"][k] / spans["spans"]
                for k in ("rows", "kv_tokens", "q_tokens")}
        flops, nbytes = costs.mla_kernel_cost(
            cfg, mean["rows"], mean["kv_tokens"], mean["q_tokens"])
        least += ran * layers * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    spent = sum(s for name, s in first["ops"].items()
                if KERNEL.match(name))
    if not least or not spent:
        return None
    return 100.0 * least / spent
