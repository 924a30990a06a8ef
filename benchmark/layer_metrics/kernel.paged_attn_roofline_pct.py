"""The paged-attention kernel's share of its roofline, bound by HBM
bytes: the least time the chip could take to read the K/V the traced
dispatches had to read, over the kernel's seconds in the trace.

Required bytes: for each step program, (mean ``kv_tokens`` a span of
its phase, from ``mxnet_tpu.profiler.phase_totals()``: the sum over the
dispatch's live rows of the row's frontier after the step) x (that
module's executions in the trace) x (KV bytes a token: K and V, every
layer, ``num_hidden`` wide, in ``deploy.kv_dtype``; 256 KiB for
``lm2048``).  The mean is the process's lifetime mean, the count the
trace's: the ramp's dispatches (40 of some 290 at ``--seconds 30``)
read shorter contexts than the window's, so the share reads low by a
few parts in a hundred (9.8-9.9 at 30 s, 8.7 at 10 s; PERF.md section
6, PR 24) until the serve driver hands readers the window's totals.  K/V
of live context counted once, nothing for dead rows, queries and
outputs left out, so the share can only under-read; a reading over 100
means the count is wrong.  The kernel is found as
``kernel.paged_attn_time_pct`` finds it (its regex, copied: the first
two operands are the s32 block tables and positions) until a
``benchmark`` PR points both at the kernel's name.  None if any part is
missing.  Layer: kernels (``pallas_ops/paged_attention.py``)."""
import re

KERNEL = re.compile(
    r"^%\S+ = f32\[[\d,]+\]\S* custom-call\(s32\[[\d,]+\]\S* %\S+, "
    r"s32\[[\d,]+\]\S* %\S+,.*custom_call_target=\"tpu_custom_call\"")
PROGRAMS = {"serve_decode": "jit_paged_decode",
            "serve_prefill": "jit_paged_prefill_chunk"}
BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


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
    token_bytes = 2 * int(cfg["num_layers"]) * int(cfg["num_hidden"]) \
        * BYTES[cfg["deploy"]["kv_dtype"]]
    required = 0.0
    for phase, module in PROGRAMS.items():
        spans = totals.get(phase)
        if not spans or not spans["spans"] \
                or "kv_tokens" not in spans["counts"]:
            return None
        ran = sum(count for name, (count, _) in first["modules"].items()
                  if name.startswith(module))
        required += spans["counts"]["kv_tokens"] / spans["spans"] \
            * ran * token_bytes
    spent = sum(s for name, s in first["ops"].items()
                if KERNEL.search(name))
    if not required or not spent:
        return None
    return 100.0 * required / peaks["hbm_bytes_per_s"] / spent
