"""Share of the first device's busy time spent in the lightning
indexer's calls: summed duration of the events named
``%dsa_index_scores…`` (the call's ``name=``, which becomes its HLO
instruction's name) over busy time.  None from a trace without such
events (a program or a model without an indexer).  The readers of the
indexer's, the selection's and the sparse attention's shares of their
rooflines take ``spent`` and ``least`` from here.  Layer: kernels
(``pallas_ops/dsa.py``)."""
import re

KERNEL = re.compile(r"^%dsa_index_scores")
PHASES = ("serve_decode", "serve_prefill")


def spent(run, kernel):
    """``(seconds in the events ``kernel`` matches, busy seconds)`` of
    the first device, None without a trace."""
    trace = run["trace"]
    if not trace or not trace["devices"]:
        return None
    first = trace["devices"][0]
    return (sum(s for name, s in first["ops"].items()
                if kernel.match(name)), first["busy_s"])


def share(run, kernel):
    got = spent(run, kernel)
    if not got or not got[0] or not got[1]:
        return None
    return 100.0 * got[0] / got[1]


def least(run, counts, cost):
    """The least seconds the chip could take for what the TRACED
    dispatches required of one kind of call: for each step program the
    mean dispatch of its phase (``counts`` a ``serve_decode`` /
    ``serve_prefill`` span, over the traced seconds alone:
    ``host["traced_phases"]``) through ``cost(means) -> (FLOPs,
    bytes)``, the larger of bytes over the HBM's rate and FLOPs over
    the bf16 peak, x its spans x the layers.  None if a part is
    missing (a driver without the traced totals, a program whose spans
    lack a count)."""
    peaks = run["peaks"]
    phases = run["host"].get("traced_phases")
    if not peaks or not phases:
        return None
    layers = int(run["config"]["spec"]["num_hidden_layers"])
    total = 0.0
    for phase in PHASES:
        spans = phases.get(phase)
        if not spans or not spans["spans"]:
            continue
        if not set(counts) <= set(spans["counts"]):
            return None
        mean = {k: spans["counts"][k] / spans["spans"] for k in counts}
        flops, nbytes = cost(mean)
        total += spans["spans"] * layers * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
    return total or None


def roofline(run, kernel, counts, cost):
    got = spent(run, kernel)
    if not got or not got[0]:
        return None
    need = least(run, counts, cost)
    return None if need is None else 100.0 * need / got[0]


def read(run):
    return share(run, KERNEL)
