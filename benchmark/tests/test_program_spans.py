"""What PR 24 added to the yardstick: the four per-layer readers that
read the program's own spans and names, on a hand-made run (a small
recorded trace, data/serve_trace.textproto, and totals written out by
hand) and on the rehearsal of each cell; the idle gaps of that trace
named by the program's spans once ``host_spans`` is extended with
``reduce/program_names.json``; and that file held against the program."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
xplane = harness.load_module(BENCH, "reduce/xplane.py")
NAMES = harness.load_json(harness.find_file(BENCH,
                                            "reduce/trace_names.json"))
PROGRAM = harness.load_json(harness.find_file(
    BENCH, "reduce/program_names.json"))
NEW = {"engine.tick_host_ms", "engine.prefill_share_pct",
       "kernel.paged_attn_roofline_pct", "input.h2d_stage_ms"}
AS_OF_PR46 = [
    "input.data_wait_pct", "step.device_ms", "engine.decode_batch_mean",
    "engine.decode_steps_per_s", "kernel.mfu_pct",
    "kernel.paged_attn_time_pct", "device.collective_exposed_pct",
    "engine.tick_host_ms", "engine.prefill_share_pct",
    "kernel.paged_attn_roofline_pct", "input.h2d_stage_ms",
    "kernel.mla_attn_time_pct", "kernel.mla_attn_roofline_pct",
    "kernel.moe_ffn_time_pct", "kernel.moe_ffn_roofline_pct",
    "moe.tokens_per_expert", "moe.load_max_over_mean",
    "kernel.gqa_attn_time_pct", "kernel.gqa_attn_roofline_pct",
    "engine.prefix_hit_pct", "engine.state_rows_live",
    "kernel.swa_attn_time_pct", "kernel.swa_attn_roofline_pct",
    "engine.window_cache_pct", "engine.starved_pct",
    "engine.starved_resolve_ms", "engine.starved_admit_ms",
    "engine.starved_prepare_ms", "engine.starved_dispatch_ms",
    "engine.starved_unspanned_pct", "kernel.dsa_index_time_pct",
    "kernel.dsa_index_roofline_pct", "kernel.dsa_attn_time_pct",
    "kernel.dsa_attn_roofline_pct", "kernel.dsa_select_time_pct",
    "kernel.dsa_select_roofline_pct", "dsa.selected_pct",
    "mtp.accept_pct", "mtp.draft_time_pct",
    "kernel.mla_verify_roofline_pct", "kernel.moe_verify_roofline_pct"]
US = 1e-6
# what the engine of the recorded trace would have totalled (by hand
# from the textproto's engine line; kv_tokens made up)
TOTALS = {
    "serve_tick": {"spans": 2, "ns": 2_000_000, "counts": {}},
    "serve_sample": {"spans": 3, "ns": 1_180_000, "counts": {}},
    "serve_decode": {"spans": 2, "ns": 50_000,
                     "counts": {"rows": 8, "kv_tokens": 100}},
    "serve_prefill": {"spans": 1, "ns": 30_000,
                      "counts": {"rows": 2, "kv_tokens": 25}},
}
CONFIG = {"num_layers": 2, "num_hidden": 128,
          "deploy": {"kv_dtype": "float32"}}


def _reader(name):
    return harness.load_module(BENCH, "layer_metrics/%s.py" % name)


def _reduce(names):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "serve_trace.textproto")) as f:
        profile = ProfileData.from_text_proto(f.read())
    return xplane.reduce_profile(profile, names)


@pytest.fixture()
def totals(monkeypatch):
    from mxnet_tpu import profiler
    monkeypatch.setattr(profiler, "phase_totals", lambda: TOTALS)


def test_every_new_metric_is_listed_with_its_cells():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert NEW <= set(entries)
    # appended, and every PR since has appended behind: the list as it
    # stood at PR 46 is the head of the list, in its order
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[7:11] == [
        "engine.tick_host_ms", "engine.prefill_share_pct",
        "kernel.paged_attn_roofline_pct", "input.h2d_stage_ms"]
    assert names[:len(AS_OF_PR46)] == AS_OF_PR46
    assert entries["input.h2d_stage_ms"]["workloads"] == [
        "resnet50.fit-b128", "resnet50.fit-dp4-b512"]


def test_idle_gaps_go_by_the_programs_spans_once_they_are_listed():
    plain = dict(_reduce(NAMES)["idle_gaps"])
    assert plain == {"loadgen.sleep": pytest.approx(500 * US)}
    spans = [s for group in PROGRAM["host_spans"].values() for s in group]
    named = dict(_reduce(dict(NAMES, host_spans=NAMES["host_spans"]
                              + spans))["idle_gaps"])
    # 400..600 us: its middle lies in serve_resolve, inside tick 1;
    # 1000..1300 us: in serve_admit, inside tick 2
    assert named == {"serve_admit": pytest.approx(300 * US),
                     "serve_resolve": pytest.approx(200 * US)}


def test_the_readers_on_a_run_made_by_hand(totals):
    trace = _reduce(NAMES)
    run = {"trace": trace, "config": CONFIG,
           "peaks": {"hbm_bytes_per_s": 1e9},
           "host": {"window_s": 1.0, "phase_ns": {"h2d_stage": 6_000_000,
                                                  "data_wait": 1}},
           "counters": {"steps": 4}}
    # (2000 us of ticks - 1180 us waiting on the device) / 2 ticks
    assert _reader("engine.tick_host_ms").read(run) == \
        pytest.approx(0.41)
    # modules: decode 2 x 400 us, prefill chunk 1 x 400 us
    assert _reader("engine.prefill_share_pct").read(run) == \
        pytest.approx(100 / 3)
    # (50 tokens x 2 decodes + 25 x 1 chunk) x 2 KiB = 256,000 bytes:
    # 256 us at 1 GB/s, over the kernel's 100 + 300 + 100 us
    assert _reader("kernel.paged_attn_roofline_pct").read(run) == \
        pytest.approx(51.2)
    assert _reader("input.h2d_stage_ms").read(run) == pytest.approx(1.5)


def test_the_readers_find_nothing_in_a_program_without_the_spans(
        monkeypatch):
    """The parent commit's program: no ``phase_totals``, both store
    programs ``jit_fn``.  Every reader returns None and none raises."""
    from mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "phase_totals")
    trace = _reduce(NAMES)
    first = trace["devices"][0]
    first["modules"] = {"jit_fn(1)": (3, 1200 * US)}
    run = {"trace": trace, "config": CONFIG,
           "peaks": {"hbm_bytes_per_s": 1e9},
           "host": {"window_s": 1.0}, "counters": {}}
    for name in sorted(NEW):
        assert _reader(name).read(run) is None, name
    run["trace"] = None
    for name in sorted(NEW):
        assert _reader(name).read(run) is None, name


def test_a_span_free_engine_reads_no_tick(monkeypatch):
    from mxnet_tpu import profiler
    monkeypatch.setattr(profiler, "phase_totals", lambda: {})
    assert _reader("engine.tick_host_ms").read({}) is None


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_the_rehearsal_runs_the_new_readers(cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 24), "--trace", "1",
         "--rehearse"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    want = {m["name"] for m in BENCH["per_layer"]
            if cell in m["workloads"]}
    assert want & NEW
    assert set(last["rehearsal"]["readers_ran"]) == want
    assert last["metrics"] == {}            # nothing measured on the CPU


def test_program_names_are_the_programs():
    """Every span, module and kernel of program_names.json is
    written in the program's source under that name."""
    source = ""
    for root, _, files in os.walk(os.path.join(harness.ROOT,
                                               "mxnet_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    source += fh.read()
    assert PROGRAM["about"]
    for group in PROGRAM["host_spans"].values():
        for span in group:
            assert re.search(r'phase\(\s*"%s"' % span, source) \
                or '"%s"' % span in source, span
    for span, counts in PROGRAM["span_counts"].items():
        for count in counts:
            assert re.search(r"\b%s\b" % count, source), (span, count)
    for module in PROGRAM["modules"]:
        name = module[len("jit_"):]
        assert re.search(r'def %s\(|"%s"' % (name, name), source), module
    kernels = set(re.findall(r'name="(\w+)"', source)) | {
        k.strip("_").replace("_kernel", "")
        for k in re.findall(r"def (_\w+_kernel)\(", source)}
    assert set(PROGRAM["kernels"]) <= kernels
