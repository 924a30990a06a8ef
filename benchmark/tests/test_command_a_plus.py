"""The ``command-a-plus`` configuration and its cell, beside what the
parametrised modules of this directory already ask of every cell: the
costs of the published widths against a hand-worked case and against
the configuration's file and the catalog's row, the cell's rehearsal
with its window crossed and its pool filled, and the timed path broken
underneath (the window's mask dropped): ``correct`` comes out false."""
import importlib
import json
import os

import pytest

from benchmark import harness

CELL = "command-a-plus.serve-ragmix-backlog"


def _rehearse(capsys, *args):
    run = importlib.import_module("benchmark.run")
    try:
        rc = run.main(["--workload", CELL, "--rehearse"] + list(args))
    finally:
        harness.REHEARSAL = False
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.strip()]
    said = {k: v for ln in out[:-1] for k, v in ln.items()}
    return rc, out[-1], said


def test_costs_of_the_published_widths():
    """``costs/command-a-plus.py`` against the hand-worked case in its
    docstring, and the configuration's file against both: every
    published width unchanged, the cut as ``reduced`` says."""
    cell = harness.Cell(CELL)
    cfg, costs = cell.config, cell.module("costs")
    assert costs.layer_parameters(cfg) == (344461312, 50331648)
    assert costs.parameters(cfg) == cfg["parameters"] == 4733292544
    shapes = cell.module("reference").param_shapes(cfg)
    assert sum(int(__import__("numpy").prod(s))
               for s in shapes.values()) == 4733292544
    assert costs.layer_counts(cfg) == (1, 3)
    assert costs.kv_row_bytes(cfg) == 4096
    assert costs.cache_bytes_per_token(cfg) == (4096, 12288)
    assert costs.expected_picks(cfg) == 1.0
    # a decode step of one sequence at 10,000 of context: the full
    # layer reads all of it, a window layer 4,096 keys; both byte-bound
    flops, nbytes = costs.gqa_kernel_cost(cfg, 1, 10000, 1)
    assert (flops, nbytes) == (2 * 128 * 2 * 128 * 10000, 4096 * 10000)
    flops, nbytes = costs.swa_kernel_cost(cfg, 1, 4096, 1)
    assert (flops, nbytes) == (2 * 128 * 2 * 128 * 4096, 4096 * 4096)
    # a chunk of 64 queries ending at 8,000: query j sees 8,000 - 63 + j
    flops, _ = costs.gqa_kernel_cost(cfg, 1, 8000, 64)
    assert flops == 2 * 128 * 2 * 128 * 64 * (8000 - 31.5)
    assert costs.gqa_kernel_cost(cfg, 0, 0, 0) == (0.0, 0.0)
    flops, nbytes = costs.moe_kernel_cost(cfg, 512, 16)
    assert (flops, nbytes) == (2.0 * 50331648 * 512, 50331648.0 * 16 * 2)
    # 2 FLOPs a weight a token, the token's one held expert a layer on
    # average, the four shared, the head; attention by layer type
    around, expert = costs.layer_parameters(cfg)
    base = 2 * (4 * (around - 4096 + expert) + 32768 * 4096)
    assert costs.forward_flops_per_token(cfg, 0) == base
    assert costs.forward_flops_per_token(cfg, 10000) == base + \
        2 * 128 * 2 * 128 * (10000 + 3 * 4096)
    spec, pub = cfg["spec"], cfg["published"]
    for key in ("hidden_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "intermediate_size",
                "num_experts", "num_experts_per_tok",
                "num_shared_experts", "sliding_window", "vocab_size",
                "num_hidden_layers", "layer_types", "rope_theta",
                "layer_norm_eps", "logit_scale"):
        assert spec[key] == cfg[key], key
    assert (spec["hidden_size"], spec["head_dim"], spec["router_width"],
            spec["num_experts_per_tok"], spec["sliding_window"]) == (
        4096, 128, 128, 8, 4096)
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size"] == list(pub)
    assert cfg["layer_types"] == pub["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert pub["num_experts"] == spec["router_width"] == 128
    assert set(cfg["limits"]) == {"flip_share", "flip_gap_mean",
                                  "token_gap_max"}


def test_the_file_holds_the_catalogs_row():
    """Every key of the catalog row's ``config`` is in the file under
    the same name with the same value, but the four ``reduced`` names
    (skipped where the catalog is not installed)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f
               if '"command-a-plus-05-2026"' in ln][0]
    cfg = harness.Cell(CELL).config
    entry = [c for c in harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["configs"]
        if c["name"] == "command-a-plus"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_the_traffic_is_the_issues():
    """The mix's one block: eight of sixteen open with one of four
    prefixes of 8,192 tokens, own parts 192 to 5,475, 89,056 prompt
    tokens a block of which 65,536 are shared."""
    import numpy as np
    from benchmark import traffic
    mix = harness.Cell(CELL).traffic
    prompt, output, shared, _ = traffic.block(mix)
    assert (mix["driver"], mix["arrival"]) == (
        "serve-window", {"kind": "backlog", "requests_per_s": 8})
    assert shared.sum() == 8 and mix["prefixes"] == 4
    own = np.where(shared, prompt - 8192, prompt)
    assert (own.min(), own.max(), int(prompt.sum())) == (192, 5475, 89056)
    assert (output.min(), output.max()) == (84, 783)
    assert (prompt + output).max() <= mix["limit"] == 16384


@pytest.mark.parametrize("fault", ["none", "window-mask-dropped"])
def test_rehearsal_crosses_the_window_and_fails_without_its_mask(
        capsys, monkeypatch, fault):
    """``run.py --rehearse`` of the cell in this process.  Sound, it is
    ``correct`` with most compared requests past the toy window and on
    a prefix hit, blocks released behind the window, pins evicted from
    a pool that fills, and the classes holding fewer bytes than one
    table would.  With the window layers attending every key (the
    program's attention door called without its window), the requests
    past the window go wrong and ``correct`` is false."""
    from mxnet_tpu.ops import attention
    honest = attention.sdp_attention_paged
    dropped = []

    def no_window(*args, **kw):
        if kw.get("window") is not None:
            dropped.append(kw.pop("window"))
        return honest(*args, **kw)

    if fault != "none":
        monkeypatch.setattr(attention, "sdp_attention_paged", no_window)
    rc, last, said = _rehearse(capsys, "--seed", str(2**31 + 33))
    assert rc == 0 and last["correct"] is (fault == "none")
    assert bool(dropped) is (fault != "none")
    c = said["counters"]
    assert c["window_blocks_released"] > 20 and c["prefix_evictions"] > 0
    assert c["prefix_hits"] > 10 and c["shed"] == c["errors"] == 0
    assert c["cache_bytes_live"] < c["cache_bytes_one_table"]
    assert said["requests_compared_sharing_a_prefix"] > 8
    assert said["prefix_hit_pct_by_slice"][0] > 30
    if fault != "none":
        bad = {k["name"] for k in said["checks"] if not k["ok"]}
        assert bad & {"flip_share", "flip_gap_mean", "token_gap_max"}


def test_the_readers_read_what_the_program_counts(capsys):
    """A traced rehearsal runs every reader the cell lists, the three
    new ones among them; on the counters alone the window share reads
    under 100 and the readers of the device trace read nothing from a
    run without one."""
    rc, last, said = _rehearse(capsys, "--seed", "5", "--trace", "1")
    assert rc == 0 and last["correct"] is True
    ran = last["rehearsal"]["readers_ran"]
    assert {"kernel.swa_attn_time_pct", "kernel.swa_attn_roofline_pct",
            "engine.window_cache_pct", "kernel.gqa_attn_roofline_pct",
            "engine.prefix_hit_pct"} <= set(ran)
    cell = harness.Cell(CELL, rehearse=True)
    run = {"counters": said["counters"], "trace": None, "peaks": None,
           "host": {}, "config": cell.config, "cell": cell}
    read = lambda name: harness.load_module(
        cell.bench, "layer_metrics/%s.py" % name).read(run)
    assert 20 < read("engine.window_cache_pct") < 100
    assert read("kernel.swa_attn_time_pct") is None
    assert read("kernel.swa_attn_roofline_pct") is None
    # a parent program counts none of it: the readers say nothing
    run["counters"] = {k: v for k, v in said["counters"].items()
                       if not k.startswith("cache_bytes")}
    assert read("engine.window_cache_pct") is None


def test_the_window_roofline_reader_on_a_recorded_dispatch():
    """``kernel.swa_attn_roofline_pct`` on hand-made totals: 10 decode
    spans of 64 rows whose windows hold 200,000 keys in all, three
    window layers, 0.05 s in the kernel: bytes bound, 4,096 B a key a
    layer at 819 GB/s."""
    cell = harness.Cell(CELL)
    reader = harness.load_module(
        cell.bench, "layer_metrics/kernel.swa_attn_roofline_pct.py")
    peaks = cell.peaks("TPU v5 lite")
    run = {"trace": {"devices": [{"ops": {
               "%window_paged_attention.3 = bf16[64,8,16,128]": 0.04,
               "%window_paged_attention.9 = bf16[64,8,16,128]": 0.01,
               "%paged_attention.5 = bf16[64,8,16,128]": 9.0}}]},
           "peaks": peaks, "config": cell.config, "cell": cell,
           "host": {"traced_phases": {"serve_decode": {
               "spans": 10, "ns": 1, "counts": {
                   "rows": 640, "kv_tokens": 6400000,
                   "kv_tokens_window": 2000000, "q_tokens": 640}}}}}
    least = 10 * 3 * (4096 * 200000) / peaks["hbm_bytes_per_s"]
    assert reader.read(run) == pytest.approx(100.0 * least / 0.05)
    # spans of a program before the window carry no such count
    del run["host"]["traced_phases"]["serve_decode"]["counts"][
        "kv_tokens_window"]
    assert reader.read(run) is None
