"""The ``openpangu-ultra-moe`` configuration and its cell, beside what
the parametrised modules of this directory already ask of every cell:
the costs of the published widths against a hand-worked case and
against the configuration's file and the catalog's row, the mix, the
readers on a made-up trace, the cell's rehearsal (drafting in every
step, proposals held to the reference's module), the control and two
broken paths coming out not ``correct``, and the four programs of a
self-drafting store compiled for a described ``v5e`` at the cell's real
size."""
import importlib
import json
import os
import re

import pytest

from benchmark import harness

CELL = "openpangu-ultra-moe.serve-reason-backlog"
LIMIT_GB = 15.0


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


# ---------------------------------------------------------------------------
# the files (fast: tier-1 runs these through
# tests/test_benchmark_contract.py)
# ---------------------------------------------------------------------------
def test_openpangu_costs_of_the_published_widths():
    """``costs/openpangu-ultra-moe.py`` against the hand-worked case in
    its docstring, and the configuration's file against both."""
    import numpy as np
    cell = harness.Cell(CELL)
    cfg, costs = cell.config, cell.module("costs")
    mla = 1536 * 7680 + 24576 * 1536 + 576 * 7680 + 32768 * 512 \
        + 7680 * 16384 + 1536 + 512 + 2 * 7680
    assert costs.layer_parameters(cfg) == (
        mla, 3 * 7680 * 18432 + 2 * 7680,
        256 * 7680 + 3 * 7680 * 2048 + 2 * 7680, 3 * 7680 * 2048) == (
        196592640, 424688640, 49167360, 47185920)
    assert mla + 49167360 == 245760000          # the catalog's "246 M"
    assert costs.module_parameters(cfg) == 1118722560 == \
        245760000 + 16 * 47185920 + 7680 * 15360 + 3 * 7680
    assert costs.parameters(cfg) == cfg["parameters"] == 6037862400 == \
        621281280 + 4 * 1000734720 + 1118722560 + 2 * 19200 * 7680 + 7680
    shapes = cell.module("reference").param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 6037862400
    off = dict(cfg, deploy=dict(cfg["deploy"], self_draft=0))
    assert costs.parameters(off) == 4919139840
    assert not [n for n in cell.module("reference").param_shapes(off)
                if n.startswith("mtp_")]
    assert costs.parameters(dict(cfg, spec=dict(
        cfg["spec"], n_routed_experts=8))) == 4150425600   # the fallback
    assert costs.latent_row_bytes(cfg) == 1152
    assert costs.expected_picks(cfg) == 0.5
    # 64 rows at 1,500, two queries each: 128 queries see 1,499.5 keys
    # in the mean; the rows are read ONCE
    flops, nbytes = costs.mla_kernel_cost(cfg, 64, 64 * 1500, 128)
    assert (flops, nbytes) == (2 * 128 * 1088 * 128 * 1499.5,
                               1152.0 * 64 * 1500)
    assert costs.mla_kernel_cost(cfg, 0, 0, 0) == (0.0, 0.0)
    assert costs.moe_kernel_cost(cfg, 64, 16) == (
        2.0 * 47185920 * 64, 47185920.0 * 16 * 2)
    # a step streams every weight but the embedding's table, and six
    # layers of latent rows
    assert costs.decode_step_bytes(cfg, [1000] * 64) == 2 * (
        6037862400 - 19200 * 7680 + 64 * 7680) + 6 * 1152 * 64000
    assert costs.forward_flops_per_token(cfg, 100) \
        - costs.forward_flops_per_token(cfg, 0) == 5 * 100 * 2 * 128 * 1088
    spec = cfg["spec"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "routed_scaling_factor", "num_hidden_layers",
                "first_k_dense_replace", "n_routed_experts", "vocab_size",
                "rms_norm_eps", "rope_theta", "sandwich_norm",
                "norm_topk_prob", "num_nextn_predict_layers"):
        assert spec[key] == cfg[key], key
    assert (spec["arch"], spec["router_width"],
            spec["num_nextn_predict_layers"]) == ("pangu_ultra_moe", 256, 1)
    assert cfg["reduced"] == list(cfg["published"]) == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    assert set(cfg["limits"]) == {"flip_share", "flip_gap_mean",
                                  "token_gap_max", "draft_flip_share"}
    dep = cfg["deploy"]
    assert dep["self_draft"] == 1 == cfg["rehearse"]["deploy"]["self_draft"]
    # half of 64 slots at kv_max: reservation is a live path
    assert dep["pool_blocks"] == 2048 == 64 * (dep["kv_max"] // 64) // 2
    assert 6 * 640 * 2 == 7680                  # bytes a token, 5 + 1


def test_openpangu_file_holds_the_catalogs_row():
    """Every key of the catalog row's ``config`` is in the file under
    the same name with the same value, but the four ``reduced`` names;
    ``num_nextn_predict_layers`` stays 1 and is not reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f
               if '"name": "openPangu-Ultra-MoE-718B"' in ln][0]
    cfg = harness.Cell(CELL).config
    entry = [c for c in harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["configs"]
        if c["name"] == "openpangu-ultra-moe"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]
    assert "num_nextn_predict_layers" not in cfg["reduced"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_openpangu_traffic_is_the_issues():
    """The mix's one block: eight of sixteen open with one of four
    system prompts of 512 tokens (8 whole blocks), own parts 104-1,415,
    outputs 251-2,048, nothing past 4,096; outputs are the larger half
    of the tokens and, with the prefixes served from the store, two
    thirds of what is computed."""
    import numpy as np
    from benchmark import traffic
    mix = harness.Cell(CELL).traffic
    prompt, output, shared, _ = traffic.block(mix)
    assert (mix["driver"], mix["arrival"]) == (
        "serve-mtp", {"kind": "backlog", "requests_per_s": 8})
    assert shared.sum() == 8 and mix["prefixes"] == 4
    assert mix["prefix_len"] == 512 == 8 * 64
    assert mix["prompt"] == {"median": 384, "sigma": 0.7, "lo": 64,
                             "hi": 2048}
    assert mix["output"] == {"median": 768, "sigma": 0.6, "lo": 128,
                             "hi": 2048}
    own = np.where(shared, prompt - 512, prompt)
    assert (own.min(), own.max()) == (104, 1415)
    assert (output.min(), output.max()) == (251, 2048)
    assert (prompt + output).max() <= mix["limit"] == 4096
    assert mix["ramp_s"] == 37.0 and mix["check_requests"] == 16
    computed = prompt.sum() - 8 * 512
    assert output.sum() > prompt.sum()
    assert round(100.0 * output.sum() / (output.sum() + computed)) == 65


def test_openpangu_readers_on_a_recorded_dispatch():
    """The four new readers on hand-made totals: 10 verify spans of 50
    rows at 1,500 with two queries a row and 4 chunk spans of 8 rows x
    32 queries; the module's programs a tenth of busy time; a verify's
    attention, at two queries a row 484 FLOP a byte of latent row
    against the chip's ridge of 240, bound by FLOPs as a chunk's is."""
    cell = harness.Cell(CELL)
    read = lambda name, run: harness.load_module(
        cell.bench, "layer_metrics/%s.py" % name).read(run)
    peaks = cell.peaks("TPU v5 lite")
    bw, fl = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    dec = {"rows": 500, "kv_tokens": 500 * 1500, "q_tokens": 1000}
    chunk = {"rows": 32, "kv_tokens": 32 * 600, "q_tokens": 1024}
    run = {"trace": {"devices": [{"busy_s": 2.0, "modules": {
               "jit_paged_self_verify(1)": (10, 1.5),
               "jit_paged_draft_step(2)": (10, 0.15),
               "jit_paged_prefill_chunk_self(3)": (4, 0.3),
               "jit_paged_prefill_chunk_draft(4)": (4, 0.05),
               "jit_copy_block(5)": (3, 0.001)}, "ops": {
               "%mla_paged_attention.3 = bf16[64,256,512]": 0.06,
               "%mla_paged_attention.9 = bf16[16,4096,512]": 0.04,
               "%ragged-dot_grouped_matmul.1 = bf16[1024,4096]": 0.5,
               "%ragged-dot-metadata = (s32[17]) custom-call(...)": 0.1,
               "%fusion.7 = bf16[64,7680]": 1.0}}]},
           "peaks": peaks, "config": cell.config, "cell": cell,
           "counters": {"spec_proposed": 400, "spec_accepted": 3,
                        "moe_expert_steps": 70,
                        "moe_local_assignments": 70 * 60,
                        "moe_experts_touched": 70 * 15},
           "host": {"traced_phases": {
               "serve_decode": {"spans": 10, "ns": 1, "counts": dec},
               "serve_prefill": {"spans": 4, "ns": 1, "counts": chunk}}}}
    assert read("mtp.accept_pct", run) == pytest.approx(0.75)
    assert read("mtp.draft_time_pct", run) == pytest.approx(10.0)
    row = 2 * 128 * 1088

    def least(rows, kv, q):
        keys = q * (kv / rows - (q / rows - 1) / 2.0)
        return max(row * keys / fl, 1152 * kv / bw)

    want = 10 * (5 * least(50, 50 * 1500, 100) + least(50, 50 * 1500, 50)) \
        + 4 * 6 * least(8, 8 * 600, 256)
    assert least(50, 50 * 1500, 100) > 1.9 * 1152 * 50 * 1500 / bw
    assert least(8, 8 * 600, 256) > 1152 * 8 * 600 / bw
    assert read("kernel.mla_verify_roofline_pct", run) == \
        pytest.approx(100.0 * want / 0.1)
    # (10 + 4) target programs x 4 expert layers + (10 + 4) x the module
    steps = 14 * 4 + 14
    want = steps * max(2.0 * 47185920 * 60 / fl, 47185920.0 * 15 * 2 / bw)
    assert read("kernel.moe_verify_roofline_pct", run) == \
        pytest.approx(100.0 * want / 0.6)
    # a program that does not draft for itself: nothing to read
    old = dict(run, counters={"moe_expert_steps": 0}, trace={"devices": [{
        "busy_s": 2.0, "modules": {"jit_paged_decode(1)": (10, 1.5)},
        "ops": {"%mla_paged_attention.1 = bf16[64,128,512]": 1.0}}]},
        host={})
    for name in ("mtp.accept_pct", "mtp.draft_time_pct",
                 "kernel.mla_verify_roofline_pct",
                 "kernel.moe_verify_roofline_pct"):
        assert read(name, old) is None, name


# ---------------------------------------------------------------------------
# the cell's rehearsal, sound and broken
# ---------------------------------------------------------------------------
def _proposals_shifted_by_one(monkeypatch):
    """The fault: a module whose proposals are shifted by one (token id
    + 1): the served tokens stay right (the target rejects them), only
    the check of the draft can see it."""
    from mxnet_tpu.serving.decode_engine import GenerationEngine
    vocab = harness.Cell(CELL, rehearse=True).config["spec"]["vocab_size"]
    honest = GenerationEngine._note_draft

    def shifted(self, st, i, r, position, token):
        honest(self, st, i, r, position, (token + 1) % vocab)

    monkeypatch.setattr(GenerationEngine, "_note_draft", shifted)


def _a_token_altered(monkeypatch):
    from mxnet_tpu.serving.decode_engine import GenerationEngine
    vocab = harness.Cell(CELL, rehearse=True).config["spec"]["vocab_size"]
    honest = GenerationEngine._push_token
    calls = {"n": 0}

    def altered(self, req, tok):
        calls["n"] += 1
        honest(self, req, (tok + 1) % vocab if calls["n"] % 9 == 0
               else tok)

    monkeypatch.setattr(GenerationEngine, "_push_token", altered)


@pytest.mark.parametrize("fault", ["none", "proposals-shifted-by-one",
                                   "a-token-altered"])
def test_rehearsal_drafts_and_fails_when_broken(capsys, monkeypatch,
                                                fault):
    """``run.py --rehearse`` of the cell in this process.  Sound, it is
    ``correct`` with a proposal in every decode step, hundreds of them
    held to the reference's module, compared requests on prefix hits,
    and every reader run.  With the module's proposals shifted by one
    only ``draft_flip_share`` fails (the served tokens are still the
    target's); with a served token altered the served checks fail."""
    if fault == "proposals-shifted-by-one":
        _proposals_shifted_by_one(monkeypatch)
    elif fault == "a-token-altered":
        _a_token_altered(monkeypatch)
    rc, last, said = _rehearse(capsys, "--seed", str(2**31 + 43),
                               "--trace", "1")
    assert rc == 0 and last["correct"] is (fault == "none")
    c = said["counters"]
    assert c["prefix_hits"] > 10 and c["shed"] == c["errors"] == 0
    assert c["cow_forks"] > 0
    assert c["spec_steps"] == c["decode_steps"] > 100
    assert c["spec_proposed"] > c["decode_steps"]
    assert c["draft_rows"] > c["generated_tokens"]
    assert said["requests_compared_sharing_a_prefix"] > 4
    assert said["proposals_compared"] > 200
    bad = {k["name"] for k in said["checks"] if not k["ok"]}
    if fault == "none":
        assert not bad and said["draft_flips"] == 0
        assert {"mtp.accept_pct", "mtp.draft_time_pct",
                "kernel.mla_verify_roofline_pct",
                "kernel.moe_verify_roofline_pct", "engine.prefix_hit_pct",
                "kernel.mla_attn_time_pct", "moe.tokens_per_expert",
                "engine.decode_batch_mean", "engine.starved_pct"} <= set(
                    last["rehearsal"]["readers_ran"])
    elif fault == "proposals-shifted-by-one":
        assert bad == {"draft_flip_share"}
    else:
        assert bad & {"flip_share", "flip_gap_mean", "token_gap_max"}


def test_openpangu_control_is_not_correct(capsys):
    rc, last, said = _rehearse(capsys, "--seed", "7", "--control")
    assert rc == 0 and last["correct"] is False
    bad = {k["name"] for k in said["checks"] if not k["ok"]}
    assert bad & {"flip_share", "draft_flip_share"}


def test_a_program_without_the_model_fails_at_once(monkeypatch, capsys):
    """What the parent commit does on this cell: no such model module,
    exit code 1 before a weight is drawn."""
    run = importlib.import_module("benchmark.run")
    honest = importlib.import_module

    def missing(name, *a, **kw):
        if name == "mxnet_tpu.models.pangu_ultra_moe":
            raise ImportError("No module named %r" % name)
        return honest(name, *a, **kw)

    monkeypatch.setattr(importlib, "import_module", missing)
    try:
        rc = run.main(["--workload", CELL, "--rehearse", "--seed", "1"])
    finally:
        harness.REHEARSAL = False
    assert rc == 1
    assert "has no model 'pangu_ultra_moe'" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the four programs for a described v5e, at the cell's real size
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def topo():
    import jax
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip("cannot describe a v5e topology: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_MOVES_THE_POOL = ("copy", "slice", "scatter", "fusion", "gather")


@pytest.mark.parametrize("kind", ["paged_self_verify", "paged_draft_step",
                                  "paged_self_chunk", "paged_draft_chunk"])
def test_programs_fit_one_chip_and_leave_the_leaf_in_place(
        topo, monkeypatch, kind):
    """The store's own four programs (``paged_program``) of the cell as
    its file deploys it — 5 layers and the module at the published
    widths, 64 slots of 64 table entries, a verify of 2 positions a
    row, a chunk of 16 rows x 32, the pool of 2,048 blocks x 6 layers —
    compiled for a described v5e: under 15 GB by the compiler (weights
    and the pool are its arguments; the PRIMARY cut stands while all
    four are), the latent kernel once a layer the program runs, the
    grouped product twice an expert layer, and no ``copy``, ``slice``,
    ``scatter``, ``fusion`` or ``gather`` that hands back something of
    the leaf's shape or of one of its layers'."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import pangu_ultra_moe as pm
    from mxnet_tpu.pallas_ops import dispatch
    from mxnet_tpu.serving.program_store import chunk_rows, paged_program
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    cell = harness.Cell(CELL)
    cfg, dep = cell.config, cell.config["deploy"]
    spec = pm.with_draft(pm.serving_spec(cfg["spec"]), dep["self_draft"])
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    wdt = jnp.dtype(cfg["weights_dtype"])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    bb, bs = dep["batch_buckets"][-1], dep["kv_block"]
    width = -(-dep["kv_max"] // bs)
    shapes = jax.eval_shape(lambda: pm.pack_params(
        {k: jnp.zeros(s, wdt) for k, s in
         cell.module("reference").param_shapes(cfg).items()}, spec))
    params = {k: sds(v.shape, v.dtype) for k, v in shapes.items()}
    pools = tuple(sds(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: pm.init_pool(spec, dep["pool_blocks"], bs,
                             dep["kv_dtype"])))
    assert [p.shape for p in pools] == [(6, 1, 2048 * 64, 640)]
    chunk = kind.endswith("chunk")
    rows = chunk_rows(bb) if chunk else bb
    lq = dep["prefill_chunk"] if chunk else dep["self_draft"] + 1
    fn, donate = paged_program(pm, spec, kind, lq, bs, len(pools))
    args = (params,) + pools + (
        sds((rows, width), jnp.int32), sds((rows, lq), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32))
    naux = len(pm.AUX_COUNTERS)
    if kind.startswith("paged_draft"):
        packed = (rows if chunk else (lq + 1) * rows) + naux
        args += (sds((rows, lq, spec["hidden_size"])),
                 sds((packed,), jnp.int32))
        layers, expert_layers = 1, 1
    else:
        args += (sds((bb, 2), jnp.uint32), sds((rows,)),
                 sds((rows,), jnp.int32), sds((rows,), jnp.bool_))
        if chunk:
            args += (sds((rows,), jnp.int32), sds((rows,), jnp.int32))
        layers, expert_layers = 5, 4
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    text = compiled.as_text()
    print("openpangu-ultra-moe %s rows=%d lq=%d: %.2f GB (arguments "
          "%.2f, scratch %.2f)" % (kind, rows, lq, total / 1e9,
                                   m.argument_size_in_bytes / 1e9,
                                   m.temp_size_in_bytes / 1e9))
    assert total < LIMIT_GB * 1e9
    assert len(re.findall(r"%mla_paged_attention[.\d]* = ", text)) \
        == layers
    assert len(re.findall(r"%ragged-dot[-\w.]* = f32", text)) \
        == 2 * expert_layers
    L, _, R, _ = pools[0].shape
    pool_shaped = re.compile(r"bf16\[(?:%d,|1,)?1,%d,640\]" % (L, R))
    moved = []
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(",
                       line)
        if hit and hit.group(2) in _MOVES_THE_POOL \
                and pool_shaped.search(hit.group(1)):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
