"""The ``deepseek-v3`` configuration and its cell: the file against the
published widths, the plain reference against the package's graph at
rehearsal size, the costs against hand counts, the readers on a made-up
trace, the control and a planted fault coming out not correct, and both
step programs compiled for a described ``v5e`` at the cell's real size
(run through ``run.py --rehearse`` by ``test_rehearse.py``, which takes
every cell of BENCHMARK.json)."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, weights

CELL = "deepseek-v3.serve-docqa-backlog"
HBM = 16e9


# ---------------------------------------------------------------------------
# the file
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_widths():
    cfg = harness.Cell(CELL).config
    published = {"hidden_size": 7168, "num_attention_heads": 128,
                 "q_lora_rank": 1536, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "intermediate_size": 18432,
                 "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
                 "n_group": 8, "topk_group": 4,
                 "routed_scaling_factor": 2.5, "n_shared_experts": 1,
                 "rope_theta": 10000}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 16, 16160, 0]
    assert cfg["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280,
        "num_nextn_predict_layers": 1}
    # the program's spec says what the file's top level says; the
    # router keeps its published width
    spec = cfg["spec"]
    assert spec["arch"] == "deepseek_v3" and spec["router_width"] == 256
    for key, value in spec.items():
        if key not in ("arch", "router_width"):
            assert cfg[key] == value, key
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in ("deployment", "assumed", "precision", "control"):
        assert cfg[key]


def test_traffic_is_the_issues_mix():
    from benchmark import traffic
    mix = harness.Cell(CELL).traffic
    prompt, output, shared, _ = traffic.block(mix)
    assert int(shared.sum()) == 12 and len(prompt) == 16
    assert prompt.max() <= 6144 and prompt.min() >= 256
    assert (prompt + output).max() <= 6656 and output.min() >= 32
    # 56 % of a block's prompt tokens are the shared prefixes
    assert 0.5 < 12 * 3072 / prompt.sum() < 0.62
    assert mix["arrival"] == {"kind": "backlog", "requests_per_s": 12}


# ---------------------------------------------------------------------------
# the reference against the package's graph
# ---------------------------------------------------------------------------
def test_reference_equals_the_paged_graph_at_rehearsal_size():
    """Prefill in two chunks and four decode steps through the latent
    pool, program against reference, the benchmark's own seeded
    weights: float32 round-off (different association: absorbed
    against plain, a masked loop against whatever the program runs)."""
    from mxnet_tpu.models import deepseek_v3 as ds
    cell = harness.Cell(CELL, rehearse=True)
    cfg, ref = cell.config, cell.module("reference")
    spec = ds.serving_spec(cfg["spec"])
    assert ref.param_shapes(cfg) == ds.param_shapes(spec)
    params = weights.draw(ref.param_shapes(cfg), 7, gain=cfg["init_gain"])
    tokens = np.random.default_rng(7).integers(
        0, spec["vocab_size"], 20).astype(np.int32)
    want = np.asarray(ref.logits(params, jnp.asarray(tokens), cfg))
    packed = ds.pack_params(dict(params), spec)
    bs = 8
    pool, = ds.init_pool(spec, 4, bs)
    tables = np.asarray([[1, 2, 3]], np.int32)
    got = {}
    for start, n in ((0, 8), (8, 8)):
        logits, pool, _ = ds.paged_step_apply(
            packed, pool, tables, tokens[None, start:start + 8],
            np.asarray([start]), np.asarray([n]), spec, bs,
            all_logits=True)
        for j in range(n):
            got[start + j] = np.asarray(logits)[0, j]
    for p in range(16, 20):
        logits, pool, _ = ds.paged_step_apply(
            packed, pool, tables, tokens[None, p:p + 1], np.asarray([p]),
            np.asarray([1]), spec, bs)
        got[p] = np.asarray(logits)[0]
    worst = max(np.abs(got[p] - want[p]).max() for p in got)
    assert worst < 1e-4, worst


def test_reference_blocks_do_not_change_its_answer(monkeypatch):
    """Attention in blocks of heads and queries (what lets a 6,656-token
    request fit) gives what one block gives."""
    cell = harness.Cell(CELL, rehearse=True)
    cfg, ref = cell.config, cell.module("reference")
    params = weights.draw(ref.param_shapes(cfg), 3, gain=cfg["init_gain"])
    tokens = jnp.arange(37, dtype=jnp.int32) * 5 % 1024
    whole = np.asarray(ref.logits(params, tokens, cfg))
    monkeypatch.setattr(ref, "HEAD_BLOCK", 2)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    blocked = np.asarray(ref.logits(params, tokens, cfg))
    assert np.abs(whole - blocked).max() < 1e-5
    # and the lower precision moves it: the control of the comparison
    low = np.asarray(ref.logits(params, tokens, cfg, jnp.bfloat16))
    assert np.abs(whole - low).max() > 1e-3


# ---------------------------------------------------------------------------
# the costs against hand counts
# ---------------------------------------------------------------------------
def test_costs_against_hand_counts():
    cell = harness.Cell(CELL)
    cfg, costs = cell.config, cell.module("costs")
    mla, dense, around, expert = costs.layer_parameters(cfg)
    assert mla == (1536 * 7168 + 24576 * 1536 + 576 * 7168 + 32768 * 512
                   + 7168 * 16384 + 7168 + 1536 + 512) == 187_114_496
    assert dense == 3 * 7168 * 18432 + 7168
    assert mla + dense == 583_483_392
    assert mla + around == 232_997_120 and expert == 44_040_192
    total = costs.parameters(cfg)
    assert total == 4_565_721_088 == cfg["parameters"]
    shapes = cell.module("reference").param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == total
    assert costs.expected_picks(cfg) == 0.5
    # one query of 128 heads over one row: 576 into the score, 512 out
    assert costs.attention_flops(cfg, 1) == 2 * 128 * (576 + 512)
    mm = (5 * (187_114_496 - 9216) + 3 * 7168 * 18432 + 4 * (
        256 * 7168 + 3 * 7168 * 2048 + 0.5 * 44_040_192) + 16160 * 7168)
    assert costs.forward_flops_per_token(cfg, 0) == 2 * mm
    assert costs.forward_flops_per_token(cfg, 4000) == \
        2 * mm + 5 * 4000 * 2 * 128 * 1088
    # bytes: a row is 576 bfloat16 values a layer, 5,760 B a token
    step = costs.decode_step_bytes(cfg, [100, 200])
    assert costs.decode_step_bytes(cfg, [100, 201]) - step == 5 * 1152
    assert costs.decode_step_bytes(cfg, []) == 2 * (
        total - 16160 * 7168)
    assert step - costs.decode_step_bytes(cfg, [100, 200], 15) == \
        4 * 2 * 44_040_192
    # the kernels: a decode dispatch is bound by both at once
    flops, nbytes = costs.mla_kernel_cost(cfg, 64, 64 * 4200, 64)
    assert nbytes == 64 * 4200 * 1152
    assert flops == 2 * 128 * 1088 * 64 * 4200
    assert flops / nbytes == pytest.approx(241.8, abs=0.1)
    # a chunk's queries end at the frontier: 32 queries see 100 .. 131
    flops, _ = costs.mla_kernel_cost(cfg, 1, 131, 32)
    assert flops == pytest.approx(2 * 128 * 1088 * sum(range(100, 132)))
    assert costs.moe_kernel_cost(cfg, 30, 13) == (
        2.0 * 44_040_192 * 30, 2.0 * 44_040_192 * 13)
    assert costs.mla_kernel_cost(cfg, 0, 0, 0) == (0.0, 0.0)


# ---------------------------------------------------------------------------
# the readers, on a made-up reduced trace
# ---------------------------------------------------------------------------
def _run_for_readers(counters, ops, modules):
    cell = harness.Cell(CELL)
    return {"cell": cell, "config": cell.config, "traffic": cell.traffic,
            "chips": 1, "end_to_end": {}, "host": {}, "counters": counters,
            "peaks": cell.peaks("TPU v5 lite"),
            "trace": {"devices": [{"id": 0, "busy_s": 2.0, "ops": ops,
                                   "modules": modules}]}}


def _reader(name):
    return harness.load_module(harness.Cell(CELL).bench,
                               "layer_metrics/%s.py" % name)


def test_readers_compute_what_they_say(monkeypatch):
    from mxnet_tpu import profiler
    counters = {"moe_expert_steps": 400, "moe_local_assignments": 16000,
                "moe_expert_load_max": 2000, "moe_experts_touched": 5600,
                "moe_tokens": 32000}
    ops = {"%mla_paged_attention.3 = bf16[64,128,512]{2,1,0} "
           "custom-call(s32[64,104] %a, ...)": 0.25,
           "%mla_paged_attention.7 = bf16[64,4096,512]{2,1,0} "
           "custom-call(s32[64,104] %a, ...)": 0.15,
           "%ragged-dot-none.2 = f32[512,4096]{1,0} custom-call(...)": 0.3,
           "%ragged-dot-metadata = (s32[17]) custom-call(...)": 0.01,
           "%fusion.1 = bf16[64,7168]{1,0} fusion(...)": 1.0}
    modules = {"jit_paged_decode(123)": (50, 1.0),
               "jit_paged_prefill_chunk(456)": (50, 1.0)}
    run = _run_for_readers(counters, ops, modules)
    assert _reader("kernel.mla_attn_time_pct").read(run) == \
        pytest.approx(20.0)
    assert _reader("kernel.moe_ffn_time_pct").read(run) == \
        pytest.approx(15.5)
    assert _reader("moe.tokens_per_expert").read(run) == \
        pytest.approx(16000 / (16 * 400))
    assert _reader("moe.load_max_over_mean").read(run) == \
        pytest.approx(2.0)
    # 40 assignments on 14 experts a layer a step: bytes bound
    # (14 x 88.08 MB = 1.51 ms against 3.52 GFLOP = 0.018 ms);
    # 100 programs x 4 expert layers over 0.31 s
    least = 100 * 4 * 14 * 2 * 44_040_192 / 819e9
    assert _reader("kernel.moe_ffn_roofline_pct").read(run) == \
        pytest.approx(100 * least / 0.31)
    totals = {
        "serve_decode": {"spans": 10, "counts": {
            "rows": 600, "kv_tokens": 600 * 4000, "q_tokens": 600}},
        "serve_prefill": {"spans": 10, "counts": {
            "rows": 20, "kv_tokens": 20 * 3000, "q_tokens": 20 * 64}}}
    monkeypatch.setattr(profiler, "phase_totals", lambda: totals)
    dec = max(2 * 128 * 1088 * 60 * 4000 / 197e12,
              60 * 4000 * 1152 / 819e9)
    pre = 2 * 128 * 1088 * 2 * 64 * (3000 - 31.5) / 197e12
    assert _reader("kernel.mla_attn_roofline_pct").read(run) == \
        pytest.approx(100 * 50 * 5 * (dec + pre) / 0.4)
    # a parent program: no q_tokens on its spans, no expert counters,
    # no kernel in its trace -> nothing to read, and nothing raised
    del totals["serve_prefill"]["counts"]["q_tokens"]
    assert _reader("kernel.mla_attn_roofline_pct").read(run) is None
    bare = _run_for_readers({"decode_steps": 5}, {
        "%fusion.1 = f32[16,2048] fusion(...)": 1.0}, modules)
    for name in ("kernel.mla_attn_time_pct", "kernel.mla_attn_roofline_pct",
                 "kernel.moe_ffn_time_pct", "kernel.moe_ffn_roofline_pct",
                 "moe.tokens_per_expert", "moe.load_max_over_mean"):
        assert _reader(name).read(bare) is None, name
        assert _reader(name).read(dict(bare, trace=None)) is None, name


# ---------------------------------------------------------------------------
# the control and a planted fault come out not correct
# ---------------------------------------------------------------------------
def _main(capsys, *args):
    import importlib
    run = importlib.import_module("benchmark.run")
    try:
        rc = run.main(["--workload", CELL, "--rehearse"] + list(args))
    finally:
        harness.REHEARSAL = False
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
           if ln.strip()]
    checks = {c["name"]: c for ln in out if "checks" in ln
              for c in ln["checks"]}
    return rc, out[-1], checks


def test_control_is_not_correct(capsys):
    """int8 weights, the configuration's control, at toy size in
    float32 (where the sound run has no flip at all: test_rehearse)."""
    rc, last, checks = _main(capsys, "--seed", "41", "--control")
    assert rc == 0 and last["correct"] is False
    # the number the lower precision fails: how many decisions flip
    assert not checks["flip_share"]["ok"]
    assert last["failed"] == 0 and checks["requests_cut_short"]["ok"]


def test_a_token_altered_where_it_is_produced(capsys, monkeypatch):
    from mxnet_tpu.serving.decode_engine import GenerationEngine
    honest = GenerationEngine._fetch_decode
    calls = {"n": 0}

    def altered(self, arr):
        out = honest(self, arr).copy()
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            out[:4] = (out[:4] + 1) % 1024      # the slots' tokens
        return out

    monkeypatch.setattr(GenerationEngine, "_fetch_decode", altered)
    rc, last, checks = _main(capsys, "--seed", "43")
    assert rc == 0 and last["correct"] is False
    assert not checks["token_gap_max"]["ok"]


# ---------------------------------------------------------------------------
# both programs for a described v5e, at the cell's real size
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def topo():
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


_MOVES_THE_POOL = ("copy", "slice", "scatter", "fusion")


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_programs_fit_one_chip_and_leave_the_pool_in_place(
        topo, monkeypatch, kind):
    """5 layers at the published widths, 16 experts a layer, 64 slots,
    the pool for every slot at ``kv_max``: under 16 GB by the compiler
    (weights and pool are its arguments), both kernels in the program,
    and no ``copy``, ``slice``, ``scatter`` or ``fusion`` of the latent
    pool's or of one of its layers' shape (PR 25's rule)."""
    from mxnet_tpu.models import deepseek_v3 as ds
    from mxnet_tpu.pallas_ops import dispatch
    from mxnet_tpu.serving.program_store import sample_tokens
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    cell = harness.Cell(CELL)
    cfg, dep = cell.config, cell.config["deploy"]
    spec = ds.serving_spec(cfg["spec"])
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    wdt = jnp.dtype(cfg["weights_dtype"])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    bb, bs = dep["batch_buckets"][-1], dep["kv_block"]
    lq = 1 if kind == "decode" else dep["prefill_chunk"]
    width = -(-dep["kv_max"] // bs)
    rows = (bb * width + 1) * bs
    shapes = jax.eval_shape(lambda: ds.pack_params(
        {k: jnp.zeros(s, wdt) for k, s in
         cell.module("reference").param_shapes(cfg).items()}, spec))
    params = {k: sds(v.shape, v.dtype) for k, v in shapes.items()}
    pool_shape = (spec["num_hidden_layers"], 1, rows,
                  ds.latent_width(spec))
    pool = sds(pool_shape, jnp.dtype(dep["kv_dtype"]))

    def fn(params, pool, tables, tokens, positions, valid, keys, temps,
           top_ks, do):
        logits, pool, aux = ds.paged_step_apply(
            params, pool, tables, tokens, positions, valid, spec, bs)
        toks, carry = sample_tokens(logits, keys, temps, top_ks)
        return (jnp.concatenate([toks, aux]), pool,
                jnp.where(do[:, None], carry, keys))

    compiled = jax.jit(fn, donate_argnums=(1, 6)).lower(
        params, pool, sds((bb, width), jnp.int32),
        sds((bb, lq), jnp.int32), sds((bb,), jnp.int32),
        sds((bb,), jnp.int32), sds((bb, 2), jnp.uint32), sds((bb,)),
        sds((bb,), jnp.int32), sds((bb,), jnp.bool_)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    text = compiled.as_text()
    print("deepseek-v3 %s lq=%d: %.2f GB (arguments %.2f, scratch %.2f)"
          % (kind, lq, total / 1e9, m.argument_size_in_bytes / 1e9,
             m.temp_size_in_bytes / 1e9))
    assert total < HBM
    assert len(re.findall(r"%mla_paged_attention[.\d]* = ", text)) == 5
    assert len(re.findall(r"%ragged-dot[-\w.]* = f32", text)) == 8
    L, _, R, W = pool_shape
    pool_shaped = re.compile(r"bf16\[(?:%d,|1,)?1,%d,%d\]" % (L, R, W))
    moved = []
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(",
                       line)
        if hit and hit.group(2) in _MOVES_THE_POOL \
                and pool_shaped.search(hit.group(1)):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    if kind == "decode":
        # its scratch is under one layer of the pool (a chunk's is the
        # activations of 64 slots x the chunk, no measure of the pool)
        assert m.temp_size_in_bytes < R * W * 2
