"""The ``deepseek-v32`` configuration and its cell, beside what the
parametrised modules of this directory already ask of every cell: the
costs of the published widths against a hand-worked case and against
the configuration's file and the catalog's row, the mix, the readers on
a made-up trace, the cell's rehearsal (its toy ``index_topk`` under its
toy contexts, so that the selection cuts), the control and three broken
paths coming out not ``correct``, and both step programs compiled for a
described ``v5e`` at the cell's real size."""
import importlib
import json
import os
import re

import pytest

from benchmark import harness

CELL = "deepseek-v32.serve-longdoc-backlog"
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
# the files (fast: tier-1 runs these four through
# tests/test_benchmark_contract.py)
# ---------------------------------------------------------------------------
def test_costs_of_the_published_widths():
    """``costs/deepseek-v32.py`` against the hand-worked case in its
    docstring, and the configuration's file against both."""
    import numpy as np
    cell = harness.Cell(CELL)
    cfg, costs = cell.config, cell.module("costs")
    assert costs.indexer_parameters(cfg) == 13959424 == \
        1536 * 8192 + 7168 * 128 + 2 * 128 + 7168 * 64
    assert costs.layer_parameters(cfg) == (
        187114496 + 13959424, 396361728 + 7168, 45882624, 44040192)
    assert costs.parameters(cfg) == cfg["parameters"] == 4635518208 == \
        4565721088 + 5 * 13959424
    shapes = cell.module("reference").param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 4635518208
    assert costs.index_pair_flops(cfg) == 16384 + 128
    assert costs.attention_pair_flops(cfg) == 278528
    assert (costs.index_key_bytes(cfg), costs.latent_row_bytes(cfg)) == (
        256, 1152)
    assert costs.cache_bytes_per_token(cfg) == 7680
    assert costs.expected_picks(cfg) == 0.5
    # 64 decode rows at 20,000: every key scored, 2,048 kept a query
    flops, nbytes = costs.index_kernel_cost(cfg, 64, 64 * 20000,
                                            64 * 20000)
    assert (flops, nbytes) == (16512.0 * 1280000, 256.0 * 1280000)
    assert costs.select_cost(cfg, 1280000) == (0.0, 4.0 * 1280000)
    flops, nbytes = costs.sparse_attn_kernel_cost(
        cfg, 64, 64 * 2048, 64 * 20000, 64)
    assert (flops, nbytes) == (278528.0 * 131072, 1152.0 * 131072)
    # a chunk: 16 rows of 32 queries keep 2,048 each; a row's selected
    # rows are required once, not once a query
    flops, nbytes = costs.sparse_attn_kernel_cost(
        cfg, 16, 512 * 2048, 16 * 20000, 512)
    assert (flops, nbytes) == (278528.0 * 1048576, 1152.0 * 16 * 2048)
    assert costs.index_kernel_cost(cfg, 0, 0, 0) == (0.0, 0.0)
    assert costs.sparse_attn_kernel_cost(cfg, 0, 0, 0, 0) == (0.0, 0.0)
    assert costs.moe_kernel_cost(cfg, 32, 16) == (
        2.0 * 44040192 * 32, 44040192.0 * 16 * 2)
    # under 2,048 of context attention is dense; past it, level
    dense = costs.forward_flops_per_token(cfg, 2048) \
        - costs.forward_flops_per_token(cfg, 0)
    assert dense == 5 * 2048 * (16512 + 278528)
    assert costs.forward_flops_per_token(cfg, 20000) \
        - costs.forward_flops_per_token(cfg, 2048) == 5 * 17952 * 16512
    spec = cfg["spec"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "n_group", "topk_group", "routed_scaling_factor",
                "index_n_heads", "index_head_dim", "index_topk",
                "num_hidden_layers", "first_k_dense_replace",
                "n_routed_experts", "vocab_size", "rope_scaling"):
        assert spec[key] == cfg[key], key
    assert (spec["arch"], spec["router_width"], spec["index_n_heads"],
            spec["index_head_dim"], spec["index_topk"]) == (
        "deepseek_v32", 256, 64, 128, 2048)
    assert cfg["reduced"] == list(cfg["published"]) == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert set(cfg["limits"]) == {"flip_share", "flip_gap_mean",
                                  "token_gap_max"}
    toy = cfg["rehearse"]
    assert toy["spec"]["index_topk"] < toy["deploy"]["kv_max"] // 4
    assert cfg["deploy"]["pool_blocks"] < 64 * 360


def test_the_file_holds_the_catalogs_row():
    """Every key of the catalog row's ``config`` is in the file under
    the same name with the same value, but the five ``reduced`` names
    (skipped where the catalog is not installed)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = [json.loads(ln) for ln in f
               if '"name": "DeepSeek-V3.2"' in ln][0]
    cfg = harness.Cell(CELL).config
    entry = [c for c in harness.load_json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["configs"]
        if c["name"] == "deepseek-v32"][0]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key


def test_the_traffic_is_the_issues():
    """The mix's one block: twelve of sixteen open with one of four
    documents of 16,384 tokens (256 whole blocks), own parts and
    outputs ``docqa``'s, every context of a sharing request 16.9-21.4 k
    and so 8 to 10 times the 2,048 a query keeps."""
    import numpy as np
    from benchmark import traffic
    mix = harness.Cell(CELL).traffic
    docqa = harness.Cell("deepseek-v3.serve-docqa-backlog").traffic
    prompt, output, shared, _ = traffic.block(mix)
    assert (mix["driver"], mix["arrival"]["kind"]) == (
        "serve-window", "backlog")
    assert shared.sum() == 12 and mix["prefixes"] == 4
    assert mix["prefix_len"] == 16384 == 256 * 64
    own = np.where(shared, prompt - 16384, prompt)
    for part in ("median", "sigma", "lo"):
        assert mix["prompt"][part] == docqa["prompt"][part]
    assert mix["output"] == docqa["output"]
    assert sorted(own) == sorted(
        traffic._lengths(docqa["prompt"], 16).tolist())
    assert (own.min(), own.max()) == (502, 4697)
    assert (output.min(), output.max()) == (63, 512)
    assert (prompt + output).max() <= mix["limit"] == 23040
    assert prompt[shared].min() + 1 > 8 * 2048
    # what a filled store serves of a block's prompt tokens
    assert round(100.0 * 12 * 16384 / prompt.sum()) == 87


def test_readers_on_a_recorded_dispatch():
    """The seven new readers on hand-made totals: 10 decode spans of 64
    rows at 20,000 and 4 chunk spans of 16 rows x 32 queries, 5 layers;
    the indexer's decode is bound by its keys' bytes and its chunk by
    its FLOPs, the attention's decode by bytes and its chunk by FLOPs
    (both forms' events counted), the selection by the scores' bytes."""
    cell = harness.Cell(CELL)
    read = lambda name, run: harness.load_module(
        cell.bench, "layer_metrics/%s.py" % name).read(run)
    peaks = cell.peaks("TPU v5 lite")
    bw, fl = peaks["hbm_bytes_per_s"], peaks["bf16_flops_per_s"]
    dec = {"rows": 640, "kv_tokens": 640 * 20000, "q_tokens": 640,
           "index_pairs": 640 * 20000, "keys_selected": 640 * 2048}
    chunk = {"rows": 64, "kv_tokens": 64 * 20000, "q_tokens": 2048,
             "index_pairs": 2048 * 19984, "keys_selected": 2048 * 2048}
    run = {"trace": {"devices": [{"busy_s": 2.0, "ops": {
               "%dsa_index_scores.3 = f32[64,1,23040]": 0.04,
               "%dsa_index_scores.7 = f32[16,32,23040]": 0.06,
               "%dsa_mla_attention.2 = bf16[64,128,512]": 0.02,
               "%dsa_mla_attention_masked.4 = bf16[16,4096,512]": 0.03,
               "%dsa_select_threshold.5 = (s32[64,1]{1,0}, s32[64,1])"
               " custom-call(%a)": 0.3,
               "%dsa_select_threshold.9 = (s32[512,1]{1,0}, s32[512,1])"
               " custom-call(%a)": 0.5,
               "%sort.2 = (f32[64,256]{1,0}, s32[64,256]) sort(": 7.0,
               "%mla_paged_attention.1 = bf16[64,128,512]": 9.0}}]},
           "peaks": peaks, "config": cell.config, "cell": cell,
           "host": {"traced_phases": {
               "serve_decode": {"spans": 10, "ns": 1, "counts": dec},
               "serve_prefill": {"spans": 4, "ns": 1, "counts": chunk}}}}
    assert read("kernel.dsa_index_time_pct", run) == pytest.approx(5.0)
    assert read("kernel.dsa_attn_time_pct", run) == pytest.approx(2.5)
    assert read("kernel.dsa_select_time_pct", run) == pytest.approx(40.0)
    least = 5 * (10 * 256 * 64 * 20000 / bw
                 + 4 * 16512 * 512 * 19984 / fl)
    assert read("kernel.dsa_index_roofline_pct", run) == \
        pytest.approx(100.0 * least / 0.1)
    least = 5 * (10 * max(1152 * 64 * 2048 / bw, 278528 * 64 * 2048 / fl)
                 + 4 * 278528 * 512 * 2048 / fl)
    assert read("kernel.dsa_attn_roofline_pct", run) == \
        pytest.approx(100.0 * least / 0.05)
    least = 5 * 4 * (10 * 64 * 20000 + 4 * 512 * 19984) / bw
    assert read("kernel.dsa_select_roofline_pct", run) == \
        pytest.approx(100.0 * least / 0.8)
    assert read("dsa.selected_pct", run) == pytest.approx(
        100.0 * (640 + 2048) * 2048 / (640 * 20000 + 2048 * 19984))
    # a program from before the indexer: no such events, no such counts
    old = dict(run, trace={"devices": [{"busy_s": 2.0, "ops": {
        "%mla_paged_attention.1 = bf16[64,128,512]": 1.0}}]},
        host={"traced_phases": {"serve_decode": {
            "spans": 10, "ns": 1, "counts": {
                "rows": 640, "kv_tokens": 1, "q_tokens": 640}}}})
    for name in ("kernel.dsa_index_time_pct", "kernel.dsa_attn_time_pct",
                 "kernel.dsa_select_time_pct", "dsa.selected_pct",
                 "kernel.dsa_index_roofline_pct",
                 "kernel.dsa_select_roofline_pct",
                 "kernel.dsa_attn_roofline_pct"):
        assert read(name, old) is None, name
    assert read("kernel.dsa_index_roofline_pct",
                dict(run, host={})) is None


def test_reference_blocks_and_segments_do_not_change_its_answer(
        monkeypatch):
    """The reference in one block (a sequence shorter than every block
    size) and in small blocks of queries, heads and rows (an expert
    gathering six of sixteen) over three segments of keys: the same
    logits and the same selections, at the rehearsal's widths with 16
    kept of up to 50."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights
    cell = harness.Cell(CELL, rehearse=True)
    ref, cfg = cell.module("reference"), cell.config
    params = weights.draw(ref.param_shapes(cfg), 3)
    tokens = jnp.asarray(np.random.RandomState(3).randint(
        0, cfg["spec"]["vocab_size"], 50).astype(np.int32))
    run = lambda: jax.jit(lambda p, t: (
        ref.logits(p, t, cfg), ref.selections(p, t, cfg)))(params, tokens)
    whole, sets = run()
    for name, size in (("HEAD_BLOCK", 2), ("QUERY_BLOCK", 8),
                       ("INDEX_QUERY_BLOCK", 16), ("INDEX_HEAD_BLOCK", 2),
                       ("ROW_BLOCK", 16), ("SEGMENTS", 3),
                       ("EXPERT_ROWS", 6)):
        monkeypatch.setattr(ref, name, size)
    blocked, sets_b = run()
    assert float(jnp.abs(whole - blocked).max()) < 1e-4
    # served_gaps of a padded sequence (a prompt of 20, 6 served, padded
    # to 50: the blocks past row 26 are skipped) = the logits' own
    served = jnp.argsort(whole[19:25], axis=-1)[:, -2]   # second best
    padded = tokens.at[26:].set(0)
    gap, best = jax.jit(lambda p, t, f, sv: ref.served_gaps(
        p, t, f, sv, cfg))(params, padded, np.int32(19), served)
    full = ref.logits(params, padded[:26], cfg)[19:25]
    assert np.array_equal(np.asarray(best), np.argmax(full, -1))
    want = full.max(-1) - jnp.take_along_axis(
        full, served[:, None], -1)[:, 0]
    assert float(jnp.abs(gap - want).max()) < 1e-4 and float(gap.min()) > 0
    for a, b in zip(sets, sets_b):
        assert a.sum(1).tolist() == [min(16, t + 1) for t in range(50)]
        assert (np.asarray(a) != np.asarray(b)).sum() <= 2   # near-ties


def test_reference_experts_gathered_or_masked_add_up_alike(monkeypatch):
    """A routed expert over the rows that picked it, gathered, and over
    every row under a mask where more picked it than ``EXPERT_ROWS``:
    both are the one sum (40 rows at the rehearsal's widths, where an
    expert is picked by a row in four: at 8 rows some experts gather
    and some do not, at 1 none gathers, at 40 and over none is tried)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights
    cell = harness.Cell(CELL, rehearse=True)
    ref, cfg = cell.module("reference"), cell.config
    spec = cfg["spec"]
    params = weights.draw(ref.param_shapes(cfg), 5)
    h = jnp.asarray(np.random.RandomState(5).randn(
        40, spec["hidden_size"]).astype(np.float32))
    layer = lambda: jax.jit(lambda p, h: ref.expert_layer(
        h, p, "l1_", spec))(params, h)
    want, picked, _ = layer()
    hits = [int((np.asarray(picked) == e).any(-1).sum())
            for e in range(spec["n_routed_experts"])]
    assert min(hits) <= 8 < max(hits), hits
    for most in (8, 1):
        monkeypatch.setattr(ref, "EXPERT_ROWS", most)
        got = layer()[0]
        assert float(jnp.abs(got - want).max()) < 1e-5 * float(
            jnp.abs(want).max()), most


# ---------------------------------------------------------------------------
# the cell's rehearsal, sound and broken
# ---------------------------------------------------------------------------
def _zero_index_keys_on_a_hit(monkeypatch):
    """The fault: a request admitted on a prefix hit finds the blocks
    it adopted with their index keys zeroed (as if adoption, or a
    fork, carried the latent leaf alone)."""
    from mxnet_tpu.serving.decode_engine import GenerationEngine
    honest = GenerationEngine._admit_paged
    zeroed = []

    def admit(self, model, dq, store):
        st = self._paged_state(model, store)
        was = [r for r in st.slots]
        honest(self, model, dq, store)
        bs = store.kv_block
        for i, r in enumerate(st.slots):
            if r is None or i < len(was) and was[i] is r:
                continue
            n = int(st.prog[i]) // bs       # whole blocks adopted
            for b in st.tables[i, :n]:
                st.pools = (st.pools[0],) + tuple(
                    p.at[:, :, int(b) * bs:(int(b) + 1) * bs].set(0)
                    for p in st.pools[1:])
                zeroed.append(int(b))

    monkeypatch.setattr(GenerationEngine, "_admit_paged", admit)
    return zeroed


def _every_key_selected(monkeypatch):
    """The fault: the selection replaced by "every key" (the indexer's
    ``index_topk`` read as the table's whole width)."""
    from mxnet_tpu.models import deepseek_v32
    honest = deepseek_v32.serving_spec
    monkeypatch.setattr(
        deepseek_v32, "serving_spec",
        lambda spec: dict(honest(spec), index_topk=10 ** 6))


def _a_token_altered(monkeypatch):
    from mxnet_tpu.serving.decode_engine import GenerationEngine
    vocab = harness.Cell(CELL, rehearse=True).config["spec"]["vocab_size"]
    honest = GenerationEngine._fetch_decode
    calls = {"n": 0}

    def altered(self, arr):
        out = honest(self, arr).copy()
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            out[:4] = (out[:4] + 1) % vocab
        return out

    monkeypatch.setattr(GenerationEngine, "_fetch_decode", altered)


@pytest.mark.parametrize("fault", ["none", "every-key-selected",
                                   "index-keys-zeroed-on-a-hit",
                                   "a-token-altered"])
def test_rehearsal_selects_and_fails_when_broken(capsys, monkeypatch,
                                                 fault):
    """``run.py --rehearse`` of the cell in this process.  Sound, it is
    ``correct`` with most compared requests on a prefix hit and well
    past the toy ``index_topk`` (16 of contexts to 160), a pool that
    fills and evicts, and every reader run.  Broken underneath in any
    of three ways, ``correct`` is false."""
    seen = None
    if fault == "every-key-selected":
        _every_key_selected(monkeypatch)
    elif fault == "index-keys-zeroed-on-a-hit":
        seen = _zero_index_keys_on_a_hit(monkeypatch)
    elif fault == "a-token-altered":
        _a_token_altered(monkeypatch)
    rc, last, said = _rehearse(capsys, "--seed", str(2**31 + 41),
                               "--trace", "1")
    assert rc == 0 and last["correct"] is (fault == "none")
    c = said["counters"]
    assert c["prefix_hits"] > 10 and c["shed"] == c["errors"] == 0
    assert c["prefix_evictions"] > 0 and c["cow_forks"] > 0
    assert said["requests_compared_sharing_a_prefix"] > 8
    assert said["prefix_hit_pct_by_slice"][0] > 30
    if fault == "none":
        assert {"kernel.dsa_index_time_pct", "kernel.dsa_attn_time_pct",
                "kernel.dsa_index_roofline_pct", "dsa.selected_pct",
                "kernel.dsa_attn_roofline_pct", "engine.prefix_hit_pct",
                "kernel.dsa_select_time_pct", "moe.tokens_per_expert",
                "kernel.dsa_select_roofline_pct",
                "engine.starved_pct"} <= set(
                    last["rehearsal"]["readers_ran"])
    else:
        bad = {k["name"] for k in said["checks"] if not k["ok"]}
        assert bad & {"flip_share", "flip_gap_mean", "token_gap_max"}
    if seen is not None:
        assert len(seen) > 10


def test_control_is_not_correct(capsys):
    rc, last, said = _rehearse(capsys, "--seed", "7", "--control")
    assert rc == 0 and last["correct"] is False
    assert not {k["name"]: k for k in said["checks"]}["flip_share"]["ok"]


# ---------------------------------------------------------------------------
# both programs for a described v5e, at the cell's real size
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


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_programs_fit_one_chip_and_leave_both_leaves_in_place(
        topo, monkeypatch, kind):
    """The store's own two programs (``paged_program``) of the cell as
    its file deploys it — 5 layers at the published widths, 64 slots of
    360 table entries, a chunk of 16 rows x 32, the pool of 6,144
    blocks in both leaves — compiled for a described v5e: under 15 GB
    by the compiler (weights and pools are its arguments), the three
    routes in it a layer, and no ``copy``, ``slice``, ``scatter``,
    ``fusion`` or ``gather`` that hands back something of either
    leaf's shape or of one of its layers' (the gather of the selected
    rows hands back ``rows x 2,048 x 640``)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import deepseek_v32 as ds
    from mxnet_tpu.pallas_ops import dispatch
    from mxnet_tpu.serving.program_store import chunk_rows, paged_program
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)
    cell = harness.Cell(CELL)
    cfg, dep = cell.config, cell.config["deploy"]
    spec = ds.serving_spec(cfg["spec"])
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    wdt = jnp.dtype(cfg["weights_dtype"])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    bb, bs = dep["batch_buckets"][-1], dep["kv_block"]
    width = -(-dep["kv_max"] // bs)
    shapes = jax.eval_shape(lambda: ds.pack_params(
        {k: jnp.zeros(s, wdt) for k, s in
         cell.module("reference").param_shapes(cfg).items()}, spec))
    params = {k: sds(v.shape, v.dtype) for k, v in shapes.items()}
    pools = tuple(sds(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: ds.init_pool(spec, dep["pool_blocks"], bs,
                             dep["kv_dtype"])))
    assert [p.shape[3] for p in pools] == [640, 128]
    if kind == "decode":
        pkind, rows, lq = "paged_step_sample", bb, 1
    else:
        pkind, rows, lq = "paged_chunk_sample", chunk_rows(bb), \
            dep["prefill_chunk"]
    fn, donate = paged_program(ds, spec, pkind, lq, bs, len(pools))
    args = (params,) + pools + (
        sds((rows, width), jnp.int32), sds((rows, lq), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.int32),
        sds((bb, 2), jnp.uint32), sds((rows,)), sds((rows,), jnp.int32),
        sds((rows,), jnp.bool_))
    if kind != "decode":
        args += (sds((rows,), jnp.int32),)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    text = compiled.as_text()
    print("deepseek-v32 %s rows=%d lq=%d: %.2f GB (arguments %.2f, "
          "scratch %.2f)" % (kind, rows, lq, total / 1e9,
                             m.argument_size_in_bytes / 1e9,
                             m.temp_size_in_bytes / 1e9))
    assert total < LIMIT_GB * 1e9
    assert len(re.findall(r"%dsa_index_scores[.\d]* = ", text)) == 5
    assert len(re.findall(r"%dsa_select_threshold[.\d]* = ", text)) == 5
    # a decode step attends over gathered rows, a chunk under the mask
    form = "dsa_mla_attention" + ("" if kind == "decode" else "_masked")
    assert len(re.findall(r"%dsa_mla_attention[_a-z]*[.\d]* = ", text)) \
        == len(re.findall(r"%" + form + r"[.\d]* = ", text)) == 5
    assert len(re.findall(r"%ragged-dot[-\w.]* = f32", text)) == 8
    assert "mla_paged_attention" not in text
    # no sort of the table's width: the selection counts
    assert not [ln for ln in text.splitlines()
                if " sort(" in ln and ",%d]" % (width * bs) in ln]
    L, _, R, _ = pools[0].shape
    pool_shaped = re.compile(r"bf16\[(?:%d,|1,)?1,%d,(?:640|128)\]"
                             % (L, R))
    moved = []
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(",
                       line)
        if hit and hit.group(2) in _MOVES_THE_POOL \
                and pool_shaped.search(hit.group(1)):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    # what a decode step's attention reads a layer: the gathered rows,
    # 2,048 a sequence; a chunk gathers nothing of the leaves
    got = re.findall(r"= (bf16\[\d+,\d+,640\])\S* gather\(", text)
    assert got == (["bf16[64,2048,640]"] * 5 if kind == "decode" else [])
