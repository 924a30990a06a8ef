"""What the ``tests/test_chip_compile*.py`` files share: the described
v5e chip, the served models' programs at their cells' sizes, and the
checks that more than one model's file runs.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2,
rehearsal 3).  Nothing runs: a compile that passes is not a chip run.
One file a model, so that ``--dist loadfile`` spreads the compiles over
the workers; a program that several tests read is compiled once a file
(:func:`compiled_paged_program`).
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.pallas_ops import dispatch
from mxnet_tpu.pallas_ops import paged_attention as pa

F32, BF16, I8, I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
# chip_smoke.py's LM: 16 heads x 128, 2048 wide, vocabulary 32768,
# batch 8 x sequence 1024; serving at batch bucket 8, 64-token blocks
B, H, L, D, W, V, BS, T = 8, 16, 1024, 128, 2048, 32768, 64, 16
ROWS = B * L


@pytest.fixture(scope="module")
def chip():
    """``struct(shape, dtype)`` placing operands on one described v5e
    chip; the persistent compile cache is off around the module (such a
    compile can be written to it but never read back without a chip)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip("cannot describe a v5e topology: %s" % e)
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_mode(monkeypatch):
    """Eligibility as it answers on a TPU (the probe sees this CPU)."""
    monkeypatch.setattr(dispatch, "_on_tpu", lambda: True)


# The paged step programs at the served models' widths, two layers
# deep, compiled as the store compiles them (``paged_program``): the
# decode step over the slots and the prompt chunk over
# ``chunk_rows(slots)`` rows beside the slots' key chains.  The KV pool
# is addressed in place from entry to exit.  A scatter on the pool, or
# a layer of it sliced out for the kernel, makes the compiler relay the
# whole pool around the program (docs/architecture/decode_engine.md,
# "The pool stays where it is").
LAYERS = 2
_MOVES_THE_POOL = ("copy", "slice", "scatter", "fusion", "gather")


def _lm_program(chip):
    """``lm2048``'s widths (16 heads x 128, 2048 wide), 16 slots of 16
    blocks of 64 tokens, chunks of 32, fp32 pools."""
    lm = importlib.import_module("mxnet_tpu.models.transformer_lm")
    spec = lm.lm_spec(num_layers=LAYERS, num_hidden=W, num_heads=H,
                      vocab_size=V)
    net = lm.get_symbol(seq_len=8, **spec)
    shapes, _, _ = net.infer_shape(data=(1, 8), softmax_label=(1, 8))
    params = {n: chip(s) for n, s in zip(net.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    pool = chip((LAYERS, H, (16 * T + 1) * BS, D))
    return dict(model=lm, spec=spec, params=params, pools=(pool, pool),
                slots=16, width=T, chunk=32, kernels=LAYERS,
                pool_shaped=r"f32\[(?:%d,|1,)?%d,%d,%d\]"
                % ((LAYERS,) + pool.shape[1:]))


def _deepseek_program(chip):
    """DeepSeek-V3's published widths, one dense and one expert layer
    of 16 held experts, 64 slots of 104 blocks of 64 tokens, chunks of
    32, bfloat16 weights and latent pool."""
    from mxnet_tpu.models import deepseek_v3 as ds
    spec = ds.serving_spec({
        "num_hidden_layers": LAYERS, "first_k_dense_replace": 1,
        "hidden_size": 7168, "num_attention_heads": 128,
        "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "n_routed_experts": 16,
        "router_width": 256, "n_shared_experts": 1,
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "vocab_size": 16160,
        "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}})
    packed = jax.eval_shape(lambda: ds.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in ds.param_shapes(spec).items()}, spec))
    params = {k: chip(v.shape, v.dtype) for k, v in packed.items()}
    pool = chip((LAYERS, 1, (64 * 104 + 1) * 64, ds.latent_width(spec)),
                BF16)
    return dict(model=ds, spec=spec, params=params, pools=(pool,),
                slots=64, width=104, chunk=32, kernels=LAYERS,
                pool_shaped=r"bf16\[(?:%d,|1,)?1,%d,%d\]"
                % ((LAYERS,) + pool.shape[2:]))


def _deepseek32_program(chip):
    """``_deepseek_program`` with DeepSeek-V3.2's indexer (64 heads of
    128, 2,048 kept): TWO token leaves on the one table, the latent
    rows and the index keys, 64 slots of 360 blocks over a pool of
    6,144 as ``deepseek-v32.serve-longdoc-backlog`` has them."""
    from mxnet_tpu.models import deepseek_v32 as ds
    m = _deepseek_program(chip)
    spec = ds.serving_spec(dict(
        m["spec"], index_n_heads=64, index_head_dim=128,
        index_topk=2048))
    packed = jax.eval_shape(lambda: ds.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in ds.param_shapes(spec).items()}, spec))
    pools = tuple(chip(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: ds.init_pool(spec, 6144, 64, BF16)))
    assert [p.shape[3] for p in pools] == [640, 128]
    # four calls a layer: the indexer, the selection, the sparse
    # attention, the experts' (the dense layer: three)
    return dict(model=ds, spec=spec,
                params={k: chip(v.shape, v.dtype)
                        for k, v in packed.items()},
                pools=pools, slots=64, width=360, chunk=32,
                kernels=3 * LAYERS,
                pool_shaped=r"bf16\[(?:%d,|1,)?1,%d,(?:640|128)\]"
                % (LAYERS, pools[0].shape[2]))


def _lfm2_program(chip):
    """The cell ``lfm2-24b-a2b.serve-agent-backlog`` as its
    configuration file deploys it: LFM2-24B-A2B's published widths, all
    nine layers (7 convolution, 2 attention, 8 of 64 experts), 128
    slots of 64 blocks of 64 tokens, bfloat16 weights, ``[K | V]`` rows
    and convolution state."""
    import json
    from mxnet_tpu.models import lfm2_moe as lfm
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "lfm2-24b-a2b.json")) as f:
        cfg = json.load(f)
    deploy = cfg["deploy"]
    spec = lfm.serving_spec({k: v for k, v in cfg["spec"].items()
                             if k != "arch"})
    packed = jax.eval_shape(lambda: lfm.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in lfm.param_shapes(spec).items()}, spec))
    params = {k: chip(v.shape, v.dtype) for k, v in packed.items()}
    slots, = deploy["batch_buckets"]
    width = deploy["kv_max"] // deploy["kv_block"]
    assert deploy["kv_block"] == BS
    pools = tuple(chip(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: lfm.init_pool(spec, slots * width + 1, BS, "bfloat16")))
    return dict(model=lfm, spec=spec, params=params, pools=pools,
                slots=slots, width=width, chunk=deploy["prefill_chunk"],
                kernels=2 + 2 * 8,
                pool_shaped="|".join(
                    r"bf16\[(?:%d,|1,)?%d,%d,%d\]" % a.shape
                    for a in pools))


def _cohere2_program(chip):
    """The cell ``command-a-plus.serve-ragmix-backlog`` as its
    configuration file deploys it: Command A+'s published widths, one
    period (three window layers, one full), 16 of 128 experts, 64 slots
    of 256 blocks of 64 tokens in EACH of the two classes of block (a
    table a class, side by side), bfloat16 weights and ``K``/``V``
    rows."""
    import json
    from mxnet_tpu.models import cohere2_moe as co
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "command-a-plus.json")) as f:
        cfg = json.load(f)
    deploy = cfg["deploy"]
    spec = co.serving_spec(cfg["spec"])
    packed = jax.eval_shape(lambda: co.pack_params(
        {k: jnp.zeros(v, BF16)
         for k, v in co.param_shapes(spec).items()}, spec))
    params = {k: chip(v.shape, v.dtype) for k, v in packed.items()}
    slots, = deploy["batch_buckets"]
    assert deploy["kv_block"] == BS
    pools = tuple(chip(a.shape, a.dtype) for a in jax.eval_shape(
        lambda: co.init_pool(spec, deploy["pool_blocks"], BS,
                             "bfloat16")))
    return dict(model=co, spec=spec, params=params, pools=pools,
                slots=slots, chunk=deploy["prefill_chunk"],
                width=len(co.cache_classes(spec))
                * (deploy["kv_max"] // BS),
                kernels=4 + 2 * 4,
                pool_shaped="|".join(
                    r"bf16\[(?:%d,|1,)?%d,%d,%d\]" % a.shape
                    for a in pools))


def _paged_program_args(build, chip, kind):
    """``(the build, operands, program, donated)`` of a store's decode
    or compacted prompt-chunk program for the described chip, or
    (``one-pass``) of the tick that runs both as two row groups."""
    from mxnet_tpu.serving.program_store import chunk_rows, paged_program

    m = build(chip)
    slots = m["slots"]
    # a one-pass store's two programs keep the slots' pending tokens on
    # the device (what its engine dispatches: the decode step too)
    pending = hasattr(m["model"], "paged_step_groups")
    if kind == "decode":
        pkind, rows, lq = "paged_step_sample", slots, 1
    else:
        pkind = "paged_tick_sample" if kind == "one-pass" \
            else "paged_chunk_sample"
        rows, lq = chunk_rows(slots), m["chunk"]
        assert rows == slots // 4
    fn, donate = paged_program(m["model"], m["spec"], pkind, lq, BS,
                               len(m["pools"]), pending=pending)
    args = (m["params"],) + m["pools"] + (
        chip((rows, m["width"]), I32), chip((rows, lq), I32),
        chip((rows,), I32), chip((rows,), I32),
        chip((slots, 2), jnp.uint32), chip((rows,)), chip((rows,), I32),
        chip((rows,), jnp.bool_))
    if kind != "decode":
        args += (chip((rows,), I32),)
    if kind == "one-pass":      # the decode group behind the chunk,
        # then the chunk rows' own chains
        args += (chip((slots, m["width"]), I32), chip((slots, 1), I32),
                 chip((slots,), I32), chip((slots,), I32),
                 chip((slots,)), chip((slots,), I32),
                 chip((slots,), jnp.bool_), chip((rows, 2), jnp.uint32))
    if kind == "one-pass" or (kind == "decode" and pending):
        args += (chip((slots,), I32), chip((slots,), jnp.bool_))
    return m, args, fn, donate


_COMPILED = {}


def compiled_paged_program(build, chip, kind):
    """``(the build, operands, program, compiled, dispatch counts)`` of
    :func:`_paged_program_args`'s program compiled for the described
    chip, once a file for each ``(build, kind)``: the tests that read
    one program's text, memory analysis or routing read the same
    compile.  Call it under ``compiled_mode``."""
    key = (build, kind)
    if key not in _COMPILED:
        m, args, fn, donate = _paged_program_args(build, chip, kind)
        dispatch.reset_dispatch_stats()
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args) \
            .compile()
        _COMPILED[key] = (m, args, fn, compiled,
                          dict(dispatch.dispatch_stats()))
    return _COMPILED[key]


def moved_pool_leaves(m, text):
    """The instructions of ``text`` that hand back something of a pool
    leaf's shape by moving it."""
    pool_shaped = re.compile(m["pool_shaped"])
    moved = []
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(",
                       line)
        if hit and hit.group(2) in _MOVES_THE_POOL \
                and pool_shaped.search(hit.group(1)):
            moved.append(line.strip()[:160])
    return moved


def paged_program_leaves_the_pool_in_place(chip, build, kind):
    """``test_paged_program_leaves_the_pool_in_place`` of every model's
    file: a case a (build, kind)."""
    m, _, fn, compiled, _ = compiled_paged_program(build, chip, kind)
    text = compiled.as_text()
    assert fn.__name__ == "paged_" + kind.replace("-", "_")
    # the attention kernel is in it, a call a layer
    assert text.count("tpu_custom_call") >= m["kernels"]
    moved = moved_pool_leaves(m, text)
    assert not moved, "\n".join(moved)
    if build is _deepseek32_program:
        # both leaves: the indexer, the selection and the sparse
        # attention a layer and no dense latent walk; the one gather of
        # a leaf hands back a decode step's selected rows, 2,048 a
        # sequence, never a leaf or a layer of one; a chunk walks under
        # the mask and gathers nothing; nothing sorts the table's width
        form = "dsa_mla_attention" + ("" if kind == "decode"
                                      else "_masked")
        for name in ("dsa_index_scores", "dsa_select_threshold", form):
            assert len(re.findall(r"%%%s[.\d]* = " % name, text)) \
                == LAYERS, name
        assert "mla_paged_attention" not in text
        gathers = re.findall(r"= (bf16\[\d+,\d+,640\])\S* gather\(", text)
        assert gathers == (["bf16[64,2048,640]"] * LAYERS
                           if kind == "decode" else [])
        assert not [ln for ln in text.splitlines()
                    if " sort(" in ln and ",23040]" in ln]
    if build is _lm_program:
        pool = m["pools"][0]
        layer_bytes = pool.size // LAYERS * pool.dtype.itemsize
        assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes


def one_pass_tick_reads_the_experts_once(chip, build, attention,
                                         scratch_gb):
    """The one-pass tick of the three expert model modules
    (``paged_tick_sample``: the slots' decode rows and the compacted
    prompt chunk as two row groups of one step) compiled for the
    described v5e at each cell's whole size: named so that what counts
    step programs by ``jit_paged_prefill_chunk`` counts it; the grouped
    product twice an expert layer, as in EACH of the two programs it
    stands for, so the experts are streamed once a tick; every
    attention kernel twice a layer, once a group, with the shapes the
    two programs call it with; no pool leaf moved; and the chunk
    program's scratch with the decode group's rows beside it, far from
    the chip's 16 GB."""
    m, args, fn, compiled, _ = compiled_paged_program(build, chip,
                                                      "one-pass")
    assert fn.__name__ == "paged_prefill_chunk_tick"
    slots, rows, lq = m["slots"], m["slots"] // 4, m["chunk"]
    layers = m["spec"]["num_hidden_layers"] \
        - m["spec"]["first_k_dense_replace"]
    text = compiled.as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * layers, "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    # the sorted rows of both groups in one product
    picks = m["spec"]["num_experts_per_tok"]
    assert all("[%d," % ((slots + rows * lq) * picks) in ln
               for ln in named)
    for name, calls in attention.items():
        attn = [ln for ln in text.splitlines() if re.match(
            r"\s*(?:ROOT )?%%%s\S* = " % name, ln)]
        assert len(attn) == 2 * calls, (name, len(attn))
        # a group each: the decode rows' call and the chunk rows'
        firsts = sorted(int(re.search(r"= \w+\[(\d+),", ln).group(1))
                        for ln in attn)
        assert firsts == [rows] * calls + [slots] * calls, (name, firsts)
    moved = moved_pool_leaves(m, text)
    assert not moved, "\n".join(moved)
    mem = compiled.memory_analysis()
    print("one-pass %s: arguments %.2f GB, scratch %.2f GB"
          % (build.__name__, mem.argument_size_in_bytes / 1e9,
             mem.temp_size_in_bytes / 1e9))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    assert mem.temp_size_in_bytes < scratch_gb * 1e9



# (build, kind): the attention kernel's calls as the program's trace
# holds them: (name, grid, Q tile, K/V block, K and V operands, whether
# the call asks for more VMEM than every call gets)
KERNEL_CALLS = {
    # one query head a pool head: the grid and the blocks it always had
    ("lm2048", "decode"): [
        ("paged_attention", (16, 16, 1, 16), (1, 1, 1, 128),
         (1, 1, 64, 128), 2, False)] * LAYERS,
    ("lm2048", "prefill-chunk"): [
        ("paged_attention", (4, 16, 1, 16), (1, 1, 32, 128),
         (1, 1, 64, 128), 2, False)] * LAYERS,
    # 4 query heads a pool head, [K | V] rows: all 8 pool heads a copy,
    # 16 table entries of 64 a step
    ("lfm2-24b-a2b", "decode"): [
        ("paged_attention", (128, 1, 1, 4), (1, 8, 4, 128),
         (1, 8, 64, 128), 16, False)] * 2,
    ("lfm2-24b-a2b", "prefill-chunk"): [
        ("paged_attention", (32, 1, 1, 4), (1, 8, 128, 128),
         (1, 8, 64, 128), 16, False)] * 2,
    # 16 query heads a pool head, K and V apart; the window layers walk
    # 5 (a decode step) or 6 (a chunk) groups of the table's 16
    ("command-a-plus", "decode"): [
        ("window_paged_attention", (64, 1, 1, 5), (1, 8, 16, 128),
         (1, 8, 64, 128), 32, False)] * 3 + [
        ("paged_attention", (64, 1, 1, 16), (1, 8, 16, 128),
         (1, 8, 64, 128), 32, False)],
    ("command-a-plus", "prefill-chunk"): [
        ("window_paged_attention", (16, 1, 1, 6), (1, 8, 512, 128),
         (1, 8, 64, 128), 32, True)] * 3 + [
        ("paged_attention", (16, 1, 1, 16), (1, 8, 512, 128),
         (1, 8, 64, 128), 32, True)],
}


def paged_programs_hand_the_kernel_its_blocks(chip, build, kind):
    """What each served model's two programs hand the paged kernel at
    the cells' shapes (traced, not compiled: the compiles are the tests
    above): ``lm2048``'s the grid ``(rows, 16, 1, 16)`` and blocks of
    ONE head ``(1, 1, 64, 128)`` they always had; the grouped-query
    models' a grid without a head axis and blocks of all 8 pool heads
    ``(1, 8, 64, 128)``, ``KV_GROUP`` 16 of them for K (and for V), the
    VMEM rule lowering nothing and asking for more than Mosaic's 16 MiB
    only for Command A+'s chunk, whose Q tile is 512 rows of 8 heads."""
    from mxnet_tpu.test_utils import pallas_calls

    m, args, fn, _ = _paged_program_args(build, chip, kind)
    calls = [c for c in pallas_calls(fn, *args)
             if c[0].endswith("paged_attention")]
    got = [(name, grid, blocks[0], blocks[1], len(blocks) - 2,
            limit is not None) for name, grid, blocks, limit in calls]
    config = {_lm_program: "lm2048", _lfm2_program: "lfm2-24b-a2b",
              _cohere2_program: "command-a-plus"}[build]
    assert got == KERNEL_CALLS[(config, kind)]
    assert all(blocks[1:-1] == [blocks[1]] * (len(blocks) - 2)
               and blocks[-1] == blocks[0] for _, _, blocks, _ in calls)
    assert all(limit is None or pa._VMEM_DEFAULT < limit
               <= pa._VMEM_BUDGET + pa._VMEM_DEFAULT
               for _, _, _, limit in calls)
