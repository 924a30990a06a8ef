"""The cell ``lfm2-24b-a2b.serve-agent-backlog``'s programs at its whole
size, compiled for a described v5e (``tests/_chip_compile_common.py``
says how).
"""
import re

import pytest

from mxnet_tpu.pallas_ops import dispatch

from _chip_compile_common import (_lfm2_program, chip,  # noqa: F401
                                  compiled_mode, compiled_paged_program,
                                  one_pass_tick_reads_the_experts_once,
                                  paged_program_leaves_the_pool_in_place,
                                  paged_programs_hand_the_kernel_its_blocks)

pytestmark = pytest.mark.quick


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_lfm2_program], ids=["lfm2-24b-a2b"])
def test_paged_program_leaves_the_pool_in_place(chip, compiled_mode,
                                                build, kind):
    paged_program_leaves_the_pool_in_place(chip, build, kind)


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_lfm2_cell_programs_fit_the_chip(chip, compiled_mode, kind):
    """The cell's two programs at the published widths, compiled for
    the described v5e: arguments (10.36 GB of weights, the 2.15 GB
    ``[K | V]`` leaf, the 0.47 GB state leaf) and scratch under 15 GB
    of the chip's 16; the grouped product eligible at both of this
    model's width pairs and in the program twice an expert layer under
    the name the benchmark's readers look for, none of another origin;
    the attention kernel once an attention layer with all four query
    heads of a KV head in its tile."""
    m, args, fn, compiled, routed = compiled_paged_program(
        _lfm2_program, chip, kind)
    rows = args[1 + len(m["pools"]) + 1].shape
    sorted_rows = rows[0] * rows[1] * m["spec"]["num_experts_per_tok"]
    assert dispatch.eligible_moe_experts(sorted_rows, 2048, 1536,
                                         "bfloat16")
    # both attention layers' calls bring all 8 pool heads in a copy
    assert routed["DotProductAttentionPaged.heads_per_copy=8"] == 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    # what the configuration's deploy_notes state: 12.97 GB of
    # arguments, 0.08 / 0.07 GB of scratch (the kernel's wider tiles
    # live in VMEM and add nothing here)
    assert abs(mem.argument_size_in_bytes - 12.97e9) < 0.02e9
    assert mem.temp_size_in_bytes < 0.1e9
    text = compiled.as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * 8, "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    assert " ragged-dot(" not in text
    attn = [ln for ln in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%paged_attention\S* = ", ln)]
    assert len(attn) == 2
    tile = "bf16[%d,8,%d,128]" % (rows[0], 4 * rows[1])
    assert all(tile in ln and "tpu_custom_call" in ln for ln in attn)


@pytest.mark.parametrize("build,attention,scratch_gb", [
    (_lfm2_program, {"paged_attention": 2}, 0.25),
], ids=["lfm2-24b-a2b"])
def test_one_pass_tick_reads_the_experts_once(chip, compiled_mode, build,
                                              attention, scratch_gb):
    one_pass_tick_reads_the_experts_once(chip, build, attention,
                                         scratch_gb)


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_lfm2_program], ids=["lfm2-24b-a2b"])
def test_paged_programs_hand_the_kernel_its_blocks(chip, compiled_mode,
                                                   build, kind):
    paged_programs_hand_the_kernel_its_blocks(chip, build, kind)
