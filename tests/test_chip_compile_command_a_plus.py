"""The cell ``command-a-plus.serve-ragmix-backlog``'s programs at its
whole size, compiled for a described v5e
(``tests/_chip_compile_common.py`` says how).
"""
import re

import pytest

from mxnet_tpu.pallas_ops import dispatch

from _chip_compile_common import (_cohere2_program, chip,  # noqa: F401
                                  compiled_mode, compiled_paged_program,
                                  one_pass_tick_reads_the_experts_once,
                                  paged_program_leaves_the_pool_in_place,
                                  paged_programs_hand_the_kernel_its_blocks)

pytestmark = pytest.mark.quick


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_cohere2_program], ids=["command-a-plus"])
def test_paged_program_leaves_the_pool_in_place(chip, compiled_mode,
                                                build, kind):
    paged_program_leaves_the_pool_in_place(chip, build, kind)


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_command_a_plus_cell_programs_fit_the_chip(chip, compiled_mode,
                                                   kind):
    """The cell's two programs at the published widths, compiled for
    the described v5e: arguments (9.47 GB of weights, the full class's
    0.81 GB of ``K`` and ``V`` rows, the window class's 2.42 GB) and
    scratch under 15 GB of the chip's 16; the grouped product in the
    program twice a layer under the name the benchmark's readers look
    for; the attention kernel once a layer with all sixteen query heads
    of a KV head in its tile: the full layer's under the name
    ``kernel.gqa_attn_*`` read, the three window layers' under their
    own, and these walk 5 (a decode step) or 6 (a chunk) groups of 16
    blocks where the full layer's walks the table's 16.  Read here:
    12.69 GB of arguments, 0.15 GB (decode) and 0.20 GB (a chunk of 32;
    0.47 GB at 64) of scratch."""
    m, args, fn, compiled, routed = compiled_paged_program(
        _cohere2_program, chip, kind)
    rows = args[1 + len(m["pools"]) + 1].shape
    assert args[1 + len(m["pools"])].shape == (rows[0], 2 * 256)
    sorted_rows = rows[0] * rows[1] * m["spec"]["num_experts_per_tok"]
    assert dispatch.eligible_moe_experts(sorted_rows, 4096, 4096,
                                         "bfloat16")
    # all four layers' calls bring all 8 pool heads in a copy
    assert routed["DotProductAttentionPaged.heads_per_copy=8"] == 4
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9
    # what the configuration's deploy_notes state: 12.69 GB of
    # arguments, 0.15 / 0.20 GB of scratch (the kernel's wider tiles
    # live in VMEM and add nothing here)
    assert abs(mem.argument_size_in_bytes - 12.69e9) < 0.02e9
    assert mem.temp_size_in_bytes < 0.25e9
    text = compiled.as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * 4, "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    assert " ragged-dot(" not in text
    tile = "bf16[%d,8,%d,128]" % (rows[0], 16 * rows[1])
    for name, calls in (("paged_attention", 1),
                        ("window_paged_attention", 3)):
        attn = [ln for ln in text.splitlines() if re.match(
            r"\s*(?:ROOT )?%%%s\S* = " % name, ln)]
        assert len(attn) == calls, (name, len(attn))
        assert all(tile in ln and "tpu_custom_call" in ln for ln in attn)
    print("command-a-plus %s: arguments %.2f GB, scratch %.2f GB"
          % (kind, mem.argument_size_in_bytes / 1e9,
             mem.temp_size_in_bytes / 1e9))


@pytest.mark.parametrize("build,attention,scratch_gb", [
    (_cohere2_program, {"paged_attention": 1,
                        "window_paged_attention": 3}, 0.45),
], ids=["command-a-plus"])
def test_one_pass_tick_reads_the_experts_once(chip, compiled_mode, build,
                                              attention, scratch_gb):
    one_pass_tick_reads_the_experts_once(chip, build, attention,
                                         scratch_gb)


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_cohere2_program], ids=["command-a-plus"])
def test_paged_programs_hand_the_kernel_its_blocks(chip, compiled_mode,
                                                   build, kind):
    paged_programs_hand_the_kernel_its_blocks(chip, build, kind)
