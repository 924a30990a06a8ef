"""DeepSeek-V3's two paged programs at its published widths, compiled for
a described v5e (``tests/_chip_compile_common.py`` says how): the pool
in place and the repo's grouped product in the expert layer (its
one-pass tick is compiled at the end of ``tests/test_chip_compile.py``,
for the files' balance)."""
import re

import pytest

from _chip_compile_common import (LAYERS, _deepseek_program,  # noqa: F401
                                  chip, compiled_mode,
                                  compiled_paged_program,
                                  paged_program_leaves_the_pool_in_place)

pytestmark = pytest.mark.quick


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_deepseek_program], ids=["deepseek-v3"])
def test_paged_program_leaves_the_pool_in_place(chip, compiled_mode,
                                                build, kind):
    paged_program_leaves_the_pool_in_place(chip, build, kind)


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
def test_expert_layer_runs_the_repos_grouped_product(chip, compiled_mode,
                                                     kind):
    """The ``deepseek_v3`` step compiled for the described v5e holds
    the repo's grouped product, twice an expert layer, under the name
    the benchmark's readers look for; no grouped product of another
    origin (XLA's own ``ragged-dot`` is an instruction or a fusion of
    that name, never a ``custom-call`` to ``tpu_custom_call``); and no
    copy, transpose or fusion that hands back something of an expert
    stack's shape: the stacks are read where they lie."""
    m, args, fn, compiled, _ = compiled_paged_program(_deepseek_program,
                                                      chip, kind)
    text = compiled.as_text()
    named = [ln for ln in text.splitlines()
             if re.match(r"\s*(?:ROOT )?%ragged-dot\S* = ", ln)]
    assert len(named) == 2 * (LAYERS - 1), "\n".join(named)
    assert all("ragged-dot_grouped_matmul" in ln
               and "tpu_custom_call" in ln for ln in named)
    assert " ragged-dot(" not in text
    stack = re.compile(r"bf16\[16,(?:7168,4096|2048,7168|4096,7168"
                       r"|7168,2048)\]")
    moved = [ln.strip()[:160] for ln in text.splitlines()
             for hit in [re.match(
                 r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([a-z-]+)\(", ln)]
             if hit and hit.group(2) != "parameter"
             and stack.search(hit.group(1))]
    assert not moved, "\n".join(moved)

