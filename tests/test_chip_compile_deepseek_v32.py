"""DeepSeek-V3.2's two paged programs over BOTH token leaves, compiled
for a described v5e (``tests/_chip_compile_common.py`` says how)."""
import pytest

from _chip_compile_common import (_deepseek32_program, chip,  # noqa: F401
                                  compiled_mode,
                                  paged_program_leaves_the_pool_in_place)

pytestmark = pytest.mark.quick


@pytest.mark.parametrize("kind", ["decode", "prefill-chunk"])
@pytest.mark.parametrize("build", [_deepseek32_program], ids=["deepseek-v32"])
def test_paged_program_leaves_the_pool_in_place(chip, compiled_mode,
                                                build, kind):
    paged_program_leaves_the_pool_in_place(chip, build, kind)

