"""Tier-1 runs the benchmark's own fast tests (benchmark/tests: the
output's contract, the cost tables, the statistics and the peaks table,
the traffic generator, the trace reducer).  They are collected from
where they live, not copied: the instrument every PR is judged by is
tested by the run every PR is held to.  The benchmark's slow modules
(rehearsals, references, real-size compiles: minutes) stay with
``python -m pytest benchmark/tests``."""
import pytest

_MODULES = ["benchmark.tests.test_%s" % m
            for m in ("contract", "costs", "stats", "traffic", "xplane",
                      "deepseek_v32", "openpangu_ultra_moe")]
pytest.register_assert_rewrite(*_MODULES)

from benchmark.tests.test_contract import *  # noqa: E402,F401,F403
from benchmark.tests.test_costs import *  # noqa: E402,F401,F403
from benchmark.tests.test_stats import *  # noqa: E402,F401,F403
from benchmark.tests.test_traffic import *  # noqa: E402,F401,F403
from benchmark.tests.test_xplane import *  # noqa: E402,F401,F403
# the two newest configurations' files, costs, mixes and readers
# (seconds; their rehearsals and real-size compiles stay with
# benchmark/tests)
from benchmark.tests.test_openpangu_ultra_moe import (  # noqa: E402,F401
    test_a_program_without_the_model_fails_at_once,
    test_openpangu_costs_of_the_published_widths,
    test_openpangu_file_holds_the_catalogs_row,
    test_openpangu_readers_on_a_recorded_dispatch,
    test_openpangu_traffic_is_the_issues)
from benchmark.tests.test_deepseek_v32 import (  # noqa: E402,F401
    test_costs_of_the_published_widths,
    test_readers_on_a_recorded_dispatch,
    test_reference_blocks_and_segments_do_not_change_its_answer,
    test_reference_experts_gathered_or_masked_add_up_alike,
    test_the_file_holds_the_catalogs_row, test_the_traffic_is_the_issues)
