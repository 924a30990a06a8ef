"""Tier-1 runs the benchmark's own fast tests (benchmark/tests: the
output's contract, the cost tables, the statistics and the peaks table,
the traffic generator, the trace reducer).  They are collected from
where they live, not copied: the instrument every PR is judged by is
tested by the run every PR is held to.  The benchmark's slow modules
(rehearsals, references, real-size compiles: minutes) stay with
``python -m pytest benchmark/tests``."""
import pytest

_MODULES = ["benchmark.tests.test_%s" % m
            for m in ("contract", "costs", "stats", "traffic", "xplane")]
pytest.register_assert_rewrite(*_MODULES)

from benchmark.tests.test_contract import *  # noqa: E402,F401,F403
from benchmark.tests.test_costs import *  # noqa: E402,F401,F403
from benchmark.tests.test_stats import *  # noqa: E402,F401,F403
from benchmark.tests.test_traffic import *  # noqa: E402,F401,F403
from benchmark.tests.test_xplane import *  # noqa: E402,F401,F403
