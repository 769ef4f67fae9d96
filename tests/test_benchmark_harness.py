"""Tier-1 collects ``tests/`` only; the benchmark's own tests live under
its ``paths`` (``benchmark/tests/``). This brings each of them in as a case
of this module, so the reducer the per-layer readers stand on is tested
with everything else."""
import pytest

pytest.register_assert_rewrite("benchmark.tests.test_benchmark_harness",
                               "benchmark.tests.test_pool_copy_share",
                               "benchmark.tests.test_moe_metrics")

from benchmark.tests.test_benchmark_harness import *  # noqa: E402,F401,F403
from benchmark.tests.test_pool_copy_share import *  # noqa: E402,F401,F403
from benchmark.tests.test_moe_metrics import *  # noqa: E402,F401,F403
