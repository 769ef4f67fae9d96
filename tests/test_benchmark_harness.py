"""Tier-1 collects ``tests/`` only; the benchmark's own tests live under
its ``paths`` (``benchmark/tests/``). This brings each of them in as a case
of this module, so the reducer the per-layer readers stand on is tested
with everything else."""
import pytest

pytest.register_assert_rewrite("benchmark.tests.test_benchmark_harness",
                               "benchmark.tests.test_pool_copy_share",
                               "benchmark.tests.test_moe_metrics",
                               "benchmark.tests.test_ssm_metrics")

from benchmark.tests.test_benchmark_harness import *  # noqa: E402,F401,F403
from benchmark.tests.test_pool_copy_share import *  # noqa: E402,F401,F403
from benchmark.tests.test_moe_metrics import *  # noqa: E402,F401,F403
from benchmark.tests import test_ssm_metrics as _ssm_tests  # noqa: E402

# test_moe_metrics and test_ssm_metrics each have a
# ``test_the_cell_is_in_the_manifest_with_its_files`` and a
# ``test_the_mix_is_what_the_issue_says...``: the state-space file's cases
# come in under names of their own, so that each file's still counts.
for _name in dir(_ssm_tests):
    if _name.startswith("test_"):
        globals()["test_ssm_" + _name[len("test_"):]] = getattr(_ssm_tests,
                                                                _name)


def test_the_cell_is_in_the_manifest_with_its_files(monkeypatch):  # noqa: F811
    """OLMoE's cell, by ``benchmark/tests/test_moe_metrics.py``'s own case,
    every assert of it. That case asks that the cell be the LAST of each
    shared metric's ``workloads``, which held until a later cell was
    appended (PR 31; the file is the benchmark's to repair, PERF.md section
    7). So the case runs over the manifest LESS the cells that later PRs
    appended behind OLMoE's, and this one then holds the whole manifest to
    the order of its cells: the later cells stand behind, nothing between."""
    import json
    import types

    from benchmark.tests import test_moe_metrics as moe

    cell = "olmoe-chat-closed64"
    whole = json.load(open(moe.os.path.join(moe.ROOT, "BENCHMARK.json")))
    cells = [c["name"] for c in whole["workloads"]]
    later = cells[cells.index(cell) + 1:]

    def load(f):
        doc = json.load(f)
        if f.name.endswith("BENCHMARK.json"):
            for m in doc["per_layer"]:
                if cell in m.get("workloads", ()):
                    m["workloads"] = [c for c in m["workloads"]
                                      if c not in later]
        return doc

    monkeypatch.setattr(moe, "json", types.SimpleNamespace(load=load))
    moe.test_the_cell_is_in_the_manifest_with_its_files()
    for m in whole["per_layer"]:
        listed = m.get("workloads")
        if listed and cell in listed:
            assert listed == [c for c in cells if c in listed], m["name"]
