"""Tier-1 collects ``tests/`` only; the benchmark's own tests live under
its ``paths`` (``benchmark/tests/``). This brings each of them in as a case
of this module, so the reducer the per-layer readers stand on is tested
with everything else."""
import json
import types

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_benchmark_harness",
                               "benchmark.tests.test_pool_copy_share",
                               "benchmark.tests.test_moe_metrics",
                               "benchmark.tests.test_ssm_metrics",
                               "benchmark.tests.test_latent_metrics",
                               "benchmark.tests.test_loop_metrics",
                               "benchmark.tests.test_looped_metrics",
                               "benchmark.tests.test_hybrid_metrics",
                               "benchmark.tests.test_prefill_padded_share",
                               "benchmark.tests.test_linear_metrics",
                               "benchmark.tests.test_prefill_prompts_per_program")

from benchmark.tests.test_benchmark_harness import *  # noqa: E402,F401,F403
from benchmark.tests.test_pool_copy_share import *  # noqa: E402,F401,F403
from benchmark.tests.test_moe_metrics import *  # noqa: E402,F401,F403
from benchmark.tests.test_loop_metrics import *  # noqa: E402,F401,F403
from benchmark.tests import test_benchmark_harness as _harness_tests  # noqa: E402
from benchmark.tests import test_latent_metrics as _latent_tests  # noqa: E402
from benchmark.tests import test_loop_metrics as _loop_tests  # noqa: E402
from benchmark.tests import test_looped_metrics as _looped_tests  # noqa: E402
from benchmark.tests.test_looped_metrics import looped_records  # noqa: E402,F401
from benchmark.tests.test_hybrid_metrics import hybrid_records  # noqa: E402,F401
from benchmark.tests import test_moe_metrics as _moe_tests  # noqa: E402
from benchmark.tests import test_ssm_metrics as _ssm_tests  # noqa: E402
from benchmark.tests import test_hybrid_metrics as _hybrid_tests  # noqa: E402
from benchmark.tests.test_prefill_padded_share import padded_records  # noqa: E402,F401
from benchmark.tests import test_prefill_padded_share as _padded_tests  # noqa: E402
from benchmark.tests.test_linear_metrics import linear_records  # noqa: E402,F401
from benchmark.tests import test_linear_metrics as _linear_tests  # noqa: E402
from benchmark.tests.test_prefill_prompts_per_program import *  # noqa: E402,F401,F403

# test_moe_metrics, test_ssm_metrics, test_latent_metrics,
# test_looped_metrics, test_hybrid_metrics and test_linear_metrics each have a
# ``test_the_cell_is_in_the_manifest_with_its_files`` and a
# ``test_the_mix_is_what_the_issue_says...``: the later files' cases come in
# under names of their own, so that each file's still counts
# (test_prefill_padded_share's likewise: its names are short).
for _prefix, _module in (("ssm", _ssm_tests), ("latent", _latent_tests),
                         ("looped", _looped_tests),
                         ("hybrid", _hybrid_tests),
                         ("padded", _padded_tests),
                         ("linear", _linear_tests)):
    for _name in dir(_module):
        if _name.startswith("test_"):
            globals()["test_%s_%s" % (_prefix, _name[len("test_"):])] = \
                getattr(_module, _name)


#: per-layer metrics that a later PR appended over cells that were there
#: (PR 46: ``prefill_padded_share``, the six cells of ``serve_out_tok_per_s``;
#: PR 51: ``prefill_prompts_per_program``, the seven).
#: Ouro's case counts the metrics its cell shares with the chat cell (21
#: when it was written), so the cells' cases run over the manifest less
#: these; where each stands is its own file's case
#: (``benchmark/tests/test_prefill_padded_share.py``).
_APPENDED_SINCE = ("prefill_padded_share", "prefill_prompts_per_program")


def _manifest_case_of(monkeypatch, module, cell,
                      case="test_the_cell_is_in_the_manifest_with_its_files",
                      appended=_APPENDED_SINCE):
    """A cell's ``test_the_cell_is_in_the_manifest_with_its_files`` (or
    another ``case`` of its file), every assert of it, over the manifest
    LESS the cells that later PRs appended behind it. OLMoE's case asks that its cell be the LAST of each shared
    metric's ``workloads``, Phi-4's that the cells of ``compile_s`` be the
    manifest's first six: each held until a later cell was appended (the
    files are the benchmark's to repair, PERF.md section 7). This then
    holds the whole manifest to the order of its cells: the later cells
    stand behind, nothing between."""
    whole = json.load(open(module.os.path.join(module.ROOT,
                                               "BENCHMARK.json")))
    cells = [c["name"] for c in whole["workloads"]]
    later = cells[cells.index(cell) + 1:]

    def load(f):
        doc = json.load(f)
        if f.name.endswith("BENCHMARK.json"):
            doc["per_layer"] = [m for m in doc["per_layer"]
                                if m["name"] not in appended]
            for m in doc["per_layer"] + doc["end_to_end"]:
                if "workloads" in m:
                    m["workloads"] = [c for c in m["workloads"]
                                      if c not in later]
        return doc

    monkeypatch.setattr(module, "json", types.SimpleNamespace(load=load))
    getattr(module, case)()
    for m in whole["per_layer"]:
        listed = m.get("workloads")
        if listed and cell in listed:
            assert listed == [c for c in cells if c in listed], m["name"]


def test_the_cell_is_in_the_manifest_with_its_files(monkeypatch):  # noqa: F811
    _manifest_case_of(monkeypatch, _moe_tests, "olmoe-chat-closed64")


def test_ssm_the_cell_is_in_the_manifest_with_its_files(monkeypatch):  # noqa: F811
    _manifest_case_of(monkeypatch, _ssm_tests, "phi4flash-reason-closed128")


def test_latent_the_cell_is_in_the_manifest_with_its_files(monkeypatch):  # noqa: F811
    _manifest_case_of(monkeypatch, _latent_tests, "dotsvlm1-chat-closed256")


def test_looped_the_cell_is_in_the_manifest_with_its_files(monkeypatch):  # noqa: F811
    _manifest_case_of(monkeypatch, _looped_tests, "ouro-chat-closed32")


def test_hybrid_the_cell_is_in_the_manifest_with_its_files(monkeypatch):  # noqa: F811
    """MiMo's case holds its seven metrics to list its cell ALONE: true
    until the tenth cell, whose "full" layers are the same kernel calls,
    was appended behind it."""
    _manifest_case_of(monkeypatch, _hybrid_tests, "mimov25-mixed-closed128")


def test_padded_the_manifest_appends_the_metric_behind_what_was_there(monkeypatch):  # noqa: F811,E501
    """PR 46's case names the six cells the metric had when it was
    appended; the tenth cell stands behind them."""
    _manifest_case_of(
        monkeypatch, _padded_tests, "mimov25-mixed-closed128",
        "test_the_manifest_appends_the_metric_behind_what_was_there", ())


def test_the_manifest_appends_the_nine_loop_metrics(monkeypatch):  # noqa: F811
    """The benchmark's case names the four cells the nine metrics had when
    PR 35 appended them; cells appended since stand behind those."""
    _manifest_case_of(monkeypatch, _loop_tests, "dotsvlm1-chat-closed256",
                      "test_the_manifest_appends_the_nine_loop_metrics")


def test_cells_files_and_the_one_four_chip_cell(monkeypatch):  # noqa: F811
    """The benchmark's case holds ``1 == max(1, cells // 4)``: false from
    the eighth cell on, though one four-chip cell of eight is inside the
    rule (at most a quarter of the cells, rounded down, and one always
    may). The file is the benchmark's to repair (PERF.md section 7). Its
    asserts, every one, run here over the seven cells its arithmetic held
    for, then over the four-chip cell with the cells appended since; and
    the rule itself over the whole manifest."""
    whole = _harness_tests.MANIFEST
    cells = whole["workloads"]
    for part in (cells[:7], [c for c in cells[:7] if c["chips"] == 4]
                 + cells[7:]):
        used = {c["config"] for c in part}
        monkeypatch.setattr(_harness_tests, "MANIFEST", dict(
            whole, workloads=part,
            configs=[c for c in whole["configs"] if c["name"] in used]))
        _harness_tests.test_cells_files_and_the_one_four_chip_cell()
    four = [c for c in cells if c["chips"] == 4]
    assert len(four) == 1 <= max(1, len(cells) // 4)
