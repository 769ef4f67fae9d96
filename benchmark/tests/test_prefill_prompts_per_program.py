"""`prefill_prompts_per_program`'s reader
(`layer_metrics/prefill_prompts_per_program.py`) over hand-made records,
over a tiny engine's own, and its entry in the manifest.

    python3 -m pytest benchmark/tests/test_prefill_prompts_per_program.py -q
"""
import collections
import json
import os
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.layer_metrics import _loop, prefill_prompts_per_program  # noqa: E402

NAME = "prefill_prompts_per_program"
#: the seven cells that report `serve_out_tok_per_s`, in the manifest's order
CELLS = ["gpt2m-chat-closed64", "olmoe-chat-closed64",
         "phi4flash-reason-closed128", "dotsvlm1-chat-closed256",
         "ouro-chat-closed32", "mimov25-mixed-closed128",
         "solaropen2-reason-closed192"]
Rec = collections.namedtuple("Rec", "prefills prefill_programs",
                             defaults=(0,))
Old = collections.namedtuple("Old", "prefills prefill_rows")    # a parent's
OBS = {"kind": "serve", "before": {"t": 100.0}, "after": {"t": 130.0},
       "window_s": 30.0, "trace": None}


@pytest.fixture
def program_records(monkeypatch):
    """Hand the reader these records in place of the process's rings."""
    def use(recs):
        monkeypatch.setattr(_loop, "records", lambda _obs: recs or None)
    return use


def test_prompts_over_programs(program_records):
    # a ramp's step of 60 prompts in 11 packs, two steady groups (five
    # prompts in two programs, one alone), a step that decoded only
    program_records([Rec(60, 11), Rec(5, 2), Rec(0), Rec(1, 1)])
    assert prefill_prompts_per_program.read(OBS) == pytest.approx(66 / 14)
    # a model with state slots: a program a prompt, whatever the group
    program_records([Rec(4, 4), Rec(7, 7)])
    assert prefill_prompts_per_program.read(OBS) == 1.0


def test_nothing_to_read_of_programs(program_records, monkeypatch):
    # the parent of the PR that added the field: nothing, and no error
    program_records([Old(3, 1536), Old(0, 0)])
    assert prefill_prompts_per_program.read(OBS) is None
    # a window in which no prompt was prefilled, and one with no step
    program_records([Rec(0), Rec(0)])
    assert prefill_prompts_per_program.read(OBS) is None
    program_records([])
    assert prefill_prompts_per_program.read(OBS) is None
    monkeypatch.undo()
    # a driver that serves nothing; a window of the real rings that is empty
    assert prefill_prompts_per_program.read(
        {"kind": "fit", "trace": None}) is None
    assert prefill_prompts_per_program.read(
        dict(OBS, before={"t": 1.0}, after={"t": 2.0})) is None


def test_it_reads_an_engines_own_packs():
    """Through the channel the benchmark uses: the engine's ring, windowed
    by `loop_records` between two readings of the clock."""
    from mxnet_tpu.serving import ServingConfig, ServingEngine

    eng = ServingEngine(ServingConfig(
        vocab_size=23, num_layers=1, model_dim=32, num_heads=2, ffn_dim=48,
        max_len=64, block_size=8, num_blocks=33, max_batch=4, num_experts=4,
        experts_per_tok=2), seed=3)       # (a model with experts packs)
    texts = [list(range(1, 10)), list(range(2, 11)), list(range(1, 18))]
    before = time.time()
    eng.generate(texts, [2, 2, 2])
    eng.stats()                     # flushes the last step's record
    obs = {"kind": "serve", "before": {"t": before},
           "after": {"t": time.time()}}
    # no rung had run: each prompt in a program of its own
    assert prefill_prompts_per_program.read(obs) == 1.0
    # every rung has: 16 + 16 + 24 rows are the rung of 64's, where three
    # programs would compute as many
    eng.warmup()
    obs["before"]["t"] = time.time()
    eng.generate(texts, [2, 2, 2])
    eng.stats()
    obs["after"]["t"] = time.time()
    assert prefill_prompts_per_program.read(obs) == 3.0
    assert eng.stats()["prefill"]["prompts_per_program"] == 6 / 4
    assert eng.stats()["loop"]["sums"]["prefill_programs"] == 4


def test_the_manifest_appends_the_metric_behind_the_padded_share():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(NAME) > names.index("prefill_padded_share")
    m = manifest["per_layer"][names.index(NAME)]
    assert m == {"name": NAME, "unit": "count", "better": "higher",
                 "source": "program_counter", "layer": "Engine loop",
                 "moves": "serve_out_tok_per_s", "workloads": CELLS}
    moved = next(e for e in manifest["end_to_end"]
                 if e["name"] == m["moves"])
    # every cell that reports the tokens a second, in the manifest's order
    assert m["workloads"] == [c for c in moved["workloads"]
                              if c in m["workloads"]] == CELLS
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       NAME + ".py"))
