"""`prefill_padded_share`'s reader (`layer_metrics/prefill_padded_share.py`)
over hand-made records, over a tiny engine's own, and its entry in the
manifest.

    python3 -m pytest benchmark/tests/test_prefill_padded_share.py -q
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

from benchmark.layer_metrics import _loop, prefill_padded_share  # noqa: E402

NAME = "prefill_padded_share"
#: the six cells that report `serve_out_tok_per_s`, in the manifest's order
CELLS = ["gpt2m-chat-closed64", "olmoe-chat-closed64",
         "phi4flash-reason-closed128", "dotsvlm1-chat-closed256",
         "ouro-chat-closed32", "mimov25-mixed-closed128"]
Rec = collections.namedtuple("Rec", "prefills prefill_tokens prefill_rows",
                             defaults=(0, 0))
Old = collections.namedtuple("Old", "prefills prefill_tokens")  # a parent's
OBS = {"kind": "serve", "before": {"t": 100.0}, "after": {"t": 130.0},
       "window_s": 30.0, "trace": None}


@pytest.fixture
def padded_records(monkeypatch):
    """Hand the reader these records in place of the process's rings."""
    def use(recs):
        monkeypatch.setattr(_loop, "records", lambda _obs: recs or None)
    return use


def test_a_known_ratio(padded_records):
    # 1,100 tokens in the 1,536 program, 4,100 in 6,144, a step that
    # decoded only, and a group of two short prompts in 64 rows each
    padded_records([Rec(1, 1100, 1536), Rec(1, 4100, 6144), Rec(0),
             Rec(2, 60 + 33, 128)])
    rows, tokens = 1536 + 6144 + 128, 1100 + 4100 + 93
    assert prefill_padded_share.read(OBS) == pytest.approx(
        100.0 * (1 - tokens / rows))
    # a prompt that fills its rung pads nothing; no trace is needed
    padded_records([Rec(1, 2048, 2048)])
    assert prefill_padded_share.read(OBS) == 0.0


def test_nothing_to_read(padded_records, monkeypatch):
    # the parent of the PR that added the field: nothing, and no error
    padded_records([Old(1, 1100), Old(0, 0)])
    assert prefill_padded_share.read(OBS) is None
    # a window in which no prompt was prefilled, and one with no step
    padded_records([Rec(0), Rec(0)])
    assert prefill_padded_share.read(OBS) is None
    padded_records([])
    assert prefill_padded_share.read(OBS) is None
    monkeypatch.undo()
    # a driver that serves nothing; a window of the real rings that is empty
    assert prefill_padded_share.read({"kind": "fit", "trace": None}) is None
    assert prefill_padded_share.read(
        dict(OBS, before={"t": 1.0}, after={"t": 2.0})) is None


def test_it_reads_an_engines_own_records():
    """Through the channel the benchmark uses: the engine's ring, windowed
    by `loop_records` between two readings of the clock."""
    from mxnet_tpu.serving import ServingConfig, ServingEngine

    eng = ServingEngine(ServingConfig(
        vocab_size=23, num_layers=1, model_dim=32, num_heads=2, ffn_dim=48,
        max_len=64, block_size=8, num_blocks=33, max_batch=4), seed=3)
    before = time.time()
    eng.generate([[1, 2, 3], list(range(1, 10)), list(range(1, 18))],
                 [2, 2, 2])
    eng.stats()                     # flushes the last step's record
    after = time.time()
    obs = {"kind": "serve", "before": {"t": before}, "after": {"t": after}}
    # 3 tokens in 8 rows, 9 in 16, 17 in 32
    assert prefill_padded_share.read(obs) == pytest.approx(
        100.0 * (1 - 29 / 56))
    assert eng.stats()["loop"]["sums"]["prefill_rows"] == 56


def test_the_manifest_appends_the_metric_behind_what_was_there():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(NAME) > names.index("loop_prefill_us_per_token")
    m = manifest["per_layer"][names.index(NAME)]
    assert m == {"name": NAME, "unit": "%", "better": "lower",
                 "source": "program_counter", "layer": "Engine loop",
                 "moves": "serve_out_tok_per_s", "workloads": CELLS}
    moved = next(e for e in manifest["end_to_end"]
                 if e["name"] == m["moves"])
    # every cell that reports the tokens a second, in the manifest's order
    assert m["workloads"] == [c for c in moved["workloads"]
                              if c in m["workloads"]] == CELLS
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics",
                                       NAME + ".py"))
