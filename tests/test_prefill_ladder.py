"""The prefill ladder (``ServingConfig.prefill_buckets``): block_size
doublings up to ``max_len`` and, from ``HALF_STEP_FROM`` (1,024) rows up, the
half step between two doublings — over the seven serving configurations of
the benchmark and four made-up ones, then a long prompt through the 1,536
program of a tiny model on the CPU (docs/serving.md, "Shape buckets").
"""
import glob
import json
import os
import sys

import numpy as np
import pytest

from mxnet_tpu import compileobs, telemetry
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving import engine as E

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from tools import wrong_servers as W  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "configs")


def _served():
    """(name, block_size, max_len) of every configuration the benchmark
    serves."""
    out = []
    for path in sorted(glob.glob(os.path.join(CONFIGS, "*.json"))):
        cfg = json.load(open(path))
        if "engine" in cfg:
            out.append((os.path.basename(path)[:-len(".json")],
                        cfg["engine"]["block_size"],
                        cfg["model"]["max_len"]))
    return out


SERVED = _served()
#: a block size that is no power of two; the three lengths around the first
#: half step (1,536 closes a list as ``max_len`` does; 1,537 is the shortest
#: that gains a rung)
MADE_UP = [("blocks-of-48", 48, 4800), ("max-1024", 64, 1024),
           ("max-1536", 64, 1536), ("max-1537", 1, 1537)]


def doublings(block_size, max_len):
    """The ladder every engine had before the half steps."""
    out, s = [], block_size
    while s < max_len:
        out.append(s)
        s *= 2
    return out + [max_len]


def test_the_benchmark_serves_seven_configurations():
    assert len(SERVED) == 7 and len({c[1:] for c in SERVED}) == 6


@pytest.mark.parametrize("name,block_size,max_len", SERVED + MADE_UP,
                         ids=[c[0] for c in SERVED + MADE_UP])
def test_the_ladder_keeps_every_doubling_and_steps_by_half_from_1024(
        name, block_size, max_len):
    got = ServingConfig(block_size=block_size,
                        max_len=max_len).prefill_buckets()
    old = doublings(block_size, max_len)
    assert got == sorted(set(got)) and got[-1] == max_len
    assert all(s % block_size == 0 for s in got)
    # a deployment's warmup(prefill_buckets=[...]) list stays valid
    assert set(old) <= set(got)
    below = E.HALF_STEP_FROM
    assert [s for s in got if s <= below] == [s for s in old if s <= below]
    if max_len <= below + below // 2:
        assert got == old
    # what is new is the half step between two doublings of 1,024 rows or
    # more, and from there no rung is more than 1.5 times the one before
    for a, b in zip(got, got[1:]):
        if a >= below:
            assert 2 * b <= 3 * a, (a, b)
    assert set(got) - set(old) == {
        3 * s // 2 for s in old[:-1]
        if s >= below and 3 * s // 2 < max_len}


def test_the_cells_lists_are_the_issues():
    """Rung for rung: the chat and long-prompt cells' list is the
    parent's; the others gain what ISSUE 46's table says."""
    by_name = {name: ServingConfig(block_size=bs,
                                   max_len=n).prefill_buckets()
               for name, bs, n in SERVED}
    assert by_name["gpt2-medium-fp32"] == [16, 32, 64, 128, 256, 512, 1024]
    assert by_name["dots-vlm1-ep16-bf16"] == [128, 256, 512, 1024, 1536,
                                              2048, 3072]
    assert by_name["olmoe-1b-7b-bf16"] == by_name["phi4-mini-flash-bf16"] \
        == [64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096]
    assert by_name["mimo-v2.5-ep16-bf16"] == [
        64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096, 6144, 8192, 8960]
    assert by_name["ouro-2.6b-bf16"][:6] == [32, 64, 128, 256, 512, 1024]
    # since PR 48: ten rungs, 5,120 (the mix's max_total) the top one
    assert by_name["solar-open2-ep16-bf16"] == [
        64, 128, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120]


# --------------------------------------- a long prompt through the engine
VOCAB = 211
#: one layer of OLMoE's block, 32 wide, float32: the plain reference of
#: ``benchmark/configs/olmoe-1b-7b-bf16.py`` is what
#: tests/test_olmoe_serving.py holds prefill to, at 1e-4 of the largest logit
TINY = {
    "model": dict(vocab=VOCAB, num_layers=1, model_dim=32, num_heads=2,
                  head_dim=16, ffn_dim=16, max_len=2048, norm="rms",
                  pos="rope", rope_theta=10000.0, qk_norm=True,
                  num_experts=4, experts_per_tok=2, bias=False),
    "engine": dict(block_size=64, num_blocks=41, max_batch=2, spec_k=0,
                   kv_dtype="float32", prefix_cache=False),
    "weights_dtype": "float32",
    "init": {"std": 0.177, "expert_gain": 1.3},
    "reference": {"seq_pad": 2048, "gen_max": 8}}


def _prefill_compiles():
    return sum(p["compile_count"] for p in compileobs.program_table()
               if p["program"] == "serving.prefill")


def test_a_prompt_of_1100_tokens_runs_the_1536_program():
    _cfg, C = W.load_config(os.path.join(CONFIGS, "olmoe-1b-7b-bf16.json"))
    params = C.init_params(TINY, 5)
    scfg = C.serving_config(TINY)
    assert scfg.prefill_buckets() == [64, 128, 256, 512, 1024, 1536, 2048]
    eng = ServingEngine(scfg, arg_params=params, seed=5)
    with pytest.raises(ValueError):
        eng.warmup(prefill_buckets=[768])       # no such rung below 1,024
    c0 = _prefill_compiles()
    eng.warmup(prefill_buckets=[1536])
    assert _prefill_compiles() == c0 + 1
    warmed = {p["program"]: p["compile_count"]
              for p in compileobs.program_table()}
    prompt = np.random.RandomState(11).randint(0, VOCAB, 1100).tolist()
    rows = telemetry.counter("serving.prefill_rows").value
    toks = telemetry.counter("serving.prefill_tokens").value
    first = eng.generate([prompt], [2])[0][0]
    # the smallest rung that holds it, not the doubling behind it
    recs = [r for r in eng.obs._ring if r.prefills]
    assert len(recs) == 1
    assert (recs[0].prefill_tokens, recs[0].prefill_rows) == (1100, 1536)
    assert eng.stats()["loop"]["sums"]["prefill_rows"] == 1536
    assert telemetry.counter("serving.prefill_rows").value - rows == 1536
    assert telemetry.counter("serving.prefill_tokens").value - toks == 1100
    # nothing compiled under the traffic: the rung's program was warm
    assert {p["program"]: p["compile_count"]
            for p in compileobs.program_table()} == warmed
    # and what it computed is the reference's forward, 436 padded rows
    # behind the prompt or not
    logits = eng.prefill_logits(prompt)
    want = C.reference_logits(TINY)(params, prompt)[-1]
    assert np.abs(logits - want).max() < 1e-4 * np.abs(want).max()
    assert first == int(logits.argmax()) == int(want.argmax())
    assert _prefill_compiles() == c0 + 1
    # an unrestricted warmup() compiles the rest of the list, the half
    # step's program not again, and the count is flat after it
    eng.warmup()
    assert _prefill_compiles() == c0 + len(scfg.prefill_buckets())
    eng.generate([prompt[:70], prompt[:1025], prompt + prompt[:500]],
                 [2, 2, 2])
    assert _prefill_compiles() == c0 + len(scfg.prefill_buckets())
    # 70 and 1,025 tokens share the 1,536 program (one step's prompts are
    # packed where that computes fewer rows: 128 + 1,536 alone)
    assert eng.stats()["loop"]["sums"]["prefill_rows"] \
        == 1536 + 1536 + 2048
