"""The looped-stack readers and their cost function on hand-made
observations (CPU, no jax), and the files of the ``ouro-chat-closed32``
cell."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402
from benchmark.layer_metrics import (_loop, _looped,  # noqa: E402
                                     looped_decode_roofline,
                                     looped_passes_per_step)

CELL, CONFIG = "ouro-chat-closed32", "ouro-2.6b-bf16"
MODEL = {"vocab": 49152, "num_layers": 48, "model_dim": 2048,
         "num_heads": 16, "head_dim": 128, "ffn_dim": 5632,
         "ffn_gated": True, "loop_steps": 4, "post_norm": True}
ENGINE = {"block_size": 32, "kv_dtype": "bfloat16"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
Rec = collections.namedtuple(
    "Rec", "chunk_steps prefills lane_steps live_blocks passes")

OPS = {
    "jit__decode/while/body/fusion.3 bf16[16,2048]": 2.4,
    "jit__decode/branch_0_fun.8 bf16[16,1,16,128]": 0.9,
    "jit__decode/copy.1 s32[16]": 0.1,
    # not the decode program
    "jit__prefill/fusion.12 bf16[512,2048]": 0.7,
    "jit_other/fusion.1 f32[8]": 0.3,
}


def obs(recs, ops=OPS, model=MODEL, window=30.0, trace_window_s=4.0):
    return {"kind": "serve", "peak": PEAK, "window_s": window,
            "config": {"model": model, "engine": ENGINE,
                       "weights_dtype": "bfloat16"},
            "before": {"t": 100.0}, "after": {"t": 100.0 + window},
            "trace": None if ops is None else {
                "op_seconds": ops, "busy_s": 3.9,
                "window_s": trace_window_s}}


@pytest.fixture
def looped_records(monkeypatch):
    """Hand the readers these records in place of the process's rings."""
    def use(recs):
        monkeypatch.setattr(_loop, "records", lambda _obs: recs or None)
    return use


def test_the_stack_and_a_step_by_hand():
    # the issue's count: 51,388,416 a layer, 2,466,643,968 the stack
    assert _looped.stack_params(MODEL) == 48 * 51388416 == 2466643968
    assert _looped.passes(MODEL) == 4 and _looped.passes({}) == 1
    # one step, no lane: four passes of the stack and the head, in bf16
    fl, nbytes = _looped.step_cost(MODEL, 1, 0, 0, 32)
    assert nbytes == 2 * (4 * 2466643968 + 49152 * 2048) == 19934478336
    assert fl == 0
    assert nbytes / 819e9 == pytest.approx(24.3e-3, rel=5e-3)
    # 16 lanes of 10 blocks each and pass: at least 9 full blocks and one
    # token a lane and pass, 8,192 B a token and cache layer, 48 layers a pass
    fl, more = _looped.step_cost(MODEL, 1, 16, 4 * 16 * 10, 32)
    tokens = 4 * 16 * (9 * 32 + 1)
    assert more - nbytes == 48 * tokens * 8192
    assert fl == 2.0 * 16 * (4 * 2466643968 + 49152 * 2048)
    assert fl / 197e12 < 0.1 * more / 819e9        # the memory's bound
    # a plain two-matrix FFN and two norms a layer; fp32 weights and pages
    small = {"vocab": 10, "num_layers": 2, "model_dim": 8, "num_heads": 2,
             "ffn_dim": 16, "loop_steps": 3}
    assert _looped.stack_params(small) == 2 * (4 * 64 + 2 * 128 + 16)
    assert _looped.step_cost(small, 2, 3, 9, 4, 4, 4) == (
        2.0 * 3 * (3 * 1056 + 80),
        2 * (3 * 1056 + 80) * 4 + 2 * (0 * 4 + 9) * 2 * 8 * 4)


def test_passes_per_step_counts_prefills_and_inner_steps(looped_records):
    records = looped_records
    records([Rec(8, 2, 100, 4000, 40), Rec(5, 0, 60, 2400, 20),
             Rec(0, 1, 0, 0, 4)])
    assert looped_passes_per_step.read(obs([])) == pytest.approx(4.0)
    # a server that skipped a pass in one chunk shows
    records([Rec(8, 0, 100, 4000, 24)])
    assert looped_passes_per_step.read(obs([])) == pytest.approx(3.0)
    # nothing to read: a stack that runs once, no step, no records, a
    # program whose records have no such field (the parent's)
    records([Rec(8, 2, 100, 4000, 0)])
    assert looped_passes_per_step.read(
        obs([], model=dict(MODEL, loop_steps=1))) is None
    records([Rec(0, 0, 0, 0, 0)])
    assert looped_passes_per_step.read(obs([])) is None
    records([])
    assert looped_passes_per_step.read(obs([])) is None
    Old = collections.namedtuple("Old", "chunk_steps prefills live_blocks")
    records([Old(8, 2, 4000)])
    assert looped_passes_per_step.read(obs([])) is None
    assert looped_passes_per_step.read({"kind": "fit"}) is None


def test_roofline_crosses_the_records_with_the_decode_programs_seconds(
        looped_records):
    records = looped_records
    # 30 s of window: 700 inner steps of 16 lanes holding 7 blocks a pass
    recs = [Rec(700, 90, 700 * 16, 700 * 16 * 7 * 4, 4 * 790)]
    records(recs)
    o = obs(recs)
    assert _looped.decode_seconds(o) == pytest.approx(3.4)
    fl, nbytes = _looped.step_cost(MODEL, 700, 700 * 16, 700 * 16 * 28, 32)
    want = 100.0 * (nbytes / 30.0 / 819e9) / (3.4 / 4.0)
    assert looped_decode_roofline.read(o) == pytest.approx(want)
    assert 60 < want < 100
    # all of the decode program's seconds count, the kernel's too; none of
    # another program's
    longer = dict(OPS, **{"jit__decode/while/body/fusion.9 f32[16]": 3.4})
    assert looped_decode_roofline.read(obs(recs, longer)) \
        == pytest.approx(want / 2)
    # nothing to read: no trace, no decode program in it, no chunk, a stack
    # that runs once, another kind of driver
    assert looped_decode_roofline.read(obs(recs, None)) is None
    assert looped_decode_roofline.read(
        obs(recs, {"jit__prefill/fusion.1 f32[8]": 1.0})) is None
    assert looped_decode_roofline.read(
        obs(recs, model=dict(MODEL, loop_steps=1))) is None
    records([Rec(0, 3, 0, 0, 12)])
    assert looped_decode_roofline.read(o) is None
    records([])
    assert looped_decode_roofline.read(o) is None
    assert looped_decode_roofline.read({"kind": "fit"}) is None


def test_the_cell_is_in_the_manifest_with_its_files():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert "4 passes" in cell["why"] and len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                               "blob/main/config.json")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, unit, better in (("looped_passes_per_step", "count", "lower"),
                               ("looped_decode_roofline", "%", "higher")):
        m = by_name[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"],
                m["better"]) == ("Looped stack", "serve_out_tok_per_s",
                                 [CELL], unit, better)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    # what the other one-block closed-loop cells report, this one does too
    chat = "gpt2m-chat-closed64"
    shared = [n for n, m in by_name.items()
              if chat in m.get("workloads", ()) and m["moves"] in (
                  "serve_out_tok_per_s", "setup_s")
              and n != "decode_pool_copy_share"]
    assert len(shared) == 21
    for name in shared:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in by_name:    # no experts, no state, no latent, no share of
        if name.startswith(("moe_", "ssm_", "latent_", "prefill_busy",
                            "prefill_us")) \
                or name.endswith("_pool_copy_share"):
            assert CELL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_out_tok_per_s"]["workloads"][-1] == CELL
    # the configuration's file: every key of the catalog's config, as
    # published, nothing reduced
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    catalog = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152, "layer_types": ["full_attention"] * 48}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["reduced"] == [] and "whole" in cfg["stands_for"]
    m = cfg["model"]
    assert (m["vocab"], m["num_layers"], m["model_dim"], m["num_heads"],
            m["head_dim"], m["ffn_dim"], m["loop_steps"], m["norm_eps"],
            m["rope_theta"]) == (49152, 48, 2048, 16, 128, 5632, 4, 1e-6,
                                 1000000)
    assert m["ffn_gated"] and m["post_norm"] and not m["bias"]
    assert (m["norm"], m["pos"], m["max_len"]) == ("rms", "rope", 4096)
    note = ("from the model's published modeling code as the issue states "
            "it; not a key of config.json, and there is no network to check "
            "it against")
    for key in ("norms", "final_norm", "cache_index", "exit_gate"):
        assert cfg["assumed"][key].endswith(note)
    for key in ("max_len", "kv_dtype", "block_size", "num_blocks",
                "max_batch", "spec_k"):
        assert cfg["assumed"][key]
    e = cfg["engine"]
    assert (e["block_size"], e["max_batch"], e["spec_k"], e["kv_dtype"],
            e["prefix_cache"]) == (32, 16, 0, "bfloat16", None)
    # the pool: a token is 1.5 MB over 192 cache layers
    assert (e["num_blocks"] - 1) * e["block_size"] == 4608
    assert 4 * 48 * 2 * 16 * 128 * 2 == 1572864


def test_the_mix_is_what_the_issue_says():
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      CELL + ".json")))
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve_arch", "closed", 32)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 16, "max": 384}
    assert mix["output_len"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 32, "max": 256}
    assert (mix["max_total"], mix["drain_s"], mix["request_rate_cap"],
            mix["trace_start_s"], mix["trace_seconds"]) == (640, 60, 30,
                                                            12.0, 4.0)
    assert mix["rescore"] == [{"max_prompt": 64},
                              {"min_prompt": 192, "max_prompt": 384}]
    plan = traffic.plan(mix, 11, 30, 49152)
    assert len(plan) == 30 * 30 + 32
    assert all(16 <= len(r["tokens"]) <= 384
               and 32 <= r["max_new_tokens"] <= 256
               and len(r["tokens"]) + r["max_new_tokens"] <= 640
               for r in plan)
    # both prompts the judge re-scores are there among the first replies
    head = [len(r["tokens"]) for r in plan[:64]]
    assert any(n <= 64 for n in head) and any(192 <= n <= 384 for n in head)
