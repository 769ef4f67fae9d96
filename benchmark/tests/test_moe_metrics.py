"""The expert-layer readers and their cost functions on hand-made
observations (CPU, no jax), and the files of the ``olmoe-chat-closed64``
cell."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402
from benchmark.layer_metrics import (_moe, moe_experts_touched_mean,  # noqa: E402
                                     moe_load_max_over_mean, moe_roofline,
                                     moe_time_share, paged_attn_time_share)

CONFIG = {"model": {"model_dim": 2048, "ffn_dim": 1024, "num_layers": 8},
          "engine": {"num_blocks": 1025}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def counters(pairs, layer_steps, touched, load):
    return {"pairs": pairs, "layer_steps": layer_steps,
            "experts_touched": touched, "tokens_per_expert": load}


def obs(ops=None, before=None, after=None, busy_s=3.0, trace_window_s=4.0):
    out = {"kind": "serve", "config": CONFIG, "peak": PEAK, "window_s": 30.0,
           "trace": None if ops is None else {
               "op_seconds": ops, "busy_s": busy_s,
               "window_s": trace_window_s}}
    if before is not None:
        out["moe"] = {"before": before, "after": after}
    return out


def test_cost_by_hand():
    # one decode layer-step of 32 streams: 256 pairs on 63 experts
    fl, nbytes = _moe.cost(256, 63, 2048, 1024)
    assert fl == 256 * 6 * 2048 * 1024 == 3221225472
    weights = 63 * 3 * 2048 * 1024 * 2          # 792.7 MB of bf16
    rows = 256 * 2 * 2048 * 2                   # in and out, 2.1 MB
    assert nbytes == weights + rows == 794820608
    # bound by the weight read: 0.97 ms at 819 GB/s against 16 us of MXU
    assert nbytes / 819e9 == pytest.approx(0.9705e-3, rel=1e-3)
    assert fl / 197e12 == pytest.approx(16.35e-6, rel=1e-3)
    assert _moe.cost(8, 8, 64, 32, itemsize=4) == (
        6 * 8 * 64 * 32, 3 * 8 * 64 * 32 * 4 + 2 * 8 * 64 * 4)


OPS = {
    "jit__decode/gmm.3 f32[256,1024]": 0.9,
    "jit__decode/gmm.5 f32[256,2048]": 0.5,
    "jit__prefill/gmm.7 f32[4096,1024]": 0.2,
    # not the experts: the paged kernel, a fusion, gmm of another program
    "jit__decode/branch_0_fun.8 bf16[32,1,16,128]": 0.6,
    "jit__decode/fusion.12 bf16[32,2048]": 0.3,
    "jit_other/gmm.1 f32[8,8]": 0.7,
}


def test_time_share_counts_the_grouped_matmuls_only():
    assert moe_time_share.read(obs(OPS)) == pytest.approx(100 * 1.6 / 3.0)
    # and the paged kernel's reader counts no expert op
    assert paged_attn_time_share.read(obs(OPS)) == pytest.approx(
        100 * 0.6 / 3.0)
    assert moe_time_share.read(obs(None)) is None
    assert moe_time_share.read(obs({"jit__decode/fusion.1 f32[8]": 1.0})) \
        is None


def test_roofline_from_counters_and_trace():
    before = counters(1000, 80, 500, [[10, 0], [5, 5]])
    # 30 s of window: 1,000 decode steps of 8 layers at 256 pairs, 63 touched
    after = counters(1000 + 8000 * 256, 80 + 8000, 500 + 8000 * 63,
                     [[10, 0], [5, 5]])
    o = obs(OPS, before, after)
    fl, nbytes = _moe.cost(8000 * 256, 8000 * 63, 2048, 1024)
    need_s_per_s = max(fl / 197e12, nbytes / 819e9) / 30.0
    want = 100.0 * need_s_per_s / (1.6 / 4.0)
    assert moe_roofline.read(o) == pytest.approx(want)
    assert 60 < want < 70        # 7.76 s of weight reads in 30 s over 0.4
    # nothing to read: no counters (the parent), no trace, no kernel
    assert moe_roofline.read(obs(OPS)) is None
    assert moe_roofline.read(obs(None, before, after)) is None
    assert moe_roofline.read(obs({"jit__decode/fusion.1 f32[8]": 1.0},
                                 before, after)) is None
    assert moe_roofline.read(obs(OPS, None, None)) is None


def test_counter_readers():
    before = counters(16, 2, 7, [[1, 1, 1, 1], [2, 2, 2, 2]])
    after = counters(16 + 96, 2 + 6, 7 + 21,
                     [[1 + 12, 1 + 4, 1 + 4, 1 + 4], [2 + 6, 2 + 6, 2 + 6,
                                                      2 + 6]])
    o = obs(None, before, after)
    assert moe_experts_touched_mean.read(o) == pytest.approx(21 / 6)
    # layer 0: 12 of 24 on one of four experts = 2.0; layer 1 even = 1.0
    assert moe_load_max_over_mean.read(o) == pytest.approx(1.5)
    for reader in (moe_experts_touched_mean, moe_load_max_over_mean):
        assert reader.read(obs(None)) is None
        assert reader.read(obs(None, before, before)) is None
        assert reader.read({"kind": "serve", "moe": {"before": None,
                                                     "after": None}}) is None


@pytest.mark.parametrize("pairs, tokens, wrong", [
    (8 * 4096, 4096, None),
    (7 * 4096, 4096, "not 8 a token"),           # seven experts of eight
    (8 * 4096 - 3, 4096, "not 8 a token"),       # a cap dropped three pairs
    (0, 0, "not 8 a token"),                     # nothing went through
])
def test_correct_holds_the_window_to_nothing_dropped(pairs, tokens, wrong):
    from benchmark.drivers import serve_arch

    cfg = {"model": {"experts_per_tok": 8}}
    moe = {"before": {"pairs": 80, "layer_tokens": 10},
           "after": {"pairs": 80 + pairs, "layer_tokens": 10 + tokens}}
    problem = serve_arch.nothing_dropped(cfg, moe)
    assert (problem is None) if wrong is None else (wrong in problem)
    # a model without experts has nothing to hold; one with experts whose
    # engine reports no counters is wrong
    assert serve_arch.nothing_dropped({"model": {}}, {"before": None,
                                                      "after": None}) is None
    assert "no expert counters" in serve_arch.nothing_dropped(
        cfg, {"before": None, "after": None})


def test_the_cell_is_in_the_manifest_with_its_files():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(c for c in manifest["workloads"]
                if c["name"] == "olmoe-chat-closed64")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-bf16", "olmoe-chat-closed64", 1)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("moe_time_share", "moe_roofline",
                 "moe_experts_touched_mean", "moe_load_max_over_mean"):
        m = by_name[name]
        # this cell first; later cells with experts stand behind it
        assert (m["layer"], m["moves"], m["workloads"][0]) == (
            "Experts", "serve_out_tok_per_s", "olmoe-chat-closed64")
    for name in ("tpot_p50_ms", "decode_occupancy", "kv_pool_tokens",
                 "preemptions", "paged_attn_time_share", "decode_idle_share",
                 "decode_idle_host_share", "decode_idle_unnamed_share",
                 "peak_hbm_gb"):
        assert "olmoe-chat-closed64" in by_name[name]["workloads"]
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "olmoe-1b-7b-bf16.json")))
    # the catalog's numbers, under its keys, at the top level
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"],
            cfg["max_position_embeddings"], cfg["rope_theta"],
            cfg["rms_norm_eps"]) == (2048, 1024, 64, 8, 16, 16, 50304, 4096,
                                     10000, 1e-5)
    assert cfg["num_hidden_layers"] == cfg["model"]["num_layers"] == 8
    assert cfg["reduced"] == ["num_hidden_layers"]
    m, e = cfg["model"], cfg["engine"]
    assert (m["model_dim"], m["ffn_dim"], m["num_experts"],
            m["experts_per_tok"], m["head_dim"], m["vocab"], m["max_len"]) \
        == (2048, 1024, 64, 8, 128, 50304, 4096)
    # 64 KB of K and V a token: at least 32,768 usable tokens
    assert (e["num_blocks"] - 1) * e["block_size"] >= 32768
    assert m["max_len"] % e["block_size"] == 0


def test_the_mix_is_what_the_issue_says_and_its_picks_fit_the_reference():
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "olmoe-chat-closed64.json")))
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "olmoe-1b-7b-bf16.json")))
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve_arch", "closed", 64)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.9, "min": 16, "max": 1536}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 32, "max": 512}
    assert mix["max_total"] == 2048 <= cfg["model"]["max_len"]
    longest = max(p.get("max_prompt", 1 << 30) for p in mix["rescore"])
    ref = cfg["reference"]
    assert longest + mix["output_len"]["max"] <= ref["seq_pad"]
    assert mix["output_len"]["max"] <= ref["gen_max"]
    plan = traffic.plan(mix, 2600000123, 30, cfg["model"]["vocab"])
    assert len(plan) == 30 * mix["request_rate_cap"] + 64
    for want in mix["rescore"]:
        assert any(want.get("min_prompt", 0) <= len(r["tokens"])
                   <= want.get("max_prompt", 1 << 30) for r in plan[:64])
