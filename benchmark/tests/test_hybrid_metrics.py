"""The readers of a "gqa" model's two walks and their cost function on
hand-made observations (CPU, no jax), and the files of the
``mimov25-mixed-closed128`` cell."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402
from benchmark.layer_metrics import (_hybrid, _loop,  # noqa: E402
                                     flash_fwd_time_share,
                                     flash_gqa_time_share,
                                     full_attn_roofline,
                                     full_attn_time_share,
                                     full_ctx_tokens_per_lane_step,
                                     loop_prefill_us_per_token,
                                     paged_attn_time_share,
                                     window_attn_roofline,
                                     window_attn_time_share)

CELL, CONFIG = "mimov25-mixed-closed128", "mimo-v2.5-ep16-bf16"
MODEL = {"num_heads": 64, "head_dim": 192, "v_dim": 128, "num_kv_heads": 4,
         "swa_kv_heads": 8, "window": 128, "attn_form": "gqa",
         "layer_kinds": ["full", "swa", "swa", "swa", "swa", "swa", "full"]}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
Rec = collections.namedtuple(
    "Rec", "lane_steps full_ctx_tokens window_ctx_tokens prefill_tokens",
    defaults=(0,))
Old = collections.namedtuple("Old", "lane_steps")    # a parent's record

OPS = {
    "jit__decode/paged_full_walk.3 bf16[64,4,16,128]": 0.30,
    "jit__decode/paged_full_walk.9 bf16[64,4,16,128]": 0.10,
    "jit__decode/paged_window_walk.5 bf16[64,8,8,128]": 0.25,
    # not these kernels: an earlier model's paged call, a fusion, a prefill
    "jit__decode/branch_0_fun.2 bf16[64,4,10,128]": 0.5,
    "jit__decode/fusion.12 bf16[64,4096]": 0.3,
    "jit__prefill/branch_0_fun.1 f32[64,8192,128]": 0.7,
    # this model's flash forward, by its name, and another prefill op
    "jit__prefill/flash_gqa_fwd.7 f32[64,8192,128]": 0.2,
    "jit__prefill/flash_gqa_fwd.13 f32[64,8192,128]": 0.16,
    "jit__prefill/fusion.353 bf16[8192,32768]": 0.14,
    "jit_other/paged_full_walk.1 bf16[8]": 0.9,
}


def obs(ops=OPS, model=MODEL, window=30.0, busy_s=3.0, trace_window_s=4.0):
    return {"kind": "serve", "peak": PEAK, "window_s": window,
            "config": {"model": model},
            "before": {"t": 100.0}, "after": {"t": 100.0 + window},
            "trace": None if ops is None else {
                "op_seconds": ops, "busy_s": busy_s,
                "window_s": trace_window_s}}


@pytest.fixture
def hybrid_records(monkeypatch):
    """Hand the readers these records in place of the process's rings."""
    def use(recs):
        monkeypatch.setattr(_loop, "records", lambda _obs: recs or None)
    return use


def test_cost_by_hand():
    """One key read in one layer: 2,560 B in a full layer (4 K/V heads of
    192 + 128 two-byte values), 5,120 in a window layer (8), 40,960 FLOPs
    in either (64 query heads, a score and an add over 320 lanes); a
    lane-step and layer: the heads' query and result rows, 40,960 B."""
    fl, nbytes = _hybrid.cost(1, 0, MODEL, "full")
    assert (fl, nbytes) == (2 * 2 * 64 * 320, 2 * 2560)      # two layers
    fl, nbytes = _hybrid.cost(1, 0, MODEL, "swa")
    assert (fl, nbytes) == (5 * 2 * 64 * 320, 5 * 5120)      # five layers
    assert _hybrid.cost(0, 1, MODEL, "full") == (0.0, 2 * 64 * 320 * 2)
    assert _hybrid.cost(0, 1, MODEL, "swa") == (0.0, 5 * 64 * 320 * 2)
    # memory bounds a full walk: 16 query heads a K/V row
    fl, nbytes = _hybrid.cost(1000, 1, MODEL, "full")
    assert fl / 197e12 < nbytes / 819e9
    # another model has no such layers
    assert _hybrid.layers({"layer_kinds": ["full", "swa"]}, "full") == 0
    assert _hybrid.layers(MODEL, "full") == 2
    assert _hybrid.layers(MODEL, "swa") == 5


def test_time_shares_read_the_two_kernels_by_name():
    assert full_attn_time_share.read(obs()) == pytest.approx(100 * 0.4 / 3)
    assert window_attn_time_share.read(obs()) == pytest.approx(
        100 * 0.25 / 3)
    # the accepted reader of the earlier models' paged calls sees neither
    assert paged_attn_time_share.read(obs()) == pytest.approx(100 * 0.5 / 3)
    only = {k: v for k, v in OPS.items() if "walk" in k}
    assert paged_attn_time_share.read(obs(only)) is None
    for reader in (full_attn_time_share, window_attn_time_share):
        assert reader.read(obs(None)) is None
        assert reader.read(obs({"jit__decode/fusion.1 f32[8]": 1.0})) is None


def test_the_prefill_readers_by_hand(hybrid_records):
    """The flash forward by its name (not the earlier models' unnamed
    call, which its accepted reader still sees alone), and the prefill
    programs' device seconds over the tokens the loop's records hold."""
    assert flash_gqa_time_share.read(obs()) == pytest.approx(100 * 0.36 / 3)
    assert flash_fwd_time_share.read(obs()) == pytest.approx(100 * 0.7 / 3)
    assert flash_gqa_time_share.read(obs(None)) is None
    assert flash_gqa_time_share.read(
        obs({"jit__prefill/branch_0_fun.1": 1.0})) is None
    hybrid_records([Rec(64, 0, 0, 9000), Rec(60, 0, 0), Rec(0, 0, 0, 3000)])
    # 1.2 s of prefill ops in a 4 s stretch; 12,000 tokens in 30 s
    assert loop_prefill_us_per_token.read(obs()) == pytest.approx(
        1e6 * (1.2 / 4.0) / (12000 / 30.0))
    assert loop_prefill_us_per_token.read(obs(None)) is None
    assert loop_prefill_us_per_token.read(
        obs({"jit__decode/fusion.1": 1.0})) is None         # no prefill op
    for recs in ([], [Old(12)], [Rec(8, 800, 400)]):        # nothing booked
        hybrid_records(recs)
        assert loop_prefill_us_per_token.read(obs()) is None
    assert loop_prefill_us_per_token.read({"kind": "fit"}) is None
    assert flash_gqa_time_share.read({"kind": "fit"}) is None


def test_rooflines_by_hand_and_never_above_the_peak(hybrid_records):
    recs = [Rec(64, 64 * 1500, 64 * 128), Rec(60, 60 * 2000, 60 * 120)]
    hybrid_records(recs)
    ctx, lanes = 64 * 1500 + 60 * 2000, 124
    nbytes = 2 * (2560 * ctx + 40960 * lanes)
    want = 100.0 * (nbytes / 30.0 / 819e9) / (0.4 / 4.0)
    assert full_attn_roofline.read(obs()) == pytest.approx(want)
    wctx = 64 * 128 + 60 * 120
    wbytes = 5 * (5120 * wctx + 40960 * lanes)
    want = 100.0 * (wbytes / 30.0 / 819e9) / (0.25 / 4.0)
    assert window_attn_roofline.read(obs()) == pytest.approx(want)
    assert full_ctx_tokens_per_lane_step.read(obs()) == pytest.approx(
        ctx / 124.0)
    # a synthetic run AT the peak reads 100 and never more: the kernel's
    # seconds are what the counted bytes take at the HBM peak
    at_peak = {"jit__decode/paged_full_walk.1": 4.0 * nbytes / 30.0 / 819e9}
    assert full_attn_roofline.read(obs(at_peak)) == pytest.approx(100.0)
    slower = {"jit__decode/paged_full_walk.1": 8.0 * nbytes / 30.0 / 819e9}
    assert full_attn_roofline.read(obs(slower)) == pytest.approx(50.0)


def test_nothing_to_read_is_none_and_never_raises(hybrid_records):
    readers = (full_attn_roofline, window_attn_roofline,
               full_ctx_tokens_per_lane_step)
    # no records; records of a program from before the counters (the
    # parent's: the fields are not there); no decode step in the window
    for recs in ([], [Old(12)], [Rec(0, 0, 0)]):
        hybrid_records(recs)
        for reader in readers:
            assert reader.read(obs()) is None
    hybrid_records([Rec(8, 800, 400)])
    for reader in readers[:2]:
        assert reader.read(obs(None)) is None                 # no trace
        assert reader.read(obs({"jit__decode/fusion.1": 1.0})) is None
        assert reader.read(dict(obs(), peak=None)) is None
        assert reader.read(obs(model=dict(MODEL, attn_form="diff"))) is None
    assert full_ctx_tokens_per_lane_step.read(obs(None)) == 100.0
    hybrid_records([])
    for reader in readers + (full_attn_time_share, window_attn_time_share):
        assert reader.read({"kind": "fit"}) is None


def test_the_cell_is_in_the_manifest_with_its_files():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert len(cell["why"]) <= 200 and "2 rows an expert" in cell["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == ("https://huggingface.co/XiaomiMiMo/MiMo-V2.5/"
                               "blob/main/config.json")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    added = (("full_attn_time_share", "%", "lower", "Kernels"),
             ("window_attn_time_share", "%", "lower", "Kernels"),
             ("full_attn_roofline", "%", "higher", "Kernels"),
             ("window_attn_roofline", "%", "higher", "Kernels"),
             ("full_ctx_tokens_per_lane_step", "count", "lower", "Kernels"),
             ("flash_gqa_time_share", "%", "lower", "Kernels"),
             ("loop_prefill_us_per_token", "us", "lower", "Device"))
    for name, unit, better, layer in added:
        m = by_name[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"],
                m["better"]) == (layer, "serve_out_tok_per_s", [CELL],
                                 unit, better)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    # appended in this order behind every metric that was there (a later
    # PR appends behind them: nothing here holds them to be the last)
    at = [list(by_name).index(name) for name, *_ in added]
    assert at == sorted(at) and at[0] > list(by_name).index(
        "plan_used_share")
    # what the other share of an expert-parallel model reports, this one
    # does too, but for the latent kernel's two and `prefill_us_per_token`
    # (its reader takes the prompt tokens from the latent counters and
    # reads nothing here: `loop_prefill_us_per_token` reads the loop's
    # records instead); and not the earlier paged calls' readers, which
    # find no op of theirs here
    share = "dotsvlm1-chat-closed256"
    for name, m in by_name.items():
        if share in m.get("workloads", ()):
            assert (CELL in m["workloads"]) == (
                not name.startswith(("latent_", "prefill_us_"))), name
            if CELL in m["workloads"]:      # appended behind it
                assert m["workloads"].index(CELL) \
                    > m["workloads"].index(share), name
    for name in ("paged_attn_time_share", "paged_attn_us_per_live_block",
                 "ssm_time_share", "looped_decode_roofline"):
        assert CELL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_out_tok_per_s"]["workloads"]
    names = [c["name"] for c in manifest["workloads"]]
    assert names.index(CELL) > names.index("ouro-chat-closed32")
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1

    # the configuration's file: every key of the catalog's config as
    # published but the three reduced; the lists copied whole
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    catalog = {
        "attention_bias": False, "attention_chunk_size": 128,
        "attention_value_scale": 0.707,
        "attention_projection_layout": "fused_qkv",
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
        "swa_num_attention_heads": 64, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
        "hidden_size": 4096, "hybrid_block_size": None,
        "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
        "max_position_embeddings": 1048576, "model_type": "mimo_v2",
        "moe_intermediate_size": 2048, "n_group": 1,
        "n_shared_experts": None, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
        "rope_scaling": {"rope_type": "default", "type": "default"},
        "rope_theta": 10000000, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_size": 128, "swa_rope_theta": 10000,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128,
        "hybrid_layer_pattern": [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7
        + [0],
        "moe_layer_freq": [0] + [1] * 47}
    assert {k: cfg[k] for k in catalog} == catalog
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 16, 19072)
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["published"]["vocab_size"] == 152576
    assert cfg["reduced"] == entry["reduced"]
    assert "rank 0 of 16" in cfg["stands_for"] and len(cfg["left_out"]) == 3
    served = cfg["layers_served"]
    assert served == [0, 6, 7, 8, 9, 10, 11]
    m = cfg["model"]
    assert m["layer_kinds"] == [
        "swa" if cfg["hybrid_layer_pattern"][i] else "full" for i in served]
    assert [i >= m["first_dense"] for i in range(7)] == [
        bool(cfg["moe_layer_freq"][i]) for i in served]
    assert (m["vocab"], m["num_layers"], m["model_dim"], m["num_heads"],
            m["head_dim"], m["v_dim"], m["rope_dim"], m["ffn_dim"],
            m["dense_ffn_dim"], m["num_kv_heads"], m["swa_kv_heads"],
            m["window"], m["num_experts"], m["experts_per_tok"],
            m["experts_held"]) == (
        19072, 7, 4096, 64, 192, 128, 64, 2048, 16384, 4, 8, 128, 256, 8,
        [0, 16])
    assert m["rope_dim"] == int(192 * cfg["partial_rotary_factor"])
    assert (m["rope_theta"], m["swa_rope_theta"], m["value_scale"],
            m["norm_eps"], m["attn_form"], m["swa_sink"]) == (
        1e7, 1e4, 0.707, 1e-5, "gqa", True)
    assert (m["router"], m["n_group"], m["topk_group"], m["route_scale"],
            m["max_len"]) == ("sigmoid_group", 1, 1, 1.0, 8960)
    e = cfg["engine"]
    assert (e["block_size"], e["max_batch"], e["spec_k"], e["kv_dtype"],
            e["prefix_cache"]) == (64, 64, 0, "bfloat16", False)
    for key in ("sink", "value_scale", "rotary_lanes", "window_edge",
                "attention_chunk_size", "attention_projection_layout",
                "block_size", "num_blocks", "max_batch", "max_len"):
        assert cfg["assumed"][key], key
    assert cfg["init"]["sink_mean"] > 0 and cfg["notes"] \
        and cfg["departures"]


def test_the_mix_is_what_the_issue_says():
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      CELL + ".json")))
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve_share", "closed", 128)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.1, "min": 64, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 64, "max": 768}
    assert (mix["max_total"], mix["drain_s"], mix["trace_start_s"],
            mix["trace_seconds"]) == (8960, 60, 12.0, 4.0)
    assert mix["rescore"] == [{"max_prompt": 128},
                              {"min_prompt": 2048, "max_prompt": 4096}]
    cap = mix["request_rate_cap"]
    plan = traffic.plan(mix, 11, 30, 19072)
    assert len(plan) == 30 * cap + 128
    assert all(64 <= len(r["tokens"]) <= 8192
               and 64 <= r["max_new_tokens"] <= 768
               and len(r["tokens"]) + r["max_new_tokens"] <= 8960
               for r in plan)
    lens = sorted(len(r["tokens"]) for r in plan)
    # short and long in ONE queue: a tenth under ~250, a tenth over ~4,200
    assert lens[len(lens) // 10] < 300 and lens[-len(lens) // 10] > 3500
    assert lens[-1] == 8192
    # both prompts the judge re-scores are there among the first replies
    head = [len(r["tokens"]) for r in plan[:128]]
    assert any(n <= 128 for n in head) \
        and any(2048 <= n <= 4096 for n in head)
