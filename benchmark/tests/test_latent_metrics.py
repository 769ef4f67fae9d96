"""The latent-attention readers, the share's row count and the driver's
identities on hand-made observations (CPU, no jax), and the files of the
``dotsvlm1-chat-closed256`` cell."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402
from benchmark.drivers import serve_share  # noqa: E402
from benchmark.layer_metrics import (_latent, latent_attn_roofline,  # noqa: E402
                                     latent_attn_time_share,
                                     moe_held_rows_per_expert,
                                     moe_time_share, paged_attn_time_share,
                                     prefill_busy_share,
                                     prefill_us_per_token)

CELL = "dotsvlm1-chat-closed256"
CONFIG = "dots-vlm1-ep16-bf16"
MODEL = {"model_dim": 7168, "ffn_dim": 2048, "num_heads": 128,
         "kv_rank": 512, "rope_dim": 64, "layer_kinds": ["mla"] * 5,
         "num_experts": 256, "experts_per_tok": 8, "experts_held": [0, 16]}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

OPS = {
    "jit__decode/latent_paged.3 bf16[128,128,512]": 0.40,
    "jit__decode/latent_paged.7 bf16[128,128,512]": 0.20,
    "jit__decode/gmm.5 f32[1024,2048]": 0.8,
    # not the latent kernel: the flash forward, a fusion, another program
    "jit__prefill/branch_0_fun.2 f32[128,2048,128]": 0.5,
    "jit__decode/fusion.12 bf16[128,7168]": 0.3,
    "jit_other/latent_paged.1 bf16[8]": 0.7,
}


def counters(ctx_tokens, lane_steps):
    return {"ctx_tokens": ctx_tokens, "lane_steps": lane_steps,
            "live_blocks": 0, "prefill_tokens": 0}


def obs(ops=None, ctx=None, lanes=None, busy_s=3.0, trace_window_s=4.0,
        model=MODEL):
    out = {"kind": "serve", "config": {"model": model}, "peak": PEAK,
           "window_s": 30.0,
           "trace": None if ops is None else {
               "op_seconds": ops, "busy_s": busy_s,
               "window_s": trace_window_s}}
    if ctx is not None:
        out["latent"] = {"before": counters(1000, 10),
                         "after": counters(1000 + ctx, 10 + lanes)}
    return out


def test_cost_by_hand():
    """One cached token read in one layer: 576 two-byte values and 278,528
    FLOPs; a lane-step and layer: the heads' query rows in, their results
    out. Five "mla" layers."""
    fl, nbytes = _latent.cost(1, 0, MODEL)
    assert (fl, nbytes) == (5 * 278528.0, 5 * 1152.0)
    fl, nbytes = _latent.cost(0, 1, MODEL)
    assert (fl, nbytes) == (0.0, 5 * (128 * 576 + 128 * 512) * 2.0)
    fl, nbytes = _latent.cost(1000, 7, dict(MODEL, layer_kinds=["mla"]))
    assert fl == 278528e3 and nbytes == 1152e3 + 7 * 278528.0
    # v5e's ridge: a cached token is as many FLOPs a byte as the chip has
    assert 235 < 278528 / 1152 < 245


def test_time_share_counts_the_named_kernel_only():
    assert latent_attn_time_share.read(obs(OPS)) == pytest.approx(
        100 * 0.6 / 3.0)
    assert latent_attn_time_share.read(obs()) is None       # untraced
    none = {k: v for k, v in OPS.items() if "/latent_paged" not in k
            or k.startswith("jit_other")}
    assert latent_attn_time_share.read(obs(none)) is None   # the parent
    # the other kernels' readers do not take it for theirs
    assert paged_attn_time_share.read(obs(OPS)) is None
    assert moe_time_share.read(obs(OPS)) == pytest.approx(100 * 0.8 / 3.0)


def test_roofline_from_the_counters_and_the_trace():
    ctx, lanes = 5_000_000_000 // 10, 300_000
    fl, nbytes = _latent.cost(ctx, lanes, MODEL)
    least = max(fl / 197e12, nbytes / 819e9) / 30.0     # s a second of window
    got = latent_attn_roofline.read(obs(OPS, ctx, lanes))
    assert got == pytest.approx(100 * least / (0.6 / 4.0))
    assert 0 < got < 100
    assert latent_attn_roofline.read(obs(OPS)) is None      # no counters
    assert latent_attn_roofline.read(obs(None, ctx, lanes)) is None
    assert latent_attn_roofline.read(obs(OPS, 0, 0)) is None
    other = obs(OPS, ctx, lanes, model={"model_dim": 1024})
    assert latent_attn_roofline.read(other) is None


def test_the_prefill_programs_share_and_a_prompt_tokens_cost():
    """Every op of ``jit__prefill`` and of no other program; a token's
    cost from rates: seconds a second of traced stretch over prefilled
    tokens a second of window."""
    ops = dict(OPS, **{"jit__prefill/gmm.9 f32[2048,2048]": 0.7})
    assert prefill_busy_share.read(obs(ops)) == pytest.approx(
        100 * 1.2 / 3.0)
    assert prefill_busy_share.read(obs()) is None           # untraced
    decode_only = {k: v for k, v in OPS.items()
                   if k.startswith("jit__decode")}
    assert prefill_busy_share.read(obs(decode_only)) is None
    seen = obs(ops, 0, 1)
    seen["latent"]["after"]["prefill_tokens"] = 150_000
    assert prefill_us_per_token.read(seen) == pytest.approx(
        1e6 * (1.2 / 4.0) / (150_000 / 30.0))
    assert prefill_us_per_token.read(obs(ops)) is None      # no counters
    assert prefill_us_per_token.read(obs(ops, 0, 1)) is None  # no prompt
    assert prefill_us_per_token.read(obs(None, 0, 1)) is None


def test_held_rows_per_expert():
    moe = {"pairs": 100, "layer_steps": 10, "experts_touched": 50,
           "tokens_per_expert": [[0] * 16] * 4}
    after = {"pairs": 100 + 64 * 4 * 25, "layer_steps": 10 + 4 * 25,
             "experts_touched": 50 + 1500,
             "tokens_per_expert": [[400] * 16] * 4}
    seen = obs()
    seen["moe"] = {"before": moe, "after": after}
    assert moe_held_rows_per_expert.read(seen) == pytest.approx(4.0)
    whole = obs(model=dict(MODEL, experts_held=None, num_experts=64))
    whole["moe"] = seen["moe"]
    assert moe_held_rows_per_expert.read(whole) == pytest.approx(1.0)
    assert moe_held_rows_per_expert.read(obs()) is None


@pytest.mark.parametrize("routed,pairs,held,tokens,wrong", [
    (8000, 510, 510, 1000, None),
    (7999, 510, 510, 1000, "not 8 a token"),
    (8000, 510, 509, 1000, "loads sum to 509"),
    (8000, 0, 0, 1000, "0 pairs computed here"),
    (0, 0, 0, 0, "not 8 a token"),
])
def test_correct_holds_the_window_to_the_shares_arithmetic(
        routed, pairs, held, tokens, wrong):
    cfg = {"model": MODEL}
    before = {"routed_pairs": 16, "pairs": 2, "layer_tokens": 2,
              "tokens_per_expert": [[1, 0], [0, 1]]}
    after = {"routed_pairs": 16 + routed, "pairs": 2 + pairs,
             "layer_tokens": 2 + tokens,
             "tokens_per_expert": [[1 + held, 0], [0, 1]]}
    got = serve_share.share_adds_up(cfg, {"before": before, "after": after})
    assert (got is None) if wrong is None else (wrong in got)
    assert "no expert counters" in serve_share.share_adds_up(
        cfg, {"before": None, "after": None})
    old = {k: v for k, v in after.items() if k != "routed_pairs"}
    assert "router's choices" in serve_share.share_adds_up(
        cfg, {"before": before, "after": old})


def test_the_cell_is_in_the_manifest_with_its_files():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert "16 of 256 experts" in cell["why"] and len(cell["why"]) <= 200
    assert "prefilled" in cell["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "n_routed_experts", "vocab_size"]
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert len(entry["source"]) <= 200
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, layer, unit in (
            ("latent_attn_time_share", "Kernels", "%"),
            ("latent_attn_roofline", "Kernels", "%"),
            ("moe_held_rows_per_expert", "Experts", "count"),
            ("prefill_busy_share", "Device", "%"),
            ("prefill_us_per_token", "Device", "us")):
        m = by_name[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"]) == (
            layer, "serve_out_tok_per_s", [CELL], unit)
    for name in ("tpot_p50_ms", "decode_occupancy", "kv_pool_tokens",
                 "preemptions", "decode_idle_share", "decode_idle_host_share",
                 "decode_idle_unnamed_share", "peak_hbm_gb", "compile_s",
                 "compiles_in_window", "moe_time_share", "moe_roofline",
                 "moe_experts_touched_mean", "moe_load_max_over_mean"):
        assert CELL in by_name[name]["workloads"]
    for name in by_name:    # the latent kernel is not `branch_0_fun`
        if name.startswith(("paged_", "ssm_")) \
                or name.endswith("_pool_copy_share"):
            assert CELL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_out_tok_per_s"]["workloads"]
    # every reader and the driver are files beside the others
    for name in ("latent_attn_time_share", "latent_attn_roofline",
                 "moe_held_rows_per_expert", "_latent",
                 "prefill_busy_share", "prefill_us_per_token"):
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    catalog = {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "num_attention_heads": 128,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "n_group": 8, "topk_group": 4,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "max_position_embeddings": 163840,
        "num_nextn_predict_layers": 1, "moe_layer_freq": 1, "ep_size": 1}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (cfg["scoring_func"], cfg["topk_method"], cfg["norm_topk_prob"],
            cfg["model_type"]) == ("sigmoid", "noaux_tc", True, "dots_vlm")
    # the four cuts, beside what was published
    assert [cfg[k] for k in cfg["reduced"]] == [5, 1, 16, 16160]
    assert cfg["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280}
    assert len(cfg["left_out"]) == 2 and "16 v5e chips" in cfg["stands_for"]
    m, e = cfg["model"], cfg["engine"]
    assert (m["model_dim"], m["num_heads"], m["head_dim"], m["rope_dim"],
            m["v_dim"], m["q_rank"], m["kv_rank"], m["ffn_dim"],
            m["dense_ffn_dim"], m["num_experts"], m["experts_per_tok"],
            m["n_group"], m["topk_group"], m["route_scale"],
            m["shared_experts"], m["norm_eps"]) == (
        7168, 128, 128, 64, 128, 1536, 512, 2048, 18432, 256, 8, 8, 4, 2.5,
        1, 1e-6)
    assert (m["num_layers"], m["first_dense"], m["experts_held"],
            m["vocab"], m["layer_kinds"]) == (5, 1, [0, 16], 16160,
                                              ["mla"] * 5)
    assert m["rope_yarn"] == [40, 4096, 32, 1, 1, 1]
    assert (e["max_batch"], e["spec_k"], e["prefix_cache"],
            e["kv_dtype"]) == (128, 0, False, "bfloat16")
    assert (e["num_blocks"] - 1) * e["block_size"] == 262144
    assert m["max_len"] % e["block_size"] == 0


def test_the_mix_is_what_the_issue_says_and_its_picks_fit_the_reference():
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      CELL + ".json")))
    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      CONFIG + ".json")))
    assert (mix["driver"], mix["loop"], mix["clients"],
            mix["request_rate_cap"], mix["drain_s"]) == (
        "serve_share", "closed", 256, 60, 60)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 64, "max": 768}
    assert mix["max_total"] == 3072 == cfg["model"]["max_len"]
    assert mix["clients"] == 2 * cfg["engine"]["max_batch"]
    assert (mix["trace_start_s"], mix["trace_seconds"]) == (12.0, 4.0)
    assert mix["rescore"] == [{"max_prompt": 128},
                              {"min_prompt": 768, "max_prompt": 1536}]
    ref = cfg["reference"]
    assert 1536 + mix["output_len"]["max"] <= ref["seq_pad"]
    assert mix["output_len"]["max"] <= ref["gen_max"]
    # the probe covers the mix's own lengths: prefixes from its shortest
    # prompt to past its longest, every lane of the engine, contexts of
    # more than two of the latent kernel's fetches
    assert ref["probe_prefixes"][0] <= mix["prompt_len"]["min"]
    assert mix["prompt_len"]["max"] < ref["probe_len"] \
        <= cfg["model"]["max_len"]
    assert ref["probe_lanes"] == cfg["engine"]["max_batch"]
    assert ref["probe_len"] > 4 * 512
    plan = traffic.plan(mix, 2931000123, 30, cfg["model"]["vocab"])
    assert len(plan) == 30 * mix["request_rate_cap"] + 256
    assert all(0 <= t < 16160 for r in plan[:64] for t in r["tokens"])
    for want in mix["rescore"]:
        assert any(want.get("min_prompt", 0) <= len(r["tokens"])
                   <= want.get("max_prompt", 1 << 30) for r in plan[:256])


def test_the_tiny_cell_rehearses_on_the_cpu():
    """``benchmark/rehearsal/dotsvlm1-tiny.json`` end to end: the
    configuration's module, ``serve_share``, the probe's two halves, the
    re-scored requests, the share's identities and every reader of the
    manifest — where a reader finds nothing (the CPU runs the XLA paths:
    no ``latent_paged`` op) the metric is left out and nothing raises."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal", "--workload", "dotsvlm1-tiny", "--seed",
         "3300000021", "--seconds", "2", "--trace", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and "metrics" not in last
    assert {"decode_occupancy", "kv_pool_tokens", "compile_s",
            "moe_held_rows_per_expert",
            "moe_experts_touched_mean"} <= set(last["observed"])
    assert "latent_attn_roofline" not in last["observed"]
    ref = next(l for l in lines if l.get("bench") == "reference")["logits"]
    assert ref["rows"] == 8 and ref["decode_quartile"] is not None
    assert ref["quartile"] <= ref["band"]
    assert ref["median"] <= ref["median_band"] == 3.5e-2
    held = ref["held"]
    assert held["rows"] == 8 and held["quartile"] <= held["band"]
    moe = next(l for l in lines if l.get("bench") == "moe")
    routed, pairs, tokens = (moe["after"][k] - moe["before"][k]
                             for k in ("routed_pairs", "pairs",
                                       "layer_tokens"))
    assert routed == 4 * tokens and 0 < pairs < routed
    lat = next(l for l in lines if l.get("bench") == "latent")
    assert lat["after"]["ctx_tokens"] > lat["after"]["lane_steps"] > 0
