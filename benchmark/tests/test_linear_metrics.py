"""The readers of a model with gated delta-rule linear layers and their cost
functions on hand-made observations (CPU, no jax), and the files of the
``solaropen2-reason-closed192`` cell."""
import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402
from benchmark.layer_metrics import (_linear, _loop,  # noqa: E402
                                     kda_chunk_roofline,
                                     kda_chunk_time_share,
                                     kda_step_roofline, kda_step_time_share,
                                     ssm_time_share)

CELL, CONFIG = "solaropen2-reason-closed192", "solar-open2-ep16-bf16"
MODEL = {"num_heads": 64, "head_dim": 128, "num_kv_heads": 8,
         "attn_form": "gqa", "kda_heads": 64, "kda_head_dim": 128,
         "layer_kinds": ["full", "kda", "kda", "kda"] * 2}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
Rec = collections.namedtuple(
    "Rec", "lane_steps prefill_tokens prefills prefill_rows",
    defaults=(0, 0, 0))
Old = collections.namedtuple("Old", "lane_steps prefills")  # before PR 42

STATE = 64 * 128 * 128 * 4                  # one layer's state a stream
ROWS = 64 * ((2 * 128 + 2 * 128) * 2 + 4 * 128 + 4)     # a token's rows

OPS = {
    "jit__decode/kda_step.3 f32[96,4,16,128]": 0.50,
    "jit__decode/kda_step.9 f32[96,4,16,128]": 0.10,
    "jit__prefill/kda_chunk.5 bf16[64,4096,128]": 0.25,
    "jit__prefill/kda_chunk.11 bf16[64,512,128]": 0.05,
    # not these kernels: the walks, a fusion, the other model's recurrences
    "jit__decode/paged_full_walk.2 bf16[96,8,8,128]": 0.2,
    "jit__decode/fusion.12 bf16[96,4096]": 0.3,
    "jit__decode/ssm_step.1 f32[64,1,5120]": 0.4,
    "jit__prefill/flash_gqa_fwd.7 f32[64,4096,128]": 0.2,
    "jit_other/kda_step.1 f32[8]": 0.9,
}


def obs(ops=OPS, model=MODEL, window=30.0, busy_s=3.0, trace_window_s=4.0):
    return {"kind": "serve", "peak": PEAK, "window_s": window,
            "config": {"model": model},
            "before": {"t": 100.0}, "after": {"t": 100.0 + window},
            "trace": None if ops is None else {
                "op_seconds": ops, "busy_s": busy_s,
                "window_s": trace_window_s}}


@pytest.fixture
def linear_records(monkeypatch):
    """Hand the readers these records in place of the process's rings."""
    def use(recs):
        monkeypatch.setattr(_loop, "records", lambda _obs: recs or None)
    return use


def test_cost_by_hand():
    """A decode update of one stream in one layer: the float32 state read
    and written, 8,388,608 B, and the token's rows (q, k, v, o in two bytes,
    the decay in four, beta), 98,560 B; 7 FLOPs a state element. A prefilled
    row: the same FLOPs, its rows, the state written once a prompt. Six
    layers."""
    assert STATE == 4194304 and ROWS == 98560
    assert _linear.row_bytes(MODEL) == ROWS
    assert _linear.step_cost(1, MODEL) == (
        6 * 7.0 * 64 * 128 * 128, 6.0 * (2 * STATE + ROWS))
    assert _linear.chunk_cost(1000, 2, MODEL) == (
        6 * 7.0 * 64 * 128 * 128 * 1000, 6.0 * (1000 * ROWS + 2 * STATE))
    # memory bounds both
    for fl, nbytes in (_linear.step_cost(96, MODEL),
                       _linear.chunk_cost(4096, 1, MODEL)):
        assert fl / 197e12 < nbytes / 819e9
    # the widths default to the attention's; another model has no such layer
    bare = {"num_heads": 4, "head_dim": 16, "layer_kinds": ["kda", "full"]}
    assert _linear.step_cost(1, bare)[0] == 7.0 * 4 * 16 * 16
    assert _linear.layers({"layer_kinds": ["mamba", "full"]}) == 0
    assert _linear.layers({}) == 0 and _linear.layers(MODEL) == 6


def test_time_shares_read_the_two_kernels_by_name():
    assert kda_step_time_share.read(obs()) == pytest.approx(100 * 0.6 / 3)
    assert kda_chunk_time_share.read(obs()) == pytest.approx(100 * 0.3 / 3)
    # the state-space reader sees its own kernels alone, and none of these
    assert ssm_time_share.read(obs()) == pytest.approx(100 * 0.4 / 3)
    only = {k: v for k, v in OPS.items() if "kda" in k}
    assert ssm_time_share.read(obs(only)) is None
    for reader in (kda_step_time_share, kda_chunk_time_share):
        assert reader.read(obs(None)) is None
        assert reader.read(obs({"jit__decode/fusion.1 f32[8]": 1.0})) is None
        assert reader.read({"kind": "fit"}) is None


def test_rooflines_by_hand_and_never_above_the_peak(linear_records):
    # the rungs the prompts ran in (the last field) are not what is priced
    recs = [Rec(96, 4096 + 512, 2, 6144 + 512), Rec(90), Rec(0, 1024, 1, 1024)]
    linear_records(recs)
    nbytes = 6.0 * 186 * (2 * STATE + ROWS)
    want = 100.0 * (nbytes / 30.0 / 819e9) / (0.6 / 4.0)
    assert kda_step_roofline.read(obs()) == pytest.approx(want)
    cbytes = 6.0 * (5632 * ROWS + 3 * STATE)
    want = 100.0 * (cbytes / 30.0 / 819e9) / (0.3 / 4.0)
    assert kda_chunk_roofline.read(obs()) == pytest.approx(want)
    # a synthetic run AT the peak reads 100 and never more: the kernel's
    # seconds are what the counted bytes take at the HBM peak
    for reader, name, b in ((kda_step_roofline, "jit__decode/kda_step.1",
                             nbytes),
                            (kda_chunk_roofline, "jit__prefill/kda_chunk.1",
                             cbytes)):
        at_peak = {name: 4.0 * b / 30.0 / 819e9}
        assert reader.read(obs(at_peak)) == pytest.approx(100.0)
        slower = {name: 8.0 * b / 30.0 / 819e9}
        assert reader.read(obs(slower)) == pytest.approx(50.0)


def test_nothing_to_read_is_none_and_never_raises(linear_records):
    readers = (kda_step_roofline, kda_chunk_roofline)
    # no records; no decode step and no prompt in the window
    for recs in ([], [Rec(0, 0, 0)]):
        linear_records(recs)
        for reader in readers:
            assert reader.read(obs()) is None
    # records from before prompt tokens were booked: the chunk's share is
    # silent
    linear_records([Old(12, 1)])
    assert kda_chunk_roofline.read(obs()) is None
    assert kda_step_roofline.read(obs()) is not None
    linear_records([Rec(8, 256, 1)])
    for reader in readers:
        assert reader.read(obs(None)) is None                 # no trace
        assert reader.read(obs({"jit__decode/fusion.1": 1.0})) is None
        assert reader.read(dict(obs(), peak=None)) is None
        # another model's trace and counters: the parent's programs
        assert reader.read(obs(model=dict(MODEL, layer_kinds=["full"]))) \
            is None
        assert reader.read({"kind": "fit"}) is None


def test_the_cell_is_in_the_manifest_with_its_files():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    assert len(cell["why"]) <= 200 and "rows an expert" in cell["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["source"] == ("https://huggingface.co/upstage/"
                               "Solar-Open2-250B/blob/main/config.json")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    added = (("kda_step_time_share", "lower"),
             ("kda_chunk_time_share", "lower"),
             ("kda_step_roofline", "higher"),
             ("kda_chunk_roofline", "higher"))
    for name, better in added:
        m = by_name[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"],
                m["better"], m["source"]) == (
            "State-space layers", "serve_out_tok_per_s", [CELL], "%",
            better, "device_trace")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"))
    at = [list(by_name).index(name) for name, _ in added]
    assert at == sorted(at) and at[0] > list(by_name).index(
        "prefill_padded_share")
    # what MiMo's cell reports this one does too, but for the window walk's
    # two (no window layer); and none of the earlier paged calls', the
    # state-space kernels', the latent kernel's or the looped stack's
    share = "mimov25-mixed-closed128"
    for name, m in by_name.items():
        if share in m.get("workloads", ()):
            assert (CELL in m["workloads"]) == (
                not name.startswith("window_attn_")), name
            if CELL in m["workloads"]:      # appended behind it
                assert m["workloads"].index(CELL) \
                    > m["workloads"].index(share), name
    for name in ("paged_attn_time_share", "paged_attn_us_per_live_block",
                 "ssm_time_share", "ssm_step_roofline",
                 "latent_attn_roofline", "prefill_us_per_token",
                 "looped_decode_roofline"):
        assert CELL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_out_tok_per_s"]["workloads"]
    names = [c["name"] for c in manifest["workloads"]]
    assert names.index(CELL) > names.index(share)
    # (a later PR appends behind these: nothing here holds them to be last)

    # the configuration's file: every key of the catalog's config as
    # published but the three reduced; the nested groups copied whole
    cfg = json.load(open(os.path.join(ROOT, entry["file"])))
    catalog = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    assert {k: cfg[k] for k in catalog} == catalog
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (8, 20, 24576)
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["n_routed_experts"] == 320
    assert cfg["published"]["vocab_size"] == 196608
    assert cfg["reduced"] == entry["reduced"]
    assert "rank 0 of 16" in cfg["stands_for"] and cfg["left_out"] == []
    served = cfg["layers_served"]
    assert served == list(range(8))
    m = cfg["model"]
    assert m["layer_kinds"] == [
        "full" if i in cfg["gqa_layers"] else "kda" for i in served]
    assert m["layer_kinds"].count("kda") == 3 * m["layer_kinds"].count("full")
    lin = cfg["linear_attn_config"]
    assert (m["vocab"], m["num_layers"], m["model_dim"], m["num_heads"],
            m["head_dim"], m["num_kv_heads"], m["ffn_dim"], m["kda_heads"],
            m["kda_head_dim"], m["kda_conv"], m["num_experts"],
            m["experts_per_tok"],
            m["shared_experts"], m["experts_held"]) == (
        24576, 8, cfg["hidden_size"], 64, 128, 8,
        cfg["moe_intermediate_size"], lin["num_heads"], lin["head_dim"],
        lin["short_conv_kernel_size"], 320, 8, 1,
        [0, 20])
    assert (m["pos"], m["attn_form"], m["attn_gate"], m["kda_neg_eigval"],
            m["norm_eps"], m["router"], m["n_group"], m["topk_group"],
            m["route_scale"], m["max_len"]) == (
        "none", "gqa", True, True, 1e-5, "sigmoid_group", 1, 1, 1.0, 5120)
    e = cfg["engine"]
    assert (e["block_size"], e["max_batch"], e["spec_k"], e["kv_dtype"],
            e["prefix_cache"]) == (64, 96, 0, "bfloat16", False)
    for key in ("kda_use_full_proj", "gqa_gate", "router", "q_scale",
                "conv_bias", "output_norm", "state_dtype", "block_size",
                "num_blocks", "max_batch", "max_len"):
        assert cfg["assumed"][key], key
    assert cfg["init"]["gate_gain"] > 1 and cfg["notes"] \
        and cfg["departures"]


def test_the_mix_is_what_the_issue_says():
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      CELL + ".json")))
    assert (mix["driver"], mix["loop"], mix["clients"]) == (
        "serve_share", "closed", 192)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 1.0, "min": 64, "max": 4096}
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.6, "min": 128, "max": 1024}
    assert (mix["max_total"], mix["drain_s"], mix["trace_start_s"],
            mix["trace_seconds"]) == (5120, 60, 12.0, 4.0)
    assert mix["rescore"] == [{"max_prompt": 128},
                              {"min_prompt": 1024, "max_prompt": 2048}]
    cap = mix["request_rate_cap"]
    plan = traffic.plan(mix, 11, 30, 24576)
    assert len(plan) == 30 * cap + 192
    assert all(64 <= len(r["tokens"]) <= 4096
               and 128 <= r["max_new_tokens"] <= 1024
               and len(r["tokens"]) + r["max_new_tokens"] <= 5120
               for r in plan)
    lens = sorted(len(r["tokens"]) for r in plan)
    # a few hundred to a few thousand: a tenth under ~150, a tenth over ~1,800
    assert lens[len(lens) // 10] < 200 and lens[-len(lens) // 10] > 1500
    assert lens[-1] == 4096
    # both prompts the judge re-scores are there among the first replies
    head = [len(r["tokens"]) for r in plan[:192]]
    assert any(n <= 128 for n in head) \
        and any(1024 <= n <= 2048 for n in head)
