"""The state-space readers and their cost function on hand-made
observations (CPU, no jax), and the files of the
``phi4flash-reason-closed128`` cell."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import traffic  # noqa: E402
from benchmark.layer_metrics import (_ssm, moe_time_share,  # noqa: E402
                                     paged_attn_time_share,
                                     ssm_step_roofline, ssm_time_share)

CELL = "phi4flash-reason-closed128"
MODEL = {"model_dim": 2560, "ssm_expand": 2, "ssm_state": 16,
         "layer_kinds": ["mamba", "swa"] * 8 + ["mamba", "full"]
         + ["gmu", "cross"] * 7}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

OPS = {
    "jit__decode/ssm_step.3 f32[64,1,5120]": 0.30,
    "jit__decode/ssm_step.11 f32[64,1,5120]": 0.10,
    "jit__prefill/ssm_scan.2 f32[1024,5120]": 0.20,
    # not the state-space kernels: the paged kernel, a fusion, another program
    "jit__decode/branch_0_fun.8 bf16[64,4,10,1,128]": 0.9,
    "jit__decode/fusion.12 bf16[64,5120]": 0.3,
    "jit_other/ssm_step.1 f32[8]": 0.7,
}


def obs(ops=None, steps=None, busy_s=3.0, trace_window_s=4.0, model=MODEL):
    out = {"kind": "serve", "config": {"model": model}, "peak": PEAK,
           "window_s": 30.0,
           "trace": None if ops is None else {
               "op_seconds": ops, "busy_s": busy_s,
               "window_s": trace_window_s}}
    if steps is not None:
        out["before"] = {"decode_batch": (100, 5000.0)}
        out["after"] = {"decode_batch": (100 + steps // 64,
                                         5000.0 + steps)}
    return out


def test_step_cost_by_hand():
    assert _ssm.state_layers(MODEL) == 9
    fl, nbytes = _ssm.step_cost(1, MODEL)
    state = 2 * 16 * 5120 * 4                   # read and written: 655 KB
    rows = (2 + 2 + 4 + 2 + 2) * 5120           # x, z, dt in; y, out back
    assert nbytes == 9 * (state + rows + 2 * 16 * 4) == 6452352
    assert fl == 9 * 7 * 16 * 5120
    # 64 streams a step: 0.41 GB, 0.5 ms at the HBM peak; the FLOPs nothing
    fl, nbytes = _ssm.step_cost(64, MODEL)
    assert nbytes / 819e9 == pytest.approx(0.504e-3, rel=1e-2)
    assert fl / 197e12 < 2e-6
    assert _ssm.step_cost(5, {"model_dim": 64, "ssm_expand": 2,
                              "ssm_state": 16,
                              "layer_kinds": ["mamba", "gmu"]},
                          act_itemsize=4) == (
        5 * 7 * 16 * 128, 5 * (2 * 4 * 16 * 128 + 20 * 128 + 128))


def test_time_share_counts_the_named_kernels_only():
    assert ssm_time_share.read(obs(OPS)) == pytest.approx(100 * 0.6 / 3.0)
    # and the other kernels' readers count no state-space op
    assert paged_attn_time_share.read(obs(OPS)) == pytest.approx(
        100 * 0.9 / 3.0)
    assert moe_time_share.read(obs(OPS)) is None
    # nothing to read: no trace, or a program from before the kernels
    assert ssm_time_share.read(obs(None)) is None
    assert ssm_time_share.read(obs({"jit__decode/fusion.1 f32[8]": 1.0})) \
        is None


def test_roofline_from_the_decode_batch_histogram_and_the_trace():
    # 30 s of window: 1,000 decode steps of 64 live streams
    o = obs(OPS, steps=64000)
    _fl, nbytes = _ssm.step_cost(64000, MODEL)
    want = 100.0 * (nbytes / 819e9 / 30.0) / (0.4 / 4.0)
    assert ssm_step_roofline.read(o) == pytest.approx(want)
    assert 16 < want < 18           # 0.504 s of state traffic in 30 s
    # the prefill kernel's seconds are not the decode update's
    only_scan = {k: v for k, v in OPS.items() if "ssm_step" not in k}
    assert ssm_step_roofline.read(obs(only_scan, steps=64000)) is None
    # nothing to read: no histogram (the parent), no trace, no such layer,
    # nothing decoded
    assert ssm_step_roofline.read(obs(OPS)) is None
    assert ssm_step_roofline.read(obs(None, steps=64000)) is None
    assert ssm_step_roofline.read(obs(OPS, steps=0)) is None
    assert ssm_step_roofline.read(
        obs(OPS, steps=64000, model={"model_dim": 1024})) is None
    gpt2 = obs(OPS, steps=64000)
    gpt2["config"] = {"model": {"vocab": 50257, "num_layers": 24}}
    assert ssm_step_roofline.read(gpt2) is None


def test_the_cell_is_in_the_manifest_with_its_files():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(c for c in manifest["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4-mini-flash-bf16", CELL, 1)
    assert "1,024" in cell["why"] and len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "phi4-mini-flash-bf16")
    assert entry["reduced"] == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("ssm_time_share", "ssm_step_roofline"):
        m = by_name[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"]) == (
            "State-space layers", "serve_out_tok_per_s", [CELL], "%")
    for name in ("tpot_p50_ms", "decode_occupancy", "kv_pool_tokens",
                 "preemptions", "paged_attn_time_share", "decode_idle_share",
                 "decode_idle_host_share", "decode_idle_unnamed_share",
                 "peak_hbm_gb"):
        assert CELL in by_name[name]["workloads"]
    # the two compile metrics list every cell there is, in the manifest's
    # order (test_benchmark_harness.py holds "every"; here: as far as this)
    cells = [c["name"] for c in manifest["workloads"]]
    for name in ("compile_s", "compiles_in_window"):
        listed = by_name[name]["workloads"]
        assert CELL in listed and listed == cells[:len(listed)]
    for name in by_name:
        if name.startswith("moe_") or name.endswith("_pool_copy_share"):
            assert CELL not in by_name[name]["workloads"]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["serve_out_tok_per_s"]["workloads"]
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "phi4-mini-flash-bf16.json")))
    # the catalog's numbers, under its keys, at the top level
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["num_hidden_layers"], cfg["vocab_size"],
            cfg["max_position_embeddings"], cfg["sliding_window"],
            cfg["mb_per_layer"], cfg["layer_norm_eps"],
            cfg["tie_word_embeddings"]) == (
        2560, 10240, 40, 20, 32, 200064, 262144, 512, 2, 1e-5, True)
    m, e = cfg["model"], cfg["engine"]
    assert cfg["reduced"] == [] and m["num_layers"] == 32
    assert (m["model_dim"], m["ffn_dim"], m["num_heads"], m["num_kv_heads"],
            m["head_dim"], m["vocab"], m["window"], m["max_len"]) == (
        2560, 10240, 40, 20, 64, 200064, 512, 4096)
    kinds = m["layer_kinds"]
    assert [kinds.count(k) for k in ("mamba", "swa", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert (e["block_size"], e["max_batch"], e["spec_k"],
            e["prefix_cache"], e["kv_dtype"]) == (64, 64, 0, False,
                                                  "bfloat16")
    # 64 streams at max_total 2,560 in the full pool (the engine sizes the
    # window pool and the state slots from max_batch)
    assert (e["num_blocks"] - 1) * e["block_size"] >= 64 * 2560


def test_the_mix_is_what_the_issue_says_and_its_picks_fit_the_reference():
    mix = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                      CELL + ".json")))
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "phi4-mini-flash-bf16.json")))
    assert (mix["driver"], mix["loop"], mix["clients"],
            mix["request_rate_cap"], mix["drain_s"]) == (
        "serve_arch", "closed", 128, 30, 60)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.8, "min": 32, "max": 1536}
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.6, "min": 128, "max": 1024}
    assert mix["max_total"] == 2560 <= cfg["model"]["max_len"]
    assert (mix["trace_start_s"], mix["trace_seconds"]) == (12.0, 4.0)
    assert mix["rescore"] == [{"max_prompt": 128},
                              {"min_prompt": 600, "max_prompt": 1200}]
    ref = cfg["reference"]
    assert 1200 + mix["output_len"]["max"] <= ref["seq_pad"]
    assert mix["output_len"]["max"] <= ref["gen_max"]
    assert ref["probe_len"] <= cfg["model"]["max_len"]
    plan = traffic.plan(mix, 2931000123, 30, cfg["model"]["vocab"])
    assert len(plan) == 30 * mix["request_rate_cap"] + 128
    for want in mix["rescore"]:
        assert any(want.get("min_prompt", 0) <= len(r["tokens"])
                   <= want.get("max_prompt", 1 << 30) for r in plan[:128])
    # nearly every stream crosses the window while decoding
    crossing = [r for r in plan[:128]
                if len(r["tokens"]) < 512 < len(r["tokens"])
                + r["max_new_tokens"]]
    assert len(crossing) >= 64


def test_the_tiny_cell_rehearses_on_the_cpu():
    """``benchmark/rehearsal/phi4flash-tiny.json`` end to end: the
    configuration's module, ``serve_arch``, the probe through
    ``prefill_logits(decode_from=)``, the re-scored requests and every
    reader of the manifest — where a reader finds nothing (the CPU runs
    the XLA paths: no ``ssm_step`` op) the metric is left out and nothing
    raises."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearsal", "--workload", "phi4flash-tiny", "--seed", "3",
         "--seconds", "2", "--trace", "1"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] is True and "metrics" not in last
    assert {"decode_occupancy", "kv_pool_tokens", "compile_s"} <= set(
        last["observed"])
    assert "ssm_step_roofline" not in last["observed"]
    ref = next(l for l in lines if l.get("bench") == "reference")["logits"]
    assert ref["rows"] == 8 and ref["decode_quartile"] is not None
    assert ref["quartile"] <= ref["band"]
    engine = next(l for l in lines if l.get("bench") == "engine")
    assert engine["pool_tokens"] == 64 * 16
