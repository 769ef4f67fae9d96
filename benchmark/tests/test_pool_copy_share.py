"""The pool-copy reader on hand-made observations (CPU, no jax)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.layer_metrics import (_pool_copies,  # noqa: E402
                                     decode_pool_copy_share,
                                     prefill_pool_copy_share)

CONFIG = {"model": {"num_layers": 24}, "engine": {"num_blocks": 513}}


def obs(ops, busy_s=4.0):
    return {"config": CONFIG, "trace": {"op_seconds": ops, "busy_s": busy_s}}


def test_counts_whole_pool_copies_of_both_serving_programs():
    ops = {
        "jit__decode/copy.8 f32[24,513,16,16,64]": 0.4,
        "jit__decode/copy.11 f32[24,513,16,16,64]": 0.4,
        "jit__prefill/copy.240 f32[24,513,16,16,64]": 0.2,
        "jit__prefill/copy-start.3 f32[24,513,16,8,128]": 0.1,
        # not the pool: a layer's slice, a small copy, another program,
        # an op that merely is not a copy
        "jit__decode/slice_bitcast_fusion.44 f32[513,16,16,64]": 0.3,
        "jit__decode/copy.2 f32[32,16,64]": 0.1,
        "jit_step/copy.1 f32[24,513,16,16,64]": 0.5,
        "jit__decode/fusion.7 f32[24,513,16,16,64]": 0.2,
        "jit__decode/branch_0_fun.3 f32[32,8,128]": 0.8,
    }
    assert _pool_copies.share(obs(ops)) == pytest.approx(100 * 1.1 / 4.0)
    assert decode_pool_copy_share.read is prefill_pool_copy_share.read


def test_reads_zero_when_the_pool_stays_put_and_nothing_without_a_trace():
    ops = {"jit__decode/branch_0_fun.3 f32[32,8,128]": 0.8,
           "jit__decode/copy.2 f32[32,16,64]": 0.1}
    assert _pool_copies.share(obs(ops)) == 0.0
    assert _pool_copies.share({"config": CONFIG, "trace": None}) is None
    assert _pool_copies.share(obs({"jit_step/fusion.1 f32[8]": 1.0})) is None


def test_the_manifest_names_both_metrics():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    want = {"decode_pool_copy_share": ("serve_out_tok_per_s",
                                       ["gpt2m-chat-closed64"]),
            "prefill_pool_copy_share": ("req_latency_p50_ms",
                                        ["gpt2m-longprompt-open"])}
    for name, (moves, cells) in want.items():
        m = by_name[name]
        assert (m["layer"], m["moves"], m["workloads"], m["unit"],
                m["better"], m["source"]) == (
            "KV cache", moves, cells, "%", "lower", "device_trace")
    # appended behind what PR 24 left, in this order (later PRs append too)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("prefill_idle_nowork_share") \
        < names.index("decode_pool_copy_share") \
        < names.index("prefill_pool_copy_share")
