"""The engine-loop readers (`layer_metrics/_loop.py` and the nine files on
it) over hand-made records, and their entries in the manifest.

    python3 -m pytest benchmark/tests/test_loop_metrics.py -q

The records go through the program's own `ServingObs.step_timeline` into
a ring and come back through `loop_records`, the channel the readers use.
"""
import importlib
import itertools
import json
import os
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.layer_metrics import _loop  # noqa: E402

CELLS = ["gpt2m-chat-closed64", "olmoe-chat-closed64",
         "phi4flash-reason-closed128", "dotsvlm1-chat-closed256"]
#: name -> (unit, better, source, the value over RECORDS' window)
READERS = {
    "host_gap_share": ("%", "lower", "program_span",
                       100.0 * (0.006 + 0.008 + 0.002) / 30.0),
    "host_gap_after_chunk_ms": ("ms", "lower", "program_span", 7.0),
    "host_gap_after_group_ms": ("ms", "lower", "program_span", 2.0),
    "retire_ms_per_chunk": ("ms", "lower", "program_span", 3.5),
    "retire_counters_ms_per_chunk": ("ms", "lower", "program_span", 0.75),
    "step_lock_wait_ms": ("ms", "lower", "program_span", 2.0),
    "decode_steps_per_dispatch": ("count", "higher", "program_counter", 6.0),
    "prefill_prompts_per_group": ("count", "higher", "program_counter", 2.5),
    # 1.2 s of kernel in a 4 s stretch over 24 x 1,500 walks in 30 s
    "paged_attn_us_per_live_block": ("us", "lower", "device_trace", 250.0),
}
#: seconds after the window opens -> what the step's record holds; the
#: first and the last lie outside the window of 30 s
RECORDS = [
    (-10.0, dict(prefills=9, chunk_steps=8, gap_chunk_s=9.0, gap_group_s=9.0,
                 retire_s=9.0, retire_counters_s=9.0, lock_s=9.0,
                 live_blocks=9000, window_live_blocks=900,
                 full_live_blocks=9000)),
    (0.5, dict(prefills=2, chunk_steps=8, gap_chunk_s=0.006,
               gap_group_s=0.002, retire_s=0.004, retire_counters_s=0.001,
               lock_s=0.001, live_blocks=1000, window_live_blocks=100,
               full_live_blocks=1000)),
    (10.0, dict(prefills=0, chunk_steps=4, gap_chunk_s=0.008,
                retire_s=0.002, retire_counters_s=0.0005, lock_s=0.003,
                live_blocks=500, window_live_blocks=50,
                full_live_blocks=500)),
    # a step that admitted three prompts and decoded nothing
    (20.0, dict(prefills=3, retire_s=0.001, lock_s=0.002)),
    (40.0, dict(prefills=9, chunk_steps=8, gap_chunk_s=9.0, gap_group_s=9.0,
                retire_s=9.0, retire_counters_s=9.0, lock_s=9.0,
                live_blocks=9000)),
]
TRACE = {"window_s": 4.0, "busy_s": 3.0, "op_seconds": {
    "jit__decode/branch_0_fun.3": 0.9, "jit__decode/branch_0_fun.4": 0.3,
    "jit__prefill/branch_0_fun.1": 5.0, "jit__decode/fusion.2": 1.0}}


#: a window of its own for every case: the rings of a process outlive the
#: engines (and the cases) that filled them
FAR_OFF = itertools.count(1)


def read(name, obs):
    return importlib.import_module(
        "benchmark.layer_metrics." + name).read(obs)


@pytest.fixture
def window():
    """`obs` of a traced serving run whose window holds the three middle
    records of RECORDS, in the ring of an engine of this process."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving.obs import ServingObs, open_record

    was = telemetry.enabled()
    telemetry.enable()
    o = ServingObs("loop-metrics")
    t0 = 1e6 * next(FAR_OFF)        # 1970: nobody else's records there
    for dt, fields in RECORDS:
        rec = open_record()
        rec.update(fields, ts=t0 + dt)
        o.step_timeline(rec, occupancy=1, admitted=0, preempted=0, queue=0,
                        running=1, kv_used=1, kv_free=1, kv_frag_slots=0)
    if not was:
        telemetry.disable()
    return {"kind": "serve", "before": {"t": t0}, "after": {"t": t0 + 30.0},
            "window_s": 30.0, "trace": TRACE,
            "config": {"model": {"num_layers": 24}}}


@pytest.mark.parametrize("name", sorted(READERS))
def test_loop_reader_over_a_window_inside_the_ring(window, name):
    assert len(_loop.records(window)) == 3
    assert read(name, window) == pytest.approx(READERS[name][3])


@pytest.mark.parametrize("name", sorted(READERS))
def test_loop_reader_finds_nothing_to_read(window, name, monkeypatch):
    # a window that holds no step
    empty = dict(window, before={"t": window["after"]["t"] + 1.0},
                 after={"t": window["after"]["t"] + 2.0})
    assert _loop.records(empty) is None and read(name, empty) is None
    # a driver that serves nothing
    assert read(name, {"kind": "fit", "trace": TRACE}) is None
    # the program before the accessor (the parent of the PR that added it):
    # nothing, and no error
    from mxnet_tpu.serving import obs as program

    monkeypatch.delattr(program, "loop_records")
    assert _loop.records(window) is None and read(name, window) is None


def test_loop_gaps_a_step_does_not_have_add_nothing(window):
    recs = _loop.records(window)
    assert [r.gap_group_s for r in recs] == [0.002, None, None]
    assert _loop.total(recs, "gap_group_s") == 0.002
    assert _loop.seconds(window) == pytest.approx(30.0)
    # the boundary is the step's entry: a step that began inside counts
    edge = dict(window, after={"t": window["before"]["t"] + 10.0})
    assert len(_loop.records(edge)) == 2


def test_paged_block_walks_of_a_model_with_layer_kinds(window):
    from benchmark.layer_metrics import paged_attn_us_per_live_block as m

    kinds = ["mamba", "swa", "mamba", "swa", "full", "gmu", "cross"]
    recs = _loop.records(window)
    assert m.walks(recs, {"num_layers": 24}) == 24 * 1500
    # two window layers' walks and two readers of the full pool
    assert m.walks(recs, {"num_layers": 7, "layer_kinds": kinds}) \
        == 2 * 150 + 2 * 1500
    hybrid = dict(window, config={"model": {"num_layers": 7,
                                            "layer_kinds": kinds}})
    assert m.read(hybrid) == pytest.approx(1e6 * 0.3 / (3300 / 30.0))
    # no trace, or a trace without the kernel: nothing
    assert m.read(dict(window, trace=None)) is None
    assert m.read(dict(window, trace=dict(TRACE, op_seconds={
        "jit__decode/fusion.2": 1.0}))) is None
    # nothing walked (a model of latent layers books none)
    assert m.read(dict(window, config={"model": {
        "num_layers": 5, "layer_kinds": ["mla"] * 5}})) is None


def test_the_manifest_appends_the_nine_loop_metrics():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("host_gap_share")       # later PRs append too
    tail = manifest["per_layer"][first:first + 9]
    assert [m["name"] for m in tail] == list(READERS)
    for m in tail:
        unit, better, source, _v = READERS[m["name"]]
        paged = m["name"].startswith("paged_")
        assert m == {
            "name": m["name"], "unit": unit, "better": better,
            "source": source, "layer": "Kernels" if paged else "Engine loop",
            "moves": "serve_out_tok_per_s",
            # the latent kernel is not `branch_0_fun`
            "workloads": CELLS[:3] if paged else CELLS}
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    # the long-prompt cell idles for want of work and gets none of them;
    # the readers they do not replace stay
    assert all("gpt2m-longprompt-open" not in m["workloads"] for m in tail)
    assert {"decode_idle_host_share", "decode_idle_unnamed_share",
            "prefill_idle_host_share", "prefill_idle_unnamed_share",
            "prefill_idle_nowork_share"} <= set(names[:first])
    paged = manifest["per_layer"][names.index("paged_attn_time_share")]
    assert tail[-1]["workloads"] == paged["workloads"]


def test_the_loop_metrics_read_in_a_rehearsed_run(tmp_path):
    """`run.py` finds each reader by its metric's name and every one reads
    in a real window (`rehearsal/chat-tiny-loop.json`: the tiny chat cell
    with these metrics); what the two counts read is what the engine's
    own `stats()` kept."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_COMPILE_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--rehearsal", "--workload",
         "chat-tiny-loop", "--seed", "7", "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    seen = {k: v["value"] for k, v in line["observed"].items()}
    # the CPU's decode program holds no Pallas call: no kernel seconds
    assert set(READERS) - {"paged_attn_us_per_live_block"} <= set(seen)
    assert 0 < seen["host_gap_share"] < 100
    assert 1 <= seen["decode_steps_per_dispatch"] <= 8
    assert 1 <= seen["prefill_prompts_per_group"]
    assert 0 <= seen["retire_counters_ms_per_chunk"] \
        <= seen["retire_ms_per_chunk"]
