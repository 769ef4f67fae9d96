"""The benchmark's own tests: they run on the CPU in seconds and start no
TPU library.

    python3 -m pytest benchmark/tests -q

(The builder's contract keeps every file of the benchmark under its
``paths``; the tier-1 command collects ``tests/`` only, so these run beside
it, not inside it.)
"""
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import flops, peaks, stats, trace_reduce, traffic  # noqa: E402

MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def mix(name):
    return json.load(open(os.path.join(BENCH, "traffic", name + ".json")))


CLOSED_CELLS = [c["name"] for c in MANIFEST["workloads"]
                if mix(c["traffic"]).get("loop") == "closed"]


def cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


# ------------------------------------------------------------ manifest --
def test_manifest_has_exactly_the_contract_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(MANIFEST)) <= 64 * 1024
    assert MANIFEST["paths"] == ["benchmark"]
    assert all(not w.startswith("/") and ".." not in w
               for w in MANIFEST["command"])


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_units_and_keys(group):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[group]
    names = [e["name"] for e in MANIFEST[group]]
    assert len(names) == len(set(names))
    for e in MANIFEST[group]:
        assert set(e) <= allowed, e
        assert set(e) >= allowed - {"workloads"}, e
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                    and "\t" not in e[key], e
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES


def test_metrics_moves_and_bounds():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {c["name"] for c in MANIFEST["workloads"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
        # reported only where the metric it moves is
        where = set(m.get("workloads", cells))
        assert where <= set(e2e[m["moves"]].get("workloads", cells)), m
    layers = {}
    for m in MANIFEST["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_cell_reports_enough():
    for c in MANIFEST["workloads"]:
        e2e = [m["name"] for m in MANIFEST["end_to_end"]
               if c["name"] in m.get("workloads", [c["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2, c["name"]
        assert any(c["name"] in m.get("workloads", [c["name"]])
                   for m in MANIFEST["per_layer"])


def test_the_compile_metrics_list_every_cell():
    cells = [c["name"] for c in MANIFEST["workloads"]]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in ("compile_s", "compiles_in_window"):
        assert by_name[name]["workloads"] == cells


def test_plan_used_share_is_listed_in_the_closed_loops_alone():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    m = by_name["plan_used_share"]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert (m["unit"], m["better"], m["source"], m["moves"]) == (
        "%", "lower", "host_clock", "serve_out_tok_per_s")
    # the load generator's other guard spells the layer
    assert m["layer"] == by_name["gen_late_p95_ms"]["layer"] \
        == "Load generator"
    moved = next(e for e in MANIFEST["end_to_end"]
                 if e["name"] == m["moves"])
    assert set(m["workloads"]) <= set(moved["workloads"])
    # every closed loop the manifest has, those of later PRs too, and no
    # other cell
    assert m["workloads"] == CLOSED_CELLS
    assert "gpt2m-longprompt-open" not in m["workloads"]


def test_every_metric_lists_its_cells_in_the_manifests_order():
    """A later PR appends its cell behind the ones that are there, in
    ``workloads`` and in every metric's list alike: nothing stands between,
    nothing is listed twice, nothing that is no cell."""
    cells = [c["name"] for c in MANIFEST["workloads"]]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        listed = m.get("workloads")
        if listed is not None:
            assert listed == [c for c in cells if c in listed], m["name"]


def test_cells_files_and_the_one_four_chip_cell():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    pairs = set()
    for c in MANIFEST["workloads"]:
        assert c["chips"] in (1, 4)
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert (c["config"], c["traffic"]) not in pairs
        pairs.add((c["config"], c["traffic"]))
        cfg = configs[c["config"]]
        assert cfg["file"].startswith("benchmark/")
        assert os.path.exists(os.path.join(ROOT, cfg["file"]))
        m = os.path.join(BENCH, "traffic", c["traffic"] + ".json")
        assert os.path.exists(m), m
        kind = json.load(open(m))["driver"]
        assert os.path.exists(os.path.join(BENCH, "drivers", kind + ".py"))
    assert {c["config"] for c in MANIFEST["workloads"]} == set(configs)
    four = [c for c in MANIFEST["workloads"] if c["chips"] == 4]
    assert len(four) == 1 == max(1, len(MANIFEST["workloads"]) // 4)
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert len(c["reduced"]) <= 16


def test_every_layer_metric_has_a_reader_of_its_own():
    for m in MANIFEST["per_layer"]:
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        assert os.path.exists(path), path


def test_run_seconds_fits_the_full_check_with_24_cells():
    rs = MANIFEST["run_seconds"]
    assert 1 <= rs <= 51 and isinstance(rs, int)
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_files_under_paths_have_allowed_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(d, f), ROOT))


# ------------------------------------------------------------- traffic --
@pytest.mark.parametrize("name,seconds", [("chat-closed64", 30),
                                          ("longprompt-open", 30)])
def test_generator_is_seeded_and_inside_its_clips(name, seconds):
    m = mix(name)
    a = traffic.plan(m, 7, seconds, 50257)
    b = traffic.plan(m, 7, seconds, 50257)
    c = traffic.plan(m, 8, seconds, 50257)
    assert a == b
    assert [r["tokens"] for r in a] != [r["tokens"] for r in c]
    for r in a:
        p, o = len(r["tokens"]), r["max_new_tokens"]
        assert m["prompt_len"]["min"] <= p <= m["prompt_len"]["max"]
        assert m["output_len"]["min"] <= o <= m["output_len"]["max"]
        assert p + o <= 1024
        assert all(0 <= t < 50257 for t in r["tokens"])
    # the same amount of work whatever the seed: one multiset of lengths
    assert sorted(len(r["tokens"]) for r in a) == \
        sorted(len(r["tokens"]) for r in c)
    assert sum(r["max_new_tokens"] for r in a) == \
        sum(r["max_new_tokens"] for r in c)


def test_length_quantiles_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 96, "sigma": 0.9,
            "min": 8, "max": 512}
    import random
    xs = traffic.lengths(spec, 64 * 20, random.Random(1))
    assert abs(stats.median(xs) - 96) <= 3
    assert min(xs) >= 8 and max(xs) <= 512
    u = {"dist": "uniform", "min": 4, "max": 16}
    ys = traffic.lengths(u, 64 * 13, random.Random(1))
    assert set(ys) == set(range(4, 17))
    # the multiset depends on the count alone, never on the seed
    assert sorted(xs) == sorted(traffic.lengths(spec, 64 * 20,
                                                random.Random(2)))
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "zipf"}, 4, random.Random(1))


def test_open_loop_due_times_and_lateness():
    m = dict(mix("longprompt-open"), rate_per_s=12.5)
    plan = traffic.plan(m, 3, 20, 50257)
    due = [r["due"] for r in plan]
    assert len(due) == 250                      # round(rate x seconds)
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20
    assert stats.lateness([1.0, 2.0, 3.0], [1.5, 2.0, 2.9]) == \
        [0.5, 0.0, 0.0]
    # plain Poisson, conditioned on its count: the count per second swings
    counts = [sum(1 for d in due if b <= d < b + 1) for b in range(20)]
    assert sum(counts) == 250 and max(counts) - min(counts) > 1
    assert "arrivals" not in m          # the one process the generator has


def test_warm_up_covers_the_buckets_the_mix_can_reach():
    sys.path.insert(0, BENCH)
    from benchmark.drivers.serve import reachable_buckets
    buckets = [16, 32, 64, 128, 256, 512, 1024]
    assert reachable_buckets(mix("longprompt-open"), buckets) == [512, 1024]
    assert reachable_buckets(mix("chat-closed64"), buckets) == buckets


# ------------------------------------------------ a closed loop's plan --
def test_the_chat_plan_is_one_no_engine_can_use_up():
    m = mix("chat-closed64")
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "gpt2-medium-fp32.json")))
    new = traffic.plan(m, 11, 30, 50257)
    assert len(new) == 30 * 200 + 64
    # what a run serves is what it served under the cap of 40: the callers
    # consume the plan from its head, and a full block of 64 holds the same
    # lengths however many blocks follow (the old plan: 19 full blocks + 48)
    old = traffic.plan(dict(m, request_rate_cap=40), 11, 30, 50257)
    assert len(old) == 30 * 40 + 64 == 19 * traffic.STRATUM + 48
    head = 19 * traffic.STRATUM
    for key in (lambda r: len(r["tokens"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, new[:head])) == sorted(map(key, old[:head]))
        assert sorted(map(key, new[:64])) == \
            sorted(map(key, new[head:head + 64]))
    # where the plan ends: every request but the callers' last in flight
    # answered inside the window. One chip cannot decode this configuration
    # that fast: max_batch streams a step of the weights' bytes at the HBM
    # peak, before a byte of cache is read or a prompt prefilled
    mean_reply = sum(r["max_new_tokens"] for r in new[:64]) / 64.0
    ends_at = (len(new) - m["clients"]) * mean_reply / 30
    peak = peaks.peaks_for("TPU v5 lite")
    weights = 4 * flops.lm_matmul_params(cfg["model"])       # float32
    ceiling = cfg["engine"]["max_batch"] * peak["hbm_bytes_per_s"] / weights
    assert 113 < mean_reply < 114 and 18000 < ceiling < 19000
    assert ends_at > 1.2 * ceiling
    # the old plan ended within reach of the cell's level (4,190-4,340)
    assert 4400 < (len(old) - 64) * mean_reply / 30 < 4600


def closed_obs(sent, cap=40, clients=64, window_s=30.0, **over):
    out = {"kind": "serve", "window_s": window_s,
           "mix": {"loop": "closed", "request_rate_cap": cap,
                   "clients": clients},
           "late_s": [0.0] * sent}
    out.update(over)
    return out


@pytest.mark.parametrize("obs,want", [
    (closed_obs(1216), 100 * 1216 / 1264.0),            # part used
    (closed_obs(1216, cap=200), 100 * 1216 / 6064.0),
    (closed_obs(1264), 100.0),                          # used up
    (closed_obs(77, cap=30, clients=6, window_s=2.5), 100 * 77 / 81.0),
    (closed_obs(0), None),                              # nothing sent
    (closed_obs(300, mix={"loop": "open", "rate_per_s": 10.4}), None),
    ({"kind": "fit", "window_s": 30.0}, None),
], ids=["part-used", "part-used-cap200", "used-up", "short-window",
        "empty", "open-loop", "fit"])
def test_plan_used_share_on_hand_made_observations(obs, want):
    from benchmark.layer_metrics import plan_used_share
    got = plan_used_share.read(obs)
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("cell", CLOSED_CELLS)
def test_a_closed_plan_is_one_no_engine_can_use_up(cell):
    """Every closed loop of the manifest, those of later PRs too: the plan
    ends (every request but the callers' last in flight answered inside the
    window) above 1.2 times what one chip can decode of the configuration,
    ``max_batch`` streams a step of the weights' bytes at the HBM peak,
    before a byte of cache is read or a prompt prefilled. The bytes are
    every parameter's (shapes alone, from the configuration's own
    ``init_params``): an embedding of which a step gathers rows is in them,
    3 to 13% of these four, so the bound is that much lower than the
    streamed bytes' (the chat cell's own case holds it to those)."""
    import jax

    from benchmark import run
    cfg, mod, m, _path, _driver = run.resolve(
        MANIFEST, run.find_cell(MANIFEST, cell))
    shapes = jax.eval_shape(lambda: mod.init_params(cfg, 0))
    weights = sum(leaf.size * leaf.dtype.itemsize
                  for leaf in jax.tree_util.tree_leaves(shapes))
    peak = peaks.peaks_for("TPU v5 lite")
    ceiling = cfg["engine"]["max_batch"] * peak["hbm_bytes_per_s"] / weights
    seconds = MANIFEST["run_seconds"]
    block = traffic.plan(m, 5, seconds, 97)[:traffic.STRATUM]
    mean_reply = sum(r["max_new_tokens"] for r in block) / float(len(block))
    ends_at = (traffic.planned(m, seconds) - m["clients"]) \
        * mean_reply / seconds
    assert ends_at >= 1.2 * ceiling, (ends_at, ceiling)


@pytest.mark.parametrize("cap,clients,seconds,want", [
    (40, 64, 30, 1264), (200, 64, 30, 6064), (30, 6, 2.5, 81),
    (0.5, 3, 3, 5)])
def test_planned_is_the_length_of_the_plan(cap, clients, seconds, want):
    m = {"loop": "closed", "clients": clients, "request_rate_cap": cap,
         "prompt_len": {"dist": "uniform", "min": 2, "max": 4},
         "output_len": {"dist": "uniform", "min": 2, "max": 4},
         "max_total": 16}
    assert traffic.planned(m, seconds) == want \
        == len(traffic.plan(m, 3, seconds, 97))


@pytest.mark.parametrize("unsent,correct", [(0, False), (1, True)],
                         ids=["plan-used-up", "one-request-left"])
def test_judge_refuses_a_run_whose_callers_used_up_the_plan(unsent, correct):
    """``serve.judge`` over a hand-made report: every reply sound, so the
    plan's end is the one thing that can be wrong with the run."""
    import types

    from benchmark.drivers import serve
    m = {"driver": "serve", "loop": "closed", "clients": 4,
         "request_rate_cap": 10,
         "prompt_len": {"dist": "uniform", "min": 4, "max": 9},
         "output_len": {"dist": "uniform", "min": 2, "max": 5},
         "max_total": 32, "rescore": [{}, {}]}
    seconds, vocab, seed = 2, 97, 5
    planned = traffic.plan(m, seed, seconds, vocab)
    assert len(planned) == 24
    records = [{"i": r["i"], "due": 0.01 * r["i"], "sent": 0.01 * r["i"],
                "done": 0.01 * r["i"] + 0.005, "status": "ok",
                "asked": r["max_new_tokens"], "n_out": r["max_new_tokens"],
                "tokens": [1] * r["max_new_tokens"], "ttft_s": 0.001,
                "latency_s": 0.004, "preemptions": 0}
               for r in planned[:len(planned) - unsent]]
    report = {"t0": 0.0, "seconds": seconds, "late_start_s": 0.0,
              "hung_threads": 0, "planned": len(planned),
              "requests": records}
    snap = {"compile": (3, 1.5), "steps": 0, "preemptions": 0,
            "restarts": 0, "resilience": {}}
    said = {}
    ctx = types.SimpleNamespace(
        config={"model": {"vocab": vocab}}, mix=m, seed=seed,
        seconds=seconds, trace=False, t_start=-1.0,
        say=lambda what, **fields: said.update({what: fields}))
    sup = types.SimpleNamespace(engine=types.SimpleNamespace(params=None))
    result = serve.judge(
        ctx, sup, lambda params, prompt, tokens: ([], len(tokens)),
        pool_tokens=0, tracer=None, report=report, before=snap, after=snap,
        final=snap, t0=0.0)
    assert result["correct"] is correct
    assert result["attempted"] == len(records) and result["failed"] == 0
    problems = said["client"]["problems"]
    if correct:
        assert problems == []
    else:
        assert len(problems) == 1 and "request_rate_cap" in problems[0] \
            and "used up the plan's 24 requests" in problems[0]


# ---------------------------------------------------------- arithmetic --
def test_median_of_windows_is_never_the_fastest():
    fences = [0.0, 1.0, 2.0, 2.5, 3.5, 5.5]      # 10 steps each
    rates = stats.window_rates(fences, 10)
    assert rates == [10.0, 10.0, 20.0, 10.0, 5.0]
    assert stats.median_of_windows(fences, 10) == 10.0 < max(rates)
    with pytest.raises(ValueError):
        stats.median_of_windows([1.0], 10)


def test_percentiles_and_the_ten_samples_beyond_rule():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.samples_beyond(200, 95) == 10
    assert stats.samples_beyond(199, 95) == 9
    assert stats.highest_percentile(1000) == 99.0
    assert stats.highest_percentile(200) == 95.0
    assert stats.highest_percentile(199) == 90.0
    assert stats.highest_percentile(30) is None


def test_histogram_delta_percentile():
    before = {"buckets": {"0.1": 10, "0.5": 10, "+Inf": 10}}
    after = {"buckets": {"0.1": 10, "0.5": 30, "+Inf": 30}, "max": 0.4}
    # twenty new observations, all in (0.1, 0.5]
    assert stats.hist_delta_percentile(before, after, 50) == \
        pytest.approx(0.3)
    assert stats.hist_delta_percentile(before, before, 50) is None


# --------------------------------------------------------------- trace --
def test_trace_arithmetic_on_a_hand_made_trace():
    ev = [("module", 0.0, 100.0), ("fusion.1", 0.0, 30.0),
          ("all-reduce.2", 30.0, 20.0), ("fusion.3", 60.0, 30.0),
          ("fusion.4", 200.0, 50.0)]
    assert trace_reduce.busy_ns(ev) == 150.0
    assert trace_reduce.busy_ns(ev, (50.0, 220.0)) == 70.0
    own = trace_reduce.self_times(ev)
    assert own == {"module": 20.0, "fusion.1": 30.0, "all-reduce.2": 20.0,
                   "fusion.3": 30.0, "fusion.4": 50.0}
    assert sum(own.values()) == trace_reduce.busy_ns(ev)
    # the all-reduce runs alone from 30 to 50: all of it is exposed
    assert trace_reduce.exposed_ns(ev) == (20.0, 20.0)
    # a compute op on another line that covers half of it hides that half
    assert trace_reduce.exposed_ns(ev + [("overlap", 40.0, 10.0)]) == \
        (20.0, 10.0)
    gaps = trace_reduce.idle_gaps(
        ev, {"driver": [("step", 0.0, 300.0), ("fetch", 90.0, 100.0)],
             "http": [("wait", 0.0, 1000.0)]}, window=(0.0, 260.0))
    assert gaps == [("fetch", 100e-9), ("step", 10e-9)]


def test_trace_reduction_on_the_recorded_trace():
    path = os.path.join(HERE, "recorded_trace.json")
    data = json.load(open(path))
    expect = data.pop("expect")
    s = trace_reduce.summarize(data)
    assert s["window_s"] == pytest.approx(expect["window_s"])
    assert s["busy_s"] == pytest.approx(expect["busy_s"])
    assert 0.0 < s["busy_s"] <= s["window_s"]
    # own times are a partition of busy time
    assert sum(s["op_seconds"].values()) == pytest.approx(s["busy_s"])
    for plane, d in s["per_device"].items():
        assert d["idle_share"] == pytest.approx(
            expect["idle_share"][plane])
        c = s["collectives"][plane]
        assert 0.0 <= c["exposed_s"] <= c["total_s"]
        assert c["total_s"] == pytest.approx(expect["collective_s"][plane])
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert s["device_ops"][0][0] == expect["top_op"]


# --------------------------------------------------------- flops, peaks --
def test_conv_and_fc_macs_by_hand():
    import mxnet_tpu as mx

    x = mx.sym.Variable("data")
    x = mx.sym.Convolution(data=x, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           stride=(2, 2), no_bias=True, name="c")
    x = mx.sym.Flatten(data=x)
    x = mx.sym.FullyConnected(data=x, num_hidden=10, name="fc")
    net = mx.sym.SoftmaxOutput(data=x, name="softmax")
    macs = flops.symbol_macs(net, data=(2, 3, 8, 8), softmax_label=(2,))
    # conv: 2 x 4 x 4 x 8 outputs, 3*3*3 MACs each; fc: 2 x 10 x 128
    assert macs == 2 * 4 * 4 * 8 * 27 + 2 * 10 * 128
    assert flops.train_flops(macs) == 6 * macs


def test_resnet50_flops():
    from mxnet_tpu import models

    net = models.resnet(num_layers=50, num_classes=1000,
                        image_shape="3,224,224")
    macs = flops.symbol_macs(net, data=(1, 3, 224, 224), softmax_label=(1,))
    # He et al. give 3.8e9 multiply-adds for ResNet-50 with the stride in
    # the 1x1; the reference's symbol strides in the 3x3 (4.09e9)
    assert macs == 4089184256
    # 3000 img/s on one v5e chip
    assert flops.mfu(3000, flops.train_flops(macs), 1, 197e12) == \
        pytest.approx(37.36, abs=0.01)


def test_gpt2_medium_flops_and_bytes_by_hand():
    cfg = {"vocab": 50257, "num_layers": 24, "model_dim": 1024,
           "num_heads": 16, "ffn_dim": 4096, "max_len": 1024}
    body = 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096)      # 301,989,888
    assert flops.lm_matmul_params(cfg) == body + 50257 * 1024
    assert flops.lm_prefill_flops(cfg, 1024) == (
        2 * 1024 * body + 2 * 50257 * 1024 + 24 * 2 * 1024 * 1024 * 1024)
    assert flops.lm_decode_flops(cfg, 32, 8000) == (
        2 * 32 * (body + 50257 * 1024) + 24 * 4 * 8000 * 1024)
    f, b = flops.flash_fwd_cost(16, 1024, 64)
    assert f == 4 * 16 * 1024 * 1024 / 2 * 64 and b == 4 * 16 * 1024 * 64 * 4
    f, b = flops.paged_attn_cost(16, 64, 8000, 32)
    assert f == 4 * 16 * 64 * 8000
    assert b == 2 * 8000 * 16 * 64 * 4 + 2 * 32 * 16 * 64 * 4


def test_roofline_share_names_its_bound():
    peak = peaks.peaks_for("TPU v5 lite")
    share, bound = flops.roofline_share(197e12 * 0.001, 1.0, 0.002, peak)
    assert (round(share, 6), bound) == (50.0, "compute")
    share, bound = flops.roofline_share(1.0, 819e9 * 0.001, 0.004, peak)
    assert (round(share, 6), bound) == (25.0, "memory")


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("TPU v9", "cpu", "_source"):
        with pytest.raises(KeyError):
            peaks.peaks_for(kind)


# ------------------------------------------------------------- command --
def run_cmd(args, cwd=ROOT, **env):
    return subprocess.run(
        [sys.executable] + args, cwd=cwd, env=cpu_env(**env),
        capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", [c["name"] for c in MANIFEST["workloads"]])
def test_no_tpu_no_result(cell):
    r = run_cmd(["benchmark/run.py", "--workload", cell, "--seed", "0",
                 "--seconds", "1", "--trace", "0"])
    assert r.returncode != 0
    assert "metrics" not in r.stdout and "needs a TPU" in r.stderr


def test_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = run_cmd(["benchmark/run.py", "--workload", "resnet50-fit-resident",
                 "--rehearsal"], cwd=str(tmp_path))
    assert r.returncode != 0 and "metrics" not in r.stdout
    r = run_cmd(["benchmark/run.py", "--workload", "fit-tiny",
                 "--rehearsal", "--seconds", "1"], cwd=str(tmp_path))
    assert r.returncode != 0 and '"correct"' not in r.stdout


def test_load_generator_against_a_stub_server(tmp_path):
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Stub(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_POST(self):
            body = json.loads(self.rfile.read(
                int(self.headers["Content-Length"])))
            time.sleep(0.02)
            code = 503 if body["tokens"][0] % 7 == 0 else 200
            data = json.dumps({
                "tokens": [1] * body["max_new_tokens"], "ttft_s": 0.01,
                "latency_s": 0.02, "preemptions": 0}).encode()
            self.send_response(code)
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        for name in ("chat-tiny", "longprompt-tiny"):
            path = os.path.join(BENCH, "rehearsal", "traffic",
                                name + ".json")
            child = subprocess.Popen(
                [sys.executable, os.path.join(BENCH, "loadgen.py"),
                 "--mix", path, "--seed", "4", "--seconds", "1.5",
                 "--vocab", "256", "--port", str(httpd.server_address[1])],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                env=cpu_env())
            assert child.stdout.readline().strip() == "READY"
            t0 = time.time() + 0.2
            child.stdin.write("%r\n" % t0)
            child.stdin.flush()
            out, _ = child.communicate(timeout=60)
            rep = json.loads(out)
            plan = traffic.plan(json.load(open(path)), 4, 1.5, 256)
            assert rep["late_start_s"] == 0.0 and rep["hung_threads"] == 0
            assert rep["planned"] == len(plan)
            for r in rep["requests"]:
                p = plan[r["i"]]
                shed = p["tokens"][0] % 7 == 0
                assert r["status"] == ("shed" if shed else "ok")
                assert r["sent"] >= r["due"] - 1e-6 and r["done"] > r["sent"]
                if not shed:
                    assert r["n_out"] == p["max_new_tokens"] == r["asked"]
            if name == "longprompt-tiny":
                assert len(rep["requests"]) == len(plan) == 12
                late = stats.lateness(
                    [plan[r["i"]]["due"] for r in rep["requests"]],
                    [r["sent"] for r in rep["requests"]])
                assert max(late) < 0.25
            else:
                assert 6 <= len(rep["requests"]) < len(plan)
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_loadgen_never_imports_jax():
    code = ("import sys; sys.argv=['x']; sys.path.insert(0, %r); "
            "import benchmark.loadgen, benchmark.traffic, benchmark.stats; "
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules"
            % ROOT)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=cpu_env())
    assert r.returncode == 0, r.stderr


DUMMY_METRIC = '''
"""A metric a later PR adds as a file of its own."""


def read(obs):
    return 42.0 if obs["kind"] == "fit" else None
'''


def test_a_new_cell_is_files_only(tmp_path):
    """A configuration, a mix and a per-layer metric arrive as NEW files in
    a copy of the tree; no file that was there is edited; the cell runs end
    to end at a rehearsal size."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("mxnet_tpu", "tools", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    before = {}
    for d, _dirs, files in os.walk(tmp_path / "benchmark"):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    reh = tmp_path / "benchmark" / "rehearsal"
    cfg = json.load(open(reh / "configs" / "resnet8-tiny.json"))
    cfg.update(name="dummy-net", batch_per_chip=4)
    json.dump(cfg, open(reh / "configs" / "dummy-net.json", "w"))
    json.dump({"driver": "fit", "warmup_steps": 4, "steps_per_window": 3,
               "trace_start_s": 0.2, "trace_seconds": 0.5},
              open(reh / "traffic" / "dummy-mix.json", "w"))
    (tmp_path / "benchmark" / "layer_metrics" / "dummy_metric.py"
     ).write_text(DUMMY_METRIC)
    cell = json.load(open(reh / "fit-tiny.json"))
    cell["configs"] = [{"name": "dummy-net",
                        "file": "benchmark/rehearsal/configs/dummy-net.json"}]
    cell["workloads"] = [{"name": "dummy-cell", "config": "dummy-net",
                          "traffic": "dummy-mix", "chips": 1}]
    cell["per_layer"].append({
        "name": "dummy_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Fused step",
        "moves": "train_img_per_s"})
    json.dump(cell, open(reh / "dummy-cell.json", "w"))
    r = run_cmd(["benchmark/run.py", "--workload", "dummy-cell",
                 "--rehearsal", "--seed", "5", "--seconds", "1.5",
                 "--trace", "1"], cwd=str(tmp_path),
                MXNET_COMPILE_CACHE_DIR=str(tmp_path / "cache"))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, r.stdout[-3000:]
    assert line["rehearsal"] is True
    # a rehearsal can never be taken for a result
    assert "metrics" not in line and "device" not in line
    assert line["observed"]["dummy_metric"] == {"value": 42.0,
                                                "unit": "count"}
    assert "step_device_ms" in line["observed"]
    for p, content in before.items():
        assert open(p, "rb").read() == content, p
