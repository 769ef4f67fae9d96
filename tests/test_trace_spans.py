"""The program's spans on the device trace's clock (docs/observability.md
§Spans): `telemetry.span` opens a `jax.profiler` annotation, the serving
engine step names its sections, and the benchmark's readers
(`benchmark/layer_metrics/_gaps.py`) sort the device's idle gaps by them.

One `jax.profiler` session records a tiny `ServingEngine` driven from a
thread, with the options the benchmark's `TraceWindow` uses; the trace is
read back with the benchmark's own `trace_reduce.load_xplane`.
"""
import importlib
import json
import os
import subprocess
import sys
import threading
import time
import types

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.serving import ServingConfig, ServingEngine
from mxnet_tpu.serving.engine import DECODE_CHUNK

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402
from benchmark.layer_metrics import _gaps  # noqa: E402

SECTIONS = ["serving.step.lock", "serving.schedule",
            "serving.prefill.build", "serving.prefill.dispatch",
            "serving.prefill.fetch", "serving.decode.build",
            "serving.decode.dispatch", "serving.decode.fetch",
            "serving.retire"]
NESTED = "serving.retire.finish"      # inside the step's serving.retire
DEFERRED = "serving.retire.deferred"  # a step's item, under the next's chunk
SPAN_NAMES = ["serving.loop.idle", "serving.step", NESTED, DEFERRED] \
    + SECTIONS
#: the fixture's requests: ten tokens each, one from the prefill and nine
#: decode steps, in dispatches of DECODE_CHUNK steps and the rest
N_NEW = 10
CHUNKS = [min(DECODE_CHUNK, N_NEW - 1 - s)
          for s in range(0, N_NEW - 1, DECODE_CHUNK)]
READERS = {"decode_idle_host_share": "host",
           "decode_idle_unnamed_share": "unnamed",
           "prefill_idle_host_share": "host",
           "prefill_idle_unnamed_share": "unnamed",
           "prefill_idle_nowork_share": "no_work"}


def trace_window(out_dir):
    """The benchmark's own `TraceWindow` (host tracer on, Python tracer
    off), writing under ``out_dir``: the options of the run that matters."""
    from benchmark.harness import TraceWindow

    return TraceWindow(types.SimpleNamespace(out_dir=str(out_dir),
                                             platform="cpu"))


def host_events(path):
    """line name -> [(name, start_ns, dur_ns, stats)] of the host plane,
    with each event's stats (the annotations' arguments)."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for k, line in enumerate(plane.lines):
                out["%s#%d" % (line.name, k)] = [
                    (e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats)) for e in line.events]
    return out


@pytest.fixture(scope="module")
def serving_trace(tmp_path_factory):
    """A few engine steps under the profiler: three prompts of different
    lengths, ten tokens each, submitted while the loop idles."""
    cfg = ServingConfig(vocab_size=23, num_layers=2, model_dim=32,
                        num_heads=2, ffn_dim=48, max_len=64, block_size=8,
                        num_blocks=64, max_batch=8, prefills_per_step=4)
    eng = ServingEngine(cfg, seed=3)
    eng.warmup()
    stop = threading.Event()
    # an idle wait far longer than the fixture: an empty queue is ONE
    # `serving.loop.idle` span, ended by a submit's or the stop's notify
    driver = threading.Thread(target=eng.run_loop, args=(stop, 120.0),
                              name="driver")
    bump, x = jax.jit(lambda x: x + 1), jax.numpy.zeros(8)
    jax.block_until_ready(bump(x))                  # compiled, and the
    jax.block_until_ready(eng.pool.k_pages)         # warm-up has ended
    window = trace_window(tmp_path_factory.mktemp("xplane"))
    window.start()
    try:
        # both Python threads' lines are named alike and the reducer keeps
        # ONE of a name, the one with fewer events: this thread's must be
        # the busier (the compile it used to do in here made it so)
        for _ in range(400):
            x = bump(x)
        jax.block_until_ready(x)
        with eng._lock:       # all three before the driver's first step
            reqs = [eng.submit(list(range(1, 6 + i)), N_NEW,
                               request_id="r%d" % i) for i in range(3)]
        driver.start()
        for r in reqs:
            assert r.done_event.wait(60)
        # the device idle under `serving.loop.idle` by construction, not by
        # the warm-up's timing: the span opens before the loop parks on its
        # condition, so once it waits there a device program a quarter of
        # a second later ends a gap of which the span is all but the
        # step's last microseconds
        while not eng._work._waiters:
            time.sleep(0.001)
        time.sleep(0.25)
        # a program of the engine's own: it runs on the client's thread,
        # where the reducer looks for device ops (`bump` runs inline)
        eng.warmup(prefill_buckets=[8])
        jax.block_until_ready(eng.pool.k_pages)
    finally:
        stop.set()
        with eng._work:
            eng._work.notify_all()
        driver.join(60)
        window.stop()
    assert not driver.is_alive()
    path = trace_reduce.newest_xplane(window.dir)
    data = trace_reduce.load_xplane(path, rehearsal=True)
    lines = host_events(path)
    mine = [evs for evs in lines.values()
            if any(e[0] == "serving.step" for e in evs)]
    assert len(mine) == 1, "the driver thread is one line of the host plane"
    others = [e[0] for evs in lines.values() if evs is not mine[0]
              for e in evs if e[0].startswith("serving.")]
    assert not others, "spans on a thread that is not the driver: %s" % others
    return {"data": data, "driver": mine[0], "requests": reqs,
            "stats": eng.stats(), "config": cfg}


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_span_is_on_the_driver_threads_line(serving_trace, name):
    assert any(e[0] == name for e in serving_trace["driver"])
    # the reducer the benchmark uses sees it by the same name
    assert any(e[0] == name for evs in serving_trace["data"]["host"].values()
               for e in evs)


def test_sections_nest_in_the_step_and_do_not_overlap(serving_trace):
    slack = 1e3   # ns; a span lasts tens of microseconds
    evs = serving_trace["driver"]
    steps = [(s, s + d) for n, s, d, _ in evs if n == "serving.step"]
    sections = sorted((s, s + d, n) for n, s, d, _ in evs if n in SECTIONS)
    # a step a dispatch of the decode program, each with its lock,
    # schedule, three of decode and retire at least
    assert len(steps) >= len(CHUNKS) and len(sections) >= 6 * len(steps)
    for s0, s1, name in sections:
        assert any(a - slack <= s0 and s1 <= b + slack for a, b in steps), \
            "%s outside every serving.step" % name
    for (_a0, a1, an), (b0, _b1, bn) in zip(sections, sections[1:]):
        assert a1 <= b0 + slack, "%s overlaps %s" % (an, bn)
    # the wait on an empty queue is outside every step
    for n, s, d, _ in evs:
        if n == "serving.loop.idle":
            assert not any(a < s + d / 2 < b for a, b in steps)


def test_the_finished_sweep_nests_in_the_steps_retire(serving_trace):
    """`serving.retire.finish` is one stretch of the step's `serving.retire`
    and a span of its own: the trace names it, and a device gap whose
    middle falls in it is the host's."""
    slack = 1e3
    evs = serving_trace["driver"]
    retires = [(s, s + d) for n, s, d, st in evs
               if n == "serving.retire" and "request_id" not in st]
    sweeps = [(s, s + d) for n, s, d, _ in evs if n == NESTED]
    steps = [e for e in evs if e[0] == "serving.step"]
    assert len(sweeps) == len(retires) == len(steps)    # one a step
    for (a, b), (s0, s1) in zip(sorted(retires), sorted(sweeps)):
        assert a - slack <= s0 and s1 <= b + slack
    assert _gaps.classify(NESTED) == "host"
    assert NESTED in telemetry.METRIC_HELP
    assert "`%s`" % NESTED in open(
        os.path.join(ROOT, "docs", "observability.md")).read()


def test_the_dispatch_that_closes_a_gap_says_so(serving_trace):
    """The loop's record measures its two gaps on the host's clock; the
    dispatch span that closes one carries it (`gap_us`, `after`), so
    whoever opens the trace sees where the host says the gap ended on the
    device's clock. The fixture's first step admits three prompts: its
    first prefill dispatch has no fetch before it (no gap), its chunk
    closes the group's; every later chunk closes the one before's."""
    closing = [(n, st["after"], st["gap_us"])
               for n, _s, _d, st in sorted(serving_trace["driver"],
                                           key=lambda e: e[1])
               if "after" in st]
    assert [(n, after) for n, after, _us in closing] == [
        ("serving.decode.dispatch", "group")] + [
        ("serving.decode.dispatch", "chunk")] * (len(CHUNKS) - 1)
    assert all(us >= 0 for _n, _a, us in closing)
    loop = serving_trace["stats"]["loop"]
    assert loop["chunks"] == len(CHUNKS) and loop["groups"] == 1
    assert loop["steps_per_dispatch"] == (N_NEW - 1) / len(CHUNKS)
    assert loop["prompts_per_group"] == 3.0
    # the spans' microseconds are the record's seconds
    assert sum(us for _n, _a, us in closing) == pytest.approx(
        1e6 * (loop["sums"]["gap_chunk_s"] + loop["sums"]["gap_group_s"]),
        abs=len(closing) + 2)       # a span's are whole, the sums rounded


def test_arguments_are_readable_from_the_events_stats(serving_trace):
    by_name = {}
    for name, _s, _d, stats in serving_trace["driver"]:
        by_name.setdefault(name, []).append(stats)
    steps = [st["step_num"] for st in by_name["serving.step"]]
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    for part in ("build", "dispatch", "fetch"):
        pre = by_name["serving.prefill." + part]
        assert sorted(st["request_id"] for st in pre) == ["r0", "r1", "r2"]
        assert sorted(st["prompt_len"] for st in pre) == [5, 6, 7]
        assert {st["bucket"] for st in pre} == {8}
        assert {st["prompts"] for st in pre} == {1}     # no experts: alone
        dec = by_name["serving.decode." + part]
        assert {st["batch"] for st in dec} == {3} \
            and {st["bucket"] for st in dec} == {4}
        # one dispatch a chunk; the contexts are its first step's: three
        # streams of 5+6+7 tokens one token further each step, the padding
        # lane's single token included
        assert [st["steps"] for st in dec] == CHUNKS
        first = dec[0]
        assert first["ctx_tokens"] == 5 + 6 + 7 + 3 + 1 \
            and first["ctx_max"] == 8
        assert [st["ctx_tokens"] for st in dec] == [
            first["ctx_tokens"] + 3 * sum(CHUNKS[:i])
            for i in range(len(dec))]
    sched = by_name["serving.schedule"][0]
    assert sched["waiting"] == 3 and sched["running"] == 0
    retired = [st["finished"] for st in by_name["serving.retire"]
               if "finished" in st]
    assert sum(retired) == 3 and retired[-1] == 3
    # what a chunk's steps walked is booked after its fetch, on the retire
    booked = [st for st in by_name["serving.retire"] if "steps" in st]
    assert [st["steps"] for st in booked] == CHUNKS
    assert [st["lane_steps"] for st in booked] == [3 * n for n in CHUNKS]
    assert sorted(st["request_id"] for st in by_name["serving.retire"]
                  if "request_id" in st) == ["r0", "r1", "r2"]
    assert all(len(r.generated) == N_NEW
               for r in serving_trace["requests"])


def test_no_dispatch_before_the_previous_fetch_returned(serving_trace):
    """The host is synchronous at every fetch: a decode program is
    dispatched only after the previous one's tokens were fetched (nothing
    is in flight while the host retires, frees and schedules), and the
    chunk counter says how many steps a dispatch ran."""
    evs = sorted((s, s + d, n) for n, s, d, _ in serving_trace["driver"]
                 if n in ("serving.decode.dispatch", "serving.decode.fetch"))
    assert [n for _s, _e, n in evs] == [
        "serving.decode.dispatch", "serving.decode.fetch"] * len(CHUNKS)
    for (_s0, e0, _n0), (s1, _e1, _n1) in zip(evs, evs[1:]):
        assert e0 <= s1
    # nor before the step's group of prefills was fetched to its last: the
    # first tokens are all on the host when the chunk is built
    last_fetch = max(s + d for n, s, d, _ in serving_trace["driver"]
                     if n == "serving.prefill.fetch")
    assert last_fetch <= evs[0][0]
    dec = serving_trace["stats"]["decode"]
    assert dec == {"dispatches": len(CHUNKS), "inner_steps": N_NEW - 1,
                   "steps_per_dispatch": (N_NEW - 1) / len(CHUNKS)}
    for name in ("serving.decode.dispatches", "serving.decode.inner_steps"):
        assert name in telemetry.METRIC_HELP
        assert "`%s`" % name in open(
            os.path.join(ROOT, "docs", "observability.md")).read()


def test_a_groups_dispatches_precede_its_fetches(serving_trace):
    """The three prompts were admitted in one step: their prefills are one
    group. On the trace the step reads `build, dispatch` x 3 and then
    `fetch, retire` x 3, each half in plan order: every dispatch has
    returned before the first blocking fetch starts, so one host gap is
    exposed to the device where three were."""
    evs = sorted((s, s + d, n, st.get("request_id"))
                 for n, s, d, st in serving_trace["driver"]
                 if n.startswith("serving.prefill.")
                 or (n == "serving.retire" and "request_id" in st))
    rids = ["r0", "r1", "r2"]
    assert [(n, rid) for _s, _e, n, rid in evs] == [
        (n, rid) for rid in rids
        for n in ("serving.prefill.build", "serving.prefill.dispatch")] + [
        (n, rid) for rid in rids
        for n in ("serving.prefill.fetch", "serving.retire")]
    dispatched = max(e for _s, e, n, _r in evs
                     if n == "serving.prefill.dispatch")
    assert dispatched <= min(s for s, _e, n, _r in evs
                             if n == "serving.prefill.fetch")
    assert serving_trace["stats"]["prefill"] == {
        "prompts": 3, "groups": 1, "prompts_per_group": 3.0,
        "programs": 3, "prompts_per_program": 1.0, "syncs_saved": 2,
        "stopped_by": {"lanes": 0, "pool": 0, "slots": 0, "cap": 0,
                       "preempted": 0, "queue": 1}}


def test_paged_counters_count_the_blocks_the_kernel_walks(serving_trace):
    """`serving.paged.live_blocks` / `.table_slots` by hand: prompts of 5,
    6 and 7 tokens, nine decode steps each, blocks of 8, tables of 8 —
    booked a step, whatever the chunks the steps ran in."""
    cfg = serving_trace["config"]
    nb_max = cfg.max_len // cfg.block_size
    steps = [[len(r.prompt) + 1 + s for r in serving_trace["requests"]]
             for s in range(N_NEW - 1)]
    assert steps[0] == [6, 7, 8] and steps[-1] == [14, 15, 16]
    by_step = [sum(-(-ctx // cfg.block_size) for ctx in step)
               for step in steps]
    assert by_step == [3, 4, 5, 6, 6, 6, 6, 6, 6]
    paged = serving_trace["stats"]["paged"]
    assert paged["live_blocks"] == sum(by_step) == 48
    assert paged["table_slots"] == 9 * 3 * nb_max == 216
    assert paged["live_share"] == pytest.approx(48 / 216)
    ends = np.cumsum(CHUNKS)
    assert [st["live_blocks"] for name, _s, _d, st
            in serving_trace["driver"]
            if name == DEFERRED and "live_blocks" in st] == [
        sum(by_step[e - n:e]) for n, e in zip(CHUNKS, ends)]
    for name in ("serving.paged.live_blocks", "serving.paged.table_slots"):
        assert name in telemetry.METRIC_HELP
        assert "`%s`" % name in open(
            os.path.join(ROOT, "docs", "observability.md")).read()


def test_idle_gaps_carry_the_programs_labels(serving_trace):
    gaps = trace_reduce.summarize(serving_trace["data"])["idle_gaps"]
    labels = [label for label, _s in gaps]
    assert "serving.loop.idle" in labels
    assert any(_gaps.classify(label) == "host" for label in labels)
    obs = {"trace": {"idle_gaps": gaps, "window_s": 1.0}}
    assert _gaps.share(obs, "host") > 0 and _gaps.share(obs, "no_work") > 0


def test_fit_step_is_a_step_annotation(tmp_path):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(np.random.rand(12, 6).astype(np.float32),
                           np.zeros(12, np.float32), batch_size=4)
    mod = mx.mod.Module(net, context=mx.cpu())
    window = trace_window(tmp_path)
    window.start()
    try:
        mod.fit(it, num_epoch=1, optimizer="sgd")
    finally:
        window.stop()
    lines = host_events(trace_reduce.newest_xplane(window.dir))
    evs = [e for line in lines.values() for e in line]
    steps = [st for n, _s, _d, st in evs if n == "fit.step"]
    assert [st["step_num"] for st in steps] == [0, 1, 2]
    assert steps[1]["epoch"] == 0 and steps[1]["nbatch"] == 1
    assert any(n == "fit.data_wait" for n, _s, _d, _st in evs)


# ------------------------------------------------------------ the readers --
def obs_of(gaps, window_s=4.0):
    return {"trace": {"idle_gaps": gaps, "window_s": window_s}}


@pytest.mark.parametrize("label,kind", [
    ("serving.schedule", "host"), ("serving.step", "host"),
    ("serving.decode.build", "host"), ("serving.loop.idle", "no_work"),
    (trace_reduce.UNTRACED, "unnamed"),
    ("np.asarray(jax.Array)", "runtime"),
    ("PjitFunction(_decode)", "runtime")])
def test_gap_classes(label, kind):
    assert _gaps.classify(label) == kind
    obs = obs_of([(label, 0.2), ("serving.retire", 0.1)])
    want = {"host": 2.5}
    want[kind] = want.get(kind, 0.0) + 5.0
    for k in ("host", "no_work", "unnamed", "runtime"):
        assert _gaps.share(obs, k) == pytest.approx(want.get(k, 0.0))


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers(name):
    read = importlib.import_module("benchmark.layer_metrics." + name).read
    assert read({"kind": "serve"}) is None          # an untraced run
    assert read({"trace": None}) is None
    gaps = [("serving.schedule", 0.08), (trace_reduce.UNTRACED, 0.02),
            ("serving.loop.idle", 0.04), ("np.asarray(jax.Array)", 0.1),
            ("serving.retire", 0.04)]
    want = {"host": 3.0, "unnamed": 0.5, "no_work": 1.0}[READERS[name]]
    assert read(obs_of(gaps)) == pytest.approx(want)
    # a program from before the spans: its unnamed share reads, the two
    # classes only spans can fill read nothing (not a zero)
    parent = obs_of([(trace_reduce.UNTRACED, 0.24),
                     ("np.asarray(jax.Array)", 0.112)])
    if READERS[name] == "unnamed":
        assert read(parent) == pytest.approx(6.0)
    else:
        assert read(parent) is None


def test_readers_see_only_the_ten_largest_labels():
    """`idle_gaps` keeps ten labels: a class can miss a sliver."""
    ops = [("op", 100.0 * i, 10.0) for i in range(14)]   # 13 gaps of 90 ns
    names = ["serving.s%d" % i for i in range(12)] + [None]
    host = {"driver": [(n, 100.0 * i + 10.0, 90.0 + i)   # longer = ranked
                       for i, n in enumerate(names) if n]}
    gaps = trace_reduce.idle_gaps(ops, host)
    assert len(gaps) == 10
    # the gap under no event and two of the twelve spans fell below the cut
    assert trace_reduce.UNTRACED in dict(trace_reduce.idle_gaps(
        ops, host, top=13))
    obs = obs_of(gaps, window_s=13 * 90e-9)
    assert _gaps.share(obs, "host") == pytest.approx(100.0 * 10 / 13)
    assert _gaps.share(obs, "unnamed") == 0.0


def test_manifest_appends_the_five_readers():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("decode_idle_host_share")   # later PRs append too
    tail = manifest["per_layer"][first:first + 5]
    assert [m["name"] for m in tail] == [
        "decode_idle_host_share", "decode_idle_unnamed_share",
        "prefill_idle_host_share", "prefill_idle_unnamed_share",
        "prefill_idle_nowork_share"]
    for m in tail:
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "lower", "program_span", "Engine loop")
        cell = ("gpt2m-chat-closed64" if m["name"].startswith("decode")
                else "gpt2m-longprompt-open")
        # later cells are appended: the first is this one, the rest are
        # cells of the manifest
        assert m["workloads"][0] == cell
        assert set(m["workloads"][1:]) <= {
            w["name"] for w in manifest["workloads"]} - {cell}


# ------------------------------------------------------- the span itself --
def test_span_with_everything_off_is_only_an_annotation():
    telemetry.disable()
    assert not profiler.is_running()
    before = set(telemetry.dump()["histograms"])
    # the best of twenty batches: the gate's other workers take the cores
    # away for milliseconds at a time, which a single mean would book to
    # the span (five batches read 6.3 us once under the gate, PR 32)
    n, per_span = 2000, float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.span("spans.off", "test", batch=3):
                pass
        per_span = min(per_span, (time.perf_counter() - t0) / n)
    assert per_span < 5e-6, "a span costs %.2f us with all off" % (
        per_span * 1e6)
    s = telemetry.span("spans.off", "test")
    with s:
        pass
    assert s._ann is not None and s._t0 is None      # no clock was read
    assert set(telemetry.dump()["histograms"]) == before
    assert not profiler._state["events"]             # no chrome event


class CountedTime:
    """``time`` for telemetry.py, counting the wall-clock reads."""

    def __init__(self):
        self.walls = 0

    def time(self):
        self.walls += 1
        return time.time()

    def __getattr__(self, name):
        return getattr(time, name)


def test_a_closed_span_keeps_its_seconds_and_reads_no_wall_clock(
        monkeypatch, tmp_path):
    """`span.seconds` is what the histogram got; the wall clock is the
    chrome trace's alone and is read only while the MXNet-API profiler
    runs."""
    clock = CountedTime()
    monkeypatch.setattr(telemetry, "time", clock)
    was = telemetry.enabled()
    telemetry.enable()
    try:
        s = telemetry.span("spans.seconds", "test")
        assert s.seconds is None
        with s:
            assert s.seconds is None            # still open
            time.sleep(0.002)
        h = telemetry.histogram("spans.seconds")
        assert 0.002 <= s.seconds == h.sum and h.count == 1
        assert clock.walls == 0 and s._wall0 is None
        # the profiler on: one wall-clock read a span, for its event's ts
        profiler.profiler_set_config(mode="all",
                                     filename=str(tmp_path / "c.json"))
        profiler.profiler_set_state("run")
        try:
            t0 = time.time()
            with telemetry.span("spans.seconds", "test") as s2:
                pass
            assert clock.walls == 1 and s2.seconds > 0
            ev = [e for e in profiler._state["events"]
                  if e["name"] == "spans.seconds"]
            assert len(ev) == 1 and t0 * 1e6 <= ev[0]["ts"] \
                <= time.time() * 1e6
            # a span the profiler started under: no event, no error
            profiler.profiler_set_state("stop")
            with telemetry.span("spans.late", "test") as s3:
                profiler.profiler_set_state("run")
            assert s3.seconds > 0 and clock.walls == 1
            assert not [e for e in profiler._state["events"]
                        if e["name"] == "spans.late"]
        finally:
            profiler.profiler_set_state("stop")
    finally:
        if not was:
            telemetry.disable()
    # everything off: no clock at all, no seconds
    telemetry.disable()
    try:
        with telemetry.span("spans.off", "test") as off:
            pass
        assert off.seconds is None and off._t0 is None
    finally:
        if was:
            telemetry.enable()


def test_span_feeds_the_chrome_trace_with_late_arguments(tmp_path):
    fname = str(tmp_path / "chrome.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    try:
        with telemetry.span("spans.step", "test", step=7, batch=2) as s:
            s.set(finished=1)
    finally:
        profiler.profiler_set_state("stop")
    profiler.dump_profile()
    evs = [e for e in json.load(open(fname))["traceEvents"]
           if e["name"] == "spans.step"]
    assert len(evs) == 1 and evs[0]["cat"] == "test"
    assert evs[0]["args"] == {"batch": 2, "finished": 1}


def test_per_operator_spans_open_no_annotation(tmp_path):
    assert profiler.record_span("op") is profiler._OFF
    profiler.profiler_set_config(mode="all",
                                 filename=str(tmp_path / "ops.json"))
    profiler.profiler_set_state("run")
    try:
        s = profiler.record_span("op")
        with s:
            pass
        assert type(s) is type(telemetry.span("x")) and s._ann is None
        assert [e["name"] for e in profiler._state["events"]] == ["op"]
    finally:
        profiler.profiler_set_state("stop")
    assert "op" not in telemetry.dump()["histograms"]


def test_importing_telemetry_starts_no_backend():
    code = ("import mxnet_tpu.telemetry, jax; "
            "from jax._src import xla_bridge; "
            "assert not xla_bridge._backends, xla_bridge._backends; "
            "print('no backend')")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0 and "no backend" in r.stdout, r.stderr[-2000:]


def test_one_span_system():
    """`TraceAnnotation` is named in telemetry.py alone."""
    hits = []
    for d, _dirs, files in os.walk(os.path.join(ROOT, "mxnet_tpu")):
        for f in files:
            if f.endswith(".py") and "TraceAnnotation" in open(
                    os.path.join(d, f), encoding="utf-8").read():
                hits.append(os.path.relpath(os.path.join(d, f), ROOT))
    assert hits == ["mxnet_tpu/telemetry.py"]
