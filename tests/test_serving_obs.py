"""Serving observability plane (docs/serving.md §observability): the
RequestTrace phase clock (attribution closes — the five phases sum
EXACTLY to end-to-end wall), compile-stall debiting, the ServingObs
lifecycle event stream, SLO counters/goodput/burn-edge, two-engine stats
isolation (a second engine in the process must not inherit the first
one's numbers), the serve.py HTTP surface (/healthz, /stats, /metrics
schemas + X-Request-Id round-trip), the request_segments walker shared
by serving_report.py and trace_merge.py — capped by a slow e2e that
drives a preemption + cold-bucket compiles through a telemetry JSONL
sink and proves the waterfall/trace tools close the attribution.

Host-side only: part of tier-1 (tests/conftest.py pins jax to the CPU);
`ci/run_tests.sh serving` runs the serving files alone, slow cases
included.
"""
import itertools
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from mxnet_tpu.serving import engine as engine_mod  # noqa: E402
from mxnet_tpu.serving import obs as obs_mod  # noqa: E402
from mxnet_tpu.serving.obs import (  # noqa: E402
    BURN_THRESHOLD, LOOP_RING, PHASES, LoopRecord, RequestTrace, ServingObs,
    loop_records, open_record)

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# same tiny config as test_serving.py: each engine pays its own XLA
# compiles on this 1-core host — keep the model small
CFG = dict(vocab_size=23, num_layers=2, model_dim=32, num_heads=2,
           ffn_dim=48, max_len=64)
SEED = 3


def _config(**over):
    kw = dict(CFG, block_size=8, num_blocks=64, max_batch=8,
              prefills_per_step=4)
    kw.update(over)
    return ServingConfig(**kw)


@pytest.fixture
def telem():
    """Clean, enabled registry; restore the default disabled state."""
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


# ---------------------------------------------------------------------------
# RequestTrace: the phase clock
# ---------------------------------------------------------------------------


def test_phase_clock_partitions_wall_exactly():
    """Phases telescope: whatever transitions happen, the settled phases
    sum EXACTLY to close_t - t0 (the invariant serving_report relies on)."""
    tr = RequestTrace(10.0)
    tr.to_phase("prefill", 10.5)     # queue_wait = 0.5
    tr.to_phase("decode", 11.25)     # prefill    = 0.75
    tr.to_phase("replay", 12.0)      # decode     = 0.75
    tr.to_phase("decode", 12.6)      # replay     = 0.6
    tr.close(13.0)                   # decode    += 0.4
    assert tr.closed
    assert tr.phases["queue_wait"] == pytest.approx(0.5)
    assert tr.phases["prefill"] == pytest.approx(0.75)
    assert tr.phases["decode"] == pytest.approx(1.15)
    assert tr.phases["replay"] == pytest.approx(0.6)
    assert tr.phases["compile_stall"] == 0.0
    assert tr.total() == pytest.approx(13.0 - 10.0, abs=1e-9)
    assert set(tr.phases) == set(PHASES)


def test_stall_debit_is_conserved():
    """add_stall moves wall INTO compile_stall and OUT of the enclosing
    phase — the total is conserved, nothing is double-counted."""
    tr = RequestTrace(0.0)
    tr.to_phase("prefill", 1.0)
    tr.add_stall(0.7)                # prefill dispatch compiled for 0.7s
    tr.to_phase("decode", 2.0)       # prefill settles 1.0 - 0.7 = 0.3
    tr.add_stall(0.25)               # cold decode bucket
    tr.close(3.0)                    # decode settles 1.0 - 0.25 = 0.75
    assert tr.phases["compile_stall"] == pytest.approx(0.95)
    assert tr.phases["prefill"] == pytest.approx(0.3)
    assert tr.phases["decode"] == pytest.approx(0.75)
    assert tr.total() == pytest.approx(3.0, abs=1e-9)


def test_closed_trace_is_frozen():
    """Terminal means terminal: late hooks (a race-y driver) are no-ops."""
    tr = RequestTrace(0.0)
    tr.close(1.0)
    snap = dict(tr.phases)
    tr.to_phase("decode", 5.0)
    tr.add_stall(2.0)
    tr.close(9.0)
    assert tr.phases == snap
    assert tr.total() == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# ServingObs: lifecycle events + SLO accounting (synthetic requests)
# ---------------------------------------------------------------------------


class _FakeReq:
    """The attribute surface ServingObs reads off a scheduler Request."""

    def __init__(self, rid, arrival_t):
        self.request_id = rid
        self.arrival_t = arrival_t
        self.prompt = [1, 2, 3]
        self.max_new_tokens = 4
        self.state = "finished"     # terminal classification (resilience)
        self.admitted_t = None
        self.preempted_t = None
        self.first_token_t = None
        self.finish_t = None
        self.generated = []
        self.preemptions = 0
        self.error = None
        self.trace = None


def _finish_one(obs, rid, ttft_s, tpot_s, n=4):
    """Drive one fresh request through the full lifecycle with a
    controlled TTFT/TPOT (timestamps are synthetic; obs judges SLOs off
    the request's own clock fields)."""
    req = _FakeReq(rid, time.time())
    obs.request_submitted(req)
    req.admitted_t = req.arrival_t + 0.001
    obs.request_admitted(req)
    req.first_token_t = req.arrival_t + ttft_s
    obs.prefill_done(req, 0.0, False)
    req.generated = [7] * n
    req.finish_t = req.first_token_t + tpot_s * (n - 1)
    obs.request_finished(req)
    return req


def test_lifecycle_event_stream(telem):
    """One serving.request event per transition, states in order, and the
    terminal event carries the full phase breakdown."""
    obs = ServingObs("ev")
    _finish_one(obs, "happy", ttft_s=0.01, tpot_s=0.002)
    evs = [e for e in telemetry.events("serving.request")
           if e["request_id"] == "happy"]
    assert [e["state"] for e in evs] == \
        ["submitted", "admitted", "decoding", "finished"]
    assert evs[0]["prompt_tokens"] == 3
    assert "queue_wait_s" in evs[1] and "ttft_s" in evs[2]
    term = evs[-1]
    assert set(term["phases"]) == set(PHASES)
    assert term["tokens"] == 4 and "e2e_s" in term
    assert term["slo_ttft_ok"] is True and term["slo_tpot_ok"] is True


def test_preemption_lifecycle_keeps_replay_clock(telem):
    """preempted -> readmitted -> replayed: readmission does NOT restart
    prefill attribution — everything until the replay prefill lands is
    replay overhead; the terminal breakdown shows it."""
    obs = ServingObs("ev2")
    req = _FakeReq("victim", time.time())
    obs.request_submitted(req)
    req.admitted_t = time.time()
    obs.request_admitted(req)
    req.first_token_t = time.time()
    obs.prefill_done(req, 0.0, False)
    req.preempted_t = time.time()
    req.preemptions = 1
    obs.request_preempted(req)
    time.sleep(0.02)                       # the replay costs real wall
    obs.request_admitted(req)              # readmission: replay continues
    assert req.trace.cur == "replay"
    obs.prefill_done(req, 0.0, True)       # replay prefill landed
    req.generated = [1, 2, 3]
    req.finish_t = time.time()
    obs.request_finished(req)
    states = [e["state"] for e in telemetry.events("serving.request")
              if e["request_id"] == "victim"]
    assert states == ["submitted", "admitted", "decoding", "preempted",
                      "readmitted", "replayed", "finished"]
    term = telemetry.events("serving.request")[-1]
    assert term["phases"]["replay"] >= 0.02
    assert term["preemptions"] == 1
    # attribution still closes exactly
    assert req.trace.total() == \
        pytest.approx(req.finish_t - req.arrival_t, abs=1e-6)


def test_slo_counters_goodput_and_burn_edge(telem):
    """Always-on good/total counters, the windowed goodput gauge, and the
    serving.slo_burn EDGE: fires once on crossing below the threshold,
    re-arms only after recovering above it."""
    obs = ServingObs("slo", slo_ttft_ms=50.0, slo_tpot_ms=10.0)
    for i in range(4):
        _finish_one(obs, "g%d" % i, ttft_s=0.01, tpot_s=0.005)
    snap = obs.slo_snapshot()
    assert snap["good"] == {"ttft": 4, "tpot": 4}
    assert snap["goodput"] == 1.0 and not snap["burning"]
    assert not telemetry.events("serving.slo_burn")

    for i in range(8):                      # drive attainment under 0.9
        _finish_one(obs, "b%d" % i, ttft_s=0.2, tpot_s=0.005)
    snap = obs.slo_snapshot()
    assert snap["burning"]
    assert snap["total"]["ttft"] == 12 and snap["good"]["ttft"] == 4
    assert snap["attainment"]["ttft"] == pytest.approx(4 / 12)
    burns = telemetry.events("serving.slo_burn")
    assert len(burns) == 1, "burn must fire ONCE per crossing, not per miss"
    assert burns[0]["attainment"] < BURN_THRESHOLD

    for i in range(60):                     # recover: window goes all-good
        _finish_one(obs, "r%d" % i, ttft_s=0.01, tpot_s=0.005)
    assert not obs.slo_snapshot()["burning"]
    assert len(telemetry.events("serving.slo_burn")) == 1

    for i in range(8):                      # second crossing re-fires
        _finish_one(obs, "b2%d" % i, ttft_s=0.2, tpot_s=0.005)
    assert len(telemetry.events("serving.slo_burn")) == 2


# ---------------------------------------------------------------------------
# engine integration: attribution closes on the real lifecycle
# ---------------------------------------------------------------------------


def test_engine_attribution_closes_and_request_ids(telem):
    """Every finished request's trace is closed with phases summing to its
    end-to-end wall; a caller-supplied request_id sticks, an omitted one
    is auto-assigned from the rid."""
    eng = ServingEngine(_config(), seed=SEED)
    r1 = eng.submit([1, 2, 3], 5, request_id="wire-abc")
    r2 = eng.submit([4, 5], 4)
    while not (r1.finished() and r2.finished()):
        eng.step()
    assert r1.request_id == "wire-abc"
    assert r2.request_id == "r%d" % r2.rid
    for req in (r1, r2):
        tr = req.trace
        assert tr is not None and tr.closed
        assert all(v >= 0.0 for v in tr.phases.values())
        assert tr.total() == \
            pytest.approx(req.finish_t - req.arrival_t, abs=1e-6)
    # fresh engine: SOMEBODY sat behind the cold-bucket compiles
    stall = sum(r.trace.phases["compile_stall"] for r in (r1, r2))
    assert stall > 0.0, "cold buckets compiled but no stall was attributed"
    # step timeline sampled the non-empty steps
    steps = telemetry.events("serving.step_timeline")
    assert steps
    for k in ("step", "occupancy", "admitted", "preempted", "finished",
              "queue", "running", "kv_used", "kv_free", "kv_frag_slots"):
        assert k in steps[0], k
    assert max(s["occupancy"] for s in steps) >= 2


def test_engine_preemption_attributes_replay(telem):
    """A pool too small for the offered load forces eviction; the victim's
    trace shows replay > 0 and its attribution still closes exactly."""
    cfg = _config(num_blocks=13, max_batch=4)   # 12 usable blocks
    eng = ServingEngine(cfg, seed=SEED)
    rng = np.random.RandomState(13)
    reqs = [eng.submit([int(x) for x in rng.randint(0, cfg.vocab_size, 8)],
                       20) for _ in range(4)]
    while not all(r.finished() for r in reqs):
        eng.step()
    victims = [r for r in reqs if r.preemptions > 0]
    assert victims, "workload sized to force eviction saw none"
    for r in victims:
        assert r.trace.phases["replay"] > 0.0
    for r in reqs:
        assert r.trace.total() == \
            pytest.approx(r.finish_t - r.arrival_t, abs=1e-6)
    assert any(e["state"] == "preempted"
               for e in telemetry.events("serving.request"))


def test_two_engines_do_not_cross_contaminate(telem):
    """Two engines in one process: stats() reads only the engine=<id>
    labeled instruments, so neither inherits the other's latency/TTFT/
    phase/SLO numbers — while the bare-name histograms still aggregate
    process-wide for dashboards (the pre-label back-compat surface)."""
    a = ServingEngine(_config(), seed=SEED)
    b = ServingEngine(_config(), seed=SEED)
    a.generate([[1, 2, 3], [4, 5, 6], [7, 8]], [4, 4, 4])
    b.generate([[1, 2], [3, 4]], [3, 3])
    sa, sb = a.stats(), b.stats()
    assert sa["engine"] != sb["engine"]
    assert sa["completed"] == 3 and sb["completed"] == 2
    for ph in PHASES:
        assert sa["phases"][ph]["count"] == 3, ph
        assert sb["phases"][ph]["count"] == 2, ph
    assert sa["slo"]["total"] == {"ttft": 3, "tpot": 3}
    assert sb["slo"]["total"] == {"ttft": 2, "tpot": 2}
    eid_a, eid_b = str(a.engine_id), str(b.engine_id)
    assert telemetry.histogram("serving.request_latency_seconds",
                               engine=eid_a).count == 3
    assert telemetry.histogram("serving.request_latency_seconds",
                               engine=eid_b).count == 2
    # the unlabeled aggregates merge both engines (dashboards)
    assert telemetry.histogram("serving.ttft_seconds").count == 5
    assert telemetry.histogram("serving.request_latency_seconds").count == 5


def test_disabled_telemetry_still_traces_and_judges():
    """With telemetry off (enable_telemetry=False opts out of the
    engine's default auto-enable) the event stream is silent but the
    phase clock and the rare-path SLO counters still run — stats()/bench
    read them without ever enabling telemetry."""
    telemetry.disable()
    telemetry.reset()
    try:
        eng = ServingEngine(_config(), seed=SEED, enable_telemetry=False)
        req = eng.submit([1, 2, 3], 4)
        while not req.finished():
            eng.step()
        assert req.trace.closed
        assert req.trace.total() == \
            pytest.approx(req.finish_t - req.arrival_t, abs=1e-6)
        assert telemetry.events("serving.request") == []
        assert telemetry.events("serving.step_timeline") == []
        assert eng.stats()["slo"]["total"]["ttft"] == 1
    finally:
        telemetry.reset()


# ---------------------------------------------------------------------------
# the loop's record of every step (LoopRecord, the ring, loop_records)
# ---------------------------------------------------------------------------

SECONDS = [f for f in LoopRecord._fields if f.endswith("_s")]
SECTIONS = [f for f in SECONDS
            if not f.startswith(("gap_", "retire_counters", "retire_tokens",
                                 "retire_finish", "deferred"))]


class TickClock:
    """``time`` for the two modules that time the loop, with a
    ``perf_counter`` that advances one tick a read: every duration is a
    count of the clock reads between its ends, and ``peek`` reads without
    advancing. ``forbidden``: any read of it fails the test."""

    def __init__(self, forbidden=False):
        self.now, self.forbidden = 0, forbidden

    def perf_counter(self):
        assert not self.forbidden, "perf_counter read with telemetry off"
        self.now += 1
        return float(self.now)

    def __getattr__(self, name):
        return getattr(time, name)


class FetchLog:
    """``numpy`` for the engine, logging the tick at which each blocking
    fetch of a device array returned."""

    def __init__(self, clock):
        self.clock, self.returned = clock, []

    def asarray(self, a, *args, **kw):
        out = np.asarray(a, *args, **kw)
        if hasattr(a, "copy_to_host_async"):
            self.returned.append(self.clock.now)
        return out

    def __getattr__(self, name):
        return getattr(np, name)


@pytest.fixture
def ticks(telem, monkeypatch):
    """A tick clock under `telemetry.span` and the engine, a fetch log
    under the engine, and a log of the tick at which each program call
    returned (``wrap(engine)``)."""
    clock = TickClock()
    fetches = FetchLog(clock)
    monkeypatch.setattr(telemetry, "time", clock)
    monkeypatch.setattr(engine_mod, "time", clock)
    monkeypatch.setattr(engine_mod, "np", fetches)
    calls = []

    def wrap(eng):
        for name in ("_dispatch_prefill", "_dispatch_decode"):
            def logged(*a, _f=getattr(eng, name), _n=name, **kw):
                out = _f(*a, **kw)
                calls.append((_n[len("_dispatch_"):], clock.now))
                return out
            monkeypatch.setattr(eng, name, logged)
        return eng

    return clock, fetches.returned, calls, wrap


def _step_logged(eng, clock, fetches, calls):
    """One step: its record, its wall in ticks, the ticks its program
    calls and its fetches returned at."""
    n_f, n_c, t0 = len(fetches), len(calls), clock.now
    eng.step()
    return (eng.obs._ring[-1], clock.now - t0, calls[n_c:], fetches[n_f:])


def test_one_record_a_non_empty_step_with_every_field(ticks):
    clock, fetches, calls, wrap = ticks
    eng = wrap(ServingEngine(_config(), seed=SEED))
    ring = eng.obs._ring
    assert eng.step() == [] and not ring        # an empty step: no record
    reqs = [eng.submit([1, 2, 3, 4 + i], 12) for i in range(2)]
    before = time.time()
    rec, wall, progs, fetched = _step_logged(eng, clock, fetches, calls)
    assert len(ring) == 1 and ring[0] is rec
    assert rec._fields == LoopRecord._fields and len(rec) == 33
    # the ring's tuple is made from the dict's values as they stand
    assert tuple(open_record()) == LoopRecord._fields
    assert before <= rec.ts <= time.time() and rec.step == eng._steps == 1
    # two prompts as one group, then a chunk of their lanes
    assert [p for p, _t in progs] == ["prefill", "prefill", "decode"]
    assert (rec.prefills, rec.lanes, rec.finished) == (2, 2, 0)
    assert 1 <= rec.chunk_steps <= engine_mod.DECODE_CHUNK
    assert rec.lane_steps == 2 * rec.chunk_steps
    assert rec.live_blocks == eng.stats()["paged"]["live_blocks"] > 0
    assert (rec.window_live_blocks, rec.full_live_blocks) == (0, 0)
    assert (rec.full_ctx_tokens, rec.window_ctx_tokens) == (0, 0)
    assert rec.prefill_tokens == sum(len(r.prompt) for r in reqs) > 0
    # each prompt's rung of the ladder: four tokens in a block of eight
    assert rec.prefill_rows == len(reqs) * eng.config.block_size
    for f in SECTIONS:      # every section ran and was timed: whole ticks
        assert getattr(rec, f) >= 1.0 and getattr(rec, f) % 1 == 0, f
    # what is left of the counters in the gap: two stretches, a clock
    # read at each end, whatever the chunk's length
    assert rec.retire_counters_s == 2
    assert rec.retire_tokens_s >= 1 and rec.retire_finish_s >= 1
    assert rec.retire_counters_s + rec.retire_tokens_s \
        + rec.retire_finish_s <= rec.retire_s
    # the public step() carried its own item out before it returned: under
    # no dispatch, and on no step's time
    assert (rec.deferred_s, rec.deferred_hidden) == (0.0, 0)
    # an engine's first step: no fetch came before it
    assert rec.gap_chunk_s is None
    # the group's gap: from its LAST fetch's return to the chunk's
    # dispatch's return, to the tick
    assert rec.gap_group_s == progs[2][1] - fetched[1]
    assert sum(getattr(rec, f) for f in SECTIONS) <= wall
    while eng.has_work():
        eng.step()
    assert all(len(r.generated) == 12 for r in reqs)
    assert len(ring) == eng._steps
    assert [r.step for r in ring] == list(range(1, eng._steps + 1))
    assert ring[-1].finished == 2


def test_gap_after_a_chunk_ends_at_the_first_dispatch_of_any_program(ticks):
    """``gap_chunk_s`` is the ticks from the return of the step before's
    last fetch to the return of this step's first program call: the decode
    program's in a step that admits nothing, the first prefill's in one
    that does (whose chunk then closes the group's gap)."""
    clock, fetches, calls, wrap = ticks
    eng = wrap(ServingEngine(_config(), seed=SEED))
    eng.submit([1, 2, 3], 40)
    _rec, _wall, _progs, last = _step_logged(eng, clock, fetches, calls)
    # nothing admitted: the chunk's dispatch is the step's first
    rec, wall, progs, fetched = _step_logged(eng, clock, fetches, calls)
    assert [p for p, _t in progs] == ["decode"] and rec.prefills == 0
    assert rec.gap_chunk_s == progs[0][1] - last[-1] > 0
    assert rec.gap_group_s is None
    assert sum(getattr(rec, f) for f in SECTIONS) <= wall
    last = fetched
    # two prompts admitted: the FIRST prefill's dispatch ends the gap, the
    # second queues behind it and closes nothing
    eng.submit([4, 5, 6, 7], 6)
    eng.submit([8, 9], 6)
    rec, wall, progs, fetched = _step_logged(eng, clock, fetches, calls)
    assert [p for p, _t in progs] == ["prefill", "prefill", "decode"]
    assert rec.gap_chunk_s == progs[0][1] - last[-1]
    assert rec.gap_group_s == progs[2][1] - fetched[1]
    assert len(fetched) == 3 and rec.lanes == 3
    # sections and the group's gap lie inside the step; the chunk's gap
    # began in the step before
    assert sum(getattr(rec, f) for f in SECTIONS) <= wall
    assert rec.gap_group_s < wall
    # a step that only retires: its prompt's one token came from the
    # prefill, nothing decodes, and the NEXT step's gap starts at that
    # prefill's fetch
    while eng.has_work():
        eng.step()
    eng.submit([1, 2], 1)
    rec, _wall, progs, last = _step_logged(eng, clock, fetches, calls)
    assert [p for p, _t in progs] == ["prefill"]
    assert (rec.chunk_steps, rec.lanes, rec.finished) == (0, 0, 1)
    assert rec.gap_group_s is None and rec.decode_fetch_s == 0.0
    eng.submit([3, 4], 3)
    rec, _wall, progs, _f = _step_logged(eng, clock, fetches, calls)
    assert rec.gap_chunk_s == progs[0][1] - last[-1]


def test_the_gaps_are_on_the_dispatch_spans(ticks, monkeypatch):
    """The dispatch span that closes a gap carries it: `gap_us`,
    `after`."""
    clock, fetches, calls, wrap = ticks
    seen = []
    real = telemetry._Span.set

    def set_(self, **args):
        if "after" in args:
            seen.append((self.name, args))
        real(self, **args)

    monkeypatch.setattr(telemetry._Span, "set", set_)
    eng = wrap(ServingEngine(_config(), seed=SEED))
    eng.submit([1, 2, 3], 30)
    eng.step()
    assert [(n, a["after"]) for n, a in seen] == [
        ("serving.decode.dispatch", "group")]
    eng.submit([1, 2, 3, 4], 3)
    eng.step()
    rec = eng.obs._ring[-1]
    assert [(n, a["after"], a["gap_us"]) for n, a in seen[1:]] == [
        ("serving.prefill.dispatch", "chunk", int(rec.gap_chunk_s * 1e6)),
        ("serving.decode.dispatch", "group", int(rec.gap_group_s * 1e6))]


def test_waits_on_an_empty_queue_are_not_in_the_gap(telem):
    """`run_loop` parks under `serving.loop.idle` between two requests:
    the next step's `gap_chunk_s` leaves that wait out."""
    eng = ServingEngine(_config(), seed=SEED)
    eng.generate([[1, 2, 3]], [4])              # compiled, and a fetch made
    stop = threading.Event()
    driver = threading.Thread(target=eng.run_loop, args=(stop, 0.1))
    driver.start()
    try:
        first = eng.submit([1, 2, 3], 4)
        assert first.done_event.wait(60)
        idle0 = telemetry.totals("serving.loop.idle")[1]
        time.sleep(0.5)                         # several idle waits
        second = eng.submit([4, 5, 6], 4)
        assert second.done_event.wait(60)
    finally:
        stop.set()
        driver.join(60)
    recs = list(eng.obs._ring)
    k = next(i for i, r in enumerate(recs) if r.ts >= second.arrival_t)
    rec, prev = recs[k], recs[k - 1]
    idled = telemetry.totals("serving.loop.idle")[1] - idle0
    assert rec.ts - prev.ts > 0.5 <= idled + 0.1
    assert 0 < rec.gap_chunk_s < rec.ts - prev.ts - 0.4


def test_the_record_is_filled_at_every_chunk(chunk, telem):
    eng = ServingEngine(_config(), seed=SEED)
    reqs = [eng.submit(list(range(1, 4 + i)), 7 + i) for i in range(3)]
    while eng.has_work():
        eng.step()
    recs, st = list(eng.obs._ring), eng.stats()
    with_chunk = [r for r in recs if r.chunk_steps]
    assert all(1 <= r.chunk_steps <= chunk for r in with_chunk)
    assert max(r.chunk_steps for r in recs) == chunk
    assert len(with_chunk) == st["decode"]["dispatches"]
    assert sum(r.chunk_steps for r in recs) == st["decode"]["inner_steps"]
    assert sum(r.prefills for r in recs) == st["prefill"]["prompts"] == 3
    assert sum(r.prefills > 0 for r in recs) == st["prefill"]["groups"]
    assert sum(r.live_blocks for r in recs) == st["paged"]["live_blocks"]
    # a token a live lane and step, and each prompt's first from its prefill
    assert sum(r.lane_steps for r in recs) + 3 == st["tokens_total"] \
        == sum(len(r.generated) for r in reqs)
    assert sum(r.finished for r in recs) == 3
    loop = st["loop"]
    assert (loop["steps"], loop["chunks"], loop["groups"]) == (
        len(recs), len(with_chunk), st["prefill"]["groups"])
    assert loop["steps_per_dispatch"] == st["decode"]["steps_per_dispatch"]
    assert loop["prompts_per_group"] == st["prefill"]["prompts_per_group"]
    for f in SECONDS:
        assert loop["sums"][f] == pytest.approx(
            sum(getattr(r, f) or 0.0 for r in recs), abs=1e-5), f
    assert set(loop["mean_ms"]) == {f[:-2] for f in SECONDS
                                    if not f.startswith("gap_")}
    assert loop["mean_ms"]["retire"] == pytest.approx(
        1e3 * loop["sums"]["retire_s"] / len(recs), abs=1e-3)
    assert loop["gap_after_chunk_ms"] == pytest.approx(
        1e3 * loop["sums"]["gap_chunk_s"] / (len(recs) - 1), abs=1e-3)
    assert loop["gap_after_group_ms"] > 0
    json.dumps(loop)


def test_a_window_of_records_is_the_window_of_two_stats_reads(telem):
    """What the benchmark does: `stats()` and the wall clock, twice, while
    the driver steps and callers submit; the records whose `ts` lies
    between the two instants hold exactly what the two `stats()` differ by
    (a step opens its record once it holds the lock `stats()` reads under).
    Here the clock is read with that lock still held, so that no scheduler
    can put a step's `ts` between a `stats()` and its instant; the
    benchmark reads it a few microseconds after, which a waiting step
    cannot beat unless the machine takes the thread away there."""
    eng = ServingEngine(_config(max_batch=4), seed=SEED)
    eng.warmup()                    # no step waits for a compile
    stop = threading.Event()
    driver = threading.Thread(target=eng.run_loop, args=(stop, 0.05))
    driver.start()

    closed = threading.Event()

    def caller(k):
        for i in itertools.count():
            req = eng.submit([1 + k, 2 + i % 5, 3], 4 + (i + k) % 9)
            assert req.done_event.wait(60)
            if closed.is_set():
                break

    callers = [threading.Thread(target=caller, args=(k,)) for k in range(6)]
    for c in callers:
        c.start()

    def snapshot():
        with eng._lock:
            return time.time(), eng.stats()

    try:
        time.sleep(0.1)
        t0, before = snapshot()
        time.sleep(0.3)
        t1, after = snapshot()
    finally:
        closed.set()
        for c in callers:
            c.join(120)
        stop.set()
        driver.join(60)
    recs = loop_records(t0, t1)
    recs = [r for r in recs if r in set(eng.obs._ring)]
    assert len(recs) == after["steps"] - before["steps"] > 3
    for field, block, key in (("chunk_steps", "decode", "inner_steps"),
                              ("prefills", "prefill", "prompts"),
                              ("live_blocks", "paged", "live_blocks")):
        assert sum(getattr(r, field) for r in recs) \
            == after[block][key] - before[block][key], field
    assert sum(r.chunk_steps > 0 for r in recs) \
        == after["decode"]["dispatches"] - before["decode"]["dispatches"]
    assert sum(r.prefills > 0 for r in recs) \
        == after["prefill"]["groups"] - before["prefill"]["groups"]
    for field in ("gap_chunk_s", "retire_s", "lock_s"):
        assert sum(getattr(r, field) or 0.0 for r in recs) \
            == pytest.approx(after["loop"]["sums"][field]
                             - before["loop"]["sums"][field], abs=1e-4)
    # the gaps are part of the window's wall clock, the sections too
    assert sum((r.gap_chunk_s or 0) + (r.gap_group_s or 0)
               for r in recs) < t1 - t0 + 0.1


def test_the_speculative_path_fills_the_record(telem):
    eng = ServingEngine(_config(spec_k=2, draft="self"), seed=SEED)
    reqs = [eng.submit([1, 2, 3 + i], 9) for i in range(2)]
    while eng.has_work():
        eng.step()
    recs = list(eng.obs._ring)
    assert all(len(r.generated) == 9 for r in reqs)
    assert recs[0].prefills == 2 and recs[0].gap_chunk_s is None
    for r in recs:
        # a draft-and-verify window is one step of two lanes
        assert (r.chunk_steps, r.lanes, r.lane_steps) == (1, 2, 2)
        assert r.live_blocks > 0
        for f in ("decode_build_s", "decode_dispatch_s", "decode_fetch_s",
                  "retire_s", "retire_counters_s", "retire_tokens_s"):
            assert getattr(r, f) > 0, f
        assert (r.deferred_s, r.deferred_hidden) == (0.0, 0)
        assert r.decode_dispatch_s + r.decode_fetch_s <= sum(
            getattr(r, f) for f in SECTIONS)
    assert recs[0].gap_group_s > 0
    assert all(r.gap_chunk_s > 0 and r.gap_group_s is None
               for r in recs[1:])
    assert sum(r.live_blocks for r in recs) \
        == eng.stats()["paged"]["live_blocks"]


def test_the_ring_is_bounded_and_windowed_over_two_engines(telem,
                                                           monkeypatch):
    monkeypatch.setattr(obs_mod, "LOOP_RING", 5)
    assert LOOP_RING == 8192
    a, b = ServingObs("a"), ServingObs("b")
    assert a._ring.maxlen == 5
    timeline = dict(occupancy=1, admitted=0, preempted=0, queue=0,
                    running=1, kv_used=1, kv_free=1, kv_frag_slots=0)
    t0 = time.time()
    for i in range(8):
        for o in (a, b):
            rec = open_record()
            # b's steps fall between a's
            rec.update(ts=t0 + i + (0.5 if o is b else 0.0), step=i + 1,
                       chunk_steps=2, retire_s=0.25)
            o.step_timeline(rec, **timeline)
    assert [r.step for r in a._ring] == [4, 5, 6, 7, 8]   # the last five
    assert a.loop_snapshot()["steps"] == 8      # the sums forget nothing
    assert a.loop_snapshot()["sums"]["retire_s"] == 2.0
    # (a window, always: the rings of other tests' engines are out there)
    both = loop_records(t0, t0 + 50)
    assert [r.ts - t0 for r in both] == [3, 3.5, 4, 4.5, 5, 5.5, 6, 6.5,
                                         7, 7.5]
    assert [r.ts - t0 for r in loop_records(t0 + 4.5, t0 + 6)] == [
        4.5, 5, 5.5, 6]                         # both ends inside
    assert [r.ts - t0 for r in loop_records(until=t0 + 3.2)
            if r.ts >= t0] == [3]
    assert loop_records(t0 + 50, t0 + 100) == []
    assert len(loop_records()) >= len(loop_records(since=t0)) >= 10
    # the rings outlive their engines: the benchmark reads after shutdown
    del a, b
    assert len(loop_records(t0, t0 + 50)) == 10
    # the event carries the record's fields beside the occupancy
    ev = telemetry.events("serving.step_timeline")[-1]
    assert set(LoopRecord._fields) - {"ts"} <= set(ev)
    assert set(timeline) <= set(ev) and ev["engine"] == "b"
    assert (ev["step"], ev["chunk_steps"], ev["gap_chunk_s"]) == (8, 2, None)
    assert ev["ts"] >= t0                       # the event's own instant


def test_telemetry_off_makes_no_record_and_reads_no_clock(monkeypatch):
    telemetry.disable()
    telemetry.reset()
    clock = TickClock(forbidden=True)
    monkeypatch.setattr(telemetry, "time", clock)
    monkeypatch.setattr(engine_mod, "time", clock)
    monkeypatch.setattr(obs_mod, "open_record", None)    # never called
    try:
        eng = ServingEngine(_config(), seed=SEED, enable_telemetry=False)
        reqs = [eng.submit([1, 2, 3 + i], 6) for i in range(2)]
        while eng.has_work():
            eng.step()
        assert all(len(r.generated) == 6 for r in reqs)
        assert not eng.obs._ring and eng._fetch_end is None
        loop = eng.stats()["loop"]
        assert loop["steps"] == 0 and loop["gap_after_chunk_ms"] is None
        assert not any(loop["sums"].values())
    finally:
        telemetry.reset()


def test_assembling_a_record_costs_under_20_us(telem):
    """What a step pays for its record when the spans have closed: the
    dict, the tuple into the ring, the sums and the event. The best of
    twenty batches, as `test_span_with_everything_off_is_only_an_annotation`
    takes it: the gate's other workers take the cores away."""
    o = ServingObs("cost")
    timeline = dict(occupancy=32, admitted=2, preempted=0, queue=30,
                    running=32, kv_used=400, kv_free=100, kv_frag_slots=9)
    n, best = 500, float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        for i in range(n):
            rec = open_record()
            rec.update(step=i, prefills=2, lanes=32, chunk_steps=8,
                       finished=1, retire_s=3e-3, gap_chunk_s=6e-3)
            o.step_timeline(rec, **timeline)
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 20e-6, "a record costs %.1f us" % (best * 1e6)
    assert len(o._ring) == min(20 * n, LOOP_RING)


def test_serving_report_prints_the_loops_record(tmp_path, monkeypatch):
    """The operator's use of the record: `tools/serving_report.py` prints
    a step's section milliseconds and its two gaps beside its occupancy,
    from the `serving.step_timeline` events of the sink file."""
    import io

    sink = tmp_path / "serving.jsonl"
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(sink))
    telemetry.reset()
    telemetry.enable()
    try:
        eng = ServingEngine(_config(), seed=SEED)
        eng.generate([[1, 2, 3], [4, 5, 6, 7]], [9, 12])
        recs = list(eng.obs._ring)
    finally:
        telemetry.disable()
        telemetry.reset()
    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    import serving_report

    _events, steps = serving_report.load_events(str(sink))
    assert [s["step"] for s in steps] == [r.step for r in recs]
    for ev, rec in zip(steps, recs):        # the sink carries the record
        for f in LoopRecord._fields:
            if f != "ts":
                assert ev[f] == getattr(rec, f), f
    rows = serving_report.loop_rows(steps)
    assert [(step, pr, n) for _e, step, pr, n, _ms in rows] == [
        (r.step, r.prefills, r.chunk_steps) for r in recs]
    first = rows[0][4]
    assert first["gap_chunk"] is None       # no fetch before the first step
    assert first["gap_group"] == pytest.approx(1e3 * recs[0].gap_group_s)
    assert first["prefill"] == pytest.approx(1e3 * (
        recs[0].prefill_build_s + recs[0].prefill_dispatch_s
        + recs[0].prefill_fetch_s + recs[0].prefill_retire_s))
    assert rows[1][4]["gap_chunk"] == pytest.approx(
        1e3 * recs[1].gap_chunk_s)
    out = io.StringIO()
    serving_report.render([], steps, file=out)
    text = out.getvalue()
    assert "occupancy timeline" in text and "engine loop" in text
    table = text[text.index("engine loop"):].splitlines()
    assert table[1].split() == ["step", "pr", "n", "lock", "sched",
                                "prefill", "build", "disp", "fetch",
                                "retire", "counters", "deferred",
                                "gap_chunk", "gap_group"]
    assert len(table) == 2 + len(recs) + 1 and table[2].split()[-2] == "--"
    assert table[-1].startswith("loop totals: %d steps, %d chunks"
                                % (len(recs), len(recs)))
    assert "% of the" in table[-1] and "retire" in table[-1]
    # events of a program from before the record: the old table alone
    old = [{k: v for k, v in s.items() if k not in LoopRecord._fields
            or k in ("step", "finished")} for s in steps]
    out = io.StringIO()
    serving_report.render([], old, file=out)
    assert "occupancy timeline" in out.getvalue()
    assert "engine loop" not in out.getvalue()


# ---------------------------------------------------------------------------
# the shared segment walker (serving_report.py + trace_merge.py lanes)
# ---------------------------------------------------------------------------


def test_request_segments_walker():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import trace_merge

    evs = [{"ts": 1.0, "state": "submitted"},
           {"ts": 2.0, "state": "admitted"},
           {"ts": 3.0, "state": "decoding"},
           {"ts": 4.0, "state": "preempted"},
           {"ts": 4.5, "state": "readmitted"},   # replay continues
           {"ts": 5.0, "state": "replayed"},
           {"ts": 6.0, "state": "finished"}]
    assert trace_merge.request_segments(evs) == [
        ("queue_wait", 1.0, 2.0), ("prefill", 2.0, 3.0),
        ("decode", 3.0, 4.0), ("replay", 4.0, 5.0), ("decode", 5.0, 6.0)]
    # in-flight request: the open phase has end=None
    assert trace_merge.request_segments(evs[:-1])[-1] == ("decode", 5.0, None)


# ---------------------------------------------------------------------------
# serve.py HTTP surface: schemas + X-Request-Id round-trip
# ---------------------------------------------------------------------------


def test_http_surface_schemas_and_request_id_roundtrip(telem):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve

    eng = ServingEngine(_config(), seed=SEED)
    stop = threading.Event()
    driver = threading.Thread(target=eng.run_loop, args=(stop, 0.01),
                              daemon=True)
    driver.start()
    server = serve.make_server(eng, "127.0.0.1", 0, driver=driver)
    srv_thread = threading.Thread(target=server.serve_forever, daemon=True)
    srv_thread.start()
    base = "http://127.0.0.1:%d" % server.server_address[1]

    def get(path):
        with urllib.request.urlopen(base + path, timeout=30) as r:
            return r.status, dict(r.headers), r.read()

    def post(body, headers=None):
        req = urllib.request.Request(base + "/generate",
                                     data=json.dumps(body).encode(),
                                     headers=headers or {})
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.status, dict(r.headers), json.loads(r.read())

    try:
        code, _h, body = get("/healthz")
        assert code == 200 and json.loads(body) == {"ok": True,
                                                   "state": "serving"}

        # header-supplied identity round-trips through header AND body
        code, hdrs, rep = post({"tokens": [1, 2, 3], "max_new_tokens": 4},
                               headers={"X-Request-Id": "wire-77"})
        assert code == 200
        assert hdrs.get("X-Request-Id") == "wire-77"
        assert rep["request_id"] == "wire-77"
        assert isinstance(rep["tokens"], list) and len(rep["tokens"]) == 4
        assert rep["ttft_s"] > 0 and rep["latency_s"] >= rep["ttft_s"]

        # no identity supplied: the engine auto-assigns one and echoes it
        code, hdrs, rep = post({"tokens": [5, 6], "max_new_tokens": 3})
        assert code == 200
        assert rep["request_id"] and hdrs.get("X-Request-Id") == \
            rep["request_id"]

        # /stats schema: the observability block rides the snapshot
        code, _h, body = get("/stats")
        stats = json.loads(body)
        assert code == 200 and stats["completed"] >= 2
        assert stats["engine"] == eng.engine_id
        assert set(stats["phases"]) == set(PHASES)
        for ph in PHASES:
            assert stats["phases"][ph]["count"] >= 2
        slo = stats["slo"]
        for k in ("ttft_target_ms", "tpot_target_ms", "good", "total",
                  "attainment", "goodput", "burning"):
            assert k in slo, k
        assert "kv_blocks_frag_slots" in stats

        # /metrics: well-formed Prometheus text incl. the new instruments
        code, hdrs, body = get("/metrics")
        text = body.decode()
        assert code == 200 and hdrs["Content-Type"].startswith("text/plain")
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            _name, val = line.rsplit(" ", 1)
            float(val)   # every sample line must parse
        assert "mxnet_serving_goodput" in text
        assert "mxnet_serving_phase_seconds" in text
        assert "mxnet_serving_slo_total" in text

        code, _h, _b = get("/healthz")   # still healthy after traffic
        assert code == 200
    finally:
        server.shutdown()
        server.server_close()
        stop.set()
        with eng._work:
            eng._work.notify_all()
        driver.join(timeout=30)


# ---------------------------------------------------------------------------
# slow e2e: preemption + cold buckets -> JSONL -> report + trace close
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_e2e_waterfall_attribution_closes(tmp_path, monkeypatch):
    """Acceptance: an unwarmed engine under a pool too small for its load
    emits a telemetry stream from which serving_report.py shows the
    preempted request's replay > 0, a cold-bucket compile_stall > 0, and
    every phase breakdown summing to e2e within 5%; trace_merge
    --serving-lanes builds a VALID chrome trace with one lane per
    request."""
    sink = tmp_path / "serving.jsonl"
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(sink))
    telemetry.reset()
    telemetry.enable()
    try:
        cfg = _config(num_blocks=7, max_batch=4)   # 6 usable blocks
        eng = ServingEngine(cfg, seed=SEED)        # no warmup: cold buckets
        long_a = eng.submit([1, 2, 3, 4, 5, 6, 7, 8] * 2, 20,
                            request_id="long-a")
        short_b = eng.submit([9, 10, 11], 20, request_id="short-b")
        while not (long_a.finished() and short_b.finished()):
            eng.step()
        assert long_a.preemptions + short_b.preemptions > 0, \
            "workload sized to force eviction saw none"
    finally:
        telemetry.disable()
        telemetry.reset()

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serving_report
    import trace_merge

    rep = serving_report.report(str(sink))
    by_id = {r["request_id"]: r for r in rep["requests"]}
    assert set(by_id) == {"long-a", "short-b"}
    for r in by_id.values():
        assert r["state"] == "finished"
        assert r["e2e_s"] > 0
        # attribution closes: phases sum to e2e within 5% (the engine's
        # clock is exact; the JSONL carries 6-decimal rounding)
        assert abs(r["phase_sum_s"] - r["e2e_s"]) <= \
            max(1e-3, 0.05 * r["e2e_s"]), r
    preempted = [r for r in by_id.values() if r["preemptions"] > 0]
    assert preempted and all(r["phases"]["replay"] > 0 for r in preempted), \
        "preempted request must show replay overhead"
    assert any(r["phases"]["compile_stall"] > 0 for r in by_id.values()), \
        "cold-bucket compiles must surface as compile_stall"
    assert rep["steps"], "step timeline must be populated"
    assert max(s["occupancy"] for s in rep["steps"]) >= 1
    # each step's event carries the loop's record: sections and gaps
    assert all(s["retire_s"] > 0 for s in rep["steps"])
    assert any(s["gap_chunk_s"] for s in rep["steps"])
    assert len(serving_report.loop_rows(rep["steps"])) == len(rep["steps"])
    assert rep["slo"]["judged"] >= 2

    # the CLI renders the same stream (human waterfall + --json)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serving_report.py"),
         "--json", str(sink)],
        capture_output=True, text=True, check=True)
    cli = json.loads(out.stdout)
    assert {r["request_id"] for r in cli["requests"]} == {"long-a", "short-b"}
    human = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serving_report.py"),
         str(sink)], capture_output=True, text=True, check=True)
    assert "engine loop (per step" in human.stdout
    assert "loop totals:" in human.stdout

    # chrome trace: one lane per request, schema-valid, replay span present
    trace = trace_merge.merge([trace_merge.load_input(str(sink))],
                              serving_lanes=True)
    assert trace_merge.validate_trace(trace) == []
    lanes = trace_merge.serving_request_lanes(trace)
    assert sorted(lanes.values()) == ["req long-a", "req short-b"]
    names = {ev.get("name") for ev in trace["traceEvents"]
             if ev.get("pid") in lanes and ev.get("ph") == "X"}
    assert {"queue_wait", "prefill", "decode", "replay"} <= names
    assert any(ev.get("name") == "preempted" and ev.get("ph") == "i"
               for ev in trace["traceEvents"] if ev.get("pid") in lanes)
