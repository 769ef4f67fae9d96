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
from mxnet_tpu.serving.obs import (  # noqa: E402
    BURN_THRESHOLD, PHASES, RequestTrace, ServingObs)

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
    assert rep["slo"]["judged"] >= 2

    # the CLI renders the same stream (human waterfall + --json)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serving_report.py"),
         "--json", str(sink)],
        capture_output=True, text=True, check=True)
    cli = json.loads(out.stdout)
    assert {r["request_id"] for r in cli["requests"]} == {"long-a", "short-b"}
    subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "serving_report.py"),
         str(sink)], capture_output=True, text=True, check=True)

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
