"""Serving resilience suite (docs/serving.md §resilience): request
deadlines and cancellation (swept requests free their KV blocks — the
pool invariant is the assertion), bounded-admission overload shedding
with the Retry-After pricing, the supervised engine-recovery loop
(salvage -> backoff -> rebuild -> replay, bit-identical to a fault-free
oracle; permanent failure past the restart budget), graceful drain, the
bounded serve.py handler wait, the ``pop_finished`` backlog bound, and
the serving fault points (``dispatch_error`` / ``kv_oom`` /
``slow_step``) — capped by the slow chaos e2e: tools/serve.py under an
injected mid-traffic dispatch fault restarts warm from the persistent
compile cache, finishes every admitted request bit-identical to the
oracle, sheds the overflow with clean 503s, and drains to exit 0 on
SIGTERM.

Host-side only: part of tier-1 (tests/conftest.py pins jax to the CPU);
`ci/run_tests.sh serving` runs the serving files alone, slow cases
included.
"""
import collections
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from mxnet_tpu import fault, telemetry  # noqa: E402
from mxnet_tpu.serving import (  # noqa: E402
    CANCELLED, FAILED, FINISHED, TIMED_OUT, EngineSupervisor, KVBlockPool,
    KVCacheOOM, Request, Scheduler, ServingConfig, ServingEngine,
    ServingOverloadError, retry_after_s)
from mxnet_tpu.serving.kv_cache import PageSpec  # noqa: E402

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


def _drain(eng):
    """Step the engine until idle (finishes whatever is enqueued)."""
    while eng.has_work():
        eng.step()


def _pool_consistent(pool):
    """Every usable block is exactly one of free / referenced."""
    with pool._lock:
        free, ref = set(pool._free), set(pool._ref)
        return (not (free & ref)
                and len(free) + len(ref) == pool.num_usable)


@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.reset()
    yield
    fault.reset()


# ---------------------------------------------------------------------------
# deadlines + cancellation: terminal states free KV blocks promptly
# ---------------------------------------------------------------------------


def test_request_deadline_validation():
    with pytest.raises(ValueError, match="timeout_s"):
        Request([1, 2], 4, timeout_s=-1.0)
    assert Request([1, 2], 4, timeout_s=None).deadline_t is None
    req = Request([1, 2], 4, timeout_s=2.5)
    assert req.deadline_t == pytest.approx(req.arrival_t + 2.5)
    assert not req.expired(now=req.arrival_t + 2.4)
    assert req.expired(now=req.arrival_t + 2.6)


def test_expired_request_times_out_and_frees_blocks():
    eng = ServingEngine(_config(), seed=SEED)
    live = eng.submit([1, 2, 3], 8)
    doomed = eng.submit([4, 5, 6, 7], 12, timeout_s=0.05)
    eng.step()                      # both admitted, holding blocks
    assert eng.pool.used() > 0
    time.sleep(0.06)
    eng.step()                      # sweep runs before scheduling
    assert doomed.state == TIMED_OUT
    assert doomed.finished() and doomed.done_event.is_set()
    assert "deadline" in doomed.error or "timed out" in doomed.error
    assert doomed.blocks == [] and doomed.shared_blocks == 0
    _drain(eng)
    assert live.state == FINISHED
    assert eng.pool.used() == 0 and _pool_consistent(eng.pool)
    res = eng.stats()["resilience"]
    assert res["timed_out"] == 1 and res["cancelled"] == 0


def test_deadlines_hold_when_one_steps_group_lasts_long():
    """No ``prefills_per_step``: eight prompts are ONE group and the step
    lasts as long as its eight prefills. A deadline that passes inside the
    step is swept at the top of the NEXT one (there is no sweep inside a
    step), admitted or waiting alike: the admitted stream's blocks and lane
    come back before that step's admission pass, which hands the lane to
    the live request behind it, and an expired request that waited is
    never prefilled."""
    eng = ServingEngine(_config(prefills_per_step=None), seed=SEED)
    dispatch, prefilled = eng._dispatch_prefill, []

    def slow(toks, spans, *rest):
        prefilled.extend(n for _row, n in spans)
        time.sleep(0.03)
        return dispatch(toks, spans, *rest)

    eng._dispatch_prefill = slow
    live = [eng.submit([1, 2, 3], 12) for _ in range(7)]
    doomed = eng.submit([4, 5, 6, 7], 12, timeout_s=0.1)
    stale = eng.submit([8] * 5, 12, timeout_s=0.1)      # no lane: it waits
    heir = eng.submit([9] * 6, 2)
    t0 = time.time()
    eng.step()
    assert time.time() - t0 >= 8 * 0.03
    assert prefilled == [3] * 7 + [4], "one group of the eight lanes"
    assert doomed.state == "decoding" and doomed.expired()
    assert stale.state == "waiting" and stale.expired()
    eng.step()
    assert doomed.state == TIMED_OUT and stale.state == TIMED_OUT
    assert doomed.blocks == [] and "deadline" in stale.error
    assert prefilled == [3] * 7 + [4, 6], \
        "the freed lane is the live heir's; the stale request never ran"
    assert eng.stats()["prefill"]["stopped_by"] == {
        "lanes": 1, "pool": 0, "slots": 0, "cap": 0, "preempted": 0,
        "queue": 1}
    _drain(eng)
    assert [r.state for r in live + [heir]] == [FINISHED] * 8
    assert eng.pool.used() == 0 and _pool_consistent(eng.pool)
    assert eng.stats()["resilience"]["timed_out"] == 2


def test_default_timeout_comes_from_config():
    eng = ServingEngine(_config(default_timeout_ms=50), seed=SEED)
    req = eng.submit([1, 2], 30)
    assert req.deadline_t is not None
    time.sleep(0.06)
    eng.step()
    assert req.state == TIMED_OUT
    # an explicit timeout_s overrides the config default
    req2 = eng.submit([1, 2], 2, timeout_s=30.0)
    assert req2.deadline_t - req2.arrival_t > 1.0
    _drain(eng)
    assert req2.state == FINISHED


def test_cancel_running_and_waiting_requests(telem):
    eng = ServingEngine(_config(max_batch=1), seed=SEED)
    running = eng.submit([1, 2, 3], 20)
    waiting = eng.submit([4, 5], 20)
    eng.step()
    assert running.state != FINISHED and running.blocks
    eng.cancel(running)
    eng.cancel(waiting)             # never admitted: dropped from waiting
    eng.step()
    assert running.state == CANCELLED and waiting.state == CANCELLED
    assert "cancelled" in running.error
    assert running.done_event.is_set() and waiting.done_event.is_set()
    assert eng.pool.used() == 0 and _pool_consistent(eng.pool)
    assert telemetry.counter("serving.cancelled").value == 2
    assert not eng.has_work()
    # terminal requests surface through pop_finished like successes
    states = {r.rid: r.state for r in eng.pop_finished()}
    assert states == {running.rid: CANCELLED, waiting.rid: CANCELLED}
    # cancel after terminal is a no-op
    eng.cancel(running)
    assert running.state == CANCELLED


def test_scheduler_sweep_is_a_unit(telem):
    pool = KVBlockPool(PageSpec.lane_dense(1, 1, 4), num_blocks=8,
                       block_size=8)
    sched = Scheduler(pool, max_batch=4)
    fresh = Request([1], 4, timeout_s=60.0)
    stale = Request([2], 4, timeout_s=60.0)
    stale.deadline_t = stale.arrival_t - 1.0    # already expired
    axed = Request([3], 4)
    axed.cancelled = True
    for r in (fresh, stale, axed):
        r.done_event = threading.Event()
        sched.add(r)
    swept = sched.sweep()
    assert {r.rid for r in swept} == {stale.rid, axed.rid}
    assert stale.state == TIMED_OUT and axed.state == CANCELLED
    assert list(sched.waiting) == [fresh]
    assert sched.pop_failed() == swept


# ---------------------------------------------------------------------------
# overload: bounded admission queue, classified shed, Retry-After pricing
# ---------------------------------------------------------------------------


def test_bounded_queue_sheds_with_classified_error(telem):
    eng = ServingEngine(_config(max_queue=2), seed=SEED)
    eng.submit([1, 2], 4)
    eng.submit([3, 4], 4)
    with pytest.raises(ServingOverloadError) as ei:
        eng.submit([5, 6], 4)
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after_s >= 1.0
    assert "max_queue 2" in str(ei.value)
    assert telemetry.counter("serving.shed").value == 1
    assert eng.stats()["resilience"]["shed"] == 1
    _drain(eng)                     # queue drains -> admission reopens
    assert eng.submit([5, 6], 4) is not None


def test_unbounded_queue_by_default():
    eng = ServingEngine(_config(), seed=SEED)
    assert eng.config.max_queue == 0
    for i in range(40):             # far beyond max_batch: all enqueue
        eng.submit([1 + i % 5], 1)
    assert len(eng.scheduler.waiting) == 40


def test_retry_after_pricing_uses_backlog_and_goodput(telem):
    eng = ServingEngine(_config(max_batch=4), seed=SEED)
    assert retry_after_s(eng) == 1.0            # cold: no history, floor
    assert retry_after_s(object()) == 1.0       # not an engine: degrade
    eid = str(eng.engine_id)
    h = telemetry.histogram("serving.request_latency_seconds", engine=eid)
    for _ in range(10):
        h.observe(2.0)
    for _ in range(6):                          # 6 waiting / 4 slots
        eng.submit([1, 2], 2)                   # -> 2 waves * ~2s p50
    priced = retry_after_s(eng)
    assert 2.0 < priced <= 8.0                  # > one wave, bounded
    telemetry.gauge("serving.goodput", engine=eid).set(0.5)
    stretched = retry_after_s(eng)              # missing SLOs: back off
    assert stretched == pytest.approx(priced * 2.0, rel=0.01)
    assert retry_after_s(eng, max_s=3.0) == 3.0  # clamped


# ---------------------------------------------------------------------------
# generate(): deadline-aware, abort-aware (no busy-poll past failure)
# ---------------------------------------------------------------------------


def test_generate_raises_on_timed_out_requests():
    eng = ServingEngine(_config(), seed=SEED)
    with pytest.raises(RuntimeError, match="timed_out"):
        eng.generate([[1, 2, 3]], 30, timeout_s=1e-4)
    assert eng.pool.used() == 0 and _pool_consistent(eng.pool)
    assert eng.aborted is None      # a deadline is not an engine failure


def test_generate_surfaces_abort_cause_instead_of_spinning():
    eng = ServingEngine(_config(), seed=SEED)
    with fault.inject("dispatch_error:raise=1,times=1"):
        with pytest.raises(fault.InjectedFault):
            eng.generate([[1, 2, 3]], 4)    # self-driven: step re-raises
    assert eng.aborted is not None and "InjectedFault" in eng.aborted
    # post-abort, generate fails FAST with the recorded cause instead of
    # busy-polling a dead engine (the classified-raise satellite)
    t0 = time.time()
    with pytest.raises(RuntimeError, match="aborted"):
        eng.generate([[4, 5]], 4)
    assert time.time() - t0 < 5.0


# ---------------------------------------------------------------------------
# pop_finished backlog stays bounded
# ---------------------------------------------------------------------------


def test_pop_finished_backlog_is_bounded():
    eng = ServingEngine(_config(max_batch=8), seed=SEED)
    cap = eng._finished.maxlen
    assert cap == max(256, 8 * eng.config.max_batch)
    fake = collections.namedtuple("F", "rid")
    with eng._lock:
        eng._finished.extend(fake(i) for i in range(cap + 50))
    assert len(eng._finished) == cap            # oldest 50 shed, no growth
    got = eng.pop_finished()
    assert [f.rid for f in got] == list(range(50, cap + 50))
    assert eng.pop_finished() == []             # drained


# ---------------------------------------------------------------------------
# serving fault points: kv_oom / dispatch_error / slow_step
# ---------------------------------------------------------------------------


def test_kv_oom_fault_counts_alloc_failures(telem):
    eng = ServingEngine(_config(), seed=SEED)
    before = telemetry.counter("serving.kv_blocks_alloc_failures").value
    with fault.inject("kv_oom:times=1"):
        with pytest.raises(KVCacheOOM, match="fault-injected"):
            eng.pool.alloc(1)
    assert telemetry.counter(
        "serving.kv_blocks_alloc_failures").value == before + 1
    assert _pool_consistent(eng.pool)           # refused != leaked
    assert eng.pool.alloc(1)                    # times=1: pool recovered


def test_kv_oom_at_admission_fails_request_not_engine(telem):
    """An admission alloc refused past the available() check (injected
    ``kv_oom``, or a racing allocator) fails THAT request through the
    classified exit door — no dispatch happened, the pool is intact, so
    the engine keeps serving its neighbours."""
    eng = ServingEngine(_config(), seed=SEED)
    req = eng.submit([1, 2, 3], 4)
    with fault.inject("kv_oom:times=1"):
        eng.step()
    assert req.state == FAILED and req.done_event.is_set()
    assert "kv_oom" in req.error
    assert eng.aborted is None, "admission refusal must not abort"
    assert telemetry.counter("serving.kv_blocks_alloc_failures").value == 1
    assert eng.pool.used() == 0 and _pool_consistent(eng.pool)
    ok = eng.submit([4, 5], 2)      # the engine is still open for work
    _drain(eng)
    assert ok.state == FINISHED


def test_slow_step_inflates_step_wall():
    eng = ServingEngine(_config(), seed=SEED)
    with fault.inject("slow_step:delay_ms=60"):
        t0 = time.time()
        eng.step()                  # no work: the wall IS the injection
        assert time.time() - t0 >= 0.06
    r = eng.submit([1, 2], 1)       # the fault leaves the engine healthy
    _drain(eng)
    assert r.state == FINISHED


# ---------------------------------------------------------------------------
# EngineSupervisor: salvage -> warm rebuild -> replay, bit-identical
# ---------------------------------------------------------------------------


def _supervised(**kw):
    cfg = _config()
    return EngineSupervisor(lambda: ServingEngine(cfg, seed=SEED), **kw)


def _run_supervised(sup, reqs, timeout=300.0):
    stop = threading.Event()
    t = threading.Thread(target=sup.run_loop, args=(stop, 0.01),
                         name="test-sup-driver", daemon=True)
    t.start()
    try:
        for r in reqs:
            assert r.done_event.wait(timeout), (r.rid, r.state)
    finally:
        stop.set()
        eng = sup.engine
        with eng._work:
            eng._work.notify_all()
        t.join(timeout=60)
    return t


def test_supervisor_restart_replays_bit_identical(telem, chunk):
    """The acceptance core: a mid-decode dispatch fault aborts the
    engine; the supervisor rebuilds and replays, and every survivor's
    tokens equal a fault-free run's exactly (greedy replay contract) —
    whatever the decode chunk: the fault is the second decode dispatch's,
    with 2 to 5 tokens a stream salvaged."""
    prompts = [[1, 2, 3, 4], [5, 6, 7], [8, 9]]
    n_new = 12
    oracle = ServingEngine(_config(), seed=SEED).generate(prompts, n_new)

    sup = _supervised(max_restarts=3, backoff_s=0.02)
    with fault.inject("dispatch_error:raise=1,after=4,times=1"):
        reqs = [sup.submit(p, n_new) for p in prompts]
        _run_supervised(sup, reqs)
    assert sup.restarts == 1 and sup.failed is None
    assert "InjectedFault" in sup.last_error
    assert [r.state for r in reqs] == [FINISHED] * 3
    assert [list(r.generated) for r in reqs] == oracle
    eng = sup.engine
    assert eng.pool.used() == 0 and _pool_consistent(eng.pool)
    assert telemetry.counter("serving.restarts").value == 1
    blk = sup.stats()["supervisor"]
    assert blk["restarts"] == 1 and not blk["restarting"]
    assert blk["failed"] is None


def test_supervisor_gives_up_past_restart_budget(telem):
    """A fault that outlives the budget turns into a permanent failure:
    pending requests FAIL with the abort cause, submits refuse, and the
    driver thread's death stays observable (run_loop re-raises)."""
    sup = _supervised(max_restarts=1, backoff_s=0.01)
    raised = []

    def drive():
        try:
            sup.run_loop(threading.Event(), idle_wait_s=0.01)
        except Exception as exc:    # the re-raised abort cause
            raised.append(exc)

    with fault.inject("dispatch_error:raise=1"):    # fires every dispatch
        req = sup.submit([1, 2, 3], 4)
        t = threading.Thread(target=drive, name="test-sup-perm",
                             daemon=True)
        t.start()
        assert req.done_event.wait(120)
        t.join(timeout=120)
    assert raised and not t.is_alive()
    assert sup.failed is not None and "restart budget" in sup.failed
    assert req.state == FAILED and "InjectedFault" in req.error
    with pytest.raises(RuntimeError, match="permanently failed"):
        sup.submit([1], 1)
    assert sup.stats()["supervisor"]["failed"] == sup.failed


def test_supervisor_sheds_during_restart_window():
    sup = _supervised(max_restarts=2, backoff_s=0.05)
    with sup._lock:
        sup._restarting = True      # pin the window open
    try:
        with pytest.raises(ServingOverloadError) as ei:
            sup.submit([1, 2], 2)
        assert ei.value.reason == "restarting"
        assert sup.has_work()       # salvaged work pending by definition
    finally:
        with sup._lock:
            sup._restarting = False


# ---------------------------------------------------------------------------
# drain: admission closes, inflight finishes, has_work() signals done
# ---------------------------------------------------------------------------


def test_drain_closes_admission_and_finishes_inflight(telem):
    eng = ServingEngine(_config(), seed=SEED)
    inflight = eng.submit([1, 2, 3], 5)
    eng.start_drain()
    eng.start_drain()               # idempotent: one counter tick
    assert eng.draining
    with pytest.raises(ServingOverloadError) as ei:
        eng.submit([4, 5], 2)
    assert ei.value.reason == "draining"
    _drain(eng)
    assert inflight.state == FINISHED
    assert not eng.has_work()
    assert telemetry.counter("serving.drains").value == 1
    assert eng.stats()["resilience"]["draining"] is True


def test_supervisor_drain_is_sticky_across_restarts():
    """A drain in progress survives an abort+restart: the replacement
    engine comes up with admission already closed, while the salvaged
    inflight request still replays to completion (drain finishes work,
    it does not drop it)."""
    sup = _supervised(max_restarts=3, backoff_s=0.01)
    with fault.inject("dispatch_error:raise=1,times=1"):
        req = sup.submit([1, 2, 3], 3)  # admitted BEFORE the drain
        sup.start_drain()
        _run_supervised(sup, [req])     # abort -> restart -> replay
    assert sup.restarts == 1
    assert req.state == FINISHED
    assert sup.draining and sup.engine.draining, \
        "a restart mid-drain must not reopen admission"
    with pytest.raises(ServingOverloadError) as ei:
        sup.submit([4], 1)
    assert ei.value.reason == "draining"
    assert not sup.has_work()           # the drain sequence can exit


# ---------------------------------------------------------------------------
# serve.py: the bounded handler wait (a wedged engine cannot hang clients)
# ---------------------------------------------------------------------------


def test_http_handler_wait_is_bounded(telem, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_HANDLER_TIMEOUT_S", "0.4")
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve

    eng = ServingEngine(_config(), seed=SEED)   # no driver: wedged
    server = serve.make_server(eng, "127.0.0.1", 0)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = "http://127.0.0.1:%d" % server.server_address[1]
    try:
        body = json.dumps({"tokens": [1, 2], "max_new_tokens": 2}).encode()
        t0 = time.time()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                urllib.request.Request(base + "/generate", data=body),
                timeout=30)
        assert ei.value.code == 504
        assert time.time() - t0 < 10.0, "handler bound did not bound"
        rep = json.loads(ei.value.read())
        assert "wedged" in rep["error"]
        # the handler cancelled the stranded request on its way out
        assert list(eng.scheduler.waiting)[0].cancelled
        eng.step()                  # sweep: blocks freed, waiter woken
        assert eng.pool.used() == 0
    finally:
        server.shutdown()
        server.server_close()


def test_mxtop_renders_resilience_line():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import mxtop

    frame = mxtop.render_serving({
        "engine": "e1", "steps": 5, "completed": 3, "failed": 0,
        "preemptions": 0, "active": 1, "waiting": 2,
        "kv_blocks_used": 4, "kv_blocks_total": 63,
        "tokens_per_sec": 10.0, "slo": {},
        "resilience": {"shed": 7, "timed_out": 2, "cancelled": 1,
                       "draining": True},
        "supervisor": {"restarts": 1, "max_restarts": 3,
                       "restarting": False, "failed": None},
    })
    assert "shed 7 to 2 cx 1" in frame
    assert "restarts 1/3" in frame and "DRAINING" in frame


# ---------------------------------------------------------------------------
# slow chaos e2e: serve.py survives an injected abort under live traffic
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_e2e_chaos_restart_shed_drain(tmp_path):
    """Acceptance: tools/serve.py with a mid-traffic ``dispatch_error``
    restarts warm (compile-cache hits, supervisor restart counted),
    every 200 response is bit-identical to the fault-free oracle,
    overflow beyond --max-queue sheds with 503 + integer Retry-After,
    an expired request gets 504 and the pool returns to empty, and
    SIGTERM drains the server to exit code 0."""
    port = 18297
    cfg = _config()
    cache_dir = str(tmp_path / "ccache")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        MXNET_FAULT_SPEC="dispatch_error:raise=1,after=6,times=1;"
                         "slow_step:delay_ms=20",
        MXNET_SERVING_RESTART_BACKOFF_MS="50")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tools", "serve.py"),
         "--port", str(port), "--vocab", str(cfg.vocab_size),
         "--num-layers", str(cfg.num_layers),
         "--model-dim", str(cfg.model_dim),
         "--num-heads", str(cfg.num_heads),
         "--ffn-dim", str(cfg.ffn_dim), "--max-len", str(cfg.max_len),
         "--block-size", str(cfg.block_size),
         "--num-blocks", str(cfg.num_blocks),
         "--max-batch", str(cfg.max_batch), "--seed", str(SEED),
         "--warmup", "--cache-dir", cache_dir,
         "--max-queue", "8", "--max-restarts", "3",
         "--drain-timeout", "30"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    base = "http://127.0.0.1:%d" % port

    def get(path, timeout=5):
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return json.loads(r.read())

    def post(payload, timeout=600):
        """(status, headers, body) — shed/timeout statuses included."""
        req = urllib.request.Request(base + "/generate",
                                     data=json.dumps(payload).encode())
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, dict(r.headers), json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers), json.loads(e.read())

    try:
        deadline = time.time() + 180
        while True:
            try:
                assert get("/healthz")["ok"]
                break
            except (OSError, AssertionError):
                if time.time() > deadline:
                    raise RuntimeError("server never came up")
                time.sleep(0.5)
        # cold-start baseline: the first warmup populated the cache
        cc0 = get("/stats")["compile_cache"]
        assert cc0["enabled"]

        rng = np.random.RandomState(11)
        n_req, n_new = 6, 6
        prompts = [[int(x) for x in rng.randint(0, cfg.vocab_size,
                                                rng.randint(2, 9))]
                   for _ in range(n_req)]
        results = [None] * n_req

        def fire(i):
            # a well-behaved client: 503 is a shed (queue_full /
            # restarting window), carries a retry hint, and is safe to
            # retry — the request never started decoding. Retrying pins
            # the documented contract instead of racing the restart.
            deadline_t = time.time() + 120
            while True:
                r = post({"tokens": prompts[i], "max_new_tokens": n_new})
                if r[0] != 503 or time.time() > deadline_t:
                    results[i] = r
                    return
                time.sleep(max(float(r[2].get("retry_after_s", 0.1)),
                               0.05))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        # while the engine chews (slow_step + the injected abort), pile
        # a concurrent burst past --max-queue: the overflow must shed
        # with a classified 503, not hang
        shed = []
        for _round in range(6):
            time.sleep(0.2)
            burst = []
            lock = threading.Lock()

            def volley():
                r = post({"tokens": [1, 2], "max_new_tokens": 2},
                         timeout=300)
                with lock:
                    burst.append(r)

            vt = [threading.Thread(target=volley) for _ in range(14)]
            for t in vt:
                t.start()
            for t in vt:
                t.join(timeout=600)
            shed += [b for b in burst if b[0] == 503]
            if shed:
                break
        for t in threads:
            t.join(timeout=900)

        # survivors: bit-identical to a fault-free in-process oracle
        assert all(r is not None and r[0] == 200 for r in results), \
            [(i, r and r[0], r and r[2]) for i, r in enumerate(results)]
        oracle = ServingEngine(_config(), seed=SEED).generate(
            prompts, n_new)
        for i in range(n_req):
            assert results[i][2]["tokens"] == oracle[i], i

        # the abort happened and the supervisor restarted warm: the
        # replacement's warmup loaded every bucket from the persistent
        # cache instead of compiling cold
        stats = get("/stats")
        assert stats["supervisor"]["restarts"] >= 1
        assert stats["supervisor"]["failed"] is None
        cc = stats["compile_cache"]
        assert cc["hits"] > cc0["hits"], \
            "restart warmup never touched the persistent cache"
        assert cc["misses"] == cc0["misses"], \
            "restart warmup compiled cold instead of loading the cache"

        # shed contract: 503, classified reason, integer Retry-After >= 1
        assert shed, "burst past --max-queue never shed"
        for code, hdrs, body in shed:
            assert body["reason"] in ("queue_full", "restarting")
            assert int(hdrs["Retry-After"]) >= 1
            assert body["retry_after_s"] > 0

        # an already-expired deadline: classified 504, engine unharmed
        code, _hdrs, body = post({"tokens": [3, 4], "max_new_tokens": 4,
                                  "timeout_s": 0.001}, timeout=120)
        assert code == 504 and body["state"] == "timed_out"

        # quiesced: every terminal path returned its KV blocks
        deadline = time.time() + 60
        while time.time() < deadline:
            stats = get("/stats")
            if (stats["active"] == 0 and stats["waiting"] == 0
                    and stats["kv_blocks_used"] == 0):
                break
            time.sleep(0.5)
        assert stats["kv_blocks_used"] == 0, stats

        # SIGTERM: graceful drain to exit 0
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
        out = proc.stdout.read().decode()
        assert "draining: admission closed" in out
        assert "drained: exiting 0" in out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
