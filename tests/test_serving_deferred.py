"""What a step sets aside (``ServingEngine._set_aside``): the chunk's
counters, the tokens' telemetry, the throughput window and the step's
record are carried out under the NEXT step's dispatch by ``run_loop``, and
before anybody reads by everything else. Same work, later: every counter
ends where an engine that books at once leaves it, no reader sees a chunk
half booked, and nothing is left pending on any way out of the loop.

Host-side only, tiny models (the rehearsal cells' configurations): part of
tier-1.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from mxnet_tpu.serving import engine as engine_mod  # noqa: E402
from mxnet_tpu.serving.obs import ServingObs  # noqa: E402

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 5
#: the record's fields that count (the rest are seconds of one run)
COUNTS = ("prefills", "chunk_steps", "lanes", "lane_steps", "live_blocks",
          "window_live_blocks", "full_live_blocks", "finished")


def family(name, **engine):
    """One of the four block families at rehearsal size (GPT-2's one
    block, OLMoE's experts, Phi-4-mini-flash's layer kinds, dots.vlm1's
    latent attention with a share of its experts), float32, no prefix
    cache: a second pass over the same prompts prefills them again."""
    path = os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                        name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["engine"].update(kv_dtype="float32", prefix_cache=False, **engine)
    return ServingConfig.from_json(cfg)


FAMILIES = {"one_block": "lm-tiny", "experts": "olmoe-tiny",
            "hybrid": "phi4flash-tiny", "latent": "dotsvlm1-tiny"}


@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


def waves(vocab, rng):
    """Two waves of requests ``(prompt, max_new_tokens)``: more than a
    batch, so that some wait; lengths that end lanes at every inner step
    of a chunk; the second wave arrives on an emptied queue."""
    def prompt(n):
        return rng.randint(1, vocab, n).tolist()
    first = [(prompt(3 + 2 * i), n)
             for i, n in enumerate((3, 30, 6, 19, 1, 41, 12))]
    second = [(prompt(4 + i), n) for i, n in enumerate((18, 2, 27))]
    return first, second


def eos_near(tokens, target):
    """A token of the stream to end it at: the one that first appears
    nearest before position ``target`` (a tiny model repeats itself)."""
    firsts = [j for j, t in enumerate(tokens) if t not in tokens[:j]]
    return tokens[max(j for j in firsts if j <= target)]


def counters():
    """Every ``serving.*`` counter of the registry, labels and all. The
    SLO verdicts (``slo_good``) judge wall-clock seconds: not compared."""
    return {k: v for k, v in
            telemetry.dump(include_events=False)["counters"].items()
            if k.startswith("serving.") and "slo_good" not in k}


def int_leaves(obj, path=()):
    """path -> int over a ``stats()`` tree (lists by index)."""
    if isinstance(obj, bool) or obj is None:
        return {}
    if isinstance(obj, int):
        return {path: obj}
    items = (obj.items() if isinstance(obj, dict)
             else enumerate(obj) if isinstance(obj, list) else ())
    out = {}
    for k, v in items:
        out.update(int_leaves(v, path + (k,)))
    return out


#: stats() leaves that are no tallies of the pass: compile counts, the
#: most window blocks a stream ever held, the SLO's clock-made verdicts
NOT_TALLIES = ("compiles", "compile_cache", "engine", "slo")


def snapshot(eng):
    st = eng.stats()
    for k in NOT_TALLIES:
        st.pop(k, None)
    st.get("state", {}).pop("window_blocks_a_stream", None)
    # seconds (an int only while they are 0) and HOW the items were
    # carried out: what the two passes differ by
    sums = st["loop"]["sums"]
    for k in [k for k in sums if k.endswith("_s")] + ["deferred_hidden"]:
        del sums[k]
    return {"counters": counters(),
            "batch": telemetry.totals("serving.decode_batch"),
            "group": telemetry.totals("serving.prefill.group"),
            "stats": int_leaves(st), "ring": len(eng.obs._ring)}


def delta(after, before):
    out = {"counters": {k: v - before["counters"].get(k, 0)
                        for k, v in after["counters"].items()},
           "stats": {k: v - before["stats"].get(k, 0)
                     for k, v in after["stats"].items()}}
    for k in ("batch", "group"):
        out[k] = tuple(a - b for a, b in zip(after[k], before[k]))
    return out


def records(eng, before, after):
    recs = list(eng.obs._ring)[before["ring"]:after["ring"]]
    return recs, [tuple(getattr(r, f) for f in COUNTS) for r in recs]


def run_pass(eng, batches, deferred, eos=None):
    """Serve ``batches`` one after the other, each on an emptied queue,
    every request of a batch queued before the batch's first step: through
    ``run_loop`` on a driver thread (``deferred``), or by hand with the
    public ``step()``, which books at once. Returns the requests."""
    eos = eos or {}
    done = []
    stop = threading.Event()
    driver = threading.Thread(target=eng.run_loop, args=(stop, 30.0))
    if deferred:
        driver.start()
    try:
        for b, batch in enumerate(batches):
            if deferred:        # the loop waits on its condition
                while not eng._work._waiters:
                    time.sleep(0.001)
            with eng._lock:
                reqs = [eng.submit(p, n, eos_id=eos.get((b, i)))
                        for i, (p, n) in enumerate(batch)]
            if deferred:
                for r in reqs:
                    assert r.done_event.wait(120)
            else:
                while eng.has_work():
                    eng.step()
            done.append(reqs)
    finally:
        stop.set()
        with eng._work:
            eng._work.notify_all()
        if deferred:
            driver.join(60)
    assert not driver.is_alive()
    return done


CASES = [("one_block", 1), ("one_block", 2), ("one_block", 4),
         ("one_block", 8), ("experts", 2), ("experts", 8), ("hybrid", 4),
         ("hybrid", 8), ("latent", 1), ("latent", 8), ("one_block_tight", 8),
         ("speculative", 8)]


@pytest.mark.parametrize("name,chunk", CASES,
                         ids=["%s-chunk%d" % c for c in CASES])
def test_deferred_booking_ends_where_booking_at_once_does(name, chunk, telem,
                                                          monkeypatch):
    """The same requests three times through one engine: once to compile
    and to find each stream's tokens (an EOS is then planted in some), once
    stepped by hand (booked at once), once through ``run_loop`` (booked
    under the next step's dispatch). The two last passes move every
    ``serving.*`` counter, the ``serving.decode_batch`` histogram, every
    integer of ``stats()`` and the records' counts by the same amounts."""
    monkeypatch.setattr(engine_mod, "DECODE_CHUNK", chunk)
    if name == "speculative":
        cfg = family("lm-tiny", spec_k=2, draft="self")
    elif name == "one_block_tight":     # 4 blocks of 16: streams preempt
        cfg = family("lm-tiny", num_blocks=5)
    else:
        cfg = family(FAMILIES[name])
    eng = ServingEngine(cfg, seed=SEED)
    batches = waves(cfg.vocab_size, np.random.RandomState(SEED))
    found = run_pass(eng, batches, deferred=False)
    # an EOS a few tokens in, for three streams of the first wave and one
    # of the second: lanes that die inside a chunk, not at its end
    eos = {(b, i): eos_near(found[b][i].generated, cut)
           for b, i, cut in ((0, 1, 12), (0, 3, 10), (0, 5, 27), (1, 2, 11))}
    s0 = snapshot(eng)
    at_once = run_pass(eng, batches, deferred=False, eos=eos)
    s1 = snapshot(eng)
    later = run_pass(eng, batches, deferred=True, eos=eos)
    s2 = snapshot(eng)
    assert eng._pending is None
    for a, b in zip(sum(at_once, []), sum(later, [])):
        assert a.generated == b.generated and a.state == b.state
    assert any(len(r.generated) < r.max_new_tokens
               for r in sum(later, [])), "no stream met its EOS"
    want, got = delta(s1, s0), delta(s2, s1)
    assert want["counters"]["serving.generated_tokens"] == sum(
        len(r.generated) for r in sum(at_once, [])) > 0
    assert want["counters"]["serving.paged.live_blocks"] > 0
    if name == "one_block_tight":
        assert want["counters"]["serving.preemptions"] > 0
    for k in set(want["counters"]) | set(got["counters"]):
        assert got["counters"].get(k) == want["counters"].get(k), k
    assert got["batch"] == want["batch"] and want["batch"][0] > 0
    assert got["group"] == want["group"]
    assert got["stats"] == want["stats"]
    assert want["stats"][("tokens_total",)] \
        == want["counters"]["serving.generated_tokens"]
    recs_once, counts_once = records(eng, s0, s1)
    recs_later, counts_later = records(eng, s1, s2)
    assert counts_later == counts_once and len(recs_later) > 4
    assert [r.step - recs_later[0].step for r in recs_later] \
        == list(range(len(recs_later)))
    # by hand every item was flushed by its own step(); in the loop every
    # step's but a wave's last ran under the next step's dispatch
    assert not any(r.deferred_hidden or r.deferred_s for r in recs_once)
    hidden = [r.deferred_hidden for r in recs_later]
    assert sum(hidden) == len(hidden) - len(batches)
    assert all(r.deferred_s > 0 for r, prev in zip(recs_later[1:], hidden)
               if prev)


def test_no_reader_sees_a_chunk_half_booked(telem):
    """A thread reads ``stats()`` as fast as it can while ``run_loop``
    serves a model with experts: at every read the router's choices are
    ``experts_per_tok`` a token and layer, exactly (both sides are booked
    by the one deferred ``_note_moe``), and the registry's token counter,
    read AFTER ``stats()``, holds every token the streams hold."""
    cfg = family("olmoe-tiny")
    eng = ServingEngine(cfg, seed=SEED)
    eng.warmup()
    rng = np.random.RandomState(SEED)
    stop, closed = threading.Event(), threading.Event()
    driver = threading.Thread(target=eng.run_loop, args=(stop, 0.05))
    driver.start()
    reqs, reads, bad = [], [], []
    tokens = telemetry.counter("serving.generated_tokens")
    base = tokens.value

    def caller(k):
        while not closed.is_set():
            with eng._lock:     # the reader counts what is in `reqs`
                req = eng.submit(rng.randint(1, cfg.vocab_size,
                                             3 + k).tolist(), 5 + 3 * k)
                reqs.append(req)
            assert req.done_event.wait(120)

    def reader():
        while not closed.is_set():
            with eng._lock:
                moe = eng.stats()["moe"]
                held = sum(len(r.generated) for r in reqs)
                booked = tokens.value - base
                pending = eng._pending
            reads.append(moe["layer_tokens"])
            if (moe["routed_pairs"] != cfg.experts_per_tok
                    * moe["layer_tokens"] or booked != held
                    or pending is not None):
                bad.append((moe["routed_pairs"], moe["layer_tokens"],
                            booked, held))

    threads = [threading.Thread(target=caller, args=(k,)) for k in range(5)]
    threads.append(threading.Thread(target=reader))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # the reader cuts in wherever it can
    try:
        for t in threads:
            t.start()
        time.sleep(1.5)
    finally:
        sys.setswitchinterval(interval)
        closed.set()
        for t in threads:
            t.join(120)
        stop.set()
        driver.join(60)
    assert not bad, bad[:3]
    # the reads fell among the steps, not before or after them all
    assert len(set(reads)) > 3 and eng.stats()["decode"]["dispatches"] > 3


def _engine(**over):
    return ServingEngine(family("lm-tiny", **over), seed=SEED)


def _booked_is_delivered(eng, reqs, base):
    """Nothing pending, and WITHOUT a read's flush the registry holds
    every token delivered and the ring every step."""
    with eng._lock:
        assert eng._pending is None
        booked = telemetry.counter("serving.generated_tokens").value - base
        recorded = len(eng.obs._ring)
        st = eng.stats()
    assert booked == st["tokens_total"] \
        == 4 + sum(len(r.generated) for r in reqs)
    assert recorded == st["steps"]


def _serve_in_a_loop(eng, n_new, stop_when):
    """Two streams through ``run_loop``; ``stop_when(reqs)`` says when the
    main thread goes on. Returns (the requests, the stop event, the
    driver)."""
    stop = threading.Event()
    driver = threading.Thread(target=eng.run_loop, args=(stop, 0.05))
    with eng._lock:
        reqs = [eng.submit([1, 2, 3 + i], n_new) for i in range(2)]
    driver.start()
    deadline = time.time() + 120
    while not stop_when(reqs) and time.time() < deadline:
        time.sleep(0.001)
    assert stop_when(reqs)
    return reqs, stop, driver


@pytest.mark.parametrize("way", ["step", "generate", "emptied_queue",
                                 "stop_event", "abort", "failed_dispatch",
                                 "drain", "warmup", "prefill_logits"])
def test_nothing_is_left_pending(way, telem, monkeypatch):
    """Every way out of the loop, and every call that runs a program of
    its own, carries out what the last step set aside: the registry then
    holds what the streams hold."""
    eng = _engine()
    eng.generate([[1, 2, 3]], [4])          # compiled
    base = telemetry.counter("serving.generated_tokens").value \
        - eng.stats()["tokens_total"]
    some = lambda reqs: all(len(r.generated) > 12 for r in reqs)  # noqa: E731
    if way == "step":
        reqs = [eng.submit([1, 2, 3 + i], 20) for i in range(2)]
        while eng.has_work():
            eng.step()
            _booked_is_delivered(eng, reqs, base)
    elif way == "generate":
        reqs = [eng.submit([5, 6], 3)]
        eng.generate([[1, 2, 3], [4, 5]], [9, 12])
        assert eng._pending is None and reqs[0].finished()
        assert telemetry.counter("serving.generated_tokens").value - base \
            == 4 + 3 + 9 + 12
    elif way == "emptied_queue":
        reqs, stop, driver = _serve_in_a_loop(
            eng, 30, lambda rs: all(r.finished() for r in rs))
        while not eng._work._waiters:       # the loop idles
            time.sleep(0.001)
        with eng._lock:                     # ... with nothing set aside
            assert eng._pending is None
            assert len(eng.obs._ring) == eng._steps
        stop.set()
        driver.join(60)
        _booked_is_delivered(eng, reqs, base)
    elif way == "stop_event":
        reqs, stop, driver = _serve_in_a_loop(eng, 60, some)
        stop.set()
        driver.join(60)
        assert not driver.is_alive() and eng.has_work()
        with eng._lock:
            assert eng._pending is None
        _booked_is_delivered(eng, reqs, base)
    elif way == "abort":
        eng.salvage_on_abort = True         # as under a supervisor
        reqs, stop, driver = _serve_in_a_loop(eng, 60, some)
        eng.abort(RuntimeError("pulled"))
        with eng._lock:
            assert eng._pending is None
        stop.set()
        driver.join(60)
        salvaged = eng.pop_salvaged()   # (the lock is not fair: a stream
        # may have run to its end before abort() got it)
        assert all(r.finished() or r in salvaged for r in reqs)
        _booked_is_delivered(eng, reqs, base)
    elif way == "failed_dispatch":
        # the step after a deferred one fails at its dispatch, before its
        # flush point: abort() carries the waiting item out
        reqs = [eng.submit([1, 2, 3 + i], 40) for i in range(2)]
        eng._guarded_step(defer=True)
        assert eng._pending is not None
        monkeypatch.setattr(eng, "_dispatch_decode", lambda *a, **k: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            eng._guarded_step(defer=True)
        assert eng.aborted is not None
        _booked_is_delivered(eng, reqs, base)
    elif way == "drain":
        reqs, stop, driver = _serve_in_a_loop(eng, 60, some)
        with eng._lock:
            eng.start_drain()
            assert eng._pending is None
        for r in reqs:
            assert r.done_event.wait(120)
        stop.set()
        driver.join(60)
        _booked_is_delivered(eng, reqs, base)
    else:
        reqs = [eng.submit([1, 2, 3 + i], 20) for i in range(2)]
        eng._guarded_step(defer=True)
        eng._guarded_step(defer=True)
        assert eng._pending is not None
        if way == "warmup":
            eng.warmup(prefill_buckets=[16])
        else:
            eng.prefill_logits([1, 2, 3, 4])
        _booked_is_delivered(eng, reqs, base)


def test_an_item_runs_after_the_dispatch_returns_and_before_its_fetch(
        telem, monkeypatch):
    """Where step N's item is carried out in step N+1: after the chunk's
    dispatch call has returned and before the host blocks in its fetch; in
    a step that only prefills, after the group's LAST dispatch and before
    its first fetch; never between two dispatches of a group."""
    eng = _engine()
    log = []

    def logged(name, f):
        def call(*a, **kw):
            out = f(*a, **kw)
            log.append(name)
            return out
        return call

    class Np:
        def asarray(self, a, *args, **kw):
            if hasattr(a, "copy_to_host_async"):
                log.append("fetch")
            return np.asarray(a, *args, **kw)

        def __getattr__(self, name):
            return getattr(np, name)

    monkeypatch.setattr(eng, "_dispatch_prefill",
                        logged("prefill", eng._dispatch_prefill))
    monkeypatch.setattr(eng, "_dispatch_decode",
                        logged("decode", eng._dispatch_decode))
    monkeypatch.setattr(ServingObs, "step_timeline",
                        logged("item", ServingObs.step_timeline))
    monkeypatch.setattr(engine_mod, "np", Np())
    eng.submit([1, 2, 3], 30)
    eng._guarded_step(defer=True)
    assert log == ["prefill", "fetch", "decode", "fetch"]
    del log[:]
    eng.submit([4, 5, 6, 7], 9)
    eng.submit([8, 9], 9)
    eng._guarded_step(defer=True)       # a group of two, then the chunk
    assert log == ["prefill", "prefill", "fetch", "fetch", "decode", "item",
                   "fetch"]
    del log[:]
    eng._guarded_step(defer=True)       # a chunk alone
    assert log == ["decode", "item", "fetch"]
    while eng.has_work():
        eng._guarded_step(defer=True)
    del log[:]
    for i in range(2):                  # prompts that end at their first
        eng.submit([1, 2 + i], 1)       # token: no chunk follows the group
    eng._guarded_step(defer=True)
    assert log == ["prefill", "prefill", "item", "fetch", "fetch"]
    assert not eng.has_work() and eng._pending is not None
    eng.stats()
    assert eng._pending is None and log[-1] == "item"


def test_hidden_items_and_what_is_left_in_the_gap(telem):
    """``deferred_hidden`` is 1 for the steps of a saturated loop and 0
    for a step followed by an empty queue; a step that carried an item out
    spent more on it (``deferred_s``) than on what bookkeeping is left in
    its gap (``retire_counters_s``); ``stats()["loop"]`` and the
    ``serving.step_timeline`` event carry both."""
    eng = _engine()
    eng.warmup()
    first = len(eng.obs._ring)
    reqs, stop, driver = _serve_in_a_loop(
        eng, 60, lambda rs: all(r.finished() for r in rs))
    while not eng._work._waiters:
        time.sleep(0.001)
    with eng._lock:
        recs = list(eng.obs._ring)[first:]
    stop.set()
    driver.join(60)
    assert len(recs) == -(-59 // engine_mod.DECODE_CHUNK) >= 8
    assert [r.deferred_hidden for r in recs] == [1] * (len(recs) - 1) + [0]
    assert recs[0].deferred_s == 0.0        # no step before it
    carried = recs[1:]
    assert all(r.deferred_s > 0 for r in carried)
    assert sum(r.retire_counters_s for r in carried) \
        < sum(r.deferred_s for r in carried)
    # the item is in no gap and in no section of the step it ran in
    assert all(r.retire_counters_s < r.retire_s for r in recs)
    loop = eng.stats()["loop"]
    assert loop["sums"]["deferred_hidden"] == len(recs) - 1
    assert loop["sums"]["deferred_s"] == pytest.approx(
        sum(r.deferred_s for r in recs), abs=1e-5)
    assert loop["mean_ms"]["deferred"] > 0
    evs = [e for e in telemetry.events("serving.step_timeline")
           if e["engine"] == str(eng.engine_id)][-len(recs):]
    assert [e["deferred_hidden"] for e in evs] \
        == [r.deferred_hidden for r in recs]
    assert [e["step"] for e in evs] == [r.step for r in recs]
    spans = telemetry.totals("serving.retire.deferred")
    assert spans[0] >= len(recs)


def test_telemetry_off_books_the_engines_own_tallies(monkeypatch):
    """No record, no clock: the deferred half still books the integers
    ``stats()`` reads."""
    telemetry.disable()
    telemetry.reset()
    try:
        eng = ServingEngine(family("olmoe-tiny"), seed=SEED,
                            enable_telemetry=False)
        with eng._lock:
            reqs = [eng.submit([1, 2, 3 + i], 12) for i in range(3)]
        while eng.has_work():
            eng._guarded_step(defer=True)
        assert eng._pending is not None and eng._pending[0] is None
        st = eng.stats()
        assert eng._pending is None and not eng.obs._ring
        assert st["tokens_total"] == 36 == sum(len(r.generated)
                                               for r in reqs)
        assert st["moe"]["routed_pairs"] == 2 * st["moe"]["layer_tokens"] > 0
        assert st["paged"]["live_blocks"] > 0
        assert st["decode"]["inner_steps"] == 11
    finally:
        telemetry.reset()


@pytest.mark.parametrize("name,share", [("one_block", 0.0), ("experts", 0.0),
                                        ("hybrid", 1.0), ("latent", 1.0)])
def test_mxu_share_is_read_off_what_is_already_counted(name, share, telem):
    """``stats()["paged"]["mxu_share"]``: of the block walks counted, the
    share on head-major pages (two MXU matmuls a block; a token-major
    block is elementwise work). Nothing is booked a step for it: read
    straight after the last dispatch, with the last chunk still set aside,
    it is whole; before any walk it is 0."""
    eng = ServingEngine(family(FAMILIES[name]), seed=SEED)
    assert eng.stats()["paged"]["mxu_share"] == 0.0
    with eng._lock:
        for i in range(3):
            eng.submit([1, 2, 3 + i], 12)
    while eng.has_work():
        eng._guarded_step(defer=True)
    assert eng._pending is not None
    paged = eng.stats()["paged"]
    assert paged["live_blocks"] > 0
    assert paged["mxu_share"] == share == float(eng.pool.spec.head_major)
    assert telem.gauge("serving.paged.mxu_share").value == share
    if name == "hybrid":            # the window pool's walks are in it
        sums = eng.stats()["loop"]["sums"]
        assert sums["window_live_blocks"] > 0
        assert eng.streams.pool.spec.head_major
        # the keys a walk read are plain grouped-query attention's to
        # book: this model's decode steps pay for none of it
        assert "hybrid" not in eng.stats()
        assert (sums["full_ctx_tokens"], sums["window_ctx_tokens"]) == (0, 0)
        assert telem.counter("serving.hybrid.lane_steps").value == 0
    assert eng.stats()["loop"]["sums"]["prefill_tokens"] == 3 * 3
