"""Serving-engine suite (docs/serving.md): paged-attention numerics vs the
contiguous-cache decoder, KV block allocator invariants, continuous-batching
scheduler fairness + preemption, the graph-level cache-overflow contract on
BOTH decode paths, compile-flat decode after bucket warmup, and the
concurrent-vs-sequential output-equality contract — capped by a slow e2e
driving >=32 concurrent variable-length HTTP requests through
``tools/serve.py`` and comparing byte-for-byte against single-stream
decoding.

Host-side only: part of tier-1 (tests/conftest.py pins jax to the CPU);
`ci/run_tests.sh serving` runs the serving files alone, slow cases included.
"""
import importlib
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

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import compileobs, telemetry  # noqa: E402
from mxnet_tpu.ops import attention as A  # noqa: E402
from mxnet_tpu.serving import (  # noqa: E402
    KVBlockPool, KVCacheOOM, Request, Scheduler, ServingConfig, ServingEngine)
from mxnet_tpu.serving import model as smodel  # noqa: E402
from mxnet_tpu.serving.kv_cache import PageSpec  # noqa: E402
from mxnet_tpu.serving.scheduler import STOP_REASONS  # noqa: E402

from chunk_cases import (  # noqa: E402
    chunk_equals_single_steps, lane, tables_for)

pytestmark = pytest.mark.serving

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
tlm = importlib.import_module("mxnet_tpu.models.transformer_lm")

# one tiny config shared across the suite (each engine pays its own XLA
# compiles on this 1-core host — keep the model small and reuse fixtures)
CFG = dict(vocab_size=23, num_layers=2, model_dim=32, num_heads=2,
           ffn_dim=48, max_len=64)
SEED = 3
# the allocator's cases: one layer of two heads of 8 a token
SPEC = PageSpec.lane_dense(1, 2, 8)


def _config(**over):
    kw = dict(CFG, block_size=8, num_blocks=64, max_batch=8,
              prefills_per_step=4)
    kw.update(over)
    return ServingConfig(**kw)


def _decode_executor(params):
    dec = tlm.get_decode_symbol(seq_len=CFG["max_len"], **CFG)
    ex = dec.simple_bind(ctx=mx.cpu(), grad_req="null", data=(1, 1))
    for n, a in ex.arg_dict.items():
        if n in params:
            a[:] = params[n]
    return ex


def _oracle_generate(ex, prompt, n_new, max_len=None):
    """Single-stream greedy decoding through the CONTIGUOUS cached decoder
    (the pre-serving path) — the numeric + token oracle."""
    max_len = max_len or CFG["max_len"]
    for a in ex.aux_dict.values():
        a[:] = 0
    out, t, nxt = [], 0, None
    for tok in prompt:
        probs = tlm.decode_step(ex, [tok], t, max_len)
        t += 1
        nxt = int(np.argmax(probs[0]))
    for _ in range(n_new):
        out.append(nxt)
        probs = tlm.decode_step(ex, [nxt], t, max_len)
        t += 1
        nxt = int(np.argmax(probs[0]))
    return out


def _mixed_workload(n, rng, vocab=None, prompt_max=9, new_max=10):
    vocab = vocab or CFG["vocab_size"]
    prompts = [[int(x) for x in rng.randint(0, vocab,
                                            rng.randint(1, prompt_max))]
               for _ in range(n)]
    n_new = [int(rng.randint(1, new_max)) for _ in range(n)]
    return prompts, n_new


# ---------------------------------------------------------------------------
# paged-attention kernel numerics
# ---------------------------------------------------------------------------


def _rand_paged(rng, B=3, H=2, D=16, bs=8, N=12, nb=4, dtype=np.float32):
    import jax.numpy as jnp

    q = jnp.asarray(rng.randn(B, H, D).astype(dtype))
    kp = jnp.asarray(rng.randn(N, bs, H, D).astype(dtype))
    vp = jnp.asarray(rng.randn(N, bs, H, D).astype(dtype))
    bt = jnp.asarray(rng.randint(1, N, (B, nb)).astype(np.int32))
    # ragged lengths spanning short / partial-block / exactly-full
    lens = [5, nb * bs // 2 + 1, nb * bs]
    cl = jnp.asarray(np.array([lens[i % 3] for i in range(B)], np.int32))
    return q, kp, vp, bt, cl


def test_paged_reference_matches_dense_oracle_fp32():
    """Gathering K/V through block tables == dense attention over the same
    tokens (per-sequence ragged lengths), at <1e-5 for fp32."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    q, kp, vp, bt, cl = _rand_paged(rng)
    out = A.paged_attention_reference(q, kp, vp, bt, cl)
    B, nb, bs = q.shape[0], bt.shape[1], kp.shape[1]
    k = jnp.take(kp, bt, axis=0).reshape(B, nb * bs, q.shape[1], q.shape[2])
    v = jnp.take(vp, bt, axis=0).reshape(B, nb * bs, q.shape[1], q.shape[2])
    for b in range(B):
        L = int(cl[b])
        dense = A.attention_reference(
            q[b:b + 1, :, None, :],
            k[b:b + 1, :L].transpose(0, 2, 1, 3),
            v[b:b + 1, :L].transpose(0, 2, 1, 3))
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(dense[0, :, 0]),
                                   rtol=1e-5, atol=1e-5)


def test_paged_reference_bf16_pages():
    """bf16 KV pages: same math within bf16 resolution (the dtype serving
    runs at to double pooled streams)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    q, kp, vp, bt, cl = _rand_paged(rng)
    ref = A.paged_attention_reference(q, kp, vp, bt, cl)
    out = A.paged_attention_reference(q.astype(jnp.bfloat16),
                                      kp.astype(jnp.bfloat16),
                                      vp.astype(jnp.bfloat16), bt, cl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)


def test_paged_reference_empty_stream_reads_exact_zero():
    """A context_len == 0 row returns exactly zero: an all-masked softmax
    would otherwise go uniform and average trash-block garbage into the
    output, diverging from the Pallas kernel's empty-stream result."""
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    q, kp, vp, bt, _cl = _rand_paged(rng)
    cl = jnp.asarray(np.array([0, 5, 0], np.int32))
    out = np.asarray(A.paged_attention_reference(q, kp, vp, bt, cl))
    assert np.all(out[0] == 0.0) and np.all(out[2] == 0.0)
    assert np.abs(out[1]).sum() > 0, "live row must still attend"
    pal = np.asarray(A._paged_pallas(q, kp, vp, bt, cl,
                                     1.0 / np.sqrt(q.shape[-1]),
                                     interpret=True))
    np.testing.assert_allclose(pal, out, rtol=1e-6, atol=1e-6)


# (the Pallas kernel in interpret mode against this reference, ragged
# contexts, both page forms: tests/test_paged_pages.py::
# test_pallas_interpret_matches_reference, which took this file's case as
# its "plain-2x32" parameter)


def test_paged_masked_slots_contribute_exactly_zero():
    """Garbage in slots past context_len — even huge values — cannot leak:
    masked scores underflow to p == 0.0 exactly."""
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    q, kp, vp, bt, cl = _rand_paged(rng)
    out = A.paged_attention_reference(q, kp, vp, bt, cl)
    # poison every slot >= context_len of each sequence's own blocks AND
    # every block the tables don't reference
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    bs = kp2.shape[1]
    referenced = set()
    for b in range(q.shape[0]):
        L = int(cl[b])
        for i, blk in enumerate(np.asarray(bt)[b]):
            lo = i * bs
            for s in range(bs):
                if lo + s < L:
                    referenced.add((int(blk), s))
    for blk in range(kp2.shape[0]):
        for s in range(bs):
            if (blk, s) not in referenced:
                kp2[blk, s] = 1e30
                vp2[blk, s] = -1e30
    out2 = A.paged_attention_reference(q, jnp.asarray(kp2),
                                       jnp.asarray(vp2), bt, cl)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ---------------------------------------------------------------------------
# full-model numerics: paged decode vs the contiguous cached decoder
# ---------------------------------------------------------------------------


def test_paged_decode_matches_contiguous_decode_probs():
    """The functional paged decode reproduces the contiguous-cache executor's
    next-token distribution at every step (<1e-5, fp32) — the serving path
    serves the SAME model the training stack trained."""
    import jax

    cfg = _config()
    params_np = smodel.random_params(cfg, seed=SEED)
    params = smodel.as_device_params(params_np, cfg)
    ex = _decode_executor(params_np)
    for a in ex.aux_dict.values():
        a[:] = 0
    pool = KVBlockPool(cfg.cache_specs().full, cfg.num_blocks,
                       cfg.block_size)
    nb_max = cfg.max_len // cfg.block_size
    blocks = pool.alloc(nb_max)
    table = np.zeros((1, nb_max), np.int32)
    table[0] = blocks
    rng = np.random.RandomState(7)
    toks = rng.randint(0, cfg.vocab_size, 20)
    kp, vp = pool.k_pages, pool.v_pages
    for t, tok in enumerate(toks):
        probs_ctg = tlm.decode_step(ex, [int(tok)], t, cfg.max_len)[0]
        _nxt, logits, kp, vp = smodel.decode(
            params, np.array([tok], np.int32), np.array([t], np.int32),
            table, np.array([t + 1], np.int32), kp, vp, cfg)
        probs_paged = np.asarray(jax.nn.softmax(logits[0], axis=-1))
        np.testing.assert_allclose(probs_paged, probs_ctg,
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# cache-overflow contract (both paths)
# ---------------------------------------------------------------------------


def test_contiguous_overflow_cannot_corrupt_cache():
    """position >= max_len through the CONTIGUOUS cached op: the KV caches
    pass through untouched and the output is NaN-poisoned (graph-level
    contract; the host guard in decode_step is tested separately)."""
    params_np = smodel.random_params(_config(), seed=SEED)
    ex = _decode_executor(params_np)
    for a in ex.aux_dict.values():
        a[:] = 0
    for t in range(3):  # legitimate steps fill slots 0..2
        tlm.decode_step(ex, [5], t, CFG["max_len"])
    before = {n: a.asnumpy().copy() for n, a in ex.aux_dict.items()}
    assert any(np.abs(v).sum() > 0 for v in before.values())
    # bypass the host guard: drive the executor directly past max_len
    ex.arg_dict["data"][:] = np.array([[5.0]], np.float32)
    ex.arg_dict["position"][:] = np.array([CFG["max_len"]], np.float32)
    ex.forward(is_train=True)
    out = ex.outputs[0].asnumpy()
    assert np.isnan(out).all(), "overflow output must be NaN-poisoned"
    for n, a in ex.aux_dict.items():
        np.testing.assert_array_equal(a.asnumpy(), before[n]), n


def test_decode_step_host_guard_still_raises():
    params_np = smodel.random_params(_config(), seed=SEED)
    ex = _decode_executor(params_np)
    with pytest.raises(ValueError, match="KV cache is full"):
        tlm.decode_step(ex, [1], CFG["max_len"], CFG["max_len"])


def test_paged_overflow_cannot_corrupt_pool():
    """position >= max_len through the PAGED decode: the write lands in the
    reserved trash block, every real block is bit-identical after the step,
    and the lane is poisoned (token -1, NaN logits)."""
    cfg = _config()
    params = smodel.as_device_params(smodel.random_params(cfg, seed=SEED),
                                     cfg)
    pool = KVBlockPool(cfg.cache_specs().full, cfg.num_blocks,
                       cfg.block_size)
    nb_max = cfg.max_len // cfg.block_size
    table = np.zeros((1, nb_max), np.int32)
    table[0] = pool.alloc(nb_max)
    kp, vp = pool.k_pages, pool.v_pages
    # one legitimate step so the pool holds real data
    _n, _l, kp, vp = smodel.decode(
        params, np.array([4], np.int32), np.array([0], np.int32), table,
        np.array([1], np.int32), kp, vp, cfg)
    before_k, before_v = np.asarray(kp).copy(), np.asarray(vp).copy()
    nxt, logits, kp2, vp2 = smodel.decode(
        params, np.array([4], np.int32),
        np.array([cfg.max_len], np.int32),  # out of range
        table, np.array([cfg.max_len + 1], np.int32), kp, vp, cfg)
    assert int(np.asarray(nxt)[0]) == -1
    assert np.isnan(np.asarray(logits)).all()
    # real blocks (everything except trash block 0) must be untouched
    np.testing.assert_array_equal(np.asarray(kp2)[:, 1:], before_k[:, 1:])
    np.testing.assert_array_equal(np.asarray(vp2)[:, 1:], before_v[:, 1:])


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------


def test_pool_alloc_free_accounting():
    pool = KVBlockPool(SPEC, 9, 4)
    assert pool.num_usable == 8
    assert pool.available() == 8
    a = pool.alloc(3)
    assert len(a) == 3 and len(set(a)) == 3
    assert 0 not in a, "trash block must never be handed out"
    assert pool.used() == 3
    b = pool.alloc(5)
    assert pool.available() == 0
    assert not (set(a) & set(b))
    pool.free(a)
    assert pool.available() == 3
    assert telemetry.gauge("serving.kv_blocks_used").value == 5
    assert telemetry.gauge("serving.kv_blocks_free").value == 3


def test_pool_oom_is_atomic():
    """A failed alloc takes NOTHING (no partial grab), raises classified
    KVCacheOOM, and bumps the always-on failure counter."""
    pool = KVBlockPool(SPEC, 5, 4)
    pool.alloc(2)
    fails0 = telemetry.counter("serving.kv_blocks_alloc_failures").value
    with pytest.raises(KVCacheOOM):
        pool.alloc(3)
    assert pool.available() == 2, "failed alloc must not leak blocks"
    assert telemetry.counter(
        "serving.kv_blocks_alloc_failures").value == fails0 + 1
    got = pool.alloc(2)
    assert len(got) == 2


def test_pool_double_free_and_bad_ids_rejected():
    pool = KVBlockPool(SPEC, 5, 4)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(ValueError, match="double free"):
        pool.free([a[0]])
    with pytest.raises(ValueError, match="invalid block"):
        pool.free([0])   # the trash block
    with pytest.raises(ValueError, match="invalid block"):
        pool.free([99])


def test_blocks_for():
    pool = KVBlockPool(SPEC, 5, 8)
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(8) == 1
    assert pool.blocks_for(9) == 2
    assert pool.blocks_for(17) == 3


# ---------------------------------------------------------------------------
# scheduler: fairness, preemption, state machine
# ---------------------------------------------------------------------------


def test_scheduler_fcfs_admission_no_skip_ahead():
    """Under mixed load the waiting queue admits head-first: a short prompt
    arriving later can NEVER overtake a long one blocked on blocks."""
    pool = KVBlockPool(SPEC, 6, 4)   # 5 usable blocks
    sched = Scheduler(pool, max_batch=8, prefills_per_step=8)
    big = Request([1] * 16, 4)          # 16 tokens + decode slot = 5 blocks
    sched.add(big)
    plan = sched.schedule()
    assert plan.prefills == [big]
    assert pool.available() == 0, "admission grant includes the decode slot"
    big.state = "decoding"
    big.context_len = 16
    big.pending_token = 1
    # pool dry; r2 needs 3 -> blocked; r3 (1 block) must NOT skip it
    r2 = Request([1] * 8, 2)
    r3 = Request([1], 2)
    sched.add(r2)
    sched.add(r3)
    plan = sched.schedule()
    assert plan.prefills == [], "head-of-line must block, not be skipped"
    assert list(sched.waiting) == [r2, r3]
    # blocks return -> FCFS order honored
    sched.finish(big)
    big.state = "finished"
    plan = sched.schedule()
    assert plan.prefills == [r2, r3]


def test_scheduler_preempts_youngest_and_replays():
    """Pool exhaustion preempts the LATEST-admitted stream: its blocks come
    back, its tokens-so-far become the replay prompt at the head of the
    queue, and the victim's output stream is preserved."""
    pool = KVBlockPool(SPEC, 6, 4)   # 5 usable
    sched = Scheduler(pool, max_batch=4, prefills_per_step=4)
    old = Request([1] * 7, 8)           # 2 blocks (7 tokens + decode slot)
    young = Request([2] * 8, 8)         # 3 blocks (8 tokens + decode slot)
    sched.add(old)
    sched.add(young)
    plan = sched.schedule()
    assert plan.prefills == [old, young]
    assert pool.available() == 0
    old.state = young.state = "decoding"
    old.context_len = 7
    old.generated = [9]
    old.pending_token = 9
    # young decoded on through its headroom block (slots 8..11): cached
    # context 12 = prompt 8 + 4 cached tokens, a 5th token pending
    young.state = "decoding"
    young.context_len = 12
    young.generated = [9] * 5
    young.pending_token = 9
    # next step: old writes into its tail slot (no alloc); young needs a
    # 4th block -> pool dry -> young preempted, old decodes on
    preempt0 = telemetry.counter("serving.preemptions").value
    plan = sched.schedule()
    assert plan.preempted == [young]
    assert plan.decodes == [old]
    assert young.state == "waiting" and young.blocks == []
    assert young.preemptions == 1
    assert sched.waiting[0] is young
    assert young.replay_tokens() == [2] * 8 + [9] * 4, \
        "pending token replays via prefill, not the cache"
    assert telemetry.counter("serving.preemptions").value == preempt0 + 1


def test_scheduler_lone_oversized_request_fails_not_wedges():
    pool = KVBlockPool(SPEC, 3, 4)   # 2 usable blocks = 8 slots
    sched = Scheduler(pool, max_batch=4, prefills_per_step=4)
    req = Request([1] * 8, 4)   # 8-token replay + decode slot = 3 blocks
    sched.add(req)
    plan = sched.schedule()     # admission must fail it outright, not
    assert plan.prefills == []  # wedge the queue behind it forever
    assert req.state == "failed"
    assert "too small" in req.error
    assert pool.available() == 2, "failed request must not hold blocks"
    assert sched.pop_failed() == [req], \
        "scheduler-side failures must queue for the engine's drain"
    assert not sched.has_work(), "failed head must leave the queue"


def test_scheduler_failure_surfaces_via_step_and_pop_finished():
    """A request FAILED inside the scheduler (pool too small for its next
    decode slot, nothing evictable) must flow through the same public
    channels as successes — step()'s return value and pop_finished() — so
    a polling driver can't lose a request to a silent failure."""
    eng = ServingEngine(_config(num_blocks=3), seed=SEED)  # 2 usable blocks
    req = Request([1] * 16, 4)     # replay + decode slot = 3 blocks > pool
    req.done_event = threading.Event()
    eng.scheduler.add(req)         # bypass submit(): its capacity check
    finished = []                  # would (rightly) reject this request
    for _ in range(4):
        finished += eng.step()
        if req.finished():
            break
    assert req in finished, "step() must return scheduler-failed requests"
    assert req.state == "failed" and "too small" in req.error
    assert req in eng.pop_finished(), \
        "pop_finished() must not drop scheduler-failed requests"
    assert req.done_event.is_set()
    assert eng.pool.available() == 2, "failed request must release blocks"


def test_pop_finished_backlog_bounded():
    """A driver that consumes done_events and never polls (serve.py) must
    not leak one retired Request per call for the life of the server."""
    eng = ServingEngine(_config(), seed=SEED)   # jit is lazy: cheap here
    cap = eng._finished.maxlen
    assert cap and cap >= 256
    for _ in range(cap + 10):
        r = Request([1], 1)
        r.state = "finished"
        eng._retire(r)
    assert len(eng._finished) == cap, "retired backlog must stay bounded"
    assert len(eng.pop_finished()) == cap and not eng._finished


# ---------------------------------------------------------------------------
# engine: equality with sequential decoding, compile-flat, preemption e2e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_pair():
    """One concurrent engine + one sequential oracle executor, same seed."""
    eng = ServingEngine(_config(), seed=SEED)
    ex = _decode_executor(smodel.random_params(_config(), seed=SEED))
    return eng, ex


def test_concurrent_outputs_equal_sequential(engine_pair):
    """The engine's continuous-batched outputs are IDENTICAL to
    single-stream contiguous-cache decoding — batching, bucket padding,
    and paging are invisible in the tokens."""
    eng, ex = engine_pair
    rng = np.random.RandomState(11)
    prompts, n_new = _mixed_workload(8, rng)
    got = eng.generate(prompts, n_new)
    for p, n, g in zip(prompts, n_new, got):
        assert g == _oracle_generate(ex, p, n)


def test_compile_count_flat_after_bucket_warmup(engine_pair):
    """After the shape buckets are warm, further traffic of any mix
    compiles NOTHING (the continuous-batching engine's no-recompile
    acceptance gate, measured by compileobs)."""
    eng, ex = engine_pair
    rng = np.random.RandomState(12)
    prompts, n_new = _mixed_workload(8, rng)
    # every bucket: which batch sizes a workload visits follows where its
    # streams end inside the decode chunks
    eng.warmup()
    eng.generate(prompts, n_new)
    counts0 = {p["program"]: p["compile_count"]
               for p in compileobs.program_table()
               if p["program"].startswith("serving.")}
    assert counts0, "serving programs must be registered with compileobs"
    prompts, n_new = _mixed_workload(8, rng)   # same bucket space
    eng.generate(prompts, n_new)
    counts1 = {p["program"]: p["compile_count"]
               for p in compileobs.program_table()
               if p["program"].startswith("serving.")}
    assert counts1 == counts0, "steady-state serving must not recompile"


def test_engine_blocks_all_freed_after_drain(engine_pair):
    eng, _ex = engine_pair
    assert eng.pool.used() == 0, \
        "drained engine must hold zero KV blocks"


def test_preemption_invisible_in_outputs(chunk):
    """A pool too small for the offered load forces evictions — also with
    a decode chunk's longer headroom (``chunk`` write slots a stream
    backed ahead of a dispatch); preempted requests replay
    deterministically and every output still equals sequential
    decoding."""
    cfg = _config(num_blocks=13, max_batch=4)   # 12 usable blocks
    eng = ServingEngine(cfg, seed=SEED)
    ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
    rng = np.random.RandomState(13)
    prompts = [[int(x) for x in rng.randint(0, cfg.vocab_size, 8)]
               for _ in range(4)]
    n_new = [20, 20, 20, 20]   # each stream wants 28 slots = 4 blocks
    pre0 = telemetry.counter("serving.preemptions").value
    got = eng.generate(prompts, n_new)
    assert telemetry.counter("serving.preemptions").value > pre0, \
        "workload sized to force eviction saw none"
    for p, n, g in zip(prompts, n_new, got):
        assert g == _oracle_generate(ex, p, n)
    assert eng.pool.used() == 0


def test_block_boundary_first_decode_token_not_lost(chunk):
    """A prompt that exactly fills its blocks writes its FIRST decode
    token at a fresh block boundary inside the same engine step. The
    engine must back that slot with a real block before the fused decode
    — otherwise the write lands in the trash block, the position's K/V is
    silently lost, and outputs drift from sequential decoding (caught as
    ~5e-4 probability divergence; argmax can mask it for many steps)."""
    cfg = _config()
    eng = ServingEngine(cfg, seed=SEED)
    bs = cfg.block_size
    for L in (bs, 2 * bs):          # exactly 1 and exactly 2 full blocks
        rng = np.random.RandomState(40 + L)
        prompt = [int(x) for x in rng.randint(0, cfg.vocab_size, L)]
        req = eng.submit(prompt, 8)
        eng.step()                   # prefill + same-step first decodes
        assert req.context_len == L + min(chunk, 7) and not req.finished()
        assert len(req.blocks) == L // bs + 1, \
            "first decode slot must be backed by a real block"
        # the boundary position's K/V must live in the new block's slot 0,
        # not in trash: nonzero on every layer
        kb = np.asarray(eng.pool.k_pages)[:, req.blocks[-1], 0]
        assert np.abs(kb).sum() > 0, "boundary K write was lost to trash"
        while not req.finished():
            eng.step()
    # and the tokens still equal sequential decoding
    ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
    for L in (bs, 2 * bs):
        rng = np.random.RandomState(40 + L)
        prompt = [int(x) for x in rng.randint(0, cfg.vocab_size, L)]
        got = eng.generate([prompt], [8])[0]
        assert got == _oracle_generate(ex, prompt, 8)


# ---------------------------------------------------------------------------
# the decode chunk: several steps a dispatch, the host synchronous at the fetch
# ---------------------------------------------------------------------------


def test_chunk_program_equals_single_steps(chunk):
    """One dispatch of ``chunk`` steps == single steps of the same
    executable, bit for bit in tokens, logits and pool, over a batch whose
    lanes die inside the chunk by their length cap, by EOS and at
    ``max_len``, with a write that crosses a block boundary and a padded
    row; a dead lane's rows are -1 and it writes to trash only."""
    import jax
    import jax.numpy as jnp

    cfg = _config()
    params = smodel.as_device_params(smodel.random_params(cfg, seed=SEED),
                                     cfg)
    nb = cfg.max_len // cfg.block_size
    lanes = [lane(5, 6, 10),                 # writes 6..9: crosses at 8
             lane(7, 20, 2),                 # its length cap, mid-chunk
             lane(9, 33, 10),                # its EOS (found below)
             lane(2, cfg.max_len - 2, 10),   # the position cap
             lane(0, 0, 0)]                  # a padded row
    tables = tables_for(lanes, nb, cfg.block_size)
    rng = np.random.RandomState(5)
    shape, _ = cfg.cache_specs().full.shape(cfg.num_blocks, cfg.block_size)
    assert shape == (2, 64, 8, 2, 16)
    caches = {k: jnp.asarray(rng.randn(*shape), jnp.float32) for k in "kv"}
    step = jax.jit(lambda *a: smodel.decode_chunk(params, *a, cfg, chunk))

    def program(tok, pos, ctx, left, eos, n, c):
        rows, logits, kp, vp = step(tok, pos, tables, ctx, left, eos,
                                    np.int32(n), c["k"], c["v"])
        return rows, logits, {"k": kp, "v": vp}, None

    rows, _ = chunk_equals_single_steps(program, cfg.max_len, lanes, caches,
                                        chunk)
    # lane 2 again, its second token now its EOS: it dies having produced it
    lanes[2] = lane(9, 33, 10, eos=int(rows[min(1, chunk - 1), 2]))
    rows, _ = chunk_equals_single_steps(program, cfg.max_len, lanes, caches,
                                        chunk)
    steps = (rows >= 0).sum(axis=0)
    assert list(steps[[0, 1, 3, 4]]) == [chunk, min(2, chunk),
                                         min(2, chunk), 0]
    assert 1 <= steps[2] <= min(2, chunk) \
        and rows[steps[2] - 1, 2] == lanes[2]["eos"]
    assert (rows[steps[2]:, 2] == -1).all()


def test_chunked_serving_equals_sequential_decoding(chunk):
    """The engine over chunks of 1, 2 and 4: every output equals
    single-stream contiguous-cache decoding — with streams that end inside
    a chunk by length, by EOS and at ``max_len`` — and after EVERY step
    ``serving.decode_batch`` has seen exactly the live lane-steps (a dead
    lane counted would push a roofline share over 100%) and the chunk
    counter says how often it engaged."""
    cfg = _config(max_batch=4)
    eng = ServingEngine(cfg, seed=SEED)
    ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
    rng = np.random.RandomState(21)
    prompts = [[int(x) for x in rng.randint(0, cfg.vocab_size, n)]
               for n in (3, 7, 12, cfg.max_len - 9, 5, 9)]
    n_new = [6, 13, 5, 9, 11, 14]            # the fourth ends AT max_len
    want = [_oracle_generate(ex, p, n) for p, n in zip(prompts, n_new)]
    eos = want[4][5]                          # the fifth meets it inside
    want[4] = want[4][:want[4].index(eos) + 1]
    batch0 = telemetry.totals("serving.decode_batch")
    reqs = [eng.submit(p, n, eos_id=eos if i == 4 else None)
            for i, (p, n) in enumerate(zip(prompts, n_new))]
    while any(not r.finished() for r in reqs):
        eng.step()
        count, total = telemetry.totals("serving.decode_batch")
        dec = eng.stats()["decode"]
        # every token but a request's first came from a live decode lane
        assert total - batch0[1] == sum(
            max(len(r.generated) - 1, 0) for r in reqs)
        assert count - batch0[0] == dec["inner_steps"]
        assert dec["dispatches"] <= dec["inner_steps"] \
            <= chunk * dec["dispatches"]
    assert [list(r.generated) for r in reqs] == want
    assert dec["steps_per_dispatch"] == \
        dec["inner_steps"] / dec["dispatches"] > (1 if chunk > 1 else 0)
    assert eng.pool.used() == 0


# ---------------------------------------------------------------------------
# admission: a free lane waits for no count
# ---------------------------------------------------------------------------


def _queue(sched, lengths, n_new=4):
    reqs = [Request([1] * n, n_new) for n in lengths]
    for r in reqs:
        sched.add(r)
    return reqs


def _stops(**counts):
    """``stopped_by`` with ``counts`` and every other reason at zero."""
    return dict(dict.fromkeys(STOP_REASONS, 0), **counts)


def _decoding(req):
    """What the engine leaves of an admitted prompt after its prefill."""
    req.state = "decoding"
    req.context_len = len(req.prompt)
    req.generated = [9]
    req.pending_token = 9


@pytest.mark.parametrize("waiting", [8, 11])
def test_no_count_one_step_takes_every_prompt_the_lanes_hold(waiting):
    """No ``prefills_per_step``: 8 free lanes and a roomy pool are ONE
    step's group of 8, whatever the pool has left over; the queue's end or
    the last lane ends the pass, never a count."""
    pool = KVBlockPool(SPEC, 129, 4)
    sched = Scheduler(pool, max_batch=8)
    assert sched.prefills_per_step is None
    reqs = _queue(sched, [5] * waiting)
    plan = sched.schedule()
    assert plan.prefills == reqs[:8]
    assert pool.available() > 64, "a roomy pool is no reason to stop"
    assert sched.last_stop == ("queue" if waiting == 8 else "lanes")
    assert sched.stopped_by == _stops(**{sched.last_stop: 1})
    assert list(sched.waiting) == reqs[8:]


@pytest.mark.parametrize("cap", [1, 2, 4])
def test_an_explicit_count_still_caps_a_step(cap):
    """``prefills_per_step=2`` means what it always did: groups of two,
    head first, and the pass it ended says ``cap``."""
    pool = KVBlockPool(SPEC, 129, 4)
    sched = Scheduler(pool, max_batch=8, prefills_per_step=cap)
    reqs = _queue(sched, [5] * 8)
    groups = []
    while sched.waiting:
        groups.append(sched.schedule().prefills)
    assert [len(g) for g in groups] == [cap] * (8 // cap)
    assert [r for g in groups for r in g] == reqs
    # the last pass emptied the queue: its end is named before the count
    assert sched.stopped_by["cap"] == 8 // cap - 1
    assert sched.stopped_by["queue"] == 1


class _Slots:
    """``StreamState`` as the scheduler sees it: ``free`` state slots, no
    window pool."""

    def __init__(self, free):
        self.free = free

    def can_admit(self, n_tokens):
        return self.free > 0

    def admit(self, req, n_tokens):
        self.free -= 1

    def release(self, req):
        self.free += 1

    def ensure(self, req, pos, last_pos=None):
        pass


@pytest.mark.parametrize("bound", ["pool", "slots", "preempted"])
def test_no_count_a_pass_still_ends_on_what_the_engine_holds(bound):
    """With no count, the pool's free list, a state slot
    (``streams.can_admit``) and a request preempted in the pass each still
    end it, first come first served: the short prompt behind the head
    that does not fit is never taken in its place."""
    pool = KVBlockPool(SPEC, 9, 4)              # 8 usable blocks
    streams = _Slots(2) if bound == "slots" else None
    sched = Scheduler(pool, max_batch=8, streams=streams)
    if bound == "preempted":
        old, young = _queue(sched, [15, 15], n_new=8)   # 4 blocks each
        assert sched.schedule().prefills == [old, young]
        _decoding(old)
        _decoding(young)
        old.context_len = young.context_len = 16        # next slot: block 5
        short, = _queue(sched, [1])
        plan = sched.schedule()
        assert plan.preempted == [young] and plan.decodes == [old]
        # the victim heads the queue and sits this step out; the blocks it
        # gave back are not the short prompt's to take past it
        assert plan.prefills == []
        assert list(sched.waiting) == [young, short]
    else:
        # pool: 3 + 3 blocks, then the head needs 3 of the 2 left;
        # slots: two slots, then the head has none
        a, b, head, short = _queue(sched, [9, 9, 9, 1])
        plan = sched.schedule()
        assert plan.prefills == [a, b]
        assert list(sched.waiting) == [head, short]
        assert pool.available() == 2
    assert sched.last_stop == bound
    assert sched.stopped_by[bound] == 1 and sched.stopped_by["cap"] == 0


@pytest.mark.parametrize("reason", STOP_REASONS)
def test_stopped_by_names_what_ended_each_pass(reason):
    """``stats()["prefill"]["stopped_by"]``: one increment a pass that
    found a request waiting, under the name of what ended it; a case
    built for each of the six."""
    family = "phi4flash" if reason == "slots" else "gpt2"
    over = dict(prefills_per_step=2 if reason == "cap" else None)
    if reason == "pool":
        over.update(num_blocks=4)               # 3 usable blocks of 8
    eng = ServingEngine(_family(family, **over), seed=SEED)
    sched = eng.scheduler
    c0 = telemetry.counter("serving.admit.stopped_by", reason=reason).value
    assert sched.schedule().empty() and sched.last_stop is None
    assert eng.stats()["prefill"]["stopped_by"] == _stops(), \
        "nothing waiting: no pass to count"
    if reason == "slots":
        eng.streams.slots.alloc()               # one of the four is held
    # prompts submitted, and how many of them the pass takes
    n, admitted = {"lanes": (5, 4), "pool": (3, 1), "slots": (4, 3),
                   "cap": (3, 2), "preempted": (0, 0),
                   "queue": (3, 3)}[reason]
    reqs = [eng.submit([1] * 9, 4) for _ in range(n)]
    if reason == "preempted":
        old, young = reqs = [eng.submit([1] * 7, 16) for _ in range(2)]
        assert sched.schedule().prefills == reqs
        _decoding(old)
        _decoding(young)
        eng.pool.alloc(eng.pool.available())    # the pool runs dry
        old.context_len = young.context_len = 8
        plan = sched.schedule()
        assert plan.preempted == [young] and plan.prefills == []
        want = {"queue": 1, "preempted": 1}
    else:
        assert sched.schedule().prefills == reqs[:admitted]
        want = {reason: 1}
    assert sched.last_stop == reason
    assert eng.stats()["prefill"]["stopped_by"] == _stops(**want)
    assert telemetry.counter("serving.admit.stopped_by",
                             reason=reason).value == c0 + 1


def test_no_count_is_the_default_and_the_environment_gives_one(monkeypatch):
    """Nothing given and nothing in the environment: no count. An integer,
    from either, caps a step as it always did."""
    monkeypatch.delenv("MXNET_SERVING_PREFILLS_PER_STEP", raising=False)
    assert ServingConfig(**CFG).prefills_per_step is None
    assert _config(prefills_per_step=None).prefills_per_step is None
    assert _config(prefills_per_step=3).prefills_per_step == 3
    monkeypatch.setenv("MXNET_SERVING_PREFILLS_PER_STEP", "2")
    assert ServingConfig(**CFG).prefills_per_step == 2
    assert _config(prefills_per_step=5).prefills_per_step == 5
    eng = ServingEngine(_config(prefills_per_step=None), seed=SEED)
    assert eng.scheduler.prefills_per_step == 2


def test_no_count_eight_prompts_are_one_group_and_the_event_says_why():
    """The engine with no count: 8 prompts and 8 lanes are one step's
    group, every first token the prompt's own; the step's
    ``serving.step_timeline`` event names what ended its pass."""
    eng = ServingEngine(_config(prefills_per_step=None), seed=SEED)
    reqs = [eng.submit(list(range(1, 4 + i)), 12) for i in range(8)]
    eng.step()
    assert eng.stats()["prefill"]["prompts_per_group"] == 8.0
    assert [r.state for r in reqs] == ["decoding"] * 8
    ev = [e for e in telemetry.events("serving.step_timeline")
          if e["engine"] == str(eng.engine_id)]
    assert [(e["admitted"], e["stopped_by"]) for e in ev] == [(8, "queue")]
    while eng.has_work():
        eng.step()
    ev = [e for e in telemetry.events("serving.step_timeline")
          if e["engine"] == str(eng.engine_id)]
    assert {e["stopped_by"] for e in ev[1:]} == {None}
    alone = ServingEngine(_config(), seed=SEED)
    assert [list(r.generated) for r in reqs] == [
        alone.generate([list(r.prompt)], 12)[0] for r in reqs]


# ---------------------------------------------------------------------------
# a step's prefills are one group: dispatched back to back, then fetched
# ---------------------------------------------------------------------------


class _Tok:
    """A prefill's token result that says when the host touched it."""

    def __init__(self, calls, value):
        self.calls, self.value = calls, value

    def copy_to_host_async(self):
        self.calls.append("copy")

    def __array__(self, dtype=None, copy=None):
        self.calls.append("fetch")
        return np.asarray(self.value)


@pytest.mark.parametrize("k", [1, 3])
def test_a_groups_dispatches_all_precede_its_first_fetch(k):
    """``k`` prompts admitted in one step: every prefill program is
    dispatched (and, in a group, its token's copy to the host asked for)
    before the first blocking fetch; the fetches follow in plan order, and
    every request is DECODING with its first token before the decode
    program is dispatched. A group of ONE is a dispatch and its fetch and
    nothing else: the sequence of calls a prompt alone always was."""
    eng = ServingEngine(_config(), seed=SEED)
    calls = []
    dispatch_prefill, dispatch_decode = (eng._dispatch_prefill,
                                         eng._dispatch_decode)

    def prefill(toks, length, *rest):
        calls.append("dispatch")
        tok, logits = dispatch_prefill(toks, length, *rest)
        return _Tok(calls, tok), logits

    def decode(*a, **kw):
        calls.append("decode")
        assert [r.state for r in reqs] == ["decoding"] * k
        assert all(len(r.generated) == 1 for r in reqs)
        return dispatch_decode(*a, **kw)

    eng._dispatch_prefill, eng._dispatch_decode = prefill, decode
    reqs = [eng.submit(list(range(1, 4 + i)), 3) for i in range(k)]
    eng.step()
    group = ["dispatch", "copy"] * k + ["fetch"] * k
    assert calls == (group if k > 1 else ["dispatch", "fetch"]) + ["decode"]
    # the first tokens are the prompts' own (plan order = fetch order)
    alone = ServingEngine(_config(), seed=SEED)
    assert [r.generated[0] for r in reqs] == [
        alone.generate([list(r.prompt)], 1)[0][0] for r in reqs]


def _family(name, **engine):
    """A tiny ``ServingConfig`` of one of the four layer families, in
    float32: GPT-2's block, routed experts (OLMoE's), ``layer_kinds`` with
    window and state layers (Phi-4-mini-flash's), latent attention beside a
    share of the experts (dots.vlm1's)."""
    if name == "gpt2":
        return _config(**{"max_batch": 4, **engine})
    path = os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                        name + "-tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["engine"].update(kv_dtype="float32", **engine)
    cfg["weights_dtype"] = "float32"
    return ServingConfig.from_json(cfg)


def _serve_spied(scfg, prompts, n_new, hog):
    """Serve ``prompts`` from a pool with ``hog`` blocks left (None: all of
    it); the requests, the engine and each step's group as
    ``[(index of the prompt, is it a preemption's replay)]``."""
    eng = ServingEngine(scfg, seed=SEED)
    groups, run = [], eng._run_prefills

    def spy(reqs):
        groups.append([(r.rid, r.pending_token is not None) for r in reqs])
        return run(reqs)

    eng._run_prefills = spy
    if hog is not None:
        eng.pool.alloc(eng.pool.available() - hog)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
    first = reqs[0].rid
    while eng.has_work():
        eng.step()
    assert [r.state for r in reqs] == ["finished"] * len(reqs)
    return eng, reqs, [[(rid - first, rp) for rid, rp in g] for g in groups]


@pytest.mark.parametrize("pool", ["roomy", "tight"])
@pytest.mark.parametrize("family",
                         ["gpt2", "olmoe", "phi4flash", "dotsvlm1"])
def test_grouped_prefills_serve_the_tokens_of_one_prompt_a_step(family,
                                                                pool):
    """Every waiting prompt the lanes and the pool hold in one step (no
    count: six lanes take all six prompts at once), up to four a step,
    their prefills dispatched back to back and fetched after the last
    dispatch, against ``prefills_per_step=1`` (a dispatch and its fetch a
    step: the sequence before groups): the tokens are the same to the
    bit, for each layer family. Two prompts with a common two-block prefix
    are admitted in the SAME step (neither can map the other's blocks: the
    index is read at admission and written after the fetch), a third with
    that prefix later; in the tight pool streams are preempted and their
    replays run inside a group."""
    scfg = _family(family)
    bs, rng = scfg.block_size, np.random.RandomState(5)
    draw = lambda n: [int(t) for t in rng.randint(0, scfg.vocab_size, n)]
    common = draw(2 * bs)
    prompts = [draw(bs + 3), common + draw(5), common + draw(3), draw(9),
               draw(bs - 1), common + draw(7)]
    # the first and the fourth end early: the last two are admitted while
    # both prompts of the prefix still hold their blocks
    n_new = [bs, 3 * bs, 3 * bs, bs + 2, 2 * bs, 2 * bs]
    hog = 14 if pool == "tight" else None
    served = {}
    for pps in (None, 4, 1):
        # no count shows only where lanes are left over after four
        over = dict(max_batch=6) if pps is None else {}
        eng, reqs, groups = _serve_spied(
            _family(family, prefills_per_step=pps, **over), prompts, n_new,
            hog)
        served[pps] = [list(r.generated) for r in reqs]
        stats = eng.stats()["prefill"]
        assert stats["prompts"] == sum(len(g) for g in groups)
        assert stats["groups"] == len(groups)
        assert stats["syncs_saved"] == stats["prompts"] - stats["groups"]
        assert pps is not None or stats["stopped_by"]["cap"] == 0
        if pps == 1:
            assert {len(g) for g in groups} == {1}
            assert stats["prompts_per_group"] == 1.0
            continue
        if pps is None:
            # one step takes what the pool holds of the six: all of them
            # where it is roomy
            assert groups[0] == [(i, False)
                                 for i in range(len(groups[0]))]
            assert len(groups[0]) == 6 or pool == "tight"
            continue
        # the first step admits the batch's four: both prompts of the
        # common prefix among them, fresh, side by side
        assert groups[0] == [(i, False) for i in range(4)]
        assert stats["prompts_per_group"] > 1.5
        replays = [g for g in groups if len(g) > 1
                   and any(replay for _i, replay in g)]
        if pool == "tight":
            assert replays, groups
            assert sum(r.preemptions for r in reqs) >= 1
        elif eng.pool.prefix_stats()["enabled"]:
            # the late third prompt of the prefix maps the two blocks the
            # first of the group wrote (its own writes to them go to trash)
            assert eng.pool.prefix_stats()["hit_blocks"] >= 2
    assert served[None] == served[1]
    assert served[4] == served[1]


def test_a_dispatch_error_inside_a_group_fails_the_whole_group():
    """``dispatch_error`` at the second of three starts: the first
    prompt's program is already queued on donated pages, so the engine
    aborts — all three requests failed, none DECODING, later submits
    refused."""
    from mxnet_tpu import fault

    eng = ServingEngine(_config(), seed=SEED)
    reqs = [eng.submit(list(range(1, 5 + i)), 4) for i in range(3)]
    started, start = [], eng._start_prefill

    def spy(pack, rung, grouped):
        started.extend(req for req, _replay in pack)
        return start(pack, rung, grouped)

    eng._start_prefill = spy
    with fault.inject("dispatch_error:raise=1,after=1,times=1"):
        with pytest.raises(fault.InjectedFault):
            eng.step()
    assert started == reqs[:2]            # the third was never built
    assert [r.state for r in reqs] == ["failed"] * 3
    assert all("aborted" in r.error for r in reqs)
    assert all(r.done_event.is_set() for r in reqs)
    assert eng.aborted and not eng.has_work()
    assert eng.stats()["prefill"]["groups"] == 0     # no group completed
    with pytest.raises(RuntimeError, match="aborted"):
        eng.submit([1], 1)


def test_prefill_group_stats_by_hand():
    """Five prompts over a batch of eight at four admissions a step: a
    group of four, then a group of one. ``stats()["prefill"]``, the
    histogram and the two counters say so."""
    eng = ServingEngine(_config(), seed=SEED)
    g0 = telemetry.totals("serving.prefill.group")
    n0 = telemetry.counter("serving.prefill.groups").value
    s0 = telemetry.counter("serving.prefill.syncs_saved").value
    assert eng.stats()["prefill"] == {
        "prompts": 0, "groups": 0, "prompts_per_group": 0.0,
        "programs": 0, "prompts_per_program": 0.0,
        "syncs_saved": 0, "stopped_by": _stops()}
    eng.generate([[1 + i, 2, 3] for i in range(5)], 3)
    # the count of four ended the first pass, the queue's end the second;
    # a pass that finds nothing waiting is no pass
    assert eng.stats()["prefill"] == {
        "prompts": 5, "groups": 2, "prompts_per_group": 2.5,
        # a model without experts: each prompt in its own program
        "programs": 5, "prompts_per_program": 1.0,
        "syncs_saved": 3, "stopped_by": _stops(cap=1, queue=1)}
    g1 = telemetry.totals("serving.prefill.group")
    assert (g1[0] - g0[0], g1[1] - g0[1]) == (2, 5.0)
    assert telemetry.counter("serving.prefill.groups").value - n0 == 2
    assert telemetry.counter("serving.prefill.syncs_saved").value - s0 == 3
    doc = open(os.path.join(ROOT, "docs", "observability.md")).read()
    for name in ("serving.prefill.group", "serving.prefill.groups",
                 "serving.prefill.syncs_saved"):
        assert name in telemetry.METRIC_HELP
        assert "`%s`" % name in doc


def test_a_cold_buckets_compile_stall_is_its_own_requests_alone():
    """Three prompts in one group, the middle one's prefill bucket cold:
    the compile happens inside ITS dispatch call, after the first prompt
    was dispatched and before it was fetched. The stall is charged to the
    middle request alone, and never more than its own dispatch call."""
    cfg = _config()
    eng = ServingEngine(cfg, seed=SEED)
    eng.warmup(prefill_buckets=[8])        # and every decode bucket
    walls = []
    start = eng._start_prefill

    def timed(pack, rung, grouped):
        t0 = time.time()
        out = start(pack, rung, grouped)
        walls.append(time.time() - t0)
        return out

    eng._start_prefill = timed
    reqs = [eng.submit(list(range(1, 1 + n)), 2) for n in (5, 12, 6)]
    eng.step()
    stalls = [r.trace.phases["compile_stall"] for r in reqs]
    assert stalls[0] == 0.0 and stalls[2] == 0.0
    assert 0.0 < stalls[1] <= walls[1]
    assert all(len(r.generated) >= 1 for r in reqs)


def test_step_failure_aborts_not_strands():
    """A device error escaping step() must fail every pending request and
    wake its waiters — a silently dead driver thread would strand HTTP
    clients on done_event.wait() forever."""
    eng = ServingEngine(_config(), seed=SEED)
    boom = RuntimeError("boom: injected device failure")

    def exploding(*a, **kw):
        raise boom

    eng._decode_fn = exploding
    req = eng.submit([1, 2, 3], 4)
    raised = []

    def drive():
        try:
            eng.run_loop(None, 0.01)
        except RuntimeError as e:
            raised.append(e)

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    assert req.done_event.wait(timeout=30), \
        "aborted request's waiters must be woken"
    t.join(timeout=30)
    assert not t.is_alive()
    assert raised and raised[0] is boom, \
        "run_loop must re-raise so the driver's death is observable"
    assert req.state == "failed"
    assert "aborted" in req.error and "boom" in req.error
    with pytest.raises(RuntimeError, match="aborted"):
        eng.submit([1], 1)          # post-abort submits refuse


def test_step_failure_aborts_direct_drivers_too():
    """The abort-on-failure contract lives in step() itself, not run_loop:
    a direct step() driver (generate(), a polling loop) must also leave
    the engine aborted — on TPU the pool pages were
    donated into the failed dispatch and cannot be dispatched again."""
    eng = ServingEngine(_config(), seed=SEED)
    boom = RuntimeError("boom: injected device failure")

    def exploding(*a, **kw):
        raise boom

    eng._decode_fn = exploding
    with pytest.raises(RuntimeError, match="boom"):
        eng.generate([[1, 2, 3]], [4])
    with pytest.raises(RuntimeError, match="aborted"):
        eng.submit([1], 1)          # post-abort submits refuse
    # the failed request surfaced through the polling channel too
    popped = eng.pop_finished()
    assert popped and all(r.state == "failed" for r in popped)


def test_warmup_compiles_every_bucket_then_flat():
    """engine.warmup() compiles one program per prefill length bucket and
    per decode batch bucket; traffic afterwards compiles nothing — and NONE
    of the bucket warmup compiles is misreported as a recompile (each
    bucket holds its own graph key, so the compile.recompile stream stays
    reserved for a bucket compiling AGAIN)."""
    cfg = _config(max_len=32, max_batch=4)
    def counts(field="compile_count"):
        return {p["program"]: p[field]
                for p in compileobs.program_table()
                if p["program"].startswith("serving.")}
    c0 = counts()
    r0 = counts("recompile_count")
    eng = ServingEngine(cfg, seed=SEED)
    eng.warmup()
    c1 = counts()
    assert (c1.get("serving.prefill", 0) - c0.get("serving.prefill", 0)
            == len(cfg.prefill_buckets()))
    assert (c1.get("serving.decode", 0) - c0.get("serving.decode", 0)
            == len(cfg.decode_buckets()))
    rng = np.random.RandomState(17)
    prompts, n_new = _mixed_workload(6, rng, prompt_max=9, new_max=6)
    eng.generate(prompts, n_new)
    assert counts() == c1, "warmed engine must not compile under traffic"
    assert counts("recompile_count") == r0, \
        "bucket warmup must not be reported as recompiles"


def test_engine_rejects_impossible_requests():
    eng = ServingEngine(_config(), seed=SEED)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit([1] * 60, 10)
    with pytest.raises(ValueError, match="seed token"):
        eng.submit([], 4)


def test_serving_metrics_flow_through_registry(engine_pair):
    """The serving.* metrics land in the shared registry (names are pinned
    by telemetry.METRIC_HELP + docs/observability.md via the drift test)."""
    for name in ("serving.requests_admitted", "serving.requests_completed",
                 "serving.generated_tokens", "serving.prefill_tokens"):
        assert telemetry.counter(name).value > 0, name
    assert telemetry.gauge("serving.kv_blocks_total").value > 0
    assert telemetry.histogram("serving.ttft_seconds").count > 0
    assert telemetry.histogram("serving.request_latency_seconds").count > 0
    text = telemetry.prometheus_text()
    assert "mxnet_serving_kv_blocks_used" in text
    assert "mxnet_serving_ttft_seconds" in text


def test_engine_stats_snapshot(engine_pair):
    eng, _ex = engine_pair
    s = eng.stats()
    assert s["completed"] >= 8
    assert s["kv_blocks_total"] == 63
    assert "serving.decode" in s["compiles"]
    assert s["compiles"]["serving.decode"]["count"] >= 1


# ---------------------------------------------------------------------------
# the slow e2e: >=32 concurrent variable-length streams over HTTP
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_e2e_32_concurrent_http_streams_match_sequential(tmp_path):
    """Acceptance: >=32 concurrent variable-length requests through
    tools/serve.py share one device's KV blocks and every response is
    bit-identical to sequential single-stream decoding; the server's
    compile count is flat after bucket warmup."""
    port = 18293
    n_req = 32
    cfg = _config(num_blocks=257, max_batch=32)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
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
         "--warmup"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    base = "http://127.0.0.1:%d" % port

    def get(path, timeout=5):
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return json.loads(r.read())

    try:
        deadline = time.time() + 120
        while True:
            try:
                assert get("/healthz")["ok"]
                break
            except (OSError, AssertionError):
                if time.time() > deadline:
                    raise RuntimeError("server never came up")
                time.sleep(0.5)

        rng = np.random.RandomState(21)
        prompts, n_new = _mixed_workload(n_req, rng,
                                         vocab=cfg.vocab_size,
                                         prompt_max=25, new_max=16)
        results = [None] * n_req
        errors = []

        def fire(i):
            body = json.dumps({"tokens": prompts[i],
                               "max_new_tokens": n_new[i]}).encode()
            req = urllib.request.Request(base + "/generate", data=body)
            try:
                with urllib.request.urlopen(req, timeout=600) as r:
                    results[i] = json.loads(r.read())
            except Exception as e:  # surfaced below with the index
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        assert not errors, errors
        assert all(r is not None for r in results)

        stats = get("/stats")
        compiles_after_load = {n: c["count"]
                               for n, c in stats["compiles"].items()}
        assert stats["completed"] >= n_req

        # sequential single-stream oracle, same seeded weights
        ex = _decode_executor(smodel.random_params(cfg, seed=SEED))
        for i in range(n_req):
            want = _oracle_generate(ex, prompts[i], n_new[i])
            assert results[i]["tokens"] == want, \
                "request %d: %s != %s" % (i, results[i]["tokens"], want)

        # flat compile count after warmup: re-fire a subset of the same
        # bucket space and require zero new compiles
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert {n: c["count"]
                for n, c in get("/stats")["compiles"].items()} \
            == compiles_after_load, "steady-state traffic recompiled"
        # prometheus exposition serves the serving.* metrics
        with urllib.request.urlopen(base + "/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "mxnet_serving_kv_blocks_used" in text
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_lock_witness_strict_clean_on_serving_engine():
    """Acceptance: MXNET_LOCK_WITNESS=strict over a live concurrent
    serving workload — handler-thread submits racing the driver loop —
    raises nothing and adds zero lock.order_violations: the runtime
    nesting of the engine/pool/supervisor locks agrees with the static
    lock graph."""
    from mxnet_tpu.analysis import witness

    witness.reset_observations()
    before = telemetry.counter(witness.COUNTER_ORDER).value
    witness.configure("strict")  # BEFORE construction: locks wrap in init
    try:
        eng = ServingEngine(_config(), seed=SEED)
        stop = threading.Event()
        errs = []

        def drive():
            try:
                eng.run_loop(stop, idle_wait_s=0.005)
            except Exception as exc:   # noqa: BLE001 — assert below
                errs.append(exc)

        t = threading.Thread(target=drive, name="witness-driver",
                             daemon=True)
        t.start()
        reqs = [eng.submit([1 + i, 2, 3], 3) for i in range(4)]
        for r in reqs:
            assert r.done_event.wait(timeout=60), "request stalled"
        stop.set()
        t.join(timeout=30)
        assert not t.is_alive()
        assert errs == [], "witness violation in the serving engine: %r" \
            % errs
        assert all(r.state == "finished" for r in reqs)
        # the witness actually watched: the engine lock was exercised
        assert any("ServingEngine._lock" in name
                   for edge in witness.observed_edges() for name in edge) \
            or telemetry.histogram(
                witness.HELD_HISTOGRAM,
                lock="mxnet_tpu.serving.engine.ServingEngine._lock").count \
            > 0
        assert telemetry.counter(witness.COUNTER_ORDER).value == before
    finally:
        witness.configure(None)
        witness.seed_static(None)
        witness.reset_observations()
